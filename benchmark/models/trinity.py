"""Trinity-Mini, one chip's share of an expert-parallel job: the Program
through the repo's public builder, seeded documents, FLOPs per example and
per kernel call, and the plain reference.

The reference is written from the equations in
`paddle_tpu/models/trinity.py`'s docstring (the model's public
`config.json`; the public `modeling_afmoe.py` of `transformers` for what
the config leaves open, listed under `assumed` in the configuration file)
in float32 `jax.numpy`. It shares nothing with `paddle_tpu`'s lowerings
but the parameters' names:

- Attention is plain softmax over explicit masks, in blocks of queries so
  that the float32 scores of 32 heads x 512 x 8,192 (0.5 GB) fit beside
  the state the device holds during the set-up check. K and V are
  repeated for the group by indexing; the program's kernels index the key
  block by `head // 8` and repeat nothing.
- Positions are the rotate-half form written out with a concatenation;
  the program rolls the lanes and folds the sign into the sine.
- The experts are a loop over the experts held, each over every token
  with a mask as its weight.
- The share is the program's: the router scores all
  `num_experts_published` experts and what the experts held elsewhere
  would add is left out; ids, logits and loss are over the slice of the
  vocabulary; the layers are the published ones from `first_layer_held`.
"""

from __future__ import annotations

import math

from benchmark.harness.datagen import zipf_ids

SCORED_SEQUENCES = 1  # the harness compares logits on this many sequences
SCORED_EVERY = 16  # ... at every sixteenth position of each (820 MB otherwise)
QUERY_BLOCK = 512  # the reference's attention, queries a block

# |program - reference| on the scored logits over the reference's own
# root-mean-square, and on the loss; each limit lies between two readings
# on the chip (PERF.md section 6, PR 33). The program computes its matrix
# products in bf16 with float32 accumulation and keeps its activations,
# the residual stream among them, in bf16: through five layers it read
# 1.55-2.93% on the logits over the final tree's ten seeds and at most
# 0.00137 on the loss over this PR's twenty-four readings. The reference
# with its matrices rounded to fp8 (e4m3) reads 13.3-14.3% on the logits,
# which is what refuses it, and 0.0021-0.031 on the loss (it passes the
# loss's limit on one seed of four); the mildest wrong model of `WRONG`
# (no QK-norm) reads 10.9-12.9%, the others 23-127%. The model hands its
# loss back in float32, so `loss_abs` is no step of bf16 near ln 25,024.
# Both limits leave twice the largest reading of room, since fresh seeds
# read higher. What the limits cannot tell apart is the reference with
# bf16 matrices (2.45%, 0.0003): the program's are bf16 already.
TOLERANCE = {"logits_rel_rms": 0.06, "loss_abs": 0.003}

# what `reference(wrong=...)` can be made to get wrong, for the tests and
# the chip readings that place the limits
WRONG = ("all_full", "no_rope", "no_gate", "no_qk_norm", "group_mod")


def held_layers(model: dict) -> list[tuple[int, int, bool]]:
    """(published index, window or 0, dense?) of each layer held."""
    first = model["first_layer_held"]
    return [(i, model["sliding_window"]
             if model["layer_types"][i] == "sliding_attention" else 0,
             i < model["num_dense_layers"])
            for i in range(first, first + model["num_hidden_layers"])]


def config(model: dict):
    from paddle_tpu.models.trinity import TrinityConfig

    layers = held_layers(model)
    return TrinityConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        layer_types=[model["layer_types"][i] for i, _, _ in layers],
        first_layer=model["first_layer_held"],
        dense_layers=sum(dense for _, _, dense in layers),
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"], sliding_window=model["sliding_window"],
        rope_theta=model["rope_theta"],
        intermediate_size=model["intermediate_size"],
        moe_intermediate_size=model["moe_intermediate_size"],
        num_experts=model["num_experts_published"],
        experts_held=model["num_experts"], held_from=model["held_from"],
        num_experts_per_token=model["num_experts_per_tok"],
        num_shared_experts=model["num_shared_experts"],
        routed_scaling_factor=model["route_scale"],
        moe_renormalize=model["route_norm"],
        mup_enabled=model["mup_enabled"],
        rms_norm_eps=model["rms_norm_eps"],
        initializer_range=model["initializer_range"],
        router_bias_scale=model["router_bias_scale"])


def build(model: dict, traffic: dict) -> dict:
    """Declare the training program in the current default programs.
    `check` names what the reference check fetches from the `for_test`
    clone: the loss and the logits at every `SCORED_EVERY`-th position."""
    from paddle_tpu import layers
    from paddle_tpu.models.trinity import build_trinity

    b, s = traffic["batch"], traffic["seq_len"]
    handles = build_trinity(config(model), b, s)
    scored = layers.strided_slice(
        handles["logits"], axes=[0, 1], starts=[0, 0],
        ends=[min(b, SCORED_SEQUENCES), s], strides=[1, SCORED_EVERY])
    return {"loss": handles["loss"].name, "feeds": handles["feeds"],
            "check": [handles["loss"].name, scored.name],
            "loads": [v.name for v in handles["loads"]]}


def make_batch(rng, model: dict, traffic: dict) -> dict:
    """One document a row, `seq_len` tokens and the token after each as its
    label: no padding, no packing, every position scored. Ids are
    Zipf(1.1) over the rows of the vocabulary held here."""
    b, s = traffic["batch"], traffic["seq_len"]
    doc = zipf_ids(rng, (b, s + 1), model["vocab_size"])
    return {"tokens": doc[:, :-1].copy(), "labels": doc[:, 1:].copy()}


def tokens_per_example(model: dict, traffic: dict) -> int:
    return traffic["seq_len"]


def matrix_params_per_token(model: dict) -> float:
    """Weights of the matrix products one token passes through in the
    layers held here, the routed experts at the share of a token's
    `num_experts_per_tok` assignments that a balanced router sends to the
    `num_experts` held of `num_experts_published`."""
    h = model["hidden_size"]
    hd = model["num_attention_heads"] * model["head_dim"]
    kvd = model["num_key_value_heads"] * model["head_dim"]
    attn = h * (2 * hd + 2 * kvd) + hd * h  # q, gate, k, v; o
    dense = 3 * h * model["intermediate_size"]
    held = (model["num_experts_per_tok"] * model["num_experts"]
            / model["num_experts_published"])
    expert = (h * model["num_experts_published"]
              + 3 * h * model["moe_intermediate_size"]
              * (model["num_shared_experts"] + held))
    total = sum(attn + (dense if is_dense else expert)
                for _, _, is_dense in held_layers(model))
    return total + h * model["vocab_size"]


def admitted_pairs(s: int, window: int) -> int:
    """(query, key) pairs of one head that the masks admit over a row of
    `s` tokens: query i sees min(i + 1, window) keys, all i + 1 of them
    where there is no window (0)."""
    full = min(s, window) if window else s
    return full * (full + 1) // 2 + (s - full) * full


def flops_per_example(model: dict, traffic: dict) -> float:
    """Matrix-product FLOPs forward and backward (3 x forward) for one
    document, from the shapes: two a weight a token
    (`matrix_params_per_token`) and, for attention, the scores and the
    values of only the pairs the masks admit (2 x head_dim each a pair a
    head), so that masked work a kernel does cannot flatter the
    utilisation. The embedding gather, the router's sort, the norms, the
    rotation and the optimizer do not count."""
    s = traffic["seq_len"]
    attn = sum(admitted_pairs(s, window) for _, window, _ in held_layers(model)
               ) * model["num_attention_heads"] * 4 * model["head_dim"]
    return 3.0 * (2 * s * matrix_params_per_token(model) + attn)


def flash_flops_per_step(model: dict, traffic: dict) -> dict:
    """Useful FLOPs of the blocked attention kernels' calls in one train
    step, by kernel name, one entry a call (a layer): the pairs the masks
    admit x head_dim x heads x batch x the products a pair costs there.
    `flash_fwd` computes q.k and p.v (4 a pair a lane); `flash_bwd_dq`
    q.k again, dO.v and dS.k (6); `flash_bwd_dkv` q.k, dO.v, p^T.dO and
    dS^T.q (8). The forward op and its gradient op share one `flash_fwd`
    call a layer. What a kernel computes of masked pairs inside the blocks
    it visits is not useful and does not count."""
    lanes = (traffic["batch"] * model["num_attention_heads"]
             * model["head_dim"])
    pairs = [admitted_pairs(traffic["seq_len"], window)
             for _, window, _ in held_layers(model)]
    return {name: [cost * lanes * n for n in pairs]
            for name, cost in (("flash_fwd", 4), ("flash_bwd_dq", 6),
                               ("flash_bwd_dkv", 8))}


# ------------------------------------------------------------ reference


def _rms(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _silu(x):
    import jax

    return x * jax.nn.sigmoid(x)


def _ffn(p, u, name):
    return (_silu(u @ p[name + ".gate.w_0"]) * (u @ p[name + ".up.w_0"])
            ) @ p[name + ".down.w_0"]


def _rope(x, theta):
    """x: [b, s, heads, d], positions 0..s-1, rotate-half:
    `x * cos + [-x2, x1] * sin`, the angles of the first half repeated."""
    import jax.numpy as jnp

    s, d = x.shape[1], x.shape[3]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], -1)[None, :, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(angle) + turned * jnp.sin(angle)


def attention_mixer(p, u, name, model, window, wrong=()):
    """u: [b, s, hidden] -> [b, s, hidden]. `window` 0: a full layer,
    which has no positions."""
    import jax
    import jax.numpy as jnp

    h, g, d = (model["num_attention_heads"], model["num_key_value_heads"],
               model["head_dim"])
    eps = model["rms_norm_eps"]
    b, s, _ = u.shape
    q = (u @ p[name + ".q.w_0"]).reshape(b, s, h, d)
    k = (u @ p[name + ".k.w_0"]).reshape(b, s, g, d)
    v = (u @ p[name + ".v.w_0"]).reshape(b, s, g, d)
    if "no_qk_norm" not in wrong:
        q = _rms(q, p[name + ".q_norm.w_0"], eps)
        k = _rms(k, p[name + ".k_norm.w_0"], eps)
    if window and "no_rope" not in wrong:
        q, k = _rope(q, model["rope_theta"]), _rope(k, model["rope_theta"])
    if "all_full" in wrong:
        window = 0
    # query head n reads key/value head n // (h / g)
    kv_of = (jnp.arange(h) % g if "group_mod" in wrong
             else jnp.arange(h) // (h // g))
    k, v = k[:, :, kv_of], v[:, :, kv_of]
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)
        first = max(0, lo - window + 1) if window else 0
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, first:hi])
        scores = scores / math.sqrt(d)
        behind = jnp.arange(lo, hi)[:, None] - jnp.arange(first, hi)[None, :]
        visible = behind >= 0
        if window:
            visible = visible & (behind < window)
        scores = jnp.where(visible, scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                              v[:, first:hi]))
    a = jnp.concatenate(out, 1).reshape(b, s, h * d)
    if "no_gate" not in wrong:
        a = a * jax.nn.sigmoid(u @ p[name + ".gate.w_0"])
    return a @ p[name + ".o.w_0"]


def expert_ffn(p, u, name, model):
    """The shared expert and the experts held: one dense FFN an expert over
    every token, weighted by what the router gave that expert there."""
    import jax
    import jax.numpy as jnp

    k = model["num_experts_per_tok"]
    scores = jax.nn.sigmoid(u @ p[name + ".moe.gate"])
    _, chosen = jax.lax.top_k(scores + p[name + ".moe.bias"], k)
    w = jnp.take_along_axis(scores, chosen, -1)
    if model["route_norm"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * model["route_scale"]
    y = _ffn(p, u, name + ".shared") if model["num_shared_experts"] else 0.0
    for e in range(model["num_experts"]):
        here = jnp.sum(jnp.where(chosen == model["held_from"] + e, w, 0.0), -1)
        one = (_silu(u @ p[name + ".moe.w_gate"][e])
               * (u @ p[name + ".moe.w_up"][e])) @ p[name + ".moe.w_down"][e]
        y = y + here[..., None] * one
    return y


def reference(p: dict, batch: dict, model: dict, drop_layers: int = 0,
              wrong=()):
    """Forward pass on some rows of a batch. Returns the sum of the
    negative log-likelihoods of the labels, their count, and the logits at
    every `SCORED_EVERY`-th position, `[rows, s / SCORED_EVERY, vocab]`.
    `drop_layers` leaves out that many of the last layers and `wrong`
    names departures of `WRONG` (every layer full, no positions, no gate,
    no QK-norm, the group mapped `n % 4`): the tests and the chip readings
    use them to show that a wrong model is caught."""
    import jax
    import jax.numpy as jnp

    eps = model["rms_norm_eps"]
    x = p["trinity.embed"][batch["tokens"]]
    if model["mup_enabled"]:
        x = x * math.sqrt(model["hidden_size"])
    layers = held_layers(model)
    for i, window, dense in layers[:len(layers) - drop_layers]:
        n = f"trinity.layer{i}"
        u = _rms(x, p[n + ".input_norm.w_0"], eps)
        m = attention_mixer(p, u, n + ".attn", model, window, wrong)
        x = x + _rms(m, p[n + ".post_attn_norm.w_0"], eps)
        u = _rms(x, p[n + ".pre_mlp_norm.w_0"], eps)
        f = _ffn(p, u, n + ".mlp") if dense else expert_ffn(p, u, n, model)
        x = x + _rms(f, p[n + ".post_mlp_norm.w_0"], eps)
    logits = _rms(x, p["trinity.final_norm.w_0"], eps) @ p["trinity.head.w_0"]
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, batch["labels"][..., None], -1)[..., 0]
    return (jnp.sum(nll), jnp.asarray(nll.size, jnp.float32),
            logits[:, ::SCORED_EVERY])
