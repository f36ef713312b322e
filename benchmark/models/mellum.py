"""Mellum2-12B-A2.5B, one chip's share of a four-chip expert-parallel host:
the Program through the repo's public builder, seeded documents, FLOPs per
example, and the plain reference.

The reference is written from the equations in
`paddle_tpu/models/mellum.py`'s docstring (the model's public
`config.json`; what it leaves open is listed under `assumed` in the
configuration file) in float32 `jax.numpy`. It shares nothing with
`paddle_tpu`'s lowerings but the parameters' names:

- Attention is plain softmax over explicit masks, in blocks of queries so
  that the float32 scores of 32 heads x 512 x 8,192 (0.5 GB) fit beside
  the state the device holds during the set-up check. K and V are
  repeated for the group by indexing; the program's kernels index the key
  block by `head // 8` and repeat nothing.
- Positions are the rotate-half form written out with a concatenation, and
  YaRN's tables are written out from the formulas; the program rolls the
  lanes, folds the sign into the sine and builds its tables in
  `rotary_tables`, which this file does not call.
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
SCORED_EVERY = 16  # ... at every sixteenth position of each (805 MB otherwise)
QUERY_BLOCK = 512  # the reference's attention, queries a block

# |program - reference| on the scored logits over the reference's own
# root-mean-square, and on the loss; the logits' limit lies between two
# readings on the chip (PERF.md section 6, PR 37, has every one). The
# program computes its matrix products in bf16 with float32 accumulation
# and keeps its activations, the residual stream among them, in bf16:
# through four layers it read 0.598-0.683% on the logits and at most
# 0.00051 on the loss over thirty seeds. The reference with its matrices
# rounded to fp8 (e4m3) reads 4.83-4.88% on the logits, which is what
# refuses it, and 0.0015-0.0023 on the loss; the mildest wrong model of
# `WRONG` (a sigmoid router) reads 2.16-2.63%, no QK-norm 2.99-3.49%, no
# renormalisation 3.43-3.73%, YaRN's tables without their factor
# 4.75-4.96%, default tables on the full layer 6.77-7.13%, every layer full
# 7.30-7.46%. The logits' limit leaves 2.2 times the program's largest
# reading of room, since fresh seeds read higher, and the mildest wrong
# model 1.4 times above it. The loss's limit is the one the harness's other
# two decoder cells have, six times the largest reading: the model
# hands its loss back in float32, a wrong model moves the mean of 8,192
# log-likelihoods by as little as 0.00004, and the tiny preset's mean of 96
# reads up to 0.0015 under bf16; the logits carry the check. What the
# limits cannot tell apart is the reference with bf16 matrices: the
# program's are bf16 already.
TOLERANCE = {"logits_rel_rms": 0.015, "loss_abs": 0.003}

# what `reference(wrong=...)` can be made to get wrong, for the tests and
# the chip readings that place the limits
WRONG = ("no_yarn", "no_attention_factor", "sigmoid_router", "no_renorm",
         "all_full", "no_qk_norm")


def held_layers(model: dict) -> list[tuple[int, int, str]]:
    """(published index, window or 0, kind) of each layer held."""
    first = model["first_layer_held"]
    kinds = model["layer_types"][first:first + model["num_hidden_layers"]]
    return [(first + at, model["sliding_window"]
             if kind == "sliding_attention" else 0, kind)
            for at, kind in enumerate(kinds)]


def config(model: dict):
    from paddle_tpu.models.mellum import MellumConfig

    return MellumConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        layer_types=[kind for _, _, kind in held_layers(model)],
        first_layer=model["first_layer_held"],
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"], sliding_window=model["sliding_window"],
        rope_parameters=model["rope_parameters"],
        moe_intermediate_size=model["moe_intermediate_size"],
        num_experts=model["num_experts_published"],
        experts_held=model["num_experts"], held_from=model["held_from"],
        num_experts_per_token=model["num_experts_per_tok"],
        norm_topk_prob=model["norm_topk_prob"],
        rms_norm_eps=model["rms_norm_eps"],
        initializer_range=model["initializer_range"],
        embedding_initializer_range=model["embedding_initializer_range"])


def build(model: dict, traffic: dict) -> dict:
    """Declare the training program in the current default programs.
    `check` names what the reference check fetches from the `for_test`
    clone: the loss and the logits at every `SCORED_EVERY`-th position."""
    from paddle_tpu import layers
    from paddle_tpu.models.mellum import build_mellum

    b, s = traffic["batch"], traffic["seq_len"]
    handles = build_mellum(config(model), b, s)
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
    `num_experts` held of `num_experts_published`: 2 of 64."""
    h = model["hidden_size"]
    hd = model["num_attention_heads"] * model["head_dim"]
    kvd = model["num_key_value_heads"] * model["head_dim"]
    attn = h * (hd + 2 * kvd) + hd * h  # q, k, v; o
    held = (model["num_experts_per_tok"] * model["num_experts"]
            / model["num_experts_published"])
    expert = (h * model["num_experts_published"]
              + 3 * h * model["moe_intermediate_size"] * held)
    return (len(held_layers(model)) * (attn + expert)
            + h * model["vocab_size"])


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


# ------------------------------------------------------------ reference


def _rms(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _silu(x):
    import jax

    return x * jax.nn.sigmoid(x)


def yarn(d, rope):
    """(frequencies [d/2], factor on cos and sin, low, high) of a YaRN
    group, from the formulas: e_i = theta^(-2i/d); c(r) = d ln(original /
    (2 pi r)) / (2 ln theta); low = max(floor(c(beta_fast)), 0); high =
    min(ceil(c(beta_slow)), d - 1); ramp_i = clip((i - low) / (high -
    low), 0, 1); f_i = e_i / factor * ramp_i + e_i (1 - ramp_i)."""
    import jax.numpy as jnp

    theta, original = rope["rope_theta"], rope["original_max_position_embeddings"]

    def c(r):
        return d * math.log(original / (2 * math.pi * r)) / (2 * math.log(theta))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), d - 1)
    i = jnp.arange(d // 2, dtype=jnp.float32)
    e = 1.0 / theta ** (2 * i / d)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (e / rope["factor"] * ramp + e * (1 - ramp),
            rope["attention_factor"], low, high)


def _rope(x, rope, wrong=()):
    """x: [b, s, heads, d], positions 0..s-1, rotate-half:
    `x * cos + [-x2, x1] * sin`, the angles of the first half repeated;
    `rope` is the layer kind's group of `rope_parameters`."""
    import jax.numpy as jnp

    s, d = x.shape[1], x.shape[3]
    factor = 1.0
    if rope["rope_type"] == "yarn" and "no_yarn" not in wrong:
        inv_freq, factor, _, _ = yarn(d, rope)
        if "no_attention_factor" in wrong:
            factor = 1.0
    else:
        inv_freq = 1.0 / rope["rope_theta"] ** (
            jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], -1)[None, :, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * (jnp.cos(angle) * factor) + turned * (jnp.sin(angle) * factor)


def attention_mixer(p, u, name, model, window, rope, wrong=()):
    """u: [b, s, hidden] -> [b, s, hidden]. `window` 0: a full layer."""
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
    q, k = _rope(q, rope, wrong), _rope(k, rope, wrong)
    if "all_full" in wrong:
        window = 0
    # query head n reads key/value head n // (h / g)
    kv_of = jnp.arange(h) // (h // g)
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
    return a @ p[name + ".o.w_0"]


def expert_ffn(p, u, name, model, wrong=()):
    """The experts held: one dense FFN an expert over every token,
    weighted by what the router gave that expert there."""
    import jax
    import jax.numpy as jnp

    k = model["num_experts_per_tok"]
    logits = u @ p[name + ".moe.gate"]
    scores = (jax.nn.sigmoid(logits) if "sigmoid_router" in wrong
              else jax.nn.softmax(logits, -1))
    w, chosen = jax.lax.top_k(scores, k)
    if model["norm_topk_prob"] and "no_renorm" not in wrong:
        w = w / jnp.sum(w, -1, keepdims=True)
    y = 0.0
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
    names departures of `WRONG` (default tables on the full layer, YaRN's
    tables without their factor, a sigmoid router, no renormalisation,
    every layer full, no QK-norm): the tests and the chip readings use
    them to show that a wrong model is caught."""
    import jax
    import jax.numpy as jnp

    eps = model["rms_norm_eps"]
    x = p["mellum.embed"][batch["tokens"]]
    layers = held_layers(model)
    for i, window, kind in layers[:len(layers) - drop_layers]:
        n = f"mellum.layer{i}"
        u = _rms(x, p[n + ".input_norm.w_0"], eps)
        x = x + attention_mixer(p, u, n + ".attn", model, window,
                                model["rope_parameters"][kind], wrong)
        u = _rms(x, p[n + ".post_attn_norm.w_0"], eps)
        x = x + expert_ffn(p, u, n, model, wrong)
    logits = _rms(x, p["mellum.final_norm.w_0"], eps) @ p["mellum.head.w_0"]
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, batch["labels"][..., None], -1)[..., 0]
    return (jnp.sum(nll), jnp.asarray(nll.size, jnp.float32),
            logits[:, ::SCORED_EVERY])
