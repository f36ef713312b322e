"""LFM2-24B-A2B, one chip's share of an 8-way expert-parallel job: the
Program through the repo's public builder, seeded documents, FLOPs per
example, and the plain reference.

The reference is written from the equations in
`paddle_tpu/models/lfm2.py`'s docstring (the model's public `config.json`;
the public `modeling_lfm2_moe.py` of `transformers` for what the config
leaves open, listed under `assumed` in the configuration file) in float32
`jax.numpy`. It shares nothing with `paddle_tpu`'s lowerings but the
parameters' names:

- The convolution is three shifted products over a padded copy, between
  two plain multiplications; the program's is the op `short_conv1d`, whose
  backward is a kernel.
- Attention is plain softmax over an explicit mask, in blocks of queries so
  that the float32 scores of 32 heads x 512 x 8,192 (0.5 GB) fit beside
  the state the device holds during the set-up check. K and V are
  repeated for the group by indexing; the program's kernels index the key
  block by `head // 4` and repeat nothing.
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
SCORED_EVERY = 16  # ... at every sixteenth position of each (268 MB otherwise)
QUERY_BLOCK = 512  # the reference's attention, queries a block

# |program - reference| on the scored logits over the reference's own
# root-mean-square, and on the loss; the logits' limit lies between two
# readings on the chip (PERF.md section 6, PR 47, has every one). The
# program computes its matrix products in bf16 with float32 accumulation
# and keeps its activations, the residual stream, the gates and the
# convolution's input and output among them, in bf16; the router, the
# softmax, the taps, the rotation and the norms' statistics are float32.
# Through five layers it read 1.89 to 2.92% on the logits and at most
# 0.0007 on the loss over this PR's 39 seeds (PERF.md has how many and at
# which seedings of the router's correction). The reference with its
# matrices rounded to fp8 (e4m3), the nearest precision below, reads 20.0
# to 20.1% on the logits at every seed tried, which is what refuses it,
# and 0.0007 to 0.0035 on the loss (over the loss's limit at one seed of
# them). The wrong models of `WRONG` read 14% (the group mapped `n % 8`),
# 76 to 78% (the SiLU left on the convolution) and 131 to 132% (its two
# gates swapped). The limit, 6%, leaves 2.1 times the program's largest
# reading of room, since fresh seeds read higher, and has the fp8
# reference 3.3 times and the mildest of those wrong models 2.3 times
# above it.
# **What the chip's limits cannot tell apart** from a program whose
# matrices and activations are bf16 already: the four lowerings to bf16
# (the reference with the taps in bf16 reads 2.36 to 2.85% against this
# program, the router 2.34 to 2.57%, the softmax 2.29 to 2.60%, the
# norms 2.66 to 2.77%, where the right reference reads 2.29 to 2.60%),
# and three mild wrong models: no QK-norm at all (2.87 to 3.07%: q and k
# come from a normed input through matrices seeded to keep its length,
# so a head's norm is near 1 before it is normed), the router's
# correction inside the weights (2.75 to 2.84% with the correction seeded
# at 0.1: renormalised weights move by a tenth and the expert layer is one
# addend of five; the cell seeds it zeros, where it is no departure), and
# QK-norm
# after the positions, which with the norms' weights at their seeded 1 is
# the same model to the last bit (a rotation keeps a head's length). They
# are caught where the program is float32:
# `tests/test_lfm2_reference.py` holds the float32 program to 5e-5 on the
# logits, each of the four lowerings reads at least nine times that at
# the rehearsal size, and the mild wrong models, with the norms' weights
# moved off 1, hundreds of times.
# The loss's limit is the one the harness's other decoder cells have, 4.5
# times the largest reading: the model hands its loss back in float32 and
# a wrong model moves a mean of 8,192 log-likelihoods by less than the
# logits show; the logits carry the check.
TOLERANCE = {"logits_rel_rms": 0.06, "loss_abs": 0.003}

# what `reference(wrong=...)` can be made to get wrong, for the tests and
# the chip readings that place the limits: the four lowerings to bf16
# first, then wrong models
WRONG = ("taps_bf16", "router_bf16", "softmax_bf16", "norm_bf16",
         "conv_silu", "gates_swapped", "norm_after_rope", "bias_in_weights",
         "no_qk_norm", "group_mod")


def held_layers(model: dict) -> list[tuple[int, str, bool]]:
    """(published index, "conv" or "full_attention", dense?) of each
    layer held."""
    first = model["first_layer_held"]
    return [(i, model["layer_types"][i], i < model["num_dense_layers"])
            for i in range(first, first + model["num_hidden_layers"])]


def config(model: dict):
    from paddle_tpu.models.lfm2 import Lfm2Config

    layers = held_layers(model)
    return Lfm2Config(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        layer_types=[kind for _, kind, _ in layers],
        first_layer=model["first_layer_held"],
        dense_layers=sum(dense for _, _, dense in layers),
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"], conv_L_cache=model["conv_L_cache"],
        rope_theta=model["rope_parameters"]["rope_theta"],
        intermediate_size=model["intermediate_size"],
        moe_intermediate_size=model["moe_intermediate_size"],
        num_experts=model["num_experts_published"],
        experts_held=model["num_experts"], held_from=model["held_from"],
        num_experts_per_token=model["num_experts_per_tok"],
        routed_scaling_factor=model["routed_scaling_factor"],
        norm_topk_prob=model["norm_topk_prob"], norm_eps=model["norm_eps"],
        initializer_range=model["initializer_range"],
        router_bias_scale=model["router_bias_scale"])


def build(model: dict, traffic: dict) -> dict:
    """Declare the training program in the current default programs.
    `check` names what the reference check fetches from the `for_test`
    clone: the loss and the logits at every `SCORED_EVERY`-th position."""
    from paddle_tpu import layers
    from paddle_tpu.models.lfm2 import build_lfm2

    b, s = traffic["batch"], traffic["seq_len"]
    handles = build_lfm2(config(model), b, s)
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


def mixer_matrix_params(model: dict, kind: str) -> int:
    """Weights of the matrix products of one mixer: `W_in` and `W_out` of
    a convolution layer, or q, k, v and o."""
    h = model["hidden_size"]
    hd = model["num_attention_heads"] * model["head_dim"]
    kvd = model["num_key_value_heads"] * model["head_dim"]
    return {"conv": 3 * h * h + h * h,
            "full_attention": h * (hd + 2 * kvd) + hd * h}[kind]


def matrix_params_per_token(model: dict) -> float:
    """Weights of the matrix products one token passes through in the
    layers held here, the routed experts at the share of a token's
    `num_experts_per_tok` assignments that a balanced router sends to the
    `num_experts` held of `num_experts_published`, and the head's slice
    (tied: the gather of the same rows on the way in is no product)."""
    h = model["hidden_size"]
    dense = 3 * h * model["intermediate_size"]
    held = (model["num_experts_per_tok"] * model["num_experts"]
            / model["num_experts_published"])
    expert = (h * model["num_experts_published"]
              + 3 * h * model["moe_intermediate_size"] * held)
    total = sum(mixer_matrix_params(model, kind)
                + (dense if is_dense else expert)
                for _, kind, is_dense in held_layers(model))
    return total + h * model["vocab_size"]


def flops_per_example(model: dict, traffic: dict) -> float:
    """Matrix-product FLOPs forward and backward (3 x forward) for one
    document, from the shapes: two a weight a token
    (`matrix_params_per_token`) and, for each attention layer, the scores
    and the values of only the pairs the causal mask admits (2 x head_dim
    each a pair a head), so that masked work a kernel does, and the lanes
    it pads a 64-lane head to, cannot flatter the utilisation. The
    convolutions' taps and gates (no matrix product), the embedding
    gather, the router's sort, the norms, the rotation and the optimizer
    do not count."""
    s = traffic["seq_len"]
    attention_layers = sum(kind == "full_attention"
                           for _, kind, _ in held_layers(model))
    attn = (attention_layers * (s * (s + 1) // 2)
            * model["num_attention_heads"] * 4 * model["head_dim"])
    return 3.0 * (2 * s * matrix_params_per_token(model) + attn)


# ------------------------------------------------------------ reference


def _rms(x, w, eps, dtype=None):
    """`dtype` (a wrong lowering): the statistics and the products in it."""
    import jax.numpy as jnp

    if dtype is not None:
        x, w = x.astype(dtype), w.astype(dtype)
    y = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w
    return y.astype(jnp.float32)


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


def _conv(a, f, dtype=None):
    """Causal, per channel, zero state, no bias, no activation: a
    [b, s, c], f [c, width]. `dtype` (a wrong lowering): the taps'
    products and their sum in it."""
    import jax.numpy as jnp

    width, s = f.shape[1], a.shape[1]
    if dtype is not None:
        a, f = a.astype(dtype), f.astype(dtype)
    padded = jnp.pad(a, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(padded[:, i:i + s] * f[:, i] for i in range(width)).astype(
        jnp.float32)


def conv_mixer(p, u, name, model, wrong=()):
    """u: [b, s, hidden] -> [b, s, hidden]: `W_out (C * conv(B * x))`,
    `[B ; C ; x] = W_in u`."""
    import jax.numpy as jnp

    h = model["hidden_size"]
    both = u @ p[name + ".in_proj.w_0"]
    b_gate, c_gate, xs = both[..., :h], both[..., h:2 * h], both[..., 2 * h:]
    if "gates_swapped" in wrong:
        b_gate, c_gate = c_gate, b_gate
    c = _conv(b_gate * xs, p[name + ".conv.w_0"],
              jnp.bfloat16 if "taps_bf16" in wrong else None)
    if "conv_silu" in wrong:
        c = _silu(c)
    return (c_gate * c) @ p[name + ".out_proj.w_0"]


def attention_mixer(p, u, name, model, wrong=()):
    """u: [b, s, hidden] -> [b, s, hidden]: full causal, positions on
    every layer, no gate."""
    import jax
    import jax.numpy as jnp

    h, g, d = (model["num_attention_heads"], model["num_key_value_heads"],
               model["head_dim"])
    eps, theta = model["norm_eps"], model["rope_parameters"]["rope_theta"]
    low = jnp.bfloat16 if "norm_bf16" in wrong else None
    b, s, _ = u.shape
    q = (u @ p[name + ".q.w_0"]).reshape(b, s, h, d)
    k = (u @ p[name + ".k.w_0"]).reshape(b, s, g, d)
    v = (u @ p[name + ".v.w_0"]).reshape(b, s, g, d)
    if "norm_after_rope" in wrong:
        q, k = _rope(q, theta), _rope(k, theta)
    if "no_qk_norm" not in wrong:
        q = _rms(q, p[name + ".q_norm.w_0"], eps, low)
        k = _rms(k, p[name + ".k_norm.w_0"], eps, low)
    if "norm_after_rope" not in wrong:
        q, k = _rope(q, theta), _rope(k, theta)
    # query head n reads key/value head n // (h / g)
    kv_of = (jnp.arange(h) % g if "group_mod" in wrong
             else jnp.arange(h) // (h // g))
    k, v = k[:, :, kv_of], v[:, :, kv_of]
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi])
        scores = scores / math.sqrt(d)
        visible = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        scores = jnp.where(visible, scores, -jnp.inf)
        if "softmax_bf16" in wrong:
            scores = scores.astype(jnp.bfloat16)
        weights = jax.nn.softmax(scores, -1).astype(jnp.float32)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", weights, v[:, :hi]))
    a = jnp.concatenate(out, 1).reshape(b, s, h * d)
    return a @ p[name + ".o.w_0"]


def expert_ffn(p, u, name, model, wrong=()):
    """The experts held: one dense FFN an expert over every token,
    weighted by what the router gave that expert there. No shared
    expert."""
    import jax
    import jax.numpy as jnp

    k = model["num_experts_per_tok"]
    gate = p[name + ".moe.gate"]
    if "router_bf16" in wrong:
        scores = jax.nn.sigmoid(jnp.dot(
            u.astype(jnp.bfloat16), gate.astype(jnp.bfloat16))).astype(
                jnp.float32)
    else:
        scores = jax.nn.sigmoid(u @ gate)
    biased = scores + p[name + ".moe.bias"]
    _, chosen = jax.lax.top_k(biased, k)
    w = jnp.take_along_axis(biased if "bias_in_weights" in wrong else scores,
                            chosen, -1)
    if model["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + model["router_norm_eps"])
    w = w * model["routed_scaling_factor"]
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
    names departures of `WRONG` (the taps, the router, the softmax or the
    norms in bf16; the SiLU left on the convolution, its two gates
    swapped, QK-norm after the positions, the router's correction inside
    the weights, no QK-norm, the group mapped `n % 8`): the tests and the
    chip readings use them to show that a wrong model is caught."""
    import jax
    import jax.numpy as jnp

    eps = model["norm_eps"]
    low = jnp.bfloat16 if "norm_bf16" in wrong else None
    x = p["lfm2.embed"][batch["tokens"]]
    layers = held_layers(model)
    for i, kind, dense in layers[:len(layers) - drop_layers]:
        n = f"lfm2.layer{i}"
        u = _rms(x, p[n + ".operator_norm.w_0"], eps, low)
        if kind == "conv":
            x = x + conv_mixer(p, u, n + ".conv", model, wrong)
        else:
            x = x + attention_mixer(p, u, n + ".attn", model, wrong)
        u = _rms(x, p[n + ".ffn_norm.w_0"], eps, low)
        x = x + (_ffn(p, u, n + ".mlp") if dense
                 else expert_ffn(p, u, n, model, wrong))
    logits = _rms(x, p["lfm2.embedding_norm.w_0"], eps, low) @ p["lfm2.embed"].T
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, batch["labels"][..., None], -1)[..., 0]
    return (jnp.sum(nll), jnp.asarray(nll.size, jnp.float32),
            logits[:, ::SCORED_EVERY])
