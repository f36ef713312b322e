"""LFM2 in plain float32 `jax.numpy`: the reference that
`tests/test_lfm2_reference.py` holds the program to, mixer by mixer, for
the whole model, and for one train step's gradients.

From `# --- reference` on this is `benchmark/models/lfm2.py`'s reference
word for word (a test holds the two files to that): the equations of
`paddle_tpu/models/lfm2.py`'s docstring with the convolution as three
shifted products between two plain multiplications, attention as plain
softmax over an explicit mask in blocks of queries, K and V repeated for
the group by indexing, positions written out with a concatenation, and
the experts as a loop over the experts held. It shares nothing with
`paddle_tpu`'s lowerings but the parameters' names. `loss` is this file's
own: the mean negative log-likelihood, for `jax.grad`.
"""

from __future__ import annotations

import math

SCORED_EVERY = 16
QUERY_BLOCK = 512


def held_layers(model: dict) -> list[tuple[int, str, bool]]:
    """(published index, "conv" or "full_attention", dense?) of each
    layer held."""
    first = model["first_layer_held"]
    return [(i, model["layer_types"][i], i < model["num_dense_layers"])
            for i in range(first, first + model["num_hidden_layers"])]


def loss(p, batch, model):
    nll, count, _ = reference(p, batch, model)
    return nll / count


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
