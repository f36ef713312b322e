"""Mellum 2 in plain float32 `jax.numpy`: the reference that
`tests/test_mellum_reference.py` holds the program to, mixer by mixer,
for the whole model, and for one train step's gradients.

From `# --- reference` on this is `benchmark/models/mellum.py`'s
reference word for word (a test holds the two files to that): the
equations of `paddle_tpu/models/mellum.py`'s docstring with attention as
plain softmax over explicit masks in blocks of queries, K and V repeated
for the group by indexing, positions written out with a concatenation,
YaRN's tables from the formulas, and the experts as a loop over the
experts held. It shares nothing with `paddle_tpu`'s lowerings but the
parameters' names. `loss` is this file's own: the mean negative
log-likelihood, for `jax.grad`.
"""

from __future__ import annotations

import math

SCORED_EVERY = 16
QUERY_BLOCK = 512


def held_layers(model: dict) -> list[tuple[int, int, str]]:
    """(published index, window or 0, kind) of each layer held."""
    first = model["first_layer_held"]
    kinds = model["layer_types"][first:first + model["num_hidden_layers"]]
    return [(first + at, model["sliding_window"]
             if kind == "sliding_attention" else 0, kind)
            for at, kind in enumerate(kinds)]


def loss(p, batch, model):
    nll, count, _ = reference(p, batch, model)
    return nll / count


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
