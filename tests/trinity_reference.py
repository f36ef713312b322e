"""Trinity in plain float32 `jax.numpy`: the reference that
`tests/test_trinity_reference.py` holds the program to, mixer by mixer,
for the whole model, and for one train step's gradients.

From `# --- reference` on this is `benchmark/models/trinity.py`'s
reference word for word (a test holds the two files to that): the
equations of `paddle_tpu/models/trinity.py`'s docstring with attention as
plain softmax over explicit masks in blocks of queries, K and V repeated
for the group by indexing, positions written out with a concatenation,
and the experts as a loop over the experts held. It shares nothing with
`paddle_tpu`'s lowerings but the parameters' names. `loss` is this
file's own: the mean negative log-likelihood, for `jax.grad`.
"""

from __future__ import annotations

import math

SCORED_EVERY = 16
QUERY_BLOCK = 512


def held_layers(model: dict) -> list[tuple[int, int, bool]]:
    """(published index, window or 0, dense?) of each layer held."""
    first = model["first_layer_held"]
    return [(i, model["sliding_window"]
             if model["layer_types"][i] == "sliding_attention" else 0,
             i < model["num_dense_layers"])
            for i in range(first, first + model["num_hidden_layers"])]


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
