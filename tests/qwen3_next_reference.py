"""Qwen3-Next in plain float32 `jax.numpy`: the reference that
`tests/test_qwen3_next_reference.py` holds the program to, mixer by mixer,
for the whole model, and for one train step's gradients.

From `# --- reference` on this is `benchmark/models/qwen3_next.py`'s
reference word for word (a test holds the two files to that): the
equations of `paddle_tpu/models/qwen3_next.py`'s docstring with the delta
rule as the recurrence itself, a token a step under `lax.scan`, q and k
repeated for the pair of value heads by indexing, the convolution as four
shifted products, attention as plain softmax over an explicit mask in
blocks of queries, the rotation written out on the first 64 lanes with a
concatenation, and the experts as a loop over the experts held. It shares
nothing with `paddle_tpu`'s lowerings but the parameters' names. `loss` is
this file's own: the mean negative log-likelihood, for `jax.grad`.
"""

from __future__ import annotations

import math

SCORED_EVERY = 16
QUERY_BLOCK = 512


def held_layers(model: dict) -> list[tuple[int, str]]:
    """(published index, "linear_attention" or "full_attention") of each
    layer held: layer l is a full-attention layer iff (l + 1) is a
    multiple of `full_attention_interval`."""
    first = model["first_layer_held"]
    return [(l, "full_attention"
             if (l + 1) % model["full_attention_interval"] == 0
             else "linear_attention")
            for l in range(first, first + model["num_hidden_layers"])]


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


def _rope(x, theta, lanes):
    """x: [b, s, heads, d], positions 0..s-1: the first `lanes` lanes turn
    in the rotate-half form, `x * cos + [-x2, x1] * sin` with the angles
    of their first half repeated, and the other lanes pass."""
    import jax.numpy as jnp

    s = x.shape[1]
    turning, passing = x[..., :lanes], x[..., lanes:]
    inv_freq = 1.0 / theta ** (jnp.arange(0, lanes, 2, dtype=jnp.float32)
                               / lanes)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], -1)[None, :, None, :]
    swapped = jnp.concatenate(
        [-turning[..., lanes // 2:], turning[..., :lanes // 2]], -1)
    return jnp.concatenate(
        [turning * jnp.cos(angle) + swapped * jnp.sin(angle), passing], -1)


def _conv(a, f):
    """Causal, per channel, zero state, no bias: a [b, s, c],
    f [c, width]; before the SiLU."""
    import jax.numpy as jnp

    width, s = f.shape[1], a.shape[1]
    padded = jnp.pad(a, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(padded[:, i:i + s] * f[:, i] for i in range(width))


def delta_recurrence(q, k, v, g, beta):
    """The gated delta rule with one decay a head, one `lax.scan` step a
    token. q, k: [b, s, h, dk] (already a key head a value head);
    v: [b, s, h, dv]; g, the log of the decay, and beta: [b, s, h].
    `S' = exp(g_t) S; S = S' + beta_t k_t (v_t - S'^T k_t)^T;
    o_t = dk^-1/2 S^T q_t`, from a zero state."""
    import jax
    import jax.numpy as jnp

    b, _, h, dk = q.shape

    def token(state, x):  # state [b, h, dk, dv]
        q, k, v, g, beta = x
        state = jnp.exp(g)[..., None, None] * state
        seen = jnp.einsum("bhkv,bhk->bhv", state, k)
        state = state + beta[..., None, None] * (
            k[..., :, None] * (v - seen)[..., None, :])
        return state, jnp.einsum("bhkv,bhk->bhv", state, q) / math.sqrt(dk)

    _, o = jax.lax.scan(
        token, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def delta_mixer(p, u, name, model, wrong=()):
    """u: [b, s, hidden] -> [b, s, hidden]: Gated DeltaNet, token by
    token."""
    import jax
    import jax.numpy as jnp

    hk, hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    b, s, _ = u.shape
    both = u @ p[name + ".in_proj_qkvz.w_0"]
    qkv, z = both[..., :2 * hk * dk + hv * dv], both[..., 2 * hk * dk + hv * dv:]
    ba = u @ p[name + ".in_proj_ba.w_0"]
    beta, a = jax.nn.sigmoid(ba[..., :hv]), ba[..., hv:]
    qkv = _conv(qkv, p[name + ".conv.w_0"])
    if "no_conv_silu" not in wrong:
        qkv = _silu(qkv)
    q = qkv[..., :hk * dk].reshape(b, s, hk, dk)
    k = qkv[..., hk * dk:2 * hk * dk].reshape(b, s, hk, dk)
    v = qkv[..., 2 * hk * dk:].reshape(b, s, hv, dv)

    def unit(t):
        return t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True)
                            + model["l2norm_epsilon"])

    # value head n reads key head n // (hv / hk)
    key_of = (jnp.arange(hv) % hk if "key_head_mod" in wrong
              else jnp.arange(hv) // (hv // hk))
    q, k = unit(q)[:, :, key_of], unit(k)[:, :, key_of]
    g = -jnp.exp(p[name + ".A_log"]) * jax.nn.softplus(
        a + p[name + ".dt_bias"])  # [b, s, hv]
    if "one_decay" in wrong:  # the first head's decay for every head
        g = jnp.broadcast_to(g[..., :1], g.shape)
    o = _rms(delta_recurrence(q, k, v, g, beta), p[name + ".norm.w_0"],
             model["rms_norm_eps"])
    y = o.reshape(b, s, hv * dv) * _silu(z)
    return y @ p[name + ".out_proj.w_0"]


def attention_mixer(p, u, name, model, wrong=()):
    """u: [b, s, hidden] -> [b, s, hidden]: full causal, grouped heads,
    positions on the first lanes of a head, the output gated."""
    import jax
    import jax.numpy as jnp

    h, g, d = (model["num_attention_heads"], model["num_key_value_heads"],
               model["head_dim"])
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    lanes = (d if "rope_whole_head" in wrong
             else int(d * model["partial_rotary_factor"]))
    b, s, _ = u.shape
    q = (u @ p[name + ".q.w_0"]).reshape(b, s, h, d)
    k = (u @ p[name + ".k.w_0"]).reshape(b, s, g, d)
    v = (u @ p[name + ".v.w_0"]).reshape(b, s, g, d)
    if "norm_after_rope" in wrong:
        q, k = _rope(q, theta, lanes), _rope(k, theta, lanes)
    q = _rms(q, p[name + ".q_norm.w_0"], eps)
    k = _rms(k, p[name + ".k_norm.w_0"], eps)
    if "norm_after_rope" not in wrong:
        q, k = _rope(q, theta, lanes), _rope(k, theta, lanes)
    kv_of = jnp.arange(h) // (h // g)  # query head n reads n // (h / g)
    k, v = k[:, :, kv_of], v[:, :, kv_of]
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi])
        scores = scores / math.sqrt(d)
        visible = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        scores = jnp.where(visible, scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                              v[:, :hi]))
    a = jnp.concatenate(out, 1).reshape(b, s, h * d)
    if "no_attn_gate" not in wrong:
        a = a * jax.nn.sigmoid(u @ p[name + ".gate.w_0"])
    return a @ p[name + ".o.w_0"]


def expert_ffn(p, u, name, model, wrong=()):
    """The experts held, one dense FFN an expert over every token,
    weighted by what the router gave that expert there, and the shared
    expert times the token's gate. `model["shared_expert"]` False leaves
    the shared expert out (a share that is not the one to count it)."""
    import jax
    import jax.numpy as jnp

    k = model["num_experts_per_tok"]
    scored = u @ p[name + ".moe.gate"]
    scores = (jax.nn.sigmoid(scored) if "sigmoid_router" in wrong
              else jax.nn.softmax(scored, -1))
    w, chosen = jax.lax.top_k(scores, k)
    if model["norm_topk_prob"] and "no_renormalize" not in wrong:
        w = w / jnp.sum(w, -1, keepdims=True)
    y = 0.0
    for e in range(model["num_experts"]):
        here = jnp.sum(jnp.where(chosen == model["held_from"] + e, w, 0.0), -1)
        one = (_silu(u @ p[name + ".moe.w_gate"][e])
               * (u @ p[name + ".moe.w_up"][e])) @ p[name + ".moe.w_down"][e]
        y = y + here[..., None] * one
    if not model.get("shared_expert", True):
        return y
    shared = _ffn(p, u, name + ".shared")
    if "no_shared_gate" not in wrong:
        shared = shared * jax.nn.sigmoid(u @ p[name + ".shared_gate.w_0"])
    return y + shared


def reference(p: dict, batch: dict, model: dict, drop_layers: int = 0,
              wrong=()):
    """Forward pass on some rows of a batch. Returns the sum of the
    negative log-likelihoods of the labels, their count, and the logits at
    every `SCORED_EVERY`-th position, `[rows, s / SCORED_EVERY, vocab]`.
    `drop_layers` leaves out that many of the last layers and `wrong`
    names departures of `WRONG` (the key head taken as `n % 16`, one decay
    for all the heads, the rotation over the whole head, the shared
    expert's gate left off, sigmoid scores for the softmax, the
    renormalisation left off, the convolution's SiLU left off, the
    attention's gate left off, QK-norm after the positions): the tests
    and the chip readings use them to show that a wrong model is caught.
    The norms' weights are the program's `1 + w`, seeded 1."""
    import jax
    import jax.numpy as jnp

    eps = model["rms_norm_eps"]
    x = p["qwen3next.embed"][batch["tokens"]]
    layers = held_layers(model)
    for l, kind in layers[:len(layers) - drop_layers]:
        n = f"qwen3next.layer{l}"
        u = _rms(x, p[n + ".input_norm.w_0"], eps)
        if kind == "linear_attention":
            x = x + delta_mixer(p, u, n + ".gdn", model, wrong)
        else:
            x = x + attention_mixer(p, u, n + ".attn", model, wrong)
        u = _rms(x, p[n + ".post_attn_norm.w_0"], eps)
        x = x + expert_ffn(p, u, n, model, wrong)
    logits = (_rms(x, p["qwen3next.final_norm.w_0"], eps)
              @ p["qwen3next.head.w_0"])
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, batch["labels"][..., None], -1)[..., 0]
    return (jnp.sum(nll), jnp.asarray(nll.size, jnp.float32),
            logits[:, ::SCORED_EVERY])
