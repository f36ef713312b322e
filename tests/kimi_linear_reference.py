"""Kimi Linear in plain float32 `jax.numpy`: the reference that
`tests/test_kimi_linear_reference.py` holds the program to, mixer by mixer,
for the whole model, and for one train step's gradients.

From `# --- reference` on this is `benchmark/models/kimi_linear.py`'s
reference word for word (a test holds the two files to that): the
equations of `paddle_tpu/models/kimi_linear.py`'s docstring with KDA as
the token-by-token recurrence, latent attention as plain softmax in blocks
of queries, and the experts as a loop over the experts held. It shares
nothing with `paddle_tpu`'s lowerings but the parameters' names. `loss` is
this file's own: the mean negative log-likelihood, for `jax.grad`.
"""

from __future__ import annotations

import math

SCORED_EVERY = 8
QUERY_BLOCK = 512


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


def _conv(a, f):
    """Causal, per channel, zero state: a [b, s, c], f [c, width]."""
    import jax.numpy as jnp

    width, s = f.shape[1], a.shape[1]
    padded = jnp.pad(a, ((0, 0), (width - 1, 0), (0, 0)))
    return _silu(sum(padded[:, i:i + s] * f[:, i] for i in range(width)))


def kda_recurrence(q, k, v, g, beta):
    """The gated delta rule, one `lax.scan` step a token. q, k, g:
    [b, s, h, dk]; v: [b, s, h, dv]; beta: [b, s, h]; `g` the log of the
    decay. `S' = Diag(exp(g_t)) S; S = S' + beta_t k_t (v_t - S'^T k_t)^T;
    o_t = dk^-1/2 S^T q_t`, from a zero state."""
    import jax
    import jax.numpy as jnp

    b, _, h, dk = q.shape

    def token(state, x):  # state [b, h, dk, dv]
        q, k, v, g, beta = x
        state = jnp.exp(g)[..., None] * state
        seen = jnp.einsum("bhkv,bhk->bhv", state, k)
        state = state + beta[..., None, None] * (
            k[..., :, None] * (v - seen)[..., None, :])
        return state, jnp.einsum("bhkv,bhk->bhv", state, q) / math.sqrt(dk)

    _, o = jax.lax.scan(
        token, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def kda_mixer(p, u, name, model, no_delta=False):
    """u: [b, s, hidden] -> [b, s, hidden], token by token."""
    import jax
    import jax.numpy as jnp

    lin = model["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    b, s, _ = u.shape

    def heads(t):
        return t.reshape(b, s, h, d)

    def unit(t):
        return t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True)
                            + model["l2norm_epsilon"])

    q, k, v = (heads(_conv(u @ p[f"{name}.{t}.w_0"], p[f"{name}.{t}_conv.w_0"]))
               for t in "qkv")
    g = -jnp.exp(p[name + ".A_log"])[:, None] * heads(jax.nn.softplus(
        u @ p[name + ".f_a.w_0"] @ p[name + ".f_b.w_0"] + p[name + ".dt_bias"]))
    beta = jax.nn.sigmoid(u @ p[name + ".b.w_0"])  # [b, s, h]
    if no_delta:
        beta = jnp.zeros_like(beta)
    o = _rms(kda_recurrence(unit(q), unit(k), v, g, beta),
             p[name + ".o_norm.w_0"], model["rms_norm_eps"])
    gate = jax.nn.sigmoid(u @ p[name + ".g_a.w_0"] @ p[name + ".g_b.w_0"])
    return (o.reshape(b, s, h * d) * gate) @ p[name + ".o.w_0"]


def latent_mixer(p, u, name, model):
    import jax
    import jax.numpy as jnp

    nh = model["num_attention_heads"]
    dn, dr, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    rank = model["kv_lora_rank"]
    b, s, _ = u.shape
    q = (u @ p[name + ".q.w_0"]).reshape(b, s, nh, dn + dr)
    kva = u @ p[name + ".kv_a.w_0"]
    c, k_r = kva[..., :rank], kva[..., rank:]
    kv = (_rms(c, p[name + ".kv_a_norm.w_0"], model["rms_norm_eps"])
          @ p[name + ".kv_b.w_0"]).reshape(b, s, nh, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r[:, :, None, :], (b, s, nh, dr))], -1)
    v = kv[..., dn:]
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi])
        scores = scores / math.sqrt(dn + dr)
        visible = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        scores = jnp.where(visible, scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                              v[:, :hi]))
    return jnp.concatenate(out, 1).reshape(b, s, nh * dv) @ p[name + ".o.w_0"]


def expert_ffn(p, u, name, model):
    """The shared expert and the experts held: one dense FFN an expert over
    every token, weighted by what the router gave that expert there."""
    import jax
    import jax.numpy as jnp

    k = model["num_experts_per_token"]
    scores = jax.nn.sigmoid(u @ p[name + ".moe.gate"])
    _, chosen = jax.lax.top_k(scores + p[name + ".moe.bias"], k)
    w = jnp.take_along_axis(scores, chosen, -1)
    if model["moe_renormalize"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * model["routed_scaling_factor"]
    y = _ffn(p, u, name + ".shared") if model["num_shared_experts"] else 0.0
    for e in range(model["num_experts"]):
        here = jnp.sum(jnp.where(chosen == model["held_from"] + e, w, 0.0), -1)
        one = (_silu(u @ p[name + ".moe.w_gate"][e])
               * (u @ p[name + ".moe.w_up"][e])) @ p[name + ".moe.w_down"][e]
        y = y + here[..., None] * one
    return y


def reference(p: dict, batch: dict, model: dict, drop_layers: int = 0,
              no_delta: bool = False):
    """Forward pass on some rows of a batch. Returns the sum of the
    negative log-likelihoods of the labels, their count, and the logits at
    every `SCORED_EVERY`-th position, `[rows, s / SCORED_EVERY, vocab]`.
    `drop_layers` leaves out that many of the last layers and `no_delta`
    sets every beta to 0 (the state then never takes a value in): the
    tests use them to show that a wrong model is caught."""
    import jax
    import jax.numpy as jnp

    eps = model["rms_norm_eps"]
    lin = model["linear_attn_config"]
    x = p["kimi.embed"][batch["tokens"]]
    for i in range(1, model["num_hidden_layers"] + 1 - drop_layers):
        n = f"kimi.layer{i}"
        u = _rms(x, p[n + ".attn_norm.w_0"], eps)
        x = x + (kda_mixer(p, u, n + ".kda", model, no_delta)
                 if i in lin["kda_layers"]
                 else latent_mixer(p, u, n + ".mla", model))
        u = _rms(x, p[n + ".ffn_norm.w_0"], eps)
        x = x + (_ffn(p, u, n + ".mlp") if i <= model["first_k_dense_replace"]
                 else expert_ffn(p, u, n, model))
    logits = _rms(x, p["kimi.final_norm.w_0"], eps) @ p["kimi.head.w_0"]
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, batch["labels"][..., None], -1)[..., 0]
    return (jnp.sum(nll), jnp.asarray(nll.size, jnp.float32),
            logits[:, ::SCORED_EVERY])
