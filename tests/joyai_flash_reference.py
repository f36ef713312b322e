"""JoyAI-LLM-Flash in plain float32 `jax.numpy`: the reference that
`tests/test_joyai_flash_reference.py` holds the program to, mixer by mixer,
for the whole model with both heads, and for one train step's gradients.

From `# --- reference` on this is `benchmark/models/joyai_flash.py`'s
reference word for word (a test holds the two files to that): the
equations of `paddle_tpu/models/joyai_flash.py`'s docstring with attention
as plain softmax over an explicit causal mask in blocks of queries, the
rotation written out on even and odd lanes, the experts as a loop over the
experts held, and both losses. It shares nothing with `paddle_tpu`'s
lowerings but the parameters' names. `loss` is this file's own: the
two-term loss, for `jax.grad`.
"""

from __future__ import annotations

import math

SCORED_EVERY = 8
QUERY_BLOCK = 512


def loss(p, batch, model, wrong=()):
    nll, count, _ = reference(p, batch, model, wrong=wrong)
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


def _rope(x, theta, half_pairs=False):
    """x: [b, s, heads, d], positions p = 0..s-1: lanes 2i and 2i+1 are a
    plane turned by `p * theta^(-2i/d)`. `half_pairs` (a wrong model):
    lanes i and i + d/2 instead."""
    import jax.numpy as jnp

    s, d = x.shape[1], x.shape[3]
    inv_freq = theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    if half_pairs:
        a, b = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


def latent_mixer(p, u, name, model, wrong=()):
    """u: [b, s, hidden] -> [b, s, hidden]."""
    import jax
    import jax.numpy as jnp

    nh = model["num_attention_heads"]
    dn, dr, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    rank, eps, theta = (model["kv_lora_rank"], model["rms_norm_eps"],
                        model["rope_theta"])
    b, s, _ = u.shape
    c_q = u @ p[name + ".q_a.w_0"]
    if "no_q_norm" not in wrong:
        c_q = _rms(c_q, p[name + ".q_a_norm.w_0"], eps)
    q = (c_q @ p[name + ".q_b.w_0"]).reshape(b, s, nh, dn + dr)
    kva = u @ p[name + ".kv_a.w_0"]
    c, k_r = kva[..., :rank], kva[..., rank:].reshape(b, s, 1, dr)
    kv = (_rms(c, p[name + ".kv_a_norm.w_0"], eps)
          @ p[name + ".kv_b.w_0"]).reshape(b, s, nh, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    q_n, q_r = q[..., :dn], q[..., dn:]
    if "rope_on_nope" in wrong:  # the first dr lanes turned, the last not
        q_n = jnp.concatenate([_rope(q_n[..., :dr], theta), q_n[..., dr:]], -1)
        k_n = jnp.concatenate([_rope(k_n[..., :dr], theta), k_n[..., dr:]], -1)
    elif "no_rope" not in wrong:
        q_r = _rope(q_r, theta, "half_pairs" in wrong)
        k_r = _rope(k_r, theta, "half_pairs" in wrong)
    q = jnp.concatenate([q_n, q_r], -1)
    k = jnp.concatenate([k_n, jnp.broadcast_to(k_r, (b, s, nh, dr))], -1)
    width = dn if "scale_128" in wrong else dn + dr
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi])
        scores = scores / math.sqrt(width)
        visible = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        scores = jnp.where(visible, scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                              v[:, :hi]))
    return jnp.concatenate(out, 1).reshape(b, s, nh * dv) @ p[name + ".o.w_0"]


def expert_ffn(p, u, name, model, wrong=()):
    """The shared expert and the experts held: one dense FFN an expert over
    every token, weighted by what the router gave that expert there."""
    import jax
    import jax.numpy as jnp

    k = model["num_experts_per_tok"]
    scores = jax.nn.sigmoid(u @ p[name + ".moe.gate"])
    _, chosen = jax.lax.top_k(scores + p[name + ".moe.bias"], k)
    w = jnp.take_along_axis(scores, chosen, -1)
    if model["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    if "no_scaling" not in wrong:
        w = w * model["routed_scaling_factor"]
    y = _ffn(p, u, name + ".shared") if model["n_shared_experts"] else 0.0
    for e in range(model["n_routed_experts"]):
        here = jnp.sum(jnp.where(chosen == model["held_from"] + e, w, 0.0), -1)
        one = (_silu(u @ p[name + ".moe.w_gate"][e])
               * (u @ p[name + ".moe.w_up"][e])) @ p[name + ".moe.w_down"][e]
        y = y + here[..., None] * one
    return y


def block(p, x, name, model, dense, wrong=()):
    eps = model["rms_norm_eps"]
    x = x + latent_mixer(p, _rms(x, p[name + ".input_norm.w_0"], eps),
                         name + ".attn", model, wrong)
    u = _rms(x, p[name + ".post_attn_norm.w_0"], eps)
    return x + (_ffn(p, u, name + ".mlp") if dense
                else expert_ffn(p, u, name, model, wrong))


def reference(p: dict, batch: dict, model: dict, drop_layers: int = 0,
              wrong=()):
    """Forward pass on some rows of a batch. Returns the weighted sum of
    the two heads' negative log-likelihoods (`sum CE_main + lambda sum
    CE_mtp`), the count of positions, so that their quotient is the loss,
    and the logits of both heads at every `SCORED_EVERY`-th position, the
    module's after the main head's, `[rows, 2 s / SCORED_EVERY, vocab]`.
    `drop_layers` leaves out that many of the last layers and `wrong`
    names departures of `WRONG`: the tests and the chip readings use them
    to show that a wrong model is caught. `no_mtp` is a model without the
    module: one loss term, and the main head's logits in the module's
    place."""
    import jax
    import jax.numpy as jnp

    eps = model["rms_norm_eps"]
    table, head = p["joyai.embed"], p["joyai.head.w_0"]
    x = table[batch["tokens"]]
    for i in range(model["num_hidden_layers"] - drop_layers):
        x = block(p, x, f"joyai.layer{i}", model,
                  i < model["first_k_dense_replace"], wrong)

    def nll(logits, labels):
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]

    logits = _rms(x, p["joyai.final_norm.w_0"], eps) @ head
    main = nll(logits, batch["labels"])
    count = jnp.asarray(main.size, jnp.float32)
    if "no_mtp" in wrong:
        return (jnp.sum(main), count, jnp.concatenate(
            [logits[:, ::SCORED_EVERY]] * 2, 1))
    if "mtp_own_embedding" in wrong:  # another table of the same law
        table = jnp.roll(table, 1, 0)
    e = table[batch["labels"]]
    if "mtp_no_norms" not in wrong:
        x = _rms(x, p["joyai.mtp.hnorm.w_0"], eps)
        e = _rms(e, p["joyai.mtp.enorm.w_0"], eps)
    h = block(p, jnp.concatenate([x, e], -1) @ p["joyai.mtp.proj.w_0"],
              "joyai.mtp", model, False, wrong)
    mtp_logits = _rms(h, p["joyai.mtp.final_norm.w_0"], eps) @ head
    mtp = nll(mtp_logits, batch["labels_mtp"])
    return (jnp.sum(main) + model["mtp_loss_weight"] * jnp.sum(mtp), count,
            jnp.concatenate([logits[:, ::SCORED_EVERY],
                             mtp_logits[:, ::SCORED_EVERY]], 1))
