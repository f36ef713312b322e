"""Nemotron-H in plain float32 `jax.numpy`: the reference that
`tests/test_nemotron_h_reference.py` holds the program to, block kind by
block kind, for the whole model, and for one train step's gradients.

From `# --- reference` on this is `benchmark/models/nemotron_h.py`'s
reference word for word (a test holds the two files to that): the
equations of `paddle_tpu/models/nemotron_h.py`'s docstring with Mamba-2's
recurrence as the recurrence itself, a token a step under `lax.scan` (no
chunks: it shares no algebra with the op `ssd_scan`), B and C repeated for
a group's heads by indexing, the convolution as four shifted products and
a bias, the gated norm's statistic over each group's channels, attention
as plain softmax over an explicit mask in blocks of queries with no
positions, and the latent expert layer as a loop over the experts held
with a mask. It shares nothing with `paddle_tpu`'s lowerings but the
parameters' names. `loss` is this file's own: the mean negative
log-likelihood, for `jax.grad`.
"""

from __future__ import annotations

import math

SCORED_EVERY = 16
QUERY_BLOCK = 512

KINDS = {"M": "mamba2", "E": "experts", "*": "attention"}


def held_layers(model: dict) -> list[tuple[int, str]]:
    """(published index, "mamba2", "experts" or "attention") of each block
    held: `hybrid_override_pattern` read from `first_layer_held` on."""
    first = model["first_layer_held"]
    return [(first + i, KINDS[c])
            for i, c in enumerate(model["hybrid_override_pattern"])]


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


def _relu2(x):
    import jax.numpy as jnp

    return jnp.square(jnp.maximum(x, 0.0))


def _rope(x, theta):
    """x: [b, s, heads, d], positions 0..s-1, the rotate-half form over
    the whole head: what the model does NOT do (`wrong` "positions")."""
    import jax.numpy as jnp

    s, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], -1)[None, :, None, :]
    swapped = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(angle) + swapped * jnp.sin(angle)


def _conv(a, f, bias):
    """Causal, per channel, zero state: a [b, s, c], f [c, width],
    bias [c]; before the SiLU."""
    import jax.numpy as jnp

    width, s = f.shape[1], a.shape[1]
    padded = jnp.pad(a, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(padded[:, i:i + s] * f[:, i] for i in range(width)) + bias


def ssm_recurrence(x, delta, a, bm, cm):
    """Mamba-2's recurrence, one `lax.scan` step a token. x: [b, s, H, P];
    delta: [b, s, H]; a: [H]; bm, cm: [b, s, H, N] (already a group a
    head). `h = exp(delta a) h + delta x B^T; y = h C`, from a zero state
    `[b, H, P, N]`; without the skip."""
    import jax
    import jax.numpy as jnp

    b, _, heads, p = x.shape

    def token(state, xs):
        x, delta, bm, cm = xs
        state = (jnp.exp(delta * a)[..., None, None] * state
                 + (delta[..., None] * x)[..., None] * bm[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, cm)

    _, y = jax.lax.scan(
        token, jnp.zeros((b, heads, p, bm.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, delta, bm, cm)))
    return jnp.moveaxis(y, 0, 1)


def mamba_mixer(p, u, name, model, wrong=()):
    """u: [b, s, hidden] -> [b, s, hidden]: Mamba-2 with the heads and
    groups held, token by token."""
    import jax
    import jax.numpy as jnp

    heads, hp, groups, n = (model["mamba_num_heads"], model["mamba_head_dim"],
                            model["n_groups"], model["ssm_state_size"])
    inner = heads * hp
    b, s, _ = u.shape
    zxbcdt = u @ p[name + ".in_proj.w_0"]
    z = zxbcdt[..., :inner]
    xbc = zxbcdt[..., inner:2 * inner + 2 * groups * n]
    dt = zxbcdt[..., 2 * inner + 2 * groups * n:]
    xbc = _silu(_conv(xbc, p[name + ".conv.w_0"], p[name + ".conv.b_0"]))
    x = xbc[..., :inner].reshape(b, s, heads, hp)
    # head h reads group h // (heads / groups)
    group_of = jnp.arange(heads) // (heads // groups)
    bm = xbc[..., inner:inner + groups * n].reshape(b, s, groups, n)
    cm = xbc[..., inner + groups * n:].reshape(b, s, groups, n)
    delta = jax.nn.softplus(dt + p[name + ".dt_bias"])
    y = ssm_recurrence(x, delta, -jnp.exp(p[name + ".A_log"]),
                       bm[:, :, group_of], cm[:, :, group_of])
    if "no_d_skip" not in wrong:
        y = y + p[name + ".D"][:, None] * x
    y = y.reshape(b, s, inner)
    w = jnp.concatenate([p[f"{name}.norm.group{i}.w_0"]
                         for i in range(groups)])
    eps = model["layer_norm_epsilon"]

    def normed(t):  # over each group's channels
        if "norm_whole" in wrong:
            return t / jnp.sqrt(jnp.mean(t * t, -1, keepdims=True) + eps)
        by_group = t.reshape(b, s, groups, inner // groups)
        return (by_group / jnp.sqrt(
            jnp.mean(by_group * by_group, -1, keepdims=True) + eps)
                ).reshape(b, s, inner)

    o = (normed(y) * _silu(z) if "gate_after_norm" in wrong
         else normed(y * _silu(z))) * w
    return o @ p[name + ".out_proj.w_0"]


def attention_mixer(p, u, name, model, wrong=()):
    """u: [b, s, hidden] -> [b, s, hidden]: full causal, grouped heads (as
    held), no positions, no QK-norm, no gate."""
    import jax
    import jax.numpy as jnp

    h, g, d = (model["num_attention_heads"], model["num_key_value_heads"],
               model["head_dim"])
    b, s, _ = u.shape
    q = (u @ p[name + ".q.w_0"]).reshape(b, s, h, d)
    k = (u @ p[name + ".k.w_0"]).reshape(b, s, g, d)
    v = (u @ p[name + ".v.w_0"]).reshape(b, s, g, d)
    if "positions" in wrong:
        q, k = _rope(q, model["rope_theta"]), _rope(k, model["rope_theta"])
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
    return a @ p[name + ".o.w_0"]


def expert_layer(p, u, name, model, wrong=()):
    """The latent expert layer: the router and the shared expert read u,
    the experts held read `W_lat_in u`, one dense ungated FFN an expert
    over every token's latent, weighted by what the router gave that
    expert there; their sum comes back through `W_lat_out`.
    `model["shared_expert"]` False leaves the shared expert out (a share
    that is not the one to count it)."""
    import jax
    import jax.numpy as jnp

    k = model["num_experts_per_tok"]
    latent = u @ p[name + ".latent_in.w_0"]
    read = u
    if "router_reads_latent" in wrong:  # the latent's part of the token
        read = latent @ p[name + ".latent_in.w_0"].T
    scores = jax.nn.sigmoid(read @ p[name + ".moe.gate"])
    _, chosen = jax.lax.top_k(scores + p[name + ".moe.bias"], k)
    w = jnp.take_along_axis(scores, chosen, -1)
    if model["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    if "no_scaling" not in wrong:
        w = w * model["routed_scaling_factor"]
    routed = 0.0
    for e in range(model["n_routed_experts"]):
        here = jnp.sum(jnp.where(chosen == model["held_from"] + e, w, 0.0), -1)
        up = latent @ p[name + ".moe.w_up"][e]
        hidden = _silu(up) * up if "gated_expert" in wrong else _relu2(up)
        routed = routed + here[..., None] * (hidden @ p[name + ".moe.w_down"][e])
    out = routed @ p[name + ".latent_out.w_0"]
    if not model.get("shared_expert", True):
        return out
    return out + (_relu2(u @ p[name + ".shared.up.w_0"])
                  @ p[name + ".shared.down.w_0"])


def reference(p: dict, batch: dict, model: dict, drop_layers: int = 0,
              wrong=()):
    """Forward pass on some rows of a batch. Returns the sum of the
    negative log-likelihoods of the labels, their count, and the logits at
    every `SCORED_EVERY`-th position, `[rows, s / SCORED_EVERY, vocab]`.
    `drop_layers` leaves out that many of the last blocks and `wrong`
    names departures of `WRONG` (the skip `D x` left off, the gated
    norm's statistic over all the channels held and not by group, the
    gate after the norm and not before its statistic, a SiLU-gated expert
    for the squared ReLU, the router reading the latent's part of the
    token, the routed scaling left out, rotary positions on q and k): the
    tests and the chip readings use them to show that a wrong model is
    caught."""
    import jax
    import jax.numpy as jnp

    eps = model["layer_norm_epsilon"]
    x = p["nemotron.embed"][batch["tokens"]]
    layers = held_layers(model)
    mixers = {"mamba2": (mamba_mixer, ".mamba"),
              "attention": (attention_mixer, ".attn"),
              "experts": (expert_layer, "")}
    for l, kind in layers[:len(layers) - drop_layers]:
        n = f"nemotron.layer{l}"
        mixer, suffix = mixers[kind]
        x = x + mixer(p, _rms(x, p[n + ".norm.w_0"], eps), n + suffix, model,
                      wrong)
    logits = (_rms(x, p["nemotron.final_norm.w_0"], eps)
              @ p["nemotron.head.w_0"])
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, batch["labels"][..., None], -1)[..., 0]
    return (jnp.sum(nll), jnp.asarray(nll.size, jnp.float32),
            logits[:, ::SCORED_EVERY])
