"""Phi-4-mini-flash in plain float32 `jax.numpy`: the reference that
`tests/test_phi4_flash_reference.py` holds the program to, mixer by mixer,
for the whole model, and for one train step's gradients.

From `# --- reference` on this is `benchmark/models/phi4_flash.py`'s
reference word for word, as are `held_layers` and `sizes` (a test holds
the two files to that): the equations of
`paddle_tpu/models/phi4_flash.py`'s docstring with the selective scan as
the recurrence itself, one `lax.scan` step a token, differential
attention as two plain softmaxes over explicit masks in blocks of
queries, the heads of a pair taken by indexing, and the convolution as
four shifted products. It shares nothing with `paddle_tpu`'s lowerings
but the parameters' names. `loss` is this file's own: the mean negative
log-likelihood, for `jax.grad`.
"""

from __future__ import annotations

import math

SCORED_EVERY = 16
QUERY_BLOCK = 512


def held_layers(model: dict) -> list[tuple[int, str]]:
    """(published index, kind) of each layer held: "mamba", "gmu",
    "window", "full" or "cross", by the published index (`mb_per_layer` 2:
    a scan or a memory unit every other layer, attention between them; the
    second decoder starts after the middle layer's scan and one full
    layer)."""
    half = model["num_hidden_layers_published"] // 2
    first = model["first_layer_held"]

    def kind(l):
        if l % model["mb_per_layer"] == 0:
            return "mamba" if l <= half else "gmu"
        return "window" if l < half else "full" if l == half + 1 else "cross"

    return [(l, kind(l)) for l in range(first,
                                        first + model["num_hidden_layers"])]


def sizes(model: dict) -> tuple[int, int, int, int]:
    """(d_inner, d_state, dt_rank, head_dim)."""
    return (model["mamba_expand"] * model["hidden_size"],
            model["mamba_d_state"], model["mamba_dt_rank"],
            model["hidden_size"] // model["num_attention_heads"])


def loss(p, batch, model, **kw):
    nll, count, _ = reference(p, batch, model, **kw)
    return nll / count


# ------------------------------------------------------------ reference


def _ln(x, p, name, eps):
    import jax.numpy as jnp

    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p[name + ".w_0"] + p[name + ".b_0"]


def _silu(x):
    import jax

    return x * jax.nn.sigmoid(x)


def _ffn(p, u, name):
    both = u @ p[name + ".fc1.w_0"]  # the gate's half first
    width = both.shape[-1] // 2
    return (both[..., width:] * _silu(both[..., :width])) @ p[name + ".fc2.w_0"]


def _conv(a, f, bias):
    """Causal, per channel, zero state: a [b, s, c], f [c, width]."""
    import jax.numpy as jnp

    width, s = f.shape[1], a.shape[1]
    padded = jnp.pad(a, ((0, 0), (width - 1, 0), (0, 0)))
    return _silu(sum(padded[:, i:i + s] * f[:, i] for i in range(width))
                 + bias)


def scan_recurrence(x, delta, a, bm, cm, dskip, dtype=None):
    """Mamba-1's recurrence, one `lax.scan` step a token. x, delta:
    [b, s, d]; a: [d, n]; bm, cm: [b, s, n]; dskip: [d]. `h = exp(delta a)
    h + (delta x) B^T; y = h C + D x`, from a zero state. `dtype` (a
    wrong lowering): the state and every product in it."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or x.dtype

    def token(h, xs):  # h [b, d, n]
        x, delta, bm, cm = (t.astype(dtype) for t in xs)
        h = (jnp.exp(delta[..., None] * a.astype(dtype)) * h
             + (delta * x)[..., None] * bm[:, None, :])
        return h, jnp.sum(h * cm[:, None, :], -1)

    _, y = jax.lax.scan(
        token, jnp.zeros((x.shape[0], *a.shape), dtype),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, delta, bm, cm)))
    return jnp.moveaxis(y, 0, 1).astype(x.dtype) + dskip * x


def mamba_mixer(p, u, name, model, wrong=()):
    """u: [b, s, hidden] -> ([b, s, hidden], the scan's output
    [b, s, d_inner] before its gate)."""
    import jax
    import jax.numpy as jnp

    di, n, r, _ = sizes(model)
    xz = u @ p[name + ".in_proj.w_0"]
    xs, z = xz[..., :di], xz[..., di:]
    xc = _conv(xs, p[name + ".conv.w_0"],
               0.0 if "no_conv_bias" in wrong else p[name + ".conv.b_0"])
    dbc = xc @ p[name + ".x_proj.w_0"]
    dt, bm, cm = dbc[..., :r], dbc[..., r:r + n], dbc[..., r + n:]
    delta = jax.nn.softplus(dt @ p[name + ".dt_proj.w_0"]
                            + p[name + ".dt_proj.b_0"])
    y = scan_recurrence(
        xc, delta, -jnp.exp(p[name + ".A_log"]), bm, cm, p[name + ".D"],
        jnp.bfloat16 if "scan_bf16" in wrong else None)
    gated = y * _silu(z)
    return (gated @ p[name + ".out_proj.w_0"],
            gated if "memory_after_gate" in wrong else y)


def gmu_mixer(p, u, memory, name):
    return (memory * _silu(u @ p[name + ".in_proj.w_0"])
            ) @ p[name + ".out_proj.w_0"]


def differential_mixer(p, u, name, model, window, lam0, kv=None, wrong=()):
    """u: [b, s, hidden] -> ([b, s, hidden], (k, v) [b, s, g, d] each).
    `kv`: another layer's, and then only the query is projected here."""
    import jax
    import jax.numpy as jnp

    h, g = model["num_attention_heads"], model["num_key_value_heads"]
    d, eps = sizes(model)[3], model["layer_norm_eps"]
    b, s, _ = u.shape
    if kv is None:
        qkv = u @ p[name + ".qkv.w_0"] + p[name + ".qkv.b_0"]
        q = qkv[..., :h * d]
        k = qkv[..., h * d:(h + g) * d].reshape(b, s, g, d)
        v = qkv[..., (h + g) * d:].reshape(b, s, g, d)
    else:
        q = u @ p[name + ".q.w_0"] + p[name + ".q.b_0"]
        k, v = kv
    q = q.reshape(b, s, h, d)
    pairs = jnp.arange(h // 2)
    kv_pair = pairs // (h // g)  # query pair n reads key/value pair n // 2
    # the two value heads of a pair side by side: [b, s, pairs, 2 d]
    values = jnp.concatenate([v[:, :, 2 * kv_pair], v[:, :, 2 * kv_pair + 1]],
                             -1)
    low = jnp.bfloat16 if "softmax_bf16" in wrong else None
    maps = []
    for c in (0, 1):  # pair n is heads 2n and 2n + 1
        q_c = q[:, :, pairs + h // 2 * c if "pair_halves" in wrong
                else 2 * pairs + c]
        k_c = k[:, :, 2 * kv_pair + c]
        out = []
        for lo in range(0, s, QUERY_BLOCK):
            hi = min(lo + QUERY_BLOCK, s)
            first = max(0, lo - window + 1) if window else 0
            scores = jnp.einsum("bqhd,bkhd->bhqk", q_c[:, lo:hi],
                                k_c[:, first:hi]) / math.sqrt(d)
            behind = (jnp.arange(lo, hi)[:, None]
                      - jnp.arange(first, hi)[None, :])
            visible = behind >= 0
            if window:
                visible = visible & (behind < window)
            scores = jnp.where(visible, scores, -jnp.inf)
            if low:
                scores = scores.astype(low)
            out.append(jnp.einsum(
                "bhqk,bkhd->bqhd",
                jax.nn.softmax(scores, -1).astype(jnp.float32),
                values[:, first:hi]))
        maps.append(jnp.concatenate(out, 1))
    lam = (jnp.exp(jnp.sum(p[name + ".lambda_q1"] * p[name + ".lambda_k1"]))
           - jnp.exp(jnp.sum(p[name + ".lambda_q2"] * p[name + ".lambda_k2"]))
           + lam0)
    low = jnp.bfloat16 if "subln_bf16" in wrong else jnp.float32
    a = maps[0].astype(low) - lam.astype(low) * maps[1].astype(low)
    a = (a / jnp.sqrt(jnp.mean(a * a, -1, keepdims=True) + eps)
         ).astype(jnp.float32) * p[name + ".subln.w_0"]
    a = ((1.0 - lam0) * a).reshape(b, s, h * d)
    return a @ p[name + ".o.w_0"] + p[name + ".o.b_0"], (k, v)


def reference(p: dict, batch: dict, model: dict, drop_layers: int = 0,
              wrong=()):
    """Forward pass on some rows of a batch. Returns the sum of the
    negative log-likelihoods of the labels, their count, and the logits at
    every `SCORED_EVERY`-th position, `[rows, s / SCORED_EVERY, vocab]`.
    `drop_layers` leaves out that many of the last layers and `wrong`
    names departures of `WRONG` (the scan's state, the softmaxes or the
    sub-norm in bf16; every attention layer full; no bias in the
    convolution; `lam0` of layer 0 in every layer; the gated scan output
    as the memory; pair n as heads n and n + h/2; the memory unit gating
    its own input's projection): the tests and the chip readings use them
    to show that a wrong lowering or model is caught."""
    import jax
    import jax.numpy as jnp

    eps = model["layer_norm_eps"]
    table = p["phi4.embed"]
    x = table[batch["tokens"]]
    memory = shared_kv = None
    layers = held_layers(model)
    for l, kind in layers[:len(layers) - drop_layers]:
        n = f"phi4.layer{l}"
        u = _ln(x, p, n + ".norm1", eps)
        if kind == "mamba":
            m, y = mamba_mixer(p, u, n + ".mamba", model, wrong)
            if l == model["num_hidden_layers_published"] // 2:
                memory = y
        elif kind == "gmu":
            m = gmu_mixer(p, u, u @ p[n + ".gmu.in_proj.w_0"]
                          if "own_memory" in wrong else memory, n + ".gmu")
        else:
            window = (model["sliding_window"]
                      if kind == "window" and "all_full" not in wrong else 0)
            lam0 = 0.8 - 0.6 * math.exp(
                -0.3 * (0 if "lam0_const" in wrong else l))
            m, kv = differential_mixer(
                p, u, n + ".attn", model, window, lam0,
                shared_kv if kind == "cross" else None, wrong)
            if kind == "full":
                shared_kv = kv
        x = x + m
        x = x + _ffn(p, _ln(x, p, n + ".norm2", eps), n + ".mlp")
    logits = _ln(x, p, "phi4.final_norm", eps) @ table.T
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, batch["labels"][..., None], -1)[..., 0]
    return (jnp.sum(nll), jnp.asarray(nll.size, jnp.float32),
            logits[:, ::SCORED_EVERY])
