"""Phi-4-mini-flash-reasoning, the pipeline stage that straddles its two
decoders: the Program through the repo's public builder, seeded documents,
FLOPs per example, and the plain reference.

The reference is written from the equations in
`paddle_tpu/models/phi4_flash.py`'s docstring (the model's public
`config.json`; SambaY, arXiv:2507.06607, Mamba, arXiv:2312.00752, and the
Differential Transformer, arXiv:2410.05258, for the layers its keys name;
what they leave open is listed under `assumed` in the configuration file)
in float32 `jax.numpy`. It shares nothing with `paddle_tpu`'s lowerings
but the parameters' names:

- The selective scan is the recurrence itself, one `lax.scan` step a
  token over a `[d_inner, d_state]` state: no chunks, no closed form of a
  chunk's decays.
- Differential attention is two plain softmaxes over explicit masks, in
  blocks of 512 queries so that the float32 scores of 40 heads x 512 x
  4,096 (0.34 GB) fit beside the state the device holds during the set-up
  check. The heads are taken from the projection's columns by indexing
  (`2n` and `2n + 1`), the key/value pair by `n // 2`; the program splits
  a reshaped array and lets the kernel read the group in place.
- The convolution is four shifted products and its bias.
- The share is the program's: ids, logits and loss are over the slice of
  the vocabulary, and the layers are the published ones from
  `first_layer_held`, their kinds by the published index.
"""

from __future__ import annotations

import math

from benchmark.harness.datagen import zipf_ids

SCORED_SEQUENCES = 1  # the harness compares logits on this many sequences
SCORED_EVERY = 16  # ... at every sixteenth position of each (410 MB otherwise)
QUERY_BLOCK = 512  # the reference's attention, queries a block

# |program - reference| on the scored logits over the reference's own
# root-mean-square, and on the loss; the logits' limit lies between two
# readings on the chip (PERF.md section 6, PR 44, has every one). The
# program computes its matrix products in bf16 with float32 accumulation
# and keeps its activations, the residual stream, the scan's inputs and
# the attention maps among them, in bf16; the scan's state and decays, the
# softmaxes, the difference of the two maps and the norms' statistics are
# float32. Through six layers it read 2.207-2.263% on the logits and at
# most 0.00033 on the loss over ten seeds, every seed within 0.06 points
# of the others. The reference with its matrices rounded to fp8 (e4m3),
# the nearest precision below, reads 26.1-26.2% on the logits, which is
# what refuses it, and 0.0005-0.0025 on the loss. The wrong models of
# `WRONG` read 14.1% (the gated scan output as the memory), 21.4-21.9%
# (every attention layer full), 44% (pairs by halves), 62% (a memory unit
# gating its own projection), 85-86% (`lam0` of layer 0 everywhere) and
# 102-103% (no bias in the convolution). The limit, 5%, leaves 2.2 times
# the program's largest reading of room, since fresh seeds read higher,
# and has the mildest wrong model 2.8 times and the fp8 reference 5.2
# times above it.
# **What the chip's limits cannot tell apart** are the three lowerings to
# bf16 inside a program whose matrices and activations are bf16 already:
# the reference with the scan's state in bf16 reads 2.30-2.36% against
# this program, with the softmaxes in bf16 2.24%, with the sub-norm in
# bf16 2.24%: 0.1 points or less from the right reference's 2.23%, less
# than the seeds differ by. They are caught where the program is float32:
# `tests/test_phi4_flash_reference.py` holds the float32 program to 5e-5
# on the logits, and each of the three reads at least four times that
# (the scan 3.6e-4) at the rehearsal size.
# The loss's limit is the one the harness's other decoder cells have, 9
# times the largest reading: the model hands its loss back in float32 and
# a wrong model moves a mean of 4,096 log-likelihoods by less than the
# logits show; the logits carry the check.
TOLERANCE = {"logits_rel_rms": 0.05, "loss_abs": 0.003}

# what `reference(wrong=...)` can be made to get wrong, for the tests and
# the chip readings that place the limits: the three lowerings to bf16
# first, then wrong models
WRONG = ("scan_bf16", "softmax_bf16", "subln_bf16", "all_full",
         "no_conv_bias", "lam0_const", "memory_after_gate", "pair_halves",
         "own_memory")


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


def config(model: dict):
    from paddle_tpu.models.phi4_flash import Phi4FlashConfig

    return Phi4FlashConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        num_hidden_layers=model["num_hidden_layers_published"],
        first_layer=model["first_layer_held"],
        layers_held=model["num_hidden_layers"],
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        intermediate_size=model["intermediate_size"],
        sliding_window=model["sliding_window"],
        mb_per_layer=model["mb_per_layer"],
        mamba_d_state=model["mamba_d_state"],
        mamba_d_conv=model["mamba_d_conv"],
        mamba_expand=model["mamba_expand"],
        mamba_dt_rank=model["mamba_dt_rank"],
        layer_norm_eps=model["layer_norm_eps"],
        initializer_range=model["initializer_range"])


def build(model: dict, traffic: dict) -> dict:
    """Declare the training program in the current default programs.
    `check` names what the reference check fetches from the `for_test`
    clone: the loss and the logits at every `SCORED_EVERY`-th position."""
    from paddle_tpu import layers
    from paddle_tpu.models.phi4_flash import build_phi4_flash

    b, s = traffic["batch"], traffic["seq_len"]
    handles = build_phi4_flash(config(model), b, s)
    scored = layers.strided_slice(
        handles["logits"], axes=[0, 1], starts=[0, 0],
        ends=[min(b, SCORED_SEQUENCES), s], strides=[1, SCORED_EVERY])
    return {"loss": handles["loss"].name, "feeds": handles["feeds"],
            "check": [handles["loss"].name, scored.name], "loads": []}


def make_batch(rng, model: dict, traffic: dict) -> dict:
    """One document a row, `seq_len` tokens and the token after each as its
    label: no padding, no packing, every position scored. Ids are
    Zipf(1.1) over the rows of the vocabulary held here."""
    b, s = traffic["batch"], traffic["seq_len"]
    doc = zipf_ids(rng, (b, s + 1), model["vocab_size"])
    return {"tokens": doc[:, :-1].copy(), "labels": doc[:, 1:].copy()}


def tokens_per_example(model: dict, traffic: dict) -> int:
    return traffic["seq_len"]


def sizes(model: dict) -> tuple[int, int, int, int]:
    """(d_inner, d_state, dt_rank, head_dim)."""
    return (model["mamba_expand"] * model["hidden_size"],
            model["mamba_d_state"], model["mamba_dt_rank"],
            model["hidden_size"] // model["num_attention_heads"])


def mixer_matrix_params(model: dict, kind: str) -> int:
    """Weights of the matrix products of one mixer."""
    h = model["hidden_size"]
    di, n, r, d = sizes(model)
    kvd = model["num_key_value_heads"] * d
    return {"mamba": h * 2 * di + di * (r + 2 * n) + r * di + di * h,
            "gmu": 2 * h * di, "cross": 2 * h * h,
            "window": h * (h + 2 * kvd) + h * h,
            "full": h * (h + 2 * kvd) + h * h}[kind]


def matrix_params_per_token(model: dict) -> int:
    """Weights of the matrix products one token passes through in the
    layers held here, and the head's slice (tied: the gather of the same
    rows on the way in is no product)."""
    h = model["hidden_size"]
    ffn = 3 * h * model["intermediate_size"]
    return (sum(mixer_matrix_params(model, kind) + ffn
                for _, kind in held_layers(model)) + h * model["vocab_size"])


def admitted_pairs(s: int, window: int) -> int:
    """(query, key) pairs of one map that the masks admit over a row of
    `s` tokens: query i sees min(i + 1, window) keys, all i + 1 of them
    where there is no window (0)."""
    full = min(s, window) if window else s
    return full * (full + 1) // 2 + (s - full) * full


def flops_per_example(model: dict, traffic: dict) -> float:
    """Matrix-product FLOPs forward and backward (3 x forward) for one
    document, from the shapes: two a weight a token
    (`matrix_params_per_token`) and, for each attention layer, its two
    score maps a query head pair over the pairs the masks admit: q.k at
    `head_dim` and p.v at twice that. The scan (no matrix product), the
    convolutions, the embedding gather, the norms and the optimizer do not
    count."""
    s = traffic["seq_len"]
    d = sizes(model)[3]
    maps = 2 * (model["num_attention_heads"] // 2)
    attn = sum(admitted_pairs(s, model["sliding_window"]
                              if kind == "window" else 0)
               for _, kind in held_layers(model)
               if kind in ("window", "full", "cross")) * maps * 2 * 3 * d
    return 3.0 * (2 * s * matrix_params_per_token(model) + attn)


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
