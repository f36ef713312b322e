"""Linear-attention op lowerings: the gated delta rule as Kimi Delta
Attention (KDA, a per-channel decay; Kimi Linear, arXiv:2510.26692, and
the public flash-linear-attention `kda` layer) and as Gated DeltaNet (one
decay a head, key heads shared by groups of value heads; arXiv:2412.06464,
Qwen3-Next's linear layers), and the short causal depthwise convolution in
front of it. No reference counterpart: Fluid ~1.5 has no recurrence over
time but its RNN ops.

Per value head n, with state `S` in R^{dk x dv}, zero at the start of a
sequence, and q, k those of key head n // group (group = value heads to a
key head, 1 for KDA):

    S' = Diag(alpha_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = dk^{-1/2} S_t^T q_t

`alpha_t` is a vector of dk decays (KDA: the op's `GRaw` is
`[b, s, h*dk]`) or one number for the head (Gated DeltaNet: `GRaw` is
`[b, s, h]`, and `Diag(alpha_t)` is `alpha_t I`). Everything below is
written for the vector; a head's one decay is the vector with equal
entries, and that is how both lowerings compute it: `kda_chunked` writes
it out in HBM (it is the oracle), the kernels in VMEM, from the
`[b, s, h]` array (a `[64, 1]` column fills as many registers as
`[64, 128]`, so nothing is spent on the copies). With grouped key heads
the kernels' index maps read key head n // group for value head n, so q
and k stay `[b, s, h_k*dk]` in HBM as the convolution wrote them, are
normed in VMEM, and the backward keeps them at that size and dtype
(17 MB each a layer at 4,096 tokens, 16 key heads and bf16, where 32
repeated float32 heads would be 67); the kernels write dq and dk a value
head and XLA adds each group's.

`kda_chunked` computes it chunk by chunk. Inside a chunk of C = 64 tokens, with
`G_t = sum_{i<=t} log alpha_i` (per channel, <= 0, falling) and `S_0` the
state the chunk starts from:

    A[i, j]  = sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])     (j <  i)
    Aq[i, j] = sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c])     (j <= i)
    (I + Diag(beta) A) [Wv, Wk] = Diag(beta) [V, K * exp(G)]
    U   = Wv - Wk S_0                 (the delta rule's corrected values)
    O   = dk^{-1/2} ((Q * exp(G)) S_0 + Aq U)
    S_C = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U

**Two lowerings of one algorithm**, chosen by what `kda_mixer_core` can
observe (`ops/pallas/kda_chunk.py::kda_chunk_viable`: heads of 128 lanes,
or with a decay a head and a key head a value head key heads of up to 128
and value heads of up to 256 lanes, Olmo-Hybrid's 96 and 192,
and a backend that runs Pallas kernels, so a TPU, or the interpreter in
tests): the kernel pair `kda_chunk` of `ops/pallas/kda_chunk.py` (PR 32),
which holds a chunk and the state in VMEM, reads the `[b, s, h*d]`
arrays as the projections wrote them and, since PR 65, makes the L2
norms, beta and a head's log decay of them in VMEM (a channel's log
decay, Kimi's, is XLA's: `kda_gate` in front of the kernels, float32 in
HBM as before); and `_prologue` and `kda_chunked` below, plain XLA, which
run everywhere else (the CPU, the rehearsal's heads of 16) and are the
kernels' test oracle. No switch selects between them.

In `kda_chunked`, `[Wv, Wk]` (the WY representation), `A` and `Aq` depend
on no state, so they are computed for every chunk at once; only the three
products with `S` run under the `lax.scan` that carries the state from
chunk to chunk.

**Keeping the cumulative decay finite.** `exp(G_i - G_j)` is at most 1,
but the factorisation `(k_i exp(G_i)) . (k_j exp(-G_j))` that turns `A`
into a matrix product is not: a decay of 0.01 a token makes `exp(-G_j)`
overflow float32 after twenty tokens. So `kda_chunked` cuts a chunk into
sub-chunks of `SUB` = 16 (its own constant: the kernels split at every
level from the chunk down to blocks of four rows instead, by the same
argument, and are a different evaluation of the same sums). For a pair of sub-chunks
I > J the exponent is split at the last position before I, `Gs_I`:
`exp(G_i - Gs_I)` and `exp(Gs_I - G_j)` are both at most 1, whatever the
decay, and an underflow to 0 is the right answer to float32's precision.
The four diagonal blocks (I = J) have no such point between i and j, and
are computed directly from the [16, 16, dk] tensor of exponents, masked
to j <= i before the `exp`.
Everything that multiplies `S_0` carries an exponent `G_i` or
`G_C - G_i`, at most 0 too. Nothing is clamped.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import profiler
from .pallas import kda_chunk as kda_kernel
from .pallas import short_conv as conv_kernel
from .pallas.kda_chunk import CHUNK
from .registry import register_op


SUB = 16  # `kda_chunked`'s sub-chunk: see the module docstring
ACTIVATIONS = ("silu", "none")  # what follows the short convolution's taps


def _conv_taps(x, w, bias):
    """The convolution before its activation, float32 whatever x arrives in:
    four products, three sums and the SiLU would each round to bf16 under
    AMP, and XLA fuses them all."""
    width = w.shape[1]
    s = x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + s, :] * w[:, i].astype(jnp.float32)
              for i in range(width))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return xp, out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def short_conv(x, w, bias=None, mesh=None, activation="silu"):
    """Causal depthwise convolution over time with zero left state, then
    SiLU. x: [b, s, c]; w: [c, width]; bias: [c] or None;
    `out_t = SiLU(sum_i w[:, i] x_{t-width+1+i} + bias)`, or with
    `activation` "none" the sum itself (a convolution between two gates).
    `mesh` is the Program's, for the backward's kernel.

    The backward keeps x as it arrived and not its float32 copy (67 MB a
    convolution at 4,096 tokens) and makes the taps again. It is written
    out, not left to `jax.vjp`: the transpose of a shifted slice is a pad,
    and XLA kept the four padded float32 products of dx as arrays of their
    own (0.27 GB written and read again a convolution at 4,096 x 4,096).
    Where `ops/pallas/short_conv.py::short_conv_viable` admits the call it
    is that module's one kernel (counter `short_conv_dispatch_pallas`);
    elsewhere the same formulas in XLA, dx from four shifted slices of the
    one float32 `dpre` (counter `short_conv_dispatch_xla`). Without the
    SiLU `dpre` is the cotangent (counter `short_conv_linear_calls`)."""
    if activation not in ACTIVATIONS:
        raise ValueError(f"short_conv: activation {activation!r}: expected "
                         f"one of {ACTIVATIONS}")
    _, out = _conv_taps(x, w, bias)
    if activation == "silu":
        out = out * jax.nn.sigmoid(out)
    return out.astype(x.dtype)


def _short_conv_fwd(x, w, bias, mesh, activation):
    return short_conv(x, w, bias, mesh, activation), (x, w, bias)


def _short_conv_bwd(mesh, activation, res, dout):
    x, w, bias = res
    width = w.shape[1]
    s = x.shape[1]
    # tied to the cotangent, as `jax.checkpoint` ties a replay: XLA would
    # else take the forward's float32 taps for these and hold them from
    # forward to backward
    x, dout = jax.lax.optimization_barrier((x, dout))
    silu = activation == "silu"
    if not silu:
        profiler.bump_counter("short_conv_linear_calls")
    if conv_kernel.short_conv_viable(x.shape[0], s, x.shape[2], width, mesh):
        profiler.bump_counter("short_conv_dispatch_pallas")
        dx, dw, dbias = conv_kernel.short_conv_bwd(x, w, bias, dout, mesh,
                                                   activation)
    else:
        profiler.bump_counter("short_conv_dispatch_xla")
        xp, out = _conv_taps(x, w, bias)
        if silu:
            sig = jax.nn.sigmoid(out)
            dpre = dout.astype(jnp.float32) * (sig * (1 + out * (1 - sig)))
        else:
            dpre = dout.astype(jnp.float32)
        dw = jnp.stack([jnp.sum(dpre * xp[:, i:i + s, :], axis=(0, 1))
                        for i in range(width)], axis=1)
        # x_t is tap i of the outputs t + width-1-i that exist
        later = jnp.pad(dpre, ((0, 0), (0, width - 1), (0, 0)))
        dx = sum(later[:, width - 1 - i:width - 1 - i + s, :]
                 * w[:, i].astype(jnp.float32)
                 for i in range(width)).astype(x.dtype)
        dbias = None if bias is None else jnp.sum(dpre, axis=(0, 1))
    return (dx, dw.astype(w.dtype),
            None if bias is None else dbias.astype(bias.dtype))


short_conv.defvjp(_short_conv_fwd, _short_conv_bwd)


@register_op("short_conv1d")
def _short_conv1d(ctx, op):
    # without a `Bias` input the traced jaxpr is what it was before the op
    # took one (tests/test_kimi_linear_reference.py compares the two),
    # and without an `activation` attribute what it was before that
    ctx.out(op, "Out", short_conv(ctx.in_(op, "X"), ctx.in_(op, "Filter"),
                                  ctx.in_(op, "Bias"), ctx.mesh,
                                  op.attr("activation", "silu")))


def kda_gate(g_raw, a_log, dt_bias, num_heads):
    """`g_t = -exp(A_log^h) * softplus(g_raw + dt_bias)`: the log of the
    decay, float32 whatever `g_raw` arrives in. g_raw: [b, s, h*dk], a
    decay a channel, -> [b, s, h, dk]; or [b, s, h], a decay a head (Gated
    DeltaNet), -> [b, s, h]."""
    b, s, hd = g_raw.shape
    x = g_raw.astype(jnp.float32) + dt_bias.astype(jnp.float32)
    if hd == num_heads:
        return -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(x)
    g = jax.nn.softplus(x).reshape(b, s, num_heads, hd // num_heads)
    return -jnp.exp(a_log.astype(jnp.float32))[None, None, :, None] * g


def l2norm(x, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def kda_chunked(q, k, v, g, beta):
    """The chunked form of the module docstring. q, k: [b, s, h_k, dk];
    v: [b, s, h, dv]; g, the log decay: [b, s, h, dk], or [b, s, h] where
    a head has one; beta: [b, s, h]; all float32. Returns o:
    [b, s, h, dv] float32. This path, the oracle, writes a head's decay
    out over its channels and a key head out for each of its value heads
    (n reads n // group) and goes on as it did; the kernels do neither."""
    h, dv = v.shape[2:]
    if g.ndim == 3:
        g = jnp.broadcast_to(g[..., None], g.shape + q.shape[3:])
    if q.shape[2] != h:
        q, k = (jnp.repeat(t, h // t.shape[2], axis=2) for t in (q, k))
    b, s, _, dk = q.shape
    c = CHUNK
    n = -(-s // c)
    pad = n * c - s
    if pad:  # k = v = beta = 0 and no decay: padded tokens change nothing
        q, k, v, g = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for t in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    ns = c // SUB

    def chunks(t):  # [b, n*c, h, d] -> [b, h, n, c, d]
        return t.reshape(b, n, c, h, -1).transpose(0, 3, 1, 2, 4)

    q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g)
    beta = beta.reshape(b, n, c, h).transpose(0, 3, 1, 2)  # [b, h, n, c]
    G = jnp.cumsum(g, axis=3)  # inclusive, within the chunk
    g_end = G[:, :, :, -1:, :]  # [b, h, n, 1, dk]

    def sub(t):  # [..., c, d] -> [..., ns, SUB, d]
        return t.reshape(t.shape[:-2] + (ns, SUB, t.shape[-1]))

    Gs, ks, qs = sub(G), sub(k), sub(q)
    # Gs_I: G at the last position before sub-chunk I (0 for the first)
    g_ref = jnp.concatenate(
        [jnp.zeros_like(Gs[..., :1, -1, :]), Gs[..., :-1, -1, :]], axis=-2)
    inner = jnp.exp(Gs - g_ref[..., :, None, :])  # exp(G_i - Gs_I) <= 1
    k_in, q_in = ks * inner, qs * inner
    # exp(Gs_I - G_j) for j in sub-chunk J < I, 0 elsewhere: [.., I, J, SUB, dk]
    earlier = (jnp.arange(ns)[:, None] > jnp.arange(ns)[None, :])
    expo = g_ref[..., :, None, None, :] - Gs[..., None, :, :, :]
    k_out = ks[..., None, :, :, :] * jnp.exp(
        jnp.where(earlier[:, :, None, None], expo, -jnp.inf))
    # the diagonal blocks, directly: exp(G_i - G_j) for j <= i of one block,
    # as one fused reduction over dk ([.., I, i, j, dk] is never stored)
    lower = jnp.tril(jnp.ones((SUB, SUB), bool))[:, :, None]

    def scores(x_in, xs):
        off = jnp.einsum("...Iid,...IJjd->...IiJj", x_in, k_out)
        decay = jnp.exp(jnp.where(
            lower, Gs[..., :, None, :] - Gs[..., None, :, :], -jnp.inf))
        diag = jnp.sum(
            xs[..., :, None, :] * ks[..., None, :, :] * decay, axis=-1)
        eye = jnp.eye(ns, dtype=diag.dtype)
        full = off + diag[..., :, :, None, :] * eye[:, None, :, None]
        return full.reshape(full.shape[:-4] + (c, c))

    a_qk = scores(q_in, qs)  # j <= i
    a_kk = jnp.tril(scores(k_in, ks), -1)  # j < i
    # (I + Diag(beta) A) [Wv, Wk] = Diag(beta) [V, K exp(G)]
    system = jnp.eye(c, dtype=a_kk.dtype) + beta[..., None] * a_kk
    rhs = beta[..., None] * jnp.concatenate([v, k * jnp.exp(G)], axis=-1)
    w = jax.lax.linalg.triangular_solve(
        system, rhs, left_side=True, lower=True, unit_diagonal=True)
    w_v, w_k = w[..., :dv], w[..., dv:]
    q_dec = q * jnp.exp(G)
    k_end = k * jnp.exp(g_end - G)

    def step(state, xs):  # state: [b, h, dk, dv]
        w_v, w_k, q_dec, a_qk, k_end, g_end = xs
        u = w_v - jnp.einsum("bhck,bhkv->bhcv", w_k, state)
        o = (jnp.einsum("bhck,bhkv->bhcv", q_dec, state)
             + jnp.einsum("bhij,bhjv->bhiv", a_qk, u))
        state = (jnp.exp(g_end)[..., 0, :, None] * state
                 + jnp.einsum("bhck,bhcv->bhkv", k_end, u))
        return state, o

    def time_major(t):  # [b, h, n, ...] -> [n, b, h, ...]
        return jnp.moveaxis(t, 2, 0)

    _, o = jax.lax.scan(
        step, jnp.zeros((b, h, dk, dv), jnp.float32),
        tuple(time_major(t) for t in (w_v, w_k, q_dec, a_qk, k_end, g_end)))
    # [n, b, h, c, dv] -> [b, n*c, h, dv]
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, n * c, h, dv)
    return dk ** -0.5 * o[:, :s]


def _prologue(q, k, v, g_raw, beta_raw, a_log, dt_bias, num_heads, eps,
              key_heads=None, beta_scale=1.0):
    """The float32 part in front of the chunks: q and k L2-normalised per
    key head, [b, s, h_k, dk], the log decay, beta = `beta_scale` x
    sigmoid; v as it arrived, [b, s, h, dv]."""
    b, s, _ = q.shape

    def heads(t, n=num_heads):
        return t.reshape(b, s, n, -1)

    key_heads = key_heads or num_heads
    q, k, v, g, beta = (
        l2norm(heads(q, key_heads), eps), l2norm(heads(k, key_heads), eps),
        heads(v), kda_gate(g_raw, a_log, dt_bias, num_heads),
        jax.nn.sigmoid(beta_raw.astype(jnp.float32)))
    if beta_scale != 1.0:  # without it the equations are what they were
        beta = beta_scale * beta
    return q, k, v, g, beta


@functools.partial(jax.checkpoint, static_argnums=(7, 8, 9, 10))
def _mixer_plain(*args):
    q, k, v, g, beta = _prologue(*args)
    return kda_chunked(q, k, v.astype(jnp.float32), g, beta)


def kda_mixer_core(q, k, v, g_raw, beta_raw, a_log, dt_bias, num_heads, eps,
                   key_heads=None, beta_scale=1.0):
    """From the convolved projections to the heads' outputs, float32
    inside: the L2 norms, the decay, beta, then the chunked delta rule,
    in the Pallas kernels where `kda_chunk_viable` admits the shape and
    the backend (they take q, k, beta's logits and a head's decay logits
    as the projections wrote them and make the norms, beta and that decay
    in VMEM; a decay a channel alone is gated here, by `kda_gate` in XLA,
    and handed over as the float32 log decay, which measured faster:
    `kda_chunk.py`'s docstring), and in `_prologue` and `kda_chunked`
    otherwise; a counter says which, once a lowering
    (`kda_dispatch_pallas`, `kda_dispatch_chunked`). `g_raw`
    [b, s, h] is a decay a head (counter `kda_decay_per_head`, once a
    lowering); `key_heads` fewer than `num_heads`: q and k arrive
    [b, s, key_heads*dk] and value head n reads key head n // group
    (gauge `kda_key_group`). With the kernels, gauge `kda_lockstep_chunks`:
    the chunks a grid step holds, whose solves run in lockstep.
    `beta_scale` 2: beta = 2 sigmoid(b) in (0, 2), so that `I - beta k k^T`
    has an eigenvalue in (-1, 1) along k (arXiv:2411.12537; the public
    `allow_neg_eigval`). The chunk's system `I + Diag(beta) A` stays unit
    lower triangular, so the solve is as exact as before; its entries are
    up to twice as large.

    What the backward keeps. With the kernels: the op's own inputs as
    the projections wrote them (q, k, v: 34 MB each a layer at 4,096
    tokens, 32 heads and bf16; before PR 65 float32 copies of q and k
    beside them, 67 MB each), of the decay a head's `[b, s, h]` logits
    or, with a decay a channel, those logits and the float32 log decay
    `kda_gate` made of them (67 MB, as before PR 65), and the state each
    chunk starts from, which `kda_fwd` writes (134 MB a layer); `kda_bwd`
    makes the norms, beta and a head's decay again and rebuilds the
    chunk's `G`, `A`, `Aq`, `T` and WY factors in VMEM, and takes its
    gradients back through the norms, the sigmoid and a head's gate
    before they leave (a channel's gate's gradient is XLA's). No
    `jax.checkpoint` there: it cost 14.5 ms of a
    263 ms step to save 1.4 GB that the cell has (PERF.md, PR 32). With
    `kda_chunked`, under `jax.checkpoint`, the arguments alone: the chunk
    states, the WY factors and the decays it would keep are several
    times their size.

    The products inside run at the backend's default precision (on a TPU
    a float32 product reads bf16, in the kernels too): at `float32` the
    XLA step measured 18 ms longer (378 -> 396 ms) and the logits 0.15
    points nearer the reference (2.23 -> 2.09%), PERF.md PR 31."""
    key_heads = key_heads or num_heads
    if num_heads % key_heads:
        raise ValueError(f"kda_attention: {key_heads} key heads do not "
                         f"divide {num_heads} value heads")
    args = (q, k, v, g_raw, beta_raw, a_log, dt_bias, num_heads, eps,
            key_heads, beta_scale)
    per_head = g_raw.shape[2] == num_heads
    if per_head:
        profiler.bump_counter("kda_decay_per_head")
    profiler.set_counter("kda_key_group", num_heads // key_heads)
    b, s, _ = q.shape
    dk, dv = q.shape[2] // key_heads, v.shape[2] // num_heads
    if kda_kernel.kda_chunk_viable(s, dk, dv, num_heads, key_heads, per_head):
        profiler.bump_counter("kda_dispatch_pallas")
        held = kda_kernel.layout(num_heads, dk, dv)[0]
        profiler.set_counter("kda_lockstep_chunks", max(held, 1)
                             * kda_kernel.lockstep_chunks(s, held))
        if per_head:
            g, gate = g_raw, (a_log, dt_bias)
        else:  # a channel's gate stays XLA's: PERF.md, PR 65
            g, gate = kda_gate(g_raw, a_log, dt_bias, num_heads), ()
        o = kda_kernel.kda_chunk(
            q.reshape(b, s, key_heads, dk), k.reshape(b, s, key_heads, dk),
            v.reshape(b, s, num_heads, dv), g, beta_raw, gate, eps,
            beta_scale)
    else:
        profiler.bump_counter("kda_dispatch_chunked")
        o = _mixer_plain(*args)
    return o.reshape(b, s, -1)


def delta_rule_lanes(s, num_heads, key_heads, d_k, d_v, per_head):
    """(published, computed): heads x key lanes x value lanes of the
    states of one `kda_attention` over rows of `s` tokens, as the shapes
    give them, and as the lowering `kda_mixer_core` will take multiplies
    them: the kernels' whole tiles a head and whole grid steps of heads
    (`kda_chunk.layout`), the shapes' own on the plain path. It asks the
    backend, as the lowering will."""
    published = num_heads * d_k * d_v
    if kda_kernel.kda_chunk_viable(s, d_k, d_v, num_heads, key_heads,
                                   per_head):
        return published, kda_kernel.layout(num_heads, d_k, d_v)[1]
    return published, published


@register_op("kda_attention")
def _kda_attention(ctx, op):
    """Q, K: [b, s, h_k*dk] and V: [b, s, h*dv], after the short
    convolution; GRaw, the decay projection's output, and DtBias:
    [b, s, h*dk] and [h*dk], a decay a channel, or [b, s, h] and [h], a
    decay a head; BetaRaw: [b, s, h] logits; ALog: [h]. Attr `num_heads`
    is h, the value heads; `num_key_heads` (absent: h) is h_k, a divisor
    of h, and value head n reads key head n // (h / h_k); `beta_scale`
    (absent: 1) multiplies the sigmoid of BetaRaw. Out: [b, s, h*dv] in
    V's dtype. The L2 norm of q and k, the decay and beta
    are computed here in float32, whatever the AMP dtype of the inputs."""
    q, k, v = ctx.in_(op, "Q"), ctx.in_(op, "K"), ctx.in_(op, "V")
    out = kda_mixer_core(
        q, k, v, ctx.in_(op, "GRaw"), ctx.in_(op, "BetaRaw"),
        ctx.in_(op, "ALog"), ctx.in_(op, "DtBias"), op.attr("num_heads"),
        op.attr("l2norm_epsilon", 1e-6), op.attr("num_key_heads", None),
        op.attr("beta_scale", 1.0))
    ctx.out(op, "Out", out.astype(v.dtype))
