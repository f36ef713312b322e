"""State-space op lowerings, two recurrences and an op each:
`selective_scan`, the selective scan of Mamba-1 (Gu and Dao,
arXiv:2312.00752, section 3 and algorithm 2), whose decay is a number a
channel and a lane of the state, and `ssd_scan`, Mamba-2's (Dao and Gu,
arXiv:2405.21060, state-space duality), whose decay is one number a head
and a token, so that a chunk is four matrix products. What picks one is
the op the model declares (`layers.selective_scan`, `layers.ssd_scan`):
the two share equations' form and no code, and nothing else chooses
between them. `ssd_scan` is the second half of this file. No reference
counterpart: Fluid ~1.5 has no recurrence over time but its RNN ops.

**`selective_scan`.** Per channel `d` of `d_inner` and state lane `n` of `d_state`, with the
state zero at the start of a row and everything float32:

    h_t[n, d] = exp(Delta_t[d] A[d, n]) h_{t-1}[n, d] + Delta_t[d] x_t[d] B_t[n]
    y_t[d]    = sum_n C_t[n] h_t[n, d] + D[d] x_t[d]

The decay depends on the input (through `Delta`), on the channel and on
the lane, so the recurrence is no matrix product: the work is
`s x d_inner x d_state` multiply-adds and as many exponentials, on the
vector units.

**Two lowerings, and what picks one is in the call.** Where
`ops/pallas/selective_scan.py::selective_scan_viable` admits the shapes,
the mesh and the backend (`d_inner` in whole groups of 128 lanes, a state
of whole eights, one device or a mesh that shards `batch` alone, a TPU or
the interpreter), both ops run that module's kernel pair, `ssm_scan_fwd`
and `ssm_scan_bwd`: the recurrence one token at a time with the state in
VMEM, one exponential a state element a pass. Everywhere else (another
mesh, a CPU without the interpreter, `d_state` 4) they run the chunked
form below, which shares the equations with the kernels and no code. No
variable, attribute or table chooses; a counter says which ran, once a
lowering of the forward op (`ssm_dispatch_pallas`,
`ssm_dispatch_chunked`), and the gauge `ssm_chunk_len` the tokens between
two states kept.

**`Starts`**, the forward op's second output, is what ties the two ops to
one path: the state each block of `kept_len` tokens starts from, and
`kept_len` is the path's (`CHUNK` here, the kernels' `BLOCK` there). Its
declared shape (`n_chunks`, which `layers/nn.py` and `shape_fns.py` call)
is a function of the operands' shapes and the backend alone: `n_chunks`
asks the same `selective_scan_viable`, with no mesh, because a Program
is declared before it meets one. On a mesh that refuses the kernels at
shapes that admit them, the forward op thins its chunked states to the
declared blocks and the gradient op rebuilds the chunks' own by running
the chunked forward again: the declaration holds on every path, and the
cost falls on that mesh alone.

**The chunked form, in chunks of `CHUNK` tokens.** `A` does not depend on the
time, so inside a chunk the decay from token j to token t is
`exp(A (S_t - S_j))` with `S` the running sum of `Delta` from the chunk's
start: an exponent that is at most 0 whatever the step sizes, because `A`
is negative and `Delta` is not. With `u_j = Delta_j x_j` and `h_0` the
state the chunk starts from:

    E[t, j] = exp(A (S_t - S_j))                       (j <= t, else 0)
    h_t     = exp(A S_t) h_0 + sum_j E[t, j] B_j u_j   (a chunk's trajectory)

The sum over j is one fused reduction (`[c, c, n, d]` is its operand and
is not stored); what a chunk writes is its trajectory `[c, n, d]`, read
once for `y`, and the state it ends in. A `lax.scan` carries the state
from chunk to chunk. The trajectory of the whole row, `[s, n, d]`
(1.34 GB in float32 at 4,096 tokens and 5,120 x 16), is never in memory,
forward or backward.

**The gradient** is an op of its own, `selective_scan_grad`: it reads the
state each chunk starts from (`[s / c, n, d]`, the forward op's second
output) and runs no forward again. It walks the chunks backwards with the
adjoint of the state, `lam`. A
chunk's trajectory is rebuilt from its start, and the adjoint's
trajectory is the same reduction read along its other axis:

    lam_j = sum_{t >= j} E[t, j] C_t dy_t + exp(A (S_c - S_j)) lam_next

From the two, with `w_t = lam_t * (h_t - B_t u_t)` (the adjoint times
the decayed last state, which is what the decay's own gradient needs):

    dC_t = sum_d dy_t h_t        dB_t = sum_d lam_t u_t
    du_t = sum_n lam_t B_t       dDelta_t = sum_n A w_t + du_t x_t
    dA   = sum_t Delta_t w_t     dx_t = du_t Delta_t + D dy_t

Inside, the state's lanes are the second-minor axis and the channels the
minor one (`[n, d]`, 16 x 5,120: whole vector registers), which is why
`A` arrives `[d, n]` and is turned once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import profiler
from .pallas import selective_scan as scan_kernel
from .registry import register_op

# Tokens a chunk. A token costs `CHUNK` exponentials a lane of the state in
# each of the three reductions and the loop one step a chunk: forward and
# backward of one layer at [1, 4096, 5120] x 16 took 44.6, 25.4, 20.4 and
# 23.0 ms at 16, 8, 4 and 2 on a v5e (PERF.md, PR 44). 8 and not 4: the
# backward keeps a state a chunk, 0.17 GB a layer at 8.
CHUNK = 8


def _chunked(t, n_chunks):
    """[b, n_chunks * c, w] -> [n_chunks, b, c, w] float32."""
    b, _, w = t.shape
    return jnp.moveaxis(
        t.astype(jnp.float32).reshape(b, n_chunks, -1, w), 1, 0)


def _unchunked(t):
    """[n_chunks, b, c, w] -> [b, n_chunks * c, w]."""
    t = jnp.moveaxis(t, 0, 1)
    return t.reshape(t.shape[0], -1, t.shape[3])


def _decays(a, s, towards="t"):
    """a: [n, d]; s: [b, c, d], `Delta`'s running sum. `E` with 0 where
    j > t, as [b, t, j, n, d] (`towards` "t": summed over axis 2 it
    gathers what earlier tokens left at t) or as [b, j, t, n, d] ("j":
    what later tokens ask of j)."""
    c = s.shape[1]
    later, earlier = ((s[:, :, None, :], s[:, None, :, :]) if towards == "t"
                      else (s[:, None, :, :], s[:, :, None, :]))
    admitted = np.tril(np.ones((c, c), bool))
    if towards == "j":
        admitted = admitted.T
    return jnp.exp(jnp.where(admitted[None, :, :, None, None],
                             a * (later - earlier)[:, :, :, None, :],
                             -jnp.inf))


def _trajectory(a, s, bu, h0):
    """The states of one chunk, [b, c, n, d], from the state it starts
    from and `bu[b, j, n, d] = B_j u_j`."""
    return (jnp.exp(a * s[:, :, None, :]) * h0[:, None]
            + jnp.sum(_decays(a, s) * bu[:, None], axis=2))


def _bu(bm, delta, x):
    return bm[:, :, :, None] * (delta * x)[:, :, None, :]


def _scan_fwd(x, delta, a, bm, cm, chunk):
    """x, delta: [b, s, d]; bm, cm: [b, s, n]; a: [n, d] float32, `s` a
    multiple of `chunk`. Returns `sum_n C h` [b, s, d] float32 and the
    state each chunk starts from, [s / chunk, b, n, d]."""
    b, s, d = x.shape
    n_chunks = s // chunk

    def step(h0, xs):
        x, delta, bm, cm = xs
        h = _trajectory(a, jnp.cumsum(delta, axis=1), _bu(bm, delta, x), h0)
        return h[:, -1], (jnp.sum(cm[:, :, :, None] * h, axis=2), h0)

    _, (y, starts) = jax.lax.scan(
        step, jnp.zeros((b, a.shape[0], d), jnp.float32),
        tuple(_chunked(t, n_chunks) for t in (x, delta, bm, cm)))
    return _unchunked(y), starts


def _scan_bwd(x, delta, a, bm, cm, starts, dy, chunk):
    """The gradients of `_scan_fwd`'s first output with respect to x,
    delta, a, bm and cm (the module docstring has the equations)."""
    b, s, d = x.shape
    n_chunks = s // chunk

    def step(carry, xs):
        lam_next, da = carry
        x, delta, bm, cm, h0, dy = xs
        bu = _bu(bm, delta, x)
        s = jnp.cumsum(delta, axis=1)
        h = _trajectory(a, s, bu, h0)
        # the same decays read along the other axis, built apart from the
        # trajectory's so that neither reduction stores its operand
        lam = (jnp.sum(_decays(a, s, "j")
                       * (cm[:, :, :, None] * dy[:, :, None, :])[:, None],
                       axis=2)
               + jnp.exp(a * (s[:, -1:] - s)[:, :, None, :]) * lam_next[:, None])
        w = lam * (h - bu)
        du = jnp.sum(lam * bm[:, :, :, None], axis=2)
        grads = (du * delta,  # dx, without the skip's part
                 jnp.sum(a * w, axis=2) + du * x,  # ddelta
                 jnp.sum(lam * (delta * x)[:, :, None, :], axis=3),  # dB
                 jnp.sum(dy[:, :, None, :] * h, axis=3))  # dC
        da = da + jnp.sum(delta[:, :, None, :] * w, axis=(0, 1))
        # what the chunk before sees of its last state: S_1 = Delta_1
        return (jnp.exp(a * delta[:, 0, None, :]) * lam[:, 0], da), grads

    (_, da), grads = jax.lax.scan(
        step, (jnp.zeros((b, a.shape[0], d), jnp.float32), jnp.zeros_like(a)),
        (*(_chunked(t, n_chunks) for t in (x, delta, bm, cm)), starts,
         _chunked(dy, n_chunks)), reverse=True)
    dx, ddelta, dbm, dcm = (_unchunked(t) for t in grads)
    return dx, ddelta, da, dbm, dcm


def _primal(x, delta, a, bm, cm, dskip, chunk):
    """(y in x's dtype, the states the chunks start from). The second
    output is what the gradient keeps; it carries no gradient itself."""
    y, starts = _scan_fwd(x, delta, a, bm, cm, chunk)
    return (y + dskip * x.astype(jnp.float32)).astype(x.dtype), starts


_selective_scan = jax.custom_vjp(_primal, nondiff_argnums=(6,))


def _selective_scan_fwd(x, delta, a, bm, cm, dskip, chunk):
    y, starts = _primal(x, delta, a, bm, cm, dskip, chunk)
    return (y, starts), (x, delta, a, bm, cm, dskip, starts)


def _selective_scan_bwd(chunk, res, cts):
    x, delta, a, bm, cm, dskip, starts = res
    dy = cts[0].astype(jnp.float32)
    dx, ddelta, da, dbm, dcm = _scan_bwd(x, delta, a, bm, cm, starts, dy,
                                         chunk)
    xf = x.astype(jnp.float32)
    return ((dx + dskip * dy).astype(x.dtype), ddelta.astype(delta.dtype), da,
            dbm.astype(bm.dtype), dcm.astype(cm.dtype),
            jnp.sum(dy * xf, axis=(0, 1)))


_selective_scan.defvjp(_selective_scan_fwd, _selective_scan_bwd)


def kept_len(s, d_inner, d_state):
    """Tokens between two states of `Starts` as the op declares it: the
    kernels' block where the shapes and the backend admit them (the mesh
    is not known where a Program is declared), else a chunk; a row
    shorter than either is one block of its own length."""
    if scan_kernel.selective_scan_viable(s, d_inner, d_state, None):
        return scan_kernel.block_len(s)
    return min(CHUNK, s)


def n_chunks(s, d_inner, d_state):
    """Blocks a row of `s` tokens keeps a state of: `Starts`' leading
    dimension."""
    return -(-s // kept_len(s, d_inner, d_state))


def _whole_chunks(s, chunk, *rows):
    """`rows` ([b, s, .]) padded to whole chunks with steps of size 0,
    which change no state, and the chunk's length."""
    chunk = min(chunk, s)
    pad = -s % chunk
    if pad:
        rows = tuple(jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in rows)
    return chunk, rows


def selective_scan_with_starts(x, delta, a, bm, cm, dskip, chunk=CHUNK):
    """x, delta: [b, s, d]; a: [d, n], negative; bm, cm: [b, s, n];
    dskip: [d]. Returns y [b, s, d] in x's dtype and the state each chunk
    starts from, [n_chunks, b, n, d] float32, which `selective_scan_grads`
    takes; float32 inside, whatever the operands arrive in."""
    s = x.shape[1]
    chunk, (x, delta, bm, cm) = _whole_chunks(s, chunk, x, delta, bm, cm)
    y, starts = _selective_scan(x, delta, a.astype(jnp.float32).T, bm, cm,
                                dskip.astype(jnp.float32), chunk)
    return y[:, :s], starts


def selective_scan(x, delta, a, bm, cm, dskip, chunk=CHUNK):
    return selective_scan_with_starts(x, delta, a, bm, cm, dskip, chunk)[0]


def selective_scan_grads(x, delta, a, bm, cm, dskip, starts, dy, chunk=CHUNK):
    """The gradients of `selective_scan`'s output with respect to its six
    operands, from the states `selective_scan_with_starts` kept: the
    forward does not run again."""
    s = x.shape[1]
    chunk, (x_p, delta_p, bm_p, cm_p, dy_p) = _whole_chunks(
        s, chunk, x, delta, bm, cm, dy)
    dx, ddelta, da, dbm, dcm, dd = _selective_scan_bwd(
        chunk, (x_p, delta_p, a.astype(jnp.float32).T, bm_p, cm_p,
                dskip.astype(jnp.float32), starts), (dy_p, None))
    return (dx[:, :s], ddelta[:, :s], da.T.astype(a.dtype), dbm[:, :s],
            dcm[:, :s], dd.astype(dskip.dtype))


_SLOTS = ("X", "Delta", "A", "B", "C", "D")


def _grad_maker(op, grad_out_names, block, helpers):
    """`selective_scan_grad` reads the forward op's `Starts` and so runs
    no forward of its own. A gradient into `Starts` itself, which nothing
    in the zoo asks for, goes through `jax.vjp` of the lowering."""
    if (grad_out_names.get("Y", [None])[0] is None
            or grad_out_names.get("Starts", [None])[0] is not None):
        return None
    return [{
        "type": "selective_scan_grad",
        "inputs": {**{slot: op.input(slot) for slot in _SLOTS},
                   "Starts": op.output("Starts"),
                   "GRAD_Y": [grad_out_names["Y"][0]]},
        "outputs": {f"IGRAD_{slot}": [helpers.grad_name(op.input(slot)[0])]
                    for slot in _SLOTS},
        "attrs": {},
    }]


def _in_kernels(ctx, x, a):
    b, s, d = x.shape
    return scan_kernel.selective_scan_viable(s, d, a.shape[1], ctx.mesh, b)


@register_op("selective_scan", grad=_grad_maker)
def _selective_scan_op(ctx, op):
    """X, Delta: [b, s, d_inner], Delta the step sizes (after their
    softplus); A: [d_inner, d_state], negative; B, C: [b, s, d_state];
    D: [d_inner]. Y: [b, s, d_inner] in X's dtype; Starts:
    [n_chunks, b, d_state, d_inner] float32, for the gradient op."""
    operands = [ctx.in_(op, slot) for slot in _SLOTS]
    x, a = operands[0], operands[2]
    s, d, n = x.shape[1], x.shape[2], a.shape[1]
    profiler.set_counter("ssm_state_size", int(n))
    if _in_kernels(ctx, x, a):
        profiler.bump_counter("ssm_dispatch_pallas")
        profiler.set_counter("ssm_chunk_len", scan_kernel.block_len(s))
        y, starts = scan_kernel.selective_scan(*operands, ctx.mesh)
    else:
        profiler.bump_counter("ssm_dispatch_chunked")
        profiler.set_counter("ssm_chunk_len", CHUNK)
        y, starts = selective_scan_with_starts(*operands)
        # a mesh refused the kernels at shapes that admit them: the
        # states of the declared blocks (the module docstring, `Starts`)
        starts = starts[::max(kept_len(s, d, n) // CHUNK, 1)][
            :n_chunks(s, d, n)]
    ctx.out(op, "Y", y)
    ctx.out(op, "Starts", starts)


@register_op("selective_scan_grad", differentiable=False)
def _selective_scan_grad_op(ctx, op):
    operands = [ctx.in_(op, slot) for slot in _SLOTS]
    x, a = operands[0], operands[2]
    starts, dy = ctx.in_(op, "Starts"), ctx.in_(op, "GRAD_Y")
    if _in_kernels(ctx, x, a):
        grads = scan_kernel.selective_scan_grads(*operands, starts, dy,
                                                 ctx.mesh)
    else:
        s = x.shape[1]
        if kept_len(s, x.shape[2], a.shape[1]) != min(CHUNK, s):
            # the forward op thinned them (the module docstring, `Starts`)
            starts = selective_scan_with_starts(*operands)[1]
        grads = selective_scan_grads(*operands, starts, dy)
    for slot, g in zip(_SLOTS, grads):
        ctx.out(op, f"IGRAD_{slot}", g)


# ---------------------------------------------------------------------------
# Mamba-2: one decay a head, a chunk is matrix products
# ---------------------------------------------------------------------------
#
# H heads of P channels, a state of N lanes a channel, G groups of H / G
# heads that share `B` and `C` (head h reads group h // (H / G)). Per head,
# with the state `h` [P, N] zero at the start of a row, `a` one negative
# number a head and `Delta` one positive number a head and a token:
#
#     h_t = exp(Delta_t a) h_{t-1} + Delta_t x_t B_t^T
#     y_t = h_t C_t + D x_t
#
# In a chunk of `c` tokens, with `S` the running sum of `Delta` from the
# chunk's start (the exponents below are at most 0 whatever the steps),
# `u_j = Delta_j x_j` and `h_0` the state the chunk starts from:
#
#     L[t, j] = exp(a (S_t - S_j))                     (j <= t, else 0)
#     Y       = ((C B^T) * L) u + exp(a S) * (C h_0^T)
#     h_c     = exp(a S_c) h_0 + (exp(a (S_c - S)) * u)^T B
#
# `C B^T` is one product a group, the three others one a head, all of them
# batched over the chunks; only the states go from chunk to chunk, an
# elementwise `lax.scan` over `s / c` steps of [H, P, N]. The row's
# trajectory `[s, H, P, N]` is never in memory. The forward op's second
# output, `Starts`, is the state each chunk starts from
# ([s / c, b, H, P, N] float32: 16.8 MB a layer at 4,096 tokens and 16
# heads of 64 x 128).
#
# The gradient op `ssd_scan_grad` reads `Starts` and runs no forward
# again. With `M = (C B^T) * L`, `e = exp(a S)`, `w = exp(a (S_c - S))`
# and `lam` the adjoint of the state a chunk ends in:
#
#     lam_0 = exp(a S_c) lam + (e * dY)^T C            (to the chunk before)
#     du = M^T dY + w * (B lam^T)       dM = mask(dY u^T)
#     dC = (dM * L) B + e * (dY h_0)    dB = (dM * L)^T C + (w * u) lam
#
# and the exponents' adjoint `r_t`, every place `a S_t` stands in:
#
#     r = rowsum(dM * M) - colsum(dM * M) + sum_p dY * Y_inter - q
#         + [t = c] (sum q + exp(a S_c) <lam, h_0>),   q_j = w_j u_j . (lam B_j)
#     da = sum_t r_t S_t     dDelta_j = a sum_{t >= j} r_t + du_j . x_j
#     dx = du * Delta + D dY                  dD = sum dY x
#
# Everything is float32 arrays; the products run at the backend's default
# precision (on a TPU a float32 product reads bf16, as in `kda_attention`).


def _ssd_chunks(t, n_chunks, *tail):
    """[b, n_chunks * c, prod(tail)] -> [b, n_chunks, c, *tail] float32."""
    b = t.shape[0]
    return t.astype(jnp.float32).reshape(b, n_chunks, -1, *tail)


def _ssd_decays(a, delta):
    """a: [g, r]; delta: [b, k, c, g, r]. Returns `S`, `L` as
    [b, k, g, r, t, j] with 0 where j > t, `e = exp(a S)` and
    `w = exp(a (S_c - S))`, both [b, k, c, g, r]."""
    c = delta.shape[2]
    s = jnp.cumsum(delta, axis=2)
    heads_first = jnp.moveaxis(s, 2, -1)  # [b, k, g, r, c]
    gap = heads_first[..., :, None] - heads_first[..., None, :]
    admitted = np.tril(np.ones((c, c), bool))
    lm = jnp.exp(jnp.where(admitted, a[:, :, None, None] * gap, -jnp.inf))
    return s, lm, jnp.exp(a * s), jnp.exp(a * (s[:, :, -1:] - s))


def _ssd_between_chunks(e, adds, reverse=False):
    """`h <- exp(a S_c) h + add` from chunk to chunk, from zero (`reverse`:
    from the last chunk back, the adjoint's way). e: [b, k, c, g, r];
    adds: [k, b, g, r, p, n]. Returns what each step starts from, like
    `adds`."""
    last = jnp.moveaxis(e[:, :, -1], 1, 0)[..., None, None]  # [k, b, g, r, 1, 1]

    def chunk(h, xs):
        decay, add = xs
        return decay * h + add, h

    return jax.lax.scan(chunk, jnp.zeros_like(adds[0]), (last, adds),
                        reverse=reverse)[1]


def _ssd_fwd(x, delta, a, bm, cm):
    """x: [b, k, c, g, r, p]; delta: [b, k, c, g, r]; a: [g, r];
    bm, cm: [b, k, c, g, n], float32. Returns `Y` without the skip, like
    x, and the state each chunk starts from, [k, b, g, r, p, n]."""
    _, lm, e, w = _ssd_decays(a, delta)
    u = x * delta[..., None]
    starts = _ssd_between_chunks(
        e, jnp.einsum("bkcgrp,bkcgn->kbgrpn", u * w[..., None], bm))
    m = jnp.einsum("bktgn,bkjgn->bkgtj", cm, bm)[:, :, :, None] * lm
    y = (jnp.einsum("bkgrtj,bkjgrp->bktgrp", m, u)
         + e[..., None] * jnp.einsum("bktgn,kbgrpn->bktgrp", cm, starts))
    return y, starts


def _ssd_bwd(x, delta, a, bm, cm, starts, dy):
    """The gradients of `_ssd_fwd`'s first output with respect to x,
    delta, a, bm and cm, from the states the forward kept (the equations
    stand above)."""
    s, lm, e, w = _ssd_decays(a, delta)
    u = x * delta[..., None]
    lam = _ssd_between_chunks(
        e, jnp.einsum("bktgrp,bktgn->kbgrpn", dy * e[..., None], cm),
        reverse=True)
    cb = jnp.einsum("bktgn,bkjgn->bkgtj", cm, bm)[:, :, :, None]
    m = cb * lm
    seen = jnp.einsum("bkjgn,kbgrpn->bkjgrp", bm, lam)  # B lam^T
    du = jnp.einsum("bkgrtj,bktgrp->bkjgrp", m, dy) + w[..., None] * seen
    dml = jnp.einsum("bktgrp,bkjgrp->bkgrtj", dy, u) * lm  # dM * L
    dcb = jnp.sum(dml, axis=3)  # [b, k, g, t, j]
    inter = jnp.einsum("bktgn,kbgrpn->bktgrp", cm, starts)
    dcm = (jnp.einsum("bkgtj,bkjgn->bktgn", dcb, bm)
           + jnp.einsum("bktgrp,kbgrpn->bktgn", dy * e[..., None], starts))
    dbm = (jnp.einsum("bkgtj,bktgn->bkjgn", dcb, cm)
           + jnp.einsum("bkjgrp,kbgrpn->bkjgn", u * w[..., None], lam))
    pairs = dml * cb  # dM * M, the adjoint of a (S_t - S_j)
    q = w * jnp.sum(u * seen, axis=-1)
    r = (jnp.moveaxis(jnp.sum(pairs, -1) - jnp.sum(pairs, -2), -1, 2)
         + e * jnp.sum(dy * inter, axis=-1) - q)
    at_end = (jnp.sum(q, axis=2)
              + e[:, :, -1] * jnp.moveaxis(
                  jnp.sum(lam * starts, axis=(-2, -1)), 0, 1))
    r = r.at[:, :, -1].add(at_end)
    da = jnp.sum(r * s, axis=(0, 1, 2))
    ddelta = (a * jnp.flip(jnp.cumsum(jnp.flip(r, 2), axis=2), 2)
              + jnp.sum(du * x, axis=-1))
    return du * delta[..., None], ddelta, da, dbm, dcm


def ssd_chunk_len(s, chunk_size):
    """Tokens a chunk: `chunk_size`, or the row where it is shorter."""
    return min(int(chunk_size), int(s))


def ssd_n_chunks(s, chunk_size):
    """`Starts`' leading dimension: the chunks of a row of `s` tokens, the
    last one filled up with steps of size 0, which change no state."""
    return -(-int(s) // ssd_chunk_len(s, chunk_size))


def _ssd_operands(x, delta, a, bm, cm, groups, chunk_size, *more):
    """The operands by chunk, group and head of the group, float32, the
    row padded to whole chunks; `more` ([b, s, H * P]) goes with x."""
    b, s, _ = x.shape
    heads = a.shape[0]
    r, n = heads // groups, bm.shape[2] // groups
    k = ssd_n_chunks(s, chunk_size)
    pad = k * ssd_chunk_len(s, chunk_size) - s
    if pad:
        x, delta, bm, cm, *more = (
            jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
            for t in (x, delta, bm, cm, *more))
    return (*(_ssd_chunks(t, k, groups, r, x.shape[2] // heads)
              for t in (x, *more)),
            _ssd_chunks(delta, k, groups, r),
            a.astype(jnp.float32).reshape(groups, r),
            _ssd_chunks(bm, k, groups, n), _ssd_chunks(cm, k, groups, n))


def ssd_scan_with_starts(x, delta, a, bm, cm, dskip, groups=1,
                         chunk_size=128):
    """x: [b, s, H * P]; delta: [b, s, H], the steps (after their
    softplus); a: [H], negative; bm, cm: [b, s, G * N]; dskip: [H].
    Returns y like x and the state each chunk starts from,
    [n_chunks, b, H, P, N] float32, which `ssd_scan_grads` takes."""
    b, s, _ = x.shape
    heads = a.shape[0]
    xc, dc, ac, bc, cc = _ssd_operands(x, delta, a, bm, cm, groups,
                                       chunk_size)
    y, starts = _ssd_fwd(xc, dc, ac, bc, cc)
    y = y + dskip.astype(jnp.float32).reshape(ac.shape)[..., None] * xc
    return (y.reshape(b, -1, x.shape[2])[:, :s].astype(x.dtype),
            starts.reshape(starts.shape[0], b, heads, *starts.shape[-2:]))


def ssd_scan_grads(x, delta, a, bm, cm, dskip, starts, dy, groups=1,
                   chunk_size=128):
    """The gradients of `ssd_scan`'s output with respect to its six
    operands, from the states `ssd_scan_with_starts` kept."""
    b, s, _ = x.shape
    xc, dyc, dc, ac, bc, cc = _ssd_operands(x, delta, a, bm, cm, groups,
                                            chunk_size, dy)
    starts = starts.reshape(starts.shape[0], b, *ac.shape, *starts.shape[-2:])
    dx, ddelta, da, dbm, dcm = _ssd_bwd(xc, dc, ac, bc, cc, starts, dyc)
    dsk = dskip.astype(jnp.float32).reshape(ac.shape)
    dx = dx + dsk[..., None] * dyc
    dd = jnp.sum(dyc * xc, axis=(0, 1, 2, 5))

    def row(t, like):
        return t.reshape(b, -1, like.shape[2])[:, :s].astype(like.dtype)

    return (row(dx, x), row(ddelta, delta), da.reshape(-1).astype(a.dtype),
            row(dbm, bm), row(dcm, cm), dd.reshape(-1).astype(dskip.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def ssd_scan(x, delta, a, bm, cm, dskip, groups=1, chunk_size=128):
    return ssd_scan_with_starts(x, delta, a, bm, cm, dskip, groups,
                                chunk_size)[0]


def _ssd_scan_vjp_fwd(x, delta, a, bm, cm, dskip, groups, chunk_size):
    y, starts = ssd_scan_with_starts(x, delta, a, bm, cm, dskip, groups,
                                     chunk_size)
    return y, (x, delta, a, bm, cm, dskip, starts)


def _ssd_scan_vjp_bwd(groups, chunk_size, res, dy):
    return ssd_scan_grads(*res, dy, groups, chunk_size)


ssd_scan.defvjp(_ssd_scan_vjp_fwd, _ssd_scan_vjp_bwd)

_SSD_SLOTS = ("X", "Dt", "DtBias", "ALog", "B", "C", "D")


def _ssd_grad_maker(op, grad_out_names, block, helpers):
    """`ssd_scan_grad` reads the forward op's `Starts`; a gradient into
    `Starts` itself goes through `jax.vjp` of the lowering."""
    if (grad_out_names.get("Y", [None])[0] is None
            or grad_out_names.get("Starts", [None])[0] is not None):
        return None
    return [{
        "type": "ssd_scan_grad",
        "inputs": {**{slot: op.input(slot) for slot in _SSD_SLOTS},
                   "Starts": op.output("Starts"),
                   "GRAD_Y": [grad_out_names["Y"][0]]},
        "outputs": {f"IGRAD_{slot}": [helpers.grad_name(op.input(slot)[0])]
                    for slot in _SSD_SLOTS},
        "attrs": {"n_groups": op.attr("n_groups", 1),
                  "chunk_size": op.attr("chunk_size", 128)},
    }]


def _ssd_step(ctx, op):
    """The op's operands with the step and the decay made from what the
    model holds, float32: `Delta = softplus(Dt + DtBias)`,
    `a = -exp(ALog)`, and `Delta`'s derivative by `Dt`."""
    x, dt, dt_bias, a_log, bm, cm, dskip = (
        ctx.in_(op, slot) for slot in _SSD_SLOTS)
    raw = dt.astype(jnp.float32) + dt_bias.astype(jnp.float32)
    return ((x, jax.nn.softplus(raw), -jnp.exp(a_log.astype(jnp.float32)),
             bm, cm, dskip), jax.nn.sigmoid(raw))


def _ssd_count(x, heads, chunk_size):
    """Once a lowering of the forward or of the gradient op:
    `ssd_scan_calls`, and `ssd_chunk_pairs`, the `[t, j]` pairs a call's
    decays and masked products walk: batch x heads x padded tokens x chunk
    length (what a roofline share of the op counts its work from)."""
    b, s = int(x.shape[0]), int(x.shape[1])
    c = ssd_chunk_len(s, chunk_size)
    profiler.bump_counter("ssd_scan_calls")
    profiler.bump_counter("ssd_chunk_pairs",
                          b * int(heads) * ssd_n_chunks(s, chunk_size) * c * c)


@register_op("ssd_scan", grad=_ssd_grad_maker)
def _ssd_scan_op(ctx, op):
    """X: [b, s, H * P]; Dt: [b, s, H], the step's projection before its
    bias and softplus; DtBias, ALog, D: [H]; B, C: [b, s, G * N]. Attrs
    `n_groups` G (head h reads group h // (H / G)) and `chunk_size`. Y
    like X; Starts: [n_chunks, b, H, P, N] float32, for the gradient op.
    The step, the decay and everything inside a chunk are float32,
    whatever the AMP dtype of the inputs."""
    operands, _ = _ssd_step(ctx, op)
    x, a, bm = operands[0], operands[2], operands[3]
    groups, chunk_size = op.attr("n_groups", 1), op.attr("chunk_size", 128)
    heads = a.shape[0]
    if heads % groups or x.shape[2] % heads or bm.shape[2] % groups:
        raise ValueError(
            f"ssd_scan: {heads} heads in {groups} groups over X "
            f"{x.shape} and B {bm.shape}: the groups divide the heads and "
            "B's width, the heads X's")
    profiler.bump_counter("ssd_dispatch_chunked")
    _ssd_count(x, heads, chunk_size)
    profiler.set_counter("ssd_chunk_len", ssd_chunk_len(x.shape[1],
                                                        chunk_size))
    profiler.set_counter("ssd_heads", int(heads))
    profiler.set_counter("ssd_groups", int(groups))
    profiler.set_counter("ssd_state_size", int(bm.shape[2] // groups))
    y, starts = ssd_scan_with_starts(*operands, groups, chunk_size)
    ctx.out(op, "Y", y)
    ctx.out(op, "Starts", starts)


@register_op("ssd_scan_grad", differentiable=False)
def _ssd_scan_grad_op(ctx, op):
    operands, dsoftplus = _ssd_step(ctx, op)
    chunk_size = op.attr("chunk_size", 128)
    _ssd_count(operands[0], operands[2].shape[0], chunk_size)
    dx, ddelta, da, dbm, dcm, dd = ssd_scan_grads(
        *operands, ctx.in_(op, "Starts"), ctx.in_(op, "GRAD_Y"),
        op.attr("n_groups", 1), chunk_size)
    dt, dt_bias, a_log = (ctx.in_(op, slot)
                          for slot in ("Dt", "DtBias", "ALog"))
    ddt = ddelta * dsoftplus
    grads = (dx, ddt.astype(dt.dtype),
             jnp.sum(ddt, axis=(0, 1)).astype(dt_bias.dtype),
             (da * operands[2]).astype(a_log.dtype),  # a = -exp(ALog)
             dbm, dcm, dd)
    for slot, g in zip(_SSD_SLOTS, grads):
        ctx.out(op, f"IGRAD_{slot}", g)
