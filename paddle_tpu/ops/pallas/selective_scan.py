"""Mamba-1's selective scan as a Pallas kernel pair: the recurrence taken
one token at a time with the state in vector registers and VMEM, so that
no decay between two tokens of a chunk is ever built.

The mathematics is `ops/ssm_ops.py`'s module docstring, term for term;
the chunked form there is the plain path and this file's test oracle.
The two share the equations and no code. What differs is the work: the
chunked form pays `CHUNK` exponentials a state element in each of its
three reductions, these kernels one a pass.

**Layout.** The channels fill whole vector registers: `d_inner` is cut
into groups of 128 lanes and a grid step holds `SUB` = 8 groups on the
sublanes, 1,024 channels an `[8, 128]` float32 register. The state of one
lane `n` of `d_state` is one such register, `h[n]`, and the sum over the
state that `y`, `du` and `dDelta` need is a sum of registers: nothing is
broadcast along a register and nothing reduced across one in the token
loop. `B_t[n]` and `C_t[n]` are scalars, read from SMEM and splat. x,
Delta and dy arrive as `[b, s, d_inner / 128, 128]` (XLA turns the
`[b, s, d_inner]` arrays once outside: a token's 1,024 channels are then
one aligned `[8, 128]` tile, in whatever dtype AMP gives them, widened
in the kernel) and `A` as `[d_state, d_inner / 128, 128]`.

**`ssm_scan_fwd`.** Grid `(b, d_inner / 1024, s / T)`, the last axis
sequential, `h` in a VMEM scratch zeroed at a row's first block and
carried through the block's `T` tokens in registers:

    h[n] = exp(Delta_t A[n]) h[n] + (Delta_t x_t) B_t[n]
    y_t  = sum_n C_t[n] h[n] + D x_t

It writes `y` in x's dtype and the state each block starts from,
`[b, s / T, d_state, d_inner]` float32 (21 MB a layer at 4,096 tokens and
5,120 x 16 where the chunked form keeps 0.17 GB).

**`ssm_scan_bwd`.** The same grid walked from the row's end. A block
rebuilds its trajectory and its decays into VMEM from its start state
(`[T, d_state, 8, 128]` each: 4 MB at `T` 64), then walks its tokens
backwards with the adjoint `g_t = exp(Delta_{t+1} A) g_{t+1} + C_t dy_t`
and forms the six gradients of the equations as it goes; the decay is
read back, not computed again. The exponent is `Delta A`, at most 0:
nothing is clamped and nothing divided by a decay. `dA` and `dD`
accumulate over a row in their output blocks, which stay in VMEM along
the sequential axis (one partial a row of the batch, added up outside).
`dB_t[n]` and `dC_t[n]` are sums over the channels, a whole register
each: the products are staged in VMEM a block, their sublanes added by
eight strided loads a token, their lanes by one product with a matrix of
ones on the otherwise idle MXU (`Precision.HIGHEST`: float32), and a
partial a channel block leaves for traced code outside to add up.

Rows are padded to whole blocks with tokens of step 0 (no decay, no
input: the state passes through) and channels to whole registers with
zeros. Everything inside is float32.

`selective_scan_viable` says where the kernels run; `ops/ssm_ops.py`
asks it, for the path and for the length `Starts` is kept at.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import cost, on_mesh
from .flash_attention import LANE, _interpret, require_pallas

SUB = 8  # groups of 128 channels a grid step: the sublanes of a register
# Tokens a grid step. The backward holds four [BLOCK, d_state, 8, 128]
# float32 arrays in VMEM and the forward writes a state a block: PERF.md
# (PR 45) has what 32, 64, 128 and 256 measured.
BLOCK = 64

_F32 = jnp.float32


def selective_scan_viable(s, d_inner, d_state, mesh, batch=1):
    """Where the kernels run: channels in whole groups of 128 lanes, a
    state of whole eights (Mamba-1 has 16; the kernels are tested at 8
    and 16), one device or a mesh that shards `batch` alone and divides
    the rows, and Mosaic (or the interpreter) to run them. Any length."""
    # read at the call: tests steer it
    from .flash_attention import _use_pallas

    return (s >= 1 and d_inner % LANE == 0 and d_state % 8 == 0
            and _use_pallas() and on_mesh.batch_shards(mesh, batch) > 0)


def block_len(s):
    """Tokens between two states kept: a row shorter than a block is one
    block of its own length."""
    return min(BLOCK, s)


# Inside the kernels the arithmetic is `jax.lax`'s primitives, not `jnp`'s
# functions and operators: each of those is a `jit` of its own, traced on the
# host once a call and taken apart again by Mosaic's lowering, and every
# process lowers both kernels at its start (PERF.md, PR 45: a second of
# `setup_s`).
_mul, _add, _exp = jax.lax.mul, jax.lax.add, jax.lax.exp


def _f32(ref, *at):
    return jax.lax.convert_element_type(ref[at], _F32)


def _splats(ref, t, n):
    """[n, SUB, LANE]: scalar k of token t's row of a block's SMEM on
    every lane of register k."""
    first = _mul(t, jnp.int32(n))
    return jax.lax.concatenate(
        [jax.lax.broadcast(ref[0, 0, 0, _add(first, jnp.int32(k))],
                           (1, SUB, LANE)) for k in range(n)], 0)


def _fwd_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, d_ref, y_ref, st_ref,
                h_ref, *, block, n):
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    st_ref[0, 0] = h_ref[...]
    dskip = d_ref[...]
    wide = lambda v: jax.lax.broadcast(v, (n,))  # a register under each lane

    def token(t, h):
        x, dt = _f32(x_ref, 0, t), _f32(dt_ref, 0, t)
        h = _add(_mul(_exp(_mul(wide(dt), a_ref[...])), h),
                 _mul(wide(_mul(dt, x)), _splats(b_ref, t, n)))
        y = _add(_mul(dskip, x),
                 jnp.sum(_mul(h, _splats(c_ref, t, n)), axis=0))
        y_ref[0, t] = jax.lax.convert_element_type(y, y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, block, token, h_ref[...])


def _bwd_kernel(b_ref, c_ref, x_ref, dt_ref, dy_ref, a_ref, d_ref, st_ref,
                dx_ref, ddt_ref, db_ref, dc_ref, da_ref, dd_ref,
                traj, decs, stage_b, stage_c, lanes_b, lanes_c, lam_ref,
                *, block, n):
    @pl.when(pl.program_id(2) == 0)
    def _():
        lam_ref[...] = jnp.zeros_like(lam_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    dskip = d_ref[...]
    wide = lambda v: jax.lax.broadcast(v, (n,))
    # the block's trajectory, traj[t + 1] = h_t, and its decays
    traj[0] = st_ref[0, 0]

    def rebuild(t, h):
        x, dt = _f32(x_ref, 0, t), _f32(dt_ref, 0, t)
        dec = _exp(_mul(wide(dt), a_ref[...]))
        h = _add(_mul(dec, h), _mul(wide(_mul(dt, x)), _splats(b_ref, t, n)))
        decs[t] = dec
        traj[_add(t, jnp.int32(1))] = h
        return h

    jax.lax.fori_loop(0, block, rebuild, st_ref[0, 0])

    def token(i, carry):
        # lam arrives as exp(Delta_{t+1} A) g_{t+1}, 0 past the row's end
        lam, da, dd = carry
        t = jax.lax.sub(jnp.int32(block - 1), i)
        x, dt, dy = _f32(x_ref, 0, t), _f32(dt_ref, 0, t), _f32(dy_ref, 0, t)
        g = _add(lam, _mul(wide(dy), _splats(c_ref, t, n)))
        stage_c[t] = _mul(wide(dy), traj[_add(t, jnp.int32(1))]).reshape(
            n * SUB, LANE)
        stage_b[t] = _mul(g, wide(_mul(dt, x))).reshape(n * SUB, LANE)
        du = jnp.sum(_mul(g, _splats(b_ref, t, n)), axis=0)
        back = _mul(decs[t], g)  # what h_{t-1} sees of g_t
        w = _mul(back, traj[t])  # g_t (h_t - B_t u_t)
        dx_ref[0, t] = jax.lax.convert_element_type(
            _add(_mul(du, dt), _mul(dskip, dy)), dx_ref.dtype)
        ddt_ref[0, t] = jax.lax.convert_element_type(
            _add(jnp.sum(_mul(a_ref[...], w), axis=0), _mul(du, x)),
            ddt_ref.dtype)
        return back, _add(da, _mul(wide(dt), w)), _add(dd, _mul(dy, x))

    lam_ref[...], da_ref[0], dd_ref[0] = jax.lax.fori_loop(
        0, block, token, (lam_ref[...], da_ref[0], dd_ref[0]))

    # dB and dC: the staged registers' sublanes by strided loads (row k of
    # each of the n registers of a token), their lanes on the MXU
    def sublanes(t, carry):
        for stage, lanes in ((stage_b, lanes_b), (stage_c, lanes_c)):
            rows = stage[t, pl.ds(0, n, stride=SUB)]
            for r in range(1, SUB):
                rows = _add(rows, stage[t, pl.ds(r, n, stride=SUB)])
            lanes[pl.ds(pl.multiple_of(_mul(t, jnp.int32(n)), n), n)] = rows
        return carry

    jax.lax.fori_loop(0, block, sublanes, 0)
    ones = jax.lax.broadcast(jnp.float32(1), (SUB, LANE))
    for lanes, out in ((lanes_b, db_ref), (lanes_c, dc_ref)):
        out[0, 0, 0] = jax.lax.dot_general(
            ones, lanes[...], (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=_F32)[:1]


def _cost(backward, b, s, d, n, dtypes):
    """What one call declares (`cost.py`: a kernel with no product counts
    one FLOP an arithmetic operation of its formulas an element) for the
    unpadded row. `dtypes`: of x (and y, dx), Delta, B and C.

    `ssm_scan_fwd`, a state element: `Delta A`, the decay times `h`,
    `u B`, their sum, `C h` and its sum over the state (6); a token a
    channel: `u = Delta x`, `D x` and its sum (3). `ssm_scan_bwd`, a
    state element: the trajectory again (4), `g = lam + C dy` (2), `dy h`
    and `g u` and their sums over the channels (4), `g B` and its sum
    (2), the decay times `g` and that times `h_{t-1}` (2), `A w` and
    `Delta w` and their sums (4): 18; a token a channel: `u`,
    `dx = du Delta + D dy` (3), `du x` and its sum (2), `dy x` and its
    sum (2): 8. One exponential a state element a pass: the backward
    keeps the decays it rebuilt. Each operand and output once, the
    gradients of B and C as the sums they are."""
    xd, dd, bd, cd = dtypes
    elements = b * s * d
    wide, state = ((b, s, d), xd), ((b, -(-s // block_len(s)), n, d), _F32)
    moved = [wide, ((b, s, d), dd), ((b, s, n), bd), ((b, s, n), cd),
             ((d, n), _F32), ((d,), _F32), state, wide]  # ..., y or dy
    if backward:  # dx, dDelta, dB, dC, dA, dD
        moved += moved[:6]
    each = (18 * n + 8) if backward else (6 * n + 3)
    return cost.estimate(elements * each, elements * n, *moved)


def _scalars(t, blocks, block):
    """[b, S, n] -> [b, blocks, 1, block * n] float32, for SMEM: a block's
    B or C as one row of scalars."""
    return t.astype(_F32).reshape(t.shape[0], blocks, 1, -1)


def _specs(block, n, at):
    """Block specs of one grid step `(i, c, j)`, the token block `at(j)`:
    the SMEM scalars, a [b, S, G, 128] array, A, D, the states."""
    return (
        pl.BlockSpec((1, 1, 1, block * n), lambda i, c, j: (i, at(j), 0, 0),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((1, block, SUB, LANE), lambda i, c, j: (i, at(j), c, 0)),
        pl.BlockSpec((n, SUB, LANE), lambda i, c, j: (0, c, 0)),
        pl.BlockSpec((SUB, LANE), lambda i, c, j: (c, 0)),
        pl.BlockSpec((1, 1, n, SUB, LANE),
                     lambda i, c, j: (i, at(j), 0, c, 0)))


_PARAMS = dict(dimension_semantics=("parallel", "parallel", "arbitrary"))


@functools.partial(jax.jit, static_argnames=("statics",))
def _call_fwd(x, delta, a, bm, cm, dskip, *, statics):
    """x, delta: [b, S, G, 128], S whole blocks and G whole eights; a:
    [n, G, 128]; bm, cm: [b, S, n]; dskip: [G, 128]. Returns y like x and
    the state each block starts from, [b, S / block, n, G, 128]. One call
    for a forward that is differentiated and one that is not (a Program's
    gradient op may lower its forward op again, and XLA merges the two
    calls only if they are the same call)."""
    block, interpret, s, d = statics
    b, S, G, _ = x.shape
    n, blocks = a.shape[0], S // block
    scalar, wide, a_spec, d_spec, state = _specs(block, n, lambda j: j)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, block=block, n=n),
        grid=(b, G // SUB, blocks),
        in_specs=[scalar, scalar, wide, wide, a_spec, d_spec],
        out_specs=[wide, state],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, blocks, n, G, LANE), _F32)],
        scratch_shapes=[pltpu.VMEM((n, SUB, LANE), _F32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
        name="ssm_scan_fwd",
        cost_estimate=_cost(False, b, s, d, n,
                            (x.dtype, delta.dtype, bm.dtype, cm.dtype)),
    )(_scalars(bm, blocks, block), _scalars(cm, blocks, block), x, delta, a,
      dskip)


@functools.partial(jax.jit, static_argnames=("statics",))
def _call_bwd(x, delta, a, bm, cm, dskip, starts, dy, *, statics):
    """The reverse sweep: grid step j holds block `last - j`. Returns dx,
    ddelta like x and delta; dB and dC as [b, G / 8, S, n] float32, a
    partial a channel block; dA [b, n, G, 128] and dD [b, G, 128], a
    partial a row."""
    block, interpret, s, d = statics
    b, S, G, _ = x.shape
    n, blocks = a.shape[0], S // block
    last = blocks - 1
    scalar, wide, a_spec, d_spec, state = _specs(block, n, lambda j: last - j)
    partial = pl.BlockSpec((1, 1, 1, 1, block * n),
                           lambda i, c, j: (i, c, last - j, 0, 0))
    stage = pltpu.VMEM((block, n * SUB, LANE), _F32)
    lanes = pltpu.VMEM((block * n, LANE), _F32)
    # the trajectory, the decays and the two stages, and 8 MiB for the
    # rows in flight and the lanes' sums: past Mosaic's 16 MiB default
    vmem = 4 * (4 * block + 1) * n * SUB * LANE + (8 << 20)
    dx, ddelta, db, dc, da, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, block=block, n=n),
        grid=(b, G // SUB, blocks),
        in_specs=[scalar, scalar, wide, wide, wide, a_spec, d_spec, state],
        out_specs=[wide, wide, partial, partial,
                   pl.BlockSpec((1, n, SUB, LANE),
                                lambda i, c, j: (i, 0, c, 0)),
                   pl.BlockSpec((1, SUB, LANE), lambda i, c, j: (i, c, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(x.shape, delta.dtype),
                   *[jax.ShapeDtypeStruct((b, G // SUB, blocks, 1, block * n),
                                          _F32)] * 2,
                   jax.ShapeDtypeStruct((b, n, G, LANE), _F32),
                   jax.ShapeDtypeStruct((b, G, LANE), _F32)],
        scratch_shapes=[pltpu.VMEM((block + 1, n, SUB, LANE), _F32),
                        pltpu.VMEM((block, n, SUB, LANE), _F32),
                        stage, stage, lanes, lanes,
                        pltpu.VMEM((n, SUB, LANE), _F32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem,
                                             **_PARAMS),
        interpret=interpret,
        name="ssm_scan_bwd",
        cost_estimate=_cost(True, b, s, d, n,
                            (x.dtype, delta.dtype, bm.dtype, cm.dtype)),
    )(_scalars(bm, blocks, block), _scalars(cm, blocks, block), x, delta, dy,
      a, dskip, starts)
    return (dx, ddelta, db.reshape(b, G // SUB, S, n),
            dc.reshape(b, G // SUB, S, n), da, dd)


def _padded(s, *rows):
    """`rows` ([b, s, .]) padded to whole blocks with tokens of step 0 and
    no input, which change no state."""
    pad = -s % block_len(s)
    return tuple(jnp.pad(t, ((0, 0), (0, pad), (0, 0))) if pad else t
                 for t in rows)


def _grouped(t, d):
    """[..., d] -> [..., G, 128], G whole eights: the channels padded with
    zeros to whole registers."""
    pad = -d % (SUB * LANE)
    if pad:
        t = jnp.pad(t, [(0, 0)] * (t.ndim - 1) + [(0, pad)])
    return t.reshape(*t.shape[:-1], -1, LANE)


def _operands(x, delta, a, bm, cm, dskip, *more):
    """The six operands (and `more` like x) as the calls take them."""
    s, d = x.shape[1:]
    x, delta, bm, cm, *more = _padded(s, x, delta, bm, cm, *more)
    return (_grouped(x, d), _grouped(delta, d), _grouped(a.astype(_F32).T, d),
            bm, cm, _grouped(dskip.astype(_F32), d),
            *(_grouped(t, d) for t in more))


def _statics(x):
    return block_len(x.shape[1]), _interpret(), *x.shape[1:]


def _with_starts(x, delta, a, bm, cm, dskip):
    b, s, d = x.shape
    y, starts = _call_fwd(*_operands(x, delta, a, bm, cm, dskip),
                          statics=_statics(x))
    return (y.reshape(b, y.shape[1], -1)[:, :s, :d],
            starts.reshape(*starts.shape[:3], -1)[..., :d])


def _grads(x, delta, a, bm, cm, dskip, starts, dy):
    b, s, d = x.shape
    *operands, dy = _operands(x, delta, a, bm, cm, dskip, dy)
    dx, ddelta, db, dc, da, dd = _call_bwd(
        *operands, _grouped(starts, d), dy, statics=_statics(x))
    wide = lambda t: t.reshape(b, t.shape[1], -1)[:, :s, :d]
    return (wide(dx), wide(ddelta), da.reshape(b, a.shape[1], -1)[..., :d],
            jnp.sum(db, axis=1)[:, :s].astype(bm.dtype),
            jnp.sum(dc, axis=1)[:, :s].astype(cm.dtype),
            dd.reshape(b, -1)[:, :d])


def selective_scan_with_starts(x, delta, a, bm, cm, dskip, mesh=None):
    """`ops/ssm_ops.py::selective_scan_with_starts`'s contract in the
    kernels. x, delta: [b, s, d]; a: [d, n], negative; bm, cm: [b, s, n];
    dskip: [d]. Returns y [b, s, d] in x's dtype and the state each block
    of `block_len(s)` tokens starts from, [blocks, b, n, d] float32. On a
    mesh that shards `batch` alone every chip runs the call on its own
    rows (`on_mesh.per_shard`)."""
    require_pallas("selective_scan")
    y, starts = on_mesh.per_shard(
        _with_starts, mesh, (True, True, False, True, True, False))(
            x, delta, a, bm, cm, dskip)
    return y, jnp.moveaxis(starts, 1, 0)


def selective_scan_grads(x, delta, a, bm, cm, dskip, starts, dy, mesh=None):
    """The gradients of `selective_scan_with_starts`'s first output with
    respect to its six operands, from the states it kept."""
    require_pallas("selective_scan")
    dx, ddelta, da, db, dc, dd = on_mesh.per_shard(
        _grads, mesh, (True, True, False, True, True, False, True, True))(
            x, delta, a, bm, cm, dskip, jnp.moveaxis(starts, 0, 1), dy)
    # a row's partials of the two parameters' gradients, added up here
    return (dx, ddelta, jnp.sum(da, axis=0).T.astype(a.dtype), db, dc,
            jnp.sum(dd, axis=0).astype(dskip.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def selective_scan(x, delta, a, bm, cm, dskip, mesh=None):
    """(y, the states kept), differentiable in y: what `jax.vjp` of the
    op's lowering and `jax.grad` in the tests run."""
    return selective_scan_with_starts(x, delta, a, bm, cm, dskip, mesh)


def _vjp_fwd(*args):
    y, starts = selective_scan_with_starts(*args)
    return (y, starts), (*args[:6], starts)


def _vjp_bwd(mesh, res, cts):
    return selective_scan_grads(*res, cts[0], mesh)


selective_scan.defvjp(_vjp_fwd, _vjp_bwd)
