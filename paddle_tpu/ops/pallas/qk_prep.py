"""What lies between the projections and the flash kernels, as one Pallas
kernel pair: the RMSNorm of each head of q and k over its lanes (where the
model has one), the rotary positions, and the head-major write, in one pass
through VMEM forward and one pass backward.

The projections write `[b, s, heads*d]`, heads side by side on the lanes;
the flash kernels read `[b, heads, s, d]`. In plain XLA the way from one
to the other is a reshape, `rms_norm` over the last axis (float32 inside),
`rotary_embedding` (float32 inside) and a transpose: four passes over q
and k, each laid out as XLA likes and relaid for the next, and their
mirror in the backward. Here a grid step reads the `[rows, d]` block of
one head from the projection's array (the head is the block's index along
the lanes), computes in float32

    n = x * rsqrt(mean(x^2) + eps),  y = n * w,
    o = y * cos + roll(y, d/2) * sin                 (where positions are asked for)

and writes the block at `(b, head, rows)` of the head-major output: the
transpose is the two index maps. The backward reads the head-major `do`
that the flash kernels' backward writes and the projection's array again,
rebuilds `n`, and writes the gradient where the projection's backward
reads it:

    dy = do * cos + roll(do * sin, d/2)       (the rotation by the negative angle)
    dw = sum over rows and heads of dy * n
    dx = inv * (dn - n * mean(dn * n)),  dn = dy * w

Nothing float32 of q's size reaches HBM and nothing is kept for the
backward but the projections' outputs.

Without the two norm weights (a model with positions and no QK-norm) the
pass only turns and moves: `y = x`, and the backward is the rotation by
the negative angle of `do`, written where the projection's backward reads
it. It reads the three head-major cotangents and the tables, nothing of
the forward's, writes no partial sums, and the `custom_vjp` keeps no
residual.

One call takes q, k and v: the grid's last axis counts the head slots, the
query heads, then k's, then v's (which are only moved), and an array's
index map holds its block still while the slots are another array's, so
that nothing is copied twice. `cos` and `sin` are `ops/nn_ops.py`'s
`rotary_tables`, float32 `[s, d]`, built by XLA as `rotate_half` builds
them and read once a row block. The norm weights' gradients leave as
`[8, d]` partial sums a row block, which XLA adds up.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..nn_ops import partial_rotary_tables, rotary_tables
from . import cost
from .flash_attention import LANE, _interpret, require_pallas

ROWS = 1024  # rows of one head a grid step
SUBLANES = 8  # of a float32 tile: the weights' partial sums keep them


def qk_prep_viable(d, dv):
    """A head is whole 128-lane slices of the projection's array, and v's
    are as wide as q's and k's."""
    return d % LANE == 0 and dv == d


def _slots(slot, heads, kv_heads):
    """Which array the head slot `slot` belongs to."""
    return (slot < heads,
            jnp.logical_and(slot >= heads, slot < heads + kv_heads),
            slot >= heads + kv_heads)


def _inverse_norm(x, eps):
    return jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _turn(y, tables, turned, back=False):
    """The rotation of `rotate_half` on a [rows, d] block: over the whole
    head one roll by d/2, which is its own inverse; over the first
    `turned` lanes `partial_rotary_tables`' two rolls. `back`: the
    transpose, which is the rotation by the negative angle."""
    d = y.shape[-1]
    if not turned:
        cos_ref, sin_ref = tables
        if back:
            return y * cos_ref[...] + pltpu.roll(y * sin_ref[...], d // 2, 1)
        return y * cos_ref[...] + pltpu.roll(y, d // 2, 1) * sin_ref[...]
    cos_ref, lower_ref, upper_ref = tables
    up, down = d - turned // 2, turned // 2
    if back:
        return (y * cos_ref[...] + pltpu.roll(y * lower_ref[...], down, 1)
                + pltpu.roll(y * upper_ref[...], up, 1))
    return (y * cos_ref[...] + pltpu.roll(y, up, 1) * lower_ref[...]
            + pltpu.roll(y, down, 1) * upper_ref[...])


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, heads, kv_heads, eps, rope,
                turned=0, normed=True):
    wq_ref, wk_ref = rest[:2] if normed else (None, None)
    tables, (qo_ref, ko_ref, vo_ref) = rest[2 * normed:-3], rest[-3:]

    def norm_rotate(x_ref, w_ref, o_ref):
        y = x_ref[0].astype(jnp.float32)
        if normed:
            y = y * _inverse_norm(y, eps) * w_ref[...]
        if rope:
            y = _turn(y, tables, turned)
        o_ref[0, 0] = y.astype(o_ref.dtype)

    is_q, is_k, is_v = _slots(pl.program_id(2), heads, kv_heads)
    pl.when(is_q)(lambda: norm_rotate(q_ref, wq_ref, qo_ref))
    pl.when(is_k)(lambda: norm_rotate(k_ref, wk_ref, ko_ref))

    @pl.when(is_v)
    def _():
        vo_ref[0, 0] = v_ref[0].astype(vo_ref.dtype)


def _bwd_kernel(dqo_ref, dko_ref, dvo_ref, q_ref, k_ref, wq_ref, wk_ref,
                *rest, heads, kv_heads, eps, rope, s, rows, turned=0):
    tables = rest[:-5]
    dq_ref, dk_ref, dv_ref, dwq_ref, dwk_ref = rest[-5:]
    slot = pl.program_id(2)
    row0 = pl.program_id(1) * rows  # read here: not inside a `when`

    def grads(do_ref, x_ref, w_ref, dx_ref, dw_ref, first):
        dy = do_ref[0, 0].astype(jnp.float32)
        if rope:
            dy = _turn(dy, tables, turned, back=True)
        x = x_ref[0].astype(jnp.float32)
        inv = _inverse_norm(x, eps)
        n = x * inv
        dn = dy * w_ref[...]
        dx = inv * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
        dx_ref[0] = dx.astype(dx_ref.dtype)
        dw = dy * n
        if s % rows:  # the last block's rows past the end hold anything
            row = row0 + jax.lax.broadcasted_iota(jnp.int32, dw.shape, 0)
            dw = jnp.where(row < s, dw, 0.0)
        dw = dw.reshape(rows // SUBLANES, SUBLANES, -1).sum(0)

        @pl.when(first)
        def _():
            dw_ref[0, 0] = dw

        @pl.when(jnp.logical_not(first))
        def _():
            dw_ref[0, 0] += dw

    is_q, is_k, is_v = _slots(slot, heads, kv_heads)
    pl.when(is_q)(lambda: grads(dqo_ref, q_ref, wq_ref, dq_ref, dwq_ref,
                                slot == 0))
    pl.when(is_k)(lambda: grads(dko_ref, k_ref, wk_ref, dk_ref, dwk_ref,
                                slot == heads))

    @pl.when(is_v)
    def _():
        dv_ref[0] = dvo_ref[0, 0].astype(dv_ref.dtype)


def _bwd_turn_kernel(dqo_ref, dko_ref, dvo_ref, *rest, heads, kv_heads, rope,
                     turned=0):
    """The backward where nothing was normed: the cotangents turned back
    and moved, from nothing but themselves and the tables."""
    tables, (dq_ref, dk_ref, dv_ref) = rest[:-3], rest[-3:]

    def turn_back(do_ref, dx_ref):
        dy = do_ref[0, 0].astype(jnp.float32)
        if rope:
            dy = _turn(dy, tables, turned, back=True)
        dx_ref[0] = dy.astype(dx_ref.dtype)

    is_q, is_k, is_v = _slots(pl.program_id(2), heads, kv_heads)
    pl.when(is_q)(lambda: turn_back(dqo_ref, dq_ref))
    pl.when(is_k)(lambda: turn_back(dko_ref, dk_ref))

    @pl.when(is_v)
    def _():
        dv_ref[0] = dvo_ref[0, 0].astype(dv_ref.dtype)


def _specs(rows, d, heads, kv_heads):
    """(flat, major, weight, table, partial): the block specs of q, k and
    v in the projections' layout and head-major, each for the head slots
    that are its own and held at its nearest head elsewhere; of a norm
    weight, a rotary table and a weight's partial sums."""
    flat, major = [], []
    for first, n in ((0, heads), (heads, kv_heads),
                     (heads + kv_heads, kv_heads)):
        def head(slot, first=first, n=n):
            return jnp.clip(slot - first, 0, n - 1)

        flat.append(pl.BlockSpec(
            (1, rows, d), lambda b, i, t, head=head: (b, i, head(t))))
        major.append(pl.BlockSpec(
            (1, 1, rows, d), lambda b, i, t, head=head: (b, head(t), i, 0)))
    return (flat, major,
            pl.BlockSpec((1, d), lambda b, i, t: (0, 0)),
            pl.BlockSpec((rows, d), lambda b, i, t: (i, 0)),
            pl.BlockSpec((1, 1, SUBLANES, d), lambda b, i, t: (b, i, 0, 0)))


def _cost(backward, b, s, d, statics, q_dtype, normed=True):
    """What one call declares (`cost.py` has the convention). No product:
    FLOPs an element of q and k, forward 4 (the mean of squares 2, times
    the inverse norm, times the weight) and 3 more where positions turn
    it (5 and three tables where only part of the head turns: a second
    roll's product and sum); backward 11 (the norm rebuilt 3, dn, its
    mean with n 2, dx 3, the weight's gradient 2) and the same 3 or 5.
    One rsqrt a row of a head of q and k. Moved once: q, k and v in and
    out, the two weights and, with positions, the tables; backward also q
    and k again and the weights' partial sums, `[8, d]` a block of
    rows. Not `normed`: the turning's 3 or 5 alone either way, no rsqrt,
    and q, k and v in and out and the tables."""
    heads, kv_heads, _, theta, rows, out_dtype, v_dtype = statics[:7]
    turned = statics[8]
    rows_qk = b * s * (heads + kv_heads)  # rows of one head of q and k
    flat = [((b, s, n * d), t) for n, t in (
        (heads, q_dtype), (kv_heads, q_dtype), (kv_heads, v_dtype))]
    major = [((b, n, s, d), out_dtype) for n in (heads, kv_heads, kv_heads)]
    moved = flat + major + [((d,), jnp.float32)] * (2 if normed else 0)
    if theta:
        moved += [((s, d), jnp.float32)] * (3 if turned else 2)
    if backward and normed:
        moved += flat[:2] + [((b, pl.cdiv(s, rows), SUBLANES, d),
                              jnp.float32)] * 2
    turning = (5 if turned else 3) if theta else 0
    norming = (11 if backward else 4) if normed else 0
    return cost.estimate((norming + turning) * rows_qk * d,
                         rows_qk if normed else 0, *moved)


def _call(kernel, name, statics, s, declared, specs_in, specs_out, shapes_out,
          *args):
    """One of the two calls: the grid over (batch, row blocks, head
    slots), the slots sequential because an output block waits, unmoved,
    through the slots of the other arrays."""
    heads, kv_heads, _, theta, rows = statics[:5]
    b = args[0].shape[0]
    return pl.pallas_call(
        functools.partial(kernel, heads=heads, kv_heads=kv_heads,
                          rope=bool(theta), turned=statics[8]),
        grid=(b, pl.cdiv(s, rows), heads + 2 * kv_heads),
        in_specs=specs_in, out_specs=specs_out, out_shape=shapes_out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=statics[-1],
        name=name,
        cost_estimate=declared,
    )(*args)


def _tables(s, d, statics):
    theta, scaling, turned = statics[3], statics[7], statics[8]
    if not theta:
        return ()
    if turned:
        return partial_rotary_tables(s, d, turned, theta, scaling)
    return rotary_tables(s, d, theta, scaling)


def _fwd_pallas(q, k, v, weights, statics):
    """`weights`: the two norm weights, or () where nothing is normed."""
    heads, kv_heads, eps, _, rows, out_dtype = statics[:6]
    b, s, _ = q.shape
    d = q.shape[2] // heads
    flat, major, weight, table, _ = _specs(rows, d, heads, kv_heads)
    tables = _tables(s, d, statics)
    normed = bool(weights)
    return _call(
        functools.partial(_fwd_kernel, eps=eps, normed=normed),
        "qk_prep_fwd", statics, s,
        _cost(False, b, s, d, statics, q.dtype, normed),
        [*flat, *[weight] * len(weights), *[table] * len(tables)], major,
        [jax.ShapeDtypeStruct((b, n, s, d), out_dtype)
         for n in (heads, kv_heads, kv_heads)],
        q, k, v, *[w.reshape(1, d) for w in weights], *tables)


def _bwd_pallas(dqo, dko, dvo, q, k, wq, wk, statics):
    heads, kv_heads, eps, _, rows, _, v_dtype = statics[:7]
    b, s, _ = q.shape
    d = q.shape[2] // heads
    flat, major, weight, table, partial = _specs(rows, d, heads, kv_heads)
    tables = _tables(s, d, statics)
    sums = jax.ShapeDtypeStruct((b, pl.cdiv(s, rows), SUBLANES, d),
                                jnp.float32)
    dq, dk, dv, dwq, dwk = _call(
        functools.partial(_bwd_kernel, eps=eps, s=s, rows=rows),
        "qk_prep_bwd", statics, s, _cost(True, b, s, d, statics, q.dtype),
        [*major, *flat[:2], weight, weight, *[table] * len(tables)],
        [*flat, partial, partial],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(k.shape, v_dtype), sums, sums],
        dqo, dko, dvo, q, k, wq.reshape(1, d), wk.reshape(1, d), *tables)
    return dq, dk, dv, (dwq.sum((0, 1, 2)), dwk.sum((0, 1, 2)))


def _bwd_turn_pallas(dqo, dko, dvo, statics):
    """The backward of a call without weights: the three head-major
    cotangents and the tables in, the projections' gradients out."""
    heads, kv_heads, _, _, rows, _, v_dtype = statics[:7]
    q_dtype, k_dtype = statics[9]
    b, _, s, d = dqo.shape
    flat, major, _, table, _ = _specs(rows, d, heads, kv_heads)
    tables = _tables(s, d, statics)
    return (*_call(
        _bwd_turn_kernel, "qk_prep_bwd", statics, s,
        _cost(True, b, s, d, statics, q_dtype, normed=False),
        [*major, *[table] * len(tables)], flat,
        [jax.ShapeDtypeStruct((b, s, n * d), t) for n, t in (
            (heads, q_dtype), (kv_heads, k_dtype), (kv_heads, v_dtype))],
        dqo, dko, dvo, *tables), ())


# One jitted call for the forward, as the flash kernels have and for their
# reason: a Program's gradient op lowers its forward op again, and XLA
# merges the two custom calls only if they are the same call.
_fwd_call = jax.jit(_fwd_pallas, static_argnums=(4,))
_bwd_call = jax.jit(_bwd_pallas, static_argnums=(7,))
_bwd_turn_call = jax.jit(_bwd_turn_pallas, static_argnums=(3,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _core(q, k, v, weights, statics):
    return tuple(_fwd_call(q, k, v, weights, statics))


def _core_fwd(q, k, v, weights, statics):
    # without weights the backward reads nothing of the forward's
    return (tuple(_fwd_call(q, k, v, weights, statics)),
            (q, k, *weights) if weights else ())


def _core_bwd(statics, res, cotangents):
    if res:
        return _bwd_call(*cotangents, *res, statics)
    return _bwd_turn_call(*cotangents, statics)


_core.defvjp(_core_fwd, _core_bwd)


def qk_prep(q, k, v, q_weight=None, k_weight=None, *, epsilon=0.0, theta=0.0,
            scaling=None, out_dtype=None, rows=ROWS, rotary_dim=0):
    """q: [b, s, h, d]; k, v: [b, s, g, d], as the projections' outputs
    are reshaped; `q_weight`, `k_weight`: [d], or both None. Returns q, k,
    v head-major, [b, h, s, d] and [b, g, s, d] in `out_dtype` (q's own by
    default): q and k normed over `d` with `epsilon` and their weight
    (with weights) and, where `theta` is not 0, turned by `rotate_half`'s
    positions 0..s-1 (under `scaling`, `rotary_tables`' scaled ones: the
    tables are the kernels' inputs, which are the same kernels either
    way); with `rotary_dim` fewer than `d`, only the first `rotary_dim`
    lanes of a head turn (`partial_rotary_tables`); v as it came. Without
    weights the backward reads the cotangents and the tables alone, and
    nothing is kept for it."""
    require_pallas("qk_prep")
    b, s, h, d = q.shape
    g = k.shape[2]
    if not qk_prep_viable(d, v.shape[3]) or v.shape[2] != g:
        raise ValueError(
            f"qk_prep: q {q.shape}, k {k.shape}, v {v.shape}: needs heads "
            f"of whole {LANE}-lane slices, v's like k's")
    if (q_weight is None) != (k_weight is None):
        raise ValueError("qk_prep: the two norm weights come together")
    # whole (16, 128) tiles of bf16, and whole [8, d] partial sums
    rows = min(rows, -(-s // 16) * 16)
    statics = (h, g, float(epsilon), float(theta), rows,
               jnp.dtype(out_dtype or q.dtype), v.dtype,
               tuple(scaling) if scaling else None,
               int(rotary_dim) if rotary_dim and rotary_dim != d else 0,
               (q.dtype, k.dtype), _interpret())
    flat = lambda t: t.reshape(b, s, -1)
    weights = () if q_weight is None else (
        q_weight.astype(jnp.float32), k_weight.astype(jnp.float32))
    return _core(flat(q), flat(k), flat(v), weights, statics)
