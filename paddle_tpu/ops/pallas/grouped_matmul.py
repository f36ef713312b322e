"""The expert layer's grouped products as a Pallas kernel pair that visits
only the row tiles that hold assignments.

`parallel/moe.py::_block` sorts a block of assignments by expert and runs
them through `[rows, K] x [G, K, N] -> [rows, N]`, group g's rows through
`w[g]`, with `sizes` (the rows of each group, a traced array) saying where
a group ends. The block is a static shape with room above the load, so the
rows past `sum(sizes)` are dead: `jax.lax.ragged_dot`, the plain path and
this file's test oracle, has to be handed a last group stretched over them
and multiplies them like any other. Here the grid walks *visits*: a visit
is one row tile of `tm` rows under one group's weights, a tile that
straddles groups is visited once a group with a row mask, and the list of
visits is computed from `sizes` by a few integer operations on `[G]` and
`[steps]` arrays in front of the call and read by the index maps from SMEM
(scalar prefetch). The grid itself is static, `cdiv(rows, tm) + G - 1`
steps, the most the visits can be; what the load leaves over is

- *fill* steps, one a dead row tile: the index maps hold the operands at
  the last live blocks (no DMA) and the kernel writes zeros, so dead
  output rows cost HBM writes and nothing else;
- *idle* steps: every index map held still, nothing done.

`moe_gmm` is the forward product, and the backward's `dx` (below). Its
output rows outside every group are zeros: inside a visited tile by the
store's mask, which takes the product where the row is the visit's
group's, zero on the tile's first visit and what is there on a later
one. `moe_tgmm` is the weight gradient, `x^T dy`
summed over a group's rows into `[G, K, N]` float32: every group is
visited, an empty one to write its zeros, and both operands are masked to
the visit's rows before the product (a select, so a NaN in a dead row
gives 0 and not NaN). Both sum in float32 (a cut contraction's partial
sums and a group's gradient wait in VMEM) and read their operands in the
dtype they arrive in. `grouped_matmul` ties them with a `jax.custom_vjp`
that casts the weights to the rows' dtype inside and keeps the weights as
they came for the backward, which casts again: a layer's three casts are
not held from forward to backward (0.1 GB each at the cells' widths).

The backward's `dx = dy w[g]^T` is `moe_gmm` again, told statically to
read the weights transposed: `w` stays `[G, K, N]` as the forward holds
it, the rows are the cotangent's `[rows, N]`, a weights' block is
`[to, tc]` at `(group, j, cut)` contracted over its second axis, and the
output is `[rows, K]` in x's dtype, rounded once from the float32 sum in
VMEM (a straddled tile's later visit reads back values that are already
that dtype's, so the re-read is exact). No `[G, N, K]` copy of the weights
and no float32 `[rows, K]` array exists, and the call is handed the
groups' true sizes like the two others, so a dead row's `dx` is zeros.
(`jax.lax.ragged_dot` over the whole block, the last group stretched over
the dead rows and every group's weights copied transposed, took 2.4 to
3.1 ms a product in Mellum's shapes where this call takes 0.5 to 0.6:
PERF.md, PR 38 and PR 73.)

`moe_tgmm` has two callers outside this layer's products, each under a
name of its own and with nothing declared, through
`embedding_grad.py::run_sums`: an embedding table's gradient
(`embed_tgmm`) and the expert layer's sums of a block's rows onto their
tokens (`onto_tokens_tgmm`, `parallel/moe.py::_onto_tokens`). Both hand
it a 0/1 matrix for `x`; the second also a float32 `dy`, which the kernel
cuts into three bfloat16 slices a tile (`_bf16_slices`).

Tile sizes follow the call's shapes (`_tiling`, `ROWS`); the scoped-VMEM
limit is stated in the call. What the calls declare is at `_cost`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import cost
from .flash_attention import LANE, _interpret, require_pallas

_VMEM_LIMIT = 64 << 20  # of v5e's 128 MiB; the default is 16 MiB
_VMEM_BUDGET = 40 << 20  # what _tiling counts: the blocks, double-buffered

# Rows a tile. Measured on the v5e at the three expert cells' shapes, even
# and skewed loads, whole widths (PERF.md, PR 38): 256 was the fastest or
# within 2% of it for every one of a layer's products (the sweep had `dx`
# as a kernel too); 512 read 4 to 8% slower (a tile that straddles two
# groups is multiplied once a group), 1,024 25% slower, 128 the same as
# 256 with twice the steps. One
# product a step over the whole widths: cut into 128-lane slices by a loop
# inside the kernel (a call site's executable then shrinks from 0.8-1.3 MB
# to 0.4-0.5) it read 17 to 22% slower in Trinity's and Mellum's shapes.
ROWS = 256

_NN = (((1,), (0,)), ((), ()))  # [m, c] x [c, o]
_NT = (((1,), (1,)), ((), ()))  # [m, c] x [o, c]: the weights read transposed
_TN = (((0,), (0,)), ((), ()))  # [m, k] x [m, n] -> [k, n]


def grouped_matmul_viable(k, n, dtype):
    """The shapes and backends the kernels are built for: both widths are
    whole 128-lane slices, the operands are bfloat16 or float32, and
    Mosaic (or the interpreter) is there to run them."""
    # read at the call, not bound at import: the v5e compile tests steer it
    from .flash_attention import _use_pallas

    return (k % LANE == 0 and n % LANE == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32))
            and _use_pallas())


def _divisors(width):
    """The multiples of 128 that divide `width`, largest first."""
    return [d for d in range(width, 0, -LANE) if width % d == 0]


def _tiling(rows, a, b, need):
    """(tm, ta, tb): the rows of a tile and how the two widths are cut.
    Whole widths where `need(tm, ta, tb)`, the bytes of the call's
    double-buffered blocks, fits the budget: a group's weights then cross
    HBM once a group, the rows once, and nothing is read again; else `a`
    is cut first, then `b`. `ROWS` rows a tile, or all of a shorter
    block."""
    tm = min(ROWS, -(-rows // 16) * 16)  # whole (16, 128) tiles of bf16
    for tb in _divisors(b):
        for ta in _divisors(a):
            if need(tm, ta, tb) <= _VMEM_BUDGET:
                return tm, ta, tb
    return tm, LANE, LANE


def _visits(sizes, rows, tm, every_group):
    """The grid's steps from the load. `sizes`: [G] int32 with
    `sum(sizes) <= rows`. Returns int32 arrays
    (group [steps], tile [steps], out_tile [steps], offsets [G + 1],
    counts [2]): step t < counts[0] is a visit of row tile `tile[t]` under
    group `group[t]`, whose rows are `offsets[g]` to `offsets[g + 1]`;
    counts[0] <= t < counts[1] fills the dead row tile `out_tile[t]` with
    zeros; the steps from counts[1] on are idle. Past the visits `group`
    and `tile` hold the last visit's, so that no operand moves.
    `every_group` gives an empty group one visit (of no rows), for the
    kernel that owes every group an output."""
    g = sizes.shape[0]
    tiles = pl.cdiv(rows, tm)
    steps = tiles + g - 1
    sizes = sizes.astype(jnp.int32)
    # Sums over the groups as masked [G, G] and [steps, G] reductions, not
    # `cumsum`, `repeat` and gathers: XLA fuses these into a few small
    # loops, where the others cost 0.7 MB of executable a call (PERF.md,
    # PR 38: a step's 84 calls are loaded at every start)
    upto = jnp.arange(g)[None, :] <= jnp.arange(g)[:, None]  # [g, g']: g' <= g
    ends = jnp.sum(jnp.where(upto, sizes[None, :], 0), axis=1)
    starts = ends - sizes
    first = jnp.minimum(starts // tm, tiles - 1)
    count = jnp.where(sizes > 0, (ends - 1) // tm - first + 1,
                      1 if every_group else 0)
    after = jnp.sum(jnp.where(upto, count[None, :], 0), axis=1)
    visits = after[-1]
    t = jnp.arange(steps, dtype=jnp.int32)
    # past the visits, the last visit's group and tile
    at = jnp.minimum(t, jnp.maximum(visits - 1, 0))
    group = jnp.sum(at[:, None] >= after[None, :], axis=1)  # [steps]
    mine = group[:, None] == jnp.arange(g)[None, :]
    tile = at + jnp.sum(
        jnp.where(mine, (first - (after - count))[None, :], 0), axis=1)
    tile = jnp.clip(tile, 0, tiles - 1)
    group = jnp.minimum(group, g - 1)
    live_tiles = (ends[-1] + tm - 1) // tm
    out_tile = jnp.where(
        t < visits, tile, jnp.minimum(live_tiles + t - visits, tiles - 1))
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    counts = jnp.stack([visits, visits + tiles - live_tiles])
    return tuple(a.astype(jnp.int32)
                 for a in (group, tile, out_tile, offsets, counts))


def _rows_of(t, tm, width, group_ref, tile_ref, offsets_ref):
    """[tm, width] bool: the rows of step t's tile that are its group's."""
    g = group_ref[t]
    row = tile_ref[t] * tm + jax.lax.broadcasted_iota(
        jnp.int32, (tm, width), 0)
    return jnp.logical_and(row >= offsets_ref[g], row < offsets_ref[g + 1])


def _gmm_kernel(group_ref, tile_ref, out_tile_ref, offsets_ref, counts_ref,
                x_ref, w_ref, o_ref, *acc, tm, cuts, contract):
    del out_tile_ref  # the output's index map reads it
    t, cut = pl.program_id(1), pl.program_id(2)

    @pl.when(t < counts_ref[0])
    def _():
        product = jax.lax.dot_general(
            x_ref[...], w_ref[...], contract,
            preferred_element_type=jnp.float32)
        if cuts > 1:  # the contraction is cut: its sum waits in VMEM
            (acc_ref,) = acc
            product += jnp.where(cut == 0, 0.0, acc_ref[...])
            acc_ref[...] = product

        @pl.when(cut == cuts - 1)
        def _():
            mine = _rows_of(t, tm, o_ref.shape[1], group_ref, tile_ref,
                            offsets_ref)
            new = jnp.logical_or(
                t == 0, tile_ref[jnp.maximum(t - 1, 0)] != tile_ref[t])
            # the rows of the tile's other groups: zeros on its first
            # visit, what the visit before this one wrote on a later one
            # (already the output dtype's values, so read back exactly)
            rest = jnp.where(new, 0.0, o_ref[...].astype(jnp.float32))
            o_ref[...] = jnp.where(mine, product, rest).astype(o_ref.dtype)

    @pl.when(jnp.logical_and(t >= counts_ref[0], t < counts_ref[1]))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _bf16_slices(v):
    """A float32 array as the three bfloat16 arrays whose sum it is: the
    upper 16 bits, the upper 16 of what that leaves, and the rest, 8 bits
    of mantissa each and each exactly a bfloat16. Cut by a mask on the
    bits: a convert there and back is a compiler's to drop."""
    def upper(a):
        bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
        return jax.lax.bitcast_convert_type(
            bits & jnp.uint32(0xFFFF0000), jnp.float32)

    hi = upper(v)
    mid = upper(v - hi)
    return tuple(a.astype(jnp.bfloat16) for a in (hi, mid, v - hi - mid))


def _tgmm_kernel(group_ref, tile_ref, offsets_ref, counts_ref, x_ref, dy_ref,
                 o_ref, *, tm):
    t = pl.program_id(2)

    @pl.when(t < counts_ref[0])
    def _():
        g = group_ref[t]
        lo, hi = offsets_ref[g], offsets_ref[g + 1]
        new = jnp.logical_or(t == 0, group_ref[jnp.maximum(t - 1, 0)] != g)
        row0 = tile_ref[t] * tm
        whole = jnp.logical_and(row0 >= lo, row0 + tm <= hi)

        def add(x, dy):
            # a float32 `dy` under a bfloat16 `x`: its three slices' products
            first, *rest = _bf16_slices(dy) if dy.dtype != x.dtype else (dy,)
            product = jax.lax.dot_general(
                x, first, _TN, preferred_element_type=jnp.float32)
            for part in rest:
                product += jax.lax.dot_general(
                    x, part, _TN, preferred_element_type=jnp.float32)
            o_ref[...] = product + jnp.where(new, 0.0, o_ref[...])

        def mine(ref):
            # a select, so that a NaN in a dead row gives 0 and not NaN;
            # through float32: the v5e's vector unit has no bf16 select
            keep = _rows_of(t, tm, ref.shape[1], group_ref, tile_ref,
                            offsets_ref)
            return jnp.where(keep, ref[...].astype(jnp.float32),
                             0.0).astype(ref.dtype)

        @pl.when(whole)
        def _():
            add(x_ref[...], dy_ref[...])

        # a tile that the group shares, both operands masked to its rows;
        # an empty group's one visit, of no row, which leaves its zeros
        @pl.when(jnp.logical_not(whole))
        def _():
            add(mine(x_ref), mine(dy_ref))


def _cost(rows, k, n, *arrays):
    """What a call declares (`cost.py` has the convention, and its
    paragraph on work that is data): the live rows are not known at trace
    time, so the count is the static one, every row of the block through
    one group's weights, `2 x rows x K x N` FLOPs, and `arrays`, every
    operand and output, once: all the groups' weights, the dead rows'
    zeros. An upper bound on the useful work, so no share of a roof is
    read against it."""
    return cost.estimate(2 * rows * k * n, 0, *arrays)


def _params(semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _gmm_pallas(x, w, sizes, statics):
    (tm, tc, to), interpret, transpose_rhs, out_dtype = statics
    rows, c = x.shape
    o = w.shape[1 if transpose_rhs else 2]
    meta = _visits(sizes, rows, tm, every_group=False)
    steps, cuts = meta[0].shape[0], c // tc

    def x_map(j, t, cut, group, tile, out_tile, offsets, counts):
        return tile[t], cut

    def w_map(j, t, cut, group, tile, out_tile, offsets, counts):
        return (group[t], j, cut) if transpose_rhs else (group[t], cut, j)

    def o_map(j, t, cut, group, tile, out_tile, offsets, counts):
        return out_tile[t], j

    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, cuts=cuts,
                          contract=_NT if transpose_rhs else _NN),
        out_shape=jax.ShapeDtypeStruct((rows, o), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(meta),
            grid=(o // to, steps, cuts),
            in_specs=[
                pl.BlockSpec((tm, tc), x_map),
                pl.BlockSpec((None, to, tc) if transpose_rhs
                             else (None, tc, to), w_map)],
            out_specs=pl.BlockSpec((tm, to), o_map),
            scratch_shapes=[pltpu.VMEM((tm, to), jnp.float32)] * (cuts > 1)),
        compiler_params=_params(("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="moe_gmm",
        cost_estimate=_cost(rows, c, o, (x.shape, x.dtype),
                            (w.shape, w.dtype), ((rows, o), out_dtype)),
    )(*meta, x, w)


def _tgmm_pallas(x, dy, sizes, statics):
    (tm, tk, tn), interpret, name, declare = statics
    rows, k = x.shape
    n, groups = dy.shape[1], sizes.shape[0]
    group, tile, _, offsets, counts = _visits(sizes, rows, tm,
                                              every_group=True)

    def x_map(i, j, t, group, tile, offsets, counts):
        return tile[t], i

    def dy_map(i, j, t, group, tile, offsets, counts):
        return tile[t], j

    def o_map(i, j, t, group, tile, offsets, counts):
        return group[t], i, j

    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(k // tk, n // tn, group.shape[0]),
            in_specs=[pl.BlockSpec((tm, tk), x_map),
                      pl.BlockSpec((tm, tn), dy_map)],
            out_specs=pl.BlockSpec((None, tk, tn), o_map)),
        compiler_params=_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
        cost_estimate=_cost(
            rows, k, n, (x.shape, x.dtype), (dy.shape, dy.dtype),
            ((groups, k, n), jnp.float32)) if declare else None,
    )(group, tile, offsets, counts, x, dy)


# One jitted call each, as the other kernels have and for their reason: a
# Program's gradient op lowers its forward op again, and XLA merges the two
# custom calls only if they are the same call.
_gmm_call = jax.jit(_gmm_pallas, static_argnums=(3,))
_tgmm_call = jax.jit(_tgmm_pallas, static_argnums=(3,))


def _check(name, x, sizes, k, n):
    require_pallas(name)
    if not grouped_matmul_viable(k, n, x.dtype):
        raise ValueError(
            f"{name}: widths {k} and {n} in {x.dtype}: needs whole "
            f"{LANE}-lane slices of bfloat16 or float32")
    if sizes.ndim != 1 or not jnp.issubdtype(sizes.dtype, jnp.integer):
        raise ValueError(f"{name}: sizes {sizes.shape} {sizes.dtype}: "
                         "expected the groups' row counts, [G] integers")


def _whole_tiles(name, tiling, k, n):
    """A caller's tile sizes cut both widths into whole tiles (`_tiling`'s
    do by construction)."""
    _, tk, tn = tiling
    if k % tk or n % tn:
        raise ValueError(f"{name}: tiles of {tk} and {tn} do not divide "
                         f"the widths {k} and {n}")
    return tuple(tiling)


def moe_gmm(x, w, sizes, *, tiling=None, transpose_rhs=False,
            out_dtype=jnp.float32):
    """x: [rows, C], sorted by group; w: [G, C, O] in x's dtype; sizes: [G]
    integers, the rows of each group, `sum(sizes) <= rows`. Returns
    [rows, O] in `out_dtype`, summed in float32 and rounded once: row r of
    group g is `x[r] w[g]`, a row past the groups zeros, whatever x holds
    there. With `transpose_rhs` (static) w is [G, O, C] and row r is
    `x[r] w[g]^T`: the weights are read as they lie, a `[to, tc]` block
    contracted over its second axis, and no transposed copy is made."""
    rows, c = x.shape
    w_c, o = w.shape[1:][::-1] if transpose_rhs else w.shape[1:]
    _check("moe_gmm", x, sizes, c, o)
    if w.dtype != x.dtype or w.shape[0] != sizes.shape[0] or c != w_c:
        raise ValueError(f"moe_gmm: x {x.shape} {x.dtype}, w {w.shape} "
                         f"{w.dtype}, sizes {sizes.shape}")
    size, out = x.dtype.itemsize, jnp.dtype(out_dtype)
    tiling = _whole_tiles("moe_gmm", tiling or _tiling(
        rows, c, o, lambda tm, tc, to: (  # x, the weights, out, the sums
            2 * (tm * tc + tc * to) * size
            + (2 * out.itemsize + 4) * tm * to)), c, o)
    return _gmm_call(x, w, sizes,
                     (tiling, _interpret(), bool(transpose_rhs), out))


def moe_tgmm(x, dy, sizes, *, tiling=None, name="moe_tgmm", declare=True):
    """x: [rows, K] and dy: [rows, N], of one dtype, sorted by group;
    sizes as `moe_gmm`'s. Returns [G, K, N] float32: `x^T dy` over the
    rows of each group, zeros for a group with none; rows past the groups
    are not read into any sum. A float32 `dy` may come with a bfloat16
    `x`: the kernel cuts each tile of it into three bfloat16 slices whose
    sum it is (`_bf16_slices`) and adds the three products, so `dy` enters
    at full precision in three passes of the MXU (its own float32 product
    takes one, of operands rounded to bfloat16: 0.02 off at unit values on
    the v5e, PERF.md, PR 69). A caller of another layer
    (`embedding_grad.py`) gives the `name` its events carry, so that the
    expert layer's metrics do not count them, and says whether the call
    declares its cost."""
    rows, k = x.shape
    n = dy.shape[1]
    _check(name, x, sizes, k, n)
    sliced = (x.dtype, dy.dtype) == (jnp.bfloat16, jnp.float32)
    if (dy.dtype != x.dtype and not sliced) or dy.shape[0] != rows:
        raise ValueError(f"{name}: x {x.shape} {x.dtype}, dy {dy.shape} "
                         f"{dy.dtype}")
    tiling = _whole_tiles(name, tiling or _tiling(
        rows, k, n, lambda tm, tk, tn: (  # x and dy, the float32 gradient
            2 * tm * (tk * x.dtype.itemsize + tn * dy.dtype.itemsize)
            + 2 * 4 * tk * tn)), k, n)
    return _tgmm_call(x, dy, sizes, (tiling, _interpret(), name, declare))


@jax.custom_vjp
def grouped_matmul(x, w, sizes):
    """The differentiable grouped product of a block of sorted rows. x:
    [rows, K] in the compute dtype; w: [G, K, N] in any float dtype, cast
    to x's inside; sizes: [G] integers, `sum(sizes) <= rows`. Returns
    [rows, N] float32, zeros in the rows past the groups. The gradients,
    each from the cotangent read in x's dtype: dw in w's dtype (summed in
    float32), which neither operand's rows past the groups reach; dx in
    x's dtype, `moe_gmm` on the weights read transposed, zeros in the rows
    past the groups whatever the cotangent holds there."""
    return moe_gmm(x, w.astype(x.dtype), sizes)


def _grouped_matmul_fwd(x, w, sizes):
    # the weights as they came: their cast is made again in the backward
    return grouped_matmul(x, w, sizes), (x, w, sizes)


def _grouped_matmul_bwd(res, dy):
    x, w, sizes = res
    dy = dy.astype(x.dtype)
    # tied to the cotangent, as `jax.checkpoint` ties the plain path's: XLA
    # would else take the forward's cast of the weights for this one too
    # and hold it from forward to backward
    w, dy = jax.lax.optimization_barrier((w, dy))
    dx = moe_gmm(dy, w.astype(x.dtype), sizes, transpose_rhs=True,
                 out_dtype=x.dtype)
    return dx, moe_tgmm(x, dy, sizes).astype(w.dtype), None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
