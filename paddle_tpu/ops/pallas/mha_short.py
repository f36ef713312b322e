"""Short-sequence attention on the layout the projections write.

Q, K, V and the output are `[b, s, heads*dh]`: the array the `fc` before
attention writes and the `fc` after it reads. XLA's own lowering wants its
batched products head-major and relays every operand out, and the context
back, with standalone copies (a third of the transformer's device time);
a kernel whose operands are head-major keeps those copies and pins them.
Here the heads are taken inside the kernel, on 128-lane boundaries, so
there is nothing to relay on either side of the call.

One grid step holds one 128-lane slice of a few batch rows, `[bb, s, 128]`
of each operand (whole tiles of the array's HBM layout, so the DMA moves
4 KB runs), and the whole score rows of its heads: they fit VMEM, so
softmax needs no online rescaling and the backward is one kernel. The
heads of a slice (two at dh=64, one at dh=128) share every product: their
queries are stacked along the rows with the other head's lanes zeroed, so
that a contraction over all 128 lanes adds exact zeros (a v5e MXU is 128
deep either way), and each head's lanes of the stacked result are selected
back.

Mathematics as `_xla_attention`, at no lower precision: products
accumulate in float32; scale, key bias `[b, sk]`, the bottom-right-aligned
causal mask and softmax in float32 (the exponential as 2**x, with log2(e)
folded into the scale); the softmax's numerators (the forward) and the
probabilities and dS (the backward) cast to the input dtype for their
products.
What the score tile `[hp*sq, sk]` costs the vector units is kept off it
where something narrower can carry it: the rows' 1/l comes from the
approximate reciprocal and two Newton steps (a float32 division without
its special cases, on a column that fills a register every eight rows),
the forward normalises its `[rows, 128]` result and not the tile, as
`flash_attention.py` does, and the backward's constants multiply its
products' float32 results.

Dropout comes from a hash of (seed, batch row, 128-lane slice, row of the
stacked tile's upper half, key): one finalised word for the two scores a
half apart in the rows (at two heads a slice, the same query and key of
the slice's two heads), the second draw being the word times an odd
constant. The mask is regenerated and never stored, the same in the
forward and the backward, whatever the block and the mesh. The backward
recomputes the probabilities, row maxima and sums included, from Q and K:
its residuals are the operands, and no `[b, h, sq, sk]` array reaches HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import cost, on_mesh
from .flash_attention import LANE, NEG_INF, _ceil_to, _interpret, require_pallas

# The longest sequence that takes this kernel. Set from chip runs of the
# one-chip cells, each with the kernel against XLA's lowering (PERF.md,
# Findings, PR 25): a length is inside only if its cell gained.
MAX_SHORT_SEQ = 512

LOG2E = 1.4426950408889634
_VMEM_LIMIT = 64 << 20  # of v5e's 128 MiB; the default is 16 MiB
_VMEM_BUDGET = 24 << 20  # what _pick_bb counts: blocks and score tiles


def mha_short_viable(sq, sk, num_heads, head_dim):
    """The shapes the kernel is built for: heads that tile 128 lanes, and
    score rows short enough that a few batch rows of them fit VMEM."""
    return (head_dim in (64, 128) and (num_heads * head_dim) % LANE == 0
            and max(sq, sk) <= MAX_SHORT_SEQ)


def _u32(x):
    return jax.lax.convert_element_type(x, jnp.uint32)


def _keep3(seed, slab, rk, dropout):
    """Hash keep-mask over [bb, rows, sk] at one finalised word for two
    scores: murmur's finaliser (flash_attention._dropout_keep's generator)
    over [bb, rows/2, sk], from `rk`, the row and key part of the hash
    [rows/2, sk], and `slab`, each batch row's global index of (batch row,
    128-lane slice). Rows r and r + rows/2 of the stacked tile share the
    word: the upper half compares it with the threshold, the lower half
    its product with an odd constant. That is a bijection of the 32-bit
    words, so each draw keeps at the one rate 1 - int(p * 2**32) / 2**32;
    and (h, a*h mod 2**32) is a point of the lattice of a multiplicative
    generator with a good multiplier, so the pair fills the unit square
    evenly (tests/test_mha_short.py counts the four joint outcomes)."""
    h = rk ^ (_u32(seed) + slab * jnp.uint32(0xC2B2AE35))
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    words = jnp.concatenate([h, h * jnp.uint32(0x9E3779B1)], axis=1)
    return words >= jnp.uint32(min(int(dropout * 2**32), 2**32 - 1))


# batched dot shorthands over a block's batch rows; all accumulate float32
def _bdot_qkT(a, b):  # [B, m, d] x [B, n, d] -> [B, m, n]
    return jax.lax.dot_general(
        a, b, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )


def _bdot_pv(p, v):  # [B, m, n] x [B, n, d] -> [B, m, d]
    return jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )


def _bdot_pTv(p, v):  # [B, n, m] x [B, n, d] -> [B, m, d]
    return jax.lax.dot_general(
        p, v, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )


def _segment(shape, axis, size, n):
    """Index // size along `axis`, for n segments, by comparisons (Mosaic
    has no vector integer division)."""
    idx = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    seg = jnp.zeros(shape, jnp.int32)
    for h in range(1, n):
        seg = seg + (idx >= h * size).astype(jnp.int32)
    return idx, seg


def _stack_heads(x, hp):
    """[bb, s, 128] -> [bb, hp*s, 128]: one copy of the slice per head in
    it, the other heads' lanes zeroed."""
    if hp == 1:
        return x
    _, head = _segment((1, *x.shape[1:]), 2, LANE // hp, hp)  # over the batch
    return jnp.concatenate(
        [jnp.where(head == h, x, jnp.zeros((), x.dtype)) for h in range(hp)],
        axis=1)


def _unstack_heads(y, hp):
    """[bb, hp*s, 128] -> [bb, s, 128]: head h's lanes from its rows."""
    if hp == 1:
        return y
    s = y.shape[1] // hp
    _, head = _segment((1, s, LANE), 2, LANE // hp, hp)
    out = y[:, :s]
    for h in range(1, hp):
        out = jnp.where(head == h, y[:, h * s:(h + 1) * s], out)
    return out


def _scores(q_ref, k_ref, bias_ref, *, hp, sm_scale, causal, causal_offset):
    """One 128-lane slice of a block: the stacked heads' queries
    [bb, hp*sq, 128] and their scores [bb, hp*sq, sk] in float32, scaled,
    biased and masked."""
    sq, sk = q_ref.shape[1], k_ref.shape[1]
    qs = _stack_heads(q_ref[...], hp)
    s = _bdot_qkT(qs, k_ref[...]) * (sm_scale * LOG2E)
    if bias_ref is not None:
        s = s + bias_ref[...] * LOG2E  # [bb, 1, sk] over the rows
    if causal:
        # what depends on the row and the key alone is computed once,
        # [hp*sq, sk], and broadcast over the block's batch rows
        row, sub = _segment((hp * sq, sk), 0, sq, hp)  # sub: head in slice
        ki = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
        s = s + jnp.where(row - sub * sq + causal_offset >= ki, 0.0, NEG_INF)
    return qs, s


def _keep(seed_ref, shape, num_heads, hp, dropout):
    """The dropout keep-mask of a block's score tile `shape`."""
    bb, rows, sk = shape
    # seed_ref[1]: the global index of the call's first batch row, so that
    # the shards of a mesh draw the masks of the whole batch
    bi = seed_ref[1] + pl.program_id(0) * bb + jax.lax.broadcasted_iota(
        jnp.int32, (bb, 1, sk), 0)
    slab = _u32(bi * (num_heads // hp) + pl.program_id(1))
    half = (rows // 2, sk)
    rk = (_u32(jax.lax.broadcasted_iota(jnp.int32, half, 0))
          * jnp.uint32(0x9E3779B1)
          ^ _u32(jax.lax.broadcasted_iota(jnp.int32, half, 1))
          * jnp.uint32(0x85EBCA6B))
    return _keep3(seed_ref[0], slab, rk, dropout)


def _softmax(s):
    """The rows' softmax of the scores as its float32 numerator e
    [bb, rows, sk] and the rows' factor 1/l [bb, rows, 1]."""
    # m is clamped so that a fully masked row underflows to e == 0 and not
    # to the uniform 2**(NEG_INF - NEG_INF); partly masked entries
    # underflow by themselves
    m = jnp.maximum(jnp.max(s, axis=2, keepdims=True), NEG_INF / 8)
    e = jnp.exp2(s - m)  # `_scores` gave the scores in units of log 2
    # l is 1 (the row's maximum) to sk, or 0 on a fully masked row, whose
    # e are all 0 and take any finite factor
    l = jnp.maximum(jnp.sum(e, axis=2, keepdims=True), 1e-30)
    # A float32 division is the chip's approximate reciprocal, one Newton
    # step and a division's special cases (LLO's own lowering), a dozen
    # operations on a column that holds eight rows a register; l has no
    # special case. Two steps: the chip's instruction gives 12 bits or
    # more and the interpreter's model of it (a bfloat16 reciprocal) 8,
    # and either squared twice is past float32's 24
    rl = pl.reciprocal(l, approx=True)
    for _ in range(2):
        rl = rl * (2.0 - l * rl)
    return e, rl


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, *rest, has_bias, num_heads,
                hp, dropout, **static):
    bias_ref, o_ref = rest if has_bias else (None, *rest)
    _, s = _scores(q_ref, k_ref, bias_ref, hp=hp, **static)
    e, rl = _softmax(s)
    if dropout > 0.0:
        e = jnp.where(_keep(seed_ref, s.shape, num_heads, hp, dropout), e, 0.0)
    # normalised after the product, as flash_attention.py does: the rows'
    # factor meets the [rows, 128] result and never the score tile
    o = _bdot_pv(e.astype(v_ref.dtype), v_ref[...]) * (
        rl * (1.0 / (1.0 - dropout)))
    o_ref[...] = _unstack_heads(o, hp).astype(o_ref.dtype)


def _bwd_kernel(seed_ref, q_ref, k_ref, v_ref, *rest, has_bias, num_heads,
                hp, dropout, **static):
    bias_ref, do_ref, dq_ref, dk_ref, dv_ref = (
        rest if has_bias else (None, *rest))
    qs, s = _scores(q_ref, k_ref, bias_ref, hp=hp, **static)
    e, rl = _softmax(s)
    p = e * rl
    dos = _stack_heads(do_ref[...], hp)
    dp = _bdot_qkT(dos, v_ref[...])
    p_drop = p
    if dropout > 0.0:
        keep = _keep(seed_ref, s.shape, num_heads, hp, dropout)
        p_drop, dp = jnp.where(keep, p, 0.0), jnp.where(keep, dp, 0.0)
    # 1/(1-rate) and the scale multiply the products' float32 results and
    # not the score tile: with c = 1/(1-rate) and delta = rowsum(p * dp)
    # over the kept pairs, dV = c * p_drop^T.dO and dS = c * scale * p *
    # (dp - delta).
    # The stacked rows of each head carry zeros in the other head's lanes,
    # so the products over the rows give dV and dK whole
    c = 1.0 / (1.0 - dropout)
    dv_ref[...] = (_bdot_pTv(p_drop.astype(dos.dtype), dos) * c).astype(
        dv_ref.dtype)
    delta = jnp.sum(p * dp, axis=2, keepdims=True)
    ds = (p * (dp - delta)).astype(qs.dtype)
    c = c * static["sm_scale"]
    dq_ref[...] = _unstack_heads(_bdot_pv(ds, k_ref[...]) * c, hp).astype(
        dq_ref.dtype)
    dk_ref[...] = (_bdot_pTv(ds, qs) * c).astype(dk_ref.dtype)


def _pick_bb(b, sq, sk, hp, itemsize):
    """Largest divisor of b whose grid step fits the VMEM budget: the
    backward's seven double-buffered [bb, s, 128] blocks and about eight
    live float32 score tiles of the hp stacked heads."""
    per_row = 2 * 7 * max(sq, sk) * LANE * itemsize + 8 * hp * sq * sk * 4
    cap = max(1, _VMEM_BUDGET // per_row)
    return max(d for d in range(1, min(b, cap) + 1) if b % d == 0)


def _cost(b, sq, sk, num_heads, width, dtype, *, causal, causal_offset,
          has_bias, backward):
    """What one call declares (`cost.py` has the convention), for `b`
    batch rows of unpadded lengths `sq`, `sk`. Products counted a pair the
    mask admits, a head, over the head's `dh` lanes: forward q.k and p.v
    (4 FLOPs a pair a lane); backward q.k again (its residuals are the
    operands), dO.v, p^T.dO, dS.k and dS^T.q (10). The stacked heads'
    products over the other head's zeroed lanes are not counted. One
    exponential a pair and one reciprocal a row."""
    pairs = b * num_heads * cost.admitted_pairs(
        sq, sk, causal=causal, causal_offset=causal_offset)
    rows, keys = ((b, sq, width), dtype), ((b, sk, width), dtype)
    return cost.estimate(
        (10 if backward else 4) * pairs * (width // num_heads),
        pairs + b * num_heads * sq,
        rows, keys, keys, *([((b, sk), jnp.float32)] if has_bias else []),
        # forward: the output; backward: dO in, dq, dk, dv out
        *([rows, rows, keys, keys] if backward else [rows]))


@functools.partial(jax.jit, static_argnames=("statics",))
def _call(seed, q, k, v, bias, do, *, statics):
    """One pallas_call, the forward without `do` and the backward with it,
    over a grid of (batch blocks, 128-lane slices): [bb, s, 128] blocks of
    every operand, which the array's (sublanes, 128) tiling keeps as whole
    tiles in HBM; the seed and the first row's global index in SMEM, the
    bias as [b, 1, sk]. On a `mesh` (on_mesh.batch_shards said so) each
    shard of `batch` makes the call on its rows. Jitted so that the calls
    of one shape in a step (twelve in BERT) are traced and lowered once."""
    (num_heads, sm_scale, causal, (sq, sk), dropout, bb, interpret,
     mesh) = statics
    causal_offset = sk - sq  # of the lengths before padding
    backward = do is not None
    has_bias = bias is not None

    def run(seed, *args):
        q = args[0]

        def spec(x, is_bias=False):
            if is_bias:
                return pl.BlockSpec((bb, *x.shape[1:]), lambda i, j: (i, 0, 0),
                                    memory_space=pltpu.VMEM)
            return pl.BlockSpec((bb, x.shape[1], LANE), lambda i, j: (i, 0, j),
                                memory_space=pltpu.VMEM)

        row0 = on_mesh.first_row(mesh, q.shape[0])
        outs = args[:3] if backward else args[:1]
        return pl.pallas_call(
            functools.partial(
                _bwd_kernel if backward else _fwd_kernel,
                has_bias=has_bias, num_heads=num_heads,
                hp=LANE * num_heads // q.shape[2], sm_scale=sm_scale,
                causal=causal, causal_offset=causal_offset, dropout=dropout),
            grid=(q.shape[0] // bb, q.shape[2] // LANE),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
            + [spec(x, has_bias and i == 3) for i, x in enumerate(args)],
            out_specs=[spec(x) for x in outs],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in outs],
            compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
            name="mha_short_bwd" if backward else "mha_short_fwd",
            # per shard on a mesh: the rows this call holds
            cost_estimate=_cost(
                q.shape[0], sq, sk, num_heads, q.shape[2], q.dtype,
                causal=causal, causal_offset=causal_offset,
                has_bias=has_bias, backward=backward),
        )(jnp.stack([seed[0], jnp.int32(row0)]), *args)

    args = [x for x in (q, k, v, bias, do) if x is not None]
    return on_mesh.per_shard(run, mesh, [False] + [True] * len(args))(
        seed, *args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _core(q, k, v, bias, seed, statics):
    return _call(seed, q, k, v, bias, None, statics=statics)[0]


def _core_fwd(q, k, v, bias, seed, statics):
    # the output is no residual: the backward recomputes what it needs
    out, = _call(seed, q, k, v, bias, None, statics=statics)
    return out, (q, k, v, bias, seed)


def _core_bwd(statics, res, do):
    q, k, v, bias, seed = res
    dq, dk, dv = _call(seed, q, k, v, bias, do, statics=statics)
    dbias = None if bias is None else jnp.zeros_like(bias)
    return dq, dk, dv, dbias, np.zeros((1,), dtype=jax.dtypes.float0)


_core.defvjp(_core_fwd, _core_bwd)


def mha_short(q, k, v, num_heads, bias=None, causal=False, sm_scale=None,
              dropout=0.0, rng_key=None, mesh=None):
    """Fused multi-head attention for short sequences. q: [b, sq, h*dh];
    k, v: [b, sk, h*dh]; bias: [b, sk] additive key bias or None. Returns
    [b, sq, h*dh] in q's dtype. `mesh`: a mesh whose `batch` axis alone
    shards the rows and divides b; the kernels then run per shard, with
    the dropout masks of the one-device call on the whole batch."""
    require_pallas("mha_short")
    b, sq, width = q.shape
    sk = k.shape[1]
    dh = width // num_heads
    if not mha_short_viable(sq, sk, num_heads, dh) or dh * num_heads != width:
        raise ValueError(
            f"mha_short: q {q.shape}, k {k.shape}, {num_heads} heads: needs "
            f"head_dim 64 or 128, heads*head_dim a multiple of {LANE} and "
            f"sequences up to {MAX_SHORT_SEQ}")
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(dh))
    if dropout > 0.0 and rng_key is None:
        raise ValueError("dropout requires rng_key")
    if dropout > 0.0:
        seed = jax.random.randint(
            rng_key, (1,), 0, np.iinfo(np.int32).max, jnp.int32)
    else:
        seed = jnp.zeros((1,), jnp.int32)

    # rows are stacked and sliced by whole sublane tiles (16 in bfloat16):
    # other lengths are padded, queries with rows that are cut off again,
    # keys with positions the bias masks
    sqp, skp = _ceil_to(sq, 16), _ceil_to(sk, 16)
    if sqp != sq:
        q = jnp.pad(q, [(0, 0), (0, sqp - sq), (0, 0)])
    if skp != sk:
        k = jnp.pad(k, [(0, 0), (0, skp - sk), (0, 0)])
        v = jnp.pad(v, [(0, 0), (0, skp - sk), (0, 0)])
        bias = jnp.zeros((b, sk), jnp.float32) if bias is None else bias
    if bias is not None:
        bias = jnp.pad(bias.astype(jnp.float32), [(0, 0), (0, skp - sk)],
                       constant_values=NEG_INF)[:, None, :]
    # the block is a rule of what one chip holds: its rows of the batch
    shards = on_mesh.batch_shards(mesh, b)
    if not shards:
        raise ValueError(
            f"mha_short: a batch of {b} on {mesh}: the mesh has to shard "
            "`batch` alone and divide the batch")
    bb = _pick_bb(b // shards, sqp, skp, LANE // dh, q.dtype.itemsize)
    statics = (num_heads, float(sm_scale), bool(causal), (sq, sk),
               float(dropout), bb, _interpret(), mesh)
    return _core(q, k, v, bias, seed, statics)[:, :sq]
