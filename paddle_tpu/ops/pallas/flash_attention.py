"""Flash attention as a Pallas TPU kernel (fwd + custom-vjp bwd).

TPU-native replacement for the reference's unfused attention math
(matmul -> softmax .cu kernel -> matmul; e.g. paddle/fluid/operators/
softmax_op.cu + matmul_op; Fluid has no fused attention at this vintage) —
designed MXU/VMEM-first instead: blocked online-softmax so the [s, s]
score matrix never hits HBM, fp32 accumulation, optional in-kernel
dropout regenerated (not stored) in the backward pass.

Layout: q [b, h, sq, d], k [b, hkv, sk, d], v [b, hkv, sk, dv] with `h`
a multiple of `hkv` (grouped key/value heads: the K and V blocks of query
head `n` are indexed `n // group`, the backward kernels that write dk and
dv run over the key/value heads and sum over each one's group of query
heads, and K and V are never repeated), optional additive key bias
[b, sk] (the padding-mask case), `causal` flag, `window` (with `causal`:
the last `window` keys a query may see), `granule` (with `causal`: the
mask is causal by blocks of `granule` rows and full inside one, which
with an offset and a window gives block diffusion's three rectangles;
`cost.py` states the predicate once, for the scores masked, the blocks
visited and the pairs declared alike). Each head width is zero-padded
to a lane multiple (128) of its own: q, k, dq and dk travel at the keys'
padded width, v, the output, dO and dv at the values', so values narrower
than the keys (latent attention's 128 beside 192) cost P.V, dO.V^T and
P^T.dO no lane they do not fill, and values wider than the keys (a
differential head's 128 beside 64) cost q.k and dS.k none either.
Sequence dims are padded to block multiples with fully-masked keys. The
arrays are head-major: cutting a head's blocks
from the lanes of [b, s, heads*128] arrays as the projections write them
was built and measured at s=8,192 (PERF.md, PR 33): the strided blocks
cost the kernels 8% and the step as a whole ran 4.8% slower than with
the four transposes, which XLA folds into the relayouts it makes around
the per-head norms anyway.

The backward is one kernel, `flash_bwd_dkv_dq`, wherever a key/value
head's dk and dv fit VMEM (`_bwd_fused_viable`: from the padded key
length, the padded widths and the item size; every call of the
benchmark's cells, up to 16,384 keys at 128 and 128 lanes in bf16). It
walks a key/value head a row of the grid, under it the group's query
heads and their query blocks, under each the run of key blocks the band
admits, forms `p` and `dS` of a block of scores once, adds dS.k to the
query block's dq and P^T.dO and dS^T.q to the head's dv and dk, which
stay in VMEM in float32 until the head's last block is done: five
products a block. A call the rule refuses runs the pair `flash_bwd_dq`
and `flash_bwd_dkv`, which keep a block of dq, or of dk and dv, and form
every block's `p` and `dS` twice, seven products; ring attention's
chunks call the pair themselves (`_bwd_pallas`). The three share one
body for a block (`_block_backward`), and the fused call adds each
block's terms in the order the pair does, so it returns the pair's
dq, dk, dv to the bit, in the interpreter and on a v5e alike.

Blocks: the backward runs at 512 x 512 unless the caller names another
pair, and the sequence dims are padded to it. `flash_fwd` has a pair of
its own where the caller names none (`_fwd_blocks`: up to 1,024 x 1,024,
multiples of the backward's that divide the padded lengths and fit
VMEM): what bounds it is paid a row of a block, and the output and
log-sum-exp rows the backward reads do not depend on the blocks that
made them. The backward was swept too (`_bwd_fused_viable`'s
docstring): at 1,024 x 1,024 a call without a window ran 4 to 11%
shorter and every call with one longer, so it keeps 512 x 512.

What the grids skip: a block of scores in which the masks admit no pair
(above the causal diagonal, below the window's edge) is neither copied
nor computed, in `flash_fwd`, `flash_bwd_dkv_dq`, `flash_bwd_dq` and
`flash_bwd_dkv` alike (`_key_band`, `_query_band`). Inside a visited
block masked pairs are still computed and thrown away: with blocks of
512 the causal kernel visits 53% of the rectangle at 16 x 16 blocks
where the mask admits 50%, and a 2,048-key window on 8,192 tokens 27.3%
where it admits 21.9%; the forward at blocks of 1,024 56% and 32.8%.
Under a granule of 4 on 4,096 rows the clean copy's call visits the 36
blocks of the lower triangle, a noisy block's call on the clean past
the same 36 and its call on its own keys the diagonal's 8 (a visit
there holds 2,048 admitted pairs of 262,144).
Leaving the mask's arithmetic out of the blocks that lie wholly inside
the band was measured in `flash_bwd_dkv_dq` and gave nothing (10.95 and
11.04 ms at 8,192 causal tokens: PERF.md, PR 48). Without `causal`
every block is visited, as before.

Mosaic compiles the kernel on a TPU. PADDLE_TPU_PALLAS_INTERPRET=1 runs
it in the Pallas interpreter so CPU tests exercise the real kernel body;
asked for on any other backend without that variable, it raises.
"""

from __future__ import annotations

import functools
import os
import typing

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import profiler
from . import cost

NEG_INF = -1e30
LANE = 128


def _interpret():
    """Interpreter mode is the environment variable and nothing else."""
    return bool(os.environ.get("PADDLE_TPU_PALLAS_INTERPRET"))


def _use_pallas():
    """True where a Pallas kernel can run at all: compiled by Mosaic on a
    TPU, or interpreted. Dispatch reads this to choose a path."""
    return _interpret() or jax.default_backend() == "tpu"


def require_pallas(kernel: str) -> None:
    """A kernel called on a backend that cannot compile it raises; it is
    never swapped for other math behind the caller's back."""
    if not _use_pallas():
        raise RuntimeError(
            f"Pallas kernel {kernel!r} was asked for on the "
            f"{jax.default_backend()!r} backend: Mosaic compiles only for "
            "a TPU (PADDLE_TPU_PALLAS_INTERPRET=1 runs the interpreter)"
        )


def _ceil_to(x, m):
    return (x + m - 1) // m * m


def _dropout_keep(seed, bh_idx, q0, k0, shape, dropout):
    """Stateless keep-mask: a murmur-style integer hash of the *global*
    (batch*head, q index, k index, seed) coordinates, so the identical mask
    is regenerated in the backward kernels (never stored to HBM) and is
    independent of block-size choices. Portable across TPU and the
    interpreter, unlike pltpu.prng_*."""
    u32 = lambda x: jax.lax.convert_element_type(x, jnp.uint32)
    qi = u32(q0) + jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    ki = u32(k0) + jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    h = (
        qi * jnp.uint32(0x9E3779B1)
        ^ ki * jnp.uint32(0x85EBCA6B)
        ^ (u32(seed) + u32(bh_idx) * jnp.uint32(0xC2B2AE35))
    )
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    thresh = jnp.uint32(min(int(dropout * 2**32), 2**32 - 1))
    return h >= thresh


# ---------------------------------------------------------------------------
# which blocks a grid visits
# ---------------------------------------------------------------------------
#
# One predicate, "this [block_q, block_k] block of scores can hold a pair
# the masks admit", from `causal`, `causal_offset`, `window` and
# `granule`: `cost.py` states it (`admits`, and its readings along either
# axis, `first_key`, `last_key`, `first_query`, `last_query`). The last
# key a query sees does not fall as the query rises, nor the first, so
# the blocks a query block can see are a run of key blocks, and the
# blocks that can see a key block a run of query blocks: a band. The two
# functions below give the run's ends from a block's first and last row.
# Each kernel's innermost grid axis is as long as the longest run, a step
# past a run's end points at the run's last block again (the pipeline
# copies nothing when the block index stays) and computes nothing. `xp`
# is `jnp` inside an index map or a kernel and `np` where blocks are
# counted.


def _key_band(j, m, xp=jnp):
    """(first, last) key block of query block `j`."""
    first, last = 0 * j, 0 * j + (m.nk - 1)
    if m.window:
        lo = cost.first_key(j * m.block_q, *m.rule)
        first = xp.minimum(xp.maximum(lo, 0) // m.block_k, m.nk - 1)
    if m.causal:
        hi = cost.last_key((j + 1) * m.block_q - 1, m.causal_offset,
                           m.granule)
        last = xp.minimum(xp.maximum(hi, 0) // m.block_k, m.nk - 1)
    return first, xp.maximum(last, first)


def _query_band(kb, m, xp=jnp):
    """(first, last) query block of key block `kb`."""
    first, last = 0 * kb, 0 * kb + (m.nq - 1)
    if m.causal:
        lo = cost.first_query(kb * m.block_k, m.causal_offset, m.granule)
        first = xp.minimum(xp.maximum(lo, 0) // m.block_q, m.nq - 1)
    if m.window:
        hi = cost.last_query((kb + 1) * m.block_k - 1, *m.rule)
        last = xp.minimum(xp.maximum(hi, 0) // m.block_q, m.nq - 1)
    return first, xp.maximum(last, first)


class _Masks(typing.NamedTuple):
    """The masks of one call and the blocks of one of its grids: the
    forward builds its own at its blocks, the backward kernels share
    one at theirs. Static and hashable, so a jitted call takes it as a
    static argument."""

    causal: bool
    causal_offset: int
    window: int
    block_q: int
    block_k: int
    nq: int
    nk: int
    granule: int = 1

    @classmethod
    def of(cls, sq, sk, *, causal, causal_offset, window, block_q, block_k,
           granule=1):
        return cls(causal, causal_offset, window, block_q, block_k,
                   sq // block_q, sk // block_k, granule)

    @property
    def rule(self):
        """What `cost.py`'s predicate takes after the row."""
        return self.causal_offset, self.window, self.granule

    def key_steps(self):
        """Length of the key axis of `flash_fwd` and `flash_bwd_dq`."""
        first, last = _key_band(np.arange(self.nq), self, np)
        return int((last - first).max()) + 1

    def query_steps(self):
        """Query blocks a key block's run holds at most (`flash_bwd_dkv`)."""
        first, last = _query_band(np.arange(self.nk), self, np)
        return int((last - first).max()) + 1

    def at(self, block_q, block_k):
        """The same masks over the same rows at other blocks."""
        return self.of(self.nq * self.block_q, self.nk * self.block_k,
                       causal=self.causal, causal_offset=self.causal_offset,
                       window=self.window, block_q=block_q, block_k=block_k,
                       granule=self.granule)

    def visited(self, fwd_blocks=None, fused=False):
        """Blocks one head's grids compute, and the rectangles', in units
        of this, the backward's, block: score area. `fwd_blocks`: the
        forward's where they are multiples of these; one of its blocks
        counts as the blocks of this size it covers. `fused`: the
        backward is one grid, `flash_bwd_dkv_dq`'s, and not two."""
        def run(band, n, m):
            first, last = band(np.arange(n), m, np)
            return int((last - first + 1).sum())

        fwd = self.at(*fwd_blocks) if fwd_blocks else self
        covers = (fwd.block_q // self.block_q) * (fwd.block_k // self.block_k)
        bwd = run(_key_band, self.nq, self)
        if not fused:
            bwd += run(_query_band, self.nk, self)
        return (covers * run(_key_band, fwd.nq, fwd) + bwd,
                (2 if fused else 3) * self.nq * self.nk)


def _block_of(band, i, t, m):
    """The `t`-th block of `i`'s run, or the run's last block past its
    end: the index stays, so the pipeline copies nothing."""
    first, last = band(i, m)
    return jnp.minimum(first + t, last)


def _admitted(s, j, kb, m, admit_ref=None):
    """The scores of block (j, kb) with what the masks refuse at NEG_INF.
    `admit_ref`: the block of a call's admission operand ([1, block_q,
    block_k] int8, one for all the heads of a batch row), whose zeros
    are refused too."""
    if admit_ref is not None:
        s = jnp.where(admit_ref[0].astype(jnp.int32) != 0, s, NEG_INF)
    if not m.causal:
        return s
    qi = j * m.block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    ki = kb * m.block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(cost.admits(qi, ki, *m.rule), s, NEG_INF)


def _head_maps(group):
    """Block index maps over [heads over the batch, s, d] arrays: a query
    head's block, a key/value head's, and the key/value block that query
    head `n` reads, which is head `n // group`'s."""
    q_at = lambda n, j: (n, j, 0)
    kv_at = lambda i, kb: (i, kb, 0)
    return q_at, kv_at, (lambda n, kb: (n // group, kb, 0))


# ---------------------------------------------------------------------------
# what a call declares
# ---------------------------------------------------------------------------

# products a pair the masks admit, as (over the keys' lanes, over the
# values' lanes): `flash_fwd` q.k and p.v; `flash_bwd_dq` q.k again, dS.k
# and dO.v; `flash_bwd_dkv` q.k, dS^T.q and dO.v, p^T.dO; the one-visit
# backward `flash_bwd_dkv_dq` q.k, dS.k, dS^T.q and dO.v, p^T.dO. At one
# head width that is 4, 6, 8 and 10 FLOPs a pair a lane: the pair
# declares 14 where the mathematics needs the fused call's 10.
_PRODUCTS = {"flash_fwd": (1, 1), "flash_bwd_dq": (2, 1),
             "flash_bwd_dkv": (2, 2), "flash_bwd_dkv_dq": (3, 2)}


def _cost(kernel, q, k, bias, masks, dims, admit=None, admit_keys=0):
    """What the call named `kernel` declares (`cost.py` has the
    convention) from the flattened, padded operands `q` [b*h, ., .] and
    `k` [b*hkv, ., .] and `dims`, the lengths and head widths before any
    padding, `(sq, sk, d, dv)` (None: the arrays' own). FLOPs follow the
    pairs `causal`, `window` and `granule` admit, so a window counts fewer than its
    causal twin by the arithmetic and not by block rounding; masked pairs
    inside a visited block and the padded lanes do not count. One
    exponential a pair; `flash_fwd` a reciprocal and a logarithm a row.
    With an admission operand `admit` ([b, sq, sk] int8) the caller says
    how many keys a query admits at most (`admit_keys`, what a selection
    of the K largest leaves: min(visible, K) a query, which is a window's
    count), and the operand's bytes count once."""
    bh, bhkv = q.shape[0], k.shape[0]
    sq, sk, d, dv = dims or (q.shape[1], k.shape[1], q.shape[2], q.shape[2])
    # the fewest keys either bound leaves a query (0: that bound is absent)
    bounds = [n for n in (masks.window, admit_keys) if n]
    pairs = bh * cost.admitted_pairs(
        sq, sk, causal=masks.causal, causal_offset=masks.causal_offset,
        window=min(bounds, default=0), granule=masks.granule)
    over_d, over_dv = _PRODUCTS[kernel]
    queries, outputs = ((bh, sq, d), q.dtype), ((bh, sq, dv), q.dtype)
    keys, values = ((bhkv, sk, d), k.dtype), ((bhkv, sk, dv), k.dtype)
    row = ((bh, sq), jnp.float32)  # log-sum-exp, delta
    moved = {"flash_fwd": [outputs, row],  # written
             "flash_bwd_dq": [outputs, row, row, queries],  # dO ... dq
             "flash_bwd_dkv": [outputs, row, row, keys, values],
             "flash_bwd_dkv_dq": [outputs, row, row, queries, keys,
                                  values]}[kernel]
    if bias is not None:
        moved.append(((bias.shape[0], sk), bias.dtype))
    if admit is not None:
        moved.append(((admit.shape[0], sq, sk), admit.dtype))
    return cost.estimate(
        2 * pairs * (over_d * d + over_dv * dv),
        pairs + (2 * bh * sq if kernel == "flash_fwd" else 0),
        queries, keys, values, *moved)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    seed_ref,
    q_ref,
    k_ref,
    v_ref,
    *refs,
    sm_scale,
    dropout,
    masks,
    steps,
    operands=(True, False),
):
    # `operands`: whether the call passes a key bias, an admission
    (bias_ref, admit_ref), (o_ref, lse_ref, m_scr, l_scr, acc_scr) = (
        _optional(refs, operands))
    n = pl.program_id(0)  # read here: the interpreter has none in a branch
    j = pl.program_id(1)
    t = pl.program_id(2)
    first, last = _key_band(j, masks)
    kb = first + t
    block_q, block_k = masks.block_q, masks.block_k

    @pl.when(t == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _visit():
        # dots run in the input dtype (bf16 on the MXU) accumulating fp32;
        # only the softmax math stays fp32
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * sm_scale
        if bias_ref is not None:
            s = s + bias_ref[0, 0].astype(jnp.float32)[None, :]
        s = _admitted(s, j, kb, masks, admit_ref)

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)

        if dropout > 0.0:
            keep = _dropout_keep(
                seed_ref[0], n, j * block_q, kb * block_k, p.shape, dropout,
            )
            p_use = jnp.where(keep, p / (1.0 - dropout), 0.0)
        else:
            p_use = p

        v = v_ref[0]
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p_use.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if masks.causal:
        pl.when(kb <= last)(_visit)
    else:
        _visit()

    @pl.when(t == steps - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[:, 0] + jnp.log(l_safe[:, 0])).astype(jnp.float32)


def _optional(refs, present):
    """A kernel's references after its fixed ones: the optional operands
    in order (None where `present` says the call passes none), then the
    rest."""
    refs = list(refs)
    return [refs.pop(0) if there else None for there in present], refs


def _fwd_pallas(q, k, v, bias, seed, h, admit=None, *, sm_scale, causal,
                causal_offset, dropout, block_q, block_k, window=0,
                dims=None, admit_keys=0, granule=1):
    """q: [b*h, sq, d], k: [b*hkv, sk, d] and v: [b*hkv, sk, dv], whole
    blocks and whole lanes; `admit`: [b, sq, sk] int8 or None; `dims` and
    `admit_keys`: what `_cost` reads. Returns the
    output, [b*h, sq, dv] in q's dtype, and the log-sum-exp rows,
    [b*h, 1, sq] float32."""
    bh, sq, d = q.shape
    sk, dv = k.shape[1], v.shape[2]
    masks = _Masks.of(sq, sk, causal=causal, causal_offset=causal_offset,
                      window=window, block_q=block_q, block_k=block_k,
                      granule=granule)
    steps = masks.key_steps()
    q_at, _, k_at = _head_maps(bh // k.shape[0])

    qspec = lambda n, j, t: q_at(n, j)
    kspec = lambda n, j, t: k_at(n, _block_of(_key_band, j, t, masks))

    bias_spec = []
    bias_args = []
    if bias is not None:
        # bias is [b, 1, sk]: 3-D so the block's trailing dims obey the
        # (8, 128) tiling rule (middle dim 1 == array dim)
        bias_spec = [
            pl.BlockSpec(
                (1, 1, block_k),
                lambda n, j, t: (n // h, 0,
                                 _block_of(_key_band, j, t, masks)),
                memory_space=pltpu.VMEM,
            )
        ]
        bias_args = [bias]
    if admit is not None:
        # one block of pairs for all the heads of a batch row
        bias_spec.append(pl.BlockSpec(
            (1, block_q, block_k),
            lambda n, j, t: (n // h, j, _block_of(_key_band, j, t, masks)),
            memory_space=pltpu.VMEM))
        bias_args.append(admit)

    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale,
        dropout=dropout,
        masks=masks,
        steps=steps,
        operands=(bias is not None, admit is not None),
    )

    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, masks.nq, steps),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # seed
            pl.BlockSpec((1, block_q, d), qspec, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kspec, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dv), kspec, memory_space=pltpu.VMEM),
            *bias_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), qspec, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), lambda n, j, t: (n, 0, j), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANE), jnp.float32),
            pltpu.VMEM((block_q, LANE), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_fwd",
        cost_estimate=_cost("flash_fwd", q, k, bias, masks, dims, admit,
                            admit_keys),
    )(seed, q, k, v, *bias_args)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dot_f32(a, b, contract):
    """a . b over `contract` = (a's axis, b's axis), accumulated float32."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.float32)


def _block_backward(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, bias_ref, admit_ref, head, j, kb, *, sm_scale,
                    dropout, masks):
    """What every backward kernel forms of block (j, kb) of query head
    `head` (its index over the batch, which the dropout mask hashes):
    q, k, dO, `p` as P^T.dO takes it (with the dropout the forward
    applied) and `dS`, both float32 [block_q, block_k]."""
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, 0].astype(jnp.float32)[:, None]
    delta = delta_ref[0, 0].astype(jnp.float32)[:, None]

    s = _dot_f32(q, k, (1, 1)) * sm_scale
    if bias_ref is not None:
        s = s + bias_ref[0, 0].astype(jnp.float32)[None, :]
    s = _admitted(s, j, kb, masks, admit_ref)
    p = jnp.exp(s - lse)  # normalized probs (fp32)

    dp = _dot_f32(do, v, (1, 1))
    p_drop = p
    if dropout > 0.0:
        keep = _dropout_keep(
            seed_ref[0], head, j * masks.block_q, kb * masks.block_k,
            dp.shape, dropout,
        )
        p_drop = jnp.where(keep, p / (1.0 - dropout), 0.0)
        dp = jnp.where(keep, dp / (1.0 - dropout), 0.0)
    ds = p * (dp - delta) * sm_scale
    return q, k, do, p_drop, ds


def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   *refs, steps, masks, operands, **kw):
    (bias_ref, admit_ref), (dq_ref, dq_scr) = _optional(refs, operands)
    n = pl.program_id(0)
    j = pl.program_id(1)
    t = pl.program_id(2)
    first, last = _key_band(j, masks)
    kb = first + t

    @pl.when(t == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _visit():
        _, k, _, _, ds = _block_backward(
            seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            bias_ref, admit_ref, n, j, kb, masks=masks, **kw)
        dq_scr[:] = dq_scr[:] + _dot_f32(ds.astype(k.dtype), k, (1, 0))

    if masks.causal:
        pl.when(kb <= last)(_visit)
    else:
        _visit()

    @pl.when(t == steps - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *refs, steps, group, masks, operands, **kw):
    (bias_ref, admit_ref), (dk_ref, dv_ref, dk_scr, dv_scr) = _optional(
        refs, operands)
    # one key/value head and one key block a (i, kb); the innermost axis
    # runs over the group's query heads and, for each, the run of query
    # blocks that can see this key block
    kb = pl.program_id(1)
    r = pl.program_id(2)
    first, last = _query_band(kb, masks)
    j = first + r % steps
    n = pl.program_id(0) * group + r // steps

    @pl.when(r == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _visit():
        q, _, do, p, ds = _block_backward(
            seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            bias_ref, admit_ref, n, j, kb, masks=masks, **kw)
        dv_scr[:] = dv_scr[:] + _dot_f32(p.astype(do.dtype), do, (0, 0))
        dk_scr[:] = dk_scr[:] + _dot_f32(ds.astype(q.dtype), q, (0, 0))

    if masks.causal:
        pl.when(j <= last)(_visit)
    else:
        _visit()

    @pl.when(r == group * steps - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, *refs, steps, group, masks, operands, **kw):
    (bias_ref, admit_ref), (dq_ref, dk_ref, dv_ref, dq_scr, dk_scr,
                            dv_scr) = _optional(refs, operands)
    # one key/value head a row of the grid; axis 1 runs over the group's
    # query heads and, for each, its query blocks; the innermost axis over
    # the run of key blocks that query block can see. dk and dv of the
    # whole head stay in `dk_scr`, `dv_scr` while its blocks are visited,
    # and a key block receives its terms in `flash_bwd_dkv`'s order
    g = pl.program_id(1)
    t = pl.program_id(2)
    j = g % masks.nq
    n = pl.program_id(0) * group + g // masks.nq
    first, last = _key_band(j, masks)
    kb = first + t

    @pl.when((g == 0) & (t == 0))
    def _init_head():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(t == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _visit():
        q, k, do, p, ds = _block_backward(
            seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            bias_ref, admit_ref, n, j, kb, masks=masks, **kw)
        rows = pl.ds(pl.multiple_of(kb * masks.block_k, masks.block_k),
                     masks.block_k)
        ds = ds.astype(q.dtype)
        dv_scr[rows, :] = dv_scr[rows, :] + _dot_f32(
            p.astype(do.dtype), do, (0, 0))
        dk_scr[rows, :] = dk_scr[rows, :] + _dot_f32(ds, q, (0, 0))
        dq_scr[:] = dq_scr[:] + _dot_f32(ds, k, (1, 0))

    if masks.causal:
        pl.when(kb <= last)(_visit)
    else:
        _visit()

    @pl.when(t == steps - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)

    @pl.when((g == group * masks.nq - 1) & (t == steps - 1))
    def _finalize_head():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _delta_rows(out, do):
    """`sum over the lanes of out * dO`, [b*h, 1, sq] float32: what a
    row's `dS` subtracts from `dP`."""
    return jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                   axis=-1)[:, None, :]


def _bwd_pallas(q, k, v, bias, seed, out, lse, do, h, admit=None, *,
                sm_scale, causal, causal_offset, dropout, block_q, block_k,
                delta=None, window=0, dims=None, admit_keys=0, granule=1):
    bh, sq, d = q.shape
    bhkv, sk, dv = k.shape[0], k.shape[1], v.shape[2]
    group = bh // bhkv
    hkv = h // group
    masks = _Masks.of(sq, sk, causal=causal, causal_offset=causal_offset,
                      window=window, block_q=block_q, block_k=block_k,
                      granule=granule)
    q_at, kv_at, k_at = _head_maps(group)

    if delta is None:
        delta = _delta_rows(out, do)

    common = dict(sm_scale=sm_scale, dropout=dropout, masks=masks,
                  operands=(bias is not None, admit is not None))

    # ---- dq: the forward's grid --------------------------------------
    steps = masks.key_steps()

    qspec = lambda n, j, t: q_at(n, j)
    kspec = lambda n, j, t: k_at(n, _block_of(_key_band, j, t, masks))
    rowspec = lambda n, j, t: (n, 0, j)

    bias_in, bias_specs_q, bias_specs_k = [], [], []
    if bias is not None:
        bias_in = [bias]
        bias_specs_q = [pl.BlockSpec(
            (1, 1, block_k),
            lambda n, j, t: (n // h, 0, _block_of(_key_band, j, t, masks)),
            memory_space=pltpu.VMEM)]
        bias_specs_k = [pl.BlockSpec(
            (1, 1, block_k), lambda i, kb, r: (i // hkv, 0, kb),
            memory_space=pltpu.VMEM)]
    if admit is not None:
        bias_in.append(admit)
        bias_specs_q.append(pl.BlockSpec(
            (1, block_q, block_k),
            lambda n, j, t: (n // h, j, _block_of(_key_band, j, t, masks)),
            memory_space=pltpu.VMEM))
        query_steps = masks.query_steps()
        bias_specs_k.append(pl.BlockSpec(
            (1, block_q, block_k),
            lambda i, kb, r: (i // hkv, _block_of(
                _query_band, kb, r % query_steps, masks), kb),
            memory_space=pltpu.VMEM))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, steps=steps, **common),
        grid=(bh, masks.nq, steps),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), qspec, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kspec, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dv), kspec, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, dv), qspec, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), rowspec, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), rowspec, memory_space=pltpu.VMEM),
            *bias_specs_q,
        ],
        out_specs=pl.BlockSpec((1, block_q, d), qspec, memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret(),
        name="flash_bwd_dq",
        cost_estimate=_cost("flash_bwd_dq", q, k, bias, masks, dims, admit,
                            admit_keys),
    )(seed, q, k, v, do, lse, delta, *bias_in)

    # ---- dk, dv: a key/value head a row of the grid -------------------
    steps = masks.query_steps()

    def query_block(kb, r):
        return _block_of(_query_band, kb, r % steps, masks)

    kq = lambda i, kb, r: q_at(i * group + r // steps, query_block(kb, r))
    kk = lambda i, kb, r: kv_at(i, kb)
    krow = lambda i, kb, r: (i * group + r // steps, 0, query_block(kb, r))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, steps=steps, group=group,
                          **common),
        grid=(bhkv, masks.nk, group * steps),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), kq, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kk, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dv), kk, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, dv), kq, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), krow, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), krow, memory_space=pltpu.VMEM),
            *bias_specs_k,
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), kk, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dv), kk, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_bwd_dkv",
        cost_estimate=_cost("flash_bwd_dkv", q, k, bias, masks, dims, admit,
                            admit_keys),
    )(seed, q, k, v, do, lse, delta, *bias_in)
    return dq, dk, dv


def _bwd_fused_pallas(q, k, v, bias, seed, out, lse, do, h, admit=None, *,
                      sm_scale, causal, causal_offset, dropout, block_q,
                      block_k, delta=None, window=0, dims=None,
                      admit_keys=0, granule=1):
    """`_bwd_pallas`' dq, dk, dv from one call that visits a block of
    scores once: `flash_bwd_dq`'s walk, a query block and its run of key
    blocks, under a key/value head's row of the grid, with that head's dk
    and dv held in VMEM in float32 until its last block is done. For the
    calls `_bwd_fused_viable` admits."""
    bh, sq, d = q.shape
    bhkv, sk, dv = k.shape[0], k.shape[1], v.shape[2]
    group = bh // bhkv
    hkv = h // group
    masks = _Masks.of(sq, sk, causal=causal, causal_offset=causal_offset,
                      window=window, block_q=block_q, block_k=block_k,
                      granule=granule)
    steps, nq = masks.key_steps(), masks.nq
    if delta is None:
        delta = _delta_rows(out, do)

    def key_block(g, t):
        return _block_of(_key_band, g % nq, t, masks)

    qspec = lambda i, g, t: (i * group + g // nq, g % nq, 0)
    kspec = lambda i, g, t: (i, key_block(g, t), 0)
    rowspec = lambda i, g, t: (i * group + g // nq, 0, g % nq)
    headspec = lambda i, g, t: (i, 0, 0)

    bias_in, bias_spec = [], []
    if bias is not None:
        bias_in = [bias]
        bias_spec = [pl.BlockSpec(
            (1, 1, block_k), lambda i, g, t: (i // hkv, 0, key_block(g, t)),
            memory_space=pltpu.VMEM)]
    if admit is not None:
        bias_in.append(admit)
        bias_spec.append(pl.BlockSpec(
            (1, block_q, block_k),
            lambda i, g, t: (i // hkv, g % nq, key_block(g, t)),
            memory_space=pltpu.VMEM))

    return pl.pallas_call(
        functools.partial(
            _bwd_fused_kernel, steps=steps, group=group, sm_scale=sm_scale,
            dropout=dropout, masks=masks,
            operands=(bias is not None, admit is not None)),
        grid=(bhkv, group * nq, steps),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), qspec, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kspec, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dv), kspec, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, dv), qspec, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), rowspec, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), rowspec, memory_space=pltpu.VMEM),
            *bias_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), qspec, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, sk, d), headspec, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, sk, dv), headspec, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((sk, d), jnp.float32),
            pltpu.VMEM((sk, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_BWD_FUSED_VMEM_LIMIT),
        interpret=_interpret(),
        name="flash_bwd_dkv_dq",
        cost_estimate=_cost("flash_bwd_dkv_dq", q, k, bias, masks, dims,
                            admit, admit_keys),
    )(seed, q, k, v, do, lse, delta, *bias_in)


# ---------------------------------------------------------------------------
# public entry: custom_vjp over padded/flattened layout
# ---------------------------------------------------------------------------

# One jitted call for a forward that is differentiated and one that is
# not: a Program's gradient op lowers its forward op again, and XLA merges
# the two custom calls only if they are the same call (a kernel traced
# under the vjp rule is named `jvp_flash_fwd_` and is another).
_STATICS = ("sm_scale", "causal", "causal_offset", "dropout", "block_q",
            "block_k", "window", "dims", "admit_keys", "granule")
_fwd_call = jax.jit(_fwd_pallas, static_argnums=(5,), static_argnames=_STATICS)
_bwd_call = jax.jit(_bwd_pallas, static_argnums=(8,), static_argnames=_STATICS)
_bwd_fused_call = jax.jit(_bwd_fused_pallas, static_argnums=(8,),
                          static_argnames=_STATICS)


def _statics_of(statics):
    """(`flash_fwd`'s statics, the backward's) from the one tuple a
    call carries: the same but for the blocks, the forward's own under
    `fwd_blocks`. The output and the log-sum-exp rows do not depend on
    the blocks that made them, so the backward reads them at its own.
    `lse_grad` is the vjp rule's alone."""
    bwd = dict(statics)
    block_q, block_k = bwd.pop("fwd_blocks")
    bwd.pop("lse_grad", None)
    return {**bwd, "block_q": block_q, "block_k": block_k}, bwd


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash_core(q, k, v, bias, seed, h, statics, admit=None):
    """(output, log-sum-exp rows). The rows carry a gradient where the
    call asks for one (`lse_grad`): a row's log-sum-exp moves with its
    scores by their probabilities, so its cotangent joins `dS = p (dP -
    delta)` as `delta - dlse`, and the kernels are the same."""
    return _fwd_call(q, k, v, bias, seed, h, admit,
                     **_statics_of(statics)[0])


def _flash_core_fwd(q, k, v, bias, seed, h, statics, admit=None):
    out, lse = _fwd_call(q, k, v, bias, seed, h, admit,
                         **_statics_of(statics)[0])
    return (out, lse), (q, k, v, bias, seed, out, lse, admit)


def _flash_core_bwd(h, statics, res, cotangents):
    q, k, v, bias, seed, out, lse, admit = res
    do, dlse = cotangents
    fused = _bwd_fused_viable(k.shape[1], k.shape[2], v.shape[2],
                              k.dtype.itemsize)
    delta = (_delta_rows(out, do) - dlse if dict(statics).get("lse_grad")
             else None)
    dq, dk, dv = (_bwd_fused_call if fused else _bwd_call)(
        q, k, v, bias, seed, out, lse, do, h, admit, delta=delta,
        **_statics_of(statics)[1])
    dbias = None if bias is None else jnp.zeros_like(bias)
    dseed = np.zeros((1,), dtype=jax.dtypes.float0)
    dadmit = None if admit is None else np.zeros(admit.shape,
                                                 jax.dtypes.float0)
    return dq, dk, dv, dbias, dseed, dadmit


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# What `flash_fwd` may hold in VMEM: Mosaic's scoped default on a v5e. The
# pair's two backward kernels at 512 x 512 stay far under it at every width;
# `flash_bwd_dkv_dq` asks for more (`_BWD_FUSED_VMEM_LIMIT`).
_FWD_VMEM_BYTES = 16 << 20


def _fwd_vmem_bytes(block_q, block_k, d_p, dv_p, itemsize):
    """What a grid step of `flash_fwd` keeps in VMEM, from above: q, k, v
    and the output's blocks twice (the pipeline's two buffers), the
    float32 accumulator, `m` and `l`, and a block of scores in float32,
    its exponentials, and those again in the values' dtype for P.V."""
    blocks = block_q * (d_p + dv_p) + block_k * (d_p + dv_p)
    scratch = 4 * block_q * (dv_p + 2 * LANE)
    return (2 * itemsize * blocks + scratch
            + (8 + itemsize) * block_q * block_k)


def _fwd_blocks(sq_p, sk_p, block_q, block_k, d_p, dv_p, itemsize):
    """`flash_fwd`'s own (block_q, block_k) for a call whose backward runs
    at `block_q` x `block_k` over `sq_p` x `sk_p` padded rows: twice each
    where they divide the padded lengths (so the padding, `causal_offset`
    and the residuals' shapes are the backward's) and the step fits VMEM,
    else the key block alone doubled, else the backward's.

    Measured alone on a v5e (PERF.md, PR 41), ms a call at 512 x 512 /
    512 x 1,024 / 1,024 x 1,024: latent attention at s=4,096 (keys in 256
    lanes, values in 128) 3.39 / 2.39 / 2.01; 32 heads over 4 at s=8,192
    and 128 lanes, full causal 10.47 / 6.17 / 5.18, a 2,048-key window
    5.46 / 3.72 / 3.22, a 1,024-key window 3.70 / 2.80 / 2.45. What bounds
    the kernel is paid a row of a block (the two lane reductions, the
    column arithmetic on `m`, `l` and `alpha`, their broadcasts), so a
    block twice as wide halves it a key; walking a wide block in 512-key
    parts inside one grid step gave nothing back. A band pays for larger
    blocks in masked area (1.20 and 1.33 times under those windows) and
    still gains; 2,048 keys and more lose to it. The backward is
    indifferent to the key block and keeps its own (`_bwd_fused_viable`
    has its sweep)."""
    for bq, bk in ((2 * block_q, 2 * block_k), (block_q, 2 * block_k)):
        if (sq_p % bq == 0 and sk_p % bk == 0 and _fwd_vmem_bytes(
                bq, bk, d_p, dv_p, itemsize) <= _FWD_VMEM_BYTES):
            return bq, bk
    return block_q, block_k


# What `flash_bwd_dkv_dq` may keep of a key/value head from one grid step to
# the next, and what the call asks Mosaic for (of a v5e's 128 MiB; the
# default is 16 MiB): the rest of a step, its operands' blocks twice and a
# few blocks of scores in float32, is under 10 MiB at 512 x 512.
_BWD_FUSED_VMEM_BYTES = 32 << 20
_BWD_FUSED_VMEM_LIMIT = 64 << 20


def _bwd_fused_viable(sk_p, d_p, dv_p, itemsize):
    """Whether a call's backward is the one-visit `flash_bwd_dkv_dq`: where
    a key/value head's dk and dv fit VMEM, as the two float32 accumulators
    and the two buffers of each output block, from the padded key length,
    the padded widths and the item size alone. The cells' calls keep 8 to
    16 MiB (8,192 keys at 128 and 128 lanes: 16; 4,096 at 256 and 128:
    12); 32,768 keys at 128 and 128 lanes would keep 64 and take the pair.
    Bias, dropout, windows, groups and blocks passed by hand are served
    with the pair's arithmetic, so nothing else of a call is asked.

    Measured alone on a v5e (PERF.md, PR 48), ms a call at 512 x 512, the
    pair and the fused call, each with the XLA pass that makes the delta
    rows: latent attention, 32 heads at s=4,096 (keys in 256 lanes, values
    in 128) 6.21 / 4.42; 32 heads over 4 at s=8,192 and 128 lanes, full
    causal 14.41 / 10.95, a 2,048-key window 7.32 / 5.47, a 1,024-key
    window 4.80 / 3.62; 32 over 8 heads of 64 in 128 lanes at s=8,192
    14.44 / 10.99; 40 over 20 differential heads at s=4,096 (keys of 64,
    values of 128) 5.06 / 3.76, under a 512-key window 2.22 / 1.65: 24 to
    29% under the pair everywhere, and the same dq, dk, dv to the bit. A
    visit takes 2.5 us where its five products at 128 lanes need 1.7 at
    the peak. Tried in the body and not kept: no mask arithmetic in the
    blocks wholly inside the band (11.04 against 10.95 at 8,192 causal
    tokens), and the scores formed transposed, k.q^T, so that P^T.dO and
    dS^T.q need no transposed operand (10.91, and 11.37 with dq
    transposed as well). Other blocks, fused call alone, the same seven
    calls: 1,024 x 1,024 4.26, 9.69, 5.67, 4.16, 9.69, 3.53, 2.51, so 4
    to 12% shorter without a window and 4 to 52% longer with one;
    1,024 x 512 and 512 x 1,024 gain less without a window and lose
    with one as well; 256 either way 26 to 50% longer."""
    return ((4 + 2 * itemsize) * sk_p * (d_p + dv_p)
            <= _BWD_FUSED_VMEM_BYTES)


def _pad_inputs(q, k, v, bias, block_q, block_k):
    """Flatten [b, h, s, d] -> [b*h, s_p, d_p] (k and v may have fewer
    heads than q, and v its own width) with lane/sublane padding for the
    kernels: block sizes sublane-aligned (16 covers bf16's (16, 128) min
    tile), each array's head dim padded to a lane multiple, sequence dims
    padded to block multiples with padded keys masked via NEG_INF bias
    (`vf` is [b*hkv, sk_p, dv_p]). Shared by the flash and ring
    entry points so their layouts (and dropout-mask coordinates) stay
    bit-compatible. Returns (qf, kf, vf, biasf, bq, bk): the blocks of the
    backward, and of the forward too unless `_fwd_blocks` gives it
    multiples of them, which need no other padding; biasf is
    [b, 1, sk_p] or None."""
    b, _, sq, _ = q.shape
    sk = k.shape[2]
    bq = min(block_q or 512, _ceil_to(max(LANE, sq), 16))
    bk = min(block_k or 512, _ceil_to(max(LANE, sk), 16))
    bq, bk = _ceil_to(bq, 16), _ceil_to(bk, 16)
    sq_p, sk_p = _ceil_to(sq, bq), _ceil_to(sk, bk)

    # each array to whole lanes of its own width: values narrower than the
    # keys stay narrower (the kernels read the widths off their operands),
    # and the zero lanes' output columns and gradients are cut off again
    qf, kf, vf = (
        t if t.shape[2] % LANE == 0 else jnp.pad(
            t, [(0, 0), (0, 0), (0, _ceil_to(t.shape[2], LANE) - t.shape[2])])
        for t in (t.reshape(-1, *t.shape[2:]) for t in (q, k, v)))
    if sq_p != sq:
        qf = jnp.pad(qf, [(0, 0), (0, sq_p - sq), (0, 0)])
    biasf = bias
    if sk_p != sk:
        kf = jnp.pad(kf, [(0, 0), (0, sk_p - sk), (0, 0)])
        vf = jnp.pad(vf, [(0, 0), (0, sk_p - sk), (0, 0)])
        if biasf is None:
            biasf = jnp.zeros((b, sk), jnp.float32)
        biasf = jnp.pad(biasf, [(0, 0), (0, sk_p - sk)], constant_values=NEG_INF)
    if biasf is not None:
        # [b, 1, sk]: kernels map the batch*head grid index back to the
        # batch row (i // h) — no h-fold HBM duplication
        biasf = biasf.astype(jnp.float32)[:, None, :]
    return qf, kf, vf, biasf, bq, bk


def _attention_unfused(q, k, v, bias, causal, sm_scale, dropout, rng_key,
                       f32_residuals, layout="bhsd", window=0, admit=None,
                       with_lse=False):
    """One implementation of the plain-XLA attention semantics (bias /
    bottom-right-aligned causal mask, with `window` > 0 only the last
    `window` keys of it / murmur-hash dropout — the contract the Pallas
    kernels are validated against), with the dtype discipline
    parameterized:

    f32_residuals=True — the all-f32 gold (_reference_attention): scores
    and probs live in f32, maximally accurate for kernel tests.
    f32_residuals=False — the production below-cutover fallback
    (_xla_attention): scores/probs live in the INPUT dtype on HBM, only
    the softmax interior upcasts. Measured on BERT b=256 s=128 (v5e):
    the f32 discipline costs ~5% end-to-end (186.3-188.1k vs 195.1-198.4k
    tok/s) — f32 score/prob tensors double the HBM bytes and are saved
    as f32 residuals by the auto-vjp (the round-2 BN/LN lesson); casting
    only the probs@V input recovered nothing, the bytes/residual effect
    dominates.

    layout="bshd": q/k/v arrive [b, s, h, d] (the shape the model's QKV
    reshape produces) and the head axis is routed through dot_general
    BATCH dims instead of an explicit [b, h, s, d] transpose — the
    round-4 xplane showed those transposes materialize as ~0.15 ms HBM
    relayout copies per q/k/v per layer on BERT (and 26% of device time
    on Transformer-base).

    K and V may have fewer heads than Q: query head `n` reads key/value
    head `n // group`. The group is a batch dimension of the products, so
    K and V are not repeated.

    `admit`: [b, sq, sk], one for all the heads, whose zeros are pairs
    refused beside the masks'. `with_lse`: returns (out, the float32
    log-sum-exp of each row's admitted scores, [b, h, sq]) as the flash
    kernel's forward leaves them."""
    bshd = layout == "bshd"
    h_ax = 2 if bshd else 1
    h, hkv = q.shape[h_ax], k.shape[h_ax]
    grouped = h != hkv
    if grouped:
        if h % hkv:
            raise ValueError(
                f"attention: {h} query heads over {hkv} key/value heads")
        shape = list(q.shape)
        shape[h_ax:h_ax + 1] = [hkv, h // hkv]
        qg = q.reshape(shape)
        s = jnp.einsum("bqngd,bknd->bngqk" if bshd else "bngqd,bnkd->bngqk",
                       qg, k)
        s = s.reshape(s.shape[0], h, *s.shape[3:])
    elif bshd:
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    else:
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k)
    if f32_residuals:
        s = s.astype(jnp.float32)
    sf = (s * jnp.asarray(sm_scale, s.dtype)).astype(jnp.float32)
    if bias is not None:
        sf = sf + bias[:, None, None, :].astype(jnp.float32)
    if causal:
        sq, sk = sf.shape[-2], sf.shape[-1]
        mask = np.tril(np.ones((sq, sk), np.bool_), k=sk - sq)
        if window:
            mask &= np.triu(np.ones((sq, sk), np.bool_),
                            k=sk - sq - window + 1)
        sf = jnp.where(mask, sf, NEG_INF)
    elif window:
        raise ValueError("attention: a window needs causal=True")
    if admit is not None:
        sf = jnp.where(admit[:, None] != 0, sf, NEG_INF)
    p = jax.nn.softmax(sf, axis=-1)
    if not f32_residuals:
        p = p.astype(q.dtype)
    if dropout > 0.0:
        # murmur counter-hash mask, 2^-32 keep-prob granularity (see
        # nn_ops._dropout_keep_mask)
        from ..nn_ops import _dropout_keep_mask

        keep, keep_prob = _dropout_keep_mask(rng_key, dropout, p.shape)
        p = jnp.where(keep, p / jnp.asarray(keep_prob, p.dtype),
                      jnp.zeros((), p.dtype))
    v = v.astype(p.dtype)
    if grouped:
        pg = p.reshape(p.shape[0], hkv, h // hkv, *p.shape[2:])
        out = jnp.einsum("bngqk,bknd->bqngd" if bshd else "bngqk,bnkd->bngqd",
                         pg, v)
        out = out.reshape(q.shape[:-1] + (v.shape[-1],))
    elif bshd:
        out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    else:
        out = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    if with_lse:
        return out.astype(q.dtype), jax.nn.logsumexp(sf, axis=-1)
    return out.astype(q.dtype)


def _reference_attention(q, k, v, bias, causal, sm_scale, dropout, rng_key,
                         window=0):
    """All-f32 gold (CPU tests / kernel validation / ragged shapes)."""
    return _attention_unfused(q, k, v, bias, causal, sm_scale, dropout,
                              rng_key, f32_residuals=True, window=window)


def _xla_attention(q, k, v, bias, causal, sm_scale, dropout, rng_key,
                   layout="bhsd", window=0, admit=None, with_lse=False):
    """Production below-cutover fallback: input-dtype HBM discipline."""
    return _attention_unfused(q, k, v, bias, causal, sm_scale, dropout,
                              rng_key, f32_residuals=False, layout=layout,
                              window=window, admit=admit, with_lse=with_lse)


def flash_attention(
    q,
    k,
    v,
    bias=None,
    causal=False,
    sm_scale=None,
    dropout=0.0,
    rng_key=None,
    block_q=None,
    block_k=None,
    window=0,
    admit=None,
    admit_keys=0,
    with_lse=False,
    granule=1,
    causal_offset=None,
    lse_grad=False,
):
    """Fused multi-head attention.

    q: [b, h, sq, d]; k: [b, hkv, sk, d]; v: [b, hkv, sk, dv], `dv`
    narrower than `d`, as wide or wider, and `h` a multiple of `hkv`
    (query head `n` reads key/value head `n // (h // hkv)`); bias: additive key bias [b, sk] (0 keep /
    -inf drop) or None. `window` > 0 (with `causal`) admits only the last
    `window` keys a query may see. Returns [b, h, sq, dv] in q's dtype.
    `block_q`, `block_k`: a block named here serves every kernel; with
    neither, the backward runs at 512 x 512 and the forward at blocks
    chosen from the call's shape (`_fwd_blocks`). The backward is one
    kernel where a key/value head's dk and dv fit VMEM and the pair
    elsewhere (`_bwd_fused_viable`: the shape decides, no argument).

    `admit`: [b, sq, sk] int8, an admission that is data: a pair whose
    entry is 0 is refused beside what the masks refuse, for every head
    alike; it carries no gradient. Every kernel reads a block of it beside
    a block of scores, once a query head (one byte a pair: at 32 heads
    and 8,192 causal tokens 1.07 GB a pass), and still visits every block
    the static masks admit. `admit_keys`: the keys a query admits at most
    (what a selection of the K largest leaves), for the declared count
    alone. `with_lse`: returns (out, the rows' log-sum-exp over the
    admitted scores, [b, h, sq] float32, which carries no gradient
    unless `lse_grad`, for a caller that joins two calls' softmaxes over
    two key sets by their rows: `out = sum_i out_i exp(lse_i - lse)`).

    `granule` > 1 (with `causal`): a mask causal by blocks of `granule`
    rows and full inside one. Query `qi` sees the keys up to
    `qi // granule * granule + granule - 1 + causal_offset`, the end of
    its own granule shifted, and with a `window` only the last `window`
    of those. `causal_offset`: None aligns the last query with the last
    key (`sk - sq`); a caller names another. Block diffusion's training
    mask over a noisy and a clean copy of a row cut in blocks of B is
    three such calls: clean on clean granule B offset 0, noisy on clean
    granule B offset -B, noisy on its own block granule B offset 0
    window B. The grids skip the blocks of scores such a rule empties as
    they skip those above the diagonal, and the declared work counts the
    pairs it admits (`cost.admitted_pairs`). A query row that admits no
    key (the first noisy block on the clean copy) returns a log-sum-exp
    of about -1e30 and an output that is no one's: the caller's join
    weighs it by exp(-1e30 - lse) = 0.
    """
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))

    require_pallas("flash_attention")
    if h % k.shape[1]:
        raise ValueError(f"flash_attention: {h} query heads over "
                         f"{k.shape[1]} key/value heads")
    if (window or granule != 1 or causal_offset is not None) and not causal:
        raise ValueError("flash_attention: a window needs causal=True, and "
                         "so do a granule and an offset")
    if dropout > 0.0 and rng_key is None:
        raise ValueError("dropout requires rng_key")
    if dropout > 0.0:
        seed = jax.random.randint(
            rng_key, (1,), 0, np.iinfo(np.int32).max, jnp.int32
        )
    else:
        seed = jnp.zeros((1,), jnp.int32)

    # bottom-right-aligned causal offset in ORIGINAL coords (matches the
    # XLA reference path when sq != sk); padding doesn't shift it because
    # padded q rows are sliced away and padded keys are bias-masked
    if causal_offset is None:
        causal_offset = sk - sq
    qf, kf, vf, biasf, bq, bk = _pad_inputs(q, k, v, bias, block_q, block_k)
    fwd_blocks = bq, bk  # blocks passed by hand serve every kernel
    if block_q is None and block_k is None:
        fwd_blocks = _fwd_blocks(qf.shape[1], kf.shape[1], bq, bk,
                                 kf.shape[2], vf.shape[2], qf.dtype.itemsize)
    masks = _Masks.of(qf.shape[1], kf.shape[1], causal=bool(causal),
                      causal_offset=causal_offset, window=int(window),
                      block_q=bq, block_k=bk, granule=int(granule))
    fused = _bwd_fused_viable(kf.shape[1], kf.shape[2], vf.shape[2],
                              kf.dtype.itemsize)
    visited, total = masks.visited(fwd_blocks, fused)
    profiler.bump_counter("flash_blocks_visited", b * h * visited)
    profiler.bump_counter("flash_blocks_total", b * h * total)
    if vf.shape[2] < kf.shape[2]:  # the values travel at a width of their own
        profiler.bump_counter("flash_narrow_value_calls")
    if dv > d:  # ... or at more lanes than the keys fill of theirs
        profiler.bump_counter("flash_wide_value_calls")
    if fwd_blocks[1] != bk:  # the forward walks the keys at a block of its own
        profiler.bump_counter("flash_fwd_wide_key_calls")
    if fused:  # the backward visits a block of scores once, in one kernel
        profiler.bump_counter("flash_bwd_fused_calls")

    statics = (("sm_scale", float(sm_scale)), ("causal", bool(causal)),
               ("causal_offset", causal_offset), ("dropout", float(dropout)),
               ("block_q", bq), ("block_k", bk), ("fwd_blocks", fwd_blocks),
               ("window", int(window)), ("dims", (sq, sk, d, dv)),
               ("admit_keys", int(admit_keys)), ("granule", int(granule)),
               ("lse_grad", bool(lse_grad)))
    if admit is not None:
        admit = jnp.pad(admit.astype(jnp.int8), [
            (0, 0), (0, qf.shape[1] - sq), (0, kf.shape[1] - sk)])
    out, lse = _flash_core(qf, kf, vf, biasf, seed, h, statics, admit)
    out = out[:, :sq, :dv].reshape(b, h, sq, dv)
    if with_lse:
        return out, lse[:, 0, :sq].reshape(b, h, sq)
    return out
