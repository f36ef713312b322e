"""The Pallas kernels of attention that chooses its keys from the data
(`ops/sparse_attn_ops.py` has the ops and their `jnp` forms, which these
replace where Mosaic compiles):

  sparse_index_fwd   I[t, s] = scale * sum_j w[t, j] relu(q[t, j] . k[s])
                     a block of scores at a time, the heads' products summed
                     in VMEM: no head's [s, s] matrix reaches HBM
  sparse_index_bwd   dq, dk, dw from dI in one visit of a block, the
                     heads' products made again; dk of a row stays in VMEM
  sparse_select      the K-th largest of each row of I by bisection on the
                     values' bits, 32 counts over rows resident in VMEM,
                     and the admission written from it: I is read once
  index_kl_target    p[t, s] = mean over the heads of exp(scale q . k -
                     lse) on the admitted pairs, the heads summed in VMEM:
                     the target of the indexer's loss, never held a head;
                     and in the same visit the loss's row sums: KL(p ||
                     softmax over the admitted of I), the sum of p and the
                     log-sum-exp of the admitted I, so the loss walks no
                     [s, s] array again and its gradient is one pass

All four walk [queries, keys] blocks of one batch row under the causal
diagonal; a block above it is written (-inf, 0) and not computed. What
each declares (`cost.py` has the convention): the products over the
causal pairs for the score (an indexer scores a key to refuse it), over
the admitted pairs for the target; `sparse_select` has no product and
declares its compares and counts, and its bytes are I once and the
admission once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import cost
from .flash_attention import NEG_INF, _interpret

BLOCK = 512
SELECT_ROWS = 64
VMEM_LIMIT = 64 << 20
SIGN = np.int32(-2 ** 31)
TINY = float(np.finfo(np.float32).tiny)


def viable(s):
    """Whole blocks: the rows these kernels take."""
    return s % BLOCK == 0


def _dot(a, b, contract):
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.float32)


def _below_diagonal(j, kb, block):
    qi = j * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    ki = kb * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    return qi >= ki


def _causal_pairs(b, s):
    return b * cost.admitted_pairs(s, s, causal=True)


# ------------------------------------------------------------- the score


def _index_fwd_kernel(q_ref, k_ref, w_ref, o_ref, *, scale, heads, block):
    j, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb > j)
    def _above():
        o_ref[0] = jnp.full((block, block), -jnp.inf, jnp.float32)

    @pl.when(kb <= j)
    def _visit():
        k = k_ref[0]
        w = w_ref[0].astype(jnp.float32)
        acc = jnp.zeros((block, block), jnp.float32)
        for h in range(heads):
            s = _dot(q_ref[0, h], k, (1, 1))
            acc = acc + w[:, h:h + 1] * jnp.maximum(s, 0.0)
        o_ref[0] = jnp.where(_below_diagonal(j, kb, block), acc * scale,
                             -jnp.inf)


def _index_fwd(q, k, w, scale, block):
    """q [b, heads, s, d], k [b, s, d], w [b, s, heads] -> [b, s, s]."""
    b, heads, s, d = q.shape
    n = s // block
    key_block = lambda i, j, kb: (i, jnp.minimum(kb, j), 0)
    return pl.pallas_call(
        functools.partial(_index_fwd_kernel, scale=scale, heads=heads,
                          block=block),
        grid=(b, n, n),
        in_specs=[
            pl.BlockSpec((1, heads, block, d), lambda i, j, kb: (i, 0, j, 0)),
            pl.BlockSpec((1, block, d), key_block),
            pl.BlockSpec((1, block, heads), lambda i, j, kb: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block, block), lambda i, j, kb: (i, j, kb)),
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=_interpret(),
        name="sparse_index_fwd",
        cost_estimate=cost.estimate(
            2 * d * heads * _causal_pairs(b, s), 0,
            (q.shape, q.dtype), (k.shape, k.dtype), (w.shape, w.dtype),
            ((b, s, s), jnp.float32)),
    )(q, k, w)


def _index_bwd_kernel(q_ref, k_ref, w_ref, g_ref, dq_ref, dk_ref, dw_ref,
                      dq_scr, dk_scr, dw_scr, *, scale, heads, block, n):
    j, kb = pl.program_id(1), pl.program_id(2)

    @pl.when((j == 0) & (kb == 0))
    def _init_row():
        dk_scr[:] = jnp.zeros_like(dk_scr)

    @pl.when(kb == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        dw_scr[:] = jnp.zeros_like(dw_scr)

    @pl.when(kb <= j)
    def _visit():
        k = k_ref[0]
        w = w_ref[0].astype(jnp.float32)
        # what the diagonal refuses carries no gradient, whatever came
        g = jnp.where(_below_diagonal(j, kb, block), g_ref[0], 0.0) * scale
        rows = pl.ds(pl.multiple_of(kb * block, block), block)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, dw_scr.shape[1]), 1)
        dw = dw_scr[:]
        dk = dk_scr[rows, :]
        for h in range(heads):
            q = q_ref[0, h]
            s = _dot(q, k, (1, 1))
            dw = dw + jnp.sum(g * jnp.maximum(s, 0.0), axis=1,
                              keepdims=True) * (lane == h)
            ds = jnp.where(s > 0.0, g * w[:, h:h + 1], 0.0).astype(k.dtype)
            dq_scr[h] = dq_scr[h] + _dot(ds, k, (1, 0))
            dk = dk + _dot(ds, q, (0, 0))
        dw_scr[:] = dw
        dk_scr[rows, :] = dk

    @pl.when(kb == n - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)
        dw_ref[0] = dw_scr[:, :heads].astype(dw_ref.dtype)

    @pl.when((j == n - 1) & (kb == n - 1))
    def _finalize_row():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)


def _index_bwd(q, k, w, g, scale, block):
    b, heads, s, d = q.shape
    n = s // block
    clamp = lambda i, j, kb: (i, jnp.minimum(kb, j), 0)
    return pl.pallas_call(
        functools.partial(_index_bwd_kernel, scale=scale, heads=heads,
                          block=block, n=n),
        grid=(b, n, n),
        in_specs=[
            pl.BlockSpec((1, heads, block, d), lambda i, j, kb: (i, 0, j, 0)),
            pl.BlockSpec((1, block, d), clamp),
            pl.BlockSpec((1, block, heads), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, block, block),
                         lambda i, j, kb: (i, j, jnp.minimum(kb, j))),
        ],
        out_specs=[
            pl.BlockSpec((1, heads, block, d), lambda i, j, kb: (i, 0, j, 0)),
            pl.BlockSpec((1, s, d), lambda i, j, kb: (i, 0, 0)),
            pl.BlockSpec((1, block, heads), lambda i, j, kb: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(w.shape, w.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, block, d), jnp.float32),
            pltpu.VMEM((s, d), jnp.float32),
            pltpu.VMEM((block, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=_interpret(),
        name="sparse_index_bwd",
        # q . k again, dS . k and dS^T . q, over the causal pairs
        cost_estimate=cost.estimate(
            6 * d * heads * _causal_pairs(b, s), 0,
            (q.shape, q.dtype), (k.shape, k.dtype), (w.shape, w.dtype),
            ((b, s, s), jnp.float32), (q.shape, q.dtype),
            (k.shape, k.dtype), (w.shape, w.dtype)),
    )(q, k, w, g)


# One jitted call a kernel, whoever traces it: a Program's gradient op
# lowers its forward op again under `jax.vjp`, and XLA merges the two
# custom calls only if they are the same call (a kernel traced under a
# vjp rule is named `jvp_..._` and is another; `flash_attention.py` has
# the same note). The blocks are arguments, read off the module where a
# public function is called.
_index_fwd_call = jax.jit(_index_fwd, static_argnums=(3, 4))
_index_bwd_call = jax.jit(_index_bwd, static_argnums=(4, 5))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _index_core(q, k, w, scale, block):
    return _index_fwd_call(q, k, w, scale, block)


def _index_core_fwd(q, k, w, scale, block):
    return _index_fwd_call(q, k, w, scale, block), (q, k, w)


def _index_core_bwd(scale, block, res, g):
    return _index_bwd_call(*res, g, scale, block)


_index_core.defvjp(_index_core_fwd, _index_core_bwd)


def index_scores(q, k, w, scale):
    """`sparse_attn_ops.index_scores` with the heads first: q [b, heads,
    s, d], k [b, s, d], w [b, s, heads] -> [b, s, s] float32, -inf above
    the diagonal."""
    return _index_core(q, k, w, float(scale), BLOCK)


# --------------------------------------------------------- the selection


def _select_kernel(x_ref, admit_ref, tau_ref, *, k, rows, s):
    j = pl.program_id(1)
    x = x_ref[0]
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    keys = jnp.where(bits < 0, bits ^ np.int32(0x7FFFFFFF), bits)

    def bit(i, found):
        # the answer's bits from the top, as `sparse_attn_ops.kth_largest`
        cand = found | jnp.left_shift(np.int32(1), 31 - i)
        count = jnp.sum((keys >= (cand ^ SIGN)).astype(jnp.int32), axis=1,
                        keepdims=True)
        return jnp.where(count >= k, cand, found)

    found = jax.lax.fori_loop(0, 32, bit, jnp.zeros((rows, 1), jnp.int32))
    key = found ^ SIGN
    tau = jax.lax.bitcast_convert_type(
        jnp.where(key < 0, key ^ np.int32(0x7FFFFFFF), key), jnp.float32)
    t = j * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    tau = jnp.where(t < k, -jnp.inf, tau)  # no more than k keys: all kept
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, s), 1)
    admit_ref[0] = ((x >= tau) & (col <= t)).astype(jnp.int8)
    tau_ref[0] = tau


def _select(index, k, rows):
    b, s, _ = index.shape
    admit, tau = pl.pallas_call(
        functools.partial(_select_kernel, k=k, rows=rows, s=s),
        grid=(b, s // rows),
        in_specs=[pl.BlockSpec((1, rows, s), lambda i, j: (i, j, 0))],
        out_specs=[pl.BlockSpec((1, rows, s), lambda i, j: (i, j, 0)),
                   pl.BlockSpec((1, rows, 1), lambda i, j: (i, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, s, s), jnp.int8),
                   jax.ShapeDtypeStruct((b, s, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=_interpret(),
        name="sparse_select",
        # a compare and a count an entry a bit, and the last compare
        cost_estimate=cost.estimate(
            65 * b * s * s, 0, ((b, s, s), jnp.float32),
            ((b, s, s), jnp.int8), ((b, s), jnp.float32)),
    )(index)
    return admit, tau[:, :, 0]


_select_call = jax.jit(_select, static_argnums=(1, 2))


def select(index, k):
    """`sparse_attn_ops.select`: index [b, s, s] float32 with -inf above
    the diagonal -> (admit [b, s, s] int8, tau [b, s] float32)."""
    return _select_call(index, int(k), SELECT_ROWS)


# ------------------------------------------------------------ the target


def _target_kernel(q_ref, k_ref, lse_ref, index_ref, admit_ref, p_ref, kl_ref,
                   sp_ref, lq_ref, m_scr, l_scr, kl_scr, sp_scr, *, sm_scale,
                   heads, group, block, n):
    j, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        kl_scr[:] = jnp.zeros_like(kl_scr)
        sp_scr[:] = jnp.zeros_like(sp_scr)

    @pl.when(kb > j)
    def _above():
        p_ref[0] = jnp.zeros((block, block), jnp.float32)

    @pl.when(kb <= j)
    def _visit():
        acc = jnp.zeros((block, block), jnp.float32)
        for h in range(heads):
            s = _dot(q_ref[0, h], k_ref[0, h // group], (1, 1)) * sm_scale
            acc = acc + jnp.exp(s - lse_ref[0, h][:, None])
        kept = admit_ref[0].astype(jnp.int32) != 0
        p = jnp.where(kept, acc * (1.0 / heads), 0.0)
        p_ref[0] = p
        # the indexer's softmax over the admitted, a key block at a time
        # as `flash_fwd` makes its own: the row's largest score so far and
        # the sum against it. A row that has admitted nothing yet sums
        # ones against NEG_INF, which its first admitted key wipes
        # (exp(NEG_INF - m) = 0); every row admits a key by its diagonal.
        m_prev, sp_prev = m_scr[:], sp_scr[:]
        x = jnp.where(kept, index_ref[0], NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(x, axis=1, keepdims=True))
        x = x - m_new
        l_scr[:] = (jnp.exp(m_prev - m_new) * l_scr[:]
                    + jnp.sum(jnp.exp(x), axis=1, keepdims=True))
        # sum of p (log p - (index - m)), held against the same maximum
        # (so no term grows with the scores' scale). 0 log 0 = 0 as
        # `kl_from_target` has it: p is 0 on a refused pair and where the
        # exponentials underflow, and the other factor stays finite
        term = p * (jnp.log(jnp.maximum(p, TINY)) - x)
        kl_scr[:] = (kl_scr[:] + (m_new - m_prev) * sp_prev
                     + jnp.sum(term, axis=1, keepdims=True))
        sp_scr[:] = sp_prev + jnp.sum(p, axis=1, keepdims=True)
        m_scr[:] = m_new

    @pl.when(kb == n - 1)
    def _finalize():
        log_l = jnp.log(l_scr[:])
        kl_ref[0] = kl_scr[:] + log_l * sp_scr[:]
        sp_ref[0] = sp_scr[:]
        lq_ref[0] = m_scr[:] + log_l


def _target(q, k, lse, index, admit, sm_scale, admit_keys, block):
    b, heads, s, d = q.shape
    groups = k.shape[1]
    n = s // block
    pairs = b * cost.admitted_pairs(s, s, causal=True, window=admit_keys)
    visited = lambda i, j, kb: (i, j, jnp.minimum(kb, j))
    row = pl.BlockSpec((1, block, 1), lambda i, j, kb: (i, j, 0))
    p, *rows = pl.pallas_call(
        functools.partial(_target_kernel, sm_scale=sm_scale, heads=heads,
                          group=heads // groups, block=block, n=n),
        grid=(b, n, n),
        in_specs=[
            pl.BlockSpec((1, heads, block, d), lambda i, j, kb: (i, 0, j, 0)),
            pl.BlockSpec((1, groups, block, d),
                         lambda i, j, kb: (i, 0, jnp.minimum(kb, j), 0)),
            pl.BlockSpec((1, heads, block), lambda i, j, kb: (i, 0, j)),
            pl.BlockSpec((1, block, block), visited),
            pl.BlockSpec((1, block, block), visited),
        ],
        out_specs=[
            pl.BlockSpec((1, block, block), lambda i, j, kb: (i, j, kb)),
            row, row, row],
        out_shape=[jax.ShapeDtypeStruct((b, s, s), jnp.float32)]
        + [jax.ShapeDtypeStruct((b, s, 1), jnp.float32)] * 3,
        scratch_shapes=[pltpu.VMEM((block, 1), jnp.float32)] * 4,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=_interpret(),
        name="index_kl_target",
        # q . k for every head on the admitted pairs, an exponential each;
        # the divergence's exponential and logarithm a pair, a logarithm
        # a row
        cost_estimate=cost.estimate(
            2 * d * heads * pairs, (heads + 2) * pairs + b * s,
            (q.shape, q.dtype), (k.shape, k.dtype), (lse.shape, lse.dtype),
            (index.shape, index.dtype), (admit.shape, admit.dtype),
            ((b, s, s), jnp.float32), ((3, b, s), jnp.float32)),
    )(q, k, lse, index, admit)
    return (p, *(r[:, :, 0] for r in rows))


_target_call = jax.jit(_target, static_argnums=(5, 6, 7))


def head_mean_probabilities(q, k, lse, index, admit, sm_scale, admit_keys=0):
    """q [b, heads, s, d], k [b, groups, s, d] (as the flash kernels take
    them), lse [b, heads, s] float32, index [b, s, s] float32, admit
    [b, s, s] int8 -> (p, kl, sp, lq), constants all (no gradient passes;
    `index_kl` has the rule). p [b, s, s] float32: the attention's
    probabilities averaged over the heads on the admitted pairs, 0
    elsewhere. And three [b, s] float32 rows, summed over a query block's
    key blocks in the same visit, so that no XLA pass walks an [s, s]
    array for the divergence: `kl`, `kl_from_target(p, index, admit)`;
    `sp`, the row's sum of p (1 only up to the rounding of the bf16
    products); `lq`, the log-sum-exp of the admitted scores of `index`.
    `admit_keys`: the keys a query admits at most, for the declared count
    (0: every causal one)."""
    return jax.lax.stop_gradient(_target_call(
        jax.lax.stop_gradient(q), jax.lax.stop_gradient(k), lse,
        jax.lax.stop_gradient(index), admit, float(sm_scale),
        int(admit_keys), BLOCK))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def index_kl(q, k, lse, index, admit, sm_scale, admit_keys):
    """`sparse_attn_ops.index_kl_rows` with the heads first, [b, s]
    float32: the divergence as `index_kl_target` sums it
    (`head_mean_probabilities`' `kl`). The gradient reaches `index` alone
    and is autodiff's of `kl_from_target`, `(softmax_admitted(index) *
    sp - p) * dOut` on the admitted pairs, from the rows the forward
    kept: one elementwise pass over p, index and admit, with `sp` the
    kernel's sum of p and not 1."""
    return head_mean_probabilities(q, k, lse, index, admit, sm_scale,
                                   admit_keys)[1]


def _index_kl_fwd(q, k, lse, index, admit, sm_scale, admit_keys):
    p, kl, sp, lq = head_mean_probabilities(q, k, lse, index, admit,
                                            sm_scale, admit_keys)
    return kl, (p, sp, lq, index, admit)


def _index_kl_bwd(sm_scale, admit_keys, res, g):
    p, sp, lq, index, admit = res
    soft = jnp.exp(index - lq[..., None]) * sp[..., None]
    grad = jnp.where(admit != 0, soft - p, 0.0) * g[..., None]
    return None, None, None, grad, np.zeros(admit.shape, jax.dtypes.float0)


index_kl.defvjp(_index_kl_fwd, _index_kl_bwd)
