"""Attention that chooses its keys from the data (DeepSeek-V3.2-Exp's
sparse attention, arXiv:2512.02556 section 2.1): the three ops round
`fused_multihead_attention`'s admission operand.

  sparse_index   I[t, s] = scale * sum_j w[t, j] relu(q[t, j] . k[s]),
                 s <= t: an indexer's score of key s for query t, float32
  sparse_select  admit[t, s] = s <= t and I[t, s] >= the K-th largest of
                 I[t, 0..t]: exact, ties kept, no gradient
  index_kl       KL(p[t] || softmax over the admitted of I[t]) a query, p
                 the attention's probabilities averaged over the heads,
                 rebuilt from q, k and the kernel's log-sum-exp rows and
                 held constant

Each is written here once in `jnp`, in blocks of `QUERY_BLOCK` queries
against the keys at or before the block's last (a static slice a block,
so the work is the causal half's), every block under `jax.checkpoint`:
the [heads, block, keys] float32 scores exist a block at a time, forward
and backward, and neither a head's [s, s] matrix nor the target for all
the heads is ever held. That is what the CPU runs, and any row a kernel
does not take. Where Pallas runs, on one device, and the row is whole
blocks (`_kernels`), `ops/pallas/sparse_index.py` makes the score and its
gradient, the selection and the target, a kernel each; the target's
kernel also sums the loss's rows as it visits a row's blocks (the
divergence, the sum of p, the log-sum-exp of the admitted scores), and
the loss's gradient is one elementwise XLA pass from those rows. The
loss from the target in `jnp` (`kl_from_target`: a softmax over a row
and a sum) is the plain path's, and what the kernel's rows are tested
against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import profiler
from .registry import register_op, register_shape

QUERY_BLOCK = 512
SIGN = np.int32(-2 ** 31)


def _kernels(ctx, b, s):
    """The module of the Pallas kernels where a lowering may call them
    (the rule of `fused_multihead_attention`'s flash path: Pallas runs,
    one device, whole blocks), else None. Imported here and not with the
    package: `import paddle_tpu` pays for no kernel module it may never
    call."""
    from .pallas import on_mesh
    from .pallas.flash_attention import _use_pallas

    if not _use_pallas() or on_mesh.batch_shards(ctx.mesh, b) != 1:
        return None
    from .pallas import sparse_index

    return sparse_index if sparse_index.viable(s) else None


def query_blocks(s):
    """(lo, hi) of each block of queries: `QUERY_BLOCK` rows where they
    divide the row, else the row whole."""
    step = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    return [(lo, lo + step) for lo in range(0, s, step)]


def _causal(lo, hi):
    """[hi - lo, hi]: query lo + i sees key j iff j <= lo + i."""
    return (jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :])


def _wide(rows, s):
    """[b, n, hi] rows of scores to [b, n, s], -inf beyond."""
    return jnp.pad(rows, [(0, 0), (0, 0), (0, s - rows.shape[2])],
                   constant_values=-jnp.inf)


# ------------------------------------------------------------ the score


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _index_block(q, k, w, scale, lo):
    """q [b, n, heads, d] (queries lo..lo+n), k [b, hi, d], w [b, n,
    heads] -> [b, n, hi] float32, -inf above the diagonal."""
    s = jnp.einsum("bqhd,bkd->bqhk", q, k,
                   preferred_element_type=jnp.float32)
    out = jnp.einsum("bqhk,bqh->bqk", jax.nn.relu(s),
                     w.astype(jnp.float32)) * scale
    return jnp.where(_causal(lo, lo + q.shape[1])[None], out, -jnp.inf)


def index_scores(q, k, w, scale):
    """`sparse_index` on arrays: q [b, s, heads, d], k [b, s, d], w
    [b, s, heads] -> [b, s, s] float32."""
    s = q.shape[1]
    return jnp.concatenate([
        _wide(_index_block(q[:, lo:hi], k[:, :hi], w[:, lo:hi], scale, lo), s)
        for lo, hi in query_blocks(s)], axis=1)


@register_op("sparse_index")
def _sparse_index(ctx, op):
    """Q [b, s, heads, d], K [b, s, 1, d], W [b, s, heads], attr `scale`
    -> Out [b, s, s] float32: `scale * sum_j W[t, j] relu(Q[t, j] .
    K[s])` at s <= t, -inf above. The products take their operands in the
    AMP dtype and accumulate float32; W, the relu and the sum over the
    heads are float32."""
    q, k = ctx.amp_cast(op, ctx.in_(op, "Q"), ctx.in_(op, "K"))
    w = ctx.in_(op, "W")
    if k.ndim != 4 or k.shape[2] != 1 or w.shape != q.shape[:3]:
        raise ValueError(
            f"sparse_index: Q {q.shape}, K {k.shape}, W {w.shape}: expected "
            "[b, s, heads, d], [b, s, 1, d], [b, s, heads]")
    profiler.set_counter("sparse_index_heads", q.shape[2])
    scale = float(op.attr("scale", 1.0))
    kernels = _kernels(ctx, q.shape[0], q.shape[1])
    if kernels is not None:
        profiler.bump_counter("sparse_index_kernel_calls")
        out = kernels.index_scores(jnp.transpose(q, (0, 2, 1, 3)),
                                   k[:, :, 0], w, scale)
    else:
        out = index_scores(q, k[:, :, 0], w, scale)
    ctx.out(op, "Out", out)


@register_shape("sparse_index")
def _shape_sparse_index(ictx, op):
    from .shape_fns import F32, VarMeta, _m

    q = _m(ictx.in_(op, "Q"))
    shape = None if q.shape is None else (q.shape[0], q.shape[1], q.shape[1])
    ictx.out(op, "Out", VarMeta(shape, F32))


# -------------------------------------------------------- the selection


def sortable(x):
    """float32 -> int32 whose signed order is the floats' (-0.0 below
    +0.0; NaNs do not occur)."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return jnp.where(bits < 0, bits ^ np.int32(0x7FFFFFFF), bits)


def unsortable(key):
    return jax.lax.bitcast_convert_type(
        jnp.where(key < 0, key ^ np.int32(0x7FFFFFFF), key), jnp.float32)


def kth_largest(keys, k):
    """The `k`-th largest of each row of int32 `keys` [..., n], exactly,
    by bisection on the bits from the top: 32 counts of a row's entries at
    or above a candidate, and no sort. Rows need at least `k` entries
    above the least int32."""
    def bit(i, found):
        # `found` holds the bits above 31 - i of the answer, as an unsigned
        # number kept in an int32; signed order is unsigned order with the
        # top bit flipped
        cand = found | jnp.left_shift(np.int32(1), 31 - i)
        count = jnp.sum(keys >= (cand ^ SIGN)[..., None], axis=-1)
        return jnp.where(count >= k, cand, found)

    found = jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(keys.shape[:-1], jnp.int32))
    return found ^ SIGN


def select(index, k):
    """`sparse_select` on arrays: index [b, s, s] float32 with -inf above
    the diagonal -> (admit [b, s, s] int8, tau [b, s] float32)."""
    s = index.shape[1]
    tau = unsortable(kth_largest(sortable(index), k))
    # a query with no more than k causal keys keeps them all
    tau = jnp.where(jnp.arange(s) < k, -jnp.inf, tau)
    admit = (index >= tau[..., None]) & _causal(0, s)[None]
    return admit.astype(jnp.int8), tau


@register_op("sparse_select", differentiable=False)
def _sparse_select(ctx, op):
    """X [b, s, s] float32 (`sparse_index`'s) -> Admit [b, s, s] int8, 1
    where s <= t and X[t, s] >= Tau[t]; Tau [b, s] float32, the `k`-th
    largest of X[t, 0..t], -inf where t < k. No `approx_max_k`, no sort:
    `kth_largest`. Gauge `sparse_attn_topk` holds `k`."""
    x = ctx.in_(op, "X")
    k = int(op.attr("k"))
    if x.ndim != 3 or x.shape[1] != x.shape[2] or k < 1:
        raise ValueError(f"sparse_select: X {x.shape}, k {k}: expected "
                         "[b, s, s] and k >= 1")
    profiler.set_counter("sparse_attn_topk", k)
    kernels = _kernels(ctx, x.shape[0], x.shape[1])
    admit, tau = (select if kernels is None else kernels.select)(x, k)
    ctx.out(op, "Admit", admit)
    ctx.out(op, "Tau", tau)


@register_shape("sparse_select")
def _shape_sparse_select(ictx, op):
    from .shape_fns import F32, VarMeta, _m

    x = _m(ictx.in_(op, "X"))
    ictx.out(op, "Admit", VarMeta(x.shape, "int8"))
    ictx.out(op, "Tau", VarMeta(
        None if x.shape is None else tuple(x.shape[:2]), F32))


# ------------------------------------------------------------- the loss


def _target_block(q, k, lse, admit, sm_scale):
    """q [b, n, h, d], k [b, hi, g, d], lse [b, h, n], admit [b, n, hi]
    -> [b, n, hi] float32: the heads' probabilities averaged on the
    admitted pairs, summed as they are made."""
    b, n, h, d = q.shape
    g = k.shape[2]
    scores = jnp.einsum(
        "bqngd,bknd->bngqk", q.reshape(b, n, g, h // g, d), k,
        preferred_element_type=jnp.float32) * sm_scale
    lse = lse.reshape(b, g, h // g, n)[..., None]
    p = jnp.sum(jnp.exp(scores - lse), axis=(1, 2)) / h
    return jnp.where(admit != 0, p, 0.0)


@jax.checkpoint
def kl_from_target(p, index, admit):
    """[b, n] float32 from p, index and admit [b, n, keys]: `sum over the
    admitted of p (log p - log softmax_admitted(index))`, p a constant.
    Under `jax.checkpoint`: the backward makes the row's softmax again and
    keeps no [n, keys] array for it. The plain path's divergence (a block
    of queries at a time, `_kl_block`) and the reference of the kernel
    path's, where `index_kl_target` sums these rows itself and the
    gradient is this function's by autodiff, written out:
    `(softmax_admitted(index) * sum(p) - p) * dOut`, the row's sum of p
    not taken for 1."""
    kept = admit != 0
    p = jax.lax.stop_gradient(p)
    logq = jax.nn.log_softmax(jnp.where(kept, index, -jnp.inf), axis=-1)
    # 0 log 0 = 0, and nothing of a refused pair (whose log is -inf)
    term = jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0))
                                 - jnp.where(kept, logq, 0.0)), 0.0)
    return jnp.sum(term, axis=-1)


@functools.partial(jax.checkpoint, static_argnums=(5,))
def _kl_block(q, k, lse, index, admit, sm_scale):
    return kl_from_target(_target_block(q, k, lse, admit, sm_scale), index,
                          admit)


def index_kl_rows(q, k, lse, index, admit, sm_scale):
    """`index_kl` on arrays, q [b, s, heads, d] and k [b, s, groups, d]
    token-major."""
    return jnp.concatenate([
        _kl_block(q[:, lo:hi], k[:, :hi], lse[:, :, lo:hi],
                  index[:, lo:hi, :hi], admit[:, lo:hi, :hi], sm_scale)
        for lo, hi in query_blocks(q.shape[1])], axis=1)


@register_op("index_kl", no_grad_inputs=("Q", "K", "Lse", "Admit"))
def _index_kl(ctx, op):
    """Q [b, heads, s, d], K [b, groups, s, d] (head-major, as the
    attention took them: its outputs QPrepared and KPrepared), Lse
    [b, heads, s] float32 (its output), Index [b, s, s] float32, Admit
    [b, s, s] int8, attrs `sm_scale` and `admit_keys` (the keys a query
    admits at most, for the kernel's declared count) -> Out [b, s]
    float32:
    `sum over the admitted s of p (log p - log softmax_admitted(Index))`
    with `p = mean over the heads of exp(sm_scale Q . K - Lse)`, a
    constant. The gradient reaches Index alone: `(softmax_admitted(Index)
    * sum(p) - p) * dOut` on the admitted pairs (the row's sum of p is 1
    only up to the products' rounding, and is used).

    Where the kernels run (`_kernels`; counters `index_kl_kernel_calls`
    and `index_kl_fused`), `index_kl_target` makes p and, in the same
    visit, the divergence, the sum of p and the log-sum-exp of the
    admitted scores a row; the gradient is one elementwise pass from
    those rows (`sparse_index.index_kl`'s rule). Elsewhere
    `index_kl_rows`: the target and `kl_from_target` a block of queries
    at a time, differentiated by JAX."""
    q, k = ctx.amp_cast(op, ctx.in_(op, "Q"), ctx.in_(op, "K"))
    lse, index, admit = (ctx.in_(op, "Lse"), ctx.in_(op, "Index"),
                         ctx.in_(op, "Admit"))
    sm_scale = float(op.attr("sm_scale"))
    kernels = _kernels(ctx, q.shape[0], q.shape[2])
    if kernels is None:
        # `index_kl_rows` cuts its blocks along a token-major axis
        q, k = (jnp.transpose(t, (0, 2, 1, 3)) for t in (q, k))
        out = index_kl_rows(q, k, lse, index, admit, sm_scale)
    else:
        # the arrays the flash kernels read, as they come
        profiler.bump_counter("index_kl_kernel_calls")
        profiler.bump_counter("index_kl_fused")
        out = kernels.index_kl(q, k, lse, index, admit, sm_scale,
                               int(op.attr("admit_keys", 0) or 0))
    ctx.out(op, "Out", out)


@register_shape("index_kl")
def _shape_index_kl(ictx, op):
    from .shape_fns import F32, VarMeta, _m

    q = _m(ictx.in_(op, "Q"))
    ictx.out(op, "Out", VarMeta(
        None if q.shape is None else (q.shape[0], q.shape[2]), F32))
