"""The backward of both norms over a row as one Pallas TPU kernel pass.

XLA splits a norm's backward into an elementwise dX pass plus separate
sublane-dim reductions for the scale's (and bias's) gradient,
materializing the recomputed fp32 normalized value between them (~30
ms/step across BERT-base's 25 LN sites, b=256; 23 ms a step over Ouro's
100 RMSNorm sites at 28% of HBM). One kernel pass reads x/dy once (bf16),
computes dX, and emits per-block partial dScale (dBias) rows that a trivial
[blocks, k] sum finishes outside. One body (`_kernel`) with a static
switch, two names:

- `ln_bwd`: LayerNorm, from the rows' saved mean and rstd. Reached from the
  op `layer_norm_grad` (ops/nn_ops.py::_layer_norm_grad) where
  `ln_bwd_viable` admits the shape. Reference semantics:
  operators/layer_norm_op.cc grad.
- `rms_bwd`: RMSNorm, `nn_ops.rms_norm`'s mathematics; the statistic is
  computed from the row block itself, so the forward op saves nothing.
  Reached from the op `rms_norm_grad` (ops/nn_ops.py::_rms_norm_grad)
  where `rms_bwd_viable` admits the shape: a decoder's block norms, not
  the norms over one head's lanes.

Both lowerings take the kernel under one mesh rule (`on_mesh.batch_shards`:
no mesh, one device, or per shard of a batch-only mesh). The forwards stay
XLA's, which fuses them into their neighbours.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import on_mesh
from .flash_attention import _ceil_to, _interpret, require_pallas


def _kernel(*refs, rms_eps):
    """One row block of either norm's backward. `rms_eps` None: LayerNorm,
    with the rows' saved mean and rstd among the operands and a partial
    dbias row among the results. A number: RMSNorm with that epsilon, the
    statistic computed here from the block itself (the whole row is in
    VMEM), no mean, no dbias."""
    rms = rms_eps is not None
    if rms:
        x_ref, dy_ref, scale_ref, dx_ref, dg_ref = refs
    else:
        (x_ref, dy_ref, mean_ref, rstd_ref, scale_ref,
         dx_ref, dg_ref, db_ref) = refs
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    if rms:
        rstd = jax.lax.rsqrt(
            jnp.mean(x * x, axis=1, keepdims=True) + rms_eps)
        nrm = x * rstd
    else:
        mean = mean_ref[...].astype(jnp.float32)  # [Bn, 1]
        rstd = rstd_ref[...].astype(jnp.float32)
        nrm = (x - mean) * rstd
    dyg = dy * scale_ref[...].astype(jnp.float32)  # [1, k] broadcasts
    if not rms:  # before m2, where it always stood: `ln_bwd` is unchanged
        m1 = jnp.mean(dyg, axis=1, keepdims=True)
    m2 = jnp.mean(dyg * nrm, axis=1, keepdims=True)
    inner = dyg - nrm * m2 if rms else dyg - m1 - nrm * m2
    dx_ref[...] = (rstd * inner).astype(dx_ref.dtype)
    dg_ref[...] = jnp.sum(dy * nrm, axis=0)[None, None, :]
    if not rms:
        db_ref[...] = jnp.sum(dy, axis=0)[None, None, :]


def ln_bwd_viable(n, k):
    # one [Bn, k] row-block ×~6 fp32 temporaries must fit VMEM
    return n >= 1024 and k <= 4096 and k % 128 == 0


def rms_bwd_viable(n, k):
    """`ln_bwd_viable` with a least width, in whole groups of 512 lanes.
    The least width keeps the norms over one head's lanes (k = 64 to 256
    on [b, s, h, d]: thousands of grid steps of a few lanes each) and the
    latents' (512) with XLA, which fuses them into their neighbours. The
    groups are a proxy, and the only one the lowering can see: at 2,048
    lanes (and JoyAI's 1,536 beside them) the kernel won or held in every
    cell, +8% in Ouro's where XLA ran each gradient as a pass of its own;
    at 2,304 (Kimi, Mellum) XLA folds the gradient into the products round
    it, books nothing under the op's scope, and the kernel cost both
    cells 0.4 to 1.5% whatever its blocks (PERF.md, PR 59)."""
    return ln_bwd_viable(n, k) and k >= 1024 and k % 512 == 0


@functools.partial(
    jax.jit, static_argnames=("rms_eps", "block_rows", "interpret", "mesh"))
def _call(x2, dy2, stats, scale, *, rms_eps, block_rows, interpret, mesh):
    """The pallas_call over whole row blocks, per shard of `batch` on a
    `mesh`: dx [n, k] and one partial dscale row per block (and one of
    dbias, LayerNorm's). `stats`: LayerNorm's (mean, rstd), nothing for
    RMSNorm. Jitted so that the calls of one shape in a step (25 of
    BERT's 26, Ouro's 100) are traced and lowered once."""
    k = x2.shape[1]
    rms = rms_eps is not None

    def run(x2, dy2, *rest):
        *stats, scale = rest
        n = x2.shape[0]
        np_ = _ceil_to(n, block_rows)
        if np_ != n:
            pad = [(0, np_ - n), (0, 0)]
            x2 = jnp.pad(x2, pad)
            dy2 = jnp.pad(dy2, pad)  # zero dy rows contribute nothing
            stats = [jnp.pad(s, [(0, np_ - n)]) for s in stats]
        rows = pl.BlockSpec((block_rows, k), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
        stat = pl.BlockSpec((block_rows, 1), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
        part = pl.BlockSpec((1, 1, k), lambda i: (i, 0, 0),
                            memory_space=pltpu.VMEM)
        nb = np_ // block_rows
        partial_row = jax.ShapeDtypeStruct((nb, 1, k), jnp.float32)
        dx, *partials = pl.pallas_call(
            functools.partial(_kernel, rms_eps=rms_eps),
            grid=(nb,),
            in_specs=[rows, rows] + [stat] * len(stats) + [
                pl.BlockSpec((1, k), lambda i: (0, 0),
                             memory_space=pltpu.VMEM)],
            out_specs=[rows] + [part] * (1 if rms else 2),
            out_shape=[jax.ShapeDtypeStruct((np_, k), x2.dtype)]
            + [partial_row] * (1 if rms else 2),
            interpret=interpret,
            name="rms_bwd" if rms else "ln_bwd",
            # Neither name declares a cost (`cost.py`). With 13 FLOPs an
            # element and x, dy, dx, the statistics and the partial rows
            # once (152 MB a call at BERT's [32768, 768]) declared,
            # `bert_base_s128` ran 1.0 to 2.7% slower in every pair on the
            # chip, and at the parent's rate with this one call left out:
            # XLA reads a custom call's cost where it places arrays in
            # VMEM, and the step compiled with the declaration keeps fewer
            # there (PERF.md, PR 35). `rms_bwd` with 11 FLOPs an element
            # and its 50.3 MB declared: Ouro's step 275.1 ms where it is
            # 272.2 without, 1.1% of the rate in three pairs of three
            # (PERF.md, PR 59).
        )(x2, dy2, *(s.reshape(np_, 1) for s in stats), scale)
        return (dx[:n], *partials)

    return on_mesh.per_shard(
        run, mesh, [True, True] + [True] * len(stats) + [False])(
            x2, dy2, *stats, scale)


def _block_rows(k):
    # ~5 fp32 row-blocks live in the kernel; keep them within ~5 MB of
    # the 16 MB scoped-VMEM budget as k grows (256 rows at k=768)
    return max(8, min(256, (1 << 18) // k // 8 * 8))


def _dividing_block_rows(n, k):
    """`_block_rows(k)`, or the largest multiple of 8 above half of it
    that divides a shard's `n` rows: a last short block costs a padded
    copy of x and of dy and a cut of dx, passes of their own that XLA
    fuses onto nothing (at 1,536 lanes 168 rows do not divide 4,096 and
    128 do)."""
    most = _block_rows(k)
    return next((rows for rows in range(most, most // 2, -8)
                 if n % rows == 0), most)


def ln_bwd(x2, dy2, mean, rstd, scale, block_rows=None, mesh=None):
    """x2/dy2: [n, k]; mean/rstd: [n] fp32; scale: [k] fp32 (ones when the
    LN has no scale). Returns (dx [n, k] in x2's dtype, dscale [k] f32,
    dbias [k] f32). `mesh`: a mesh whose `batch` axis alone shards the
    rows and divides n; the kernel then runs per shard, and the sum of
    the shards' partial dscale/dbias rows is left to the caller's graph,
    where XLA may combine its all-reduce with the other gradients'."""
    require_pallas("ln_bwd")
    k = x2.shape[1]
    dx, dg, db = _call(
        x2, dy2, (mean, rstd), scale.reshape(1, k).astype(jnp.float32),
        rms_eps=None, block_rows=block_rows or _block_rows(k),
        interpret=_interpret(), mesh=mesh)
    return dx, jnp.sum(dg[:, 0], axis=0), jnp.sum(db[:, 0], axis=0)


def rms_bwd(x2, dy2, scale, epsilon, block_rows=None, mesh=None):
    """RMSNorm's backward, `nn_ops.rms_norm`'s mathematics: x2/dy2 [n, k],
    scale [k], `epsilon` a Python number. Float32 inside, the statistic
    from x2 itself. Returns (dx [n, k] in x2's dtype, dscale [k] f32);
    `mesh` as `ln_bwd`'s."""
    require_pallas("rms_bwd")
    n, k = x2.shape
    shard = n // max(on_mesh.batch_shards(mesh, n), 1)
    dx, dg = _call(
        x2, dy2, (), scale.reshape(1, k).astype(jnp.float32),
        rms_eps=float(epsilon),
        block_rows=block_rows or _dividing_block_rows(shard, k),
        interpret=_interpret(), mesh=mesh)
    return dx, jnp.sum(dg[:, 0], axis=0)
