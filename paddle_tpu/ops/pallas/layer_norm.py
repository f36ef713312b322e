"""Fused LayerNorm backward as a Pallas TPU kernel.

XLA splits the LN backward into an elementwise dX pass plus separate
sublane-dim reductions for dScale/dBias, materializing the recomputed
fp32 normalized value between them (~30 ms/step across BERT-base's 25 LN
sites, b=256). One kernel pass reads x/dy once (bf16), computes dX, and
emits per-block partial dScale/dBias rows that a trivial [blocks, k] sum
finishes outside. Reference semantics: operators/layer_norm_op.cc grad.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import on_mesh
from .flash_attention import _ceil_to, _interpret, require_pallas


def _kernel(x_ref, dy_ref, mean_ref, rstd_ref, scale_ref, dx_ref, dg_ref,
            db_ref, *, k):
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    mean = mean_ref[...].astype(jnp.float32)  # [Bn, 1]
    rstd = rstd_ref[...].astype(jnp.float32)
    nrm = (x - mean) * rstd
    dyg = dy * scale_ref[...].astype(jnp.float32)  # [1, k] broadcasts
    m1 = jnp.mean(dyg, axis=1, keepdims=True)
    m2 = jnp.mean(dyg * nrm, axis=1, keepdims=True)
    dx_ref[...] = (rstd * (dyg - m1 - nrm * m2)).astype(dx_ref.dtype)
    dg_ref[...] = jnp.sum(dy * nrm, axis=0)[None, None, :]
    db_ref[...] = jnp.sum(dy, axis=0)[None, None, :]


def ln_bwd_viable(n, k):
    # one [Bn, k] row-block ×~6 fp32 temporaries must fit VMEM
    return n >= 1024 and k <= 4096 and k % 128 == 0


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret", "mesh"))
def _call(x2, dy2, mean, rstd, scale, *, block_rows, interpret, mesh):
    """The pallas_call over whole row blocks, per shard of `batch` on a
    `mesh`: dx [n, k] and one partial dscale and dbias row per block.
    Jitted so that the calls of one shape in a step (25 of BERT's 26) are
    traced and lowered once."""
    k = x2.shape[1]

    def run(x2, dy2, mean, rstd, scale):
        n = x2.shape[0]
        np_ = _ceil_to(n, block_rows)
        if np_ != n:
            pad = [(0, np_ - n), (0, 0)]
            x2 = jnp.pad(x2, pad)
            dy2 = jnp.pad(dy2, pad)  # zero dy rows contribute nothing
            mean = jnp.pad(mean, [(0, np_ - n)])
            rstd = jnp.pad(rstd, [(0, np_ - n)])
        rows = pl.BlockSpec((block_rows, k), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
        stat = pl.BlockSpec((block_rows, 1), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
        part = pl.BlockSpec((1, 1, k), lambda i: (i, 0, 0),
                            memory_space=pltpu.VMEM)
        nb = np_ // block_rows
        dx, dg, db = pl.pallas_call(
            functools.partial(_kernel, k=k),
            grid=(nb,),
            in_specs=[rows, rows, stat, stat,
                      pl.BlockSpec((1, k), lambda i: (0, 0),
                                   memory_space=pltpu.VMEM)],
            out_specs=[rows, part, part],
            out_shape=[
                jax.ShapeDtypeStruct((np_, k), x2.dtype),
                jax.ShapeDtypeStruct((nb, 1, k), jnp.float32),
                jax.ShapeDtypeStruct((nb, 1, k), jnp.float32),
            ],
            interpret=interpret,
            name="ln_bwd",
            # The one call of this package that declares no cost
            # (`cost.py`). With 13 FLOPs an element and x, dy, dx, the
            # statistics and the partial rows once (152 MB a call at
            # BERT's [32768, 768]) declared, `bert_base_s128` ran 1.0 to
            # 2.7% slower in every pair on the chip, and at the parent's
            # rate with this one call left out: XLA reads a custom call's
            # cost where it places arrays in VMEM, and the step compiled
            # with the declaration keeps fewer there (PERF.md, PR 35).
        )(x2, dy2, mean.reshape(np_, 1), rstd.reshape(np_, 1), scale)
        return dx[:n], dg, db

    return on_mesh.per_shard(run, mesh, [True, True, True, True, False])(
        x2, dy2, mean, rstd, scale)


def ln_bwd(x2, dy2, mean, rstd, scale, block_rows=None, mesh=None):
    """x2/dy2: [n, k]; mean/rstd: [n] fp32; scale: [k] fp32 (ones when the
    LN has no scale). Returns (dx [n, k] in x2's dtype, dscale [k] f32,
    dbias [k] f32). `mesh`: a mesh whose `batch` axis alone shards the
    rows and divides n; the kernel then runs per shard, and the sum of
    the shards' partial dscale/dbias rows is left to the caller's graph,
    where XLA may combine its all-reduce with the other gradients'."""
    require_pallas("ln_bwd")
    k = x2.shape[1]
    if block_rows is None:
        # ~5 fp32 row-blocks live in the kernel; keep them within ~5 MB of
        # the 16 MB scoped-VMEM budget as k grows (256 rows at k=768)
        block_rows = max(8, min(256, (1 << 18) // k // 8 * 8))
    dx, dg, db = _call(
        x2, dy2, mean, rstd, scale.reshape(1, k).astype(jnp.float32),
        block_rows=block_rows, interpret=_interpret(), mesh=mesh)
    return dx, jnp.sum(dg[:, 0], axis=0), jnp.sum(db[:, 0], axis=0)
