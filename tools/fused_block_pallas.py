"""ResNet fused-block Pallas experiment (VERDICT round-4 #3: "test the
fused-block bet").

The ResNet roofline (BASELINE.md round-4) says the workload is
HBM-pass-structure-bound (~60 GB/step over ~13 mandatory passes) and no
XLA flag moves it. The two pass-cuts a hand kernel could buy, each A/B'd
here in isolation on the chip at the top bottleneck-block 1x1-conv
shapes (1x1 convs are plain matmuls — the MXU shape where a Pallas
kernel can plausibly match XLA):

A. PROLOGUE: z = relu(x * scale + shift [+ residual]); y = z @ w
   — BN-apply (+relu+residual) executed in the conv's input read, vs the
   XLA formulation of exactly the same math (which XLA may well fuse
   itself — a parity result here is the honest negative evidence).

B. EPILOGUE STATS: y = x @ w; sum_c = sum(y, rows); sumsq_c = sum(y^2)
   — the NEXT BN's batch stats accumulated while y is still in VMEM,
   vs XLA's conv-then-reduce (an extra full read of y from HBM).

Usage: python tools/fused_block_pallas.py [--interpret]
Prints one JSON line per (shape, experiment, path).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

INTERPRET = "--interpret" in sys.argv

# top bottleneck-block 1x1 shapes, ResNet-50 b=256 NHWC (M = b*h*w)
SHAPES = [
    ("stage2_reduce", 256 * 56 * 56, 256, 64),
    ("stage3_reduce", 256 * 28 * 28, 512, 128),
    ("stage4_reduce", 256 * 14 * 14, 1024, 256),
]


def _prologue_kernel(x_ref, scale_ref, shift_ref, res_ref, w_ref, y_ref):
    x = x_ref[...].astype(jnp.float32)
    z = x * scale_ref[...].astype(jnp.float32) + shift_ref[...].astype(
        jnp.float32)
    z = jnp.maximum(z + res_ref[...].astype(jnp.float32), 0.0)
    y_ref[...] = jax.lax.dot(
        z.astype(x_ref.dtype), w_ref[...],
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32,
    ).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m",))
def fused_prologue_conv1x1(x, scale, shift, res, w, block_m=512):
    """relu(x*scale+shift+res) @ w in one kernel; x/res [M, K], w [K, N]."""
    m, k = x.shape
    n = w.shape[1]
    grid = (m // block_m,)
    return pl.pallas_call(
        _prologue_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, k), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((block_m, k), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, n), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_m, n), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=INTERPRET,
    )(x, scale.reshape(1, k), shift.reshape(1, k), res, w)


def _stats_kernel(x_ref, w_ref, y_ref, sum_ref, sumsq_ref):
    i = pl.program_id(0)
    y = jax.lax.dot(
        x_ref[...], w_ref[...],
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32,
    )
    y_ref[...] = y.astype(y_ref.dtype)

    @pl.when(i == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        sumsq_ref[...] = jnp.zeros_like(sumsq_ref)

    sum_ref[...] += jnp.sum(y, axis=0)[None, :]
    sumsq_ref[...] += jnp.sum(y * y, axis=0)[None, :]


@functools.partial(jax.jit, static_argnames=("block_m",))
def conv1x1_with_stats(x, w, block_m=512):
    """y = x @ w plus per-channel sum / sum-of-squares accumulated while
    the output block is still in VMEM (the next BN's batch stats)."""
    m, k = x.shape
    n = w.shape[1]
    grid = (m // block_m,)
    return pl.pallas_call(
        _stats_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, k), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, n), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_m, n), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), x.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        interpret=INTERPRET,
    )(x, w)


# ------------------------------------------------------------ XLA twins


@functools.partial(jax.jit, static_argnames=())
def xla_prologue(x, scale, shift, res, w):
    z = jnp.maximum(
        x.astype(jnp.float32) * scale + shift + res.astype(jnp.float32), 0.0
    ).astype(x.dtype)
    return jnp.dot(z, w, preferred_element_type=jnp.float32).astype(x.dtype)


@jax.jit
def xla_stats(x, w):
    y = jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)
    yf = y.astype(jnp.float32)
    return y, jnp.sum(yf, axis=0), jnp.sum(yf * yf, axis=0)


def _chained(fn, n_rep):
    """n_rep dependent executions inside ONE jit — a single dispatch, so
    host dispatch doesn't drown the ~1-2 ms kernels. The
    scalar feedback multiply adds one identical elementwise pass to BOTH
    paths."""

    @jax.jit
    def run(x, *rest):
        def body(_, x):
            out = fn(x, *rest)
            leaf = jax.tree.leaves(out)[0]
            return x * (1.0 + 0.0 * leaf[0, 0].astype(x.dtype))

        x = jax.lax.fori_loop(0, n_rep, body, x)
        return x[0, 0].astype(jnp.float32)

    return run


def _time(fn, *args, iters=20, windows=3):
    run = _chained(fn, iters)
    np.asarray(run(*args))  # compile
    dts = []
    for _ in range(windows):
        t0 = time.time()
        np.asarray(run(*args))
        dts.append((time.time() - t0) / iters)
    return min(dts) * 1e3  # ms


def main():
    rng = np.random.RandomState(0)
    results = []
    for name, m, k, n in SHAPES:
        if INTERPRET:
            m = min(m, 2048)
        x = jnp.asarray(rng.randn(m, k).astype(np.float32),
                        jnp.bfloat16)
        res = jnp.asarray(rng.randn(m, k).astype(np.float32) * 0.1,
                          jnp.bfloat16)
        w = jnp.asarray(rng.randn(k, n).astype(np.float32) * 0.05,
                        jnp.bfloat16)
        scale = jnp.asarray(rng.rand(k).astype(np.float32) + 0.5)
        shift = jnp.asarray(rng.randn(k).astype(np.float32) * 0.1)

        # correctness first
        yp = np.asarray(fused_prologue_conv1x1(x, scale, shift, res, w),
                        np.float32)
        yx = np.asarray(xla_prologue(x, scale, shift, res, w), np.float32)
        err = np.abs(yp - yx).max() / max(np.abs(yx).max(), 1e-6)
        assert err < 5e-2, (name, "prologue", err)

        ys, s1, s2 = conv1x1_with_stats(x, w)
        yxs, xs1, xs2 = xla_stats(x, w)
        np.testing.assert_allclose(np.asarray(s1).reshape(-1),
                                   np.asarray(xs1), rtol=2e-2, atol=2.0)
        np.testing.assert_allclose(np.asarray(ys, np.float32),
                                   np.asarray(yxs, np.float32), rtol=5e-2,
                                   atol=1e-2)

        if not INTERPRET:
            t_pal = _time(fused_prologue_conv1x1, x, scale, shift, res, w)
            t_xla = _time(xla_prologue, x, scale, shift, res, w)
            results.append({"shape": name, "exp": "prologue",
                            "pallas_ms": round(t_pal, 3),
                            "xla_ms": round(t_xla, 3),
                            "speedup": round(t_xla / t_pal, 3)})
            print(json.dumps(results[-1]), flush=True)

            t_pal = _time(conv1x1_with_stats, x, w)
            t_xla = _time(xla_stats, x, w)
            results.append({"shape": name, "exp": "epilogue_stats",
                            "pallas_ms": round(t_pal, 3),
                            "xla_ms": round(t_xla, 3),
                            "speedup": round(t_xla / t_pal, 3)})
            print(json.dumps(results[-1]), flush=True)
        else:
            print(json.dumps({"shape": name, "correctness": "ok",
                              "prologue_err": float(err)}), flush=True)


if __name__ == "__main__":
    main()
