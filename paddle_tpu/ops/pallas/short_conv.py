"""The short causal convolution's backward in one Pallas TPU kernel.

`ops/linear_attn_ops.py::short_conv` is `SiLU(sum_i w[:, i] x_{t-width+1+i}
+ bias)` over `[b, s, c]`, depthwise, or that sum with no activation. Its
backward in XLA is, a
convolution, a float32 copy of x, one fusion that makes the taps again,
writes the float32 `dpre` and reduces the taps' gradients, and one more
that reads `dpre` at four offsets for dx: 0.3 GB through HBM a
convolution at 4,096 x 4,096, read at shifted rows. It ran at 0.27 or at
0.9 ms by whether XLA had placed the float32 operands in VMEM, which
turned on what else the step held (PERF.md, PR 46: 8.3 and 18.1 ms a step
in `kimi_linear_ep32_s4096`, twelve convolutions, with no change to this
code). Here a grid step holds a row's whole time axis for 128 channels
in VMEM: x and the cotangent are read once as they arrive, the taps are
sublane rotations of the one float32 copy (`pltpu.roll`, the rows that
wrapped masked to the zero state), and dx, the taps' gradients and the
bias's leave from the same pass. 96 MB through HBM where x is bf16.

`short_conv_viable` says where the backward takes the kernel; the
written-out XLA backward of `short_conv` stays the other path and the
tests' oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import cost, on_mesh
from .flash_attention import LANE, _interpret, require_pallas

_VMEM_LIMIT = 64 << 20  # of v5e's 128 MiB; the default is 16 MiB
# rows of the partial the kernel writes a batch row and a channel block:
# the taps' gradients, then the bias's, then zeros
PARTIAL_ROWS = 8
# a dozen float32 `[s, 128]` arrays live in a grid step
MAX_SEQ = 8192


def short_conv_viable(batch, s, c, width, mesh):
    """The kernel where Mosaic or the interpreter can run it, the channels
    come in whole 128-lane slices, the time axis in whole packed
    sublanes of bf16 and short enough for VMEM, the taps and the bias
    fit the partial's rows, and on one device or a mesh that shards
    `batch` alone and divides the rows."""
    from .flash_attention import _use_pallas

    return (_use_pallas() and c % LANE == 0 and s % 16 == 0
            and 16 <= s <= MAX_SEQ and 1 <= width < PARTIAL_ROWS
            and on_mesh.batch_shards(mesh, batch) > 0)


def _kernel(x_ref, w_ref, b_ref, dy_ref, dx_ref, part_ref, *, width, silu):
    x = x_ref[...].astype(jnp.float32)  # [s, 128]
    dy = dy_ref[...].astype(jnp.float32)
    s = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)

    def back(t, k):  # t_{r-k}, the zero state before the row's start
        return t if k == 0 else jnp.where(
            row >= k, pltpu.roll(t, k, 0), 0.0)

    def ahead(t, k):  # t_{r+k}, nothing past the row's end
        return t if k == 0 else jnp.where(
            row < s - k, pltpu.roll(t, s - k, 0), 0.0)

    taps = [back(x, width - 1 - i) for i in range(width)]
    dpre = dy
    if silu:
        pre = b_ref[...] + sum(taps[i] * w_ref[i:i + 1, :]
                               for i in range(width))
        sig = jax.nn.sigmoid(pre)
        dpre = dy * (sig * (1 + pre * (1 - sig)))
    part_ref[...] = jnp.zeros(part_ref.shape, jnp.float32)
    for i in range(width):
        part_ref[i:i + 1, :] = jnp.sum(dpre * taps[i], axis=0, keepdims=True)
    part_ref[width:width + 1, :] = jnp.sum(dpre, axis=0, keepdims=True)
    dx = sum(ahead(dpre, width - 1 - i) * w_ref[i:i + 1, :]
             for i in range(width))
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _cost(b, s, c, width, x_dtype, dy_dtype, silu):
    """`cost.py`'s convention for a kernel with no product, one FLOP an
    arithmetic operation of the formulas an element: the taps made again
    (`2 width`), the bias, the SiLU's derivative (8), the taps' gradients
    and dx (`2 width` each) and the bias's: `6 width + 10`; the logistic
    one exponential and one reciprocal. Without the SiLU the taps'
    gradients, dx and the bias's alone: `4 width + 1`, no transcendental.
    x, the cotangent and dx once, the taps, the bias and the partial rows
    beside them."""
    return cost.estimate(
        b * s * c * (6 * width + 10 if silu else 4 * width + 1),
        2 * b * s * c if silu else 0,
        ((b, s, c), x_dtype), ((b, s, c), dy_dtype), ((b, s, c), x_dtype),
        ((width + 1, c), jnp.float32),
        ((b, PARTIAL_ROWS, c), jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret", "mesh", "silu"))
def _call(x, w_rows, b_row, dy, *, interpret, mesh, silu):
    """One jitted call, as the other kernels have: the convolutions of
    one shape in a step are traced and lowered once."""
    width, c = w_rows.shape

    def run(x, w_rows, b_row, dy):
        b, s, _ = x.shape
        rows = pl.BlockSpec((None, s, LANE), lambda i, j: (i, 0, j))
        return pl.pallas_call(
            functools.partial(_kernel, width=width, silu=silu),
            grid=(b, c // LANE),
            in_specs=[rows,
                      pl.BlockSpec((width, LANE), lambda i, j: (0, j)),
                      pl.BlockSpec((1, LANE), lambda i, j: (0, j)),
                      rows],
            out_specs=[rows, pl.BlockSpec((None, PARTIAL_ROWS, LANE),
                                          lambda i, j: (i, 0, j))],
            out_shape=[
                jax.ShapeDtypeStruct(x.shape, x.dtype),
                jax.ShapeDtypeStruct((b, PARTIAL_ROWS, c), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
            name="short_conv_bwd",
            cost_estimate=_cost(b, s, c, width, x.dtype, dy.dtype, silu),
        )(x, w_rows, b_row, dy)

    return on_mesh.per_shard(run, mesh, (True, False, False, True))(
        x, w_rows, b_row, dy)


def short_conv_bwd(x, w, bias, dy, mesh=None, activation="silu"):
    """x, dy: [b, s, c]; w: [c, width]; bias: [c] or None. Returns dx in
    x's dtype, dw [c, width] and dbias [c] (None without a bias) in
    float32: the gradients of `SiLU(taps(x, w) + bias)`, or with
    `activation` "none" of `taps(x, w) + bias`, under the cotangent dy,
    everything inside in float32."""
    require_pallas("short_conv_bwd")
    b, s, c = x.shape
    width = w.shape[1]
    if not short_conv_viable(b, s, c, width, mesh) or dy.shape != x.shape:
        raise ValueError(
            f"short_conv_bwd: x {x.shape}, dy {dy.shape}, {width} taps on "
            f"the mesh {None if mesh is None else dict(mesh.shape)}: needs "
            f"whole {LANE}-lane channels, 16 to {MAX_SEQ} tokens in whole "
            f"16s, under {PARTIAL_ROWS} taps, one device or a mesh that "
            "shards `batch` alone")
    b_row = (jnp.zeros((1, c), jnp.float32) if bias is None
             else bias.astype(jnp.float32)[None, :])
    dx, part = _call(x, w.astype(jnp.float32).T, b_row, dy,
                     interpret=_interpret(), mesh=mesh,
                     silu=activation == "silu")
    part = jnp.sum(part, axis=0)
    return (dx, part[:width].T,
            None if bias is None else part[width])
