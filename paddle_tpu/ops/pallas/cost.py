"""What a Pallas call declares of its work: the one convention.

Every `pallas_call` of this package but `layer_norm.py`'s two (`ln_bwd`,
`rms_bwd`) and `embed_tgmm` (`layer_norm.py` and `embedding_grad.py` say
what their declarations cost on the chip) passes a `cost_estimate` that a
pure function of its module computes from the call's static shapes,
dtypes and masks, at trace time (a handful of integer products a
lowering; nothing on the hot path). The count travels inside the
compiled step on the custom call that does the work: XLA reads it where
it schedules the step and places arrays in VMEM, and the profiler writes
it beside the call's time, so that an operator's op profile shows the
kernel's FLOP/s and bytes like XLA's own operations' (README, Profiling;
PERF.md section 3 says which metric reads which). Because XLA reads it, a
declaration can move a step: measure the cells that run the kernel, with
and without it, before it goes in.

- `flops` is **useful** work: 2 x the multiply-adds of the products the
  mathematics needs, on the pairs the static masks admit (`causal`,
  `window`; a key bias's refusals are data and not known here), at the
  widths the model has. Not counted: padded lanes and rows, masked pairs
  inside a visited block, a product the kernel makes to get round its
  own layout (a matrix inverse where a solve would do). What a backward
  kernel recomputes *by the algorithm's design* (the probabilities from
  q and k) counts, as it does in the benchmark's own count of the flash
  kernels. Each module says which products it counts. A kernel with no
  product counts one FLOP an arithmetic operation of its formulas an
  element.
- `transcendentals`: the exponentials, reciprocals, logarithms and
  rsqrt's of the same useful work (one exponential an admitted pair, the
  rest a row).
- `bytes_accessed` is the **least** traffic: each operand read once and
  each output written once, at the dtype and the unpadded shape it has
  in HBM (scalars in SMEM left out). A block read again and again by the
  grid is the kernel's doing and not the problem's.

So a share of a roof read against either count cannot honestly pass 100:
a reading above it is a wrong count, never a fast kernel.

**A kernel whose work is data** (`grouped_matmul.py`: the grid visits only
the row tiles that hold assignments, and how many do is a traced array)
cannot count its useful work at trace time. It declares the static
count, every row of its block through the product and every operand and
output once: an *upper bound* on the useful work, which is what XLA's
scheduler needs and what the static masks' rule above would give a mask
that admits everything. A share of a roof read against an upper bound
can pass 100 when tiles are skipped, so no roofline metric is defined on
such a kernel until the live count is read from the program (the op's
`Load`); its time a step and its calls are.
"""

from __future__ import annotations

import math

import numpy as np
from jax.experimental import pallas as pl


def nbytes(*arrays) -> int:
    """Bytes of `(shape, dtype)` pairs, each once."""
    return sum(math.prod(shape) * np.dtype(dtype).itemsize
               for shape, dtype in arrays)


def admitted_pairs(sq, sk, causal=False, causal_offset=0, window=0) -> int:
    """(query, key) pairs of one head's [sq, sk] rectangle that the static
    masks admit: query `qi` sees the keys `ki <= qi + causal_offset` and,
    with a window, only the last `window` of them (the predicate of
    `flash_attention._admitted`, counted and not rounded to blocks)."""
    if not causal:
        return sq * sk
    end = np.arange(sq) + causal_offset + 1  # one past the last key seen
    first = np.clip(end - window, 0, sk) if window else 0
    return int(np.sum(np.clip(end, 0, sk) - first))


def estimate(flops, transcendentals, *arrays) -> pl.CostEstimate:
    """The declaration itself; `arrays` are the `(shape, dtype)` pairs of
    every operand and output, each moved once."""
    return pl.CostEstimate(flops=int(flops),
                           transcendentals=int(transcendentals),
                           bytes_accessed=nbytes(*arrays))
