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


# The static masks' predicate, stated here once: the flash kernels mask a
# block of scores by `admits`, their grids skip the blocks outside
# `first_key` .. `last_key` of a query block's ends and `first_query` ..
# `last_query` of a key block's, and `admitted_pairs` counts by the keys'
# pair. The queries' pair is the keys' read along the other axis
# (tests/test_flash_attention.py holds the two against each other by
# brute force).
#
# A query `qi` sees the keys up to the end of its own granule of `granule`
# rows, shifted by `causal_offset`, and with a window only the last
# `window` of them:
#
#     last_key(qi) = qi // granule * granule + granule - 1 + causal_offset
#     admitted iff ki <= last_key(qi)
#                  and (no window or last_key(qi) - ki < window)
#
# Granule 1 is the causal mask (`ki <= qi + causal_offset`), a granule B
# a mask causal by blocks of B rows and full inside a block (block
# diffusion's: `block_diffusion_rules`). Every argument may be a Python
# int, a NumPy array or a traced value; the granule and the window are
# static. None is clipped to the rows there are. At granule 1 each is the
# expression the kernels and the bands had before there was a granule,
# term for term, so such a call traces the text it traced
# (`tests/test_parents_jaxprs.py`).


def last_key(qi, causal_offset=0, granule=1):
    """The last key query `qi` admits."""
    if granule == 1:
        return qi + causal_offset
    if granule & (granule - 1) == 0:  # no division inside a kernel
        return (qi | (granule - 1)) + causal_offset
    return qi // granule * granule + granule - 1 + causal_offset


def first_key(qi, causal_offset, window, granule=1):
    """The first key query `qi` admits under a window."""
    return last_key(qi, causal_offset, granule) - window + 1


def first_query(ki, causal_offset=0, granule=1):
    """The first query that admits key `ki`: the first row of the first
    granule whose end reaches `ki - causal_offset` (floor division)."""
    if granule == 1:
        return ki - causal_offset
    return -((granule - 1 - (ki - causal_offset)) // granule) * granule


def last_query(ki, causal_offset, window, granule=1):
    """The last query that admits key `ki` under a window: the last row
    of the last granule whose end stays under `ki - causal_offset +
    window`."""
    if granule == 1:
        return ki - causal_offset + window - 1
    return ((ki - causal_offset + window - granule) // granule * granule
            + granule - 1)


def admits(qi, ki, causal_offset=0, window=0, granule=1):
    """Whether query `qi` admits key `ki` (arrays that broadcast)."""
    keep = last_key(qi, causal_offset, granule) >= ki
    if window:  # the end formed again, as ever: the compilers merge the two
        keep = keep & (last_key(qi, causal_offset, granule) - ki < window)
    return keep


def admitted_pairs(sq, sk, causal=False, causal_offset=0, window=0,
                   granule=1) -> int:
    """(query, key) pairs of one head's [sq, sk] rectangle that the static
    masks admit (`admits`, counted and not rounded to blocks); every pair
    without `causal`."""
    if not causal:
        return sq * sk
    qi = np.arange(sq)
    first = (np.clip(first_key(qi, causal_offset, window, granule), 0, sk)
             if window else 0)
    return int(np.sum(
        np.clip(last_key(qi, causal_offset, granule) + 1, 0, sk) - first))


def block_diffusion_rules(block) -> dict:
    """Block diffusion's training mask over a sequence's noisy and clean
    copy, cut in blocks of `block`, as three rules of granule `block`,
    each `(causal_offset, window)` over an [L, L] rectangle: `own`, a
    noisy row on its own noisy block, both ways; `past`, a noisy row on
    the clean blocks before its own, whole; `clean`, a clean row on the
    clean blocks up to and with its own. No row sees a noisy block that
    is not its own. What the attention op masks by, calls the flash
    kernels under and counts (`ops/fused_ops.py`)."""
    return {"own": (0, block), "past": (-block, 0), "clean": (0, 0)}


def block_diffusion_pairs(length, block) -> int:
    """Pairs a head admits under `block_diffusion_rules` over the
    2 x `length` rows: L B + (L^2 - L B) / 2 + (L^2 + L B) / 2."""
    return sum(admitted_pairs(length, length, True, offset, window, block)
               for offset, window in block_diffusion_rules(block).values())


def estimate(flops, transcendentals, *arrays) -> pl.CostEstimate:
    """The declaration itself; `arrays` are the `(shape, dtype)` pairs of
    every operand and output, each moved once."""
    return pl.CostEstimate(flops=int(flops),
                           transcendentals=int(transcendentals),
                           bytes_accessed=nbytes(*arrays))
