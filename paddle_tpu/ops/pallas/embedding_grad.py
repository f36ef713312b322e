"""An embedding table's gradient as grouped products over the tokens sorted
by id.

The gradient of `take(w, ids)` is `zeros.at[ids].add(dy)`. XLA lowers it on
the TPU as a sort of the ids, a gather of the cotangent's rows in that
order (widened to float32 first) and a `scatter(indices_are_sorted=true)`
that reads, adds and writes one table row a token in HBM. Here the sums
are products:

1. the ids are sorted with an iota and the cotangent's rows gathered in
   that order *in the dtype they arrive in*;
2. the table is cut into runs of `R` rows (`run_rows`); the sorted tokens
   of run g are contiguous and `sizes[g]` counts them;
3. `dW[g] = onehot(id - g R)^T dy_sorted` over run g's tokens: a 0/1
   matrix `[T, R]` in the cotangent's dtype against `[T, D]`, summed in
   float32 into `[G, R, D]`. That is `grouped_matmul.py::moe_tgmm`'s
   contract (every group visited, an empty one written as zeros, a tile
   that straddles runs masked by a select), so the kernel is that one,
   called under a name of its own, `embed_tgmm`: the expert cells' metrics
   count the events named `moe_tgmm`;
4. `[G, R, D]` reshaped to `[G R, D]` and cut to the table's rows.

A product of a bf16 value with an exact 0 or 1 is the value, and the sums
are float32: the scatter's sums up to the order of the additions. A table
of at most `R` rows is one run, which every order of the tokens sorts, so
it takes neither sort nor gather.

`embedding_grad_viable` says where `ops/nn_ops.py::_lookup_table` takes
this path; XLA's scatter stays the other and the tests' oracle.

The call declares no cost (`cost.py` has the convention; `layer_norm.py`'s
`ln_bwd` and `rms_bwd` are the others without one, for the same reason).
What it would declare is the `tokens x width` additions of the sums, the 0/1 product being one made
to get round a layout, and the 0/1 matrix, the cotangent and the float32
runs once each. With that declared `phi4_mini_flash_vp8_longdoc` read
6.1565 documents/s and `bwd/mul_grad` 80.74 ms a step, with nothing
declared 6.1760 and 80.26 at the same seeds (PERF.md, PR 46: XLA places
the neighbours' arrays by what a call declares), so nothing is.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import on_mesh
from .flash_attention import LANE, _ceil_to
from .grouped_matmul import grouped_matmul_viable, moe_tgmm

# Rows of the table a run, where the table has more.
RUN = 512


def run_rows(vocab):
    """R: `RUN`, or a smaller table's rows rounded up to whole lanes."""
    return min(RUN, _ceil_to(vocab, LANE))


# Lanes of a table row from which the products are taken. A proxy, set
# from one cell on each side (one v5e chip, PERF.md, PR 46), for a cause
# this function cannot see. The scatter's cost a token grows with the
# row's width (0.036 us at 512 lanes, 0.039 at 768, 0.23 to 0.28 at 2,048,
# 0.37 to 0.57 at 2,304, 2.45 at 2,560) while the products pay passes over
# the whole table whatever its rows' width, so narrow rows have the least
# to gain. What made them lose: `transformer_base_s64`'s table (16,384
# tokens into 37,000 x 512 twice a step) is tied to the head, and XLA
# folds the two scatters into the head's gradient in place (`bwd/sum`),
# where the products write a table of their own that is read again.
# Alone the two paths took the same time there (0.585 and 0.537 ms); in
# the step the products were 1.9% slower (3,685.0 -> 3,613.7 pairs/s).
# At 768 lanes, 32,768 tokens into 30,522, 512 and 2 rows, tied as well,
# they were 2.1% faster (`bert_base_s128`: 1,796.5 -> 1,835.1). A table
# of 512 lanes that no head shares may well gain and is refused; nothing
# between 512 and 768 lanes was measured. Both cells guard the number.
MIN_WIDTH = 768


def embedding_grad_viable(rows, vocab, width, dtype, mesh, table_spec=None):
    """Where the grouped products take the scatter's place. `rows` tokens
    into a table `[vocab, width]`, the cotangent in `dtype`; `table_spec`
    is the table's PartitionSpec where the Program states one.

    - the width in whole 128-lane slices, and Mosaic or the interpreter
      to run the kernel (`grouped_matmul_viable`);
    - a bfloat16 cotangent: its product with a 0/1 matrix is exact and
      the sums are float32. A float32 cotangent would need the product at
      full precision, six passes of the MXU, and keeps the scatter;
    - one device, or a mesh that shards `batch` alone and divides the
      tokens, the table whole on every chip: each shard's tokens are a
      whole problem (`on_mesh.per_shard`) and the shards' tables are
      summed outside. A table sharded over its rows or its width keeps
      the scatter, which GSPMD partitions;
    - rows of `MIN_WIDTH` lanes or more (its comment has the two
      measurements and what they stand for)."""
    if jnp.dtype(dtype) != jnp.dtype(jnp.bfloat16):
        return False
    if table_spec is not None and any(a is not None for a in table_spec):
        return False
    return (rows >= 1 and vocab >= 1
            and on_mesh.batch_shards(mesh, rows) > 0
            and grouped_matmul_viable(run_rows(vocab), width, dtype)
            and width >= MIN_WIDTH)


def _runs(ids, dy, vocab):
    """ids: [T] int32 in `[0, vocab)`; dy: [T, D]. Returns [G, R, D]
    float32, the table's gradient run by run (rows past `vocab` zeros)."""
    run = run_rows(vocab)
    groups = pl.cdiv(vocab, run)
    if groups > 1:
        tokens = jax.lax.iota(jnp.int32, ids.shape[0])
        ids, order = jax.lax.sort((ids, tokens), num_keys=1)
        dy = dy.at[order].get(mode="promise_in_bounds", unique_indices=True)
    of_run = (ids // run)[:, None] == jnp.arange(groups)[None, :]
    sizes = jnp.sum(of_run, axis=0, dtype=jnp.int32)
    onehot = ((ids % run)[:, None] == jnp.arange(run)[None, :]
              ).astype(dy.dtype)
    # `moe_tgmm`'s contract, check, tiling and kernel under this layer's
    # name, nothing declared (the module's docstring has both reasons)
    return moe_tgmm(onehot, dy, sizes, name="embed_tgmm", declare=False)


def embedding_grad(ids, dy, vocab, mesh=None):
    """`zeros([vocab, D]).at[ids].add(dy)` in float32. ids: [T] integers in
    `[0, vocab)`; dy: [T, D]. On a mesh that shards `batch` alone, ids and
    dy split over the tokens, each shard's table computed where its
    tokens are and the shards' tables added up outside (GSPMD's
    all-reduce, where the scatter's was)."""
    shards = on_mesh.batch_shards(mesh, ids.shape[0])
    if not shards:
        raise ValueError(
            f"embedding_grad: {ids.shape[0]} tokens on the mesh "
            f"{dict(mesh.shape)}: needs one that shards `batch` alone")
    dw = on_mesh.per_shard(
        lambda i, d: _runs(i, d, vocab), mesh, (True, True)
    )(ids.astype(jnp.int32), dy)
    width = dy.shape[1]
    dw = dw.reshape(shards, -1, width)
    dw = dw[0] if shards == 1 else jnp.sum(dw, axis=0)
    return dw[:vocab]
