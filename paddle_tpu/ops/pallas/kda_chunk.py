"""The KDA chunk as a Pallas kernel pair: the gated delta rule's work
inside a chunk and the state it carries from chunk to chunk, in VMEM.

The mathematics is `ops/linear_attn_ops.py`'s module docstring, term for
term; `kda_chunked` there is the plain path and this file's test oracle.
What differs is where things live. XLA's lowering relays q, k, v, g out
to `[b, h, n, c, d]`, runs a dozen float32 passes and small batched
products over them, a triangular-solve custom call, and a 64-step
`lax.scan` for the state. Here one grid step holds `CHUNKS_PER_STEP`
chunks of one head, `[rows, 128]` blocks cut from the `[b, s, h*128]`
arrays the op's inputs arrive in (no relayout on either side of the
call), and the state `S^T` `[dv, dk]` float32 in a VMEM scratch that
lives across the sequential chunk axis of the grid and is zeroed at
chunk 0.

**The op's float32 prologue is the kernels' too** (PR 65), as far as it is
a row's: they read q, k and beta's logits in the dtype and layout the
projections wrote them (bf16 under AMP) and make
`x * rsqrt(sum(x*x) + eps)` over a head's lanes and
`beta_scale * sigmoid(b)` in VMEM, float32, once over the grid step's
stacked rows (`_gate_rows`: `linear_attn_ops._prologue` formula for
formula, which stays the plain path's and the oracle's); a head's decay
too, `-exp(A_log) * softplus(a + dt_bias)` on its column, from its logits
and two `[1, h]` float32 blocks. The backward makes them again from the
same arrays and takes the sweep's gradients back through them before
they leave (`_gate_grads`). No float32 copy of q, k, beta or a head's
decay crosses HBM in either direction, and what the backward keeps is
the projections' arrays and the chunks' states. The gradients of q and k
leave in the dtype those arrived in, which is the cast XLA's `astype`
gradient made on the same float32 numbers (float32 a value head under
groups); a head's decay logits' and beta's leave as float32 rows a
chunk, and A_log's terms (`dg * g`) as a third, so that the parameters'
gradients stay float32 sums of float32 terms, which XLA sums. **A decay a
channel is not the kernels': it stays gated by XLA** (`kda_gate` in
`kda_mixer_core`, which hands over the float32 log decay `[b, s, h*dk]`
as an operand, 67 MB a layer at Kimi's shape, kept for the backward,
and its gradient leaves float32 the same way: in Kimi's lowering the
kernels make the norms and beta and nothing of the decay). Decided by
measurement: gated in VMEM (softplus over `[256, 128]` a grid step, the
sigmoid again backward, the parameters' terms as a row of 128 lanes a
grid step) Kimi's cell read 6.5617 and 6.5319 documents/s and the pair
19.04 ms a step, with XLA's gate 6.5875 and 6.5883 and 18.25 ms plus
0.41 ms of XLA's, for 0.28 GB more at the peak (the chip runs of refused
PR 64's builder, one call, `chiprun_out/pr64b/`; PERF.md, PR 65): the
kernels' vector units pay more for the gate than HBM does for one 67 MB
array a layer each way. So the decay operand is a head's logits with
`(A_log, dt_bias)` beside it, or a channel's float32 log decay with
nothing beside it, and `kda_chunk` raises on any other pairing. Rows
padded up to a whole grid step have logits of 0, so a beta of
`beta_scale / 2` and a head's decay of its own: they stand behind the
row's last token with q = k = v = 0, and change no output and no
gradient.

A grid step is stated in three parts. **What is a row's** (`_rows`): the
cumulative log-decay `G` (no sum crosses a chunk), `exp(G)`,
`exp(G_C - G)`, `exp(G_C)`, each level's `e`, `k*e` and `q*e`, the blocks
of four's decays, rotated keys and lane sums, `Aq`'s diagonal, `beta v`,
`beta k exp(G)`, `q exp(G)`, `k exp(G_C - G)`, once over the step's blocks
whole, `[steps*64, 128]` (beta's and a head's decay's column taken out of
their `[steps*64, h]` blocks once): one equation for any width, and a
row's arithmetic is what a chunk alone would do, so the bits are. **What
is a chunk's own from no state** (`_state_free`): it cuts its 64 rows of
those (`_chunk_rows`, where `k*e` and `q*e` are cast to what a product
reads: cast on the stacked rows they cost the chain four cycles) and
states the four levels' `[64, 64]` products and their `where`s, the solve,
product by product across the step's chunks (`_inverse`), `Aq` and `W`.
The `[64, 64]` masks are made once a kernel (`_pair_masks`). **What starts
from the state** follows chunk by chunk, copies of the chunk's code
(`_chunk_fwd`, `_chunk_bwd`: the order of the products with the state is
the order of the chunks, and hoisting them across chunks was slower); what
follows the backward sweep's last product and is sums alone (dG's last
row, the lanes' sum, the reversed cumulative sum, the casts and stores)
runs once over the stacked rows again (`_sweep_tail`). The host lowers
every equation of the body at every start of a job, compile cache or not:
a further chunk a step adds 127 equations forward and 312 backward (324
with a decay a head) where a whole copy added 344 and 609
(`tools/kda_vreg_count.py`; `tests/test_kda_kernel.py` holds them to 230
and 500, and what is stated once, the prologue since PR 65 included, to
400).

Per chunk, all in VMEM:

- `A` and `Aq` as masked products, one a level of a split carried
  down from the chunk to blocks of LEAF = 4 rows (`_scores`,
  `_query_scores`). At the level of blocks of m rows (m = 64, 32, 16,
  8) a pair i > j whose rows lie in the two halves of one block has its
  exponent split at the last row of the lower half: `exp(G_i - G_ref)`
  and `exp(G_ref - G_j)` are both at most 1, as in `kda_chunked`'s
  sub-chunks, and here at every distance down to the blocks of 4. A row
  is in one half a level, so a level is one [64, 128] array of
  exponentials and one product `(k*e) (k*e)^T` (and `(q*e) (k*e)^T`),
  masked to its own pairs; the levels' products do not depend on one
  another, and each pair j < i outside the blocks of 4 belongs to one.
  The six pairs inside a block of 4, three distances, are formed
  directly, `exp(G_i - G_j)` masked before the `exp`, times `k_j`,
  summed over the lanes: three [64, 128] arrays (four levels are one
  product a unit of the MXU; a fifth and a sixth, down to pairs of
  rows, waited behind them and measured slower: PERF.md, PR 50). `Aq`'s
  diagonal is a sum over the lanes. Nothing is clamped.
- `T = (I + Diag(beta) A)^-1` by block forward substitution, doubling
  the block from 2 to 64 (`_inverse`: ten 64x64 products, no loop over
  rows; each waits for the one before, and the step's other chunks'
  fill the wait). `[Wv, Wk] = T Diag(beta) [V, K exp(G)]`.
- `U`, `O` and the next state from the state in the scratch. The state
  is kept transposed so that its decay `Diag(exp(G_C))` is a product
  with a row over the lanes and no product needs a transpose.

The backward is a reverse sweep over the chunks with `dS^T` in the
scratch. It rebuilds the chunk's `G`, `A`, `Aq`, `T`, `W`, `U` from q, k,
v, g, beta and the state the chunk started from, which the forward wrote
(`[b*h, n, dv, dk]` float32, 134 MB a layer at 4,096 tokens and 32
heads), and writes dq, dk, dv, dg and dbeta. The gradient of the solve
is the transposed solve, `Lambda = T^T [dWv, dWk]`, with the `T` just
rebuilt; the gradients of `A` and `Aq` go back level by level, two
products a level, and through the blocks of 4 directly, with the factors
the rebuilt forward kept.

**A decay a head, key heads by groups** (Gated DeltaNet; the kernels are
then named `gdn_fwd` and `gdn_bwd` in a trace). `g` arrives `[b, s, h]`
and its block is beta's, `[rows, h]`: this head's column is taken out by a
mask and a sum over the lanes and written along the 128 lanes in VMEM
(`_operands`), after which a chunk is computed exactly as above, levels,
blocks of four and all; its gradient is summed over the lanes before the
reversed adds and leaves as a row a chunk, as beta's does. A `[64, 1]`
column fills the registers `[64, 128]` fills, so the exponentials cost
what they would on the column. With `h_k` key heads under `h` value
heads the blocks of q and k are cut at lane slice `(n % h) // group` of
`[b, s, h_k*128]` arrays: a key head's block is read (and normed) once for
each of its value heads and nothing is repeated in HBM. dq and dk are
written a value head, `[b, s, h*128]` float32, the norm's gradient already
applied (it is linear in them), and XLA adds each group's and casts
(measured
against nothing: accumulating them in VMEM across a group's grid steps
would want the group innermost in the grid and a state a group member in
the scratch; the two arrays are 0.27 GB written and read once a layer at
4,096 tokens, a third of a millisecond at the chip's bandwidth). With
one decay a head `exp(G_i - G_j)` is one `[64, 64]` array a chunk and `A`
and `Aq` could each be one masked product times it, with no levels and
no blocks of four; PERF.md section 7 (PR 50) sized that at 1.3 ms a step
of three layers at most, the levels stubbed, because the chain of
dependent 64-row products bounds the kernels either way, and it is not
built. With a decay a channel and a key head a value head the calls, the
kernels and their declarations are what they were.

**Heads that are no tile** (PR 63: Olmo-Hybrid's 96 key lanes and 192 value
lanes; with a decay a head and a key head a value head alone, `gdn_fwd`
and `gdn_bwd`). A block of 96 lanes is no block Mosaic takes, so a grid
step holds `step_heads` heads side by side, the fewest whose key lanes and
value lanes both end on a tile's edge (4: blocks of 384 and 768 lanes of
the same `[b, s, h*d]` arrays, nothing relaid or padded in HBM), cuts each
head's lanes out in VMEM and pads them with zeros to whole tiles
(`_narrow_operands`), and stacks the heads along the rows: `_rows`,
`_state_free` and the solve's lockstep then run over the step's heads as
they run over a step's chunks above, each head with a state of its own in
the scratch (`_fwd_narrow`, `_bwd_narrow`). Zeros in the padded lanes stay
zeros through every product, lane sum, solve and state, so a head's
results are the unpadded mathematics; they, the gradients and the chunks'
states leave at the lanes the head has, and `_cost` declares those. Where
the heads are no multiple of the step's, the last step's blocks hang over
the arrays' edge: the empty head slots compute on what lies in VMEM and
are stored nowhere. With heads of 128 lanes the calls, the kernels and
their declarations are what they were (`tests/test_gdn_kernel.py` pins
their jaxprs).

Precision is the op's: every `exp`, mask, sum and the state are float32.
The cumulative log-decay `G` is summed in the kernel in float32, by
shifted adds over the chunk's rows (and `dG` back by the same adds
reversed), never by a triangular product. A product reads its operands
as the backend's default precision reads a float32 product: bf16 on a
TPU, float32 elsewhere and wherever `jax.default_matmul_precision` asks
for more; it accumulates in float32.
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import cost
from .flash_attention import LANE, _interpret, _use_pallas, require_pallas

CHUNK = 64
LEAF = 4  # rows of the blocks whose pairs `_leaf_pairs` forms directly
TILE = 8  # sublanes of a float32 register: a rotation stays inside one
# Chunks a grid step, and the width of the lockstep (`_inverse`): the ten
# dependent products of a solve wait 125 to 131 cycles each from push to
# pop, and the step's chunks share those waits. Four where PR 52 had two:
# the pair 16.66 ms a step where two have 21.62 in Kimi's cell and 13.19
# where 17.34 in Qwen3-Next's, 3.0% more documents a second in both (five
# pairs a cell, PERF.md, PR 54), the results the same to the last bit.
# What a wider step costs is the host, which lowers every equation of the
# body at every start of a job: with the rows' arithmetic stated once
# (`_rows`) a further chunk is 127 equations forward and 311 backward
# where a whole copy was 344 and 609, the backward kernel's lowering read
# 1.20 to 1.24 s at two and 1.45 to 1.50 at four on the chip's host (the
# plain four 2.25), and warm `setup_s` +2.3% and +1.9% (medians of five;
# `step_lower_s` +1.1 and +0.8 s by one launch, +0.3 by another: it
# spreads by 0.4 s from run to run). Eight measured 14.46 ms and 1.4% more
# in Kimi's cell for `step_lower_s` 3.7 -> 7.9 s and `setup_s` +13%: four.
CHUNKS_PER_STEP = 4

_NN = ((1,), (0,))  # [m, k] x [k, n]
_NT = ((1,), (1,))  # [m, k] x [n, k]
_TN = ((0,), (0,))  # [k, m] x [k, n]


# The most chunks a grid step may hold where it holds several heads
# (`step_heads`): what the host lowers grows with them.
MAX_UNITS = 8


def step_heads(heads, d_k, d_v):
    """The heads a grid step holds where a head is no tile: the fewest
    whose key lanes and whose value lanes both end on a tile's edge, so
    that the step's blocks are whole tiles of the `[b, s, h*d]` arrays (4
    at 96 and 192 lanes: 384 and 768), or all of them where they are
    fewer (a block as wide as its array is any width). Where the heads
    are no multiple of it the last step's block hangs over the arrays'
    edge, and what it computes there is stored nowhere."""
    whole = next(n for n in range(1, LANE + 1)
                 if n * d_k % LANE == 0 and n * d_v % LANE == 0)
    return min(whole, heads)


def kda_chunk_viable(s, d_k, d_v, heads=None, key_heads=None,
                     per_head=False):
    """The shapes and backends the kernels are built for, with Mosaic (or
    the interpreter) there to run them: a head that is one 128-lane slice
    of q, k, g and one of v, with a decay a channel or a head and key
    heads by groups; or, with a decay a head and a key head a value head
    (`gdn_fwd`, `gdn_bwd` alone), key heads of up to 128 lanes and value
    heads of up to 256, both multiples of 8 (96 and 192: a head's lanes
    are cut out of the step's block in VMEM and padded there with zeros to
    whole tiles), as long as `step_heads` of them are at most `MAX_UNITS`.
    Any length: rows are padded to whole grid steps."""
    if s < 1 or not _use_pallas():
        return False
    if d_k == LANE and d_v == LANE:
        return True
    return bool(per_head and heads and key_heads == heads
                and d_k % TILE == 0 and d_v % TILE == 0
                and d_k <= LANE and d_v <= 2 * LANE
                and step_heads(heads, d_k, d_v) <= MAX_UNITS)


def layout(heads, d_k, d_v):
    """(the heads a grid step holds side by side, 0 where a head is a tile
    and the step holds one; heads x key lanes x value lanes of the states
    as the kernels' layout multiplies them: whole tiles a head, and whole
    grid steps of heads)."""
    if (d_k, d_v) == (LANE, LANE):
        return 0, heads * d_k * d_v
    held = step_heads(heads, d_k, d_v)
    return held, -(-heads // held) * held * _tiles(d_k) * _tiles(d_v)


def lockstep_chunks(s, narrow=0):
    """The chunks of one head that a grid step of a call over `s` tokens a
    row holds: 1 where the row is one chunk and nothing can be hidden.
    `narrow`: the heads the step holds side by side (`step_heads`; 0 where
    a head is a tile and the step holds one), which share the width of the
    lockstep. The chunks solved together are this times the heads (gauge
    `kda_lockstep_chunks`)."""
    return min(max(1, CHUNKS_PER_STEP // max(narrow, 1)), -(-s // CHUNK))


def _product_dtype():
    """What a float32 product reads at the backend's default precision."""
    asked = jax.config.jax_default_matmul_precision
    if jax.default_backend() == "tpu" and asked in (None, "default",
                                                    "bfloat16", "fastest"):
        return jnp.bfloat16
    return jnp.float32


def _mm(a, b, dims, dtype):
    return jax.lax.dot_general(
        a.astype(dtype), b.astype(dtype), (dims, ((), ())),
        precision=(jax.lax.Precision.HIGHEST if dtype == jnp.float32
                   else None),
        preferred_element_type=jnp.float32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _cumsum(x, reverse=False):
    """Along the rows of each chunk of `x` (whole chunks stacked, or one),
    inclusive, in float32: log2(C) shifted adds on the sublanes
    (`reverse`: from a chunk's last row back, which is the sum's
    transpose). The rows turn inside their own chunk, `[n, C, d]`, so no
    sum crosses from one chunk into the next."""
    rows, d = x.shape
    x = x.reshape(rows // CHUNK, CHUNK, d)
    row = _iota(x.shape, 1)
    shift = 1
    while shift < CHUNK:
        if reverse:  # x_i += x_(i + shift)
            keep, by = row < CHUNK - shift, CHUNK - shift
        else:  # x_i += x_(i - shift)
            keep, by = row >= shift, shift
        x = x + jnp.where(keep, pltpu.roll(x, by, 1), 0.0)
        shift *= 2
    return x.reshape(rows, d)


def _block_reference(G, m):
    """At every row of a block of `m` rows, the block's reference row:
    the last of its lower half, `mid - 1`. Blocks are whole sublane
    tiles (m >= 8), so it is a slice and a broadcast."""
    c, dk = G.shape
    ref = G.reshape(c // m, m, dk)[:, m // 2 - 1:m // 2]
    return jnp.broadcast_to(ref, (c // m, m, dk)).reshape(c, dk)


def _levels(c):
    """The blocks a chunk of `c` rows is split at: c, c/2, ..., 2 * LEAF
    rows."""
    return tuple(c >> n for n in range((c // LEAF).bit_length() - 1))


# Which pairs (i, j) of a chunk's [C, C] are whose: a level's, a distance's
# inside a block of LEAF rows, the diagonal, j < i, j <= i, j >= i; the
# levels' and the distances' masks twice over, [2C, C], for `_scores_grad`;
# and what the pairs that are nobody's read, zeros [C, C] and [2C, C] (an
# array made once where every `where` would broadcast its own scalar).
_Masks = collections.namedtuple(
    "_Masks",
    "levels leaves diagonal below lower upper levels2 leaves2 zero zero2")


def _pair_masks(c):
    """The `_Masks` of a chunk of `c` rows, from i ^ j: its highest bit
    says at which level the pair is split, m / 2 <= i ^ j < m for blocks
    of m rows, and under LEAF both rows lie in one block of LEAF rows,
    i - j apart. Made once a kernel (the host pays for every jnp call of
    the kernel's body at each start of a job)."""
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    level = row ^ col
    levels = tuple(level >> (m.bit_length() - 2) == 1 for m in _levels(c))
    leaves = tuple((level < LEAF) & (row - col == o) for o in range(1, LEAF))

    def twice(masks):
        return tuple(jnp.concatenate([m, m], axis=0) for m in masks)

    diagonal, below = row == col, row > col
    return _Masks(levels, leaves, diagonal, below, below | diagonal, ~below,
                  twice(levels), twice(leaves), jnp.zeros((c, c), jnp.float32),
                  jnp.zeros((2 * c, c), jnp.float32))


def _leaf_pairs(k, G):
    """The pairs inside a block of LEAF rows, formed directly, by their
    distance o = i - j: `decay` = exp(G_i - G_(i-o)) at the rows i that
    have a row i - o in their block and 0 at the others (masked before
    the exp), and k_(i-o) * decay. The earlier row comes by a rotation
    of each tile's sublanes."""
    c, dk = k.shape
    tiles = (c // TILE, TILE, dk)
    G, k = G.reshape(tiles), k.reshape(tiles)
    place = _iota(tiles, 1) & (LEAF - 1)
    decays, earlier = (), ()
    for o in range(1, LEAF):
        decay = jnp.exp(jnp.where(place >= o, G - pltpu.roll(G, o, 1),
                                  -jnp.inf))
        decays += (decay.reshape(c, dk),)
        earlier += ((pltpu.roll(k, o, 1) * decay).reshape(c, dk),)
    return decays, earlier


# What is a row's own, or its chunk's earlier rows', and no product's: the
# grid step's rows stacked [steps*C, ...], computed once for all its chunks
# (`_rows`). beta [R, 1]; a level's k*e and q*e, float32 until a chunk cuts
# its rows (`_chunk_rows`); the lane sums of the pairs inside the blocks of LEAF rows, k_i.k_j (`kk`) and
# q_i.k_j (`qk`) a distance, and Aq's diagonal q_i.k_i (`diag`), [R, 1];
# [beta v, beta k exp(G)] side by side; q exp(G), k exp(G), k exp(G_C - G);
# exp(G_C) a chunk, [steps, 1, dk]. `back`: what the backward alone reads.
_Rows = collections.namedtuple(
    "_Rows", "beta ke qe kk qk diag bvk qE kE k_end decay_end back")
_Back = collections.namedtuple("_Back", "q k v E e_end e decay kj")


@jax.jit
def _rows(q, k, v, g, beta):
    """The `_Rows` of a grid step's rows, whole chunks stacked `[R, 128]`
    float32 (beta `[R, 1]`). One equation for any number of chunks where a
    chunk's copy of the code would state its own: the host lowers this
    once a kernel. A row's arithmetic is what a chunk alone would do, so
    are the bits.

    Level m = C, C/2, ..., 2 * LEAF of `_scores` gives the pairs whose
    rows lie in the two halves of one block of m rows, i in the upper and
    j in the lower: with `ref` the lower half's last row, exp(G_i - G_j) =
    exp(G_i - G_ref) exp(G_ref - G_j), both exponents <= 0 as G does not
    rise along the rows. A row has one role a level, so one `e` =
    exp(-|G - G_ref|) serves all rows and one product (k*e) (k*e)^T
    all blocks."""
    rows, dk = g.shape
    by_chunk = (rows // CHUNK, CHUNK, dk)
    G = _cumsum(g)
    G_end = G.reshape(by_chunk)[:, -1:]  # a chunk's last row
    E = jnp.exp(G)
    e_end = jnp.exp(G_end - G.reshape(by_chunk)).reshape(rows, dk)
    upper = _iota((rows, dk), 0)
    e, ke, qe = (), (), ()
    for m in _levels(CHUNK):
        d = G - _block_reference(G, m)
        e += (jnp.exp(jnp.where(upper & (m // 2) != 0, d, -d)),)
        ke += (k * e[-1],)
        qe += (q * e[-1],)
    decay, kj = _leaf_pairs(k, G)

    def lanes(x):
        return jnp.sum(x, 1, keepdims=True)

    kE = k * E
    return _Rows(
        beta, ke, qe, tuple(lanes(k * x) for x in kj),
        tuple(lanes(q * x) for x in kj), lanes(q * k),
        jnp.concatenate([beta * v, beta * kE], axis=1), q * E, kE, k * e_end,
        jnp.exp(G_end), _Back(q, k, v, E, e_end, e, decay, kj))


def _cut(x, t):
    """Chunk `t`'s 64 rows of a grid step's stacked `x`: whole tiles."""
    return jax.lax.slice_in_dim(x, t * CHUNK, (t + 1) * CHUNK)


def _chunk_rows(rows, t, dtype, backward=False):
    """Chunk `t`'s 64 rows of the step's `_Rows` (and of `back` for the
    backward). The levels' k*e and q*e become what a product reads here,
    a chunk at a time: cast on the stacked rows and then cut, each
    register was packed alone and packed again in pairs in front of the
    first product (four cycles of the chain)."""
    at = functools.partial(_cut, t=t)
    chunk = jax.tree.map(at, rows._replace(decay_end=None, back=None))
    return chunk._replace(
        ke=tuple(x.astype(dtype) for x in chunk.ke),
        qe=tuple(x.astype(dtype) for x in chunk.qe),
        decay_end=rows.decay_end[t],
        back=jax.tree.map(at, rows.back) if backward else None)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _scores(ke, kk, masks, *, dtype):
    """A (j < i) of one chunk, [C, C]: a level's pairs from the product
    (k*e) (k*e)^T, masked to "same block of m rows, i upper, j lower"
    (what the product holds outside them is masked away); the pairs
    inside the blocks of LEAF rows from their lane sums. The levels' pairs
    are disjoint, and with those they are all j < i. (Jitted, as every
    part of a chunk that the kernels state once a chunk, so that the
    copies are traced once: the host pays for every jnp call at each start
    of a job, compile cache or not.)"""
    A = masks.zero
    for here, x in zip(masks.levels, ke):
        A = jnp.where(here, _mm(x, x, _NT, dtype), A)
    for here, pairs in zip(masks.leaves, kk):
        A = jnp.where(here, pairs, A)
    return jnp.where(masks.below, A, masks.zero)


def _query_scores(chunk, masks, dtype, transposed=False):
    """Aq (j <= i) as `_scores` forms A, with q for the rows, or its
    transpose (the levels' products the other way round: the backward
    reads Aq as Aq^T alone). Apart from `_scores`, because nothing before
    U reads Aq: its products queue behind the solve's."""
    Aq = jnp.where(masks.diagonal, chunk.diag, masks.zero)  # j = i
    for here, pairs in zip(masks.leaves, chunk.qk):
        Aq = jnp.where(here, pairs, Aq)
    if transposed:  # the levels' masks are their own transposes
        Aq = Aq.T
    for here, ke, qe in zip(masks.levels, chunk.ke, chunk.qe):
        pairs = _mm(ke, qe, _NT, dtype) if transposed else _mm(qe, ke, _NT,
                                                               dtype)
        Aq = jnp.where(here, pairs, Aq)
    # a level's product holds the pairs i < j too
    return jnp.where(masks.upper if transposed else masks.lower, Aq,
                     masks.zero)


def _scores_grad(dA, dAq, chunk, masks, dtype):
    """The gradients of `_scores` and `_query_scores`: of q (`dq`), of k
    as the row operand of A (`dk_row`) and of k as the column operand of
    both (`dk_col`). dG's share is q*dq + k*dk_row - k*dk_col. `dA` is 0
    from the diagonal up and `dAq` above it."""
    q, k = chunk.back.q, chunk.back.k
    c, dk = q.shape
    d_both = jnp.concatenate([dA, dAq], axis=0)  # [2C, C]
    # Aq's diagonal carries no decay: its k goes with the columns', so
    # that dG's share of it is q*k - k*q
    on_diagonal = jnp.sum(jnp.where(masks.diagonal, dAq, masks.zero), 1,
                          keepdims=True)
    dq, dk_row, dk_col = on_diagonal * k, 0.0, on_diagonal * q
    for here, e, ke, qe in zip(masks.levels2, chunk.back.e, chunk.ke,
                               chunk.qe):
        d = jnp.where(here, d_both, masks.zero2).astype(dtype)  # read twice
        d_in = _mm(d, ke, _NN, dtype)  # to the rows i of the upper halves
        dk_row = dk_row + d_in[:c] * e
        dq = dq + d_in[c:] * e
        # to the lower halves' j
        dk_col = dk_col + _mm(d, jnp.concatenate([ke, qe], axis=0), _TN,
                              dtype) * e
    tiles = (c // TILE, TILE, dk)
    for o, (here, decay, kj) in enumerate(zip(
            masks.leaves2, chunk.back.decay, chunk.back.kj), 1):
        d = jnp.sum(jnp.where(here, d_both, masks.zero2), 1, keepdims=True)
        da, daq = d[:c], d[c:]
        dk_row = dk_row + da * kj
        dq = dq + daq * kj
        # to the rows i - o: the tiles' sublanes rotated back
        back = pltpu.roll(((da * k + daq * q) * decay).reshape(tiles),
                          TILE - o, 1)
        dk_col = dk_col + back.reshape(c, dk)
    return dq, dk_row, dk_col


def _inverse(Ns, dtype):
    """(I + N)^-1 for each strictly lower [C, C] N of `Ns`, by halves: on
    2x2 diagonal blocks it is I - N exactly, and the inverse on blocks of
    2m follows from the one on blocks of m, `T`, and the part `L` of N
    inside the blocks of 2m and outside those of m as T - T L T (the block
    formula [[a, 0], [l, b]]^-1 = [[a^-1, 0], [-b^-1 l a^-1, b^-1]], on
    every diagonal block at once). Block forward substitution, so as
    stable as the system: keys that are nearly parallel make N's entries
    near 1, and a series in N's powers, however short, then sums terms of
    1e8.

    The ten products of one N depend each on the one before. They are
    stated in lockstep over `Ns`, every N's `T L` of a doubling, then
    every N's `(T L) T`: a unit of the MXU takes its products in the order
    the program states them, so one N's wait from push to pop is the
    others' time on the units. Each N's products and operands are what a
    call with it alone states: the inverses are the same to the bit."""
    c = Ns[0].shape[0]
    r, l = _iota((c, c), 0), _iota((c, c), 1)

    def same_block(bits):  # blocks of 2**bits rows
        return (r >> bits) == (l >> bits)

    eye, pairs = (r == l).astype(jnp.float32), same_block(1)
    zero = jnp.zeros((c, c), jnp.float32)
    invs = [eye - jnp.where(pairs, N, zero) for N in Ns]
    for bits in range(1, c.bit_length() - 1):
        inside = same_block(bits + 1) & ~same_block(bits)
        read = [inv.astype(dtype) for inv in invs]  # by both products
        TL = [_mm(inv, jnp.where(inside, N, zero), _NN, dtype)
              for inv, N in zip(read, Ns)]
        invs = [inv - _mm(tl, as_read, _NN, dtype)
                for tl, inv, as_read in zip(TL, invs, read)]
    return invs


# What a chunk computes from no state beside its rows of `_Rows`: A, Aq or
# its transpose, T and the WY factors [Wv, Wk].
_StateFree = collections.namedtuple("_StateFree", "rows A Aq T W")


@functools.partial(jax.jit, static_argnames=("dtype", "transposed_aq"))
def _after_solve(chunk, T, masks, *, dtype, transposed_aq):
    """Aq (or its transpose) and the WY factors [Wv, Wk] from the chunk's
    `T`."""
    return (_query_scores(chunk, masks, dtype, transposed_aq),
            _mm(T, chunk.bvk, _NN, dtype))


def _state_free(rows, masks, dtype, backward=False):
    """A `_StateFree` for each chunk of a grid step's `_Rows`: all of
    them up to their N = beta A, then the solves in lockstep (`_inverse`),
    then each chunk's Aq (transposed for the backward), behind the solve's
    products where nothing waits for it, and W."""
    chunks = [_chunk_rows(rows, t, dtype, backward)
              for t in range(rows.beta.shape[0] // CHUNK)]
    As = [_scores(c.ke, c.kk, masks, dtype=dtype) for c in chunks]
    Ts = _inverse([c.beta * A for c, A in zip(chunks, As)], dtype)
    free = []
    for chunk, A, T in zip(chunks, As, Ts):
        Aq, W = _after_solve(chunk, T, masks, dtype=dtype,
                             transposed_aq=backward)
        free.append(_StateFree(chunk, A, Aq, T, W))
    return free


@functools.partial(jax.jit, static_argnames=("dtype", "scale"))
def _chunk_fwd(free, St, *, dtype, scale):
    """One chunk from the state it starts with, `St` = S^T [dv, dk], and
    its state-free half: its outputs [C, dv] and the state it leaves.
    `scale`: dk^-1/2 of the key lanes the head has, which padded lanes
    are not."""
    chunk, _, Aq, _, W = free
    c, dk = chunk.qE.shape
    dv = W.shape[1] - dk
    # [Wk; Q exp(G)] S in one product
    by_state = _mm(jnp.concatenate([W[:, dv:], chunk.qE], axis=0), St, _NT,
                   dtype)
    U = W[:, :dv] - by_state[:c]
    o = scale * (by_state[c:] + _mm(Aq, U, _NN, dtype))
    return o, St * chunk.decay_end + _mm(U, chunk.k_end, _TN, dtype)


def _as_row(column, masks):
    """A chunk's column [C, 1] as a row [1, C], for a lane-dense store."""
    return jnp.sum(jnp.where(masks.diagonal, column, masks.zero), 0,
                   keepdims=True)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _chunk_bwd(free, masks, St, dSt, dO, *, dtype):
    """One chunk of the reverse sweep: from its state-free half (Aq
    transposed), the state the chunk started with, the gradient `dSt` of
    the state it left and of its outputs (scaled by dk^-1/2 already):
    (the gradients of q, k and v, that of G short of its last row's share,
    and that share, the gradient of exp(G_C), [1, 1, dk]: `_sweep_tail`
    makes g's from the two), beta's gradient as a row [1, C], and the
    gradient of the state the chunk started with. Every product of a
    multiply and an add is stated here, a chunk at a time, as the plain
    chunk states it: only sums are left to the step's tail."""
    mm = functools.partial(_mm, dtype=dtype)
    chunk, A, AqT, T, W = free
    ke, decay_end = chunk.k_end, chunk.decay_end
    c, dv = chunk.back.v.shape
    # as the products read them, once where two or three products do
    Wk, St_, dSt_, dO_ = (x.astype(dtype) for x in (W[:, dv:], St, dSt, dO))
    U = W[:, :dv] - mm(Wk, St_, _NT)
    U_ = U.astype(dtype)
    dU = mm(AqT, dO_, _NN) + mm(ke, dSt_, _NT)
    by_state = mm(jnp.concatenate([dO, dU], axis=0), St_, _NN)
    d_qd, dWk = by_state[:c], -by_state[c:]
    d_ke = mm(U_, dSt_, _NN)
    dAq = jnp.where(masks.lower, mm(dO_, U_, _NT), masks.zero)
    # the solve's gradient is the transposed solve
    lam = mm(T, jnp.concatenate([dU, dWk], axis=1), _TN)
    lam_w = jnp.where(masks.below, mm(lam, W, _NT), masks.zero)
    by_v, by_k = lam[:, :dv] * chunk.back.v, lam[:, dv:] * chunk.kE
    if by_v.shape == by_k.shape:  # one sum over the lanes, as it was
        by_w = jnp.sum(by_v + by_k, 1, keepdims=True)
    else:
        by_w = (jnp.sum(by_v, 1, keepdims=True)
                + jnp.sum(by_k, 1, keepdims=True))
    dbeta = by_w - jnp.sum(lam_w * A, 1, keepdims=True)
    dSt_new = dSt * decay_end + mm(dO_, chunk.qE, _TN) - mm(dU, Wk, _TN)
    dg_end = (jnp.sum(dSt * St, 0, keepdims=True) * decay_end
              + jnp.sum(d_ke * ke, 0, keepdims=True))
    dq, dkl, dkr = _scores_grad(-chunk.beta * lam_w, dAq, chunk, masks, dtype)
    back, by_beta = chunk.back, chunk.beta * lam
    dq = dq + d_qd * back.E
    dkl = dkl + by_beta[:, dv:] * back.E
    dkr = dkr + d_ke * back.e_end
    dG = back.q * dq + back.k * (dkl - dkr)
    return ((dq, dkl + dkr, by_beta[:, :dv], dG, dg_end[None]), dbeta,
            dSt_new)


@functools.partial(jax.jit, static_argnames=("per_head",))
def _sweep_tail(parts, *, per_head=False):
    """The gradients of q, k, v and g over a grid step's stacked rows
    from what `_chunk_bwd` left of each chunk (`parts`, in the rows'
    order). What is left is sums alone, a row's or its chunk's: dG's last
    row takes the gradient of exp(G_C), and g's gradient is the reversed
    `_cumsum`, stated once for the step. `per_head`: `g` is one decay a
    row written along the lanes, and its gradient the sum over them, a
    column [R, 1]."""
    dq, dk, dv, dG, dg_end = (
        jnp.concatenate(part, axis=0) for part in zip(*parts))
    rows, d = dG.shape
    by_chunk = (rows // CHUNK, CHUNK, d)
    dG = dG.reshape(by_chunk) + jnp.where(
        _iota(by_chunk, 1) == CHUNK - 1, dg_end, 0.0)
    dG = dG.reshape(rows, d)
    if per_head:  # the sum over the lanes first: the adds are linear
        dG = jnp.sum(dG, 1, keepdims=True)
    return dq, dk, dv, _cumsum(dG, reverse=True)


# What the prologue keeps of a grid step's rows for its own gradient:
# rsqrt(sum x^2 + eps) of q's and k's rows, [R, 1]; sigmoid of beta's
# logit, [R, 1]; and of a head's decay the log decay as the gate made it,
# its logit plus its bias, both columns [R, 1], and -exp(A_log), [1, 1] (a
# row each where heads are stacked, [R, 1]): None with a decay a channel,
# whose gate is XLA's.
_Gates = collections.namedtuple("_Gates", "rq rk sig g x rate")


@functools.partial(jax.jit, static_argnames=("eps", "beta_scale"))
def _gate_rows(q, k, b, x, a_log, *, eps, beta_scale):
    """The op's float32 prologue (`linear_attn_ops._prologue`, formula
    for formula) over a grid step's stacked rows, in VMEM: q and k
    `[R, lanes]` as the convolution wrote them, cast, become
    `x * rsqrt(sum(x * x) + eps)` over the head's lanes (a padded lane is
    0, adds 0 to the sum and stays 0); beta's logits `b` `[R, 1]` become
    `beta_scale * sigmoid(b)`; a head's decay logit plus its bias `x`
    `[R, 1]` becomes `-exp(A_log) * softplus(x)`. With a decay a channel
    `a_log` is None and `x` `[R, 128]` is the log decay already (XLA's
    gate made it: the module docstring says why). Returns q, k, the log
    decay, beta and the `_Gates`. Stated once whatever the step's width,
    and a row's own."""
    def unit(t):
        r = jax.lax.rsqrt(jnp.sum(t * t, 1, keepdims=True) + eps)
        return t * r, r

    q, rq = unit(q)
    k, rk = unit(k)
    sig = jax.nn.sigmoid(b)
    beta = sig if beta_scale == 1.0 else beta_scale * sig
    if a_log is None:
        return q, k, x, beta, _Gates(rq, rk, sig, None, None, None)
    rate = -jnp.exp(a_log)
    # softplus as `jnp.logaddexp(x, 0)` evaluates it
    g = rate * (jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x))))
    return q, k, g, beta, _Gates(rq, rk, sig, g, x, rate)


@functools.partial(jax.jit, static_argnames=("beta_scale",))
def _gate_grads(dq, dk, dg, dbeta, q, k, gates, *, beta_scale):
    """`_gate_rows` backwards over the step's stacked rows, float32: the
    norm's own gradient `rsqrt * (d - x_n * sum(d * x_n))` on the dq and
    dk the sweep holds (`q`, `k`: the normed rows), beta's through the
    sigmoid and, with a decay a head, the decay's gradient back onto its
    logits (`dg * -exp(A_log) * sigmoid(x)`: DtBias's gradient is their
    sum) and, last, what A_log's gradient sums (`dg * g`: its derivative
    is the log decay itself; None with a decay a channel, where `dg` is
    the log decay's and leaves as it is)."""
    def unit_grad(d, t, r):
        return r * (d - t * jnp.sum(d * t, 1, keepdims=True))

    sig, da = gates.sig, None
    if beta_scale != 1.0:
        dbeta = beta_scale * dbeta
    if gates.rate is not None:
        dg, da = dg * gates.rate * jax.nn.sigmoid(gates.x), dg * gates.g
    return (unit_grad(dq, q, gates.rq), unit_grad(dk, k, gates.rk), dg,
            dbeta * sig * (1.0 - sig), da)


def _column(blk, mine):
    """The column `[R, 1]` that the mask `mine` picks of each row of an
    `[R, h]` block: a sum over the lanes of what it leaves."""
    return jnp.sum(jnp.where(mine, blk, 0.0), 1, keepdims=True)


def _operands(refs, heads, per_head, eps, beta_scale):
    """The grid step's blocks whole, float32 `[R, 128]`, from the arrays
    the projections wrote: q and k normed and beta made of its logits in
    VMEM (`_gate_rows`), this head's column `[R, 1]` taken out of the
    `[R, h]` block of beta's logits by a mask and a sum over the lanes.
    `per_head`: `g_ref` is an `[R, h]` block of logits too, with A_log
    and the decay's bias as `[1, h]` blocks behind beta's; the bias is
    added before the column is taken, the gate is made on the column, and
    this head's decay is written along the lanes here: a column fills as
    many registers as `[R, 128]`. Returns (q, k, v, g, beta) and the
    `_Gates`."""
    head = pl.program_id(0) % heads
    q, k, v, x, logits = (r[0].astype(jnp.float32) for r in refs[:5])
    mine = _iota(logits.shape, 1) == head
    a_log = None
    if per_head:
        a_log, dt_bias = (r[...] for r in refs[5:])
        x = _column(x + dt_bias, mine)
        a_log = _column(a_log, _iota(a_log.shape, 1) == head)
    q, k, g, beta, gates = _gate_rows(q, k, _column(logits, mine), x, a_log,
                                      eps=eps, beta_scale=beta_scale)
    return (q, k, v, jnp.broadcast_to(g, q.shape), beta), gates


# Heads that are no tile (`step_heads`): how many a grid step holds, of
# how many groups of them a row has, and the key and value lanes a head
# has in HBM.
_Narrow = collections.namedtuple("_Narrow", "heads groups dk dv")


def _padded(x, rows=None, lanes=None):
    """`x` with zeros behind it, up to `rows` rows and `lanes` lanes."""
    if lanes and lanes > x.shape[1]:
        x = jnp.concatenate(
            [x, jnp.zeros((x.shape[0], lanes - x.shape[1]), x.dtype)], axis=1)
    if rows and rows > x.shape[0]:
        x = jnp.concatenate(
            [x, jnp.zeros((rows - x.shape[0], x.shape[1]), x.dtype)], axis=0)
    return x


def _tiles(d):
    """`d` lanes as whole tiles."""
    return -(-d // LANE) * LANE


def _narrow_operands(refs, narrow, eps, beta_scale):
    """`_operands` where a head is no tile and the decay is a head's: the
    step's heads one under the other, `[heads*R, tiles]` float32, each
    cut out of the step's block at its own lanes and padded with zeros to
    whole tiles, then the prologue once over the stacked rows
    (`_gate_rows`: the zeros add nothing to a row's sum of squares, so the
    norm is over the lanes the head has, and a padded lane stays 0). A
    padded key lane of q and k is 0 in every product and every lane sum;
    a padded value lane of v stays 0 through the solve, the state and the
    output: what a head computes is the unpadded mathematics. A head past
    the row's last (the last group's, where the heads are no multiple of
    the step's) reads what lies in VMEM and takes logits and an A_log of
    0; its results are stored nowhere."""
    q_ref, k_ref, v_ref, g_ref, beta_ref, a_ref, dt_ref = refs
    first = pl.program_id(0) % narrow.groups * narrow.heads
    rows = q_ref.shape[1]

    def lanes(ref, d):
        return jnp.concatenate([
            _padded(ref[0, :, n * d:(n + 1) * d].astype(jnp.float32),
                    lanes=_tiles(d)) for n in range(narrow.heads)], axis=0)

    def columns(blk):  # of an `[R, h]` block, or a `[1, h]` for every row
        at = _iota(blk.shape, 1)
        return jnp.concatenate([
            jnp.broadcast_to(_column(blk, at == first + n), (rows, 1))
            for n in range(narrow.heads)], axis=0)

    q, k, g, beta, gates = _gate_rows(
        lanes(q_ref, narrow.dk), lanes(k_ref, narrow.dk),
        columns(beta_ref[0].astype(jnp.float32)),
        columns(g_ref[0].astype(jnp.float32) + dt_ref[...]),
        columns(a_ref[...]), eps=eps, beta_scale=beta_scale)
    return ((q, k, lanes(v_ref, narrow.dv), jnp.broadcast_to(g, q.shape),
             beta), gates)


def _fwd_kernel(*refs, heads, steps, dtype, per_head, gate, narrow=None):
    """`refs`: q, k, v, the decay's and beta's blocks, with a decay a head
    A_log's and the bias's, then the output's, the chunks' states' and the
    scratch. `gate`: the prologue's `eps` and `beta_scale`."""
    *refs, o_ref, st_ref, s_ref = refs

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    if narrow:
        _fwd_narrow(refs, o_ref, st_ref, s_ref, steps, dtype, narrow, gate)
        return
    # the rows' arithmetic once over the step's stacked rows; then copies
    # of the chunk and not a loop: a `pl.loop` over four chunks measured
    # 0.8 ms a call slower than four copies (1.3 ms backward)
    rows = _rows(*_operands(refs, heads, per_head, *gate)[0])
    free = _state_free(rows, _pair_masks(CHUNK), dtype)
    scale = refs[0].shape[2] ** -0.5
    for t in range(steps):
        St = st_ref[0, t] = s_ref[...]  # the state the chunk starts from
        o, s_ref[...] = _chunk_fwd(free[t], St, dtype=dtype, scale=scale)
        o_ref[0, pl.ds(t * CHUNK, CHUNK), :] = o.astype(o_ref.dtype)


def _fwd_narrow(refs, o_ref, st_ref, s_ref, steps, dtype, narrow, gate):
    """The forward grid step over `narrow.heads` heads of `steps` chunks
    each: the same rows' arithmetic, solves in lockstep and chunks, each
    head with a state of its own in the scratch, `[heads, dv, dk]` in
    whole tiles; a head's outputs and states leave at the lanes it has."""
    dk, dv = narrow.dk, narrow.dv
    rows = _rows(*_narrow_operands(refs, narrow, *gate)[0])
    free = _state_free(rows, _pair_masks(CHUNK), dtype)
    for n in range(narrow.heads):
        for t in range(steps):
            St = s_ref[n]
            st_ref[0, n, t] = St[:dv, :dk]
            o, s_ref[n] = _chunk_fwd(free[n * steps + t], St, dtype=dtype,
                                     scale=dk ** -0.5)
            o_ref[0, pl.ds(t * CHUNK, CHUNK), n * dv:(n + 1) * dv] = (
                o[:, :dv].astype(o_ref.dtype))


def _sweep_gates(parts, dbetas, rows, gates, beta_scale, per_head):
    """What leaves a reverse sweep's grid step, over its stacked rows:
    `_sweep_tail`'s sums, then the prologue's own gradient
    (`_gate_grads`). Returns the gradients of q, k and v as they arrived,
    of the decay (its logits', a column `[R, 1]`, with a decay a head),
    of beta's logits `[R, 1]` and, with a decay a head, what A_log's
    gradient sums, `[R, 1]`."""
    dq, dk, dv, dg = _sweep_tail(parts, per_head=per_head)
    dq, dk, *decay = _gate_grads(
        dq, dk, dg, jnp.concatenate(dbetas, axis=0), rows.back.q, rows.back.k,
        gates, beta_scale=beta_scale)
    return (dq, dk, dv, *decay)


def _bwd_kernel(*refs, heads, steps, dtype, per_head, gate, narrow=None):
    """`refs`: the forward's operands, the chunks' states and the
    output's cotangent; then the gradients of q, k, v, the decay and
    beta's logits, with a decay a head A_log's terms; the scratch."""
    ins = 9 if per_head else 7
    (*refs, st_ref, do_ref), outs, ds_ref = refs[:ins], refs[ins:-1], refs[-1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    if narrow:
        _bwd_narrow(refs, st_ref, do_ref, outs, ds_ref, steps, dtype, narrow,
                    gate)
        return
    operands, gates = _operands(refs, heads, per_head, *gate)
    rows = _rows(*operands)
    masks = _pair_masks(CHUNK)
    free = _state_free(rows, masks, dtype, backward=True)
    dO = refs[0].shape[2] ** -0.5 * do_ref[0].astype(jnp.float32)
    parts, dbetas = [None] * steps, [None] * steps
    for t in reversed(range(steps)):
        parts[t], dbetas[t], ds_ref[...] = _chunk_bwd(
            free[t], masks, st_ref[0, t], ds_ref[...], _cut(dO, t),
            dtype=dtype)
    grads = _sweep_gates(parts, dbetas, rows, gates, gate[1], per_head)
    # with a decay a head its gradient and A_log's terms leave as beta's
    # does, a row a chunk
    wide = 3 if per_head else 4
    for ref, d in zip(outs[:wide], grads):
        ref[0] = d.astype(ref.dtype)
    for ref, d in zip(outs[wide:], grads[wide:]):
        for t in range(steps):
            ref[0, t] = _as_row(_cut(d, t), masks)


def _bwd_narrow(refs, st_ref, do_ref, outs, ds_ref, steps, dtype, narrow,
                gate):
    """The reverse sweep's grid step over `narrow.heads` heads (see
    `_fwd_narrow`): a head's states and cotangents are padded with zeros
    to whole tiles as they are read, and its gradients leave at the lanes
    it has."""
    dk, dv = narrow.dk, narrow.dv
    operands, gates = _narrow_operands(refs, narrow, *gate)
    rows = _rows(*operands)
    masks = _pair_masks(CHUNK)
    free = _state_free(rows, masks, dtype, backward=True)
    parts = [None] * (narrow.heads * steps)
    dbetas = [None] * len(parts)
    for n in range(narrow.heads):
        dO = dk ** -0.5 * _padded(
            do_ref[0, :, n * dv:(n + 1) * dv].astype(jnp.float32),
            lanes=_tiles(dv))
        for t in reversed(range(steps)):
            at = n * steps + t
            St = _padded(st_ref[0, n, t], _tiles(dv), _tiles(dk))
            parts[at], dbetas[at], ds_ref[n] = _chunk_bwd(
                free[at], masks, St, ds_ref[n], _cut(dO, t), dtype=dtype)
    grads = _sweep_gates(parts, dbetas, rows, gates, gate[1], True)
    span = steps * CHUNK
    for n in range(narrow.heads):
        for ref, d, lanes in zip(outs[:3], grads, (dk, dk, dv)):
            ref[0, :, n * lanes:(n + 1) * lanes] = (
                d[n * span:(n + 1) * span, :lanes].astype(ref.dtype))
        for ref, d in zip(outs[3:], grads[3:]):
            for t in range(steps):
                ref[0, n, t] = _as_row(_cut(d, n * steps + t), masks)


def _cost(backward, b, s, heads, dk, dv, dtypes, group=1, per_head=False):
    """What one call declares (`cost.py` has the convention) for `s`
    unpadded tokens a row; `dtypes`: of q and k, of v and o, of the
    decay's array and of beta's logits, as they arrive. With `group`
    value heads to a key head q and k are moved once a key head in, and
    their gradients leave float32 a value head each (XLA adds the
    group's); with a key head a value head they leave in q's dtype.
    `per_head`: the decay's logits are `[b, s, h]` as beta's, with A_log
    and the bias read once, and the gradients of both leave as float32
    rows, with a third of them for what A_log's gradient sums; with a
    decay a channel the log decay arrives `[b, s, h*dk]` and its gradient
    leaves in its dtype. The
    products and the exponentials are a value head's either way: the
    decay is written along the lanes in VMEM and evaluated there. Products
    counted a chunk of `c` rows (the last may be short) of one head, in
    multiply-adds, with `lower` = c(c+1)/2 pairs j <= i, `strict` =
    c(c-1)/2 and `state` = c*dk*dv:

    - `kda_fwd`: A and Aq over the keys' lanes (c*c*dk, the two triangles
      together); [Wv, Wk] as the triangular solve it is (lower*(dk+dv));
      Wk.S, Q.S and the state's update U^T.K (3 state); Aq.U (lower*dv).
    - `kda_bwd`: A, Aq, W and Wk.S again (the reverse sweep keeps only
      the chunks' states); dU = Aq^T.dO + K.dS (lower*dv + state);
      dO.S, dU.S, U.dS, dO^T.Q, dU^T.Wk (5 state); dAq = dO.U^T
      (lower*dv); the transposed solve and its product with W
      ((lower+strict)*(dk+dv)); dA and dAq back to q and to k as row and
      as column (2*c*c*dk).

    Not counted: the ten [c, c] products that build the inverse (a solve
    needs none), what the four levels' products hold outside their
    own pairs (each is a whole [c, c] of which a level keeps c*c/4 entries
    or fewer), and the prologue's sums and multiplies (no product).
    Exponentials as the kernels evaluate them a chunk: exp(G),
    exp(G_c - G), exp(G_c), the rows' factors at each of the four levels
    and the decays at the three distances inside a block of LEAF rows,
    over the keys' lanes; and the prologue's a token of a value head: two
    rsqrt and beta's sigmoid, and with a decay a head the softplus' exp
    and log1p (backward the sigmoid of the same logit on top)."""
    def chunk(c):
        lower, strict, state = c * (c + 1) // 2, c * (c - 1) // 2, c * dk * dv
        if backward:
            return (3 * c * c * dk + (2 * lower + strict) * (dk + dv)
                    + 2 * lower * dv + 7 * state)
        return c * c * dk + lower * (dk + dv) + lower * dv + 3 * state

    chunks = -(-s // CHUNK)
    macs = (s // CHUNK) * chunk(CHUNK) + chunk(s % CHUNK)
    exps = chunks * dk * (2 * CHUNK + 1
                          + (len(_levels(CHUNK)) + LEAF - 1) * CHUNK)
    exps += s * (3 + ((3 if backward else 2) if per_head else 0))
    f32 = jnp.float32
    wide, narrow, decays, betas = dtypes
    keys, values = (b, s, heads * dk), ((b, s, heads * dv), narrow)
    row = (b, s, heads)
    decay = (row if per_head else keys, decays)
    states = ((b * heads, chunks, dv, dk), f32)
    held = ((b, s, heads // group * dk), wide)  # q, k as they arrive
    moved = [held] * 2 + [decay, values, (row, betas), states,
                          values]  # .., o or dO
    if per_head:  # A_log and the bias
        moved += [((heads,), f32)] * 2
    if backward:  # dq, dk, dv, dbeta, then the decay's
        moved += [(keys, wide if group == 1 else f32)] * 2 + [
            values, (row, f32)]
        moved += [(row, f32)] * 2 if per_head else [decay]
    return cost.estimate(2 * b * heads * macs, b * heads * exps, *moved)


# What a call is built from beside its operands: the value heads, the
# chunks a grid step, what a product reads, the interpreter, the unpadded
# length, the value heads to a key head, whether the decay is a head's,
# the heads a grid step holds where a head is no tile (`step_heads`; 0: a
# head is a tile and the step holds one), and the prologue's `eps` and
# `beta_scale`.
_Statics = collections.namedtuple(
    "_Statics",
    "heads steps dtype interpret s group per_head narrow eps beta_scale",
    defaults=(1, False, 0, 1e-6, 1.0))


def _specs(statics, chunk_of):
    """(of a value head's [rows, 128] block, of its key head's, of the
    [rows, h] block all the heads of a row share), at the chunk step
    `chunk_of(j)` of grid step j. A value head n reads key head
    n // group: the index map, and nothing copied."""
    heads, rows, group = statics.heads, statics.steps * CHUNK, statics.group

    def head(d):
        return pl.BlockSpec((1, rows, d), lambda i, j: (
            i // heads, chunk_of(j), i % heads))

    def key_head(d):
        if group == 1:
            return head(d)
        return pl.BlockSpec((1, rows, d), lambda i, j: (
            i // heads, chunk_of(j), i % heads // group))

    shared = pl.BlockSpec((1, rows, heads),
                          lambda i, j: (i // heads, chunk_of(j), 0))
    return head, key_head, shared


def _narrow_specs(statics, chunk_of, dk, dv):
    """`_specs` where a grid step holds `statics.narrow` heads of `dk` and
    `dv` lanes side by side: (of the step's heads' lanes of a
    `[b, S, h*d]` array, of the `[rows, h]` block, of an array
    `[b, h, S/C, ...]` that has `tail` a chunk), and the step's `_Narrow`:
    grid step (i, j) is row i // groups, group i % groups."""
    heads, rows, held = statics.heads, statics.steps * CHUNK, statics.narrow
    groups = -(-heads // held)

    def lanes(d):
        return pl.BlockSpec((1, rows, held * d), lambda i, j: (
            i // groups, chunk_of(j), i % groups))

    shared = pl.BlockSpec((1, rows, heads),
                          lambda i, j: (i // groups, chunk_of(j), 0))

    def by_chunk(*tail):
        return pl.BlockSpec((1, held, statics.steps, *tail), lambda i, j: (
            i // groups, i % groups, chunk_of(j), *(0,) * len(tail)))

    return lanes, shared, by_chunk, _Narrow(held, groups, dk, dv)


def _numbers(heads):
    """Of a `[1, h]` array of the heads' numbers (A_log, a head's decay's
    bias), whole at every grid step."""
    return pl.BlockSpec((1, heads), lambda i, j: (0, 0))


def _names(statics):
    """The pair's names in a trace: a decay a head has its own, so that
    the metrics of one never read the other's events."""
    return ("gdn_fwd", "gdn_bwd") if statics.per_head else ("kda_fwd",
                                                            "kda_bwd")


def _body(kernel, statics, narrow=None):
    return functools.partial(
        kernel, heads=statics.heads, steps=statics.steps,
        dtype=statics.dtype, per_head=statics.per_head,
        gate=(statics.eps, statics.beta_scale), narrow=narrow)


def _declared(backward, operands, statics, dk, dv):
    q, _, v, g, beta = operands[:5]
    return _cost(backward, q.shape[0], statics.s, statics.heads, dk, dv,
                 (q.dtype, v.dtype, g.dtype, beta.dtype), statics.group,
                 statics.per_head)


@functools.partial(jax.jit, static_argnames=("statics",))
def _call_fwd(q, k, v, g, beta, gate, *, statics):
    """q, k: [b, S, h_k*dk] and v: [b, S, h*dv] as the convolution wrote
    them; beta's logits: [b, S, h], as their projection did. With a decay
    a head g: [b, S, h], its logits, and `gate`: (A_log, the decay's
    bias), [1, h] float32 each; with a decay a channel g: [b, S, h*dk],
    the log decay, and `gate`: (). S whole grid steps. Returns o
    [b, S, h*dv] in v's dtype and the state each chunk starts from,
    [b*h, S/C, dv, dk] float32. One call for a forward that is
    differentiated and one that is not: a Program's gradient op lowers
    its forward op again, and XLA merges the two calls only if they are
    the same call."""
    statics = _Statics(*statics)  # a caller may pass the first five alone
    heads, steps = statics.heads, statics.steps
    operands = (q, k, v, g, beta, *gate)
    b, S, _ = q.shape
    dk, dv = q.shape[2] * statics.group // heads, v.shape[2] // heads
    if statics.narrow:
        return _call_fwd_narrow(operands, statics, dk, dv)
    spec, key_spec, shared = _specs(statics, lambda j: j)
    decay = ([shared, shared, _numbers(heads), _numbers(heads)]
             if statics.per_head else [spec(dk), shared])
    return pl.pallas_call(
        _body(_fwd_kernel, statics),
        grid=(b * heads, S // (steps * CHUNK)),
        in_specs=[key_spec(dk), key_spec(dk), spec(dv)] + decay,
        out_specs=[spec(dv), pl.BlockSpec((1, steps, dv, dk),
                                          lambda i, j: (i, j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b * heads, S // CHUNK, dv, dk),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=statics.interpret,
        name=_names(statics)[0],
        cost_estimate=_declared(False, operands, statics, dk, dv),
    )(*operands)


def _call_fwd_narrow(operands, statics, dk, dv):
    """`_call_fwd` where a head is no tile: `statics.narrow` heads a grid
    step, the states `[b, h, S/C, dv, dk]` at the lanes the heads have,
    and the declaration that of `dk` and `dv` lanes, whatever the tiles
    in VMEM multiply."""
    heads, steps = statics.heads, statics.steps
    v = operands[2]
    b, S, _ = v.shape
    lanes, shared, by_chunk, narrow = _narrow_specs(statics, lambda j: j, dk,
                                                    dv)
    numbers = _numbers(heads)
    return pl.pallas_call(
        _body(_fwd_kernel, statics, narrow),
        grid=(b * narrow.groups, S // (steps * CHUNK)),
        in_specs=[lanes(dk), lanes(dk), lanes(dv), shared, shared, numbers,
                  numbers],
        out_specs=[lanes(dv), by_chunk(dv, dk)],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b, heads, S // CHUNK, dv, dk),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((narrow.heads, _tiles(dv), _tiles(dk)),
                                   jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=statics.interpret,
        name=_names(statics)[0],
        cost_estimate=_declared(False, operands, statics, dk, dv),
    )(*operands)


def _by_token(rows, like):
    """The kernel's rows `[..., n, 1, C]`, heads before chunks, by token:
    `[b, S, h]` float32, `like`'s shape."""
    b, S, heads = like.shape
    return rows.reshape(b, heads, S).transpose(0, 2, 1)


def _logit_grads(dg, dbeta, da, operands):
    """The op's gradients of GRaw, BetaRaw, ALog and DtBias with a decay a
    head, from the kernel's rows (the decay's logits', beta's logits',
    what A_log's sums): by token in the logits' dtypes, and the float32
    sums over the tokens that the two parameters' gradients are."""
    g, beta, a_log, dt_bias = operands[3:]
    dg = _by_token(dg, g)
    return (dg.astype(g.dtype), _by_token(dbeta, beta).astype(beta.dtype),
            (_by_token(da, g).sum((0, 1)).reshape(a_log.shape),
             dg.sum((0, 1)).reshape(dt_bias.shape)))


def _sweep_narrow(operands, states, do, statics, dk, dv):
    """`_sweep` where a head is no tile (see `_call_fwd_narrow`)."""
    heads, steps = statics.heads, statics.steps
    q, _, v = operands[:3]
    b, S, _ = q.shape
    last = S // (steps * CHUNK) - 1
    lanes, shared, by_chunk, narrow = _narrow_specs(
        statics, lambda j: last - j, dk, dv)
    numbers = _numbers(heads)
    rows = jax.ShapeDtypeStruct((b, heads, S // CHUNK, 1, CHUNK), jnp.float32)
    return pl.pallas_call(
        _body(_bwd_kernel, statics, narrow),
        grid=(b * narrow.groups, last + 1),
        in_specs=[lanes(dk), lanes(dk), lanes(dv), shared, shared, numbers,
                  numbers, by_chunk(dv, dk), lanes(dv)],
        out_specs=[lanes(dk), lanes(dk), lanes(dv)] + [by_chunk(1, CHUNK)] * 3,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype), rows, rows, rows],
        scratch_shapes=[pltpu.VMEM((narrow.heads, _tiles(dv), _tiles(dk)),
                                   jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=statics.interpret,
        name=_names(statics)[1],
        cost_estimate=_declared(True, operands, statics, dk, dv),
    )(*operands, states, do)


def _sweep(operands, states, do, statics):
    """The reverse sweep's call alone, and what the kernel itself writes:
    grid step j holds the chunks of step last - j. `operands`: those of
    `_call_fwd`, the gate's two flat behind beta's. Returns dq, dk, dv,
    then with a decay a head three arrays of rows a chunk
    `[.., S/C, 1, C]` float32 (the gradients of the decay's logits and of
    beta's, and what A_log's gradient sums), with a decay a channel the
    log decay's gradient in its shape and dtype and beta's logits' rows.
    `_call_bwd` makes the op's gradients of these; the tests of a grid
    step's width (`tests/kernel_cases.py::pair_at_widths`) hold these,
    before XLA sums, casts or lays anything by token."""
    heads, steps, group = statics.heads, statics.steps, statics.group
    q, _, v, g = operands[:4]
    b, S, _ = q.shape
    dk, dv = q.shape[2] * group // heads, v.shape[2] // heads
    if statics.narrow:
        return _sweep_narrow(operands, states, do, statics, dk, dv)
    last = S // (steps * CHUNK) - 1
    spec, key_spec, shared = _specs(statics, lambda j: last - j)
    row = pl.BlockSpec((1, steps, 1, CHUNK), lambda i, j: (i, last - j, 0, 0))
    rows = jax.ShapeDtypeStruct((b * heads, S // CHUNK, 1, CHUNK),
                                jnp.float32)
    wide = jax.ShapeDtypeStruct((b, S, heads * dk),
                                q.dtype if group == 1 else jnp.float32)
    if statics.per_head:
        decay = [shared, shared, _numbers(heads), _numbers(heads)]
        grad_specs, grad_shapes = [row] * 3, [rows] * 3
    else:
        decay = [spec(dk), shared]
        grad_specs = [spec(dk), row]
        grad_shapes = [jax.ShapeDtypeStruct(g.shape, g.dtype), rows]
    return pl.pallas_call(
        _body(_bwd_kernel, statics),
        grid=(b * heads, last + 1),
        in_specs=[key_spec(dk), key_spec(dk), spec(dv)] + decay + [
            pl.BlockSpec((1, steps, dv, dk),
                         lambda i, j: (i, last - j, 0, 0)),
            spec(dv)],
        out_specs=[spec(dk), spec(dk), spec(dv)] + grad_specs,
        out_shape=[wide, wide, jax.ShapeDtypeStruct(v.shape, v.dtype)]
        + grad_shapes,
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=statics.interpret,
        name=_names(statics)[1],
        cost_estimate=_declared(True, operands, statics, dk, dv),
    )(*operands, states, do)


@functools.partial(jax.jit, static_argnames=("statics",))
def _call_bwd(q, k, v, g, beta, gate, states, do, *, statics):
    """The reverse sweep (`_sweep`) and what XLA makes of its arrays: the
    gradients of the operands of `_call_fwd`, each in its operand's shape
    and dtype. With a key head a value head the kernel writes dq and dk
    as they arrived; with grouped key heads it writes them float32 a
    value head, `[b, S, h*dk]`, and XLA adds each group's to the
    `[b, S, h_k*dk]` the op returns (two float32 arrays of q's width
    times the group written and read once: 0.27 GB a layer at 4,096
    tokens, 32 heads and groups of 2). The gradient of beta's logits
    leaves as a row a chunk; with a decay a head so do its logits' and, a
    third, what A_log's gradient sums, and XLA lays them by token and
    sums the parameters'; with a decay a channel the log decay's leaves
    in its dtype."""
    statics = _Statics(*statics)
    heads, group = statics.heads, statics.group
    operands = (q, k, v, g, beta, *gate)
    b, S, _ = q.shape
    dk = q.shape[2] * group // heads
    dq, dk_, dv_, *decay = _sweep(operands, states, do, statics)
    if group > 1:
        dq, dk_ = (t.reshape(b, S, heads // group, group, dk).sum(3)
                   .reshape(q.shape).astype(q.dtype) for t in (dq, dk_))
    if statics.per_head:
        return (dq, dk_, dv_, *_logit_grads(*decay, operands))
    dg, dbeta = decay
    return dq, dk_, dv_, dg, _by_token(dbeta, beta).astype(beta.dtype), ()


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _core(q, k, v, g, beta, gate, statics):
    return _call_fwd(q, k, v, g, beta, gate, statics=statics)[0]


def _core_fwd(q, k, v, g, beta, gate, statics):
    o, states = _call_fwd(q, k, v, g, beta, gate, statics=statics)
    return o, (q, k, v, g, beta, gate, states)


def _core_bwd(statics, res, do):
    return _call_bwd(*res, do, statics=statics)


_core.defvjp(_core_fwd, _core_bwd)


def kda_chunk(q, k, v, g, beta, gate=(), eps=1e-6, beta_scale=1.0):
    """`kda_chunked` behind the op's float32 prologue, in the kernels,
    from the arrays the projections wrote, in the dtypes they wrote them
    (the kernels compute in float32 whatever arrives). q, k:
    [b, s, h_k, dk], not normed; v: [b, s, h, dv]; beta, its logits:
    [b, s, h]; `h_k` divides `h`, and value head n reads key head
    n // (h / h_k). Where a head has one decay, g: [b, s, h], its logits,
    and `gate`: (a_log, dt_bias), [h] each; with a decay a channel g:
    [b, s, h, dk] float32, the log decay itself as `kda_gate` makes it,
    and no `gate`. Returns o: [b, s, h, dv] in v's dtype. Nothing is
    normed, repeated or written out in front of the kernels: they read a
    key head's block for each of its value heads, a head's decay as a
    column (`gdn_fwd`, `gdn_bwd` in a trace; with a decay a channel
    `kda_fwd`, `kda_bwd`), and make the L2 norms over `dk` lanes
    (+ `eps`), `beta_scale * sigmoid(beta)` and a head's
    `-exp(a_log) softplus(g + dt_bias)` in VMEM; the gradients of all
    come back in their dtypes."""
    operands, statics = step_operands(q, k, v, g, beta, gate, eps,
                                      beta_scale)
    b, s, h, dv = v.shape
    return _core(*operands, statics)[:, :s].reshape(b, s, h, dv)


def step_operands(q, k, v, g, beta, gate=(), eps=1e-6, beta_scale=1.0):
    """`kda_chunk`'s arguments as its calls take them: (q, k, v, the
    decay's array and beta's logits `[b, S, ...]`, heads side by side on
    the lanes and rows padded to whole grid steps, and the gate's two
    `[1, h]` float32), and the calls' `_Statics`."""
    require_pallas("kda_chunk")
    b, s, h_k, dk = q.shape
    h, dv = v.shape[2:]
    per_head = beta.shape == g.shape
    if not kda_chunk_viable(s, dk, dv, h, h_k, per_head) or h % h_k:
        raise ValueError(
            f"kda_chunk: q {q.shape}, v {v.shape}: needs head widths of "
            f"{LANE} and key heads that divide the value heads, or a decay "
            "a head, a key head a value head and the widths "
            "`kda_chunk_viable` states")
    if per_head != bool(gate):
        raise ValueError("kda_chunk: a head's decay comes as logits with "
                         "`gate` = (a_log, dt_bias), a channel's as the log "
                         "decay without")
    narrow = layout(h, dk, dv)[0]
    steps = lockstep_chunks(s, narrow)
    pad = -s % (steps * CHUNK)
    # heads side by side on the lanes, as the projections write them
    q, k, v, g = (t.reshape(b, s, -1) for t in (q, k, v, g))
    if pad:  # q = k = v = 0 behind the row's last token: a padded token
        # decays the state it leaves to no one and changes nothing (logits
        # of 0 are a beta of beta_scale / 2 and a head's decay, not 0)
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                            for t in (q, k, v, g, beta))
    gate = tuple(t.astype(jnp.float32).reshape(1, h) for t in gate)
    return (q, k, v, g, beta, gate), _Statics(
        h, steps, _product_dtype(), _interpret(), s, h // h_k, per_head,
        narrow, float(eps), float(beta_scale))
