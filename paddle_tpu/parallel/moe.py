"""Mixture-of-Experts with expert parallelism (SURVEY.md §2.8 'Expert
parallel (EP/MoE)' — absent from the reference; built TPU-first as a new
capability per the build plan). Two formulations:

`moe_ffn` (op `moe_ffn`) **drops**: the GShard/Mesh-TF dense dispatch.
Tokens route to experts through one-hot dispatch/combine einsums over a
`[N, E, capacity]` tensor, so under pjit with the expert dim sharded over
the `ep` mesh axis XLA lowers the dispatch einsum to the all-to-all over
ICI — no hand-written collectives. Softmax top-k; an assignment past an
expert's capacity is dropped the way GShard drops it, and the standard
load-balancing auxiliary loss is returned for the trainer to add.

`moe_experts` (op `moe_experts`) is **dropless**, and is told which
experts it holds: a router over all `experts_total` experts (sigmoid
scores with a correction, or softmax probabilities: `score_func`) picks
k a token, the assignments to the `experts_held` experts from `held_from`
on are sorted by expert and run through one grouped product (the experts
SiLU-gated, or with no `w_gate` `W_down relu(W_up x)^2`; reading the
router's rows, or rows of another width given beside them)
(`jax.lax.ragged_dot`, or where the widths and the backend allow
`ops/pallas/grouped_matmul.py`, whose product and both of its
gradients are Pallas kernels that skip the dead rows of a block),
whatever the skew, at static shapes. What the
experts held elsewhere would add is left out: on one chip the layer runs
without its exchange. Gradients flow through the combine weights in both."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["MoEParams", "init_moe_params", "moe_ffn", "moe_shardings",
           "moe_route", "moe_experts"]


def init_moe_params(rng, d_model, d_ff, num_experts, dtype=jnp.float32):
    """Returns a dict pytree: gate [D, E], per-expert FFN stacks
    w1 [E, D, F], b1 [E, F], w2 [E, F, D], b2 [E, D]."""
    import numpy as np

    r = np.random.RandomState(rng)
    s1 = (2.0 / (d_model + d_ff)) ** 0.5
    return {
        "gate": jnp.asarray(
            r.randn(d_model, num_experts).astype("float32") * 0.02, dtype
        ),
        "w1": jnp.asarray(
            r.randn(num_experts, d_model, d_ff).astype("float32") * s1, dtype
        ),
        "b1": jnp.zeros((num_experts, d_ff), dtype),
        "w2": jnp.asarray(
            r.randn(num_experts, d_ff, d_model).astype("float32") * s1, dtype
        ),
        "b2": jnp.zeros((num_experts, d_model), dtype),
    }


MoEParams = dict  # alias for annotation clarity


def moe_shardings(mesh, axis="model"):
    """NamedShardings placing the expert (leading) dim of each expert leaf
    on `axis` (canonically the unified mesh's 'model' axis; legacy 'ep'
    accepted); gate replicated. Feed to jax.jit in/out_shardings."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from .mesh import canonical_axis

    e = P(canonical_axis(axis))
    return {
        "gate": NamedSharding(mesh, P()),
        "w1": NamedSharding(mesh, e),
        "b1": NamedSharding(mesh, e),
        "w2": NamedSharding(mesh, e),
        "b2": NamedSharding(mesh, e),
    }


def moe_ffn(params, x, capacity_factor=1.25, k=2, compute_dtype=None):
    """Top-k gated MoE FFN.

    x: [..., D] (leading dims flattened to tokens). Returns (y, aux_loss)
    with y.shape == x.shape; aux_loss is the GShard load-balance loss
    (mean fraction * mean gate prob per expert, scaled by E).

    compute_dtype: AMP dtype for the two expert FFN einsums (the MXU hot
    path); routing softmax/argmax/bookkeeping and the aux loss always run
    in the input dtype — casting must happen INSIDE (both operands of
    each dot), or jnp promotion silently undoes it.
    """
    orig_shape = x.shape
    d = orig_shape[-1]
    tokens = x.reshape(-1, d)
    n = tokens.shape[0]
    e = params["gate"].shape[1]
    cap = max(1, int(n * capacity_factor * k / e))

    logits = tokens @ params["gate"]  # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)

    combine = jnp.zeros((n, e, cap), tokens.dtype)
    remaining = probs
    # position counters per expert accumulate across the k routing rounds
    fill = jnp.zeros((e,), jnp.int32)
    frac_routed = jnp.zeros((e,), probs.dtype)
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)  # [N]
        gate = jnp.take_along_axis(remaining, idx[:, None], axis=-1)[:, 0]
        onehot = jax.nn.one_hot(idx, e, dtype=tokens.dtype)  # [N, E]
        frac_routed = frac_routed + jnp.mean(onehot, axis=0)
        # position of each token within its expert's buffer
        pos = (jnp.cumsum(onehot, axis=0) - onehot) + fill[None, :]
        pos_t = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)  # [N]
        keep = pos_t < cap
        gate = gate * keep.astype(gate.dtype)
        pos_onehot = jax.nn.one_hot(pos_t, cap, dtype=tokens.dtype)
        combine = combine + gate[:, None, None] * (
            onehot[:, :, None] * pos_onehot[:, None, :]
        )
        fill = fill + jnp.sum(
            onehot * keep[:, None].astype(onehot.dtype), axis=0
        ).astype(jnp.int32)
        remaining = remaining * (1.0 - onehot)  # mask the chosen expert

    # renormalize the k gates per token (GShard normalizes top-k probs)
    denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
    combine = combine / jnp.maximum(denom, 1e-9)

    dispatch = (combine > 0).astype(tokens.dtype)
    # all-to-all happens here under GSPMD: tokens -> expert shards
    expert_in = jnp.einsum("nec,nd->ecd", dispatch, tokens)
    cd = compute_dtype or tokens.dtype
    h = jax.nn.relu(
        jnp.einsum("ecd,edf->ecf", expert_in.astype(cd),
                   params["w1"].astype(cd))
        + params["b1"].astype(cd)[:, None, :]
    )
    expert_out = (
        jnp.einsum("ecf,efd->ecd", h, params["w2"].astype(cd))
        + params["b2"].astype(cd)[:, None, :]
    ).astype(tokens.dtype)
    y = jnp.einsum("nec,ecd->nd", combine, expert_out)

    # load-balance aux loss (Shazeer/GShard): E * sum_e f_e * p_e
    mean_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum((frac_routed / k) * mean_prob)
    return y.reshape(orig_shape), aux


# ---------------------------------------------------------------------------
# dropless experts, a share of them held here
# ---------------------------------------------------------------------------

# Rows of one grouped product: the sorted assignments go through their
# experts a block at a time: the first block always, straight-line, and as
# many more as the assignments to the held experts fill past it (a `while`
# with a trip count read from the load). The first block is also what is
# cheap (PERF.md, PR 36): its gathered rows, its two float32 products, `h`
# and the down product are made once and read by its backward, where a
# block past it makes its three forward products again inside the
# backward's loop. So one block has to be the rule, and a step's time must
# not follow the seed; a skew past it costs blocks, not tokens: both are
# static shapes, and nothing is dropped.
#
# Its size follows the share of the experts held (`_block_rows`): room for
# `BLOCK_ROOM` times the load a balanced router sends here, and never under
# a `BLOCK_FLOOR`-th of the N*k assignments. The floor is what the small
# shares need: a balanced router sends 8 experts of 256 a 32nd of the
# assignments, but a freshly seeded one is skewed and Adam moves it towards
# the experts it has: at 4,096 tokens a layer's load passed an eighth in
# two seeds of nine, one that started at 4,000 of 32,768 and one that
# started at 2,700 and grew by 150 a step (PERF.md, PR 31); a quarter holds
# 8 and 4 balanced loads at the shares of 1/32 and 1/16. A chip of a
# four-chip host holds a quarter of the experts: there the balanced load
# *is* a quarter, and the room above it is what the loads called for on
# the chip (PERF.md, PR 37): with a router that tells tokens apart, 16 of
# 64 experts drew 0.195 to 0.305 of the assignments at the first step over
# 12 seeds and 4 layers and 0.385 at most over 44 steps of Adam, so 7/16
# holds them with a standard deviation or two to spare and 3/8 did not.
BLOCK_FLOOR = 4  # at least 1/4 of the N*k assignments
BLOCK_ROOM = 1.75  # times the balanced load of the share held

# What a device trace calls the parts of `moe_experts`, one level below the
# op's scope: `fwd/moe_experts/moe.route/...`,
# `bwd/moe_experts_grad/transpose(jvp(moe.gather))/...`. Every operation
# traced from here lies under one of them; inside `_overflow`'s loops the
# innermost counts (`moe.combine/while/body/moe.gather/...` is the gather's).
# PERF.md, section 3, says which metric reads them.
STAGES = (
    "moe.route",     # the float32 logits, the scores, top-k, the k selected
                     # scores by a compare over the E lanes, the weights
    "moe.sort",      # held mask, keys, one sort of (key, place, weight): the
                     # order and the weights permuted; the load counted off
                     # the keys. Nothing in these two addresses one
                     # assignment at a time: no gather, no scatter
    "moe.gather",    # a block's part of each group, its rows of x and weight;
                     # transposed, the rows' cotangent summed onto the tokens
    "moe.products",  # the grouped products and the activation between them
    "moe.combine",   # the weighting and the sum onto the tokens; the
                     # overflow loops' own counting and sums
)
# The two sums onto the tokens (`moe.gather`'s transpose, `moe.combine`) are
# XLA's `scatter-add` on the plain path and, with the kernels, a sort of the
# block's tokens, a gather of its rows in that order and the call
# `onto_tokens_tgmm` (`_onto_tokens`).


def stage(name):
    """The `jax.named_scope` of one of `STAGES`: names in the HLO's
    metadata, and no computation."""
    if name not in STAGES:
        raise ValueError(f"no stage {name!r} of moe_experts: {STAGES}")
    return jax.named_scope(name)


def moe_route(x, gate, bias, k, scaling, renormalize=True,
              score_func="sigmoid", norm_eps=0.0):
    """Router over all the experts `gate` has columns for. x: [N, D];
    gate: [D, E]; bias: [E], the correction that enters the selection and
    not the weights. The scores `s` are `sigmoid(x gate)`, each expert's
    own, or with `score_func` "softmax" the probabilities
    `softmax(x gate)` over all E. Returns (idx [N, k] int32, weights
    [N, k] float32): the k largest of `s + bias`, weighted
    `scaling * s_i / (sum_selected s_j + norm_eps)` (without
    `renormalize`, `scaling * s_i`). float32 throughout. The selected
    scores are read by `_selected`, a compare and a reduce over the E
    lanes: no gather here, and no scatter-add in the transpose."""
    if score_func not in ("sigmoid", "softmax"):
        raise ValueError(f"moe_route: score_func {score_func!r}: expected "
                         "'sigmoid' or 'softmax'")
    logits = jnp.dot(x.astype(jnp.float32), gate.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = (jax.nn.sigmoid(logits) if score_func == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    _, idx = jax.lax.top_k(
        jax.lax.stop_gradient(scores + bias.astype(jnp.float32)), k)
    w = _selected(scores, idx)
    if renormalize:
        total = jnp.sum(w, axis=-1, keepdims=True)
        w = w / (total + norm_eps if norm_eps else total)
    return idx.astype(jnp.int32), scaling * w


def _selected(scores, idx):
    """`take_along_axis(scores, idx, -1)` for rows of distinct indices
    (top-k's), scores [N, E], idx [N, k], without a gather: each of the k
    compares its index with the lane's and sums the one lane that
    matches. Its transpose is the same compare summed over k, where a lane
    matches one index at most: value and gradient hold one non-zero term a
    sum, so both are the gather's bit for bit.

    The barrier keeps the k scores a value of their own, as a gather's
    are. Without it XLA folds this sum over E into the renormalisation's
    sum over k, one reduce in another order: on the v5e the weights then
    differ from the gather's in the last bit of up to six in a hundred
    and the router's gradient in most elements, and a training run
    leaves the parent's after a step. With it weights and gradients are
    the parent's bit for bit at every cell's shape, at no cost (PERF.md,
    PR 67)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, scores.shape[-1]), 2)
    return jax.lax.optimization_barrier(
        jnp.sum(jnp.where(idx[:, :, None] == lane, scores[:, None, :],
                          jnp.zeros((), scores.dtype)), axis=-1))


@jax.custom_vjp
def _sorted_by(key, weights):
    """`key` int32 [M] and `weights` [M] through one stable sort of
    (key, place, weight) on the key. Returns (`order` int32 [M], which is
    `argsort(key, stable=True)`; `weights[order]`)."""
    place = jax.lax.iota(jnp.int32, key.shape[0])
    _, order, weight = jax.lax.sort((key, place, weights), num_keys=1,
                                    is_stable=True)
    return order, weight


def _sorted_by_fwd(key, weights):
    order, weight = _sorted_by(key, weights)
    return (order, weight), order


def _sorted_by_bwd(order, g):
    # JAX's rule for a sort's operand is a gather and its transpose a
    # scatter-add of M elements; `order` is a permutation, so sorting the
    # cotangent on it puts each element back where it came from: an exact
    # inverse, no sum, and no two keys for a stable sort to keep apart
    # (XLA's stable sort carries a place of its own along). The stage is
    # named here because a custom backward is traced outside the
    # forward's scope.
    with stage("moe.sort"):
        _, dweights = jax.lax.sort((order, g[1]), num_keys=1,
                                   is_stable=False)
    return None, dweights


_sorted_by.defvjp(_sorted_by_fwd, _sorted_by_bwd)


def _held_first(idx, weights, experts_held, held_from):
    """The router's [N, k] choices in the order the grouped product takes
    them: by held expert, a token's place deciding between equals, those
    to experts held elsewhere last with a weight of zero. Returns (token
    [N*k] int32, weight [N*k], load [experts_held] int32: how many each
    held expert drew, a compare and a count over the keys)."""
    local = idx.reshape(-1) - held_from
    held = (local >= 0) & (local < experts_held)
    key = jnp.where(held, local, experts_held)
    order, weight = _sorted_by(key, jnp.where(held, weights.reshape(-1), 0.0))
    load = jnp.sum(
        key == jax.lax.broadcasted_iota(jnp.int32, (experts_held, 1), 0),
        axis=1, dtype=jnp.int32)
    return (order // idx.shape[1]).astype(jnp.int32), weight, load


# A block's rows of x and their sum back onto the tokens where the grouped
# products are the kernels' (`_block`, `kernel`): each the other's
# transpose, so each is the other's backward, and neither's is XLA's
# `scatter`, which reads, adds and writes one row of `[N, D]` at a time in
# HBM, dead rows like live ones (0.085 to 0.12 us a row of 2,048 or 2,304
# lanes on the v5e, 3.5 to 11.9 ms a sum in the expert cells: PERF.md,
# PR 69).
def _rows_of(x, token, live, dtype):
    """`x[token]` in `dtype` for the rows that are `live` ([rows, 1] bool),
    zeros for the rest. x: [N, D]; token: [rows] int32."""
    return jnp.where(live, x[token], jnp.zeros((), x.dtype)).astype(dtype)


# `_rows_of` with `_onto_tokens` for its backward. The cast lies inside, so
# the backward is handed the cotangent in `dtype` and sums that.
_from_tokens = jax.custom_vjp(_rows_of, nondiff_argnums=(3,))


def _from_tokens_fwd(x, token, live, dtype):
    # of x, its rows and its dtype: an array of no elements
    return _from_tokens(x, token, live, dtype), (token, live, x[:, :0])


def _from_tokens_bwd(dtype, res, g):
    token, live, like = res
    # a custom backward is traced outside the forward's scope
    with stage("moe.gather"):
        return _onto_tokens(g, token, live, like.shape[0],
                            like.dtype), None, None


_from_tokens.defvjp(_from_tokens_fwd, _from_tokens_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _onto_tokens(values, token, live, n, dtype):
    """`zeros([n, D]).at[token].add(values)` over the `live` rows, summed
    in float32 and returned in `dtype`, as grouped 0/1 products over the
    rows sorted by token (`ops/pallas/embedding_grad.py::run_sums`, the
    embedding table's gradient: the kernel is `moe_tgmm` under the name
    `onto_tokens_tgmm`). A dead row takes a key past every token, is
    sorted last and lies in no group: it costs no visit, and what it
    holds reaches nothing. bfloat16 values go through as they are,
    float32 ones as three bfloat16 slices each: the scatter's sums up to
    the order of the float32 additions, either way."""
    from ..ops.pallas.embedding_grad import run_sums

    key = jnp.where(live[:, 0], token, jnp.iinfo(jnp.int32).max)
    sums = run_sums(key, values, n, name="onto_tokens_tgmm")
    return sums.reshape(-1, values.shape[1])[:n].astype(dtype)


def _onto_tokens_fwd(values, token, live, n, dtype):
    return (_onto_tokens(values, token, live, n, dtype),
            (token, live, values[:0]))


def _onto_tokens_bwd(n, dtype, res, g):
    token, live, like = res
    with stage("moe.combine"):
        return _from_tokens(g, token, live, like.dtype), None, None


_onto_tokens.defvjp(_onto_tokens_fwd, _onto_tokens_bwd)


def _block_rows(total, share):
    """Rows of a block of `total` sorted assignments where `share` of the
    experts are held (`experts_held / experts_total`): 8,192 of 32,768 at
    1/32, 16,384 of 65,536 at 1/16, 28,672 of 65,536 at 1/4."""
    room = math.ceil(BLOCK_ROOM * share * total)
    return min(max(total // BLOCK_FLOOR, room, 1), total)


def _block_count(sizes, rows):
    return (jnp.sum(sizes) + rows - 1) // rows


def _block(j, rows, x, w_gate, w_up, w_down, token, weight, sizes, dtype,
           kernel):
    """Rows [j*rows, (j+1)*rows) of the sorted assignments through their
    experts' SiLU-gated FFN (`w_gate` None: `W_down relu(W_up x)^2`, no
    gate), weighted and summed onto their tokens:
    [N, D] float32. Rows past the held assignments carry a zero input and
    weight. With `kernel` the three products are `grouped_matmul`'s
    (ops/pallas/grouped_matmul.py), which is told the groups' true sizes
    and whose kernels visit no row tile past them, forward and backward:
    a dead row's product and its `dx` are zeros, and nothing of a block's
    three kinds of product (`y`, `dx`, `dw`) is XLA's; and the rows come
    from their tokens and go back onto them
    through `_from_tokens` and `_onto_tokens`, each the other's backward:
    the sums are grouped 0/1 products over the live rows, in which a dead
    row lies in no group, so nothing masks what it holds. Without,
    the products are `jax.lax.ragged_dot`'s, for which the last group is
    stretched over the dead rows so that every row lies in a group, the
    sums XLA's `scatter-add` over every row of the block, and the `where`
    below keeps the dead rows from every token."""
    with stage("moe.gather"):
        lo = j * rows
        ends = jnp.cumsum(sizes)
        # this block's part of each group
        part = (jnp.clip(ends - lo, 0, rows)
                - jnp.clip(ends - sizes - lo, 0, rows)).astype(jnp.int32)
        live = (lo + jnp.arange(rows) < ends[-1])[:, None]
        token = jax.lax.dynamic_slice_in_dim(token, lo, rows)
        weight = jax.lax.dynamic_slice_in_dim(weight, lo, rows)
        xs = (_from_tokens if kernel else _rows_of)(x, token, live, dtype)

    if kernel:
        from ..ops.pallas.grouped_matmul import grouped_matmul

        # casts the weight inside, again for the backward (as below)
        def dot(a, w):
            return grouped_matmul(a, w, part)
    else:
        with stage("moe.gather"):
            part = part.at[-1].add(rows - jnp.sum(part))

        @jax.checkpoint
        def dot(a, w):
            # a weight's cast is made again for the backward's transposed
            # product and not kept from the forward: the first block's
            # backward would hold 0.1 GB a weight a layer at the cells'
            # widths
            return jax.lax.ragged_dot(a, w.astype(dtype), part,
                                      preferred_element_type=jnp.float32)

    with stage("moe.products"):
        h = (jnp.square(jax.nn.relu(dot(xs, w_up))) if w_gate is None
             else jax.nn.silu(dot(xs, w_gate)) * dot(xs, w_up))
        y = dot(h.astype(dtype), w_down)
    with stage("moe.combine"):
        y = y * weight[:, None]
        if kernel:
            return _onto_tokens(y, token, live, x.shape[0], jnp.float32)
        return jnp.zeros(x.shape, jnp.float32).at[token].add(
            jnp.where(live, y, 0.0))


def _held_experts(x, w_gate, w_up, w_down, token, weight, sizes, dtype,
                  rows, kernel):
    """The sorted assignments through the experts held here, `rows` of
    them a block: [N, D] float32. The first block is straight-line JAX
    that `jax.vjp` goes through like any other op's lowering, so the
    forward op and the replay inside its gradient op emit one block and
    XLA merges them; the blocks a skewed load fills past it are added by
    `_overflow`."""
    # whole blocks: a last block that ran past the assignments would have
    # its slice moved back over rows already done
    short = -token.shape[0] % rows
    if short:
        with stage("moe.sort"):
            token = jnp.pad(token, (0, short))
            weight = jnp.pad(weight, (0, short))
    first = _block(0, rows, x, w_gate, w_up, w_down, token, weight, sizes,
                   dtype, kernel)
    with stage("moe.combine"):
        return _overflow(first, x, w_gate, w_up, w_down, token, weight, sizes,
                         dtype, rows, kernel)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _overflow(first, x, w_gate, w_up, w_down, token, weight, sizes, dtype,
              rows, kernel):
    """`first` and the blocks from the second on, as many as the load
    fills: none in a layer whose load stays under a block."""
    return jax.lax.fori_loop(
        1, _block_count(sizes, rows),
        lambda j, out: out + _block(j, rows, x, w_gate, w_up, w_down, token,
                                    weight, sizes, dtype, kernel),
        first)


def _overflow_fwd(first, x, w_gate, w_up, w_down, token, weight, sizes, dtype,
                  rows, kernel):
    out = _overflow(first, x, w_gate, w_up, w_down, token, weight, sizes,
                    dtype, rows, kernel)
    return out, (x, w_gate, w_up, w_down, token, weight, sizes)


def _overflow_bwd(dtype, rows, kernel, res, g):
    # A loop of its own, block by block as the forward: the trip count is
    # data, which jax.vjp cannot take through a `while`, so an overflow
    # block's intermediates are rebuilt here and not kept from the forward
    # (nine grouped products a trip where the first block's backward makes
    # six). XLA's CSE does not look into a `while` body either: what runs
    # every step has to stay out of one.
    x, w_gate, w_up, w_down, token, weight, sizes = res
    diff = (x, w_gate, w_up, w_down, weight)

    def body(j, grads):
        _, pull = jax.vjp(
            lambda x, a, b, c, w: _block(j, rows, x, a, b, c, token, w,
                                         sizes, dtype, kernel), *diff)
        return jax.tree.map(jnp.add, grads, pull(g))

    with stage("moe.combine"):
        zeros = jax.tree.map(lambda t: jnp.zeros(t.shape, t.dtype), diff)
        dx, da, db, dc, dw = jax.lax.fori_loop(
            1, _block_count(sizes, rows), body, zeros)
    return g, dx, da, db, dc, None, dw, None


_overflow.defvjp(_overflow_fwd, _overflow_bwd)


def moe_experts(x, gate, bias, w_gate, w_up, w_down, k, scaling,
                experts_held, held_from, renormalize=True,
                compute_dtype=None, score_func="sigmoid", kernel=False,
                norm_eps=0.0, experts_x=None):
    """The part of a dropless expert layer that the experts held here
    give. x: [..., D]; gate: [D, experts_total]; bias: [experts_total];
    w_gate, w_up: [experts_held, D, F]; w_down: [experts_held, F, D].
    Returns (y like x, load [experts_held] int32: assignments per held
    expert). `w_gate` None: the experts are `W_down relu(W_up x)^2`, with
    no gate. `experts_x` [..., D_e]: what the experts read where it is
    not what the router reads (a latent of the token); the experts'
    matrices are then D_e wide and y is like `experts_x`.

    The N*k assignments are sorted by held expert, those to experts held
    elsewhere last (one sort that carries the weights along: `_sorted_by`),
    the load is a count of each held expert's keys, and the sorted rows go
    through the grouped product
    with the load as the group sizes, a block of `_block_rows` of them a
    time: no `[N, E, capacity]` tensor, no capacity, no loop over k, and
    up to the rows of x no gather or scatter of single assignments.
    `kernel` is the caller's word that `grouped_matmul_viable` admits the
    widths and the dtype here (the op's lowering asks, and counts):
    the product is then the Pallas kernels' and no row of x is scattered
    either (a block's rows are gathered, and summed back by `_onto_tokens`'
    products), else `jax.lax.ragged_dot` between XLA's gather and
    `scatter-add`."""
    shape = x.shape
    with stage("moe.route"):
        tokens = x.reshape(-1, shape[-1])
        idx, weights = moe_route(tokens, gate, bias, k, scaling, renormalize,
                                 score_func, norm_eps)
    with stage("moe.sort"):
        token, weight, load = _held_first(idx, weights, experts_held,
                                          held_from)
    if experts_x is not None:
        x, shape = experts_x, experts_x.shape
        with stage("moe.gather"):
            tokens = x.reshape(-1, shape[-1])
    y = _held_experts(tokens, w_gate, w_up, w_down, token, weight, load,
                      compute_dtype or tokens.dtype,
                      _block_rows(token.shape[0],
                                  experts_held / gate.shape[1]), kernel)
    with stage("moe.combine"):
        return y.astype(x.dtype).reshape(shape), load
