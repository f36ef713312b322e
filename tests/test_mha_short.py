"""Short-sequence attention kernel (ops/pallas/mha_short.py), whose
operands are the [b, s, heads*dh] arrays the projections write, against the
float32 reference, in the Pallas interpreter on the CPU (same harness
pattern as tests/test_flash_attention.py)."""

import os

os.environ.setdefault("PADDLE_TPU_PALLAS_INTERPRET", "1")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import mha_short as kernel_module
from paddle_tpu.ops.pallas.flash_attention import _reference_attention
from paddle_tpu.ops.pallas.mha_short import (
    MAX_SHORT_SEQ,
    _pick_bb,
    mha_short,
    mha_short_viable,
)

KEY = jax.random.key(0)


def _mk(b, h, sq, sk, d, use_bias, causal=False, dtype=jnp.float32):
    shape = lambda s: (b, s, h * d)  # noqa: E731
    q = jax.random.normal(jax.random.fold_in(KEY, 1), shape(sq)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 2), shape(sk)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 3), shape(sk)).astype(dtype)
    bias = None
    if use_bias:
        bias = jnp.where(
            jax.random.uniform(jax.random.fold_in(KEY, 4), (b, sk)) > 0.2,
            0.0, -1e30,
        ).astype(jnp.float32)
        # a row whose only visible key is masked out is undefined in
        # softmax; keep key 0 live
        bias = bias.at[:, 0].set(0.0)
    return q, k, v, bias


def _heads(x, h):
    b, s, w = x.shape
    return x.reshape(b, s, h, w // h).transpose(0, 2, 1, 3)


def _reference(q, k, v, bias, causal, h):
    """The float32 reference on the merged layout."""
    d = q.shape[-1] // h
    out = _reference_attention(
        _heads(q.astype(jnp.float32), h), _heads(k.astype(jnp.float32), h),
        _heads(v.astype(jnp.float32), h), bias, causal, 1.0 / np.sqrt(d),
        0.0, None)
    b, _, s, _ = out.shape
    return out.transpose(0, 2, 1, 3).reshape(b, s, h * d)


SHAPES = [
    # b, h, sq, sk, d, bias, causal
    (2, 2, 64, 64, 64, False, False),   # the transformer's, two heads a slice
    (2, 4, 32, 32, 64, True, False),    # two 128-lane slices
    (2, 2, 32, 48, 64, True, False),    # cross attention, sq != sk
    (3, 2, 20, 20, 64, True, True),     # no multiple of 8: padded
    (1, 2, 100, 100, 64, False, True),
    (2, 2, 1, 24, 64, True, False),     # the decode step
    (2, 1, 16, 64, 128, False, True),   # dh=128, one head a slice, sq < sk
    (3, 2, 32, 32, 128, True, False),
    # score rows of one lane tile and of several
    (1, 2, 128, 128, 64, True, False),   # BERT phase 1's
    (1, 2, 512, 512, 64, True, False),   # BERT phase 2's
    (2, 2, 48, 144, 64, True, True),     # sq != sk, causal
    (1, 2, 100, 200, 64, True, False),   # no multiple of 16: padded
    (2, 1, 40, 136, 128, False, True),   # dh=128, two lane tiles of keys
]


@pytest.mark.parametrize("b,h,sq,sk,d,use_bias,causal", SHAPES)
def test_matches_reference(b, h, sq, sk, d, use_bias, causal):
    q, k, v, bias = _mk(b, h, sq, sk, d, use_bias, causal)
    out = jax.jit(lambda q, k, v: mha_short(q, k, v, h, bias=bias,
                                            causal=causal))(q, k, v)
    assert out.shape == q.shape and out.dtype == q.dtype
    want = jax.jit(lambda q, k, v: _reference(q, k, v, bias, causal, h))(
        q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def _grads(fn, q, k, v):
    # compiled whole: op by op, every primitive is a module of its own
    return jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v).astype(jnp.float32))),
        argnums=(0, 1, 2)))(q, k, v)


@pytest.mark.parametrize("b,h,sq,sk,d,use_bias,causal", [
    SHAPES[0], SHAPES[2], SHAPES[3], SHAPES[6], SHAPES[7], *SHAPES[8:]])
def test_grads_match_reference(b, h, sq, sk, d, use_bias, causal):
    q, k, v, bias = _mk(b, h, sq, sk, d, use_bias, causal)
    want = _grads(lambda q, k, v: _reference(q, k, v, bias, causal, h),
                  q, k, v)
    got = _grads(lambda q, k, v: mha_short(q, k, v, h, bias=bias,
                                           causal=causal), q, k, v)
    for a, b_ in zip(want, got):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-5)


@pytest.mark.parametrize("sk", [64, 512])
def test_the_rows_reciprocal_is_a_float32_one(sk):
    """v == ones: an output row is l * (1/l). The approximate reciprocal
    is a bfloat16 one here (8 bits; the chip's has 12 or more), and a
    single Newton step from it would leave 2**-16."""
    q, k, v, bias = _mk(2, 2, 64, sk, 64, True)
    out = mha_short(q * 4.0, k, jnp.ones_like(v), 2, bias=bias)
    assert float(jnp.max(jnp.abs(out - 1.0))) < 2e-6


@pytest.mark.parametrize("d,causal", [(64, True), (128, False)])
def test_bf16_forward_and_grads(d, causal):
    """bfloat16 operands, float32 inside: as close to the float32
    reference on the same rounded operands as bfloat16's own step."""
    b, h, s = 2, 2, 32
    q, k, v, bias = _mk(b, h, s, s, d, True, causal, dtype=jnp.bfloat16)
    out = mha_short(q, k, v, h, bias=bias, causal=causal)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(_reference(q, k, v, bias, causal, h)), atol=3e-2)
    want = _grads(lambda q, k, v: _reference(q, k, v, bias, causal, h),
                  q, k, v)
    got = _grads(lambda q, k, v: mha_short(q, k, v, h, bias=bias,
                                           causal=causal), q, k, v)
    for a, b_ in zip(want, got):
        assert b_.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b_, np.float32), atol=5e-2)


# key lengths of one lane tile, of two with padding, and BERT phase 2's,
# which the benchmark's `correct` runs without dropout
@pytest.mark.parametrize("b,s,sk", [(4, 64, 64), (4, 48, 144), (2, 64, 512)])
def test_dropout_deterministic_and_unbiased(b, s, sk):
    h, d = 4, 64
    q, k, v, _ = _mk(b, h, s, sk, d, False)
    v = jnp.ones_like(v)
    rng = jax.random.fold_in(KEY, 7)
    o1 = mha_short(q, k, v, h, dropout=0.3, rng_key=rng)
    o2 = mha_short(q, k, v, h, dropout=0.3, rng_key=rng)
    assert bool(jnp.all(o1 == o2))
    o3 = mha_short(q, k, v, h, dropout=0.3,
                   rng_key=jax.random.fold_in(KEY, 8))
    assert not bool(jnp.all(o1 == o3))
    # v == ones: an output row is the kept probability mass over the keep
    # probability, 1 in expectation
    assert abs(float(jnp.mean(o1)) - 1.0) < 0.02
    # every head and every batch row draws a mask of its own
    per_head = np.asarray(o1).reshape(b, s, h, d)[..., 0]
    assert len({per_head[i, :, j].tobytes()
                for i in range(b) for j in range(h)}) == b * h


def _keep_mask(b, h, sq, sk, d, rate, seed):
    """The kernel's keep-mask [b, h, sq, sk], read off its output: zero
    queries and keys make every probability 1/sk, and values that are
    the identity on a head's first sk lanes put pair (q, k)'s kept
    probability on lane k of row q."""
    assert sk <= d
    q = jnp.zeros((b, sq, h * d), jnp.float32)
    k = jnp.zeros((b, sk, h * d), jnp.float32)
    v = jnp.tile(jnp.eye(sk, d, dtype=jnp.float32), (b, 1, h))
    out = mha_short(q, k, v, h, dropout=rate,
                    rng_key=jax.random.fold_in(KEY, seed))
    return np.asarray(out).reshape(b, sq, h, d)[..., :sk].transpose(
        0, 2, 1, 3) > 0.0


# 2**20 draws each: (b, h, sq, sk, d), two heads a slice and one
MASKS = {2: (16, 2, 512, 64, 64), 1: (16, 1, 512, 128, 128)}


@pytest.mark.parametrize("rate", [0.1, 0.5])
@pytest.mark.parametrize("hp", [1, 2])
def test_dropout_rate_is_the_stated_one(hp, rate):
    keep = _keep_mask(*MASKS[hp], rate, seed=21)
    assert keep.size >= 2**20
    sigma = np.sqrt(rate * (1 - rate) / keep.size)
    assert abs(keep.mean() - (1 - rate)) < 3 * sigma


@pytest.mark.parametrize("rate", [0.1, 0.5])
@pytest.mark.parametrize("hp", [1, 2])
def test_the_two_scores_of_a_hash_word_are_independent(hp, rate):
    """Rows r and r + rows/2 of the stacked tile share a finalised word
    (at two heads a slice, the slice's two heads at one query and key; at
    one, queries half the padded length apart): the four joint outcomes
    come as often as the product of the margins says."""
    keep = _keep_mask(*MASKS[hp], rate, seed=22)
    if hp == 2:
        first, second = keep[:, 0], keep[:, 1]
    else:
        half = keep.shape[2] // 2
        first, second = keep[:, :, :half], keep[:, :, half:]
    n = first.size
    for a in (False, True):
        for b_ in (False, True):
            want = (first == a).mean() * (second == b_).mean()
            sigma = np.sqrt(want * (1 - want) / n)
            assert abs(((first == a) & (second == b_)).mean() - want) \
                < 3 * sigma


def test_dropout_mask_does_not_depend_on_the_block():
    """The hash is over global (batch, head, query, key) indices: the
    batch rows a grid step holds do not change the mask."""
    b, h, s, d = 4, 2, 32, 64
    q, k, v, _ = _mk(b, h, s, s, d, False)
    rng = jax.random.fold_in(KEY, 5)
    whole = mha_short(q, k, v, h, dropout=0.4, rng_key=rng)
    pick = kernel_module._pick_bb
    try:
        kernel_module._pick_bb = lambda *a: 1
        by_row = mha_short(q, k, v, h, dropout=0.4, rng_key=rng)
    finally:
        kernel_module._pick_bb = pick
    np.testing.assert_allclose(np.asarray(whole), np.asarray(by_row),
                               atol=1e-6)


@pytest.mark.parametrize("sk", [32, 144, 512])
def test_dropout_grad_uses_the_forwards_mask(sk):
    b, h, s, d = 1, 2, 32, 64
    q, k, v, _ = _mk(b, h, s, sk, d, False)
    rng = jax.random.fold_in(KEY, 9)

    def loss(q):
        o = mha_short(q, k, v, h, dropout=0.5, rng_key=rng)
        return jnp.sum(o.astype(jnp.float64) ** 2)

    g = jax.grad(loss)(q)
    # full-tensor directional derivative (single-coordinate fd drowns in
    # f32 cancellation); same rng -> same regenerated mask both sides
    u = jax.random.normal(jax.random.fold_in(KEY, 11), q.shape)
    eps = 1e-2
    fd = (loss(q + eps * u) - loss(q - eps * u)) / (2 * eps)
    np.testing.assert_allclose(float(jnp.vdot(g, u)), float(fd), rtol=5e-2)


def test_pick_bb_divides_the_batch_and_keeps_the_budget():
    # the transformer's, BERT phase 1's and phase 2's blocks, bfloat16
    for b, s, most in [(256, 64, 32), (256, 128, 16), (48, 512, 1)]:
        bb = _pick_bb(b, s, s, 2, 2)
        assert b % bb == 0 and bb == most
    assert _pick_bb(7, 16, 16, 1, 4) == 7  # a prime batch, whole


def test_viable_is_the_shape_rule():
    assert mha_short_viable(64, 64, 8, 64)      # the transformer's
    assert mha_short_viable(128, 128, 12, 64)   # BERT phase 1
    assert mha_short_viable(1, 64, 8, 64)       # the decode step
    assert mha_short_viable(16, 16, 2, 128)
    assert not mha_short_viable(64, 64, 8, 32)  # four heads a slice
    assert not mha_short_viable(64, 64, 3, 64)  # 192 lanes
    assert not mha_short_viable(MAX_SHORT_SEQ + 1, 64, 8, 64)
    assert not mha_short_viable(64, MAX_SHORT_SEQ + 1, 8, 64)


def test_shapes_outside_the_rule_raise():
    q, k, v, _ = _mk(1, 3, 16, 16, 64, False)
    with pytest.raises(ValueError, match="head_dim 64 or 128"):
        mha_short(q, k, v, 3)
    q, k, v, _ = _mk(1, 2, 16, 16, 64, False)
    with pytest.raises(ValueError, match="rng_key"):
        mha_short(q, k, v, 2, dropout=0.1)


@pytest.mark.parametrize("kernel", ["mha_short_fwd", "mha_short_bwd"])
@pytest.mark.parametrize("b,h,sq,sk,d,use_bias,causal,pairs", [
    # the transformer's decoder: every key up to the query's own
    (4, 4, 64, 64, 64, False, True, 64 * 65 // 2),
    # cross attention with a key bias, padded inside to 32 and 48: the
    # count is at 20 and 40, every pair (a bias's refusals are data)
    (2, 2, 20, 40, 128, True, False, 20 * 40),
])
def test_declared_cost_against_a_count_by_hand(kernel, b, h, sq, sk, d,
                                               use_bias, causal, pairs):
    """ops/pallas/cost.py's convention: forward q.k and p.v, backward q.k
    again, dO.v, p^T.dO, dS.k, dS^T.q, each 2 FLOPs a pair a lane of the
    head; an exponential a pair and a reciprocal a row; every operand and
    output once (bf16 here, the bias float32)."""
    from pallas_costs import declared

    q, k, v, bias = _mk(b, h, sq, sk, d, use_bias, dtype=jnp.bfloat16)
    found = declared(jax.grad(lambda q, k, v: jnp.sum(mha_short(
        q, k, v, h, bias=bias, causal=causal).astype(jnp.float32)),
        argnums=(0, 1, 2)), q, k, v)
    (got,) = found[kernel]
    pairs *= b * h
    q_bytes, k_bytes = 2 * b * sq * h * d, 2 * b * sk * h * d
    read = q_bytes + 2 * k_bytes + (4 * b * sk if use_bias else 0)
    if kernel == "mha_short_fwd":
        want = (2 * 2 * pairs * d, read + q_bytes)
    else:  # dO in; dq, dk, dv out
        want = (5 * 2 * pairs * d, read + q_bytes + q_bytes + 2 * k_bytes)
    assert (got.flops, got.bytes_accessed) == want
    assert got.transcendentals == pairs + b * h * sq
