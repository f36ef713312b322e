"""The Pallas kernels of attention that chooses its keys
(`ops/pallas/sparse_index.py`) in the interpreter, at blocks of 128,
against the ops' `jnp` forms (`ops/sparse_attn_ops.py`), which the
decoder suite holds to the plain reference: the indexer's score and its
three gradients, the selection with ties and short rows, the target of the
indexer's loss, and what each declares."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import sparse_attn_ops as ops

si = importlib.import_module("paddle_tpu.ops.pallas.sparse_index")


@pytest.fixture(autouse=True)
def _interpret_at_small_blocks(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(si, "BLOCK", 128)
    monkeypatch.setattr(si, "SELECT_ROWS", 32)


def rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / (np.sqrt(np.mean(want ** 2)) + 1e-30))


def _indexer(r, b, s, heads, d, dtype=jnp.float32):
    return (jnp.asarray(r.randn(b, s, heads, d), dtype),
            jnp.asarray(r.randn(b, s, d), dtype),
            jnp.asarray(r.randn(b, s, heads), jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_score_and_its_gradients_are_the_plain_forms(rng, dtype):
    b, s, heads, d = 2, 384, 4, 16
    q, k, w = _indexer(rng, b, s, heads, d, dtype)
    cot = jnp.asarray(np.tril(rng.randn(b, s, s)), jnp.float32)

    def ours(q, k, w):
        out = si.index_scores(jnp.transpose(q, (0, 2, 1, 3)), k, w, 0.125)
        return jnp.sum(jnp.where(jnp.isfinite(out), out, 0.0) * cot), out

    def theirs(q, k, w):
        out = ops.index_scores(q, k, w, 0.125)
        return jnp.sum(jnp.where(jnp.isfinite(out), out, 0.0) * cot), out

    (_, got), grads = jax.value_and_grad(ours, (0, 1, 2), has_aux=True)(q, k, w)
    (_, want), wants = jax.value_and_grad(theirs, (0, 1, 2), has_aux=True)(
        q, k, w)
    assert np.array_equal(np.isneginf(got), np.isneginf(want))
    assert np.isneginf(got[0, 5, 6]) and np.isfinite(got[0, 6, 6])
    limit = 1e-5 if dtype == jnp.float32 else 2e-2
    assert rel(np.tril(got), np.tril(want)) < limit
    for name, g, w_ in zip("qkw", grads, wants):
        assert g.dtype == w_.dtype and g.shape == w_.shape, name
        assert rel(g, w_) < limit, name


def test_a_gradient_above_the_diagonal_is_refused(rng):
    """Whatever arrives for a pair the diagonal refuses moves nothing."""
    b, s, heads, d = 1, 256, 2, 8
    q, k, w = _indexer(rng, b, s, heads, d)
    qh = jnp.transpose(q, (0, 2, 1, 3))
    g = jnp.asarray(rng.randn(b, s, s), jnp.float32)
    below = jnp.asarray(np.tril(np.asarray(g)))
    for x, y in zip(si._index_bwd(qh, k, w, g, 0.5, 128),
                    si._index_bwd(qh, k, w, below, 0.5, 128)):
        assert np.array_equal(x, y)


@pytest.mark.parametrize("k", [1, 24, 200])
def test_the_selection_is_the_plain_forms_bit_for_bit(rng, k):
    b, s = 2, 256
    index = np.where(np.tril(np.ones((s, s), bool)), rng.randn(b, s, s),
                     -np.inf).astype(np.float32)
    index[0, 100, :40] = 0.25  # ties across the threshold
    index[0, 101, :101:2] = 0.0
    index[0, 101, 1:101:2] = -0.0
    index[1, 200] = np.where(np.arange(s) <= 200,
                             np.round(index[1, 200]), -np.inf)
    admit, tau = si.select(jnp.asarray(index), k)
    want_admit, want_tau = ops.select(jnp.asarray(index), k)
    assert admit.dtype == jnp.int8 and tau.shape == (b, s)
    assert np.array_equal(admit, want_admit)
    assert np.array_equal(tau, want_tau)
    sizes = np.asarray(admit).sum(-1)
    assert (sizes >= np.minimum(np.arange(s) + 1, k)).all()
    # against a sort, row by row
    for t in (0, k - 1, k, 100, 255):
        row = np.sort(index[0, t, :t + 1])
        kth = row[len(row) - k] if t + 1 > k else -np.inf
        assert np.asarray(tau)[0, t] == kth


def test_the_target_is_the_heads_probabilities_averaged(rng):
    b, s, h, g, d, k = 2, 256, 4, 2, 16, 24
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    kk = jnp.asarray(rng.randn(b, s, g, d), jnp.float32)
    index = np.where(np.tril(np.ones((s, s), bool)), rng.randn(b, s, s),
                     -np.inf).astype(np.float32)
    admit, _ = ops.select(jnp.asarray(index), k)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(kk, h // g, 2)) / 4
    scores = jnp.where(admit[:, None] != 0, scores, -jnp.inf)
    lse = jax.nn.logsumexp(scores, -1)
    got = si.head_mean_probabilities(
        jnp.transpose(q, (0, 2, 1, 3)), jnp.transpose(kk, (0, 2, 1, 3)), lse,
        admit, 0.25, k)
    want = jnp.mean(jax.nn.softmax(scores, -1), 1)
    assert rel(got, want) < 1e-5
    assert not np.asarray(got)[np.asarray(admit) == 0].any()
    np.testing.assert_allclose(np.asarray(got).sum(-1), 1.0, rtol=1e-5)
    # and the loss from it is the blocked form's
    rows = ops.kl_from_target(got, jnp.asarray(index), admit)
    np.testing.assert_allclose(
        rows, ops.index_kl_rows(q, kk, lse, jnp.asarray(index), admit, 0.25),
        rtol=1e-4, atol=1e-6)


def test_what_the_kernels_declare(monkeypatch):
    """The score's products over the causal pairs (2 d a head forward, 6 d
    backward), the target's over the admitted pairs with an exponential a
    head, the selection's compares and counts; every operand and output
    once."""
    seen = {}
    real = si.pl.pallas_call

    def spy(kernel, **kw):
        seen[kw["name"]] = kw["cost_estimate"]
        return real(kernel, **kw)

    monkeypatch.setattr(si.pl, "pallas_call", spy)
    b, s, heads, d, h, g, dh, k = 1, 256, 4, 16, 8, 2, 32, 64
    q = jnp.zeros((b, heads, s, d), jnp.bfloat16)
    kk = jnp.zeros((b, s, d), jnp.bfloat16)
    w = jnp.zeros((b, s, heads), jnp.float32)
    jax.vjp(lambda *a: si.index_scores(*a, 1.0), q, kk, w)[1](
        jnp.zeros((b, s, s), jnp.float32))
    index = jnp.zeros((b, s, s), jnp.float32)
    admit, _ = si.select(index, k)
    si.head_mean_probabilities(
        jnp.zeros((b, h, s, dh), jnp.bfloat16),
        jnp.zeros((b, g, s, dh), jnp.bfloat16),
        jnp.zeros((b, h, s), jnp.float32), admit, 1.0, k)
    causal = s * (s + 1) // 2
    admitted = sum(min(t + 1, k) for t in range(s))
    moved = 2 * heads * s * d + 2 * s * d + 4 * s * heads
    assert (seen["sparse_index_fwd"].flops,
            seen["sparse_index_fwd"].bytes_accessed) == (
        2 * d * heads * causal, moved + 4 * s * s)
    assert (seen["sparse_index_bwd"].flops,
            seen["sparse_index_bwd"].bytes_accessed) == (
        6 * d * heads * causal, 2 * moved + 4 * s * s)
    assert (seen["sparse_select"].flops,
            seen["sparse_select"].bytes_accessed) == (
        65 * s * s, 4 * s * s + s * s + 4 * s)
    target = seen["index_kl_target"]
    assert (target.flops, target.transcendentals) == (
        2 * dh * h * admitted, h * admitted)
    assert target.bytes_accessed == (2 * h * s * dh + 2 * g * s * dh
                                     + 4 * h * s + s * s + 4 * s * s)
