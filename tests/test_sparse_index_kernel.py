"""The Pallas kernels of attention that chooses its keys
(`ops/pallas/sparse_index.py`) in the interpreter, at blocks of 128,
against the ops' `jnp` forms (`ops/sparse_attn_ops.py`), which the
decoder suite holds to the plain reference: the indexer's score and its
three gradients, the selection with ties and short rows, the target of the
indexer's loss with the loss's rows and its gradient, and what each
declares."""

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
    got, kl, sp, lq = si.head_mean_probabilities(
        jnp.transpose(q, (0, 2, 1, 3)), jnp.transpose(kk, (0, 2, 1, 3)), lse,
        jnp.asarray(index), admit, 0.25, k)
    want = jnp.mean(jax.nn.softmax(scores, -1), 1)
    assert rel(got, want) < 1e-5
    assert not np.asarray(got)[np.asarray(admit) == 0].any()
    np.testing.assert_allclose(np.asarray(got).sum(-1), 1.0, rtol=1e-5)
    # and the loss from it is the blocked form's, in `jnp` and as the
    # kernel summed it
    rows = ops.kl_from_target(got, jnp.asarray(index), admit)
    blocked = ops.index_kl_rows(q, kk, lse, jnp.asarray(index), admit, 0.25)
    np.testing.assert_allclose(rows, blocked, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(kl, blocked, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(sp, 1.0, rtol=1e-5)
    np.testing.assert_allclose(lq, jax.nn.logsumexp(
        jnp.where(admit != 0, index, -jnp.inf), -1), rtol=1e-5)


def _loss_case(r, case, b=2, s=384, h=4, g=2, d=16, k=24):
    """q and k head-major, lse, index, admit and the rows' cotangent of
    one `index_kl` call at three blocks of 128 a row, so a row's sums
    cross key blocks and rows under `k` admit every causal key."""
    q = jnp.asarray(r.randn(b, h, s, d), jnp.float32)
    kk = jnp.asarray(r.randn(b, g, s, d), jnp.float32)
    index = np.where(np.tril(np.ones((s, s), bool)), 3 * r.randn(b, s, s),
                     -np.inf).astype(np.float32)
    admit, _ = ops.select(jnp.asarray(index), k)
    cot = jnp.ones((b, s), jnp.float32)
    if case == "a_zero_target":
        # a key at -1,000 from the last query in every head, so that each
        # exponential is 0: an admitted pair with p == 0, whose term is 0
        # and not nan
        last = np.asarray(q)[:, :, s - 1].reshape(b, g, h // g, d)
        far = -1e3 * np.einsum("bgdh,h->bgd", np.linalg.pinv(last),
                               np.ones(h // g))
        kk = kk.at[:, :, 300].set(jnp.asarray(far, jnp.float32))
        admit = admit.at[:, s - 1, 300].set(1)
    elif case == "weighted_rows":
        cot = jnp.asarray(r.randn(b, s), jnp.float32)
    elif case == "scores_far_from_0":
        # the sums are held against the row's largest score: a shift of
        # the scores leaves the loss where it was
        index = index + np.float32(30.0)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(kk, h // g, 1)) / 4
    lse = jax.nn.logsumexp(
        jnp.where(admit[:, None] != 0, scores, -jnp.inf), -1)
    if case == "rows_off_one":
        # log-sum-exp rows that are not the admitted scores' own: the
        # target's rows sum to 0.85 to 1.15, as far from 1 as one likes
        lse = lse + 0.1 * jnp.asarray(r.randn(b, h, s), jnp.float32)
    return q, kk, lse, jnp.asarray(index), admit, cot, k


LOSS_CASES = ["plain", "rows_off_one", "a_zero_target", "weighted_rows",
              "scores_far_from_0"]


@pytest.mark.parametrize("case", LOSS_CASES)
def test_the_kernels_rows_are_the_plain_divergence(rng, case):
    """`index_kl_target`'s three rows against `kl_from_target` on the p it
    wrote, the sum of that p and the log-sum-exp of the admitted scores:
    float32's order of summation apart."""
    q, kk, lse, index, admit, _, k = _loss_case(rng, case)
    p, kl, sp, lq = si.head_mean_probabilities(q, kk, lse, index, admit,
                                               0.25, k)
    assert all(x.shape == lse.shape[::2] and x.dtype == jnp.float32
               for x in (kl, sp, lq))
    assert np.asarray(admit)[:, :k].sum(-1).tolist() == [
        list(range(1, k + 1))] * 2  # fewer causal keys than k: all kept
    if case == "a_zero_target":
        assert np.asarray(admit)[:, -1, 300].all()
        assert not np.asarray(p)[:, -1, 300].any()
    if case == "rows_off_one":
        assert np.abs(np.asarray(sp) - 1).max() > 0.1
    np.testing.assert_allclose(kl, ops.kl_from_target(p, index, admit),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(sp, jnp.sum(p, -1), rtol=1e-5)
    np.testing.assert_allclose(lq, jax.nn.logsumexp(
        jnp.where(admit != 0, index, -jnp.inf), -1), rtol=1e-5)
    assert np.array_equal(si.index_kl(q, kk, lse, index, admit, 0.25, k), kl)


@pytest.mark.parametrize("case", LOSS_CASES)
def test_the_loss_gradient_is_autodiffs_of_the_plain_divergence(rng, case):
    """`index_kl`'s rule against `jax.grad` of `kl_from_target`: the
    admitted softmax times the row's sum of p, less p, times the row's
    cotangent; nothing for q, k, the log-sum-exp rows or a refused pair."""
    q, kk, lse, index, admit, cot, k = _loss_case(rng, case)
    p = si.head_mean_probabilities(q, kk, lse, index, admit, 0.25, k)[0]
    got = jax.grad(lambda i: jnp.sum(
        si.index_kl(q, kk, lse, i, admit, 0.25, k) * cot))(index)
    want = jax.grad(lambda i: jnp.sum(
        ops.kl_from_target(p, i, admit) * cot))(index)
    assert got.dtype == jnp.float32 and np.isfinite(got).all()
    assert not np.asarray(got)[np.asarray(admit) == 0].any()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    if case == "rows_off_one":
        # a gradient that took the row's sum of p for 1 is another
        soft = jax.nn.softmax(jnp.where(admit != 0, index, -jnp.inf), -1)
        assert rel((soft - p) * cot[..., None], want) > 0.05
    others = jax.grad(lambda *a: jnp.sum(
        si.index_kl(*a, index, admit, 0.25, k)), (0, 1, 2))(q, kk, lse)
    assert not any(np.asarray(x).any() for x in others)


def test_what_the_kernels_declare(monkeypatch):
    """The score's products over the causal pairs (2 d a head forward, 6 d
    backward), the target's over the admitted pairs with an exponential a
    head (and the loss's two a pair), the selection's compares and counts;
    every operand and output once."""
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
        jnp.zeros((b, h, s), jnp.float32), index, admit, 1.0, k)
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
    # the products and the heads' exponentials as before the kernel
    # summed the loss's rows; with them an exponential and a logarithm a
    # pair and a logarithm a row, the scores read and three rows written
    assert (target.flops, target.transcendentals) == (
        2 * dh * h * admitted, (h + 2) * admitted + s)
    assert target.bytes_accessed == (2 * h * s * dh + 2 * g * s * dh
                                     + 4 * h * s + 4 * s * s + s * s
                                     + 4 * s * s + 3 * 4 * s)
