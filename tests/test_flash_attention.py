"""Flash-attention kernel tests (run in Pallas interpret mode on the CPU
backend so the real kernel body is exercised — the analog of the
reference's per-op CUDA kernel tests, SURVEY.md §4 tier 2)."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kernel_cases import attn_program

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


def _rand_qkv(rng, b=2, h=2, s=128, d=64, dtype=jnp.float32):
    q = jnp.asarray(rng.randn(b, h, s, d), dtype)
    k = jnp.asarray(rng.randn(b, h, s, d), dtype)
    v = jnp.asarray(rng.randn(b, h, s, d), dtype)
    return q, k, v


def _gold(qn, kn, vn, bias=None, causal=False):
    """float64 numpy reference."""
    d = qn.shape[-1]
    s_ = np.einsum("bhqd,bhkd->bhqk", qn, kn, dtype=np.float64) / np.sqrt(d)
    if bias is not None:
        s_ = s_ + np.asarray(bias, np.float64)[:, None, None, :]
    if causal:
        sq, sk = s_.shape[-2:]
        m = np.tril(np.ones((sq, sk), bool), k=sk - sq)
        s_ = np.where(m, s_, -1e30)
    p = np.exp(s_ - s_.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, vn, dtype=np.float64)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_gold(rng, causal):
    b, h, s, d = 2, 2, 256, 64
    qn, kn, vn = rng.randn(b, h, s, d), rng.randn(b, h, s, d), rng.randn(b, h, s, d)
    q, k, v = (jnp.asarray(x, jnp.float32) for x in (qn, kn, vn))
    out = fa.flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    gold = _gold(qn, kn, vn, causal=causal)
    assert np.abs(np.asarray(out) - gold).max() < 2e-2


def test_key_bias_masks_keys(rng):
    b, h, s, d = 2, 2, 128, 64
    q, k, v = _rand_qkv(rng, b, h, s, d)
    valid = 100
    bias = jnp.where(jnp.arange(s)[None, :] < valid, 0.0, fa.NEG_INF) * jnp.ones(
        (b, 1)
    )
    out = fa.flash_attention(q, k, v, bias=bias, block_q=128, block_k=128)
    gold = _gold(
        np.asarray(q), np.asarray(k), np.asarray(v), bias=np.asarray(bias)
    )
    assert np.abs(np.asarray(out) - gold).max() < 2e-2
    # masked keys must have zero influence: perturb them
    v2 = v.at[:, :, valid:, :].set(123.0)
    out2 = fa.flash_attention(q, k, v2, bias=bias, block_q=128, block_k=128)
    assert np.abs(np.asarray(out) - np.asarray(out2)).max() < 1e-6


def test_uneven_seq_padding(rng):
    # seq not a multiple of the block size exercises the padding path
    b, h, s, d = 1, 2, 200, 32
    q, k, v = _rand_qkv(rng, b, h, s, d)
    out = fa.flash_attention(q, k, v, block_q=128, block_k=128)
    gold = _gold(np.asarray(q), np.asarray(k), np.asarray(v))
    assert out.shape == (b, h, s, d)
    assert np.abs(np.asarray(out) - gold).max() < 2e-2


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_xla_reference(rng, causal):
    b, h, s, d = 2, 2, 128, 64
    q, k, v = _rand_qkv(rng, b, h, s, d)
    sm = 1.0 / np.sqrt(d)

    def loss_flash(q, k, v):
        return jnp.sum(
            fa.flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
            ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(
            fa._reference_attention(q, k, v, None, causal, sm, 0.0, None) ** 2
        )

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(g1, g2, "qkv"):
        scale = max(1.0, float(jnp.abs(b_).max()))
        assert (
            float(jnp.abs(a - b_).max()) / scale < 2e-2
        ), f"d{name} mismatch"


def test_causal_cross_length_alignment(rng):
    """causal with sq != sk must be bottom-right aligned, matching the
    XLA reference path."""
    b, h, sq, sk, d = 1, 2, 128, 256, 32
    q = jnp.asarray(rng.randn(b, h, sq, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, sk, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, sk, d), jnp.float32)
    out = fa.flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = fa._reference_attention(
        q, k, v, None, True, 1.0 / np.sqrt(d), 0.0, None
    )
    assert np.abs(np.asarray(out) - np.asarray(ref)).max() < 2e-2


# ------------------------------------ the band and the key/value group


def _band_case(rng, b, h, hkv, sq, sk, d, layout="bhsd", dv=None,
               dtype=jnp.float32):
    """q, k, v and a weight for the output; `dv`: values (and so the
    output) of another width than the keys'."""
    def shape(n, s, width):
        return (b, s, n, width) if layout == "bshd" else (b, n, s, width)

    q, k, v, w = (jnp.asarray(rng.randn(*shape(n, s, width)), dtype)
                  for n, s, width in ((h, sq, d), (hkv, sk, d),
                                      (hkv, sk, dv or d), (h, sq, dv or d)))
    return q, k, v, w


def _out_and_grads(fn, q, k, v, w):
    """`fn`'s output and the gradients of its sum under `w`, from one
    compiled function: op by op, each primitive of the interpreter's walk
    and of the plain path is a module of its own to lower and compile."""
    def both(q, k, v):
        grads = jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2))
        return (fn(q, k, v), *grads(q, k, v))

    return jax.jit(both)(q, k, v)


@pytest.fixture
def pair_backward(monkeypatch):
    """The backward of every `flash_attention` call in the test is the
    pair `flash_bwd_dq` + `flash_bwd_dkv`, through `_bwd_pallas`, as for a
    call whose key/value head does not fit VMEM: nothing fits in none."""
    monkeypatch.setattr(fa, "_BWD_FUSED_VMEM_BYTES", 0)


@pytest.fixture(params=["fused", "pair"])
def backward(request):
    if request.param == "pair":
        request.getfixturevalue("pair_backward")
    return request.param


_FUSED_CASES = {
    # (b, h, hkv, sq, sk, d, dv), causal, window, key bias, dropout, dtype
    "causal": ((1, 2, 2, 384, 384, 64, 64), True, 0, False, 0.0,
               jnp.float32),
    "window_below_a_block": ((1, 2, 2, 384, 384, 64, 64), True, 50, False,
                             0.0, jnp.float32),
    "window_above_a_block": ((1, 2, 2, 384, 384, 64, 64), True, 200, False,
                             0.0, jnp.float32),
    "sq_is_not_sk": ((1, 2, 2, 128, 384, 64, 64), True, 150, False, 0.0,
                     jnp.float32),
    "ragged_length": ((1, 2, 2, 300, 300, 64, 64), True, 130, False, 0.0,
                      jnp.float32),
    "group_of_2": ((2, 4, 2, 256, 256, 64, 64), True, 0, False, 0.0,
                   jnp.float32),
    "group_of_8": ((1, 8, 1, 256, 256, 64, 64), True, 100, False, 0.0,
                   jnp.float32),
    "latent_192_128": ((1, 2, 2, 256, 256, 192, 128), True, 0, False, 0.0,
                       jnp.float32),
    "differential_64_128": ((1, 4, 2, 384, 384, 64, 128), True, 130, False,
                            0.0, jnp.float32),
    "no_mask": ((1, 2, 2, 256, 384, 64, 64), False, 0, False, 0.0,
                jnp.float32),
    "key_bias": ((2, 4, 2, 256, 256, 64, 64), False, 0, True, 0.0,
                 jnp.float32),
    "causal_key_bias": ((2, 2, 1, 200, 200, 192, 128), True, 0, True, 0.0,
                        jnp.float32),
    "dropout": ((2, 4, 2, 256, 256, 64, 64), False, 0, False, 0.3,
                jnp.float32),
    "causal_bias_dropout": ((2, 4, 2, 200, 200, 64, 64), True, 150, True,
                            0.3, jnp.float32),
    "bf16": ((1, 4, 2, 256, 256, 192, 128), True, 0, False, 0.0,
             jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(_FUSED_CASES))
def test_fused_backward_is_bitwise_the_pair(rng, case):
    """dq, dk, dv of `flash_bwd_dkv_dq` against `flash_bwd_dq` and
    `flash_bwd_dkv` from the same residuals: the fused call forms the
    pair's `p` and `dS`, adds a key block's terms in `flash_bwd_dkv`'s
    order (the group's heads outermost, query blocks ascending) and a
    query block's in `flash_bwd_dq`'s, so in the interpreter no bit
    differs, with a band, an offset, a group, either width, a key bias
    and dropout."""
    dims, causal, window, with_bias, dropout, dtype = _FUSED_CASES[case]
    b, h, _, sq, sk, d, dv = dims
    q, k, v, do = _band_case(rng, *dims[:6], dv=dv, dtype=dtype)
    bias = None
    if with_bias:
        bias = jnp.where(jnp.arange(sk)[None, :] < sk - 37, 0.0,
                         fa.NEG_INF) * jnp.ones((b, 1))
    qf, kf, vf, biasf, bq, bk = fa._pad_inputs(q, k, v, bias, 128, 128)
    dof = fa._pad_inputs(do, k, v, None, 128, 128)[0]
    seed = jnp.full((1,), 11, jnp.int32)
    statics = dict(sm_scale=d ** -0.5, causal=causal, causal_offset=sk - sq,
                   dropout=dropout, block_q=bq, block_k=bk, window=window,
                   dims=(sq, sk, d, dv))
    out, lse = fa._fwd_call(qf, kf, vf, biasf, seed, h, **statics)
    pair = fa._bwd_call(qf, kf, vf, biasf, seed, out, lse, dof, h, **statics)
    fused = fa._bwd_fused_call(qf, kf, vf, biasf, seed, out, lse, dof, h,
                               **statics)
    for a, b_, name in zip(fused, pair, ("dq", "dk", "dv")):
        assert a.shape == b_.shape and a.dtype == b_.dtype == dtype
        assert np.asarray(b_, np.float32).any(), name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_), name)


@pytest.mark.parametrize("case", [
    # (b, h, hkv, sq, sk, d), window, layout
    ((1, 2, 2, 384, 384, 64), 50, "bhsd"),    # smaller than a block
    ((1, 2, 2, 384, 384, 64), 200, "bhsd"),   # no multiple of the block
    ((1, 2, 2, 256, 256, 64), 256, "bhsd"),   # at least s: the causal mask
    ((1, 2, 2, 128, 384, 64), 150, "bhsd"),   # sq != sk, with the offset
    ((1, 2, 2, 300, 300, 64), 130, "bhsd"),   # a length that is no block's
    ((1, 8, 1, 256, 256, 64), 0, "bhsd"),     # a group of 8, causal alone
    ((2, 8, 2, 300, 300, 128), 130, "bhsd"),  # groups of 4 and a window
    ((1, 4, 2, 128, 384, 64), 0, "bhsd"),     # sq != sk, a group, no window
    ((1, 4, 4, 256, 256, 128), 0, "bhsd"),    # group 1, a head of 128 lanes
    ((1, 32, 8, 256, 256, 64), 0, "bhsd"),    # 32 over 8 heads of 64 lanes
], ids=lambda c: f"{c[0]}-w{c[1]}-{c[2]}")
def test_band_and_group_match_the_plain_path(rng, case, backward):
    """out, dq, dk, dv of the kernels, interpreted, with a window and with
    fewer key/value heads than query heads, against `_attention_unfused`
    in float32, the backward in one kernel and in the pair."""
    dims, window, _ = case
    q, k, v, w = _band_case(rng, *dims)
    sm = 1.0 / np.sqrt(dims[-1])
    got = _out_and_grads(
        lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, window=window, block_q=128, block_k=128),
        q, k, v, w)
    want = _out_and_grads(
        lambda q, k, v: fa._attention_unfused(
            q, k, v, None, True, sm, 0.0, None, True, window=window),
        q, k, v, w)
    for a, b_, name in zip(got, want, ("out", "dq", "dk", "dv")):
        assert a.shape == b_.shape
        scale = max(1.0, float(jnp.abs(b_).max()))
        assert float(jnp.abs(a - b_).max()) / scale < 1e-5, name


@pytest.mark.parametrize("case", [
    # (b, h, hkv, sq, sk, d, dv), window: a differential head's calls, heads
    # of 64 under values of 128, two query heads a key/value head
    ((1, 4, 2, 384, 384, 64, 128), 0),     # causal alone
    ((1, 4, 2, 384, 384, 64, 128), 130),   # a window that is no block's
    ((2, 4, 2, 128, 384, 64, 128), 150),   # sq != sk, with the offset
    ((1, 2, 2, 300, 300, 64, 256), 0),     # group 1, values four times as wide
    ((1, 4, 4, 256, 256, 128, 192), 50),   # keys of whole lanes, values of 1.5
], ids=lambda c: f"{c[0]}-w{c[1]}")
def test_values_wider_than_the_keys_match_the_plain_path(rng, case):
    """out, dq, dk, dv of the three kernels, interpreted, where `dv > d`
    (and heads of 64 lanes), against `_attention_unfused` in float32."""
    dims, window = case
    q, k, v, w = _band_case(rng, *dims[:6], dv=dims[6])
    sm = 1.0 / np.sqrt(dims[5])
    got = _out_and_grads(
        lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, window=window, block_q=128, block_k=128),
        q, k, v, w)
    want = _out_and_grads(
        lambda q, k, v: fa._attention_unfused(
            q, k, v, None, True, sm, 0.0, None, True, window=window),
        q, k, v, w)
    for a, b_, name in zip(got, want, ("out", "dq", "dk", "dv")):
        assert a.shape == b_.shape
        scale = max(1.0, float(jnp.abs(b_).max()))
        assert float(jnp.abs(a - b_).max()) / scale < 1e-5, name


def test_a_window_of_at_least_s_is_bitwise_the_causal_kernel(rng):
    q, k, v, w = _band_case(rng, 1, 2, 2, 300, 300, 64)
    run = lambda window: _out_and_grads(  # noqa: E731
        lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, window=window, block_q=128, block_k=128),
        q, k, v, w)
    for a, b_ in zip(run(0), run(300)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


@pytest.mark.parametrize("window", [0, 130])
def test_skipping_blocks_changes_no_bit(rng, window, monkeypatch):
    """Group 1: the grids that visit only the band give bitwise what the
    whole rectangles give (which is what the kernels computed before they
    skipped anything): a block no pair is admitted in adds exact zeros."""
    q, k, v, w = _band_case(rng, 1, 2, 2, 384, 384, 64)
    run = lambda: _out_and_grads(  # noqa: E731
        lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, window=window, block_q=128, block_k=128),
        q, k, v, w)
    banded = run()
    monkeypatch.setattr(fa, "_key_band", lambda j, m, xp=jnp: (
        0 * j, 0 * j + (m.nk - 1)))
    monkeypatch.setattr(fa, "_query_band", lambda kb, m, xp=jnp: (
        0 * kb, 0 * kb + (m.nq - 1)))
    jax.clear_caches()  # the jitted calls traced the banded grids
    try:
        whole = run()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    for a, b_ in zip(banded, whole):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_a_group_is_what_repeated_heads_give(rng):
    """8 query heads over 1 key/value head against 8 over 8 copies: the
    same out and dq to the bit, and dk, dv the sum over the copies'."""
    q, k, v, w = _band_case(rng, 1, 8, 1, 256, 256, 64)
    fn = lambda q, k, v: fa.flash_attention(  # noqa: E731
        q, k, v, causal=True, window=100, block_q=128, block_k=128)
    got = _out_and_grads(fn, q, k, v, w)
    rep = _out_and_grads(fn, q, jnp.repeat(k, 8, 1), jnp.repeat(v, 8, 1), w)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(rep[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(rep[1]))
    for a, b_ in zip(got[2:], rep[2:]):
        np.testing.assert_allclose(a, b_.sum(1, keepdims=True), atol=2e-5)


# ---------------------------------------------- values narrower than the keys


_NARROW_CASES = {
    # (b, h, hkv, sq, sk, d, dv), causal, window, key bias, dtype
    "causal": ((1, 2, 2, 256, 256, 192, 128), True, 0, False, jnp.float32),
    "key_bias": ((2, 2, 2, 256, 256, 192, 128), False, 0, True, jnp.float32),
    "causal_key_bias": ((2, 2, 2, 200, 200, 192, 128), True, 0, True,
                        jnp.float32),
    "group_of_2": ((1, 4, 2, 256, 256, 192, 128), True, 0, False,
                   jnp.float32),
    "window": ((1, 2, 2, 384, 384, 192, 128), True, 130, False, jnp.float32),
    "group_window_bias": ((1, 4, 1, 128, 384, 192, 128), True, 150, True,
                          jnp.float32),
    "bf16": ((1, 2, 2, 256, 256, 192, 128), True, 0, False, jnp.bfloat16),
    # two lane tiles of values under three of keys
    "wider": ((1, 2, 2, 128, 128, 320, 200), True, 0, False, jnp.float32),
}


@pytest.mark.parametrize("case", list(_NARROW_CASES))
def test_narrow_values_are_bitwise_the_padded_call(rng, case):
    """Values of 128 lanes under keys of 192 (256 padded) travel through
    the three kernels at 128: out, dq, dk and dv equal to the bit what the
    call gives when the values come padded with zeros to the keys' width
    (every operand then at 256 lanes, which is what the kernels ran before
    they had a value width) and the results are cut back; and they agree
    with the plain path in float32 like every other case."""
    dims, causal, window, with_bias, dtype = _NARROW_CASES[case]
    b, sk, d, dv = dims[0], dims[4], dims[5], dims[6]
    q, k, v, w = _band_case(rng, *dims[:6], dv=dv, dtype=dtype)
    bias = None
    if with_bias:
        bias = jnp.where(jnp.arange(sk)[None, :] < sk - 37, 0.0,
                         fa.NEG_INF) * jnp.ones((b, 1))
    flash = lambda q, k, v: fa.flash_attention(  # noqa: E731
        q, k, v, bias=bias, causal=causal, window=window, block_q=128,
        block_k=128)
    got = _out_and_grads(flash, q, k, v, w)
    assert got[0].shape == w.shape and got[3].shape == v.shape

    grow = lambda t: jnp.pad(t, [(0, 0)] * 3 + [(0, d - dv)])  # noqa: E731
    padded = _out_and_grads(flash, q, k, grow(v), grow(w))
    for a, b_, name in zip(got, padded, ("out", "dq", "dk", "dv")):
        if name in ("out", "dv"):
            assert not np.asarray(b_[..., dv:]).any(), name
            b_ = b_[..., :dv]
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_), name)

    if dtype != jnp.float32:
        return
    want = _out_and_grads(
        lambda q, k, v: fa._attention_unfused(
            q, k, v, bias, causal, 1.0 / np.sqrt(d), 0.0, None, True,
            window=window),
        q, k, v, w)
    for a, b_, name in zip(got, want, ("out", "dq", "dk", "dv")):
        scale = max(1.0, float(jnp.abs(b_).max()))
        assert float(jnp.abs(a - b_).max()) / scale < 1e-5, name


# --------------------------------------------- the forward's own blocks


def _flash_at(q, k, v, fwd_blocks, block=128, bias=None, causal=True,
              window=0, dropout=0.0):
    """`flash_attention` with the backward at `block` x `block` and the
    forward at `fwd_blocks`, set where the call carries them: in the
    statics (no argument of the entry point picks the forward's blocks).
    Returns the call and the forward alone, for its log-sum-exp rows."""
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    qf, kf, vf, biasf, bq, bk = fa._pad_inputs(q, k, v, bias, block, block)
    assert qf.shape[1] % fwd_blocks[0] == kf.shape[1] % fwd_blocks[1] == 0
    seed = jnp.full((1,), 11, jnp.int32)
    statics = (("sm_scale", d ** -0.5), ("causal", causal),
               ("causal_offset", sk - sq), ("dropout", dropout),
               ("block_q", bq), ("block_k", bk), ("fwd_blocks", fwd_blocks),
               ("window", window), ("dims", (sq, sk, d, dv)))

    def call(q, k, v):
        qf, kf, vf = fa._pad_inputs(q, k, v, bias, block, block)[:3]
        out = fa._flash_core(qf, kf, vf, biasf, seed, h, statics)[0]
        return out[:, :sq, :dv].reshape(b, h, sq, dv)

    out, lse = fa._fwd_call(qf, kf, vf, biasf, seed, h,
                            **fa._statics_of(statics)[0])
    return call, (out[:, :sq, :dv].reshape(b, h, sq, dv),
                  lse[:, 0, :sq].reshape(b, h, sq))


_OWN_BLOCK_CASES = {
    # (b, h, hkv, sq, sk, d, dv), the forward's blocks (the backward's:
    # 128 x 128), causal, window, key bias, dtype
    "causal": ((1, 2, 2, 512, 512, 64, 64), (256, 256), True, 0, False,
               jnp.float32),
    "key_block_alone": ((1, 2, 2, 384, 512, 64, 64), (128, 256), True, 0,
                        False, jnp.float32),
    "window_below_the_block": ((1, 2, 2, 512, 512, 64, 64), (256, 256), True,
                               100, False, jnp.float32),
    "window_the_block": ((1, 2, 2, 512, 512, 64, 64), (256, 256), True, 256,
                         False, jnp.float32),
    "window_twice_the_block": ((1, 2, 2, 1024, 1024, 64, 64), (256, 256),
                               True, 512, False, jnp.float32),
    "group_of_8": ((1, 8, 1, 512, 512, 64, 64), (256, 256), True, 0, False,
                   jnp.float32),
    "narrow_values": ((1, 2, 2, 512, 512, 192, 128), (256, 256), True, 0,
                      False, jnp.float32),
    "sq_is_not_sk": ((1, 4, 2, 256, 768, 64, 64), (256, 256), True, 300,
                     False, jnp.float32),
    "key_bias_ragged": ((2, 2, 2, 200, 470, 64, 64), (256, 256), False, 0,
                        True, jnp.float32),
    "causal_key_bias": ((2, 2, 2, 500, 500, 64, 64), (128, 256), True, 0,
                        True, jnp.float32),
    "bf16": ((1, 2, 2, 512, 512, 192, 128), (256, 256), True, 0, False,
             jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(_OWN_BLOCK_CASES))
def test_forward_at_blocks_of_its_own(rng, case):
    """`flash_fwd` at larger blocks than the backward pair's: its output
    and log-sum-exp rows within rounding of the same call at equal blocks
    (the online softmax sums in another order, nothing else), and out, dq,
    dk, dv, the gradients computed by the backward at its own blocks from
    the wide forward's residuals, against `_attention_unfused` in float32
    like every other case."""
    dims, fwd_blocks, causal, window, with_bias, dtype = _OWN_BLOCK_CASES[case]
    b, sk, d, dv = dims[0], dims[4], dims[5], dims[6]
    q, k, v, w = _band_case(rng, *dims[:6], dv=dv, dtype=dtype)
    bias = None
    if with_bias:
        bias = jnp.where(jnp.arange(sk)[None, :] < sk - 37, 0.0,
                         fa.NEG_INF) * jnp.ones((b, 1))
    kw = dict(bias=bias, causal=causal, window=window)
    wide, (out_w, lse_w) = _flash_at(q, k, v, fwd_blocks, **kw)
    equal, (out_e, lse_e) = _flash_at(q, k, v, (128, 128), **kw)
    rounding = 1e-5 if dtype == jnp.float32 else 2 ** -7  # a step of bf16
    for a, b_, name in ((out_w, out_e, "out"), (lse_w, lse_e, "lse")):
        scale = max(1.0, float(jnp.abs(b_.astype(jnp.float32)).max()))
        assert float(jnp.abs(a.astype(jnp.float32) - b_.astype(jnp.float32))
                     .max()) / scale <= rounding, name
    got = _out_and_grads(wide, q, k, v, w)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(out_w))
    if dtype != jnp.float32:
        ours = _out_and_grads(equal, q, k, v, w)
        for a, b_, name in zip(got, ours, ("out", "dq", "dk", "dv")):
            scale = max(1.0, float(jnp.abs(b_.astype(jnp.float32)).max()))
            assert float(jnp.abs(a.astype(jnp.float32) - b_.astype(
                jnp.float32)).max()) / scale < 2 ** -5, name
        return
    want = _out_and_grads(
        lambda q, k, v: fa._attention_unfused(
            q, k, v, bias, causal, d ** -0.5, 0.0, None, True, window=window),
        q, k, v, w)
    for a, b_, name in zip(got, want, ("out", "dq", "dk", "dv")):
        assert a.shape == b_.shape
        scale = max(1.0, float(jnp.abs(b_).max()))
        assert float(jnp.abs(a - b_).max()) / scale < 1e-5, name


def test_dropout_keeps_the_same_pairs_at_any_forward_block(rng):
    """`_dropout_keep` hashes global coordinates: with one-hot values the
    output *is* the kept probabilities, and the forward at 256 x 256 keeps
    to the bit the pairs it keeps at the backward's 128 x 128, which are
    the pairs the backward kernels regenerate."""
    s = 256
    q, k, _, w = _band_case(rng, 1, 2, 2, s, s, 64, dv=s)
    v = jnp.broadcast_to(jnp.eye(s, dtype=jnp.float32), (1, 2, s, s))
    wide, (p_wide, _) = _flash_at(q, k, v, (256, 256), causal=False,
                                  dropout=0.3)
    equal, (p_equal, _) = _flash_at(q, k, v, (128, 128), causal=False,
                                    dropout=0.3)
    kept = np.asarray(p_equal) != 0
    assert 0.68 < kept.mean() < 0.72
    np.testing.assert_array_equal(np.asarray(p_wide) != 0, kept)
    np.testing.assert_allclose(p_wide, p_equal, rtol=1e-5, atol=1e-7)
    for a, b_ in zip(_out_and_grads(wide, q, k, v, w)[1:],
                     _out_and_grads(equal, q, k, v, w)[1:]):
        np.testing.assert_allclose(a, b_, rtol=1e-4, atol=1e-5)


def test_a_ragged_key_length_keeps_the_backwards_blocks(rng):
    """sk = 1,100 pads to three blocks of 512, which no block of 1,024
    divides: the chooser falls back, the call is the parent's to the bit
    (the same blocks passed by hand) and the counter stays."""
    from paddle_tpu import profiler

    q, k, v, w = _band_case(rng, 1, 1, 1, 1100, 1100, 64)
    bias = jnp.where(jnp.arange(1100)[None, :] < 1000, 0.0, fa.NEG_INF)
    before = profiler.counters().get("flash_fwd_wide_key_calls", 0)
    chosen = _out_and_grads(lambda *a: fa.flash_attention(
        *a, bias=bias, causal=True), q, k, v, w)
    assert profiler.counters().get("flash_fwd_wide_key_calls", 0) == before
    by_hand = _out_and_grads(lambda *a: fa.flash_attention(
        *a, bias=bias, causal=True, block_q=512, block_k=512), q, k, v, w)
    for a, b_ in zip(chosen, by_hand):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


# The forward's blocks by shape: (sq_p, sk_p, d_p, dv_p, itemsize) at the
# backward's 512 x 512 -> what the measurement on the chip settled
# (PERF.md, PR 41). The first four are the six calls the cells make.
_FWD_BLOCK_TABLE = {
    "joyai_and_kimi_latent_s4096": ((4096, 4096, 256, 128, 2), (1024, 1024)),
    "trinity_window_2048_and_full_s8192": ((8192, 8192, 128, 128, 2),
                                           (1024, 1024)),
    "mellum_window_1024_and_full_s8192": ((8192, 8192, 128, 128, 2),
                                          (1024, 1024)),
    "one_width_256_bf16": ((2048, 2048, 256, 256, 2), (1024, 1024)),
    "sk_no_multiple_of_1024": ((4096, 1536, 128, 128, 2), (512, 512)),
    "sq_no_multiple_of_1024": ((1536, 4096, 128, 128, 2), (512, 1024)),
    "one_block": ((512, 512, 128, 128, 2), (512, 512)),
    # VMEM: Mosaic refuses 1,024 x 1,024 at these for a v5e (PR 41's probe)
    "float32_256_256": ((2048, 2048, 256, 256, 4), (512, 1024)),
    "bf16_512_512": ((2048, 2048, 512, 512, 2), (512, 1024)),
    "bf16_384_256": ((2048, 2048, 384, 256, 2), (512, 1024)),
    "bf16_1024_1024": ((2048, 2048, 1024, 1024, 2), (512, 512)),
    "float32_512_512": ((2048, 2048, 512, 512, 4), (512, 512)),
}


@pytest.mark.parametrize("case", list(_FWD_BLOCK_TABLE))
def test_forward_blocks_by_shape(case):
    (sq_p, sk_p, d_p, dv_p, itemsize), want = _FWD_BLOCK_TABLE[case]
    assert fa._fwd_blocks(sq_p, sk_p, 512, 512, d_p, dv_p, itemsize) == want


# The backward by shape: (sk_p, d_p, dv_p, itemsize) -> whether a key/value
# head's dk and dv stay in VMEM (`flash_bwd_dkv_dq`) or the pair runs. The
# first four are the calls the six cells make.
_FUSED_RULE_TABLE = {
    "joyai_and_kimi_latent_s4096": ((4096, 256, 128, 2), True),   # 12 MiB
    "trinity_mellum_s8192": ((8192, 128, 128, 2), True),          # 16 MiB
    "lfm2_s8192_heads_of_64": ((8192, 128, 128, 2), True),
    "phi4_differential_s4096": ((4096, 128, 128, 2), True),       # 8 MiB
    "float32_s8192": ((8192, 128, 128, 4), True),                 # 24 MiB
    "s16384_at_the_budget": ((16384, 128, 128, 2), True),         # 32 MiB
    "s16384_latent": ((16384, 256, 128, 2), False),               # 48 MiB
    "float32_s8192_latent": ((8192, 256, 128, 4), False),         # 36 MiB
    "s32768": ((32768, 128, 128, 2), False),                      # 64 MiB
    "s32768_latent": ((32768, 256, 128, 2), False),
}


@pytest.mark.parametrize("case", list(_FUSED_RULE_TABLE))
def test_fused_backward_by_shape(case):
    shape, want = _FUSED_RULE_TABLE[case]
    assert fa._bwd_fused_viable(*shape) == want


@pytest.mark.parametrize("s,fused", [(16384, True), (32768, False)])
def test_a_head_that_does_not_fit_vmem_takes_the_pair(s, fused):
    """The rule through the entry point, traced and not run: 32,768 keys
    at 128 and 128 lanes are 64 MiB of accumulators and output blocks, so
    the call's backward is the pair, its counter stays, and the blocks
    count three grids; half as many keys fit."""
    from pallas_costs import operand_shapes

    from paddle_tpu import profiler

    q = jnp.zeros((1, 1, s, 128), jnp.bfloat16)
    c0 = profiler.counters()
    found = operand_shapes(jax.grad(lambda *a: jnp.sum(fa.flash_attention(
        *a, causal=True, window=512).astype(jnp.float32)),
        argnums=(0, 1, 2)), q, q, q)
    c1 = profiler.counters()
    assert set(found) == ({"flash_fwd", "flash_bwd_dkv_dq"} if fused else
                          {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"})
    assert (c1.get("flash_bwd_fused_calls", 0)
            - c0.get("flash_bwd_fused_calls", 0)) == fused
    n = s // 512
    assert (c1["flash_blocks_total"] - c0.get("flash_blocks_total", 0)
            == (2 if fused else 3) * n * n)
    # a query block of 512 sees its own key block and the one before; a
    # forward block of 1,024 likewise, four blocks of 512 each
    assert (c1["flash_blocks_visited"] - c0.get("flash_blocks_visited", 0)
            == 4 * (n - 1) + (1 if fused else 2) * (2 * n - 1))


@pytest.mark.parametrize("by_hand", [
    {}, {"block_k": 512}, {"block_q": 512}, {"block_q": 256, "block_k": 512}],
    ids=["default", "block_k", "block_q", "both"])
def test_blocks_passed_by_hand_serve_all_three_kernels(by_hand, backward):
    """The chooser acts on the default alone: a call that names a block
    gets it in `flash_fwd` and in its backward alike, `flash_bwd_dkv_dq`
    or `flash_bwd_dq` and `flash_bwd_dkv` (ring attention's chunks, the
    tests at 128), and bumps no counter. The fused call's grid is a
    key/value head a row, its group's query blocks, and the longest run of
    key blocks a query block sees: under `causal` all of them, the last
    block's."""
    from pallas_costs import block_shapes

    from paddle_tpu import profiler

    b, h, hkv, s, d, dv = 1, 4, 2, 2048, 192, 128
    q = jnp.zeros((b, h, s, d), jnp.bfloat16)
    k = jnp.zeros((b, hkv, s, d), jnp.bfloat16)
    v = jnp.zeros((b, hkv, s, dv), jnp.bfloat16)
    before = profiler.counters().get("flash_fwd_wide_key_calls", 0)
    found = block_shapes(jax.grad(lambda *a: jnp.sum(fa.flash_attention(
        *a, causal=True, **by_hand).astype(jnp.float32)),
        argnums=(0, 1, 2)), q, k, v)
    bq, bk = by_hand.get("block_q", 512), by_hand.get("block_k", 512)
    fq, fk = (bq, bk) if by_hand else (1024, 1024)
    assert (profiler.counters().get("flash_fwd_wide_key_calls", 0) - before
            == (not by_hand))

    def blocks(bq, bk):
        qs, ks, vs = (1, bq, 256), (1, bk, 256), (1, bk, 128)
        return [(1,), qs, ks, vs], (1, bq, 128), (1, 1, bq), qs, ks, vs

    ins, outs, rows, *_ = blocks(fq, fk)
    ((grid, found_fwd),) = found["flash_fwd"]
    assert grid[:2] == (b * h, s // fq) and found_fwd == [*ins, outs, rows]
    ins, outs, rows, qs, ks, vs = blocks(bq, bk)
    if backward == "fused":
        assert set(found) == {"flash_fwd", "flash_bwd_dkv_dq"}
        ((grid, found_bwd),) = found["flash_bwd_dkv_dq"]
        assert grid == (b * hkv, h // hkv * s // bq, s // bk)
        assert found_bwd == [*ins, outs, rows, rows, qs, (1, s, 256),
                             (1, s, 128)]
        return
    assert set(found) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    ((grid, found_dq),) = found["flash_bwd_dq"]
    assert grid[:2] == (b * h, s // bq)
    assert found_dq == [*ins, outs, rows, rows, qs]
    ((grid, found_dkv),) = found["flash_bwd_dkv"]
    assert grid[:2] == (b * hkv, s // bk)
    assert found_dkv == [*ins, outs, rows, rows, ks, vs]


@pytest.mark.parametrize("window,steps", [(0, 16), (2048, 5), (1024, 3),
                                          (100, 2)])
def test_the_fused_grid_is_as_long_as_the_longest_run(window, steps):
    """`flash_bwd_dkv_dq`'s innermost axis under a band: the blocks of
    512 keys a query block of 512 can see at most, of the sixteen."""
    from pallas_costs import block_shapes

    b, h, hkv, s, d = 1, 8, 2, 8192, 128
    q = jnp.zeros((b, h, s, d), jnp.bfloat16)
    k = jnp.zeros((b, hkv, s, d), jnp.bfloat16)
    found = block_shapes(jax.grad(lambda *a: jnp.sum(fa.flash_attention(
        *a, causal=True, window=window).astype(jnp.float32)),
        argnums=(0, 1, 2)), q, k, k)
    ((grid, _),) = found["flash_bwd_dkv_dq"]
    assert grid == (b * hkv, h // hkv * 16, steps)


# (b, h, hkv, s, d, dv) -> the lanes q, k, dq, dk and v, out, dO, dv travel
# at, and `flash_narrow_value_calls`. Where both widths round to the same
# lanes the arrays are what they were before the values had a width of
# their own (the second and third rows: every operand at the one width)
_WIDTH_CASES = {
    "latent_192_128": ((1, 4, 2, 256, 192, 128), 256, 128, 1),
    "one_width_128": ((1, 4, 2, 256, 128, 128), 128, 128, 0),
    "one_width_192": ((1, 4, 2, 256, 192, 192), 256, 256, 0),
    "narrower_in_the_same_lanes": ((1, 4, 2, 256, 128, 64), 128, 128, 0),
    "half_lanes_64": ((1, 2, 2, 256, 64, 64), 128, 128, 0),
    "latent_320_200": ((1, 2, 2, 256, 320, 200), 384, 256, 1),
    # long enough for the forward's own blocks: 1,024 x 1,024 over the
    # backward's 512 x 512 (at s=256 every kernel has the one block)
    "latent_192_128_s2048": ((1, 4, 2, 2048, 192, 128), 256, 128, 1),
    "one_width_128_s2048": ((1, 4, 2, 2048, 128, 128), 128, 128, 0),
    # values wider than the keys (`flash_wide_value_calls`): a differential
    # head's 64 and 128 travel in the same lanes; 128 and 192 do not
    "differential_64_128": ((1, 4, 2, 256, 64, 128), 128, 128, 0),
    "differential_64_128_s2048": ((1, 4, 2, 2048, 64, 128), 128, 128, 0),
    "wider_128_192": ((1, 4, 2, 256, 128, 192), 128, 256, 0),
    # 32 query heads over 8 key/value heads of 64 lanes, keys and values
    # alike: every operand travels padded to 128
    "gqa_32_over_8_at_64": ((1, 32, 8, 256, 64, 64), 128, 128, 0),
    "gqa_32_over_8_at_64_s2048": ((1, 32, 8, 2048, 64, 64), 128, 128, 0),
}


@pytest.mark.parametrize("with_bias", [False, True], ids=["nobias", "bias"])
@pytest.mark.parametrize("case", list(_WIDTH_CASES))
def test_operand_widths_of_the_three_calls(case, with_bias, backward):
    """The arrays the custom calls read and write: v, the output, dO and
    dv at the values' lanes, q, k, dq and dk at the keys'; the blocks the
    forward cuts them in; and the counters that say a call's values
    travelled narrower than its keys, or were wider than they, its forward
    at wider blocks than its backward, and its backward in one kernel."""
    from pallas_costs import block_shapes, operand_shapes

    from paddle_tpu import profiler

    (b, h, hkv, s, d, dv), d_p, dv_p, narrow = _WIDTH_CASES[case]
    q = jnp.zeros((b, h, s, d), jnp.bfloat16)
    k = jnp.zeros((b, hkv, s, d), jnp.bfloat16)
    v = jnp.zeros((b, hkv, s, dv), jnp.bfloat16)
    bias = jnp.zeros((b, s), jnp.float32) if with_bias else None
    before = profiler.counters()
    loss = jax.grad(lambda *a: jnp.sum(fa.flash_attention(
        *a, bias=bias, causal=True).astype(jnp.float32)), argnums=(0, 1, 2))
    found = operand_shapes(loss, q, k, v)
    # the forward is traced once for the output and the residuals alike
    bumped = {name: profiler.counters().get(name, 0) - before.get(name, 0)
              for name in ("flash_narrow_value_calls",
                           "flash_wide_value_calls",
                           "flash_fwd_wide_key_calls",
                           "flash_bwd_fused_calls")}
    wide = s == 2048
    assert bumped == {"flash_narrow_value_calls": narrow,
                      "flash_wide_value_calls": int(dv > d),
                      "flash_fwd_wide_key_calls": int(wide),
                      "flash_bwd_fused_calls": int(backward == "fused")}
    fq = fk = 1024 if wide else s
    ((grid, blocks),) = block_shapes(loss, q, k, v)["flash_fwd"]
    assert grid[:2] == (b * h, s // fq)
    assert blocks == [(1,), (1, fq, d_p), (1, fk, d_p), (1, fk, dv_p),
                      *([(1, 1, fk)] if with_bias else []),
                      (1, fq, dv_p), (1, 1, fq)]

    seed, rows = (1,), (b * h, 1, s)
    qs, ks, vs = (b * h, s, d_p), (b * hkv, s, d_p), (b * hkv, s, dv_p)
    outs = (b * h, s, dv_p)
    biases = [(b, 1, s)] if with_bias else []
    read = [seed, qs, ks, vs, outs, rows, rows, *biases]
    assert found == {
        "flash_fwd": [([seed, qs, ks, vs, *biases], [outs, rows])],
        **({"flash_bwd_dkv_dq": [(read, [qs, ks, vs])]}
           if backward == "fused" else
           {"flash_bwd_dq": [(read, [qs])],
            "flash_bwd_dkv": [(read, [ks, vs])]}),
    }


@pytest.mark.parametrize("dims,window,by_hand,fwd_blocks", [
    # 3 x 3 blocks of 128: causal alone visits the lower triangle, 6
    ((1, 2, 2, 384, 384, 64), 0, (3 * 6, 3 * 9), None),
    # window 200: query block 2 (rows 256-383) sees keys 57-383: blocks
    # 0, 1, 2; block 1: keys from -71: blocks 0, 1; block 0: block 0
    ((1, 2, 2, 384, 384, 64), 200, (3 * 6, 3 * 9), None),
    # window 50 < a block: each query block sees its own and the one before
    ((1, 2, 2, 384, 384, 64), 50, (3 * 5, 3 * 9), None),
    # sq 128, sk 384 (offset 256), window 150: keys 107-383: blocks 0, 1, 2
    ((1, 2, 2, 128, 384, 64), 150, (3 * 3, 3 * 3), None),
    # the cell's shape: 16 x 16 blocks of 512, window 2048: 1+2+3+4+12*5
    ((1, 32, 4, 8192, 8192, 128), 2048, (3 * 70, 3 * 256), None),
    ((1, 32, 4, 8192, 8192, 128), 0, (3 * 136, 3 * 256), None),
    # Mellum's window layers: 1,024 keys are two blocks, so a query block
    # reads its own and the two before, three of the sixteen: 1+2+14*3
    ((1, 32, 4, 8192, 8192, 128), 1024, (3 * 45, 3 * 256), None),
    # the forward at 1,024 x 1,024, each of its blocks four of the
    # backward's: under the 2,048-key window a query block reads its own
    # and the two before, 1+2+6*3 = 21 blocks; the backward pair as above
    ((1, 32, 4, 8192, 8192, 128), 2048, (4 * 21 + 2 * 70, 3 * 256),
     (1024, 1024)),
    # full causal: 8 x 8 blocks, 36 of them
    ((1, 32, 4, 8192, 8192, 128), 0, (4 * 36 + 2 * 136, 3 * 256),
     (1024, 1024)),
    # 1,024 keys: its own and the one before, 1+7*2
    ((1, 32, 4, 8192, 8192, 128), 1024, (4 * 15 + 2 * 45, 3 * 256),
     (1024, 1024)),
    # the key block alone doubled under the 2,048-key window: query blocks
    # 0 to 3 read 1, 1, 2, 2 blocks of 1,024 keys, the other twelve 3
    ((1, 32, 4, 8192, 8192, 128), 2048, (2 * 42 + 2 * 70, 3 * 256),
     (512, 1024)),
    # the latent cells' call: 4 x 4 blocks, 10; the backward 8 x 8, 36
    ((1, 32, 32, 4096, 4096, 192), 0, (4 * 10 + 2 * 36, 3 * 64),
     (1024, 1024)),
    # what the chooser gives s=1,024: one forward block over the
    # backward's 2 x 2, of which causal visits 3
    ((1, 2, 2, 1024, 1024, 64), 0, (4 * 1 + 2 * 3, 3 * 4), (1024, 1024)),
])
def test_blocks_visited_against_a_count_by_hand(dims, window, by_hand,
                                                fwd_blocks, backward):
    """`flash_blocks_visited` and `_total`: what a call's three grids
    compute and the whole rectangles, a head, in units of the backward's
    block (score area: a forward block twice as wide and twice as tall
    counts four); the counters take them times the heads and the batch. A
    call whose backward is `flash_bwd_dkv_dq` has two grids, and counts
    the blocks the band admits, and the rectangle, once less."""
    from paddle_tpu import profiler

    b, h, _, sq, sk, d = dims
    block = 512 if sq >= 1024 else 128
    masks = fa._Masks(True, sk - sq, window, block, block, sq // block,
                      sk // block)
    fwd = masks.at(*fwd_blocks) if fwd_blocks else None
    assert masks.visited(fwd_blocks) == by_hand
    admitted = 0
    # the same count with the predicate asked of every block
    for m in filter(None, (masks, fwd)):
        first, last = fa._key_band(np.arange(m.nq), m, np)
        qfirst, qlast = fa._query_band(np.arange(m.nk), m, np)
        for j in range(m.nq):
            for kb in range(m.nk):
                qi = np.arange(j * m.block_q, (j + 1) * m.block_q)[:, None]
                ki = np.arange(kb * m.block_k, (kb + 1) * m.block_k)[None, :]
                keep = (ki <= qi + sk - sq)
                if window:
                    keep &= qi + sk - sq - ki < window
                assert keep.any() == (first[j] <= kb <= last[j]) == (
                    qfirst[kb] <= j <= qlast[kb]), (j, kb)
                admitted += int(keep.any() and m is masks)
    fused = backward == "fused"
    if fused:
        by_hand = by_hand[0] - admitted, by_hand[1] - masks.nq * masks.nk
        assert masks.visited(fwd_blocks, fused=True) == by_hand
    if sq > 1024:
        return
    c0 = profiler.counters()
    rng = np.random.RandomState(0)
    q, k, v, _ = _band_case(rng, *dims)
    # the chooser acts where no block is named: s=1,024 at the defaults
    by_hand_blocks = {} if fwd else {"block_q": block, "block_k": block}
    fa.flash_attention(q, k, v, causal=True, window=window, **by_hand_blocks)
    c1 = profiler.counters()
    assert (c1["flash_blocks_visited"] - c0.get("flash_blocks_visited", 0),
            c1["flash_blocks_total"] - c0.get("flash_blocks_total", 0)) == (
                b * h * by_hand[0], b * h * by_hand[1])
    assert (c1.get("flash_fwd_wide_key_calls", 0)
            - c0.get("flash_fwd_wide_key_calls", 0)) == bool(fwd)
    assert (c1.get("flash_bwd_fused_calls", 0)
            - c0.get("flash_bwd_fused_calls", 0)) == fused


# the cost each kernel declares (ops/pallas/cost.py has the convention):
# (b, h, hkv, s, d, dv), window, pairs a head the masks admit, by hand
_COST_CASES = {
    # every key up to the query's own: 1 + 2 + ... + 1024
    "causal": ((1, 2, 2, 1024, 128, 128), 0, 1024 * 1025 // 2),
    # 2,048 keys at most, over a row twice as long: the first 2,048 rows
    # see 1 .. 2,048 keys, the other 2,048 rows 2,048 each
    "window": ((1, 2, 2, 4096, 128, 128), 2048,
               2048 * 2049 // 2 + 2048 * 2048),
    "window_s_causal_twin": ((1, 2, 2, 4096, 128, 128), 0, 4096 * 4097 // 2),
    # eight query heads a key/value head: K and V are moved once, not eight
    # times
    "group_of_8": ((1, 8, 1, 512, 128, 128), 0, 512 * 513 // 2),
    # the latent layer: keys 192 wide, values 128, padded inside to 256
    # and 128 lanes; the count is at 192 and 128
    "latent_values_narrower": ((1, 2, 2, 512, 192, 128), 0, 512 * 513 // 2),
    # a differential head's call: keys 64 wide and values 128, both in 128
    # lanes inside; the count is at 64 and 128. Two query heads a key head
    "differential_values_wider": ((1, 4, 2, 512, 64, 128), 0, 512 * 513 // 2),
    # ... under a 512-key window over a row twice as long
    "differential_window": ((1, 4, 2, 1024, 64, 128), 512,
                            512 * 513 // 2 + 512 * 512),
    # four query heads a key/value head at 64 lanes, keys and values alike
    # (both in 128 lanes inside): the count is at 64, half of what the
    # calls execute, and K and V are moved once for the four
    "gqa_32_over_8_at_64": ((1, 32, 8, 512, 64, 64), 0, 512 * 513 // 2),
}


def _declared_by_flash(dims, window, monkeypatch):
    """What the four kernels declare for one call: its forward and
    `flash_bwd_dkv_dq`, and the pair that a call whose key/value head does
    not fit VMEM gets for a backward."""
    from pallas_costs import declared

    b, h, hkv, s, d, dv = dims
    q = jnp.zeros((b, h, s, d), jnp.bfloat16)
    k = jnp.zeros((b, hkv, s, d), jnp.bfloat16)
    v = jnp.zeros((b, hkv, s, dv), jnp.bfloat16)
    def grads():  # a new function a trace: JAX keeps a function's trace
        return jax.grad(lambda *a: jnp.sum(fa.flash_attention(
            *a, causal=True, window=window).astype(jnp.float32)),
            argnums=(0, 1, 2))

    found = declared(grads(), q, k, v)
    assert {n: len(c) for n, c in found.items()} == {
        "flash_fwd": 1, "flash_bwd_dkv_dq": 1}
    with monkeypatch.context() as patch:
        patch.setattr(fa, "_BWD_FUSED_VMEM_BYTES", 0)
        pair = declared(grads(), q, k, v)
    assert {n: len(c) for n, c in pair.items()} == {
        "flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1}
    assert pair["flash_fwd"] == found["flash_fwd"]
    return {name: calls[0] for name, calls in {**pair, **found}.items()}


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dkv", "flash_bwd_dkv_dq"])
@pytest.mark.parametrize("case", list(_COST_CASES))
def test_declared_cost_against_a_count_by_hand(case, kernel, monkeypatch):
    """Useful FLOPs on the pairs the masks admit at the model's widths,
    one exponential a pair, and every operand and output moved once at
    its unpadded shape (bf16: 2 bytes; the log-sum-exp and delta rows
    float32)."""
    dims, window, pairs = _COST_CASES[case]
    b, h, hkv, s, d, dv = dims
    got = _declared_by_flash(dims, window, monkeypatch)[kernel]
    pairs *= b * h
    q_bytes, o_bytes = 2 * b * h * s * d, 2 * b * h * s * dv
    k_bytes, v_bytes = 2 * b * hkv * s * d, 2 * b * hkv * s * dv
    row_bytes = 4 * b * h * s
    flops, exps, moved = {
        # q.k over d lanes, p.v over dv: 2 FLOPs a multiply-add
        "flash_fwd": (2 * pairs * (d + dv), pairs + 2 * b * h * s,
                      q_bytes + k_bytes + v_bytes + o_bytes + row_bytes),
        # q.k, dS.k over d; dO.v over dv. In: q, k, v, dO, lse, delta
        "flash_bwd_dq": (2 * pairs * (2 * d + dv), pairs,
                         q_bytes + k_bytes + v_bytes + o_bytes
                         + 2 * row_bytes + q_bytes),
        # q.k, dS^T.q over d; dO.v, p^T.dO over dv. Out: dk, dv
        "flash_bwd_dkv": (2 * pairs * (2 * d + 2 * dv), pairs,
                          q_bytes + k_bytes + v_bytes + o_bytes
                          + 2 * row_bytes + k_bytes + v_bytes),
        # q.k, dS.k, dS^T.q over d; dO.v, p^T.dO over dv: each product and
        # each operand once. Out: dq, dk, dv
        "flash_bwd_dkv_dq": (2 * pairs * (3 * d + 2 * dv), pairs,
                             q_bytes + k_bytes + v_bytes + o_bytes
                             + 2 * row_bytes + q_bytes + k_bytes + v_bytes),
    }[kernel]
    assert (got.flops, got.transcendentals, got.bytes_accessed) == (
        flops, exps, moved)
    if d == dv:  # the benchmark adapter's 4, 6 and 8 FLOPs a pair a lane,
        # and the 10 the pair's 14 come to when no product is formed twice
        assert got.flops == {"flash_fwd": 4, "flash_bwd_dq": 6,
                             "flash_bwd_dkv": 8,
                             "flash_bwd_dkv_dq": 10}[kernel] * pairs * d


def test_a_window_declares_fewer_flops_than_its_causal_twin_by_the_pairs(
        monkeypatch):
    """The window's count differs from the causal one's by the pairs the
    window refuses, (s - w)(s - w + 1) / 2 a head, and not by what whole
    512 x 512 blocks would give (8 x 8 blocks: 36 against 26 visited)."""
    (b, h, _, s, d, _), window, _ = _COST_CASES["window"]
    causal = _declared_by_flash(*_COST_CASES["window_s_causal_twin"][:2],
                                monkeypatch)
    banded = _declared_by_flash(*_COST_CASES["window"][:2], monkeypatch)
    refused = b * h * (s - window) * (s - window + 1) // 2
    for kernel, per_pair in (("flash_fwd", 4), ("flash_bwd_dq", 6),
                             ("flash_bwd_dkv", 8), ("flash_bwd_dkv_dq", 10)):
        assert (causal[kernel].flops - banded[kernel].flops
                == per_pair * d * refused)
        assert causal[kernel].bytes_accessed == banded[kernel].bytes_accessed


def test_window_and_group_are_refused_where_they_cannot_run(rng):
    q, k, v, _ = _band_case(rng, 1, 4, 3, 128, 128, 64)
    with pytest.raises(ValueError, match="4 query heads over 3"):
        fa.flash_attention(q, k, v, causal=True)
    q, k, v, _ = _band_case(rng, 1, 2, 2, 128, 128, 64)
    with pytest.raises(ValueError, match="window needs causal"):
        fa.flash_attention(q, k, v, window=64)
    with pytest.raises(ValueError, match="window needs causal"):
        fa._attention_unfused(q, k, v, None, False, 0.125, 0.0, None, True,
                              window=64)


@pytest.mark.parametrize("window,group,layout", [
    (0, 2, "bshd"), (24, 1, "bshd"), (24, 2, "bshd"), (24, 2, "bhsd")])
def test_op_takes_a_window_and_a_group_on_the_plain_path(
        rng, window, group, layout):
    """Through the Program on the CPU (the XLA path, the oracle): Out and
    the three gradients against a float64 softmax over explicit masks."""
    import paddle_tpu as fluid
    from paddle_tpu import profiler

    b, s, nh, dh = 2, 40, 4, 8
    g = nh // group

    def shape(n):
        return [b, s, n, dh] if layout == "bshd" else [b, n, s, dh]

    feeds = [fluid.layers.data(n, shape(heads), append_batch_size=False)
             for n, heads in (("q", nh), ("k", g), ("v", g))]
    for t in feeds:
        t.stop_gradient = False
    out = fluid.layers.fused_multihead_attention(
        *feeds, causal=True, layout=layout, window=window)
    assert list(out.shape) == shape(nh)
    w = rng.randn(*shape(nh)).astype("float32")
    loss = fluid.layers.reduce_sum(
        fluid.layers.elementwise_mul(out, fluid.layers.assign(w)))
    grads = fluid.backward.calc_gradient(loss, feeds)
    data = {n: rng.randn(*shape(heads)).astype("float32")
            for n, heads in (("q", nh), ("k", g), ("v", g))}
    exe = fluid.Executor(fluid.CPUPlace())
    got = exe.run(feed=data, fetch_list=[out, *grads])
    assert profiler.counters()["attn_kv_group"] == group

    def gold(q, k, v):
        if layout == "bshd":
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        k, v = (jnp.repeat(t, group, 1) for t in (k, v))
        sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(dh)
        behind = np.arange(s)[:, None] - np.arange(s)[None, :]
        keep = (behind >= 0) & ((behind < window) if window else True)
        p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), -1)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
        return o.transpose(0, 2, 1, 3) if layout == "bshd" else o

    args = [jnp.asarray(data[n]) for n in "qkv"]
    want = [gold(*args), *jax.grad(
        lambda *a: jnp.sum(gold(*a) * w), argnums=(0, 1, 2))(*args)]
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(a, b_, rtol=2e-4, atol=2e-5)


def test_dropout_deterministic_and_consistent(rng):
    """In-kernel dropout: same key -> same output; fwd/bwd agree exactly
    with a pure-XLA attention using the identical (reconstructed) mask."""
    b, h, s, d = 2, 2, 128, 64
    q, k, v = _rand_qkv(rng, b, h, s, d)
    key = jax.random.PRNGKey(7)
    drop = 0.3

    o1 = fa.flash_attention(q, k, v, dropout=drop, rng_key=key)
    o2 = fa.flash_attention(q, k, v, dropout=drop, rng_key=key)
    assert bool(jnp.allclose(o1, o2))

    seed = jax.random.randint(key, (1,), 0, np.iinfo(np.int32).max, jnp.int32)
    mask = jnp.stack(
        [
            fa._dropout_keep(seed[0], bh, jnp.uint32(0), jnp.uint32(0), (s, s), drop)
            for bh in range(b * h)
        ]
    ).reshape(b, h, s, s)
    # keep-rate sanity
    keep_rate = float(jnp.mean(mask.astype(jnp.float32)))
    assert abs(keep_rate - (1 - drop)) < 0.02

    sm = 1.0 / np.sqrt(d)

    def ref(q, k, v):
        sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) * sm
        p = jax.nn.softmax(sc, -1)
        p = jnp.where(mask, p / (1 - drop), 0.0)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    assert float(jnp.abs(o1 - ref(q, k, v)).max()) < 1e-2

    gk = jax.grad(
        lambda *a: jnp.sum(fa.flash_attention(*a, dropout=drop, rng_key=key) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(ref(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gk, gr, "qkv"):
        scale = max(1.0, float(jnp.abs(b_).max()))
        assert float(jnp.abs(a - b_).max()) / scale < 2e-2, f"d{name}"


def test_bf16_inputs(rng):
    b, h, s, d = 1, 2, 128, 64
    q, k, v = _rand_qkv(rng, b, h, s, d, dtype=jnp.bfloat16)
    out = fa.flash_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    gold = _gold(
        np.asarray(q, np.float64), np.asarray(k, np.float64), np.asarray(v, np.float64)
    )
    assert np.abs(np.asarray(out, np.float64) - gold).max() < 0.1


def test_fused_mha_layer_in_program(rng):
    """Layer-level plumbing: program with fused_multihead_attention trains
    (CPU backend lowers to the XLA reference path) and matches the unfused
    BERT graph in eval mode."""
    import paddle_tpu as fluid
    from paddle_tpu.models.bert import BertConfig, build_bert_pretrain

    losses = {}
    for use_flash in (True, False):
        import paddle_tpu.framework as framework

        framework.switch_main_program(framework.Program())
        framework.switch_startup_program(framework.Program())
        framework.unique_name.switch()
        import paddle_tpu.scope as scope_mod

        scope_mod._global_scope = scope_mod.Scope()
        scope_mod._scope_stack[:] = [scope_mod._global_scope]

        cfg = BertConfig.tiny()
        cfg.use_flash_attention = use_flash
        np.random.seed(0)
        handles = build_bert_pretrain(cfg, batch_size=2, seq_len=32, is_test=True)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        rs = np.random.RandomState(3)
        feed = {
            "src_ids": rs.randint(0, cfg.vocab_size, (2, 32)).astype("int64"),
            "sent_ids": rs.randint(0, cfg.type_vocab_size, (2, 32)).astype("int64"),
            "pos_ids": np.tile(np.arange(32), (2, 1)).astype("int64"),
            "input_mask": (rs.rand(2, 32) > 0.2).astype("float32"),
            "mask_label": rs.randint(0, cfg.vocab_size, (2, 32)).astype("int64"),
            "mask_weight": (rs.rand(2, 32) < 0.15).astype("float32"),
            "nsp_label": rs.randint(0, 2, (2, 1)).astype("int64"),
        }
        (loss,) = exe.run(
            fluid.default_main_program(),
            feed=feed,
            fetch_list=[handles["loss"]],
        )
        losses[use_flash] = float(np.asarray(loss).reshape(-1)[0])

    assert abs(losses[True] - losses[False]) < 1e-3, losses


def test_fused_mha_xla_fallback_dropout_trains():
    """The below-cutover XLA fallback (_xla_attention) WITH dropout,
    through the executor: regression for a relative-import bug that made
    this exact path (and only it) raise ModuleNotFoundError — every
    other test drove either dropout=0 or the kernels directly."""
    import paddle_tpu as fluid

    b, nh, s, dh = 2, 4, 16, 8
    q = fluid.layers.data("fa_q", [b, nh, s, dh], append_batch_size=False)
    k = fluid.layers.data("fa_k", [b, nh, s, dh], append_batch_size=False)
    v = fluid.layers.data("fa_v", [b, nh, s, dh], append_batch_size=False)
    out = fluid.layers.fused_multihead_attention(q, k, v, attn_dropout=0.1)
    loss = fluid.layers.reduce_mean(out)
    fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = {n: rng.randn(b, nh, s, dh).astype("float32")
            for n in ("fa_q", "fa_k", "fa_v")}
    lv = exe.run(feed=feed, fetch_list=[loss])[0]
    assert np.isfinite(np.asarray(lv)).all()


def test_fused_mha_bshd_layout_matches_bhsd(rng):
    """The layout='bshd' op plumbing (transpose-free head routing) is
    numerically identical to the default bhsd path, including grads —
    op-level A/B through the executor."""
    import paddle_tpu as fluid
    from paddle_tpu.framework import Program

    b, nh, s, dh = 2, 4, 16, 8
    q_np = rng.randn(b, s, nh, dh).astype("float32")
    k_np = rng.randn(b, s, nh, dh).astype("float32")
    v_np = rng.randn(b, s, nh, dh).astype("float32")
    bias_np = np.where(rng.rand(b, s) > 0.2, 0.0, -1e9).astype("float32")

    def run(layout):
        main, startup = Program(), Program()
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                if layout == "bshd":
                    qv = fluid.layers.data(
                        "q", [b, s, nh, dh], append_batch_size=False)
                    kv = fluid.layers.data(
                        "k", [b, s, nh, dh], append_batch_size=False)
                    vv = fluid.layers.data(
                        "v", [b, s, nh, dh], append_batch_size=False)
                    qh, kh, vh = qv, kv, vv
                else:
                    qv = fluid.layers.data(
                        "q", [b, s, nh, dh], append_batch_size=False)
                    kv = fluid.layers.data(
                        "k", [b, s, nh, dh], append_batch_size=False)
                    vv = fluid.layers.data(
                        "v", [b, s, nh, dh], append_batch_size=False)
                    qh = fluid.layers.transpose(qv, [0, 2, 1, 3])
                    kh = fluid.layers.transpose(kv, [0, 2, 1, 3])
                    vh = fluid.layers.transpose(vv, [0, 2, 1, 3])
                for t in (qv, kv, vv):
                    t.stop_gradient = False
                biasv = fluid.layers.assign(bias_np)
                out = fluid.layers.fused_multihead_attention(
                    qh, kh, vh, key_bias=biasv, causal=True,
                    sm_scale=1.0 / np.sqrt(dh), layout=layout)
                if layout == "bhsd":
                    out = fluid.layers.transpose(out, [0, 2, 1, 3])
                loss = fluid.layers.reduce_sum(
                    fluid.layers.elementwise_mul(out, out))
                grads = fluid.backward.calc_gradient(loss, [qv, kv, vv])
        exe = fluid.Executor(fluid.CPUPlace())
        sc = fluid.Scope()
        with fluid.scope_guard(sc):
            exe.run(startup)
            vals = exe.run(
                main, feed={"q": q_np, "k": k_np, "v": v_np},
                fetch_list=[out] + [g for g in grads])
        return [np.asarray(x) for x in vals]

    a = run("bhsd")
    c = run("bshd")
    for x, y in zip(a, c):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-5)


def _run_attn_program(rng, shape, layout="bshd", compiled=False, **kw):
    import paddle_tpu as fluid
    from paddle_tpu import profiler

    b, sq, sk, nh, dh = shape
    main, startup, fetches = attn_program(*shape, layout, **kw)
    feed = {"q": rng.randn(b, sq, nh * dh).astype("float32"),
            "k": rng.randn(b, sk, nh * dh).astype("float32"),
            "v": rng.randn(b, sk, nh * dh).astype("float32"),
            "bias": np.where(rng.rand(b, sk) > 0.2, 0.0, -1e9).astype(
                "float32")}
    feed["bias"][:, 0] = 0.0
    exe = fluid.Executor(fluid.CPUPlace())
    profiler.reset_profiler()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        program = main
        if compiled:  # two of the virtual devices: a mesh
            program = fluid.CompiledProgram(main).with_data_parallel(places=2)
        vals = exe.run(program, feed=feed, fetch_list=fetches)
    c = profiler.counters()
    paths = {p for p in ("short", "flash", "xla")
             if c.get(f"attn_dispatch_{p}", 0)}
    return [np.asarray(x) for x in vals], paths


@pytest.mark.parametrize("shape,causal", [
    ((2, 64, 64, 8, 64), False),    # the transformer's encoder self-attention
    ((2, 64, 64, 8, 64), True),     # its decoder's
    ((2, 32, 64, 8, 64), False),    # cross attention, sq != sk
    ((2, 128, 128, 12, 64), False),  # BERT phase 1
    ((2, 16, 16, 2, 128), True),    # dh = 128
])
def test_dispatch_takes_the_short_kernel_for_the_models_shapes(
        shape, causal, attn_path):
    """One device, "bshd", dh 64 or 128, short rows: the Program's op
    lowers to the kernel, bumps `attn_dispatch_short`, and gives what
    XLA's lowering gives, gradients included."""
    got, paths = _run_attn_program(np.random.RandomState(3), shape,
                                   causal=causal)
    assert paths == {"short"}
    attn_path("xla")
    want, paths = _run_attn_program(np.random.RandomState(3), shape,
                                    causal=causal)
    assert paths == {"xla"}
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(a, b_, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("case", ["bhsd", "mesh", "above_the_bound",
                                  "head_dim_32", "no_pallas"])
def test_dispatch_leaves_xla_what_the_short_kernel_is_not_built_for(
        case, monkeypatch):
    from paddle_tpu.ops.pallas.mha_short import MAX_SHORT_SEQ

    shape, kw = (2, 32, 32, 2, 64), {}
    if case == "bhsd":
        kw["layout"] = "bhsd"
    elif case == "mesh":  # two devices and a batch they do not divide
        shape, kw["compiled"] = (3, 32, 32, 2, 64), True
    elif case == "above_the_bound":
        shape = (1, 16, MAX_SHORT_SEQ + 16, 2, 64)
    elif case == "head_dim_32":
        shape = (2, 32, 32, 4, 32)
    else:  # the CPU as it is: no interpreter, so no Pallas kernel runs
        monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET")
    vals, paths = _run_attn_program(np.random.RandomState(4), shape, **kw)
    assert paths == {"xla"}
    assert all(np.isfinite(v).all() for v in vals)


def test_short_kernel_dropout_trains_through_the_program():
    """Dropout on, through the executor: the op's rng seeds the kernel,
    the same step twice from one seed agrees, and the rate is applied."""
    shape = (2, 32, 32, 2, 64)
    a, paths = _run_attn_program(np.random.RandomState(5), shape, dropout=0.5)
    assert paths == {"short"}
    b_, _ = _run_attn_program(np.random.RandomState(5), shape, dropout=0.5)
    plain, _ = _run_attn_program(np.random.RandomState(5), shape)
    np.testing.assert_array_equal(a[0], b_[0])
    assert np.abs(a[0] - plain[0]).max() > 1e-2
    assert all(np.isfinite(v).all() for v in a)


# ------------------------------------------------- the chooser, as a table

# (q, k, v shapes in the layout; layout; causal; window; group;
#  mesh as (batch, model) or None; Pallas runs; path)
_DEFAULTS = dict(layout="bshd", causal=False, window=0, group=1, mesh=None,
             pallas=True)


def _row(name, path, q, k=None, v=None, **kw):
    k = q if k is None else k
    return pytest.param(q, k, k if v is None else v, {**_DEFAULTS, **kw}, path,
                        id=name)


_LONG = (1, 4096, 32, 192)  # one document, 32 heads, keys of 192 lanes
_GQA_Q, _GQA_KV = (1, 8192, 32, 128), (1, 8192, 4, 128)


@pytest.mark.parametrize("q,k,v,call,want", [
    # the benchmark's cells (resnet50_b128 lowers no attention), with the
    # path the ledger's per-layer lines show for each
    _row("bert_base_s128", "short", (256, 128, 12, 64)),
    _row("bert_base_s512", "short", (48, 512, 12, 64)),
    _row("bert_base_s128_dp4", "short", (1024, 128, 12, 64), mesh=(4, 1)),
    _row("transformer_base_s64_self", "short", (256, 64, 8, 64)),
    _row("transformer_base_s64_causal", "short", (256, 64, 8, 64),
         causal=True),
    _row("kimi_linear_ep32_s4096", "flash", _LONG, v=(1, 4096, 32, 128),
         causal=True),
    _row("joyai_flash_ep32_s4096", "flash", _LONG, v=(1, 4096, 32, 128),
         causal=True),
    _row("trinity_mini_ep16_s8192_window", "flash", _GQA_Q, _GQA_KV,
         causal=True, window=2048, group=8),
    _row("trinity_mini_ep16_s8192_full", "flash", _GQA_Q, _GQA_KV,
         causal=True, group=8),
    _row("mellum2_ep4_s8192_window", "flash", _GQA_Q, _GQA_KV,
         causal=True, window=1024, group=8),
    # FLASH_MIN_SEQ: both lengths at it, one below it
    _row("at_flash_min_seq", "flash", (1, 2048, 1, 64)),
    _row("below_flash_min_seq", "xla", (1, 2047, 1, 64)),
    _row("keys_below_flash_min_seq", "xla", (1, 2048, 1, 64),
         (1, 1024, 1, 64)),
    _row("at_flash_min_seq_bhsd", "flash", (1, 1, 2048, 64), layout="bhsd"),
    _row("at_flash_min_seq_no_pallas", "xla", (1, 2048, 1, 64),
         pallas=False),
    # FLASH_MIN_SCORE_BYTES: 4,096 rows of 12 heads of 128 x 128 float32
    # scores are 3 GiB; 2,730 rows fall just under 2 GiB, 2,731 just over
    _row("score_bytes_above", "flash", (4096, 128, 12, 64)),
    _row("score_bytes_just_above", "flash", (2731, 128, 12, 64)),
    _row("score_bytes_just_below", "short", (2730, 128, 12, 64)),
    _row("score_bytes_above_per_shard", "xla", (16384, 128, 12, 64),
         mesh=(4, 1)),
    _row("score_bytes_below_per_shard", "short", (4096, 128, 12, 64),
         mesh=(4, 1)),
    # the short kernel's own bounds, and what makes a call not plain
    _row("at_max_short_seq", "short", (2, 512, 2, 64)),
    _row("above_max_short_seq", "xla", (2, 528, 2, 64)),
    _row("keys_above_max_short_seq", "xla", (2, 16, 2, 64), (2, 528, 2, 64)),
    _row("short_bhsd", "xla", (2, 2, 64, 64), layout="bhsd"),
    _row("head_dim_32", "xla", (2, 64, 4, 32)),
    _row("head_dim_128", "short", (2, 64, 2, 128)),
    _row("values_narrower", "xla", (2, 64, 2, 128), v=(2, 64, 2, 64)),
    _row("grouped_heads", "xla", (2, 64, 4, 64), (2, 64, 2, 64), group=2),
    _row("window", "xla", (2, 64, 2, 64), causal=True, window=16),
    _row("short_no_pallas", "xla", (2, 64, 2, 64), pallas=False),
    # meshes: only `batch` alone, dividing the batch, runs a kernel
    _row("batch_undivided", "xla", (6, 64, 2, 64), mesh=(4, 1)),
    _row("model_axis_2_short_seq", "xla", (8, 64, 2, 64), mesh=(2, 2)),
    _row("model_axis_2_at_flash_min_seq", "xla", (2, 2048, 2, 64),
         mesh=(1, 2)),
    # RING_MIN_SEQ on a `model` axis of 2
    _row("ring_at_min_seq", "ring", (2, 4096, 2, 64), mesh=(1, 2)),
    _row("ring_below_min_seq", "xla", (2, 4094, 2, 64), mesh=(1, 2)),
    _row("ring_no_pallas", "ring", (2, 4096, 2, 64), mesh=(2, 2),
         pallas=False),
    _row("ring_seq_undivided", "xla", (2, 4097, 2, 64), mesh=(1, 2)),
    _row("ring_keys_undivided", "xla", (2, 4096, 2, 64), (2, 4097, 2, 64),
         mesh=(1, 2)),
    _row("ring_needs_a_model_axis", "flash", (1, 4096, 2, 64)),
    _row("long_on_a_batch_mesh", "xla", (4, 4096, 2, 64), mesh=(4, 1)),
])
def test_attention_path_decision_table(q, k, v, call, want, monkeypatch):
    """`fused_ops.attention_path` row by row: the shape of each benchmark
    cell's attention calls with the path the ledger shows for it, and both
    sides of every threshold and of every kernel's own bounds."""
    from paddle_tpu.ops import fused_ops
    from paddle_tpu.parallel.mesh import build_mesh

    call = dict(call)
    pallas = call.pop("pallas")
    module = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(module, "_use_pallas", lambda: pallas)
    if call["mesh"]:
        batch, model = call["mesh"]
        call["mesh"] = build_mesh(batch=batch, model=model,
                                  devices=jax.devices()[:batch * model])
    assert fused_ops.attention_path(q, k, v, **call) == want


def test_dispatch_seq_floor_defaults_flash_on():
    # at FLASH_MIN_SEQ the blocked kernel is chosen even when the score
    # tensor is small (tiny batch); the interpreter counts as a backend
    # that runs Pallas
    from paddle_tpu.ops import fused_ops

    def path(s):
        shape = (1, 1, s, 64)
        return fused_ops.attention_path(
            shape, shape, shape, layout="bhsd", causal=False, window=0,
            group=1, mesh=None)

    assert path(fused_ops.FLASH_MIN_SEQ) == "flash"
    assert path(fused_ops.FLASH_MIN_SEQ // 2) == "xla"


def test_dispatch_never_swaps_a_kernel_that_was_asked_for(monkeypatch):
    from paddle_tpu.ops import fused_ops

    # no interpreter, no TPU: the chooser observes that no Pallas kernel
    # can run here and picks XLA; a kernel called by name raises rather
    # than run other math in its place
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    long = (1, 1, fused_ops.FLASH_MIN_SEQ, 64)
    assert fused_ops.attention_path(
        long, long, long, layout="bhsd", causal=False, window=0, group=1,
        mesh=None) == "xla"
    q = jnp.zeros((1, 1, 16, 64))
    with pytest.raises(RuntimeError, match="'cpu' backend"):
        fa.flash_attention(q, q, q)


def test_forced_path_on_a_backend_without_pallas_raises(monkeypatch,
                                                        attn_path):
    """The tests' way to a named path patches the chooser only: the
    lowering then calls the kernel, and the kernel still refuses a backend
    that cannot compile it."""
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET")
    attn_path("flash")
    with pytest.raises(RuntimeError, match="'cpu' backend"):
        _run_attn_program(np.random.RandomState(6), (2, 32, 32, 2, 64))


def test_dispatch_counters_bump(rng):
    from paddle_tpu import profiler
    from paddle_tpu.ops import fused_ops

    profiler.reset_profiler()
    q, k, v = _rand_qkv(rng, s=16)
    out = fa._xla_attention(q, k, v, None, False, 0.125, 0.0, None)
    assert out.shape == q.shape  # sanity; counters come from fused_mha
    # drive the registered op through a tiny program
    import paddle_tpu as fluid

    qv = fluid.layers.data("q", [1, 2, 16, 64], append_batch_size=False)
    kv = fluid.layers.data("k", [1, 2, 16, 64], append_batch_size=False)
    vv = fluid.layers.data("v", [1, 2, 16, 64], append_batch_size=False)
    helper = fluid.layer_helper.LayerHelper("fmha")
    o = helper.create_variable_for_type_inference("float32",
                                                  (1, 2, 16, 64))
    helper.append_op(
        type="fused_multihead_attention",
        inputs={"Q": [qv], "K": [kv], "V": [vv]},
        outputs={"Out": [o]},
        attrs={"causal": False, "attn_dropout": 0.0},
    )
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    r = np.random.RandomState(0)
    feed = {n: r.randn(1, 2, 16, 64).astype("float32")
            for n in ("q", "k", "v")}
    exe.run(feed=feed, fetch_list=[o])
    c = profiler.counters()
    assert sum(c.get(f"attn_dispatch_{p}", 0)
               for p in ("short", "xla", "flash", "ring")) > 0


@pytest.mark.parametrize("path,d,lanes", [
    ("xla", 64, 16), ("flash", 64, 16), ("flash", 128, 32),
    ("flash", 256, 64)], ids=lambda v: str(v))
def test_rotary_dim_turns_the_first_lanes_and_passes_the_rest(
        monkeypatch, attn_path, path, d, lanes):
    """The op with QK-norm, `rope_theta` and `rotary_dim`: the first
    `rotary_dim` lanes of every head of q and k turn as a head of that
    width and the others pass as normed, on the plain path, through the
    flash kernel with the two ops' own functions in front (heads of 64)
    and through `qk_prep` (heads of 128 and of 256 lanes), output and the
    gradients of q, k and v, against plain softmax attention over a norm
    and a rotation written out with a concatenation."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import profiler
    from kernel_cases import written_out

    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    attn_path(path)
    b, s, h, g, theta, eps = 1, 96, 4, 2, 1e4, 1e-6
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds = [fluid.layers.data(n, [b, s, heads * d],
                                   append_batch_size=False)
                 for n, heads in (("q", h), ("k", g), ("v", g))]
        for t in feeds:
            t.stop_gradient = False
        q, k, v = (fluid.layers.reshape(t, [b, s, -1, d]) for t in feeds)
        out = fluid.layers.fused_multihead_attention(
            q, k, v, causal=True, sm_scale=d ** -0.5, layout="bshd",
            q_norm_attr=fluid.ParamAttr(name="qn"),
            k_norm_attr=fluid.ParamAttr(name="kn"), qk_norm_epsilon=eps,
            rope_theta=theta, rotary_dim=lanes)
        loss = fluid.layers.reduce_sum(fluid.layers.elementwise_mul(out, out))
        grads = fluid.backward.calc_gradient(loss, feeds)
    r = np.random.RandomState(d + lanes)
    feed = {n: r.randn(b, s, heads * d).astype("float32")
            for n, heads in (("q", h), ("k", g), ("v", g))}
    weights = {n: r.uniform(0.5, 1.5, d).astype("float32")
               for n in ("qn", "kn")}
    exe = fluid.Executor(fluid.CPUPlace())
    before = profiler.counters()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for n, w in weights.items():
            fluid.global_scope().set(n, w)
        got = exe.run(main, feed=feed, fetch_list=[out, *grads])
    after = profiler.counters()
    assert after["attn_rotary_lanes"] == lanes
    assert after.get("attn_qk_prep_fused", 0) - before.get(
        "attn_qk_prep_fused", 0) == (2 if d % 128 == 0 else 0)

    def plain(q, k, v, lanes):
        def normed(t, w):
            t = t.reshape(b, s, -1, d)
            t = t / jnp.sqrt(jnp.mean(t * t, -1, keepdims=True) + eps) * w
            return written_out(t, theta, lanes)

        q, k = normed(q, weights["qn"]), normed(k, weights["kn"])
        v = v.reshape(b, s, g, d)
        of = jnp.arange(h) // (h // g)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k[:, :, of]) * d ** -0.5
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                          v[:, :, of])

    args = [jnp.asarray(feed[n]) for n in "qkv"]
    with jax.default_matmul_precision("highest"):
        want = plain(*args, lanes)
        want_grads = jax.grad(lambda *a: jnp.sum(plain(*a, lanes) ** 2),
                              argnums=(0, 1, 2))(*args)
        whole = plain(*args, d)
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    for a, w in zip(got[1:], want_grads):
        np.testing.assert_allclose(a, w, atol=2e-4, rtol=2e-4)
    assert np.abs(np.asarray(whole) - got[0]).max() > 0.05


# ------------------- positions without a norm, and the prepared pair (PR 61)


def _qkv_program(b, s, h, g, d, build):
    """A Program over flat q, k, v feeds ([b, s, heads * d], as the
    projections write them): `build(q, k, v)` on the `[b, s, heads, d]`
    reshapes returns the vars to fetch, the first of them the attention's
    output; the gradients of sum(out^2) in the three feeds follow."""
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds = [fluid.layers.data(n, [b, s, heads * d],
                                   append_batch_size=False)
                 for n, heads in (("q", h), ("k", g), ("v", g))]
        for t in feeds:
            t.stop_gradient = False
        fetch = build(*(fluid.layers.reshape(t, [b, s, -1, d])
                        for t in feeds))
        loss = fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(fetch[0], fetch[0]))
        grads = fluid.backward.calc_gradient(loss, feeds)
    return main, startup, [*fetch, *grads]


def _run_qkv(program, feed, weights=()):
    import paddle_tpu as fluid

    main, startup, fetch = program
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for n, w in dict(weights).items():
            fluid.global_scope().set(n, w)
        return exe.run(main, feed=feed, fetch_list=fetch)


YARN = {"rope_type": "yarn", "factor": 8.0,
        "original_max_position_embeddings": 64}


@pytest.mark.parametrize("path,d,kw", [
    ("xla", 64, {}), ("flash", 64, {}), ("flash", 128, {}),
    ("xla", 128, {"rope_scaling": YARN}),
    ("flash", 128, {"rope_scaling": YARN}),
], ids=["xla-64", "flash-64", "flash-128", "xla-128-yarn", "flash-128-yarn"])
def test_positions_without_a_norm_are_the_two_rotary_ops_in_front(
        monkeypatch, attn_path, path, d, kw):
    """`rope_theta` and no `QNorm`: the op gives the outputs and the
    gradients of two `rotary_embedding` ops and the op without positions,
    on the plain path, through the flash kernel with `rotate_half` in
    front (heads of 64) and through `qk_prep` without weights (heads of
    128: counters `attn_qk_prep_fused` and `attn_qk_prep_rope_only`, the
    forward op's lowering and the gradient op's replay)."""
    import paddle_tpu as fluid
    from paddle_tpu import profiler

    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    attn_path(path)
    b, s, h, g, theta = 1, 96, 4, 2, 1e4
    call = dict(causal=True, sm_scale=d ** -0.5, layout="bshd")

    def inside(q, k, v):
        return [fluid.layers.fused_multihead_attention(
            q, k, v, rope_theta=theta, **kw, **call)]

    def in_front(q, k, v):
        q, k = (fluid.layers.rotary_embedding(t, theta=theta, **kw)
                for t in (q, k))
        return [fluid.layers.fused_multihead_attention(q, k, v, **call)]

    programs = [_qkv_program(b, s, h, g, d, fn) for fn in (inside, in_front)]
    ops = [[op.type for op in p[0].global_block().ops] for p in programs]
    assert "rotary_embedding" not in ops[0]
    assert ops[1].count("rotary_embedding") == 2
    (attn,) = [op for op in programs[0][0].global_block().ops
               if op.type == "fused_multihead_attention"]
    assert attn.attr("rope_theta") == theta and not attn.input("QNorm")
    assert "qk_norm_epsilon" not in attn.attrs
    r = np.random.RandomState(d)
    feed = {n: r.randn(b, s, heads * d).astype("float32")
            for n, heads in (("q", h), ("k", g), ("v", g))}
    before = profiler.counters()
    got = _run_qkv(programs[0], feed)
    after = profiler.counters()
    want = _run_qkv(programs[1], feed)
    fused = 2 if path == "flash" and d % 128 == 0 else 0
    for name in ("attn_qk_prep_fused", "attn_qk_prep_rope_only"):
        assert after.get(name, 0) - before.get(name, 0) == fused, name
    assert after.get("attn_qk_prep_handed_back", 0) == before.get(
        "attn_qk_prep_handed_back", 0)
    assert after.get("attn_rope_scaled", 0) - before.get(
        "attn_rope_scaled", 0) == (2 if kw else 0)
    np.testing.assert_allclose(got[0], want[0], atol=2e-5)
    for a, w in zip(got[1:], want[1:]):
        np.testing.assert_allclose(a, w, atol=2e-4, rtol=2e-4)
    # and the positions bite
    plain = _run_qkv(_qkv_program(
        b, s, h, g, d, lambda q, k, v: [
            fluid.layers.fused_multihead_attention(q, k, v, **call)]), feed)
    assert np.abs(plain[0] - got[0]).max() > 0.05


def test_positions_need_the_token_major_layout_and_scaling_needs_positions():
    import paddle_tpu as fluid

    q = fluid.layers.data("q", [1, 2, 16, 64], append_batch_size=False)
    with pytest.raises(ValueError, match="rope_scaling needs rope_theta"):
        fluid.layers.fused_multihead_attention(q, q, q, rope_scaling=YARN)
    out = fluid.layers.fused_multihead_attention(q, q, q, rope_theta=1e4)
    exe = fluid.Executor(fluid.CPUPlace())
    with pytest.raises(Exception, match="bshd"):
        exe.run(feed={"q": np.zeros((1, 2, 16, 64), "float32")},
                fetch_list=[out])


@pytest.mark.parametrize("path,d,normed", [
    ("xla", 64, True), ("flash", 64, True), ("flash", 128, True),
    ("flash", 128, False), ("xla", 128, False),
], ids=["xla-64", "flash-64", "flash-128", "flash-128-no_norm",
        "xla-128-no_norm"])
def test_the_prepared_pair_is_what_the_attention_took_and_index_kl_reads_it(
        monkeypatch, attn_path, path, d, normed):
    """`return_prepared`: QPrepared and KPrepared are the `jnp`
    preparation (`rms_norm`, `rotate_half`) transposed head-major, on the
    plain path, on the flash path with the two functions in front and as
    `qk_prep`'s own outputs (heads of 128: counter
    `attn_qk_prep_handed_back`); asking for them changes neither the
    output nor a gradient; and `index_kl` on the pair is `index_kl_rows`
    on the same pair token-major."""
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import profiler
    from paddle_tpu.ops.nn_ops import rms_norm, rotate_half
    from paddle_tpu.ops.sparse_attn_ops import index_kl_rows

    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    attn_path(path)
    b, s, h, g, theta, eps = 1, 96, 4, 2, 1e4, 1e-6
    call = dict(causal=True, sm_scale=d ** -0.5, layout="bshd",
                rope_theta=theta)
    if normed:
        call.update(q_norm_attr=fluid.ParamAttr(name="qn"),
                    k_norm_attr=fluid.ParamAttr(name="kn"),
                    qk_norm_epsilon=eps)
    r = np.random.RandomState(d)
    feed = {n: r.randn(b, s, heads * d).astype("float32")
            for n, heads in (("q", h), ("k", g), ("v", g))}
    feed["index"] = np.where(np.tril(np.ones((s, s), bool)),
                             r.randn(b, s, s), -np.inf).astype("float32")
    feed["admit"] = np.tril(np.ones((b, s, s), "int8"))
    weights = {n: r.uniform(0.5, 1.5, d).astype("float32")
               for n in ("qn", "kn")} if normed else {}

    def asked(q, k, v):
        out, lse, qp, kp = fluid.layers.fused_multihead_attention(
            q, k, v, return_lse=True, return_prepared=True, **call)
        assert qp.stop_gradient and kp.stop_gradient
        assert tuple(qp.shape) == (b, h, s, d)
        assert tuple(kp.shape) == (b, g, s, d)
        index = fluid.layers.data("index", [b, s, s],
                                  append_batch_size=False)
        admit = fluid.layers.data("admit", [b, s, s], dtype="int8",
                                  append_batch_size=False)
        kl = fluid.layers.index_kl(qp, kp, lse, index, admit, d ** -0.5)
        assert tuple(kl.shape) == (b, s)
        return [out, qp, kp, lse, kl]

    def not_asked(q, k, v):
        return [fluid.layers.fused_multihead_attention(q, k, v, **call)]

    before = profiler.counters()
    out, qp, kp, lse, kl, *grads = _run_qkv(
        _qkv_program(b, s, h, g, d, asked), feed, weights)
    after = profiler.counters()
    want_out, *want_grads = _run_qkv(
        _qkv_program(b, s, h, g, d, not_asked), feed, weights)
    fused = 2 if path == "flash" and d % 128 == 0 else 0
    assert after.get("attn_qk_prep_handed_back", 0) - before.get(
        "attn_qk_prep_handed_back", 0) == fused
    assert after.get("attn_qk_prep_rope_only", 0) - before.get(
        "attn_qk_prep_rope_only", 0) == (0 if normed else fused)
    # the kernels' outputs to the bit; where XLA prepares, it fuses the
    # preparation otherwise once its result is an output too
    tol = dict(rtol=0, atol=0) if fused else dict(rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(out, want_out, **tol)
    for a, w in zip(grads, want_grads):
        np.testing.assert_allclose(a, w, **tol)

    def prepared(t, w):
        t = jnp.asarray(t).reshape(b, s, -1, d)
        if normed:
            t = rms_norm(t, jnp.asarray(weights[w]), eps, 3)
        return np.transpose(rotate_half(t, theta), (0, 2, 1, 3))

    # outside a jit XLA folds the frequencies another way (5e-4 rad at
    # position 8,191; here 96 positions)
    np.testing.assert_allclose(qp, prepared(feed["q"], "qn"), atol=5e-5)
    np.testing.assert_allclose(kp, prepared(feed["k"], "kn"), atol=5e-5)
    assert np.isfinite(kl).all() and kl.max() > 0
    rows = index_kl_rows(
        *(jnp.transpose(jnp.asarray(t), (0, 2, 1, 3)) for t in (qp, kp)),
        jnp.asarray(lse), jnp.asarray(feed["index"]),
        jnp.asarray(feed["admit"]), d ** -0.5)
    np.testing.assert_allclose(kl, rows, rtol=1e-5, atol=1e-6)


def test_a_loss_on_the_prepared_pair_reaches_no_input():
    """QPrepared and KPrepared carry no gradient, as Lse does not: a loss
    made of them alone leaves q and k without one."""
    import paddle_tpu as fluid

    b, s, h, d = 1, 16, 2, 64
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q = fluid.layers.data("q", [b, s, h, d], append_batch_size=False)
        q.stop_gradient = False
        _, qp, kp = fluid.layers.fused_multihead_attention(
            q, q, q, causal=True, layout="bshd", rope_theta=1e4,
            return_prepared=True)
        loss = fluid.layers.reduce_sum(fluid.layers.elementwise_add(qp, kp))
        assert fluid.backward.calc_gradient(loss, [q]) == [None]


# ------------------------------------------------- a mask causal by blocks
#
# `granule` B: a query sees the keys up to the end of its own block of B
# rows, shifted by `causal_offset`, and with a window the last `window`
# of them. Block diffusion's training mask is three such rules
# (`ops/fused_ops.py::block_diffusion_mask`).

cost = importlib.import_module("paddle_tpu.ops.pallas.cost")


def _dense(sq, sk, offset, window, granule):
    """The rule a pair at a time, from its sentence and not from
    `cost.py`: the end of the query's granule, shifted."""
    last = np.arange(sq) // granule * granule + granule - 1 + offset
    ki = np.arange(sk)[None, :]
    keep = ki <= last[:, None]
    if window:
        keep &= last[:, None] - ki < window
    return keep


# (granule, causal_offset, window): the clean copy on itself, a noisy
# block on the clean blocks before it, a noisy block on itself
_GRANULE_RULES = {
    "g4_clean": (4, 0, 0), "g4_past": (4, -4, 0), "g4_own": (4, 0, 4),
    "g8_clean": (8, 0, 0), "g8_past": (8, -8, 0), "g8_own": (8, 0, 8),
    # no power of two: the division stays
    "g6_clean": (6, 0, 0), "g6_own": (6, 0, 6),
}


@pytest.mark.parametrize("rule", list(_GRANULE_RULES))
def test_a_granule_matches_the_plain_path_under_the_dense_mask(
        rng, rule, backward):
    """The output, the log-sum-exp rows and all three gradients, the rows'
    own cotangent among them (`lse_grad`), at sq != sk and eight query
    heads on one key/value head, against `_attention_unfused` under the
    rule as an admission. A query that admits no key (offset -g: the first
    granule) is no one's and carries no weight."""
    granule, offset, window = _GRANULE_RULES[rule]
    b, h, hkv, sq, sk, d = 1, 8, 1, 264, 384, 32
    q, k, v, w = _band_case(rng, b, h, hkv, sq, sk, d)
    mask = _dense(sq, sk, offset, window, granule)
    seen = jnp.asarray(mask.any(1))
    w = w * seen[None, None, :, None]
    w_lse = jnp.asarray(rng.randn(b, h, sq), jnp.float32) * seen
    admit = jnp.asarray(mask[None], jnp.int8)

    def flash(q, k, v):
        return fa.flash_attention(
            q, k, v, causal=True, granule=granule, causal_offset=offset,
            window=window, block_q=128, block_k=128, with_lse=True,
            lse_grad=True)

    def plain(q, k, v):
        return fa._attention_unfused(q, k, v, None, False, d ** -0.5, 0.0,
                                     None, True, admit=admit, with_lse=True)

    def weighted(fn):
        def loss(q, k, v):
            out, lse = fn(q, k, v)
            return jnp.sum(out * w) + jnp.sum(jnp.where(seen, lse, 0.0)
                                              * w_lse)
        return loss

    # each side compiled whole, not op by op
    (out, lse), (want, want_lse) = (jax.jit(flash)(q, k, v),
                                    jax.jit(plain)(q, k, v))
    assert float(jnp.abs((out - want) * seen[:, None]).max()) < 2e-6
    assert float(jnp.abs(jnp.where(seen, lse - want_lse, 0.0)).max()) < 2e-6
    if offset < 0:  # the rows with no key say so in their log-sum-exp
        assert float(lse[..., :granule].max()) < -1e29
    got = jax.jit(jax.grad(weighted(flash), (0, 1, 2)))(q, k, v)
    for g, want in zip(got, jax.jit(jax.grad(weighted(plain), (0, 1, 2)))(
            q, k, v)):
        assert float(jnp.abs(g - want).max()) < 2e-5


@pytest.mark.parametrize("rule", list(_GRANULE_RULES))
@pytest.mark.parametrize("sq,sk", [(37, 41), (64, 48)])
def test_admitted_pairs_and_both_spans_against_a_count(rule, sq, sk):
    """`cost.admitted_pairs`, `admits`, and the queries' pair of ends as
    the keys' read along the other axis, keys and queries outside the rectangle
    among them (the bands ask for them before they clip)."""
    granule, offset, window = _GRANULE_RULES[rule]
    mask = _dense(sq, sk, offset, window, granule)
    assert cost.admitted_pairs(sq, sk, True, offset, window, granule) == (
        mask.sum())
    np.testing.assert_array_equal(cost.admits(
        np.arange(sq)[:, None], np.arange(sk)[None, :], offset, window,
        granule), mask)
    queries = np.arange(-4 * granule, max(sq, sk) + 6 * granule)
    last = cost.last_key(queries, offset, granule)
    if window:
        np.testing.assert_array_equal(
            cost.first_key(queries, offset, window, granule),
            last - window + 1)
    for ki in range(-2 * granule, sk + 2 * granule):
        sees = last >= ki
        if window:
            sees &= last - ki < window
        first_q = cost.first_query(ki, offset, granule)
        last_q = cost.last_query(ki, offset, window, granule)
        if sees.any():
            assert queries[sees].min() == first_q
            assert not window or queries[sees].max() == last_q
        else:
            assert window and last_q < first_q


def test_granule_one_is_the_causal_mask_and_its_spans():
    for offset, window in ((0, 0), (5, 0), (-3, 7), (2, 4)):
        qi, ki = np.arange(40)[:, None], np.arange(50)[None, :]
        keep = ki <= qi + offset
        if window:
            keep &= qi + offset - ki < window
        np.testing.assert_array_equal(cost.admits(qi, ki, offset, window),
                                      keep)
        assert cost.last_key(9, offset) == 9 + offset
        assert cost.first_query(9, offset) == 9 - offset
        if window:
            assert cost.first_key(9, offset, window) == (
                9 + offset - window + 1)
            assert cost.last_query(9, offset, window) == (
                9 - offset + window - 1)


@pytest.mark.parametrize("rule,length,block,fwd_blocks,by_hand", [
    # the cell's three calls, L = 4,096 in blocks of 4, 8 x 8 blocks of
    # 512: the clean copy's lower triangle 36; the past the same 36 (the
    # diagonal block holds the block's earlier granules); a noisy block's
    # own keys the diagonal, 8. The forward at 1,024: 10, 10 and 4, each
    # four of the backward's
    ((4, 0, 0), 4096, 512, None, (36, 36)),
    ((4, -4, 0), 4096, 512, None, (36, 36)),
    ((4, 0, 4), 4096, 512, None, (8, 8)),
    ((4, 0, 0), 4096, 512, (1024, 1024), (4 * 10, 36)),
    ((4, -4, 0), 4096, 512, (1024, 1024), (4 * 10, 36)),
    ((4, 0, 4), 4096, 512, (1024, 1024), (4 * 4, 8)),
    # a granule as long as a block: the past leaves the diagonal out
    ((128, 0, 0), 512, 128, None, (10, 10)),
    ((128, -128, 0), 512, 128, None, (6 + 1, 6 + 1)),  # + the ragged edge
    ((128, 0, 128), 512, 128, None, (4, 4)),
])
def test_granule_grids_skip_every_empty_tile(rule, length, block, fwd_blocks,
                                             by_hand):
    """What `flash_fwd`'s grid (at its own blocks) and the backward's
    visit a head, and that every tile visited admits a pair but where a
    run would otherwise be empty (the first query block of a past that
    starts a whole block later)."""
    granule, offset, window = rule
    masks = fa._Masks.of(length, length, causal=True, causal_offset=offset,
                         window=window, block_q=block, block_k=block,
                         granule=granule)
    fwd, bwd = by_hand
    assert masks.visited(fwd_blocks, fused=True) == (
        fwd + bwd, 2 * masks.nq * masks.nk)
    dense = _dense(length, length, offset, window, granule)
    for m in (masks, masks.at(*fwd_blocks) if fwd_blocks else masks):
        tiles = dense.reshape(m.nq, m.block_q, m.nk, m.block_k).any((1, 3))
        first, last = fa._key_band(np.arange(m.nq), m, np)
        qfirst, qlast = fa._query_band(np.arange(m.nk), m, np)
        for j in range(m.nq):
            for kb in range(m.nk):
                by_keys = first[j] <= kb <= last[j]
                by_queries = qfirst[kb] <= j <= qlast[kb]
                if tiles[j, kb]:
                    assert by_keys and by_queries, (j, kb)
                elif tiles[j].any():  # no empty tile in a run that has one
                    assert not by_keys, (j, kb)
                elif tiles[:, kb].any():
                    assert not by_queries, (j, kb)


def test_the_block_diffusion_calls_declare_the_masks_pairs(monkeypatch):
    """The three calls of `fused_ops._block_diffusion_flash` declare, in
    `flash_fwd` and in `flash_bwd_dkv_dq`, the pairs the mask admits and
    no other: L B + (L^2 - L B) / 2 + (L^2 + L B) / 2 a head."""
    from pallas_costs import declared

    from paddle_tpu.ops import fused_ops

    length, block, h, d = 512, 4, 8, 128
    q = jnp.zeros((2, h, length, d), jnp.bfloat16)
    kv = jnp.zeros((2, 1, length, d), jnp.bfloat16)

    def grads():
        return jax.grad(lambda *a: jnp.sum(fused_ops._block_diffusion_flash(
            *a, block, None).astype(jnp.float32)), argnums=(0, 1, 2))

    found = declared(grads(), q, kv, kv)
    assert {n: len(c) for n, c in found.items()} == {
        "flash_fwd": 3, "flash_bwd_dkv_dq": 3}
    pairs = h * (length * block + (length * length - length * block) // 2
                 + (length * length + length * block) // 2)
    assert pairs == h * fused_ops.block_diffusion_mask(length, block).sum()
    assert sum(c.flops for c in found["flash_fwd"]) == 4 * d * pairs
    assert sum(c.flops for c in found["flash_bwd_dkv_dq"]) == 10 * d * pairs
    assert sum(c.transcendentals for c in found["flash_bwd_dkv_dq"]) == pairs


def test_a_granule_needs_causal_and_the_op_refuses_what_it_cannot_join(rng):
    q, k, v = _rand_qkv(rng, 1, 2, 128, 32)
    with pytest.raises(ValueError, match="window needs causal"):
        fa.flash_attention(q, k, v, granule=4)
    with pytest.raises(ValueError, match="window needs causal"):
        fa.flash_attention(q, k, v, causal_offset=-4)
    import paddle_tpu as fluid

    for rows, more in ((64, {"window": 8, "causal": True}), (60, {}),
                       (64, {"layout": "bhsd"})):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            x = fluid.layers.data("x", [1, rows, 2, 16],
                                  append_batch_size=False)
            out = fluid.layers.fused_multihead_attention(
                x, x, x, **{"layout": "bshd", "diffusion_block": 4, **more})
            with pytest.raises(Exception, match="diffusion_block takes"):
                fluid.Executor(fluid.CPUPlace()).run(
                    feed={"x": np.zeros((1, rows, 2, 16), np.float32)},
                    fetch_list=[out])
