"""The delta rule with a decay a head and grouped key heads (Gated
DeltaNet; `ops/linear_attn_ops.py`, `ops/pallas/kda_chunk.py`): the kernel
pair under the Pallas interpreter and `kda_chunked`, the plain path,
against the token-a-step recurrence of `benchmark/models/qwen3_next.py`,
outputs and the five gradients (each side compiled:
`tests/kernel_cases.py`), with key groups of 2 and at decays down to 0.01
a token; that nothing is written out in front of the kernels; the
names, the declaration and the counters."""

import numpy as np
import pytest

from kernel_cases import compiled, pair_at_widths, rel, value_and_grads

from benchmark.models import qwen3_next as ref

B, HK, HV, D = 2, 2, 4, 128


def _args(length, g_lo, g_hi, seed=None, parallel=False, hk=HK, hv=HV):
    """q, k [B, s, hk, D] unit rows, v [B, s, hv, D], the log decay and
    beta [B, s, hv]. `parallel`: keys one direction a head plus 0.3 of
    noise and beta near 1, so that A's entries are near 1."""
    import jax.numpy as jnp

    r = np.random.RandomState(length if seed is None else seed)

    def unit(t):
        return t / np.linalg.norm(t, axis=-1, keepdims=True)

    def direction():
        noise = r.randn(B, length, hk, D)
        return unit(r.randn(1, 1, hk, D) + 0.3 * noise if parallel else noise)

    return [jnp.asarray(t, jnp.float32) for t in (
        direction(), direction(), r.randn(B, length, hv, D),
        r.uniform(g_lo, g_hi, (B, length, hv)),
        r.uniform(0.9 if parallel else 0, 1, (B, length, hv)))]


def recurrence(q, k, v, g, beta):
    """The reference's recurrence with key head n // group under value
    head n, by indexing."""
    import jax.numpy as jnp

    key_of = jnp.arange(v.shape[2]) // (v.shape[2] // q.shape[2])
    return ref.delta_recurrence(q[:, :, key_of], k[:, :, key_of], v, g, beta)


@pytest.fixture
def interpreter(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


REGIMES = [
    (64, -0.1, -0.001, ""),  # one whole chunk, mild decay
    (130, -2.0, -0.01, ""),  # two tokens into a third chunk and grid step
    # a decay of 0.01 a token and slower: exp(-G) would overflow float32
    # after twenty tokens; nothing overflows, nothing is clamped
    (100, -4.7, -3.0, ""),
    (200, -1e-4, -1e-6, ""),  # decays near 1: the state forgets nothing
    (37, -20.0, 0.0, ""),  # shorter than a chunk, both extremes in a row
    (127, -0.01, -1e-4, "parallel"),  # the system far from I
]


@pytest.mark.parametrize("length,g_lo,g_hi,kind", REGIMES)
def test_kernels_and_plain_path_equal_the_recurrence(
        interpreter, length, g_lo, g_hi, kind):
    from paddle_tpu.ops.linear_attn_ops import kda_chunked
    from paddle_tpu.ops.pallas.kda_chunk import kda_chunk

    args = _args(length, g_lo, g_hi, parallel=kind == "parallel")
    (got, g_got), (want, g_want), (plain, g_plain) = (
        value_and_grads(fn, args)
        for fn in (kda_chunk, recurrence, kda_chunked))
    assert got.shape == want.shape == (B, length, HV, D)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_allclose(plain, want, atol=2e-6)
    for name, a, w, p, like in zip("q k v g beta".split(), g_got, g_want,
                                   g_plain, args):
        assert a.shape == like.shape == p.shape, name
        assert np.isfinite(a).all(), name
        # as tests/test_kda_kernel.py holds `g`: where a token all but
        # erases the state its gradient is what float32 leaves of a
        # difference, and the kernel is held to twice the plain path's
        # own distance there
        limit = max(1e-4, 2 * rel(p, w)) if name == "g" else 1e-4
        assert rel(a, w) < limit, (name, rel(a, w), rel(p, w))
        assert rel(p, w) < limit, (name, rel(p, w))


def test_a_key_head_serves_its_group_and_no_other(interpreter):
    """Groups of 2 and of 4: the kernel's key head is n // group. With
    `n % h_k` (the wrong map) the output is another model's."""
    from paddle_tpu.ops.pallas.kda_chunk import kda_chunk

    for hk in (1, 2, 4):
        args = _args(96, -0.5, -0.01, seed=hk, hk=hk)
        got, want = compiled(kda_chunk, *args), compiled(recurrence, *args)
        q, k, v, g, beta = args
        mod = np.arange(HV) % hk
        wrong = compiled(ref.delta_recurrence, q[:, :, mod], k[:, :, mod], v,
                        g, beta)
        np.testing.assert_allclose(got, want, atol=2e-6)
        if hk == 2:
            assert rel(wrong, want) > 0.3


def test_the_decay_is_a_heads_and_not_one_for_all(interpreter):
    from paddle_tpu.ops.pallas.kda_chunk import kda_chunk

    q, k, v, g, beta = _args(128, -1.0, -0.01)
    got = compiled(kda_chunk, q, k, v, g, beta)
    same = compiled(kda_chunk, q, k, v,
                   np.array(np.broadcast_to(g[..., :1], g.shape)), beta)
    np.testing.assert_allclose(got[:, :, 0], same[:, :, 0], atol=2e-6)
    assert rel(same[:, :, 1:], got[:, :, 1:]) > 0.05


def test_nothing_is_written_out_in_front_of_the_kernels(interpreter):
    """At the cell's shape, forward and backward of the op's core from
    the projections' arrays: the kernels read q and k at
    `[1, 4096, 16*128]`, the decay at `[1, 4096, 32]`; no float32
    `[1, 4096, 32, 128]` (or `[1, 4096, 4096]`) decay and no repeated q
    or k is among their operands, and the backward's dq and dk leave a
    value head each for XLA to add by pairs."""
    import jax
    import jax.numpy as jnp
    from pallas_costs import operand_shapes

    from paddle_tpu.ops.linear_attn_ops import kda_mixer_core

    b, s, hk, hv, d = 1, 4096, 16, 32, 128
    sds = jax.ShapeDtypeStruct
    args = (sds((b, s, hk * d), jnp.bfloat16), sds((b, s, hk * d),
                                                   jnp.bfloat16),
            sds((b, s, hv * d), jnp.bfloat16), sds((b, s, hv), jnp.bfloat16),
            sds((b, s, hv), jnp.bfloat16), sds((hv,), jnp.float32),
            sds((hv,), jnp.float32))

    def both(*a):
        out, pull = jax.vjp(
            lambda *a: kda_mixer_core(*a, hv, 1e-6, hk), *a)
        return pull(out)

    calls = operand_shapes(both, *args)
    assert set(calls) == {"gdn_fwd", "gdn_bwd"}
    keys, values, heads = (b, s, hk * d), (b, s, hv * d), (b, s, hv)
    states = (b * hv, s // 64, d, d)
    rows = (b * hv, s // 64, 1, 64)
    for ins, outs in calls["gdn_fwd"]:
        assert ins == [keys, keys, values, heads, heads]
        assert outs == [values, states]
    ((ins, outs),) = calls["gdn_bwd"]
    assert ins == [keys, keys, values, heads, heads, states, values]
    assert outs == [values, values, values, rows, rows]
    text = str(jax.make_jaxpr(both)(*args))
    assert f"f32[{b},{s},{hv},{d}]" not in text  # no decay a channel
    # float32 arrays as wide as the value heads: dq and dk from the
    # kernel, nothing else (q and k are never repeated)
    assert text.count(f":f32[{b},{s},{hv * d}]") == 2


def test_names_declaration_and_counters(interpreter):
    """`gdn_fwd`/`gdn_bwd` match this cell's metrics and not Kimi's
    `^%?kda_(fwd|bwd)`, and the other way round; the declaration moves q
    and k once a key head and the decay as beta; the lowering counts."""
    import json
    import os
    import re

    import jax
    import jax.numpy as jnp
    from pallas_costs import declared, numbers

    from paddle_tpu import profiler
    from paddle_tpu.ops.linear_attn_ops import kda_mixer_core
    from paddle_tpu.ops.pallas import kda_chunk as kernel

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def pattern(metric):
        with open(os.path.join(root, "benchmark", "layer_metrics",
                               metric + ".json")) as f:
            return json.load(f)["args"]["name"]

    mine, theirs = (pattern("qwen3next_gdn_kernel_ms_per_step"),
                    pattern("kda_kernel_ms_per_step"))
    for name in ("gdn_fwd", "gdn_bwd"):
        assert re.search(mine, name) and not re.search(theirs, name)
    for name in ("kda_fwd", "kda_bwd"):
        assert re.search(theirs, name) and not re.search(mine, name)

    b, s, hk, hv, d = 1, 200, 2, 4, 128
    r = np.random.RandomState(0)
    args = [jnp.asarray(r.randn(*shape), jnp.float32) for shape in (
        (b, s, hk * d), (b, s, hk * d), (b, s, hv * d), (b, s, hv),
        (b, s, hv), (hv,), (hv,))]

    def both(*a):
        out, pull = jax.vjp(lambda *a: kda_mixer_core(*a, hv, 1e-6, hk), *a)
        return pull(out)

    before = profiler.counters()
    found = declared(both, *args)
    after = profiler.counters()
    assert after["kda_key_group"] == 2
    # 200 tokens are four chunks: a grid step solves as many together as
    # it holds
    assert after["kda_lockstep_chunks"] == min(kernel.CHUNKS_PER_STEP, 4)
    assert after["kda_decay_per_head"] - before.get(
        "kda_decay_per_head", 0) >= 1
    assert after["kda_dispatch_pallas"] - before.get(
        "kda_dispatch_pallas", 0) >= 1
    f32 = jnp.float32
    for name, backward in (("gdn_fwd", False), ("gdn_bwd", True)):
        got = numbers(found[name][0])
        want = numbers(kernel._cost(backward, b, s, hv, d, d, (f32, f32), 2,
                                    True))
        assert got == want
        # against a decay a channel and a key head a value head: the same
        # products and exponentials, fewer bytes
        kda = numbers(kernel._cost(backward, b, s, hv, d, d, (f32, f32)))
        assert got[:2] == kda[:2] and got[2] < kda[2]
    # by hand, forward: q, k at two key heads, v and o at four, the decay
    # and beta [b, s, 4], the chunks' states
    chunks = -(-s // 64)
    moved = 4 * (2 * b * s * hk * d + 2 * b * s * hv * d + 2 * b * s * hv
                 + b * hv * chunks * d * d)
    assert numbers(found["gdn_fwd"][0])[2] == moved


@pytest.mark.parametrize("length,per_step", [
    (100, 2), (193, 2), (100, 4), (193, 4), (150, 4), (327, 4)])
def test_any_width_of_the_lockstep_is_the_pair_at_one_chunk_a_step(
        interpreter, monkeypatch, length, per_step):
    """As tests/test_kda_kernel.py's, with a decay a head and key groups
    of 2: on a length that leaves a padded tail (150: a grid step of three
    chunks under a width of 4; 5 x 64 + 7: two padded chunks), 1, 2 or 4
    chunks a grid step give the outputs and the five gradients bit for
    bit (the head's column of the decay is taken out of the step's whole
    block; dq and dk are summed over each key head's group after the
    kernel, by the same adds)."""
    pair_at_widths(_args(length, -2.0, -0.01, seed=length), per_step,
                   monkeypatch)


@pytest.mark.parametrize("per_head", [False, True], ids=["kda", "gdn"])
@pytest.mark.parametrize("chunks", [1, 2, 4])
def test_the_sweeps_tail_is_each_chunks_own_to_the_bit(chunks, per_head):
    """`_sweep_tail` states once, over the grid step's stacked rows, what
    follows the reverse sweep's last product and is sums alone: dG's last
    row, the sum over the lanes where the decay is a head's, the reversed
    cumulative sum. No add crosses a chunk: each chunk's rows are what
    the tail of that chunk alone gives."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.kda_chunk import _sweep_tail

    r = np.random.RandomState(chunks)
    parts = [tuple(jnp.asarray(r.randn(*shape), jnp.float32) for shape in (
        (64, D), (64, D), (64, D), (64, D), (1, 1, D)))
        for _ in range(chunks)]
    together = jax.jit(lambda p: _sweep_tail(p, per_head=per_head))(parts)
    assert together[3].shape == (chunks * 64, 1 if per_head else D)
    for t, part in enumerate(parts):
        alone = jax.jit(lambda p: _sweep_tail([p], per_head=per_head))(part)
        for got, want in zip(together, alone):
            assert np.array_equal(np.asarray(got[t * 64:(t + 1) * 64]),
                                  np.asarray(want))
    # and it is the sum from each row to its chunk's last, dg_end included
    dG = np.concatenate([np.asarray(p[3], np.float64) for p in parts])
    dG[63::64] += np.concatenate([np.asarray(p[4][0]) for p in parts])
    if per_head:
        dG = dG.sum(1, keepdims=True)
    want = dG.reshape(chunks, 64, -1)[:, ::-1].cumsum(1)[:, ::-1]
    np.testing.assert_allclose(np.asarray(together[3]),
                               want.reshape(dG.shape), rtol=2e-5, atol=2e-5)
