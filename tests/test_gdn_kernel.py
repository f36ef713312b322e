"""The delta rule with a decay a head and grouped key heads (Gated
DeltaNet; `ops/linear_attn_ops.py`, `ops/pallas/kda_chunk.py`): the kernel
pair under the Pallas interpreter and `kda_chunked`, the plain path,
against the token-a-step recurrence of `benchmark/models/qwen3_next.py`,
each behind the op's float32 prologue (since PR 65 the kernels take the
projections' arrays and make the norms, the decay and beta in VMEM),
outputs and the seven gradients, A_log's and the decay's bias's among
them (each side compiled: `tests/kernel_cases.py`), float32 and bf16
inputs, with key groups of 2 and at decays down to 0.01 a token; that
nothing is normed, gated or written out in front of the kernels (a
channel's gate alone, which is XLA's); the names, the declaration and
the counters. Since PR 63 also heads that are
no tile (Olmo-Hybrid's 96 key and 192 value lanes, the rehearsal's 24 and
48): the same comparisons with beta in (0, 1) and in (0, 2), a row of
repeated keys with beta within 1e-3 of 2, what the kernels read and
declare at the cell's shape, and the digests of the 128/128 calls of
Kimi's and Qwen3-Next's cells (`PARENTS_CALLS`: re-taken by PR 65, which
changed those calls on purpose)."""

import numpy as np
import pytest

from kernel_cases import (OPERANDS, after_prologue, compiled,
                          gradients_held, kernel_path, logits_of,
                          one_cotangent, oracles, pair_at_widths, rel,
                          value_and_grads)

from benchmark.models import qwen3_next as ref

B, HK, HV, D = 2, 2, 4, 128


def _raw(r, q, k, v, g, beta, beta_scale=1.0, dtype="float32"):
    """What `kernel_path` takes, from unit rows q and k (each row given a
    length of its own in (1/e, e): the kernels norm them), v, the log
    decay a head `g` and `beta`: the logits that the prologue turns into
    those under a random A_log and bias (`logits_of`), in `dtype`; A_log
    and the bias are parameters, float32."""
    import jax.numpy as jnp

    h = v.shape[2]
    q, k = (t * np.exp(r.uniform(-1, 1, t.shape[:3] + (1,))) for t in (q, k))
    a_log, dt_bias = r.uniform(-0.5, 0.5, h), r.uniform(-1, 1, h)
    raw, logits = logits_of(g, beta, a_log, dt_bias, beta_scale)
    return [jnp.asarray(t, dtype) for t in (q, k, v, raw, logits)] + [
        jnp.asarray(t, jnp.float32) for t in (a_log, dt_bias)]


def _args(length, g_lo, g_hi, seed=None, parallel=False, hk=HK, hv=HV,
          dtype="float32"):
    """q, k [B, s, hk, D], v [B, s, hv, D], the logits of a log decay a
    head uniform in (`g_lo`, `g_hi`) and of a beta in (0, 1), [B, s, hv],
    A_log and the bias [hv]. `parallel`: keys one direction a head plus
    0.3 of noise and beta near 1, so that A's entries are near 1."""
    r = np.random.RandomState(length if seed is None else seed)

    def unit(t):
        return t / np.linalg.norm(t, axis=-1, keepdims=True)

    def direction():
        noise = r.randn(B, length, hk, D)
        return unit(r.randn(1, 1, hk, D) + 0.3 * noise if parallel else noise)

    return _raw(r, direction(), direction(), r.randn(B, length, hv, D),
                r.uniform(g_lo, g_hi, (B, length, hv)),
                r.uniform(0.9 if parallel else 0, 1, (B, length, hv)),
                dtype=dtype)


def recurrence(q, k, v, g, beta):
    """The reference's recurrence with key head n // group under value
    head n, by indexing, on what the prologue made."""
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
    # q, k, v and the logits bf16, as the projections write them under AMP
    (100, -1.0, -0.01, "bf16"),
]


@pytest.mark.parametrize("length,g_lo,g_hi,kind", REGIMES)
def test_kernels_and_plain_path_equal_the_recurrence(
        interpreter, length, g_lo, g_hi, kind):
    args = _args(length, g_lo, g_hi, parallel=kind == "parallel",
                 dtype="bfloat16" if kind == "bf16" else "float32")
    assert args[2].shape == (B, length, HV, D)
    _held_to_the_recurrence(args, atol=2e-6)


def _held_to_the_recurrence(args, atol=4e-6, beta_scale=1.0):
    """The kernel pair, from the projections' arrays (2 key heads under 4
    value heads, or a key head a value head), and the plain path against
    the recurrence, each behind the op's float32 prologue: outputs to
    `atol`, the seven gradients as `gradients_held` holds them (the
    limits this file had on normed q and k, the log decay and beta). With
    bf16 inputs every side rounds its outputs once, 2^-9 an entry (a
    relative 2^-7 between two at the most), and the three are
    differentiated under one cotangent. A length that is no whole grid
    step leaves padded tokens with logits of 0, so a beta of half the
    scale and a decay of the head's own, where the plain path pads with a
    beta of 0 and no decay: they stand behind the row's last token with
    q = k = v = 0 and change nothing, the sums over every row that A_log's
    and the bias's gradients are included."""
    weight = one_cotangent(args)
    bf16 = weight is not None
    got, g_got = value_and_grads(
        lambda *a: kernel_path(*a, 1e-6, beta_scale), args, weight)
    (want, g_want), (plain, g_plain) = oracles(recurrence, args, weight,
                                               beta_scale)
    assert got.shape == want.shape == args[2].shape
    assert got.dtype == want.dtype == args[2].dtype
    got, want, plain = (np.asarray(t, np.float32) for t in (got, want, plain))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=atol, rtol=2 ** -7 * bf16)
    np.testing.assert_allclose(plain, want, atol=atol, rtol=2 ** -7 * bf16)
    gradients_held(g_got, g_want, g_plain, args, bf16)


def test_a_key_head_serves_its_group_and_no_other(interpreter):
    """Groups of 2 and of 4: the kernel's key head is n // group. With
    `n % h_k` (the wrong map) the output is another model's."""
    for hk in (1, 2, 4):
        args = _args(96, -0.5, -0.01, seed=hk, hk=hk)
        got = compiled(kernel_path, *args)
        want = compiled(after_prologue(recurrence), *args)
        q, k = args[:2]
        mod = np.arange(HV) % hk
        wrong = compiled(after_prologue(ref.delta_recurrence), q[:, :, mod],
                         k[:, :, mod], *args[2:])
        np.testing.assert_allclose(got, want, atol=2e-6)
        if hk == 2:
            assert rel(wrong, want) > 0.3


def test_the_decay_is_a_heads_and_not_one_for_all(interpreter):
    q, k, v, g, beta, a_log, dt_bias = _args(128, -1.0, -0.01)
    got = compiled(kernel_path, q, k, v, g, beta, a_log, dt_bias)
    # every head under the first head's logits, A_log and bias
    first = [np.array(np.broadcast_to(t[..., :1], t.shape))
             for t in (g, a_log, dt_bias)]
    same = compiled(kernel_path, q, k, v, first[0], beta, *first[1:])
    np.testing.assert_allclose(got[:, :, 0], same[:, :, 0], atol=2e-6)
    assert rel(same[:, :, 1:], got[:, :, 1:]) > 0.05


def _core_pair(hv, hk, dk):
    """The op's core forward and backward from the projections' arrays,
    and those arrays' shapes under AMP at one row of 4,096 tokens: q, k,
    v and the logits bf16 (a decay a head where `dk` is 0, else a decay a
    channel of `dk` lanes), A_log and the decay's bias float32."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.linear_attn_ops import kda_mixer_core

    b, s, d = 1, 4096, 128
    sds, bf16 = jax.ShapeDtypeStruct, jnp.bfloat16
    wide = hv * dk if dk else hv
    args = (sds((b, s, hk * d), bf16), sds((b, s, hk * d), bf16),
            sds((b, s, hv * d), bf16), sds((b, s, wide), bf16),
            sds((b, s, hv), bf16), sds((hv,), jnp.float32),
            sds((wide,), jnp.float32))

    def both(*a):
        out, pull = jax.vjp(
            lambda *a: kda_mixer_core(*a, hv, 1e-6, hk), *a)
        return pull(out)

    return both, args


@pytest.mark.parametrize("decay", ["a-head", "a-channel"])
def test_nothing_is_written_out_in_front_of_the_kernels(interpreter, decay):
    """At the cells' shapes, forward and backward of the op's core from
    the projections' arrays, read from the traced calls' operands: what
    crosses HBM, and in which dtype.

    A decay a head (Qwen3-Next's call): the kernels read q and k at
    `[1, 4096, 16*128]`, the decay's and beta's logits at `[1, 4096, 32]`,
    bf16 as they arrive, and A_log and the bias `[1, 32]` float32; no
    float32 `[1, 4096, 32, 128]` (or `[1, 4096, 4096]`) decay, no
    repeated q or k and no normed float32 q or k is among their operands
    or anywhere in front of them, and the backward's dq and dk leave a
    value head each, float32, for XLA to add by pairs; the gradients of
    the logits, and what A_log's sums, leave as float32 rows a chunk.

    A decay a channel (Kimi's call): q, k, v and beta's logits go in bf16
    as they arrive and dq, dk come back bf16; the one float32 array in
    front of the kernels is the log decay `[1, 4096, 32*128]` that XLA's
    `kda_gate` makes, which is their operand as before PR 65, and its
    gradient comes back float32 the same way. A channel's gate is not
    the kernels'."""
    import jax
    from pallas_costs import operand_types

    per_head = decay == "a-head"
    b, s, hk, hv, d = 1, 4096, 16 if per_head else 32, 32, 128
    both, args = _core_pair(hv, hk, 0 if per_head else d)
    calls = operand_types(both, *args)
    fwd, bwd = ("gdn_fwd", "gdn_bwd") if per_head else ("kda_fwd", "kda_bwd")
    assert set(calls) == {fwd, bwd}
    bf16, f32 = "bfloat16", "float32"
    keys, values = ((b, s, hk * d), bf16), ((b, s, hv * d), bf16)
    heads = ((b, s, hv), bf16)
    states = ((b * hv, s // 64, d, d), f32)
    rows = ((b * hv, s // 64, 1, 64), f32)
    if per_head:  # the logits, then A_log and the decay's bias
        decay_in = [heads, heads, ((1, hv), f32), ((1, hv), f32)]
        grads = [((b, s, hv * d), f32)] * 2 + [values, rows, rows, rows]
    else:  # the float32 log decay, then beta's logits
        decay_in = [((b, s, hv * d), f32), heads]
        grads = [keys, keys, values, ((b, s, hv * d), f32), rows]
    for ins, outs in calls[fwd]:
        assert ins == [keys, keys, values] + decay_in
        assert outs == [values, states]
    ((ins, outs),) = calls[bwd]
    assert ins == [keys, keys, values] + decay_in + [states, values]
    assert outs == grads
    text = str(jax.make_jaxpr(both)(*args))
    front = text.split("pallas_call")[0]
    if per_head:
        assert f"f32[{b},{s},{hv},{d}]" not in text  # no decay a channel
        # float32 arrays as wide as the value heads: dq and dk from the
        # kernel, nothing else (q and k are never repeated)
        assert text.count(f":f32[{b},{s},{hv * d}]") == 2
        # and as wide as the key heads: the two sums of those by pairs, on
        # their way to bf16; no float32 q, k in front of the kernels
        assert text.count(f":f32[{b},{s},{hk * d}]") == 2
        # in front of the first call nothing float32 of q's, k's or the
        # logits' size: they go in as they arrived
        for shape in (f"{hk * d}", f"{hk},{d}", f"{hv}"):
            assert f"f32[{b},{s},{shape}]" not in front, shape
    else:
        # in front of the first call: nothing float32 of beta's size, and
        # of q's size (the decay's too) only what `kda_gate` makes, heads
        # apart; its result, laid flat, is the kernels' operand
        assert f"f32[{b},{s},{hv}]" not in front
        assert f":f32[{b},{s},{hv * d}]" in front


def test_names_declaration_and_counters(interpreter):
    """`gdn_fwd`/`gdn_bwd` match the delta rule's metric as Kimi's
    `kda_fwd`/`kda_bwd` do, and what reads a kernel's output or another
    layer's kernel does not; the declaration moves q
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

    pair = pattern("delta_rule_kernel_ms_per_step")
    for name in ("gdn_fwd", "gdn_bwd", "kda_fwd", "kda_bwd"):
        assert re.search(pair, name) and re.search(pair, f"%{name}.3 = (")
    for other in ("short_conv_bwd", "flash_fwd",
                  "%fusion.1 = f32[8] fusion(%gdn_fwd.2)"):
        assert not re.search(pair, other)

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
        want = numbers(kernel._cost(backward, b, s, hv, d, d, (f32,) * 4, 2,
                                    True))
        assert got == want
        # against a decay a channel and a key head a value head: the same
        # products, a head's gate's exponentials on top, fewer bytes
        kda = numbers(kernel._cost(backward, b, s, hv, d, d, (f32,) * 4))
        assert got[0] == kda[0] and got[2] < kda[2]
        # a token of a head: the softplus' exp and log1p, and backward the
        # sigmoid of the same logit
        assert got[1] - kda[1] == b * hv * s * (3 if backward else 2)
    # by hand, forward: q, k at two key heads, v and o at four, the
    # decay's and beta's logits [b, s, 4], A_log and the bias [4], the
    # chunks' states
    chunks = -(-s // 64)
    moved = 4 * (2 * b * s * hk * d + 2 * b * s * hv * d + 2 * b * s * hv
                 + 2 * hv + b * hv * chunks * d * d)
    assert numbers(found["gdn_fwd"][0])[2] == moved


@pytest.mark.parametrize("length,per_step", [
    (100, 2), (193, 2), (100, 4), (193, 4), (150, 4), (327, 4)])
def test_any_width_of_the_lockstep_is_the_pair_at_one_chunk_a_step(
        interpreter, monkeypatch, length, per_step):
    """As tests/test_kda_kernel.py's, with a decay a head and key groups
    of 2, where the gate too is the kernels': on a length that leaves a
    padded tail (150: a grid step of three chunks under a width of 4;
    5 x 64 + 7: two padded chunks), 1, 2 or 4 chunks a grid step, each
    traced afresh and shown to differ (`pair_at_widths`).

    Equal to the bit across the widths (`np.array_equal`): the output and
    every array the backward kernel writes, as its call returns them: dq
    and dk a value head, float32 (the norm's gradient applied in the
    kernel; the head's column of the logits is taken out of the step's
    whole block), dv, and the three arrays of rows a chunk: the gradient
    of the decay's logits (through the gate, in the kernel), of beta's
    logits, and what A_log's gradient sums.

    Held at each width to the recurrence and to `kda_chunked` behind
    `_prologue`, at this file's limits (`gradients_held`), and not to the
    other width: the seven gradients as the op returns them, of which XLA
    forms, after the kernels, a group's sum of dq and of dk and its cast,
    the rows by token, and A_log's and the bias's sums over the tokens."""
    pair_at_widths(_args(length, -2.0, -0.01, seed=length), per_step,
                   monkeypatch, recurrence)


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


# ------------------------------------------- heads that are no tile (PR 63)


def _narrow_args(length, h, dk, dv, beta_hi, seed=0, b=B, g=(-2.0, -0.01),
                 repeated=False, beta_lo=0.0, dtype="float32"):
    """`_args` at `h` heads of `dk` key and `dv` value lanes, a key head a
    value head, the logits of a beta uniform in (`beta_lo`, `beta_hi`)
    under a `beta_scale` of `beta_hi`. `repeated`: every token of a head
    has the same key, so that `A`'s entries are the decays alone and the
    solve is as far from `I` as it gets."""
    r = np.random.RandomState(seed)

    def unit(t):
        return t / np.linalg.norm(t, axis=-1, keepdims=True)

    k = unit(r.randn(b, 1 if repeated else length, h, dk))
    return _raw(r, unit(r.randn(b, length, h, dk)),
                np.broadcast_to(k, (b, length, h, dk)),
                r.randn(b, length, h, dv), r.uniform(*g, (b, length, h)),
                r.uniform(beta_lo, beta_hi, (b, length, h)), beta_hi, dtype)


@pytest.mark.parametrize("beta_hi", [1.0, 2.0], ids=["beta1", "beta2"])
@pytest.mark.parametrize("length,h,dk,dv,dtype", [
    (130, 4, 96, 192, "float32"),  # the published lanes, four heads a step
    # the last step's block hangs over the arrays' edge (six heads are no
    # multiple of the step's four), and 100 tokens are no whole grid step
    (100, 6, 96, 192, "float32"),
    (70, 4, 24, 48, "float32"),  # the rehearsal's: every head in one block
    (100, 6, 96, 192, "bfloat16"),  # as the projections write them
], ids=["96x192", "96x192-six", "24x48", "96x192-six-bf16"])
def test_heads_that_are_no_tile_equal_the_recurrence(interpreter, length, h,
                                                     dk, dv, dtype, beta_hi):
    """Key heads of 96 lanes and value heads of 192 (and 24 and 48), a key
    head a value head, beta in (0, 1) as `sigmoid` gives it and in (0, 2)
    as `beta_scale` 2 does: the kernel pair, whose grid step cuts four
    heads out of whole tiles, pads each with zeros in VMEM and norms it
    there over the lanes it has, and the plain path against the
    recurrence on the state `[dk, dv]`, each behind the op's prologue,
    outputs and the seven gradients."""
    _held_to_the_recurrence(_narrow_args(length, h, dk, dv, beta_hi,
                                         seed=length, dtype=dtype),
                            beta_scale=beta_hi)


@pytest.mark.parametrize("dk,dv", [(96, 192), (128, 128)])
def test_repeated_keys_with_beta_next_to_2(interpreter, dk, dv):
    """Where the solve is worst: one key a head for the whole row, decays
    of 0.9999 a token, so that `A` is all ones below the diagonal, and
    beta within 1e-3 of 2, where `I - beta k k^T` all but reflects along
    k and `(I + beta A)^-1` has entries near +-2 in every place. Nothing
    damps here, so the outputs are twenty times the size they have at
    beta near 1 (0.2 where 0.01) and the rounding of 64 rows' worth of
    +-2s shows: against the recurrence in float64 the kernels read 2.6e-5
    of the outputs' size, the plain path's triangular solve 1.6e-5, the
    float32 recurrence itself 2.4e-6 (at beta near 1 and over (0, 2) all
    three read 2e-6 or less). Held to 1e-4 of the outputs' size, and the
    gradients to 1e-3 of the recurrence's."""
    args = _narrow_args(192, 4, dk, dv, 2.0, seed=7, g=(-2e-4, -1e-6),
                        repeated=True, beta_lo=2.0 - 1e-3)
    (got, g_got), (want, g_want) = (
        value_and_grads(fn, args) for fn in (
            lambda *a: kernel_path(*a, 1e-6, 2.0),
            after_prologue(ref.delta_recurrence, beta_scale=2.0)))
    assert np.isfinite(got).all() and np.abs(want).max() > 0.1
    assert rel(got, want) < 1e-4, rel(got, want)
    for name, a, w in zip(OPERANDS, g_got, g_want):
        assert np.isfinite(a).all(), name
        assert rel(a, w) < 1e-3, (name, rel(a, w))


@pytest.mark.parametrize("scale", [1.0, 2.0])
def test_beta_scale_is_the_ops_on_both_paths(monkeypatch, scale):
    """`kda_mixer_core(beta_scale=...)` on the plain path and through the
    kernels against the recurrence with beta = scale x sigmoid(b), from
    the projections' arrays at 24 and 48 lanes; without the scale the
    output is another model's."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.linear_attn_ops import kda_mixer_core, l2norm, kda_gate

    b, s, h, dk, dv = 1, 80, 4, 24, 48
    r = np.random.RandomState(int(scale))
    q, k, v, a, raw = (jnp.asarray(r.randn(b, s, n), jnp.float32) for n in (
        h * dk, h * dk, h * dv, h, h))
    a_log = jnp.asarray(r.uniform(0, 2.7, h), jnp.float32)
    dt_bias = jnp.asarray(r.uniform(-6.9, -2.25, h), jnp.float32)

    def want(scale):
        return ref.delta_recurrence(
            l2norm(q.reshape(b, s, h, dk), 1e-6),
            l2norm(k.reshape(b, s, h, dk), 1e-6), v.reshape(b, s, h, dv),
            kda_gate(a, a_log, dt_bias, h),
            scale * jax.nn.sigmoid(raw)).reshape(b, s, h * dv)

    right, other = compiled(want, scale), compiled(want, 3.0 - scale)
    assert rel(other, right) > 0.05
    for interpret in ("", "1"):
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", interpret)
        got = compiled(lambda *t: kda_mixer_core(
            *t, h, 1e-6, None, scale), q, k, v, a, raw, a_log, dt_bias)
        np.testing.assert_allclose(got, right, atol=4e-6)


def test_what_the_kernels_admit_and_how_they_lay_the_heads(interpreter):
    from paddle_tpu.ops.pallas import kda_chunk as kernel

    assert kernel.kda_chunk_viable(4096, 128, 128)
    # 96 and 192 lanes: a decay a head and a key head a value head
    assert kernel.kda_chunk_viable(4096, 96, 192, 30, 30, True)
    assert not kernel.kda_chunk_viable(4096, 96, 192)
    assert not kernel.kda_chunk_viable(4096, 96, 192, 30, 30, False)
    assert not kernel.kda_chunk_viable(4096, 96, 192, 30, 15, True)
    assert not kernel.kda_chunk_viable(4096, 96, 320, 30, 30, True)
    assert not kernel.kda_chunk_viable(4096, 100, 192, 30, 30, True)
    # sixteen heads of 24 and 48 lanes end on a tile's edge: too many
    assert kernel.kda_chunk_viable(4096, 24, 48, 4, 4, True)
    assert not kernel.kda_chunk_viable(4096, 24, 48, 32, 32, True)
    assert kernel.step_heads(30, 96, 192) == 4  # 384 and 768 lanes
    assert kernel.step_heads(2, 96, 192) == 2
    # eight grid steps of four heads at 128 x 256 lanes for thirty heads
    assert kernel.layout(30, 96, 192) == (4, 32 * 128 * 256)
    assert kernel.layout(32, 128, 128) == (0, 32 * 128 * 128)
    # four heads a step share the lockstep's width: a chunk each
    assert kernel.lockstep_chunks(4096, 4) == 1
    assert kernel.lockstep_chunks(4096) == kernel.CHUNKS_PER_STEP


def test_the_published_lanes_are_read_written_and_declared(interpreter):
    """At Olmo-Hybrid's shape, forward and backward of the op's core from
    the projections' arrays: the kernels read q and k at `[1, 4096, 2880]`
    and v at `[1, 4096, 5760]` and write the output and the gradients at
    those widths, and the chunks' states at `[96 x 192]`: no array padded
    to whole tiles is among their operands or results. The declaration
    is that of 96 and 192 lanes, whatever the tiles in VMEM multiply."""
    import jax
    import jax.numpy as jnp
    from pallas_costs import block_shapes, declared, numbers, operand_shapes

    from paddle_tpu import profiler
    from paddle_tpu.ops.linear_attn_ops import kda_mixer_core
    from paddle_tpu.ops.pallas import kda_chunk as kernel

    b, s, h, dk, dv = 1, 4096, 30, 96, 192
    sds = jax.ShapeDtypeStruct
    args = (sds((b, s, h * dk), jnp.bfloat16), sds((b, s, h * dk),
                                                   jnp.bfloat16),
            sds((b, s, h * dv), jnp.bfloat16), sds((b, s, h), jnp.bfloat16),
            sds((b, s, h), jnp.bfloat16), sds((h,), jnp.float32),
            sds((h,), jnp.float32))

    def both(*a):
        out, pull = jax.vjp(
            lambda *a: kda_mixer_core(*a, h, 1e-6, None, 2.0), *a)
        return pull(out)

    before = profiler.counters()
    calls = operand_shapes(both, *args)
    after = profiler.counters()
    assert set(calls) == {"gdn_fwd", "gdn_bwd"}
    assert after["kda_dispatch_pallas"] - before.get(
        "kda_dispatch_pallas", 0) >= 1
    assert after["kda_lockstep_chunks"] == 4
    keys, values, heads = (b, s, h * dk), (b, s, h * dv), (b, s, h)
    states, rows = (b, h, s // 64, dv, dk), (b, h, s // 64, 1, 64)
    each = (1, h)  # A_log, and the decay's bias
    for ins, outs in calls["gdn_fwd"]:
        assert ins == [keys, keys, values, heads, heads, each, each]
        assert outs == [values, states]
    ((ins, outs),) = calls["gdn_bwd"]
    assert ins == [keys, keys, values, heads, heads, each, each, states,
                   values]
    assert outs == [keys, keys, values, rows, rows, rows]
    # a grid step's blocks: four heads' lanes, whole tiles of the arrays
    grid, blocks = block_shapes(both, *args)["gdn_fwd"][0]
    assert grid == (8, 64)
    assert blocks[:3] == [(1, 64, 384), (1, 64, 384), (1, 64, 768)]
    bf16 = jnp.bfloat16
    found = declared(both, *args)
    for name, backward in (("gdn_fwd", False), ("gdn_bwd", True)):
        got = numbers(found[name][0])
        assert got == numbers(kernel._cost(backward, b, s, h, dk, dv,
                                           (bf16,) * 4, 1, True))
        # under what heads of 128 and 256 lanes would declare
        padded = numbers(kernel._cost(backward, b, s, h, 128, 256,
                                      (bf16,) * 4, 1, True))
        assert all(x < y for x, y in zip(got, padded))
    # by hand, forward: q, k, v, o and the two logits [b, s, 30] bf16 at
    # the published lanes, as they arrive, A_log and the bias float32,
    # the chunks' states
    moved = (2 * 2 * b * s * h * dk + 2 * 2 * b * s * h * dv
             + 2 * 2 * b * s * h + 2 * 4 * h
             + 4 * b * h * (s // 64) * dk * dv)
    assert numbers(found["gdn_fwd"][0])[2] == moved


# as PR 65's finished tree traces them, under jax 0.9.0 (`python
# tests/test_gdn_kernel.py` prints them). PR 63 took them from its parent,
# to show that heads that are no tile had left these calls alone; PR 65
# changed all four on purpose (the kernels take the projections' arrays
# and make the norms, beta and a head's decay themselves) and re-took them,
# so that the next change to these calls shows again.
PARENTS_CALLS = {
    # (b, s, key heads, value heads, a decay a head): the digest
    (1, 4096, 16, 32, True): "f724540926e6ecfd",  # Qwen3-Next's cell
    (2, 200, 2, 4, True): "cb869a8b378aba6b",
    (1, 4096, 32, 32, False): "8f09985963400cfa",  # Kimi's cell
    (2, 200, 4, 4, False): "f7f292ca1f42e653",
}


def _call_digest(b, s, hk, hv, per_head):
    import jax
    import jax.numpy as jnp
    from pallas_costs import jaxpr_digest

    from paddle_tpu.ops.linear_attn_ops import kda_mixer_core

    sds, d = jax.ShapeDtypeStruct, D
    wide = hv if per_head else hv * d
    args = (sds((b, s, hk * d), jnp.bfloat16), sds((b, s, hk * d),
                                                   jnp.bfloat16),
            sds((b, s, hv * d), jnp.bfloat16), sds((b, s, wide), jnp.bfloat16),
            sds((b, s, hv), jnp.bfloat16), sds((hv,), jnp.float32),
            sds((wide,), jnp.float32))

    def both(*a):
        out, pull = jax.vjp(lambda *a: kda_mixer_core(*a, hv, 1e-6, hk), *a)
        return pull(out)

    return jaxpr_digest(both, *args)


@pytest.mark.parametrize("call", list(PARENTS_CALLS), ids=str)
def test_calls_at_heads_of_128_trace_the_jaxpr_they_had(interpreter, call):
    """Kimi's and Qwen3-Next's calls, forward and backward from the
    projections' arrays, trace equation for equation what they traced
    when the digests were taken (PR 65's finished tree), so the kernels
    those cells run are the ones they ran: a path added beside them (PR
    63's heads that are no tile) is off where a head is a tile."""
    assert _call_digest(*call) == PARENTS_CALLS[call]


if __name__ == "__main__":
    import os

    os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
    for call in PARENTS_CALLS:
        print(f"    {call!r}: \"{_call_digest(*call)}\",", flush=True)
