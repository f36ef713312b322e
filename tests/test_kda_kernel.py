"""The KDA chunk kernels (`ops/pallas/kda_chunk.py`) under the Pallas
interpreter, at the published head width of 128, through the op's core
from the arrays the projections write (since PR 65 the kernels make the
L2 norms and beta themselves, in VMEM; a channel's decay is gated by XLA
in front of them): outputs and the seven gradients, A_log's and the
decay's bias's among them, against the token recurrence of
`benchmark/models/kimi_linear.py` and against `kda_chunked`, the plain
path, each behind the op's float32 prologue (`_prologue`), each side and
each gradient compiled (`tests/kernel_cases.py`), float32 and bf16
inputs, rows of q and k of any length; the state carried over grid
steps; a grid step's stacked rows and lockstep solve against each chunk's
own, bit for bit, and the pair at any width against one chunk a step;
what a further chunk of a grid step costs the host in equations; the
dispatch and its counters."""

import numpy as np
import pytest

from kernel_cases import (OPERANDS, after_prologue, compiled,
                          gradients_held, kernel_path, logits_of,
                          one_cotangent, oracles, pair_at_widths, rel,
                          value_and_grads)

from benchmark.models import kimi_linear as ref

B, H, D = 2, 2, 128


def _args(length, g_lo, g_hi, seed=None, parallel=False, alternate=False,
          dtype="float32"):
    """What `kernel_path` takes: q, k (unit rows times a length of their
    own in (1/e, e): the kernels norm them), v, the decay's logits a
    channel, beta's logits, A_log and the decay's bias, the logits such
    that the log decay is uniform in (`g_lo`, `g_hi`) and beta in (0, 1)
    (`logits_of`). `parallel`: every q and k is one direction a head plus
    0.3 of noise, and beta is near 1, as a trained or freshly SiLU-ed
    projection gives them: A's entries are then near 1, not near
    128^-1/2. `alternate`: the log decay is `g_lo` or `g_hi` and nothing
    between, changing from each row to the next and from each channel to
    the next, so every split of the kernel's levels, and every pair of
    rows inside its smallest blocks, has both extremes on both of its
    sides. `dtype`: of q, k, v and the logits (A_log and the bias are
    parameters, float32)."""
    import jax.numpy as jnp

    r = np.random.RandomState(length if seed is None else seed)

    def direction():
        noise = r.randn(B, length, H, D)
        rows = r.randn(1, 1, H, D) + 0.3 * noise if parallel else noise
        return (rows / np.linalg.norm(rows, axis=-1, keepdims=True)
                * np.exp(r.uniform(-1, 1, (B, length, H, 1))))

    g = r.uniform(g_lo, g_hi, (B, length, H, D))
    if alternate:
        even = (np.arange(length)[:, None, None] + np.arange(D)) % 2 == 0
        g = np.broadcast_to(np.where(even, g_lo, g_hi), g.shape)
    q, k, v = direction(), direction(), r.randn(B, length, H, D)
    beta = r.uniform(0.9 if parallel else 0, 1, (B, length, H))
    a_log, dt_bias = r.uniform(-0.5, 0.5, H), r.uniform(-1, 1, (H, D))
    raw, logits = logits_of(g, beta, a_log, dt_bias)
    return [jnp.asarray(t, dtype) for t in (q, k, v, raw, logits)] + [
        jnp.asarray(t, jnp.float32) for t in (a_log, dt_bias)]


@pytest.fixture
def interpreter(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


# the regimes of test_chunked_kda_equals_the_token_recurrence, and three more
REGIMES = [
    (64, -0.1, -0.001, ""),  # one whole chunk, mild decay
    (128, -1.0, -0.01, ""),  # two whole chunks, one grid step
    (100, -8.0, -3.0, ""),  # decays near 0: exp(-G) would overflow
    (200, -1e-4, -1e-6, ""),  # decays near 1: the state forgets nothing
    (37, -20.0, 0.0, ""),  # shorter than a chunk, both extremes in a row
    (130, -2.0, -0.01, ""),  # two tokens into a third chunk and grid step
    # nearly parallel keys, beta near 1, hardly any decay: the system
    # I + Diag(beta) A is far from I, and a series in A's powers diverges
    (127, -0.01, -1e-4, "parallel"),
    # both extremes on both sides of every split of the kernel's levels
    # and between any two rows of its blocks of 4: a factor that left its
    # split's side would read exp(+20) there (-0.05 where the log decay
    # itself was an argument and this regime had 0: a log decay of 0 is a
    # logit of -inf, through which no gradient of the decay comes back,
    # and all that is left to compare is the 1e-9s of the erased half)
    (64, -20.0, -0.05, "alternate"),
    # A's entries near 1 where they are not 0: the pairs a few rows apart
    # are all that is left of them, the ones the lowest levels and the
    # blocks of 4 form
    (100, -8.0, -3.0, "parallel"),
    # q, k, v and the logits bf16, as the projections write them under AMP
    # (the kernels cast a block in VMEM and compute in float32), at a
    # length that is no whole grid step and at one that is
    (100, -1.0, -0.01, "bf16"),
    (256, -2.0, -0.01, "bf16"),
]


@pytest.mark.parametrize("length,g_lo,g_hi,kind", REGIMES)
def test_kernel_equals_the_recurrence_and_the_plain_path(
        interpreter, length, g_lo, g_hi, kind):
    """The kernels from the projections' arrays against the recurrence
    and `kda_chunked`, both behind `_prologue`: outputs to 2e-6 and the
    seven gradients as `gradients_held` holds them, the limits this test
    had on normed q and k, the log decay and beta. The bf16 cases add the
    one rounding of every side's outputs, 2^-9 an entry (a relative 2^-7
    between two of them at the most), and of the gradients that leave in
    bf16. A padded token (100, 37, 130, 127 are no whole grid steps) has
    logits of 0, so a beta of 0.5, where the plain path pads with a beta
    of 0: it stands behind the row's last token with q = k = v = 0 and
    changes nothing, the sums over every row that A_log's and the bias's
    gradients are included."""
    bf16 = kind == "bf16"
    args = _args(length, g_lo, g_hi, parallel=kind == "parallel",
                 alternate=kind == "alternate",
                 dtype="bfloat16" if bf16 else "float32")
    weight = one_cotangent(args)
    got, g_got = value_and_grads(kernel_path, args, weight)
    (want, g_want), (plain, g_plain) = oracles(ref.kda_recurrence, args,
                                               weight)
    assert got.shape == want.shape and got.dtype == want.dtype == args[2].dtype
    got, want, plain = (np.asarray(t, np.float32) for t in (got, want, plain))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2 ** -7 * bf16)
    np.testing.assert_allclose(got, plain, atol=2e-6, rtol=2 ** -7 * bf16)
    gradients_held(g_got, g_want, g_plain, args, bf16)


@pytest.mark.parametrize("per_step", [1, 2, 4])
def test_the_state_is_carried_from_chunk_to_chunk(
        interpreter, monkeypatch, per_step):
    """Five chunks with a decay near 1: the last chunk's outputs are made
    almost wholly of earlier chunks' state, within a grid step and across
    grid steps; a kernel that dropped either would read off by the norm
    of the output."""
    from paddle_tpu.ops.pallas import kda_chunk as kernel

    monkeypatch.setattr(kernel, "CHUNKS_PER_STEP", per_step)
    args = _args(320, -1e-3, -1e-5, seed=per_step)
    recurrence = after_prologue(ref.kda_recurrence)
    got, g_got = value_and_grads(kernel_path, args)
    want, g_want = value_and_grads(recurrence, args)
    # the same rows with the first four chunks cut off: what a chunk
    # that started from a zero state would compute
    fresh = compiled(recurrence, *(a[:, 256:] for a in args[:5]), *args[5:])
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert rel(fresh, want[:, 256:]) > 0.3  # the state matters here
    for a, w in zip(g_got, g_want):
        assert rel(a, w) < 1e-4


def test_dispatch_takes_the_kernel_at_width_128_and_the_plain_path_at_16(
        interpreter, monkeypatch):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import profiler
    from paddle_tpu.ops import linear_attn_ops
    from paddle_tpu.ops.pallas import kda_chunk as kernel

    assert kernel.kda_chunk_viable(4096, 128, 128)
    assert not kernel.kda_chunk_viable(4096, 16, 16)
    assert not kernel.kda_chunk_viable(4096, 128, 64)
    calls = []
    monkeypatch.setattr(
        kernel, "kda_chunk",
        lambda *a: calls.append("pallas") or jnp.zeros(a[2].shape))
    monkeypatch.setattr(
        linear_attn_ops, "kda_chunked",
        lambda *a: calls.append("chunked") or jnp.zeros(a[2].shape))

    def build_and_run(width, heads=2, seq=8):
        L = fluid.layers
        with fluid.program_guard(fluid.Program(), fluid.Program()), \
                fluid.unique_name.guard(), fluid.scope_guard(fluid.Scope()):
            u = L.data("u", shape=[seq, heads * width], dtype="float32")
            o = L.kda_attention(u, u, u, u,
                                L.fc(u, heads, num_flatten_dims=2), heads)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            out, = exe.run(feed={"u": np.ones((1, seq, heads * width),
                                              np.float32)}, fetch_list=[o])
        return out

    before = profiler.counters()
    assert build_and_run(128).shape == (1, 8, 256)
    mid = profiler.counters()
    assert calls == ["pallas"]
    assert mid["kda_dispatch_pallas"] - before.get(
        "kda_dispatch_pallas", 0) == 1
    assert mid.get("kda_dispatch_chunked", 0) == before.get(
        "kda_dispatch_chunked", 0)
    assert build_and_run(16).shape == (1, 8, 32)
    after = profiler.counters()
    assert calls == ["pallas", "chunked"]
    assert after["kda_dispatch_chunked"] - mid.get(
        "kda_dispatch_chunked", 0) == 1
    assert after["kda_dispatch_pallas"] == mid["kda_dispatch_pallas"]
    # without Mosaic or the interpreter the plain path runs at any width
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET")
    assert jax.default_backend() == "cpu"
    assert not kernel.kda_chunk_viable(4096, 128, 128)
    # (another length: a path is chosen when a program is lowered)
    assert build_and_run(128, seq=9).shape == (1, 9, 256)
    assert calls == ["pallas", "chunked", "chunked"]


@pytest.mark.parametrize("kernel", ["kda_fwd", "kda_bwd"])
@pytest.mark.parametrize("length", [128, 100])
def test_declared_cost_against_a_count_by_hand(interpreter, kernel, length):
    """ops/pallas/cost.py's convention at dk = dv = 128: the multiply-adds
    a chunk of c rows needs, listed in `kda_chunk._cost`, here written out
    for c = 64 and for the 36 rows of a short last chunk (the rows padded
    up to whole grid steps count nothing); q and k float32 here, values,
    output and beta's logits bf16, each moved in the dtype it arrives in
    and its gradient in the same (beta's logits' as float32 rows); the log
    decay, which XLA's gate hands over, its gradient and the chunks'
    states float32."""
    import jax
    import jax.numpy as jnp
    from pallas_costs import declared

    q, k, v, g, beta, a_log, dt_bias = _args(length, -1.0, -0.01)
    v, beta = v.astype(jnp.bfloat16), beta.astype(jnp.bfloat16)
    found = declared(jax.grad(lambda *a: jnp.sum(
        kernel_path(*a).astype(jnp.float32)), argnums=range(7)),
        q, k, v, g, beta, a_log, dt_bias)
    (got,) = found[kernel]

    def macs(c):
        lower, strict, state = c * (c + 1) // 2, c * (c - 1) // 2, c * D * D
        if kernel == "kda_fwd":
            # A and Aq; the solve for [Wv, Wk]; Wk.S, Q.S, U^T.K; Aq.U
            return c * c * D + lower * 2 * D + 3 * state + lower * D
        # A, Aq, the solve and Wk.S again; dU (Aq^T.dO, K.dS); dO.S, dU.S,
        # U.dS, dO^T.Q, dU^T.Wk; dAq; the transposed solve and lam.W^T; dA
        # and dAq back to q and to k (as row and as column)
        return (c * c * D + lower * 2 * D + state + lower * D + state
                + 5 * state + lower * D + (lower + strict) * 2 * D
                + 2 * c * c * D)

    chunks = {128: [64, 64], 100: [64, 36]}[length]
    assert got.flops == 2 * B * H * sum(macs(c) for c in chunks)
    # a chunk's exponentials over the 128 lanes: exp(G) and exp(G_c - G)
    # 64 rows each, exp(G_c) 1, the rows' factors at each of the four
    # levels (blocks of 64, 32, 16, 8 rows) and the decays at the three
    # distances inside a block of 4 rows, 64 rows each; and the prologue's
    # a token of a head: two rsqrt and beta's sigmoid
    assert got.transcendentals == B * H * (
        2 * D * (64 + 64 + 1 + 4 * 64 + 3 * 64) + 3 * length)
    wide, narrow = 4 * B * length * H * D, 2 * B * length * H * D
    logits, states = 2 * B * length * H, 4 * B * H * 2 * D * D
    moved = 3 * wide + narrow + logits + states + narrow  # ..., o or dO
    if kernel == "kda_bwd":  # dq, dk, dg, dv, dbeta (float32 rows)
        moved += 3 * wide + narrow + 2 * logits
    assert got.bytes_accessed == moved


def test_bf16_products_against_the_float32_recurrence(interpreter):
    """What the chip runs: `_chunk_fwd` and `_chunk_bwd` with
    `dtype=bfloat16`, every product's operands rounded to bf16 and summed
    in float32, here under the interpreter on the CPU, against the float32
    token recurrence. The regime is the one that rounding hurts most:
    nearly parallel keys, so A's entries are near 1 and the solve
    amplifies what they lost.

    Distances read here, relative, in the order o, dq, dk, dv, dg, dbeta
    (before PR 65, on normed q and k, the log decay and beta themselves;
    since, from the projections' arrays, with A_log's and the bias's
    behind them: 0.0116, 0.0111, 0.0112, 0.0139, 0.0107, 0.0100, 0.0107,
    0.0107).
    The sub-chunk scheme of PR 32 to 49, whose diagonal blocks of 16 were
    float32 sums: 0.0115, 0.0099, 0.0101, 0.0138, 0.0097, 0.0103. The
    levels, where every pair outside a block of 4 is a product: 0.0116,
    0.0106, 0.0108, 0.0139, 0.0105, 0.0099. The limit is twice the
    largest."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.linear_attn_ops import kda_gate
    from paddle_tpu.ops.pallas import kda_chunk as kernel

    args = _args(127, -0.01, -1e-4, parallel=True)
    statics = (H, 2, jnp.bfloat16, True, 127)

    def bf16(q, k, v, g, beta, a_log, dt_bias):
        # as `kda_mixer_core` hands a decay a channel over: gated by XLA
        flat = [jnp.pad(t.reshape(B, 127, -1), ((0, 0), (0, 1), (0, 0)))
                for t in (q, k, v, kda_gate(g.reshape(B, 127, -1), a_log,
                                            dt_bias.reshape(-1), H), beta)]
        return kernel._core(*flat, (), statics)[:, :127].reshape(v.shape)

    def grads(fn, args):
        return jax.grad(lambda *a: jnp.sum(fn(*a) ** 2),
                        argnums=range(7))(*args)

    got = (bf16(*args), *grads(bf16, args))
    out, gradients = value_and_grads(after_prologue(ref.kda_recurrence), args)
    read = []
    for a, w in zip(got, (out, *gradients)):
        assert a.shape == w.shape and np.isfinite(np.asarray(a)).all()
        read.append(rel(a, w))
    for name, distance in zip(("o", *OPERANDS), read):
        assert distance < 0.028, (name, read)


def _systems(n, seed):
    """`n` strictly lower [64, 64] N = Diag(beta) A as a chunk forms them,
    by regime in turn: nearly parallel keys under hardly any decay (N's
    entries near 1, where a series in N's powers diverges), keys at
    random under a mild decay, and a decay that all but erases the
    state."""
    r = np.random.RandomState(seed)
    out = []
    for at in range(n):
        noise = r.randn(64, D)
        k = r.randn(1, D) + 0.3 * noise if at % 3 == 0 else noise
        k /= np.linalg.norm(k, axis=-1, keepdims=True)
        lo, hi = [(-0.01, -1e-4), (-1.0, -0.01), (-8.0, -3.0)][at % 3]
        G = np.cumsum(r.uniform(lo, hi, (64, D)), 0)
        A = np.einsum("id,jd,ijd->ij", k, k, np.exp(np.minimum(
            G[:, None] - G[None], 0.0)))
        beta = r.uniform(0.9 if at % 3 == 0 else 0, 1, (64, 1))
        out.append(np.tril(beta * A, -1).astype(np.float32))
    return out


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("chunks", [1, 2, 4])
def test_the_lockstep_solve_is_each_chunks_own_to_the_bit(chunks, dtype):
    """`_inverse` over the chunks of a grid step states every chunk's
    product of a doubling before any chunk's next: the same products on
    the same operands in another order, so each inverse is the one a call
    with that N alone gives, bit for bit, and it is an inverse."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.kda_chunk import _inverse

    dtype = jnp.dtype(dtype)
    Ns = [jnp.asarray(N) for N in _systems(chunks, seed=chunks)]
    together = jax.jit(lambda *Ns: _inverse(list(Ns), dtype))(*Ns)
    assert len(together) == chunks
    for N, T in zip(Ns, together):
        (alone,) = jax.jit(lambda N: _inverse([N], dtype))(N)
        assert np.array_equal(np.asarray(T), np.asarray(alone))
        if dtype == jnp.float32:
            eye = np.eye(64)
            np.testing.assert_allclose(
                (eye + np.asarray(N, np.float64)) @ np.asarray(T, np.float64),
                eye, atol=2e-4)


def _step_rows(chunks, seed):
    """q, k, v, g [chunks*64, 128] and beta [chunks*64, 1] as a grid step
    of `chunks` chunks of one head holds them."""
    import jax.numpy as jnp

    r = np.random.RandomState(seed)
    rows = chunks * 64

    def unit(t):
        return t / np.linalg.norm(t, axis=-1, keepdims=True)

    return [jnp.asarray(t, jnp.float32) for t in (
        unit(r.randn(rows, D)), unit(r.randn(rows, D)), r.randn(rows, D),
        r.uniform(-1.0, -0.01, (rows, D)), r.uniform(0, 1, (rows, 1)))]


def _leaves_equal(got, want):
    import jax

    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for a, w in zip(got, want):
        assert a.shape == w.shape and a.dtype == w.dtype
        assert np.array_equal(np.asarray(a), np.asarray(w))


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("chunks", [1, 2, 4])
def test_the_stacked_rows_are_each_chunks_own_to_the_bit(chunks, dtype,
                                                         backward):
    """`_rows` states a grid step's row arithmetic once over its stacked
    rows (the cumulative sum, the exponentials, the levels' `e`, `k*e` and
    `q*e`, the blocks of four, the lane sums, `beta v`, `k exp(G)`), and a
    chunk cuts its 64 rows out: every array of every chunk's state-free
    half, its rows of `_Rows`, A, Aq (or its transpose), T and W, is what
    `_rows` over that chunk alone gives, bit for bit."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import kda_chunk as kernel

    dtype = jnp.dtype(dtype)

    @jax.jit
    def state_free(*operands):
        return kernel._state_free(
            kernel._rows(*operands),
            kernel._pair_masks(kernel.CHUNK), dtype, backward)

    operands = _step_rows(chunks, seed=chunks)
    together = state_free(*operands)
    assert len(together) == chunks
    for t, free in enumerate(together):
        (alone,) = state_free(*(x[t * 64:(t + 1) * 64] for x in operands))
        assert (free.rows.back is None) != backward
        _leaves_equal(free, alone)


@pytest.mark.parametrize("per_head", [False, True], ids=["kda", "gdn"])
def test_a_further_chunk_of_a_grid_step_costs_the_host_this_much(per_head):
    """The host lowers every equation of a kernel's body at every start
    of a job, compile cache or not, and a grid step is so many copies of
    the chunk's code: about 0.8 ms an equation on the chip's host (PERF.md,
    PR 52 and PR 54). With a step's row arithmetic stated once, a further
    chunk adds 127 equations forward and 311 backward (317 with a decay a
    head) where it added 344 and 609 (364 and 638): held to 230 and 500,
    so that the next change to the body sees what it costs a start
    (`tools/kda_vreg_count.py` prints the same counts)."""
    from tools.kda_vreg_count import kernel_equations

    from paddle_tpu.ops.pallas import kda_chunk as kernel

    two, four = (kernel_equations(kernel, steps, per_head)
                 for steps in (2, 4))
    fwd, bwd = ("gdn_fwd", "gdn_bwd") if per_head else ("kda_fwd", "kda_bwd")
    assert set(two) == set(four) == {fwd, bwd}
    for name, limit in ((fwd, 230), (bwd, 500)):
        a_chunk = (four[name] - two[name]) / 2
        assert 0 < a_chunk <= limit, (name, two[name], four[name])
        # and what is stated once stays a small part of the whole
        assert two[name] - 2 * a_chunk <= 400, (name, two[name], four[name])


@pytest.mark.parametrize("length,per_step", [
    (100, 2), (193, 2), (100, 4), (193, 4), (150, 4), (327, 4)])
def test_any_width_of_the_lockstep_is_the_pair_at_one_chunk_a_step(
        interpreter, monkeypatch, length, per_step):
    """On a length that leaves a padded tail (100: 36 rows of a second
    chunk; 150: three chunks, which a width of 4 takes as one grid step of
    three; 192 + 1: one row of a fourth; 5 x 64 + 7: a sixth chunk of
    seven rows, two grid steps of four with two padded chunks), with 1, 2
    or 4 chunks a grid step the same products read the same operands and
    the stacked rows are each chunk's own, whichever chunks share a step
    and however many padded ones follow (`pair_at_widths` says how each
    width is traced afresh and shown to differ from the other).

    Equal to the bit across the widths (`np.array_equal`): the output and
    every array the backward kernel writes, as its call returns them, on
    the float32 log decay `kda_gate` made once in front of both widths:
    dq and dk (the norm's gradient applied in the kernel), dv, dg (the
    log decay's, `[b, S, h*128]` float32) and the rows a chunk of beta's
    logits' gradient (through the sigmoid, in the kernel).

    Held at each width to the recurrence and to `kda_chunked` behind
    `_prologue`, at this file's limits (`gradients_held`: 1e-4, the
    decay's three twice the plain path's own distance where that is
    more), and not to the other width: the seven gradients as the op
    returns them, of which XLA forms, after the kernels, beta's logits' by
    token and the decay's logits', A_log's and the bias's through
    `kda_gate`'s backward and its sums over the tokens."""
    pair_at_widths(_args(length, -1.0, -0.01, seed=length), per_step,
                   monkeypatch, ref.kda_recurrence)


@pytest.mark.parametrize("seq,chunks", [(8, 1), (64, 1), (65, 2), (150, 3),
                                        (640, 10)])
def test_the_gauge_reads_the_chunks_a_grid_step_solves_together(
        interpreter, monkeypatch, seq, chunks):
    """`kda_lockstep_chunks` is `steps` of the call: the row's chunks up
    to `CHUNKS_PER_STEP`, 1 where the row is one chunk."""
    import jax.numpy as jnp

    from paddle_tpu import profiler
    from paddle_tpu.ops import linear_attn_ops
    from paddle_tpu.ops.pallas import kda_chunk as kernel

    seen = []
    monkeypatch.setattr(kernel, "kda_chunk",
                        lambda *a: seen.append(a[2].shape[1])
                        or jnp.zeros(a[2].shape))
    x = jnp.ones((1, seq, 2 * D), jnp.float32)
    logits = jnp.ones((1, seq, 2), jnp.float32)
    heads = jnp.zeros((2,), jnp.float32)
    linear_attn_ops.kda_mixer_core(x, x, x, x, logits, heads,
                                   jnp.zeros((2 * D,), jnp.float32), 2, 1e-6)
    assert seen == [seq]
    want = min(kernel.CHUNKS_PER_STEP, chunks)
    assert kernel.lockstep_chunks(seq) == want
    assert profiler.counters()["kda_lockstep_chunks"] == want
