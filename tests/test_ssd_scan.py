"""The op `ssd_scan` (ops/ssm_ops.py, Mamba-2's recurrence in chunks of
matrix products) and its gradient op against the recurrence taken one
token at a time (each side and each gradient compiled): through the
function, forward and the gradients by x,
Delta, a, B, C and D, at rows of one chunk, several chunks, a ragged last
chunk and fewer tokens than a chunk, with one group and with several; and
through a Program, where the step's softplus and the decay's exponential
are the op's, with static shapes, counters and scopes."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kernel_cases import compiled, loss_grads, value_and_grads

from paddle_tpu.ops import ssm_ops


def recurrence(x, delta, a, bm, cm, dskip, groups=1):
    """`h = exp(delta a) h + (delta x) B^T; y = h C + D x`, one `lax.scan`
    step a token from a zero state. x: [b, s, H * P]; delta: [b, s, H];
    a, dskip: [H]; bm, cm: [b, s, G * N]; head h reads group
    h // (H / G)."""
    b, s, _ = x.shape
    heads = a.shape[0]
    group_of = jnp.arange(heads) // (heads // groups)
    x = x.reshape(b, s, heads, -1)
    bm = bm.reshape(b, s, groups, -1)[:, :, group_of]
    cm = cm.reshape(b, s, groups, -1)[:, :, group_of]

    def token(h, xs):  # h [b, H, P, N]
        x, delta, bm, cm = xs
        h = (jnp.exp(delta * a)[..., None, None] * h
             + (delta[..., None] * x)[..., None] * bm[:, :, None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", h, cm) + dskip[:, None] * x

    _, y = jax.lax.scan(
        token, jnp.zeros((b, heads, x.shape[-1], bm.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, delta, bm, cm)))
    return jnp.moveaxis(y, 0, 1).reshape(b, s, -1)


def operands(b, s, heads, p, n, groups=1, seed=0, step=(-5.0, 0.0)):
    """Steps log-uniform in `exp(step)`: at 1 a token and a = -15 a state
    is gone in one token, at 0.007 it lasts the row."""
    r = np.random.RandomState(seed)
    return tuple(jnp.asarray(t, jnp.float32) for t in (
        r.randn(b, s, heads * p), np.exp(r.uniform(*step, (b, s, heads))),
        -np.exp(r.uniform(0.0, 2.7, heads)), r.randn(b, s, groups * n),
        r.randn(b, s, groups * n), r.randn(heads)))


NAMES = ("x", "delta", "a", "b", "c", "d")
# (b, s, H, P, N, G, chunk): a row of one chunk, of several, with a ragged
# last chunk, shorter than a chunk, one token; one group and several
CASES = {"one_chunk": (1, 16, 4, 8, 16, 1, 16),
         "four_chunks_b2": (2, 64, 4, 8, 16, 1, 16),
         "ragged_b2_two_groups": (2, 37, 4, 8, 8, 2, 16),
         "shorter_than_a_chunk": (2, 5, 2, 4, 8, 1, 128),
         "a_group_a_head": (1, 48, 4, 4, 8, 4, 8),
         "one_token": (1, 1, 2, 4, 8, 1, 128),
         "published_chunk": (1, 300, 2, 8, 16, 1, 128)}


@pytest.mark.parametrize("case", list(CASES))
def test_chunks_and_gradients_equal_the_recurrence(case):
    b, s, heads, p, n, groups, chunk = CASES[case]
    args = operands(b, s, heads, p, n, groups)
    w = jnp.asarray(np.random.RandomState(1).randn(*args[0].shape),
                    jnp.float32)

    def chunked(*t):
        return ssm_ops.ssd_scan(*t, groups, chunk)

    def by_token(*t):
        return recurrence(*t, groups=groups)

    (got, grads), (want, grads_want) = (
        value_and_grads(fn, args, w) for fn in (chunked, by_token))
    starts = compiled(lambda *t: ssm_ops.ssd_scan_with_starts(
        *t, groups, chunk)[1], *args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert starts.shape == (-(-s // min(chunk, s)), b, heads, p, n)
    scale = max(float(jnp.abs(want).max()), 1.0)
    assert float(jnp.abs(got - want).max()) < 5e-6 * scale
    for name, g, g_want in zip(NAMES, grads, grads_want):
        assert g.shape == g_want.shape, name
        scale = max(float(jnp.abs(g_want).max()), 1.0)
        assert float(jnp.abs(g - g_want).max()) < 2e-5 * scale, name


def test_the_states_kept_are_the_recurrences():
    """`Starts[k]` is the state after `k * chunk` tokens."""
    args = operands(1, 48, 2, 4, 8, seed=2)
    _, starts = ssm_ops.ssd_scan_with_starts(*args, 1, 16)
    x, delta, a, bm, _, _ = args
    h = np.zeros((2, 4, 8), np.float32)
    for t in range(32):
        h = (np.exp(np.asarray(delta[0, t] * a))[:, None, None] * h
             + np.asarray(delta[0, t])[:, None, None]
             * np.asarray(x[0, t]).reshape(2, 4, 1)
             * np.asarray(bm[0, t])[None, None, :])
        if t + 1 in (16, 32):
            np.testing.assert_allclose(starts[(t + 1) // 16, 0], h,
                                       atol=1e-5)
    assert float(jnp.abs(starts[0]).max()) == 0.0


def test_steps_of_any_size_overflow_nothing():
    """Steps up to e^6 a token under a = -15: every exponent inside is at
    most 0, so a decay underflows to 0 and nothing reaches infinity."""
    args = operands(1, 64, 2, 4, 8, seed=4, step=(-8.0, 6.0))
    def chunked(*t):
        return ssm_ops.ssd_scan(*t, 1, 16)

    got = jax.jit(chunked)(*args)
    grads = loss_grads(chunked, args, 1.0)
    assert all(bool(jnp.isfinite(t).all()) for t in (got, *grads))
    want = jax.jit(recurrence)(*args)
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(jnp.abs(want).max())


def test_float32_inside_under_bf16_operands():
    x, delta, a, bm, cm, dskip = operands(1, 256, 2, 8, 16, seed=5,
                                          step=(-6.0, -3.0))
    low = [t.astype(jnp.bfloat16) for t in (x, bm, cm)]
    got = ssm_ops.ssd_scan(low[0], delta, a, low[1], low[2], dskip, 1, 64)
    assert got.dtype == jnp.bfloat16
    up = [t.astype(jnp.float32) for t in low]
    want = jax.jit(recurrence)(up[0], delta, a, up[1], up[2], dskip)
    err = float(jnp.sqrt(jnp.mean((got.astype(jnp.float32) - want) ** 2))
                / jnp.sqrt(jnp.mean(want ** 2)))
    assert err < 3e-3  # half an ulp of bf16, 2^-9, on average less


def test_no_array_of_the_rows_whole_trajectory():
    """What the train step lowers, forward and gradient, holds no array
    of `s x H x P x N` elements; the states the chunks start from are
    what the backward keeps."""
    b, s, heads, p, n, chunk = 1, 512, 2, 8, 16, 64
    args = operands(b, s, heads, p, n)

    def loss(*t):
        return jnp.sum(ssm_ops.ssd_scan(*t, 1, chunk))

    jaxpr = str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=range(6)))(
        *args))
    assert f"{s // chunk},{b},1,{heads},{p},{n}]" in jaxpr
    for whole in (f"{s},{heads},{p},{n}]", f"{s},1,{heads},{p},{n}]",
                  f"{chunk},1,{heads},{p},{n}]"):
        assert whole not in jaxpr, whole


def test_op_in_a_program_value_gradient_shape_and_counters():
    """The op with its parameters as the layer creates them: the step's
    softplus and the decay's exponential are the op's, the gradients go
    to `Dt`, `dt_bias` and `A_log`; static shapes, counters, scopes."""
    import paddle_tpu as fluid
    from paddle_tpu import profiler
    from tools.verify_bench_programs import compare_static_vs_traced

    b, s, heads, p, n, groups, chunk = 2, 21, 4, 4, 8, 2, 8
    x, dt, _, bm, cm, _ = operands(b, s, heads, p, n, groups, seed=6)
    dt = jnp.log(dt)  # raw: any sign
    L = fluid.layers
    shapes = {"x": (b, s, heads * p), "dt": (b, s, heads),
              "b": (b, s, groups * n), "c": (b, s, groups * n)}
    feed = dict(zip(shapes, (np.asarray(t) for t in (x, dt, bm, cm))))
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.scope_guard(fluid.Scope()):
        vs = [L.data(name, list(shape), append_batch_size=False)
              for name, shape in shapes.items()]
        for v in vs:
            v.stop_gradient = False
        y = L.ssd_scan(*vs, num_heads=heads, n_groups=groups,
                       chunk_size=chunk,
                       a_log_attr=fluid.ParamAttr(name="A_log"),
                       dt_bias_attr=fluid.ParamAttr(name="dt_bias"),
                       d_attr=fluid.ParamAttr(name="D"))
        assert tuple(y.shape) == (b, s, heads * p)
        w = np.random.RandomState(1).randn(b, s, heads * p).astype(np.float32)
        loss = L.reduce_sum(L.elementwise_mul(y, L.assign(w)))
        block = main.global_block()
        held = [block.var(n) for n in ("dt_bias", "A_log", "D")]
        grads = fluid.backward.calc_gradient(loss, vs + held)
        n_ops, mismatches, unknown = compare_static_vs_traced(
            main, {k: (v, "float32") for k, v in shapes.items()})
        assert n_ops >= 2 and mismatches == [] and unknown == []
        before = profiler.counters()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope = fluid.global_scope()
        a_log, dt_bias, dskip = (np.asarray(scope.get(n)) for n in (
            "A_log", "dt_bias", "D"))
        assert (0 <= a_log).all() and (a_log <= np.log(16)).all()
        assert (-6.9 <= dt_bias).all() and (dt_bias <= -2.25).all()
        np.testing.assert_array_equal(dskip, 1.0)
        got = exe.run(main, feed=feed, fetch_list=[y, *grads])
    after = profiler.counters()
    # once a lowering of the forward op: the gradient op reads Starts
    assert (after["ssd_dispatch_chunked"]
            - before.get("ssd_dispatch_chunked", 0)) == 1
    assert [after[k] for k in ("ssd_chunk_len", "ssd_heads", "ssd_groups",
                               "ssd_state_size")] == [chunk, heads, groups, n]

    def model(x, dt, bm, cm, dt_bias, a_log, dskip):
        return recurrence(x, jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log),
                          bm, cm, dskip, groups)

    args = (x, dt, bm, cm, *(jnp.asarray(t) for t in (dt_bias, a_log, dskip)))
    np.testing.assert_allclose(got[0], jax.jit(model)(*args), atol=1e-5)
    want = jax.jit(jax.grad(lambda *t: jnp.sum(model(*t) * w),
                            argnums=range(7)))(*args)
    for name, g, g_want in zip((*shapes, "dt_bias", "A_log", "D"),
                               got[1:], want):
        np.testing.assert_allclose(g, g_want, atol=2e-5, rtol=2e-5,
                                   err_msg=name)
    scopes = {fluid.ops.registry.op_scope(op) for op in block.ops}
    assert {"fwd/ssd_scan", "bwd/ssd_scan_grad"} <= scopes


def test_the_op_refuses_heads_the_groups_do_not_divide():
    import paddle_tpu as fluid

    L = fluid.layers
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.scope_guard(fluid.Scope()):
        x = L.data("x", [1, 8, 12], append_batch_size=False)
        dt = L.data("dt", [1, 8, 3], append_batch_size=False)
        bc = L.data("bc", [1, 8, 16], append_batch_size=False)
        y = L.ssd_scan(x, dt, bc, bc, num_heads=3, n_groups=2)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        with pytest.raises(ValueError, match="3 heads in 2 groups"):
            exe.run(main, feed={"x": np.zeros((1, 8, 12), np.float32),
                                "dt": np.zeros((1, 8, 3), np.float32),
                                "bc": np.zeros((1, 8, 16), np.float32)},
                    fetch_list=[y])
