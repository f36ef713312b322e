"""The op `selective_scan` (ops/ssm_ops.py) and its gradient against
Mamba-1's recurrence taken one token at a time
(`tests/kernel_cases.py`; each side and each gradient compiled), through
the function and through a Program; float32 inside under bf16 operands; and that neither
the forward nor the backward it lowers to holds an array of the row's
whole trajectory, `s x d_inner x d_state`."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kernel_cases import SSM_NAMES as NAMES
from kernel_cases import compiled, loss_grads, value_and_grads
from kernel_cases import ssm_operands as operands
from kernel_cases import ssm_recurrence as recurrence

from paddle_tpu.ops import ssm_ops


# rows that are a multiple of the chunk (8), that are not, that are shorter
# than one chunk, and one chunk exactly; batch 2 and batch 1
CASES = {"six_chunks_b2": (2, 48, 24, 4), "ragged_b2": (2, 37, 24, 4),
         "shorter_than_a_chunk": (2, 5, 24, 4), "one_chunk": (1, 8, 16, 16),
         "one_token": (1, 1, 8, 4), "long_b1": (1, 200, 8, 16)}


@pytest.mark.parametrize("case", list(CASES))
def test_scan_and_gradient_equal_the_recurrence(case):
    args = operands(*CASES[case])
    w = jnp.asarray(np.random.RandomState(1).randn(*args[0].shape),
                    jnp.float32)
    (got, grads), (want, grads_want) = (
        value_and_grads(fn, args, w)
        for fn in (ssm_ops.selective_scan, recurrence))
    assert got.shape == want.shape and got.dtype == jnp.float32
    scale = float(np.abs(want).max())
    assert float(np.abs(got - want).max()) < 2e-6 * max(scale, 1.0)
    for name, g, g_want in zip(NAMES, grads, grads_want):
        assert g.shape == g_want.shape, name
        scale = max(float(np.abs(g_want).max()), 1.0)
        assert float(np.abs(g - g_want).max()) < 5e-6 * scale, name


@pytest.mark.parametrize("chunk", [1, 2, 4, 16, 64])
def test_the_chunk_changes_nothing_but_rounding(chunk):
    args = operands(2, 40, 16, 4, seed=3)
    want = compiled(recurrence, *args)
    got = compiled(lambda *t: ssm_ops.selective_scan(*t, chunk=chunk), *args)
    assert float(np.abs(got - want).max()) < 1e-4  # of values up to 60


def test_steps_of_any_size_overflow_nothing():
    """Steps up to e^6 a token under A = -15: every exponent inside is at
    most 0, so a decay underflows to 0 and nothing reaches infinity."""
    args = operands(1, 32, 8, 4, seed=4, step=(-8.0, 6.0))
    got, grads = value_and_grads(ssm_ops.selective_scan, args, 1.0)
    assert all(bool(np.isfinite(t).all()) for t in (got, *grads))
    want = compiled(recurrence, *args)
    assert float(np.abs(got - want).max()) < 1e-4 * float(np.abs(want).max())


def test_float32_inside_under_bf16_operands():
    """bf16 in and out, the state and the decays float32: against the
    recurrence in float32 on the same rounded operands the output differs
    by its own rounding to bf16 and no more; the recurrence with a bf16
    state is ten times further off."""
    x, delta, a, bm, cm, dskip = operands(1, 256, 16, 8, seed=5,
                                          step=(-6.0, -3.0))
    low = [t.astype(jnp.bfloat16) for t in (x, delta, bm, cm)]
    got = ssm_ops.selective_scan(low[0], low[1], a, low[2], low[3], dskip)
    assert got.dtype == jnp.bfloat16
    up = [t.astype(jnp.float32) for t in low]
    want = jax.jit(recurrence)(up[0], up[1], a, up[2], up[3], dskip)
    err = float(jnp.sqrt(jnp.mean((got.astype(jnp.float32) - want) ** 2))
                / jnp.sqrt(jnp.mean(want ** 2)))
    assert err < 3e-3  # half an ulp of bf16, 2^-9, on average less


def _sizes_in(text):
    """Element counts of every tensor type in a StableHLO module."""
    return [int(np.prod([int(v) for v in m.group(1).split("x") if v]))
            for m in re.finditer(r"tensor<((?:\d+x)+)(?:f32|bf16|i1|i32)>",
                                 text)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_no_array_of_the_rows_whole_trajectory(dtype):
    """What the train step lowers, forward and gradient: the largest array
    anywhere in it is a chunk's fused operand, `c x c x n x d`, and nothing
    has `s x d x n` elements (at 4,096 x 5,120 x 16 that array is
    1.34 GB)."""
    b, s, d, n = 1, 512, 128, 16
    x, delta, a, bm, cm, dskip = operands(b, s, d, n)
    x, delta, bm, cm = (t.astype(dtype) for t in (x, delta, bm, cm))

    def loss(*t):
        return jnp.sum(ssm_ops.selective_scan(*t).astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, argnums=range(6))).lower(
        x, delta, a, bm, cm, dskip).as_text()
    assert "while" in text  # the chunks are a loop, not unrolled
    sizes = _sizes_in(text)
    c = ssm_ops.CHUNK
    assert max(sizes) == b * c * c * n * d < b * s * d * n
    # the states the chunks start from are what the backward keeps
    assert b * (s // c) * n * d in sizes
    jaxpr = str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=range(6)))(
        x, delta, a, bm, cm, dskip))
    assert f"{s},{n},{d}]" not in jaxpr and f"{s},{d},{n}]" not in jaxpr


def test_op_in_a_program_value_gradient_shape_and_counters():
    import paddle_tpu as fluid
    from paddle_tpu import profiler
    from tools.verify_bench_programs import compare_static_vs_traced

    b, s, d, n = 2, 21, 16, 4
    args = operands(b, s, d, n, seed=6)
    L = fluid.layers
    shapes = {"x": (b, s, d), "delta": (b, s, d), "a": (d, n),
              "b": (b, s, n), "c": (b, s, n), "d": (d,)}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        vs = [L.data(name, list(shapes[name]), append_batch_size=False)
              for name in NAMES]
        for v in vs:
            v.stop_gradient = False
        y = L.selective_scan(*vs)
        assert tuple(y.shape) == (b, s, d)
        w = np.random.RandomState(1).randn(b, s, d).astype(np.float32)
        loss = L.reduce_sum(L.elementwise_mul(y, L.assign(w)))
        grads = fluid.backward.calc_gradient(loss, vs)
        n_ops, mismatches, unknown = compare_static_vs_traced(
            main, {k: (v, "float32") for k, v in shapes.items()})
        assert n_ops >= 2 and mismatches == [] and unknown == []
        before = profiler.counters()
        exe = fluid.Executor(fluid.CPUPlace())
        got = exe.run(main, feed={k: np.asarray(v) for k, v in
                                  zip(NAMES, args)},
                      fetch_list=[y, *grads])
    after = profiler.counters()
    assert (after["ssm_dispatch_chunked"]
            - before.get("ssm_dispatch_chunked", 0)) == 1  # the gradient op reads Starts
    assert (after["ssm_state_size"], after["ssm_chunk_len"]) == (
        n, ssm_ops.CHUNK)
    np.testing.assert_allclose(got[0], jax.jit(recurrence)(*args), atol=1e-5)
    want = loss_grads(recurrence, args, w)
    for name, g, g_want in zip(NAMES, got[1:], want):
        np.testing.assert_allclose(g, g_want, atol=2e-5, rtol=2e-6,
                                   err_msg=name)
    scopes = {fluid.ops.registry.op_scope(op) for op in main.global_block().ops}
    assert {"fwd/selective_scan", "bwd/selective_scan_grad"} <= scopes
