"""Custom grad-maker protocol regressions (backward.py custom branch):
partial-grad accumulation when two custom-grad ops consume one variable,
stop_gradient pruning, and maker fallback to the generic vjp path."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.framework import Program

from op_test_base import check_grad


@pytest.fixture
def rng():
    return np.random.RandomState(7)


def test_var_feeding_two_adds_accumulates(rng):
    # x feeds two custom-maker adds: dx must be the sum of both partials
    check_grad(lambda x: layers.elementwise_add(x, x), [("x", (3, 4))], rng)


def test_pre_ln_residual_grad_matches_fd(rng):
    # the pre-LN residual pattern: x feeds BOTH layer_norm and the
    # residual add — both custom makers must accumulate into dx
    check_grad(
        lambda x: layers.elementwise_add(
            x, layers.layer_norm(x, begin_norm_axis=1)
        ),
        [("x", (4, 16))],
        rng,
        rtol=2e-2,
        atol=5e-3,
    )


def test_layer_norm_scale_bias_grads(rng):
    def build(x):
        return layers.layer_norm(x, begin_norm_axis=1)

    # grads wrt x through the explicit layer_norm_grad op
    check_grad(build, [("x", (4, 16))], rng, rtol=2e-2, atol=5e-3)


def test_shared_bias_two_sites(rng):
    # one small tensor consumed (broadcast) by two adds: its grad is the
    # sum of both sites' column sums
    def build(x, b):
        s1 = layers.elementwise_add(x, b, axis=1)
        s2 = layers.elementwise_add(layers.scale(x, scale=2.0), b, axis=1)
        return layers.elementwise_add(s1, s2)

    check_grad(build, [("x", (2, 3, 4)), ("b", (3,))], rng)


def test_stop_gradient_blocks_custom_add_grad():
    main, startup = Program(), Program()
    with fluid.program_guard(main, startup):
        w = fluid.layers.data("w", [3], append_batch_size=False)
        w.stop_gradient = False
        x = fluid.layers.data("x", [3], append_batch_size=False)
        x.stop_gradient = False
        d = layers.scale(w, scale=2.0)
        d.stop_gradient = True
        s = layers.elementwise_add(x, d)
        loss = layers.reduce_sum(layers.square(s))
        gx = fluid.backward.calc_gradient(loss, [x])[0]
    # no grad op may write into w@GRAD across the stopped boundary
    assert not any(
        "w@GRAD" in op.output_arg_names() for op in main.global_block().ops
    )
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = {"w": np.full(3, 2.0, "float32"), "x": np.ones(3, "float32")}
    (gxv,) = exe.run(main, feed=feed, fetch_list=[gx.name])
    np.testing.assert_allclose(gxv, 2.0 * (1.0 + 4.0) * np.ones(3))


def test_layer_norm_mean_only_grad_falls_back():
    # differentiating only the Mean output must not crash (maker defers
    # to the generic vjp path)
    main, startup = Program(), Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4, 8], append_batch_size=False)
        x.stop_gradient = False
        layers.layer_norm(x, begin_norm_axis=1)
        blk = main.global_block()
        mean = None
        for op in blk.ops:
            if op.type == "layer_norm":
                mean = blk.var(op.output("Mean")[0])
        mean.stop_gradient = False
        loss = layers.reduce_sum(layers.square(mean))
        g = fluid.backward.calc_gradient(loss, [x])[0]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(5)
    feed = {"x": rng.randn(4, 8).astype("float32")}
    (gv,) = exe.run(main, feed=feed, fetch_list=[g.name])
    assert np.isfinite(np.asarray(gv)).all()
    # d(sum(mean^2))/dx = 2*mean/k broadcast
    expect = np.tile(
        2.0 * feed["x"].mean(axis=1, keepdims=True) / 8.0, (1, 8)
    )
    np.testing.assert_allclose(gv, expect, rtol=1e-3, atol=1e-5)


def test_ln_bwd_pallas_kernel_matches_fallback(monkeypatch):
    # interpret-mode run of the Pallas LN-backward kernel at a
    # production-viable size (n >= 1024), against the plain-JAX math
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.layer_norm import ln_bwd, ln_bwd_viable

    rng = np.random.RandomState(11)
    n, k = 1280, 128
    assert ln_bwd_viable(n, k)
    x = jnp.asarray(rng.randn(n, k).astype("float32"))
    dy = jnp.asarray(rng.randn(n, k).astype("float32"))
    scale = jnp.asarray((rng.rand(k) + 0.5).astype("float32"))
    mean = jnp.mean(x, axis=1)
    rstd = jax.lax.rsqrt(jnp.var(x, axis=1) + 1e-5)

    dx, dg, db = ln_bwd(x, dy, mean, rstd, scale)

    nrm = (x - mean[:, None]) * rstd[:, None]
    dyg = dy * scale[None, :]
    m1 = jnp.mean(dyg, axis=1, keepdims=True)
    m2 = jnp.mean(dyg * nrm, axis=1, keepdims=True)
    np.testing.assert_allclose(
        np.asarray(dx), np.asarray(rstd[:, None] * (dyg - m1 - nrm * m2)),
        atol=1e-4,
    )
    np.testing.assert_allclose(
        np.asarray(dg), np.asarray(jnp.sum(dy * nrm, axis=0)), atol=1e-3
    )
    np.testing.assert_allclose(
        np.asarray(db), np.asarray(jnp.sum(dy, axis=0)), atol=1e-3
    )


def test_neither_norms_backward_declares_a_cost(monkeypatch):
    """ops/pallas/cost.py's convention holds for every other call of the
    package but `embed_tgmm` (tests/test_embedding_grad.py has why).
    Declared, `ln_bwd` cost `bert_base_s128` 1.0 to 2.7% on the chip
    (PERF.md, Findings, PR 35) and `rms_bwd` cost Ouro's cell 1.1% (PR
    59), so neither call passes a `cost_estimate`: whoever declares one
    again has that cell to win."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    import jax.numpy as jnp
    from pallas_costs import declared

    from paddle_tpu.ops.pallas.layer_norm import ln_bwd, rms_bwd

    x, stat = jnp.zeros((2048, 768), jnp.bfloat16), jnp.zeros((2048,))
    assert declared(ln_bwd, x, x, stat, stat, jnp.ones((768,))) == {
        "ln_bwd": [None]}
    x = jnp.zeros((2048, 2048), jnp.bfloat16)
    assert declared(lambda x, s: rms_bwd(x, x, s, 1e-6), x,
                    jnp.ones((2048,))) == {"rms_bwd": [None]}


def test_ln_bwd_pallas_kernel_padded_rows(monkeypatch):
    # n not a multiple of block_rows: padded rows must contribute nothing
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.layer_norm import ln_bwd

    rng = np.random.RandomState(12)
    n, k = 1100, 128
    x = jnp.asarray(rng.randn(n, k).astype("float32"))
    dy = jnp.asarray(rng.randn(n, k).astype("float32"))
    scale = jnp.ones((k,), jnp.float32)
    mean = jnp.mean(x, axis=1)
    rstd = jax.lax.rsqrt(jnp.var(x, axis=1) + 1e-5)
    dx, dg, db = ln_bwd(x, dy, mean, rstd, scale)
    assert dx.shape == (n, k)
    np.testing.assert_allclose(
        np.asarray(db), np.asarray(jnp.sum(dy, axis=0)), atol=1e-3
    )
