"""RMSNorm's gradient as an op of its own (`rms_norm_grad`, ops/nn_ops.py)
and the kernel it takes where the shape admits it (`rms_bwd`,
ops/pallas/layer_norm.py): the kernel against `jax.vjp` of
`nn_ops.rms_norm` under the interpreter, the shape rule, the grad maker,
a weight shared by two norms, and the lowering on both of its paths
against the generic `__auto_grad__` that stood in its place."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, profiler
from paddle_tpu.ops import nn_ops
from paddle_tpu.ops.pallas.layer_norm import rms_bwd, rms_bwd_viable
from paddle_tpu.ops.registry import get_op


def _vjp(x, dy, scale, eps=1e-6, begin=1):
    import jax

    _, pullback = jax.vjp(
        lambda x, s: nn_ops.rms_norm(x, s, eps, begin), x, scale)
    return pullback(dy)


@pytest.mark.parametrize("n,k,dtype", [
    (4096, 2048, "bfloat16"),  # Ouro's
    (1000, 2304, "bfloat16"),  # a short last block, zero rows padded in
    (2048, 4096, "float32"),
])
def test_rms_bwd_is_the_vjp_of_rms_norm(n, k, dtype, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    import jax.numpy as jnp

    r = np.random.RandomState(n + k)
    x = jnp.asarray(r.randn(n, k), dtype)
    dy = jnp.asarray(r.randn(n, k), dtype)
    scale = jnp.asarray(r.uniform(0.5, 1.5, k), jnp.float32)
    want_dx, want_dscale = _vjp(x, dy, scale)
    dx, dscale = rms_bwd(x, dy, scale, 1e-6)
    assert (dx.shape, dx.dtype) == (x.shape, x.dtype)
    assert (dscale.shape, dscale.dtype) == ((k,), jnp.float32)
    # float32 inside on both sides: one rounding to x's dtype apart at most
    ulp = 2.0 ** -7 if dtype == "bfloat16" else 2.0 ** -22
    np.testing.assert_allclose(
        np.asarray(dx, np.float32), np.asarray(want_dx, np.float32),
        rtol=ulp, atol=ulp)
    # a sum over n rows in another order
    np.testing.assert_allclose(np.asarray(dscale), np.asarray(want_dscale),
                               rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("n,k,admitted", [
    (4096, 2048, True),    # Ouro's block norms
    (8192, 4096, True),    # the widest of the expert decoders'
    (1024, 1024, True),    # the least of both
    (4096, 1536, True),    # JoyAI's compressed query beside its 2,048
    (8192, 2304, False),   # Kimi's and Mellum's: XLA's own is the faster
    (4096 * 16, 128, False),   # a norm over one head's lanes
    (4096 * 12, 192, False),
    (4096 * 8, 256, False),
    (4096, 512, False),    # a latent's norm
    (1016, 2048, False),   # fewer rows than a kernel is worth
    (4096, 2000, False),   # not whole lanes
    (4096, 4224, False),   # a row block past VMEM
])
def test_rms_bwd_viable_is_a_rule_of_the_shape(n, k, admitted):
    assert rms_bwd_viable(n, k) is admitted


@pytest.mark.parametrize("n,k,rows", [
    (4096, 2048, 128), (4096, 4096, 64),  # the most VMEM holds divides
    (4096, 1536, 128),   # 168 would leave a short block, 128 divides
    (1000, 2304, 112),   # nothing near divides: the last block is padded
])
def test_the_block_divides_the_rows_where_one_near_the_most_does(n, k, rows):
    from paddle_tpu.ops.pallas.layer_norm import _dividing_block_rows

    assert _dividing_block_rows(n, k) == rows


def _norm_program(shape, begin, scale_name="norm.w", twice=False):
    """x -> rms_norm (-> rms_norm with the same weight) -> a weighted sum;
    the Program, the loss and the (parameter, gradient) pairs, with x's
    gradient wanted too."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", shape, dtype="float32", append_batch_size=False)
        x.stop_gradient = False
        w = layers.data("w", shape, dtype="float32", append_batch_size=False)
        y = x
        for _ in range(2 if twice else 1):
            y = layers.rms_norm(y, begin_norm_axis=begin, epsilon=1e-6,
                                param_attr=fluid.ParamAttr(name=scale_name))
        loss = layers.reduce_sum(layers.elementwise_mul(y, w))
        pairs = fluid.backward.append_backward(loss)
    return main, startup, loss, pairs


def _run(main, startup, fetch, feed, scale):
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.global_scope().set("norm.w", scale)
        return [np.asarray(v) for v in exe.run(main, feed=feed,
                                               fetch_list=fetch)]


def test_the_grad_maker_defers_to_the_vjp_when_y_has_no_cotangent():
    main, _, _, _ = _norm_program([4, 8], 1)
    (op,) = [op for op in main.global_block().ops if op.type == "rms_norm"]
    maker = get_op("rms_norm").grad
    helpers = fluid.backward._GradHelpers
    assert maker(op, {"Y": [None]}, main.global_block(), helpers) is None
    (desc,) = maker(op, {"Y": ["y@GRAD"]}, main.global_block(), helpers)
    assert desc["type"] == "rms_norm_grad"
    assert desc["inputs"] == {"X": ["x"], "Scale": ["norm.w"],
                              "GRAD_Y": ["y@GRAD"]}
    assert sorted(desc["outputs"]) == ["IGRAD_Scale", "IGRAD_X"]
    assert desc["attrs"] == {"epsilon": 1e-6, "begin_norm_axis": 1}
    assert get_op("rms_norm_grad").differentiable is False


def test_one_scale_under_two_norms_sums_two_partials():
    import jax
    import jax.numpy as jnp

    before = profiler.counters()
    main, startup, loss, pairs = _norm_program([6, 16], 1, twice=True)
    after = profiler.counters()
    assert [after.get(n, 0) - before.get(n, 0) for n in (
        "param_grads_summed", "param_grad_partials")] == [1, 2]
    ops = main.global_block().ops
    grads = [op for op in ops if op.type == "rms_norm_grad"]
    assert len(grads) == 2
    assert not [op for op in ops if op.type == "__auto_grad__"
                and op.attr("fwd_type") == "rms_norm"]
    (total,) = [op for op in ops if op.type == "sum"
                and op.output("Out") == ["norm.w@GRAD"]]
    assert sorted(total.input("X")) == sorted(
        g.output("IGRAD_Scale")[0] for g in grads)

    r = np.random.RandomState(0)
    x, w = (r.randn(6, 16).astype(np.float32) for _ in range(2))
    scale = r.uniform(0.5, 1.5, 16).astype(np.float32)
    ((_, g),) = pairs
    got_scale, got_x = _run(main, startup, [g.name, "x@GRAD"],
                            {"x": x, "w": w}, scale)

    def twice(x, s):
        y = nn_ops.rms_norm(nn_ops.rms_norm(x, s, 1e-6, 1), s, 1e-6, 1)
        return jnp.sum(y * w)

    want_x, want_scale = jax.grad(twice, argnums=(0, 1))(x, scale)
    np.testing.assert_allclose(got_scale, want_scale, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_x, want_x, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape,begin,kernel_calls", [
    ([2, 512, 1024], 2, 1),     # a block norm: 1,024 rows of 1,024
    ([1, 256, 8, 128], 3, 0),   # a norm over one head's lanes
    ([2, 256, 1024], 2, 0),     # too few rows
], ids=["block", "per-head", "few-rows"])
@pytest.mark.parametrize("amp", [False, True], ids=["float32", "amp"])
def test_the_lowering_on_either_path_is_what_auto_grad_lowered(
        shape, begin, kernel_calls, amp, monkeypatch):
    """The op's two gradients against the generic grad op's (the maker
    taken away: the tree before), under the interpreter so that the shape
    rule alone decides; with AMP on and `rms_norm` on the white list, the
    lists move neither."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    r = np.random.RandomState(1)
    feed = {"x": r.randn(*shape).astype(np.float32),
            "w": r.randn(*shape).astype(np.float32)}
    scale = r.uniform(0.5, 1.5, shape[begin:]).astype(np.float32)

    def grads(explicit):
        with monkeypatch.context() as m:
            if not explicit:
                m.setattr(get_op("rms_norm"), "grad", None)
            main, startup, _, pairs = _norm_program(shape, begin)
        ops = main.global_block().ops
        assert any(op.type == "rms_norm_grad" for op in ops) is explicit
        assert any(op.type == "__auto_grad__" and op.attr("fwd_type")
                   == "rms_norm" for op in ops) is not explicit
        if amp:
            main._amp_dtype = "bfloat16"
            main._amp_white_list = {"rms_norm"}
        before = profiler.counters().get("rms_bwd_calls", 0)
        got = _run(main, startup, [pairs[0][1].name, "x@GRAD"], feed, scale)
        return got, profiler.counters().get("rms_bwd_calls", 0) - before

    (want_scale, want_x), none = grads(explicit=False)
    (got_scale, got_x), calls = grads(explicit=True)
    assert (none, calls) == (0, kernel_calls)
    assert got_x.dtype == want_x.dtype == np.float32
    assert got_scale.shape == want_scale.shape == tuple(shape[begin:])
    np.testing.assert_allclose(got_x, want_x, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_scale, want_scale, rtol=1e-5, atol=1e-3)


def test_the_op_runs_under_its_own_scope_and_without_the_interpreter(
        monkeypatch):
    """On a backend with no Pallas the op is the vjp and counts no kernel;
    its scope is the one `ouro_norm_device_pct` reads."""
    from paddle_tpu.ops.registry import op_scope

    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    main, startup, _, pairs = _norm_program([2, 512, 1024], 2)
    (op,) = [op for op in main.global_block().ops
             if op.type == "rms_norm_grad"]
    assert op_scope(op) == "bwd/rms_norm_grad"
    r = np.random.RandomState(2)
    feed = {n: r.randn(2, 512, 1024).astype(np.float32) for n in "xw"}
    before = profiler.counters().get("rms_bwd_calls", 0)
    (got,) = _run(main, startup, [pairs[0][1].name], feed,
                  np.ones(1024, np.float32))
    assert profiler.counters().get("rms_bwd_calls", 0) == before
    assert np.isfinite(got).all() and np.abs(got).max() > 0
