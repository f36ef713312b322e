"""Aux subsystem tests: EMA/ModelAverage/Lookahead wrappers, quantization
(QAT rewrite), profiler spans in the jax.profiler trace, sync BN,
DGC/LocalSGD fallbacks (reference: optimizer.py:2263,2453,2976,805;
contrib/slim/quantization; SURVEY.md §5)."""

import warnings

import numpy as np
import pytest

import paddle_tpu as fluid


def _linreg(lr=0.1, opt=None):
    x = fluid.layers.data("x", [4])
    y = fluid.layers.data("y", [1])
    pred = fluid.layers.fc(x, 1, param_attr=fluid.initializer.Constant(0.0))
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    (opt or fluid.optimizer.SGD(lr)).minimize(loss)
    return loss, pred


def _run_steps(exe, loss, steps=8, seed=0):
    rng = np.random.RandomState(seed)
    w = np.full((4, 1), 0.5, "float32")
    out = None
    for _ in range(steps):
        xv = rng.randn(32, 4).astype("float32")
        out = exe.run(feed={"x": xv, "y": xv @ w}, fetch_list=[loss])
    return float(np.asarray(out[0]).reshape(-1)[0])


def test_ema_shadow_tracks_params():
    loss, _ = _linreg()
    ema = fluid.optimizer.ExponentialMovingAverage(0.5)
    ema.update()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    _run_steps(exe, loss, steps=10)
    scope = fluid.global_scope()
    pname, sname = ema._pairs[0]
    p = np.asarray(scope.get(pname))
    t = int(np.asarray(scope.get(ema._step_name)).reshape(-1)[0])
    shadow = np.asarray(scope.get(sname)) / (1.0 - 0.5**t)
    # with decay 0.5 over 10 steps the corrected shadow is close to current
    np.testing.assert_allclose(shadow, p, atol=0.15)
    with ema.apply(exe):
        np.testing.assert_allclose(np.asarray(scope.get(pname)), shadow,
                                   atol=1e-5)
    np.testing.assert_allclose(np.asarray(scope.get(pname)), p, atol=1e-7)


def test_model_average_apply_restores():
    loss, _ = _linreg()
    ma = fluid.optimizer.ModelAverage(max_average_window=100)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    _run_steps(exe, loss, steps=6)
    scope = fluid.global_scope()
    pname, sname, cname = ma._triples[0]
    p = np.asarray(scope.get(pname))
    assert int(np.asarray(scope.get(cname)).reshape(-1)[0]) == 6
    avg = np.asarray(scope.get(sname)) / 6
    with ma.apply(exe):
        np.testing.assert_allclose(np.asarray(scope.get(pname)), avg,
                                   atol=1e-6)
    np.testing.assert_allclose(np.asarray(scope.get(pname)), p)


def test_lookahead_syncs_every_k():
    opt = fluid.optimizer.LookaheadOptimizer(
        fluid.optimizer.SGD(0.1), alpha=0.5, k=2
    )
    loss, _ = _linreg(opt=opt)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    main = fluid.default_main_program()
    slow_names = [n for n in main.global_block().vars if n.endswith("_slow_0")
                  or "_slow" in n]
    assert slow_names
    _run_steps(exe, loss, steps=2)  # step 2 -> sync happened
    pname = "fc_0.w_0"
    slow = next(n for n in slow_names if n.startswith(pname))
    np.testing.assert_allclose(
        np.asarray(scope.get(slow)), np.asarray(scope.get(pname)), atol=1e-6
    )


def test_quant_aware_training_and_convert():
    from paddle_tpu.contrib.slim.quantization import convert, quant_aware

    rng = np.random.RandomState(0)
    x = fluid.layers.data("x", [8])
    y = fluid.layers.data("y", [1])
    h = fluid.layers.fc(x, 16, act="relu")
    pred = fluid.layers.fc(h, 1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    main = fluid.default_main_program()
    quant_aware(main)
    qtypes = {op.type for op in main.global_block().ops
              if "quant" in op.type}
    assert qtypes == {
        "fake_quantize_dequantize_abs_max",
        "fake_quantize_dequantize_moving_average_abs_max",
    }
    fluid.optimizer.Adam(1e-2).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    w = rng.randn(8, 1).astype("float32")
    losses = []
    for _ in range(40):
        xv = rng.randn(64, 8).astype("float32")
        lv = exe.run(feed={"x": xv, "y": xv @ w}, fetch_list=[loss])[0]
        losses.append(float(np.asarray(lv).reshape(-1)[0]))
    assert losses[-1] < losses[0] * 0.2, (losses[0], losses[-1])
    # activation scales were learned
    scope = fluid.global_scope()
    scales = [n for n in main.global_block().vars if "quant_scale" in n]
    assert scales and all(
        float(np.asarray(scope.get(n))[0]) > 0 for n in scales
    )
    # freeze + infer
    test_prog = convert(main._prune([pred.name]))
    out = exe.run(test_prog, feed={"x": rng.randn(4, 8).astype("float32"),
                                   "y": np.zeros((4, 1), "float32")},
                  fetch_list=[pred])
    assert np.isfinite(np.asarray(out[0])).all()


def test_ema_step_counts_training_steps_not_params():
    """The EMA step var must advance once per executor run, regardless of
    parameter count (bias correction uses it as t)."""
    x = fluid.layers.data("x", [4])
    y = fluid.layers.data("y", [1])
    h = fluid.layers.fc(x, 8, act="relu")  # 2 params
    pred = fluid.layers.fc(h, 1)  # 2 more params
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(0.01).minimize(loss)
    ema = fluid.optimizer.ExponentialMovingAverage(0.9)
    ema.update()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    _run_steps(exe, loss, steps=5)
    t = int(np.asarray(fluid.global_scope().get(ema._step_name))
            .reshape(-1)[0])
    assert t == 5, t


def test_quant_aware_for_test_freezes_scales():
    from paddle_tpu.contrib.slim.quantization import quant_aware

    x = fluid.layers.data("x", [8])
    pred = fluid.layers.fc(x, 1)
    main = fluid.default_main_program()
    quant_aware(main, for_test=True)
    qops = [op for op in main.global_block().ops
            if op.type == "fake_quantize_dequantize_moving_average_abs_max"]
    assert qops and all(op.attr("is_test") for op in qops)
    # frozen ops must not write the scale state back
    assert all(not op.output("OutScale") for op in qops)


def test_dgc_tolerates_reference_kwargs():
    import warnings as w

    with w.catch_warnings(record=True):
        w.simplefilter("always")
        opt = fluid.optimizer.DGCMomentumOptimizer(
            0.1, 0.9, rampup_begin_step=0, num_trainers=2,
            local_grad_clip_norm=1.0,
        )
    assert opt._momentum == 0.9


def test_profiler_record_event_lands_in_the_jax_trace(tmp_path):
    """A RecordEvent span is a row of the table and, under
    `profiler(trace_dir=...)`, an event of the jax.profiler trace, nested
    as it was entered."""
    import glob

    from jax.profiler import ProfileData

    import paddle_tpu.profiler as prof

    prof.reset_profiler()
    with prof.profiler(profile_path=str(tmp_path / "table.txt"),
                       trace_dir=str(tmp_path / "trace")):
        with prof.RecordEvent("step"):
            with prof.RecordEvent("forward"):
                sum(range(1000))
    table = (tmp_path / "table.txt").read_text()
    assert "step" in table and "forward" in table
    (path,) = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                        recursive=True)
    spans = {ev.name: (ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name in ("step", "forward")}
    assert set(spans) == {"step", "forward"}
    assert spans["step"][0] <= spans["forward"][0]
    assert spans["forward"][1] <= spans["step"][1]


def test_sync_batch_norm_is_batch_norm():
    img = fluid.layers.data("img", [3, 8, 8])
    out = fluid.layers.sync_batch_norm(img)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xv = np.random.RandomState(0).randn(4, 3, 8, 8).astype("float32")
    (ov,) = exe.run(feed={"img": xv}, fetch_list=[out])
    np.testing.assert_allclose(
        np.asarray(ov).mean(axis=(0, 2, 3)), 0.0, atol=1e-4
    )


def test_dgc_and_local_sgd_fallbacks():
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        opt = fluid.optimizer.DGCMomentumOptimizer(0.1, 0.9,
                                                   rampup_begin_step=0)
        assert any("ICI" in str(w.message) for w in rec)
    loss, _ = _linreg(opt=opt)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    final = _run_steps(exe, loss, steps=5)
    assert np.isfinite(final)

    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        inner = fluid.optimizer.SGD(0.1)
        fluid.optimizer.LocalSGDOptimizer(inner, k_steps=4)
        assert any("LocalSGD" in str(w.message) for w in rec)


def test_check_nan_inf_flag(monkeypatch):
    """FLAGS_check_nan_inf analog: names the offending op outputs,
    including gradients (reference operator.cc:949-961)."""
    monkeypatch.setenv("PADDLE_TPU_CHECK_NAN_INF", "1")
    x = fluid.layers.data("x", [4])
    y = fluid.layers.data("y", [1])
    pred = fluid.layers.fc(x, 1, param_attr=fluid.initializer.Constant(0.1))
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(1.0).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    # finite input passes cleanly first
    out = exe.run(feed={"x": np.ones((8, 4), "float32"),
                        "y": np.zeros((8, 1), "float32")},
                  fetch_list=[loss])
    assert np.isfinite(np.asarray(out[0])).all()
    with pytest.raises(RuntimeError, match="nan/inf"):
        exe.run(feed={"x": np.full((8, 4), 1e30, "float32"),
                      "y": np.zeros((8, 1), "float32")},
                fetch_list=[loss])


def test_check_nan_inf_works_with_microbatching(monkeypatch):
    """Round 3: the nan guard runs UNDER microbatching (flags AND-reduce
    over the scan); clean batches pass, poisoned ones raise (see
    test_amp.py::test_nan_guard_under_microbatching for the raise)."""
    monkeypatch.setenv("PADDLE_TPU_CHECK_NAN_INF", "1")
    x = fluid.layers.data("x", [4])
    y = fluid.layers.data("y", [1])
    pred = fluid.layers.fc(x, 1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.PipelineOptimizer(
        fluid.optimizer.SGD(0.1), num_microbatches=2).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    out = exe.run(feed={"x": np.ones((8, 4), "float32"),
                        "y": np.zeros((8, 1), "float32")},
                  fetch_list=[loss])
    assert np.isfinite(np.asarray(out[0])).all()
    with pytest.raises(RuntimeError, match="nan/inf"):
        exe.run(feed={"x": np.full((8, 4), 1e30, "float32"),
                      "y": np.zeros((8, 1), "float32")},
                fetch_list=[loss])


def test_recompute_optimizer_matches_plain():
    """RecomputeOptimizer (jax.checkpoint segments + jax.grad) must produce
    the exact same training trajectory as the explicit-backward path."""
    def build(recompute):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                x = fluid.layers.data("x", [8])
                y = fluid.layers.data("y", [1])
                h = x
                for i in range(3):
                    with fluid.recompute_scope(i):
                        h = fluid.layers.fc(
                            h, 16, act="tanh",
                            param_attr=fluid.initializer.Constant(
                                0.05 + 0.01 * i),
                        )
                pred = fluid.layers.fc(
                    h, 1, param_attr=fluid.initializer.Constant(0.1))
                loss = fluid.layers.mean(
                    fluid.layers.square_error_cost(pred, y))
                opt = fluid.optimizer.Adam(1e-2)
                if recompute:
                    opt = fluid.optimizer.RecomputeOptimizer(opt)
                opt.minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(0)
    feeds = [(rng.randn(16, 8).astype("float32"),
              rng.randn(16, 1).astype("float32")) for _ in range(5)]
    results = {}
    for rc in (False, True):
        main, startup, loss = build(rc)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            ls = []
            for xv, yv in feeds:
                (lv,) = exe.run(main, feed={"x": xv, "y": yv},
                                fetch_list=[loss], scope=scope)
                ls.append(float(np.asarray(lv).reshape(-1)[0]))
        results[rc] = ls
    np.testing.assert_allclose(results[False], results[True], rtol=1e-5)


def test_check_nan_inf_on_pp_mesh(monkeypatch):
    """The nan hunt runs on Program-pipeline (pipe>1) meshes. Under the
    GSPMD-native pipeline the step is ordinary traced code, so the hunt
    keeps the PER-OP granularity of the single-device path (the legacy
    manual schedule could only flag at fetch/state level); a poisoned
    batch raises naming the first offending op outputs."""
    from paddle_tpu.framework import Program, device_guard

    monkeypatch.setenv("PADDLE_TPU_CHECK_NAN_INF", "1")

    def build():
        main, startup = Program(), Program()
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                x = fluid.layers.data("x", [16])
                y = fluid.layers.data("y", [1])
                with device_guard("gpu:0"):
                    h = fluid.layers.fc(
                        x, 8, act="relu",
                        param_attr=fluid.initializer.Constant(0.05))
                with device_guard("gpu:1"):
                    pred = fluid.layers.fc(
                        h, 1, param_attr=fluid.initializer.Constant(0.1))
                    loss = fluid.layers.mean(
                        fluid.layers.square_error_cost(pred, y))
                fluid.optimizer.PipelineOptimizer(
                    fluid.optimizer.SGD(0.1), num_microbatches=2
                ).minimize(loss)
        return main, startup, loss

    main, startup, loss = build()
    compiled = fluid.CompiledProgram(main).with_pipeline(
        loss_name=loss.name, num_stages=2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        out = exe.run(compiled,
                      feed={"x": np.ones((8, 16), "float32"),
                            "y": np.zeros((8, 1), "float32")},
                      fetch_list=[loss])
        assert np.isfinite(np.asarray(out[0])).all()
        with pytest.raises(RuntimeError, match=r"nan/inf detected"):
            exe.run(compiled,
                    feed={"x": np.full((8, 16), 1e30, "float32"),
                          "y": np.zeros((8, 1), "float32")},
                    fetch_list=[loss])
