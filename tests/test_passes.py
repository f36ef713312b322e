"""Program IR pass manager (paddle_tpu/passes/): DCE safety, constant
folding, fused multi-tensor optimizer updates, selection knobs, and
numeric equivalence of pass-enabled vs pass-disabled execution."""

import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.passes import (
    PASS_REGISTRY,
    apply_program_passes,
    resolve_pass_names,
)


@pytest.fixture(autouse=True)
def _no_pass_env(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_PASSES", raising=False)


def _op_types(block):
    return [op.type for op in block.ops]


# ------------------------------------------------------------ selection


def test_registry_has_the_passes():
    assert set(PASS_REGISTRY) >= {
        "dce", "const_fold", "copy_prop", "fuse_optimizer",
        "fuse_conv_bn", "layout_opt",
    }


def test_env_override(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PASSES", "none")
    assert resolve_pass_names(None) == ()
    monkeypatch.setenv("PADDLE_TPU_PASSES", "all")
    assert set(resolve_pass_names(None)) == set(PASS_REGISTRY)
    monkeypatch.setenv("PADDLE_TPU_PASSES", "dce")
    assert resolve_pass_names(None) == ("dce",)
    monkeypatch.setenv("PADDLE_TPU_PASSES", "nope")
    with pytest.raises(ValueError, match="nope"):
        resolve_pass_names(None)


def test_build_strategy_knobs_gate_passes():
    bs = fluid.BuildStrategy()
    assert set(resolve_pass_names(bs)) == {
        "dce", "const_fold", "copy_prop", "fuse_optimizer",
        "fuse_conv_bn", "layout_opt",
    }
    bs.fuse_all_optimizer_ops = False
    assert "fuse_optimizer" not in resolve_pass_names(bs)
    bs.memory_optimize = False
    assert "dce" not in resolve_pass_names(bs)
    bs.enable_inplace = False
    assert "copy_prop" not in resolve_pass_names(bs)
    bs.fuse_conv_bn = False
    assert "fuse_conv_bn" not in resolve_pass_names(bs)
    bs.enable_layout_opt = False
    assert "layout_opt" not in resolve_pass_names(bs)
    bs.constant_folding = False
    assert resolve_pass_names(bs) == ()


def test_original_program_is_not_mutated():
    x = fluid.layers.data("x", [4])
    h = fluid.layers.fc(x, 8)
    fluid.layers.fc(h, 3)  # dead head
    loss = fluid.layers.mean(h)
    prog = fluid.default_main_program()
    n_before = len(prog.global_block().ops)
    p2, b2, stats = apply_program_passes(prog, ("x",), (loss.name,))
    assert len(prog.global_block().ops) == n_before
    assert p2 is not prog
    assert stats["ops_after"] < stats["ops_before"]


# ------------------------------------------------------------------ DCE


def test_dce_removes_dead_ops_keeps_fetched():
    x = fluid.layers.data("x", [4])
    h = fluid.layers.fc(x, 8)
    dead = fluid.layers.fc(h, 3)  # never fetched, feeds nothing live
    loss = fluid.layers.mean(h)
    prog = fluid.default_main_program()
    _, b2, stats = apply_program_passes(prog, ("x",), (loss.name,))
    assert stats["passes"]["dce"] >= 2  # dead fc = mul + elementwise_add
    live = {n for op in b2.ops for n in op.output_arg_names()}
    assert dead.name not in live
    # the fetched intermediate survives when IT is the fetch target
    _, b3, _ = apply_program_passes(prog, ("x",), (dead.name,))
    live3 = {n for op in b3.ops for n in op.output_arg_names()}
    assert dead.name in live3


def test_dce_keeps_persistable_writes():
    x = fluid.layers.data("x", [4])
    h = fluid.layers.fc(x, 8)
    loss = fluid.layers.mean(h)
    block = fluid.default_main_program().global_block()
    shadow = block.create_var(
        name="shadow_stat", shape=[8], dtype="float32", persistable=True
    )
    # writes a persistable, output reaches no fetch: must survive
    block.append_op(
        "reduce_mean", {"X": [h.name]}, {"Out": [shadow.name]},
        {"dim": [0], "keep_dim": False},
    )
    prog = fluid.default_main_program()
    _, b2, _ = apply_program_passes(prog, ("x",), (loss.name,))
    assert any(
        "shadow_stat" in op.output_arg_names() for op in b2.ops
    )
    # and executing actually lands the value in the scope
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xv = np.random.RandomState(0).randn(2, 4).astype("float32")
    exe.run(feed={"x": xv}, fetch_list=[loss])
    assert np.asarray(fluid.global_scope().get("shadow_stat")).shape == (8,)


def test_dce_keeps_order_rng_ops_and_collectives():
    x = fluid.layers.data("x", [4])
    h = fluid.layers.fc(x, 8)
    loss = fluid.layers.mean(h)
    block = fluid.default_main_program().global_block()
    noise = block.create_var(name="dead_noise", shape=[2, 2],
                             dtype="float32")
    block.append_op(
        "uniform_random", {}, {"Out": [noise.name]},
        {"shape": [2, 2], "min": -1.0, "max": 1.0, "dtype": "float32"},
    )
    cred = block.create_var(name="dead_coll", shape=[2, 2],
                            dtype="float32")
    block.append_op(
        "c_allreduce_sum", {"X": [noise.name]}, {"Out": [cred.name]}, {}
    )
    prog = fluid.default_main_program()
    _, b2, _ = apply_program_passes(prog, ("x",), (loss.name,))
    types = _op_types(b2)
    assert "uniform_random" in types  # next_rng consumer anchors
    assert "c_allreduce_sum" in types  # collectives stay symmetric


def test_dropout_not_anchored():
    # dropout draws from the name-keyed rng_for stream: a DEAD dropout is
    # safe to eliminate (and must be, or dead towers would keep tracing)
    x = fluid.layers.data("x", [4])
    h = fluid.layers.fc(x, 8)
    fluid.layers.dropout(h, dropout_prob=0.5)  # dead
    loss = fluid.layers.mean(h)
    prog = fluid.default_main_program()
    _, b2, _ = apply_program_passes(prog, ("x",), (loss.name,))
    assert "dropout" not in _op_types(b2)


# ----------------------------------------------------- copy propagation


def test_copy_prop_drops_grad_accumulation_assigns():
    x = fluid.layers.data("x", [8])
    label = fluid.layers.data("y", [1])
    pred = fluid.layers.fc(x, 1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, label))
    fluid.optimizer.SGD(0.1).minimize(loss)
    prog = fluid.default_main_program()
    n_assigns = sum(
        1 for op in prog.global_block().ops if op.type == "assign"
    )
    assert n_assigns >= 2  # per-param single-partial grads
    _, b2, stats = apply_program_passes(prog, ("x", "y"), (loss.name,))
    assert stats["passes"]["copy_prop"] >= n_assigns - 1
    # grads keep their @GRAD names: the fused op reads w@GRAD, not
    # the @PARTIAL name (microbatch averaging keys on the suffix)
    from paddle_tpu.framework import GRAD_SUFFIX

    fused = [op for op in b2.ops if op.type == "fused_sgd"]
    assert fused and all(
        g.endswith(GRAD_SUFFIX) for g in fused[0].input("Grad")
    )


def test_copy_prop_keeps_fetched_source_binding():
    x = fluid.layers.data("x", [4])
    h = fluid.layers.fc(x, 4)
    block = fluid.default_main_program().global_block()
    alias = block.create_var(name="alias_out", shape=[4], dtype="float32")
    block.append_op("assign", {"X": [h.name]}, {"Out": [alias.name]}, {})
    prog = fluid.default_main_program()
    # fetching BOTH names: the rename would erase h's binding — kept
    _, b2, _ = apply_program_passes(
        prog, ("x",), (h.name, alias.name)
    )
    assert "assign" in _op_types(b2)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xv = np.random.RandomState(0).randn(2, 4).astype("float32")
    a, b = exe.run(feed={"x": xv}, fetch_list=[h, alias])
    np.testing.assert_allclose(a, b, rtol=0)


# ------------------------------------------------------- const folding


def test_const_fold_collapses_chain():
    with program_guard(Program(), Program()):
        x = fluid.layers.data("x", [4])
        c = fluid.layers.fill_constant([4], "float32", 3.0)
        s = fluid.layers.scale(c, scale=2.0, bias=1.0)
        cc = fluid.layers.cast(s, "int32")
        out = x + fluid.layers.cast(cc, "float32")
        prog = fluid.default_main_program()
        _, b2, stats = apply_program_passes(prog, ("x",), (out.name,))
        types = _op_types(b2)
        assert "fill_constant" not in types
        assert "scale" not in types
        assert types.count("assign_value") == 1  # one materialized const
        assert stats["passes"]["const_fold"] >= 3

        exe = fluid.Executor(fluid.CPUPlace())
        xv = np.zeros((2, 4), "float32")
        (ov,) = exe.run(feed={"x": xv}, fetch_list=[out])
        np.testing.assert_allclose(ov, np.full((2, 4), 7.0), rtol=0)


def test_const_fold_skips_persistable_writes_and_feeds():
    x = fluid.layers.data("x", [4])
    block = fluid.default_main_program().global_block()
    pv = block.create_var(name="pconst", shape=[4], dtype="float32",
                          persistable=True)
    block.append_op(
        "fill_constant", {}, {"Out": [pv.name]},
        {"shape": [4], "value": 5.0, "dtype": "float32"},
    )
    out = x + pv
    prog = fluid.default_main_program()
    _, b2, _ = apply_program_passes(prog, ("x",), (out.name,))
    assert "fill_constant" in _op_types(b2)  # persistable write kept as-is


# -------------------------------------------------- optimizer fusion


def _mlp_with_opt(opt):
    x = fluid.layers.data("x", [8])
    label = fluid.layers.data("y", [1])
    h = fluid.layers.fc(x, 16, act="relu")
    pred = fluid.layers.fc(h, 1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, label))
    opt.minimize(loss)
    return loss


@pytest.mark.parametrize(
    "mk_opt,base_type",
    [
        (lambda: fluid.optimizer.SGD(0.05), "sgd"),
        (lambda: fluid.optimizer.Momentum(0.05, 0.9), "momentum"),
        (lambda: fluid.optimizer.Adam(0.01), "adam"),
        (lambda: fluid.optimizer.Lamb(0.01), "lamb"),
    ],
)
def test_fused_optimizer_matches_unfused(mk_opt, base_type):
    import paddle_tpu.framework as framework
    import paddle_tpu.scope as scope_mod

    results = {}
    for mode in ("none", "all"):
        framework.switch_main_program(framework.Program())
        framework.switch_startup_program(framework.Program())
        framework.unique_name.switch()
        scope_mod._scope_stack[:] = [scope_mod.Scope()]
        fluid.default_startup_program().random_seed = 11
        os.environ["PADDLE_TPU_PASSES"] = mode
        try:
            loss = _mlp_with_opt(mk_opt())
            prog = fluid.default_main_program()
            if mode == "all":
                _, b2, stats = apply_program_passes(
                    prog, ("x", "y"), (loss.name,)
                )
                types = _op_types(b2)
                assert f"fused_{base_type}" in types
                assert base_type not in types
                assert stats["passes"]["fuse_optimizer"] >= 3  # 4 params -> 1
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            rng = np.random.RandomState(3)
            xv = rng.randn(16, 8).astype("float32")
            yv = rng.randn(16, 1).astype("float32")
            out = []
            for _ in range(5):
                (lv,) = exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss])
                out.append(float(np.asarray(lv).reshape(-1)[0]))
            results[mode] = out
        finally:
            os.environ.pop("PADDLE_TPU_PASSES", None)
    np.testing.assert_allclose(results["none"], results["all"],
                               rtol=1e-6, atol=1e-7)


def test_fusion_skips_duplicate_params():
    # one param updated twice in a run: a double write is NOT commutative
    with program_guard(Program(), Program()):
        x = fluid.layers.data("x", [4])
        h = fluid.layers.fc(x, 4, bias_attr=False)
        loss = fluid.layers.mean(h)
        pg = fluid.backward.append_backward(loss)
        block = fluid.default_main_program().global_block()
        lr = fluid.layers.fill_constant([1], "float32", 0.1)
        p, g = pg[0]
        for _ in range(2):
            block.append_op(
                "sgd",
                {"Param": [p.name], "Grad": [g.name],
                 "LearningRate": [lr.name]},
                {"ParamOut": [p.name]},
                {"op_role": 2},
            )
        prog = fluid.default_main_program()
        _, b2, _ = apply_program_passes(prog, ("x",), (loss.name,))
        assert "fused_sgd" not in _op_types(b2)


# ----------------------------------------------- end-to-end equivalence


# ~42 s (two full transformer train-step compiles) — slow-marked for
# tier-1 headroom (round 11); covered by the tools/ci.sh slow-model
# stage, and the pass set stays guarded in tier-1 by the unit passes
# above + the bench_passes --guard ci stage
@pytest.mark.slow
def test_transformer_train_step_equivalence():
    """Acceptance criterion: pass-enabled vs pass-disabled fetches agree
    numerically on a transformer train step (dropout + adam + masks)."""
    import paddle_tpu.framework as framework
    import paddle_tpu.scope as scope_mod
    from paddle_tpu.models.transformer import (
        TransformerConfig,
        build_transformer,
    )

    b, s = 2, 8
    cfg_kw = dict(
        src_vocab=64, trg_vocab=64, d_model=16, n_heads=2, d_ff=32,
        n_layers=2, max_len=16, dropout=0.1,
    )
    rng = np.random.RandomState(0)
    pos = np.tile(np.arange(s), (b, 1)).astype("int64")
    feed_base = {
        "src_ids": rng.randint(1, 64, (b, s)).astype("int64"),
        "trg_ids": rng.randint(1, 64, (b, s)).astype("int64"),
        "lbl_ids": rng.randint(1, 64, (b, s)).astype("int64"),
        "src_mask": np.ones((b, s), "float32"),
        "trg_mask": np.ones((b, s), "float32"),
    }

    losses = {}
    for mode in ("none", "all"):
        framework.switch_main_program(framework.Program())
        framework.switch_startup_program(framework.Program())
        framework.unique_name.switch()
        scope_mod._scope_stack[:] = [scope_mod.Scope()]
        fluid.default_main_program().random_seed = 5
        fluid.default_startup_program().random_seed = 5
        os.environ["PADDLE_TPU_PASSES"] = mode
        try:
            handles = build_transformer(TransformerConfig(**cfg_kw), b, s, s)
            fluid.optimizer.Adam(1e-3).minimize(handles["loss"])
            feed = dict(feed_base)
            feed[handles["src_pos_name"]] = pos
            feed[handles["trg_pos_name"]] = pos
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            out = []
            for _ in range(3):
                (lv,) = exe.run(feed=feed, fetch_list=[handles["loss"]])
                out.append(float(np.asarray(lv).reshape(-1)[0]))
            losses[mode] = out
        finally:
            os.environ.pop("PADDLE_TPU_PASSES", None)
    np.testing.assert_allclose(losses["none"], losses["all"],
                               rtol=1e-6, atol=1e-7)


def test_pass_env_change_recompiles():
    # same executor, env flipped between runs: the cache key carries the
    # resolved pass set, so the second run must not serve the first step
    x = fluid.layers.data("x", [4])
    h = fluid.layers.fc(x, 8)
    loss = fluid.layers.mean(h)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xv = np.random.RandomState(0).randn(2, 4).astype("float32")
    os.environ["PADDLE_TPU_PASSES"] = "none"
    try:
        (a,) = exe.run(feed={"x": xv}, fetch_list=[loss])
        n_cached = len(exe._cache)
        os.environ["PADDLE_TPU_PASSES"] = "all"
        (bv,) = exe.run(feed={"x": xv}, fetch_list=[loss])
        assert len(exe._cache) == n_cached + 1
        np.testing.assert_allclose(a, bv, rtol=0)
    finally:
        os.environ.pop("PADDLE_TPU_PASSES", None)


def test_profiler_counters_present():
    from paddle_tpu import profiler

    profiler.reset_profiler()
    x = fluid.layers.data("x", [4])
    h = fluid.layers.fc(x, 8)
    loss = fluid.layers.mean(h)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xv = np.zeros((2, 4), "float32")
    exe.run(feed={"x": xv}, fetch_list=[loss])
    c = profiler.counters()
    assert c.get("program_compile_count", 0) >= 2  # startup + main
    assert c.get("program_traced_ops", 0) > 0
    assert "program_trace_ms" not in c  # PR 49: by stage and by owner
    # the startup program, and a main program with no backward: forward
    assert c["program_first_call_us.forward"] >= c["compile_trace_us.forward"]
    assert "program_first_call_us.train" not in c
    assert "pass_manager_us" in c
    assert c.get("program_ops_before", 0) >= c.get("program_ops_after", 0)


# --------------------------------------------------- layout_opt (round 12)


def _resnet_block(train=True, seed=7):
    """Mini ResNet block: s2d-shaped stem conv + residual + both pool
    kinds + fc head — the op mix layout_opt targets, small enough to
    compile in seconds."""
    fluid.default_main_program().random_seed = seed
    fluid.default_startup_program().random_seed = seed
    img = fluid.layers.data("img", [2, 3, 16, 16], append_batch_size=False)
    label = fluid.layers.data("label", [2, 1], dtype="int64",
                              append_batch_size=False)

    def conv_bn(x, c, k, s=1, act=None, name=None):
        conv = fluid.layers.conv2d(
            x, num_filters=c, filter_size=k, stride=s,
            padding=(k - 1) // 2, bias_attr=False, name=name)
        return fluid.layers.batch_norm(conv, act=act,
                                       name=(name or "") + "_bn")

    x = conv_bn(img, 8, 7, s=2, act="relu", name="c1")
    y = conv_bn(x, 8, 3, name="c2")
    x = fluid.layers.elementwise_add(x, y, act="relu")
    x = fluid.layers.pool2d(x, pool_size=2, pool_type="max", pool_stride=2)
    pool = fluid.layers.pool2d(x, pool_type="avg", global_pooling=True)
    pred = fluid.layers.fc(pool, 10, act="softmax")
    loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
    if train:
        fluid.optimizer.Momentum(0.05, 0.9).minimize(loss)
    return pred, loss


def _run_block_steps(passes, train=True, steps=3, fetch_pred=True):
    import paddle_tpu.framework as framework
    import paddle_tpu.scope as scope_mod

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    framework.unique_name.switch()
    scope_mod._scope_stack[:] = [scope_mod.Scope()]
    os.environ["PADDLE_TPU_PASSES"] = passes
    try:
        pred, loss = _resnet_block(train=train)
        prog = fluid.default_main_program()
        if not train:
            prog = prog.clone(for_test=True)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        rng = np.random.RandomState(0)
        feed = {"img": rng.rand(2, 3, 16, 16).astype("float32"),
                "label": rng.randint(0, 10, (2, 1)).astype("int64")}
        fetches = [loss, pred] if fetch_pred else [loss]
        out = []
        for _ in range(steps if train else 1):
            vals = exe.run(prog, feed=feed, fetch_list=fetches)
            out.append([np.asarray(v).copy() for v in vals])
        return out
    finally:
        os.environ.pop("PADDLE_TPU_PASSES", None)


def test_layout_opt_resnet_train_bitwise():
    # transposes are exact data movement and every converted lowering
    # canonicalizes channel-last before its arithmetic, so the converted
    # program computes the IDENTICAL float graph: fetches must be
    # BITWISE equal across 3 train steps (stats updates included)
    off = _run_block_steps("none", train=True)
    on = _run_block_steps("all", train=True)
    for step_off, step_on in zip(off, on):
        for a, b in zip(step_off, step_on):
            assert np.array_equal(a, b), "layout_opt broke train bitwise"


def test_layout_opt_resnet_eval_bitwise():
    # eval clone, fuse_conv_bn excluded (it reassociates the BN affine
    # into the weights — tolerance-tested separately): layout alone must
    # be bitwise
    off = _run_block_steps("none", train=False)
    on = _run_block_steps("const_fold,copy_prop,dce,layout_opt",
                          train=False)
    for a, b in zip(off[0], on[0]):
        assert np.array_equal(a, b), "layout_opt broke eval bitwise"


def test_layout_opt_stats_and_counters():
    from paddle_tpu import profiler
    from paddle_tpu.passes import apply_program_passes

    _resnet_block(train=True)
    prog = fluid.default_main_program()
    profiler.reset_profiler()
    p2, b2, stats = apply_program_passes(
        prog, ("img", "label"),
        (prog.global_block().ops[-1].output("ParamOut")[0]
         if prog.global_block().ops[-1].output("ParamOut") else "loss",))
    lo = p2._layout_opt_stats
    frac = (lo["removed"] - lo["inserted"]) / max(
        lo["removed"] + lo["remaining"], 1)
    assert frac >= 0.8, lo  # the ISSUE-9 acceptance floor
    assert lo["converted_ops"] > 0
    c = profiler.counters()
    assert c["transpose_ops_before"] > c["transpose_ops_after"]
    # every conv/pool/bn in the rewritten block runs NHWC
    for op in b2.ops:
        if op.type in ("conv2d", "depthwise_conv2d", "pool2d"):
            assert op.attr("data_format") == "NHWC", op
        if op.type == "batch_norm":
            assert op.attr("data_layout") == "NHWC", op


def test_layout_opt_keeps_fetched_intermediate_nchw():
    # a fetched conv activation is user-visible: its value must arrive
    # in the authored NCHW layout (and stay bitwise) even though the
    # producing conv converts
    import paddle_tpu.scope as scope_mod

    img = fluid.layers.data("img", [2, 3, 8, 8], append_batch_size=False)
    conv = fluid.layers.conv2d(img, 4, 3, padding=1, bias_attr=False)
    out = fluid.layers.relu(conv)
    loss = fluid.layers.mean(out)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {"img": np.random.RandomState(0).rand(2, 3, 8, 8)
            .astype("float32")}
    os.environ["PADDLE_TPU_PASSES"] = "none"
    try:
        a = exe.run(feed=feed, fetch_list=[conv, loss])
        os.environ["PADDLE_TPU_PASSES"] = "layout_opt"
        b = exe.run(feed=feed, fetch_list=[conv, loss])
    finally:
        os.environ.pop("PADDLE_TPU_PASSES", None)
    assert np.asarray(a[0]).shape == (2, 4, 8, 8)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------- fuse_conv_bn (round 12)


def test_fuse_conv_bn_inference_within_tolerance():
    off = _run_block_steps("none", train=False)
    on = _run_block_steps("all", train=False)
    for a, b in zip(off[0], on[0]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_fuse_conv_bn_rewrites_the_graph():
    import paddle_tpu.scope as scope_mod
    from paddle_tpu.passes import apply_program_passes

    _resnet_block(train=False)
    prog = fluid.default_main_program().clone(for_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = scope_mod.global_scope()
    pred_name = [op for op in prog.global_block().ops
                 if op.type == "softmax"][-1].output("Out")[0]
    os.environ["PADDLE_TPU_PASSES"] = "fuse_conv_bn"
    try:
        p2, b2, stats = apply_program_passes(
            prog, ("img",), (pred_name,), scope=scope)
    finally:
        os.environ.pop("PADDLE_TPU_PASSES", None)
    assert stats["passes"]["fuse_conv_bn"] > 0
    assert not any(op.type == "batch_norm" for op in b2.ops)
    convs = [op for op in b2.ops if op.type == "conv2d"]
    assert all(op.input("Bias") for op in convs)
    # the relu-activated conv absorbed its relu
    assert any(op.attr("fused_act") == "relu" for op in convs)
    # folded weights live in the scope under derived persistable names
    wf = convs[0].input("Filter")[0]
    assert wf.endswith("@bnfold.w") and scope.has(wf)


def test_fuse_conv_bn_never_fires_on_training():
    import paddle_tpu.scope as scope_mod
    from paddle_tpu.passes import apply_program_passes

    _, loss = _resnet_block(train=True)
    prog = fluid.default_main_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    os.environ["PADDLE_TPU_PASSES"] = "fuse_conv_bn"
    try:
        p2, b2, stats = apply_program_passes(
            prog, ("img", "label"), (loss.name,),
            scope=scope_mod.global_scope())
    finally:
        os.environ.pop("PADDLE_TPU_PASSES", None)
    assert stats["passes"]["fuse_conv_bn"] == 0
    assert any(op.type == "batch_norm" for op in b2.ops)


# ------------------------------------------ what resolves by default


DEFAULT_PASSES = ("const_fold", "copy_prop", "dce", "fuse_conv_bn",
                  "layout_opt", "fuse_optimizer")


def test_the_default_pass_list_is_the_documented_one(monkeypatch):
    """With no variable and no knob turned: the six passes, in the order
    they run, whether or not a BuildStrategy is given; and the package's
    docstring has a bullet for every registered pass and no other."""
    import re

    import paddle_tpu.passes as passes_mod

    monkeypatch.delenv("PADDLE_TPU_AUTOSHARD", raising=False)
    assert resolve_pass_names(None) == DEFAULT_PASSES
    assert resolve_pass_names(fluid.BuildStrategy()) == DEFAULT_PASSES
    documented = re.findall(r"^  \* (\w+) +—", passes_mod.__doc__, re.M)
    assert documented == list(DEFAULT_PASSES) + ["shard_propagation"]
    assert documented == list(PASS_REGISTRY)


def _fc_stack_train_step(width=16):
    """Eight fc+relu layers under Adam, in fresh default Programs:
    (program, feed names, fetch names)."""
    import paddle_tpu.framework as framework

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    framework.unique_name.switch()
    h = x = fluid.layers.data("x", [width])
    for _ in range(8):
        h = fluid.layers.fc(h, width, act="relu")
    loss = fluid.layers.mean(h)
    fluid.optimizer.Adam(1e-3).minimize(loss)
    return fluid.default_main_program(), ("x",), (loss.name,)


def _rewritten(build_strategy):
    _, block, stats = apply_program_passes(
        *_fc_stack_train_step(), build_strategy=build_strategy)
    return [(op.type, sorted(op.attrs.items(), key=repr),
             op.input_arg_names(), op.output_arg_names())
            for op in block.ops], stats


@pytest.mark.parametrize("removed", ["fuse_layer_scan", "optimizer_overlap"])
@pytest.mark.parametrize("left_in", ["a BuildStrategy attribute",
                                     "the environment"])
def test_a_switch_of_a_removed_pass_changes_nothing(removed, left_in,
                                                    monkeypatch):
    """PR 74 took out two opt-in passes with a strategy attribute and a
    variable each. A script or a shell that still sets one gets the
    default passes, the same rewritten Program, and no error."""
    want, want_stats = _rewritten(fluid.BuildStrategy())
    bs = fluid.BuildStrategy()
    if left_in == "the environment":
        monkeypatch.setenv("PADDLE_TPU_" + removed.upper(), "1")
    else:
        setattr(bs, removed, True)
    assert resolve_pass_names(bs) == DEFAULT_PASSES
    assert removed not in PASS_REGISTRY
    got, stats = _rewritten(bs)
    assert got == want and stats == want_stats


def test_the_default_passes_shed_a_third_of_a_bert_layers_train_ops():
    """One BERT-base encoder layer with its masked-LM head under Adam,
    nothing run: constant folding, copy propagation, dead-op elimination
    and the optimizer's fusion together remove at least 30% of the ops
    (47.7% at PR 74: 199 to 104), and the fusion some of them."""
    from paddle_tpu.models.bert import BertConfig, build_bert_pretrain

    cfg = BertConfig.base()
    cfg.num_layers = 1
    handles = build_bert_pretrain(cfg, 2, 16, mlm_only=True, max_preds=4)
    fluid.optimizer.Adam(1e-4).minimize(handles["loss"])
    prog = fluid.default_main_program()
    feeds = tuple(
        n for n in ("src_ids", "pos_ids", "sent_ids", "input_mask",
                    "mask_pos", "mask_label", "mask_weight")
        if prog.global_block().has_var(n))
    _, _, stats = apply_program_passes(prog, feeds, (handles["loss"].name,))
    assert stats["ops_after"] <= 0.70 * stats["ops_before"], stats
    assert stats["passes"]["fuse_optimizer"] > 0
