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


# ---------------------------------------- pass-set signature (round 12)


def test_cache_signature_names_passes_and_versions(monkeypatch):
    from paddle_tpu.passes import _OPT_IN_GATES, PASS_REGISTRY, cache_signature

    monkeypatch.delenv("PADDLE_TPU_PASSES", raising=False)
    monkeypatch.delenv("PADDLE_TPU_AUTOSHARD", raising=False)
    sig = cache_signature()
    for name in PASS_REGISTRY:
        if _OPT_IN_GATES.get(name) is not None:
            # opt-in (rounds 16/20): absent from the signature until
            # enabled, so the flip itself recompiles
            assert f"{name}:" not in sig
            continue
        assert f"{name}:{PASS_REGISTRY[name][2]}" in sig
    monkeypatch.setenv("PADDLE_TPU_AUTOSHARD", "1")
    assert "shard_propagation:" in cache_signature()
    monkeypatch.delenv("PADDLE_TPU_AUTOSHARD", raising=False)
    monkeypatch.setenv("PADDLE_TPU_PASSES", "none")
    assert cache_signature() == "nopass"
    monkeypatch.setenv("PADDLE_TPU_PASSES", "dce")
    assert cache_signature() == f"dce:{PASS_REGISTRY['dce'][2]}"


# ------------------------------------- fused train-step compilation
# (round 20: layer-stacked scan + optimizer-overlapped backward)


def _reset_graph_state(seed=5):
    """Fresh default programs/scope/unique-name stream so two build modes
    of the same model get identical variable names and initial params."""
    import paddle_tpu.framework as framework
    import paddle_tpu.scope as scope_mod

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    framework.unique_name.switch()
    scope_mod._scope_stack[:] = [scope_mod.Scope()]
    fluid.default_main_program().random_seed = seed
    fluid.default_startup_program().random_seed = seed


def _build_fc_stack(n_layers=4, width=16):
    """n_layers structurally-identical blocks (two fc+relu each: 6 ops,
    above the fuse_layer_scan minimum segment size) — the smallest IR
    with a fusable run."""
    x = fluid.layers.data("x", [width])
    h = x
    for _ in range(n_layers):
        h = fluid.layers.fc(h, width, act="relu")
        h = fluid.layers.fc(h, width, act="relu")
    return x, h


def test_opt_in_passes_gated_and_signed(monkeypatch):
    # absent from the default resolution AND the cache signature until
    # explicitly enabled — existing users' compile caches stay warm
    from paddle_tpu.passes import cache_signature

    assert "fuse_layer_scan" in PASS_REGISTRY
    assert "optimizer_overlap" in PASS_REGISTRY
    bs = fluid.BuildStrategy()
    base_names = resolve_pass_names(bs)
    base_sig = cache_signature(bs)
    assert "fuse_layer_scan" not in base_names
    assert "optimizer_overlap" not in base_names

    bs.fuse_layer_scan = True
    bs.optimizer_overlap = True
    names = resolve_pass_names(bs)
    assert "fuse_layer_scan" in names and "optimizer_overlap" in names
    assert cache_signature(bs) != base_sig
    # ordering: scan before fuse_optimizer (backward scanning must see
    # raw per-param grad producers), overlap after fuse_optimizer (it
    # splits the fused waves)
    assert names.index("fuse_layer_scan") < names.index("fuse_optimizer")
    assert names.index("fuse_optimizer") < names.index("optimizer_overlap")

    # env spelling, no strategy object (executor cache-key path)
    env_base = cache_signature(None)
    monkeypatch.setenv("PADDLE_TPU_FUSE_LAYER_SCAN", "1")
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZER_OVERLAP", "1")
    assert {"fuse_layer_scan", "optimizer_overlap"} <= set(
        resolve_pass_names(None)
    )
    assert cache_signature(None) != env_base


def test_fuse_layer_scan_stacks_fc_run_bitwise(monkeypatch):
    from paddle_tpu import profiler
    from paddle_tpu.passes import apply_program_passes

    outs = {}
    counts = {}
    for mode in ("off", "on"):
        _reset_graph_state()
        if mode == "on":
            monkeypatch.setenv("PADDLE_TPU_FUSE_LAYER_SCAN", "1")
        else:
            monkeypatch.delenv("PADDLE_TPU_FUSE_LAYER_SCAN", raising=False)
        x, h = _build_fc_stack(n_layers=4)
        prog = fluid.default_main_program()
        before = profiler.counters().get("scan_fused_layers", 0)
        _, blk, _ = apply_program_passes(prog, ("x",), (h.name,))
        counts[mode] = len(blk.ops)
        types = [op.type for op in blk.ops]
        if mode == "on":
            assert "layer_scan" in types
            assert profiler.counters().get("scan_fused_layers", 0) >= before + 4
        else:
            assert "layer_scan" not in types
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        xv = np.random.RandomState(3).randn(2, 16).astype("float32")
        (out,) = exe.run(feed={"x": xv}, fetch_list=[h])
        outs[mode] = np.asarray(out).copy()
    assert counts["on"] < counts["off"]
    # bitwise, not allclose: the scan body re-lowers the template ops
    # verbatim, so on/off must agree to the last bit
    assert np.array_equal(outs["off"], outs["on"])


def test_optimizer_overlap_groups_before_last_grad_and_bitwise(monkeypatch):
    from paddle_tpu import profiler
    from paddle_tpu.framework import core_op_role
    from paddle_tpu.passes import apply_program_passes

    losses = {}
    for mode in ("off", "on"):
        _reset_graph_state()
        if mode == "on":
            monkeypatch.setenv("PADDLE_TPU_OPTIMIZER_OVERLAP", "1")
        else:
            monkeypatch.delenv("PADDLE_TPU_OPTIMIZER_OVERLAP", raising=False)
        x, h = _build_fc_stack(n_layers=4)
        loss = fluid.layers.mean(h)
        fluid.optimizer.Adam(1e-3).minimize(loss)
        prog = fluid.default_main_program()
        before = profiler.counters().get("optimizer_overlap_groups", 0)
        _, blk, _ = apply_program_passes(prog, ("x",), (loss.name,))
        n_waves = sum(1 for op in blk.ops if op.type == "fused_adam")
        if mode == "on":
            # acceptance pin (static, from op order): at least two update
            # groups land BEFORE the final grad producer — the overlap
            # the single trailing wave could never give XLA
            last_bwd = max(
                i for i, op in enumerate(blk.ops)
                if op.attr("op_role", 0) & core_op_role.Backward
                and op.type != "fused_adam"
            )
            early = sum(
                1 for i, op in enumerate(blk.ops)
                if op.type == "fused_adam" and i < last_bwd
            )
            assert n_waves >= 2
            assert early >= 2
            assert (
                profiler.counters().get("optimizer_overlap_groups", 0)
                >= before + 2
            )
        else:
            assert n_waves == 1
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        xv = np.random.RandomState(3).randn(2, 16).astype("float32")
        out = []
        for _ in range(3):
            (lv,) = exe.run(feed={"x": xv}, fetch_list=[loss])
            out.append(np.asarray(lv).copy())
        losses[mode] = out
    for a, b in zip(losses["off"], losses["on"]):
        assert np.array_equal(a, b)


# ~70 s (two full 4-layer transformer train compiles) — slow-marked for
# tier-1 headroom like the 2-layer equivalence gate above; runs in the
# tools/ci.sh slow lane and is ALSO the tools/bench_passes.py --guard pin.
@pytest.mark.slow
def test_fused_step_transformer_acceptance(monkeypatch):
    """Round-20 acceptance: on the 4-layer transformer train step,
    scan+overlap cut the traced op count >=40% and the CPU compile wall
    >=1.25x while every fetched loss stays BITWISE equal over 3 Adam
    steps."""
    import time as _time

    from paddle_tpu.models.transformer import (
        TransformerConfig,
        build_transformer,
    )
    from paddle_tpu.passes import apply_program_passes

    b, s = 2, 8
    cfg_kw = dict(
        src_vocab=64, trg_vocab=64, d_model=16, n_heads=2, d_ff=32,
        n_layers=4, max_len=16, dropout=0.1,
    )
    rng_np = np.random.RandomState(0)
    pos = np.tile(np.arange(s), (b, 1)).astype("int64")
    feed_base = {
        "src_ids": rng_np.randint(1, 64, (b, s)).astype("int64"),
        "trg_ids": rng_np.randint(1, 64, (b, s)).astype("int64"),
        "lbl_ids": rng_np.randint(1, 64, (b, s)).astype("int64"),
        "src_mask": np.ones((b, s), "float32"),
        "trg_mask": np.ones((b, s), "float32"),
    }

    losses, op_counts, walls = {}, {}, {}
    for mode in ("off", "on"):
        _reset_graph_state()
        if mode == "on":
            monkeypatch.setenv("PADDLE_TPU_FUSE_LAYER_SCAN", "1")
            monkeypatch.setenv("PADDLE_TPU_OPTIMIZER_OVERLAP", "1")
        else:
            monkeypatch.delenv("PADDLE_TPU_FUSE_LAYER_SCAN", raising=False)
            monkeypatch.delenv("PADDLE_TPU_OPTIMIZER_OVERLAP", raising=False)
        handles = build_transformer(TransformerConfig(**cfg_kw), b, s, s)
        fluid.optimizer.Adam(1e-3).minimize(handles["loss"])
        feed = dict(feed_base)
        feed[handles["src_pos_name"]] = pos
        feed[handles["trg_pos_name"]] = pos
        prog = fluid.default_main_program()
        _, blk, _ = apply_program_passes(
            prog, tuple(feed.keys()), (handles["loss"].name,)
        )
        op_counts[mode] = len(blk.ops)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        t0 = _time.time()
        out = []
        for i in range(3):
            (lv,) = exe.run(feed=feed, fetch_list=[handles["loss"]])
            if i == 0:
                walls[mode] = _time.time() - t0  # trace+lower+compile
            out.append(np.asarray(lv).copy())
        losses[mode] = out

    reduction = 1.0 - op_counts["on"] / op_counts["off"]
    assert reduction >= 0.40, (op_counts, reduction)
    speedup = walls["off"] / walls["on"]
    assert speedup >= 1.25, (walls, speedup)
    for a, b_ in zip(losses["off"], losses["on"]):
        assert np.array_equal(a, b_), (losses["off"], losses["on"])
