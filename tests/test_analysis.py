"""Static analysis layer (round 15): IR verifier mutation tests,
static-vs-traced bitwise shape/dtype inference, sharding checker,
pass-manager verification hook.

The mutation tests corrupt CLONES of a known-good program one invariant
at a time and assert the verifier reports the precise op/var with a
readable message; the traced tests prove the static inference
reproduces jax.eval_shape of the lowered block bitwise for the four
bench workloads (tools/verify_bench_programs.py shares the builders, so
the ci.sh lane and tier-1 pin the same contract)."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import analysis, framework, layers  # noqa: E402
from paddle_tpu.analysis import VarMeta  # noqa: E402
from tools.verify_bench_programs import (  # noqa: E402
    build_bench_program,
    compare_static_vs_traced,
)


def _tiny_train_program():
    """fc -> relu -> fc -> mse -> SGD: every verifier surface (feeds,
    params, backward, optimizer) in ~30 ops."""
    main = framework.Program()
    startup = framework.Program()
    with framework.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.data("y", shape=[1], dtype="float32")
        h = layers.fc(x, size=8, act="relu")
        pred = layers.fc(h, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main


def _findings_with(findings, code):
    return [f for f in findings if f.code == code]


# ---------------------------------------------------------------------------
# clean programs
# ---------------------------------------------------------------------------


def test_clean_tiny_program_zero_findings():
    prog = _tiny_train_program()
    assert analysis.verify_program(prog) == []


def test_clean_bench_program_zero_findings():
    # a tier-1-representative full program (BERT tiny train incl.
    # backward + Adam) passes the verifier clean
    prog, feeds = build_bench_program("bert")
    findings = analysis.verify_program(prog, feed_names=tuple(feeds))
    assert findings == []
    result = analysis.infer_program(prog, feeds=feeds)
    assert result.missing == [] and result.errors == []
    assert result.ops_covered == result.ops_total > 0


# ---------------------------------------------------------------------------
# mutation tests: >= 6 distinct corruption classes, op/var-precise
# ---------------------------------------------------------------------------


def test_mutation_dropped_var_declaration():
    prog = _tiny_train_program().clone()
    blk = prog.global_block()
    # drop the first fc weight's declaration; its reader must be named
    victim = next(n for n in blk.vars if n.startswith("fc_0.w"))
    del blk.vars[victim]
    findings = analysis.verify_program(prog)
    hits = [
        f for f in _findings_with(findings, "dangling-input")
        if f.var == victim
    ]
    assert hits, findings
    assert hits[0].op_type == "mul"
    assert "no Variable declaration" in str(hits[0])
    assert victim in str(hits[0])


def test_mutation_retyped_input():
    prog = _tiny_train_program().clone()
    blk = prog.global_block()
    # retype an intermediate: its producer still emits float32
    victim = next(
        op.output("Out")[0] for op in blk.ops if op.type == "relu"
    )
    blk.vars[victim].dtype = "int32"
    findings = analysis.verify_program(prog)
    hits = [
        f for f in _findings_with(findings, "dtype-mismatch")
        if f.var == victim
    ]
    assert hits, findings
    assert hits[0].op_type == "relu"
    assert "float32" in hits[0].message and "int32" in hits[0].message


def test_mutation_orphaned_op_output():
    prog = _tiny_train_program().clone()
    blk = prog.global_block()
    op = next(o for o in blk.ops if o.type == "relu")
    op.outputs["Out"] = ["never_declared_var"]
    findings = analysis.verify_program(prog)
    hits = _findings_with(findings, "dangling-output")
    assert any(f.var == "never_declared_var" and f.op_type == "relu"
               for f in hits), findings


def test_mutation_use_before_def():
    prog = _tiny_train_program().clone()
    blk = prog.global_block()
    # hoist the loss-mean op to the front: it now reads its input
    # before any producer ran
    idx = next(i for i, o in enumerate(blk.ops) if o.type == "mean")
    op = blk.ops.pop(idx)
    blk.ops.insert(0, op)
    findings = analysis.verify_program(prog)
    hits = _findings_with(findings, "use-before-def")
    assert any(f.op_type == "mean" and f.op_idx == 0 for f in hits), findings


def test_mutation_shard_on_nonexistent_mesh_axis():
    prog = _tiny_train_program().clone()
    from jax.sharding import PartitionSpec as P

    w = next(n for n in prog.global_block().vars if n.startswith("fc_0.w"))
    prog._sharding_specs[w] = P("bogus_axis")
    findings = analysis.verify_program(prog)
    hits = _findings_with(findings, "sharding-unknown-axis")
    assert any(f.var == w and "bogus_axis" in f.message for f in hits), (
        findings
    )


def test_mutation_indivisible_sharding():
    prog = _tiny_train_program()
    from jax.sharding import PartitionSpec as P

    # the fc_1 bias (`fc_1.w_1`) has dim0 == 1: not divisible by a
    # 4-wide batch axis
    b = next(n for n in prog.global_block().vars if n.startswith("fc_1.w_1"))
    findings = analysis.check_sharding(
        prog,
        mesh={"batch": 4, "model": 1, "pipe": 1},
        specs={b: P("batch")},
    )
    hits = _findings_with(findings, "sharding-indivisible")
    assert any(f.var == b and "not divisible" in f.message for f in hits), (
        findings
    )
    # degrade semantics are an explicit opt-in, mirroring
    # mesh.sharding_with_degrade
    assert analysis.check_sharding(
        prog, mesh={"batch": 4}, specs={b: P("batch")}, allow_degrade=True,
    ) == []


def test_mutation_conflicting_state_shardings():
    prog = _tiny_train_program()
    from jax.sharding import PartitionSpec as P

    w = next(n for n in prog.global_block().vars if n.startswith("fc_0.w"))
    findings = analysis.check_sharding(
        prog,
        mesh={"batch": 2, "model": 2, "pipe": 1},
        specs={w: P(None, "model")},
        extra_specs={w: P("batch")},
    )
    hits = _findings_with(findings, "sharding-conflict")
    assert any(f.var == w for f in hits), findings
    assert "two different ways" in str(hits[0])


def test_mutation_write_to_feed():
    prog = _tiny_train_program().clone()
    blk = prog.global_block()
    op = next(o for o in blk.ops if o.type == "relu")
    op.outputs["Out"] = ["x"]  # overwrite the feed
    findings = analysis.verify_program(prog, feed_names=("x", "y"))
    hits = _findings_with(findings, "write-to-feed")
    assert any(f.var == "x" and f.op_type == "relu" for f in hits), findings


def test_mutation_corrupt_block_nesting():
    prog = _tiny_train_program().clone()
    sub = prog._create_block()
    sub.parent_idx = sub.idx  # self-parent cycle
    findings = analysis.verify_program(prog)
    assert _findings_with(findings, "bad-nesting"), findings


def test_mutation_shape_drift():
    prog = _tiny_train_program().clone()
    blk = prog.global_block()
    # the optimizer LR fill_constant emits [1]; redeclare the var [3]
    victim = next(
        op.output("Out")[0] for op in blk.ops
        if op.type == "fill_constant" and tuple(op.attr("shape")) == (1,)
    )
    blk.vars[victim].shape = (3,)
    findings = analysis.verify_program(prog)
    hits = [
        f for f in _findings_with(findings, "shape-mismatch")
        if f.var == victim
    ]
    assert hits, findings
    assert "(1,)" in hits[0].message and "(3,)" in hits[0].message


def test_mutation_param_written_by_forward_op():
    prog = _tiny_train_program().clone()
    blk = prog.global_block()
    w = next(n for n in blk.vars if n.startswith("fc_0.w"))
    op = next(o for o in blk.ops if o.type == "relu")
    op.outputs["Out"] = [w]
    findings = analysis.verify_program(prog)
    hits = _findings_with(findings, "param-write-role")
    assert any(f.var == w for f in hits), findings


# ---------------------------------------------------------------------------
# static inference == traced shapes, bitwise, for the bench programs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["bert", "transformer", "resnet", "ctr"])
def test_static_inference_matches_trace_bitwise(name):
    prog, feeds = build_bench_program(name)
    n, mismatches, unknown = compare_static_vs_traced(prog, feeds)
    assert n > 100  # the trace binds every var in the program
    assert mismatches == []
    assert unknown == []


def test_static_inference_without_feed_shapes_keeps_dtypes():
    # no concrete feed signature: batch-dependent shapes are unknown but
    # dtypes and the persistable/optimizer side stay concrete
    prog, feeds = build_bench_program("ctr")
    result = analysis.infer_program(prog)
    assert result.errors == []
    blk = prog.global_block()
    adam = next(op for op in blk.ops if op.type in ("adam", "fused_adam"))
    pname = adam.input("Param")[0]
    meta = result.env[adam.output("ParamOut")[0]]
    assert meta.shape == tuple(blk.var(pname).shape)
    assert meta.dtype == "float32"


def test_infer_reports_missing_ops_and_poisons_downstream():
    prog = _tiny_train_program().clone()
    blk = prog.global_block()
    relu = next(o for o in blk.ops if o.type == "relu")
    relu.type = "totally_unknown_op"
    feeds = {"x": ((4, 4), "float32"), "y": ((4, 1), "float32")}
    result = analysis.infer_program(prog, feeds=feeds)
    assert "totally_unknown_op" in result.missing_types
    out = relu.output("Out")[0]
    assert result.env[out] == VarMeta(None, None)
    assert result.ops_covered < result.ops_total


# ---------------------------------------------------------------------------
# pass-manager hook (PADDLE_TPU_VERIFY)
# ---------------------------------------------------------------------------


def _with_corrupting_pass(breaker):
    """Temporarily register an IR pass that corrupts the program."""
    import contextlib

    from paddle_tpu import passes as passes_mod

    @contextlib.contextmanager
    def guard():
        name = "_test_corruptor"
        passes_mod.PASS_REGISTRY[name] = (breaker, None, None)
        passes_mod._PASS_ORDER.append(name)
        old = os.environ.get("PADDLE_TPU_PASSES")
        os.environ["PADDLE_TPU_PASSES"] = name
        try:
            yield
        finally:
            passes_mod.PASS_REGISTRY.pop(name, None)
            passes_mod._PASS_ORDER.remove(name)
            if old is None:
                os.environ.pop("PADDLE_TPU_PASSES", None)
            else:
                os.environ["PADDLE_TPU_PASSES"] = old

    return guard()


def test_verifier_runs_after_every_pass_and_names_the_culprit():
    from paddle_tpu.analysis import VerifierError
    from paddle_tpu.passes import apply_program_passes

    prog = _tiny_train_program()
    loss_name = next(
        op.output("Out")[0] for op in prog.global_block().ops
        if op.type == "mean"
    )

    def breaker(program, block, feed_names, fetch_names, ctx=None):
        op = next(o for o in block.ops if o.type == "relu")
        op.outputs["Out"] = ["pass_made_this_up"]
        return 0

    with _with_corrupting_pass(breaker):
        with pytest.raises(VerifierError) as ei:
            apply_program_passes(prog, ("x", "y"), (loss_name,))
    msg = str(ei.value)
    assert "after pass '_test_corruptor'" in msg
    assert "pass_made_this_up" in msg
    assert "dangling-output" in msg


def test_verifier_checks_input_program_before_passes():
    from paddle_tpu.analysis import VerifierError
    from paddle_tpu.passes import apply_program_passes

    prog = _tiny_train_program()
    blk = prog.global_block()
    op = next(o for o in blk.ops if o.type == "relu")
    op.outputs["Out"] = ["authored_bug"]
    with pytest.raises(VerifierError) as ei:
        apply_program_passes(prog, ("x", "y"), ())
    assert "input program" in str(ei.value)


def test_verifier_disabled_by_env(monkeypatch):
    from paddle_tpu.passes import apply_program_passes

    monkeypatch.setenv("PADDLE_TPU_VERIFY", "0")
    prog = _tiny_train_program()
    blk = prog.global_block()
    op = next(o for o in blk.ops if o.type == "relu")
    op.outputs["Out"] = ["authored_bug"]
    # verification off: the (broken) program passes through untouched
    apply_program_passes(prog, ("x", "y"), ())


def test_verifier_never_mutates_the_program():
    from paddle_tpu.passes import apply_program_passes

    prog = _tiny_train_program()
    loss_name = next(
        op.output("Out")[0] for op in prog.global_block().ops
        if op.type == "mean"
    )
    before = prog.fingerprint()
    apply_program_passes(prog, ("x", "y"), (loss_name,))
    assert prog.fingerprint() == before


def test_unused_decl_report_names_rewrite_litter():
    """copy_prop drops the backward @PARTIAL assigns by renaming the
    producer's output — the PARTIAL declaration stays behind. That is
    harmless (only ops lower) so default verification is clean, but the
    opt-in hygiene report names every leftover."""
    from paddle_tpu.passes import apply_program_passes

    prog = _tiny_train_program()
    loss_name = next(
        op.output("Out")[0] for op in prog.global_block().ops
        if op.type == "mean"
    )
    os.environ["PADDLE_TPU_PASSES"] = "copy_prop"
    try:
        p2, b2, stats = apply_program_passes(prog, ("x", "y"), (loss_name,))
    finally:
        del os.environ["PADDLE_TPU_PASSES"]
    assert stats["passes"]["copy_prop"] > 0
    assert analysis.verify_program(p2, fetch_names=(loss_name,)) == []
    unused = [
        f for f in analysis.verify_program(
            p2, fetch_names=(loss_name,), report_unused=True
        )
        if f.code == "unused-var-decl"
    ]
    assert unused and all("@PARTIAL" in f.var for f in unused)


def test_layout_opt_rewritten_program_verifies_and_matches_trace():
    """Round-15 audit regression: layout_opt's NHWC rewrite renames
    grad-side vars to @lo.N aliases; the grad inference must follow the
    rewritten INPUT slots, not parse forward names out of the grad var
    (the original rule inferred NCHW metas for NHWC values and flagged
    five tier-1 tests with phantom shape-mismatch findings)."""
    import jax

    from paddle_tpu.ops.registry import JNP_DTYPE, LoweringContext, lower_op
    from paddle_tpu.passes import apply_program_passes

    main = framework.Program()
    startup = framework.Program()
    with framework.program_guard(main, startup):
        x = layers.data("x", [4, 3, 2, 2], append_batch_size=False)
        x.stop_gradient = False
        bn = layers.batch_norm(x)
        act = layers.relu(bn)
        loss = layers.reduce_sum(act)
        grads = fluid.backward.calc_gradient(loss, [x])
    fetch = tuple(g.name for g in grads)
    os.environ["PADDLE_TPU_PASSES"] = "layout_opt"
    try:
        # the PADDLE_TPU_VERIFY hook itself is part of the regression:
        # a phantom finding would raise here
        p2, b2, _stats = apply_program_passes(main, ("x",), fetch)
    finally:
        del os.environ["PADDLE_TPU_PASSES"]
    assert any("@lo." in n for blk in p2.blocks for n in blk.vars)

    feeds = {"x": ((4, 3, 2, 2), "float32")}
    result = analysis.infer_program(p2, feeds=feeds)
    assert result.errors == []
    state = {
        n: jax.ShapeDtypeStruct(tuple(v.shape), JNP_DTYPE(v.dtype))
        for blk in p2.blocks for n, v in blk.vars.items() if v.persistable
    }
    fv = {"x": jax.ShapeDtypeStruct((4, 3, 2, 2), JNP_DTYPE("float32"))}

    def run(state, fv):
        ctx = LoweringContext(p2, rng_key=jax.random.key(0), is_test=False)
        ctx.values.update(state)
        ctx.values.update(fv)
        for op in b2.ops:
            lower_op(ctx, op)
        return dict(ctx.values)

    traced = jax.eval_shape(run, state, fv)
    for n, sd in traced.items():
        meta = result.env.get(n)
        assert meta is not None and meta.shape is not None, n
        assert meta.shape == tuple(sd.shape), (n, meta, sd)
        assert meta.dtype == np.dtype(sd.dtype).name, (n, meta, sd)


# ---------------------------------------------------------------------------
# coverage ratchet
# ---------------------------------------------------------------------------


def test_round18_ctr_op_shape_fns_match_trace():
    """The round-18 registrations (CTR family + small tensor ops) are
    proven bitwise against the abstract trace, same as the bench
    programs — shape AND lowered dtype (hash emits int32 under the
    x64-disabled default, not the IR's int64)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.layer_helper import LayerHelper

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [8], dtype="float32")
        y = layers.data("y", [8], dtype="float32")
        lbl = layers.data("lbl", [1], dtype="int64")
        cvm_in = layers.data("cvm_in", [2], dtype="float32")
        layers.continuous_value_model(x, cvm_in, use_cvm=True)
        layers.continuous_value_model(x, cvm_in, use_cvm=False)
        layers.data_norm(x)
        layers.hinge_loss(x, y)
        layers.bpr_loss(layers.softmax(x), lbl)
        layers.cos_sim(x, y)
        layers.is_empty(x)
        layers.filter_by_instag(
            x, layers.cast(lbl, "int32"),
            layers.assign(np.array([1], np.int32)))
        layers.diag(layers.reduce_sum(x, dim=1))
        layers.hash(layers.cast(lbl, "int32"), hash_size=1000, num_hash=3)
        helper = LayerHelper("index_sample")
        out_is = helper.create_variable_for_type_inference(
            "float32", (4, 3))
        idx = layers.assign(np.zeros((4, 3), np.int64))
        helper.append_op(type="index_sample",
                         inputs={"X": [x], "Index": [idx]},
                         outputs={"Out": [out_is]}, attrs={})
        out_fz = helper.create_variable_for_type_inference(
            "float32", (4, 8))
        helper.append_op(type="fill_zeros_like2", inputs={"X": [x]},
                         outputs={"Out": [out_fz]},
                         attrs={"dtype": "float32"})

    feeds = {"x": ((4, 8), "float32"), "y": ((4, 8), "float32"),
             "lbl": ((4, 1), "int64"), "cvm_in": ((4, 2), "float32")}
    n, mismatches, unknown = compare_static_vs_traced(main, feeds)
    assert n >= 29
    assert mismatches == []
    assert unknown == []


def test_bench_op_families_have_shape_fns():
    from paddle_tpu.ops.registry import has_shape_fn

    for t in (
        "matmul", "mul", "conv2d", "pool2d", "batch_norm", "layer_norm",
        "elementwise_add", "reduce_sum", "reshape2", "transpose2",
        "lookup_table", "softmax", "softmax_with_cross_entropy",
        "fused_multihead_attention", "dropout", "adam", "fused_adam",
        "concat", "cast", "fill_constant",
    ):
        assert has_shape_fn(t), t


def test_shape_coverage_ratchet_matches_checkin():
    from tools.shape_coverage import current_state, load_recorded

    recorded = load_recorded()
    assert recorded is not None, "tools/shape_coverage.json missing"
    now = set(current_state()["missing"])
    regressed = now - set(recorded["missing"])
    assert not regressed, (
        f"ops lost shape functions (or landed without them): "
        f"{sorted(regressed)}"
    )

def test_round20_transformer_body_shape_fns_match_trace():
    """The round-20 registrations (the scan-blocked transformer-body
    stragglers: positional encoding, sequence softmax/reverse, strided
    slicing, channel rearrangements, im2col) are proven bitwise against
    the abstract trace — shape AND lowered dtype."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = layers.data("img", [4, 8, 8], dtype="float32")
        seq = layers.data("seq", [6], dtype="float32")
        s3 = layers.data("s3", [6, 4], dtype="float32")
        lbl = layers.data("lbl", [1], dtype="int64")
        x1 = layers.data("x1", [8], dtype="float32")
        x2 = layers.data("x2", [8], dtype="float32")
        idx = layers.data("idx", [1], dtype="int32")

        layers.add_position_encoding(s3, alpha=1.0, beta=1.0)
        layers.temporal_shift(img, seg_num=2)
        layers.shuffle_channel(img, group=2)
        layers.space_to_depth(img, blocksize=2)
        layers.pixel_shuffle(img, upscale_factor=2)
        layers.maxout(img, groups=2)
        layers.lrn(img)
        layers.unfold(img, kernel_sizes=[3, 3])
        layers.im2sequence(img, filter_size=3)
        layers.reverse(img, axis=[2])
        small = layers.strided_slice(
            img, axes=[2, 3], starts=[0, 0], ends=[6, 7], strides=[2, 1]
        )
        layers.pad_constant_like(img, small, pad_value=0.5)
        layers.shard_index(lbl, index_num=20, nshards=4, shard_id=1)
        layers.sequence_softmax(seq)
        layers.sequence_reverse(seq)
        layers.multiplex([x1, x2], idx)

    feeds = {
        "img": ((2, 4, 8, 8), "float32"), "seq": ((2, 6), "float32"),
        "s3": ((2, 6, 4), "float32"), "lbl": ((2, 1), "int64"),
        "x1": ((2, 8), "float32"), "x2": ((2, 8), "float32"),
        "idx": ((2, 1), "int32"),
    }
    n, mismatches, unknown = compare_static_vs_traced(main, feeds)
    assert n >= 16
    assert mismatches == []
    assert unknown == []


def test_round21_ranking_detection_sequence_shape_fns_match_trace():
    """The round-21 registrations (ranking losses, mean-IoU, crop,
    affine_channel, IoU similarity, sampling, dense sequence pad/concat,
    batch shuffle, bilinear product, similarity focus) are proven
    bitwise against the abstract trace — shape AND lowered dtype
    (sampling_id / sequence_pad Length / mean_iou histograms emit int32
    under the x64-disabled default, not the IR's int64)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.layer_helper import LayerHelper

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [8], dtype="float32")
        y = layers.data("y", [8], dtype="float32")
        lbl = layers.data("lbl", [1], dtype="float32")
        img = layers.data("img", [4, 6, 6], dtype="float32")
        cy = layers.data("cy", [2, 3, 3], dtype="float32")
        boxes = layers.data("boxes", [4], dtype="float32")
        gts = layers.data("gts", [3, 4], dtype="float32")
        priors = layers.data("priors", [4], dtype="float32")
        pred = layers.data("pred", [1], dtype="int64")
        plbl = layers.data("plbl", [1], dtype="int64")
        s1 = layers.data("s1", [3, 4], dtype="float32")
        s2 = layers.data("s2", [2, 4], dtype="float32")

        layers.rank_loss(lbl, x, y)
        layers.margin_rank_loss(lbl, x, y, margin=0.2)
        layers.modified_huber_loss(x, lbl)
        layers.teacher_student_sigmoid_loss(x, lbl)
        layers.mean_iou(pred, plbl, num_classes=5)
        layers.crop(img, shape=[2, 2, 4, 4], offsets=[0, 0, 1, 1])
        layers.crop(img, shape=cy)  # Y-variable path
        layers.affine_channel(
            img,
            scale=layers.assign(np.ones((4,), np.float32)),
            bias=layers.assign(np.zeros((4,), np.float32)))
        layers.iou_similarity(boxes, priors)
        layers.iou_similarity(gts, priors)  # batched ssd_loss shape
        layers.sampling_id(layers.softmax(x))
        layers.sequence_pad(s1, layers.assign(np.zeros(1, np.float32)))
        layers.sequence_concat([s1, s2])
        layers.bilinear_tensor_product(x, y, size=6)
        layers.similarity_focus(img, axis=1, indexes=[0])
        helper = LayerHelper("shuffle_batch")
        sb_out = helper.create_variable_for_type_inference(
            "float32", x.shape)
        sb_idx = helper.create_variable_for_type_inference(
            "int32", (x.shape[0],))
        sb_seed = helper.create_variable_for_type_inference("int32", (1,))
        helper.append_op(
            type="shuffle_batch", inputs={"X": [x]},
            outputs={"Out": [sb_out], "ShuffleIdx": [sb_idx],
                     "SeedOut": [sb_seed]}, attrs={})

    feeds = {
        "x": ((4, 8), "float32"), "y": ((4, 8), "float32"),
        "lbl": ((4, 1), "float32"), "img": ((2, 4, 6, 6), "float32"),
        "cy": ((2, 2, 3, 3), "float32"), "boxes": ((4, 4), "float32"),
        "gts": ((2, 3, 4), "float32"), "priors": ((5, 4), "float32"),
        "pred": ((4, 1), "int64"), "plbl": ((4, 1), "int64"),
        "s1": ((2, 3, 4), "float32"), "s2": ((2, 2, 4), "float32"),
    }
    n, mismatches, unknown = compare_static_vs_traced(main, feeds)
    assert n >= 16
    assert mismatches == []
    assert unknown == []


def test_round22_vision_pool_random_shape_fns_match_trace():
    """The round-22 registrations (affine_grid, grid_sampler,
    spectral_norm, pool3d, max-pool-with-index 2d/3d, unpool, row_conv,
    spp, fsp, conv_shift, scatter_nd, *_batch_size_like randoms,
    sigmoid_focal_loss, polygon_box_transform, box_clip) are proven
    bitwise against the abstract trace — shape AND lowered dtype (the
    with-index Mask and the uniform batch-size-like sample stay int32 /
    float32 regardless of the IR labels)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.layer_helper import LayerHelper

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = layers.data("img", [4, 6, 6], dtype="float32")
        vol = layers.data("vol", [4, 6, 6, 6], dtype="float32")
        theta = layers.data("theta", [2, 3], dtype="float32")
        seq = layers.data("seq", [5, 8], dtype="float32")
        x2 = layers.data("x2", [7], dtype="float32")
        y2 = layers.data("y2", [3], dtype="float32")
        boxes = layers.data("boxes", [9, 4], dtype="float32")
        iminfo = layers.data("iminfo", [3], dtype="float32")
        cls = layers.data("cls", [5], dtype="float32")
        lbl = layers.data("lbl", [1], dtype="int32")
        fg = layers.data("fg", [1], dtype="int32")
        geo = layers.data("geo", [8, 6, 6], dtype="float32")
        sc_idx = layers.data("sc_idx", [2], dtype="int32")
        sc_upd = layers.data("sc_upd", [], dtype="float32")

        grid = layers.affine_grid(theta, out_shape=[2, 4, 5, 5])
        layers.grid_sampler(img, grid)
        layers.spectral_norm(
            layers.assign(np.ones((4, 3, 3), np.float32)),
            dim=0, power_iters=2)
        layers.pool3d(vol, pool_size=2, pool_type="avg", pool_stride=2)
        layers.pool3d(vol, pool_size=3, pool_type="max", pool_stride=2,
                      pool_padding=1)
        layers.pool3d(vol, global_pooling=True)
        po, pm = layers.max_pool2d_with_index(img, ksize=2)
        layers.unpool(po, pm, ksize=[2, 2])
        layers.unpool(po, pm, unpooled_size=[6, 6])
        helper = LayerHelper("max_pool3d_with_index")
        o3 = helper.create_variable_for_type_inference(
            "float32", (2, 4, 3, 3, 3))
        m3 = helper.create_variable_for_type_inference(
            "int32", (2, 4, 3, 3, 3))
        helper.append_op(
            type="max_pool3d_with_index", inputs={"X": [vol]},
            outputs={"Out": [o3], "Mask": [m3]},
            attrs={"ksize": [2, 2, 2], "strides": [2, 2, 2],
                   "paddings": [0, 0, 0]})
        layers.row_conv(seq, future_context_size=2)
        layers.spp(img, pyramid_height=3)
        layers.spp(img, pyramid_height=2, pool_type="avg")
        layers.fsp_matrix(img, layers.relu(img))
        layers.conv_shift(x2, y2)
        layers.scatter_nd(sc_idx, sc_upd, shape=[6, 6])
        layers.uniform_random_batch_size_like(x2, shape=[-1, 3])
        layers.gaussian_random_batch_size_like(x2, shape=[-1, 4])
        layers.sigmoid_focal_loss(cls, lbl, fg)
        layers.polygon_box_transform(geo)
        layers.box_clip(boxes, iminfo)

    feeds = {
        "img": ((2, 4, 6, 6), "float32"),
        "vol": ((2, 4, 6, 6, 6), "float32"),
        "theta": ((2, 2, 3), "float32"),
        "seq": ((2, 5, 8), "float32"),
        "x2": ((3, 7), "float32"), "y2": ((3, 3), "float32"),
        "boxes": ((2, 9, 4), "float32"), "iminfo": ((2, 3), "float32"),
        "cls": ((6, 5), "float32"), "lbl": ((6, 1), "int32"),
        "fg": ((1, 1), "int32"),
        "geo": ((2, 8, 6, 6), "float32"),
        "sc_idx": ((4, 2), "int32"), "sc_upd": ((4,), "float32"),
    }
    n, mismatches, unknown = compare_static_vs_traced(main, feeds)
    assert n >= 23
    assert mismatches == []
    assert unknown == []
