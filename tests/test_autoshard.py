"""Autoshard: cost-model-driven auto-parallel placement planner
(round 16).

Everything here is device-free (static analysis + plain arithmetic)
except the pass-integration test, which dispatches on the 8-virtual-
device CPU mesh the suite always runs with. The acceptance gates:

* on the pp=4 x tp=2 dryrun grid, the planner pinned to each
  hand-written config's mesh shape matches or beats the hand specs on
  BOTH static hbm_state_mb_per_device and tier-weighted collective
  bytes;
* the free choice selects ZeRO-1 over replicated — pinned at BERT-BASE
  width (the 424 MB replicated / ~106 MB sharded r05 evidence scale);
* every world the supervisor's shrink policy can pick yields a valid,
  checker-clean plan (property sweep over divisor worlds);
* PADDLE_TPU_AUTOSHARD=1 flows planner specs through
  mesh.assign_state_shardings with fetches bitwise-equal to the manual
  path, and flips the pass cache signature.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu import analysis  # noqa: E402
from paddle_tpu.autoshard import (  # noqa: E402
    CostModel,
    PlanError,
    Topology,
    hand_config_specs,
    mesh_shape_candidates,
    plan_program,
)
from paddle_tpu.autoshard.cost_table import (  # noqa: E402
    param_groups,
    state_var_names,
)
from paddle_tpu.autoshard.elastic import (  # noqa: E402
    PLACEMENT_ENV,
    best_shrink_world,
    load_plan_table,
    placement_env_value,
    placement_from_env,
)


@pytest.fixture(scope="module")
def bert_program():
    from tools.verify_bench_programs import build_bench_program

    return build_bench_program("bert")


@pytest.fixture(scope="module")
def bert_annotated(bert_program):
    program, feeds = bert_program
    result = analysis.infer_program(program, feeds=feeds)
    names = state_var_names(program)
    groups = param_groups(program.global_block(), names, result.env)
    return program, feeds, result, names, groups


# ---------------------------------------------------------------------------
# the dryrun-grid acceptance gate
# ---------------------------------------------------------------------------


def test_planner_matches_or_beats_every_hand_config_on_the_grid(
    bert_annotated,
):
    program, feeds, result, names, groups = bert_annotated
    topo = Topology.single_slice(8)
    model = CostModel(topo)
    configs = hand_config_specs(program, 8)
    tags = [t for t, _, _ in configs]
    assert "replicated_dp" in tags and "zero1_dp8" in tags
    assert "zero_over_pipe4" in tags and "pp4xtp2" in tags
    for tag, axis_sizes, specs in configs:
        hand = model.cost(result.env, names, groups, specs, axis_sizes)
        plan = plan_program(program, topo, feeds=feeds,
                            mesh_shape=axis_sizes, baseline_specs=specs)
        assert plan.cost.dominates(hand), (
            f"{tag}: planner {plan.cost} does not match-or-beat "
            f"hand {hand}"
        )
        # the planner's specs came out of the checker clean (plan_program
        # validates); spot-check the sharded footprint is real
        if specs:
            assert plan.cost.hbm_per_device_mb < hand.hbm_replicated_mb


def test_planner_strictly_beats_replicated_via_zero1(bert_annotated):
    program, feeds, result, names, groups = bert_annotated
    topo = Topology.single_slice(8)
    model = CostModel(topo)
    axis_sizes = {"batch": 8, "model": 1, "pipe": 1}
    hand = model.cost(result.env, names, groups, {}, axis_sizes)
    plan = plan_program(program, topo, feeds=feeds, mesh_shape=axis_sizes,
                        baseline_specs={})
    # strictly better HBM at identical wire bytes: ZeRO-1 is free
    assert plan.cost.hbm_per_device_mb < hand.hbm_per_device_mb * 0.6
    assert plan.cost.collective_bytes == hand.collective_bytes
    assert any(t == "zero1" for t in plan.choices.values())


def test_free_choice_selects_zero1_on_dp_mesh(bert_program):
    program, feeds = bert_program
    plan = plan_program(program, Topology.single_slice(8), feeds=feeds)
    assert plan.axis_sizes == {"batch": 8, "model": 1, "pipe": 1}
    assert any(t == "zero1" for t in plan.choices.values())
    assert plan.cost.feasible


def test_selects_zero1_over_replicated_at_bert_base_scale():
    """The r05 evidence scale: 423.5 MB replicated state at BERT-BASE
    width must come back ZeRO-sharded, not replicated."""
    from tools.autoshard_plan import build_program

    program, feeds = build_program("bert-base-pp4")
    plan = plan_program(program, Topology.single_slice(8), feeds=feeds)
    assert plan.cost.hbm_replicated_mb == pytest.approx(423.5, abs=1.0)
    assert any(t in ("zero1", "pipe", "pipe_z")
               for t in plan.choices.values())
    assert plan.cost.hbm_per_device_mb < plan.cost.hbm_replicated_mb / 2


# ---------------------------------------------------------------------------
# cost model / topology tiers
# ---------------------------------------------------------------------------


def test_axis_tier_weights_cross_domain_axis_pays_dcn():
    topo = Topology(chips=8, ici_gbps=400.0, dcn_gbps=25.0, ici_domain=4)
    w = topo.axis_tier_weights({"batch": 2, "model": 1, "pipe": 4})
    # pipe (stride 1, extent 4) fits one domain; batch (stride 4,
    # extent 2) spans both -> DCN weight 400/25
    assert w["pipe"] == 1.0
    assert w["batch"] == pytest.approx(16.0)
    # single-slice default: everything ICI
    w2 = Topology.single_slice(8).axis_tier_weights(
        {"batch": 2, "model": 1, "pipe": 4})
    assert set(w2.values()) == {1.0}


def test_tier_weighting_steers_the_search(bert_annotated):
    """With 'batch' forced across DCN, grad sync gets 16x more
    expensive — the planner must stop spending wire on the batch axis
    (smaller batch extent, or none) versus the single-slice choice."""
    program, feeds, result, names, groups = bert_annotated
    flat = plan_program(program, Topology.single_slice(8), feeds=feeds)
    tiered = plan_program(
        program,
        Topology(chips=8, ici_gbps=400.0, dcn_gbps=25.0, ici_domain=1),
        feeds=feeds,
    )
    assert flat.axis_sizes["batch"] == 8
    # every axis is cross-domain on ici_domain=1, so the cheapest wire
    # is the least wire: the tiered plan must not out-spend the flat one
    m_flat = CostModel(Topology(chips=8, ici_gbps=400.0, dcn_gbps=25.0,
                                ici_domain=1))
    flat_coll_tiered = m_flat.collective_bytes(
        groups, flat.specs, flat.axis_sizes)
    assert tiered.cost.collective_bytes <= flat_coll_tiered


def test_infeasible_when_state_busts_hbm(bert_annotated):
    program, feeds, result, names, groups = bert_annotated
    # ~1 MB of state, cap it at ~0.1 MB usable per chip, replicated-only
    tiny = Topology(chips=1, hbm_gb_per_chip=0.1 / 650)
    with pytest.raises(PlanError):
        plan_program(program, tiny, feeds=feeds, world=1)


def test_bubble_fraction_and_compute_fraction():
    assert CostModel.bubble_fraction({"pipe": 4}, 4) == pytest.approx(
        3 / 7)
    assert CostModel.bubble_fraction({"pipe": 1}, 8) == 0.0
    assert CostModel.compute_fraction(
        {"batch": 4, "model": 2, "pipe": 1}, False) == 0.25
    # 'pipe' splits compute only when a schedule runs; 'model' without
    # annotations never does
    assert CostModel.compute_fraction(
        {"batch": 2, "model": 2, "pipe": 2}, True) == 0.25
    assert CostModel.compute_fraction(
        {"batch": 1, "model": 8, "pipe": 1}, False) == 1.0


def test_mesh_shape_candidates_cover_factorizations():
    shapes = mesh_shape_candidates(8)
    assert {"batch": 8, "model": 1, "pipe": 1} in shapes
    assert {"batch": 1, "model": 2, "pipe": 4} in shapes
    for s in shapes:
        assert s["batch"] * s["model"] * s["pipe"] == 8
    # dp-leaning order: ties break toward data parallelism
    assert shapes[0] == {"batch": 8, "model": 1, "pipe": 1}


# ---------------------------------------------------------------------------
# unknown-shape refusal (the ratchet contract)
# ---------------------------------------------------------------------------


def test_plan_refuses_unknown_shape_state_var():
    import paddle_tpu as fluid
    from paddle_tpu import framework, layers

    main = framework.Program()
    startup = framework.Program()
    with framework.program_guard(main, startup):
        x = layers.data("x", shape=[4, 6], dtype="float32")
        helper = fluid.layer_helper.LayerHelper("autoshard_t")
        w = main.global_block().create_var(
            name="mystery_state", shape=[4, 6], dtype="float32",
            persistable=True)
        # sequence_expand_as has a lowering but (deliberately) no shape
        # function: its persistable output meta poisons to unknown
        main.global_block().append_op(
            type="sequence_expand_as", inputs={"X": x, "Y": x},
            outputs={"Out": w}, attrs={})
    with pytest.raises(PlanError) as ei:
        plan_program(main, Topology.single_slice(8),
                     feeds={"x": ((2, 4, 6), "float32")})
    assert "mystery_state" in str(ei.value)
    assert "shape" in str(ei.value)


# ---------------------------------------------------------------------------
# shrink-world sweep: every supervisor-pickable world must plan clean
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("base_world", [8, 12])
def test_every_shrink_world_yields_valid_plan(bert_program, base_world):
    from paddle_tpu.parallel.mesh import smaller_mesh_shapes

    program, feeds = bert_program
    worlds = smaller_mesh_shapes(base_world)
    assert worlds, f"no shrink candidates for base {base_world}"
    for w in worlds:
        plan = plan_program(program, Topology.single_slice(w),
                            feeds=feeds, world=w)
        b, m, p = (plan.axis_sizes[a] for a in ("batch", "model", "pipe"))
        assert b * m * p == w
        assert plan.cost.feasible
        # plan_program ran analysis.check_sharding on the result; a
        # second independent validation here pins the contract
        result = analysis.infer_program(program, feeds=feeds)
        findings = analysis.check_sharding(
            program, mesh=plan.axis_sizes, specs={},
            extra_specs=plan.specs, env=result,
        )
        assert findings == [], f"world {w}: {findings[:3]}"


# ---------------------------------------------------------------------------
# elastic: plan-table world pick + supervisor wiring
# ---------------------------------------------------------------------------


def _plan_dict(world, score, feasible=True, config="dpX"):
    return {
        "world": world,
        "mesh": {"batch": world, "model": 1, "pipe": 1},
        "config": config,
        "specs": {"p0_moment1_0": ["batch"]},
        "cost": {"score": score, "feasible": feasible},
    }


def test_best_shrink_world_prefers_score_skips_infeasible():
    table = {
        4: _plan_dict(4, 0.9, feasible=False),  # would not fit
        2: _plan_dict(2, 0.5, config="dp2+zero1"),
        1: _plan_dict(1, 0.8),
    }
    w, plan = best_shrink_world(table, [4, 2, 1])
    assert (w, plan["config"]) == (2, "dp2+zero1")
    # no feasible entry at all -> largest candidate (round-13
    # behavior) with NO plan: an infeasible placement must never be
    # exported to the relaunched workers
    bad = {4: _plan_dict(4, 1.0, feasible=False)}
    w2, p2 = best_shrink_world(bad, [4, 2, 1])
    assert (w2, p2) == (4, None)
    # equal scores tie to the LARGER world
    tie = {4: _plan_dict(4, 0.5), 2: _plan_dict(2, 0.5)}
    w3, _ = best_shrink_world(tie, [4, 2])
    assert w3 == 4


def test_placement_env_round_trip(monkeypatch):
    plan = _plan_dict(4, 0.5, config="dp4+zero1")
    val = placement_env_value(plan)
    assert "cost" not in json.loads(val)  # slimmed for the env
    monkeypatch.setenv(PLACEMENT_ENV, val)
    got = placement_from_env()
    assert got["mesh"] == {"batch": 4, "model": 1, "pipe": 1}
    assert got["config"] == "dp4+zero1"
    monkeypatch.setenv(PLACEMENT_ENV, "")
    assert placement_from_env() is None

    from paddle_tpu.autoshard import Plan

    specs = Plan.specs_from_dict(got)
    assert tuple(specs["p0_moment1_0"]) == ("batch",)


def test_supervisor_shrink_uses_plan_table_and_exports_placement():
    from paddle_tpu.resilience.trainer_fleet import TrainSupervisor

    table = {
        4: _plan_dict(4, 0.9),
        2: _plan_dict(2, 0.3, config="dp2+zero1"),  # planner's pick
    }
    sup = TrainSupervisor(["true"], nproc_per_node=1, elastic_world=8,
                          allow_shrink=True, plan_table=table)
    try:
        w, plan = sup._next_world()
        assert (w, plan["config"]) == (2, "dp2+zero1")
        sup._shrink_to(w, "test", plan=plan)
        assert sup.cur_world == 2
        env = sup._per_rank_env(0)(0)
        assert env["PADDLE_TPU_ELASTIC_WORLD"] == "2"
        assert json.loads(env[PLACEMENT_ENV])["config"] == "dp2+zero1"
        assert sup.stats()["placement"]["config"] == "dp2+zero1"
    finally:
        sup.close()


def test_supervisor_without_table_keeps_round13_behavior():
    from paddle_tpu.resilience.trainer_fleet import TrainSupervisor

    sup = TrainSupervisor(["true"], nproc_per_node=1, elastic_world=8,
                          allow_shrink=True)
    try:
        w, plan = sup._next_world()
        assert (w, plan) == (4, None)  # largest proper divisor, no plan
        sup._shrink_to(w, "test")
        env = sup._per_rank_env(0)(0)
        assert env[PLACEMENT_ENV] == ""  # never leaks a stale placement
    finally:
        sup.close()


# ---------------------------------------------------------------------------
# pass + executor integration (8-virtual-device CPU mesh)
# ---------------------------------------------------------------------------


def _tiny_train_setup(seed=7):
    import paddle_tpu as fluid
    from paddle_tpu import framework

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    framework.unique_name.switch()
    x = fluid.layers.data("x", [16])
    y = fluid.layers.data("y", [1], dtype="int64")
    pred = fluid.layers.fc(x, 8, act="softmax")
    loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, y))
    fluid.default_main_program().random_seed = seed
    fluid.optimizer.Adam(1e-2).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {
        "x": np.random.RandomState(1).rand(8, 16).astype("float32"),
        "y": np.random.RandomState(2).randint(0, 8, (8, 1)).astype(
            "int64"),
    }
    return fluid, exe, loss, feed


def _run_compiled(autoshard, steps=3):
    fluid, exe, loss, feed = _tiny_train_setup()
    bs = fluid.BuildStrategy()
    bs.auto_shard = autoshard
    cp = fluid.CompiledProgram(
        fluid.default_main_program()
    ).with_data_parallel(loss_name=loss.name, build_strategy=bs)
    return [
        np.asarray(exe.run(cp, feed=feed, fetch_list=[loss.name])[0])
        for _ in range(steps)
    ]


def test_autoshard_pass_bitwise_equal_and_plans_moments():
    from paddle_tpu import profiler

    off = _run_compiled(False)
    on = _run_compiled(True)
    for a, b in zip(off, on):
        assert np.array_equal(a, b), "autoshard changed the math"
    # the planner sharded the Adam moments (2 per param x 2 params)
    assert profiler.counters().get("autoshard_planned_vars", 0) >= 4


def test_autoshard_flip_changes_the_resolved_pass_set(monkeypatch):
    import paddle_tpu as fluid
    from paddle_tpu.passes import resolve_pass_names

    monkeypatch.delenv("PADDLE_TPU_AUTOSHARD", raising=False)
    assert "shard_propagation" not in resolve_pass_names(None)
    monkeypatch.setenv("PADDLE_TPU_AUTOSHARD", "1")
    assert "shard_propagation" in resolve_pass_names(None)
    # resolved LAST: plans on the graph the other rewrites produced
    assert resolve_pass_names(None)[-1] == "shard_propagation"
    monkeypatch.setenv("PADDLE_TPU_AUTOSHARD", "0")
    assert "shard_propagation" not in resolve_pass_names(None)
    monkeypatch.delenv("PADDLE_TPU_AUTOSHARD", raising=False)
    # BuildStrategy knob path (no env)
    bs = fluid.BuildStrategy()
    bs.auto_shard = True
    assert "shard_propagation" in resolve_pass_names(bs)


def test_pass_is_noop_without_mesh_or_when_disabled():
    """The single-device executor path and the disabled state must not
    attach specs (PassContext.mesh is None there)."""
    from paddle_tpu import framework
    from paddle_tpu.passes import PassContext
    from paddle_tpu.passes.shard_propagation import shard_propagation_pass

    prog = framework.Program()
    ctx = PassContext()  # no mesh, no strategy
    os.environ["PADDLE_TPU_AUTOSHARD"] = "1"
    try:
        removed = shard_propagation_pass(
            prog, prog.global_block(), (), (), ctx)
    finally:
        del os.environ["PADDLE_TPU_AUTOSHARD"]
    assert removed == 0
    assert not hasattr(prog, "_autoshard_specs")
    assert ctx.mutated is False
