"""The cell `nemotron3_super_ep64_s4096` on the CPU: its configuration
against the catalog row, its traffic, its metrics looked up by name and by
`where` (and not at the tail of a list that the next PR appends to), its
tiny preset through the runner, and the reference check there, which
passes for the program and fails for the wrong models. No number read here
is a device number."""

from __future__ import annotations

import json
import os
import re

import numpy as np
import pytest

from benchmark.harness import spec
from benchmark.tests.test_harness import last_line, run_cell

CELL = "nemotron3_super_ep64_s4096"
CONFIG = "nemotron3_super_120b_a12b_ep64"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
           "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
# the catalog row's `config` (architectures.jsonl beside the model-configs
# guide), whole
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 2688,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 2688,
    "moe_latent_size": 1024, "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072,
}
HELD = {"num_hidden_layers": 11, "hybrid_override_pattern": "MEMEMEM*EME",
        "n_routed_experts": 8, "vocab_size": 16384, "mamba_num_heads": 16,
        "n_groups": 1, "num_attention_heads": 4, "num_key_value_heads": 1}
NEW_METRICS = {
    "nemotron_ssd_device_pct": "trace_scope_share",
    "nemotron_ssd_roofline_pct": "trace_roofline",
    "nemotron_moe_device_pct": "trace_scope_share",
    "nemotron_moe_gmm_ms_per_step": "trace_kernel",
    "nemotron_moe_gmm_calls_per_step": "trace_kernel_calls",
    "nemotron_fc_roofline_pct": "trace_roofline",
    "nemotron_flash_ms_per_step": "trace_kernel",
    "nemotron_flash_roofline_pct": "trace_roofline",
    "nemotron_short_conv_kernel_ms_per_step": "trace_kernel",
    "nemotron_attn_device_pct": "trace_scope_share",
    "nemotron_moe_assignments_per_layer": "counter_ratio",
}


def _benchmark():
    with open(os.path.join(os.path.dirname(spec.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        return json.load(f)


def test_configuration_is_the_catalogs_but_for_the_share():
    with open(os.path.join(spec.BENCH_DIR, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    assert config["reduced"] == list(HELD)
    for key, value in PUBLISHED.items():
        assert config[key] == HELD.get(key, value), key
    if os.path.exists(CATALOG):  # the row itself, where the guide is there
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"]
                       == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
        assert row["config"] == PUBLISHED
        assert config["source"] == row["source_url"]
    # every reduced key has its published number beside it
    for key in HELD:
        assert config[key + "_published"] == PUBLISHED[key], key
    # every key beside the published ones is the share's, the run's or
    # listed under `assumed`
    beside = set(config) - set(PUBLISHED) - {
        "source", "adapter", "reduced", "deployment", "assumed", "rehearse"}
    assert beside == {key + "_published" for key in HELD} | {
        "held_from", "first_layer_held", "initializer_range",
        "router_bias_scale", "context", "optimizer", "precision",
        "loss_fall_margin"}
    assert (config["held_from"], config["first_layer_held"]) == (0, 0)
    assert config["adapter"] == "nemotron_h"
    assert config["deployment"].startswith("64 chips share each layer")
    for key in (*HELD, "expand", "intermediate_size", "rope_theta", "mtp",
                "block", "mamba2", "seeding_of_the_decay", "conv_seeding",
                "attention", "expert_layer", "router_bias", "rescale",
                "initializer_range", "optimizer", "precision",
                "loss_fall_margin", "context"):
        assert config["assumed"][key], key
    # the floors: a whole period of at least four blocks, at least 8
    # routed experts a layer, an eighth of the vocabulary; the pattern
    # held is the published one's first eleven; no width is cut outside
    # the rehearsal
    adapter = spec.plugin("models", "nemotron_h")
    assert PATTERN.startswith(config["hybrid_override_pattern"])
    assert len(config["hybrid_override_pattern"]) == config[
        "num_hidden_layers"]
    kinds = [kind for _, kind in adapter.held_layers(config)]
    assert (kinds.count("mamba2"), kinds.count("experts"),
            kinds.count("attention")) == (5, 5, 1)
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 == config["vocab_size_published"]
    assert config["n_routed_experts"] * 64 == config[
        "n_routed_experts_published"]
    for key in ("mamba_num_heads", "n_groups", "num_attention_heads"):
        assert config[key] * 8 == config[key + "_published"], key
    traffic = spec.load("traffic", "longdoc_b1_s4096")
    assert (traffic["batch"], traffic["seq_len"]) == (1, 4096)
    assert traffic["runner"] == "train_loop" and traffic["mesh"] is None
    c = spec.cell(CELL)
    assert c["chips"] == 1 and 1 <= len(c["why"]) <= 200


def test_new_metrics_name_the_cell_and_the_adapter():
    bench = _benchmark()
    declared = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert len(cells) == 13
    assert sum(w["chips"] == 4 for w in cells.values()) == 1
    assert cells[CELL] == {
        "name": CELL, **{k: v for k, v in spec.load("workloads", CELL).items()
                         if k != "name"}}
    assert configs[CONFIG]["file"] == f"benchmark/configs/{CONFIG}.json"
    assert configs[CONFIG]["reduced"] == list(HELD)
    assert configs[CONFIG]["source"] == spec.load("configs", CONFIG)["source"]
    assert 1 <= len(configs[CONFIG]["why"]) <= 200
    found = {m["name"]: m for m in spec.layer_metrics(spec.cell(CELL))}
    others = [w["name"] for w in bench["workloads"] if w["name"] != CELL]
    for name, kind in NEW_METRICS.items():
        assert declared[name]["workloads"] == [CELL]
        m = spec.load("layer_metrics", name)
        assert m["where"] == {"config.adapter": ["nemotron_h"]}
        assert (m["kind"], m["unit"], m["layer"], m["better"], m["moves"],
                m["source"]) == (
            kind, declared[name]["unit"], declared[name]["layer"],
            declared[name]["better"], "train_examples_per_s",
            declared[name]["source"])
        assert m["note"] and name in found
        for other in others:
            assert name not in {x["name"] for x in spec.layer_metrics(
                spec.cell(other))}, (name, other)
    # the new op's two readings are of its own two scopes, and of no
    # other state-space op's, convolution's or product's
    scopes = {found[n]["args"]["scope"] for n in (
        "nemotron_ssd_device_pct", "nemotron_ssd_roofline_pct")}
    assert len(scopes) == 1
    scope = re.compile(scopes.pop())
    for s in ("fwd/ssd_scan", "bwd/ssd_scan_grad"):
        assert scope.search(s), s
    for s in ("fwd/selective_scan", "bwd/selective_scan_grad",
              "fwd/short_conv1d", "fwd/mul", "opt/fused_adam",
              "bwd/moe_experts_grad"):
        assert not scope.search(s), s
    phi4 = spec.load("layer_metrics", "phi4_ssm_device_pct")["args"]["scope"]
    assert not re.search(phi4, "fwd/ssd_scan")
    assert {found[n]["args"]["name"] for n in (
        "nemotron_flash_ms_per_step", "nemotron_flash_roofline_pct")} == {
        "^%?flash_(fwd|bwd_dq|bwd_dkv)"}
    assert {found[n]["args"]["name"] for n in (
        "nemotron_moe_gmm_ms_per_step",
        "nemotron_moe_gmm_calls_per_step")} == {"^%?moe_t?gmm"}
    for name in ("nemotron_ssd_roofline_pct", "nemotron_flash_roofline_pct",
                 "nemotron_fc_roofline_pct"):
        assert found[name]["args"]["bound"] == "bf16_flops"
    assert re.search(found["nemotron_moe_device_pct"]["args"]["scope"],
                     "bwd/moe_experts_grad")
    assert found["nemotron_moe_assignments_per_layer"]["args"] == {
        "numerator": "moe_assignments", "denominator": "moe_dispatch_grouped",
        "phase": "setup"}
    # the metrics that list cells by name were not edited and do not gain
    # this cell; the other decoders' name their adapters and leave it out
    for name, m in declared.items():
        if name not in NEW_METRICS:
            assert CELL not in m.get("workloads", ()), name
    assert not set(found) & {
        "moe_device_pct", "kda_device_pct", "phi4_ssm_device_pct",
        "phi4_ssm_kernel_ms_per_step", "flash_attn_ms_per_step",
        "flash_roofline_pct", "fc_roofline_pct", "moe_gmm_ms_per_step",
        "qwen3next_fc_roofline_pct", "qwen3next_moe_gmm_calls_per_step",
        "qk_prep_hbm_pct", "attn_gqa_device_pct"}
    # every accepted metric with no `workloads` list is read here too
    everywhere = {n for n, m in declared.items() if "workloads" not in m}
    assert everywhere <= set(found)


def test_the_assignments_metric_reads_nothing_where_the_counter_is_not():
    """On a program without `moe_assignments` (the parent's) the reader
    returns nothing and does not raise; with it, tokens x k a lowering."""
    from benchmark.harness.sources import counter_ratio

    args = spec.load("layer_metrics",
                     "nemotron_moe_assignments_per_layer")["args"]
    assert counter_ratio.read(args, {"counters": {"setup": {}}}) is None
    assert counter_ratio.read(args, {"counters": {"setup": {
        "moe_dispatch_grouped": 15}}}) == 0
    assert counter_ratio.read(args, {"counters": {"setup": {
        "moe_dispatch_grouped": 15, "moe_assignments": 15 * 90112}}}) == 90112


def test_the_cell_rehearses_at_a_large_seed():
    out = last_line(run_cell(["--workload", CELL, "--seed", "2147483777",
                              "--seconds", "2", "--trace", "0", "--rehearse"]))
    assert out["correct"] is True and out["failed"] == 0
    # whole blocks of ten steps: 40 alone, one beside busy test workers
    assert out["attempted"] >= 10 and out["metrics"] == {}


def _checker(**config):
    """The tiny preset's programs and the reference check as the runner
    makes it, with a wrong model on request."""
    import paddle_tpu as fluid
    from benchmark.models import nemotron_h as adapter
    from benchmark.runners import train_loop

    c = spec.cell(CELL, rehearse=True)
    model, traffic = dict(c["config"], **config), c["traffic"]
    with fluid.program_guard(fluid.Program(), fluid.Program()), \
            fluid.unique_name.guard(), fluid.scope_guard(fluid.Scope()):
        main, startup, built, eval_prog = train_loop.build_programs(
            fluid, adapter, model, traffic, 3)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        batch = adapter.make_batch(np.random.RandomState(3), model, traffic)

        def check(**kw):
            return train_loop.reference_check(
                fluid, exe, adapter, eval_prog, built, batch, model, traffic,
                **kw)

        yield check


@pytest.fixture(scope="module")
def checked():
    yield from _checker()


@pytest.fixture(scope="module")
def checked_at_width():
    """With the matrices seeded as wide as they weigh at the published
    width and a block's last product as the others: at 0.02, and 13 times
    less for the last product, a block adds next to nothing to a stream
    64 wide and no wrong model shows."""
    yield from _checker(initializer_range=0.1, rescale_prenorm_residual=False)


def test_the_reference_check_passes_at_the_tiny_preset(checked):
    check = checked()
    assert check["ok"], check


def _wrong():
    from benchmark.models.nemotron_h import WRONG

    return WRONG


@pytest.mark.parametrize("wrong", _wrong())
def test_the_reference_check_fails_for_a_wrong_model(checked_at_width, wrong):
    """Each entry of `WRONG` is refused by the cell's logits' limit at the
    tiny preset (four Mamba-2 heads in two groups, so the norm by groups
    shows), in the cell's precision, where the right reference reads under
    it (the loss here is a mean of 96 bf16 losses, too coarse for its
    limit to say anything)."""
    from benchmark.models.nemotron_h import TOLERANCE

    limit = TOLERANCE["logits_rel_rms"]
    assert checked_at_width()["logits_rel_rms"] < limit
    check = checked_at_width(wrong=(wrong,))
    assert not check["ok"] and check["logits_rel_rms"] > limit, (wrong, check)
