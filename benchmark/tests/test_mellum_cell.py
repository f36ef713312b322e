"""The cell `mellum2_ep4_s8192` on the CPU: its configuration against the
catalog row, its traffic, its metrics, the new source kind, and its tiny
preset through the runner. No number read here is a device number."""

from __future__ import annotations

import json
import os

from benchmark.harness import spec
from benchmark.tests.test_harness import last_line, run_cell

CELL = "mellum2_ep4_s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's `config` (architectures.jsonl beside the model-configs
# guide), whole: numbers, flags, names and the nested groups
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 7,
    "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True,
}
HELD = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 24576}
NEW_METRICS = {
    "mellum_moe_device_pct": "trace_scope_share",
    "mellum_moe_grouped_ms_per_step": "trace_kernel",
    "mellum_moe_grouped_roofline_pct": "trace_roofline",
    "mellum_moe_grouped_calls_per_step": "trace_kernel_calls",
    "mellum_attn_device_pct": "trace_scope_share",
    "mellum_flash_roofline_pct": "trace_roofline",
    "mellum_qk_prep_hbm_pct": "trace_roofline",
    "mellum_fc_roofline_pct": "trace_roofline",
}


def test_configuration_is_the_catalogs_but_for_the_share():
    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "mellum2_12b_a2p5b_ep4.json")) as f:
        config = json.load(f)
    assert sorted(config["reduced"]) == sorted(HELD)
    for key, value in PUBLISHED.items():
        assert config[key] == HELD.get(key, value), key
    if os.path.exists(CATALOG):  # the row itself, where the guide is there
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        assert row["config"] == PUBLISHED
        assert config["source"] == row["source_url"]
    assert (config["num_experts_published"], config["held_from"],
            config["first_layer_held"], config["vocab_size_published"],
            config["num_hidden_layers_published"]) == (64, 0, 0, 98304, 28)
    assert config["adapter"] == "mellum"
    assert config["deployment"].startswith("4 chips share each layer")
    for key in ("num_hidden_layers", "num_experts", "vocab_size", "qk_norm",
                "norms", "positions", "window", "router", "balancing_loss",
                "mtp_head", "embedding_scale", "initializer_range",
                "optimizer", "precision", "loss_fall_margin", "context"):
        assert config["assumed"][key]
    # the rehearsal's YaRN ramp is neither all 0 nor all 1 at s=48
    from benchmark.models.mellum import yarn

    tiny = config["rehearse"]
    _, _, low, high = yarn(tiny["head_dim"],
                           tiny["rope_parameters"]["full_attention"])
    assert 0 <= low < high < tiny["head_dim"] // 2 - 1
    traffic = spec.load("traffic", "longdoc_b1_s8192")
    assert (traffic["batch"], traffic["seq_len"]) == (1, 8192)
    assert traffic["runner"] == "train_loop" and traffic["mesh"] is None
    c = spec.cell(CELL)
    assert c["chips"] == 1 and len(c["why"]) <= 200


def test_new_metrics_name_the_cell_and_the_adapter():
    with open(os.path.join(os.path.dirname(spec.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-8:] == list(NEW_METRICS)
    assert bench["workloads"][-1] == {
        "name": CELL, **{k: v for k, v in spec.load("workloads", CELL).items()
                         if k != "name"}}
    assert bench["configs"][-1]["file"] == (
        "benchmark/configs/mellum2_12b_a2p5b_ep4.json")
    assert 1 <= len(bench["configs"][-1]["why"]) <= 200
    found = {m["name"]: m for m in spec.layer_metrics(spec.cell(CELL))}
    for name, kind in NEW_METRICS.items():
        assert declared[name]["workloads"] == [CELL]
        m = spec.load("layer_metrics", name)
        assert m["where"] == {"config.adapter": ["mellum"]}
        assert (m["kind"], m["unit"], m["layer"]) == (
            kind, declared[name]["unit"], declared[name]["layer"])
        assert name in found
        for other in ("kimi_linear_ep32_s4096", "trinity_mini_ep16_s8192"):
            assert name not in {x["name"] for x in spec.layer_metrics(
                spec.cell(other))}
    # the products and not the metadata calls beside them
    for name in ("mellum_moe_grouped_ms_per_step",
                 "mellum_moe_grouped_roofline_pct",
                 "mellum_moe_grouped_calls_per_step"):
        assert found[name]["args"]["name"] == "^%?ragged-dot-none"
    for name in ("attn_short_ms_per_step", "ln_bwd_ms_per_step"):
        assert declared[name]["workloads"][-1] == CELL and name in found
    # the other decoders' metrics name their adapters and leave this cell out
    assert not set(found) & {
        "moe_device_pct", "moe_grouped_ms_per_step", "flash_roofline_pct",
        "moe_grouped_roofline_pct", "fc_roofline_pct", "qk_prep_hbm_pct",
        "attn_gqa_device_pct", "flash_gqa_roofline_pct", "kda_device_pct"}
    # every accepted metric with no `workloads` list is read here too
    everywhere = {n for n, m in declared.items() if "workloads" not in m}
    assert everywhere <= set(found)


def test_trace_kernel_calls_reads_nothing_where_there_is_nothing():
    import types

    calls = spec.plugin("harness.sources", "trace_kernel_calls")
    args = {"name": "^%?ragged-dot-none"}
    assert calls.read(args, {"trace": None}) is None
    assert calls.read(args, {}) is None

    def event(name):
        return types.SimpleNamespace(name=name, dur=1000.0)

    ops = ([event("%ragged-dot-none.7")] * 72
           + [event("%ragged-dot-metadata.1")] * 40
           + [event("ragged-dot-none")] * 12 + [event("%fusion.3")] * 5)
    reading = {"trace": types.SimpleNamespace(devices={"/device:TPU:0": ops}),
               "traced": {"steps": 2}}
    assert calls.read(args, reading) == 42.0
    assert calls.read({"name": "^%?flash_fwd"}, reading) == 0.0


def test_the_cell_rehearses_at_a_large_seed():
    out = last_line(run_cell(["--workload", CELL, "--seed", "2147483777",
                              "--seconds", "2", "--trace", "0", "--rehearse"]))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 20 and out["metrics"] == {}
