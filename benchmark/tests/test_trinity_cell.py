"""The cell `trinity_mini_ep16_s8192` on the CPU: its configuration against
the published one, its traffic, its metrics, and its tiny preset through
the runner. No number read here is a device number."""

from __future__ import annotations

import json
import os

from benchmark.harness import spec
from benchmark.tests.test_harness import last_line, run_cell

CELL = "trinity_mini_ep16_s8192"
# the catalog row's `config` (architectures.jsonl beside the model-configs
# guide), for the keys that are numbers, flags or names at the top level
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024, "mup_enabled": True,
    "n_group": 1, "num_attention_heads": 32, "num_dense_layers": 2,
    "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_key_value_heads": 4,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192,
}
HELD = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 25024}
NEW_METRICS = ("attn_gqa_device_pct", "flash_gqa_ms_per_step",
               "flash_blocks_visited_pct", "flash_gqa_roofline_pct")


def test_configuration_is_the_published_one_but_for_the_share():
    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "trinity_mini_26b_a3b_ep16.json")) as f:
        config = json.load(f)
    assert sorted(config["reduced"]) == sorted(HELD)
    for key, value in PUBLISHED.items():
        assert config[key] == HELD.get(key, value), key
    assert config["layer_types"] == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 8
    assert (config["num_experts_published"], config["held_from"],
            config["first_layer_held"]) == (128, 0, 1)
    assert config["deployment"].startswith("16 chips share each layer")
    for key in ("num_hidden_layers", "num_experts", "vocab_size", "gate",
                "qk_norm", "norms", "positions", "embedding_scale", "window",
                "router", "initializer_range", "router_bias_scale",
                "optimizer", "precision", "loss_fall_margin", "context"):
        assert config["assumed"][key]
    traffic = spec.load("traffic", "longdoc_b1_s8192")
    assert (traffic["batch"], traffic["seq_len"]) == (1, 8192)
    assert traffic["runner"] == "train_loop" and traffic["mesh"] is None
    c = spec.cell(CELL)
    assert c["chips"] == 1 and len(c["why"]) <= 200


def test_new_metrics_name_the_cell_and_the_adapter():
    with open(os.path.join(os.path.dirname(spec.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    found = {m["name"] for m in spec.layer_metrics(spec.cell(CELL))}
    for name in NEW_METRICS:
        assert declared[name]["workloads"] == [CELL]
        assert spec.load("layer_metrics", name)["where"] == {
            "config.adapter": ["trinity"]}
        assert name in found
        assert name not in {m["name"] for m in spec.layer_metrics(
            spec.cell("kimi_linear_ep32_s4096"))}
    for name in ("attn_short_ms_per_step", "ln_bwd_ms_per_step"):
        assert CELL in declared[name]["workloads"] and name in found
    # PR 31's three name the Kimi adapter and do not admit this cell
    assert not found & {"moe_device_pct", "moe_grouped_ms_per_step",
                        "flash_attn_ms_per_step", "kda_device_pct"}


def test_the_new_source_kinds_read_nothing_where_there_is_nothing():
    """`counter_ratio` and `kernel_roofline` on a program without the
    counters, a run without a trace, an adapter without the function."""
    import types

    ratio = spec.plugin("harness.sources", "counter_ratio")
    args = {"numerator": "flash_blocks_visited",
            "denominator": "flash_blocks_total", "phase": "setup",
            "scale": 100.0}
    assert ratio.read(args, {"counters": {"setup": {}}}) is None
    assert ratio.read(args, {"counters": {"setup": {
        "flash_blocks_visited": 416, "flash_blocks_total": 1280}}}) == 32.5
    roofline = spec.plugin("harness.sources", "kernel_roofline")
    args = {"flops": "flash_flops_per_step", "bound": "bf16_flops"}
    assert roofline.read(args, {"adapter": types.SimpleNamespace()}) is None
    assert roofline.read(args, {"adapter": spec.plugin("models", "trinity"),
                                "trace": None}) is None


def test_the_cell_rehearses_at_a_large_seed():
    out = last_line(run_cell(["--workload", CELL, "--seed", "2147483777",
                              "--seconds", "2", "--trace", "0", "--rehearse"]))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 20 and out["metrics"] == {}
