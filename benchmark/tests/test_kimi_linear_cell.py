"""The cell `kimi_linear_ep32_s4096` on the CPU: its configuration against
the published one, its traffic, the counters its set-up leaves, and its
tiny preset through the runner. No number read here is a device number."""

from __future__ import annotations

import json
import os

from benchmark.harness import spec
from benchmark.tests.test_harness import last_line, run_cell

CELL = "kimi_linear_ep32_s4096"
# the catalog row's `config` (architectures.jsonl beside the model-configs
# guide), for the keys that are numbers or flags at the top level
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_size": 2304,
    "intermediate_size": 9216, "kv_lora_rank": 512, "mla_use_nope": True,
    "model_max_length": 1048576, "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True, "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "routed_scaling_factor": 2.446,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True,
    "v_head_dim": 128, "vocab_size": 163840,
}
HELD = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 20480}


def test_configuration_is_the_published_one_but_for_the_share():
    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "kimi_linear_48b_a3b_ep32.json")) as f:
        config = json.load(f)
    assert sorted(config["reduced"]) == sorted(HELD)
    for key, value in PUBLISHED.items():
        assert config[key] == HELD.get(key, value), key
    assert config["num_experts_published"] == PUBLISHED["num_experts"]
    lin = config["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert lin["full_attn_layers"][0] == 4 and lin["kda_layers"][:4] == [1, 2, 3, 5]
    assert config["deployment"].startswith("32 chips share each layer")
    for key in ("kda_low_rank", "decay", "q_scale", "l2norm_epsilon",
                "initializer_range", "router_bias_scale", "optimizer",
                "precision", "loss_fall_margin", "context"):
        assert config["assumed"][key]
    traffic = spec.load("traffic", "longdoc_b1_s4096")
    assert (traffic["batch"], traffic["seq_len"]) == (1, 4096)
    assert traffic["runner"] == "train_loop" and traffic["mesh"] is None


def test_new_metrics_name_the_cell_and_the_adapter():
    with open(os.path.join(os.path.dirname(spec.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    found = {m["name"] for m in spec.layer_metrics(spec.cell(CELL))}
    for name in ("kda_device_pct", "moe_device_pct", "latent_attn_device_pct",
                 "flash_attn_ms_per_step", "moe_grouped_ms_per_step"):
        assert declared[name]["workloads"] == [CELL]
        assert spec.load("layer_metrics", name)["where"] == {
            "config.adapter": ["kimi_linear"]}
        assert name in found
    for name in ("attn_short_ms_per_step", "ln_bwd_ms_per_step"):
        assert declared[name]["workloads"][-1] == CELL and name in found
    assert "pool_device_pct" not in found


def test_the_cell_rehearses_at_a_large_seed():
    out = last_line(run_cell(["--workload", CELL, "--seed", "2147483777",
                              "--seconds", "2", "--trace", "0", "--rehearse"]))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 20 and out["metrics"] == {}
