"""The cell `joyai_flash_ep32_s4096` on the CPU: its configuration against
the catalog row, its traffic, its metrics looked up by name (and not at
the tail of a list that the next PR appends to), and its tiny preset
through the runner. No number read here is a device number."""

from __future__ import annotations

import json
import os

from benchmark.harness import spec
from benchmark.tests.test_harness import last_line, run_cell

CELL = "joyai_flash_ep32_s4096"
CONFIG = "joyai_llm_flash_48b_ep32"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's `config` (architectures.jsonl beside the model-configs
# guide), whole
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280,
}
HELD = {"num_hidden_layers": 5, "n_routed_experts": 8, "vocab_size": 16160}
NEW_METRICS = {
    "joyai_attn_device_pct": "trace_scope_share",
    "joyai_flash_ms_per_step": "trace_kernel",
    "joyai_flash_calls_per_step": "trace_kernel_calls",
    "joyai_flash_roofline_pct": "trace_roofline",
    "joyai_latent_glue_device_pct": "trace_scope_share",
    "joyai_fc_roofline_pct": "trace_roofline",
    "joyai_moe_device_pct": "trace_scope_share",
    "joyai_loss_device_pct": "trace_scope_share",
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
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "JoyAI-LLM-Flash")
        assert row["config"] == PUBLISHED
        assert config["source"] == row["source_url"]
    # every key beside the published ones is the share's, the run's or
    # listed under `assumed`
    beside = set(config) - set(PUBLISHED) - {
        "source", "adapter", "reduced", "deployment", "assumed", "rehearse"}
    assert beside == {
        "n_routed_experts_published", "held_from",
        "num_hidden_layers_published", "vocab_size_published",
        "mtp_loss_weight", "initializer_range", "embedding_initializer_range",
        "router_bias_scale", "context", "optimizer", "precision",
        "loss_fall_margin"}
    assert (config["n_routed_experts_published"], config["held_from"],
            config["vocab_size_published"],
            config["num_hidden_layers_published"]) == (256, 0, 129280, 40)
    assert config["adapter"] == "joyai_flash"
    assert config["deployment"].startswith("32 chips share each layer")
    for key in ("num_hidden_layers", "n_routed_experts", "vocab_size",
                "mtp_module", "mtp_loss_weight", "positions", "norms",
                "router", "balancing_loss", "initializer_range",
                "embedding_initializer_range", "router_bias_scale",
                "latent_attention", "optimizer", "precision",
                "loss_fall_margin", "context"):
        assert config["assumed"][key], key
    # the floors: a period and four expert layers, 8 experts, an eighth
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["vocab_size_published"]
    # the rehearsal changes sizes only, and keeps a head's two parts
    tiny = config["rehearse"]
    assert tiny["qk_nope_head_dim"] + tiny["qk_rope_head_dim"] == tiny[
        "qk_head_dim"] and tiny["qk_rope_head_dim"] % 2 == 0
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
    assert cells[CELL] == {
        "name": CELL, **{k: v for k, v in spec.load("workloads", CELL).items()
                         if k != "name"}}
    assert configs[CONFIG]["file"] == f"benchmark/configs/{CONFIG}.json"
    assert configs[CONFIG]["reduced"] == list(HELD)
    assert 1 <= len(configs[CONFIG]["why"]) <= 200
    found = {m["name"]: m for m in spec.layer_metrics(spec.cell(CELL))}
    others = [w["name"] for w in bench["workloads"] if w["name"] != CELL]
    for name, kind in NEW_METRICS.items():
        assert declared[name]["workloads"] == [CELL]
        m = spec.load("layer_metrics", name)
        assert m["where"] == {"config.adapter": ["joyai_flash"]}
        assert (m["kind"], m["unit"], m["layer"], m["better"]) == (
            kind, declared[name]["unit"], declared[name]["layer"],
            declared[name]["better"])
        assert name in found
        for other in others:
            assert name not in {x["name"] for x in spec.layer_metrics(
                spec.cell(other))}, (name, other)
    # one set of kernel names behind the three flash readings
    flash = {found[n]["args"]["name"] for n in (
        "joyai_flash_ms_per_step", "joyai_flash_calls_per_step",
        "joyai_flash_roofline_pct")}
    assert flash == {"^%?flash_(fwd|bwd_dq|bwd_dkv)"}
    for name in ("attn_short_ms_per_step", "ln_bwd_ms_per_step"):
        assert CELL in declared[name]["workloads"] and name in found
    # the other decoders' metrics name their adapters and leave this cell out
    assert not set(found) & {
        "moe_device_pct", "latent_attn_device_pct", "flash_attn_ms_per_step",
        "flash_roofline_pct", "fc_roofline_pct", "mellum_fc_roofline_pct",
        "moe_gmm_ms_per_step", "kda_device_pct", "attn_gqa_device_pct"}
    # every accepted metric with no `workloads` list is read here too
    everywhere = {n for n, m in declared.items() if "workloads" not in m}
    assert everywhere <= set(found)
    # and every metric that lists this cell is one this cell's run reads
    assert {n for n, m in declared.items()
            if CELL in m.get("workloads", ())} <= set(found)


def test_the_cell_rehearses_at_a_large_seed():
    out = last_line(run_cell(["--workload", CELL, "--seed", "2147483777",
                              "--seconds", "2", "--trace", "0", "--rehearse"]))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 20 and out["metrics"] == {}
