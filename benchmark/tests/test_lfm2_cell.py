"""The cell `lfm2_24b_ep8_longdoc` on the CPU: its configuration against
the catalog row, its traffic, its metrics looked up by name (and not at
the tail of a list that the next PR appends to), and its tiny preset
through the runner. No number read here is a device number."""

from __future__ import annotations

import json
import os
import re

from benchmark.harness import spec
from benchmark.tests.test_harness import last_line, run_cell

CELL = "lfm2_24b_ep8_longdoc"
CONFIG = "lfm2_24b_a2b_ep8"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LAYER_TYPES = (["conv", "conv", "full_attention"]
               + (["conv"] * 3 + ["full_attention"]) * 9 + ["conv"])
# the catalog row's `config` (architectures.jsonl beside the model-configs
# guide), whole
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "layer_types": LAYER_TYPES,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}
HELD = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 8192}
NEW_METRICS = {
    "lfm2_conv_device_pct": "trace_scope_share",
    "lfm2_short_conv_kernel_ms_per_step": "trace_kernel",
    "lfm2_short_conv_hbm_pct": "trace_roofline",
    "lfm2_attn_device_pct": "trace_scope_share",
    "lfm2_flash_ms_per_step": "trace_kernel",
    "lfm2_flash_roofline_pct": "trace_roofline",
    "lfm2_moe_device_pct": "trace_scope_share",
    "lfm2_moe_gmm_ms_per_step": "trace_kernel",
    "lfm2_moe_gmm_calls_per_step": "trace_kernel_calls",
    "lfm2_moe_grouped_ms_per_step": "trace_kernel",
    "lfm2_fc_roofline_pct": "trace_roofline",
}


def _benchmark():
    with open(os.path.join(os.path.dirname(spec.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        return json.load(f)


def test_configuration_is_the_catalogs_but_for_the_share():
    with open(os.path.join(spec.BENCH_DIR, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    assert config["reduced"] == list(HELD) == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in PUBLISHED.items():
        assert config[key] == HELD.get(key, value), key
    if os.path.exists(CATALOG):  # the row itself, where the guide is there
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-24B-A2B")
        assert row["config"] == PUBLISHED
        assert config["source"] == row["source_url"]
    # every key beside the published ones is the share's, the run's or
    # listed under `assumed`
    beside = set(config) - set(PUBLISHED) - {
        "source", "adapter", "reduced", "deployment", "assumed", "rehearse"}
    assert beside == {
        "num_experts_published", "held_from", "num_hidden_layers_published",
        "first_layer_held", "vocab_size_published", "head_dim",
        "tie_word_embeddings", "initializer_range", "router_bias_scale",
        "router_norm_eps", "context", "optimizer", "precision",
        "loss_fall_margin"}
    assert (config["num_experts_published"], config["held_from"],
            config["num_hidden_layers_published"], config["first_layer_held"],
            config["vocab_size_published"]) == (64, 0, 40, 1, 65536)
    assert (config["head_dim"] * config["num_attention_heads"]
            == config["hidden_size"])
    assert config["adapter"] == "lfm2"
    assert config["deployment"].startswith("8 chips share each layer")
    for key in ("num_hidden_layers", "num_experts", "vocab_size",
                "tie_word_embeddings", "head_dim", "conv", "conv_seeding",
                "qk_norm", "norms", "positions", "attention", "dense_ffn",
                "router", "router_bias_scale", "initializer_range",
                "optimizer", "precision", "loss_fall_margin", "context"):
        assert config["assumed"][key], key
    # the floors: a whole period after the leading dense layer counted
    # once, at least 8 routed experts a layer, an eighth of the
    # vocabulary; no width is cut outside the rehearsal
    adapter = spec.plugin("models", "lfm2")
    held = adapter.held_layers(config)
    assert [(kind, dense) for _, kind, dense in held] == [
        ("conv", True), ("full_attention", False), ("conv", False),
        ("conv", False), ("conv", False)]
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["vocab_size_published"]
    traffic = spec.load("traffic", "longdoc_b1_s8192")
    assert (traffic["batch"], traffic["seq_len"]) == (1, 8192)
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
    assert configs[CONFIG]["source"] == spec.load("configs", CONFIG)["source"]
    assert 1 <= len(configs[CONFIG]["why"]) <= 200
    found = {m["name"]: m for m in spec.layer_metrics(spec.cell(CELL))}
    others = [w["name"] for w in bench["workloads"] if w["name"] != CELL]
    for name, kind in NEW_METRICS.items():
        assert declared[name]["workloads"] == [CELL]
        m = spec.load("layer_metrics", name)
        assert m["where"] == {"config.adapter": ["lfm2"]}
        assert (m["kind"], m["unit"], m["layer"], m["better"], m["moves"],
                m["source"]) == (
            kind, declared[name]["unit"], declared[name]["layer"],
            declared[name]["better"], "train_examples_per_s",
            declared[name]["source"])
        assert m["note"] and name in found
        for other in others:
            assert name not in {x["name"] for x in spec.layer_metrics(
                spec.cell(other))}, (name, other)
    # one set of kernel names behind each kernel's readings
    assert {found[n]["args"]["name"] for n in (
        "lfm2_flash_ms_per_step", "lfm2_flash_roofline_pct")} == {
        "^%?flash_(fwd|bwd_dq|bwd_dkv)"}
    assert {found[n]["args"]["name"] for n in (
        "lfm2_short_conv_kernel_ms_per_step", "lfm2_short_conv_hbm_pct")} == {
        "^%?short_conv_bwd"}
    assert {found[n]["args"]["name"] for n in (
        "lfm2_moe_gmm_ms_per_step", "lfm2_moe_gmm_calls_per_step")} == {
        "^%?moe_t?gmm"}
    assert found["lfm2_short_conv_hbm_pct"]["args"]["bound"] == (
        "hbm_bytes_per_s")
    for name in ("lfm2_flash_roofline_pct", "lfm2_fc_roofline_pct"):
        assert found[name]["args"]["bound"] == "bf16_flops"
    # the convolution's share reads the scopes that come with the op
    scope = re.compile(found["lfm2_conv_device_pct"]["args"]["scope"])
    for s in ("fwd/short_conv1d", "bwd/short_conv1d_grad"):
        assert scope.search(s), s
    for s in ("fwd/elementwise_mul", "fwd/mul", "opt/adam",
              "bwd/moe_experts_grad"):
        assert not scope.search(s), s
    assert re.search(found["lfm2_moe_device_pct"]["args"]["scope"],
                     "bwd/moe_experts_grad")
    # the metrics that list cells by name were not edited and do not gain
    # this cell; the other decoders' name their adapters and leave it out
    for name, m in declared.items():
        if name not in NEW_METRICS:
            assert CELL not in m.get("workloads", ()), name
    assert not set(found) & {
        "moe_device_pct", "latent_attn_device_pct", "flash_attn_ms_per_step",
        "flash_roofline_pct", "fc_roofline_pct", "mellum_fc_roofline_pct",
        "moe_gmm_ms_per_step", "kda_device_pct", "attn_gqa_device_pct",
        "joyai_flash_roofline_pct", "phi4_ssm_device_pct",
        "phi4_flash_roofline_pct", "qk_prep_hbm_pct"}
    # every accepted metric with no `workloads` list is read here too
    everywhere = {n for n, m in declared.items() if "workloads" not in m}
    assert everywhere <= set(found)
    # `BENCHMARK.json` only gained entries: the new ones are its last
    assert [m["name"] for m in bench["per_layer"]][-len(NEW_METRICS):] == (
        list(NEW_METRICS))
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == CONFIG


def test_the_cell_rehearses_at_a_large_seed():
    out = last_line(run_cell(["--workload", CELL, "--seed", "2147483777",
                              "--seconds", "2", "--trace", "0", "--rehearse"]))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 20 and out["metrics"] == {}
