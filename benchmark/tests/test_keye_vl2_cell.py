"""The cell `keye_vl2_ep16_s8192` on the CPU: its configuration against
the catalog row, its traffic, its metrics looked up by name (and not at
the tail of a list that the next PR appends to), and its tiny preset
through the runner. No number read here is a device number."""

from __future__ import annotations

import json
import os
import re

from benchmark.harness import spec
from benchmark.tests.test_harness import last_line, run_cell

CELL = "keye_vl2_ep16_s8192"
CONFIG = "keye_vl2_30b_a3b_ep16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's `config` (architectures.jsonl beside the model-configs
# guide), whole
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
# six, which is what the contract's 128 per-layer metrics leave beside the
# 122 there: the mechanism's share of the step, the share of the kernels'
# pairs that count, and each kernel's share of its roof (whose notes in a
# traced run's log give the kernel's ms a step)
NEW_METRICS = {
    "keye_sparse_attn_device_pct": "trace_scope_share",
    "keye_pairs_admitted_pct": "counter_ratio",
    "keye_flash_roofline_pct": "trace_roofline",
    "keye_sparse_index_roofline_pct": "trace_roofline",
    "keye_sparse_select_roofline_pct": "trace_roofline",
    "keye_index_kl_target_roofline_pct": "trace_roofline",
}


def _benchmark():
    with open(os.path.join(os.path.dirname(spec.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        return json.load(f)


def test_configuration_is_the_catalogs_but_for_the_share():
    with open(os.path.join(spec.BENCH_DIR, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    held = {"num_hidden_layers": 4, "num_experts": 8, "vocab_size": 18992}
    assert config["reduced"] == list(held)
    for key, value in PUBLISHED.items():
        assert config[key] == held.get(key, value), key
    if os.path.exists(CATALOG):  # the row itself, where the guide is there
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert row["config"] == PUBLISHED
        assert config["source"] == row["source_url"]
    # every key beside the published ones is the share's, the run's or
    # listed under `assumed`
    beside = set(config) - set(PUBLISHED) - {
        "source", "adapter", "reduced", "deployment", "assumed", "rehearse"}
    assert beside == {
        "num_experts_published", "held_from", "num_hidden_layers_published",
        "first_layer_held", "vocab_size_published", "initializer_range",
        "embedding_initializer_range", "indexer_layer_norm_eps",
        "index_loss_weight", "context", "optimizer", "precision",
        "loss_fall_margin"}
    assert (config["num_experts_published"], config["held_from"],
            config["num_hidden_layers_published"], config["first_layer_held"],
            config["vocab_size_published"]) == (128, 0, 48, 0, 151936)
    assert config["adapter"] == "keye_vl2"
    assert config["deployment"].startswith("16 chips share each layer")
    assert "text rows only" in config["deployment"]
    for key in ("num_hidden_layers", "num_experts", "vocab_size", "tower",
                "positions", "qk_norm", "norms", "indexer",
                "indexer_positions", "chunk_sizes", "selection", "index_loss",
                "router", "balancing_loss", "initializer_range",
                "embedding_initializer_range", "optimizer", "precision",
                "loss_fall_margin", "context", "tolerance", "peak_memory"):
        assert config["assumed"][key], key
    # the floors: four layers behind no dense one, 8 routed experts, an
    # eighth of the vocabulary; no width is cut, and not the selection's K
    assert config["num_experts"] >= 8 and config["num_hidden_layers"] >= 4
    assert config["vocab_size"] * 8 == config["vocab_size_published"]
    assert config["index_loss_weight"] == 1.0
    tiny = config["rehearse"]
    assert tiny["sa_config"]["topk"] == 16 and tiny["num_hidden_layers"] == 2
    assert set(tiny["sa_config"]) == set(PUBLISHED["sa_config"])
    assert sum(tiny["rope_scaling"]["mrope_section"]) == tiny["head_dim"] // 2
    traffic = spec.load("traffic", "longdoc_b1_s8192")
    assert (traffic["batch"], traffic["seq_len"]) == (1, 8192)
    assert traffic["runner"] == "train_loop" and traffic["mesh"] is None
    # the selection bites at both sizes: four times K, and three times
    assert traffic["seq_len"] == 4 * config["sa_config"]["topk"]
    assert traffic["rehearse"]["seq_len"] == 3 * tiny["sa_config"]["topk"]
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
    assert configs[CONFIG]["reduced"] == ["num_hidden_layers", "num_experts",
                                          "vocab_size"]
    assert configs[CONFIG]["source"] == spec.load("configs", CONFIG)["source"]
    assert 1 <= len(configs[CONFIG]["why"]) <= 200
    found = {m["name"]: m for m in spec.layer_metrics(spec.cell(CELL))}
    others = [w["name"] for w in bench["workloads"] if w["name"] != CELL]
    for name, kind in NEW_METRICS.items():
        assert declared[name]["workloads"] == [CELL]
        m = spec.load("layer_metrics", name)
        assert m["where"] == {"config.adapter": ["keye_vl2"]}
        assert (m["kind"], m["unit"], m["layer"], m["better"], m["moves"],
                m["source"]) == (
            kind, declared[name]["unit"], declared[name]["layer"],
            declared[name]["better"], "train_examples_per_s",
            declared[name]["source"])
        assert name in found
        for other in others:
            assert name not in {x["name"] for x in spec.layer_metrics(
                spec.cell(other))}, (name, other)
    assert len(bench["per_layer"]) <= 128
    # the flash kernels a call with an admission gets, and each kernel this
    # model brings, behind its share of its roof
    flash = found["keye_flash_roofline_pct"]["args"]
    assert flash == {"name": "^%?flash_(fwd|bwd_dkv_dq)",
                     "bound": "bf16_flops"}
    for kernel, hits, bound in (
            ("sparse_index", ("%sparse_index_fwd.3", "sparse_index_bwd"),
             "bf16_flops"),
            ("sparse_select", ("sparse_select", "%sparse_select.1"),
             "hbm_bytes_per_s"),
            ("index_kl_target", ("index_kl_target",), "bf16_flops")):
        roof = found[f"keye_{kernel}_roofline_pct"]["args"]
        assert roof["bound"] == bound
        assert all(re.search(roof["name"], h) for h in hits), kernel
        assert not re.search(roof["name"], "flash_fwd")
        assert not re.search(roof["name"], "fusion.sparse_index")
    # the scopes that come with the four ops of the mechanism
    scope = re.compile(found["keye_sparse_attn_device_pct"]["args"]["scope"])
    assert all(scope.search(s) for s in (
        "fwd/sparse_index", "bwd/sparse_index_grad", "fwd/sparse_select",
        "fwd/index_kl", "bwd/index_kl_grad", "fwd/fused_multihead_attention",
        "bwd/fused_multihead_attention_grad"))
    assert not any(scope.search(s) for s in (
        "fwd/mul", "fwd/rotary_embedding", "fwd/rms_norm", "fwd/moe_experts",
        "opt/fused_adam"))
    ratio = found["keye_pairs_admitted_pct"]["args"]
    assert (ratio["numerator"], ratio["denominator"], ratio["phase"],
            ratio["scale"]) == ("attn_pairs_admitted", "attn_pairs_causal",
                                "setup", 100)
    # the other decoders' metrics name their adapters and leave this cell out
    assert not set(found) & {
        "moe_device_pct", "latent_attn_device_pct", "flash_attn_ms_per_step",
        "flash_roofline_pct", "fc_roofline_pct", "mellum_flash_roofline_pct",
        "mellum_moe_device_pct", "flash_gqa_ms_per_step",
        "ouro_flash_ms_per_step", "joyai_loss_device_pct"}
    # every accepted metric with no `workloads` list is read here too,
    # the whole step's share of the peak among them
    everywhere = {n for n, m in declared.items() if "workloads" not in m}
    assert "model_flops_util_pct" in everywhere <= set(found)
    # and the metrics that list this cell are this PR's, each read here
    assert {n for n, m in declared.items()
            if CELL in m.get("workloads", ())} == set(NEW_METRICS)


def test_the_cell_rehearses_at_a_large_seed():
    out = last_line(run_cell(["--workload", CELL, "--seed", "2147483777",
                              "--seconds", "2", "--trace", "0", "--rehearse"]))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 20 and out["metrics"] == {}
