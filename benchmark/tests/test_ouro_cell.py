"""The cell `ouro_2p6b_vp8_s4096` on the CPU: its configuration against
the catalog row, its traffic, its metrics looked up by name (and not at
the tail of a list that the next PR appends to), and its tiny preset
through the runner. No number read here is a device number."""

from __future__ import annotations

import json
import os
import re

from benchmark.harness import spec
from benchmark.tests.test_harness import last_line, run_cell

CELL = "ouro_2p6b_vp8_s4096"
CONFIG = "ouro_2p6b_vp8"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's `config` (architectures.jsonl beside the model-configs
# guide), whole
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152,
}
NEW_METRICS = {
    "ouro_attn_device_pct": "trace_scope_share",
    "ouro_flash_ms_per_step": "trace_kernel",
    "ouro_flash_calls_per_step": "trace_kernel_calls",
    "ouro_flash_roofline_pct": "trace_roofline",
    "ouro_fc_roofline_pct": "trace_roofline",
    "ouro_norm_device_pct": "trace_scope_share",
    "ouro_loss_device_pct": "trace_scope_share",
    "ouro_grad_sum_device_pct": "trace_scope_share",
    "ouro_uses_per_shared_weight": "counter_ratio",
}


def _benchmark():
    with open(os.path.join(os.path.dirname(spec.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        return json.load(f)


def test_configuration_is_the_catalogs_but_for_the_share():
    with open(os.path.join(spec.BENCH_DIR, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    held = {"num_hidden_layers": config["num_hidden_layers"],
            "vocab_size": 6144}
    assert config["reduced"] == list(held) == ["num_hidden_layers",
                                               "vocab_size"]
    # six layers if they fit by ISSUE 57's rule, else four: a whole
    # pipeline stage of eight, or the guide's floor
    assert held["num_hidden_layers"] in (4, 6)
    for key, value in PUBLISHED.items():
        assert config[key] == held.get(key, value), key
    if os.path.exists(CATALOG):  # the row itself, where the guide is there
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ouro-2.6B")
        assert row["config"] == PUBLISHED
        assert config["source"] == row["source_url"]
    # every key beside the published ones is the share's, the run's or
    # listed under `assumed`
    beside = set(config) - set(PUBLISHED) - {
        "source", "adapter", "reduced", "deployment", "assumed", "rehearse"}
    assert beside == {
        "num_hidden_layers_published", "first_layer_held",
        "vocab_size_published", "entropy_weight", "initializer_range",
        "embedding_initializer_range", "context", "optimizer", "precision",
        "loss_fall_margin"}
    assert (config["num_hidden_layers_published"], config["first_layer_held"],
            config["vocab_size_published"]) == (48, 0, 49152)
    assert config["adapter"] == "ouro"
    assert config["deployment"].startswith(
        "8 chips share the embedding and the head")
    for key in ("num_hidden_layers", "vocab_size", "sandwich_norms",
                "loop", "exit_gate", "loss", "entropy_weight", "attention",
                "initializer_range", "embedding_initializer_range",
                "optimizer", "precision", "loss_fall_margin", "context",
                "peak_memory"):
        assert config["assumed"][key], key
    # the floors: the loop whole, an eighth of the vocabulary, at least
    # four layers; no width is cut outside the rehearsal
    assert config["total_ut_steps"] == 4
    assert config["vocab_size"] * 8 >= config["vocab_size_published"]
    assert (config["num_attention_heads"] * config["head_dim"]
            == config["hidden_size"])
    assert config["rehearse"]["num_hidden_layers"] == 2
    assert "total_ut_steps" not in config["rehearse"]  # kept at 4
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
    assert configs[CONFIG]["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert configs[CONFIG]["source"] == spec.load("configs", CONFIG)["source"]
    assert 1 <= len(configs[CONFIG]["why"]) <= 200
    found = {m["name"]: m for m in spec.layer_metrics(spec.cell(CELL))}
    others = [w["name"] for w in bench["workloads"] if w["name"] != CELL]
    for name, kind in NEW_METRICS.items():
        assert declared[name]["workloads"] == [CELL]
        m = spec.load("layer_metrics", name)
        assert m["where"] == {"config.adapter": ["ouro"]}
        assert (m["kind"], m["unit"], m["layer"], m["better"], m["moves"],
                m["source"]) == (
            kind, declared[name]["unit"], declared[name]["layer"],
            declared[name]["better"], "train_examples_per_s",
            declared[name]["source"])
        assert name in found
        for other in others:
            assert name not in {x["name"] for x in spec.layer_metrics(
                spec.cell(other))}, (name, other)
    # one set of kernel names behind the three flash readings: the
    # forward and the one-visit backward, which is what a call with a
    # key/value head to every query head gets
    flash = {found[n]["args"]["name"] for n in (
        "ouro_flash_ms_per_step", "ouro_flash_calls_per_step",
        "ouro_flash_roofline_pct")}
    assert flash == {"^%?flash_(fwd|bwd_dkv_dq)"}
    (pattern,) = flash
    assert re.search(pattern, "%flash_fwd.12")
    assert re.search(pattern, "flash_bwd_dkv_dq")
    assert not re.search(pattern, "flash_bwd_dq")
    # the scopes that come with the ops
    for name, hits, misses in (
            ("ouro_norm_device_pct", ("fwd/rms_norm", "bwd/rms_norm_grad"),
             ("fwd/mul", "fwd/layer_norm")),
            ("ouro_loss_device_pct",
             ("fwd/softmax_with_cross_entropy",
              "bwd/softmax_with_cross_entropy_grad"), ("fwd/softmax",)),
            ("ouro_grad_sum_device_pct", ("bwd/sum",),
             ("fwd/sum", "bwd/reduce_sum_grad", "opt/sum")),
            ("ouro_attn_device_pct",
             ("fwd/fused_multihead_attention",
              "bwd/fused_multihead_attention_grad"),
             ("fwd/rotary_embedding",))):
        scope = re.compile(found[name]["args"]["scope"])
        assert all(scope.search(s) for s in hits), name
        assert not any(scope.search(s) for s in misses), name
    ratio = found["ouro_uses_per_shared_weight"]["args"]
    assert (ratio["numerator"], ratio["denominator"], ratio["phase"]) == (
        "param_grad_partials", "param_grads_summed", "setup")
    # the other decoders' metrics name their adapters and leave this cell out
    assert not set(found) & {
        "moe_device_pct", "latent_attn_device_pct", "flash_attn_ms_per_step",
        "flash_roofline_pct", "fc_roofline_pct", "phi4_flash_roofline_pct",
        "nemotron_fc_roofline_pct", "kda_device_pct", "attn_gqa_device_pct",
        "joyai_loss_device_pct"}
    # every accepted metric with no `workloads` list is read here too,
    # the whole step's share of the peak among them
    everywhere = {n for n, m in declared.items() if "workloads" not in m}
    assert "model_flops_util_pct" in everywhere <= set(found)
    # and every metric that lists this cell is one this cell's run reads
    assert {n for n, m in declared.items()
            if CELL in m.get("workloads", ())} <= set(found)
    assert {n for n, m in declared.items()
            if CELL in m.get("workloads", ())} == set(NEW_METRICS)


def test_the_cell_rehearses_at_a_large_seed():
    out = last_line(run_cell(["--workload", CELL, "--seed", "2147483777",
                              "--seconds", "2", "--trace", "0", "--rehearse"]))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 20 and out["metrics"] == {}
