"""The cell `phi4_mini_flash_vp8_longdoc` on the CPU: its configuration
against the catalog row, its traffic, its metrics looked up by name (and
not at the tail of a list that the next PR appends to), and its tiny
preset through the runner. No number read here is a device number."""

from __future__ import annotations

import json
import os

from benchmark.harness import spec
from benchmark.tests.test_harness import last_line, run_cell

CELL = "phi4_mini_flash_vp8_longdoc"
CONFIG = "phi4_mini_flash_3p8b_vp8"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's `config` (architectures.jsonl beside the model-configs
# guide), whole
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064,
}
HELD = {"num_hidden_layers": 6, "vocab_size": 25008}
NEW_METRICS = {
    "phi4_ssm_device_pct": "trace_scope_share",
    "phi4_attn_device_pct": "trace_scope_share",
    "phi4_flash_ms_per_step": "trace_kernel",
    "phi4_flash_calls_per_step": "trace_kernel_calls",
    "phi4_flash_roofline_pct": "trace_roofline",
    "phi4_fc_roofline_pct": "trace_roofline",
}


def _benchmark():
    with open(os.path.join(os.path.dirname(spec.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        return json.load(f)


def test_configuration_is_the_catalogs_but_for_the_share():
    with open(os.path.join(spec.BENCH_DIR, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    assert config["reduced"] == list(HELD) == ["num_hidden_layers",
                                               "vocab_size"]
    for key, value in PUBLISHED.items():
        assert config[key] == HELD.get(key, value), key
    if os.path.exists(CATALOG):  # the row itself, where the guide is there
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Phi-4-mini-flash-reasoning")
        assert row["config"] == PUBLISHED
        assert config["source"] == row["source_url"]
    # every key beside the published ones is the share's, the run's or
    # listed under `assumed`
    beside = set(config) - set(PUBLISHED) - {
        "source", "adapter", "reduced", "deployment", "assumed", "rehearse"}
    assert beside == {
        "num_hidden_layers_published", "first_layer_held",
        "vocab_size_published", "mamba_d_state", "mamba_d_conv",
        "mamba_expand", "mamba_dt_rank", "initializer_range", "context",
        "optimizer", "precision", "loss_fall_margin"}
    assert (config["num_hidden_layers_published"], config["first_layer_held"],
            config["vocab_size_published"]) == (32, 14, 200064)
    assert config["adapter"] == "phi4_flash"
    assert config["deployment"].startswith(
        "8 chips share the embedding and the head")
    for key in ("num_hidden_layers", "layer_ratio", "vocab_size",
                "mamba_sizes", "mamba_seeding", "biases", "mlp",
                "differential_attention", "window", "memory", "shared_kv",
                "initializer_range", "optimizer", "precision",
                "loss_fall_margin", "context"):
        assert config["assumed"][key], key
    # the floors: six kinds of layer in the shortest run that holds them,
    # an eighth of the vocabulary; no width is cut outside the rehearsal
    adapter = spec.plugin("models", "phi4_flash")
    assert sorted(k for _, k in adapter.held_layers(config)) == [
        "cross", "full", "gmu", "mamba", "mamba", "window"]
    assert config["vocab_size"] * 8 >= config["vocab_size_published"]
    assert (config["mamba_expand"] * config["hidden_size"],
            config["mamba_dt_rank"] * 16) == (5120, config["hidden_size"])
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
        assert m["where"] == {"config.adapter": ["phi4_flash"]}
        assert (m["kind"], m["unit"], m["layer"], m["better"], m["moves"]) == (
            kind, declared[name]["unit"], declared[name]["layer"],
            declared[name]["better"], "train_examples_per_s")
        assert name in found
        for other in others:
            assert name not in {x["name"] for x in spec.layer_metrics(
                spec.cell(other))}, (name, other)
    # one set of kernel names behind the three flash readings
    flash = {found[n]["args"]["name"] for n in (
        "phi4_flash_ms_per_step", "phi4_flash_calls_per_step",
        "phi4_flash_roofline_pct")}
    assert flash == {"^%?flash_(fwd|bwd_dq|bwd_dkv)"}
    # the scan's share reads the scopes that come with the op
    import re

    scope = re.compile(found["phi4_ssm_device_pct"]["args"]["scope"])
    for s in ("fwd/selective_scan", "bwd/selective_scan_grad",
              "fwd/short_conv1d", "bwd/short_conv1d_grad"):
        assert scope.search(s), s
    assert not scope.search("fwd/mul") and not scope.search("opt/adam")
    for name in ("attn_short_ms_per_step", "ln_bwd_ms_per_step"):
        assert CELL in declared[name]["workloads"] and name in found
    # the other decoders' metrics name their adapters and leave this cell out
    assert not set(found) & {
        "moe_device_pct", "latent_attn_device_pct", "flash_attn_ms_per_step",
        "flash_roofline_pct", "fc_roofline_pct", "mellum_fc_roofline_pct",
        "moe_gmm_ms_per_step", "kda_device_pct", "attn_gqa_device_pct",
        "joyai_flash_roofline_pct"}
    # every accepted metric with no `workloads` list is read here too
    everywhere = {n for n, m in declared.items() if "workloads" not in m}
    assert everywhere <= set(found)
    # and every metric that lists this cell is one this cell's run reads
    assert {n for n, m in declared.items()
            if CELL in m.get("workloads", ())} <= set(found)
    # nothing an accepted metric said changed: lists only grew, by this cell
    for name, m in declared.items():
        if name not in NEW_METRICS and CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL


def test_the_cell_rehearses_at_a_large_seed():
    out = last_line(run_cell(["--workload", CELL, "--seed", "2147483777",
                              "--seconds", "2", "--trace", "0", "--rehearse"]))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 20 and out["metrics"] == {}
