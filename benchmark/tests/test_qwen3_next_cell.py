"""The cell `qwen3_next_ep16_s4096` on the CPU: its configuration against
the catalog row, its traffic, its metrics looked up by name and by `where`
(and not at the tail of a list that the next PR appends to), its tiny
preset through the runner, and the reference check there, which passes for
the program and fails for the wrong models. No number read here is a
device number."""

from __future__ import annotations

import json
import os
import re

import numpy as np
import pytest

from benchmark.harness import spec
from benchmark.tests.test_harness import last_line, run_cell

CELL = "qwen3_next_ep16_s4096"
CONFIG = "qwen3_next_80b_a3b_ep16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's `config` (architectures.jsonl beside the model-configs
# guide), whole
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
HELD = {"num_hidden_layers": 4, "num_experts": 32, "vocab_size": 18992}
NEW_METRICS = {
    "qwen3next_gdn_device_pct": "trace_scope_share",
    "qwen3next_gdn_kernel_ms_per_step": "trace_kernel",
    "qwen3next_gdn_roofline_pct": "trace_roofline",
    "qwen3next_short_conv_kernel_ms_per_step": "trace_kernel",
    "qwen3next_attn_device_pct": "trace_scope_share",
    "qwen3next_flash_ms_per_step": "trace_kernel",
    "qwen3next_flash_roofline_pct": "trace_roofline",
    "qwen3next_moe_device_pct": "trace_scope_share",
    "qwen3next_moe_gmm_ms_per_step": "trace_kernel",
    "qwen3next_moe_gmm_calls_per_step": "trace_kernel_calls",
    "qwen3next_fc_roofline_pct": "trace_roofline",
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
                       if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert row["config"] == PUBLISHED
        assert config["source"] == row["source_url"]
    # every key beside the published ones is the share's, the run's or
    # listed under `assumed`
    beside = set(config) - set(PUBLISHED) - {
        "source", "adapter", "reduced", "deployment", "assumed", "rehearse"}
    assert beside == {
        "num_experts_published", "held_from", "num_hidden_layers_published",
        "first_layer_held", "vocab_size_published", "initializer_range",
        "l2norm_epsilon", "context", "optimizer", "precision",
        "loss_fall_margin"}
    assert (config["num_experts_published"], config["held_from"],
            config["num_hidden_layers_published"], config["first_layer_held"],
            config["vocab_size_published"]) == (512, 0, 48, 0, 151936)
    assert config["adapter"] == "qwen3_next"
    assert config["deployment"].startswith("16 chips share each layer")
    for key in ("num_hidden_layers", "num_experts", "vocab_size",
                "intermediate_size", "norms", "gated_delta_net",
                "projection_order", "decay_seeding", "conv_seeding",
                "attention", "positions", "router", "shared_expert", "mtp",
                "initializer_range", "optimizer", "precision",
                "loss_fall_margin", "context"):
        assert config["assumed"][key], key
    # the floors: a whole period (the model has no leading dense layer)
    # of at least four layers, at least 8 routed experts a layer, an
    # eighth of the vocabulary; no width is cut outside the rehearsal
    adapter = spec.plugin("models", "qwen3_next")
    assert [kind for _, kind in adapter.held_layers(config)] == (
        ["linear_attention"] * 3 + ["full_attention"])
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["vocab_size_published"]
    assert config["num_experts"] * 16 == config["num_experts_published"]
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
    assert configs[CONFIG]["source"] == spec.load("configs", CONFIG)["source"]
    assert 1 <= len(configs[CONFIG]["why"]) <= 200
    found = {m["name"]: m for m in spec.layer_metrics(spec.cell(CELL))}
    others = [w["name"] for w in bench["workloads"] if w["name"] != CELL]
    for name, kind in NEW_METRICS.items():
        assert declared[name]["workloads"] == [CELL]
        m = spec.load("layer_metrics", name)
        assert m["where"] == {"config.adapter": ["qwen3_next"]}
        assert (m["kind"], m["unit"], m["layer"], m["better"], m["moves"],
                m["source"]) == (
            kind, declared[name]["unit"], declared[name]["layer"],
            declared[name]["better"], "train_examples_per_s",
            declared[name]["source"])
        assert m["note"] and name in found
        for other in others:
            assert name not in {x["name"] for x in spec.layer_metrics(
                spec.cell(other))}, (name, other)
    # one set of kernel names behind each kernel's readings, and the
    # delta rule's two never read each other's events
    assert {found[n]["args"]["name"] for n in (
        "qwen3next_gdn_kernel_ms_per_step",
        "qwen3next_gdn_roofline_pct")} == {"^%?gdn_(fwd|bwd)"}
    kimis = spec.load("layer_metrics", "kda_roofline_pct")["args"]["name"]
    for mine, theirs in (("gdn_fwd", "kda_fwd"), ("gdn_bwd", "kda_bwd")):
        assert re.search("^%?gdn_(fwd|bwd)", mine)
        assert not re.search("^%?gdn_(fwd|bwd)", theirs)
        assert re.search(kimis, theirs) and not re.search(kimis, mine)
    assert {found[n]["args"]["name"] for n in (
        "qwen3next_flash_ms_per_step", "qwen3next_flash_roofline_pct")} == {
        "^%?flash_(fwd|bwd_dq|bwd_dkv)"}
    assert re.search("^%?flash_(fwd|bwd_dq|bwd_dkv)", "flash_bwd_dkv_dq")
    assert {found[n]["args"]["name"] for n in (
        "qwen3next_moe_gmm_ms_per_step",
        "qwen3next_moe_gmm_calls_per_step")} == {"^%?moe_t?gmm"}
    for name in ("qwen3next_gdn_roofline_pct", "qwen3next_flash_roofline_pct",
                 "qwen3next_fc_roofline_pct"):
        assert found[name]["args"]["bound"] == "bf16_flops"
    # the delta rule's share reads the op's own scopes and not the
    # convolution's, which has a kernel reading of its own
    scope = re.compile(found["qwen3next_gdn_device_pct"]["args"]["scope"])
    for s in ("fwd/kda_attention", "bwd/kda_attention_grad"):
        assert scope.search(s), s
    for s in ("fwd/short_conv1d", "fwd/mul", "opt/adam",
              "bwd/moe_experts_grad"):
        assert not scope.search(s), s
    assert re.search(found["qwen3next_moe_device_pct"]["args"]["scope"],
                     "bwd/moe_experts_grad")
    # the metrics that list cells by name were not edited and do not gain
    # this cell; the other decoders' name their adapters and leave it out
    for name, m in declared.items():
        if name not in NEW_METRICS:
            assert CELL not in m.get("workloads", ()), name
    assert not set(found) & {
        "moe_device_pct", "kda_device_pct", "kda_kernel_ms_per_step",
        "kda_roofline_pct", "flash_attn_ms_per_step", "flash_roofline_pct",
        "fc_roofline_pct", "moe_gmm_ms_per_step", "lfm2_fc_roofline_pct",
        "lfm2_moe_gmm_calls_per_step", "mellum_fc_roofline_pct",
        "qk_prep_hbm_pct", "attn_gqa_device_pct"}
    # every accepted metric with no `workloads` list is read here too
    everywhere = {n for n, m in declared.items() if "workloads" not in m}
    assert everywhere <= set(found)


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
    from benchmark.models import qwen3_next as adapter
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
    width (0.1 x sqrt(64) = 0.8 a product, where 0.02 x sqrt(2048) = 0.9):
    at 0.02 a mixer adds next to nothing to a stream 64 wide and no wrong
    model shows."""
    yield from _checker(initializer_range=0.1)


def test_the_reference_check_passes_at_the_tiny_preset(checked):
    check = checked()
    assert check["ok"], check


def _wrong():
    from benchmark.models.qwen3_next import WRONG

    return WRONG


@pytest.mark.parametrize("wrong", _wrong())
def test_the_reference_check_fails_for_a_wrong_model(checked_at_width, wrong):
    """Each entry of `WRONG` is refused by the cell's logits' limit at the
    tiny preset, in the cell's precision, where the right reference reads
    under it (the loss here is a mean of 96 bf16 losses, too coarse for
    its limit to say anything); but for QK-norm after the positions, which
    with the norms' weights at their seeded 1 is the same model (a
    rotation keeps a head's length) and is caught where the weights are
    moved and the program is float32 (tests/test_qwen3_next_reference.py)."""
    from benchmark.models.qwen3_next import TOLERANCE

    limit = TOLERANCE["logits_rel_rms"]
    assert checked_at_width()["logits_rel_rms"] < limit
    check = checked_at_width(wrong=(wrong,))
    if wrong == "norm_after_rope":
        assert check["logits_rel_rms"] < limit, check
    else:
        assert not check["ok"] and check["logits_rel_rms"] > limit, (
            wrong, check)
