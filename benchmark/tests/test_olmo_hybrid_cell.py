"""The cell `olmo_hybrid_7b_vp8_longdoc` on the CPU: its configuration
against the catalog row, its traffic, its metrics looked up by name and by
`where` (and not at the tail of a list that the next PR appends to),
`BENCHMARK.json` mirroring the files, its tiny preset through the runner,
and the reference check there, which passes for the program and fails for
the wrong models. No number read here is a device number."""

from __future__ import annotations

import json
import os
import re

import numpy as np
import pytest

from benchmark.harness import spec
from benchmark.tests.test_harness import last_line, run_cell
from benchmark.tests.test_mechanisms import benchmark_json, check_cell_metrics

CELL = "olmo_hybrid_7b_vp8_longdoc"
CONFIG = "olmo_hybrid_7b_vp8"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# the catalog row's `config` (architectures.jsonl beside the model-configs
# guide), whole
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
}
HELD = {"num_hidden_layers": 4, "vocab_size": 12544}
# what Olmo-Hybrid alone has: its three mixer ops' share of the device and
# what the delta-rule kernels' layout wastes of the lanes it multiplies;
# the rest are its mechanisms' (PR 62)
OWN_METRICS = {"olmo_hybrid_mixer_device_pct": "trace_scope_share",
               "olmo_hybrid_delta_rule_lanes_used_pct": "counter_ratio"}
# not `qk_prep`: the attention op norms nothing and turns nothing here
MECHANISMS = ["fc", "attention_op", "flash", "delta_rule",
              "short_conv_kernel"]


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
                       if r["name"] == "Olmo-Hybrid-7B")
        assert row["config"] == PUBLISHED
        assert config["source"] == row["source_url"]
    # every key beside the published ones is the share's, the run's or
    # listed under `assumed`
    beside = set(config) - set(PUBLISHED) - {
        "source", "adapter", "mechanisms", "reduced", "deployment",
        "assumed", "rehearse"}
    assert beside == {
        "num_hidden_layers_published", "first_layer_held",
        "vocab_size_published", "head_dim", "initializer_range",
        "embedding_initializer_range",
        "l2norm_epsilon", "context", "optimizer", "precision",
        "loss_fall_margin"}
    assert (config["num_hidden_layers_published"], config["first_layer_held"],
            config["vocab_size_published"]) == (32, 0, 100352)
    assert config["adapter"] == "olmo_hybrid"
    assert config["deployment"].startswith(
        "8 chips share the embedding and the head")
    for key in ("num_hidden_layers", "vocab_size", "parameters",
                "peak_memory", "head_dim", "norms", "block",
                "gated_delta_net", "beta", "projection_order",
                "decay_seeding", "conv_seeding", "attention", "positions",
                "mlp", "initializer_range", "embedding_initializer_range",
                "optimizer", "precision",
                "loss_fall_margin", "context"):
        assert config["assumed"][key], key
    # the floors: one whole period of the published list, an eighth of the
    # vocabulary, every head; no width is cut outside the rehearsal
    adapter = spec.plugin("models", "olmo_hybrid")
    assert [kind for _, kind in adapter.held_layers(config)] == PERIOD
    assert config["vocab_size"] * 8 == config["vocab_size_published"]
    assert (config["num_attention_heads"] * config["head_dim"]
            == config["hidden_size"])
    # the rehearsal keeps key and value heads of two widths, neither a
    # multiple of the other's tile
    tiny = config["rehearse"]
    assert (tiny["linear_key_head_dim"], tiny["linear_value_head_dim"]) == (
        24, 48)
    assert "linear_allow_neg_eigval" not in tiny  # kept: beta in (0, 2)
    traffic = spec.load("traffic", "longdoc_b1_s4096")
    assert (traffic["batch"], traffic["seq_len"]) == (1, 4096)
    assert traffic["runner"] == "train_loop" and traffic["mesh"] is None
    c = spec.cell(CELL)
    assert c["chips"] == 1 and 1 <= len(c["why"]) <= 200


def test_the_cells_metrics_are_its_own_and_its_mechanisms():
    found = check_cell_metrics(CELL, CONFIG, list(HELD), "olmo_hybrid",
                               OWN_METRICS, MECHANISMS)
    # the delta rule's one pattern finds the kernels this cell runs
    for name in ("delta_rule_kernel_ms_per_step", "delta_rule_roofline_pct"):
        pattern = found[name]["args"]["name"]
        assert all(re.search(pattern, k) for k in ("gdn_fwd", "%gdn_bwd.3"))
    for name in ("delta_rule_roofline_pct", "flash_kernels_roofline_pct",
                 "fc_mxu_roofline_pct"):
        assert found[name]["args"]["bound"] == "bf16_flops"
    # the mixers' share reads the three mixer ops' scopes and nothing of
    # the dense products, the norms or the optimizer
    scope = re.compile(found["olmo_hybrid_mixer_device_pct"]["args"]["scope"])
    for s in ("fwd/kda_attention", "bwd/kda_attention_grad",
              "fwd/short_conv1d", "bwd/short_conv1d_grad",
              "fwd/fused_multihead_attention",
              "bwd/fused_multihead_attention_grad"):
        assert scope.search(s), s
    for s in ("fwd/mul", "bwd/mul_grad", "fwd/rms_norm", "opt/adam",
              "bwd/rms_norm_grad", "fwd/swish"):
        assert not scope.search(s), s
    ratio = found["olmo_hybrid_delta_rule_lanes_used_pct"]
    assert (ratio["args"], ratio["better"], ratio["source"]) == (
        {"numerator": "delta_rule_lanes_published",
         "denominator": "delta_rule_lanes_computed", "phase": "setup",
         "scale": 100}, "higher", "program_counter")
    assert found["olmo_hybrid_mixer_device_pct"]["better"] == "lower"
    assert not set(found) & {
        "moe_device_pct", "moe_grouped_ms_per_step", "flash_roofline_pct",
        "fc_roofline_pct", "kda_roofline_pct", "kda_kernel_ms_per_step",
        "qwen3next_gdn_kernel_ms_per_step", "qk_prep_hbm_roofline_pct",
        "loss_device_pct"}


def test_benchmark_json_mirrors_the_new_files():
    bench = benchmark_json()
    assert bench["configs"][-1]["name"] == CONFIG
    assert bench["workloads"][-1] == {
        "name": CELL, "config": CONFIG, "traffic": "longdoc_b1_s4096",
        "chips": 1, "why": spec.load("workloads", CELL)["why"]}
    assert [m["name"] for m in bench["per_layer"][-2:]] == list(OWN_METRICS)
    assert len(bench["per_layer"]) == 83
    # appended, and nothing else: the cell is the last of every list it is in
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL, m["name"]
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 1


def test_the_cell_rehearses_at_a_large_seed():
    out = last_line(run_cell(["--workload", CELL, "--seed", "2147483777",
                              "--seconds", "2", "--trace", "0", "--rehearse"]))
    assert out["correct"] is True and out["failed"] == 0
    # whole blocks of ten steps: 40 alone, one beside busy test workers
    assert out["attempted"] >= 10 and out["metrics"] == {}


def test_the_traced_rehearsal_reads_the_lanes_counters():
    """On the CPU the plain path runs, which multiplies the published
    lanes: the counter's metric is there and reads 100."""
    out = last_line(run_cell(["--workload", CELL, "--seed", "5", "--seconds",
                              "2", "--trace", "1", "--rehearse"]))
    assert out["correct"] is True
    used = out["metrics"]["olmo_hybrid_delta_rule_lanes_used_pct"]
    assert used == {"value": 100.0, "unit": "%"}


def _checker(**config):
    """The tiny preset's programs and the reference check as the runner
    makes it, with a wrong model on request; the norms' weights moved off
    their seeded 1, so that a norm on the wrong side of its sublayer or a
    statistic a head shows."""
    import paddle_tpu as fluid
    from benchmark.models import olmo_hybrid as adapter
    from benchmark.runners import train_loop

    c = spec.cell(CELL, rehearse=True)
    model, traffic = dict(c["config"], **config), c["traffic"]
    with fluid.program_guard(fluid.Program(), fluid.Program()), \
            fluid.unique_name.guard(), fluid.scope_guard(fluid.Scope()):
        main, startup, built, eval_prog = train_loop.build_programs(
            fluid, adapter, model, traffic, 3)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope, r = fluid.global_scope(), np.random.RandomState(3)
        for p in main.global_block().all_parameters():
            if p.name.endswith("norm.w_0"):
                scope.set(p.name, r.uniform(0.5, 1.5, p.shape).astype(
                    np.float32))
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
    width (0.1 x sqrt(64) = 0.8 a product, where 0.02 x sqrt(3840) = 1.2):
    at 0.02 beta's logits sit at 0 and a wrong beta hardly shows."""
    yield from _checker(initializer_range=0.1)


def test_the_reference_check_passes_at_the_tiny_preset(checked):
    check = checked()
    assert check["ok"], check


def _wrong():
    from benchmark.models.olmo_hybrid import WRONG

    return WRONG


@pytest.mark.parametrize("wrong", _wrong())
def test_the_reference_check_fails_for_a_wrong_model(checked_at_width, wrong):
    """Each entry of `WRONG` is refused by the cell's logits' limit at the
    tiny preset, in the cell's precision, where the right reference reads
    under it; but for the solve's products read in bf16, which is what the
    program's own bf16 activations round to, and QK-norm a head at a
    time, which moves the logits by about the program's own reading (on
    the chip 2.3% beside 1.7%): both are caught where the program is
    float32 (tests/test_olmo_hybrid_reference.py)."""
    from benchmark.models.olmo_hybrid import TOLERANCE

    limit = TOLERANCE["logits_rel_rms"]
    right = checked_at_width()["logits_rel_rms"]
    assert right < limit
    check = checked_at_width(wrong=(wrong,))
    if wrong == "bf16_solve":
        assert check["logits_rel_rms"] < limit, check
    elif wrong == "per_head_qk_norm":
        # with the norms' weights moved it shows here, on either side of
        # the limit by the batch (4.6% and 11.3% seen)
        assert check["logits_rel_rms"] > 1.5 * right, check
    else:
        assert not check["ok"] and check["logits_rel_rms"] > limit, (
            wrong, check)
