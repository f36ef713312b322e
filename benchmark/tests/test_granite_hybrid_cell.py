"""The cell `granite4_h_micro_vp8_longdoc` on the CPU: its configuration
against the catalog row, its traffic, its metrics looked up by name and by
`where` (it has none of its own: `per_layer` is full, and the two readers
of the `ssd_scan` scopes name Nemotron's adapter), `BENCHMARK.json`
mirroring the files, its tiny preset through the runner, and the reference
check there, which passes for the program and fails for the wrong models.
No number read here is a device number."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from benchmark.harness import spec
from benchmark.tests.test_harness import last_line, run_cell
from benchmark.tests.test_mechanisms import benchmark_json, check_cell_metrics

CELL = "granite4_h_micro_vp8_longdoc"
CONFIG = "granite4_h_micro_3b_vp8"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
# the catalog row's `config` (architectures.jsonl beside the model-configs
# guide), whole
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": PERIOD * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352,
}
HELD = {"num_hidden_layers": 10, "vocab_size": 12544}
# not `qk_prep` (the attention op norms nothing and turns nothing), not
# `experts`; the op `ssd_scan` has no word yet (ROADMAP Open item (1) (ii))
MECHANISMS = ["fc", "attention_op", "flash", "short_conv_kernel"]


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
                       if r["name"] == "granite-4.0-h-micro")
        assert row["config"] == PUBLISHED
        assert config["source"] == row["source_url"]
    # every key beside the published ones is the share's, the run's or
    # listed under `assumed`
    beside = set(config) - set(PUBLISHED) - {
        "source", "adapter", "mechanisms", "reduced", "deployment",
        "assumed", "rehearse"}
    assert beside == {
        "num_hidden_layers_published", "first_layer_held",
        "vocab_size_published", "head_dim", "initializer_range", "context",
        "optimizer", "precision", "loss_fall_margin"}
    assert (config["num_hidden_layers_published"], config["first_layer_held"],
            config["vocab_size_published"]) == (40, 0, 100352)
    assert config["adapter"] == "granite_hybrid"
    assert config["deployment"].startswith(
        "8 chips share the embedding and the head")
    for key in ("num_hidden_layers", "vocab_size", "parameters",
                "peak_memory", "head_dim", "block", "multipliers", "mlp",
                "mamba2", "decay_seeding", "conv_seeding", "attention",
                "positions", "initializer_range", "optimizer", "precision",
                "loss_fall_margin", "context", "tolerance"):
        assert config["assumed"][key], key
        assert "TO_FILL" not in config["assumed"][key], key
    # the floors: one whole period of the published list, an eighth of the
    # vocabulary; no width, no head count and no group is cut outside the
    # rehearsal
    adapter = spec.plugin("models", "granite_hybrid")
    assert [kind for _, kind in adapter.held_layers(config)] == PERIOD
    assert config["vocab_size"] * 8 == config["vocab_size_published"]
    assert (config["num_attention_heads"] * config["head_dim"]
            == config["hidden_size"])
    assert (config["mamba_n_heads"] * config["mamba_d_head"]
            == config["mamba_expand"] * config["hidden_size"])
    assert "772,160,448" in config["assumed"]["parameters"]
    # the rehearsal holds a run that starts inside the period, both kinds
    # of mixer in it, one group as published, rows of a chunk and a half
    tiny = config["rehearse"]
    assert (tiny["first_layer_held"], tiny["num_hidden_layers"]) == (4, 3)
    assert "mamba_n_groups" not in tiny and "layer_types" not in tiny
    for key in ("attention_multiplier", "embedding_multiplier",
                "residual_multiplier", "logits_scaling"):
        # the multipliers stay what they are but the attention's, which
        # keeps its eighth of the usual scale on heads of 16
        assert key not in tiny or key == "attention_multiplier"
    assert tiny["attention_multiplier"] * 8 == tiny["head_dim"] ** -0.5
    traffic = spec.load("traffic", "longdoc_b1_s4096")
    assert (traffic["batch"], traffic["seq_len"]) == (1, 4096)
    assert traffic["runner"] == "train_loop" and traffic["mesh"] is None
    assert 1 < traffic["rehearse"]["seq_len"] / tiny["mamba_chunk_size"] < 2
    c = spec.cell(CELL)
    assert c["chips"] == 1 and 1 <= len(c["why"]) <= 200


def test_the_cells_metrics_are_its_mechanisms():
    found = check_cell_metrics(CELL, CONFIG, list(HELD), "granite_hybrid",
                               {}, MECHANISMS)
    for name in ("fc_mxu_roofline_pct", "attn_device_pct",
                 "flash_ms_per_step", "flash_calls_per_step",
                 "flash_kernels_roofline_pct",
                 "short_conv_kernel_ms_per_step", "rms_bwd_ms_per_step",
                 "model_flops_util_pct", "peak_hbm_gb", "device_idle_pct"):
        assert name in found, name
    for name in ("flash_kernels_roofline_pct", "fc_mxu_roofline_pct"):
        assert found[name]["args"]["bound"] == "bf16_flops"
    # no file of its own (ISSUE 72: `per_layer` is full), and the readers
    # of the scopes and counters it shares stay bound to their adapters
    bench = benchmark_json()
    assert not [m["name"] for m in bench["per_layer"]
                if m.get("workloads") == [CELL]]
    assert not set(found) & {
        "nemotron_ssd_device_pct", "nemotron_ssd_roofline_pct",
        "phi4_ssm_device_pct", "moe_device_pct", "delta_rule_device_pct",
        "qk_prep_hbm_roofline_pct", "loss_device_pct", "flash_roofline_pct",
        "fc_roofline_pct", "flash_gqa_ms_per_step"}
    # every metric that lists the cell is one its traced run reads
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == {
        "fc_mxu_roofline_pct", "attn_device_pct", "flash_ms_per_step",
        "flash_calls_per_step", "flash_kernels_roofline_pct",
        "short_conv_kernel_ms_per_step", "rms_bwd_ms_per_step"}
    assert listed <= set(found)


def test_benchmark_json_mirrors_the_new_files():
    bench = benchmark_json()
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    assert configs[CONFIG]["file"] == f"benchmark/configs/{CONFIG}.json"
    assert cells[CELL] == {
        "name": CELL, "config": CONFIG, "traffic": "longdoc_b1_s4096",
        "chips": 1, "why": spec.load("workloads", CELL)["why"]}
    assert len(bench["per_layer"]) == len(spec.names("layer_metrics")) == 85
    # appended, and nothing else: the cell is the last of every list it is in
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL, m["name"]
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 1
    assert len(bench["workloads"]) == 18 and len(bench["configs"]) == 16


def test_the_adapters_count_is_the_programs():
    """`parameters` under `assumed` is the program's own count at the
    published widths, by shape inference alone, and the adapter's FLOPs
    count the table's product once."""
    import paddle_tpu as fluid
    from benchmark.runners import train_loop

    c = spec.cell(CELL)
    adapter = spec.plugin("models", "granite_hybrid")
    with fluid.program_guard(fluid.Program(), fluid.Program()), \
            fluid.unique_name.guard():
        main = train_loop.build_programs(fluid, adapter, c["config"],
                                         c["traffic"], 3)[0]
        held = sum(int(np.prod(p.shape))
                   for p in main.global_block().all_parameters())
    assert held == 772160448
    matrices = adapter.matrix_params_per_token(c["config"])
    # all but the convolutions' filters and biases, the decays, the skips
    # and the norms
    assert held - matrices == 9 * (4352 * 5 + 3 * 64 + 4096) + 21 * 2048
    assert 19.6e12 < adapter.flops_per_example(c["config"],
                                               c["traffic"]) < 19.7e12


def test_the_cell_rehearses_at_a_large_seed():
    out = last_line(run_cell(["--workload", CELL, "--seed", "2147483777",
                              "--seconds", "2", "--trace", "0", "--rehearse"]))
    assert out["correct"] is True and out["failed"] == 0
    # whole blocks of ten steps: one beside busy test workers
    assert out["attempted"] >= 10 and out["metrics"] == {}


def _checker(**config):
    """The tiny preset's programs and the reference check as the runner
    makes it, with a wrong model on request; the norms' weights and the
    skips moved off their seeded 1, so that a norm on the wrong side of
    the gate or a skip left off shows."""
    import paddle_tpu as fluid
    from benchmark.models import granite_hybrid as adapter
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
            if "norm" in p.name or p.name.endswith(".D"):
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
    width (0.1 x sqrt(64) = 0.8 a product, where 0.02 x sqrt(2048) = 0.9):
    at 0.02 a sublayer times 0.22 adds next to nothing to the stream."""
    yield from _checker(initializer_range=0.1)


def test_the_reference_check_passes_at_the_tiny_preset(checked):
    check = checked()
    assert check["ok"], check


def _wrong():
    from benchmark.models.granite_hybrid import WRONG

    return WRONG


@pytest.mark.parametrize("wrong", _wrong())
def test_the_reference_check_fails_for_a_wrong_model(checked_at_width, wrong):
    """Each entry of `WRONG` is refused by the cell's tolerance at the
    tiny preset, in the cell's precision, where the right reference reads
    under it; but for the two that touch only the attention's scores
    (`scale_rsqrt`, `rope`): under `attention_multiplier` the seeded
    scores are a tenth wide and the softmax all but uniform, so both move
    the logits by less than the program's own rounding, here as on the
    chip (PERF.md section 6, PR 72). The float32 program refuses both
    (tests/test_granite_hybrid_reference.py)."""
    from benchmark.models.granite_hybrid import TOLERANCE

    limit = TOLERANCE["logits_rel_rms"]
    right = checked_at_width()
    assert right["ok"] and right["logits_rel_rms"] < limit
    check = checked_at_width(wrong=(wrong,))
    if wrong in ("scale_rsqrt", "rope"):
        assert check["logits_rel_rms"] < limit, check
    else:
        assert not check["ok"], (wrong, check)
