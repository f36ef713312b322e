"""The cell `sdar_30b_a3b_ep8_s4096` on the CPU: its configuration against
the catalog row, its traffic, its metrics looked up by name and by `where`
(it has none of its own: the mask runs inside the flash kernels, whose
readings are the mechanisms'), `BENCHMARK.json` mirroring the files, its
tiny preset through the runner, and the reference check there, which
passes for the program and fails for the wrong models. No number read
here is a device number."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from benchmark.harness import spec
from benchmark.tests.test_harness import last_line, run_cell
from benchmark.tests.test_mechanisms import benchmark_json, check_cell_metrics

CELL = "sdar_30b_a3b_ep8_s4096"
CONFIG = "sdar_30b_a3b_ep8"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's `config` (architectures.jsonl beside the model-configs
# guide), whole
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}
HELD = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 18992}
MECHANISMS = ["fc", "attention_op", "flash", "qk_prep", "experts",
              "grouped_kernels"]


def test_configuration_is_the_catalogs_but_for_the_share():
    with open(os.path.join(spec.BENCH_DIR, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    assert config["reduced"] == list(HELD)
    for key, value in PUBLISHED.items():
        assert config[key] == HELD.get(key, value), key
    if os.path.exists(CATALOG):  # the row itself, where the guide is there
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SDAR-30B-A3B-Chat")
        assert row["config"] == PUBLISHED
        assert config["source"] == row["source_url"]
        assert set(row["not_given"]) == {"block length", "noise schedule"}
    # every key beside the published ones is the share's, the run's or
    # listed under `assumed`
    beside = set(config) - set(PUBLISHED) - {
        "source", "adapter", "mechanisms", "reduced", "deployment", "assumed",
        "rehearse"}
    assert beside == {
        "num_experts_published", "held_from", "num_hidden_layers_published",
        "first_layer_held", "vocab_size_published", "block_length",
        "mask_token_id", "noise_floor", "initializer_range",
        "embedding_initializer_range", "context", "optimizer", "precision",
        "loss_fall_margin"}
    assert (config["num_experts_published"], config["held_from"],
            config["num_hidden_layers_published"], config["first_layer_held"],
            config["vocab_size_published"]) == (128, 0, 48, 0, 151936)
    assert config["adapter"] == "sdar"
    assert config["deployment"].startswith("8 chips share each layer")
    for key in ("num_hidden_layers", "num_experts", "vocab_size",
                "block_length", "schedule", "targets", "mask_token_id",
                "training_layout", "positions", "qk_norm", "norms", "router",
                "balancing_loss", "initializer_range",
                "embedding_initializer_range", "optimizer", "precision",
                "loss_fall_margin", "context", "tolerance", "peak_memory"):
        assert config["assumed"][key], key
    # the floors: four layers behind no dense one, at least 8 routed
    # experts, an eighth of the vocabulary; no width is cut
    assert config["num_experts"] >= 8 and config["num_hidden_layers"] >= 4
    assert config["vocab_size"] * 8 == config["vocab_size_published"]
    # the mask's id is the slice's last row, and the block divides the row
    assert config["mask_token_id"] == config["vocab_size"] - 1
    assert config["block_length"] == 4 and 0.0 <= config["noise_floor"] < 1.0
    tiny = config["rehearse"]
    assert tiny["block_length"] == 4 and tiny["num_hidden_layers"] == 2
    assert tiny["mask_token_id"] == tiny["vocab_size"] - 1
    traffic = spec.load("traffic", "longdoc_b1_s4096")
    assert (traffic["batch"], traffic["seq_len"]) == (1, 4096)
    assert traffic["runner"] == "train_loop" and traffic["mesh"] is None
    assert traffic["seq_len"] % config["block_length"] == 0
    assert traffic["rehearse"]["seq_len"] % tiny["block_length"] == 0
    c = spec.cell(CELL)
    assert c["chips"] == 1 and 1 <= len(c["why"]) <= 200


def test_the_cells_metrics_are_its_mechanisms():
    found = check_cell_metrics(CELL, CONFIG, list(HELD), "sdar", {},
                               MECHANISMS)
    for name in ("flash_ms_per_step", "flash_calls_per_step",
                 "flash_kernels_roofline_pct", "fc_mxu_roofline_pct",
                 "attn_device_pct", "qk_prep_hbm_roofline_pct",
                 "moe_device_pct", "moe_dispatch_device_pct",
                 "moe_gmm_ms_per_step", "moe_gmm_calls_per_step",
                 "moe_grouped_ms_per_step", "moe_grouped_calls_per_step",
                 "moe_held_load_pct", "rms_bwd_ms_per_step",
                 "model_flops_util_pct", "peak_hbm_gb"):
        assert name in found, name
    for name in ("flash_kernels_roofline_pct", "fc_mxu_roofline_pct"):
        assert found[name]["args"]["bound"] == "bf16_flops"
    # no file of its own (ISSUE 68: `per_layer` is full), and the two
    # readers of the counters it bumps stay bound to their adapters
    bench = benchmark_json()
    assert not [m["name"] for m in bench["per_layer"]
                if m.get("workloads") == [CELL]]
    assert not set(found) & {
        "keye_pairs_admitted_pct", "flash_blocks_visited_pct",
        "keye_sparse_attn_device_pct", "delta_rule_device_pct",
        "loss_device_pct", "flash_roofline_pct", "fc_roofline_pct",
        "flash_gqa_ms_per_step", "short_conv_kernel_ms_per_step"}


def test_benchmark_json_mirrors_the_new_files():
    bench = benchmark_json()
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    assert configs[CONFIG]["file"] == f"benchmark/configs/{CONFIG}.json"
    assert cells[CELL] == {
        "name": CELL, "config": CONFIG, "traffic": "longdoc_b1_s4096",
        "chips": 1, "why": spec.load("workloads", CELL)["why"]}
    assert len(bench["per_layer"]) == len(spec.names("layer_metrics"))
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 1


def test_the_cell_rehearses_at_a_large_seed():
    out = last_line(run_cell(["--workload", CELL, "--seed", "2147483777",
                              "--seconds", "2", "--trace", "0", "--rehearse"]))
    assert out["correct"] is True and out["failed"] == 0
    # whole blocks of ten steps: one beside busy test workers
    assert out["attempted"] >= 10 and out["metrics"] == {}


def test_the_traced_rehearsal_notes_the_masks_counters():
    """The build's and the lowerings' counters, on a line of the run's
    log: the pairs the mask admits of the doubled row's causal pairs (a
    rehearsal row of 48 tokens in blocks of 4: 48 x 4 + 48 x 44 / 2 + 48
    x 52 / 2 = 2,496 of 96 x 97 / 2)."""
    proc = run_cell(["--workload", CELL, "--seed", "5", "--seconds", "2",
                     "--trace", "1", "--rehearse"])
    out = last_line(proc)
    assert out["correct"] is True
    (note,) = [line for line in proc.stdout.splitlines()
               if line.startswith("block diffusion:")]
    layers, rows = 2, 2
    assert f"block 4, {layers} layers built, loss_terms 1" in note
    assert (f"attn_pairs_admitted {layers * rows * 2496} of "
            f"attn_pairs_causal {layers * rows * 4656} = 53.61%") in note
    assert out["metrics"]["moe_held_load_pct"]["value"] > 0


def _checker(**config):
    """The tiny preset's programs and the reference check as the runner
    makes it, with a wrong model on request; the norms' weights moved off
    their seeded 1, so that a norm left out shows."""
    import paddle_tpu as fluid
    from benchmark.models import sdar as adapter
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
def checked_at_width():
    """With the matrices seeded nearly as wide as they weigh at the
    published width (0.07 x sqrt(64) = 0.56 a product, where 0.02 x
    sqrt(2048) = 0.9) and the embedding at 0.3: at 0.02 a layer adds a
    thousandth of the stream and a wrong mask hardly shows; at 0.1 the
    bf16 program itself reads 0.0094 here, over the cell's limit, where
    the chip's reads 0.0045."""
    yield from _checker(initializer_range=0.07,
                        embedding_initializer_range=0.3)


def test_the_reference_check_passes_at_the_tiny_preset(checked_at_width):
    check = checked_at_width()
    assert check["ok"], check


def _wrong():
    from benchmark.models.sdar import WRONG

    return WRONG


@pytest.mark.parametrize("wrong", _wrong())
def test_the_reference_check_fails_for_a_wrong_model(checked_at_width, wrong):
    """Each departure of the mask, the positions and the norm is refused
    at the tiny preset, in the cell's precision, by the logits' limit.
    Those of the loss alone leave the logits as they are and move the
    loss: by 5.7 (no weights) and 0.021 (the next token's label) on the
    chip, over the limit; here, over a vocabulary of 128, a shifted label
    moves it by 0.008, many times what the right reference reads."""
    from benchmark.models.sdar import TOLERANCE

    right = checked_at_width()
    check = checked_at_width(wrong=(wrong,))
    if wrong in ("shifted_targets", "unweighted_loss"):
        assert check["logits_rel_rms"] == right["logits_rel_rms"]
        assert check["loss_abs"] > max(5 * right["loss_abs"], 0.005), (
            wrong, check)
        assert not check["ok"] or wrong == "shifted_targets", (wrong, check)
    else:
        assert not check["ok"], (wrong, check)
        assert check["logits_rel_rms"] > TOLERANCE["logits_rel_rms"], (
            wrong, check)
