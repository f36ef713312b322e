"""The harness end to end on the CPU, at the tiny presets: every cell
through the same runner, the result line's shape, the refusal to measure
without a TPU, new cells and metrics as added files only, and a wrong
reference caught. No number read here is a device number."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness import spec, stats

REPO = os.path.dirname(spec.BENCH_DIR)
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def run_cell(args, root=REPO, env=None):
    full = dict(os.environ, PYTHONPATH=REPO)
    full.update(env or {})
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        cwd=root, env=full, capture_output=True, text=True, timeout=600)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", spec.names("workloads"))
def test_every_cell_rehearses_through_the_runner(cell):
    """dp4 runs on four virtual devices. The traced run reports the
    per-layer metrics that are counts; times and rates are left out."""
    out = last_line(run_cell(["--workload", cell, "--seed", "3", "--seconds",
                              "2", "--trace", "1", "--rehearse"]))
    assert set(out) == CONTRACT_KEYS
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 20
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 4
    assert set(out["metrics"]) == {"compiles_in_window", "traced_ops"}
    assert out["metrics"]["compiles_in_window"] == {"value": 0, "unit": "count"}


def test_untraced_rehearsal_prints_no_metric_at_all():
    out = last_line(run_cell(["--workload", "transformer_base_s64", "--seed",
                              "4", "--seconds", "1", "--trace", "0",
                              "--rehearse"]))
    assert set(out) == CONTRACT_KEYS and out["metrics"] == {}


def test_measuring_without_a_tpu_fails_and_prints_no_result():
    proc = run_cell(["--workload", "bert_base_s128", "--seed", "0",
                     "--seconds", "1", "--trace", "0"],
                    env={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "no CPU fallback" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def copy_of_the_benchmark(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return root, root / "benchmark"


def test_a_resnet50_cell_needs_nothing_but_a_data_file(tmp_path):
    """The adapter, its configuration and its traffic mix ship without a
    cell; the PR that optimises ResNet-50 adds `workloads/<cell>.json` and
    the entry in BENCHMARK.json, and no code."""
    root, bench = copy_of_the_benchmark(tmp_path)
    (bench / "workloads" / "resnet50_b128.json").write_text(json.dumps({
        "config": "resnet50_imagenet", "traffic": "imagenet_b128", "chips": 1,
        "why": "added by a test"}))
    out = last_line(run_cell(["--workload", "resnet50_b128", "--seed", "6",
                              "--seconds", "2", "--trace", "1", "--rehearse"],
                             root=str(root)))
    assert out["correct"] is True and out["attempted"] >= 10
    assert out["metrics"]["traced_ops"]["value"] > 0


def test_a_cell_a_mix_a_metric_and_a_source_kind_are_added_as_files_only(tmp_path):
    """A later PR adds files and edits none: the harness finds a new cell,
    its traffic mix, a new per-layer metric on an existing source kind,
    and a new source kind with its metric (which is how a derived metric
    or a kernel's roofline share arrives) by their names."""
    root, bench = copy_of_the_benchmark(tmp_path)
    (bench / "traffic" / "added_mix.json").write_text(json.dumps({
        "runner": "train_loop", "pool_batches": 3, "mesh": None,
        "batch": 6, "seq_len": 24, "masked_positions": 5}))
    (bench / "workloads" / "added_cell.json").write_text(json.dumps({
        "config": "bert_base_pretrain", "traffic": "added_mix", "chips": 1,
        "why": "added by a test"}))
    metric = {"layer": "Program to passes", "unit": "count", "better": "lower",
              "source": "program_counter", "moves": "setup_s",
              "where": {"traffic.name": ["added_mix"]}}
    (bench / "layer_metrics" / "ops_after_passes.json").write_text(json.dumps({
        **metric, "kind": "counter_delta",
        "args": {"counters": ["program_ops_after"], "phase": "setup"}}))
    (bench / "harness" / "sources" / "added_kind.py").write_text(
        "def read(args, r):\n"
        "    cell = r['cell']\n"
        "    return args['per_row'] * cell['traffic']['batch']\n")
    (bench / "layer_metrics" / "rows_times_three.json").write_text(json.dumps({
        **metric, "kind": "added_kind", "args": {"per_row": 3}}))
    out = last_line(run_cell(["--workload", "added_cell", "--seed", "5",
                              "--seconds", "1", "--trace", "1", "--rehearse"],
                             root=str(root)))
    assert out["correct"] is True
    assert out["metrics"]["ops_after_passes"]["value"] > 0
    assert out["metrics"]["rows_times_three"] == {"value": 18, "unit": "count"}
    # and the cells that were there do not report the new metrics
    there = [m["name"] for m in spec.layer_metrics(spec.cell("bert_base_s128"))]
    assert "ops_after_passes" not in there and "rows_times_three" not in there


@pytest.mark.parametrize("config,traffic", [
    ("bert_base_pretrain", "bert_phase1_b256_s128"),
    ("transformer_base_wmt", "wmt_pairs_b256_s64"),
    ("resnet50_imagenet", "imagenet_b128")])
def test_reference_agrees_with_the_program_and_a_wrong_one_is_caught(
        config, traffic):
    """At the tiny preset on the CPU the program (bf16 products, float32
    accumulation) stays within 3% of the float32 reference's logits,
    relative to their root-mean-square: bf16 rounds each input at 2^-9,
    and two small layers measured 0.5-1%. With one layer left out of the
    reference the same comparison fails, so `correct` would be false."""
    import paddle_tpu as fluid
    from paddle_tpu.scope import Scope

    from benchmark.runners import train_loop

    c = spec.resolve({"config": config, "traffic": traffic, "chips": 1},
                     rehearse=True)
    config, traffic = c["config"], c["traffic"]
    adapter = spec.plugin("models", config["adapter"])
    with fluid.program_guard(fluid.Program(), fluid.Program()), \
            fluid.unique_name.guard(), fluid.scope_guard(Scope()):
        main, startup, built, eval_prog = train_loop.build_programs(
            fluid, adapter, config, traffic, seed=7)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        # at width 64 and below, weights drawn at 0.02 leave every layer a
        # small correction to the embeddings, and a missing layer would
        # hardly show: scale each matrix to unit gain, as the published
        # widths nearly have (0.02 * sqrt(768) = 0.55)
        scope = fluid.global_scope()
        for p in main.global_block().all_parameters():
            if (p.name.endswith(".w_0") and len(p.shape) == 2
                    and config["adapter"] != "resnet50"):
                scope.set(p.name, scope.get(p.name)
                          / (0.02 * np.sqrt(p.shape[0])))
        batch = adapter.make_batch(np.random.RandomState(7), config, traffic)
        args = (fluid, exe, adapter, eval_prog, built, batch, config, traffic)
        good = train_loop.reference_check(*args)
        bad = train_loop.reference_check(*args, drop_layers=1)
    print(good, bad)
    assert good["ok"], good
    assert good["logits_rel_rms"] < 0.02
    assert not bad["ok"], bad
    assert bad["logits_rel_rms"] > 3 * adapter.TOLERANCE["logits_rel_rms"]


def test_seeded_batches_repeat_and_differ_by_seed():
    c = spec.cell("bert_base_s128", rehearse=True)
    adapter = spec.plugin("models", c["config"]["adapter"])
    make = lambda seed: adapter.make_batch(  # noqa: E731
        np.random.RandomState(seed), c["config"], c["traffic"])
    a, b, other = make(1), make(1), make(2)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["src_ids"], other["src_ids"])
    # Zipf: the most frequent id is drawn far more often than a uniform
    # draw would give it
    ids = make(3)["mask_label"]
    assert (ids == 0).mean() > 5.0 / c["config"]["vocab_size"]


def test_flops_per_example_match_the_programs_own_count():
    """The copied arithmetic agrees with the original it was copied from
    (the BERT copy adds the 2*h*h of the MLM transform, which the original
    leaves out: 0.1% at BERT-base)."""
    from paddle_tpu.models.bert import bert_flops_per_token
    from paddle_tpu.models.transformer import transformer_flops_per_trg_token

    c = spec.cell("bert_base_s128")
    bert = spec.plugin("models", "bert")
    s, p = c["traffic"]["seq_len"], c["traffic"]["masked_positions"]
    theirs = s * bert_flops_per_token(bert.config(c["config"]), s, p)
    assert bert.flops_per_example(c["config"], c["traffic"]) == pytest.approx(
        theirs, rel=2e-3)
    c = spec.cell("transformer_base_s64")
    tf = spec.plugin("models", "transformer")
    theirs = 64 * transformer_flops_per_trg_token(tf.config(c["config"]), 64, 64)
    assert tf.flops_per_example(c["config"], c["traffic"]) == pytest.approx(theirs)


def test_benchmark_json_mirrors_the_files_and_keeps_the_contracts_limits():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"]
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= bench["run_seconds"] <= 51

    cells = {w["name"]: w for w in bench["workloads"]}
    assert sorted(cells) == spec.names("workloads")
    for name, w in cells.items():
        on_disk = spec.load("workloads", name)
        assert {k: w[k] for k in ("config", "traffic", "chips", "why")} == {
            k: on_disk[k] for k in ("config", "traffic", "chips", "why")}
        assert NAME.match(name) and len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 4)

    configs = {c["name"]: c for c in bench["configs"]}
    assert set(configs) == {w["config"] for w in cells.values()}
    for name, c in configs.items():
        assert c["file"] == f"benchmark/configs/{name}.json"
        on_disk = spec.load("configs", name)
        assert c["source"] == on_disk["source"] and len(c["source"]) <= 200
        assert c["reduced"] == on_disk["reduced"] == []

    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert sorted(per_layer) == spec.names("layer_metrics")
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in end_to_end
    for name, m in per_layer.items():
        on_disk = spec.load("layer_metrics", name)
        for k in ("unit", "better", "source", "layer", "moves"):
            assert m[k] == on_disk[k], (name, k)
        assert m["moves"] in end_to_end
        reported_in = sorted(
            n for n in cells
            if name in [x["name"] for x in spec.layer_metrics(spec.cell(n))])
        assert m.get("workloads", sorted(cells)) == reported_in, name
    for m in list(per_layer.values()) + list(end_to_end.values()):
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in end_to_end.values():
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1


def test_order_statistics():
    xs = list(range(1, 102))  # 1..101
    assert stats.quartiles(xs) == (26.0, 51.0, 76.0)
    assert stats.highest_percentile(19) is None
    assert stats.highest_percentile(20) == 50.0
    assert stats.highest_percentile(200) == 95.0
    assert stats.highest_percentile(1000) == 99.0
