"""Bench chip-session resumability (round 20).

A chip session that dies mid-bench (preemption, a lost machine) used to
cost the whole round. bench.py now checkpoints the full collected state
to a partial file after every workload (temp + os.replace), keyed on
the resolved pass signature; `--resume` restores the snapshot and runs
only the remainder. These tests drive the exact production loop
(bench._run_workloads) with an injectable workload list:

  - simulated mid-run abort (fault site bench.workload) -> the partial
    file survives with only the pre-abort workloads marked completed
  - --resume runs ONLY the remainder and the merged state is identical
    to an uninterrupted run
  - a workload that raises is recorded and the run goes on; when it
    was the headline workload, main() exits non-zero
  - a partial written under a different pass signature is void
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402
from paddle_tpu.resilience import faults  # noqa: E402


@pytest.fixture(autouse=True)
def _bench_state(tmp_path, monkeypatch):
    """Isolate and restore bench's module-level mutable state."""
    monkeypatch.setattr(bench.CLI, "partial_file", str(tmp_path / "p.json"))
    monkeypatch.setattr(bench.CLI, "resume", False)
    saved = (dict(bench._RESULTS), dict(bench._EXTRA), list(bench._ERRORS))
    bench._RESULTS.clear()
    bench._EXTRA.clear()
    bench._ERRORS[:] = []
    yield
    faults.clear()
    bench._RESULTS.clear()
    bench._RESULTS.update(saved[0])
    bench._EXTRA.clear()
    bench._EXTRA.update(saved[1])
    bench._ERRORS[:] = saved[2]


def _reset_collected():
    bench._RESULTS.clear()
    bench._EXTRA.clear()
    bench._ERRORS[:] = []


def _make_workloads(calls):
    """Three deterministic workloads writing fixed payloads — the same
    numbers no matter which session runs them, so merged-vs-uninterrupted
    comparison is meaningful."""

    def mk(name, value):
        def fn():
            calls.append(name)
            bench._EXTRA[name] = {"value": value}
            if name == "bert":
                bench._RESULTS["value"] = value
                bench._RESULTS["vs_baseline"] = value / 2.0
        return (name, fn, 0)

    return [mk("bert", 100.0), mk("transformer", 20.0), mk("resnet", 30.0)]


def _snapshot():
    return (
        dict(bench._RESULTS),
        {k: dict(v) for k, v in bench._EXTRA.items()},
        list(bench._ERRORS),
    )


def test_abort_preserves_partial_and_resume_matches_uninterrupted():
    # uninterrupted reference run
    calls = []
    assert bench._run_workloads(_make_workloads(calls)) is None
    assert calls == ["bert", "transformer", "resnet"]
    reference = _snapshot()
    partial = bench._load_partial_raw(bench._partial_path())
    assert set(partial["completed"]) == {"bert", "transformer", "resnet"}

    # fresh session, abort at the 2nd workload via the fault site
    os.unlink(bench._partial_path())
    _reset_collected()
    calls = []
    plan = faults.FaultPlan(seed=7).add(
        "bench.workload", raises="FaultError", nth=2
    )
    with faults.active(plan):
        with pytest.raises(faults.FaultError):
            bench._run_workloads(_make_workloads(calls))
    assert calls == ["bert"]
    partial = bench._load_partial_raw(bench._partial_path())
    assert set(partial["completed"]) == {"bert"}
    assert partial["extra"] == {"bert": {"value": 100.0}}
    assert partial["results"]["value"] == 100.0

    # next session resumes: only the remainder runs, merged state is
    # identical to the uninterrupted run
    _reset_collected()
    bench.CLI.resume = True
    calls = []
    assert bench._run_workloads(_make_workloads(calls)) is None
    assert calls == ["transformer", "resnet"]
    assert _snapshot() == reference


def test_failed_headline_workload_exits_nonzero(monkeypatch, capsys):
    """main() still prints its one JSON line, but a run whose headline
    workload raised (so no value was measured) does not exit 0."""
    calls = []
    workloads = _make_workloads(calls)

    def failing_bert():
        calls.append("bert")
        raise RuntimeError("Mosaic refused the kernel")

    workloads[0] = ("bert", failing_bert, 0)
    monkeypatch.setattr(bench, "_main_body",
                        lambda: bench._run_workloads(workloads))
    monkeypatch.setattr(bench, "_watchdog", lambda: None)
    monkeypatch.setattr(bench, "_EMITTED", bench.threading.Event())
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 1
    # the secondary workloads still ran, and the line names the failure
    assert calls == ["bert", "transformer", "resnet"]
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["value"] == 0.0
    assert "bert: RuntimeError: Mosaic refused the kernel" in line["error"]


def test_healthy_run_exits_zero(monkeypatch, capsys):
    workloads = _make_workloads([])
    monkeypatch.setattr(bench, "_main_body",
                        lambda: bench._run_workloads(workloads))
    monkeypatch.setattr(bench, "_watchdog", lambda: None)
    monkeypatch.setattr(bench, "_EMITTED", bench.threading.Event())
    bench.main()  # no SystemExit
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["value"] == 100.0 and "error" not in line


def test_workload_error_continues_and_checkpoints():
    calls = []
    workloads = _make_workloads(calls)
    workloads[1] = (
        "transformer",
        lambda: (_ for _ in ()).throw(ValueError("bad shape")),
        0,
    )
    assert bench._run_workloads(workloads) is None
    assert calls == ["bert", "resnet"]
    assert any("transformer: ValueError" in e for e in bench._ERRORS)
    # the errored workload IS marked completed: an uninterrupted run
    # would carry the same error entry, so --resume must not re-run it
    partial = bench._load_partial_raw(bench._partial_path())
    assert set(partial["completed"]) == {"bert", "transformer", "resnet"}
    assert partial["errors"] == bench._ERRORS


def test_stale_pass_signature_voids_partial():
    calls = []
    assert bench._run_workloads(_make_workloads(calls)) is None
    path = bench._partial_path()
    state = bench._load_partial_raw(path)
    state["completed"]["bert"] = "dce:999"  # signature from another world
    with open(path, "w") as f:
        json.dump(state, f)

    _reset_collected()
    bench.CLI.resume = True
    calls = []
    assert bench._run_workloads(_make_workloads(calls)) is None
    assert calls == ["bert", "transformer", "resnet"]


def test_checkpoint_is_atomic_no_temp_left_behind():
    calls = []
    assert bench._run_workloads(_make_workloads(calls)) is None
    d = os.path.dirname(bench._partial_path())
    assert [f for f in os.listdir(d) if ".tmp." in f] == []
