"""The reduction from a trace to numbers, on a hand-built trace with known
answers and on a small trace recorded on the chip."""

from __future__ import annotations

import gzip
import os
from types import SimpleNamespace as NS

import pytest

from benchmark.harness import spec
from benchmark.harness import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def profile(device_events, host_events, second_device=None):
    """Shaped like `jax.profiler.ProfileData`."""
    planes = [
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=[ev("jit_step", 0, 1000)]),
            NS(name="XLA Ops", events=device_events)]),
        NS(name="/host:CPU", lines=[
            NS(name="python", events=host_events)]),
        NS(name="/device:CUSTOM:metadata", lines=[]),
    ]
    if second_device is not None:
        planes.append(NS(name="/device:TPU:1", lines=[
            NS(name="XLA Ops", events=second_device)]))
    return NS(planes=planes)


@pytest.fixture
def synthetic():
    # device 0, ns:  [100,300) fusion.1 (matmul)   [300,400) copy.2
    #   gap [400,600)   [600,900) while.3 enclosing [650,750) fusion.4 and
    #   [800,850) ln_bwd   gap [900,950)   [950,1000) fusion.1 again
    device = [
        ev("%fusion.1 = bf16[8,8] fusion(%a, %b), kind=kOutput, calls=%f", 100, 200),
        ev("%copy.2 = bf16[8,8] copy(%fusion.1)", 300, 100),
        ev("%while.3 = (s32[]) while(%t), body=%b", 600, 300),
        ev("%add_fusion.4 = f32[8] fusion(%c), kind=kOutput, calls=%g", 650, 100),
        ev("%ln_bwd.5 = (bf16[8,8]) custom-call(%x)", 800, 50),
        ev("%fusion.1 = bf16[8,8] fusion(%a, %b), kind=kOutput, calls=%f", 950, 50),
    ]
    host = [
        ev("bench.exe_run", 0, 380), ev("bench.next_batch", 380, 40),
        ev("bench.read_loss", 420, 200),  # covers most of gap [400,600)
        ev("bench.exe_run", 890, 70),  # covers gap [900,950)
        ev("not.ours", 0, 1000),
    ]
    return tr.from_profile(profile(device, host))


def test_busy_union_idle_share_and_gaps(synthetic):
    ops = synthetic.devices["/device:TPU:0"]
    assert synthetic.window() == (100, 1000)
    assert tr.busy(ops) == [(100, 400), (600, 900), (950, 1000)]
    assert tr.idle_share(ops, (100, 1000)) == pytest.approx(250 / 900)
    assert tr.gaps(ops, (100, 1000)) == [(400, 600), (900, 950)]


def test_gaps_go_to_the_host_span_that_covers_most_of_them(synthetic):
    ops = synthetic.devices["/device:TPU:0"]
    assert [h.name for h in synthetic.host_spans].count("not.ours") == 0
    by = tr.attribute_gaps(tr.gaps(ops, (100, 1000)), synthetic.host_spans)
    assert by == {"bench.read_loss": 200, "bench.exe_run": 50}
    assert tr.attribute_gaps([(2000, 2100)], synthetic.host_spans) == {
        "(no bench span)": 100}


def test_self_time_shares_add_up_to_busy(synthetic):
    ops = synthetic.devices["/device:TPU:0"]
    selfs = {e.name.split(" ")[0]: t for e, t in tr.self_times(ops)
             if e.start != 950}
    assert selfs["%while.3"] == 150  # 300 less its two children
    assert sum(t for _, t in tr.self_times(ops)) == tr.length(tr.busy(ops))
    matmul = spec.load("layer_metrics", "xla_matmul_pct")["args"]["name"]
    copies = spec.load("layer_metrics", "xla_copy_pct")["args"]["name"]
    assert tr.share(ops, matmul) == pytest.approx(350 / 650)
    assert tr.share(ops, copies) == pytest.approx(100 / 650)
    assert tr.share(ops, "ln_bwd") == pytest.approx(50 / 650)


def test_kernel_sum_by_name_and_top_groups(synthetic):
    ops = synthetic.devices["/device:TPU:0"]
    assert tr.kernel(ops, "ln_bwd") == (1, 50)
    assert tr.kernel(ops, "flash_fwd") == (0, 0)
    assert tr.top_groups(ops, 2) == [("fusion/kOutput", 250), ("while", 150)]


def test_collective_time_in_the_operation_stream():
    # all-reduce.1 [100,200), synchronous. An asynchronous pair whose
    # start [300,310) and done [480,500) are in the stream, with a fusion
    # [320,450) between them that hides the rest, which is not counted. A
    # reduce-scatter [600,700) inside a while [550,800), not counted twice.
    device = [
        ev("%all-reduce.1 = f32[8] all-reduce(%g)", 100, 100),
        ev("%fusion.1 = f32[8] fusion(%a), kind=kLoop", 200, 100),
        ev("%all-gather-start.2 = (f32[2], f32[8]) all-gather-start(%x)", 300, 10),
        ev("%fusion.2 = f32[8] fusion(%a), kind=kLoop", 320, 130),
        ev("%all-gather-done.2 = f32[8] all-gather-done(%all-gather-start.2)",
           480, 20),
        ev("%while.3 = (f32[8]) while(%all-reduce.1)", 550, 250),
        ev("%reduce-scatter.4 = f32[2] reduce-scatter(%y)", 600, 100),
        ev("%fusion.5 = f32[8] fusion(%all-reduce.1), kind=kLoop", 700, 100),
    ]
    trace = tr.from_profile(profile(device, [], second_device=device[:2]))
    assert tr.collective(trace.devices["/device:TPU:0"]) == 100 + 10 + 20 + 100
    # the metric: ms a step, mean over the devices (the second has 100 ns)
    metric = spec.load("layer_metrics", "collective_in_stream_ms_per_step")
    got = spec.plugin("harness.sources", metric["kind"]).read(
        metric.get("args", {}), {"trace": trace, "traced": {"steps": 2}})
    assert got == pytest.approx((230 + 100) / 2 / 1e6 / 2)


def test_summary_averages_busy_over_devices_and_names_the_idlest():
    first = [ev("%fusion.1", 0, 1000)]
    second = [ev("%fusion.1", 0, 500)]
    s = tr.summarize(tr.from_profile(profile(first, [], second)))
    assert s["window_s"] == pytest.approx(1e-6)
    assert s["busy_s"] == pytest.approx(0.75e-6)
    assert s["worst_device"] == "/device:TPU:1"
    assert s["idle_gaps"] == [["(no bench span)", pytest.approx(0.5e-6)]]


def test_interval_arithmetic():
    assert tr.union([(5, 7), (1, 3), (2, 4), (9, 9)]) == [(1, 4), (5, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 11)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert tr.subtract([(0, 4)], []) == [(0, 4)]


def test_a_trace_with_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        tr.from_profile(profile([], [])).window()


RECORDED = os.path.join(DATA, "v5e_tiny_steps.xplane.pb.gz")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in the repo")
def test_recorded_chip_trace(tmp_path):
    """A trace of a few small steps recorded on the v5e by
    `benchmark/tests/record_trace.py`, which printed the expected numbers
    next to it (`v5e_tiny_steps.expected.json`)."""
    import json

    raw = tmp_path / "t.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        raw.write_bytes(f.read())
    with open(os.path.join(DATA, "v5e_tiny_steps.expected.json")) as f:
        want = json.load(f)
    trace = tr.load(str(raw))
    assert sorted(trace.devices) == want["devices"]
    ops = trace.devices[want["devices"][0]]
    assert len(ops) == want["events"]
    lo, hi = trace.window()
    assert hi - lo == pytest.approx(want["window_ns"])
    assert tr.length(tr.busy(ops)) == pytest.approx(want["busy_ns"])
    assert 0.0 < tr.idle_share(ops, (lo, hi)) < 1.0
    # the three steps were dispatched under bench.exe_run spans and the
    # device waited for the host between them
    names = {h.name for h in trace.host_spans}
    assert {"bench.exe_run", "bench.read_loss"} <= names
    by = tr.attribute_gaps(tr.gaps(ops, (lo, hi)), trace.host_spans)
    assert sum(by.values()) == pytest.approx(hi - lo - tr.length(tr.busy(ops)))
    assert max(by, key=by.get) == want["largest_gap_span"]
    calls, ns = tr.kernel(ops, want["kernel"])
    assert (calls, ns) == (want["kernel_calls"], pytest.approx(want["kernel_ns"]))
    assert tr.share(ops, r"\bkind=kOutput\b") == pytest.approx(
        want["matmul_share"])
