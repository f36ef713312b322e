"""The program's own names read back from a trace: the event metadata of
the raw `.xplane.pb` (`harness/xplane_meta.py`) on the trace recorded on
the v5e, and the two source kinds that read scopes and `pt.*` spans on a
hand-built trace with known answers."""

from __future__ import annotations

import os

import pytest

from benchmark.harness import spec, trace_reduce, xplane_meta
from benchmark.harness.sources import trace_scope_share

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "v5e_tiny_steps.xplane.pb.gz")


def test_event_metadata_of_the_recorded_chip_trace():
    (plane, events), = xplane_meta.read(RECORDED).items()
    assert plane == "/device:TPU:0" and len(events) == 11
    (ln_bwd,) = [s for name, s in events.items() if name.startswith("%ln_bwd.1 = ")]
    assert ln_bwd["tf_op"] == "jit(step)/ln_bwd/pallas_call:"
    assert ln_bwd["hlo_category"] == "custom-call"
    assert ln_bwd["source"].startswith(
        "/root/repo/paddle_tpu/ops/pallas/layer_norm.py:")
    (dot,) = [s for s in events.values() if s.get("tf_op", "").endswith(
        "dot_general:")]
    assert dot["flops"] == 2_149_580_800 and dot["bytes_accessed"] == 4_194_308
    assert dot["hlo_category"] == "convolution fusion"
    # `ln_bwd` is a name scope, and not one of a Program op's phase
    assert {xplane_meta.scope(s.get("tf_op")) for s in events.values()} == {""}


@pytest.mark.parametrize("tf_op, scope, phase_op", [
    ("jit(step)/fwd/matmul/dot_general:", "fwd/matmul/dot_general", "fwd/matmul"),
    ("jit(step)/bwd/mul_grad/transpose(jvp())/transpose:",
     "bwd/mul_grad/transpose(jvp())/transpose", "bwd/mul_grad"),
    ("jit(step)/while/body/opt/fused_adam/mul:", "opt/fused_adam/mul",
     "opt/fused_adam"),
    ("jit(step)/checkpoint(fwd/gelu/tanh):", "fwd/gelu/tanh)", "fwd/gelu"),
    ("jit(step)/ln_bwd/pallas_call:", "", ""),
    ("jit(step)/prefwd/x:", "", ""),
    (None, "", ""),
])
def test_scope_is_the_name_stack_from_the_phase_on(tf_op, scope, phase_op):
    assert xplane_meta.scope(tf_op) == scope
    assert xplane_meta.phase_op(scope) == phase_op


# ----------------------------------------------------- a hand-built trace

# device 0, in ms (the trace's clock starts 1000 ns earlier):
#   [100,300) fusion.1  fwd/matmul      [300,400) copy.2  fwd/transpose2
#   gap [400,600)
#   [600,900) while.3 (unscoped) enclosing [650,750) fusion.4 bwd/matmul_grad
#     and [800,850) fusion.5 opt/fused_adam
#   gap [900,950)       [950,1000) copy.6, no tf_op at all
# so busy 650: fwd 300, bwd 100, opt 50, unscoped 150 (while's own) + 50
# device 1 runs fusion.1 alone: fwd 100%
OPS = [  # id, name, tf_op, start, duration
    (1, "%fusion.1 = bf16[8,8] fusion(%a), kind=kOutput",
     "jit(step)/fwd/matmul/dot_general:", 100, 200),
    (2, "%copy.2 = bf16[8,8] copy(%fusion.1)",
     "jit(step)/fwd/transpose2/transpose:", 300, 100),
    (3, "%while.3 = (s32[]) while(%t), body=%b", "jit(step)/while:", 600, 300),
    (4, "%fusion.4 = f32[8] fusion(%c), kind=kOutput",
     "jit(step)/while/body/bwd/matmul_grad/transpose(jvp())/dot_general:",
     650, 100),
    (5, "%fusion.5 = f32[8] fusion(%d), kind=kLoop",
     "jit(step)/while/body/opt/fused_adam/mul:", 800, 50),
    (6, "%copy.6 = f32[8] copy(%e)", None, 950, 50),
]
HOST = [  # name, start, duration: two steps
    ("bench.exe_run", 0, 390), ("pt.exe.prepare", 10, 40),
    ("pt.exe.compile", 20, 10), ("pt.exe.state", 50, 100),
    ("pt.exe.dispatch", 150, 200), ("pt.exe.writeback", 350, 30),
    ("bench.read_loss", 400, 190),
    ("bench.exe_run", 600, 390), ("pt.exe.prepare", 610, 20),
    ("pt.exe.state", 630, 60), ("pt.exe.dispatch", 690, 280),
    ("pt.exe.writeback", 970, 10),
    ("not.ours", 0, 1000),
]
STAGER = [("pt.reader.stage", 200, 30), ("pt.reader.stage", 400, 350)]


def xspace(ops, host_lines, scoped=True) -> bytes:
    from jax.profiler import ProfileData

    def events(rows, ids):
        return " ".join(
            f"events {{ metadata_id: {ids[name]} offset_ps: {start * 10**9} "
            f"duration_ps: {dur * 10**9} }}" for name, start, dur in rows)

    def device(number, ops):
        meta = " ".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{name}" '
            + (f'stats {{ metadata_id: 1 str_value: "{tf_op}" }} '
               if tf_op and scoped else "")
            + "stats { metadata_id: 2 uint64_value: 7 } } }"
            for i, name, tf_op, _, _ in ops)
        rows = [(name, start, dur) for _, name, _, start, dur in ops]
        ids = {name: i for i, name, _, _, _ in ops}
        return (f'planes {{ name: "/device:TPU:{number}" lines {{ name: '
                f'"XLA Ops" timestamp_ns: 1000 {events(rows, ids)} }} {meta} '
                'stat_metadata { key: 1 value { id: 1 name: "tf_op" } } '
                'stat_metadata { key: 2 value { id: 2 name: "flops" } } }')

    names = sorted({name for rows in host_lines for name, _, _ in rows})
    ids = {name: i + 1 for i, name in enumerate(names)}
    host = ('planes { name: "/host:CPU" ' + " ".join(
        f'lines {{ id: {n} name: "python" timestamp_ns: 1000 '
        f"{events(rows, ids)} }}" for n, rows in enumerate(host_lines)) + " "
        + " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                   f'"{name}" }} }}' for name, i in ids.items()) + " }")
    return ProfileData.text_proto_to_serialized_xspace(
        device(0, ops) + device(1, ops[:1]) + host)


def reading_of(tmp_path, serialized: bytes, steps=2) -> dict:
    """What `run.py` hands a source kind after a traced run."""
    (tmp_path / "host.xplane.pb").write_bytes(serialized)
    return {"traced": {"dir": str(tmp_path), "steps": steps}, "notes": [],
            "trace": trace_reduce.load(str(tmp_path))}


def metric(name, reading):
    m = spec.load("layer_metrics", name)
    return spec.plugin("harness.sources", m["kind"]).read(m["args"], reading)


def test_scope_shares_of_busy_time_and_the_table_of_program_ops(tmp_path):
    r = reading_of(tmp_path, xspace(OPS, [HOST, STAGER]))
    # mean over the two devices of the shares of self time
    assert metric("bwd_device_pct", r) == pytest.approx(50 * 100 / 650)
    assert metric("fwd_device_pct", r) == pytest.approx(50 * (300 / 650 + 1))
    assert metric("opt_device_pct", r) == pytest.approx(50 * 50 / 650)
    assert metric("unscoped_device_pct", r) == pytest.approx(50 * 200 / 650)
    assert sum(metric(f"{p}_device_pct", r) for p in (
        "fwd", "bwd", "opt", "unscoped")) == pytest.approx(100)
    # the notes are written once, by whichever metric is read first
    table, *groups = r["notes"]
    head, rows = table.split(": ")
    assert head == "device busy seconds by Program op scope"
    assert [row.rsplit(" ", 1) for row in rows.split(", ")] == [
        ["fwd/matmul", "0.2000"], ["(unscoped)", "0.1000"],
        ["fwd/transpose2", "0.0500"], ["bwd/matmul_grad", "0.0500"],
        ["opt/fused_adam", "0.0250"]]
    assert groups[0] == ("  XLA group fusion/kOutput 0.2500 s, of which: "
                         "fwd/matmul 0.2000, bwd/matmul_grad 0.0500")
    assert ("  XLA group copy 0.0750 s, of which: fwd/transpose2 0.0500, "
            "(unscoped) 0.0250") in groups
    assert groups[-2:] == [
        "  (unscoped) by XLA group: while 0.0750, copy 0.0250",
        trace_scope_share.BOOKED]


def test_program_spans_and_the_gaps_by_pt_span(tmp_path):
    r = reading_of(tmp_path, xspace(OPS, [HOST, STAGER]))
    # the median of each span's durations, two calls here
    assert metric("exe_prepare_ms", r) == pytest.approx((40 + 20) / 2)
    assert metric("exe_state_ms", r) == pytest.approx((100 + 60) / 2)
    assert metric("exe_dispatch_ms", r) == pytest.approx((200 + 280) / 2)
    assert metric("exe_writeback_ms", r) == pytest.approx((30 + 10) / 2)
    assert metric("reader_stage_ms", r) == pytest.approx((30 + 350) / 2)
    spans, gaps = r["notes"]
    assert spans.startswith(
        "pt spans in the traced window, ms (median / mean / longest): "
        "pt.exe.compile 10.000 / 10.000 / 10.000, "
        "pt.exe.dispatch 240.000 / 240.000 / 280.000, ")
    # device 1 idles most: one gap, [300,1000) of the window, and of the pt
    # spans the two dispatches cover most of it (50 + 280 ms); the stager's
    # thread does not count, however long it stages meanwhile
    assert gaps == ("idle gaps of the idlest device by pt span, ms: "
                    "pt.exe.dispatch 700.00")


def test_a_span_that_waits_in_some_calls_reads_as_what_a_call_costs(tmp_path):
    """A device-bound loop blocks in some dispatches: the median says what
    a dispatch costs, the note keeps the mean and the longest."""
    host = [("pt.exe.dispatch", 100 * i, 170 if i % 4 == 0 else 8)
            for i in range(9)]
    r = reading_of(tmp_path, xspace(OPS, [host]), steps=9)
    assert metric("exe_dispatch_ms", r) == pytest.approx(8)
    assert r["notes"][0].endswith("pt.exe.dispatch 8.000 / 62.000 / 170.000")


@pytest.mark.parametrize("names", [
    ["fwd_device_pct", "bwd_device_pct", "opt_device_pct",
     "unscoped_device_pct"],
    ["exe_prepare_ms", "exe_state_ms", "exe_dispatch_ms", "exe_writeback_ms",
     "reader_stage_ms"]], ids=["scopes", "spans"])
def test_a_program_without_the_names_reads_as_none_never_zero(tmp_path, names):
    """The parent commit's program, or a compile cache entry it wrote:
    the trace has device operations and the benchmark's spans only."""
    bench_only = [row for row in HOST if row[0].startswith("bench.")]
    r = reading_of(tmp_path, xspace(OPS, [bench_only], scoped=False))
    assert [metric(n, r) for n in names] == [None] * len(names)
    assert r["notes"] == []
    # and the rehearsal, which has no device plane, hands over no trace
    assert [metric(n, {"traced": r["traced"], "notes": []}) for n in names] == [
        None] * len(names)


def test_a_missing_span_is_left_out_while_the_others_are_read(tmp_path):
    r = reading_of(tmp_path, xspace(OPS, [HOST]))  # no stager thread
    assert metric("reader_stage_ms", r) is None
    assert metric("exe_state_ms", r) == pytest.approx(80)


# ------------------------------------------- three steps through the Executor

PROGRAM = os.path.join(DATA, "v5e_program_steps.xplane.pb.gz")


@pytest.mark.skipif(not os.path.exists(PROGRAM),
                    reason="no recorded trace in the repo")
def test_scopes_and_spans_survive_the_chips_compiler_and_profiler(tmp_path):
    """Three steps of a small Program through the real `Executor`,
    recorded on the v5e by `record_program_trace.py`, which wrote what
    the two source kinds read next to it: XLA's fusion leaves every phase
    some device time and little of it unscoped, and the Executor's spans
    come once a step."""
    import gzip
    import json

    with gzip.open(PROGRAM, "rb") as f:
        r = reading_of(tmp_path, f.read(), steps=3)
    with open(os.path.join(DATA, "v5e_program_steps.expected.json")) as f:
        want = json.load(f)
    got = {name: metric(name, r) for name in want["metrics"]}
    assert got == pytest.approx(want["metrics"])
    assert r["notes"] == want["notes"]
    shares = [got[f"{p}_device_pct"] for p in ("fwd", "bwd", "opt", "unscoped")]
    assert sum(shares) == pytest.approx(100)
    # at this size XLA's own prefetches (`copy-done`, no metadata) weigh most
    assert min(shares[:3]) > 1 and shares[3] < 50
    assert got["reader_stage_ms"] is None  # fed from host arrays, no DataLoader
    assert all(0 < got[f"exe_{s}_ms"] < 50 for s in (
        "prepare", "state", "dispatch", "writeback"))
    spans = trace_reduce.load(str(tmp_path), host_prefixes=("pt.",)).host_spans
    assert sorted(h.name for h in spans) == sorted(3 * [
        "pt.exe.prepare", "pt.exe.state", "pt.exe.dispatch",
        "pt.exe.writeback"])
