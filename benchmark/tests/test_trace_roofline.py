"""The source kind `trace_roofline` on a hand-built trace with known
answers, written as the profiler writes one (event metadata with `flops`,
`bytes_accessed` and `tf_op`) and read back from its file, and on the
trace recorded on the v5e."""

from __future__ import annotations

import os
import types

import pytest

from benchmark.harness import trace_reduce
from benchmark.harness.sources import trace_roofline

KIND = "TPU v5 lite"  # 197e12 FLOP/s, 819e9 bytes/s
DATA = os.path.join(os.path.dirname(__file__), "data")

# device 0, two steps, in ms: name, tf_op, start, duration, flops, bytes
#   [0,100)     the kernel, 1e13 useful FLOPs and 2e10 bytes a call
#   [100,150)   what reads its output: the kernel's name is in its operands
#   [200,500)   a while that repeats its body's counts, around
#     [220,320) a product under bwd/mul_grad, 1.5e13 FLOPs
#     [400,450) Adam's update, under opt/, elementwise: 1e9 FLOPs, 3e10 bytes
#   [500,550)   a copy that carries no count at all, and no scope
#   [600,700)   the kernel again
#   [700,800)   a product under fwd/mul, 1.2e13 FLOPs
OPS = [
    ("%flash_fwd.3 = (bf16[8,8]) custom-call(%q, %k, %v)",
     "jit(step)/fwd/fused_multihead_attention/pallas_call:", 0, 100,
     10**13, 2 * 10**10),
    ("%fusion.9 = bf16[8,8] fusion(%flash_fwd.3), kind=kLoop",
     "jit(step)/fwd/fused_multihead_attention/transpose:", 100, 50,
     10**6, 10**9),
    ("%while.4 = (s32[]) while(%t), body=%b", "jit(step)/while:", 200, 300,
     15 * 10**12 + 10**9, 4 * 10**10),
    ("%fusion.5 = f32[8] fusion(%c), kind=kOutput",
     "jit(step)/while/body/bwd/mul_grad/dot_general:", 220, 100,
     15 * 10**12, 10**10),
    ("%fusion.6 = f32[8] fusion(%d), kind=kLoop",
     "jit(step)/while/body/opt/fused_adam/mul:", 400, 50, 10**9, 3 * 10**10),
    ("%copy.7 = f32[8] copy(%e)", None, 500, 50, None, None),
    ("%fusion.8 = bf16[8,8] fusion(%a), kind=kOutput",
     "jit(step)/fwd/mul/dot_general:", 700, 100, 12 * 10**12, 10**10),
]
AGAIN = [(OPS[0][0], 600, 100)]  # events beyond each instruction's first


def xspace(ops=OPS, again=AGAIN, counted=True) -> bytes:
    from jax.profiler import ProfileData

    ids = {op[0]: i + 1 for i, op in enumerate(ops)}
    rows = [(name, start, dur) for name, _, start, dur, _, _ in ops] + again
    events = " ".join(
        f"events {{ metadata_id: {ids[name]} offset_ps: {start * 10**9} "
        f"duration_ps: {dur * 10**9} }}" for name, start, dur in rows)

    def stats(tf_op, flops, moved):
        out = f'stats {{ metadata_id: 1 str_value: "{tf_op}" }} ' if tf_op else ""
        if flops is not None:  # a kernel that declares nothing carries 0
            out += (f"stats {{ metadata_id: 2 uint64_value: "
                    f"{flops if counted else 0} }} "
                    f"stats {{ metadata_id: 3 uint64_value: "
                    f"{moved if counted else 0} }} ")
        return out

    meta = " ".join(
        f'event_metadata {{ key: {ids[name]} value {{ id: {ids[name]} name: '
        f'"{name}" {stats(tf_op, flops, moved)}}} }}'
        for name, tf_op, _, _, flops, moved in ops)
    return ProfileData.text_proto_to_serialized_xspace(
        'planes { name: "/device:TPU:0" lines { name: "XLA Ops" '
        f"timestamp_ns: 1000 {events} }} {meta} "
        'stat_metadata { key: 1 value { id: 1 name: "tf_op" } } '
        'stat_metadata { key: 2 value { id: 2 name: "flops" } } '
        'stat_metadata { key: 3 value { id: 3 name: "bytes_accessed" } } }')


def reading_of(tmp_path, serialized: bytes, steps=2) -> dict:
    """What `run.py` hands a source kind after a traced run."""
    (tmp_path / "host.xplane.pb").write_bytes(serialized)
    adapter = types.SimpleNamespace(
        flops_per_example=lambda config, traffic: 5e12)
    return {"traced": {"dir": str(tmp_path), "steps": steps}, "notes": [],
            "trace": trace_reduce.load(str(tmp_path)), "device_kind": KIND,
            "adapter": adapter,
            "cell": {"config": {}, "traffic": {"batch": 4}, "chips": 1}}


def share(r, bound="bf16_flops", **args):
    return trace_roofline.read({"bound": bound, **args}, r)


def test_an_anchored_name_reads_the_kernel_and_not_what_reads_its_output(
        tmp_path):
    r = reading_of(tmp_path, xspace())
    # two calls of 1e13 FLOPs in 100 ms each: 1e14 FLOP/s of 1.97e14
    assert share(r, name="^%?flash_fwd") == pytest.approx(100 * 1e14 / 197e12)
    # the same calls' declared bytes: 2e10 in 0.1 s of 8.19e11 a second
    assert share(r, "hbm_bytes_per_s", name="^%?flash_fwd") == pytest.approx(
        100 * 2e11 / 819e9)
    # unanchored, the fusion that reads the kernel's output counts too:
    # its 1e6 FLOPs and its 50 ms
    assert share(r, name="flash_fwd") == pytest.approx(
        100 * (2e13 + 1e6) / 0.25 / 197e12)
    assert r["notes"][-1] == (
        "roofline 'flash_fwd': 3 events in 2 traced steps, 1e+13 flops a "
        "step in 125.000 ms: 80.00 TFLOP/s")


def test_a_scope_sums_leaf_events_and_a_while_is_not_counted_twice(tmp_path):
    r = reading_of(tmp_path, xspace())
    # fwd/mul 1.2e13 in 100 ms and bwd/mul_grad 1.5e13 in 100 ms; the while
    # around the second carries the same 1.5e13 again and is no leaf
    assert share(r, scope="^(fwd/mul|bwd/mul_grad)") == pytest.approx(
        100 * 2.7e13 / 0.2 / 197e12)
    assert share(r, scope="^jit") is None  # the while's own tf_op has no scope
    assert share(r, "hbm_bytes_per_s", scope="^opt/") == pytest.approx(
        100 * 3e10 / 0.05 / 819e9)


def test_the_rows_by_program_op_and_the_whole_count_are_noted_once(tmp_path):
    r = reading_of(tmp_path, xspace())
    share(r, name="^%?flash_fwd")
    share(r, scope="^fwd/mul")
    head, *rows, whole, took, first, second = r["notes"]
    assert took.startswith("  (metadata, leaf events and these rows took ")
    assert head.startswith("the 12 Program ops with the most device time")
    assert rows == [
        # the kernel 200 ms and its reader 50: 2e13 + 1e6 FLOPs, 4e10 + 1e9
        # bytes in 0.25 s; the MXU's roof is the nearer one
        "  fwd/fused_multihead_attention: 125.000 ms, 80.00 TFLOP/s, "
        "164.0 GB/s, 40.6% of the MXU",
        "  bwd/mul_grad: 50.000 ms, 150.00 TFLOP/s, 100.0 GB/s, "
        "76.1% of the MXU",
        "  fwd/mul: 50.000 ms, 120.00 TFLOP/s, 100.0 GB/s, 60.9% of the MXU",
        # 3e10 bytes in 50 ms: 600 GB/s of 819
        "  opt/fused_adam: 25.000 ms, 0.02 TFLOP/s, 600.0 GB/s, "
        "73.3% of HBM",
        "  (unscoped) copy: 25.000 ms, 0.00 TFLOP/s, 0.0 GB/s, "
        "0.0% of the MXU"]
    # 2e13 + 1e6 + 1.5e13 + 1e9 + 1.2e13 over two steps, against 4 rows of
    # 5e12 by the adapter
    assert whole == ("FLOPs a step a chip: 2.35e+13 in the trace's leaf "
                     "events, 2e+13 by the adapter's model count: 1.175")
    assert first.startswith("roofline '^%?flash_fwd'")
    assert second.startswith("roofline '^fwd/mul'")


def test_no_count_reads_as_none_and_never_raises(tmp_path):
    """The parent commit's program: its kernels declare nothing, so their
    events carry 0; a copy carries no statistic at all; a name nothing
    matches; and a run that was not traced."""
    r = reading_of(tmp_path, xspace(counted=False))
    assert share(r, name="^%?flash_fwd") is None
    assert share(r, "hbm_bytes_per_s", name="^%?flash_fwd") is None
    r = reading_of(tmp_path, xspace())
    assert share(r, name="^%?copy") is None
    assert share(r, name="^%?kda_(fwd|bwd)") is None
    assert share({"notes": []}, name="^%?flash_fwd") is None


def test_on_the_trace_recorded_on_the_chip(tmp_path):
    """`v5e_program_steps`: a small Program's steps recorded on a v5e by
    `record_program_trace.py`. Its products carry XLA's FLOPs; its one
    Pallas call (`ln_bwd`, before it declared anything) carries 0."""
    import gzip
    import shutil

    with gzip.open(os.path.join(DATA, "v5e_program_steps.xplane.pb.gz")) as f:
        with open(tmp_path / "chip.xplane.pb", "wb") as out:
            shutil.copyfileobj(f, out)
    r = {"traced": {"dir": str(tmp_path), "steps": 1}, "notes": [],
         "trace": trace_reduce.load(str(tmp_path)), "device_kind": KIND,
         "adapter": types.SimpleNamespace(
             flops_per_example=lambda config, traffic: 1e9),
         "cell": {"config": {}, "traffic": {"batch": 1}, "chips": 1}}
    products = share(r, scope="^(fwd/mul|bwd/mul_grad)")
    assert 0 < products <= 100
    assert share(r, name="^%?ln_bwd") is None
    assert share(r, "hbm_bytes_per_s", name="^%?ln_bwd") is None
