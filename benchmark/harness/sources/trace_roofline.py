"""A share of one of the chip's roofs, in %, from counts that travel in
the trace itself: the FLOPs or bytes that the matching device events'
metadata carries, over those events' device time and the peak.

The count is written where the work is done. XLA's cost model gives every
instruction of the compiled step its `flops` and `bytes_accessed`, and a
Pallas call declares its own (`paddle_tpu/ops/pallas/cost.py`: useful
FLOPs on the pairs the masks admit, each operand and output moved once),
which XLA takes over for the custom call; the profiler writes both into
the event's metadata, where `harness/xplane_meta` reads them. Nothing is
counted here, so a kernel PR that changes what a call computes changes
the count with it.

args: `name` (regular expression on the event's name, the whole HLO
instruction: anchor it, `^%?flash_(fwd|bwd_dq|bwd_dkv)`, so that what
reads a kernel's output does not count as the kernel) or `scope` (on the
`pt` scope of the event's `tf_op`, as `trace_scope_share` reads it), and
`bound`, a key of `peaks.PEAKS`: `bf16_flops` reads `flops`,
`hbm_bytes_per_s` reads `bytes_accessed`. Over the matching *leaf* events
of the traced window on the first device (an event that encloses others,
a `while` or a conditional, repeats its body's counts and its time), it
sums the count and the time and returns 100 x count / time / peak. None
where no matching event carries a count: a program whose kernels declare
nothing (every one before PR 35) leaves their metadata at 0.

What the counts are: XLA's are what the instruction *executes* (the
padding rows of a grouped product included, an update fused onto a
product included); a kernel's are what the mathematics needs. Either way
a reading above 100 is a wrong count and never a fast kernel. One such
wrong count is known: XLA's `bytes_accessed` counts every operand and
output of an instruction, and on a v5e XLA keeps arrays of tens of MB in
VMEM from one operation to the next (`S(1)` in the instruction's
layout), so the note rows of elementwise operations read up to 150% of
HBM's peak. No metric is defined on XLA's bytes for that reason.

Each reading notes the calls seen, the count a step, the ms a step and
the TFLOP/s or GB/s. Once a run it notes the twelve Program op scopes
that take the most device time with each one's ms a step, TFLOP/s, GB/s
and the roof it is nearer to, the trace's whole FLOP count a step beside
the adapter's model FLOPs a step, and the seconds this reduction took."""

import re
import time
import typing

from benchmark.harness import peaks, trace_reduce, xplane_meta

COUNT = {"bf16_flops": "flops", "hbm_bytes_per_s": "bytes_accessed"}
TOP_SCOPES = 12


class Leaf(typing.NamedTuple):
    """A device event that encloses no other."""

    name: str
    scope: str
    ns: float
    counts: dict  # `flops`, `bytes_accessed`: 0 where it carries none


def _leaves(r: dict) -> list[Leaf]:
    """The leaf events of the first device, once a run."""
    if "roofline_leaves" not in r:
        t0 = time.perf_counter()
        plane, ops = next(iter(r["trace"].devices.items()))
        meta = xplane_meta.read(trace_reduce.find_xplane(
            r["traced"]["dir"])).get(plane, {})
        r["roofline_leaves"] = []
        for ev, own in trace_reduce.self_times(ops):
            if own >= ev.dur:
                stats = meta.get(ev.name, {})
                r["roofline_leaves"].append(Leaf(
                    ev.name, xplane_meta.scope(stats.get("tf_op")), ev.dur,
                    {c: stats.get(c) or 0 for c in COUNT.values()}))
        r["notes"].extend(_notes(r))
        r["notes"].append("  (metadata, leaf events and these rows took "
                          f"{time.perf_counter() - t0:.2f} s of the host)")
    return r["roofline_leaves"]


def _notes(r: dict) -> list[str]:
    steps, kind = r["traced"]["steps"], r["device_kind"]
    roofs = {c: peaks.peak(kind, bound) for bound, c in COUNT.items()}
    by_op = {}
    for leaf in r["roofline_leaves"]:
        # what carries no scope goes by XLA's name for it
        op = xplane_meta.phase_op(leaf.scope) or (
            f"(unscoped) {trace_reduce.group(leaf.name)}")
        row = by_op.setdefault(op, {"ns": 0.0, **dict.fromkeys(roofs, 0)})
        row["ns"] += leaf.ns
        for c in roofs:
            row[c] += leaf.counts[c]
    notes = [f"the {TOP_SCOPES} Program ops with the most device time: ms a "
             "step, TFLOP/s, GB/s, share of the nearer roof (XLA counts an "
             "operand's bytes wherever it lives: with arrays kept in VMEM "
             "between operations a share of HBM can pass 100)"]
    for op, row in sorted(by_op.items(),
                          key=lambda kv: -kv[1]["ns"])[:TOP_SCOPES]:
        if not row["ns"]:
            break
        s = row["ns"] / 1e9
        # the least time each roof allows, over the time taken
        share = {c: row[c] / roofs[c] / s for c in roofs}
        nearer = max(share, key=share.get)
        notes.append(
            f"  {op}: {row['ns'] / 1e6 / steps:.3f} ms, "
            f"{row['flops'] / s / 1e12:.2f} TFLOP/s, "
            f"{row['bytes_accessed'] / s / 1e9:.1f} GB/s, "
            f"{100 * share[nearer]:.1f}% of "
            f"{'the MXU' if nearer == 'flops' else 'HBM'}")
    cell = r["cell"]
    traced = sum(row["flops"] for row in by_op.values()) / steps
    model = (r["adapter"].flops_per_example(cell["config"], cell["traffic"])
             * cell["traffic"]["batch"] / cell["chips"])
    notes.append(
        f"FLOPs a step a chip: {traced:.4g} in the trace's leaf events, "
        f"{model:.4g} by the adapter's model count: {traced / model:.3f}")
    return notes


def read(args: dict, r: dict):
    if r.get("trace") is None:
        return None
    field = "name" if "name" in args else "scope"
    pattern, count = args[field], COUNT[args["bound"]]
    hit = [leaf for leaf in _leaves(r)
           if re.search(pattern, getattr(leaf, field))]
    total = sum(leaf.counts[count] for leaf in hit)
    ns = sum(leaf.ns for leaf in hit)
    if not total or not ns:
        return None
    steps = r["traced"]["steps"]
    rate, unit = ((total / ns / 1e3, "TFLOP/s") if count == "flops"
                  else (total / ns, "GB/s"))
    r["notes"].append(
        f"roofline {pattern!r}: {len(hit)} events in {steps} traced steps, "
        f"{total / steps:.5g} {count} a step in {ns / 1e6 / steps:.3f} ms: "
        f"{rate:.2f} {unit}")
    return 100.0 * total / (ns / 1e9) / peaks.peak(r["device_kind"],
                                                   args["bound"])
