"""A family of kernels' share of the chip's peak: the useful FLOPs of
their calls in a step over their device time and the peak, in %. The
FLOPs come from the adapter's function named by `flops`, which returns
{kernel name: [FLOPs of each call a step makes]}; a kernel the trace
shows called more often than that (a forward the compiler did not merge)
has its list counted that many times over, since each call does the
work. Nothing where the trace has none of the kernels, or the adapter no
such function. args: flops (the adapter function's name), bound (a key of
`peaks.PEAKS`: what the kernels are bound by)."""

import re

from benchmark.harness import peaks, trace_reduce


def read(args: dict, r: dict):
    trace, count = r.get("trace"), getattr(r["adapter"], args["flops"], None)
    if trace is None or count is None:
        return None
    cell, steps = r["cell"], r["traced"]["steps"]
    ops = next(iter(trace.devices.values()))
    flops = ns = 0.0
    for name, calls in count(cell["config"], cell["traffic"]).items():
        # an event is named by its whole instruction: anchored, so that
        # what reads the kernel's output does not count as the kernel
        seen, spent = trace_reduce.kernel(ops, "^%?" + re.escape(name))
        r["notes"].append(f"kernel {name!r}: {seen} calls in {steps} traced "
                          f"steps, {len(calls)} expected a step")
        flops += sum(calls) * seen / (len(calls) * steps)
        ns += spent / steps
    if not ns:
        return None
    return 100.0 * flops / (ns / 1e9) / peaks.peak(r["device_kind"],
                                                   args["bound"])
