"""A named kernel's calls a step: the device events whose name the regular
expression `name` finds, over the traced steps. A count, not a time: it
says which path a step took where the path follows the data (an expert
layer's overflow trips), so 0 calls reads as 0. args: name."""

from benchmark.harness import trace_reduce


def read(args: dict, r: dict):
    trace = r.get("trace")
    if trace is None:
        return None
    ops = next(iter(trace.devices.values()))
    calls, _ = trace_reduce.kernel(ops, args["name"])
    return calls / r["traced"]["steps"]
