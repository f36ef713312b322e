"""A named kernel's device time a step, in ms; 0 calls reads as 0 and the
count is noted. args: name (regular expression on the event name)."""

from benchmark.harness import trace_reduce


def read(args: dict, r: dict):
    trace = r.get("trace")
    if trace is None:
        return None
    ops = next(iter(trace.devices.values()))
    calls, ns = trace_reduce.kernel(ops, args["name"])
    steps = r["traced"]["steps"]
    r["notes"].append(f"kernel {args['name']!r}: {calls} calls in {steps} "
                      "traced steps")
    return ns / 1e6 / steps
