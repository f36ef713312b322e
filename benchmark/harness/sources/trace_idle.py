"""1 - union of operation intervals / traced window, in %, on the device
that idles most."""

from benchmark.harness import trace_reduce


def read(args: dict, r: dict):
    trace = r.get("trace")
    if trace is None:
        return None
    window = trace.window()
    return 100.0 * max(trace_reduce.idle_share(ops, window)
                       for ops in trace.devices.values())
