"""Share of device busy time, in %, in operations whose name (on the
TPU the whole HLO instruction) the regular expression `name` finds; mean
over the devices."""

from benchmark.harness import trace_reduce


def read(args: dict, r: dict):
    trace = r.get("trace")
    if trace is None:
        return None
    shares = [trace_reduce.share(ops, args["name"])
              for ops in trace.devices.values()]
    return 100.0 * sum(shares) / len(shares)
