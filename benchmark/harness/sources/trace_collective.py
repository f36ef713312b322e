"""Time a step's operation stream spends in collective operations, in
ms, mean over the devices: the communication the step waits for (see
`trace_reduce.collective`). No args."""

from benchmark.harness import trace_reduce


def read(args: dict, r: dict):
    trace = r.get("trace")
    if trace is None:
        return None
    per_device = [trace_reduce.collective(ops) for ops in trace.devices.values()]
    return sum(per_device) / len(per_device) / 1e6 / r["traced"]["steps"]
