"""A statistic of one of the benchmark's own spans, host clock.
args: span, stat ("median" | "sum"), scale (default 1; samples are ms)."""

from benchmark.harness import stats


def read(args: dict, r: dict):
    samples = r["spans"].get(args["span"])
    if not samples:
        return None
    value = sum(samples) if args["stat"] == "sum" else stats.median(samples)
    return value * args.get("scale", 1)
