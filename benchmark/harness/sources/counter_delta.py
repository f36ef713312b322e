"""The increase of a counter, or of the sum of several, over set-up or
over the window. args: counters (names of `paddle_tpu.profiler` counters,
or the runner's own `jax_compile_events`), phase ("setup" | "window"),
scale (default 1)."""


def read(args: dict, r: dict):
    seen = r["counters"][args["phase"]]
    return sum(seen.get(c, 0) for c in args["counters"]) * args.get("scale", 1)
