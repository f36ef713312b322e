"""One counter's increase over another's, over set-up or over the window,
times `scale` (default 1); nothing where the denominator did not move (a
program without the counters). args: numerator, denominator (names of
`paddle_tpu.profiler` counters), phase ("setup" | "window"), scale."""


def read(args: dict, r: dict):
    seen = r["counters"][args["phase"]]
    below = seen.get(args["denominator"], 0)
    if not below:
        return None
    return seen.get(args["numerator"], 0) / below * args.get("scale", 1)
