"""One of the program's own spans (`pt.*`, written by
`paddle_tpu.profiler.RecordEvent` on the trace's clock), in ms.
args: span. The median of its durations in the traced window: what one
call costs when it does not wait (a device-bound loop blocks in some
dispatches until the device's queue has room, and the benchmark's own
`exe_run_host_ms` is a median too). None where the trace holds no such
span.

Once a run it notes every `pt.*` span's median, mean and longest
duration, and the idle gaps of the idlest device by the `pt.*` span that
covers most of each: the attribution the result line's `breakdown` gives
by the benchmark's own spans only. The spans of the dispatching thread,
that is: what the stager's thread does while the device idles is beside
the point, and a wait for it is `pt.reader.wait`."""

from benchmark.harness import stats, trace_reduce

OTHER_THREADS = ("pt.reader.stage",)


def _spans(r: dict) -> list:
    if "pt_spans" not in r:
        pt = trace_reduce.load(r["traced"]["dir"], host_prefixes=("pt.",))
        r["pt_spans"] = pt.host_spans
        ms = {}
        for h in pt.host_spans:
            ms.setdefault(h.name, []).append(h.dur / 1e6)
        if ms:
            r["notes"].append(
                "pt spans in the traced window, ms (median / mean / longest): "
                + ", ".join(f"{name} {stats.median(v):.3f} / "
                            f"{sum(v) / len(v):.3f} / {max(v):.3f}"
                            for name, v in sorted(ms.items())))
        if pt.host_spans and pt.devices:
            window = pt.window()
            ops = max(pt.devices.values(),
                      key=lambda ops: trace_reduce.idle_share(ops, window))
            by = trace_reduce.attribute_gaps(
                trace_reduce.gaps(ops, window),
                [h for h in pt.host_spans if h.name not in OTHER_THREADS],
                none="(no pt span)")
            r["notes"].append(
                "idle gaps of the idlest device by pt span, ms: " + ", ".join(
                    f"{k} {v / 1e6:.2f}" for k, v in
                    sorted(by.items(), key=lambda kv: -kv[1])))
    return r["pt_spans"]


def read(args: dict, r: dict):
    if r.get("trace") is None:
        return None
    found = [h.dur / 1e6 for h in _spans(r) if h.name == args["span"]]
    return stats.median(found) if found else None
