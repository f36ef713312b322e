"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process per run. It needs a TPU with as many chips as the cell asks
for and exits non-zero, printing no result, without one. The last line of
standard output is one JSON object: `correct`, `attempted`, `failed`,
`metrics`, `device`, and with `--trace 1` `breakdown`. With `--trace 0`
the metrics are the cell's end-to-end metrics, with `--trace 1` its
per-layer metrics. Everything else (tokens/s, step-time quartiles, the
loss trace, the reference check) goes on earlier lines.

`--rehearse` runs the cell's tiny preset on the CPU (four virtual devices)
through the same code. It is for the tests: it proves the control flow,
and its result line carries counts only, never a time or a rate.

The benchmark sets no `PADDLE_TPU_*` variable and passes no compiler
option: a cell measures the program's defaults. The persistent compile
cache is wherever the program puts it (`JAX_COMPILATION_CACHE_DIR`, else
`<checkout>/.jax_cache`).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)

from benchmark.harness import spec  # noqa: E402


def log(*parts):
    print(*parts, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny preset on the CPU, for the tests; no device "
                    "metric is printed")
    args = ap.parse_args(argv)

    if args.rehearse:
        # before the first JAX import, and for nothing but this process
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
        # XLA's CPU loader logs an error for every cached executable
        os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    cell = spec.cell(args.workload, rehearse=args.rehearse)

    import jax

    # The seconds the runtime takes to hand the chip over (11.9 to 16.0 s
    # in thirteen runs of one cell whose every other part of set-up
    # repeated to 0.1 s) are neither the program's nor the benchmark's:
    # they are printed, and left out of `setup_s`, which they would
    # drown.
    t_reach = time.perf_counter()
    devices = jax.devices()
    reach_s = time.perf_counter() - t_reach
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    log(f"device: {device}, reached in {reach_s:.2f} s; jax {jax.__version__}; "
        f"cell {cell['name']} "
        f"seed {args.seed} seconds {args.seconds} trace {args.trace}"
        + (" REHEARSAL on the CPU: no number below is a device number"
           if args.rehearse else ""))
    if not args.rehearse and device["platform"] != "tpu":
        print(f"FAIL: this benchmark measures on a TPU and JAX found "
              f"{device}; there is no CPU fallback", file=sys.stderr)
        return 3
    if len(devices) < cell["chips"]:
        print(f"FAIL: cell {cell['name']} needs {cell['chips']} chips and "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 3

    trace_dir = os.path.join(CHECKOUT, ".bench_out", "trace", cell["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = types.SimpleNamespace(
        cell=cell, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), rehearse=args.rehearse,
        t_start=T_START + reach_s, trace_dir=trace_dir, log=log)
    runner = spec.plugin("runners", cell["traffic"]["runner"])
    out = runner.run(ctx)

    # the allocator counts live arrays under `bytes_in_use` and what a
    # loaded program keeps for its temporaries under `bytes_reserved`: a
    # chip's peak is the two together
    stats = [d.memory_stats() or {} for d in devices[:cell["chips"]]]
    fullest = max(stats, key=lambda m: m.get("peak_bytes_in_use", 0)
                  + m.get("peak_bytes_reserved", 0))
    device["memory_peak_bytes"] = (fullest.get("peak_bytes_in_use", 0)
                                   + fullest.get("peak_bytes_reserved", 0))
    log(f"memory on the fullest device: {fullest}")
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "device": device}

    if not args.trace:
        for name, value in out["end_to_end"].items():
            if not args.rehearse:  # host-clock numbers of a CPU run are not
                result["metrics"][name] = {  # written under a device metric
                    "value": value, "unit": runner.END_TO_END[name]}
    else:
        reading = out["reading"]
        reading.update(device_kind=device["kind"], notes=[],
                       memory_peak_bytes=device["memory_peak_bytes"])
        if reading["traced"]:
            from benchmark.harness import trace_reduce

            trace = trace_reduce.load(reading["traced"]["dir"])
        if reading["traced"] and args.rehearse:  # a CPU trace has no device plane
            log(f"rehearsal trace: {len(trace.host_spans)} of the "
                "benchmark's spans are in the profiler's trace")
        elif reading["traced"]:
            reading["trace"] = trace
            summary = trace_reduce.summarize(trace)
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            result["breakdown"] = {"device_ops": summary["device_ops"],
                                   "idle_gaps": summary["idle_gaps"]}
            log(f"trace: {reading['traced']['steps']} steps, window "
                f"{summary['window_s']:.4f} s, busy {summary['busy_s']:.4f} s "
                f"(mean over {len(trace.devices)} devices), idlest "
                f"device {summary['worst_device']}")
        for metric in spec.layer_metrics(cell):
            if args.rehearse and metric["source"] != "program_counter":
                continue
            kind = spec.plugin("harness.sources", metric["kind"])
            value = kind.read(metric.get("args", {}), reading)
            if value is not None:
                result["metrics"][metric["name"]] = {
                    "value": value, "unit": metric["unit"]}
        for note in reading["notes"]:
            log(note)
    if not args.trace or args.rehearse:
        shutil.rmtree(trace_dir, ignore_errors=True)

    log(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
