#!/usr/bin/env python
"""Multichip dryrun CLI: runs the virtual-CPU-mesh dryrun
(__graft_entry__.dryrun_multichip — forced-CPU virtual devices, never
chips) and writes a JSON report with the per-config HBM + collective
evidence lines (hbm_state_mb_per_device / _replicated and
collective_bytes_estimate per config).

    python tools/dryrun_multichip.py [n_devices] [--out report.json]
    python tools/dryrun_multichip.py 8 --static

--static consumes the STATIC analysis layer instead of tracing: the
BERT train program is built, paddle_tpu.analysis.infer_program
annotates every state var with its concrete shape/dtype (no JAX trace,
no virtual devices, no subprocess), the ZeRO-1/pipe spec helpers
propose shardings, the sharding checker validates them, and the same
per-config hbm_state_mb evidence is computed from the annotated
program. This is the placement-search substrate (ROADMAP
shard_propagation): candidate PartitionSpec assignments can be costed
per config in milliseconds instead of per-compile minutes.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def static_report(n_devices: int) -> dict:
    """The --static body: annotate, propose, validate, cost. Pure
    host-side analysis — no tracing, no devices. The costing internals
    live in paddle_tpu/autoshard/cost_table.py (the placement planner's
    substrate); this CLI is a thin wrapper that keeps the MULTICHIP
    evidence-line format byte-identical to r06."""
    from paddle_tpu import analysis
    from paddle_tpu.autoshard.cost_table import (
        config_state_mb as _static_config_mb,
    )
    from paddle_tpu.autoshard.cost_table import (
        state_var_names as _static_state_names,
    )
    from paddle_tpu.parallel import mesh as mesh_mod
    from tools.verify_bench_programs import build_bench_program

    program, feeds = build_bench_program("bert", batch=2 * max(n_devices, 1))
    block = program.global_block()
    result = analysis.infer_program(program, feeds=feeds)
    findings = analysis.verify_program(
        program, feed_names=tuple(sorted(feeds))
    )
    state_names = _static_state_names(program)

    configs = []
    pipe_n = 4 if n_devices % 4 == 0 else (2 if n_devices % 2 == 0 else 1)
    for tag, axis_sizes, specs in (
        ("replicated_dp", {"batch": n_devices, "model": 1, "pipe": 1}, {}),
        (
            f"zero1_dp{n_devices}",
            {"batch": n_devices, "model": 1, "pipe": 1},
            mesh_mod.zero1_accumulators(block, state_names, n_devices),
        ),
        (
            f"zero_over_pipe{pipe_n}",
            {"batch": n_devices // pipe_n, "model": 1, "pipe": pipe_n},
            mesh_mod.pipe_shardable_state(block, state_names, pipe_n),
        ),
    ):
        sharding_findings = analysis.check_sharding(
            program, mesh=axis_sizes, specs={}, extra_specs=specs,
            env=result,
        )
        per_dev, full = _static_config_mb(
            result.env, state_names, specs, axis_sizes
        )
        line = {
            "config": tag,
            "hbm_state_mb_per_device": round(per_dev, 2),
            "hbm_state_mb_replicated": round(full, 2),
            "sharded_vars": len(specs),
            "sharding_findings": [str(f) for f in sharding_findings],
        }
        print("MULTICHIP_STATIC " + json.dumps(line), flush=True)
        configs.append(line)

    ok = (
        not findings
        and not result.missing
        and not result.errors
        and not any(c["sharding_findings"] for c in configs)
    )
    return {
        "n_devices": n_devices,
        "mode": "static",
        "ok": ok,
        "verifier_findings": [str(f) for f in findings],
        "infer": {
            "ops_total": result.ops_total,
            "ops_covered": result.ops_covered,
            "missing": sorted(result.missing_types),
            "errors": [list(e) for e in result.errors],
        },
        "state_vars": len(state_names),
        "mesh_axes": ["batch", "model", "pipe"],
        "configs": configs,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("n_devices", nargs="?", type=int, default=8)
    ap.add_argument("--out", default=None,
                    help="write the JSON report here (default: stdout)")
    ap.add_argument("--static", action="store_true",
                    help="consume the static analysis layer instead of "
                         "tracing (no devices, no subprocess)")
    args = ap.parse_args()

    if args.static:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        report = static_report(args.n_devices)
        text = json.dumps(report, indent=2)
        if args.out:
            with open(args.out, "w") as f:
                f.write(text + "\n")
            print(f"wrote {args.out}")
        else:
            print(text)
        return 0 if report["ok"] else 1

    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "__graft_entry__.py"),
         str(args.n_devices)],
        cwd=REPO, capture_output=True, text=True,
        timeout=int(os.environ.get("PADDLE_TPU_DRYRUN_TIMEOUT", "2700")),
    )
    out = (proc.stdout or "") + (proc.stderr or "")
    configs = []
    tail = ""
    for line in out.splitlines():
        if line.startswith("MULTICHIP_CONFIG "):
            try:
                configs.append(json.loads(line[len("MULTICHIP_CONFIG "):]))
            except ValueError:
                pass
        elif line.startswith("dryrun_multichip OK"):
            tail = line
    report = {
        "n_devices": args.n_devices,
        "rc": proc.returncode,
        "ok": proc.returncode == 0,
        "skipped": False,
        "mesh_axes": ["batch", "model", "pipe"],
        "configs": configs,
        "tail": tail + "\n" if tail else out[-2000:],
    }
    text = json.dumps(report, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"wrote {args.out}")
    else:
        print(text)
    return proc.returncode


if __name__ == "__main__":
    sys.exit(main())
