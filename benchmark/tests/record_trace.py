"""Record the small trace that `test_trace_reduce.py` checks the reduction
on. Run on the chip, once, when the test data has to be made anew:

    python3 benchmark/tests/record_trace.py chiprun_out/trace_data

Three small steps (a bf16 matrix product, then the repo's `ln_bwd` Pallas
kernel at [1024, 128]) dispatched under `bench.exe_run` spans; after each
the host reads a value under `bench.read_loss` and sleeps 2 ms inside that
span, so by construction the longest idle gaps lie under
`bench.read_loss` and `ln_bwd` is called three times. The other expected
numbers are what the reduction read when the trace was recorded.
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.layer_norm import ln_bwd

    from benchmark.harness import trace_reduce as tr

    @jax.jit
    def step(a, x, dy):
        y = (a @ a).astype(jnp.bfloat16)
        n = x.shape[0]
        dx, dg, db = ln_bwd(x, dy, jnp.zeros((n,)), jnp.ones((n,)),
                            jnp.ones((x.shape[1],)))
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(dx) + jnp.sum(dg + db)

    a = jnp.ones((1024, 1024), jnp.bfloat16)
    x = jnp.ones((1024, 128), jnp.float32)
    step(a, x, x).block_until_ready()

    trace_dir = os.path.join(out_dir, "raw")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.exe_run"):
            out = step(a, x, x)
        with jax.profiler.TraceAnnotation("bench.read_loss"):
            float(out)
            time.sleep(0.002)
    jax.profiler.stop_trace()

    path = tr.find_xplane(trace_dir)
    trace = tr.load(path)
    devices = sorted(trace.devices)
    ops = trace.devices[devices[0]]
    lo, hi = trace.window()
    by = tr.attribute_gaps(tr.gaps(ops, (lo, hi)), trace.host_spans)
    calls, ns = tr.kernel(ops, "ln_bwd")
    expected = {
        "devices": devices, "events": len(ops), "window_ns": hi - lo,
        "busy_ns": tr.length(tr.busy(ops)),
        "largest_gap_span": max(by, key=by.get), "gaps_ns": by,
        "kernel": "ln_bwd", "kernel_calls": calls, "kernel_ns": ns,
        "matmul_share": tr.share(ops, r"\bkind=kOutput\b"),
        "device_kind": jax.devices()[0].device_kind,
        "jax": jax.__version__,
    }
    print(json.dumps(expected, indent=1))
    with open(path, "rb") as f, gzip.open(
            os.path.join(out_dir, "v5e_tiny_steps.xplane.pb.gz"), "wb") as g:
        g.write(f.read())
    with open(os.path.join(out_dir, "v5e_tiny_steps.expected.json"), "w") as f:
        json.dump(expected, f, indent=1)
    shutil.rmtree(trace_dir)


if __name__ == "__main__":
    main(sys.argv[1])
