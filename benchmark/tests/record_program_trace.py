"""Record the small trace on which `test_program_names.py` checks that the
program's scopes and spans survive the chip's compiler and profiler. Run
on the chip, once, when the test data has to be made anew:

    python3 benchmark/tests/record_program_trace.py chiprun_out/trace_data

Three steps of a small Program (two `fc` layers at width 512 around a
`layer_norm`, batch 256, Adam) through the real `Executor` under
`bench.exe_run` spans, each followed by a read of the loss. The expected
numbers are what the two source kinds read when the trace was recorded;
what the test holds them to is written next to each in the test.
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
STEPS = 3
NAME = "v5e_program_steps"


def main(out_dir: str) -> None:
    import jax
    import numpy as np

    import paddle_tpu as fluid

    from benchmark.harness import spec, trace_reduce

    x = fluid.layers.data("x", [512])
    y = fluid.layers.data("y", [1])
    h = fluid.layers.layer_norm(fluid.layers.fc(x, 512, act="relu"))
    loss = fluid.layers.mean(
        fluid.layers.square_error_cost(fluid.layers.fc(h, 1), y))
    fluid.optimizer.Adam(1e-3).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(256, 512).astype("float32"),
            "y": rng.randn(256, 1).astype("float32")}
    main_program = fluid.default_main_program()
    exe.run(main_program, feed=feed, fetch_list=[loss])  # compiles

    trace_dir = os.path.join(out_dir, "raw")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for _ in range(STEPS):
        with jax.profiler.TraceAnnotation("bench.exe_run"):
            (out,) = exe.run(main_program, feed=feed, fetch_list=[loss],
                             return_numpy=False)
        with jax.profiler.TraceAnnotation("bench.read_loss"):
            float(np.asarray(out).reshape(-1)[0])
    jax.profiler.stop_trace()

    reading = {"traced": {"dir": trace_dir, "steps": STEPS}, "notes": [],
               "trace": trace_reduce.load(trace_dir)}
    expected = {"device_kind": jax.devices()[0].device_kind,
                "jax": jax.__version__, "steps": STEPS, "metrics": {}}
    for name in spec.names("layer_metrics"):
        metric = spec.load("layer_metrics", name)
        if metric["kind"] in ("trace_scope_share", "trace_program_span"):
            expected["metrics"][name] = spec.plugin(
                "harness.sources", metric["kind"]).read(metric["args"], reading)
    expected["notes"] = reading["notes"]
    print(json.dumps(expected, indent=1))
    with open(trace_reduce.find_xplane(trace_dir), "rb") as f, gzip.open(
            os.path.join(out_dir, NAME + ".xplane.pb.gz"), "wb") as g:
        g.write(f.read())
    with open(os.path.join(out_dir, NAME + ".expected.json"), "w") as f:
        json.dump(expected, f, indent=1)
    shutil.rmtree(trace_dir)


if __name__ == "__main__":
    main(sys.argv[1])
