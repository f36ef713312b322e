"""Capture a jax.profiler trace of the Transformer-base bench step
(mirrors tools/profile_resnet.py). Parse with
    PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION=python \
        python tools/parse_xplane.py /tmp/jaxprof_tf [--detail N]
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax

    os.environ.setdefault("TF_BATCH", "256")
    os.environ.setdefault("TF_STEPS", "5")
    import bench

    bench.require_tpu()
    # run the canonical workload once to compile + warm, then trace the
    # timing windows
    import json

    import jax.numpy as jnp  # noqa: F401

    steps = int(os.environ["TF_STEPS"])
    os.environ["TF_STEPS"] = str(steps)
    with jax.profiler.trace("/tmp/jaxprof_tf"):
        bench.bench_transformer()
    payload = bench._EXTRA.get(
        "transformer_base_wmt16_tokens_per_sec_per_chip", {}
    )
    print(json.dumps({"metric": "transformer_profile", **payload}))
    print("xplane under /tmp/jaxprof_tf; parse with tools/parse_xplane.py",
          file=sys.stderr)


if __name__ == "__main__":
    main()
