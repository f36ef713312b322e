"""The benchmark's own tests run on the CPU: `JAX_PLATFORMS=cpu python -m
pytest benchmark/tests`. They are not part of the repo's tier-1 suite."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4").strip()
# XLA's CPU loader logs an error for every cached executable it reads
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
os.environ.setdefault("TPU_LOG_DIR", "disabled")

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
