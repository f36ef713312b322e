"""Supervised topology-elastic training worker (tests/test_elastic_mesh.py
and the tools/ci.sh mesh-shrink stage).

The mesh-wide sibling of tests/trainer_worker.py: the SAME dropout-MLP /
cursor-tracked-DataLoader / auto-resume wiring, but the train step runs
through `CompiledProgram.with_data_parallel(places=W, zero1=True)` on a
W-wide batch mesh, where W comes from the supervisor's elastic contract:

    W  = PADDLE_TPU_ELASTIC_WORLD (default 8)  — this attempt's width
    W0 = PADDLE_TPU_BASE_WORLD    (default W)  — the job's original width

This is the single-process GSPMD flavor of the global-batch contract:
the worker always feeds the full GLOBAL batch and the mesh only shards
its layout, so shrinking W changes no math inputs — the exact path, no
grad-accum scaling needed (a multi-process worker would scale accum by
W0//W instead). A non-divisor W is logged as documented drift.

ZeRO-1 is ON so optimizer moments live sharded P('batch') at rest: the
mesh-elastic restore path (CheckpointManager.restore re-placing recorded
PartitionSpecs under the CURRENT, possibly smaller, mesh) is exercised
end-to-end — an 8-wide snapshot's moments re-split across the 4 surviving
devices on resume.

argv: workdir
env:  ELASTIC_RESULT   — JSONL appended across attempts; one line per
                         step: {attempt, world, epoch, batch, gstep,
                         crc, loss}
      ELASTIC_STEP_DT  — seconds slept per step (default 0.05; keeps
                         step-pinned supervisor chaos deliverable, see
                         trainer_worker.py)
"""

import json
import os
import sys
import time
import zlib

# the supervisor's workers do not inherit conftest: pin the virtual
# 8-device CPU mesh before jax initializes
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import layers, resilience  # noqa: E402
from paddle_tpu import reader as rdr  # noqa: E402
from paddle_tpu.parallel.mesh import build_mesh  # noqa: E402

EPOCHS, N_SAMPLES, BATCH = 3, 48, 16  # 3 batches/epoch, 9 steps total


def samples():
    for i in range(N_SAMPLES):
        rs = np.random.RandomState(2000 + i)
        x = rs.rand(16).astype("float32")
        y = np.asarray([x.sum() * 0.5], dtype="float32")
        yield (x, y)


def main():
    workdir = sys.argv[1]
    attempt = int(os.environ.get("PADDLE_TPU_TRAINER_ATTEMPT", "0"))
    result_path = os.environ["ELASTIC_RESULT"]
    world = int(os.environ.get("PADDLE_TPU_ELASTIC_WORLD", "8"))
    base = int(os.environ.get("PADDLE_TPU_BASE_WORLD", str(world)))
    if base % world:
        # the documented degraded mode: a non-divisor width cannot keep
        # the global batch exact on the multi-process path — loud, never
        # silent (the single-process GSPMD feed below stays exact anyway)
        print(json.dumps({"batch_drift": True, "world": world,
                          "base": base}), flush=True)

    main_p = fluid.default_main_program()
    main_p.random_seed = 7
    x = layers.data("x", [16])
    y = layers.data("y", [1])
    h = layers.fc(x, 16, act="relu")
    h = layers.dropout(h, dropout_prob=0.3)  # PRNG half of exact resume
    pred = layers.fc(h, 1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    fluid.optimizer.Adam(1e-2).minimize(loss)

    loader = rdr.DataLoader.from_generator([x, y], capacity=4)
    loader.set_sample_generator(samples, batch_size=BATCH, drop_last=True,
                                shuffle_buf=16, shuffle_seed=13)

    # build THIS attempt's mesh BEFORE restore: the mesh-elastic restore
    # re-places the snapshot's recorded PartitionSpecs (ZeRO-1 moments,
    # P('batch')) under the current — possibly smaller — batch extent
    build_mesh(batch=world, devices=jax.devices()[:world])
    compiled = fluid.CompiledProgram(main_p).with_data_parallel(
        loss_name=loss.name, places=world, zero1=True)

    exe = fluid.Executor(fluid.CPUPlace())
    mgr = resilience.CheckpointManager(
        os.path.join(workdir, "ckpt"), save_interval=1, keep=20)
    mgr.track_reader(loader, "train")
    restored = mgr.restore_or_initialize(
        exe, main_p, fluid.default_startup_program())
    mgr.attach(main_p)

    cursor = loader.state_dict()
    print(json.dumps({"resumed_from": restored, "world": world,
                      "cursor": cursor}), flush=True)

    per_epoch = N_SAMPLES // BATCH
    step_dt = float(os.environ.get("ELASTIC_STEP_DT", "0.05"))
    with open(result_path, "a") as result:
        for epoch in range(cursor["epoch"], EPOCHS):
            for feed in loader():
                idx = loader.state_dict()["batch"] - 1
                crc = zlib.crc32(
                    np.asarray(feed["x"]).tobytes()) & 0xFFFFFFFF
                (lv,) = exe.run(compiled, feed=feed, fetch_list=[loss])
                result.write(json.dumps({
                    "attempt": attempt, "world": world, "epoch": epoch,
                    "batch": idx, "gstep": epoch * per_epoch + idx,
                    "crc": crc,
                    "loss": float(np.asarray(lv).reshape(-1)[0]),
                }) + "\n")
                result.flush()
                if step_dt > 0:
                    time.sleep(step_dt)

    mgr.drain()
    print("WORKER_DONE", flush=True)


if __name__ == "__main__":
    main()
