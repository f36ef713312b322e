"""Worker for the resilience kill/resume test (reference io.py:487
save_persistables round-trips + the pserver-crash story of
checkpoint_notify_op.cc — here generalized to any training run via
paddle_tpu.resilience).

Modes (argv[1] = workdir, argv[2] = mode):
  full    — train steps 0..STEPS-1 with auto-checkpointing; print losses
  killed  — same, but after step CKPT's snapshot commits print CKPT_DONE,
            slow down snapshot file writes (test-hook env), run step
            CKPT+1 (whose async save is now mid-flush), print SAVING and
            hang — the parent SIGKILLs us with the flush torn in @tmp
  resume  — restore_or_initialize from the newest VALID snapshot (the
            torn one must be skipped), train the remaining steps; losses
            must match `full` bitwise (dropout active: the snapshot's
            seed_counter replays the exact mask sequence)
"""

import json
import os
import sys
import time

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import layers, resilience  # noqa: E402

STEPS, CKPT, BATCH = 10, 5, 8


def batch_for_step(step):
    rng = np.random.RandomState(1000 + step)
    return {
        "x": rng.rand(BATCH, 6).astype("float32"),
        "y": rng.rand(BATCH, 1).astype("float32"),
    }


def main():
    workdir, mode = sys.argv[1], sys.argv[2]
    root = os.path.join(workdir, "ckpt")

    main_p = fluid.default_main_program()
    main_p.random_seed = 7
    x = layers.data("x", [BATCH, 6], append_batch_size=False)
    y = layers.data("y", [BATCH, 1], append_batch_size=False)
    h = layers.fc(x, 16, act="relu")
    h = layers.dropout(h, dropout_prob=0.3)  # exercises seed_counter resume
    pred = layers.fc(h, 1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    fluid.optimizer.Adam(1e-2).minimize(loss)

    exe = fluid.Executor(fluid.CPUPlace())
    mgr = resilience.CheckpointManager(root, save_interval=1, keep=4)

    start = 0
    if mode == "resume":
        restored = mgr.restore_or_initialize(
            exe, main_p, fluid.default_startup_program()
        )
        print(json.dumps({"resumed_from": restored}), flush=True)
        start = restored + 1
    else:
        exe.run(fluid.default_startup_program())
    mgr.attach(main_p)

    for step in range(start, STEPS):
        if mode == "killed" and step == CKPT + 1:
            mgr.drain()  # snapshot CKPT is committed on disk
            print("CKPT_DONE", flush=True)
            # slow every subsequent snapshot file write: step CKPT+1's
            # async flush stays in progress for many seconds
            os.environ["PADDLE_TPU_CKPT_TEST_SLEEP_PER_FILE"] = "0.25"
        (lv,) = exe.run(feed=batch_for_step(step), fetch_list=[loss])
        print(json.dumps(
            {"step": step, "loss": float(np.asarray(lv).reshape(-1)[0])}
        ), flush=True)
        if mode == "killed" and step == CKPT + 1:
            print("SAVING", flush=True)
            time.sleep(600)  # parent SIGKILLs us mid-flush here

    mgr.drain()
    print("WORKER_DONE", flush=True)


if __name__ == "__main__":
    main()
