"""Supervised elastic-training worker (tests/test_trainer_fleet.py and
the tools/ci.sh elastic-chaos stage).

A small dropout MLP trained over a DataLoader with a seeded per-epoch
shuffle, wired for EXACT resume: `CheckpointManager.track_reader` rides
the data cursor in every snapshot manifest next to `seed_counter`, and
`restore_or_initialize` rewinds both — so however many times the
supervisor kills and respawns this process, the union of its per-step
logs must be bitwise-identical to an uninterrupted run (same batch for
every global step, same loss — no batch replayed, none skipped).

argv: workdir
env:  ELASTIC_RESULT  — JSONL file APPENDED across attempts; one line
                        per trained step: {attempt, epoch, batch, crc,
                        loss} (crc = crc32 of the step's x batch bytes —
                        the data-cursor fingerprint)
      ELASTIC_STEP_DT — seconds slept per step (default 0.05). The
                        supervisor observes heartbeats at its poll
                        interval (50 ms): steps at least that long keep
                        every step value observable, so a seed-pinned
                        fleet.kill_trainer:nth=N lands at (or within a
                        step of) global step N instead of wherever a
                        sub-poll-interval run happened to be — and can
                        never miss a run that finishes inside one poll
                        gap.
      PADDLE_TPU_TRAINER_ATTEMPT — set by the TrainSupervisor
"""

import json
import os
import sys
import time
import zlib

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import layers, resilience  # noqa: E402
from paddle_tpu import reader as rdr  # noqa: E402

EPOCHS, N_SAMPLES, BATCH = 3, 48, 8  # 6 batches/epoch, 18 steps total


def samples():
    for i in range(N_SAMPLES):
        rs = np.random.RandomState(1000 + i)
        x = rs.rand(6).astype("float32")
        y = np.asarray([x.sum() * 0.5], dtype="float32")
        yield (x, y)


def main():
    workdir = sys.argv[1]
    attempt = int(os.environ.get("PADDLE_TPU_TRAINER_ATTEMPT", "0"))
    result_path = os.environ["ELASTIC_RESULT"]

    main_p = fluid.default_main_program()
    main_p.random_seed = 7
    x = layers.data("x", [6])
    y = layers.data("y", [1])
    h = layers.fc(x, 16, act="relu")
    h = layers.dropout(h, dropout_prob=0.3)  # PRNG half of exact resume
    pred = layers.fc(h, 1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    fluid.optimizer.Adam(1e-2).minimize(loss)

    loader = rdr.DataLoader.from_generator([x, y], capacity=4)
    loader.set_sample_generator(samples, batch_size=BATCH, drop_last=True,
                                shuffle_buf=16, shuffle_seed=11)

    exe = fluid.Executor(fluid.CPUPlace())
    mgr = resilience.CheckpointManager(
        os.path.join(workdir, "ckpt"), save_interval=1, keep=10)
    mgr.track_reader(loader, "train")
    restored = mgr.restore_or_initialize(
        exe, main_p, fluid.default_startup_program())
    mgr.attach(main_p)

    cursor = loader.state_dict()
    print(json.dumps({"resumed_from": restored, "cursor": cursor}),
          flush=True)

    step_dt = float(os.environ.get("ELASTIC_STEP_DT", "0.05"))
    with open(result_path, "a") as result:
        for epoch in range(cursor["epoch"], EPOCHS):
            for feed in loader():
                idx = loader.state_dict()["batch"] - 1  # this batch's raw
                crc = zlib.crc32(
                    np.asarray(feed["x"]).tobytes()) & 0xFFFFFFFF
                (lv,) = exe.run(main_p, feed=feed, fetch_list=[loss])
                result.write(json.dumps({
                    "attempt": attempt, "epoch": epoch, "batch": idx,
                    "crc": crc,
                    "loss": float(np.asarray(lv).reshape(-1)[0]),
                }) + "\n")
                result.flush()
                if step_dt > 0:
                    time.sleep(step_dt)  # see ELASTIC_STEP_DT above

    mgr.drain()
    print("WORKER_DONE", flush=True)


if __name__ == "__main__":
    main()
