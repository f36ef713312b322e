"""Worker for the host-table kill/resume test (reference
checkpoint_notify_op.cc:49-87 + io.py:306 _save_distributed_persistables:
pserver table shards persist and training resumes from them).

Modes (argv[1] = workdir, argv[2] = mode):
  full    — train steps 0..N-1, checkpointing at step CKPT; print losses
  killed  — same, but after the checkpoint lands print CKPT_DONE and
            hang (the parent SIGKILLs us mid-"training")
  resume  — load the checkpoint, train steps CKPT+1..N-1, print losses
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
from paddle_tpu import layers  # noqa: E402
from paddle_tpu.incubate.fleet.parameter_server.host_table import (  # noqa: E402
    HostEmbeddingTable,
    HostTableSession,
    host_embedding,
    load_distributed_persistables,
    save_distributed_persistables,
)

STEPS, CKPT, BATCH, VOCAB, DIM, MAXU = 10, 4, 16, 50_000, 8, 64


def batch_for_step(step):
    rng = np.random.RandomState(1000 + step)
    return {
        "ids": rng.randint(0, VOCAB, (BATCH, 2)).astype("int64"),
        "dense": rng.rand(BATCH, 4).astype("float32"),
        "label": (rng.rand(BATCH, 1) > 0.5).astype("float32"),
    }


def main():
    workdir, mode = sys.argv[1], sys.argv[2]
    ckpt_dir = os.path.join(workdir, "ckpt")

    main_p = fluid.default_main_program()
    main_p.random_seed = 7
    ids = layers.data("ids", [BATCH, 2], dtype="int64",
                      append_batch_size=False)
    dense = layers.data("dense", [BATCH, 4], dtype="float32",
                        append_batch_size=False)
    label = layers.data("label", [BATCH, 1], dtype="float32",
                        append_batch_size=False)
    emb = host_embedding(ids, "ctr_table", DIM, MAXU)
    emb_sum = layers.reduce_sum(emb, dim=1)
    x = layers.concat([emb_sum, dense], axis=1)
    h = layers.fc(x, 16, act="relu")
    pred = layers.fc(h, 1, act="sigmoid")
    loss = layers.mean(layers.log_loss(pred, label, epsilon=1e-6))
    fluid.optimizer.Adam(1e-2).minimize(loss)

    table = HostEmbeddingTable(
        VOCAB, DIM, lr=0.1, optimizer="adagrad", seed=5,
        mmap_path=os.path.join(workdir, f"table_{mode}.dat"),
    )
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    sess = HostTableSession(
        exe, main_p, {"ctr_table": (table, "ids", MAXU)}
    )

    start = 0
    if mode == "resume":
        load_distributed_persistables(exe, ckpt_dir, main_p, sess)
        start = CKPT + 1

    for step in range(start, STEPS):
        (lv,) = sess.run(feed=batch_for_step(step), fetch_list=[loss])
        print(json.dumps(
            {"step": step, "loss": float(np.asarray(lv).reshape(-1)[0])}
        ), flush=True)
        if step == CKPT and mode in ("full", "killed"):
            save_distributed_persistables(
                exe, ckpt_dir, main_p, sess, num_shards=3
            )
            if mode == "killed":
                print("CKPT_DONE", flush=True)
                time.sleep(600)  # parent SIGKILLs us here

    print("WORKER_DONE", flush=True)


if __name__ == "__main__":
    main()
