"""The small click-through model of the host-table tests
(`tests/test_host_table.py`, `tests/test_sharded_table.py`) and a batch
for it: a plain module beside them."""

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.incubate.fleet.parameter_server.host_table import (
    host_embedding)


def build_ctr(main, startup, dim=8, max_unique=64, slots=2):
    """DeepFM-ish: sparse id embeddings + dense feature -> fc tower."""
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            ids = layers.data("ids", [16, slots], dtype="int64",
                              append_batch_size=False)
            dense = layers.data("dense", [16, 4], dtype="float32",
                                append_batch_size=False)
            label = layers.data("label", [16, 1], dtype="float32",
                                append_batch_size=False)
            emb = host_embedding(ids, "ctr_table", dim, max_unique)
            emb_sum = layers.reduce_sum(emb, dim=1)  # [b, dim]
            x = layers.concat([emb_sum, dense], axis=1)
            h = layers.fc(x, 16, act="relu")
            pred = layers.fc(h, 1, act="sigmoid")
            loss = layers.mean(
                layers.log_loss(pred, label, epsilon=1e-6)
            )
            fluid.optimizer.Adam(1e-2).minimize(loss)
    return loss


def batch(rng, vocab, slots=2):
    return {
        "ids": rng.randint(0, vocab, (16, slots)).astype("int64"),
        "dense": rng.rand(16, 4).astype("float32"),
        "label": (rng.rand(16, 1) > 0.5).astype("float32"),
    }
