"""ResNet-50 v1.5 (Goyal et al. 2017, arXiv:1706.02677, at the widths of
He et al. 2015, table 1) with the initialisation its configuration
states: the Program through the repo's public builder, which takes the
seeded scale of a bottleneck's last batch normalisation as a keyword.

Everything but `build` is `benchmark/models/resnet50.py`'s, imported:
the batches, the FLOPs from the shapes, the float32 reference of the
evaluation forward and its tolerance. The reference reads the scales
from the scope like every other parameter, so it needs no change.

A `paddle_tpu` whose `models.resnet.resnet` lacks the keyword raises
`TypeError` from `build`, before any device work: a configuration that
names this adapter cannot be run with another initialisation than the
one it states.
"""

from __future__ import annotations

from benchmark.models.resnet50 import (  # noqa: F401 — the adapter's surface
    SCORED_SEQUENCES,
    TOLERANCE,
    flops_per_example,
    make_batch,
    reference,
    tokens_per_example,
)


def build(model: dict, traffic: dict) -> dict:
    from paddle_tpu import layers
    from paddle_tpu.framework import default_main_program
    from paddle_tpu.models.resnet import resnet

    b, hw = traffic["batch"], traffic["image_size"]
    img = layers.data("img", [b, 3, hw, hw], append_batch_size=False)
    label = layers.data("label", [b, 1], dtype="int64", append_batch_size=False)
    _, loss, _, _ = resnet(
        img, label, depth=model["depth"], class_num=model["num_classes"],
        bottleneck_last_bn_scale=model["init"]["bottleneck_last_bn_scale"])
    # as in resnet50.py: the logits are what the builder's last softmax reads
    block = default_main_program().global_block()
    softmax = [op for op in block.ops if op.type == "softmax"][-1]
    scored = layers.slice(block.var(softmax.inputs["X"][0]), axes=[0],
                          starts=[0], ends=[min(b, SCORED_SEQUENCES)])
    return {"loss": loss.name, "feeds": ["img", "label"],
            "check": [loss.name, scored.name]}
