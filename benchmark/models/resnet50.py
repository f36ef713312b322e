"""ResNet image classification (He et al. 2015, "Deep Residual Learning
for Image Recognition", table 1): the Program through the repo's public
builder, seeded images, FLOPs per image from the shapes, and the plain
reference. Shipped without a cell: the cell `resnet50_b128` (PERF.md,
Open questions) is then data files only.

The reference is the v1 network as published: 7x7 stem, 3x3 max pool,
four stages of bottleneck (or, below depth 50, basic) blocks with the
stride on the block's 3x3 (first, for basic blocks) convolution, global
average pool, one linear layer. Batch normalisation uses the moving
statistics, as the `for_test` clone does.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness.datagen import zipf_ids

SCORED_SEQUENCES = 8  # images whose logits are compared
# see benchmark/models/bert.py; convolutions round their inputs to bf16 too
TOLERANCE = {"logits_rel_rms": 0.03, "loss_abs": 0.06}

STAGES = {18: ([2, 2, 2, 2], False), 34: ([3, 4, 6, 3], False),
          50: ([3, 4, 6, 3], True), 101: ([3, 4, 23, 3], True)}


def build(model: dict, traffic: dict) -> dict:
    from paddle_tpu import layers
    from paddle_tpu.framework import default_main_program
    from paddle_tpu.models.resnet import resnet

    b, hw = traffic["batch"], traffic["image_size"]
    img = layers.data("img", [b, 3, hw, hw], append_batch_size=False)
    label = layers.data("label", [b, 1], dtype="int64", append_batch_size=False)
    _, loss, _, _ = resnet(img, label, depth=model["depth"],
                           class_num=model["num_classes"])
    # the builder hands back probabilities; the logits are what its last
    # softmax reads
    softmax = [op for op in default_main_program().global_block().ops
               if op.type == "softmax"][-1]
    logits = default_main_program().global_block().var(softmax.inputs["X"][0])
    scored = layers.slice(logits, axes=[0], starts=[0],
                          ends=[min(b, SCORED_SEQUENCES)])
    return {"loss": loss.name, "feeds": ["img", "label"],
            "check": [loss.name, scored.name]}


def make_batch(rng, model: dict, traffic: dict) -> dict:
    """Normalised images (zero mean, unit variance a pixel) and labels
    drawn Zipf(1.1) over the classes, so the classifier has a prior to
    learn."""
    b, hw = traffic["batch"], traffic["image_size"]
    return {
        "img": rng.standard_normal((b, 3, hw, hw)).astype(np.float32),
        "label": zipf_ids(rng, (b, 1), model["num_classes"]),
    }


def tokens_per_example(model: dict, traffic: dict) -> int:
    return 1  # an image


def _convs(model: dict, hw: int):
    """Every convolution as (name, c_in, c_out, kernel, stride, hw_out),
    in order, and at last the pooled feature width."""
    blocks, bottleneck = STAGES[model["depth"]]
    hw = (hw + 1) // 2
    yield "conv1", 3, 64, 7, 2, hw
    hw = (hw + 1) // 2  # the max pool
    c_in = 64
    for stage, n in enumerate(blocks):
        f = 64 * 2 ** stage
        c_out = 4 * f if bottleneck else f
        for blk in range(n):
            stride = 2 if blk == 0 and stage > 0 else 1
            name = f"res{stage + 2}{chr(ord('a') + blk)}"
            out = (hw + stride - 1) // stride
            if bottleneck:
                yield name + "_a", c_in, f, 1, 1, hw
                yield name + "_b", f, f, 3, stride, out
                yield name + "_c", f, c_out, 1, 1, out
            else:
                yield name + "_a", c_in, f, 3, stride, out
                yield name + "_b", f, f, 3, 1, out
            if c_in != c_out or stride != 1:
                yield name + "_sc", c_in, c_out, 1, stride, out
            c_in, hw = c_out, out


def flops_per_example(model: dict, traffic: dict) -> float:
    """2 x multiply-adds of every convolution and of the classifier,
    forward and backward (3 x forward). From the shapes: ResNet-50 at 224
    has 4.09e9 multiply-adds, so 8.2e9 FLOPs forward. (The repo's
    `RESNET50_TRAIN_FLOPS_PER_IMG` takes the 4.1e9 multiply-adds for
    FLOPs and so counts half of this.)"""
    convs = list(_convs(model, traffic["image_size"]))
    macs = sum(k * k * ci * co * hw * hw for _, ci, co, k, _, hw in convs)
    macs += convs[-1][2] * model["num_classes"]
    return 3.0 * 2.0 * macs


# ------------------------------------------------------------ reference


def _conv_bn(x, p, name, stride, relu, eps=1e-5):
    import jax
    import jax.numpy as jnp

    w = p[name + ".w_0"]
    pad = (w.shape[2] - 1) // 2
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    c = lambda v: v.reshape(1, -1, 1, 1)  # noqa: E731
    bn = name + "_bn"
    y = ((y - c(p[bn + ".mean"])) / jnp.sqrt(c(p[bn + ".var"]) + eps)
         * c(p[bn + ".w_0"]) + c(p[bn + ".w_1"]))
    return jax.nn.relu(y) if relu else y


def reference(p: dict, batch: dict, model: dict, drop_layers: int = 0):
    """Forward pass on some images. Returns the sum of the negative
    log-likelihoods, the number of images and the logits `[rows,
    classes]`. `drop_layers` leaves out that many of the last residual
    blocks (tests only)."""
    import jax
    import jax.numpy as jnp

    blocks, bottleneck = STAGES[model["depth"]]
    x = _conv_bn(batch["img"], p, "conv1", 2, True)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2), [(0, 0), (0, 0), (1, 1), (1, 1)])
    todo = sum(blocks) - drop_layers
    for stage, n in enumerate(blocks):
        for blk in range(n):
            stride = 2 if blk == 0 and stage > 0 else 1
            name = f"res{stage + 2}{chr(ord('a') + blk)}"
            todo -= 1
            if todo < 0 and stride == 1 and blk > 0:
                continue
            if bottleneck:
                y = _conv_bn(x, p, name + "_a", 1, True)
                y = _conv_bn(y, p, name + "_b", stride, True)
                y = _conv_bn(y, p, name + "_c", 1, False)
            else:
                y = _conv_bn(x, p, name + "_a", stride, True)
                y = _conv_bn(y, p, name + "_b", 1, False)
            if name + "_sc.w_0" in p:
                x = _conv_bn(x, p, name + "_sc", stride, False)
            x = jax.nn.relu(x + y)
    pooled = jnp.mean(x, axis=(2, 3))
    (w_name,) = [n for n in p if n.startswith("fc_") and n.endswith(".w_0")]
    logits = pooled @ p[w_name] + p[w_name[:-1] + "1"]  # bias: fc_N.w_1
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, batch["label"], axis=1)[:, 0]
    return jnp.sum(nll), jnp.float32(nll.shape[0]), logits
