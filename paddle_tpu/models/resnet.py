"""ResNet-50 (the reference's image_classification workload; the
benchmark's resnet50_b128 cell). NCHW; bottlenecks with the stride on the 3x3
convolution ("v1.5"), like the reference model zoo.
"""

from __future__ import annotations

import math

from .. import layers
from ..initializer import Constant, Uniform
from ..param_attr import ParamAttr

__all__ = ["resnet50", "resnet", "RESNET50_TRAIN_FLOPS_PER_IMG"]

# FLOPs of one training step an image at 224x224. The 53 convolutions and
# the classifier are 4.09e9 multiply-adds forward with a bottleneck's
# stride on its 3x3 convolution (He et al. 2015, table 1, has it on the
# first 1x1 and counts 3.8e9); a multiply-add is 2 FLOPs, and the backward
# pass is twice the forward. `tests/test_resnet_reference.py` holds this
# to the count from the shapes (`benchmark/models/resnet50.py`, 24.535e9).
RESNET50_TRAIN_FLOPS_PER_IMG = 3 * 2 * 4.09e9

_DEPTH_CFG = {
    18: ([2, 2, 2, 2], False),
    34: ([3, 4, 6, 3], False),
    50: ([3, 4, 6, 3], True),
    101: ([3, 4, 23, 3], True),
    152: ([3, 8, 36, 3], True),
}


def _conv_bn(x, num_filters, filter_size, stride=1, act=None, name=None,
             groups=1, bn_scale=1.0):
    conv = layers.conv2d(
        x,
        num_filters=num_filters,
        filter_size=filter_size,
        stride=stride,
        padding=(filter_size - 1) // 2,
        groups=groups,
        bias_attr=False,
        name=name,
    )
    return layers.batch_norm(
        conv, act=act, name=name + "_bn" if name else None,
        param_attr=ParamAttr(initializer=Constant(bn_scale)))


def _shortcut(x, num_filters, stride, name):
    if x.shape[1] != num_filters or stride != 1:
        return _conv_bn(x, num_filters, 1, stride, name=name + "_sc")
    return x


def _bottleneck(x, num_filters, stride, name, last_bn_scale):
    c1 = _conv_bn(x, num_filters, 1, act="relu", name=name + "_a")
    c2 = _conv_bn(c1, num_filters, 3, stride=stride, act="relu", name=name + "_b")
    c3 = _conv_bn(c2, num_filters * 4, 1, name=name + "_c",
                  bn_scale=last_bn_scale)
    sc = _shortcut(x, num_filters * 4, stride, name)
    return layers.elementwise_add(sc, c3, act="relu")


def _basic(x, num_filters, stride, name):
    c1 = _conv_bn(x, num_filters, 3, stride=stride, act="relu", name=name + "_a")
    c2 = _conv_bn(c1, num_filters, 3, name=name + "_b")
    sc = _shortcut(x, num_filters, stride, name)
    return layers.elementwise_add(sc, c2, act="relu")


def resnet(img, label=None, depth=50, class_num=1000,
           bottleneck_last_bn_scale=1.0):
    """`bottleneck_last_bn_scale` seeds the scale of the last batch
    normalisation of every bottleneck block (depth 50 and up; basic
    blocks keep 1). He et al. 2015 start it at 1, the default; Goyal et
    al. 2017 (arXiv:1706.02677, section 5.1) at 0, so that every block
    starts as the identity. An evaluation before any training reads moving
    statistics of 0 and 1, that is, does not normalise, so this scale
    alone decides how fast the residual stream grows through the blocks
    there (PERF.md, PR 27)."""
    blocks, use_bottleneck = _DEPTH_CFG[depth]
    x = _conv_bn(img, 64, 7, stride=2, act="relu", name="conv1")
    x = layers.pool2d(x, pool_size=3, pool_type="max", pool_stride=2,
                      pool_padding=1)
    num_filters = [64, 128, 256, 512]
    for stage, n in enumerate(blocks):
        for blk in range(n):
            stride = 2 if blk == 0 and stage > 0 else 1
            name = f"res{stage + 2}{chr(ord('a') + blk)}"
            if use_bottleneck:
                x = _bottleneck(x, num_filters[stage], stride, name,
                                bottleneck_last_bn_scale)
            else:
                x = _basic(x, num_filters[stage], stride, name)
    pool = layers.pool2d(x, pool_type="avg", global_pooling=True)
    stdv = 1.0 / math.sqrt(float(pool.shape[1]))
    pred = layers.fc(
        pool,
        class_num,
        act="softmax",
        param_attr=ParamAttr(initializer=Uniform(-stdv, stdv)),
    )
    if label is None:
        return pred
    loss = layers.mean(layers.cross_entropy(pred, label))
    acc1 = layers.accuracy(pred, label, k=1)
    acc5 = layers.accuracy(pred, label, k=5)
    return pred, loss, acc1, acc5


def resnet50(img, label=None, class_num=1000):
    return resnet(img, label, depth=50, class_num=class_num)
