"""Reference workload models (the benchmark's configurations + the
reference's test model zoo), built through the framework's own layers API —
LeNet-5 (MNIST), ResNet (ImageNet), SE-ResNeXt, VGG, Transformer/BERT
(WMT16 / pretrain), DeepFM (CTR)."""

from . import (  # noqa: F401
    bert,
    deepfm,
    lenet,
    resnet,
    se_resnext,
    transformer,
    vgg,
)
