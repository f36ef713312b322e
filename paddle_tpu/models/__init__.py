"""Reference workload models (the benchmark's configurations + the
reference's test model zoo), built through the framework's own layers API —
LeNet-5 (MNIST), ResNet (ImageNet), SE-ResNeXt, VGG, Transformer/BERT
(WMT16 / pretrain), DeepFM (CTR), Kimi Linear, Trinity, Mellum, JoyAI
Flash, LFM2, Qwen3-Next and Nemotron-H (each a share of an
expert-parallel decoder; Nemotron-H's mixers hold a share of their heads
too), Keye-VL-2.0's language model (a share of an expert-parallel
decoder whose attention keeps the keys a learned indexer chooses),
SDAR's language model (the same share of the same decoder trained as a
block-diffusion denoiser: a noisy and a clean copy of each row under one
block-granular attention mask),
Phi-4-mini-flash (a pipeline stage's share of a
decoder-hybrid-decoder), Ouro (a pipeline stage's share of a decoder
that runs its layers several times with one set of weights), and
Olmo-Hybrid (a pipeline stage's share of a dense decoder whose mixers are
Gated DeltaNet layers with key and value heads of two widths and
attention without positions, every sublayer's output normed), and
Granite 4.0-H (a pipeline stage's share of a dense decoder of whole-width
Mamba-2 mixers and attention without positions under the family's four
multipliers, on one tied table)."""

from . import (  # noqa: F401
    bert,
    deepfm,
    granite_hybrid,
    joyai_flash,
    keye_vl2,
    kimi_linear,
    lenet,
    lfm2,
    mellum,
    nemotron_h,
    olmo_hybrid,
    ouro,
    phi4_flash,
    qwen3_next,
    resnet,
    sdar,
    se_resnext,
    transformer,
    trinity,
    vgg,
)
from .granite_hybrid import GraniteHybridConfig, build_granite_hybrid  # noqa: E402,F401
from .joyai_flash import JoyAIFlashConfig, build_joyai_flash  # noqa: E402,F401
from .keye_vl2 import KeyeVL2Config, build_keye_vl2  # noqa: E402,F401
from .kimi_linear import KimiLinearConfig, build_kimi_linear  # noqa: E402,F401
from .lfm2 import Lfm2Config, build_lfm2  # noqa: E402,F401
from .mellum import MellumConfig, build_mellum  # noqa: E402,F401
from .nemotron_h import NemotronHConfig, build_nemotron_h  # noqa: E402,F401
from .olmo_hybrid import OlmoHybridConfig, build_olmo_hybrid  # noqa: E402,F401
from .ouro import OuroConfig, build_ouro  # noqa: E402,F401
from .phi4_flash import Phi4FlashConfig, build_phi4_flash  # noqa: E402,F401
from .qwen3_next import Qwen3NextConfig, build_qwen3_next  # noqa: E402,F401
from .sdar import SdarConfig, build_sdar  # noqa: E402,F401
from .trinity import TrinityConfig, build_trinity  # noqa: E402,F401
