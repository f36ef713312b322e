"""Op lowering registry — importing this package registers all ops.

The registry is the TPU-native analog of the reference's global OpInfoMap
populated by REGISTER_OPERATOR/REGISTER_OP_*_KERNEL static registrars
(paddle/fluid/framework/op_registry.h:199,240,243).
"""

from . import (  # noqa: F401
    crf_ops,
    ctc_ops,
    ctr_ops,
    detection_ops,
    detection_train_ops,
    fused_ops,
    linear_attn_ops,
    loss_ops,
    math_ops,
    misc_ops,
    moe_ops,
    nn_ops,
    optimizer_ops,
    quant_ops,
    registry,
    rnn_ops,
    sequence_ops,
    sparse_attn_ops,
    ssm_ops,
    tensor_ops,
    vision_ops,
)

# static shape/dtype functions attach to the OpDefs registered above
from . import shape_fns  # noqa: E402,F401
from .registry import (  # noqa: F401
    LoweringContext,
    get_op,
    get_shape_fn,
    has_op,
    has_shape_fn,
    register_op,
    register_shape,
)
