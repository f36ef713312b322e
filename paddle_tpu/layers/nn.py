"""Neural-net layers (reference: python/paddle/fluid/layers/nn.py:38 —
fc, embedding, conv2d, batch_norm, dropout, softmax_with_cross_entropy, ...).

Each layer builds IR ops into the default main program; shapes are inferred
here at build time (the reference does this in C++ InferShape,
framework/operator.h:455)."""

from __future__ import annotations

import math

import numpy as np

from ..framework import Variable, unique_name
from ..initializer import Constant, Normal, Uniform, Xavier
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr

__all__ = [
    "resize_trilinear",
    "trilinear_interp",
    "var_conv_2d",
    "conv3d",
    "brelu",
    "scatter_nd",
    "shard_index",
    "unique",
    "npair_loss",
    "py_func",
    "tree_conv",
    "warpctc",
    "ctc_greedy_decoder",
    "edit_distance",
    "affine_channel",
    "affine_grid",
    "grid_sampler",
    "spectral_norm",
    "temporal_shift",
    "shuffle_channel",
    "space_to_depth",
    "pool3d",
    "im2sequence",
    "row_conv",
    "psroi_pool",
    "deformable_conv",
    "deformable_roi_pooling",
    "bilinear_tensor_product",
    "fsp_matrix",
    "conv_shift",
    "add_position_encoding",
    "pad_constant_like",
    "conv3d_transpose",
    "unpool",
    "max_pool2d_with_index",
    "spp",
    "continuous_value_model",
    "data_norm",
    "cos_sim",
    "rank_loss",
    "margin_rank_loss",
    "bpr_loss",
    "hinge_loss",
    "modified_huber_loss",
    "teacher_student_sigmoid_loss",
    "squared_l2_distance",
    "center_loss",
    "sampled_softmax_with_cross_entropy",
    "selu",
    "mean_iou",
    "multiplex",
    "crop",
    "fc",
    "moe",
    "moe_experts",
    "rms_norm",
    "short_conv1d",
    "selective_scan",
    "ssd_scan",
    "kda_attention",
    "embedding",
    "conv2d",
    "conv2d_transpose",
    "pool2d",
    "batch_norm",
    "sync_batch_norm",
    "layer_norm",
    "group_norm",
    "instance_norm",
    "dropout",
    "softmax",
    "log_softmax",
    "cross_entropy",
    "softmax_with_cross_entropy",
    "sigmoid_cross_entropy_with_logits",
    "log_loss",
    "square_error_cost",
    "huber_loss",
    "kldiv_loss",
    "smooth_l1",
    "mean",
    "mul",
    "matmul",
    "bmm",
    "elementwise_add",
    "elementwise_sub",
    "elementwise_mul",
    "elementwise_div",
    "elementwise_max",
    "elementwise_min",
    "elementwise_pow",
    "elementwise_mod",
    "reduce_sum",
    "reduce_mean",
    "reduce_max",
    "reduce_min",
    "reduce_prod",
    "reduce_all",
    "reduce_any",
    "clip",
    "clip_by_norm",
    "l2_normalize",
    "relu",
    "leaky_relu",
    "prelu",
    "relu6",
    "elu",
    "swish",
    "hard_swish",
    "hard_sigmoid",
    "gelu",
    "soft_relu",
    "maxout",
    "fused_multihead_attention",
    "sparse_index",
    "sparse_select",
    "index_kl",
    "rotary_embedding",
    "topk",
    "accuracy",
    "auc",
    "linear_chain_crf",
    "nce",
    "hsigmoid",
    "crf_decoding",
    "one_hot",
    "scale",
    "dist",
    "pad",
    "pad2d",
    "label_smooth",
    "lrn",
    "flatten",
    "unfold",
    "image_resize",
    "resize_nearest",
    "resize_bilinear",
    "pixel_shuffle",
    "split",
    "slice",
    "strided_slice",
    "gather",
    "gather_nd",
    "scatter",
    "scatter_nd_add",
    "where",
    "cond_select",
    "expand",
    "expand_as",
    "stack",
    "unstack",
    "squeeze",
    "unsqueeze",
    "reshape",
    "transpose",
    "shape",
    "cumsum",
    "argmax",
    "argmin",
    "argsort",
    "logsumexp",
    "matmul_v2",
    "uniform_random_batch_size_like",
    "gaussian_random",
    "sampling_id",
]

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _elementwise_out_shape(xs, ys):
    if xs is None or ys is None:
        return xs or ys
    return xs if len(xs) >= len(ys) else ys


def _single_out(helper, op_type, inputs, attrs=None, dtype=None, shape=None, out_slot="Out"):
    first = None
    for vs in inputs.values():
        for v in vs:
            if isinstance(v, Variable):
                first = v
                break
        if first:
            break
    dtype = dtype or (first.dtype if first else "float32")
    out = helper.create_variable_for_type_inference(dtype, shape)
    helper.append_op(
        type=op_type, inputs=inputs, outputs={out_slot: [out]}, attrs=attrs or {}
    )
    return out


# ---------------------------------------------------------------------------
# dense / embedding
# ---------------------------------------------------------------------------


def fc(
    input,
    size,
    num_flatten_dims=1,
    param_attr=None,
    bias_attr=None,
    act=None,
    name=None,
):
    """reference: layers/nn.py `fc` — mul(+sum) + bias + act. Lowers to one
    MXU matmul per input."""
    helper = LayerHelper("fc", name=name, act=act)
    inputs = input if isinstance(input, (list, tuple)) else [input]
    param_attrs = ParamAttr._to_attr(param_attr)
    if not isinstance(param_attrs, list):
        param_attrs = [param_attrs] * len(inputs)
    mul_results = []
    for x, pattr in zip(inputs, param_attrs):
        in_dim = int(np.prod(x.shape[num_flatten_dims:]))
        w = helper.create_parameter(pattr, [in_dim, size], dtype=x.dtype)
        out_shape = tuple(x.shape[:num_flatten_dims]) + (size,)
        tmp = helper.create_variable_for_type_inference(x.dtype, out_shape)
        helper.append_op(
            type="mul",
            inputs={"X": [x], "Y": [w]},
            outputs={"Out": [tmp]},
            attrs={"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1},
        )
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(
            mul_results[0].dtype, mul_results[0].shape
        )
        helper.append_op(
            type="sum", inputs={"X": mul_results}, outputs={"Out": [pre_bias]}
        )
    pre_act = helper.append_bias_op(pre_bias, bias_attr, size, num_flatten_dims)
    return helper.append_activation(pre_act)


def _suffixed_attr(param_attr, suffix, **overrides):
    """One param_attr names several parameters: a copy for each with the
    name suffixed, so a named ParamAttr doesn't silently alias them onto
    one variable, and with `overrides` set on it."""
    import copy

    a = copy.copy(ParamAttr._to_attr(param_attr))
    if a and a.name:
        a.name = f"{a.name}.{suffix}"
    for key, value in overrides.items():
        setattr(a, key, value)
    return a


def moe(
    input,
    num_experts,
    d_ff,
    capacity_factor=1.25,
    k=2,
    param_attr=None,
    name=None,
    ep_axis="ep",
):
    """Mixture-of-Experts FFN layer (GShard top-k dense dispatch; no
    reference counterpart — Fluid ~1.5 has no MoE, built TPU-first per
    SURVEY §2.8). Expert parameters are annotated to shard their leading
    (expert) dim over the `ep_axis` mesh axis; under a mesh with that
    axis, GSPMD lowers the dispatch/combine einsums to the all-to-all
    over ICI. Returns (out, aux_loss): add `aux_loss` (shape [1], the
    load-balance loss) to the training objective."""
    helper = LayerHelper("moe", name=name)
    d = int(input.shape[-1])

    def pattr(suffix):
        return _suffixed_attr(param_attr, suffix)

    gate = helper.create_parameter(pattr("gate"), [d, num_experts],
                                   dtype=input.dtype)
    w1 = helper.create_parameter(pattr("w1"), [num_experts, d, d_ff],
                                 dtype=input.dtype)
    b1 = helper.create_parameter(pattr("b1"), [num_experts, d_ff],
                                 dtype=input.dtype, is_bias=True)
    w2 = helper.create_parameter(pattr("w2"), [num_experts, d_ff, d],
                                 dtype=input.dtype)
    b2 = helper.create_parameter(pattr("b2"), [num_experts, d],
                                 dtype=input.dtype, is_bias=True)
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    aux = helper.create_variable_for_type_inference(input.dtype, (1,))
    helper.append_op(
        type="moe_ffn",
        inputs={"X": [input], "Gate": [gate.name], "W1": [w1.name],
                "B1": [b1.name], "W2": [w2.name], "B2": [b2.name]},
        outputs={"Out": [out], "AuxLoss": [aux]},
        attrs={"capacity_factor": float(capacity_factor), "k": int(k)},
    )
    from ..parallel import shard_parameter

    prog = helper.main_program
    from jax.sharding import PartitionSpec as _P

    for p_ in (w1, b1, w2, b2):
        shard_parameter(prog, p_.name, _P(ep_axis))
    return out, aux


def embedding(
    input,
    size,
    is_sparse=False,
    is_distributed=False,
    padding_idx=None,
    param_attr=None,
    dtype="float32",
    name=None,
):
    """reference: layers/nn.py `embedding` → lookup_table op. is_sparse is
    accepted for API parity; the grad is always the dense scatter-add (XLA)."""
    helper = LayerHelper("embedding", name=name)
    w = helper.create_parameter(
        param_attr, list(size), dtype=dtype, default_initializer=Xavier()
    )
    in_shape = tuple(input.shape)
    out_shape = (
        in_shape[:-1] if in_shape and in_shape[-1] == 1 else in_shape
    ) + (size[1],)
    out = helper.create_variable_for_type_inference(dtype, out_shape)
    padding_idx = (
        -1
        if padding_idx is None
        else padding_idx
        if padding_idx >= 0
        else size[0] + padding_idx
    )
    helper.append_op(
        type="lookup_table",
        inputs={"W": [w], "Ids": [input]},
        outputs={"Out": [out]},
        attrs={
            "padding_idx": padding_idx,
            "is_sparse": is_sparse,
            "is_distributed": is_distributed,
        },
    )
    return out


# ---------------------------------------------------------------------------
# conv / pool / norm
# ---------------------------------------------------------------------------


def _pair(v):
    return list(v) if isinstance(v, (list, tuple)) else [v, v]


def _conv_out_dim(in_dim, k, pad, stride, dilation=1):
    if in_dim in (-1, None):
        return -1
    eff = dilation * (k - 1) + 1
    return (in_dim + 2 * pad - eff) // stride + 1


def conv2d(
    input,
    num_filters,
    filter_size,
    stride=1,
    padding=0,
    dilation=1,
    groups=1,
    param_attr=None,
    bias_attr=None,
    use_cudnn=True,
    act=None,
    name=None,
    data_format="NCHW",
):
    """reference: layers/nn.py `conv2d` (conv_op.cc). NCHW only."""
    helper = LayerHelper("conv2d", name=name, act=act)
    ksize = _pair(filter_size)
    stride = _pair(stride)
    padding = _pair(padding)
    dilation = _pair(dilation)
    groups = groups or 1
    c_in = input.shape[1]
    w_shape = [num_filters, c_in // groups] + ksize
    fan_in = (c_in // groups) * ksize[0] * ksize[1]
    w = helper.create_parameter(
        param_attr,
        w_shape,
        dtype=input.dtype,
        default_initializer=Normal(0.0, (2.0 / fan_in) ** 0.5),
    )
    out_shape = (
        input.shape[0],
        num_filters,
        _conv_out_dim(input.shape[2], ksize[0], padding[0], stride[0], dilation[0]),
        _conv_out_dim(input.shape[3], ksize[1], padding[1], stride[1], dilation[1]),
    )
    out = helper.create_variable_for_type_inference(input.dtype, out_shape)
    helper.append_op(
        type="conv2d",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={
            "strides": stride,
            "paddings": padding,
            "dilations": dilation,
            "groups": groups,
        },
    )
    pre_act = helper.append_bias_op(out, bias_attr, num_filters, 1)
    return helper.append_activation(pre_act)


def conv2d_transpose(
    input,
    num_filters,
    output_size=None,
    filter_size=None,
    padding=0,
    stride=1,
    dilation=1,
    groups=1,
    param_attr=None,
    bias_attr=None,
    act=None,
    name=None,
):
    helper = LayerHelper("conv2d_transpose", name=name, act=act)
    stride = _pair(stride)
    padding = _pair(padding)
    dilation = _pair(dilation)
    groups = groups or 1
    c_in = input.shape[1]
    if filter_size is None:
        raise ValueError("filter_size required")
    ksize = _pair(filter_size)
    w = helper.create_parameter(
        param_attr, [c_in, num_filters // groups] + ksize, dtype=input.dtype
    )

    def _o(i, k, p, s, d):
        if i in (-1, None):
            return -1
        return (i - 1) * s - 2 * p + d * (k - 1) + 1

    out_shape = (
        input.shape[0],
        num_filters,
        _o(input.shape[2], ksize[0], padding[0], stride[0], dilation[0]),
        _o(input.shape[3], ksize[1], padding[1], stride[1], dilation[1]),
    )
    out = helper.create_variable_for_type_inference(input.dtype, out_shape)
    helper.append_op(
        type="conv2d_transpose",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={
            "strides": stride,
            "paddings": padding,
            "dilations": dilation,
            "groups": groups,
        },
    )
    pre_act = helper.append_bias_op(out, bias_attr, num_filters, 1)
    return helper.append_activation(pre_act)


def pool2d(
    input,
    pool_size=-1,
    pool_type="max",
    pool_stride=1,
    pool_padding=0,
    global_pooling=False,
    use_cudnn=True,
    ceil_mode=False,
    exclusive=True,
    name=None,
):
    helper = LayerHelper("pool2d", name=name)
    ksize = _pair(pool_size)
    stride = _pair(pool_stride)
    padding = _pair(pool_padding)
    if global_pooling:
        out_shape = (input.shape[0], input.shape[1], 1, 1)
    else:
        def _o(i, k, p, s):
            if i in (-1, None):
                return -1
            if ceil_mode:
                return (i - k + 2 * p + s - 1) // s + 1
            return (i - k + 2 * p) // s + 1

        out_shape = (
            input.shape[0],
            input.shape[1],
            _o(input.shape[2], ksize[0], padding[0], stride[0]),
            _o(input.shape[3], ksize[1], padding[1], stride[1]),
        )
    out = helper.create_variable_for_type_inference(input.dtype, out_shape)
    helper.append_op(
        type="pool2d",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={
            "pooling_type": pool_type,
            "ksize": ksize,
            "strides": stride,
            "paddings": padding,
            "global_pooling": global_pooling,
            "ceil_mode": ceil_mode,
            "exclusive": exclusive,
        },
    )
    return out


def batch_norm(
    input,
    act=None,
    is_test=False,
    momentum=0.9,
    epsilon=1e-5,
    param_attr=None,
    bias_attr=None,
    data_layout="NCHW",
    name=None,
    moving_mean_name=None,
    moving_variance_name=None,
    use_global_stats=False,
):
    """reference: layers/nn.py `batch_norm` (batch_norm_op.cc). Running stats
    are persistable state vars functionally updated each step."""
    helper = LayerHelper("batch_norm", name=name, act=act)
    c = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    scale = helper.create_parameter(
        param_attr, [c], dtype="float32", default_initializer=Constant(1.0)
    )
    bias = helper.create_parameter(bias_attr, [c], dtype="float32", is_bias=True)
    mean = helper.create_or_get_global_variable(
        moving_mean_name or helper.prefix + ".mean",
        [c],
        "float32",
        initializer=Constant(0.0),
    )
    variance = helper.create_or_get_global_variable(
        moving_variance_name or helper.prefix + ".var",
        [c],
        "float32",
        initializer=Constant(1.0),
    )
    saved_mean = helper.create_variable_for_type_inference("float32", (c,))
    saved_var = helper.create_variable_for_type_inference("float32", (c,))
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    helper.append_op(
        type="batch_norm",
        inputs={
            "X": [input],
            "Scale": [scale],
            "Bias": [bias],
            "Mean": [mean],
            "Variance": [variance],
        },
        outputs={
            "Y": [out],
            "MeanOut": [mean],
            "VarianceOut": [variance],
            "SavedMean": [saved_mean],
            "SavedVariance": [saved_var],
        },
        attrs={
            "momentum": momentum,
            "epsilon": epsilon,
            "is_test": is_test,
            "data_layout": data_layout,
            "use_global_stats": use_global_stats,
        },
    )
    return helper.append_activation(out)


def sync_batch_norm(input, act=None, momentum=0.9, epsilon=1e-5,
                    param_attr=None, bias_attr=None, data_layout="NCHW",
                    name=None):
    """Cross-replica batch norm (reference: sync_batch_norm_op.cu +
    sync_batch_norm_pass, details/build_strategy.cc:61).

    On TPU this IS batch_norm: the program has single-device semantics and
    the batch dim is sharded over the mesh, so the mean/variance XLA
    computes are already the GLOBAL batch stats — GSPMD inserts the
    cross-replica reductions the reference implements by hand in CUDA."""
    return batch_norm(
        input, act=act, momentum=momentum, epsilon=epsilon,
        param_attr=param_attr, bias_attr=bias_attr,
        data_layout=data_layout, name=name,
    )


def layer_norm(
    input,
    scale=True,
    shift=True,
    begin_norm_axis=1,
    epsilon=1e-5,
    param_attr=None,
    bias_attr=None,
    act=None,
    name=None,
):
    helper = LayerHelper("layer_norm", name=name, act=act)
    norm_dim = int(np.prod(input.shape[begin_norm_axis:]))
    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(
            param_attr, [norm_dim], dtype="float32", default_initializer=Constant(1.0)
        )
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(
            bias_attr, [norm_dim], dtype="float32", is_bias=True
        )
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    mean = helper.create_variable_for_type_inference(
        "float32", input.shape[:begin_norm_axis]
    )
    var = helper.create_variable_for_type_inference(
        "float32", input.shape[:begin_norm_axis]
    )
    helper.append_op(
        type="layer_norm",
        inputs=inputs,
        outputs={"Y": [out], "Mean": [mean], "Variance": [var]},
        attrs={"epsilon": epsilon, "begin_norm_axis": begin_norm_axis},
    )
    return helper.append_activation(out)


def rms_norm(input, begin_norm_axis=1, epsilon=1e-5, param_attr=None,
             name=None):
    """`x / sqrt(mean(x^2) + epsilon) * scale` over the axes from
    `begin_norm_axis` on, with a learned scale seeded at 1 and no shift
    (arXiv:1910.07467)."""
    helper = LayerHelper("rms_norm", name=name)
    scale = helper.create_parameter(
        param_attr, [int(np.prod(input.shape[begin_norm_axis:]))],
        dtype="float32", default_initializer=Constant(1.0))
    return _single_out(
        helper, "rms_norm", {"X": [input], "Scale": [scale]},
        {"epsilon": epsilon, "begin_norm_axis": begin_norm_axis},
        dtype=input.dtype, shape=input.shape, out_slot="Y")


def _yarn_attr(rope_scaling):
    """YaRN's five numbers as an op attribute, from the keys a published
    `rope_parameters` group gives them under: factor,
    original_max_position_embeddings, beta_fast (32), beta_slow (1),
    attention_factor (0.1 ln(factor) + 1). None or `rope_type` "default":
    no scaling, and no attribute."""
    kind = (rope_scaling or {}).get("rope_type", "yarn")
    if not rope_scaling or kind == "default":
        return None
    if kind != "yarn":
        raise ValueError(f"rope_scaling: rope_type {kind!r}: only 'yarn' "
                         "and 'default' are built")
    factor = float(rope_scaling["factor"])
    return [factor, float(rope_scaling["original_max_position_embeddings"]),
            float(rope_scaling.get("beta_fast") or 32.0),
            float(rope_scaling.get("beta_slow") or 1.0),
            float(rope_scaling.get("attention_factor")
                  or 0.1 * math.log(factor) + 1.0)]


def rotary_embedding(input, theta=10000.0, name=None, rope_scaling=None,
                     interleaved=False):
    """Rotary positions 0..s-1 on `input` [b, s, heads, d], base `theta`;
    float32 inside the op, the output in `input`'s dtype. The lanes pair
    in the rotate-half convention, `(i, i + d/2)` (ops/nn_ops.py
    `rotate_half`), or with `interleaved` as neighbours, `(2i, 2i+1)`
    (`rotate_pairs`). `rope_scaling`: a published YaRN group
    (`_yarn_attr`), whose blended frequencies and factor the tables then
    carry."""
    helper = LayerHelper("rotary_embedding", name=name)
    scaling = _yarn_attr(rope_scaling)
    return _single_out(
        helper, "rotary_embedding", {"X": [input]},
        {"theta": float(theta), **({"scaling": scaling} if scaling else {}),
         **({"interleaved": True} if interleaved else {})},
        dtype=input.dtype, shape=input.shape)


def short_conv1d(input, width=4, param_attr=None, name=None, bias_attr=None,
                 act="silu"):
    """Causal depthwise convolution over time, zero state at the start of
    a sequence, then SiLU: input [b, s, c], filter [c, width],
    `out_t = SiLU(sum_i filter[:, i] * input_{t-width+1+i} + bias)`. No
    bias unless `bias_attr`, a `ParamAttr`, is given: a [c] parameter
    inside the SiLU, seeded 0 unless the attribute says otherwise. `act`
    None: the sum itself, no activation (the op then carries the
    attribute `activation` "none")."""
    if act not in ("silu", None):
        raise ValueError(f"short_conv1d: act {act!r}: expected 'silu' or None")
    helper = LayerHelper("short_conv1d", name=name)
    channels = int(input.shape[-1])
    w = helper.create_parameter(param_attr, [channels, width],
                                dtype="float32")
    inputs = {"X": [input], "Filter": [w]}
    if bias_attr:
        inputs["Bias"] = [helper.create_parameter(
            bias_attr, [channels], dtype="float32", is_bias=True)]
    return _single_out(helper, "short_conv1d", inputs,
                       {} if act else {"activation": "none"},
                       dtype=input.dtype, shape=input.shape)


def selective_scan(x, delta, a, b, c, d, name=None):
    """Mamba-1's selective scan over one sequence a row
    (ops/ssm_ops.py has the equations): `x` and the step sizes `delta`
    (after their softplus) [b, s, d_inner], `a` [d_inner, d_state]
    (negative), `b` and `c` [b, s, d_state], `d` [d_inner] the skip's
    weight. float32 inside the op, whatever the inputs arrive in; the
    state is zero at the start of a row. Returns y [b, s, d_inner] in
    `x`'s dtype."""
    from ..ops.ssm_ops import n_chunks

    helper = LayerHelper("selective_scan", name=name)
    y = helper.create_variable_for_type_inference(x.dtype, x.shape)
    # the states the chunks start from: what the gradient op keeps
    starts = helper.create_variable_for_type_inference(
        "float32", (n_chunks(int(x.shape[1]), int(x.shape[2]),
                             int(a.shape[1])), int(x.shape[0]),
                    int(a.shape[1]), int(x.shape[2])), stop_gradient=True)
    helper.append_op(
        type="selective_scan",
        inputs={"X": [x], "Delta": [delta], "A": [a], "B": [b], "C": [c],
                "D": [d]},
        outputs={"Y": [y], "Starts": [starts]})
    return y


def ssd_scan(x, dt, b, c, num_heads, n_groups=1, chunk_size=128,
             a_log_attr=None, dt_bias_attr=None, d_attr=None, name=None):
    """Mamba-2's recurrence over one sequence a row (ops/ssm_ops.py has
    the equations): `x` [b, s, num_heads * P], `dt` [b, s, num_heads] the
    step's projection, `b` and `c` [b, s, n_groups * N], head h reading
    group h // (num_heads / n_groups). `num_heads` and `n_groups` are the
    heads and groups held here, which may be a share of a mixer's. Inside
    the op, in float32: the step `softplus(dt + dt_bias)`, the decay
    `exp(step * -exp(A_log))`, one number a head and a token, and the
    chunks of `chunk_size` tokens; the state is zero at the start of a
    row. Creates `A_log` (exp of it log-uniform in 1 to 16), `dt_bias`
    (the step log-uniform in 0.001 to 0.1: `kda_attention`'s seeding) and
    the skip's weight `D` (ones), [num_heads] each. Returns y like `x`."""
    from ..ops.ssm_ops import ssd_n_chunks

    helper = LayerHelper("ssd_scan", name=name)
    a_log = helper.create_parameter(
        a_log_attr, [num_heads], dtype="float32",
        default_initializer=Uniform(0.0, float(np.log(16.0))))
    dt_bias = helper.create_parameter(
        dt_bias_attr, [num_heads], dtype="float32",
        default_initializer=Uniform(-6.9, -2.25))
    d = helper.create_parameter(d_attr, [num_heads], dtype="float32",
                                default_initializer=Constant(1.0))
    y = helper.create_variable_for_type_inference(x.dtype, x.shape)
    # the states the chunks start from: what the gradient op keeps
    starts = helper.create_variable_for_type_inference(
        "float32", (ssd_n_chunks(int(x.shape[1]), chunk_size),
                    int(x.shape[0]), int(num_heads),
                    int(x.shape[2]) // int(num_heads),
                    int(b.shape[2]) // int(n_groups)), stop_gradient=True)
    helper.append_op(
        type="ssd_scan",
        inputs={"X": [x], "Dt": [dt], "DtBias": [dt_bias], "ALog": [a_log],
                "B": [b], "C": [c], "D": [d]},
        outputs={"Y": [y], "Starts": [starts]},
        attrs={"n_groups": int(n_groups), "chunk_size": int(chunk_size)})
    return y


def kda_attention(q, k, v, g, beta, num_heads, l2norm_epsilon=1e-6,
                  a_log_attr=None, dt_bias_attr=None, name=None,
                  num_key_heads=None, beta_scale=1.0):
    """The gated delta rule over one sequence a row (ops/linear_attn_ops.py
    has the equations): Kimi Delta Attention with q, k, g [b, s, h*dk], a
    decay a channel, or Gated DeltaNet with g [b, s, h], a decay a head;
    v [b, s, h*dv], beta [b, s, h]. With `num_key_heads` = h_k, a divisor
    of h, q and k are [b, s, h_k*dk] and value head n reads key head
    n // (h / h_k). `q` and `k` are L2-normalised per head, `g` goes
    through `-exp(A_log) * softplus(g + dt_bias)` to the log of the decay
    and `beta` through a sigmoid times `beta_scale` (2: beta in (0, 2), the
    public `allow_neg_eigval`), all in float32 inside the op; the
    output is scaled by `dk^-1/2` and the state is zero at the start of a
    row. Creates `A_log` [h] and `dt_bias`, as wide as g. Returns
    [b, s, h*dv]."""
    helper = LayerHelper("kda_attention", name=name)
    # the public KDA layer's seeding: A in [1, 16] and the step
    # softplus(dt_bias) in [0.001, 0.1], here both log-uniform
    a_log = helper.create_parameter(
        a_log_attr, [num_heads], dtype="float32",
        default_initializer=Uniform(0.0, float(np.log(16.0))))
    dt_bias = helper.create_parameter(
        dt_bias_attr, [int(g.shape[-1])], dtype="float32",
        default_initializer=Uniform(-6.9, -2.25))
    return _single_out(
        helper, "kda_attention",
        {"Q": [q], "K": [k], "V": [v], "GRaw": [g], "BetaRaw": [beta],
         "ALog": [a_log], "DtBias": [dt_bias]},
        {"num_heads": num_heads, "l2norm_epsilon": l2norm_epsilon,
         # on a Program with a key head a value head the op is as it was
         **({"num_key_heads": int(num_key_heads)}
            if num_key_heads and num_key_heads != num_heads else {}),
         **({"beta_scale": float(beta_scale)} if beta_scale != 1.0 else {})},
        dtype=v.dtype, shape=v.shape)


def moe_experts(input, experts_total, experts_held, d_ff, k, held_from=0,
                scaling=1.0, renormalize=True, bias_scale=0.0,
                param_attr=None, name=None, score_func="sigmoid",
                norm_eps=0.0, experts_input=None, expert_form="silu_gated"):
    """The held experts' part of a dropless expert layer (SiLU-gated
    FFNs of width `d_ff`): a router over all `experts_total` (`score_func`
    "sigmoid": each expert's own sigmoid; "softmax": the probabilities
    over all of them, float32) picks `k` a token by `score + bias`, weights
    them `scaling * score / (sum of the selected scores + norm_eps)`, and
    the assignments to the `experts_held` experts from `held_from` on run
    through one grouped product, every one of them, whatever the skew.
    What experts held elsewhere would add is left out. `bias` is the router's
    correction: persistable, seeded Normal(0, bias_scale), never
    trained. `experts_input`: what the experts read where that is not the
    router's `input` (same leading dimensions, another width: a latent of
    the token); the experts' matrices and `out` then have its width.
    `expert_form` "relu2": an expert is `W_down relu(W_up x)^2` and there
    is no `w_gate`. Returns (out like what the experts read, load): the
    op's second output, `Load`, is `load` [experts_held] int32, the
    assignments each held expert took this step, which carries no
    gradient and which the cells fetch to say how full the first block
    ran."""
    if score_func not in ("sigmoid", "softmax"):
        raise ValueError(
            f"score_func must be 'sigmoid' or 'softmax', got {score_func!r}")
    if expert_form not in ("silu_gated", "relu2"):
        raise ValueError("expert_form must be 'silu_gated' or 'relu2', got "
                         f"{expert_form!r}")
    helper = LayerHelper("moe_experts", name=name)
    read = input if experts_input is None else experts_input
    d_router, d = int(input.shape[-1]), int(read.shape[-1])

    def pattr(suffix, **overrides):
        return _suffixed_attr(param_attr, suffix, **overrides)

    gate = helper.create_parameter(pattr("gate"), [d_router, experts_total],
                                   dtype="float32")
    bias = helper.create_parameter(
        pattr("bias", trainable=False,
              initializer=Normal(0.0, bias_scale) if bias_scale
              else Constant(0.0)),
        [experts_total], dtype="float32")
    inputs = {"X": [input], "Gate": [gate], "Bias": [bias]}
    if experts_input is not None:
        inputs["XExperts"] = [experts_input]
    if expert_form == "silu_gated":
        inputs["WGate"] = [helper.create_parameter(
            pattr("w_gate"), [experts_held, d, d_ff], dtype="float32")]
    inputs["WUp"] = [helper.create_parameter(
        pattr("w_up"), [experts_held, d, d_ff], dtype="float32")]
    inputs["WDown"] = [helper.create_parameter(
        pattr("w_down"), [experts_held, d_ff, d], dtype="float32")]
    out = helper.create_variable_for_type_inference(read.dtype, read.shape)
    load = helper.create_variable_for_type_inference(
        "int32", (experts_held,), stop_gradient=True)
    helper.append_op(
        type="moe_experts",
        inputs=inputs,
        outputs={"Out": [out], "Load": [load]},
        # on a Program of SiLU-gated experts that read the router's rows
        # the op is as it was
        attrs={"experts_total": int(experts_total),
               "experts_held": int(experts_held), "held_from": int(held_from),
               "k": int(k), "scaling": float(scaling),
               "renormalize": bool(renormalize), "score_func": score_func,
               **({"norm_eps": float(norm_eps)} if norm_eps else {}),
               **({"expert_form": expert_form}
                  if expert_form != "silu_gated" else {})},
    )
    return out, load


def group_norm(
    input, groups, epsilon=1e-5, param_attr=None, bias_attr=None, act=None, name=None
):
    helper = LayerHelper("group_norm", name=name, act=act)
    c = input.shape[1]
    inputs = {"X": [input]}
    if param_attr is not False:
        inputs["Scale"] = [
            helper.create_parameter(
                param_attr, [c], dtype="float32", default_initializer=Constant(1.0)
            )
        ]
    if bias_attr is not False:
        inputs["Bias"] = [
            helper.create_parameter(bias_attr, [c], dtype="float32", is_bias=True)
        ]
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    mean = helper.create_variable_for_type_inference(
        "float32", (input.shape[0], groups)
    )
    var = helper.create_variable_for_type_inference(
        "float32", (input.shape[0], groups)
    )
    helper.append_op(
        type="group_norm",
        inputs=inputs,
        outputs={"Y": [out], "Mean": [mean], "Variance": [var]},
        attrs={"groups": groups, "epsilon": epsilon},
    )
    return helper.append_activation(out)


def instance_norm(input, epsilon=1e-5, param_attr=None, bias_attr=None, name=None):
    helper = LayerHelper("instance_norm", name=name)
    c = input.shape[1]
    inputs = {"X": [input]}
    if param_attr is not False:
        inputs["Scale"] = [
            helper.create_parameter(
                param_attr, [c], dtype="float32", default_initializer=Constant(1.0)
            )
        ]
        inputs["Bias"] = [
            helper.create_parameter(bias_attr, [c], dtype="float32", is_bias=True)
        ]
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    helper.append_op(
        type="instance_norm",
        inputs=inputs,
        outputs={"Y": [out]},
        attrs={"epsilon": epsilon},
    )
    return out


def dropout(
    x,
    dropout_prob,
    is_test=False,
    seed=None,
    name=None,
    dropout_implementation="downgrade_in_infer",
):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    mask = helper.create_variable_for_type_inference(
        "uint8", x.shape, stop_gradient=True
    )
    helper.append_op(
        type="dropout",
        inputs={"X": [x]},
        outputs={"Out": [out], "Mask": [mask]},
        attrs={
            "dropout_prob": dropout_prob,
            "is_test": is_test,
            "seed": seed if seed is not None else 0,
            "dropout_implementation": dropout_implementation,
        },
    )
    return out


# ---------------------------------------------------------------------------
# losses / softmax
# ---------------------------------------------------------------------------


def softmax(input, axis=-1, name=None, use_cudnn=False):
    helper = LayerHelper("softmax", name=name)
    return _single_out(helper, "softmax", {"X": [input]}, {"axis": axis},
                       shape=input.shape)


def log_softmax(input, axis=-1, name=None):
    helper = LayerHelper("log_softmax", name=name)
    return _single_out(helper, "log_softmax", {"X": [input]}, {"axis": axis},
                       shape=input.shape)


def softmax_with_cross_entropy(
    logits,
    label,
    soft_label=False,
    ignore_index=-100,
    numeric_stable_mode=True,
    return_softmax=False,
    axis=-1,
):
    helper = LayerHelper("softmax_with_cross_entropy")
    softmax_out = helper.create_variable_for_type_inference(
        logits.dtype, logits.shape
    )
    loss_shape = tuple(logits.shape[:-1]) + (1,)
    loss = helper.create_variable_for_type_inference(logits.dtype, loss_shape)
    helper.append_op(
        type="softmax_with_cross_entropy",
        inputs={"Logits": [logits], "Label": [label]},
        outputs={"Softmax": [softmax_out], "Loss": [loss]},
        attrs={
            "soft_label": soft_label,
            "ignore_index": ignore_index,
            "axis": axis,
        },
    )
    if return_softmax:
        return loss, softmax_out
    return loss


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy")
    loss_shape = tuple(input.shape[:-1]) + (1,)
    out = helper.create_variable_for_type_inference(input.dtype, loss_shape)
    helper.append_op(
        type="cross_entropy",
        inputs={"X": [input], "Label": [label]},
        outputs={"Y": [out]},
        attrs={"soft_label": soft_label, "ignore_index": ignore_index},
    )
    return out


def sigmoid_cross_entropy_with_logits(
    x, label, ignore_index=-100, name=None, normalize=False
):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", name=name)
    return _single_out(
        helper,
        "sigmoid_cross_entropy_with_logits",
        {"X": [x], "Label": [label]},
        {"ignore_index": ignore_index, "normalize": normalize},
        shape=x.shape,
    )


def log_loss(input, label, epsilon=1e-4, name=None):
    """reference: operators/log_loss_op.cc — negative log likelihood of a
    probability prediction: -label*log(p+eps) - (1-label)*log(1-p+eps)."""
    helper = LayerHelper("log_loss", name=name)
    return _single_out(
        helper, "log_loss", {"Predicted": [input], "Labels": [label]},
        {"epsilon": float(epsilon)}, shape=input.shape,
    )


def square_error_cost(input, label):
    helper = LayerHelper("square_error_cost")
    return _single_out(
        helper, "square_error_cost", {"X": [input], "Y": [label]}, shape=input.shape
    )


def huber_loss(input, label, delta):
    helper = LayerHelper("huber_loss")
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    residual = helper.create_variable_for_type_inference(input.dtype, input.shape)
    helper.append_op(
        type="huber_loss",
        inputs={"X": [input], "Y": [label]},
        outputs={"Out": [out], "Residual": [residual]},
        attrs={"delta": delta},
    )
    return out


def kldiv_loss(x, target, reduction="mean", name=None):
    helper = LayerHelper("kldiv_loss", name=name)
    shape = (1,) if reduction != "none" else x.shape
    return _single_out(
        helper,
        "kldiv_loss",
        {"X": [x], "Target": [target]},
        {"reduction": reduction},
        shape=shape,
        out_slot="Loss",
    )


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper("smooth_l1")
    out = helper.create_variable_for_type_inference(x.dtype, (x.shape[0], 1))
    diff = helper.create_variable_for_type_inference(x.dtype, x.shape)
    helper.append_op(
        type="smooth_l1_loss",
        inputs={"X": [x], "Y": [y]},
        outputs={"Out": [out], "Diff": [diff]},
        attrs={"sigma": sigma or 1.0},
    )
    return out


def mean(x, name=None):
    helper = LayerHelper("mean", name=name)
    return _single_out(helper, "mean", {"X": [x]}, shape=(1,))


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32", name=None):
    helper = LayerHelper("label_smooth", name=name)
    k = label.shape[-1]
    out = helper.create_variable_for_type_inference(dtype, label.shape)
    one = helper.create_variable_for_type_inference(dtype, label.shape)
    helper.append_op(
        type="scale",
        inputs={"X": [label]},
        outputs={"Out": [one]},
        attrs={"scale": 1.0 - epsilon, "bias": epsilon / k, "bias_after_scale": True},
    )
    return one


# ---------------------------------------------------------------------------
# math layers
# ---------------------------------------------------------------------------


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul", name=name)
    shape = tuple(x.shape[:x_num_col_dims]) + tuple(y.shape[y_num_col_dims:])
    return _single_out(
        helper,
        "mul",
        {"X": [x], "Y": [y]},
        {"x_num_col_dims": x_num_col_dims, "y_num_col_dims": y_num_col_dims},
        shape=shape,
    )


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", name=name)
    xs = list(x.shape)
    ys = list(y.shape)
    if transpose_x and len(xs) >= 2:
        xs[-1], xs[-2] = xs[-2], xs[-1]
    if transpose_y and len(ys) >= 2:
        ys[-1], ys[-2] = ys[-2], ys[-1]
    shape = tuple(xs[:-1]) + (ys[-1],)
    return _single_out(
        helper,
        "matmul",
        {"X": [x], "Y": [y]},
        {"transpose_X": transpose_x, "transpose_Y": transpose_y, "alpha": alpha},
        shape=shape,
    )


def matmul_v2(x, y, trans_x=False, trans_y=False, name=None):
    return matmul(x, y, trans_x, trans_y, 1.0, name)


def bmm(x, y, name=None):
    helper = LayerHelper("bmm", name=name)
    return _single_out(
        helper, "bmm", {"X": [x], "Y": [y]},
        shape=(x.shape[0], x.shape[1], y.shape[2]),
    )


def _ew_layer(op_type):
    def f(x, y, axis=-1, act=None, name=None):
        helper = LayerHelper(op_type, name=name, act=act)
        out = _single_out(
            helper, op_type, {"X": [x], "Y": [y]}, {"axis": axis},
            shape=_elementwise_out_shape(x.shape, y.shape),
        )
        return helper.append_activation(out, act)

    f.__name__ = op_type
    return f


elementwise_add = _ew_layer("elementwise_add")
elementwise_sub = _ew_layer("elementwise_sub")
elementwise_mul = _ew_layer("elementwise_mul")
elementwise_div = _ew_layer("elementwise_div")
elementwise_max = _ew_layer("elementwise_max")
elementwise_min = _ew_layer("elementwise_min")
elementwise_pow = _ew_layer("elementwise_pow")
elementwise_mod = _ew_layer("elementwise_mod")


def _reduce_layer(op_type):
    def f(input, dim=None, keep_dim=False, name=None):
        helper = LayerHelper(op_type, name=name)
        reduce_all = dim is None
        dims = [0] if dim is None else (dim if isinstance(dim, (list, tuple)) else [dim])
        if input.shape is None or reduce_all:
            # full reduce: [1] tensor (fluid convention) unless keep_dim,
            # which keeps the rank as all-ones (matches the runtime's
            # jnp keepdims semantics, ops/math_ops.py _reduce)
            if keep_dim and input.shape is not None:
                shape = (1,) * len(input.shape) or (1,)
            else:
                shape = (1,)
        else:
            nd = len(input.shape)
            axes = {d % nd for d in dims}
            shape = tuple(
                (1 if i in axes else s)
                for i, s in enumerate(input.shape)
                if keep_dim or i not in axes
            ) or (1,)
        return _single_out(
            helper,
            op_type,
            {"X": [input]},
            {"dim": dims, "keep_dim": keep_dim, "reduce_all": reduce_all},
            shape=shape,
        )

    f.__name__ = op_type
    return f


reduce_sum = _reduce_layer("reduce_sum")
reduce_mean = _reduce_layer("reduce_mean")
reduce_max = _reduce_layer("reduce_max")
reduce_min = _reduce_layer("reduce_min")
reduce_prod = _reduce_layer("reduce_prod")
reduce_all = _reduce_layer("reduce_all")
reduce_any = _reduce_layer("reduce_any")


def logsumexp(x, dim=None, keepdim=False, name=None):
    helper = LayerHelper("logsumexp", name=name)
    if dim is None:
        dims = None
        shape = tuple(1 for _ in x.shape) if keepdim else (1,)
    else:
        dims = [dim] if isinstance(dim, int) else list(dim)
        dims = [d % len(x.shape) for d in dims]
        shape = tuple(
            1 if i in dims else s for i, s in enumerate(x.shape)
            if keepdim or i not in dims
        ) or (1,)
    return _single_out(
        helper,
        "logsumexp",
        {"X": [x]},
        {"dim": dims, "keep_dim": keepdim, "reduce_all": dims is None},
        shape=shape,
    )


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    helper = LayerHelper("scale", name=name, act=act)
    out = _single_out(
        helper,
        "scale",
        {"X": [x]},
        {"scale": scale, "bias": bias, "bias_after_scale": bias_after_scale},
        shape=x.shape,
    )
    return helper.append_activation(out, act)


def clip(x, min, max, name=None):
    helper = LayerHelper("clip", name=name)
    return _single_out(helper, "clip", {"X": [x]}, {"min": min, "max": max},
                       shape=x.shape)


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper("clip_by_norm", name=name)
    return _single_out(
        helper, "clip_by_norm", {"X": [x]}, {"max_norm": max_norm}, shape=x.shape
    )


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    # Norm is the keepdims denominator: axis collapses to 1 (the
    # round-16 shape functions surfaced the old full-shape declaration
    # as a verifier shape-mismatch)
    rank = max(len(x.shape), 1)
    ax = axis % rank
    norm = helper.create_variable_for_type_inference(
        x.dtype, tuple(1 if i == ax else d for i, d in enumerate(x.shape))
    )
    helper.append_op(
        type="l2_normalize",
        inputs={"X": [x]},
        outputs={"Out": [out], "Norm": [norm]},
        attrs={"axis": axis, "epsilon": epsilon},
    )
    return out


def dist(x, y, p=2.0):
    helper = LayerHelper("dist")
    return _single_out(helper, "p_norm", {"X": [x]}, {"porder": p}, shape=(1,))


# ---------------------------------------------------------------------------
# activations as layers
# ---------------------------------------------------------------------------


def _act_layer(op_type, **default_attrs):
    def f(x, name=None, **kwargs):
        helper = LayerHelper(op_type, name=name)
        attrs = dict(default_attrs)
        attrs.update({k: v for k, v in kwargs.items() if v is not None})
        return _single_out(helper, op_type, {"X": [x]}, attrs, shape=x.shape)

    f.__name__ = op_type
    return f


relu = _act_layer("relu")
relu6 = _act_layer("relu6", threshold=6.0)
elu = _act_layer("elu", alpha=1.0)
swish = _act_layer("swish", beta=1.0)
hard_swish = _act_layer("hard_swish")
hard_sigmoid = _act_layer("hard_sigmoid")
gelu = _act_layer("gelu")


def leaky_relu(x, alpha=0.02, name=None):
    helper = LayerHelper("leaky_relu", name=name)
    return _single_out(helper, "leaky_relu", {"X": [x]}, {"alpha": alpha},
                       shape=x.shape)


def prelu(x, mode="all", param_attr=None, name=None):
    helper = LayerHelper("prelu", name=name)
    if mode == "all":
        alpha_shape = [1]
    elif mode == "channel":
        alpha_shape = [x.shape[1]]
    else:
        alpha_shape = list(x.shape[1:])
    alpha = helper.create_parameter(
        param_attr, alpha_shape, dtype=x.dtype, default_initializer=Constant(0.25)
    )
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    helper.append_op(
        type="prelu",
        inputs={"X": [x], "Alpha": [alpha]},
        outputs={"Out": [out]},
        attrs={"mode": mode},
    )
    return out


def soft_relu(x, threshold=40.0, name=None):
    helper = LayerHelper("soft_relu", name=name)
    clipped = clip(x, -threshold, threshold)
    return _single_out(helper, "softplus", {"X": [clipped]}, shape=x.shape)


def maxout(x, groups, name=None, axis=1):
    helper = LayerHelper("maxout", name=name)
    c = x.shape[axis]
    shape = list(x.shape)
    shape[axis] = c // groups
    r = reshape(
        x,
        list(x.shape[:axis]) + [c // groups, groups] + list(x.shape[axis + 1:]),
    )
    return reduce_max(r, dim=axis + 1)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def fused_multihead_attention(
    q,
    k,
    v,
    key_bias=None,
    causal=False,
    attn_dropout=0.0,
    sm_scale=None,
    is_test=False,
    layout="bhsd",
    name=None,
    window=0,
    q_norm_attr=None,
    k_norm_attr=None,
    qk_norm_epsilon=1e-5,
    rope_theta=0.0,
    rope_scaling=None,
    q_lora_rank=0,
    rotary_dim=0,
    admit=None,
    admit_keys=0,
    return_lse=False,
    return_prepared=False,
    diffusion_block=0,
):
    """Flash attention over q/k/v (Pallas kernel on TPU). layout="bhsd"
    (default): [b, nh, s, dh]; layout="bshd": [b, s, nh, dh] — the shape
    the QKV head-split reshape produces, so the model graph carries NO
    head transposes (they otherwise materialize as HBM relayout copies).

    `v`'s last dim may be narrower than `q`'s and `k`'s (latent attention:
    192-wide scores, 128-wide values) or wider (a differential head: 64-wide
    scores over a pair of value heads, 128) and is then the output's. `k` and
    `v` may have fewer heads than `q`, a divisor of its count (grouped
    key/value heads): query head n reads key/value head n // group.
    `window` > 0, with `causal`, admits only the last `window` keys a
    query may see (key j for query i iff 0 <= i - j < window).

    `key_bias` is an additive [b, sv_len] bias (0 keep / large-negative
    mask). The unfused equivalent is matmul+softmax+dropout+matmul — this
    layer replaces that chain with one kernel so the [s, s] scores never
    reach HBM.

    `q_norm_attr` and `k_norm_attr`, given together, add QK-norm: `q` and
    `k` are normed head by head over `dh` as `rms_norm` does, each with a
    learned `[dh]` weight seeded at 1 and `qk_norm_epsilon`. With them
    or without, `rope_theta` > 0 (layout "bshd") then turns q and k by
    `rotary_embedding`'s positions 0..s-1, with a window or without
    one; `rope_scaling` (a published YaRN group, as `rotary_embedding`
    takes it) scales the tables; `rotary_dim` fewer than `dh` (0: the
    whole head) turns only the first `rotary_dim` lanes of a head, as a
    head of that width, and passes the rest as they were
    (`partial_rotary_factor`). Inside the op the norm, the positions and
    the kernel's head-major write are one pass over q and k, where the
    kernel runs.

    `q_lora_rank` > 0 says that `q` came through a compressed query of
    that rank (latent attention, `decoder_parts.latent_attention`). The
    op computes nothing differently; its lowering counts the call
    (`attn_latent_q_lora`).

    `admit` is an admission that is data, [b, sq, sk] int8 as
    `sparse_select` gives it: a pair whose entry is 0 is refused for every
    head, beside what `causal` and `window` refuse; it carries no
    gradient. `admit_keys` says how many keys a query admits at most (the
    selection's K), which the kernels' declared work counts by. With
    `return_lse` the layer returns (out, lse): each row's log-sum-exp over
    its admitted scaled scores, [b, heads, sq] float32, with no gradient.
    With `return_prepared` it returns, after those, q and k as the
    attention took them (normed and turned, in its dtype): head-major,
    [b, heads, sq, dh] and [b, groups, sk, dh] in either layout, with no
    gradient.

    `diffusion_block` B > 0 (layout "bshd", in place of `causal`): block
    diffusion's training mask. The sequence axis holds each sequence
    twice, its L noisy rows and then its L clean rows, both at positions
    0..L-1, cut in blocks of B: a noisy row sees its own noisy block,
    both ways, and the clean blocks before it; a clean row the clean
    blocks up to and with its own (`ops/fused_ops.py::
    block_diffusion_mask`).
    """
    if layout not in ("bhsd", "bshd"):
        raise ValueError(f"layout must be 'bhsd' or 'bshd', got {layout!r}")
    if (q_norm_attr is None) != (k_norm_attr is None):
        raise ValueError("q_norm_attr and k_norm_attr come together")
    scaling = _yarn_attr(rope_scaling)
    if scaling and not rope_theta:
        raise ValueError("rope_scaling needs rope_theta")
    helper = LayerHelper("fused_multihead_attention", name=name)
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if key_bias is not None:
        inputs["KeyBias"] = [key_bias]
    if q_norm_attr is not None:
        for slot, attr in (("QNorm", q_norm_attr), ("KNorm", k_norm_attr)):
            inputs[slot] = [helper.create_parameter(
                attr, [int(q.shape[-1])], dtype="float32",
                default_initializer=Constant(1.0))]
    if admit is not None:
        inputs["Admit"] = [admit]
    out = helper.create_variable_for_type_inference(
        q.dtype, list(q.shape[:-1]) + [v.shape[-1]])
    outputs = {"Out": [out]}
    s_ax, h_ax = (1, 2) if layout == "bshd" else (2, 1)
    if return_lse:
        lse = helper.create_variable_for_type_inference(
            "float32", [q.shape[0], q.shape[h_ax], q.shape[s_ax]],
            stop_gradient=True)
        outputs["Lse"] = [lse]
    if return_prepared:
        prepared = [helper.create_variable_for_type_inference(
            q.dtype, [q.shape[0], t.shape[h_ax], t.shape[s_ax], t.shape[3]],
            stop_gradient=True) for t in (q, k)]
        outputs["QPrepared"], outputs["KPrepared"] = ([t] for t in prepared)
    helper.append_op(
        type="fused_multihead_attention",
        inputs=inputs,
        outputs=outputs,
        attrs={
            "causal": causal,
            "attn_dropout": float(attn_dropout),
            "sm_scale": float(sm_scale or 0.0),
            "is_test": is_test,
            "layout": layout,
            "window": int(window),
            # on a Program that asks for neither, the op is as it was
            **({"qk_norm_epsilon": float(qk_norm_epsilon)}
               if q_norm_attr is not None else {}),
            **({"rope_theta": float(rope_theta)}
               if q_norm_attr is not None or rope_theta else {}),
            **({"rope_scaling": scaling} if scaling else {}),
            **({"q_lora_rank": int(q_lora_rank)} if q_lora_rank else {}),
            **({"rotary_dim": int(rotary_dim)}
               if rotary_dim and rotary_dim != q.shape[-1] else {}),
            **({"admit_keys": int(admit_keys)} if admit is not None else {}),
            **({"diffusion_block": int(diffusion_block)}
               if diffusion_block else {}),
        },
    )
    more = ([lse] if return_lse else []) + (
        prepared if return_prepared else [])
    return (out, *more) if more else out


def sparse_index(q, k, w, scale, name=None):
    """The score by which a learned indexer ranks the keys of a query
    (DeepSeek-V3.2-Exp's lightning indexer): `I[t, s] = scale * sum_j
    w[t, j] relu(q[t, j] . k[s])` for `s <= t`, and -inf above the
    diagonal; q [b, s, heads, d], k [b, s, 1, d] (one key head for all),
    w [b, s, heads]. Returns [b, s, s] float32."""
    helper = LayerHelper("sparse_index", name=name)
    b, s = q.shape[0], q.shape[1]
    return _single_out(helper, "sparse_index",
                       {"Q": [q], "K": [k], "W": [w]},
                       {"scale": float(scale)}, dtype="float32",
                       shape=[b, s, s])


def sparse_select(index, k, name=None):
    """The keys a query keeps of `index` [b, s, s] (`sparse_index`'s): the
    causal ones whose score is at least the row's `k`-th largest, every
    causal one where a row has no more than `k` (ties at the threshold
    are all kept). Returns (admit [b, s, s] int8, 1 where kept; tau
    [b, s] float32, the threshold, -inf where every causal key is kept).
    Exact, and no gradient passes."""
    helper = LayerHelper("sparse_select", name=name)
    b, s = index.shape[0], index.shape[1]
    admit = helper.create_variable_for_type_inference(
        "int8", [b, s, s], stop_gradient=True)
    tau = helper.create_variable_for_type_inference(
        "float32", [b, s], stop_gradient=True)
    helper.append_op(type="sparse_select", inputs={"X": [index]},
                     outputs={"Admit": [admit], "Tau": [tau]},
                     attrs={"k": int(k)})
    return admit, tau


def index_kl(q, k, lse, index, admit, sm_scale, admit_keys=0, name=None):
    """The loss that trains an indexer towards the attention it selects
    for, a number a query: `KL(p[t] || softmax over the admitted keys of
    index[t])` with the target `p[t, s]` the mean over the heads of the
    attention's probabilities `exp(sm_scale q[t, head] . k[s, group] -
    lse[head, t])` on the admitted pairs. q [b, heads, s, d] and k
    [b, groups, s, d] head-major as the attention took them (normed and
    turned): what `fused_multihead_attention(return_prepared=True)` hands
    back; lse as its `return_lse=True` gives it, `admit` as
    `sparse_select`'s. The target is rebuilt here, summed head by head
    and never held for all the heads, and is a constant: the gradient
    reaches `index` alone, `(softmax over the admitted of index[t]) *
    sum(p[t]) - p[t]` times the row's cotangent, the row's sum of p used
    and not taken for 1. Where the Pallas kernels run (one device, rows of
    whole 512 blocks) the kernel that makes the target sums the
    divergence's rows in the same visit and the gradient is one
    elementwise pass; elsewhere both are `jnp`, a block of queries at a
    time (`ops/sparse_attn_ops.py`). Returns [b, s] float32."""
    helper = LayerHelper("index_kl", name=name)
    return _single_out(
        helper, "index_kl",
        {"Q": [q], "K": [k], "Lse": [lse], "Index": [index],
         "Admit": [admit]},
        {"sm_scale": float(sm_scale), "admit_keys": int(admit_keys)},
        dtype="float32", shape=[q.shape[0], q.shape[2]])


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    shape = tuple(input.shape[:-1]) + (k,)
    values = helper.create_variable_for_type_inference(input.dtype, shape)
    indices = helper.create_variable_for_type_inference(
        "int64", shape, stop_gradient=True
    )
    helper.append_op(
        type="top_k",
        inputs={"X": [input]},
        outputs={"Out": [values], "Indices": [indices]},
        attrs={"k": k},
    )
    return values, indices


def accuracy(input, label, k=1, correct=None, total=None):
    """reference: layers/metric_op.py accuracy — fraction of top-k hits."""
    helper = LayerHelper("accuracy")
    _, indices = topk(input, k)
    out = helper.create_variable_for_type_inference("float32", (1,),
                                                    stop_gradient=True)
    helper.append_op(
        type="accuracy",
        inputs={"Indices": [indices], "Label": [label]},
        outputs={"Accuracy": [out]},
        attrs={},
    )
    return out


def auc(input, label, curve="ROC", num_thresholds=200, topk=1, slide_steps=1):
    """Streaming AUC (reference: operators/metrics/auc_op.cc + layers'
    metric_op.py auc). Keeps persistable positive/negative histograms over
    `num_thresholds` buckets of the positive-class probability
    (input[:, 1]), updated in-graph each batch; returns the accumulated AUC
    scalar computed by trapezoid rule over the ROC curve."""
    helper = LayerHelper("auc")
    stat_pos = helper.create_or_get_global_variable(
        unique_name.generate("auc_stat_pos"), [num_thresholds + 1], "float32",
        initializer=Constant(0.0),
    )
    stat_neg = helper.create_or_get_global_variable(
        unique_name.generate("auc_stat_neg"), [num_thresholds + 1], "float32",
        initializer=Constant(0.0),
    )
    out = helper.create_variable_for_type_inference("float32", (1,),
                                                    stop_gradient=True)
    batch_out = helper.create_variable_for_type_inference(
        "float32", (1,), stop_gradient=True)
    helper.append_op(
        type="auc",
        inputs={
            "Predict": [input],
            "Label": [label],
            "StatPos": [stat_pos],
            "StatNeg": [stat_neg],
        },
        outputs={
            "AUC": [out],
            "BatchAUC": [batch_out],
            "StatPosOut": [stat_pos],
            "StatNegOut": [stat_neg],
        },
        attrs={"num_thresholds": num_thresholds, "curve": curve},
    )
    # reference returns (accumulated auc, batch auc, state vars)
    return out, batch_out, [stat_pos, stat_neg]


def one_hot(input, depth, allow_out_of_range=False):
    helper = LayerHelper("one_hot")
    in_shape = tuple(input.shape)
    shape = (in_shape[:-1] if in_shape[-1] == 1 else in_shape) + (depth,)
    return _single_out(
        helper, "one_hot", {"X": [input]}, {"depth": depth},
        dtype="float32", shape=shape,
    )


# ---------------------------------------------------------------------------
# shape manipulation layers
# ---------------------------------------------------------------------------


def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper("reshape2", name=name, act=act)
    out_shape = []
    for i, s in enumerate(shape):
        if s == 0:
            out_shape.append(x.shape[i])
        else:
            out_shape.append(s)
    # resolve -1 at build time when the input shape is fully static, so
    # downstream build-time shape inference sees real dims
    if -1 in out_shape and x.shape and all(
        d is not None and d > 0 for d in x.shape
    ):
        known = int(np.prod([s for s in out_shape if s != -1]))
        total = int(np.prod(x.shape))
        if known > 0 and total % known == 0:
            out_shape[out_shape.index(-1)] = total // known
    out = helper.create_variable_for_type_inference(x.dtype, tuple(out_shape))
    xshape = helper.create_variable_for_type_inference(
        x.dtype, (0,) + tuple(x.shape or ()), stop_gradient=True
    )
    helper.append_op(
        type="reshape2",
        inputs={"X": [x]},
        outputs={"Out": [out], "XShape": [xshape]},
        attrs={"shape": list(shape)},
    )
    return helper.append_activation(out, act)


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose2", name=name)
    shape = tuple(x.shape[p] for p in perm) if x.shape else None
    out = helper.create_variable_for_type_inference(x.dtype, shape)
    xshape = helper.create_variable_for_type_inference(
        x.dtype, (0,) + tuple(x.shape or ()), stop_gradient=True
    )
    helper.append_op(
        type="transpose2",
        inputs={"X": [x]},
        outputs={"Out": [out], "XShape": [xshape]},
        attrs={"axis": list(perm)},
    )
    return out


def squeeze(input, axes, name=None):
    helper = LayerHelper("squeeze2", name=name)
    shape = tuple(
        s for i, s in enumerate(input.shape) if i not in [a % len(input.shape) for a in axes]
    )
    out = helper.create_variable_for_type_inference(input.dtype, shape)
    xshape = helper.create_variable_for_type_inference(
        input.dtype, (0,) + tuple(input.shape), stop_gradient=True
    )
    helper.append_op(
        type="squeeze2",
        inputs={"X": [input]},
        outputs={"Out": [out], "XShape": [xshape]},
        attrs={"axes": list(axes)},
    )
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze2", name=name)
    shape = list(input.shape)
    for a in sorted(axes):
        shape.insert(a if a >= 0 else a + len(shape) + 1, 1)
    out = helper.create_variable_for_type_inference(input.dtype, tuple(shape))
    xshape = helper.create_variable_for_type_inference(
        input.dtype, (0,) + tuple(input.shape), stop_gradient=True
    )
    helper.append_op(
        type="unsqueeze2",
        inputs={"X": [input]},
        outputs={"Out": [out], "XShape": [xshape]},
        attrs={"axes": list(axes)},
    )
    return out


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten2", name=name)
    lead = int(np.prod(x.shape[:axis] or (1,)))
    rest = int(np.prod(x.shape[axis:] or (1,)))
    out = helper.create_variable_for_type_inference(x.dtype, (lead, rest))
    xshape = helper.create_variable_for_type_inference(
        x.dtype, (0,) + tuple(x.shape), stop_gradient=True
    )
    helper.append_op(
        type="flatten2",
        inputs={"X": [x]},
        outputs={"Out": [out], "XShape": [xshape]},
        attrs={"axis": axis},
    )
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", name=name)
    nd = len(input.shape)
    d = dim % nd
    if isinstance(num_or_sections, int):
        n = num_or_sections
        sections = []
        sizes = [input.shape[d] // n] * n
    else:
        sections = list(num_or_sections)
        n = len(sections)
        sizes = sections
    outs = []
    for s in sizes:
        shape = list(input.shape)
        shape[d] = s
        outs.append(helper.create_variable_for_type_inference(input.dtype, tuple(shape)))
    helper.append_op(
        type="split",
        inputs={"X": [input]},
        outputs={"Out": outs},
        attrs={
            "axis": d,
            "num": 0 if sections else n,
            "sections": sections,
        },
    )
    return outs


def slice(input, axes, starts, ends, name=None):
    helper = LayerHelper("slice", name=name)
    shape = list(input.shape)
    for a, s, e in zip(axes, starts, ends):
        dim = shape[a]
        if dim not in (-1, None):
            s_ = s + dim if s < 0 else min(s, dim)
            e_ = e + dim if e < 0 else min(e, dim)
            shape[a] = max(e_ - s_, 0)
    return _single_out(
        helper,
        "slice",
        {"Input": [input]},
        {"axes": list(axes), "starts": list(starts), "ends": list(ends),
         "decrease_axis": []},
        shape=tuple(shape),
    )


def strided_slice(input, axes, starts, ends, strides, name=None):
    helper = LayerHelper("strided_slice", name=name)
    return _single_out(
        helper,
        "strided_slice",
        {"Input": [input]},
        {"axes": list(axes), "starts": list(starts), "ends": list(ends),
         "strides": list(strides)},
    )


def gather(input, index, overwrite=True):
    helper = LayerHelper("gather")
    shape = (index.shape[0],) + tuple(input.shape[1:])
    return _single_out(
        helper, "gather", {"X": [input], "Index": [index]}, shape=shape
    )


def gather_nd(input, index, name=None):
    helper = LayerHelper("gather_nd", name=name)
    shape = tuple(index.shape[:-1]) + tuple(input.shape[index.shape[-1]:])
    return _single_out(
        helper, "gather_nd", {"X": [input], "Index": [index]}, shape=shape
    )


def scatter(input, index, updates, overwrite=True, name=None):
    helper = LayerHelper("scatter", name=name)
    return _single_out(
        helper,
        "scatter",
        {"X": [input], "Ids": [index], "Updates": [updates]},
        {"overwrite": overwrite},
        shape=input.shape,
    )


def scatter_nd_add(ref, index, updates, name=None):
    helper = LayerHelper("scatter_nd_add", name=name)
    return _single_out(
        helper,
        "scatter_nd_add",
        {"X": [ref], "Index": [index], "Updates": [updates]},
        shape=ref.shape,
    )


def where(condition):
    """reference: layers/nn.py where (where_index_op.cc) — indices of
    true elements. Static-shape redesign (the NMS convention): the
    output is [numel, rank] int64 with the true-element coordinates
    LEFT-PACKED and pad rows filled with -1; count the valid rows with
    reduce_sum(cast(condition)) or test row[0] >= 0."""
    helper = LayerHelper("where")
    n = 1
    for s in condition.shape:
        n *= s
    return _single_out(
        helper, "where_index", {"Condition": [condition]},
        shape=(n, len(condition.shape)), dtype="int64",
    )


def cond_select(condition, x, y, name=None):
    helper = LayerHelper("where", name=name)
    # declare with X's dtype, not the Condition's bool (_single_out
    # takes the FIRST input otherwise; the round-16 `where` shape
    # function surfaced the stale bool declaration as a verifier
    # dtype-mismatch)
    return _single_out(
        helper, "where", {"Condition": [condition], "X": [x], "Y": [y]},
        dtype=x.dtype, shape=x.shape,
    )


def expand(x, expand_times, name=None):
    helper = LayerHelper("expand", name=name)
    shape = tuple(
        (s * t if s not in (-1, None) else -1)
        for s, t in zip(x.shape, expand_times)
    )
    return _single_out(
        helper, "expand", {"X": [x]}, {"expand_times": list(expand_times)},
        shape=shape,
    )


def expand_as(x, target_tensor, name=None):
    helper = LayerHelper("expand_as", name=name)
    return _single_out(
        helper,
        "expand_as",
        {"X": [x], "target_tensor": [target_tensor]},
        shape=target_tensor.shape,
    )


def stack(x, axis=0, name=None):
    helper = LayerHelper("stack", name=name)
    xs = x if isinstance(x, (list, tuple)) else [x]
    shape = list(xs[0].shape)
    shape.insert(axis if axis >= 0 else axis + len(shape) + 1, len(xs))
    return _single_out(
        helper, "stack", {"X": xs}, {"axis": axis}, shape=tuple(shape),
        out_slot="Y",
    )


def unstack(x, axis=0, num=None, name=None):
    helper = LayerHelper("unstack", name=name)
    n = num or x.shape[axis]
    shape = tuple(s for i, s in enumerate(x.shape) if i != axis % len(x.shape))
    outs = [
        helper.create_variable_for_type_inference(x.dtype, shape) for _ in range(n)
    ]
    helper.append_op(
        type="unstack", inputs={"X": [x]}, outputs={"Y": outs},
        attrs={"axis": axis},
    )
    return outs


def shape(input):
    helper = LayerHelper("shape")
    return _single_out(
        helper, "shape", {"Input": [input]}, dtype="int32",
        shape=(len(input.shape),),
    )


def cumsum(x, axis=-1, exclusive=False, reverse=False, name=None):
    helper = LayerHelper("cumsum", name=name)
    return _single_out(
        helper,
        "cumsum",
        {"X": [x]},
        {"axis": axis, "exclusive": exclusive, "reverse": reverse},
        shape=x.shape,
    )


def argmax(x, axis=0, name=None):
    helper = LayerHelper("arg_max", name=name)
    shape = tuple(s for i, s in enumerate(x.shape) if i != axis % len(x.shape))
    return _single_out(
        helper, "arg_max", {"X": [x]}, {"axis": axis}, dtype="int64",
        shape=shape or (1,),
    )


def argmin(x, axis=0, name=None):
    helper = LayerHelper("arg_min", name=name)
    shape = tuple(s for i, s in enumerate(x.shape) if i != axis % len(x.shape))
    return _single_out(
        helper, "arg_min", {"X": [x]}, {"axis": axis}, dtype="int64",
        shape=shape or (1,),
    )


def argsort(x, axis=-1, descending=False, name=None):
    helper = LayerHelper("argsort", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    indices = helper.create_variable_for_type_inference(
        "int64", x.shape, stop_gradient=True
    )
    helper.append_op(
        type="argsort",
        inputs={"X": [x]},
        outputs={"Out": [out], "Indices": [indices]},
        attrs={"axis": axis, "descending": descending},
    )
    return out, indices


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper("pad", name=name)
    shape = tuple(
        s + paddings[2 * i] + paddings[2 * i + 1] if s not in (-1, None) else -1
        for i, s in enumerate(x.shape)
    )
    return _single_out(
        helper, "pad", {"X": [x]},
        {"paddings": list(paddings), "pad_value": pad_value}, shape=shape,
    )


def pad2d(input, paddings=[0, 0, 0, 0], mode="constant", pad_value=0.0,
          data_format="NCHW", name=None):
    helper = LayerHelper("pad2d", name=name)
    n, c, h, w = input.shape
    shape = (n, c,
             h + paddings[0] + paddings[1] if h not in (-1, None) else -1,
             w + paddings[2] + paddings[3] if w not in (-1, None) else -1)
    return _single_out(
        helper,
        "pad2d",
        {"X": [input]},
        {"paddings": list(paddings), "mode": mode, "pad_value": pad_value},
        shape=shape,
    )


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    """reference: operators/lrn_op.cc — across-channel local response
    normalization over an n-wide channel window (NCHW)."""
    helper = LayerHelper("lrn", name=name)
    return _single_out(
        helper, "lrn", {"X": [input]},
        {"n": int(n), "k": float(k), "alpha": float(alpha),
         "beta": float(beta)},
        shape=input.shape,
    )


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """reference: operators/unfold_op.cc (im2col): NCHW -> [N, C*kh*kw, L]."""
    helper = LayerHelper("unfold", name=name)
    ks = [kernel_sizes] * 2 if isinstance(kernel_sizes, int) else list(kernel_sizes)
    st = [strides] * 2 if isinstance(strides, int) else list(strides)
    pd = [paddings] * 4 if isinstance(paddings, int) else list(paddings)
    if len(pd) == 2:
        pd = [pd[0], pd[1], pd[0], pd[1]]
    dl = [dilations] * 2 if isinstance(dilations, int) else list(dilations)
    n, c, h, w = x.shape
    oh = (h + pd[0] + pd[2] - (dl[0] * (ks[0] - 1) + 1)) // st[0] + 1
    ow = (w + pd[1] + pd[3] - (dl[1] * (ks[1] - 1) + 1)) // st[1] + 1
    return _single_out(
        helper, "unfold", {"X": [x]},
        {"kernel_sizes": ks, "strides": st, "paddings": pd,
         "dilations": dl},
        shape=(n, c * ks[0] * ks[1], oh * ow),
    )


def image_resize(input, out_shape=None, scale=None, resample="BILINEAR",
                 align_corners=True, name=None):
    helper = LayerHelper("image_resize", name=name)
    n, c, h, w = input.shape
    if out_shape is None:
        if scale is None:
            raise ValueError(
                "image_resize: one of out_shape or scale is required"
            )
        out_shape = [int(h * scale), int(w * scale)]
    op_type = "nearest_interp" if resample == "NEAREST" else "bilinear_interp"
    return _single_out(
        helper,
        op_type,
        {"X": [input]},
        {"out_h": out_shape[0], "out_w": out_shape[1],
         "align_corners": align_corners},
        shape=(n, c, out_shape[0], out_shape[1]),
    )


def resize_nearest(input, out_shape=None, scale=None, align_corners=True, name=None):
    return image_resize(input, out_shape, scale, "NEAREST", align_corners, name)


def resize_bilinear(input, out_shape=None, scale=None, align_corners=True, name=None):
    return image_resize(input, out_shape, scale, "BILINEAR", align_corners, name)


def resize_trilinear(input, out_shape=None, scale=None, align_corners=True,
                     name=None):
    """reference: layers/nn.py resize_trilinear (interpolate_op.cc
    trilinear path). NCDHW."""
    helper = LayerHelper("resize_trilinear", name=name)
    n, c, d, h, w = input.shape
    if out_shape is None:
        if scale is None:
            raise ValueError(
                "resize_trilinear: one of out_shape or scale is required"
            )
        out_shape = [int(d * scale), int(h * scale), int(w * scale)]
    return _single_out(
        helper,
        "trilinear_interp",
        {"X": [input]},
        {"out_d": out_shape[0], "out_h": out_shape[1],
         "out_w": out_shape[2], "align_corners": align_corners},
        shape=(n, c, out_shape[0], out_shape[1], out_shape[2]),
    )


trilinear_interp = resize_trilinear


def pixel_shuffle(x, upscale_factor):
    helper = LayerHelper("pixel_shuffle")
    n, c, h, w = x.shape
    r = upscale_factor
    return _single_out(
        helper, "pixel_shuffle", {"X": [x]}, {"upscale_factor": r},
        shape=(n, c // (r * r), h * r, w * r),
    )


def uniform_random_batch_size_like(input, shape, min=-1.0, max=1.0,
                                   input_dim_idx=0, output_dim_idx=0,
                                   dtype="float32", seed=0):
    helper = LayerHelper("uniform_random_batch_size_like")
    return _single_out(
        helper,
        "uniform_random_batch_size_like",
        {"Input": [input]},
        {"shape": list(shape), "min": min, "max": max, "seed": seed,
         "input_dim_idx": input_dim_idx, "output_dim_idx": output_dim_idx},
        dtype=dtype,
        shape=tuple(shape),
    )


def gaussian_random(shape, mean=0.0, std=1.0, seed=0, dtype="float32"):
    helper = LayerHelper("gaussian_random")
    return _single_out(
        helper,
        "gaussian_random",
        {},
        {"shape": list(shape), "mean": mean, "std": std, "seed": seed,
         "dtype": dtype},
        dtype=dtype,
        shape=tuple(shape),
    )


def sampling_id(x, min=0.0, max=1.0, seed=0, dtype="float32"):
    helper = LayerHelper("sampling_id")
    return _single_out(
        helper, "sampling_id", {"X": [x]}, {"seed": seed}, dtype="int64",
        shape=(x.shape[0],),
    )


def _crf_transition_param(helper, param_attr, n_tags, dtype):
    """Create — or REUSE by name — the [n_tags+2, n_tags] transition
    parameter, so linear_chain_crf and crf_decoding share one variable
    without appending a second (clobbering) startup initializer."""
    from ..framework import default_main_program
    from ..param_attr import ParamAttr as _PA

    attr = _PA._to_attr(param_attr)
    pname = getattr(attr, "name", None)
    if pname:
        gb = default_main_program().global_block()
        if pname in gb.vars:
            return gb.vars[pname]
    return helper.create_parameter(
        param_attr, [n_tags + 2, n_tags], dtype=dtype,
        default_initializer=Normal(0.0, 0.1),
    )


def linear_chain_crf(input, label, param_attr=None, length=None, mask=None,
                     name=None):
    """reference: layers/nn.py linear_chain_crf (linear_chain_crf_op.cc).
    input [b, s, n_tags] emissions, label [b, s] int; returns the per-
    sequence negative log-likelihood [b, 1]. The transition parameter
    ([n_tags+2, n_tags]: start row, end row, tag->tag) is created here and
    shared with crf_decoding via param_attr name. `length` [b] (the
    reference padded-Tensor API) builds the padding mask when `mask` is
    not given."""
    helper = LayerHelper("linear_chain_crf", name=name)
    n_tags = input.shape[-1]
    transition = _crf_transition_param(
        helper, param_attr, n_tags, input.dtype)
    if mask is None and length is not None:
        from .sequence import sequence_mask
        from .tensor import cast

        mask = cast(sequence_mask(length, maxlen=input.shape[1]), "float32")
    out = helper.create_variable_for_type_inference(
        input.dtype, (input.shape[0], 1))
    inputs = {"Emission": [input], "Transition": [transition],
              "Label": [label]}
    if mask is not None:
        inputs["Mask"] = [mask]
    helper.append_op(
        type="linear_chain_crf",
        inputs=inputs,
        outputs={"LogLikelihood": [out]},
        attrs={},
    )
    return out


def crf_decoding(input, param_attr, label=None, mask=None, length=None,
                 name=None):
    """reference: layers/nn.py crf_decoding (crf_decoding_op.cc): Viterbi
    decode [b, s, n_tags] emissions -> best tag path [b, s] int64 using the
    transition parameter created by linear_chain_crf (shared by name).
    With `label` given, returns 0/1 correctness marks instead (1 where the
    decoded tag equals the label — the reference evaluation convention)."""
    helper = LayerHelper("crf_decoding", name=name)
    n_tags = input.shape[-1]
    transition = _crf_transition_param(
        helper, param_attr, n_tags, input.dtype)
    if mask is None and length is not None:
        from .sequence import sequence_mask
        from .tensor import cast

        mask = cast(sequence_mask(length, maxlen=input.shape[1]), "float32")
    out = helper.create_variable_for_type_inference(
        "int64", tuple(input.shape[:-1]), stop_gradient=True)
    inputs = {"Emission": [input], "Transition": [transition]}
    if mask is not None:
        inputs["Mask"] = [mask]
    helper.append_op(
        type="crf_decoding",
        inputs=inputs,
        outputs={"ViterbiPath": [out]},
        attrs={},
    )
    if label is not None:
        from .tensor import cast, equal

        marks = cast(equal(out, label), "int64")
        marks.stop_gradient = True
        return marks
    return out


def nce(input, label, num_total_classes, sample_weight=None, param_attr=None,
        bias_attr=None, num_neg_samples=10, name=None, sampler="uniform",
        custom_dist=None, seed=0, is_sparse=False):
    """reference: layers/nn.py nce (nce_op.cc). Uniform negative sampler;
    returns the per-sample NCE cost [b, 1] (minimize its mean)."""
    if sampler not in ("uniform", "log_uniform", "custom_dist"):
        raise ValueError(f"nce: unknown sampler {sampler!r}")
    if sampler == "custom_dist" and custom_dist is None:
        raise ValueError("nce: sampler='custom_dist' needs custom_dist")
    helper = LayerHelper("nce", name=name)
    d = input.shape[-1]
    weight = helper.create_parameter(
        param_attr, [num_total_classes, d], dtype=input.dtype,
        default_initializer=Normal(0.0, 1.0 / float(np.sqrt(d))),
    )
    inputs = {"Input": [input], "Label": [label], "Weight": [weight]}
    if bias_attr is not False:
        bias = helper.create_parameter(
            bias_attr, [num_total_classes], dtype=input.dtype, is_bias=True)
        inputs["Bias"] = [bias]
    if sampler == "custom_dist":
        from .tensor import assign

        inputs["CustomDistProbs"] = [
            assign(np.asarray(custom_dist, dtype="float32"))
        ]
    cost = helper.create_variable_for_type_inference(
        input.dtype, (input.shape[0], 1))
    helper.append_op(
        type="nce",
        inputs=inputs,
        outputs={"Cost": [cost]},
        attrs={
            "num_total_classes": num_total_classes,
            "num_neg_samples": num_neg_samples,
            "sampler": sampler,
            "seed": seed,
        },
    )
    return cost


def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None,
             name=None, path_table=None, path_code=None, is_custom=False,
             is_sparse=False):
    """reference: layers/nn.py hsigmoid (hierarchical_sigmoid_op.cc):
    default complete binary tree, or a custom tree via path_table
    (per-sample weight-row ids, -1 padded) + path_code (per-edge bits).
    Returns the per-sample cost [b, 1]."""
    if is_custom and (path_table is None or path_code is None):
        raise ValueError(
            "hsigmoid: is_custom=True needs path_table AND path_code"
        )
    helper = LayerHelper("hsigmoid", name=name)
    d = input.shape[-1]
    rows = num_classes if (is_custom or path_table is not None) \
        else num_classes - 1
    w = helper.create_parameter(
        param_attr, [rows, d], dtype=input.dtype,
        default_initializer=Normal(0.0, 1.0 / float(np.sqrt(d))),
    )
    inputs = {"X": [input], "W": [w], "Label": [label]}
    if path_table is not None:
        inputs["PathTable"] = [path_table]
        inputs["PathCode"] = [path_code]
    if bias_attr is not False:
        bias = helper.create_parameter(
            bias_attr, [rows], dtype=input.dtype, is_bias=True)
        inputs["Bias"] = [bias]
    cost = helper.create_variable_for_type_inference(
        input.dtype, (input.shape[0], 1))
    helper.append_op(
        type="hierarchical_sigmoid",
        inputs=inputs,
        outputs={"Cost": [cost]},
        attrs={"num_classes": num_classes},
    )
    return cost


# ---------------------------------------------------------------------------
# ranking / metric-learning / CTR losses (reference layers/nn.py:366,1566,
# 1782,9335,9410,12032 — rank_loss_op.cc, margin_rank_loss_op.cc,
# bpr_loss_op.cc, center_loss_op.cc, cos_sim_op.cc,
# teacher_student_sigmoid_loss_op.cc)
# ---------------------------------------------------------------------------


def cos_sim(X, Y):
    helper = LayerHelper("cos_sim")
    return _single_out(
        helper, "cos_sim", {"X": [X], "Y": [Y]},
        shape=(X.shape[0], 1),
    )


def rank_loss(label, left, right, name=None):
    helper = LayerHelper("rank_loss", name=name)
    return _single_out(
        helper, "rank_loss",
        {"Label": [label], "Left": [left], "Right": [right]},
        shape=left.shape,
    )


def margin_rank_loss(label, left, right, margin=0.1, name=None):
    helper = LayerHelper("margin_rank_loss", name=name)
    act = helper.create_variable_for_type_inference(left.dtype, left.shape)
    out = helper.create_variable_for_type_inference(left.dtype, left.shape)
    helper.append_op(
        type="margin_rank_loss",
        inputs={"Label": [label], "X1": [left], "X2": [right]},
        outputs={"Out": [out], "Activated": [act]},
        attrs={"margin": margin},
    )
    return out


def bpr_loss(input, label, name=None):
    helper = LayerHelper("bpr_loss", name=name)
    return _single_out(
        helper, "bpr_loss", {"X": [input], "Label": [label]},
        shape=(input.shape[0], 1), out_slot="Y",
    )


def hinge_loss(input, label, name=None):
    helper = LayerHelper("hinge_loss", name=name)
    return _single_out(
        helper, "hinge_loss", {"Logits": [input], "Labels": [label]},
        shape=input.shape, out_slot="Loss",
    )


def modified_huber_loss(input, label, name=None):
    helper = LayerHelper("modified_huber_loss", name=name)
    inter = helper.create_variable_for_type_inference(
        input.dtype, input.shape)
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    helper.append_op(
        type="modified_huber_loss",
        inputs={"X": [input], "Y": [label]},
        outputs={"Out": [out], "IntermediateVal": [inter]},
    )
    return out


def teacher_student_sigmoid_loss(input, label, soft_max_up_bound=15.0,
                                 soft_max_lower_bound=-15.0):
    helper = LayerHelper("teacher_student_sigmoid_loss")
    return _single_out(
        helper, "teacher_student_sigmoid_loss",
        {"X": [input], "Label": [label]},
        {"soft_max_up_bound": soft_max_up_bound,
         "soft_max_lower_bound": soft_max_lower_bound},
        shape=input.shape, out_slot="Y",
    )


def squared_l2_distance(x, y):
    helper = LayerHelper("squared_l2_distance")
    sub = helper.create_variable_for_type_inference(x.dtype, x.shape)
    out = helper.create_variable_for_type_inference(x.dtype, (x.shape[0], 1))
    helper.append_op(
        type="squared_l2_distance",
        inputs={"X": [x], "Y": [y]},
        outputs={"Out": [out], "sub_result": [sub]},
    )
    return out


def center_loss(input, label, num_classes, alpha, param_attr,
                update_center=True):
    """reference layers/nn.py:366 (center_loss_op.cc). The centers are a
    persistable parameter updated in the forward pass (stateful output)."""
    helper = LayerHelper("center_loss")
    d = input.shape[-1]
    centers = helper.create_parameter(
        param_attr, [num_classes, d], dtype="float32",
        default_initializer=Constant(0.0),
    )
    centers.stop_gradient = True
    from .tensor import fill_constant

    rate = fill_constant([1], "float32", float(alpha))
    loss = helper.create_variable_for_type_inference(
        input.dtype, (input.shape[0], 1))
    diff = helper.create_variable_for_type_inference(input.dtype, input.shape)
    helper.append_op(
        type="center_loss",
        inputs={"X": [input], "Label": [label], "Centers": [centers],
                "CenterUpdateRate": [rate]},
        outputs={"Loss": [loss], "SampleCenterDiff": [diff],
                 "CentersOut": [centers]},
        attrs={"cluster_num": num_classes, "need_update": update_center},
    )
    return loss


def sampled_softmax_with_cross_entropy(
    logits, label, num_samples, num_true=1, remove_accidental_hits=True,
    use_customized_samples=False, customized_samples=None,
    customized_probabilities=None, seed=0,
):
    """reference layers/nn.py:6748 (sample_logits_op.cc +
    softmax_with_cross_entropy): estimate full-softmax cross entropy from
    num_true + num_samples gathered classes."""
    helper = LayerHelper("sampled_softmax_with_cross_entropy")
    n = logits.shape[0]
    k = num_true + num_samples
    samples = helper.create_variable_for_type_inference("int64", (n, k))
    probs = helper.create_variable_for_type_inference(logits.dtype, (n, k))
    sampled_logits = helper.create_variable_for_type_inference(
        logits.dtype, (n, k))
    sampled_label = helper.create_variable_for_type_inference(
        "int64", (n, num_true))
    inputs = {"Logits": [logits], "Labels": [label]}
    if use_customized_samples:
        inputs["CustomizedSamples"] = [customized_samples]
        inputs["CustomizedProbabilities"] = [customized_probabilities]
    helper.append_op(
        type="sample_logits",
        inputs=inputs,
        outputs={"Samples": [samples], "Probabilities": [probs],
                 "SampledLogits": [sampled_logits],
                 "SampledLabels": [sampled_label]},
        attrs={"num_samples": num_samples,
               "use_customized_samples": use_customized_samples,
               "remove_accidental_hits": remove_accidental_hits,
               "seed": seed},
    )
    loss = helper.create_variable_for_type_inference(logits.dtype, (n, 1))
    helper.append_op(
        type="softmax_with_cross_entropy",
        inputs={"Logits": [sampled_logits], "Label": [sampled_label]},
        outputs={"Loss": [loss],
                 "Softmax": [helper.create_variable_for_type_inference(
                     logits.dtype, (n, k))]},
        attrs={"soft_label": False},
    )
    return loss


def selu(x, scale=None, alpha=None, name=None):
    helper = LayerHelper("selu", name=name)
    attrs = {}
    if scale is not None:
        attrs["scale"] = scale
    if alpha is not None:
        attrs["alpha"] = alpha
    return _single_out(helper, "selu", {"X": [x]}, attrs, shape=x.shape)


def mean_iou(input, label, num_classes):
    helper = LayerHelper("mean_iou")
    miou = helper.create_variable_for_type_inference("float32", (1,))
    wrong = helper.create_variable_for_type_inference(
        "int32", (num_classes,))
    correct = helper.create_variable_for_type_inference(
        "int32", (num_classes,))
    helper.append_op(
        type="mean_iou",
        inputs={"Predictions": [input], "Labels": [label]},
        outputs={"OutMeanIou": [miou], "OutWrong": [wrong],
                 "OutCorrect": [correct]},
        attrs={"num_classes": num_classes},
    )
    return miou, wrong, correct


def multiplex(inputs, index):
    helper = LayerHelper("multiplex")
    return _single_out(
        helper, "multiplex",
        {"X": list(inputs), "Ids": [index]},
        shape=inputs[0].shape,
    )


def crop(x, shape=None, offsets=None, name=None):
    helper = LayerHelper("crop", name=name)
    attrs = {}
    inputs = {"X": [x]}
    if hasattr(shape, "dtype"):  # Variable: crop to its shape
        inputs["Y"] = [shape]
        out_shape = shape.shape
    else:
        attrs["shape"] = list(shape)
        out_shape = tuple(shape)
    if offsets is not None:
        attrs["offsets"] = list(offsets)
    out = helper.create_variable_for_type_inference(x.dtype, out_shape)
    helper.append_op(type="crop", inputs=inputs,
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def continuous_value_model(input, cvm, use_cvm=True):
    """reference layers/nn.py:12962 (cvm_op.cc): CTR show/click feature
    transform. input [N, D] whose first two columns are show/click; cvm
    [N, 2]."""
    helper = LayerHelper("cvm")
    d = input.shape[1] if use_cvm else input.shape[1] - 2
    return _single_out(
        helper, "cvm", {"X": [input], "CVM": [cvm]},
        {"use_cvm": use_cvm}, shape=(input.shape[0], d), out_slot="Y",
    )


def data_norm(input, act=None, epsilon=1e-05, param_attr=None,
              data_layout="NCHW", in_place=False, name=None,
              moving_mean_name=None, moving_variance_name=None,
              do_model_average_for_mean_and_var=False):
    """reference layers/nn.py:3501 (data_norm_op.cc): normalization by
    running batch statistics accumulated THROUGH the gradient contract
    (d_stats are the batch count/sum/square-sum)."""
    helper = LayerHelper("data_norm", name=name)
    c = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    defaults = {"batch_size": 1e4, "batch_sum": 0.0, "batch_square": 1e4}
    if param_attr and isinstance(param_attr, dict):
        defaults.update(param_attr)
    stats = {}
    for slot, key in (("BatchSize", "batch_size"), ("BatchSum", "batch_sum"),
                      ("BatchSquareSum", "batch_square")):
        stats[slot] = helper.create_parameter(
            ParamAttr(name=(name or helper.prefix) + "." + key,
                      initializer=Constant(float(defaults[key]))),
            [c], dtype="float32",
        )
    y = helper.create_variable_for_type_inference(input.dtype, input.shape)
    means = helper.create_variable_for_type_inference("float32", (c,))
    scales = helper.create_variable_for_type_inference("float32", (c,))
    helper.append_op(
        type="data_norm",
        inputs={"X": [input], "BatchSize": [stats["BatchSize"]],
                "BatchSum": [stats["BatchSum"]],
                "BatchSquareSum": [stats["BatchSquareSum"]]},
        outputs={"Y": [y], "Means": [means], "Scales": [scales]},
        attrs={"epsilon": epsilon, "data_layout": data_layout},
    )
    return helper.append_activation(y)


# ---------------------------------------------------------------------------
# vision / spatial-transform layers (reference layers/nn.py: affine_channel,
# affine_grid, grid_sampler, spectral_norm, temporal_shift, shuffle_channel,
# space_to_depth, pool3d, im2sequence, row_conv, psroi_pool, deformable_conv,
# bilinear_tensor_product, fsp_matrix, add_position_encoding,
# pad_constant_like, conv3d_transpose)
# ---------------------------------------------------------------------------


def affine_channel(x, scale=None, bias=None, data_layout="NCHW", name=None,
                   act=None):
    helper = LayerHelper("affine_channel", name=name, act=act)
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    helper.append_op(
        type="affine_channel",
        inputs={"X": [x], "Scale": [scale], "Bias": [bias]},
        outputs={"Out": [out]},
        attrs={"data_layout": data_layout},
    )
    return helper.append_activation(out)


def affine_grid(theta, out_shape, name=None):
    helper = LayerHelper("affine_grid", name=name)
    if hasattr(out_shape, "dtype"):
        inputs = {"Theta": [theta], "OutputShape": [out_shape]}
        attrs = {}
        shape = None
    else:
        inputs = {"Theta": [theta]}
        attrs = {"output_shape": list(out_shape)}
        shape = (out_shape[0], out_shape[2], out_shape[3], 2)
    out = helper.create_variable_for_type_inference(theta.dtype, shape)
    helper.append_op(type="affine_grid", inputs=inputs,
                     outputs={"Output": [out]}, attrs=attrs)
    return out


def grid_sampler(x, grid, name=None):
    helper = LayerHelper("grid_sampler", name=name)
    shape = (x.shape[0], x.shape[1], grid.shape[1], grid.shape[2])
    out = helper.create_variable_for_type_inference(x.dtype, shape)
    helper.append_op(type="grid_sampler",
                     inputs={"X": [x], "Grid": [grid]},
                     outputs={"Output": [out]})
    return out


def spectral_norm(weight, dim=0, power_iters=1, eps=1e-12, name=None):
    helper = LayerHelper("spectral_norm", name=name)
    h = weight.shape[dim]
    w = 1
    for i, s in enumerate(weight.shape):
        if i != dim:
            w *= s
    u = helper.create_or_get_global_variable(
        (name or helper.prefix) + ".u", [h], "float32",
        initializer=Normal(0.0, 1.0),
    )
    v = helper.create_or_get_global_variable(
        (name or helper.prefix) + ".v", [w], "float32",
        initializer=Normal(0.0, 1.0),
    )
    out = helper.create_variable_for_type_inference(weight.dtype,
                                                    weight.shape)
    helper.append_op(
        type="spectral_norm",
        inputs={"Weight": [weight], "U": [u], "V": [v]},
        outputs={"Out": [out]},
        attrs={"dim": dim, "power_iters": power_iters, "eps": eps},
    )
    return out


def temporal_shift(x, seg_num, shift_ratio=0.25, name=None):
    helper = LayerHelper("temporal_shift", name=name)
    return _single_out(
        helper, "temporal_shift", {"X": [x]},
        {"seg_num": seg_num, "shift_ratio": shift_ratio}, shape=x.shape,
    )


def shuffle_channel(x, group, name=None):
    helper = LayerHelper("shuffle_channel", name=name)
    return _single_out(helper, "shuffle_channel", {"X": [x]},
                       {"group": group}, shape=x.shape)


def space_to_depth(x, blocksize, name=None):
    helper = LayerHelper("space_to_depth", name=name)
    n, c, h, w = x.shape
    return _single_out(
        helper, "space_to_depth", {"X": [x]}, {"blocksize": blocksize},
        shape=(n, c * blocksize * blocksize, h // blocksize,
               w // blocksize),
    )


def pool3d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, name=None, exclusive=True):
    helper = LayerHelper("pool3d", name=name)
    ksize = ([pool_size] * 3 if isinstance(pool_size, int) else
             list(pool_size))
    strides = ([pool_stride] * 3 if isinstance(pool_stride, int) else
               list(pool_stride))
    pads = ([pool_padding] * 3 if isinstance(pool_padding, int) else
            list(pool_padding))
    n, c, d, h, w = input.shape
    if global_pooling:
        shape = (n, c, 1, 1, 1)
    else:
        shape = tuple(
            [n, c] + [
                (s + 2 * p - k) // st + 1
                for s, k, st, p in zip((d, h, w), ksize, strides, pads)
            ]
        )
    return _single_out(
        helper, "pool3d", {"X": [input]},
        {"ksize": ksize, "strides": strides, "paddings": pads,
         "pooling_type": pool_type, "global_pooling": global_pooling,
         "exclusive": exclusive},
        shape=shape,
    )


def im2sequence(input, filter_size=1, stride=1, padding=0, input_image_size=None,
                out_stride=1, name=None):
    helper = LayerHelper("im2sequence", name=name)
    ks = [filter_size] * 2 if isinstance(filter_size, int) else list(filter_size)
    st = [stride] * 2 if isinstance(stride, int) else list(stride)
    pd = [padding] * 4 if isinstance(padding, int) else list(padding)
    n, c, h, w = input.shape
    oh = (h + pd[0] + pd[2] - ks[0]) // st[0] + 1
    ow = (w + pd[1] + pd[3] - ks[1]) // st[1] + 1
    return _single_out(
        helper, "im2sequence", {"X": [input]},
        {"kernels": ks, "strides": st, "paddings": pd},
        shape=(n, oh * ow, c * ks[0] * ks[1]),
    )


def row_conv(input, future_context_size, param_attr=None, act=None):
    helper = LayerHelper("row_conv", act=act)
    d = input.shape[-1]
    f = helper.create_parameter(
        param_attr, [future_context_size + 1, d], dtype="float32",
    )
    out = _single_out(helper, "row_conv",
                      {"X": [input], "Filter": [f]}, shape=input.shape)
    return helper.append_activation(out)


def psroi_pool(input, rois, output_channels, spatial_scale, pooled_height,
               pooled_width, rois_num=None, name=None):
    helper = LayerHelper("psroi_pool", name=name)
    inputs = {"X": [input], "ROIs": [rois]}
    if rois_num is not None:
        inputs["RoisNum"] = [rois_num]
    return _single_out(
        helper, "psroi_pool", inputs,
        {"output_channels": output_channels, "spatial_scale": spatial_scale,
         "pooled_height": pooled_height, "pooled_width": pooled_width},
        shape=(rois.shape[0], output_channels, pooled_height, pooled_width),
    )


def deformable_conv(input, offset, mask, num_filters, filter_size,
                    stride=1, padding=0, dilation=1, groups=1,
                    deformable_groups=1, im2col_step=1, param_attr=None,
                    bias_attr=None, modulated=True, name=None):
    helper = LayerHelper("deformable_conv", name=name)
    c = input.shape[1]
    ks = ([filter_size] * 2 if isinstance(filter_size, int)
          else list(filter_size))
    st = [stride] * 2 if isinstance(stride, int) else list(stride)
    pd = [padding] * 2 if isinstance(padding, int) else list(padding)
    dl = [dilation] * 2 if isinstance(dilation, int) else list(dilation)
    w = helper.create_parameter(
        param_attr, [num_filters, c // groups] + ks, dtype=input.dtype,
        default_initializer=Normal(
            0.0, 1.0 / float(np.sqrt(c * ks[0] * ks[1]))),
    )
    n, _, h, wd = input.shape
    oh = (h + 2 * pd[0] - (dl[0] * (ks[0] - 1) + 1)) // st[0] + 1
    ow = (wd + 2 * pd[1] - (dl[1] * (ks[1] - 1) + 1)) // st[1] + 1
    inputs = {"Input": [input], "Offset": [offset], "Filter": [w]}
    if modulated and mask is not None:
        inputs["Mask"] = [mask]
    out = helper.create_variable_for_type_inference(
        input.dtype, (n, num_filters, oh, ow))
    helper.append_op(
        type="deformable_conv", inputs=inputs,
        outputs={"Output": [out]},
        attrs={"strides": st, "paddings": pd, "dilations": dl,
               "groups": groups, "deformable_groups": deformable_groups,
               "im2col_step": im2col_step},
    )
    if bias_attr is not False:
        out = helper.append_bias_op(out, bias_attr, num_filters,
                                    dim_start=1)
    return out


def deformable_roi_pooling(input, rois, trans, no_trans=False,
                           spatial_scale=1.0, group_size=[1, 1],
                           pooled_height=1, pooled_width=1, part_size=None,
                           sample_per_part=1, trans_std=0.1,
                           position_sensitive=False, name=None):
    """reference: layers/nn.py:13469 deformable_roi_pooling — emits the
    deformable_psroi_pooling op (deformable_psroi_pooling_op.cc:260);
    output_dim follows the reference: C when not position-sensitive,
    C/(ph*pw) when position-sensitive."""
    helper = LayerHelper("deformable_psroi_pooling", name=name)
    c = input.shape[1]
    if position_sensitive:
        output_channels = int(c // (pooled_height * pooled_width))
    else:
        output_channels = int(c)
    if part_size is None:
        part_size = [pooled_height, pooled_width]
    part_size = ([part_size] * 2 if isinstance(part_size, int)
                 else list(part_size))
    group_size = ([group_size] * 2 if isinstance(group_size, int)
                  else list(group_size))
    out = helper.create_variable_for_type_inference(
        input.dtype,
        (rois.shape[0], output_channels, pooled_height, pooled_width))
    top_count = helper.create_variable_for_type_inference(
        "float32",
        (rois.shape[0], output_channels, pooled_height, pooled_width))
    top_count.stop_gradient = True
    helper.append_op(
        type="deformable_psroi_pooling",
        inputs={"Input": [input], "ROIs": [rois], "Trans": [trans]},
        outputs={"Output": [out], "TopCount": [top_count]},
        attrs={"no_trans": no_trans, "spatial_scale": spatial_scale,
               "output_dim": output_channels, "group_size": group_size,
               "pooled_height": pooled_height, "pooled_width": pooled_width,
               "part_size": part_size, "sample_per_part": sample_per_part,
               "trans_std": trans_std},
    )
    return out


def bilinear_tensor_product(x, y, size, act=None, name=None,
                            param_attr=None, bias_attr=None):
    helper = LayerHelper("bilinear_tensor_product", name=name, act=act)
    w = helper.create_parameter(
        param_attr, [size, x.shape[1], y.shape[1]], dtype=x.dtype)
    inputs = {"X": [x], "Y": [y], "Weight": [w]}
    if bias_attr is not False:
        bias = helper.create_parameter(
            bias_attr, [1, size], dtype=x.dtype, is_bias=True)
        inputs["Bias"] = [bias]
    out = _single_out(helper, "bilinear_tensor_product", inputs,
                      shape=(x.shape[0], size))
    return helper.append_activation(out)


def fsp_matrix(x, y):
    helper = LayerHelper("fsp_matrix")
    return _single_out(helper, "fsp", {"X": [x], "Y": [y]},
                       shape=(x.shape[0], x.shape[1], y.shape[1]))


def conv_shift(x, y, name=None):
    helper = LayerHelper("conv_shift", name=name)
    return _single_out(helper, "conv_shift", {"X": [x], "Y": [y]},
                       shape=x.shape)


def add_position_encoding(input, alpha, beta, name=None):
    helper = LayerHelper("add_position_encoding", name=name)
    return _single_out(
        helper, "add_position_encoding", {"X": [input]},
        {"alpha": alpha, "beta": beta}, shape=input.shape,
    )


def pad_constant_like(x, y, pad_value=0.0, name=None):
    helper = LayerHelper("pad_constant_like", name=name)
    return _single_out(
        helper, "pad_constant_like", {"X": [x], "Y": [y]},
        {"pad_value": pad_value}, shape=x.shape,
    )


def conv3d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=1,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None):
    helper = LayerHelper("conv3d_transpose", name=name, act=act)
    c = input.shape[1]
    ks = ([filter_size] * 3 if isinstance(filter_size, int)
          else list(filter_size))
    st = [stride] * 3 if isinstance(stride, int) else list(stride)
    pd = [padding] * 3 if isinstance(padding, int) else list(padding)
    dl = [dilation] * 3 if isinstance(dilation, int) else list(dilation)
    w = helper.create_parameter(
        param_attr, [c, num_filters // groups] + ks, dtype=input.dtype)
    n, _, d, h, wd = input.shape
    shape = tuple([n, num_filters] + [
        (s - 1) * stt - 2 * p + (dll * (k - 1) + 1)
        for s, stt, p, k, dll in zip((d, h, wd), st, pd, ks, dl)
    ])
    out = helper.create_variable_for_type_inference(input.dtype, shape)
    helper.append_op(
        type="conv3d_transpose",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={"strides": st, "paddings": pd, "dilations": dl,
               "groups": groups},
    )
    if bias_attr is not False:
        out = helper.append_bias_op(out, bias_attr, num_filters,
                                    dim_start=1)
    return helper.append_activation(out)


def unpool(x, indices, ksize=None, strides=None, unpooled_size=None):
    helper = LayerHelper("unpool")
    n, c, h, w = x.shape
    ks = ksize or [2, 2]
    st = strides or ks
    if unpooled_size:
        oh, ow = unpooled_size
    else:
        oh = (h - 1) * st[0] + ks[0]
        ow = (w - 1) * st[1] + ks[1]
    return _single_out(
        helper, "unpool", {"X": [x], "Indices": [indices]},
        {"ksize": ks, "strides": st, "unpooled_size": [oh, ow]},
        shape=(n, c, oh, ow),
    )


def max_pool2d_with_index(x, ksize, strides=None, paddings=None):
    helper = LayerHelper("max_pool2d_with_index")
    ks = [ksize] * 2 if isinstance(ksize, int) else list(ksize)
    st = strides or ks
    pd = paddings or [0, 0]
    n, c, h, w = x.shape
    oh = (h + 2 * pd[0] - ks[0]) // st[0] + 1
    ow = (w + 2 * pd[1] - ks[1]) // st[1] + 1
    out = helper.create_variable_for_type_inference(x.dtype, (n, c, oh, ow))
    mask = helper.create_variable_for_type_inference("int32", (n, c, oh, ow))
    helper.append_op(
        type="max_pool2d_with_index", inputs={"X": [x]},
        outputs={"Out": [out], "Mask": [mask]},
        attrs={"ksize": ks, "strides": st, "paddings": pd},
    )
    return out, mask


def spp(input, pyramid_height, pool_type="max"):
    helper = LayerHelper("spp")
    n, c = input.shape[0], input.shape[1]
    total = sum(4 ** p for p in range(pyramid_height))
    return _single_out(
        helper, "spp", {"X": [input]},
        {"pyramid_height": pyramid_height, "pooling_type": pool_type},
        shape=(n, c * total),
    )


# ---------------------------------------------------------------------------
# CTC / speech (reference layers/nn.py warpctc, ctc_greedy_decoder,
# edit_distance — warpctc_op.cc, ctc_align_op.cc, edit_distance_op.cc)
# ---------------------------------------------------------------------------


def warpctc(input, label, blank=0, norm_by_times=False,
            input_length=None, label_length=None):
    """CTC loss. Dense convention: input [B, T, C] raw logits, label
    [B, L] padded ids, optional [B] lengths (see ops/ctc_ops.py)."""
    helper = LayerHelper("warpctc")
    inputs = {"Logits": [input], "Label": [label]}
    if input_length is not None:
        inputs["LogitsLength"] = [input_length]
    if label_length is not None:
        inputs["LabelLength"] = [label_length]
    b = input.shape[0] if len(input.shape) == 3 else 1
    loss = helper.create_variable_for_type_inference("float32", (b, 1))
    grad = helper.create_variable_for_type_inference("float32", input.shape)
    helper.append_op(
        type="warpctc", inputs=inputs,
        outputs={"Loss": [loss], "WarpCTCGrad": [grad]},
        attrs={"blank": blank, "norm_by_times": norm_by_times},
    )
    return loss


def ctc_greedy_decoder(input, blank, input_length=None, padding_value=0,
                       name=None):
    """argmax over class probs then CTC collapse (reference
    layers/nn.py ctc_greedy_decoder = top-k(1) + ctc_align)."""
    helper = LayerHelper("ctc_greedy_decoder", name=name)
    ids = argmax(input, axis=-1)
    inputs = {"Input": [ids]}
    if input_length is not None:
        inputs["InputLength"] = [input_length]
    b, t = ids.shape if len(ids.shape) == 2 else (1, ids.shape[0])
    out = helper.create_variable_for_type_inference("int32", (b, t))
    out_len = helper.create_variable_for_type_inference("int32", (b, 1))
    helper.append_op(
        type="ctc_align", inputs=inputs,
        outputs={"Output": [out], "OutputLength": [out_len]},
        attrs={"blank": blank, "padding_value": padding_value,
               "merge_repeated": True},
    )
    return out, out_len


def edit_distance(input, label, normalized=True, ignored_tokens=None,
                  input_length=None, label_length=None):
    """Levenshtein distance per sequence (edit_distance_op.h). Dense
    convention: input/label [B, L] padded + optional [B] lengths."""
    helper = LayerHelper("edit_distance")
    inputs = {"Hyps": [input], "Refs": [label]}
    if input_length is not None:
        inputs["HypsLength"] = [input_length]
    if label_length is not None:
        inputs["RefsLength"] = [label_length]
    b = input.shape[0] if len(input.shape) >= 2 else 1
    out = helper.create_variable_for_type_inference("float32", (b, 1))
    seq_num = helper.create_variable_for_type_inference("int64", (1,))
    helper.append_op(
        type="edit_distance", inputs=inputs,
        outputs={"Out": [out], "SequenceNum": [seq_num]},
        attrs={"normalized": normalized},
    )
    return out, seq_num


def tree_conv(nodes_vector, edge_set, output_size, num_filters=1,
              max_depth=2, act="tanh", param_attr=None, bias_attr=None,
              name=None):
    """reference: contrib/layers tree_conv (tree_conv_op.cc, TBCNN)."""
    helper = LayerHelper("tree_conv", name=name, act=act)
    feat = nodes_vector.shape[-1]
    w = helper.create_parameter(
        param_attr, [feat, 3, output_size, num_filters],
        dtype="float32",
    )
    n = nodes_vector.shape[1]
    b = nodes_vector.shape[0]
    out = helper.create_variable_for_type_inference(
        "float32", (b, n, output_size, num_filters))
    helper.append_op(
        type="tree_conv",
        inputs={"NodesVector": [nodes_vector], "EdgeSet": [edge_set],
                "Filter": [w]},
        outputs={"Out": [out]},
        attrs={"max_depth": max_depth},
    )
    if bias_attr is not False and bias_attr is not None:
        out = helper.append_bias_op(out, bias_attr, num_filters,
                                    dim_start=3)
    return helper.append_activation(out)


def conv3d(input, num_filters, filter_size, stride=1, padding=0,
           dilation=1, groups=1, param_attr=None, bias_attr=None,
           use_cudnn=True, act=None, name=None):
    """reference: layers/nn.py `conv3d` (conv_op.cc 3D path). NCDHW."""
    helper = LayerHelper("conv3d", name=name, act=act)
    ks = [filter_size] * 3 if isinstance(filter_size, int) \
        else list(filter_size)
    st = [stride] * 3 if isinstance(stride, int) else list(stride)
    pd = [padding] * 3 if isinstance(padding, int) else list(padding)
    dl = [dilation] * 3 if isinstance(dilation, int) else list(dilation)
    groups = groups or 1
    c_in = input.shape[1]
    fan_in = (c_in // groups) * ks[0] * ks[1] * ks[2]
    w = helper.create_parameter(
        param_attr, [num_filters, c_in // groups] + ks,
        dtype=input.dtype,
        default_initializer=Normal(0.0, (2.0 / fan_in) ** 0.5),
    )
    out_shape = tuple(
        [input.shape[0], num_filters]
        + [
            _conv_out_dim(input.shape[2 + i], ks[i], pd[i], st[i], dl[i])
            for i in range(3)
        ]
    )
    out = helper.create_variable_for_type_inference(input.dtype, out_shape)
    helper.append_op(
        type="conv3d",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={"strides": st, "paddings": pd, "dilations": dl,
               "groups": groups},
    )
    pre_act = helper.append_bias_op(out, bias_attr, num_filters, 1)
    return helper.append_activation(pre_act)


def brelu(x, t_min=0.0, t_max=24.0, name=None):
    """reference: layers/ops.py brelu (activation_op.cc BRelu)."""
    helper = LayerHelper("brelu", name=name)
    return _single_out(
        helper, "brelu", {"X": [x]},
        {"t_min": float(t_min), "t_max": float(t_max)}, shape=x.shape,
    )


def scatter_nd(index, updates, shape, name=None):
    """reference: layers/nn.py scatter_nd (scatter_nd_op.cc): zeros of
    `shape` with `updates` scatter-added at `index`."""
    helper = LayerHelper("scatter_nd", name=name)
    return _single_out(
        helper, "scatter_nd", {"Index": [index], "Updates": [updates]},
        {"shape": list(shape)}, dtype=updates.dtype, shape=tuple(shape),
    )


def shard_index(input, index_num, nshards, shard_id, ignore_value=-1):
    """reference: layers/nn.py shard_index (shard_index_op.cc)."""
    if shard_id < 0 or shard_id >= nshards:
        raise ValueError(
            f"shard_id {shard_id} out of range [0, {nshards})"
        )
    helper = LayerHelper("shard_index")
    return _single_out(
        helper, "shard_index", {"X": [input]},
        {"index_num": index_num, "nshards": nshards, "shard_id": shard_id,
         "ignore_value": ignore_value},
        shape=input.shape,
    )


def unique(x, dtype="int64", return_count=False):
    """reference: layers/nn.py unique (unique_op.cc). Static-shape
    convention: Out is padded to len(x) (left-packed unique values in
    first-occurrence order, pad = last unique repeated); the extra
    Count output gives the true unique count — see ops/tensor_ops.py."""
    helper = LayerHelper("unique")
    n = 1
    for s in x.shape:
        n *= s
    out = helper.create_variable_for_type_inference(x.dtype, (n,))
    index = helper.create_variable_for_type_inference(dtype, (n,))
    outputs = {"Out": [out], "Index": [index]}
    count = None
    if return_count:
        count = helper.create_variable_for_type_inference("int64", (1,))
        outputs["Count"] = [count]
    helper.append_op(
        type="unique", inputs={"X": [x]}, outputs=outputs,
        attrs={"dtype": 3 if dtype == "int64" else 2},
    )
    return (out, index, count) if return_count else (out, index)


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    """reference: layers/nn.py npair_loss:12800 — softmax CE over the
    anchor@positive^T similarity matrix with row-normalized
    label-equality soft targets, plus Beta*l2_reg embedding L2."""
    from .tensor import cast as _cast
    from .tensor import equal as _equal

    beta = 0.25
    b = labels.shape[0]
    lab = reshape(labels, [b, 1])
    lab = expand(lab, [1, b])
    eq = _cast(_equal(lab, transpose(lab, [1, 0])), "float32")
    eq = elementwise_div(
        eq, reduce_sum(eq, dim=1, keep_dim=True)
    )
    from .ops import square as _square

    l2loss = elementwise_add(
        reduce_mean(reduce_sum(_square(anchor), 1)),
        reduce_mean(reduce_sum(_square(positive), 1)),
    )
    l2loss = scale(l2loss, beta * l2_reg)
    sim = matmul(anchor, positive, transpose_y=True)
    ce = softmax_with_cross_entropy(sim, eq, soft_label=True)
    celoss = reduce_mean(reduce_sum(elementwise_mul(eq, ce), 0))
    return elementwise_add(l2loss, celoss)


def py_func(func, x, out, backward_func=None,
            skip_vars_in_backward_input=None):
    """reference: layers/nn.py py_func:12435 (py_func_op.cc) — run a
    python callable on host values mid-graph via a registered callable
    id; `out` vars must be pre-created with shapes/dtypes (the reference
    contract). backward_func receives (inputs..., outputs...,
    out-grads...) and returns input grads."""
    from ..ops.misc_ops import register_py_func

    helper = LayerHelper("py_func")
    xs = [x] if isinstance(x, Variable) else list(x)
    outs = [out] if isinstance(out, Variable) else list(out)
    if skip_vars_in_backward_input:
        raise NotImplementedError(
            "skip_vars_in_backward_input: the TPU py_func passes all "
            "inputs+outputs+grads to backward_func (reference default)"
        )
    attrs = {"forward_callable_id": register_py_func(func)}
    if backward_func is not None:
        attrs["backward_callable_id"] = register_py_func(backward_func)
    helper.append_op(
        type="py_func", inputs={"X": xs}, outputs={"Out": outs},
        attrs=attrs,
    )
    return outs[0] if isinstance(out, Variable) else outs


def var_conv_2d(input, row, col, input_channel, output_channel,
                filter_size, stride=1, param_attr=None, act=None,
                name=None):
    """reference: var_conv_2d_op.cc (text-image conv over variable
    extents). Dense idiom: `input` is a padded canvas [b, in_c, H, W];
    `row`/`col` are [b] int tensors of each sample's valid rows/cols
    (the LoD analog). Output [b, out_c, ceil(H/s), ceil(W/s)] masked to
    each sample's own output extent."""
    helper = LayerHelper("var_conv_2d", name=name, act=act)
    ks = [filter_size] * 2 if isinstance(filter_size, int) \
        else list(filter_size)
    st = [stride] * 2 if isinstance(stride, int) else list(stride)
    w = helper.create_parameter(
        param_attr, [output_channel, input_channel * ks[0] * ks[1]],
        dtype=input.dtype,
    )
    b, _, h, wd = input.shape
    oh = (h - 1) // st[0] + 1
    ow = (wd - 1) // st[1] + 1
    out = helper.create_variable_for_type_inference(
        input.dtype, (b, output_channel, oh, ow))
    helper.append_op(
        type="var_conv_2d",
        inputs={"X": [input], "ROW": [row], "COLUMN": [col], "W": [w]},
        outputs={"Out": [out]},
        attrs={"InputChannel": input_channel,
               "OutputChannel": output_channel,
               "KernelH": ks[0], "KernelW": ks[1],
               "StrideH": st[0], "StrideW": st[1]},
    )
    return helper.append_activation(out)
