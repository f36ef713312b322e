"""NN op lowerings: conv / pool / norm / dropout / softmax / losses / embedding.

Capability parity with the dense-op core of reference
paddle/fluid/operators/ (conv_op.cc, pool_op.cc, batch_norm_op.cc,
layer_norm_op.cc, dropout_op.cc, softmax_op.cc,
softmax_with_cross_entropy_op.cc, cross_entropy_op.cc, lookup_table_op.cc).
Convs lower to lax.conv_general_dilated (MXU path); the embedding grad is
dense, the equivalent of the reference's SelectedRows rows
(framework/selected_rows.h:32), per SURVEY.md §7 hard-part 3: grouped
products over the tokens sorted by id where
`ops/pallas/embedding_grad.py::embedding_grad_viable` admits the call, the
vjp's scatter-add everywhere else (the section comment at `lookup_table`).
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from .. import profiler
from .registry import JNP_DTYPE, register_op

# ---------------------------------------------------------------------------
# convolution
# ---------------------------------------------------------------------------


def _conv_padding(padding, ndim):
    if isinstance(padding, str):
        return padding.upper()  # SAME / VALID
    if isinstance(padding, int):
        padding = [padding] * ndim
    if len(padding) == ndim:
        return [(p, p) for p in padding]
    if len(padding) == 2 * ndim:
        return [(padding[2 * i], padding[2 * i + 1]) for i in range(ndim)]
    raise ValueError(f"bad conv padding: {padding}")


def _s2d_stem_conv(x, w, pad, nhwc):
    """Space-to-depth stem conv: a 7x7/s2 conv on few input channels (the
    ResNet/VGG stem) leaves the MXU nearly idle — cin=3 occupies 3 of the
    128 lanes. Exact rearrangement: pad, fold each 2x2 pixel block into
    channels (cin -> 4*cin), and run the equivalent 4x4/s1 VALID conv whose
    kernel holds the same taps (zeros in the folded-out slots). Same math,
    4x the lane occupancy and half the spatial extent (the MLPerf-style
    stem trick, done as an IR lowering rewrite, not a model change).
    Returns the NHWC result."""
    o = w.shape[0]
    c = w.shape[1]
    xh = x if nhwc else jnp.transpose(x, (0, 2, 3, 1))  # NHWC
    n = xh.shape[0]
    xp = jnp.pad(xh, ((0, 0), tuple(pad[0]), tuple(pad[1]), (0, 0)))
    hp, wp = xp.shape[1], xp.shape[2]
    x2 = xp.reshape(n, hp // 2, 2, wp // 2, 2, c)
    # channel packing order (dh, dw, ci) — the kernel transpose matches it
    x2 = jnp.transpose(x2, (0, 1, 3, 2, 4, 5)).reshape(
        n, hp // 2, wp // 2, 4 * c
    )
    w8 = jnp.pad(w, ((0, 0), (0, 0), (0, 1), (0, 1)))  # 7x7 -> 8x8 taps
    wk = w8.reshape(o, c, 4, 2, 4, 2)
    wk = jnp.transpose(wk, (2, 4, 3, 5, 1, 0)).reshape(4, 4, 4 * c, o)
    return jax.lax.conv_general_dilated(
        x2, wk, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


@register_op("conv2d", no_grad_inputs=())
def _conv2d(ctx, op):
    x = ctx.in_(op, "Input")  # NCHW (fluid convention) or NHWC (layout_opt)
    w = ctx.in_(op, "Filter")  # OIHW in BOTH layouts
    bias = ctx.in_(op, "Bias")  # optional [O]: fuse_conv_bn folded shift
    x, w = ctx.amp_cast(op, x, w)
    strides = op.attr("strides", [1, 1])
    paddings = op.attr("paddings", [0, 0])
    dilations = op.attr("dilations", [1, 1])
    groups = op.attr("groups", 1) or 1
    nhwc = op.attr("data_format", "NCHW") == "NHWC"
    cin = x.shape[3] if nhwc else x.shape[1]
    pad = _conv_padding(paddings, 2)
    if (
        tuple(strides) == (2, 2)
        and tuple(dilations) == (1, 1)
        and groups == 1
        and w.shape[2] == 7 and w.shape[3] == 7
        and cin <= 8
        and not isinstance(pad, str)
        and (x.shape[1 if nhwc else 2] + pad[0][0] + pad[0][1]) % 2 == 0
        and (x.shape[2 if nhwc else 3] + pad[1][0] + pad[1][1]) % 2 == 0
        and os.environ.get("PADDLE_TPU_S2D_STEM", "1") == "1"
    ):
        # which lowering the op took, counted where it is traced (as
        # `attn_dispatch_*` is); nothing is counted when the step runs
        profiler.bump_counter("conv_dispatch_s2d_stem")
        out = _s2d_stem_conv(x, w, pad, nhwc)
    else:
        profiler.bump_counter("conv_dispatch_nhwc")
        # compute in NHWC — the TPU-native conv layout (channels ride the
        # lanes; NCHW convs measured ~2x slower on v5e). With the default
        # NCHW IR, XLA cancels the transpose pairs between adjacent
        # NHWC-internal ops (conv -> bn -> relu chains); the layout_opt
        # pass (passes/layout_opt.py) rewrites whole regions to
        # data_format=NHWC so the pairs never exist in the first place.
        out = jax.lax.conv_general_dilated(
            x if nhwc else jnp.transpose(x, (0, 2, 3, 1)),
            jnp.transpose(w, (2, 3, 1, 0)),
            window_strides=tuple(strides),
            padding=pad,
            rhs_dilation=tuple(dilations),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups,
            # NOTE: no preferred_element_type here — with bf16 operands
            # JAX's conv transpose rule would emit a mixed bf16/fp32 conv
            # (cotangent in the preferred dtype) and lax rejects it; the
            # MXU accumulates bf16 convs in fp32 regardless.
        )
    if bias is not None:
        # fuse_conv_bn's folded shift rides the conv epilogue (channel =
        # the NHWC-internal last dim either way)
        out = out + bias.astype(out.dtype)
    act = op.attr("fused_act", "") or ""
    if act:
        if act != "relu":
            raise ValueError(f"conv2d fused_act supports 'relu', got {act!r}")
        out = jax.nn.relu(out)
    ctx.out(op, "Output", out if nhwc else jnp.transpose(out, (0, 3, 1, 2)))


@register_op("depthwise_conv2d")
def _depthwise_conv2d(ctx, op):
    _conv2d(ctx, op)


@register_op("conv2d_transpose")
def _conv2d_transpose(ctx, op):
    x = ctx.in_(op, "Input")
    w = ctx.in_(op, "Filter")  # fluid: [in_c, out_c/groups, kh, kw]
    strides = tuple(op.attr("strides", [1, 1]))
    paddings = op.attr("paddings", [0, 0])
    dilations = tuple(op.attr("dilations", [1, 1]))
    groups = op.attr("groups", 1) or 1
    pad = _conv_padding(paddings, 2)
    if groups != 1:
        # lax.conv_transpose has no feature groups, but a transposed conv
        # IS the input-vjp of the forward grouped conv whose OIHW kernel
        # is exactly fluid's [in_c, out_c/groups, kh, kw] filter — exact
        # math for ANY groups (depthwise and channel-multiplier included)
        if isinstance(pad, str):
            raise NotImplementedError(
                "grouped conv2d_transpose with SAME/VALID string paddings"
                " — pass explicit pads"
            )
        n, in_c, h, wd = x.shape
        kh, kw = w.shape[2], w.shape[3]
        out_c = w.shape[1] * groups
        oh = (h - 1) * strides[0] - (pad[0][0] + pad[0][1]) + (
            (kh - 1) * dilations[0] + 1)
        ow = (wd - 1) * strides[1] - (pad[1][0] + pad[1][1]) + (
            (kw - 1) * dilations[1] + 1)

        def fwd(img):  # [n, out_c, oh, ow] -> [n, in_c, h, w]
            return jax.lax.conv_general_dilated(
                img,
                jnp.transpose(w, (2, 3, 1, 0)),  # HWIO
                window_strides=strides,
                padding=pad,
                rhs_dilation=dilations,
                dimension_numbers=("NCHW", "HWIO", "NCHW"),
                feature_group_count=groups,
            )

        zeros = jnp.zeros((n, out_c, oh, ow), x.dtype)
        _, vjp = jax.vjp(fwd, zeros)
        (out,) = vjp(x)
        ctx.out(op, "Output", out)
        return
    if isinstance(pad, str):
        pad_pairs = pad
    else:
        # fluid: out = (i-1)*stride - 2*pad + (k-1)*dilation + 1;
        # lax.conv_transpose explicit pairs use the FORWARD-conv
        # convention, so paddle's pad p maps to (ke - 1 - p) per side
        kh, kw = w.shape[2], w.shape[3]
        ke = [(kh - 1) * dilations[0] + 1, (kw - 1) * dilations[1] + 1]
        pad_pairs = [
            (ke[i] - 1 - p[0], ke[i] - 1 - p[1])
            for i, p in enumerate(pad)
        ]
    # fluid filter layout is [in_c, out_c, kh, kw]; transpose_kernel=True
    # wants the spec of the UNDERLYING FORWARD conv (out_c -> in_c), i.e.
    # OIHW: O = transpose input, I = transpose output. The former IOHW
    # spec crashed whenever in_c != out_c and silently used W[i,o] as
    # W[o,i] when they were equal (round-4 fix, caught by the dygraph
    # adapter's in!=out test).
    out = jax.lax.conv_transpose(
        x,
        w,
        strides=strides,
        padding=pad_pairs,
        rhs_dilation=dilations,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        transpose_kernel=True,
    )
    ctx.out(op, "Output", out)


@register_op("conv3d")
def _conv3d(ctx, op):
    x = ctx.in_(op, "Input")
    w = ctx.in_(op, "Filter")
    out = jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=tuple(op.attr("strides", [1, 1, 1])),
        padding=_conv_padding(op.attr("paddings", [0, 0, 0]), 3),
        rhs_dilation=tuple(op.attr("dilations", [1, 1, 1])),
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        feature_group_count=op.attr("groups", 1) or 1,
    )
    ctx.out(op, "Output", out)


# ---------------------------------------------------------------------------
# pooling (reference: operators/pool_op.cc)
# ---------------------------------------------------------------------------


def _adaptive_mask(size, out_size):
    """[out_size, size] f32 bin-membership mask with the reference's
    adaptive windows: bin i covers [floor(i*size/out), ceil((i+1)*size/
    out)) (adaptive pooling start/end index convention); the pooling
    einsum runs in f32 and casts back to the input dtype."""
    import numpy as _np

    idx = _np.arange(size)
    starts = _np.floor(_np.arange(out_size) * size / out_size)
    ends = _np.ceil((_np.arange(out_size) + 1) * size / out_size)
    m = (idx[None, :] >= starts[:, None]) & (idx[None, :] < ends[:, None])
    return jnp.asarray(m.astype(_np.float32), dtype=jnp.float32)


@register_op("pool2d")
def _pool2d(ctx, op):
    x = ctx.in_(op, "X")  # NCHW, or NHWC under layout_opt's data_format
    ptype = op.attr("pooling_type", "max")
    ksize = list(op.attr("ksize", [2, 2]))
    strides = list(op.attr("strides", ksize))
    paddings = op.attr("paddings", [0, 0])
    global_pool = op.attr("global_pooling", False)
    adaptive = op.attr("adaptive", False)
    exclusive = op.attr("exclusive", True)
    ceil_mode = op.attr("ceil_mode", False)
    nhwc = op.attr("data_format", "NCHW") == "NHWC"

    if global_pool or (adaptive and ksize == [1, 1]):
        red = jnp.max if ptype == "max" else jnp.mean
        ctx.out(op, "Out",
                red(x, axis=(1, 2) if nhwc else (2, 3), keepdims=True))
        return

    if adaptive and nhwc:
        # layout_opt never converts non-global adaptive pools (their
        # reshape/mask paths are written against NCHW) — reaching here
        # means a pass bug, not a user error
        raise ValueError(
            "pool2d: adaptive pooling has no NHWC lowering — layout_opt "
            "should not have converted this op")
    if adaptive:
        # adaptive pooling: output H,W = ksize. Even splits reshape;
        # uneven avg uses bin-membership masks (start=floor(i*H/oh),
        # end=ceil((i+1)*H/oh), the reference's AdaptiveStartIndex/
        # EndIndex windows) via one einsum; uneven max is rejected with
        # a clear error (variable windows don't map to reduce_window)
        n, c, h, w = x.shape
        oh, ow = ksize
        if h % oh == 0 and w % ow == 0:
            x_ = x.reshape(n, c, oh, h // oh, ow, w // ow)
            red = jnp.max if ptype == "max" else jnp.mean
            ctx.out(op, "Out", red(x_, axis=(3, 5)))
            return
        if ptype == "max":
            raise ValueError(
                f"adaptive max pool needs output sizes dividing the "
                f"input ({oh}x{ow} vs {h}x{w}); use avg, or an even "
                "split")
        row_m = _adaptive_mask(h, oh)  # [oh, H]
        col_m = _adaptive_mask(w, ow)
        sums = jnp.einsum("ih,jw,nchw->ncij", row_m, col_m,
                          x.astype(jnp.float32))
        cnt = jnp.einsum("ih,jw->ij", row_m, col_m)
        ctx.out(op, "Out", (sums / cnt).astype(x.dtype))
        return

    pads = _conv_padding(paddings, 2)
    # windowed pooling computes channel-LAST (pairs with the NHWC convs;
    # XLA cancels the boundary transposes; under layout_opt's NHWC IR
    # there is nothing to cancel)
    xi = x if nhwc else jnp.transpose(x, (0, 2, 3, 1))
    if isinstance(pads, str):
        pad_cfg = pads
    else:
        pad_cfg = [(0, 0)] + list(pads) + [(0, 0)]
        if ceil_mode:
            strides_n = [1] + strides + [1]
            pad_cfg = [
                (lo, hi + s - 1) if 1 <= i <= 2 else (lo, hi)
                for i, ((lo, hi), s) in enumerate(
                    zip(pad_cfg, strides_n)
                )
            ]
    window = (1,) + tuple(ksize) + (1,)
    strides4 = (1,) + tuple(strides) + (1,)
    if ptype == "max":
        init = -jnp.inf
        out = jax.lax.reduce_window(
            xi, init, jax.lax.max, window, strides4,
            pad_cfg if isinstance(pad_cfg, str) else pad_cfg,
        )
    else:
        summed = jax.lax.reduce_window(
            xi, 0.0, jax.lax.add, window, strides4,
            pad_cfg if isinstance(pad_cfg, str) else pad_cfg,
        )
        if exclusive and (isinstance(pad_cfg, str) or any(p != (0, 0) for p in pad_cfg[1:3])):
            ones = jnp.ones_like(xi)
            counts = jax.lax.reduce_window(
                ones, 0.0, jax.lax.add, window, strides4,
                pad_cfg if isinstance(pad_cfg, str) else pad_cfg,
            )
            out = summed / counts
        else:
            out = summed / float(np.prod(ksize))
    ctx.out(op, "Out", out if nhwc else jnp.transpose(out, (0, 3, 1, 2)))


# ---------------------------------------------------------------------------
# normalisation
# ---------------------------------------------------------------------------


def _batch_norm_grad_maker(op, grad_out_names, block, helpers):
    # explicit grad: recompute the normalized value from (bf16) X and the
    # tiny SavedMean/SavedVariance instead of letting auto-vjp keep fp32
    # activation residuals across fwd->bwd (the LN finding applied to BN:
    # f32 copies of every conv activation cost ~2x HBM on ResNet)
    if grad_out_names.get("Y", [None])[0] is None:
        return None
    for stats_slot in ("MeanOut", "VarianceOut", "SavedMean",
                       "SavedVariance"):
        if grad_out_names.get(stats_slot, [None])[0] is not None:
            return None  # cotangents into the stats outputs: defer to vjp
    if op.attr("is_test", False) or op.attr("use_global_stats", False):
        return None  # eval-mode grads: defer to the generic vjp
    inputs = {
        "X": op.input("X"),
        "Scale": op.input("Scale"),
        "SavedMean": [op.output("SavedMean")[0]],
        "SavedVariance": [op.output("SavedVariance")[0]],
        "GRAD_Y": [grad_out_names["Y"][0]],
    }
    outputs = {
        "IGRAD_X": [helpers.grad_name(op.input("X")[0])],
        "IGRAD_Scale": [helpers.grad_name(op.input("Scale")[0])],
        "IGRAD_Bias": [helpers.grad_name(op.input("Bias")[0])],
    }
    return [
        {
            "type": "batch_norm_grad",
            "inputs": inputs,
            "outputs": outputs,
            "attrs": {
                "epsilon": op.attr("epsilon", 1e-5),
                "data_layout": op.attr("data_layout", "NCHW"),
            },
        }
    ]


@register_op("batch_norm_grad", differentiable=False)
def _batch_norm_grad(ctx, op):
    """Training-mode BN backward from saved batch stats (reference:
    batch_norm_op.cc grad): dx = (gamma*inv/M) * (M*dy - sum(dy)
    - xhat * sum(dy*xhat))."""
    x = ctx.in_(op, "X")
    scale = ctx.in_(op, "Scale")
    mean = ctx.in_(op, "SavedMean")
    inv = ctx.in_(op, "SavedVariance")  # 1/sqrt(var+eps), saved by fwd
    dy = ctx.in_(op, "GRAD_Y")
    layout = op.attr("data_layout", "NCHW")
    # canonicalize to channel-LAST once; identity perm for NHWC inputs
    if layout == "NCHW" and x.ndim > 2:
        perm = (0,) + tuple(range(2, x.ndim)) + (1,)
        inv_perm = (0, x.ndim - 1) + tuple(range(1, x.ndim - 1))
    else:
        perm = inv_perm = tuple(range(x.ndim))
    xi = jnp.transpose(x, perm)
    dyi = jnp.transpose(dy, perm)
    axes = tuple(range(xi.ndim - 1))
    m = 1
    for a in axes:
        m *= xi.shape[a]
    xf = xi.astype(jnp.float32)
    dyf = dyi.astype(jnp.float32)
    # dgamma via raw sums (one fused pass): sum(dy*xhat) =
    # inv*(sum(dy*x) - mean*sum(dy))
    dbeta = jnp.sum(dyf, axis=axes)
    dxy = jnp.sum(dyf * xf, axis=axes)
    dgamma = inv * (dxy - mean * dbeta)
    xhat = (xf - mean) * inv
    dx = (scale * inv / m) * (m * dyf - dbeta - xhat * dgamma)
    dx = jnp.transpose(dx.astype(x.dtype), inv_perm)
    ctx.out(op, "IGRAD_X", dx)
    if op.output("IGRAD_Scale"):
        ctx.out(op, "IGRAD_Scale", dgamma)
    if op.output("IGRAD_Bias"):
        ctx.out(op, "IGRAD_Bias", dbeta)


@register_op(
    "batch_norm",
    stateful_outputs=("MeanOut", "VarianceOut", "SavedMean", "SavedVariance"),
    no_grad_inputs=("Mean", "Variance"),
    grad=_batch_norm_grad_maker,
)
def _batch_norm(ctx, op):
    """reference: operators/batch_norm_op.cc. Train mode computes batch stats
    and updates the running stats vars (MeanOut/VarianceOut alias the same var
    names as Mean/Variance inputs, captured as functional state)."""
    x = ctx.in_(op, "X")
    scale = ctx.in_(op, "Scale")
    bias = ctx.in_(op, "Bias")
    mean = ctx.in_(op, "Mean")
    var = ctx.in_(op, "Variance")
    eps = op.attr("epsilon", 1e-5)
    momentum = op.attr("momentum", 0.9)
    is_test = op.attr("is_test", False) or ctx.is_test
    layout = op.attr("data_layout", "NCHW")
    use_global = op.attr("use_global_stats", False) or is_test

    # compute channel-LAST internally (the TPU-native layout: per-channel
    # stats/affine ride the lanes; XLA cancels the transposes against the
    # NHWC-internal convs around this op)
    nchw4 = layout == "NCHW" and x.ndim == 4
    xi = jnp.transpose(x, (0, 2, 3, 1)) if nchw4 else x
    ch_axis = xi.ndim - 1 if (nchw4 or layout != "NCHW") else 1
    axes = tuple(i for i in range(xi.ndim) if i != ch_axis)
    bshape = [1] * xi.ndim
    bshape[ch_axis] = xi.shape[ch_axis]

    if use_global:
        use_mean, use_var = mean, var
    else:
        # ONE pass for both stats: jnp.var would chain a second,
        # mean-dependent pass — on ResNet conv1's 822 MB fp32 view the
        # two-pass form cost ~30 ms/step of extra HBM traffic. The sums
        # are SHIFTED by the running mean (E[(x-rm)^2] - (E[x]-rm)^2) so
        # the classic E[x^2]-E[x]^2 fp32 cancellation cannot blow up:
        # the error scales with |batch_mean - running_mean|/std, tiny in
        # steady state (and rm=0 at init reduces to the raw form).
        xf = xi.astype(jnp.float32)
        m_count = 1
        for a in axes:
            m_count *= xi.shape[a]
        rm = jax.lax.stop_gradient(mean.astype(jnp.float32))
        d = xf - rm
        s1 = jnp.sum(d, axis=axes) / m_count
        s2 = jnp.sum(jnp.square(d), axis=axes) / m_count
        # under the unified mesh the whole-graph jit always sees the
        # GLOBAL batch (GSPMD shards the reduction itself), so no manual
        # cross-replica averaging is needed — the legacy shard-map
        # pipeline was the only path that saw per-device shards here
        use_mean = rm + s1
        use_var = jnp.maximum(s2 - jnp.square(s1), 0.0)
        new_mean = momentum * mean + (1 - momentum) * use_mean
        new_var = momentum * var + (1 - momentum) * use_var
        ctx.out(op, "MeanOut", new_mean)
        ctx.out(op, "VarianceOut", new_var)
        ctx.out(op, "SavedMean", use_mean)
        ctx.out(op, "SavedVariance", 1.0 / jnp.sqrt(use_var + eps))

    inv = jax.lax.rsqrt(use_var.reshape(bshape) + eps)
    y = (
        xi.astype(jnp.float32) - use_mean.reshape(bshape)
    ) * inv * scale.reshape(bshape) + bias.reshape(bshape)
    y = y.astype(x.dtype)
    if nchw4:
        y = jnp.transpose(y, (0, 3, 1, 2))
    ctx.out(op, "Y", y)


def _layer_norm_grad_maker(op, grad_out_names, block, helpers):
    # explicit grad op so the backward recomputes the normalized value
    # from the (bf16) X and the tiny saved Mean/Variance: the auto-vjp
    # path saved jax.vjp's fp32-upcast residual — ~100 MB per LN site on
    # BERT-base b=256, ~17 ms/step of pure HBM traffic
    if grad_out_names.get("Y", [None])[0] is None:
        return None  # only Mean/Variance differentiated: defer to vjp
    if (grad_out_names.get("Mean", [None])[0] is not None
            or grad_out_names.get("Variance", [None])[0] is not None):
        return None  # cotangents into the stats outputs: defer to vjp
    inputs = {
        "X": op.input("X"),
        "Mean": [op.output("Mean")[0]],
        "Variance": [op.output("Variance")[0]],
        "GRAD_Y": [grad_out_names["Y"][0]],
    }
    outputs = {"IGRAD_X": [helpers.grad_name(op.input("X")[0])]}
    if op.input("Scale"):
        inputs["Scale"] = op.input("Scale")
        outputs["IGRAD_Scale"] = [helpers.grad_name(op.input("Scale")[0])]
    if op.input("Bias"):
        outputs["IGRAD_Bias"] = [helpers.grad_name(op.input("Bias")[0])]
    return [
        {
            "type": "layer_norm_grad",
            "inputs": inputs,
            "outputs": outputs,
            "attrs": {
                "epsilon": op.attr("epsilon", 1e-5),
                "begin_norm_axis": op.attr("begin_norm_axis", 1),
            },
        }
    ]


@register_op("layer_norm", grad=_layer_norm_grad_maker)
def _layer_norm(ctx, op):
    """reference: operators/layer_norm_op.cc."""
    x = ctx.in_(op, "X")
    eps = op.attr("epsilon", 1e-5)
    begin = op.attr("begin_norm_axis", 1)
    lead = x.shape[:begin]
    n = int(np.prod(lead or (1,)))
    scale = ctx.in_(op, "Scale")
    bias = ctx.in_(op, "Bias")
    # NOTE: the forward deliberately stays plain XLA — it fuses into the
    # surrounding residual-add/matmul chain; a Pallas forward (tried)
    # forces materialization boundaries and LOSES ~13 ms/step on
    # BERT-base. Only the backward uses the fused kernel (see
    # _layer_norm_grad / ops/pallas/layer_norm.py).
    x2 = x.reshape((n, -1)).astype(jnp.float32)
    mean = jnp.mean(x2, axis=1, keepdims=True)
    var = jnp.var(x2, axis=1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    y = (x2 - mean) * inv
    if scale is not None:
        y = y * scale.reshape((1, -1)).astype(jnp.float32)
    if bias is not None:
        y = y + bias.reshape((1, -1)).astype(jnp.float32)
    ctx.out(op, "Y", y.reshape(x.shape).astype(x.dtype))
    ctx.out(op, "Mean", mean.reshape(lead))
    ctx.out(op, "Variance", var.reshape(lead))


def _rms_norm_grad_maker(op, grad_out_names, block, helpers):
    # an explicit grad op, as layer_norm's: its lowering can then take one
    # kernel pass (ops/pallas/layer_norm.py::rms_bwd) where the generic
    # vjp leaves XLA a pass of its own a norm at a quarter of HBM
    if grad_out_names.get("Y", [None])[0] is None:
        return None  # nothing flows into Y: defer to vjp
    return [
        {
            "type": "rms_norm_grad",
            "inputs": {
                "X": op.input("X"),
                "Scale": op.input("Scale"),
                "GRAD_Y": [grad_out_names["Y"][0]],
            },
            "outputs": {
                "IGRAD_X": [helpers.grad_name(op.input("X")[0])],
                "IGRAD_Scale": [helpers.grad_name(op.input("Scale")[0])],
            },
            "attrs": {
                "epsilon": op.attr("epsilon", 1e-5),
                "begin_norm_axis": op.attr("begin_norm_axis", 1),
            },
        }
    ]


@register_op("rms_norm", grad=_rms_norm_grad_maker)
def _rms_norm(ctx, op):
    """`y = x / sqrt(mean(x^2) + epsilon) * scale` over the axes from
    `begin_norm_axis` on (Zhang and Sennrich 2019, arXiv:1910.07467): no
    mean is subtracted and there is no shift. The statistics and the
    product with the scale are float32; Y has X's dtype. The forward stays
    XLA's, which fuses it into its consumer (`_layer_norm`'s note)."""
    ctx.out(op, "Y", rms_norm(
        ctx.in_(op, "X"), ctx.in_(op, "Scale"), op.attr("epsilon", 1e-5),
        op.attr("begin_norm_axis", 1)))


def rms_norm(x, scale, epsilon, begin):
    """The op `rms_norm` on arrays: over the axes from `begin` on."""
    axes = tuple(range(begin, x.ndim))
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(
        jnp.mean(xf * xf, axis=axes, keepdims=True) + epsilon)
    y = xf * inv * scale.reshape(x.shape[begin:]).astype(jnp.float32)
    return y.astype(x.dtype)


def yarn_frequencies(d, theta, scaling):
    """YaRN's blended frequencies (Peng et al. 2023, arXiv:2309.00071, as
    the public `transformers` code computes them) and the factor on cos
    and sin. `scaling` is (factor, original_max_position_embeddings,
    beta_fast, beta_slow, attention_factor). With `e_i = theta^(-2i/d)`
    and `c(r) = d ln(original / (2 pi r)) / (2 ln theta)`, the index whose
    wave turns `r` times over the original context: below
    `low = floor(c(beta_fast))` the frequencies stay `e_i`, above
    `high = ceil(c(beta_slow))` they are `e_i / factor`, and between the
    two they blend linearly in `i`. Returns ([d/2] float32, factor)."""
    factor, original, beta_fast, beta_slow, attention_factor = scaling

    def turns(r):
        return d * math.log(original / (r * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(turns(beta_fast)), 0)
    high = min(math.ceil(turns(beta_slow)), d - 1)
    if low == high:
        high += 0.001  # the published guard against a ramp of no width
    extrapolated = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ramp = jnp.clip(
        (jnp.arange(d // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    freq = extrapolated / factor * ramp + extrapolated * (1 - ramp)
    return freq, attention_factor


def rotary_tables(s, d, theta, scaling=None, interleaved=False):
    """`cos a` and the signed `sin a` of `rotate_half`, [s, d] float32:
    `y = x * cos + roll(x, d/2) * sin` along the last axis. `scaling`:
    None, or YaRN's five numbers (`yarn_frequencies`), which blend the
    frequencies and scale both tables. `interleaved`: the tables of
    `rotate_pairs`, angle `a_i` on lanes 2i and 2i+1 and the sign on the
    even lane's sine."""
    # the published form, 1 / theta^(2i/d) in float32: another way round
    # the power differs by an ulp, which position 8,191 makes 4e-4 rad
    if scaling is None:
        freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    else:
        freq, factor = yarn_frequencies(d, theta, scaling)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    if interleaved:
        cos = jnp.repeat(jnp.cos(angle), 2, -1)
        sin = jnp.stack([-jnp.sin(angle), jnp.sin(angle)], -1).reshape(s, d)
    else:
        cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)
        # the half-turn's sign rides the sine: rotate_half(x) = [-x2, x1]
        sin = jnp.concatenate([-jnp.sin(angle), jnp.sin(angle)], -1)
    if scaling is not None:
        cos, sin = cos * factor, sin * factor
    return cos, sin


def partial_rotary_tables(s, d, rotary_dim, theta, scaling=None):
    """`rotary_tables` of a head of `d` lanes whose first `rotary_dim`
    turn, in the rotate-half convention within them, and whose other
    lanes pass (`partial_rotary_factor` in published configs): three
    [s, d] float32 tables for
    `y = x * cos + roll(x, d - r/2) * sin_lower + roll(x, r/2) * sin_upper`,
    r = `rotary_dim`. Lane i < r/2 reads lane i + r/2 (a roll by d - r/2)
    with `-sin a_i`, lane r/2 <= i < r reads lane i - r/2 with `sin
    a_(i-r/2)`; `cos` is 1 and both sines 0 from lane r on. With the whole
    head the two rolls are one and `sin_lower + sin_upper` is
    `rotary_tables`' sine."""
    r = rotary_dim
    cos, sin = rotary_tables(s, r, theta, scaling)
    rest = jnp.zeros((s, d - r), jnp.float32)
    half = jnp.zeros((s, r // 2), jnp.float32)
    return (jnp.concatenate([cos, rest + 1.0], -1),
            jnp.concatenate([sin[:, :r // 2], half, rest], -1),
            jnp.concatenate([half, sin[:, r // 2:], rest], -1))


def rotate_half(x, theta, scaling=None, rotary_dim=None):
    """Rotary positions on [b, s, h, d], positions 0..s-1, the rotate-half
    convention (Su et al. 2021, arXiv:2104.09864, as the public
    `transformers` code lays it out): with `a_i = p * theta^(-2i/d)` for
    i < d/2, `y[..., i] = x[..., i] cos a_i - x[..., i + d/2] sin a_i` and
    `y[..., i + d/2] = x[..., i + d/2] cos a_i + x[..., i] sin a_i`; with
    `scaling`, `rotary_tables`' scaled angles and factor. `rotary_dim`
    fewer than d: the first `rotary_dim` lanes turn as a head of that
    width and the rest pass as they came.
    float32 inside whatever x arrives in: a bf16 angle at position 8,191
    is off by whole turns."""
    if rotary_dim and rotary_dim != x.shape[3]:
        return jnp.concatenate(
            [rotate_half(x[..., :rotary_dim], theta, scaling),
             x[..., rotary_dim:]], -1)
    s, d = x.shape[1], x.shape[3]
    cos, sin = (t[None, :, None, :]
                for t in rotary_tables(s, d, theta, scaling))
    xf = x.astype(jnp.float32)
    return (xf * cos + jnp.roll(xf, d // 2, axis=-1) * sin).astype(x.dtype)


def rotate_pairs(x, theta, scaling=None):
    """`rotate_half`'s positions with the lanes paired as the paper pairs
    them (`rope_interleave` in the DeepSeek-V3 family's configs): lanes
    2i and 2i+1 are one plane, `y[..., 2i] = x[..., 2i] cos a_i -
    x[..., 2i+1] sin a_i` and `y[..., 2i+1] = x[..., 2i+1] cos a_i +
    x[..., 2i] sin a_i`, the output in the input's lane order. float32
    inside."""
    s, d = x.shape[1], x.shape[3]
    cos, sin = (t[None, :, None, :]
                for t in rotary_tables(s, d, theta, scaling, interleaved=True))
    xf = x.astype(jnp.float32)
    swapped = xf.reshape(*xf.shape[:-1], d // 2, 2)[..., ::-1].reshape(xf.shape)
    return (xf * cos + swapped * sin).astype(x.dtype)


def rope_scaling_attr(op, name):
    """An op's YaRN attribute as `rotary_tables` takes it: None where the
    attribute is absent or empty."""
    scaling = op.attr(name, None)
    return tuple(float(v) for v in scaling) if scaling else None


@register_op("rotary_embedding")
def _rotary_embedding(ctx, op):
    """X: [b, s, heads, d], d even; attrs `theta` and, optionally,
    `scaling` (YaRN's five numbers) and `interleaved` (pairs of
    neighbouring lanes, `rotate_pairs`; absent: `rotate_half`). Out has
    X's shape and dtype."""
    x = ctx.in_(op, "X")
    if x.ndim != 4 or x.shape[3] % 2:
        raise ValueError(
            f"rotary_embedding: X {x.shape}: expected [b, s, heads, d], d even")
    interleaved = bool(op.attr("interleaved", False))
    if interleaved:
        profiler.bump_counter("rope_interleaved")
    ctx.out(op, "Out", (rotate_pairs if interleaved else rotate_half)(
        x, float(op.attr("theta", 10000.0)), rope_scaling_attr(op, "scaling")))


def _norm_kernel_admitted(ctx, x, n, k, viable) -> bool:
    """Whether a norm's gradient over x as [n, k] takes its Pallas kernel:
    the rule of fused_multihead_attention. A Pallas custom call is
    something GSPMD cannot partition, so a mesh of several devices keeps
    the XLA formulation, which shards by propagation, unless it shards
    the batch alone: there the rows are batch-major, each chip's are a
    whole problem, and the kernel runs per shard, of which `viable` (the
    kernel's shape rule) is asked. Counts a lowering that runs per shard
    (`pallas_on_mesh_calls`)."""
    from .pallas import on_mesh
    from .pallas.flash_attention import _use_pallas

    shards = on_mesh.batch_shards(ctx.mesh, x.shape[0])
    if not (shards and viable(n // shards, k) and _use_pallas()):
        return False
    if shards > 1:
        profiler.bump_counter("pallas_on_mesh_calls")
    return True


@register_op("rms_norm_grad", differentiable=False)
def _rms_norm_grad(ctx, op):
    """dX in X's dtype and dScale in Scale's from X, Scale and dY, float32
    inside; nothing was saved by the forward. One pass of `rms_bwd` where
    `_layer_norm_grad`'s mesh rule and `rms_bwd_viable` admit the shape (a
    decoder's block norms at the widths the kernel won at); everywhere
    else `jax.vjp` of `rms_norm`, which is what the generic grad op
    lowered before this one existed (the norms over one head's lanes).
    AMP's lists read neither this op nor `rms_norm`: both are float32
    inside whatever X arrives in."""
    x = ctx.in_(op, "X")
    scale = ctx.in_(op, "Scale")
    eps = op.attr("epsilon", 1e-5)
    begin = op.attr("begin_norm_axis", 1)
    dy = jnp.asarray(ctx.in_(op, "GRAD_Y"), dtype=x.dtype).reshape(x.shape)
    n = int(np.prod(x.shape[:begin] or (1,)))
    k = int(np.prod(x.shape[begin:]))
    from .pallas.layer_norm import rms_bwd, rms_bwd_viable

    if _norm_kernel_admitted(ctx, x, n, k, rms_bwd_viable):
        profiler.bump_counter("rms_bwd_calls")
        dx, dscale = rms_bwd(x.reshape(n, k), dy.reshape(n, k),
                             scale.reshape(-1), eps, mesh=ctx.mesh)
        dx = dx.reshape(x.shape)
        dscale = dscale.reshape(scale.shape).astype(scale.dtype)
    else:
        _, pullback = jax.vjp(
            lambda x, scale: rms_norm(x, scale, eps, begin), x, scale)
        dx, dscale = pullback(dy)
    ctx.out(op, "IGRAD_X", dx)
    ctx.out(op, "IGRAD_Scale", dscale)


@register_op("layer_norm_grad", differentiable=False)
def _layer_norm_grad(ctx, op):
    """dX, dScale, dBias from the saved per-row stats; the normalized
    value is recomputed from X (bf16 read) instead of a saved fp32
    residual. dBias rides the MXU (ones-vector contraction) — a VPU
    sublane-dim reduce reads the same bytes at a fraction of the rate."""
    x = ctx.in_(op, "X")
    dy = ctx.in_(op, "GRAD_Y")
    mean = ctx.in_(op, "Mean")
    var = ctx.in_(op, "Variance")
    scale = ctx.in_(op, "Scale")
    eps = op.attr("epsilon", 1e-5)
    begin = op.attr("begin_norm_axis", 1)
    n = int(np.prod(x.shape[:begin] or (1,)))
    k = int(np.prod(x.shape[begin:]))
    from .pallas.layer_norm import ln_bwd, ln_bwd_viable

    if _norm_kernel_admitted(ctx, x, n, k, ln_bwd_viable):
        rstd = jax.lax.rsqrt(var.reshape(-1).astype(jnp.float32) + eps)
        sc = (scale if scale is not None
              else jnp.ones((k,), jnp.float32)).reshape(-1)
        dx, dscale, dbias = ln_bwd(
            x.reshape(n, k), dy.reshape(n, k),
            mean.reshape(-1).astype(jnp.float32), rstd, sc,
            mesh=ctx.mesh,
        )
        ctx.out(op, "IGRAD_X", dx.reshape(x.shape))
        if scale is not None and op.output("IGRAD_Scale"):
            ctx.out(op, "IGRAD_Scale", dscale)
        if op.output("IGRAD_Bias"):
            ctx.out(op, "IGRAD_Bias", dbias)
        return
    x2 = x.reshape(n, k).astype(jnp.float32)
    dy2 = dy.reshape(n, k).astype(jnp.float32)
    inv = jax.lax.rsqrt(var.reshape(n, 1) + eps)
    nrm = (x2 - mean.reshape(n, 1)) * inv
    dyg = dy2
    if scale is not None:
        dyg = dy2 * scale.reshape(1, k).astype(jnp.float32)
    m1 = jnp.mean(dyg, axis=1, keepdims=True)
    m2 = jnp.mean(dyg * nrm, axis=1, keepdims=True)
    dx = (inv * (dyg - m1 - nrm * m2)).astype(x.dtype)
    ctx.out(op, "IGRAD_X", dx.reshape(x.shape))
    if scale is not None and op.output("IGRAD_Scale"):
        if x.dtype == jnp.bfloat16:
            # AMP path: materialize the shared normalized tensor in bf16
            # (f32 doubles the HBM round-trip; the reduce still
            # accumulates f32). Pure-fp32 models keep exact products.
            dscale = jnp.sum(
                dy2.astype(jnp.bfloat16) * nrm.astype(jnp.bfloat16),
                axis=0, dtype=jnp.float32,
            )
        else:
            dscale = jnp.sum(dy2 * nrm, axis=0, dtype=jnp.float32)
        ctx.out(op, "IGRAD_Scale", dscale)
    if op.output("IGRAD_Bias"):
        ones = jnp.ones((n,), dy.dtype)
        db = jax.lax.dot_general(
            ones, dy.reshape(n, k), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ctx.out(op, "IGRAD_Bias", db)


@register_op("group_norm")
def _group_norm(ctx, op):
    x = ctx.in_(op, "X")  # NCHW
    groups = op.attr("groups", 32)
    eps = op.attr("epsilon", 1e-5)
    n, c = x.shape[:2]
    xg = x.reshape((n, groups, c // groups) + x.shape[2:])
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    y = ((xg - mean) * jax.lax.rsqrt(var + eps)).reshape(x.shape)
    scale = ctx.in_(op, "Scale")
    bias = ctx.in_(op, "Bias")
    bshape = (1, c) + (1,) * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(bshape)
    if bias is not None:
        y = y + bias.reshape(bshape)
    ctx.out(op, "Y", y)
    ctx.out(op, "Mean", mean.reshape(n, groups))
    ctx.out(op, "Variance", var.reshape(n, groups))


@register_op("instance_norm")
def _instance_norm(ctx, op):
    x = ctx.in_(op, "X")
    eps = op.attr("epsilon", 1e-5)
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    scale = ctx.in_(op, "Scale")
    bias = ctx.in_(op, "Bias")
    if scale is not None:
        bshape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
        y = y * scale.reshape(bshape) + bias.reshape(bshape)
    ctx.out(op, "Y", y)


@register_op("l2_normalize")
def _l2_normalize(ctx, op):
    x = ctx.in_(op, "X")
    axis = op.attr("axis", -1)
    eps = op.attr("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)
    ctx.out(op, "Out", x / norm)
    ctx.out(op, "Norm", norm)


# ---------------------------------------------------------------------------
# dropout — custom grad via saved mask (reference: operators/dropout_op.cc)
# ---------------------------------------------------------------------------


def _dropout_grad_maker(op, grad_out_names, block, helpers):
    if grad_out_names.get("Out", [None])[0] is None:
        return None
    # dx = dy * mask (scaled per implementation). The mask is REGENERATED
    # in the backward from the same per-variable rng (rng_for keyed on the
    # Out name) instead of loading the saved Mask output: storing ~1 GB of
    # uint8 masks across fwd->bwd on BERT-base b=256 cost more in HBM
    # pressure than the ~5-op hash regen (reference keeps the mask,
    # operators/dropout_op.cc — a GPU-appropriate choice, not a TPU one).
    return [
        {
            "type": "dropout_grad",
            "inputs": {
                "GRAD_Out": [grad_out_names["Out"][0]],
            },
            "outputs": {"IGRAD_X": [helpers.grad_name(op.input("X")[0])]},
            "attrs": {
                "dropout_prob": op.attr("dropout_prob", 0.5),
                "dropout_implementation": op.attr(
                    "dropout_implementation", "downgrade_in_infer"
                ),
                "rng_name": op.output("Out")[0],
            },
        }
    ]


def _drop_threshold(p):
    """uint32 threshold of the hash mask (2^-32 granularity)."""
    return min(int(round(p * 2.0**32)), 2**32 - 1)


def _quantized_keep_prob(p):
    """Effective keep probability of the hash mask — must stay
    bit-identical between forward and grad."""
    return 1.0 - _drop_threshold(p) / 2.0**32


def _murmur_mix(h):
    """murmur3 finalizer — full avalanche on a uint32 lane."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def _dropout_keep_mask(rng, p, shape):
    """Keep-mask from a murmur-mixed counter hash (the same generator the
    Pallas attention kernels regenerate in-kernel): one uint32 word per
    ELEMENT, compared against round(p * 2^32). ~6 VPU ops per element vs
    threefry's 20 rounds, and — unlike jax.random.bits inside a large
    program — the whole chain (iota -> hash -> compare) fuses into the
    consuming select, so no mask bytes ever hit HBM. An earlier variant
    packed 4 uint8 lanes per word to quarter the hash work; the
    bitcast/reshape it needed materialized full-size u32 tensors instead
    of fusing (~38 ms/step of copies on BERT-base b=256) — packing LOST.
    Returns (keep_bool, effective_keep_prob)."""
    thresh = _drop_threshold(p)
    keep_prob = _quantized_keep_prob(p)
    kd = jnp.asarray(jax.random.key_data(rng), jnp.uint32).reshape(-1)
    seed = _murmur_mix(kd[0] * jnp.uint32(0x9E3779B1) ^ kd[-1])
    n = 1
    for d in shape:
        n *= int(d)
    i = jax.lax.iota(jnp.uint32, n).reshape(shape)
    words = _murmur_mix(i * jnp.uint32(0x9E3779B1) ^ seed)
    keep = words >= jnp.uint32(thresh)
    return keep, keep_prob


@register_op("dropout", grad=_dropout_grad_maker)
def _dropout(ctx, op):
    x = ctx.in_(op, "X")
    p = op.attr("dropout_prob", 0.5)
    is_test = op.attr("is_test", False) or ctx.is_test
    impl = op.attr("dropout_implementation", "downgrade_in_infer")
    if is_test or p == 0.0:
        # test mode: upscale_in_train -> identity; downgrade_in_infer -> x*(1-p)
        out = x if impl == "upscale_in_train" or p == 0.0 else x * (1.0 - p)
        ctx.out(op, "Out", out)
        ctx.out(op, "Mask", jnp.ones_like(x, dtype=jnp.uint8))
        return
    keep, keep_prob = _dropout_keep_mask(
        ctx.rng_for(op.output("Out")[0]), p, x.shape
    )
    if impl == "upscale_in_train":
        out = jnp.where(keep, x * (1.0 / keep_prob), 0.0).astype(x.dtype)
    else:
        out = jnp.where(keep, x, 0.0).astype(x.dtype)
    ctx.out(op, "Out", out)
    ctx.out(op, "Mask", keep.astype(jnp.uint8))


@register_op("dropout_grad", differentiable=False)
def _dropout_grad(ctx, op):
    dy = ctx.in_(op, "GRAD_Out")
    p = op.attr("dropout_prob", 0.5)
    impl = op.attr("dropout_implementation", "downgrade_in_infer")
    keep_prob = _quantized_keep_prob(p)
    rng_name = op.attr("rng_name")
    if rng_name is not None:
        # regenerate the forward's mask bit-identically from the shared rng
        keep, keep_prob = _dropout_keep_mask(
            ctx.rng_for(rng_name), p, dy.shape
        )
    else:
        # program serialized before mask regeneration existed: use the
        # stored Mask input
        mask = ctx.in_(op, "Mask")
        if mask is None:
            raise ValueError(
                "dropout_grad needs either an 'rng_name' attr or a saved "
                "'Mask' input; this op has neither"
            )
        keep = mask.astype(jnp.bool_)
    scale = 1.0 / keep_prob if impl == "upscale_in_train" else 1.0
    dx = jnp.where(keep, dy * scale if scale != 1.0 else dy, 0.0)
    ctx.out(op, "IGRAD_X", dx.astype(dy.dtype))


# ---------------------------------------------------------------------------
# softmax & losses
# ---------------------------------------------------------------------------


def _softmax_grad_maker(op, grad_out_names, block, helpers):
    # dX = (dY - sum(dY * Y, axis)) * Y from the op's OWN output: the
    # auto-vjp instead saves the f32 softmax interior as a residual
    # (e.g. [256,12,128,128] f32 = 603 MB/layer on unfused BERT
    # attention) — the same f32-residual lever as BN/LN/attention/xent
    if grad_out_names.get("Out", [None])[0] is None:
        return None
    return [
        {
            "type": "softmax_grad",
            "inputs": {
                "Out": [op.output("Out")[0]],
                "GRAD_Out": [grad_out_names["Out"][0]],
            },
            "outputs": {
                "IGRAD_X": [helpers.grad_name(op.input("X")[0])],
            },
            "attrs": {"axis": op.attr("axis", -1)},
        }
    ]


@register_op("softmax_grad")  # differentiable: double-grad via auto-vjp
def _softmax_grad(ctx, op):
    """reference: softmax_op.cc grad kernel (dX = (dY - dot(dY, Y)) * Y)."""
    y = ctx.in_(op, "Out")
    dy = ctx.in_(op, "GRAD_Out")
    axis = op.attr("axis", -1)
    yf = y.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    dx = (dyf - jnp.sum(dyf * yf, axis=axis, keepdims=True)) * yf
    ctx.out(op, "IGRAD_X", dx.astype(y.dtype))


@register_op("softmax", grad=_softmax_grad_maker)
def _softmax(ctx, op):
    x = ctx.in_(op, "X")
    axis = op.attr("axis", -1)
    # numerics stay fp32 under bf16 AMP; result returns in input dtype
    out = jax.nn.softmax(x.astype(jnp.float32), axis=axis)
    ctx.out(op, "Out", out.astype(x.dtype))


@register_op("log_loss", no_grad_inputs=("Labels",))
def _log_loss(ctx, op):
    """reference: operators/log_loss_op.cc."""
    p = ctx.in_(op, "Predicted")
    y = ctx.in_(op, "Labels")
    eps = op.attr("epsilon", 1e-4)
    pf = p.astype(jnp.float32)
    yf = y.astype(jnp.float32)
    out = -yf * jnp.log(pf + eps) - (1.0 - yf) * jnp.log(1.0 - pf + eps)
    ctx.out(op, "Out", out.astype(p.dtype))


def _log_softmax_grad_maker(op, grad_out_names, block, helpers):
    # dX = dY - exp(Y) * sum(dY, axis), from the op's own output — same
    # f32-residual discipline as the softmax maker above
    if grad_out_names.get("Out", [None])[0] is None:
        return None
    return [
        {
            "type": "log_softmax_grad",
            "inputs": {
                "Out": [op.output("Out")[0]],
                "GRAD_Out": [grad_out_names["Out"][0]],
            },
            "outputs": {
                "IGRAD_X": [helpers.grad_name(op.input("X")[0])],
            },
            "attrs": {"axis": op.attr("axis", -1)},
        }
    ]


@register_op("log_softmax_grad")
def _log_softmax_grad(ctx, op):
    """reference: log_softmax_op.cc grad kernel."""
    y = ctx.in_(op, "Out")
    dy = ctx.in_(op, "GRAD_Out")
    axis = op.attr("axis", -1)
    yf = y.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    dx = dyf - jnp.exp(yf) * jnp.sum(dyf, axis=axis, keepdims=True)
    ctx.out(op, "IGRAD_X", dx.astype(y.dtype))


@register_op("log_softmax", grad=_log_softmax_grad_maker)
def _log_softmax(ctx, op):
    x = ctx.in_(op, "X")
    axis = op.attr("axis", -1)
    out = jax.nn.log_softmax(x.astype(jnp.float32), axis=axis)
    ctx.out(op, "Out", out.astype(x.dtype))


def _swce_grad_maker(op, grad_out_names, block, helpers):
    # classic xent gradient from the op's OWN Softmax output:
    # dLogits = (p - onehot(label)) * dLoss. Without this maker the
    # auto-vjp saves log_softmax's f32 interior as a residual — at a
    # [256, 64, 30k] seq2seq head that is a ~2 GB f32 tensor written and
    # re-read across fwd->bwd, where the bf16 Softmax output (already
    # materialized as an op output) carries the same information
    if grad_out_names.get("Loss", [None])[0] is None:
        return None
    if grad_out_names.get("Softmax", [None])[0] is not None:
        return None  # cotangent into the Softmax output: defer to vjp
    return [
        {
            "type": "softmax_with_cross_entropy_grad",
            "inputs": {
                # recompute the softmax from the (bf16) LOGITS rather than
                # consuming the Softmax output: the traced Softmax value is
                # exp(logp_f32), so referencing it keeps the f32 log-probs
                # alive fwd->bwd — a [256,64,30k] head pins 2 GB f32 (seen
                # as the f32 convert/recompute fusions in the round-4
                # transformer xplane); referencing Logits pins the 1 GB
                # bf16 tensor instead and the f32 softmax interior streams
                # inside the one grad fusion (the BN/LN recompute lesson)
                "Logits": op.input("Logits"),
                "Label": op.input("Label"),
                "GRAD_Loss": [grad_out_names["Loss"][0]],
            },
            "outputs": {
                "IGRAD_Logits": [helpers.grad_name(op.input("Logits")[0])],
            },
            "attrs": {
                "soft_label": op.attr("soft_label", False),
                "ignore_index": op.attr("ignore_index", -100),
                "axis": op.attr("axis", -1),
            },
        }
    ]


@register_op("softmax_with_cross_entropy_grad", no_grad_inputs=("Label",))
def _softmax_with_cross_entropy_grad(ctx, op):
    """reference: softmax_with_cross_entropy_op.cc grad kernel (p
    recomputed from Logits — see the maker's residual note)."""
    logits = ctx.in_(op, "Logits")
    axis_attr = op.attr("axis", -1) % logits.ndim
    p = jax.nn.softmax(
        logits.astype(jnp.float32), axis=axis_attr
    ).astype(logits.dtype)
    label = ctx.in_(op, "Label")
    dloss = ctx.in_(op, "GRAD_Loss")
    soft_label = op.attr("soft_label", False)
    ignore_index = op.attr("ignore_index", -100)
    axis = op.attr("axis", -1) % p.ndim
    dl = dloss.astype(p.dtype)
    if soft_label:
        lf = label.astype(p.dtype)
        d = p * jnp.sum(lf, axis=axis, keepdims=True) - lf
        dx = d * dl
    else:
        lbl = label.astype(jnp.int32)
        lbl_idx = lbl.squeeze(axis) if lbl.ndim == p.ndim else lbl
        # one_hot = iota-compare: fuses into the subtract, no [.., V]
        # materialization
        onehot = jax.nn.one_hot(lbl_idx, p.shape[axis], axis=axis,
                                dtype=p.dtype)
        dx = (p - onehot) * dl
        if ignore_index >= 0:
            keep = jnp.expand_dims(lbl_idx != ignore_index, axis)
            dx = jnp.where(keep, dx, jnp.zeros((), p.dtype))
    ctx.out(op, "IGRAD_Logits", dx)


@register_op(
    "softmax_with_cross_entropy",
    no_grad_inputs=("Label",),
    stateful_outputs=(),
    grad=_swce_grad_maker,
)
def _softmax_with_cross_entropy(ctx, op):
    """reference: operators/softmax_with_cross_entropy_op.cc — outputs both
    Softmax and per-row Loss."""
    logits = ctx.in_(op, "Logits")
    label = ctx.in_(op, "Label")
    soft_label = op.attr("soft_label", False)
    ignore_index = op.attr("ignore_index", -100)
    axis = op.attr("axis", -1)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=axis)
    if soft_label:
        loss = -jnp.sum(label * logp, axis=axis, keepdims=True)
    else:
        lbl = label.astype(jnp.int32)
        squeeze_axis = axis % logits.ndim
        lbl_idx = lbl.squeeze(squeeze_axis) if lbl.ndim == logits.ndim else lbl
        picked = jnp.take_along_axis(
            logp, lbl_idx[..., None].astype(jnp.int32), axis=axis
        )
        loss = -picked
        if ignore_index >= 0:
            mask = (lbl_idx != ignore_index)[..., None]
            loss = jnp.where(mask, loss, 0.0)
    ctx.out(op, "Softmax", jnp.exp(logp).astype(logits.dtype))
    ctx.out(op, "Loss", loss.astype(logits.dtype))


@register_op("cross_entropy", no_grad_inputs=("Label",))
def _cross_entropy(ctx, op):
    """reference: operators/cross_entropy_op.cc — takes probabilities."""
    x = ctx.in_(op, "X")
    label = ctx.in_(op, "Label")
    soft_label = op.attr("soft_label", False)
    ignore_index = op.attr("ignore_index", -100)
    eps = 1e-12
    if soft_label:
        loss = -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        lbl = label.astype(jnp.int32)
        lbl_idx = lbl.squeeze(-1) if lbl.ndim == x.ndim else lbl
        picked = jnp.take_along_axis(x, lbl_idx[..., None], axis=-1)
        loss = -jnp.log(picked + eps)
        if ignore_index >= 0:
            loss = jnp.where((lbl_idx != ignore_index)[..., None], loss, 0.0)
    ctx.out(op, "Y", loss)


@register_op("sigmoid_cross_entropy_with_logits", no_grad_inputs=("Label",))
def _sigmoid_ce(ctx, op):
    x = ctx.in_(op, "X")
    label = ctx.in_(op, "Label")
    ignore_index = op.attr("ignore_index", -100)
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    if ignore_index >= 0:
        mask = label != ignore_index
        loss = jnp.where(mask, loss, 0.0)
        if op.attr("normalize", False):
            loss = loss / jnp.maximum(jnp.sum(mask), 1)
    ctx.out(op, "Out", loss)


@register_op("square_error_cost")
def _square_error_cost(ctx, op):
    x = ctx.in_(op, "X")
    y = ctx.in_(op, "Y")
    ctx.out(op, "Out", jnp.square(x - y))


@register_op("huber_loss")
def _huber_loss(ctx, op):
    x = ctx.in_(op, "X")
    y = ctx.in_(op, "Y")
    delta = op.attr("delta", 1.0)
    r = y - x
    ar = jnp.abs(r)
    loss = jnp.where(ar <= delta, 0.5 * r * r, delta * (ar - 0.5 * delta))
    ctx.out(op, "Out", loss)
    ctx.out(op, "Residual", r)


@register_op("smooth_l1_loss")
def _smooth_l1(ctx, op):
    x = ctx.in_(op, "X")
    y = ctx.in_(op, "Y")
    sigma = op.attr("sigma", 1.0)
    s2 = sigma * sigma
    d = x - y
    ad = jnp.abs(d)
    loss = jnp.where(ad < 1.0 / s2, 0.5 * d * d * s2, ad - 0.5 / s2)
    loss = jnp.sum(loss.reshape(loss.shape[0], -1), axis=1, keepdims=True)
    ctx.out(op, "Out", loss)
    ctx.out(op, "Diff", d)


@register_op("kldiv_loss", no_grad_inputs=("Target",))
def _kldiv_loss(ctx, op):
    x = ctx.in_(op, "X")
    target = ctx.in_(op, "Target")
    reduction = op.attr("reduction", "mean")
    loss = target * (jnp.log(jnp.maximum(target, 1e-12)) - x)
    if reduction == "mean":
        loss = jnp.mean(loss).reshape((1,))
    elif reduction == "sum":
        loss = jnp.sum(loss).reshape((1,))
    elif reduction == "batchmean":
        loss = (jnp.sum(loss) / x.shape[0]).reshape((1,))
    ctx.out(op, "Loss", loss)


# ---------------------------------------------------------------------------
# embedding (reference: operators/lookup_table_op.cc)
#
# The forward is `take(W, ids)` cast to the AMP dtype. Its gradient op is the
# generic `__auto_grad__`, `jax.vjp` of this lowering, on one of two paths
# that the lowering picks from what it can see (the tokens, the table's
# shape, the cotangent's dtype, the mesh, the table's stated sharding):
# - `embedding_grad_viable`: a `jax.custom_vjp` round the gather and the cast,
#   whose backward is `ops/pallas/embedding_grad.py`'s grouped products over
#   the tokens sorted by id (a bf16 cotangent, rows of 768 lanes or more in
#   whole 128s, a TPU or the interpreter, one device or a mesh that shards
#   `batch` alone with the table whole); counter
#   `embed_grad_dispatch_grouped`;
# - everywhere else `jnp.take`'s own gradient, XLA's sorted scatter-add;
#   counter `embed_grad_dispatch_scatter`.
# The clamp of ids below 0 and `padding_idx` lie outside the `custom_vjp`,
# so both behave alike on either path. Nothing but shapes chooses.
# The counters count lowerings of this op, like `moe_dispatch_*` and
# `ssm_dispatch_*`: a train step lowers it twice a table (the forward op and
# the gradient op's replay of it), a forward-only Program once, where the
# counter says which path a gradient would take and none is built.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _take_rows_grouped(w, idx, dtype, mesh, like):
    """`take(w, idx).astype(dtype)` whose gradient for the table is
    `ops/pallas/embedding_grad.py`'s grouped products. `like` is the
    table's `(rows, dtype)`, which is all the backward needs of it."""
    return jnp.take(w, idx, axis=0).astype(dtype)


def _take_rows_fwd(w, idx, dtype, mesh, like):
    return _take_rows_grouped(w, idx, dtype, mesh, like), idx


def _take_rows_bwd(dtype, mesh, like, idx, dy):
    from .pallas.embedding_grad import embedding_grad

    vocab, table_dtype = like
    dw = embedding_grad(idx.reshape(-1), dy.reshape(-1, dy.shape[-1]),
                        vocab, mesh)
    return dw.astype(table_dtype), None


_take_rows_grouped.defvjp(_take_rows_fwd, _take_rows_bwd)


@register_op("lookup_table", no_grad_inputs=("Ids",))
def _lookup_table(ctx, op):
    from .pallas.embedding_grad import embedding_grad_viable, run_rows

    w = ctx.in_(op, "W")
    ids = ctx.in_(op, "Ids")
    padding_idx = op.attr("padding_idx", -1)
    idx = ids.astype(jnp.int32)
    squeeze_last = idx.ndim >= 2 and idx.shape[-1] == 1
    if squeeze_last:
        idx = idx.squeeze(-1)
    rows = jnp.maximum(idx, 0)
    # AMP: cast the gathered rows, not the whole table (HBM traffic)
    amp = ctx.amp_dtype_for(op)
    floating = jnp.issubdtype(w.dtype, jnp.floating)
    dtype = jnp.dtype(w.dtype if amp is None or not floating else amp)
    specs = getattr(ctx.program, "_sharding_specs", None) or {}
    if w.ndim == 2 and embedding_grad_viable(
            rows.size, w.shape[0], w.shape[1], dtype, ctx.mesh,
            specs.get(op.input("W")[0])):
        profiler.bump_counter("embed_grad_dispatch_grouped")
        profiler.set_counter("embed_grad_run_rows", run_rows(w.shape[0]))
        out = _take_rows_grouped(w, rows, dtype, ctx.mesh,
                                 (w.shape[0], jnp.dtype(w.dtype)))
    else:
        profiler.bump_counter("embed_grad_dispatch_scatter")
        out = jnp.take(w, rows, axis=0).astype(dtype)
    if padding_idx is not None and padding_idx != -1:
        out = jnp.where((idx == padding_idx)[..., None], 0.0, out)
    ctx.out(op, "Out", out)


@register_op("lookup_table_v2", no_grad_inputs=("Ids",))
def _lookup_table_v2(ctx, op):
    _lookup_table(ctx, op)


@register_op("one_hot", differentiable=False)
def _one_hot(ctx, op):
    x = ctx.in_(op, "X")
    depth = op.attr("depth")
    idx = x.astype(jnp.int32)
    if idx.ndim >= 2 and idx.shape[-1] == 1:
        idx = idx.squeeze(-1)
    ctx.out(op, "Out", jax.nn.one_hot(idx, depth, dtype=jnp.float32))


@register_op("embedding_bag", no_grad_inputs=("Ids",))
def _embedding_bag(ctx, op):
    # sum-pooled embedding lookup — the dense analog of the reference's
    # fused_embedding_seq_pool (operators/fused/fused_embedding_seq_pool_op.cc)
    w = ctx.in_(op, "W")
    ids = ctx.in_(op, "Ids").astype(jnp.int32)  # [batch, bag]
    weights = ctx.in_(op, "PerSampleWeights")
    emb = jnp.take(w, jnp.maximum(ids, 0), axis=0)
    mask = (ids >= 0)[..., None]
    emb = jnp.where(mask, emb, 0.0)
    if weights is not None:
        emb = emb * weights[..., None]
    ctx.out(op, "Out", jnp.sum(emb, axis=1))


@register_op("lrn")
def _lrn(ctx, op):
    """reference: operators/lrn_op.cc — across-channel LRN (NCHW):
    out = x / (k + alpha * sum_{window n} x^2)^beta."""
    x = ctx.in_(op, "X")
    n = op.attr("n", 5)
    k = op.attr("k", 1.0)
    alpha = op.attr("alpha", 1e-4)
    beta = op.attr("beta", 0.75)
    sq = jnp.square(x.astype(jnp.float32))
    half = n // 2
    sqsum = jax.lax.reduce_window(
        sq, 0.0, jax.lax.add,
        window_dimensions=(1, n, 1, 1),
        window_strides=(1, 1, 1, 1),
        padding=((0, 0), (half, n - 1 - half), (0, 0), (0, 0)),
    )
    out = x.astype(jnp.float32) * jax.lax.pow(k + alpha * sqsum, -beta)
    ctx.out(op, "Out", out.astype(x.dtype))


@register_op("unfold")
def _unfold(ctx, op):
    """reference: operators/unfold_op.cc (im2col): NCHW -> [N, C*kh*kw, L]
    via conv_general_dilated_patches."""
    x = ctx.in_(op, "X")
    ks = op.attr("kernel_sizes")
    st = op.attr("strides", [1, 1])
    pd = op.attr("paddings", [0, 0, 0, 0])
    dl = op.attr("dilations", [1, 1])
    patches = jax.lax.conv_general_dilated_patches(
        x,
        filter_shape=tuple(ks),
        window_strides=tuple(st),
        padding=((pd[0], pd[2]), (pd[1], pd[3])),
        rhs_dilation=tuple(dl),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )  # [N, C*kh*kw, OH, OW]
    n, ckk = patches.shape[:2]
    ctx.out(op, "Out", patches.reshape(n, ckk, -1))


@register_op("var_conv_2d", no_grad_inputs=("ROW", "COLUMN"))
def _var_conv_2d(ctx, op):
    """Variable-size 2D conv over per-sample image extents (reference:
    operators/var_conv_2d_op.cc — LoD images, half-kernel zero padding at
    each sample's OWN boundary, out dim (d-1)/stride+1). Dense redesign:
    X is a padded canvas [b, in_c, H, W] with ROW/COLUMN [b] giving each
    sample's valid rows/cols; masking X outside the valid extent to zero
    before a SAME-style conv reproduces the per-sample boundary padding,
    and the output is re-masked to each sample's own output extent."""
    x = ctx.in_(op, "X")  # [b, in_c, H, W]
    row = ctx.in_(op, "ROW").reshape(-1)       # [b] valid heights
    col = ctx.in_(op, "COLUMN").reshape(-1)    # [b] valid widths
    w = ctx.in_(op, "W")  # [out_c, in_c*kh*kw]
    kh = int(op.attr("KernelH", 1))
    kw = int(op.attr("KernelW", 1))
    sh = int(op.attr("StrideH", 1))
    sw = int(op.attr("StrideW", 1))
    out_c = int(op.attr("OutputChannel"))
    in_c = int(op.attr("InputChannel"))
    b, _, h, wd = x.shape
    wk = w.reshape(out_c, in_c, kh, kw)

    yy = jnp.arange(h)[None, :, None]
    xx = jnp.arange(wd)[None, None, :]
    valid_in = (
        (yy < row[:, None, None]) & (xx < col[:, None, None])
    )  # [b, H, W]
    xm = jnp.where(valid_in[:, None], x, 0.0)

    # reference half-kernel convention: pad k//2 low, k-1-k//2 high
    pad = ((kh // 2, kh - 1 - kh // 2), (kw // 2, kw - 1 - kw // 2))
    out = jax.lax.conv_general_dilated(
        jnp.transpose(xm, (0, 2, 3, 1)),
        jnp.transpose(wk, (2, 3, 1, 0)),
        window_strides=(sh, sw),
        padding=pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    out = jnp.transpose(out, (0, 3, 1, 2))  # [b, out_c, OH, OW]
    oh, ow = out.shape[2], out.shape[3]
    o_rows = jnp.where(row > 0, (row - 1) // sh + 1, 0)
    o_cols = jnp.where(col > 0, (col - 1) // sw + 1, 0)
    oyy = jnp.arange(oh)[None, :, None]
    oxx = jnp.arange(ow)[None, None, :]
    valid_out = (
        (oyy < o_rows[:, None, None]) & (oxx < o_cols[:, None, None])
    )
    ctx.out(op, "Out", jnp.where(valid_out[:, None], out, 0.0))


@register_op("depthwise_conv2d_transpose")
def _depthwise_conv2d_transpose(ctx, op):
    """reference: conv_transpose_op.cc depthwise path (MobileNet-style
    deconv) — the grouped branch of conv2d_transpose (the vjp-of-forward
    mechanism there handles any groups/channel-multiplier). The op TYPE
    declares depthwise, so groups must equal in_channels — falling
    through to the ungrouped branch would be silently wrong semantics."""
    in_c = ctx.in_(op, "Input").shape[1]
    if (op.attr("groups", 1) or 1) != in_c:
        raise ValueError(
            f"depthwise_conv2d_transpose: groups attr "
            f"({op.attr('groups', 1)}) must equal in_channels ({in_c})"
        )
    _conv2d_transpose(ctx, op)
