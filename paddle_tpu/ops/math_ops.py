"""Elementwise / activation / reduce / comparison op lowerings.

Covers the capability of reference paddle/fluid/operators/elementwise/,
operators/reduce_ops/, the activation zoo (operators/activation_op.cc), and
matmul/mul (operators/matmul_op.cc, mul_op.cc). Each op is a pure JAX
lowering fused by XLA — there is no per-op kernel launch to optimise; the
design goal is keeping everything traceable into one module so elementwise
chains fuse into the surrounding matmuls (HBM-bandwidth-friendly).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .registry import JNP_DTYPE, register_op

# ---------------------------------------------------------------------------
# elementwise binary ops with fluid axis-broadcast semantics
# (reference: operators/elementwise/elementwise_op_function.h — Y is
# broadcast against X starting at `axis`)
# ---------------------------------------------------------------------------


def _broadcast_y(x, y, axis):
    if x.ndim == y.ndim:
        return y
    if axis is None or axis == -1:
        axis = x.ndim - y.ndim
    # insert trailing singleton dims so y aligns with x at `axis`
    shape = [1] * x.ndim
    for i, s in enumerate(y.shape):
        shape[axis + i] = s
    return y.reshape(shape)


def _ew(fn):
    def lower(ctx, op):
        x = ctx.in_(op, "X")
        y = ctx.in_(op, "Y")
        axis = op.attr("axis", -1)
        # reference convention: Out takes X's dtype (elementwise_op.h).
        # Critical under AMP: jnp promotion of bf16 activations + f32
        # params would silently upcast the whole activation stream to f32
        # (every fc bias-add doubling downstream HBM traffic; measured
        # ~2x on BERT-base gelu/LN/residual chains)
        if (
            hasattr(x, "dtype") and hasattr(y, "dtype")
            and x.dtype != y.dtype
            and jnp.issubdtype(x.dtype, jnp.floating)
            and jnp.issubdtype(y.dtype, jnp.floating)
        ):
            y = y.astype(x.dtype)
        y = _broadcast_y(x, y, axis)
        out = fn(x, y)
        scale = op.attr("Scale_out", 1.0)
        if scale != 1.0:
            out = out * scale
        ctx.out(op, "Out", out)

    return lower


def _ew_linear_grad_maker(op_type):
    # explicit grad for add/sub so the broadcast-reduce over Y's missing
    # dims (the fc-bias-grad pattern: [b*s, o] -> [o]) can ride the MXU
    # instead of a slow VPU sublane-dim reduce
    def maker(op, grad_out_names, block, helpers):
        if grad_out_names.get("Out", [None])[0] is None:
            return None
        return [
            {
                "type": op_type + "_grad",
                "inputs": {
                    "X": op.input("X"),
                    "Y": op.input("Y"),
                    "GRAD_Out": [grad_out_names["Out"][0]],
                },
                "outputs": {
                    "IGRAD_X": [helpers.grad_name(op.input("X")[0])],
                    "IGRAD_Y": [helpers.grad_name(op.input("Y")[0])],
                },
                "attrs": {
                    "axis": op.attr("axis", -1),
                    "Scale_out": op.attr("Scale_out", 1.0),
                },
            }
        ]

    return maker


def _reduce_to_y(d, x, y, axis):
    """Sum the full-shape cotangent `d` down to y's shape under the
    elementwise broadcast convention; prefers a ones-vector MXU
    contraction when the reduced dims form a leading prefix. Accumulates
    and returns f32 — the caller casts once to the param dtype (rounding
    a 32k-term bias-grad sum through bf16 mid-way would cost ~8 mantissa
    bits)."""
    if tuple(y.shape) == tuple(d.shape):
        return d
    yb_shape = _broadcast_y(x, y, axis).shape
    red = tuple(
        i for i, (db, yb) in enumerate(zip(d.shape, yb_shape)) if yb == 1
    )
    lead = tuple(range(len(red)))
    if red == lead and len(red) < d.ndim:
        n = int(np.prod(d.shape[: len(red)]))
        k = int(np.prod(d.shape[len(red):]))
        ones = jnp.ones((n,), d.dtype)
        out = jax.lax.dot_general(
            ones, d.reshape(n, k), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return out.reshape(y.shape)
    return jnp.sum(d, axis=red, dtype=jnp.float32).reshape(y.shape)


def _ew_add_sub_grad(sign):
    def lower(ctx, op):
        d = ctx.in_(op, "GRAD_Out")
        x = ctx.in_(op, "X")
        y = ctx.in_(op, "Y")
        axis = op.attr("axis", -1)
        scale = op.attr("Scale_out", 1.0)
        if scale != 1.0:
            d = d * scale
        ctx.out(op, "IGRAD_X", d.astype(x.dtype))
        dy = _reduce_to_y(d, x, y, axis)
        if sign < 0:
            dy = -dy
        ctx.out(op, "IGRAD_Y", dy.astype(y.dtype))

    return lower


register_op("elementwise_add_grad", differentiable=False)(_ew_add_sub_grad(1))
register_op("elementwise_sub_grad", differentiable=False)(_ew_add_sub_grad(-1))

for _name, _fn in {
    "elementwise_add": jnp.add,
    "elementwise_sub": jnp.subtract,
    "elementwise_mul": jnp.multiply,
    "elementwise_div": jnp.divide,
    "elementwise_min": jnp.minimum,
    "elementwise_max": jnp.maximum,
    "elementwise_pow": jnp.power,
    "elementwise_mod": jnp.mod,
    "elementwise_floordiv": jnp.floor_divide,
}.items():
    if _name in ("elementwise_add", "elementwise_sub"):
        register_op(_name, grad=_ew_linear_grad_maker(_name))(_ew(_fn))
    else:
        register_op(_name)(_ew(_fn))


# ---------------------------------------------------------------------------
# unary / activation ops
# ---------------------------------------------------------------------------


def _unary(fn, **reg_kwargs):
    def lower(ctx, op):
        ctx.out(op, "Out", fn(ctx.in_(op, "X"), op))

    return lower


def _simple_unary(name, fn, **reg_kwargs):
    register_op(name, **reg_kwargs)(_unary(lambda x, op: fn(x)))


_simple_unary("relu", jax.nn.relu)
_simple_unary("sigmoid", jax.nn.sigmoid)
_simple_unary("logsigmoid", jax.nn.log_sigmoid)
_simple_unary("tanh", jnp.tanh)
_simple_unary("exp", jnp.exp)
_simple_unary("log", jnp.log)
_simple_unary("log2", jnp.log2)
_simple_unary("log10", jnp.log10)
_simple_unary("log1p", jnp.log1p)
_simple_unary("sqrt", jnp.sqrt)
_simple_unary("rsqrt", jax.lax.rsqrt)
_simple_unary("square", jnp.square)
_simple_unary("abs", jnp.abs)
_simple_unary("sign", jnp.sign, differentiable=False)
_simple_unary("floor", jnp.floor, differentiable=False)
_simple_unary("ceil", jnp.ceil, differentiable=False)
_simple_unary("round", jnp.round, differentiable=False)
_simple_unary("reciprocal", jnp.reciprocal)
_simple_unary("sin", jnp.sin)
_simple_unary("cos", jnp.cos)
_simple_unary("tan", jnp.tan)
_simple_unary("asin", jnp.arcsin)
_simple_unary("acos", jnp.arccos)
_simple_unary("atan", jnp.arctan)
_simple_unary("sinh", jnp.sinh)
_simple_unary("cosh", jnp.cosh)
_simple_unary("erf", jax.scipy.special.erf)
_simple_unary("softsign", jax.nn.soft_sign)
_simple_unary("tanh_shrink", lambda x: x - jnp.tanh(x))
_simple_unary("softshrink", lambda x: jnp.sign(x) * jnp.maximum(jnp.abs(x) - 0.5, 0))


@register_op("gelu")
def _gelu(ctx, op):
    x = ctx.in_(op, "X")
    approximate = bool(op.attr("approximate", False))
    ctx.out(op, "Out", jax.nn.gelu(x, approximate=approximate))


@register_op("leaky_relu")
def _leaky_relu(ctx, op):
    x = ctx.in_(op, "X")
    alpha = op.attr("alpha", 0.02)
    ctx.out(op, "Out", jnp.where(x >= 0, x, alpha * x))


@register_op("relu6")
def _relu6(ctx, op):
    x = ctx.in_(op, "X")
    threshold = op.attr("threshold", 6.0)
    ctx.out(op, "Out", jnp.clip(x, 0.0, threshold))


@register_op("pow")
def _pow(ctx, op):
    x = ctx.in_(op, "X")
    ctx.out(op, "Out", jnp.power(x, op.attr("factor", 1.0)))


@register_op("softplus")
def _softplus(ctx, op):
    ctx.out(op, "Out", jax.nn.softplus(ctx.in_(op, "X")))


@register_op("swish")
def _swish(ctx, op):
    x = ctx.in_(op, "X")
    beta = op.attr("beta", 1.0)
    ctx.out(op, "Out", x * jax.nn.sigmoid(beta * x))


@register_op("hard_sigmoid")
def _hard_sigmoid(ctx, op):
    x = ctx.in_(op, "X")
    slope = op.attr("slope", 0.2)
    offset = op.attr("offset", 0.5)
    ctx.out(op, "Out", jnp.clip(slope * x + offset, 0.0, 1.0))


@register_op("hard_swish")
def _hard_swish(ctx, op):
    x = ctx.in_(op, "X")
    threshold = op.attr("threshold", 6.0)
    scale = op.attr("scale", 6.0)
    offset = op.attr("offset", 3.0)
    ctx.out(op, "Out", x * jnp.clip(x + offset, 0.0, threshold) / scale)


@register_op("elu")
def _elu(ctx, op):
    x = ctx.in_(op, "X")
    alpha = op.attr("alpha", 1.0)
    ctx.out(op, "Out", jnp.where(x > 0, x, alpha * (jnp.exp(x) - 1)))


@register_op("prelu")
def _prelu(ctx, op):
    x = ctx.in_(op, "X")
    alpha = ctx.in_(op, "Alpha")
    mode = op.attr("mode", "all")
    if mode == "channel":
        alpha = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    ctx.out(op, "Out", jnp.where(x >= 0, x, alpha * x))


@register_op("clip")
def _clip(ctx, op):
    x = ctx.in_(op, "X")
    ctx.out(op, "Out", jnp.clip(x, op.attr("min"), op.attr("max")))


@register_op("scale")
def _scale(ctx, op):
    x = ctx.in_(op, "X")
    scale = op.attr("scale", 1.0)
    if op.input("ScaleTensor"):
        scale = ctx.in_(op, "ScaleTensor")
    bias = op.attr("bias", 0.0)
    if op.attr("bias_after_scale", True):
        out = x * scale + bias
    else:
        out = (x + bias) * scale
    ctx.out(op, "Out", out)


# ---------------------------------------------------------------------------
# matmul / mul
# ---------------------------------------------------------------------------


@register_op("matmul")
def _matmul(ctx, op):
    """Fluid matmul with transpose flags + alpha and batch broadcasting
    (reference: operators/matmul_op.cc). Large batched matmuls land on the
    MXU; bf16 inputs keep the MXU in its fast path."""
    x = ctx.in_(op, "X")
    y = ctx.in_(op, "Y")
    x, y = ctx.amp_cast(op, x, y)
    tx = op.attr("transpose_X", False)
    ty = op.attr("transpose_Y", False)
    alpha = op.attr("alpha", 1.0)
    if x.ndim == 1:
        x = x[None, :]
    if y.ndim == 1:
        y = y[:, None]
    if tx:
        x = jnp.swapaxes(x, -1, -2)
    if ty:
        y = jnp.swapaxes(y, -1, -2)
    out = x @ y
    if alpha != 1.0:
        out = out * alpha
    ctx.out(op, "Out", out)


@register_op("matmul_v2")
def _matmul_v2(ctx, op):
    x = ctx.in_(op, "X")
    y = ctx.in_(op, "Y")
    x, y = ctx.amp_cast(op, x, y)
    if op.attr("trans_x", False):
        x = jnp.swapaxes(x, -1, -2)
    if op.attr("trans_y", False):
        y = jnp.swapaxes(y, -1, -2)
    ctx.out(op, "Out", x @ y)


@register_op("mul")
def _mul(ctx, op):
    """Flattening matmul (reference: operators/mul_op.cc): X flattened to 2-D
    at x_num_col_dims, Y at y_num_col_dims; output unflattened."""
    x = ctx.in_(op, "X")
    y = ctx.in_(op, "Y")
    x, y = ctx.amp_cast(op, x, y)
    xn = op.attr("x_num_col_dims", 1)
    yn = op.attr("y_num_col_dims", 1)
    x_lead = x.shape[:xn]
    x2 = x.reshape((int(np.prod(x_lead or (1,))), -1))
    y2 = y.reshape((int(np.prod(y.shape[:yn])), -1))
    out = x2 @ y2
    ctx.out(op, "Out", out.reshape(tuple(x_lead) + tuple(y.shape[yn:])))


@register_op("bmm")
def _bmm(ctx, op):
    x, y = ctx.amp_cast(op, ctx.in_(op, "X"), ctx.in_(op, "Y"))
    ctx.out(op, "Out", x @ y)


@register_op("dot")
def _dot(ctx, op):
    x = ctx.in_(op, "X")
    y = ctx.in_(op, "Y")
    ctx.out(op, "Out", jnp.sum(x * y, axis=-1, keepdims=x.ndim > 1))


# ---------------------------------------------------------------------------
# reductions (reference: operators/reduce_ops/)
# ---------------------------------------------------------------------------


def _reduce(fn):
    def lower(ctx, op):
        x = ctx.in_(op, "X")
        dims = op.attr("dim", [0])
        keep = op.attr("keep_dim", False)
        if op.attr("reduce_all", False) or dims is None:
            axis = None
        else:
            axis = tuple(d % x.ndim for d in (dims if isinstance(dims, (list, tuple)) else [dims]))
        out = fn(x, axis=axis, keepdims=keep)
        if axis is None and not keep:
            out = out.reshape((1,))  # fluid full-reduce yields a [1] tensor
        ctx.out(op, "Out", out)

    return lower


for _name, _fn in {
    "reduce_sum": jnp.sum,
    "reduce_mean": jnp.mean,
    "reduce_max": jnp.max,
    "reduce_min": jnp.min,
    "reduce_prod": jnp.prod,
    "reduce_all": jnp.all,
    "reduce_any": jnp.any,
}.items():
    register_op(_name)(_reduce(_fn))


@register_op("mean")
def _mean(ctx, op):
    # fluid `mean` reduces to a [1] tensor (reference: operators/mean_op.cc)
    x = ctx.in_(op, "X")
    ctx.out(op, "Out", jnp.mean(x).reshape((1,)))


@register_op("sum")
def _sum(ctx, op):
    # multi-input accumulate (reference: operators/sum_op.cc); grad-merge path
    xs = ctx.ins(op, "X")
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    ctx.out(op, "Out", out)


@register_op("logsumexp")
def _logsumexp(ctx, op):
    x = ctx.in_(op, "X")
    dims = op.attr("dim", None)
    keep = op.attr("keep_dim", False)
    axis = None if op.attr("reduce_all", False) or dims is None else tuple(dims)
    out = jax.scipy.special.logsumexp(x, axis=axis, keepdims=keep)
    if out.ndim == 0:
        out = out.reshape(1)  # fluid reductions never return rank-0
    ctx.out(op, "Out", out)


@register_op("frobenius_norm")
def _frobenius_norm(ctx, op):
    x = ctx.in_(op, "X")
    ctx.out(op, "Out", jnp.sqrt(jnp.sum(jnp.square(x))))


@register_op("squared_l2_norm")
def _squared_l2_norm(ctx, op):
    x = ctx.in_(op, "X")
    ctx.out(op, "Out", jnp.sum(jnp.square(x)).reshape((1,)))


@register_op("p_norm")
def _p_norm(ctx, op):
    x = ctx.in_(op, "X")
    porder = op.attr("porder", 2.0)
    axis = op.attr("axis", None)
    keepdim = op.attr("keepdim", False)
    ctx.out(
        op,
        "Out",
        jnp.power(
            jnp.sum(jnp.power(jnp.abs(x), porder), axis=axis, keepdims=keepdim),
            1.0 / porder,
        ),
    )


# ---------------------------------------------------------------------------
# comparison / logical (non-differentiable)
# ---------------------------------------------------------------------------

for _name, _fn in {
    "equal": jnp.equal,
    "not_equal": jnp.not_equal,
    "less_than": jnp.less,
    "less_equal": jnp.less_equal,
    "greater_than": jnp.greater,
    "greater_equal": jnp.greater_equal,
    "logical_and": jnp.logical_and,
    "logical_or": jnp.logical_or,
    "logical_xor": jnp.logical_xor,
}.items():
    register_op(_name, differentiable=False)(_ew(_fn))


@register_op("logical_not", differentiable=False)
def _logical_not(ctx, op):
    ctx.out(op, "Out", jnp.logical_not(ctx.in_(op, "X")))


@register_op("isfinite", differentiable=False)
def _isfinite(ctx, op):
    x = ctx.in_(op, "X")
    ctx.out(op, "Out", jnp.all(jnp.isfinite(x)).reshape((1,)))


# ---------------------------------------------------------------------------
# index / search ops (non-differentiable index outputs)
# ---------------------------------------------------------------------------


@register_op("arg_max", differentiable=False)
def _arg_max(ctx, op):
    x = ctx.in_(op, "X")
    axis = op.attr("axis", -1)
    ctx.out(op, "Out", jnp.argmax(x, axis=axis).astype(JNP_DTYPE(op.attr("out_dtype", "int64"))))


@register_op("arg_min", differentiable=False)
def _arg_min(ctx, op):
    x = ctx.in_(op, "X")
    axis = op.attr("axis", -1)
    ctx.out(op, "Out", jnp.argmin(x, axis=axis).astype(JNP_DTYPE(op.attr("out_dtype", "int64"))))


@register_op("top_k", no_grad_inputs=("Indices",))
def _top_k(ctx, op):
    x = ctx.in_(op, "X")
    k = op.attr("k", 1)
    if op.input("K"):
        k = int(np.asarray(ctx.in_(op, "K")))  # provlint: disable=no-host-pull-in-ops
    vals, idx = jax.lax.top_k(x, k)
    ctx.out(op, "Out", vals)
    ctx.out(op, "Indices", idx.astype(jnp.int32))


@register_op("argsort", differentiable=False)
def _argsort(ctx, op):
    x = ctx.in_(op, "X")
    axis = op.attr("axis", -1)
    descending = op.attr("descending", False)
    key = -x if descending else x
    idx = jnp.argsort(key, axis=axis)
    ctx.out(op, "Indices", idx.astype(jnp.int32))
    ctx.out(op, "Out", jnp.take_along_axis(x, idx, axis=axis))


@register_op("cumsum")
def _cumsum(ctx, op):
    x = ctx.in_(op, "X")
    axis = op.attr("axis", -1)
    if op.attr("flatten", False):
        x = x.reshape(-1)
        axis = 0
    reverse = op.attr("reverse", False)
    out = jnp.cumsum(x, axis=axis)
    if reverse:
        out = jnp.flip(jnp.cumsum(jnp.flip(x, axis), axis=axis), axis)
    if op.attr("exclusive", False):
        # exclusive shifts one step along the scan direction: forward pads
        # the front; reverse pads the end
        pad = [(0, 0)] * x.ndim
        sel = [slice(None)] * x.ndim
        if reverse:
            pad[axis] = (0, 1)
            sel[axis] = slice(1, x.shape[axis] + 1)
        else:
            pad[axis] = (1, 0)
            sel[axis] = slice(0, x.shape[axis])
        out = jnp.pad(out, pad)[tuple(sel)]
    ctx.out(op, "Out", out)


# ---------------------------------------------------------------------------
# misc
# ---------------------------------------------------------------------------


@register_op("increment")
def _increment(ctx, op):
    x = ctx.in_(op, "X")
    # preserve integer counters (the While-loop idiom) — no float promotion
    ctx.out(op, "Out", x + jnp.asarray(op.attr("step", 1.0), dtype=x.dtype))


@register_op("size", differentiable=False)
def _size(ctx, op):
    x = ctx.in_(op, "Input")
    ctx.out(op, "Out", jnp.asarray(int(np.prod(x.shape)), dtype=jnp.int32))


@register_op("maximum")
def _maximum(ctx, op):
    ctx.out(op, "Out", jnp.maximum(ctx.in_(op, "X"), ctx.in_(op, "Y")))


@register_op("minimum")
def _minimum(ctx, op):
    ctx.out(op, "Out", jnp.minimum(ctx.in_(op, "X"), ctx.in_(op, "Y")))


@register_op("where")
def _where(ctx, op):
    ctx.out(
        op,
        "Out",
        jnp.where(ctx.in_(op, "Condition"), ctx.in_(op, "X"), ctx.in_(op, "Y")),
    )


@register_op("clip_by_norm")
def _clip_by_norm(ctx, op):
    x = ctx.in_(op, "X")
    max_norm = op.attr("max_norm")
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    ctx.out(op, "Out", jnp.where(norm > max_norm, x * (max_norm / norm), x))


@register_op("brelu")
def _brelu(ctx, op):
    """reference: operators/activation_op.cc BRelu — clip(x, t_min, t_max)."""
    x = ctx.in_(op, "X")
    t_min = float(op.attr("t_min", 0.0))
    t_max = float(op.attr("t_max", 24.0))
    ctx.out(op, "Out", jnp.clip(x, t_min, t_max))


@register_op("label_smooth")
def _label_smooth(ctx, op):
    """reference: operators/label_smooth_op.cc — out = (1-eps)*X + eps *
    (PriorDist | 1/num_classes)."""
    x = ctx.in_(op, "X")
    eps = float(op.attr("epsilon", 0.0))
    prior = ctx.in_(op, "PriorDist")
    if prior is not None:
        smooth = prior.reshape((1,) * (x.ndim - 1) + (-1,))
    else:
        smooth = 1.0 / x.shape[-1]
    ctx.out(op, "Out", (1.0 - eps) * x + eps * smooth)


@register_op("maxout")
def _maxout(ctx, op):
    """reference: operators/maxout_op.cc — max over `groups` consecutive
    channels: [N, C, H, W] -> [N, C/groups, H, W]."""
    x = ctx.in_(op, "X")
    g = int(op.attr("groups"))
    n, c = x.shape[0], x.shape[1]
    xg = x.reshape((n, c // g, g) + x.shape[2:])
    ctx.out(op, "Out", jnp.max(xg, axis=2))


@register_op("reverse")
def _reverse(ctx, op):
    """reference: operators/reverse_op.cc — flip along `axis` list."""
    x = ctx.in_(op, "X")
    axes = op.attr("axis")
    axes = [axes] if isinstance(axes, int) else list(axes)
    ctx.out(op, "Out", jnp.flip(x, axis=tuple(axes)))
