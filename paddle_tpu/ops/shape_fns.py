"""Static shape/dtype functions for the op registry.

Each function is the static mirror of its lowering in this package:
given input VarMetas (shape tuple + LOWERED dtype name) it computes the
output VarMetas the traced step would produce — bit-identical shape
tuples and dtype names, per the lowering's actual casts (f32 stat
outputs, uint8 dropout masks, the `(0,) + x.shape` XShape convention,
fluid's [1]-shaped full reductions), with zero JAX tracing.

Coverage targets the op families the bench programs use (matmul / conv /
pool / norm / elementwise / reduce / reshape / transpose / embedding /
softmax / attention) plus everything cheap around them; the remaining
registry is tracked by tools/shape_coverage.json, which CI only lets
shrink. Grad ops are handled generically by the engine
(analysis/shape_infer.py) — IGRAD outputs carry the forward input's
meta — so only forward/optimizer ops appear here.
"""

from __future__ import annotations

from ..analysis.meta import (
    InferError,
    Unknown,
    VarMeta,
    broadcast_shapes,
    conv_out_dim,
    ew_broadcast,
    is_float,
    lowered_dtype,
    pool_out_dim,
    prod,
)
from .registry import register_shape

F32 = "float32"
I32 = "int32"
BOOL = "bool"
U8 = "uint8"


def _m(meta) -> VarMeta:
    return meta if meta is not None else VarMeta(None, None)


def _known(*metas) -> bool:
    return all(m is not None and m.shape is not None for m in metas)


def _promote(*dtypes):
    from ..analysis.meta import promote

    return promote(*dtypes)


# ---------------------------------------------------------------------------
# passthrough: same shape, same dtype as X
# ---------------------------------------------------------------------------

_PASSTHROUGH = (
    "relu", "sigmoid", "logsigmoid", "tanh", "exp", "log", "log2", "log10",
    "log1p", "sqrt", "rsqrt", "square", "abs", "sign", "floor", "ceil",
    "round", "reciprocal", "sin", "cos", "tan", "asin", "acos", "atan",
    "sinh", "cosh", "erf", "softsign", "tanh_shrink", "softshrink",
    "gelu", "leaky_relu", "relu6", "pow", "softplus", "swish",
    "hard_sigmoid", "hard_swish", "elu", "brelu", "selu", "clip",
    "assign", "fill_zeros_like", "softmax", "log_softmax", "label_smooth",
)


@register_shape(*_PASSTHROUGH)
def _shape_passthrough(ictx, op):
    ictx.out(op, "Out", _m(ictx.in_(op, "X")))


@register_shape("prelu")
def _shape_prelu(ictx, op):
    ictx.out(op, "Out", _m(ictx.in_(op, "X")))


@register_shape("scale")
def _shape_scale(ictx, op):
    x = _m(ictx.in_(op, "X"))
    dt = x.dtype
    if dt is not None and not is_float(dt):
        # the lowering always computes x*scale + bias with python-float
        # attrs: jnp weak promotion floats an int tensor unless both
        # attrs are ints
        scale = op.attr("scale", 1.0)
        bias = op.attr("bias", 0.0)
        if op.input("ScaleTensor"):
            st = _m(ictx.in_(op, "ScaleTensor"))
            dt = _promote(dt, st.dtype)
        elif not (isinstance(scale, int) and isinstance(bias, int)):
            dt = _promote(dt, F32)
    ictx.out(op, "Out", VarMeta(x.shape, dt))


@register_shape("cast")
def _shape_cast(ictx, op):
    x = _m(ictx.in_(op, "X"))
    ictx.out(op, "Out", VarMeta(x.shape, lowered_dtype(op.attr("out_dtype"))))


@register_shape("fill_any_like")
def _shape_fill_any_like(ictx, op):
    x = _m(ictx.in_(op, "X"))
    dta = op.attr("dtype", None)
    dt = x.dtype if dta in (None, -1) else lowered_dtype(dta)
    ictx.out(op, "Out", VarMeta(x.shape, dt))


# ---------------------------------------------------------------------------
# elementwise binary (fluid axis-broadcast)
# ---------------------------------------------------------------------------


def _ew_dtype(op_type, x, y):
    if x.dtype is None or y.dtype is None:
        return None
    if is_float(x.dtype) and is_float(y.dtype):
        # the lowering casts Y to X's dtype (Out takes X's dtype)
        dt = x.dtype
    else:
        dt = _promote(x.dtype, y.dtype)
    if op_type == "elementwise_div" and dt is not None and not is_float(dt):
        dt = F32  # jnp true division
    return dt


@register_shape(
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_min", "elementwise_max",
    "elementwise_pow", "elementwise_mod", "elementwise_floordiv",
)
def _shape_elementwise(ictx, op):
    x = _m(ictx.in_(op, "X"))
    y = _m(ictx.in_(op, "Y"))
    shape = ew_broadcast(x.shape, y.shape, op.attr("axis", -1))
    ictx.out(op, "Out", VarMeta(shape, _ew_dtype(op.type, x, y)))


@register_shape(
    "equal", "not_equal", "less_than", "less_equal", "greater_than",
    "greater_equal", "logical_and", "logical_or", "logical_xor",
)
def _shape_compare(ictx, op):
    x = _m(ictx.in_(op, "X"))
    y = _m(ictx.in_(op, "Y"))
    shape = ew_broadcast(x.shape, y.shape, op.attr("axis", -1))
    ictx.out(op, "Out", VarMeta(shape, BOOL))


@register_shape("elementwise_add_grad", "elementwise_sub_grad")
def _shape_ew_add_sub_grad(ictx, op):
    # IGRAD_X is the (possibly broadcast-widened) cotangent in X's
    # dtype; IGRAD_Y reduces back to Y's own meta
    d = _m(ictx.in_(op, "GRAD_Out"))
    x = _m(ictx.in_(op, "X"))
    ictx.out(op, "IGRAD_X", VarMeta(d.shape, x.dtype))
    ictx.out(op, "IGRAD_Y", _m(ictx.in_(op, "Y")))


@register_shape("logical_not")
def _shape_logical_not(ictx, op):
    x = _m(ictx.in_(op, "X"))
    ictx.out(op, "Out", VarMeta(x.shape, BOOL))


@register_shape("isfinite")
def _shape_isfinite(ictx, op):
    ictx.out(op, "Out", VarMeta((1,), BOOL))


# ---------------------------------------------------------------------------
# matmul family
# ---------------------------------------------------------------------------


def _matmul_shape(xs, ys, tx, ty):
    xs, ys = list(xs), list(ys)
    if len(xs) == 1:
        xs = [1] + xs
    if len(ys) == 1:
        ys = ys + [1]
    if tx:
        xs[-1], xs[-2] = xs[-2], xs[-1]
    if ty:
        ys[-1], ys[-2] = ys[-2], ys[-1]
    if xs[-1] != ys[-2]:
        raise InferError(
            f"matmul contraction mismatch: {tuple(xs)} @ {tuple(ys)}"
        )
    batch = broadcast_shapes(tuple(xs[:-2]), tuple(ys[:-2]))
    return tuple(batch) + (xs[-2], ys[-1])


@register_shape("matmul")
def _shape_matmul(ictx, op):
    x, y = _m(ictx.in_(op, "X")), _m(ictx.in_(op, "Y"))
    dt = _promote(x.dtype, y.dtype)
    if not _known(x, y):
        ictx.out(op, "Out", VarMeta(None, dt))
        return
    shape = _matmul_shape(
        x.shape, y.shape,
        op.attr("transpose_X", False), op.attr("transpose_Y", False),
    )
    ictx.out(op, "Out", VarMeta(shape, dt))


@register_shape("matmul_v2")
def _shape_matmul_v2(ictx, op):
    x, y = _m(ictx.in_(op, "X")), _m(ictx.in_(op, "Y"))
    dt = _promote(x.dtype, y.dtype)
    if not _known(x, y):
        ictx.out(op, "Out", VarMeta(None, dt))
        return
    shape = _matmul_shape(
        x.shape, y.shape,
        op.attr("trans_x", False), op.attr("trans_y", False),
    )
    ictx.out(op, "Out", VarMeta(shape, dt))


@register_shape("bmm")
def _shape_bmm(ictx, op):
    x, y = _m(ictx.in_(op, "X")), _m(ictx.in_(op, "Y"))
    dt = _promote(x.dtype, y.dtype)
    if not _known(x, y):
        ictx.out(op, "Out", VarMeta(None, dt))
        return
    ictx.out(op, "Out", VarMeta(_matmul_shape(x.shape, y.shape, 0, 0), dt))


@register_shape("mul")
def _shape_mul(ictx, op):
    x, y = _m(ictx.in_(op, "X")), _m(ictx.in_(op, "Y"))
    dt = _promote(x.dtype, y.dtype)
    if not _known(x, y):
        ictx.out(op, "Out", VarMeta(None, dt))
        return
    xn = op.attr("x_num_col_dims", 1)
    yn = op.attr("y_num_col_dims", 1)
    k_x = prod(x.shape[xn:])
    k_y = prod(y.shape[:yn])
    if k_x != k_y:
        raise InferError(
            f"mul contraction mismatch: {x.shape} (cols {xn}) vs "
            f"{y.shape} (rows {yn})"
        )
    ictx.out(op, "Out", VarMeta(tuple(x.shape[:xn]) + tuple(y.shape[yn:]), dt))


@register_shape("dot")
def _shape_dot(ictx, op):
    x, y = _m(ictx.in_(op, "X")), _m(ictx.in_(op, "Y"))
    dt = _promote(x.dtype, y.dtype)
    x = ictx.require(x)
    keep = (1,) if len(x.shape) > 1 else ()
    ictx.out(op, "Out", VarMeta(tuple(x.shape[:-1]) + keep, dt))


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

_SMALL_INTS = ("bool", "int8", "int16", "uint8")


def _reduce_shape(shape, dims, keep, reduce_all):
    if reduce_all or dims is None:
        return tuple(1 for _ in shape) if keep else (1,)
    if not isinstance(dims, (list, tuple)):
        dims = [dims]
    axes = {d % len(shape) for d in dims}
    if keep:
        return tuple(1 if i in axes else d for i, d in enumerate(shape))
    return tuple(d for i, d in enumerate(shape) if i not in axes)


def _shape_reduce_common(ictx, op, dtype_of):
    x = ictx.require(_m(ictx.in_(op, "X")))
    shape = _reduce_shape(
        x.shape, op.attr("dim", [0]), op.attr("keep_dim", False),
        op.attr("reduce_all", False),
    )
    ictx.out(op, "Out", VarMeta(shape, dtype_of(x.dtype)))


@register_shape("reduce_sum", "reduce_prod")
def _shape_reduce_sum(ictx, op):
    _shape_reduce_common(
        ictx, op, lambda dt: I32 if dt in _SMALL_INTS else dt
    )


@register_shape("reduce_mean")
def _shape_reduce_mean(ictx, op):
    _shape_reduce_common(
        ictx, op, lambda dt: dt if is_float(dt) else F32
    )


@register_shape("reduce_max", "reduce_min")
def _shape_reduce_minmax(ictx, op):
    _shape_reduce_common(ictx, op, lambda dt: dt)


@register_shape("reduce_all", "reduce_any")
def _shape_reduce_bool(ictx, op):
    _shape_reduce_common(ictx, op, lambda dt: BOOL)


@register_shape("mean")
def _shape_mean(ictx, op):
    x = _m(ictx.in_(op, "X"))
    dt = x.dtype if (x.dtype and is_float(x.dtype)) else (
        F32 if x.dtype else None
    )
    ictx.out(op, "Out", VarMeta((1,), dt))


@register_shape("sum")
def _shape_sum(ictx, op):
    metas = [_m(m) for m in ictx.ins(op, "X")]
    if not metas:
        raise Unknown()
    shape = metas[0].shape
    dt = metas[0].dtype
    for m in metas[1:]:
        shape = broadcast_shapes(shape, m.shape) if (
            shape is not None and m.shape is not None
        ) else None
        dt = _promote(dt, m.dtype)
    ictx.out(op, "Out", VarMeta(shape, dt))


@register_shape("squared_l2_norm", "frobenius_norm")
def _shape_sq_norm(ictx, op):
    x = _m(ictx.in_(op, "X"))
    # squared_l2_norm reshapes to [1]; frobenius_norm stays rank-0
    shape = (1,) if op.type == "squared_l2_norm" else ()
    ictx.out(op, "Out", VarMeta(shape, x.dtype))


# ---------------------------------------------------------------------------
# reshape / transpose / squeeze family (XShape = (0,) + x.shape)
# ---------------------------------------------------------------------------


def _xshape(ictx, op, x):
    if op.output("XShape"):
        shape = (0,) + tuple(x.shape) if x.shape is not None else None
        ictx.out(op, "XShape", VarMeta(shape, x.dtype))


def _infer_reshape_shape(x_shape, target):
    shape = [int(s) for s in target]
    for i, s in enumerate(shape):
        if s == 0:
            shape[i] = x_shape[i]
    if -1 in shape:
        total = prod(x_shape)
        rest = prod([s for s in shape if s != -1])
        if rest <= 0 or total % rest != 0:
            raise InferError(f"cannot reshape {x_shape} to {tuple(target)}")
        shape[shape.index(-1)] = total // rest
    if prod(shape) != prod(x_shape):
        # the lowering's leading-dim salvage (executor feeds a different
        # batch than authored): rescale dim 0 when divisible
        rest = prod(shape[1:])
        if rest > 0 and prod(x_shape) % rest == 0:
            shape[0] = prod(x_shape) // rest
        else:
            raise InferError(f"cannot reshape {x_shape} to {tuple(target)}")
    return tuple(shape)


@register_shape("reshape", "reshape2")
def _shape_reshape(ictx, op):
    x = _m(ictx.in_(op, "X"))
    _xshape(ictx, op, x)
    if op.input("Shape"):
        ictx.out(op, "Out", VarMeta(None, x.dtype))  # value-dependent
        return
    if x.shape is None:
        ictx.out(op, "Out", VarMeta(None, x.dtype))
        return
    ictx.out(
        op, "Out",
        VarMeta(_infer_reshape_shape(x.shape, op.attr("shape")), x.dtype),
    )


@register_shape("transpose", "transpose2")
def _shape_transpose(ictx, op):
    x = _m(ictx.in_(op, "X"))
    _xshape(ictx, op, x)
    if x.shape is None:
        ictx.out(op, "Out", VarMeta(None, x.dtype))
        return
    axis = op.attr("axis")
    if axis is None or len(axis) != len(x.shape):
        raise InferError(f"transpose axis {axis} vs shape {x.shape}")
    ictx.out(
        op, "Out", VarMeta(tuple(x.shape[a] for a in axis), x.dtype)
    )


@register_shape("flatten", "flatten2")
def _shape_flatten(ictx, op):
    x = _m(ictx.in_(op, "X"))
    _xshape(ictx, op, x)
    if x.shape is None:
        ictx.out(op, "Out", VarMeta(None, x.dtype))
        return
    axis = op.attr("axis", 1)
    lead = prod(x.shape[:axis])
    ictx.out(op, "Out", VarMeta((lead, prod(x.shape) // lead), x.dtype))


@register_shape("flatten_contiguous_range")
def _shape_flatten_range(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    start = op.attr("start_axis", 1)
    stop = op.attr("stop_axis", -1) % len(x.shape)
    mid = prod(x.shape[start:stop + 1])
    ictx.out(
        op, "Out",
        VarMeta(tuple(x.shape[:start]) + (mid,) + tuple(x.shape[stop + 1:]),
                x.dtype),
    )


@register_shape("squeeze", "squeeze2")
def _shape_squeeze(ictx, op):
    x = _m(ictx.in_(op, "X"))
    _xshape(ictx, op, x)
    if x.shape is None:
        ictx.out(op, "Out", VarMeta(None, x.dtype))
        return
    axes = op.attr("axes", [])
    if axes:
        drop = {a % len(x.shape) for a in axes}
        bad = [a for a in drop if x.shape[a] != 1]
        if bad:
            raise InferError(f"squeeze of non-1 dims {bad} in {x.shape}")
        shape = tuple(d for i, d in enumerate(x.shape) if i not in drop)
    else:
        shape = tuple(d for d in x.shape if d != 1)
    ictx.out(op, "Out", VarMeta(shape, x.dtype))


@register_shape("unsqueeze", "unsqueeze2")
def _shape_unsqueeze(ictx, op):
    x = _m(ictx.in_(op, "X"))
    _xshape(ictx, op, x)
    if x.shape is None:
        ictx.out(op, "Out", VarMeta(None, x.dtype))
        return
    shape = list(x.shape)
    for a in sorted(op.attr("axes")):
        shape.insert(a % (len(shape) + 1), 1)
    ictx.out(op, "Out", VarMeta(tuple(shape), x.dtype))


@register_shape("concat")
def _shape_concat(ictx, op):
    if op.input("AxisTensor"):
        raise Unknown()  # value-dependent axis
    metas = [_m(m) for m in ictx.ins(op, "X")]
    dt = _promote(*[m.dtype for m in metas]) if metas else None
    if not all(_known(m) for m in metas):
        ictx.out(op, "Out", VarMeta(None, dt))
        return
    axis = op.attr("axis", 0) % len(metas[0].shape)
    shape = list(metas[0].shape)
    shape[axis] = sum(m.shape[axis] for m in metas)
    for m in metas[1:]:
        for i, (a, b) in enumerate(zip(metas[0].shape, m.shape)):
            if i != axis and a != b:
                raise InferError(
                    f"concat dim {i} mismatch: {metas[0].shape} vs {m.shape}"
                )
    ictx.out(op, "Out", VarMeta(tuple(shape), dt))


@register_shape("split")
def _shape_split(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    axis = op.attr("axis", 0) % len(x.shape)
    sections = op.attr("sections", [])
    outs = op.output("Out")
    if sections:
        sizes = list(sections)
    else:
        num = op.attr("num", 0) or len(outs)
        if x.shape[axis] % num != 0:
            raise InferError(
                f"split {x.shape} into {num} along axis {axis}"
            )
        sizes = [x.shape[axis] // num] * num
    for i, s in enumerate(sizes):
        shape = list(x.shape)
        shape[axis] = s
        ictx.out(op, "Out", VarMeta(tuple(shape), x.dtype), idx=i)


@register_shape("stack")
def _shape_stack(ictx, op):
    metas = [_m(m) for m in ictx.ins(op, "X")]
    dt = _promote(*[m.dtype for m in metas]) if metas else None
    if not all(_known(m) for m in metas):
        ictx.out(op, "Y", VarMeta(None, dt))
        return
    shape = list(metas[0].shape)
    axis = op.attr("axis", 0) % (len(shape) + 1)
    shape.insert(axis, len(metas))
    ictx.out(op, "Y", VarMeta(tuple(shape), dt))


@register_shape("expand")
def _shape_expand(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    times = op.attr("expand_times")
    ictx.out(
        op, "Out",
        VarMeta(tuple(d * t for d, t in zip(x.shape, times)), x.dtype),
    )


@register_shape("tile")
def _shape_tile(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    reps = list(op.attr("repeat_times"))
    shape = list(x.shape)
    if len(reps) < len(shape):
        reps = [1] * (len(shape) - len(reps)) + reps
    else:
        shape = [1] * (len(reps) - len(shape)) + shape
    ictx.out(
        op, "Out",
        VarMeta(tuple(d * t for d, t in zip(shape, reps)), x.dtype),
    )


@register_shape("slice")
def _shape_slice(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "Input")))
    shape = list(x.shape)
    for a, s, e in zip(op.attr("axes"), op.attr("starts"), op.attr("ends")):
        dim = shape[a]
        s = s + dim if s < 0 else min(s, dim)
        e = e + dim if e < 0 else min(e, dim)
        shape[a] = max(e - s, 0)
    decrease = op.attr("decrease_axis", [])
    if decrease:
        shape = [d for i, d in enumerate(shape) if i not in decrease]
    ictx.out(op, "Out", VarMeta(tuple(shape), x.dtype))


@register_shape("cumsum")
def _shape_cumsum(ictx, op):
    x = _m(ictx.in_(op, "X"))
    dt = I32 if x.dtype in _SMALL_INTS else x.dtype
    if x.shape is None:
        ictx.out(op, "Out", VarMeta(None, dt))
    elif op.attr("flatten", False):
        ictx.out(op, "Out", VarMeta((prod(x.shape),), dt))
    else:
        ictx.out(op, "Out", VarMeta(x.shape, dt))


# ---------------------------------------------------------------------------
# gather / embedding
# ---------------------------------------------------------------------------


def _squeeze_trailing_1(shape):
    if len(shape) >= 2 and shape[-1] == 1:
        return tuple(shape[:-1])
    return tuple(shape)


@register_shape("gather")
def _shape_gather(ictx, op):
    x, idx = ictx.require(_m(ictx.in_(op, "X")), _m(ictx.in_(op, "Index")))
    ishape = tuple(idx.shape)
    if len(ishape) == 2 and ishape[1] == 1:
        ishape = ishape[:1]
    axis = op.attr("overwrite_axis", 0)
    shape = tuple(x.shape[:axis]) + ishape + tuple(x.shape[axis + 1:])
    ictx.out(op, "Out", VarMeta(shape, x.dtype))


@register_shape("gather_nd")
def _shape_gather_nd(ictx, op):
    x, idx = ictx.require(_m(ictx.in_(op, "X")), _m(ictx.in_(op, "Index")))
    nd = idx.shape[-1]
    ictx.out(
        op, "Out",
        VarMeta(tuple(idx.shape[:-1]) + tuple(x.shape[nd:]), x.dtype),
    )


@register_shape("lookup_table", "lookup_table_v2")
def _shape_lookup_table(ictx, op):
    w = _m(ictx.in_(op, "W"))
    ids = _m(ictx.in_(op, "Ids"))
    if not _known(w, ids):
        ictx.out(op, "Out", VarMeta(None, w.dtype))
        return
    ishape = _squeeze_trailing_1(ids.shape)
    ictx.out(op, "Out", VarMeta(ishape + tuple(w.shape[1:]), w.dtype))


@register_shape("embedding_bag")
def _shape_embedding_bag(ictx, op):
    w, ids = ictx.require(_m(ictx.in_(op, "W")), _m(ictx.in_(op, "Ids")))
    ictx.out(
        op, "Out",
        VarMeta((ids.shape[0],) + tuple(w.shape[1:]), w.dtype),
    )


@register_shape("one_hot", "one_hot_v2")
def _shape_one_hot(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    ishape = _squeeze_trailing_1(x.shape)
    ictx.out(op, "Out", VarMeta(ishape + (op.attr("depth"),), F32))


@register_shape("index_select")
def _shape_index_select(ictx, op):
    x, idx = ictx.require(_m(ictx.in_(op, "X")), _m(ictx.in_(op, "Index")))
    axis = op.attr("dim", 0)
    shape = list(x.shape)
    shape[axis] = prod(idx.shape)
    ictx.out(op, "Out", VarMeta(tuple(shape), x.dtype))


@register_shape("scatter", "scatter_nd_add")
def _shape_scatter(ictx, op):
    ictx.out(op, "Out", _m(ictx.in_(op, "X")))


# ---------------------------------------------------------------------------
# creation ops
# ---------------------------------------------------------------------------


@register_shape("fill_constant")
def _shape_fill_constant(ictx, op):
    ictx.out(
        op, "Out",
        VarMeta(tuple(op.attr("shape", [1])),
                lowered_dtype(op.attr("dtype", "float32"))),
    )


@register_shape("fill_constant_batch_size_like")
def _shape_fill_bsl(ictx, op):
    dt = lowered_dtype(op.attr("dtype", "float32"))
    ref = _m(ictx.in_(op, "Input"))
    if ref.shape is None:
        ictx.out(op, "Out", VarMeta(None, dt))
        return
    shape = list(op.attr("shape"))
    shape[op.attr("output_dim_idx", 0)] = ref.shape[op.attr("input_dim_idx", 0)]
    ictx.out(op, "Out", VarMeta(tuple(shape), dt))


@register_shape("assign_value")
def _shape_assign_value(ictx, op):
    ictx.out(
        op, "Out",
        VarMeta(tuple(op.attr("shape")),
                lowered_dtype(op.attr("dtype", "float32"))),
    )


@register_shape("shape")
def _shape_shape(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "Input")))
    ictx.out(op, "Out", VarMeta((len(x.shape),), I32))


@register_shape("eye")
def _shape_eye(ictx, op):
    n = op.attr("num_rows")
    m = op.attr("num_columns", None) or n
    ictx.out(
        op, "Out", VarMeta((n, m), lowered_dtype(op.attr("dtype", "float32")))
    )


@register_shape("arg_max", "arg_min")
def _shape_argminmax(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    axis = op.attr("axis", -1) % len(x.shape)
    shape = tuple(d for i, d in enumerate(x.shape) if i != axis)
    ictx.out(
        op, "Out",
        VarMeta(shape, lowered_dtype(op.attr("out_dtype", "int64"))),
    )


@register_shape("top_k")
def _shape_top_k(ictx, op):
    if op.input("K"):
        raise Unknown()  # value-dependent k
    x = ictx.require(_m(ictx.in_(op, "X")))
    shape = tuple(x.shape[:-1]) + (op.attr("k", 1),)
    ictx.out(op, "Out", VarMeta(shape, x.dtype))
    ictx.out(op, "Indices", VarMeta(shape, I32))


@register_shape("argsort")
def _shape_argsort(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    ictx.out(op, "Out", VarMeta(x.shape, x.dtype))
    ictx.out(op, "Indices", VarMeta(x.shape, I32))


# ---------------------------------------------------------------------------
# conv / pool / norm
# ---------------------------------------------------------------------------


def _conv_pad_pairs(padding, ndim):
    if isinstance(padding, str):
        return padding.upper()
    if isinstance(padding, int):
        padding = [padding] * ndim
    if len(padding) == ndim:
        return [(p, p) for p in padding]
    if len(padding) == 2 * ndim:
        return [(padding[2 * i], padding[2 * i + 1]) for i in range(ndim)]
    raise InferError(f"bad conv padding: {padding}")


@register_shape("conv2d", "depthwise_conv2d")
def _shape_conv2d(ictx, op):
    x = _m(ictx.in_(op, "Input"))
    w = _m(ictx.in_(op, "Filter"))
    dt = _promote(x.dtype, w.dtype)
    if not _known(x, w):
        ictx.out(op, "Output", VarMeta(None, dt))
        return
    strides = op.attr("strides", [1, 1])
    pad = _conv_pad_pairs(op.attr("paddings", [0, 0]), 2)
    dil = op.attr("dilations", [1, 1])
    nhwc = op.attr("data_format", "NCHW") == "NHWC"
    n = x.shape[0]
    h, wd = (x.shape[1], x.shape[2]) if nhwc else (x.shape[2], x.shape[3])
    o = w.shape[0]
    k_eff = [(w.shape[2] - 1) * dil[0] + 1, (w.shape[3] - 1) * dil[1] + 1]
    oh = conv_out_dim(h, k_eff[0], pad if isinstance(pad, str) else pad[0],
                      strides[0])
    ow = conv_out_dim(wd, k_eff[1], pad if isinstance(pad, str) else pad[1],
                      strides[1])
    shape = (n, oh, ow, o) if nhwc else (n, o, oh, ow)
    ictx.out(op, "Output", VarMeta(shape, dt))


@register_shape("conv2d_transpose", "depthwise_conv2d_transpose")
def _shape_conv2d_transpose(ictx, op):
    x, w = ictx.require(_m(ictx.in_(op, "Input")), _m(ictx.in_(op, "Filter")))
    pad = _conv_pad_pairs(op.attr("paddings", [0, 0]), 2)
    if isinstance(pad, str):
        raise Unknown()  # SAME/VALID transpose output needs lax's rule
    strides = op.attr("strides", [1, 1])
    dil = op.attr("dilations", [1, 1])
    groups = op.attr("groups", 1) or 1
    n, _, h, wd = x.shape
    kh_eff = (w.shape[2] - 1) * dil[0] + 1
    kw_eff = (w.shape[3] - 1) * dil[1] + 1
    oh = (h - 1) * strides[0] - (pad[0][0] + pad[0][1]) + kh_eff
    ow = (wd - 1) * strides[1] - (pad[1][0] + pad[1][1]) + kw_eff
    out_c = w.shape[1] * groups
    ictx.out(
        op, "Output",
        VarMeta((n, out_c, oh, ow), _promote(x.dtype, w.dtype)),
    )


@register_shape("pool2d")
def _shape_pool2d(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    nhwc = op.attr("data_format", "NCHW") == "NHWC"
    ksize = list(op.attr("ksize", [2, 2]))
    adaptive = op.attr("adaptive", False)
    n = x.shape[0]
    c = x.shape[3] if nhwc else x.shape[1]
    h, w = (x.shape[1], x.shape[2]) if nhwc else (x.shape[2], x.shape[3])
    if op.attr("global_pooling", False) or (adaptive and ksize == [1, 1]):
        oh = ow = 1
    elif adaptive:
        oh, ow = ksize
    else:
        strides = list(op.attr("strides", ksize))
        pads = _conv_pad_pairs(op.attr("paddings", [0, 0]), 2)
        ceil_mode = op.attr("ceil_mode", False)
        oh = pool_out_dim(h, ksize[0],
                          pads if isinstance(pads, str) else pads[0],
                          strides[0], ceil_mode)
        ow = pool_out_dim(w, ksize[1],
                          pads if isinstance(pads, str) else pads[1],
                          strides[1], ceil_mode)
    shape = (n, oh, ow, c) if nhwc else (n, c, oh, ow)
    ictx.out(op, "Out", VarMeta(shape, x.dtype))


@register_shape("batch_norm")
def _shape_batch_norm(ictx, op):
    x = _m(ictx.in_(op, "X"))
    ictx.out(op, "Y", x)
    if op.attr("use_global_stats", False) or ictx.op_is_test(op):
        return  # running-stat outputs are not written in test mode
    if x.shape is None:
        meta_c = VarMeta(None, F32)
    else:
        layout = op.attr("data_layout", "NCHW")
        ch = (
            x.shape[-1] if layout != "NCHW" else x.shape[1]
        )
        meta_c = VarMeta((ch,), F32)
    for slot in ("MeanOut", "VarianceOut", "SavedMean", "SavedVariance"):
        ictx.out(op, slot, meta_c)


@register_shape("layer_norm")
def _shape_layer_norm(ictx, op):
    x = _m(ictx.in_(op, "X"))
    ictx.out(op, "Y", x)
    lead = None if x.shape is None else tuple(
        x.shape[:op.attr("begin_norm_axis", 1)]
    )
    ictx.out(op, "Mean", VarMeta(lead, F32))
    ictx.out(op, "Variance", VarMeta(lead, F32))


@register_shape("dropout")
def _shape_dropout(ictx, op):
    x = _m(ictx.in_(op, "X"))
    ictx.out(op, "Out", x)
    ictx.out(op, "Mask", VarMeta(x.shape, U8))


@register_shape("fused_multihead_attention")
def _shape_fused_mha(ictx, op):
    q, v = _m(ictx.in_(op, "Q")), _m(ictx.in_(op, "V"))
    if q.shape is not None and v.shape is not None:
        q = VarMeta(tuple(q.shape[:-1]) + (v.shape[-1],), q.dtype)
    ictx.out(op, "Out", q)
    if op.output("Lse"):
        if q.shape is None:
            ictx.out(op, "Lse", VarMeta(None, F32))
        else:
            b, s, h = q.shape[:3]
            if op.attr("layout", "bhsd") != "bshd":
                s, h = h, s
            ictx.out(op, "Lse", VarMeta((b, h, s), F32))
    for slot, t in (("QPrepared", "Q"), ("KPrepared", "K")):
        if op.output(slot):
            t = _m(ictx.in_(op, t))
            shape = t.shape
            if shape is not None and op.attr("layout", "bhsd") == "bshd":
                shape = (shape[0], shape[2], shape[1], shape[3])
            ictx.out(op, slot, VarMeta(shape, q.dtype))


@register_shape("rms_norm")
def _shape_rms_norm(ictx, op):
    ictx.out(op, "Y", _m(ictx.in_(op, "X")))


@register_shape("rotary_embedding")
def _shape_rotary_embedding(ictx, op):
    ictx.out(op, "Out", _m(ictx.in_(op, "X")))


@register_shape("short_conv1d")
def _shape_short_conv1d(ictx, op):
    ictx.out(op, "Out", _m(ictx.in_(op, "X")))


@register_shape("selective_scan")
def _shape_selective_scan(ictx, op):
    from .ssm_ops import n_chunks

    x, a = ictx.in_(op, "X"), ictx.in_(op, "A")
    ictx.out(op, "Y", _m(x))
    if _known(x, a):
        b, s, d = x.shape
        ictx.out(op, "Starts",
                 VarMeta((n_chunks(s, d, a.shape[1]), b, a.shape[1], d),
                         "float32"))


@register_shape("selective_scan_grad")
def _shape_selective_scan_grad(ictx, op):
    for slot in ("X", "Delta", "A", "B", "C", "D"):
        ictx.out(op, "IGRAD_" + slot, _m(ictx.in_(op, slot)))


@register_shape("ssd_scan")
def _shape_ssd_scan(ictx, op):
    from .ssm_ops import ssd_n_chunks

    x, a, bm = ictx.in_(op, "X"), ictx.in_(op, "ALog"), ictx.in_(op, "B")
    ictx.out(op, "Y", _m(x))
    if _known(x, a, bm):
        b, s, d = x.shape
        heads = a.shape[0]
        ictx.out(op, "Starts", VarMeta(
            (ssd_n_chunks(s, op.attr("chunk_size", 128)), b, heads,
             d // heads, bm.shape[2] // op.attr("n_groups", 1)), "float32"))


@register_shape("ssd_scan_grad")
def _shape_ssd_scan_grad(ictx, op):
    for slot in ("X", "Dt", "DtBias", "ALog", "B", "C", "D"):
        ictx.out(op, "IGRAD_" + slot, _m(ictx.in_(op, slot)))


@register_shape("kda_attention")
def _shape_kda_attention(ictx, op):
    ictx.out(op, "Out", _m(ictx.in_(op, "V")))


@register_shape("moe_experts")
def _shape_moe_experts(ictx, op):
    ictx.out(op, "Out", _m(ictx.in_(
        op, "XExperts" if op.input("XExperts") else "X")))
    ictx.out(op, "Load", VarMeta((op.attr("experts_held"),), "int32"))


# ---------------------------------------------------------------------------
# losses / metrics
# ---------------------------------------------------------------------------


@register_shape("softmax_with_cross_entropy")
def _shape_swce(ictx, op):
    logits = ictx.require(_m(ictx.in_(op, "Logits")))
    axis = op.attr("axis", -1) % len(logits.shape)
    if axis != len(logits.shape) - 1:
        raise InferError("softmax_with_cross_entropy: axis must be last")
    ictx.out(op, "Softmax", logits)
    ictx.out(
        op, "Loss", VarMeta(tuple(logits.shape[:-1]) + (1,), logits.dtype)
    )


@register_shape("cross_entropy", "cross_entropy2")
def _shape_cross_entropy(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    ictx.out(op, "Y", VarMeta(tuple(x.shape[:-1]) + (1,), x.dtype))


@register_shape("sigmoid_cross_entropy_with_logits", "log_loss")
def _shape_sigmoid_ce(ictx, op):
    slot = "Predicted" if op.type == "log_loss" else "X"
    ictx.out(op, "Out", _m(ictx.in_(op, slot)))


@register_shape("square_error_cost")
def _shape_square_error(ictx, op):
    x, y = _m(ictx.in_(op, "X")), _m(ictx.in_(op, "Y"))
    shape = None
    if _known(x, y):
        shape = broadcast_shapes(x.shape, y.shape)
    ictx.out(op, "Out", VarMeta(shape, _promote(x.dtype, y.dtype)))


@register_shape("accuracy")
def _shape_accuracy(ictx, op):
    ictx.out(op, "Accuracy", VarMeta((1,), F32))
    ictx.out(op, "Correct", VarMeta((1,), I32))
    ictx.out(op, "Total", VarMeta((1,), I32))


@register_shape("auc")
def _shape_auc(ictx, op):
    ictx.out(op, "AUC", VarMeta((1,), F32))
    if op.output("BatchAUC"):
        ictx.out(op, "BatchAUC", VarMeta((1,), F32))
    for in_slot, out_slot in (("StatPos", "StatPosOut"),
                              ("StatNeg", "StatNegOut")):
        m = _m(ictx.in_(op, in_slot))
        ictx.out(op, out_slot, VarMeta(m.shape, F32))


# ---------------------------------------------------------------------------
# optimizer updates: every <Slot>Out mirrors its <Slot> input
# ---------------------------------------------------------------------------


def _shape_optimizer_update(ictx, op):
    for out_slot, names in op.outputs.items():
        if not out_slot.endswith("Out"):
            continue
        src = out_slot[:-3]
        src_names = op.inputs.get(src, ())
        for i, n in enumerate(names):
            if n and i < len(src_names) and src_names[i]:
                meta = ictx.meta(src_names[i])
                if meta is not None:
                    ictx.env[n] = meta


register_shape(
    "sgd", "momentum", "lars_momentum", "adam", "adamw", "adamax",
    "adagrad", "adadelta", "decayed_adagrad", "rmsprop", "ftrl", "lamb",
    "proximal_gd", "proximal_adagrad",
    "fused_sgd", "fused_momentum", "fused_adam", "fused_adamw",
    "fused_lamb",
)(_shape_optimizer_update)


@register_shape("clip_by_norm")
def _shape_clip_by_norm(ictx, op):
    ictx.out(op, "Out", _m(ictx.in_(op, "X")))


@register_shape("check_finite_and_unscale")
def _shape_check_finite(ictx, op):
    for i, m in enumerate(ictx.ins(op, "X")):
        ictx.out(op, "Out", _m(m), idx=i)
    ictx.out(op, "FoundInfinite", VarMeta((1,), BOOL))


# ---------------------------------------------------------------------------
# round-16 ratchet shrink: ops the autoshard planner's cost extraction
# can meet on real train programs (AMP loss scaling, ModelAverage
# accumulators, norm/pad/random families) — planning must never hit an
# unknown-shape state var, so each gets its lowering's exact static
# mirror
# ---------------------------------------------------------------------------


@register_shape("increment")
def _shape_increment(ictx, op):
    # x + asarray(step, dtype=x.dtype): dtype preserved (int counters)
    ictx.out(op, "Out", _m(ictx.in_(op, "X")))


@register_shape("size")
def _shape_size(ictx, op):
    ictx.out(op, "Out", VarMeta((), I32))


@register_shape("maximum", "minimum", "minus")
def _shape_binary_numpy_broadcast(ictx, op):
    # jnp.maximum/minimum/subtract: numpy broadcast, jnp promotion
    x = ictx.require(_m(ictx.in_(op, "X")))
    y = ictx.require(_m(ictx.in_(op, "Y")))
    ictx.out(op, "Out", VarMeta(
        broadcast_shapes(x.shape, y.shape), _promote(x.dtype, y.dtype)
    ))


@register_shape("where")
def _shape_where(ictx, op):
    c = ictx.require(_m(ictx.in_(op, "Condition")))
    x = ictx.require(_m(ictx.in_(op, "X")))
    y = ictx.require(_m(ictx.in_(op, "Y")))
    ictx.out(op, "Out", VarMeta(
        broadcast_shapes(c.shape, x.shape, y.shape),
        _promote(x.dtype, y.dtype),
    ))


@register_shape("logsumexp")
def _shape_logsumexp(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    dims = op.attr("dim", None)
    keep = op.attr("keep_dim", False)
    if op.attr("reduce_all", False) or dims is None:
        shape = tuple(1 for _ in x.shape) if keep else (1,)
    else:
        axes = {d % len(x.shape) for d in tuple(dims)}
        if keep:
            shape = tuple(1 if i in axes else d
                          for i, d in enumerate(x.shape))
        else:
            shape = tuple(d for i, d in enumerate(x.shape)
                          if i not in axes)
            if not shape:
                shape = (1,)  # lowering reshapes rank-0 to [1]
    ictx.out(op, "Out", VarMeta(
        shape, x.dtype if is_float(x.dtype) else F32
    ))


@register_shape("p_norm")
def _shape_p_norm(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    axis = op.attr("axis", None)
    keep = op.attr("keepdim", False)
    if axis is None:
        shape = tuple(1 for _ in x.shape) if keep else ()
    else:
        a = axis % len(x.shape)
        shape = (tuple(1 if i == a else d for i, d in enumerate(x.shape))
                 if keep else
                 tuple(d for i, d in enumerate(x.shape) if i != a))
    ictx.out(op, "Out", VarMeta(
        shape, x.dtype if is_float(x.dtype) else F32
    ))


@register_shape("unstack")
def _shape_unstack(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    axis = op.attr("axis", 0) % len(x.shape)
    out = tuple(d for i, d in enumerate(x.shape) if i != axis)
    for i in range(len(op.output("Y"))):
        ictx.out(op, "Y", VarMeta(out, x.dtype), idx=i)


@register_shape("expand_as")
def _shape_expand_as(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    t = ictx.require(_m(ictx.in_(op, "target_tensor")))
    # lowering tiles by t_i // x_i (exact when divisible, floor when not)
    ictx.out(op, "Out", VarMeta(
        tuple(xd * (td // xd) for xd, td in zip(x.shape, t.shape)),
        x.dtype,
    ))


@register_shape("pad")
def _shape_pad(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    p = op.attr("paddings")
    ictx.out(op, "Out", VarMeta(
        tuple(d + p[2 * i] + p[2 * i + 1]
              for i, d in enumerate(x.shape)),
        x.dtype,
    ))


@register_shape("pad2d")
def _shape_pad2d(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))  # NCHW
    p = op.attr("paddings", [0, 0, 0, 0])  # t,b,l,r
    n, c, h, w = x.shape
    ictx.out(op, "Out", VarMeta(
        (n, c, h + p[0] + p[1], w + p[2] + p[3]), x.dtype
    ))


@register_shape("roll", "flip", "tril_triu")
def _shape_same_as_x(ictx, op):
    ictx.out(op, "Out", ictx.require(_m(ictx.in_(op, "X"))))


@register_shape("uniform_random")
def _shape_uniform_random(ictx, op):
    if op.input("ShapeTensor"):
        raise Unknown()  # shape is a runtime tensor value
    ictx.out(op, "Out", VarMeta(
        tuple(op.attr("shape")),
        lowered_dtype(op.attr("dtype", "float32")),
    ))


@register_shape("gaussian_random", "truncated_gaussian_random")
def _shape_gaussian_random(ictx, op):
    ictx.out(op, "Out", VarMeta(
        tuple(op.attr("shape")),
        lowered_dtype(op.attr("dtype", "float32")),
    ))


@register_shape("randint")
def _shape_randint(ictx, op):
    ictx.out(op, "Out", VarMeta(
        tuple(op.attr("shape")),
        lowered_dtype(op.attr("dtype", "int64")),
    ))


@register_shape("randperm")
def _shape_randperm(ictx, op):
    ictx.out(op, "Out", VarMeta(
        (int(op.attr("n")),), lowered_dtype(op.attr("dtype", "int64"))
    ))


@register_shape("sequence_mask")
def _shape_sequence_mask(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    maxlen = op.attr("maxlen", None)
    if maxlen is None or maxlen < 0:
        raise InferError(
            "sequence_mask requires an explicit maxlen on TPU (static "
            "shapes)"
        )
    dt = (F32 if str(op.attr("out_dtype", "int64")).startswith("float")
          else I32)
    ictx.out(op, "Y", VarMeta((prod(x.shape), int(maxlen)), dt))


@register_shape("group_norm")
def _shape_group_norm(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))  # NCHW
    groups = op.attr("groups", 32)
    ictx.out(op, "Y", x)
    ictx.out(op, "Mean", VarMeta((x.shape[0], groups), x.dtype))
    ictx.out(op, "Variance", VarMeta((x.shape[0], groups), x.dtype))


@register_shape("instance_norm")
def _shape_instance_norm(ictx, op):
    ictx.out(op, "Y", ictx.require(_m(ictx.in_(op, "X"))))


@register_shape("l2_normalize")
def _shape_l2_normalize(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    axis = op.attr("axis", -1) % len(x.shape)
    ictx.out(op, "Out", x)
    ictx.out(op, "Norm", VarMeta(
        tuple(1 if i == axis else d for i, d in enumerate(x.shape)),
        x.dtype,
    ))


@register_shape("norm")
def _shape_norm(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    axis = op.attr("axis", 1) % len(x.shape)
    ictx.out(op, "Out", x)
    ictx.out(op, "Norm", VarMeta(
        tuple(1 if i == axis else d for i, d in enumerate(x.shape)),
        x.dtype,
    ))


@register_shape("squared_l2_distance")
def _shape_squared_l2_distance(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    y = ictx.require(_m(ictx.in_(op, "Y")))
    sub = broadcast_shapes(x.shape, y.shape)
    dt = _promote(x.dtype, y.dtype)
    ictx.out(op, "Out", VarMeta(sub[:-1] + (1,), dt))
    ictx.out(op, "sub_result", VarMeta(sub, dt))


@register_shape("l1_norm")
def _shape_l1_norm(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    ictx.out(op, "Out", VarMeta(
        (1,), I32 if x.dtype in _SMALL_INTS else x.dtype
    ))


@register_shape("kldiv_loss")
def _shape_kldiv_loss(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    t = ictx.require(_m(ictx.in_(op, "Target")))
    dt = _promote(x.dtype, t.dtype)
    if op.attr("reduction", "mean") in ("mean", "sum", "batchmean"):
        ictx.out(op, "Loss", VarMeta((1,), dt))
    else:
        ictx.out(op, "Loss", VarMeta(
            broadcast_shapes(x.shape, t.shape), dt
        ))


@register_shape("smooth_l1_loss")
def _shape_smooth_l1_loss(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    y = ictx.require(_m(ictx.in_(op, "Y")))
    d = broadcast_shapes(x.shape, y.shape)
    dt = _promote(x.dtype, y.dtype)
    ictx.out(op, "Out", VarMeta((d[0], 1), dt))
    ictx.out(op, "Diff", VarMeta(d, dt))


@register_shape("huber_loss")
def _shape_huber_loss(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    y = ictx.require(_m(ictx.in_(op, "Y")))
    r = broadcast_shapes(x.shape, y.shape)
    dt = _promote(x.dtype, y.dtype)
    ictx.out(op, "Out", VarMeta(r, dt))
    ictx.out(op, "Residual", VarMeta(r, dt))


@register_shape("average_accumulates")
def _shape_average_accumulates(ictx, op):
    # windowed ModelAverage sums keep their input metas; the three
    # counters are [1]-shaped int32 (the lowering's reshape(1))
    for slot in ("sum_1", "sum_2", "sum_3"):
        ictx.out(op, f"out_{slot}",
                 ictx.require(_m(ictx.in_(op, f"in_{slot}"))))
    for slot in ("num_accumulates", "old_num_accumulates",
                 "num_updates"):
        ictx.out(op, f"out_{slot}", VarMeta((1,), I32))


@register_shape("update_loss_scaling")
def _shape_update_loss_scaling(ictx, op):
    ictx.out(op, "LossScalingOut", VarMeta((1,), F32))
    ictx.out(op, "OutGoodSteps", VarMeta((1,), I32))
    ictx.out(op, "OutBadSteps", VarMeta((1,), I32))


# ---------------------------------------------------------------------------
# CTR family (ctr_ops.py / loss_ops.py / misc_ops.py round-18 additions)
# ---------------------------------------------------------------------------


@register_shape("cvm")
def _shape_cvm(ictx, op):
    # use_cvm=True rewrites the show/click columns in place; False
    # drops them (cvm_op.h)
    x = ictx.require(_m(ictx.in_(op, "X")))
    if op.attr("use_cvm", True):
        ictx.out(op, "Y", x)
    else:
        ictx.out(op, "Y", VarMeta((x.shape[0], x.shape[1] - 2), x.dtype))


@register_shape("data_norm")
def _shape_data_norm(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    bsum = ictx.require(_m(ictx.in_(op, "BatchSum")))
    bsize = ictx.require(_m(ictx.in_(op, "BatchSize")))
    stat = broadcast_shapes(bsum.shape, bsize.shape)
    ictx.out(op, "Y", x)
    # means/scales come off the f32-cast running stats
    ictx.out(op, "Means", VarMeta(stat, F32))
    ictx.out(op, "Scales", VarMeta(stat, F32))


@register_shape("hinge_loss")
def _shape_hinge_loss(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "Logits")))
    y = ictx.require(_m(ictx.in_(op, "Labels")))
    ictx.out(op, "Loss", VarMeta(
        broadcast_shapes(x.shape, y.shape), _promote(x.dtype, y.dtype)
    ))


@register_shape("bpr_loss")
def _shape_bpr_loss(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    ictx.out(op, "Y", VarMeta((x.shape[0], 1), x.dtype))


@register_shape("cos_sim")
def _shape_cos_sim(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    y = ictx.require(_m(ictx.in_(op, "Y")))
    ictx.out(op, "Out", VarMeta(
        x.shape[:-1] + (1,), _promote(x.dtype, y.dtype)
    ))
    if op.output("XNorm"):
        ictx.out(op, "XNorm", VarMeta(x.shape[:-1] + (1,), x.dtype))
    if op.output("YNorm"):
        ictx.out(op, "YNorm", VarMeta(y.shape[:-1] + (1,), y.dtype))


@register_shape("is_empty")
def _shape_is_empty(ictx, op):
    ictx.require(_m(ictx.in_(op, "X")))
    ictx.out(op, "Out", VarMeta((1,), BOOL))


@register_shape("fill_zeros_like2")
def _shape_fill_zeros_like2(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    dt = op.attr("dtype")
    ictx.out(op, "Out", VarMeta(
        x.shape, lowered_dtype(dt) if isinstance(dt, str) else x.dtype
    ))


@register_shape("filter_by_instag")
def _shape_filter_by_instag(ictx, op):
    ins = ictx.require(_m(ictx.in_(op, "Ins")))
    n = ins.shape[0]
    ictx.out(op, "Out", ins)  # static-shape form zeroes, never drops
    ictx.out(op, "LossWeight", VarMeta((n, 1), F32))
    if op.output("IndexMap"):
        ictx.out(op, "IndexMap", VarMeta((n, 2), I32))


@register_shape("index_sample")
def _shape_index_sample(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    index = ictx.require(_m(ictx.in_(op, "Index")))
    ictx.out(op, "Out", VarMeta(index.shape, x.dtype))


@register_shape("diag")
def _shape_diag(ictx, op):
    d = ictx.require(_m(ictx.in_(op, "Diagonal")))
    n = d.shape[0]
    ictx.out(op, "Out", VarMeta((n, n), d.dtype))


@register_shape("hash")
def _shape_hash(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    num_hash = int(op.attr("num_hash", 1))
    ictx.out(op, "Out", VarMeta(
        (x.shape[0], num_hash, 1), lowered_dtype("int64")
    ))


# ---------------------------------------------------------------------------
# round 20: scan-blocked transformer-body stragglers
# ---------------------------------------------------------------------------

# elementwise rearrangements whose lowerings end in .astype(x.dtype) or
# slice/concat of X itself: Out mirrors X exactly
_PASSTHROUGH_R20 = (
    "temporal_shift", "shuffle_channel", "shard_index", "reverse",
    "sequence_softmax", "lrn",
)


@register_shape(*_PASSTHROUGH_R20)
def _shape_passthrough_r20(ictx, op):
    ictx.out(op, "Out", ictx.require(_m(ictx.in_(op, "X"))))


@register_shape("add_position_encoding")
def _shape_add_position_encoding(ictx, op):
    # alpha (python float) * x: jnp weak promotion floats an int input
    x = ictx.require(_m(ictx.in_(op, "X")))
    dt = x.dtype if is_float(x.dtype) else _promote(x.dtype, F32)
    ictx.out(op, "Out", VarMeta(x.shape, dt))


@register_shape("sequence_reverse")
def _shape_sequence_reverse(ictx, op):
    # take_along_axis over the time axis: Y mirrors X
    ictx.out(op, "Y", ictx.require(_m(ictx.in_(op, "X"))))


@register_shape("pad_constant_like")
def _shape_pad_constant_like(ictx, op):
    # Y padded up to X's extent; values (and dtype) come from Y
    x = ictx.require(_m(ictx.in_(op, "X")))
    y = ictx.require(_m(ictx.in_(op, "Y")))
    ictx.out(op, "Out", VarMeta(x.shape, y.dtype))


@register_shape("maxout")
def _shape_maxout(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    g = int(op.attr("groups"))
    ictx.out(op, "Out", VarMeta(
        (x.shape[0], x.shape[1] // g) + x.shape[2:], x.dtype
    ))


@register_shape("multiplex")
def _shape_multiplex(ictx, op):
    # Out[i] = X[Ids[i]][i]: row count follows the flattened Ids
    ids = ictx.require(_m(ictx.in_(op, "Ids")))
    xs = [ictx.require(_m(m)) for m in ictx.ins(op, "X")]
    ictx.out(op, "Out", VarMeta(
        (prod(ids.shape),) + xs[0].shape[1:],
        _promote(*[m.dtype for m in xs]),
    ))


@register_shape("strided_slice")
def _shape_strided_slice(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "Input")))
    shape = list(x.shape)
    for a, s, e, st in zip(op.attr("axes"), op.attr("starts"),
                           op.attr("ends"), op.attr("strides")):
        shape[a] = len(range(*slice(s, e, st).indices(x.shape[a])))
    ictx.out(op, "Out", VarMeta(tuple(shape), x.dtype))


@register_shape("space_to_depth")
def _shape_space_to_depth(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    b = int(op.attr("blocksize"))
    n, c, h, w = x.shape
    ictx.out(op, "Out", VarMeta((n, c * b * b, h // b, w // b), x.dtype))


@register_shape("pixel_shuffle")
def _shape_pixel_shuffle(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    r = int(op.attr("upscale_factor"))
    n, c, h, w = x.shape
    ictx.out(op, "Out", VarMeta((n, c // (r * r), h * r, w * r), x.dtype))


@register_shape("unfold")
def _shape_unfold(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    ks = op.attr("kernel_sizes")
    st = op.attr("strides", [1, 1])
    pd = op.attr("paddings", [0, 0, 0, 0])
    dl = op.attr("dilations", [1, 1])
    n, c, h, w = x.shape
    oh = conv_out_dim(h, dl[0] * (ks[0] - 1) + 1, (pd[0], pd[2]), st[0])
    ow = conv_out_dim(w, dl[1] * (ks[1] - 1) + 1, (pd[1], pd[3]), st[1])
    ictx.out(op, "Out", VarMeta((n, c * ks[0] * ks[1], oh * ow), x.dtype))


@register_shape("im2sequence")
def _shape_im2sequence(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    kh, kw = op.attr("kernels")
    st = op.attr("strides", [1, 1])
    pd = op.attr("paddings", [0, 0, 0, 0])
    n, c, h, w = x.shape
    oh = conv_out_dim(h, kh, (pd[0], pd[2]), st[0])
    ow = conv_out_dim(w, kw, (pd[1], pd[3]), st[1])
    ictx.out(op, "Out", VarMeta((n, oh * ow, c * kh * kw), x.dtype))


# ---------------------------------------------------------------------------
# round 21: ranking-loss / detection / sequence stragglers
# ---------------------------------------------------------------------------


@register_shape("rank_loss")
def _shape_rank_loss(ictx, op):
    label = ictx.require(_m(ictx.in_(op, "Label")))
    left = ictx.require(_m(ictx.in_(op, "Left")))
    right = ictx.require(_m(ictx.in_(op, "Right")))
    d = broadcast_shapes(left.shape, right.shape)
    ictx.out(op, "Out", VarMeta(
        broadcast_shapes(label.shape, d),
        _promote(label.dtype, left.dtype, right.dtype),
    ))


@register_shape("margin_rank_loss")
def _shape_margin_rank_loss(ictx, op):
    label = ictx.require(_m(ictx.in_(op, "Label")))
    x1 = ictx.require(_m(ictx.in_(op, "X1")))
    x2 = ictx.require(_m(ictx.in_(op, "X2")))
    d = broadcast_shapes(label.shape,
                         broadcast_shapes(x1.shape, x2.shape))
    ictx.out(op, "Out",
             VarMeta(d, _promote(label.dtype, x1.dtype, x2.dtype)))
    # Activated = 1[out>0] cast back to X1's dtype by the lowering
    ictx.out(op, "Activated", VarMeta(d, x1.dtype))


@register_shape("modified_huber_loss")
def _shape_modified_huber_loss(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    y = ictx.require(_m(ictx.in_(op, "Y")))
    d = broadcast_shapes(x.shape, y.shape)
    dt = _promote(x.dtype, y.dtype)
    ictx.out(op, "Out", VarMeta(d, dt))
    ictx.out(op, "IntermediateVal", VarMeta(d, dt))


@register_shape("teacher_student_sigmoid_loss")
def _shape_teacher_student_sigmoid_loss(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    label = ictx.require(_m(ictx.in_(op, "Label")))
    ictx.out(op, "Y", VarMeta(
        broadcast_shapes(x.shape, label.shape),
        _promote(x.dtype, label.dtype),
    ))


@register_shape("mean_iou")
def _shape_mean_iou(ictx, op):
    # outputs depend only on num_classes: [1] f32 mean, [K] i32
    # wrong/correct histograms (the lowering's astype(int32))
    k = int(op.attr("num_classes"))
    ictx.out(op, "OutMeanIou", VarMeta((1,), F32))
    ictx.out(op, "OutWrong", VarMeta((k,), I32))
    ictx.out(op, "OutCorrect", VarMeta((k,), I32))


@register_shape("crop")
def _shape_crop(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    y = _m(ictx.in_(op, "Y"))
    if op.input("Y"):
        shape = ictx.require(y).shape
    else:
        shape = tuple(int(s) for s in op.attr("shape"))
    ictx.out(op, "Out", VarMeta(shape, x.dtype))


@register_shape("affine_channel")
def _shape_affine_channel(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    scale = ictx.require(_m(ictx.in_(op, "Scale")))
    bias = ictx.require(_m(ictx.in_(op, "Bias")))
    ictx.out(op, "Out", VarMeta(
        x.shape, _promote(x.dtype, scale.dtype, bias.dtype)))


@register_shape("iou_similarity")
def _shape_iou_similarity(ictx, op):
    # [N, 4] x [P, 4] -> [N, P]; batched [B, G, 4] -> [B, G, P]
    x = ictx.require(_m(ictx.in_(op, "X")))
    y = ictx.require(_m(ictx.in_(op, "Y")))
    ictx.out(op, "Out", VarMeta(
        x.shape[:-1] + (y.shape[0],), _promote(x.dtype, y.dtype)))


@register_shape("sampling_id")
def _shape_sampling_id(ictx, op):
    # categorical over the last axis, cast int32 by the lowering
    x = ictx.require(_m(ictx.in_(op, "X")))
    ictx.out(op, "Out", VarMeta(x.shape[:-1], I32))


@register_shape("sequence_pad")
def _shape_sequence_pad(ictx, op):
    # dense convention: X is already padded; Length is the full time
    # dim replicated per row (the lowering's jnp.full(..., int32))
    x = ictx.require(_m(ictx.in_(op, "X")))
    ictx.out(op, "Out", x)
    ictx.out(op, "Length", VarMeta((x.shape[0],), I32))


@register_shape("sequence_concat")
def _shape_sequence_concat(ictx, op):
    # per-row concat along time then left-pack: [b, sum(t_i), ...]
    xs = [ictx.require(_m(m)) for m in ictx.ins(op, "X")]
    t = sum(m.shape[1] for m in xs)
    shape = (xs[0].shape[0], t) + xs[0].shape[2:]
    ictx.out(op, "Out",
             VarMeta(shape, _promote(*[m.dtype for m in xs])))
    ictx.out(op, "OutMask", VarMeta(shape[:2], F32))


@register_shape("shuffle_batch")
def _shape_shuffle_batch(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    ictx.out(op, "Out", x)
    ictx.out(op, "ShuffleIdx", VarMeta((x.shape[0],), I32))
    ictx.out(op, "SeedOut", VarMeta((1,), I32))


@register_shape("bilinear_tensor_product")
def _shape_bilinear_tensor_product(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    y = ictx.require(_m(ictx.in_(op, "Y")))
    w = ictx.require(_m(ictx.in_(op, "Weight")))
    ictx.out(op, "Out", VarMeta(
        (x.shape[0], w.shape[0]),
        _promote(x.dtype, y.dtype, w.dtype),
    ))


@register_shape("similarity_focus")
def _shape_similarity_focus(ictx, op):
    # a 0/1 focus mask broadcast back over the chosen axis, cast to
    # X's dtype: Out mirrors X exactly
    ictx.out(op, "Out", ictx.require(_m(ictx.in_(op, "X"))))


# ---------------------------------------------------------------------------
# vision / detection / batch-size-like tail (round 22)
# ---------------------------------------------------------------------------


@register_shape("affine_grid")
def _shape_affine_grid(ictx, op):
    theta = ictx.require(_m(ictx.in_(op, "Theta")))
    shape = list(op.attr("output_shape") or [])
    if not shape:
        # OutputShape tensor path: the grid size is value-dependent
        ictx.out(op, "Output", VarMeta(None, theta.dtype))
        return
    n, _, h, w = shape
    ictx.out(op, "Output", VarMeta((n, h, w, 2), theta.dtype))


@register_shape("grid_sampler")
def _shape_grid_sampler(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    grid = ictx.require(_m(ictx.in_(op, "Grid")))
    ictx.out(op, "Output", VarMeta(
        (x.shape[0], x.shape[1], grid.shape[1], grid.shape[2]), x.dtype,
    ))


@register_shape("spectral_norm")
def _shape_spectral_norm(ictx, op):
    ictx.out(op, "Out", _m(ictx.in_(op, "Weight")))


@register_shape("pool3d")
def _shape_pool3d(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))  # NCDHW
    ksize = list(op.attr("ksize", [2, 2, 2]))
    gp = op.attr("global_pooling", False)
    if gp:
        ksize = list(x.shape[2:])
    n, c = x.shape[0], x.shape[1]
    if op.attr("adaptive", False):
        od, oh, ow = ksize
    else:
        strides = list(op.attr("strides", ksize))
        pads = [0, 0, 0] if gp else list(op.attr("paddings", [0, 0, 0]))
        od, oh, ow = (
            pool_out_dim(s, k, (p, p), st)
            for s, k, p, st in zip(x.shape[2:], ksize, pads, strides)
        )
    ictx.out(op, "Out", VarMeta((n, c, od, oh, ow), x.dtype))


@register_shape("max_pool2d_with_index", "max_pool3d_with_index")
def _shape_max_pool_with_index(ictx, op):
    nd = 3 if op.type == "max_pool3d_with_index" else 2
    x = ictx.require(_m(ictx.in_(op, "X")))
    ksize = list(op.attr("ksize"))
    if op.attr("global_pooling", False):
        ksize = list(x.shape[2:])
    strides = list(op.attr("strides", ksize))
    pads = list(op.attr("paddings", [0] * nd))
    spatial = tuple(
        pool_out_dim(s, k, (p, p), st)
        for s, k, p, st in zip(x.shape[2:], ksize, pads, strides)
    )
    shape = (x.shape[0], x.shape[1]) + spatial
    ictx.out(op, "Out", VarMeta(shape, x.dtype))
    ictx.out(op, "Mask", VarMeta(shape, I32))


@register_shape("unpool")
def _shape_unpool(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    size = list(op.attr("unpooled_size") or [])
    if size:
        oh, ow = size[:2]
    else:
        ks = list(op.attr("ksize", [2, 2]))
        st = list(op.attr("strides", ks))
        oh = (x.shape[2] - 1) * st[0] + ks[0]
        ow = (x.shape[3] - 1) * st[1] + ks[1]
    ictx.out(op, "Out", VarMeta((x.shape[0], x.shape[1], oh, ow), x.dtype))


@register_shape("row_conv")
def _shape_row_conv(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    f = ictx.require(_m(ictx.in_(op, "Filter")))
    ictx.out(op, "Out", VarMeta(x.shape, _promote(x.dtype, f.dtype)))


@register_shape("spp")
def _shape_spp(ictx, op):
    # level p pools ceil(h/2^p)-sized windows with centering pads, so
    # the per-level bin count follows the floor formula, not always 4^p
    x = ictx.require(_m(ictx.in_(op, "X")))
    n, c, h, w = x.shape
    total = 0
    for p in range(int(op.attr("pyramid_height"))):
        bins = 2 ** p
        dims = []
        for s in (h, w):
            k = -(-s // bins)  # ceil
            pad = (k * bins - s + 1) // 2
            dims.append(pool_out_dim(s, k, (pad, pad), k))
        total += dims[0] * dims[1]
    ictx.out(op, "Out", VarMeta((n, c * total), x.dtype))


@register_shape("fsp")
def _shape_fsp(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    y = ictx.require(_m(ictx.in_(op, "Y")))
    ictx.out(op, "Out", VarMeta(
        (x.shape[0], x.shape[1], y.shape[1]),
        _promote(x.dtype, y.dtype),
    ))


@register_shape("conv_shift")
def _shape_conv_shift(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    y = ictx.require(_m(ictx.in_(op, "Y")))
    ictx.out(op, "Out", VarMeta(x.shape, _promote(x.dtype, y.dtype)))


@register_shape("scatter_nd")
def _shape_scatter_nd(ictx, op):
    upd = _m(ictx.in_(op, "Updates"))
    ictx.out(op, "Out",
             VarMeta(tuple(int(s) for s in op.attr("shape")), upd.dtype))


def _shape_batch_size_like(ictx, op, dtype):
    ref = ictx.require(_m(ictx.in_(op, "Input")))
    shape = list(op.attr("shape"))
    shape[int(op.attr("output_dim_idx", 0))] = ref.shape[
        int(op.attr("input_dim_idx", 0))
    ]
    ictx.out(op, "Out", VarMeta(tuple(shape), dtype))


@register_shape("uniform_random_batch_size_like")
def _shape_uniform_random_bsl(ictx, op):
    # the lowering samples f32 and never casts
    _shape_batch_size_like(ictx, op, F32)


@register_shape("gaussian_random_batch_size_like")
def _shape_gaussian_random_bsl(ictx, op):
    dt = op.attr("dtype")
    _shape_batch_size_like(
        ictx, op, lowered_dtype(dt) if isinstance(dt, str) else F32,
    )


@register_shape("sigmoid_focal_loss")
def _shape_sigmoid_focal_loss(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "X")))
    ictx.out(op, "Out", VarMeta(x.shape, _promote(x.dtype, F32)))


@register_shape("polygon_box_transform")
def _shape_polygon_box_transform(ictx, op):
    ictx.out(op, "Output", _m(ictx.in_(op, "Input")))


@register_shape("box_clip")
def _shape_box_clip(ictx, op):
    x = ictx.require(_m(ictx.in_(op, "Input")))
    info = ictx.require(_m(ictx.in_(op, "ImInfo")))
    ictx.out(op, "Output", VarMeta(x.shape, _promote(x.dtype, info.dtype)))
