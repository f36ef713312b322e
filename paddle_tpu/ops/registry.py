"""Op registry + lowering context.

TPU-native replacement for Fluid's kernel registry/dispatch
(reference: paddle/fluid/framework/op_registry.h:199,240,243 and
operator.cc:886,971): instead of selecting a device kernel per op at run time,
each registered op provides a *lowering* — a function from JAX values to JAX
values — and a whole Block is traced into ONE XLA computation. Grad-op
machinery (reference: framework/grad_op_desc_maker.h:36,146) is replaced by a
generic vjp-based grad op: `append_backward` emits a `{type}_grad` op whose
default lowering is `jax.vjp` of the forward lowering; XLA CSE dedupes the
recomputed forward. Ops with run-time state (dropout masks) register custom
grad makers/lowerings.
"""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import profiler
from ..framework import convert_dtype, core_op_role, is_float_dtype
from ..jit_compile import current_owner

__all__ = [
    "OpDef",
    "register_op",
    "get_op",
    "has_op",
    "LoweringContext",
    "JNP_DTYPE",
    "register_shape",
    "get_shape_fn",
    "has_shape_fn",
    "all_op_types",
    "all_shape_fn_types",
]


def JNP_DTYPE(dtype) -> jnp.dtype:
    # x64 stays disabled (TPU-native): int64/float64 IR dtypes run as 32-bit
    # on device, matching the reference's int64 labels without the cost.
    name = convert_dtype(dtype)
    return {
        "float32": jnp.float32,
        "float64": jnp.float32,
        "float16": jnp.float16,
        "bfloat16": jnp.bfloat16,
        "int8": jnp.int8,
        "uint8": jnp.uint8,
        "int16": jnp.int16,
        "int32": jnp.int32,
        "int64": jnp.int32,
        "bool": jnp.bool_,
    }[name]


class OpDef:
    def __init__(
        self,
        type: str,
        lower,
        grad=None,
        no_grad_inputs=(),
        stateful_outputs=(),
        differentiable=True,
        device_counts=(),
    ):
        self.type = type
        self.lower = lower
        # grad: None -> auto vjp; callable -> custom grad maker returning op
        # descs; False -> non-differentiable
        self.grad = grad
        self.no_grad_inputs = frozenset(no_grad_inputs)
        # output slots that alias persistable state (running stats, optimizer
        # accumulators); excluded from differentiation
        self.stateful_outputs = frozenset(stateful_outputs)
        self.differentiable = differentiable
        # the device counts its lowering adds to (`LoweringContext.count`):
        # the Executor reads them off the block before it traces anything,
        # so a step knows its counts' names, and that it has any, on a warm
        # start too
        self.device_counts = tuple(device_counts)
        # static shape/dtype inference function (register_shape), or None.
        # Signature mirrors the lowering: fn(ictx, op) sets output VarMetas
        # on an analysis.shape_infer.InferContext instead of JAX values.
        self.shape_fn = None


_OP_REGISTRY: dict[str, OpDef] = {}
_SHAPE_FN_REGISTRY: dict[str, object] = {}


def register_op(type, **kwargs):
    """Decorator: @register_op("relu") def _(ctx, op): ..."""

    def deco(fn):
        _OP_REGISTRY[type] = OpDef(type, fn, **kwargs)
        return fn

    return deco


def get_op(type) -> OpDef:
    if type not in _OP_REGISTRY:
        raise NotImplementedError(f"op {type!r} has no registered TPU lowering")
    return _OP_REGISTRY[type]


def has_op(type) -> bool:
    return type in _OP_REGISTRY


def all_op_types() -> tuple:
    """Every registered op type, sorted (the shape-coverage ratchet's
    denominator)."""
    return tuple(sorted(_OP_REGISTRY))


# ---------------------------------------------------------------------------
# static shape/dtype inference functions (paddle_tpu/analysis)
# ---------------------------------------------------------------------------
#
# Each op may register, alongside its lowering, a *shape function* — the
# static mirror of the lowering that maps input VarMetas (shape tuple +
# lowered dtype name) to output VarMetas without touching JAX tracing.
# The analysis engine (analysis/shape_infer.py) drives these over a whole
# Program; the IR verifier cross-checks their results against declared
# Variable dtypes/shapes, and the auto-parallel placement work consumes
# the resulting annotated program (ROADMAP: shard_propagation).


def register_shape(*types):
    """Decorator: @register_shape("matmul", "matmul_v2")
    def _(ictx, op): ...

    The function receives an analysis InferContext and the Operator and
    must set a VarMeta for every output it can determine (helpers on the
    context mirror LoweringContext's in_/ins/out sugar). Registration is
    independent of lowering registration order; the OpDef (if present)
    gets its .shape_fn backfilled for introspection."""

    def deco(fn):
        for t in types:
            _SHAPE_FN_REGISTRY[t] = fn
            if t in _OP_REGISTRY:
                _OP_REGISTRY[t].shape_fn = fn
        return fn

    return deco


def get_shape_fn(type):
    return _SHAPE_FN_REGISTRY.get(type)


def has_shape_fn(type) -> bool:
    return type in _SHAPE_FN_REGISTRY


def all_shape_fn_types() -> tuple:
    return tuple(sorted(_SHAPE_FN_REGISTRY))


class LoweringContext:
    """Carries name->JAX-value bindings while a Block is traced to XLA.

    Plays the role of Fluid's Scope during execution
    (reference: framework/scope.h:46) but is purely functional: ops `set`
    new bindings; the executor snapshots persistable bindings as the step
    function's returned state.
    """

    def __init__(self, program=None, rng_key=None, is_test=False, mesh=None):
        self.program = program
        self.values: dict[str, object] = {}
        self.rng_key = rng_key
        self._rng_counter = 0
        self.is_test = is_test
        self.mesh = mesh
        # bf16 compute policy for MXU ops (contrib.mixed_precision)
        self.amp_dtype = getattr(program, "_amp_dtype", None)
        self.amp_black_list = getattr(program, "_amp_black_list", set())
        # ops the user promoted to the amp dtype beyond the default MXU
        # set (reference fp16_lists.py custom white list): their float32
        # inputs are pre-cast by lower_op
        self.amp_white_list = getattr(program, "_amp_white_list", set())
        # FLAGS_check_nan_inf analog (reference operator.cc:949-961): when
        # enabled, every float op output contributes an all-finite flag the
        # executor checks host-side after the step
        self.nan_flags: dict[str, object] | None = None
        # counts the step makes on the device (`count`): name -> traced
        # int32 scalar. None, as here, in every context whose tracers
        # nobody carries out of the step: a loop's or a scan's body, a
        # recompute segment, a micro-batch, a NaN-checked step. The plain
        # step (executor.py) gives its own context a dict and returns it.
        self.device_counts: dict[str, object] | None = None
        # `__auto_grad__`'s replay of a forward op that has counted already
        self.replays = False

    # -- value access -------------------------------------------------------
    def get(self, name):
        if name not in self.values:
            raise KeyError(
                f"variable {name!r} used before it holds a value — "
                "did you run the startup program / feed it?"
            )
        return self.values[name]

    def get_list(self, names):
        return [self.get(n) for n in names]

    def set(self, name, value):
        self.values[name] = value

    def has(self, name):
        return name in self.values

    # -- op-facing sugar ----------------------------------------------------
    def in_(self, op, slot, idx=0, default=None):
        names = op.input(slot)
        if len(names) <= idx:
            return default
        return self.get(names[idx])

    def ins(self, op, slot):
        return self.get_list(op.input(slot))

    def out(self, op, slot, value, idx=0):
        names = op.output(slot)
        if names:
            self.set(names[idx], value)
            if self.nan_flags is not None and hasattr(value, "dtype") and (
                jnp.issubdtype(value.dtype, jnp.floating)
            ):
                self.nan_flags[names[idx]] = jnp.all(jnp.isfinite(value))

    def count(self, name, value):
        """Add `value` to the step's device count `name`, which the op has
        to declare (`register_op(..., device_counts=...)`): an integer, or
        a function that makes an integer scalar of data (an expert layer's
        load), called only where the count is carried, so that a step
        which drops it traces nothing for it. The step hands the sums back
        beside its fetches and the Executor folds them into
        `profiler.counters()` under the same name once the step has run:
        `profiler.bump_counter` runs while tracing and cannot see data. A
        gradient op's replay counts nothing (its forward op has, and a
        tracer may not leave the `vjp`); any other context that cannot
        carry a count out says so, once a count: `device_counts_dropped`."""
        if self.replays:
            return
        if self.device_counts is None:
            profiler.bump_counter("device_counts_dropped")
            return
        if callable(value):
            value = value()
        self.device_counts[name] = (self.device_counts.get(name, 0)
                                    + jnp.asarray(value, jnp.int32))

    def next_rng(self):
        if self.rng_key is None:
            raise RuntimeError(
                "op requires randomness but no rng key threaded — executor bug"
            )
        self._rng_counter += 1
        return jax.random.fold_in(self.rng_key, self._rng_counter)

    def rng_for(self, name):
        """Rng key derived from a variable name, NOT the lowering order: ops
        whose grad goes through __auto_grad__ (which re-lowers the forward
        inside jax.vjp) must see the identical key in both lowerings."""
        import zlib

        if self.rng_key is None:
            raise RuntimeError(
                "op requires randomness but no rng key threaded — executor bug"
            )
        return jax.random.fold_in(
            self.rng_key, zlib.crc32(name.encode()) & 0x7FFFFFFF
        )

    def child(self):
        sub = LoweringContext(self.program, self.rng_key, self.is_test, self.mesh)
        sub._rng_counter = self._rng_counter + 1000
        return sub

    def amp_dtype_for(self, op):
        """The AMP compute dtype for this op, or None (fp32): the single
        gating rule shared by amp_cast and lowerings that cast internally
        (e.g. moe_ffn)."""
        if self.amp_dtype is None or op.type in self.amp_black_list:
            return None
        return self.amp_dtype

    def amp_cast(self, op, *vals):
        """Cast float inputs of an MXU op to the amp dtype (bf16), unless the
        op type is black-listed back to fp32."""
        if self.amp_dtype_for(op) is None:
            return vals
        out = []
        for v in vals:
            if v is not None and jnp.issubdtype(
                jnp.asarray(v).dtype, jnp.floating
            ):
                v = v.astype(self.amp_dtype)
            out.append(v)
        return out


def _amp_precast(ctx, op):
    """custom_white_list support: cast the op's float32 input bindings
    to the amp dtype before lowering (the reference inserts cast ops in
    rewrite_program, fp16_utils.py:69). Returns the shadowed originals."""
    saved = {}
    if (
        not getattr(ctx, "amp_white_list", None)
        or op.type not in ctx.amp_white_list
        or ctx.amp_dtype_for(op) is None
    ):
        return saved
    for n in op.input_arg_names():
        if not n or not ctx.has(n):
            continue
        v = ctx.values[n]
        if hasattr(v, "dtype") and v.dtype == jnp.float32:
            saved[n] = v
            ctx.values[n] = v.astype(ctx.amp_dtype)
    return saved


def op_scope(op) -> str:
    """`phase/name` of a Program op, the jax.named_scope its lowering runs
    under: every HLO instruction it traces carries it in its metadata, so
    a device trace reads in Program vocabulary. Phase from `op_role`:
    `fwd` (Forward, Loss), `bwd` (Backward), `opt` (Optimize, LRSched and
    anything else); an `__auto_grad__` is named after its forward op."""
    role = op.attr("op_role", 0) or 0
    if role & core_op_role.Backward:
        phase = "bwd"
    elif role & ~core_op_role.Loss:
        phase = "opt"
    else:
        phase = "fwd"
    if op.type == "__auto_grad__":
        return f"{phase}/{op.attr('fwd_type')}_grad"
    return f"{phase}/{op.type}"


class _LoweringClock(threading.local):
    inner = 0.0  # seconds the ops lowered inside the open one have taken


_clock = _LoweringClock()


def lower_op(ctx: LoweringContext, op):
    scope = op_scope(op)
    # the op's own lowering time, at trace time only: what a `while`
    # body lowers through here inside it is its own
    t0 = time.perf_counter()
    outer, _clock.inner = _clock.inner, 0.0
    try:
        # names only: HLO metadata, not the computation
        with jax.named_scope(scope):
            saved = _amp_precast(ctx, op)
            try:
                get_op(op.type).lower(ctx, op)
            finally:
                for _n, _v in saved.items():
                    ctx.values[_n] = _v
        return
    except Exception as e:
        # op_call_stack.cc analog: a failing lowering names the op AND the
        # user's layer call that created it, instead of a bare JAX
        # traceback from deep inside a 500-op trace
        site = getattr(op, "callsite", None)
        note = f"[paddle_tpu] while lowering op {op.type!r}"
        if site:
            note += f" created at {site}"
        outs = [n for n in op.output_arg_names() if n][:3]
        if outs:
            note += f" (outputs: {', '.join(outs)})"
        existing = list(getattr(e, "__notes__", ()) or ())
        if note not in existing:
            if hasattr(e, "add_note"):  # py3.11+ (PEP 678)
                e.add_note(note)
            else:  # py3.10: set the attribute by hand; pytest/traceback
                # machinery reads __notes__ the same way
                try:
                    e.__notes__ = existing + [note]
                except (AttributeError, TypeError):
                    pass
        raise
    finally:
        whole = time.perf_counter() - t0
        profiler.bump_counter(
            f"trace_op_us.{current_owner()}.{scope}",
            int((whole - _clock.inner) * 1e6))
        _clock.inner = outer + whole


def lower_block(ctx: LoweringContext, block):
    for op in block.ops:
        lower_op(ctx, op)


# ---------------------------------------------------------------------------
# Generic vjp-based grad op
# ---------------------------------------------------------------------------
#
# append_backward (backward.py) emits for forward op F an op:
#   type:   "__auto_grad__"
#   attrs:  fwd_type, fwd_inputs, fwd_outputs, fwd_attrs (block refs illegal)
#   inputs: the fwd op's inputs under their original slots prefixed "FWD_",
#           plus output grads under "GRAD_<slot>"
#   outputs: input grads under "IGRAD_<slot>"
#
# Its lowering reconstructs the forward computation as a pure function of the
# differentiable inputs and pulls cotangents through jax.vjp. The recomputed
# forward is structurally identical to the original forward appearing earlier
# in the same XLA module, so XLA CSE merges them (no double compute) — the
# TPU-idiomatic replacement for Fluid's hand-written per-op grad kernels.


class _FwdOpView:
    """Duck-typed Operator for re-running a forward lowering inside vjp."""

    def __init__(self, type, inputs, outputs, attrs):
        self.type = type
        self.inputs = inputs
        self.outputs = outputs
        self.attrs = attrs

    def input(self, slot):
        return self.inputs.get(slot, [])

    def output(self, slot):
        return self.outputs.get(slot, [])

    def attr(self, name, default=None):
        return self.attrs.get(name, default)

    def input_arg_names(self):
        return [n for ns in self.inputs.values() for n in ns]

    def output_arg_names(self):
        return [n for ns in self.outputs.values() for n in ns]


def _is_differentiable_value(v):
    return hasattr(v, "dtype") and jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating)


@register_op("__auto_grad__")
def _auto_grad_lower(ctx, op):
    fwd_type = op.attr("fwd_type")
    fwd_inputs = op.attr("fwd_inputs")
    fwd_outputs = op.attr("fwd_outputs")
    fwd_attrs = dict(op.attr("fwd_attrs") or {})
    opdef = get_op(fwd_type)

    fwd_op = _FwdOpView(fwd_type, fwd_inputs, fwd_outputs, fwd_attrs)

    # Ordered list of differentiable (slot, idx, name) among fwd inputs.
    # Empty-string names are positional markers for missing grads (they
    # appear when differentiating an __auto_grad__ op itself — the
    # double-grad path): skip them.
    diff_in = []
    all_in = []
    for slot, names in fwd_inputs.items():
        for i, n in enumerate(names):
            if not n:
                continue
            v = ctx.get(n)
            all_in.append((slot, i, n, v))
            wants = any(
                gslot == f"IGRAD_{slot}" and i < len(gnames) and gnames[i]
                for gslot, gnames in op.outputs.items()
            )
            if (
                wants
                and slot not in opdef.no_grad_inputs
                and _is_differentiable_value(v)
            ):
                diff_in.append((slot, i, n))

    # Canonical ordered outputs (excluding stateful aliases).
    out_order = []
    for slot, names in fwd_outputs.items():
        if slot in opdef.stateful_outputs:
            continue
        for i, n in enumerate(names):
            if not n:
                continue
            out_order.append((slot, i, n))

    diff_vals = [ctx.get(n) for (_, _, n) in diff_in]

    def fwd_fn(*dvals):
        sub = ctx.child()
        sub.replays = True  # `count`: the forward op has counted
        for (slot, i, n, v) in all_in:
            sub.set(n, v)
        for (slot, i, n), dv in zip(diff_in, dvals):
            sub.set(n, dv)
        opdef.lower(sub, fwd_op)
        return tuple(sub.get(n) for (_, _, n) in out_order)

    primal_out, pullback = jax.vjp(fwd_fn, *diff_vals)

    # Cotangents: output grad if provided, else zeros.
    cts = []
    for (slot, i, n), po in zip(out_order, primal_out):
        gnames = op.inputs.get(f"GRAD_{slot}", [])
        gname = gnames[i] if i < len(gnames) else None
        if gname and ctx.has(gname):
            g = ctx.get(gname)
            cts.append(jnp.asarray(g, dtype=po.dtype).reshape(po.shape))
        else:
            cts.append(jnp.zeros_like(po))

    in_grads = pullback(tuple(cts))

    for (slot, i, n), g in zip(diff_in, in_grads):
        onames = op.outputs.get(f"IGRAD_{slot}", [])
        if i < len(onames) and onames[i]:
            ctx.set(onames[i], g)
            if ctx.nan_flags is not None and hasattr(g, "dtype") and (
                jnp.issubdtype(g.dtype, jnp.floating)
            ):
                # gradients are the most common nan source — flag them too
                ctx.nan_flags[onames[i]] = jnp.all(jnp.isfinite(g))
