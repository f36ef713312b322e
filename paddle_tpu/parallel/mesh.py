"""One named device mesh — axes ``('batch', 'model', 'pipe')`` — for every
parallelism flavor in the tree.

This is the GSPMD-native substrate that replaced the legacy
``shard-map``/``p-map`` layer (removed from modern JAX): every multi-device
path — data parallel, tensor parallel, sequence parallel (ring),
expert parallel, pipeline microbatching, ZeRO-1 optimizer-state sharding —
is expressed as a PartitionSpec assignment over ONE mesh and compiled with
plain ``jax.jit(..., in_shardings=..., out_shardings=...,
donate_argnums=...)``. XLA/GSPMD chooses, inserts and overlaps the
collectives; there are no hand-written per-device programs left.

Axis contract:

- ``batch``  — data parallelism. Feed batch dims shard here; gradient
  all-reduce over this axis is GSPMD-inserted. ZeRO-1 shards optimizer
  accumulators along it.
- ``model``  — everything intra-layer: Megatron column/row tensor
  parallelism, ring sequence parallelism (the sequence dim),
  MoE expert sharding. One axis, one vocabulary — the search space the
  auto-placement pass (ROADMAP) optimizes over.
- ``pipe``   — pipeline stages: the microbatch schedule runs along it and
  per-stage parameters + optimizer state live sharded over it at rest
  (ZeRO-style, the memory analog of the reference's per-section scopes).

All three axes always exist (size 1 when unused), so a ``1×1×1`` mesh is
the degenerate single-device case and must produce bitwise-identical
fetches to the non-mesh executor path (tests/test_mesh.py pins this).

Legacy axis names used by existing annotations and callers (``dp``,
``tp``, ``sp``, ``ep``, ``pp``) are accepted everywhere and canonicalized:
dp→batch, tp/sp/ep→model, pp→pipe.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = [
    "AXES",
    "axis_sizes",
    "build_mesh",
    "current_mesh",
    "set_current_mesh",
    "canonical_axis",
    "canonicalize_spec",
    "spec_to_manifest",
    "spec_from_manifest",
    "named_sharding",
    "sharding_with_degrade",
    "smaller_mesh_shapes",
    "mesh_signature",
    "assign_state_shardings",
    "feed_shardings",
    "zero1_accumulators",
    "pipe_shardable_state",
]

AXES = ("batch", "model", "pipe")

# legacy axis vocabulary -> the one mesh's axes
_LEGACY = {
    "dp": "batch",
    "data": "batch",
    "batch": "batch",
    "tp": "model",
    "mp": "model",
    "sp": "model",
    "ep": "model",
    "model": "model",
    "pp": "pipe",
    "stage": "pipe",
    "pipe": "pipe",
}

_current_mesh: Mesh | None = None


def canonical_axis(name):
    """Map a legacy axis name onto the unified mesh axis (None passes
    through; unknown names raise — a typo'd annotation must be loud)."""
    if name is None:
        return None
    try:
        return _LEGACY[name]
    except KeyError:
        raise ValueError(
            f"unknown mesh axis {name!r}: the unified mesh has axes "
            f"{AXES} (legacy dp/tp/sp/ep/pp accepted)"
        )


def canonicalize_spec(spec) -> P:
    """PartitionSpec with every axis name canonicalized. Two legacy axes
    that fold into the same unified axis (e.g. a ``P('tp', 'sp')`` pair)
    cannot both shard one tensor: the FIRST occurrence wins, later
    duplicates degrade to replicated on their dim."""
    if spec is None:
        return P()
    seen = set()
    out = []
    for el in spec:
        names = el if isinstance(el, tuple) else (el,)
        keep = []
        for a in names:
            c = canonical_axis(a)
            if c is not None and c not in seen:
                seen.add(c)
                keep.append(c)
        out.append(tuple(keep) if len(keep) > 1
                   else (keep[0] if keep else None))
    return P(*out)


def spec_to_manifest(spec) -> list:
    """JSON-serializable form of a PartitionSpec (snapshot manifests
    record one per var so sharded checkpoints restore shard-aware)."""
    out = []
    for el in canonicalize_spec(spec):
        if el is None:
            out.append(None)
        elif isinstance(el, tuple):
            out.append(list(el))
        else:
            out.append(el)
    return out


def spec_from_manifest(entry) -> P:
    """Inverse of spec_to_manifest."""
    if not entry:
        return P()
    return P(*[tuple(e) if isinstance(e, list) else e for e in entry])


def build_mesh(batch=None, model=1, pipe=1, devices=None) -> Mesh:
    """THE mesh: axes ('batch', 'model', 'pipe'), all present (size 1
    when unused). batch=None fills the remaining devices."""
    devices = list(devices if devices is not None else jax.devices())
    model = max(1, int(model))
    pipe = max(1, int(pipe))
    if batch is None:
        batch = max(1, len(devices) // (model * pipe))
    batch = max(1, int(batch))
    n = batch * model * pipe
    if n > len(devices):
        raise ValueError(
            f"mesh batch={batch} x model={model} x pipe={pipe} needs {n} "
            f"devices, have {len(devices)}"
        )
    arr = np.array(devices[:n]).reshape(batch, model, pipe)
    mesh = Mesh(arr, AXES)
    set_current_mesh(mesh)
    return mesh


def set_current_mesh(mesh: Mesh | None):
    global _current_mesh
    _current_mesh = mesh
    _publish_counters(mesh)
    return mesh


def current_mesh() -> Mesh | None:
    return _current_mesh


def _publish_counters(mesh):
    """Always-on mesh gauges (PR 1/2 counter conventions): mesh_axes =
    number of non-trivial axes, mesh_shape = total mesh devices, plus a
    per-axis gauge each (mesh_shape_batch/_model/_pipe)."""
    from .. import profiler

    if mesh is None:
        return
    shape = dict(mesh.shape)
    profiler.set_counter(
        "mesh_axes", sum(1 for v in shape.values() if v > 1)
    )
    profiler.set_counter("mesh_shape", int(np.prod(list(shape.values()))))
    for ax in AXES:
        profiler.set_counter(f"mesh_shape_{ax}", int(shape.get(ax, 1)))


def mesh_signature(mesh, specs=None) -> tuple:
    """Hashable (mesh shape, spec assignment) digest for compile caches:
    the executor/CompiledProgram cache keys, the pass-manager signature
    and the dygraph JIT cache key all carry it so flipping a sharding
    recompiles instead of serving a stale executable."""
    if mesh is None:
        return ("nomesh",)
    shape = tuple((a, int(s)) for a, s in mesh.shape.items())
    if not specs:
        return (shape,)
    table = tuple(sorted(
        (name, str(canonicalize_spec(s))) for name, s in specs.items()
    ))
    return (shape, table)


def named_sharding(mesh, spec, shape=None) -> NamedSharding:
    """NamedSharding with the degrade rule every consumer shares: axes the
    mesh doesn't carry (never happens on the unified mesh, but specs may
    predate it) and dims whose size the axis group doesn't divide (odd
    vocab on a row-sharded table) fall back to replicated on that dim."""
    return sharding_with_degrade(mesh, spec, shape)[0]


def sharding_with_degrade(mesh, spec, shape=None):
    """The degrade rule of `named_sharding`, plus a report: returns
    ``(NamedSharding, degraded)`` where `degraded` lists one
    ``(dim, axes, dim_size, group_size)`` tuple per dim that wanted to
    shard but fell back to replicated (axis absent from the mesh counts
    with group_size 0). The mesh-elastic restore path uses the report to
    degrade LOUDLY — a var whose recorded axis no longer divides the new
    mesh extent must warn, never crash and never silently shard wrong."""
    spec = canonicalize_spec(spec)
    clean = []
    degraded = []
    for i, el in enumerate(spec):
        names = el if isinstance(el, tuple) else (el,)
        wanted = tuple(a for a in names if a is not None)
        keep = tuple(a for a in wanted if a in mesh.axis_names)
        if wanted and not keep:
            degraded.append((i, wanted, None, 0))
        if keep and shape is not None and i < len(shape):
            group = 1
            for a in keep:
                group *= mesh.shape[a]
            if not isinstance(shape[i], int) or shape[i] % group != 0:
                degraded.append((i, keep,
                                 shape[i] if i < len(shape) else None,
                                 group))
                keep = ()
        clean.append(keep if len(keep) > 1
                     else (keep[0] if keep else None))
    return NamedSharding(mesh, P(*clean)), degraded


def axis_sizes(mesh_or_sizes) -> dict:
    """{axis: size} from a jax Mesh or a plain dict — the normalization
    the autoshard planner, the sharding checker and the dryrun cost
    table share (the planner works on plain dicts so placement search
    never needs a device mesh to exist)."""
    if mesh_or_sizes is None:
        return {}
    shape = getattr(mesh_or_sizes, "shape", mesh_or_sizes)
    return {a: int(s) for a, s in dict(shape).items()}


def smaller_mesh_shapes(base_world: int):
    """Valid shrink targets for a `base_world`-wide job, descending
    (the supervisor's shrink policy; canonical implementation lives in
    distributed.launch so the JAX-free supervisor can import it).
    With an autoshard plan table the supervisor re-ranks these by
    planner score (autoshard/elastic.py best_shrink_world) instead of
    taking the first — every candidate here must therefore yield a
    valid plan (tests/test_autoshard.py pins the sweep)."""
    from ..distributed.launch import shrink_candidates

    return shrink_candidates(base_world)


# ---------------------------------------------------------------------------
# PartitionSpec assignment over Program IR variables
# ---------------------------------------------------------------------------


def _post_ops(block):
    from ..framework import core_op_role

    post_role = core_op_role.Optimize | core_op_role.LRSched
    return [op for op in block.ops
            if (op.attrs.get("op_role") or 0) & post_role]


def _fwd_ops(block):
    from ..framework import core_op_role

    post_role = core_op_role.Optimize | core_op_role.LRSched
    return [op for op in block.ops
            if not ((op.attrs.get("op_role") or 0) & post_role)]


def _var_shape(block, name):
    v = block._find_var_recursive(name)
    return tuple(v.shape) if v is not None and v.shape else ()


def _param_grad_pairs(block, state_names):
    """(param, grad) pairs the optimizer segment consumes, plus the read
    count per grad (multi-consumer grads — global-norm clip chains — need
    full-grad semantics and are excluded from sharded updates)."""
    from ..framework import GRAD_SUFFIX

    post = _post_ops(block)
    post_reads = {n for op in post for n in op.input_arg_names()}
    grad_names = sorted(n for n in post_reads if n.endswith(GRAD_SUFFIX))
    state_set = set(state_names)
    pairs = [
        (g[: -len(GRAD_SUFFIX)], g) for g in grad_names
        if g[: -len(GRAD_SUFFIX)] in state_set
    ]
    counts = {}
    for op in post:
        for n in op.input_arg_names():
            if n.endswith(GRAD_SUFFIX):
                counts[n] = counts.get(n, 0) + 1
    return pairs, counts, post


def _accumulators_for(block, state_names, param, grad, post_ops, fwd_read):
    """Optimizer accumulators ride with their param, associated
    STRUCTURALLY: the optimizer op consuming the param's grad names them
    as its other param-shaped persistable inputs/outputs (name-prefix
    matching could mis-claim across params)."""
    state_set = set(state_names)
    shape = _var_shape(block, param)
    out = set()
    for op in post_ops:
        if grad not in op.input_arg_names():
            continue
        for n in set(op.input_arg_names()) | set(op.output_arg_names()):
            if (
                n in state_set
                and n not in (param, grad)
                and n not in fwd_read
                and _var_shape(block, n) == shape
            ):
                out.add(n)
    return out


def zero1_accumulators(block, state_names, axis_size) -> dict:
    """ZeRO-1 over 'batch': optimizer accumulators (moments) whose dim0
    divides the batch axis get P('batch') on dim0; parameters stay
    replicated (GSPMD reduce-scatters the grads into the sharded moment
    update and all-gathers the param delta — the ZeRO-1 dataflow, chosen
    by the compiler instead of hand-rolled)."""
    if axis_size <= 1:
        return {}
    pairs, counts, post = _param_grad_pairs(block, state_names)
    fwd_read = {n for op in _fwd_ops(block)
                for n in op.input_arg_names()}
    specs = {}
    for p, g in pairs:
        shp = _var_shape(block, p)
        if not (shp and isinstance(shp[0], int) and shp[0] % axis_size == 0):
            continue
        if counts.get(g, 0) != 1:
            continue
        for acc in _accumulators_for(block, state_names, p, g, post,
                                     fwd_read):
            specs[acc] = P("batch")
    return specs


def pipe_shardable_state(block, state_names, pipe_size,
                         stateful_fwd=(), model_dim0=()) -> dict:
    """ZeRO over 'pipe' for pipeline programs: master params AND their
    accumulators live sharded 1/pipe per device at rest (the memory
    analog of the reference's per-section scopes). A param qualifies when
    dim0 divides pipe, its grad feeds exactly one optimizer op, it is not
    forward-stateful (BN stats), and dim0 is not already model-sharded."""
    if pipe_size <= 1:
        return {}
    pairs, counts, post = _param_grad_pairs(block, state_names)
    fwd_read = {n for op in _fwd_ops(block)
                for n in op.input_arg_names()}
    stateful = set(stateful_fwd)
    model0 = set(model_dim0)
    specs = {}
    for p, g in pairs:
        shp = _var_shape(block, p)
        if (
            shp
            and isinstance(shp[0], int)
            and shp[0] >= pipe_size
            and shp[0] % pipe_size == 0
            and counts.get(g, 0) == 1
            and p not in stateful
            and p not in model0
        ):
            specs[p] = P("pipe")
            for acc in _accumulators_for(block, state_names, p, g, post,
                                         fwd_read):
                specs[acc] = P("pipe")
    return specs


def assign_state_shardings(program, block, state_names, mesh, scope=None,
                           extra_specs=None) -> dict:
    """THE spec-assignment layer: map every Program IR persistable (params,
    optimizer accumulators, BN stats, embedding tables) to a NamedSharding
    on the unified mesh.

    Priority per var: `extra_specs` (ZeRO-1 / pipe-ZeRO assignments
    computed for THIS compile — hand-configured, or chosen by the
    autoshard planner via the shard_propagation pass; both enter
    here) > the program's `shard_parameter`
    annotations (Megatron tp splits, MoE expert dims, PS row shards) >
    a live value already sharded on this mesh > replicated. Declared
    intents outrank the layout an EARLIER compile happened to leave
    behind — otherwise flipping zero1 on, or editing an annotation,
    would be a silent no-op — while un-annotated state keeps its live
    layout (pipe-ZeRO params evaluated via the fold-into-batch eval path
    must not be forcibly re-replicated). Dispatch device_puts committed
    arrays whose layout disagrees (executor reshard map)."""
    annotations = dict(getattr(program, "_sharding_specs", {}) or {})
    extra_specs = dict(extra_specs or {})
    out = {}
    for n in state_names:
        live = scope.get(n) if scope is not None and scope.has(n) else None
        dims = getattr(live, "shape", None)
        if dims is None:
            dims = _var_shape(block, n) or None
        if n in extra_specs:
            out[n] = named_sharding(mesh, extra_specs[n], dims)
            continue
        if n in annotations:
            out[n] = named_sharding(mesh, annotations[n], dims)
            continue
        live_sh = getattr(live, "sharding", None)
        if isinstance(live_sh, NamedSharding) and live_sh.mesh == mesh:
            out[n] = live_sh
            continue
        out[n] = named_sharding(mesh, None, dims)
    return out


def feed_shardings(mesh, feed_sig, batch_axes=("batch",)) -> dict:
    """Feeds shard their batch (leading) dim over `batch_axes`
    (canonicalized); scalars replicate. Eval on a pipeline mesh folds
    'pipe' into the batch axes (there is no microbatch schedule to run)."""
    axes = tuple(dict.fromkeys(
        canonical_axis(a) for a in batch_axes if a is not None
    ))
    axes = tuple(a for a in axes if a in mesh.axis_names
                 and mesh.shape[a] >= 1)
    spec = axes if len(axes) > 1 else (axes[0] if axes else None)
    out = {}
    for n, shape, _ in feed_sig:
        if len(shape) >= 1:
            out[n] = named_sharding(
                mesh, P(spec, *([None] * (len(shape) - 1))), shape
            )
        else:
            out[n] = NamedSharding(mesh, P())
    return out
