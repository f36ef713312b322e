"""Executor: lowers a Program Block whole-graph to ONE XLA computation.

TPU-native replacement for the reference's interpreting executor
(paddle/fluid/framework/executor.cc:172,276 — the per-op Run loop at
:431-437): instead of dispatching a kernel per op, the whole block is traced
through the op lowerings into a single jitted function

    step(state, feeds, rng) -> (fetches, new_state)

with `state` (persistables: params, optimizer accumulators, BN stats) donated,
so parameter updates are buffer-in-place at the XLA level. Compiled steps are
cached keyed on (program fingerprint, feed signature, fetch names) — the role
of Fluid's program caches (executor.py:253). Feed/fetch keeps the reference
API (executor.py:619,730).
"""

from __future__ import annotations

import contextlib
import functools
import os
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from .compiler import CompiledProgram
from .framework import (
    GRAD_SUFFIX,
    Program,
    Variable,
    convert_dtype,
    core_op_role,
)
from .ops.registry import JNP_DTYPE, LoweringContext, lower_block, lower_op
from .place import CPUPlace, Place, TPUPlace
from . import profiler, step_store
from .profiler import RecordEvent
from .resilience.faults import fault_point
from .scope import Scope, global_scope

__all__ = ["Executor"]


# one shared jit wrapper for BOTH execution modes (static executor here,
# the dygraph JIT bridge in dygraph/jit.py): PADDLE_TPU_XLA_OPTIONS set
# once applies to every compiled step in the process
from .jit_compile import compile_owner, xla_jit as _jit  # noqa: E402
from .passes import resolve_pass_names as _resolve_pass_names  # noqa: E402

# step-progress heartbeat for the elastic TrainSupervisor
# (resilience/trainer_fleet.py): when the supervisor set
# PADDLE_TPU_PROGRESS_FILE, every completed step publishes
# {step, tick, pid, time} to that per-rank file (temp + os.replace —
# the watchdog never reads a torn JSON). Disabled = one dict lookup.
_PROGRESS_ENV = "PADDLE_TPU_PROGRESS_FILE"


def _trainer_heartbeat(step, tick: int) -> None:
    """`tick` is the per-process dispatch ordinal (EVERY dispatch,
    startup programs included — liveness for the hang watchdog);
    `step` is the attached CheckpointManager's training-step number
    (None when no manager is attached) — the value fleet.kill_trainer
    schedules and the resume/MTTR gauges read, kept separate so a
    startup-program dispatch can never impersonate training step N."""
    path = os.environ.get(_PROGRESS_ENV)
    if not path:
        return
    try:
        # chaos site: a raise here is a LOST heartbeat, not a crash —
        # training continues but the supervisor's watchdog sees a
        # silent/straggling rank and restarts the job (the wedged-
        # collective containment path)
        fault_point("trainer.heartbeat")
        import json as _json
        import time as _time

        payload = {"tick": int(tick), "pid": os.getpid(),
                   "time": _time.time()}
        if step is not None:
            payload["step"] = int(step)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            _json.dump(payload, f)
        os.replace(tmp, path)
    except Exception:  # noqa: BLE001 — heartbeat loss must never kill
        pass           # training; prolonged absence is the watchdog's job


def _ckpt_manager(program, cp):
    """The CheckpointManager attached (manager.attach) to the Program or,
    failing that, to the CompiledProgram it was run through; else None."""
    return (getattr(program, "_ckpt_manager", None)
            or getattr(cp, "_ckpt_manager", None))


def _as_feed_array(value, dtype=None):
    if dtype is None:
        # no declared var for this feed name: take the value's own dtype
        dtype = getattr(value, "dtype", None)
        if dtype is None:
            value = np.asarray(value)
            dtype = value.dtype
    want = convert_dtype(dtype)
    # x64 is disabled on TPU: map 64-bit feeds down explicitly
    if want == "int64":
        want = "int32"
    elif want == "float64":
        want = "float32"
    if isinstance(value, jax.Array):
        # device-staged feed (DataLoader prefetch / user device_put):
        # NEVER round-trip it through numpy — np.asarray here is a
        # device->host fetch of the whole batch every step
        if str(value.dtype) == want:
            return value
        return value.astype(want)
    arr = np.asarray(value)
    if str(arr.dtype) != want:
        arr = arr.astype(want)
    return arr


def _seed_words(program, counter) -> np.ndarray:
    """What a dispatch hands the step for its PRNG key: `[base, counter]`
    as two uint32 words of HOST memory. `jit` copies them to every device
    of the step; an array made with `jnp` or `jax.random` would be born
    on device 0, behind the step running there, and on a mesh the
    dispatch would wait for it (PERF.md, Findings, PR 30)."""
    base = program.random_seed or 42
    return np.array([base & 0xFFFFFFFF, counter & 0xFFFFFFFF], np.uint32)


def _step_key(seed):
    """Inside the compiled step, the key of tick `counter`:
    `fold_in(key(base), counter)`, bit for bit what the executor used to
    fold before the dispatch (`key` of a Python int keeps its low 32
    bits). A typed key is taken as it is: callers that lower a step ahead
    of time (tools, the benchmark's compile test) hand it one."""
    if jnp.issubdtype(seed.dtype, jax.dtypes.prng_key):
        return seed
    return jax.random.fold_in(jax.random.key(seed[0]), seed[1])


class _CompiledStep:
    def __init__(self, lowered, jit_kwargs, state_names, feed_names,
                 fetch_names):
        # named `step`: the trace's `jit(step)/<phase>/<op>` scopes and
        # its `PjitFunction(step)` host event carry the name
        def step(state, feeds, seed):
            return lowered(state, feeds, _step_key(seed))

        self.fn = _jit(step, **jit_kwargs)
        # the same step for tracing inside another jit (run_repeated's
        # scan): JAX accepts compiler_options on a top-level jit only, so
        # this one leaves PADDLE_TPU_XLA_OPTIONS to the jit around it
        self.nested_fn = jax.jit(step, **jit_kwargs)
        self.state_names = state_names
        self.feed_names = feed_names
        self.fetch_names = fetch_names
        # the device counts the step returns, as one int32 array after its
        # last fetch (`_device_count_names`); () where it makes none
        self.count_names: tuple = ()
        # name -> the NamedSharding the step reads that feed with; a mesh
        # compile fills it, one device has none to state
        self.feed_shardings: dict = {}


def _device_count_names(block) -> tuple:
    """The device counts the block's own ops declare
    (`register_op(..., device_counts=...)`), sorted: what the plain step
    returns after its fetches, known from the Program alone, so that a
    step loaded from `step_store` has its names too. An op inside a loop's
    or a scan's body is not among them: its context drops what it counts
    (`LoweringContext.count`)."""
    from .ops.registry import get_op, has_op

    return tuple(sorted({name for op in block.ops if has_op(op.type)
                         for name in get_op(op.type).device_counts}))


def _step_owner(block) -> str:
    """Whom a step's compile is filed under (`jit_compile.compile_owner`):
    "train" where the block has a Backward or Optimize op, "forward" for
    any other Program (a startup program, a `for_test` clone, a
    predictor's)."""
    trains = core_op_role.Backward | core_op_role.Optimize
    if any((op.attrs.get("op_role") or 0) & trains for op in block.ops):
        return "train"
    return "forward"


@contextlib.contextmanager
def _first_call(owner):
    """Round the first call of a step's jit, the one that traces, lowers
    and compiles (or reads the persistent cache, or finds the step whole
    in `step_store`): the one place the
    compile path's owner is set. What the call paid by stage lands in
    `compile_*.<owner>` (`jit_compile`), each op's lowering in
    `trace_op_us.<owner>.<scope>` (`ops/registry.py::lower_op`), and its
    whole wall time, if it returns, in `program_first_call_us.<owner>`."""
    t0 = time.perf_counter()
    with RecordEvent("pt.exe.first_call"), compile_owner(owner):
        yield
    profiler.bump_counter(f"program_first_call_us.{owner}",
                          int((time.perf_counter() - t0) * 1e6))


def _instrument_compiled(compiled, block, store_key=None):
    """Always-on compile-path counters (style of dygraph_jit_*): every
    cache miss bumps program_compile_count and program_traced_ops (ops
    the jit trace will lower), and the first dispatch runs under
    `_first_call`, where `step_store` is asked for the step under
    `store_key` (`_store_key`; None: a step that is not stored).
    Steady-state calls pay one flag check."""
    profiler.bump_counter("program_compile_count")
    profiler.bump_counter("program_traced_ops", len(block.ops))
    compiled.owner = owner = _step_owner(block)
    inner = compiled.fn
    compiled.jit_fn = inner  # raw jax.jit callable: .lower() = AOT
    # trace+StableHLO without XLA compile (tools/bench_passes.py times
    # the trace/lower phase through this)
    pending = [True]
    call = [inner]  # after the first call: what `step_store` made of it

    def fn(*args):
        if not pending:
            return call[0](*args)
        with _first_call(owner):
            call[0], result = step_store.first_call(
                inner, args, store_key, owner)
        pending.clear()
        return result

    compiled.fn = fn
    return compiled


# What rides on a Program beside `to_dict()` (so beside its fingerprint)
# and a lowering, a step maker or the state's placement reads: each is in
# `_store_key`. Whoever teaches a lowering to read another adds it here;
# `tests/test_step_store.py` reads the package's source for every private
# attribute of a Program and fails on one that is in neither tuple.
_THE_LOWERINGS_READ = (
    "_amp_dtype", "_amp_black_list", "_amp_white_list",  # LoweringContext
    "_pipeline_microbatches", "_pipeline_loss",  # the microbatched step
    "_recompute_loss",  # the recompute step
    "_sharding_specs", "_autoshard_specs",  # placement; `lookup_table`
    "_is_test_clone",
)
# ... and what no trace reads: who built or runs the Program (the mesh a
# fleet strategy resolves to is in `asked`), and what a compile leaves
_NOT_THE_LOWERINGS = (
    "_version", "_cached_fp", "_ckpt_manager", "_fleet_strategy",
    "_fleet_compiled", "_feed_shardings", "_layout_opt_stats",
    "_autoshard_plan",
    "_program",  # a CompiledProgram's: the Program it wraps
)


def _rides_on(program) -> tuple:
    def said(value):
        if isinstance(value, (set, frozenset)):
            return sorted(map(str, value))
        if isinstance(value, dict):
            return sorted((str(k), str(v)) for k, v in value.items())
        return repr(value)

    return tuple((name, said(getattr(program, name, None)))
                 for name in _THE_LOWERINGS_READ)


def _lowered_from_elsewhere(program) -> bool:
    """A lowering that lives outside this package (`register_op` in a
    user's module, one patched in place) is in no digest of the store's:
    a step that takes one is not stored."""
    from .ops.registry import get_op, has_op

    package = __name__.split(".")[0] + "."
    return any(
        t and has_op(t)
        and not (get_op(t).lower.__module__ or "").startswith(package)
        for block in program.blocks for op in block.ops
        # `__auto_grad__` runs its forward op's lowering again
        for t in (op.type, op.attr("fwd_type")))


def _store_key(program, lowered, asked, is_test, jit_kwargs):
    """What `step_store` files a step under, from what is in hand without
    tracing it: `_prepare_run`'s key without the scope's identity
    (`asked`), the Program as it was handed in and as `_compile` lowers it
    (after the passes, which may have read the scope), each without its
    seed (an argument of the step: two seeds, one executable) and with
    what rides on it outside its fingerprint (`_THE_LOWERINGS_READ`: the
    AMP lists that decide an op's precision, the pipeline's loss, the
    specs), and what the jit was told. The arguments' avals and
    shardings, which say the rest of what the scope gave, and the
    process's surroundings are the store's to add. Everything the three
    step makers close over follows from these, but a lowering from
    outside the package: None, not stored."""
    # both: a pass may fold the authored ops into one of its own
    if _lowered_from_elsewhere(program) or _lowered_from_elsewhere(lowered):
        return None
    return (program.fingerprint(with_seed=False), _rides_on(program),
            lowered.fingerprint(with_seed=False), _rides_on(lowered),
            asked, is_test, jit_kwargs)


def check_nan_result(result, compiled, scope):
    """PADDLE_TPU_CHECK_NAN_INF result handling for Executor.run: one
    stacked host fetch of all flags (per-op
    bool() reads would cost a device round-trip each), offender naming in
    execution order, and state persistence so the scope stays debuggable
    after the donated buffers are gone."""
    fetches, new_state, flag_vals = result
    names = getattr(compiled, "nan_names", None) or []
    flags = np.asarray(jnp.stack(flag_vals)) if flag_vals else np.ones(0)
    bad = [n for n, ok in zip(names, flags) if not bool(ok)]
    if bad:
        for n, v in new_state.items():
            scope.set(n, v)
        # flags are per-op outputs in execution order on every path now
        # (the GSPMD pipeline runs ordinary traced code); the
        # fetch:/state: prefix branch survives for older coarse-grained
        # flag producers
        granularity = (
            "fetch/state values (pipeline meshes check variables, not "
            "op order)" if bad[0].startswith(("fetch:", "state:"))
            else "op outputs (first offenders, in execution order)"
        )
        raise RuntimeError(
            f"nan/inf detected in {granularity}: " + ", ".join(bad[:8])
            + " — FLAGS_check_nan_inf analog, reference operator.cc:949"
        )
    return fetches, new_state


class Executor:
    def __init__(self, place: Place = None):
        self.place = place or TPUPlace()
        # a TPUPlace on a process with no TPU raises here, naming what
        # JAX found, instead of running the program on that
        self.place.require_backend()
        # LRU-bounded (PADDLE_TPU_JIT_CACHE_CAP, default 256): the
        # serving coalescer feeds one executable per padded shape
        # bucket through here — a long-lived server must not leak
        # compiled programs for shapes it no longer sees
        from collections import OrderedDict as _OD

        self._cache: "_OD[tuple, _CompiledStep]" = _OD()
        self._multi_cache: dict[tuple, object] = {}  # run_repeated wrappers
        self._sharding_sigs: dict = {}  # program key -> last mesh signature
        self._seed_counter = 0
        self._dispatch_count = 0  # heartbeat tick (every dispatch)

    # ------------------------------------------------------------------
    def _program_key(self, program: Program) -> str:
        cached = getattr(program, "_cached_fp", None)
        if cached and cached[0] == program._version:
            return cached[1]
        fp = program.fingerprint()
        program._cached_fp = (program._version, fp)
        return fp

    def _analyze_block(self, program, block, feed_names, scope):
        """Classify vars: state (persistables read/written), feeds, locals.
        Recurses into control-flow sub-blocks (while/cond), whose bodies may
        be the only readers of a persistable (e.g. weights used in a loop)."""
        state_read, state_written = set(), set()
        defined = set(feed_names)

        def walk(blk):
            for op in blk.ops:
                for n in op.input_arg_names():
                    if not n:
                        continue
                    v = blk._find_var_recursive(n)
                    if v is not None and v.persistable and n not in defined:
                        state_read.add(n)
                for attr in op.attrs.values():
                    if hasattr(attr, "ops") and hasattr(attr, "vars"):
                        walk(attr)
                for n in op.output_arg_names():
                    if not n:
                        continue
                    v = blk._find_var_recursive(n)
                    if v is not None and v.persistable:
                        state_written.add(n)
                    defined.add(n)

        walk(block)
        return state_read, state_written

    # ------------------------------------------------------------------
    def _make_microbatched_step(
        self, program, block, feed_names, fetch_names, state_names,
        micro, is_test, mesh,
    ):
        """Pipeline/gradient-merge execution (PipelineOptimizer): split the
        block at the op-role boundary the reference uses for program cutting
        (optimizer.py:2683), lax.scan the fwd+bwd segment over `micro`
        microbatches accumulating averaged gradients, then run the
        optimizer/LR segment once on the accumulated grads."""
        post_role = core_op_role.Optimize | core_op_role.LRSched
        ops = list(block.ops)
        fwd_ops = [
            op for op in ops
            if not ((op.attrs.get("op_role") or 0) & post_role)
        ]
        post_ops = [
            op for op in ops
            if (op.attrs.get("op_role") or 0) & post_role
        ]
        fwd_produced = {n for op in fwd_ops for n in op.output_arg_names()}
        post_reads = {n for op in post_ops for n in op.input_arg_names()}
        # values flowing fwd-segment -> opt-segment: @GRAD vars are averaged
        # across microbatches, anything else takes its last-microbatch value
        carried = sorted(post_reads & fwd_produced)
        grad_carried = [n for n in carried if n.endswith(GRAD_SUFFIX)]
        other_carried = [n for n in carried if not n.endswith(GRAD_SUFFIX)]
        fwd_fetches = [
            n for n in fetch_names
            if n in fwd_produced or n in set(state_names) | set(feed_names)
        ]
        state_set = set(state_names)

        def _zero_like_grad(name, state):
            pname = name[: -len(GRAD_SUFFIX)]
            if pname in state:
                return jnp.zeros(state[pname].shape, state[pname].dtype)
            v = block._find_var_recursive(name)
            if v is None or v.shape is None:
                raise RuntimeError(
                    f"cannot infer shape for accumulated grad {name!r}"
                )
            return jnp.zeros(tuple(v.shape), JNP_DTYPE(v.dtype))

        check_nan = os.environ.get("PADDLE_TPU_CHECK_NAN_INF") == "1"
        nan_names: list = []  # filled at trace time, execution order

        def step(state: dict, feeds: dict, rng_key):
            from .ops.tensor_ops import batch_flexible_reshapes

            with batch_flexible_reshapes(micro):
                return _step_inner(state, feeds, rng_key)

        step._nan_names = nan_names

        def _step_inner(state: dict, feeds: dict, rng_key):
            m_feeds = {}
            for n, a in feeds.items():
                if a.ndim == 0 or a.shape[0] % micro != 0:
                    raise ValueError(
                        f"feed {n!r} batch dim {a.shape} not divisible by "
                        f"num_microbatches={micro}"
                    )
                m_feeds[n] = a.reshape(
                    (micro, a.shape[0] // micro) + a.shape[1:]
                )

            def micro_step(carry, xs):
                st, acc, _last = carry
                mfeed, idx = xs
                ctx = LoweringContext(
                    program,
                    rng_key=jax.random.fold_in(rng_key, idx),
                    is_test=is_test,
                    mesh=mesh,
                )
                if check_nan:
                    # FLAGS_check_nan_inf under microbatching: per-op
                    # flags AND-reduce over the scan below
                    ctx.nan_flags = {}
                ctx.values.update(st)
                ctx.values.update(mfeed)
                for op in fwd_ops:
                    lower_op(ctx, op)
                new_st = {
                    n: ctx.values[n] if n in ctx.values else st[n]
                    for n in state_names
                }
                acc2 = {
                    g: acc[g] + ctx.get(g).astype(acc[g].dtype) / micro
                    for g in grad_carried
                }
                last = {n: ctx.get(n) for n in other_carried}
                outs = [ctx.get(n) for n in fwd_fetches]
                flags = ()
                if check_nan:
                    nan_names[:] = list(ctx.nan_flags.keys())
                    flags = tuple(ctx.nan_flags.values())
                return (new_st, acc2, last), (outs, flags)

            acc0 = {g: _zero_like_grad(g, state) for g in grad_carried}
            if other_carried:
                # trace one microbatch abstractly to size the non-grad carries
                mfeed0 = {n: a[0] for n, a in m_feeds.items()}
                shapes = jax.eval_shape(
                    lambda st, mf: micro_step(
                        (st, acc0, None), (mf, 0))[0][2],
                    state, mfeed0,
                )
                last0 = {
                    n: jnp.zeros(s.shape, s.dtype) for n, s in shapes.items()
                }
            else:
                last0 = {}
            (final_state, acc, last), (outs, mb_flags) = jax.lax.scan(
                micro_step,
                (state, acc0, last0),
                (m_feeds, jnp.arange(micro)),
            )

            ctx = LoweringContext(
                program,
                rng_key=jax.random.fold_in(rng_key, micro + 1),
                is_test=is_test,
                mesh=mesh,
            )
            if check_nan:
                ctx.nan_flags = {}
            ctx.values.update(final_state)
            ctx.values.update(acc)
            ctx.values.update(last)
            for op in post_ops:
                lower_op(ctx, op)
            new_state = {
                n: ctx.values[n] if n in ctx.values else final_state[n]
                for n in state_names
            }

            # fetch semantics: per-example values (leading dim == microbatch
            # size) are concatenated back to the full batch; per-batch
            # reductions (loss etc.) are averaged (float) or taken from the
            # last microbatch (ints) — matches what the full-batch run of the
            # same program would return
            mb_size = next(iter(m_feeds.values())).shape[1] if m_feeds else 0
            fetches = []
            for n in fetch_names:
                if n in fwd_fetches:
                    v = outs[fwd_fetches.index(n)]  # [micro, ...]
                    if v.ndim >= 2 and v.shape[1] == mb_size and mb_size:
                        fetches.append(
                            v.reshape((micro * v.shape[1],) + v.shape[2:])
                        )
                    elif jnp.issubdtype(v.dtype, jnp.floating):
                        fetches.append(jnp.mean(v, axis=0))
                    else:
                        fetches.append(v[-1])
                else:
                    fetches.append(ctx.get(n))
            if check_nan:
                # AND each op's flag over the microbatches, then append
                # the optimizer segment's own flags. Names and flags stay
                # index-aligned: duplicates (an optimizer op rewriting a
                # fwd-segment name) keep BOTH entries.
                all_flags = tuple(
                    jnp.all(f) for f in mb_flags
                ) + tuple(ctx.nan_flags.values())
                nan_names.extend(ctx.nan_flags.keys())
                return fetches, new_state, all_flags
            return fetches, new_state

        return step

    # ------------------------------------------------------------------
    def _make_recompute_step(
        self, program, block, feed_names, fetch_names, state_names,
        is_test, mesh,
    ):
        """RecomputeOptimizer execution: gradients come from jax.grad over
        the FORWARD lowering (explicit backward ops are skipped) so
        recompute_scope segments can be wrapped in jax.checkpoint —
        activations inside a segment are rematerialized during backward
        instead of living in HBM across the step (reference capability:
        incubate RecomputeOptimizer; SURVEY.md §7 'memory parity')."""
        post_role = core_op_role.Optimize | core_op_role.LRSched
        fwd_ops = [
            op for op in block.ops
            if not ((op.attrs.get("op_role") or 0)
                    & (post_role | core_op_role.Backward))
        ]
        post_ops = [
            op for op in block.ops
            if (op.attrs.get("op_role") or 0) & post_role
        ]
        loss_name = program._recompute_loss
        post_reads = {n for op in post_ops for n in op.input_arg_names()}
        grad_names = sorted(
            n for n in post_reads if n.endswith(GRAD_SUFFIX)
        )
        param_names = [n[: -len(GRAD_SUFFIX)] for n in grad_names]
        state_set = set(state_names)
        for p in param_names:
            if p not in state_set:
                raise RuntimeError(
                    f"recompute: optimizer reads {p}@GRAD but {p} is not "
                    "persistable state"
                )

        # group consecutive fwd ops by their recompute segment tag
        groups = []  # (segment_or_None, [ops])
        for op in fwd_ops:
            seg = op.attrs.get("recompute_segment")
            if groups and groups[-1][0] == seg:
                groups[-1][1].append(op)
            else:
                groups.append((seg, [op]))

        fwd_produced = (
            {n for op in fwd_ops for n in op.output_arg_names()}
            | set(feed_names)
        )
        fwd_fetches = [
            n for n in fetch_names
            if n in fwd_produced and not n.endswith(GRAD_SUFFIX)
        ]
        grad_set = set(grad_names)
        for n in fetch_names:
            if n in fwd_fetches or n in grad_set or n in state_set:
                continue
            if not any(n in op.output_arg_names() for op in post_ops):
                raise RuntimeError(
                    f"fetch {n!r} is not available under RecomputeOptimizer"
                    " (backward intermediates are rematerialized, not "
                    "stored) — fetch it without recompute"
                )

        check_nan = os.environ.get("PADDLE_TPU_CHECK_NAN_INF") == "1"
        nan_names: list = []  # filled at trace time, execution order

        def step(state: dict, feeds: dict, rng_key):
            non_param_state = {
                n: v for n, v in state.items() if n not in set(param_names)
            }
            params = {n: state[n] for n in param_names}

            def run_forward(params):
                ctx = LoweringContext(
                    program, rng_key=rng_key, is_test=is_test, mesh=mesh
                )
                if check_nan:
                    ctx.nan_flags = {}
                ctx.values.update(non_param_state)
                ctx.values.update(feeds)
                ctx.values.update(params)
                for gi, (seg, ops) in enumerate(groups):
                    if seg is None:
                        for op in ops:
                            lower_op(ctx, op)
                        continue
                    # each segment gets its own RNG stream (child() alone
                    # would give consecutive segments identical counters ->
                    # identical dropout masks across layers)
                    ctx._rng_counter += 1000 * (gi + 1)
                    # jax.checkpoint over the segment: inputs are every
                    # name the segment reads that already has a value;
                    # outputs are everything it defines
                    reads, defined = [], set()
                    for op in ops:
                        for n in op.input_arg_names():
                            if n and n not in defined and ctx.has(n):
                                if n not in reads:
                                    reads.append(n)
                        defined.update(
                            n for n in op.output_arg_names() if n
                        )
                    out_names = sorted(defined)

                    seg_flag_names: list = []  # set at trace time

                    def seg_fn(in_vals, _ops=tuple(ops), _reads=tuple(reads),
                               _outs=tuple(out_names),
                               _fn=seg_flag_names):
                        sub = ctx.child()
                        sub.values = dict(ctx.values)
                        if check_nan:
                            # flags become checkpoint OUTPUTS so they
                            # escape the remat region (scalars — cheap
                            # to store, not worth rematerializing)
                            sub.nan_flags = {}
                        sub.values.update(dict(zip(_reads, in_vals)))
                        for op in _ops:
                            lower_op(sub, op)
                        res = tuple(sub.get(n) for n in _outs)
                        if check_nan:
                            _fn[:] = list(sub.nan_flags.keys())
                            res = res + tuple(sub.nan_flags.values())
                        return res

                    outs = jax.checkpoint(seg_fn)(
                        tuple(ctx.get(n) for n in reads)
                    )
                    for n, v in zip(out_names, outs):
                        ctx.set(n, v)
                    if check_nan:
                        for n, v in zip(seg_flag_names,
                                        outs[len(out_names):]):
                            ctx.nan_flags[n] = v
                loss = ctx.get(loss_name).reshape(())
                new_state = {
                    n: ctx.values[n] if n in ctx.values else state[n]
                    for n in state_names
                }
                fwd_vals = [ctx.get(n) for n in fwd_fetches]
                fwd_flags = ()
                if check_nan:
                    nan_names[:] = list(ctx.nan_flags.keys())
                    fwd_flags = tuple(ctx.nan_flags.values())
                return loss, (new_state, fwd_vals, fwd_flags)

            grads, (mid_state, fwd_vals, fwd_flags) = jax.grad(
                run_forward, has_aux=True
            )(params)

            ctx = LoweringContext(
                program, rng_key=jax.random.fold_in(rng_key, 7),
                is_test=is_test, mesh=mesh,
            )
            if check_nan:
                ctx.nan_flags = {}
            ctx.values.update(mid_state)
            for g, p in zip(grad_names, param_names):
                ctx.values[g] = grads[p]
            for op in post_ops:
                lower_op(ctx, op)
            new_state = {
                n: ctx.values[n] if n in ctx.values else mid_state[n]
                for n in state_names
            }
            fetches = []
            for n in fetch_names:
                if n in fwd_fetches:
                    fetches.append(fwd_vals[fwd_fetches.index(n)])
                elif n in grad_set:
                    fetches.append(grads[n[: -len(GRAD_SUFFIX)]])
                elif n in new_state:
                    fetches.append(new_state[n])  # post-update value
                else:
                    fetches.append(ctx.get(n))
            if check_nan:
                all_flags = fwd_flags + tuple(ctx.nan_flags.values())
                nan_names.extend(ctx.nan_flags.keys())
                return fetches, new_state, all_flags
            return fetches, new_state

        step._nan_names = nan_names
        return step

    # ------------------------------------------------------------------
    def _compile(
        self,
        program,
        block,
        feed_sig,
        fetch_names,
        scope,
        is_test,
        mesh=None,
        sharding_specs=None,
        batch_axes=("batch",),
        build_strategy=None,
        zero1=False,
        asked=None,
    ):
        """`asked` is `_prepare_run`'s key without the Program and the
        scope, for `step_store`; without it the step is not stored."""
        from .parallel import mesh as mesh_mod

        handed = program  # the passes below put their clone in its name
        feed_names = tuple(n for n, _, _ in feed_sig)
        pipe_n = mesh.shape.get("pipe", 1) if mesh is not None else 1
        use_pp_schedule = pipe_n > 1 and not is_test
        pipe_specs = {}
        if use_pp_schedule:
            # Program-level pipeline parallelism over device_guard stages
            # (reference: PipelineOptimizer program cutting,
            # optimizer.py:2683 + section_worker.cc). GSPMD-native: the
            # stage structure is VALIDATED (non-decreasing tags, loss on
            # the last stage) and classified for ZeRO-over-pipe state
            # sharding, then execution is the same microbatched
            # grad-accumulation step as a single device — jitted over the
            # mesh, with params/accumulators sharded along 'pipe' at rest
            # and the compiler inserting the gathers/reduce-scatters the
            # legacy shard-map schedule hand-wrote.
            from .parallel.program_pipeline import pipeline_state_specs

            state_read0, state_written0 = self._analyze_block(
                program, block, feed_names, scope
            )
            pipe_specs = pipeline_state_specs(
                program, block, feed_names,
                tuple(sorted(state_read0 | state_written0)),
                pipe_n, sharding_specs=sharding_specs,
            )
        # zero1 arrives as an explicit argument from the CompiledProgram
        # handle (never a Program attribute — see with_data_parallel)
        zero1 = bool(zero1) and not is_test
        # IR passes (DCE / const-fold / optimizer fusion) rewrite a CLONE
        # of the program before the trace. Pipeline programs stay exempt
        # (their classification above reads the authored op list; the
        # device-tagged stage structure must survive for validation).
        if not use_pp_schedule:
            from .passes import apply_program_passes

            program, block, _pass_stats = apply_program_passes(
                program, feed_names, fetch_names,
                build_strategy=build_strategy,
                scope=scope,
                mesh=mesh,
                feed_sig=feed_sig,
            )
        state_read, state_written = self._analyze_block(
            program, block, feed_names, scope
        )
        for n in sorted(state_read):
            if not scope.has(n) or scope.get(n) is None:
                raise RuntimeError(
                    f"persistable var {n!r} is not initialized in scope — "
                    "run the startup program first "
                    "(reference behavior: executor.cc var-init check)"
                )
        state_names = tuple(sorted(state_read | state_written))
        written_only = frozenset(state_written - state_read)

        micro = 1 if is_test else getattr(program, "_pipeline_microbatches", 1)
        if pipe_n > 1 and is_test:
            # eval/inference on a pipeline mesh: there is no microbatch
            # schedule to run, so fold the pipe axis into data
            # parallelism — the whole-graph GSPMD path shards the eval
            # batch over batch x pipe (pipe-sharded training params are
            # re-gathered by GSPMD automatically)
            batch_axes = tuple(dict.fromkeys(tuple(batch_axes) + ("pipe",)))
        # the plain step alone carries its device counts out; in the two
        # other forms a context has none to add to, and says so
        # (`device_counts_dropped`)
        count_names = ()
        if micro > 1:
            step = self._make_microbatched_step(
                program, block, feed_names, fetch_names, state_names,
                micro, is_test, mesh,
            )
        elif not is_test and getattr(program, "_recompute_loss", None):
            step = self._make_recompute_step(
                program, block, feed_names, fetch_names, state_names,
                is_test, mesh,
            )
        else:
            check_nan = os.environ.get("PADDLE_TPU_CHECK_NAN_INF") == "1"
            if not check_nan:  # whose third result is the flags
                count_names = _device_count_names(block)

            nan_names: list = []  # filled at trace time, execution order

            def step(state: dict, feeds: dict, rng_key):
                ctx = LoweringContext(
                    program, rng_key=rng_key, is_test=is_test, mesh=mesh
                )
                if check_nan:
                    # FLAGS_check_nan_inf analog (operator.cc:949-961)
                    ctx.nan_flags = {}
                if count_names:
                    ctx.device_counts = {}
                ctx.values.update(state)
                ctx.values.update(feeds)
                lower_block(ctx, block)
                fetches = [ctx.get(n) for n in fetch_names]
                if count_names:
                    undeclared = set(ctx.device_counts) - set(count_names)
                    if undeclared:
                        raise RuntimeError(
                            f"device counts {sorted(undeclared)} are counted "
                            "by a lowering whose op does not declare them "
                            "(register_op(..., device_counts=...))")
                    fetches.append(jnp.stack([
                        jnp.asarray(ctx.device_counts.get(n, 0), jnp.int32)
                        for n in count_names]))
                new_state = {
                    n: ctx.values[n] if n in ctx.values else state[n]
                    for n in state_names
                }
                if check_nan:
                    # names travel OUTSIDE the jit (a dict output would be
                    # re-sorted by the pytree flatten, losing exec order)
                    nan_names[:] = list(ctx.nan_flags.keys())
                    return fetches, new_state, tuple(ctx.nan_flags.values())
                return fetches, new_state

            step._nan_names = nan_names

        def finish(compiled, jit_kwargs):
            compiled.nan_names = getattr(step, "_nan_names", None)
            compiled.written_only = written_only
            compiled.count_names = count_names
            return _instrument_compiled(
                compiled, block, asked and functools.partial(
                    _store_key, handed, program, asked, is_test, jit_kwargs))

        if mesh is not None:
            # GSPMD path (CompiledProgram / fleet / dryrun): the
            # spec-assignment layer (parallel/mesh.py) maps every Program
            # IR persistable to a NamedSharding on the unified
            # (batch, model, pipe) mesh — annotations (tensor/expert/PS
            # splits), ZeRO-1 accumulators along 'batch', pipeline state
            # along 'pipe' — and feeds shard their batch dim; XLA inserts
            # and overlaps the collectives.
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            extra_specs = dict(pipe_specs)
            if zero1:
                extra_specs.update(mesh_mod.zero1_accumulators(
                    block, state_names, mesh.shape.get("batch", 1)
                ))
            # autoshard (opt-in): the shard_propagation pass attached
            # the planner's assignment to the program clone — it wins
            # over the manual zero1 flag (the planner's choice IS the
            # placement; the executor stays the single emission point)
            auto_specs = getattr(program, "_autoshard_specs", None)
            if auto_specs:
                extra_specs.update(auto_specs)
            state_sh = mesh_mod.assign_state_shardings(
                program, block, state_names, mesh, scope=scope,
                extra_specs=extra_specs,
            )
            feed_sh = mesh_mod.feed_shardings(mesh, feed_sig, batch_axes)

            # sharding_recompiles: bump when a program recompiles under a
            # DIFFERENT (mesh shape, spec assignment) signature than its
            # previous compile — a flipped sharding invalidating the
            # cached executable, observable next to the compile counters
            all_specs = dict(getattr(program, "_sharding_specs", {}) or {})
            all_specs.update(extra_specs)
            sig = mesh_mod.mesh_signature(mesh, all_specs)
            pkey = self._program_key(program)
            prev = self._sharding_sigs.get(pkey)
            if prev is not None and prev != sig:
                profiler.bump_counter("sharding_recompiles")
            self._sharding_sigs[pkey] = sig

            # collective_bytes_estimate: crude per-step wire-traffic gauge
            # — each state var counts once for the batch-axis grad
            # all-reduce (train only) and once more if it lives sharded
            # (GSPMD all-gather on use / reduce-scatter on update). An
            # estimate for dashboards, not a measurement.
            est = 0
            batch_n = mesh.shape.get("batch", 1)
            for n in state_names:
                live = scope.get(n) if scope.has(n) else None
                sz = int(getattr(live, "size", 0) or 0)
                item = getattr(getattr(live, "dtype", None), "itemsize", 4)
                nbytes = sz * int(item or 4)
                sharded = any(el is not None for el in state_sh[n].spec)
                if batch_n > 1 and not is_test:
                    est += nbytes
                if sharded:
                    est += nbytes
            profiler.set_counter("collective_bytes_estimate", est)

            out_sh = [
                [NamedSharding(mesh, P())] * (
                    len(fetch_names) + bool(count_names)),
                state_sh,
            ]
            if (
                os.environ.get("PADDLE_TPU_CHECK_NAN_INF") == "1"
                and getattr(step, "_nan_names", None) is not None
            ):
                # flags output present iff the env flag is on AND the
                # builder supports it (plain, microbatched AND recompute
                # all attach _nan_names as of round 3)
                out_sh.append(NamedSharding(mesh, P()))
            jit_kwargs = dict(donate_argnums=(0,),
                              in_shardings=(state_sh, feed_sh, None),
                              out_shardings=tuple(out_sh))
            compiled = _CompiledStep(step, jit_kwargs, state_names,
                                     feed_names, fetch_names)
            # dispatch-side reshard map: a live COMMITTED array whose
            # layout disagrees with this compile's assignment (e.g. a
            # replicated moment from a pre-zero1 run) must be device_put
            # onto the new sharding before the call — jit raises on the
            # mismatch instead of resharding committed args
            compiled.state_shardings = dict(state_sh)
            compiled.feed_shardings = feed_sh
            return finish(compiled, jit_kwargs)

        # The state keeps the default layout. `Layout.AUTO` on it does
        # not survive the persistent compile cache: an executable read
        # back reports default parameter layouts, so `jit` relays every
        # convolution filter out on the host in each dispatch, and an
        # array left in a compiler-chosen layout in the scope is misread
        # by the next `jit` that takes it (PERF.md, PR 27).
        jit_kwargs = dict(donate_argnums=(0,))
        compiled = _CompiledStep(step, jit_kwargs, state_names, feed_names,
                                 fetch_names)
        return finish(compiled, jit_kwargs)

    # ------------------------------------------------------------------
    def _unwrap(self, program):
        """What `run` and `run_repeated` were handed, as (the Program to
        step, the CompiledProgram that says how to lay it over a mesh, or
        None). Every stage below takes the pair; there is no other path."""
        if program is None:
            from .framework import default_main_program

            program = default_main_program()
        if isinstance(program, CompiledProgram):
            return program._program, program
        # fleet collective path: a program minimized through
        # fleet.distributed_optimizer carries its DistributedStrategy —
        # run it over the strategy's mesh (all chips) transparently
        strategy = getattr(program, "_fleet_strategy", None)
        if strategy is None or len(jax.devices()) == 1:
            return program, None
        cp = getattr(program, "_fleet_compiled", None)
        if cp is None:
            cp = CompiledProgram(program).with_data_parallel(
                zero1=bool(getattr(strategy, "zero1", False)))
            cp._mesh = strategy.build_mesh()
            program._fleet_compiled = cp
        return program, cp

    def run(
        self,
        program: Program = None,
        feed: dict = None,
        fetch_list=None,
        scope: Scope = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,
    ):
        program, cp = self._unwrap(program)
        scope = scope or global_scope()
        # PERF.md lists what reads the pt.exe.* spans
        with RecordEvent("pt.exe.prepare"):
            compiled, feeds, fetch_names = self._prepare_run(
                program, feed, fetch_list, scope, cp
            )
        with RecordEvent("pt.exe.state"):
            state = self._assemble_state(compiled, scope)

        result = self._dispatch(program, compiled, state, feeds)
        # the donated inputs are dead: the write-back, which replaces them
        # in the scope, releases them (1,010 arrays for BERT-base), and
        # not the return from this frame
        del state
        with RecordEvent("pt.exe.writeback"):
            return self._write_back(
                program, compiled, result, scope, return_numpy,
                _ckpt_manager(program, cp))

    def _dispatch(self, program, compiled, state, feeds):
        """Enqueue one step."""
        with RecordEvent("pt.exe.dispatch"):
            # functional PRNG: the step folds a per-run counter into the
            # program's seed so randomness varies across steps; with
            # program.random_seed set the whole sequence is reproducible
            # from run 0 (reference: Program.random_seed semantics). The
            # seed is read at each dispatch and travels as an argument: a
            # seed changed between two runs meets no stale executable
            seed = _seed_words(program, self._seed_counter + 1)

            # chaos site: a raise here is a device/runtime failure at the
            # dispatch boundary (before any executor-visible mutation — the
            # seed counter only advances once the step actually dispatched,
            # so a caught-and-retried failure replays the same PRNG tick)
            fault_point("executor.dispatch")
            result = compiled.fn(state, feeds, seed)
        self._seed_counter += 1
        return result

    def _step_boundary(self, program, scope, new_state, mgr, steps=1):
        """What follows a dispatch of `steps` training steps: the new
        state into the scope, then the step boundary's hooks (`mgr` is the
        attached CheckpointManager or None)."""
        for n, v in new_state.items():
            scope.set(n, v)

        # state written back: trainer.step is the chaos anchor for
        # "crash/wedge at step N", then the heartbeat publishes the
        # supervised rank's progress (of a run_repeated window, its final
        # step). BOTH run before the checkpoint hook below on purpose — a
        # crash or hold here leaves the newest snapshot at step N-1, so
        # the respawned attempt RETRAINS step N (and re-emits its
        # fetches/logs) instead of resuming past a step nobody observed
        # complete. A hold also keeps THIS step's heartbeat from landing —
        # the watchdog sees progress stuck at N-1.
        self._dispatch_count += 1
        fault_point("trainer.step")
        _trainer_heartbeat(
            None if mgr is None else mgr._auto_step + steps - 1,
            self._dispatch_count)

        # resilience wiring: a CheckpointManager attached to this program
        # (manager.attach) counts each dispatch as `steps` steps and
        # snapshots the persistable state on its cadence (of a window, the
        # final state: the intermediate ones lived only inside the scan).
        # The host pull happens here at the step boundary (the donated
        # state buffers die on the next dispatch); serialization + file
        # I/O flush on the engine's background thread, overlapping the
        # next step.
        if mgr is not None:
            mgr._on_executor_step(program, scope, self, steps=steps)

    def _write_back(self, program, compiled, result, scope, return_numpy,
                    mgr):
        """What follows a step's dispatch: the new state and the step
        boundary's hooks, then the fetches."""
        if len(result) == 3:  # PADDLE_TPU_CHECK_NAN_INF=1 debug mode
            fetches, new_state = check_nan_result(result, compiled, scope)
        else:
            fetches, new_state = result
        if compiled.count_names:
            profiler.hold_device_counts(compiled.count_names, fetches.pop())
        self._step_boundary(program, scope, new_state, mgr)
        if return_numpy:
            return [np.asarray(f) for f in fetches]
        return list(fetches)

    # ------------------------------------------------------------------
    def _prepare_run(self, program, feed, fetch_list, scope, cp=None):
        """run()'s prelude: feed normalization, the compile-cache lookup
        (the one key, the one LRU cap) and the device feeds. `cp` is the
        CompiledProgram `_unwrap` found, or None for one device. Returns
        (compiled, device feeds dict, fetch_names)."""
        feed = feed or {}
        fetch_list = fetch_list or []
        fetch_names = [
            v.name if isinstance(v, Variable) else str(v) for v in fetch_list
        ]

        block = program.global_block()
        feed_items = []
        for name in sorted(feed.keys()):
            v = block._find_var_recursive(name)
            dtype = v.dtype if v is not None else None
            arr = _as_feed_array(feed[name], dtype)
            feed_items.append((name, arr))
        feed_sig = tuple(
            (name, arr.shape, str(arr.dtype)) for name, arr in feed_items
        )

        mesh = strategy = placement = None
        zero1 = False
        if cp is not None:
            from .parallel.mesh import mesh_signature

            mesh, strategy, zero1 = (
                cp._get_mesh(), cp._build_strategy, cp._zero1)
            # mesh shape + spec assignment: flipping a shard_parameter
            # annotation (or the zero1 flag) must recompile, not serve
            # the stale executable
            placement = (
                mesh_signature(mesh, program._sharding_specs), zero1)
        check_nan = os.environ.get("PADDLE_TPU_CHECK_NAN_INF") == "1"
        asked = (
            feed_sig,
            tuple(fetch_names),
            getattr(program, "_pipeline_microbatches", 1),
            getattr(program, "_recompute_loss", None),
            # amp dtype rides on the program WITHOUT bumping _version
            # (mixed_precision.decorate / the float16-transpiler analog
            # set it post-build): without it in the key, flipping a
            # program to bf16 after an fp32 run served the fp32 step
            getattr(program, "_amp_dtype", None),
            check_nan,
            # flipping PADDLE_TPU_PASSES between runs must recompile —
            # a stale step would keep the old pass set's graph
            _resolve_pass_names(strategy),
            placement,
        )
        key = (self._program_key(program), id(scope)) + asked
        compiled = self._cache.get(key)
        if compiled is None:
            with RecordEvent("pt.exe.compile"):
                compiled = self._compile(
                    program, block, feed_sig, fetch_names, scope,
                    # on a mesh an explicit for_test clone compiles as
                    # eval (on pp meshes this folds pp into data
                    # parallelism instead of running the microbatch
                    # schedule); any other program keeps train-mode
                    # semantics
                    is_test=mesh is not None
                    and bool(getattr(program, "_is_test_clone", False)),
                    mesh=mesh,
                    sharding_specs=program._sharding_specs,
                    build_strategy=strategy,
                    zero1=zero1,
                    # a NaN check's trace leaves the flags' names in this
                    # process: such a step is not stored
                    asked=None if check_nan else asked,
                )
            # _assemble_state lays replicated state over it on a fleet of
            # processes
            compiled.mesh = mesh
            self._cache[key] = compiled
            from .dygraph.jit import _jit_cache_cap

            while len(self._cache) > _jit_cache_cap(256):
                # LRU eviction: the evicted (program, shape-bucket)
                # recompiles on its next dispatch
                self._cache.popitem(last=False)
                profiler.bump_counter("executor_cache_evictions")
        else:
            self._cache.move_to_end(key)
        if mesh is None or jax.process_count() == 1:
            # where the next batches are best put: the reader's stager
            # reads it at each batch (reader/stager.py::stage_feed). A
            # hint, so whatever arrives laid out otherwise is resharded
            # here, and counted: on a mesh that reshard runs on device 0
            # behind the step before, and the dispatch waits for it
            program._feed_shardings = want = compiled.feed_shardings
            feeds = {}
            for name, arr in feed_items:
                sharding = want.get(name)
                if not isinstance(arr, jax.Array):
                    arr = (jnp.asarray(arr) if sharding is None
                           else jax.device_put(arr, sharding))
                elif sharding is not None and arr.sharding != sharding:
                    profiler.bump_counter("feed_reshard_at_dispatch")
                    arr = jax.device_put(arr, sharding)
                feeds[name] = arr
        else:
            # multi-process (fleet) execution: each trainer feeds its
            # process-LOCAL batch shard (the reference's trainers read
            # disjoint file splits); assemble global arrays spanning all
            # processes
            feeds = {
                name: jax.make_array_from_process_local_data(
                    NamedSharding(
                        mesh,
                        P("batch", *([None] * (arr.ndim - 1)))
                        if arr.ndim else P(),
                    ),
                    np.asarray(arr),
                )
                for name, arr in feed_items
            }
        return compiled, feeds, fetch_names

    def _assemble_state(self, compiled, scope, placeholders=None):
        """Build the state dict for compiled.fn. `placeholders`, when a
        set is passed, collects the names that received the zero-scalar
        written-only placeholder (no settled scope value yet)."""
        mesh = compiled.mesh
        state_sh = fleet_rep = None
        if mesh is not None and jax.process_count() > 1:
            # on a fleet of processes the state is replicated — every
            # process initialized identically from the seeded startup
            # program
            fleet_rep = NamedSharding(mesh, P())
        elif mesh is not None:
            state_sh = compiled.state_shardings
        state = {}
        for n in compiled.state_names:
            val = scope.get(n) if scope.has(n) else None
            if val is None:
                if n not in compiled.written_only:
                    # a READ state var with no value would silently become
                    # a zero scalar — the reference errors instead
                    # (executor.cc var-init check)
                    raise RuntimeError(
                        f"persistable var {n!r} is read by the program but "
                        "holds no value — run the startup program (or load "
                        "checkpointed state) first"
                    )
                # written-only state (e.g. startup program creating params)
                val = jnp.zeros((), dtype=jnp.float32)
                if placeholders is not None:
                    placeholders.add(n)
            elif not isinstance(val, jax.Array):
                val = jnp.asarray(val)
            elif state_sh:
                want = state_sh.get(n)
                if want is None or val.sharding is want:
                    pass  # steady state: the step's own output
                elif val.sharding != want:
                    # one-time reshard: a committed layout from an
                    # earlier compile (different zero1/pipe specs)
                    # moves onto this compile's assignment; steady
                    # state re-enters already matching (out_shardings)
                    val = jax.device_put(val, want)
                else:
                    # equal and another object (a step that `step_store`
                    # loaded hands out its own): adopted, so that the
                    # next step's compare is the identity above and not
                    # a thousand `__eq__`s
                    state_sh[n] = val.sharding
            if fleet_rep is not None and val.is_fully_addressable:
                # anything else is already a global (possibly sharded)
                # array from a previous step — pass through, never fetch
                # to host
                val = jax.make_array_from_process_local_data(
                    fleet_rep, np.asarray(val))
            state[n] = val
        return state

    def run_repeated(
        self,
        program: Program = None,
        feed: dict = None,
        fetch_list=None,
        steps: int = 1,
        scope: Scope = None,
        return_numpy: bool = True,
    ):
        """Run the SAME program `steps` times with the SAME feed in ONE
        device dispatch: the persistable state (on a mesh, sharded and
        multi-process global arrays included) threads through an
        on-device lax.scan, the functional PRNG folds the same per-run
        counters run() would, and each fetch comes back stacked with a
        leading [steps] axis (last element == what the final run() would
        fetch).

        This is the steady-state benchmark/soak loop (the reference's
        repeat-run ParallelExecutor benchmarks): host dispatch is paid
        once per call instead of once per step. Numerics match `steps`
        consecutive run() calls exactly (same PRNG fold sequence).
        Constant-feed only by construction; for real data pipelines use
        run() per batch."""
        if steps < 1:
            raise ValueError(f"steps must be >= 1, got {steps}")
        if os.environ.get("PADDLE_TPU_CHECK_NAN_INF") == "1":
            raise RuntimeError(
                "run_repeated does not support PADDLE_TPU_CHECK_NAN_INF "
                "(per-op flag shapes vary per step); use run()"
            )
        program, cp = self._unwrap(program)
        scope = scope or global_scope()
        compiled, feeds, fetch_names = self._prepare_run(
            program, feed, fetch_list, scope, cp
        )
        placeholders: set = set()
        state = self._assemble_state(compiled, scope,
                                     placeholders=placeholders)
        if placeholders:
            raise RuntimeError(
                f"persistable vars {sorted(placeholders)} have no settled "
                "value yet — run the startup program before run_repeated "
                "(the scan carry needs stable shapes)"
            )

        multi_key = (id(compiled), steps)
        multi = self._multi_cache.get(multi_key)
        first = multi is None
        if first:
            # the step's nested jit (inlines under the outer one), never
            # the instrumented wrapper: the scan's jit is the one that
            # compiles here, so its first call below is the one that sets
            # the step's owner, and the wrapper's stays for the first
            # `run` of the step alone
            step_fn = compiled.nested_fn

            def multi(state, feeds, seed):
                def body(st, i):
                    fetches, new_state = step_fn(
                        st, feeds, seed.at[1].add(i.astype(seed.dtype))
                    )
                    return new_state, tuple(fetches)

                final_state, stacked = jax.lax.scan(
                    body, state, jnp.arange(steps)
                )
                return stacked, final_state

            # NO state donation: a mid-execution failure (OOM) must leave
            # the scope's arrays alive for the caller — donation would
            # delete them
            multi = _jit(multi)

        with (_first_call(compiled.owner) if first
              else contextlib.nullcontext()):
            stacked, new_state = multi(
                state, feeds, _seed_words(program, self._seed_counter + 1)
            )
        if first:  # kept once it has run: a scan whose first call raised
            # is built, and filed under its owner, again
            self._multi_cache[multi_key] = multi
        if compiled.count_names:  # stacked like a fetch: [steps, counts]
            *stacked, counts = stacked
            profiler.hold_device_counts(compiled.count_names, counts)
        # advance only on success: a failed trace must not skip PRNG
        # counters (the N-consecutive-run() equivalence contract)
        self._seed_counter += steps
        self._step_boundary(program, scope, new_state,
                            _ckpt_manager(program, cp), steps=steps)
        if return_numpy:
            return [np.asarray(f) for f in stacked]
        return list(stacked)

    # ------------------------------------------------------------------
    def _run_dataset(self, program, dataset, scope, fetch_list, fetch_info,
                     print_period, debug, num_threads=1):
        if dataset is None:
            raise ValueError("dataset is required")
        fetch_list = fetch_list or []
        fetch_info = fetch_info or [
            getattr(v, "name", str(v)) for v in fetch_list
        ]
        step = 0
        last = None
        # Double-buffer the DEVICE side too (round-2 weak item: parsing
        # was threaded but each step still uploaded its batch inline):
        # the shared DeviceStager (reader/stager.py — also behind
        # DataLoader's prefetch path) converts + device_puts batch N+1
        # while the compiled step for batch N executes, so host->device
        # transfer overlaps compute — the role of the reference's
        # buffered_reader (operators/reader/buffered_reader.cc) on the
        # dataset path.
        from .reader.stager import DeviceStager, stage_feed

        stepped = self._unwrap(program)[0]  # the Program the feeds belong to
        block = stepped.global_block()

        # multi-process fleet programs rebuild feeds with
        # make_array_from_process_local_data from HOST arrays
        # (_prepare_run) — device-staging there would force a download
        # per step; stage to device only in the single-process case
        to_device = jax.process_count() == 1

        def _stage(feed):
            out = {}
            for k, v in feed.items():
                var = block._find_var_recursive(k)
                out[k] = _as_feed_array(
                    v, var.dtype if var is not None else None
                )
            return stage_feed(out, stepped) if to_device else out

        stager = DeviceStager(dataset.batches(num_threads), _stage, depth=2)
        try:
            for feed in stager:
                # return_numpy=False keeps dispatch async (no device->
                # host sync per batch); values materialize on debug
                # prints/at the end
                last = self.run(
                    program, feed=feed, fetch_list=fetch_list,
                    scope=scope, return_numpy=False,
                )
                step += 1
                if debug and fetch_list and step % print_period == 0:
                    msg = ", ".join(
                        f"{info}={np.asarray(v).reshape(-1)[0]:.6f}"
                        for info, v in zip(fetch_info, last)
                    )
                    print(f"step {step}: {msg}")
        finally:
            stager.close()  # unblock the stager whatever happened
        if last is not None:
            last = [np.asarray(v) for v in last]
        return last

    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """File-driven training (reference: executor.py:894
        train_from_dataset → TrainerDesc + run_from_dataset,
        hogwild_worker.cc:163 per-thread op loops). Here each batch runs the
        one compiled XLA step; `thread` parallelizes the HOST side — file
        shards parse on `thread` concurrent readers feeding the batch
        queue (the TPU analog of Hogwild's per-thread data feeds; the
        device still runs one compiled step stream)."""
        # reference semantics (executor.py:894): thread=0 means "use the
        # dataset's configured thread num" (set_thread)
        n = int(thread or 0) or int(getattr(dataset, "thread_num", 0) or 0)
        return self._run_dataset(
            program, dataset, scope, fetch_list, fetch_info, print_period,
            debug, num_threads=max(1, n),
        )

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """reference: executor.py:817 (same loop, inference program);
        `thread` parallelizes host-side file parsing like
        train_from_dataset."""
        n = int(thread or 0) or int(getattr(dataset, "thread_num", 0) or 0)
        return self._run_dataset(
            program, dataset, scope, fetch_list, fetch_info, print_period,
            debug, num_threads=max(1, n),
        )

    # -- fluid-compat no-ops -------------------------------------------
    def close(self):
        self._cache.clear()
        # keyed by id(compiled): must die with the compiled steps, or a
        # recycled object id could serve a stale scan wrapper
        self._multi_cache.clear()
