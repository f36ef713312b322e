"""Program IR pass manager: high-level graph rewrites before lowering.

The reference Fluid runs a battery of IR passes when building the
executor graph (details/build_strategy.cc:299 — fuse_all_optimizer_ops,
fuse_elewise_add_act_pass, memory-optimize/inplace). Here, high-level
rewrites that the backend compiler cannot recover run over the Program IR
after the executor resolves the (feed, fetch, state) signature and
before the jit trace:

  * const_fold     — fold fill_constant/scale/cast/shape chains so
                     shape-plumbing never reaches the tracer
                     (passes/const_fold.py)
  * copy_prop      — eliminate pure `assign` renames (backward's
                     single-partial grad accumulation; the reference's
                     enable_inplace analog; passes/copy_prop.py)
  * dce            — fetch/state-driven dead-op elimination
                     (Program._prune generalized to run per compiled
                     step; passes/dce.py)
  * fuse_conv_bn   — inference-only: fold BatchNorm scale/shift into the
                     preceding conv's weights/bias and absorb the
                     trailing relu (reference fuse_conv_bn_pass /
                     conv_affine_channel_fuse_pass; passes/fuse_conv_bn.py)
  * layout_opt     — propagate NHWC through conv/pool/batch_norm/
                     elementwise chains (forward AND backward) so vision
                     networks run in the TPU-native layout with boundary
                     transposes only at graph edges (the reference's
                     MKLDNN/cuDNN layout-assignment passes;
                     passes/layout_opt.py)
  * fuse_optimizer — coalesce per-param sgd/momentum/adam/adamw ops into
                     one grouped multi-tensor update (reference
                     fuse_all_optimizer_ops; passes/fuse_optimizer.py)
  * shard_propagation — OPT-IN (PADDLE_TPU_AUTOSHARD=1 or
                     BuildStrategy.auto_shard): run the autoshard
                     planner for the compile's mesh shape and attach
                     the winning PartitionSpec assignment for the
                     executor to emit through
                     mesh.assign_state_shardings extra-specs
                     (passes/shard_propagation.py). Unlike the knob-
                     gated passes it is absent from the resolved set
                     unless enabled, so flipping autoshard recompiles.

Selection: BuildStrategy knobs (compiler.py) choose the default set;
the PADDLE_TPU_PASSES env var overrides both ("all", "none"/"", or a
comma list of pass names). Passes run on a CLONE of the program — the
user's Program (and its fingerprint, which keys the compile cache) is
never mutated. Per-pass wall time and op counts are always-on profiler
counters (pass_<name>_us, pass_<name>_ops_removed, program_ops_before/
_after) in the style of the dygraph_jit_* counters.

Verifier contract (PADDLE_TPU_VERIFY): when the env var is truthy
(default-on under pytest via tests/conftest.py; any of ""/"0"/"off"/
"none"/"false" disables), apply_program_passes runs the IR verifier
(paddle_tpu/analysis/verifier.py) over the incoming program and again
after EVERY enabled pass — def-before-use, dangling references, dtype
consistency against the static shape functions, persistable/parameter
write rules, block nesting, sharding-annotation axis validity. A
finding raises VerifierError naming the pass whose output broke (or
"input program" when the authored IR was already bad), with op/var-
precise messages instead of an opaque tracer error deep in jit_compile.
Interaction with PADDLE_TPU_PASSES: verification follows the RESOLVED
pass set — with passes disabled ("none") the input program is still
verified once; unknown pass names still raise before any verification.
The verifier only reads the program clone; it never mutates it, so
the program fingerprint that keys the compile caches is unaffected by
PADDLE_TPU_VERIFY in either state.
"""

from __future__ import annotations

import os

from .. import profiler
from ..framework import Program

__all__ = [
    "register_pass",
    "resolve_pass_names",
    "apply_program_passes",
    "verify_enabled",
    "PassContext",
    "PASS_REGISTRY",
]

# name -> (fn(program, block, feed_names, fetch_names, ctx) -> int removed,
#          strategy_knob: BuildStrategy attr gating the pass, or None,
#          gate: enabled(build_strategy) of a default-OFF pass, or None)
PASS_REGISTRY: dict[str, tuple] = {}
_PASS_ORDER: list[str] = []  # registration order == execution order


class PassContext:
    """Per-application context handed to every pass. `scope` carries the
    executor scope when the caller has one (fuse_conv_bn const-evaluates
    parameter values through it); `build_strategy`, `mesh` and
    `feed_sig` ride along for shard_propagation (the planner needs the
    compile's mesh shape and concrete feed shapes). Passes must
    tolerate all of them being None — direct apply_program_passes
    callers (tests) run scopeless and meshless."""

    def __init__(self, scope=None, build_strategy=None, mesh=None,
                 feed_sig=None):
        self.scope = scope
        self.build_strategy = build_strategy
        self.mesh = mesh
        self.feed_sig = feed_sig
        # set True by a pass that changed the program WITHOUT a net op
        # count change (layout_opt may only rewrite attrs) so the
        # manager keeps the rewritten clone
        self.mutated = False


def register_pass(name: str, strategy_knob: str = None, gate=None):
    """Decorator. A pass takes (program, block, feed_names, fetch_names,
    ctx), mutates `block` (of an executor-private program clone) in
    place, and returns the number of ops it removed (net; may be
    negative for passes that insert boundary ops). A pass that rewrites
    the program without changing the op count must set ctx.mutated.
    `gate` makes the pass opt-in: it runs only where
    `gate(build_strategy)` is true."""

    def deco(fn):
        PASS_REGISTRY[name] = (fn, strategy_knob, gate)
        _PASS_ORDER.append(name)
        return fn

    return deco


def resolve_pass_names(build_strategy=None) -> tuple:
    """The enabled pass names, in execution order. PADDLE_TPU_PASSES wins
    over BuildStrategy knobs; with neither, every registered pass runs.
    Also part of the executor compile-cache key — flipping the env var
    between runs must not serve a stale compiled step."""
    env = os.environ.get("PADDLE_TPU_PASSES")
    if env is not None:
        env = env.strip()
        if env in ("", "none", "off", "0"):
            return ()
        if env == "all":
            return tuple(_PASS_ORDER)
        requested = [p.strip() for p in env.split(",") if p.strip()]
        unknown = [p for p in requested if p not in PASS_REGISTRY]
        if unknown:
            raise ValueError(
                f"PADDLE_TPU_PASSES names unknown passes {unknown}; "
                f"registered: {sorted(PASS_REGISTRY)}"
            )
        return tuple(p for p in _PASS_ORDER if p in requested)
    enabled = []
    for name in _PASS_ORDER:
        _, knob, gate = PASS_REGISTRY[name]
        if gate is not None:
            # opt-in, env-or-strategy gated (default OFF — the inverse
            # of the knob passes) and therefore absent from the resolved
            # set until enabled: flipping PADDLE_TPU_AUTOSHARD must MISS
            # the executor cache instead of serving a stale executable
            if not gate(build_strategy):
                continue
        elif (
            build_strategy is not None
            and knob is not None
            and not getattr(build_strategy, knob, True)
        ):
            continue
        enabled.append(name)
    return tuple(enabled)


# program attrs the executor reads post-transform that Program.clone()
# does not carry over (clone covers random_seed/_sharding_specs/
# _amp_dtype/_is_test_clone/_pipeline_microbatches)
_CARRIED_ATTRS = (
    "_recompute_loss",
    "_pipeline_loss",
    "_amp_black_list",
    "_amp_white_list",
)


def _clone_for_passes(program: Program) -> Program:
    p = program.clone()
    for a in _CARRIED_ATTRS:
        if hasattr(program, a):
            setattr(p, a, getattr(program, a))
    return p


def verify_enabled() -> bool:
    """PADDLE_TPU_VERIFY truthiness (default off outside pytest;
    tests/conftest.py sets it to 1)."""
    return os.environ.get("PADDLE_TPU_VERIFY", "").strip().lower() not in (
        "", "0", "off", "none", "false"
    )


def _verify(program, feed_names, fetch_names, where):
    """Run the IR verifier, naming `where` (the pass whose output is
    being checked) in any raised VerifierError."""
    from ..analysis.verifier import check_program

    with profiler.time_counter("pass_verify"):
        check_program(
            program,
            feed_names=tuple(feed_names),
            fetch_names=tuple(fetch_names),
            where=where,
        )


def apply_program_passes(
    program: Program,
    feed_names,
    fetch_names,
    build_strategy=None,
    scope=None,
    mesh=None,
    feed_sig=None,
):
    """Run the enabled passes over a clone of `program`. Returns
    (program, block, stats) — the original objects (stats=None) when no
    pass is enabled or nothing changed, so the no-pass path costs one
    tuple check."""
    names = resolve_pass_names(build_strategy)
    verify = verify_enabled()
    if verify:
        # the authored program must be clean BEFORE any rewrite — a layer
        # bug shows up here as "input program", never blamed on a pass
        _verify(program, feed_names, fetch_names, "input program")
    if not names:
        return program, program.global_block(), None
    clone = _clone_for_passes(program)
    block = clone.global_block()
    ops_before = len(block.ops)
    stats = {"ops_before": ops_before, "passes": {}}
    total_removed = 0
    ctx = PassContext(scope=scope, build_strategy=build_strategy,
                      mesh=mesh, feed_sig=feed_sig)
    with profiler.time_counter("pass_manager"):
        for name in names:
            fn, _, _ = PASS_REGISTRY[name]
            with profiler.time_counter(f"pass_{name}"):
                removed = fn(
                    clone, block, tuple(feed_names), tuple(fetch_names), ctx
                )
            profiler.bump_counter(f"pass_{name}_ops_removed", removed)
            stats["passes"][name] = removed
            total_removed += removed
            if verify:
                _verify(clone, feed_names, fetch_names, f"after pass {name!r}")
    stats["ops_after"] = len(block.ops)
    profiler.bump_counter("program_ops_before", ops_before)
    profiler.bump_counter("program_ops_after", len(block.ops))
    if total_removed == 0 and not ctx.mutated:
        # nothing changed: lower the original (identical) program and let
        # its Variable.op links etc. stay canonical
        return program, program.global_block(), stats
    return clone, block, stats


# importing the modules registers the passes, in execution order:
# fold constants first (exposes dead feeder chains), then copy
# propagation (drops backward's grad-accumulation assigns), then DCE,
# then the inference conv+BN fold (removes BN ops before layout
# assignment sees them), then NHWC layout propagation (on the cleaned
# graph), then optimizer fusion (runs on the final op list)
from . import const_fold as _const_fold  # noqa: E402,F401
from . import copy_prop as _copy_prop  # noqa: E402,F401
from . import dce as _dce  # noqa: E402,F401
from . import fuse_conv_bn as _fuse_conv_bn  # noqa: E402,F401
from . import layout_opt as _layout_opt  # noqa: E402,F401
from . import fuse_optimizer as _fuse_optimizer  # noqa: E402,F401
# shard_propagation LAST: it plans on the graph the other rewrites
# produced (post-DCE state set), and only participates when autoshard
# is enabled (see resolve_pass_names)
from . import shard_propagation as _shard_propagation  # noqa: E402,F401
