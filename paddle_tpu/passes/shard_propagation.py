"""shard_propagation: emit the autoshard planner's PartitionSpec
assignment into the compiled step.

The auto-parallel pass (ROADMAP "Auto-parallel placement as an IR
pass"): when autoshard is enabled — `PADDLE_TPU_AUTOSHARD=1` or
`BuildStrategy.auto_shard=True` — and the step compiles onto a real
multi-device mesh, the pass runs the device-free planner
(paddle_tpu/autoshard) for the mesh shape the executor is about to use
and records the winning specs on the program clone as
`_autoshard_specs`. The executor merges them into the extra-specs it
hands `mesh.assign_state_shardings`, exactly where the hand-written
ZeRO-1 / pipe assignments enter — so a planned placement and a manual
one flow through one emission layer and one dispatch-side reshard map.

Contract notes:

* The pass never edits ops (returns 0 removed; `ctx.mutated` keeps the
  clone when specs were attached), so the per-pass verifier sees an
  unchanged op graph and `analysis.check_sharding` has already
  validated the specs inside the planner.
* It is in `resolve_pass_names()` ONLY while autoshard is enabled
  (`register_pass`'s `gate`), so flipping `PADDLE_TPU_AUTOSHARD`
  recompiles — the executor cache keys on the resolved pass set.
* A plan failure (unknown-shape state var, no feasible placement)
  degrades to the manual behavior with one loud warning per program —
  opting into autoshard must never turn a compilable program into an
  error when the hand-written path still works.
* The pipeline microbatch schedule path never runs IR passes (executor
  contract since round 6), so pp-scheduled TRAINING keeps its manual
  specs; eval/inference clones of pp programs and every plain mesh
  program take the planned path.
"""

from __future__ import annotations

import os
import sys

from . import register_pass

__all__ = ["AUTOSHARD_ENV", "autoshard_enabled"]

AUTOSHARD_ENV = "PADDLE_TPU_AUTOSHARD"

_warned_programs = set()


def autoshard_enabled(build_strategy=None) -> bool:
    """The env var wins over the BuildStrategy knob (same precedence as
    PADDLE_TPU_PASSES over the pass knobs)."""
    env = os.environ.get(AUTOSHARD_ENV)
    if env is not None:
        return env.strip().lower() not in ("", "0", "off", "none", "false")
    return bool(getattr(build_strategy, "auto_shard", False))


@register_pass("shard_propagation", gate=autoshard_enabled)
def shard_propagation_pass(program, block, feed_names, fetch_names, ctx):
    if not autoshard_enabled(getattr(ctx, "build_strategy", None)):
        return 0
    mesh = getattr(ctx, "mesh", None)
    if mesh is None:
        return 0  # single-device executor path: nothing to place
    from ..parallel.mesh import axis_sizes as _axis_sizes

    axis_sizes = _axis_sizes(mesh)
    total = 1
    for s in axis_sizes.values():
        total *= s
    if total <= 1:
        return 0

    from ..autoshard import PlanError, Topology, plan_program

    feeds = None
    feed_sig = getattr(ctx, "feed_sig", None)
    if feed_sig:
        feeds = {n: (tuple(s), dt) for n, s, dt in feed_sig}
    try:
        plan = plan_program(
            program,
            Topology.from_env(default_chips=total),
            feeds=feeds,
            mesh_shape=axis_sizes,
        )
    except PlanError as e:
        # content-keyed dedup: the executor hands a fresh clone per
        # compile, so id() would warn on every recompile of the same
        # source program
        key = (program.fingerprint()
               if hasattr(program, "fingerprint") else id(program))
        if key not in _warned_programs:
            _warned_programs.add(key)
            sys.stderr.write(
                f"shard_propagation: planner declined ({e}); compiling "
                "with the manual spec assignment\n")
        return 0
    if plan.specs:
        # the executor merges these into assign_state_shardings
        # extra-specs; keep the full plan for observability (profiler
        # gauges + tools/autoshard_plan.py --explain)
        program._autoshard_specs = dict(plan.specs)
        program._autoshard_plan = plan.to_dict()
        ctx.mutated = True
        from .. import profiler

        profiler.set_counter("autoshard_planned_vars", len(plan.specs))
    return 0
