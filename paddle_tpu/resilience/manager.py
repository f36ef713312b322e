"""CheckpointManager — cadence, discovery, and restore-or-initialize.

Reference framing: fluid's trainers pair io.py:487 save_persistables with
a checkpoint cadence and io.py:128-style per-var restore
(_load_distributed_persistables / checkpoint_notify round-trips). The
reference's load path silently skips missing tensors; this manager's
discovery (`latest_step`) skips CORRUPT OR UNCOMMITTED snapshots instead
and restores the newest one that fully validates — a torn save can cost
at most one checkpoint interval, never a silently-mixed state.

Two restore surfaces, matching the two execution modes:

- static graph: `restore_or_initialize(executor, program, startup)` runs
  the startup program, then overwrites every persistable the snapshot
  carries (params, optimizer accumulators, BN stats — all persistables,
  so optimizer state rides along automatically) and rewinds the
  executor's functional-PRNG seed counter so a resumed run replays the
  exact dropout-mask sequence of the uninterrupted run.
- dygraph: `restore_or_initialize_dygraph(layer, optimizer)` restores
  `Layer.state_dict()` plus `Optimizer.state_dict()` (optimizer.py —
  moments, velocity, step count) name-keyed.

`attach(program, executor)` wires auto-checkpointing into Executor.run:
every run of that program counts one step, `should_save` steps snapshot
asynchronously (AsyncSnapshotEngine) without touching user training
loops.
"""

from __future__ import annotations

import logging

import numpy as np

from .snapshot import (
    AsyncSnapshotEngine,
    SnapshotError,
    list_snapshots,
    load_snapshot,
    validate_snapshot,
    write_snapshot,
)

__all__ = ["CheckpointManager"]

_log = logging.getLogger("paddle_tpu.resilience")

_DY_PARAM = "param:"
_DY_OPT = "opt:"


def _persistable_state(program, scope):
    """name -> value for every persistable of `program` with a settled
    scope value (reference: io.py:128 save_vars' persistable predicate).
    Unsettled vars (declared, never initialized) are skipped at SAVE and
    therefore never demanded at restore."""
    state = {}
    for v in program.list_vars():
        if not getattr(v, "persistable", False) or getattr(v, "is_data", False):
            continue
        if scope.has(v.name) and scope.get(v.name) is not None:
            state[v.name] = scope.get(v.name)
    return state


class CheckpointManager:
    def __init__(self, root, save_interval=1, keep=3, async_save=True):
        self.root = str(root)
        self.save_interval = int(save_interval)
        self.keep = int(keep)
        self._engine = (
            AsyncSnapshotEngine(self.root, keep=keep) if async_save else None
        )
        self._auto_step = 0  # attach() cadence counter
        self._autosave_suspended = False  # NanGuard holds this on a streak
        self._readers = {}  # name -> tracked DataLoader (cursor resume)

    # -- data-pipeline cursor --------------------------------------------
    def track_reader(self, loader, name="reader0"):
        """Register a DataLoader whose cursor (epoch, batch, shuffle
        seed — reader/dataloader.py state_dict) rides in every snapshot
        manifest `extra` next to `seed_counter`, and is rewound by
        restore: an interrupted-and-restarted run re-fetches exactly the
        batches the uninterrupted run would have — no batch replayed or
        skipped (the PRNG counter alone replays dropout masks but not
        the data stream; this closes that resume hole). Returns self
        (chainable)."""
        if not hasattr(loader, "state_dict"):
            raise TypeError(
                f"track_reader needs a DataLoader with state_dict(), got "
                f"{type(loader).__name__}")
        self._readers[str(name)] = loader
        return self

    def _reader_cursors(self):
        return {n: dict(r.state_dict()) for n, r in self._readers.items()}

    def _rewind_readers(self, manifest):
        cursors = manifest.get("extra", {}).get("reader_cursors") or {}
        for name, cursor in cursors.items():
            loader = self._readers.get(name)
            if loader is not None:
                loader.set_state_dict(cursor)

    # -- cadence ---------------------------------------------------------
    def should_save(self, step: int) -> bool:
        return step >= 0 and step % self.save_interval == 0

    # -- save ------------------------------------------------------------
    def save(self, step, state=None, program=None, scope=None,
             executor=None, extra=None, blocking=False):
        """Snapshot `state` (or `program`'s persistables from `scope`).
        Async by default; `blocking=True` forces a synchronous commit
        (the preemption handler's final save). `executor` records the
        PRNG seed counter in the manifest for exact-replay resume."""
        if state is None:
            if program is None:
                raise ValueError("save() needs state= or program=")
            if scope is None:
                from ..scope import global_scope

                scope = global_scope()
            state = _persistable_state(program, scope)
        if not state:
            raise ValueError(
                "nothing to snapshot: no persistable has a settled value "
                "(run the startup program first)"
            )
        extra = dict(extra or {})
        if executor is not None:
            extra["seed_counter"] = int(executor._seed_counter)
        if self._readers and "reader_cursors" not in extra:
            # cursor captured HERE on the training thread, not on the
            # flush thread: by submit time the loader has yielded (and
            # the step consumed) exactly the batches the cursor counts —
            # the producer thread's prefetch lead never leaks in
            extra["reader_cursors"] = self._reader_cursors()
        if self._engine is not None and not blocking:
            self._engine.submit(int(step), state, extra=extra)
            return None
        return write_snapshot(self.root, int(step), state, extra=extra,
                              keep=self.keep)

    def drain(self):
        """Wait for in-flight async saves (no-op in sync mode)."""
        if self._engine is not None:
            self._engine.drain()

    def close(self):
        if self._engine is not None:
            self._engine.close()

    # -- discovery -------------------------------------------------------
    def all_steps(self):
        """Committed snapshot steps, newest first (validity not checked)."""
        return [s for s, _ in list_snapshots(self.root)]

    def latest_step(self, deep=False):
        """Newest step whose snapshot fully validates (manifest + file
        sizes; `deep=True` adds crc32). Corrupt/uncommitted dirs are
        skipped — a SIGKILL mid-save falls back to the previous good
        snapshot. Returns None when no valid snapshot exists."""
        for step, path in list_snapshots(self.root):
            try:
                validate_snapshot(path, deep=deep)
            except SnapshotError:
                continue
            return step
        return None

    def _iter_valid(self, names=None, step=None, kind=None):
        """(step, arrays, manifest) newest-first, skipping snapshots that
        fail crc verification at read time. `step`/`kind` filter on the
        MANIFEST (a small JSON read) BEFORE the tensor payload is read
        and checksummed — restore(step=S) must not pay full-checkpoint
        reads for the newer snapshots it is going to discard."""
        from .snapshot import read_manifest

        for got_step, path in list_snapshots(self.root):
            if step is not None and got_step != step:
                continue
            if kind is not None:
                m = read_manifest(path)
                if m is None or m.get("extra", {}).get("kind") != kind:
                    continue
            try:
                arrays, manifest = load_snapshot(path, names=names)
            except SnapshotError:
                continue
            yield got_step, arrays, manifest

    # -- snapshot-vs-program validation ----------------------------------
    @staticmethod
    def _mismatches(program, chosen):
        """Shape/dtype conflicts between restored arrays and the
        program's declarations, as human-readable offender strings.
        Only concrete declared shapes participate (a -1/None dim is a
        deferred batch dim, not a contract); dtypes compare through the
        executor's TPU narrowing (int64->int32, float64->float32 — the
        lowered dtype is what the scope actually holds)."""
        from ..framework import convert_dtype

        offenders = []
        block = program.global_block()
        for name in sorted(chosen):
            v = block._find_var_recursive(name)
            if v is None:
                continue
            arr = np.asarray(chosen[name])
            shape = getattr(v, "shape", None)
            if (shape is not None
                    and all(d is not None and int(d) >= 0 for d in shape)
                    and tuple(int(d) for d in shape) != tuple(arr.shape)):
                offenders.append(
                    f"{name}: snapshot shape {tuple(arr.shape)} != program "
                    f"shape {tuple(int(d) for d in shape)}")
                continue
            want = convert_dtype(v.dtype) if v.dtype is not None else None
            if want == "int64":
                want = "int32"
            elif want == "float64":
                want = "float32"
            if want is not None and str(arr.dtype) != want:
                offenders.append(
                    f"{name}: snapshot dtype {arr.dtype} != program dtype "
                    f"{want}")
        return offenders

    # -- mesh-elastic re-placement ----------------------------------------
    @staticmethod
    def _place_elastic(chosen, manifest, mesh, scope):
        """Re-place restored host arrays under `mesh` from each var's
        RECORDED PartitionSpec — the topology-elastic half of restore.

        The spec is mesh-shape-agnostic (``P('batch')`` means "shard dim0
        over however wide the batch axis is NOW"), so the same manifest
        restores onto an 8-wide or a 4-wide mesh: ZeRO-1 optimizer
        moments re-split across the new batch extent, pipe-sharded params
        re-bucket across the new pipe extent. A dim whose recorded axis
        no longer divides it degrades to replicated LOUDLY (WARNING) per
        the shared `named_sharding` rule — never a crash, never a wrong
        shard. Specs absent (manifest written on a 1x1x1 mesh, or no mesh
        at all) restore replicated-by-default: the next compile's
        `assign_state_shardings` recomputes this compile's extra specs
        (zero1/pipe) and the dispatch device_puts any disagreement.

        All placements land in ONE `jax.device_put` wave (transfers
        overlap; the per-var Python-loop placement was the measured
        restore bottleneck on large sharded states) timed into the
        always-on `restore_place_ms` counter, with `restore_resharded_
        vars` / `restore_degraded_vars` gauges for the drills."""
        import time

        from ..parallel.mesh import (
            sharding_with_degrade,
            spec_from_manifest,
        )

        var_meta = manifest.get("vars", {})
        src_mesh = manifest.get("mesh")
        dst_mesh = {a: int(s) for a, s in mesh.shape.items()}
        names, arrays, shardings = [], [], []
        degraded = 0
        for name, arr in chosen.items():
            spec_entry = var_meta.get(name, {}).get("spec")
            if not spec_entry:
                scope.set(name, arr)
                continue
            shape = tuple(np.asarray(arr).shape)
            sharding, fell = sharding_with_degrade(
                mesh, spec_from_manifest(spec_entry), shape)
            if fell:
                degraded += 1
                detail = "; ".join(
                    f"dim{d} (size {sz}) not divisible by axis group "
                    f"{list(axes)} (extent {grp})"
                    for d, axes, sz, grp in fell)
                _log.warning(
                    "mesh-elastic restore: %s recorded spec %s does not "
                    "fit mesh %s — degrading to replicated (%s)",
                    name, spec_entry, dst_mesh, detail)
            names.append(name)
            arrays.append(arr)
            shardings.append(sharding)
        if src_mesh and src_mesh != dst_mesh:
            _log.info(
                "mesh-elastic restore: snapshot written on mesh %s "
                "re-placed onto mesh %s (%d sharded var(s), %d degraded "
                "to replicated)", src_mesh, dst_mesh, len(names), degraded)
        if names:
            import jax

            t0 = time.perf_counter()
            placed = jax.device_put(arrays, shardings)
            for n, v in zip(names, placed):
                scope.set(n, v)
            from .. import profiler

            profiler.bump_counter(
                "restore_place_ms",
                int((time.perf_counter() - t0) * 1000))
        from .. import profiler

        # gauges always reset per restore; "resharded" means the
        # manifest RECORDED a mesh and it differs (a pre-recording
        # manifest restored onto any mesh is not a topology change)
        profiler.set_counter(
            "restore_resharded_vars",
            len(names) if (src_mesh and src_mesh != dst_mesh) else 0)
        profiler.set_counter("restore_degraded_vars", degraded)

    # -- restore: static graph -------------------------------------------
    def restore(self, program=None, scope=None, executor=None, step=None,
                require_finite=False, strict=False, mesh=None):
        """Restore the newest valid snapshot (or exactly `step`) into
        `scope`. With `program`, only its persistables restore — snapshot
        vars the program no longer declares are ignored, program
        persistables the snapshot lacks keep their current (startup)
        values (`strict=True` turns BOTH into errors listing the
        offenders). A shape- or dtype-mismatched var ALWAYS raises,
        listing every offender, before a single value lands in `scope` —
        a partially-restored state (half old shapes, half new) is the
        torn-checkpoint failure mode this subsystem exists to kill.
        `require_finite=True` additionally skips snapshots whose
        float state carries NaN/Inf — the NanGuard rollback path, which
        must never land on a snapshot the auto-cadence took of an
        already-poisoned step.

        `mesh=` is the TARGET topology (default: the active
        `current_mesh()`). It may differ from the mesh the manifest was
        written on — chip loss shrinks the fleet, the supervisor resumes
        the survivors on a smaller mesh, and this restore re-places every
        recorded-spec var under the new shape (see `_place_elastic`:
        loud replicated degrade on divisibility failures, one batched
        device_put wave, `restore_place_ms` counter). Returns the
        restored step, or None if nothing valid."""
        if scope is None:
            from ..scope import global_scope

            scope = global_scope()
        if strict and program is None:
            # every strict check compares snapshot vars AGAINST a
            # program; silently skipping them would be a false sense
            # of safety
            raise ValueError("restore(strict=True) requires program=")
        wanted = None
        if program is not None:
            wanted = {
                v.name for v in program.list_vars()
                if getattr(v, "persistable", False)
                and not getattr(v, "is_data", False)
            }
        for got_step, arrays, manifest in self._iter_valid(step=step):
            chosen = {
                name: arr for name, arr in arrays.items()
                if wanted is None or name in wanted
            }
            if not chosen:
                continue  # snapshot from an unrelated program: keep looking
            if program is not None:
                offenders = self._mismatches(program, chosen)
                if strict:
                    extra_vars = sorted(set(arrays) - wanted)
                    missing = sorted(wanted - set(arrays))
                    offenders += [
                        f"{n}: in snapshot but not a program persistable"
                        for n in extra_vars
                    ] + [
                        f"{n}: program persistable missing from snapshot"
                        for n in missing
                    ]
                if offenders:
                    raise SnapshotError(
                        f"snapshot step {got_step} does not match the "
                        f"program ({len(offenders)} offender(s)); nothing "
                        "was restored:\n  " + "\n  ".join(offenders))
            if require_finite and any(
                np.issubdtype(np.asarray(a).dtype, np.floating)
                and not np.isfinite(np.asarray(a)).all()
                for a in chosen.values()
            ):
                # poisoned snapshot: delete it so it can never become the
                # resume point of a LATER restart (the attach-cadence may
                # have saved the bad step before the guard observed it),
                # then fall back to an older one
                import shutil

                from .snapshot import snapshot_dir

                shutil.rmtree(snapshot_dir(self.root, got_step),
                              ignore_errors=True)
                continue
            # shard-aware, topology-elastic restore: the manifest records
            # each var's PartitionSpec (snapshot.snapshot_specs) — when a
            # mesh is active (the `mesh=` target, defaulting to the
            # current one), re-place every recorded-spec var under the
            # TARGET mesh in one batched device_put wave; the target may
            # be a different shape than the writer's (chip loss -> the
            # survivors' smaller mesh)
            from ..parallel.mesh import current_mesh

            target = mesh if mesh is not None else current_mesh()
            if target is not None:
                self._place_elastic(chosen, manifest, target, scope)
            else:
                for name, arr in chosen.items():
                    scope.set(name, arr)
            if executor is not None:
                sc = manifest.get("extra", {}).get("seed_counter")
                if sc is not None:
                    executor._seed_counter = int(sc)
            # rewind every tracked DataLoader to the manifest's cursor —
            # the data-stream half of exact resume (seed_counter above
            # is the PRNG half)
            self._rewind_readers(manifest)
            from .. import profiler

            profiler.set_counter("resume_step", int(got_step))
            self._auto_step = int(got_step) + 1
            return got_step
        return None

    def restore_or_initialize(self, executor, program, startup_program=None,
                              scope=None, require_finite=True, mesh=None):
        """Resume-or-fresh-start in one call: run `startup_program` (so
        every declared persistable gets a value — vars added since the
        snapshot keep their fresh init), then overwrite from the newest
        valid snapshot. `require_finite` (default on) skips — and
        deletes — snapshots carrying NaN/Inf state: a poisoned step
        auto-saved just before the process died must not become the
        resume point. `mesh=` passes the target topology through to
        `restore` (mesh-elastic resume). Returns the restored step, or
        -1 after a fresh initialize (reference: the trainer-side
        init/restore fork around io.py:487)."""
        if startup_program is not None:
            executor.run(startup_program)
        step = self.restore(program=program, scope=scope, executor=executor,
                            require_finite=require_finite, mesh=mesh)
        return -1 if step is None else step

    # -- restore: dygraph -------------------------------------------------
    def save_dygraph(self, step, layer_state, opt_state=None, extra=None,
                     blocking=False):
        """Snapshot a dygraph `Layer.state_dict()` (+ optionally an
        `Optimizer.state_dict()`, optimizer.py) — namespaced in one
        snapshot so params and optimizer state commit atomically together
        (the reference splits .pdparams/.pdopt and can tear between
        them)."""
        state = {_DY_PARAM + k: np.asarray(v) for k, v in layer_state.items()}
        for k, v in (opt_state or {}).items():
            state[_DY_OPT + k] = np.asarray(v)
        extra = dict(extra or {})
        extra["kind"] = "dygraph"
        if self._engine is not None and not blocking:
            self._engine.submit(int(step), state, extra=extra)
            return None
        return write_snapshot(self.root, int(step), state, extra=extra,
                              keep=self.keep)

    def restore_or_initialize_dygraph(self, layer, optimizer=None):
        """Restore the newest valid dygraph snapshot into `layer` (and
        `optimizer`). Returns the restored step or -1 (layer keeps its
        constructor initialization — the dygraph 'initialize' arm)."""
        for step, arrays, manifest in self._iter_valid(kind="dygraph"):
            params = {
                k[len(_DY_PARAM):]: v for k, v in arrays.items()
                if k.startswith(_DY_PARAM)
            }
            opt_state = {
                k[len(_DY_OPT):]: v for k, v in arrays.items()
                if k.startswith(_DY_OPT)
            }
            layer.set_dict(params)
            if optimizer is not None and opt_state:
                optimizer.set_state_dict(opt_state)
            from .. import profiler

            profiler.set_counter("resume_step", int(step))
            self._auto_step = int(step) + 1
            return step
        return -1

    # -- executor wiring ---------------------------------------------------
    def attach(self, program):
        """Auto-checkpoint this program: every successful executor step
        bumps a per-manager counter and snapshots on the should_save
        cadence — training loops need no checkpoint code at all. Covers
        Executor.run, run_repeated (counter advances by the whole scan
        window), over one device and over a CompiledProgram's or a fleet
        strategy's mesh alike (one step path, executor.py); `program` may be a Program or a CompiledProgram. Returns self
        (chainable after restore_or_initialize)."""
        program._ckpt_manager = self
        return self

    def detach(self, program):
        if getattr(program, "_ckpt_manager", None) is self:
            program._ckpt_manager = None

    def suspend_autosave(self):
        """Stop attach-cadence saves without detaching (the NanGuard
        holds this during a non-finite streak: snapshotting poisoned
        persistables would poison the very state a rollback needs)."""
        self._autosave_suspended = True

    def resume_autosave(self):
        self._autosave_suspended = False

    def _on_executor_step(self, program, scope, executor, steps=1):
        """Called by the executor after state write-back (executor.py
        `_step_boundary`, for run and run_repeated, mesh or not).
        `steps` > 1 covers one dispatch that advanced several training
        steps (run_repeated's on-device scan): the counter advances by
        all of them and one snapshot of the FINAL state lands if any
        cadence boundary was crossed inside the window."""
        first = self._auto_step
        self._auto_step += int(steps)
        if self._autosave_suspended:
            return self._auto_step - 1
        hits = [s for s in range(first, self._auto_step)
                if self.should_save(s)]
        if hits:
            # the scan's intermediate states no longer exist; snapshot
            # the newest boundary with the current (final) state
            self.save(hits[-1] if steps == 1 else self._auto_step - 1,
                      program=program, scope=scope, executor=executor)
        return self._auto_step - 1
