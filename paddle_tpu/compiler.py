"""CompiledProgram: data/model-parallel execution via GSPMD.

TPU-native replacement for the reference's ParallelExecutor machinery
(paddle/fluid/framework/parallel_executor.cc:370, details/build_strategy.cc:299,
ir/multi_devices_graph_pass/multi_devices_graph_pass.cc:454): instead of
cloning the graph per device and inserting AllReduce op-handles, the SAME
whole-block XLA computation is jitted over a jax.sharding.Mesh with the batch
dimension sharded — XLA/GSPMD inserts the gradient all-reduces over ICI.
BuildStrategy knobs map to sharding + compiler options.

Tensor-parallel params can carry PartitionSpecs in program._sharding_specs
(set by paddle_tpu.parallel annotations) — GSPMD then partitions the matmuls,
giving TP without graph rewriting (SURVEY.md §2.8: TP "build as first-class").
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from .framework import Program
from .profiler import RecordEvent
from .scope import global_scope

__all__ = ["CompiledProgram", "BuildStrategy", "ExecutionStrategy"]


class BuildStrategy:
    """Build-time knobs (reference: details/build_strategy.h). Each knob
    is either WIRED to a Program IR pass (paddle_tpu/passes/), covered by
    XLA/GSPMD automatically, or an accepted no-op for API parity — see
    PARITY.md "Build-strategy pass parity" for the pass-by-pass map.

    Wired knobs (select passes run per compiled step, before the trace;
    the PADDLE_TPU_PASSES env var overrides all of them):

      * fuse_all_optimizer_ops (default True) — coalesce per-param
        sgd/momentum/adam/adamw ops into one fused multi-tensor update
        per dtype bucket (passes/fuse_optimizer.py; reference
        fuse_all_optimizer_ops pass).
      * memory_optimize (default True) — fetch/state-driven dead-op
        elimination (passes/dce.py): ops reaching neither fetches nor
        persistables never trace, so their buffers never exist. The
        reference pass reuses dead buffers; with whole-graph XLA the
        stronger form is to delete the dead computation outright
        (donation already makes live-state updates in-place).
      * constant_folding (default True) — fold
        fill_constant/scale/cast/shape chains at compile time
        (passes/const_fold.py); no reference build_strategy knob, the
        reference folds in framework/ir/constant_folding_pass.cc.
      * enable_inplace (default True) — copy propagation
        (passes/copy_prop.py): pure `assign` renames (backward's
        single-partial grad accumulation) resolve at pass time, the
        compile-time face of the reference's inplace pass (buffer
        donation already covers the runtime face, always on).

    Parity no-ops, each covered downstream: fuse_elewise_add_act_ops
    (XLA elementwise fusion), fuse_all_reduce_ops (GSPMD coalesces
    collectives over ICI), reduce_strategy / gradient_scale_strategy
    (GSPMD all-reduce
    placement; loss scaling is the program's own math), sync_batch_norm
    (a mesh-wide compiled step sees the global batch already),
    num_trainers / trainer_id (jax.process_* describes the fleet)."""

    class ReduceStrategy:
        AllReduce = 0
        Reduce = 1

    class GradientScaleStrategy:
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = (
            BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        )
        self.fuse_elewise_add_act_ops = False  # XLA fuses automatically
        self.fuse_all_reduce_ops = True  # GSPMD coalesces collectives
        self.fuse_all_optimizer_ops = True  # passes/fuse_optimizer.py
        self.memory_optimize = True  # passes/dce.py (+ donation always on)
        self.constant_folding = True  # passes/const_fold.py
        self.enable_inplace = True
        self.fuse_conv_bn = True  # passes/fuse_conv_bn.py (is_test only)
        self.enable_layout_opt = True  # passes/layout_opt.py (NHWC)
        # OPT-IN auto-parallel placement (passes/shard_propagation.py):
        # the autoshard planner chooses the ZeRO/pipe PartitionSpec
        # assignment for the compile's mesh instead of the zero1 flag /
        # hand-written extra specs. PADDLE_TPU_AUTOSHARD overrides.
        self.auto_shard = False
        # OPT-IN fused-step compilation (passes/fuse_layer_scan.py):
        # collapse repeated layer blocks — forward and their backward
        # closures — into single lax.scan ops, shrinking traced-op
        # count and compile time on deep stacked models.
        # PADDLE_TPU_FUSE_LAYER_SCAN overrides.
        self.fuse_layer_scan = False
        # OPT-IN optimizer/backward overlap (passes/optimizer_overlap.py):
        # split each fused optimizer wave by grad-finalization order so
        # updates schedule under the backward tail instead of after it.
        # PADDLE_TPU_OPTIMIZER_OVERLAP overrides.
        self.optimizer_overlap = False
        self.num_trainers = 1
        self.trainer_id = 0
        self.sync_batch_norm = False


class ExecutionStrategy:
    def __init__(self):
        self.num_threads = 1  # XLA runtime scheduling; kept for parity
        self.num_iteration_per_drop_scope = 1
        self.use_experimental_executor = False


class CompiledProgram:
    """reference: python/paddle/fluid/compiler.py:65,143."""

    def __init__(self, program_or_graph, build_strategy=None):
        if not isinstance(program_or_graph, Program):
            raise TypeError("CompiledProgram expects a Program")
        self._program = program_or_graph
        self._build_strategy = build_strategy or BuildStrategy()
        self._exec_strategy = None
        self._loss_name = None
        self._is_data_parallel = False
        self._places = None
        self._mesh = None
        self._share_vars_from = None

    # ------------------------------------------------------------------
    def with_data_parallel(
        self,
        loss_name=None,
        build_strategy=None,
        exec_strategy=None,
        share_vars_from=None,
        places=None,
        zero1=False,
    ):
        """zero1=True additionally shards optimizer accumulators along
        the mesh's 'batch' axis (ZeRO-1: mesh.zero1_accumulators) — GSPMD
        reduce-scatters the grads into the sharded moment update and
        all-gathers the param delta."""
        self._is_data_parallel = True
        self._loss_name = loss_name
        if build_strategy is not None:
            self._build_strategy = build_strategy
        self._exec_strategy = exec_strategy or ExecutionStrategy()
        self._places = places
        self._share_vars_from = share_vars_from
        # per-HANDLE flag (never stored on the shared Program: another
        # CompiledProgram over the same Program must not flip this one's
        # ZeRO-1 on or off)
        self._zero1 = bool(zero1)
        return self

    def with_inference_optimize(self, config):
        # analysis passes are XLA's job; compile-as-is
        return self

    def with_pipeline(self, loss_name=None, num_stages=2, places=None,
                      tensor_parallel=1):
        """Pipeline execution over device_guard stage cuts: the unified
        mesh's 'pipe' axis takes `num_stages` and the executor runs the
        microbatched grad-accumulation step over the mesh with master
        params + optimizer accumulators sharded along 'pipe' at rest
        (parallel/program_pipeline.py; reference: PipelineOptimizer
        program cutting, optimizer.py:2683). Remaining devices form the
        'batch' axis.

        tensor_parallel>1 sizes the 'model' axis; the program's
        shard_parameter annotations (Megatron splits) ride it — both are
        just PartitionSpec assignments on one jit, so they compose
        freely."""
        self._is_data_parallel = True
        self._loss_name = loss_name
        self._pp = int(num_stages)
        self._tp = int(tensor_parallel)
        self._places = places
        return self

    # ------------------------------------------------------------------
    def _get_mesh(self) -> Mesh:
        if self._mesh is None:
            from .parallel.mesh import build_mesh

            devices = jax.devices()
            if self._places is not None and not isinstance(self._places, int):
                ndev = len(self._places)
                devices = devices[:ndev]
            elif isinstance(self._places, int):
                devices = devices[: self._places]
            pp = getattr(self, "_pp", 1)
            tp = getattr(self, "_tp", 1)
            if len(devices) % (pp * tp):
                raise ValueError(
                    f"{len(devices)} devices not divisible by "
                    f"num_stages={pp} x tensor_parallel={tp}"
                )
            # THE unified mesh (batch, model, pipe) — all axes always
            # present; a 1x1x1 mesh is the degenerate single-device case
            # and compiles bitwise-equal to the non-mesh executor path
            self._mesh = build_mesh(
                batch=len(devices) // (pp * tp), model=tp, pipe=pp,
                devices=devices,
            )
        return self._mesh

    def _run(self, executor, feed, fetch_list, scope, return_numpy):
        """Execute under the dp mesh. Reuses the executor's lowering; only
        shardings differ from the single-device path."""
        scope = scope or global_scope()
        compiled, state, feeds, program = self._prepare_mesh_run(
            executor, feed, fetch_list, scope
        )

        result = executor._dispatch(program, compiled, state, feeds)
        del state  # dead once donated: released by the write-back
        # the step boundary (write-back, chaos anchor, heartbeat, then the
        # checkpoint hook) is Executor.run's own: a supervised multi-rank
        # job dispatches HERE, and a CheckpointManager attached to either
        # the CompiledProgram or its underlying Program snapshots there
        with RecordEvent("pt.exe.writeback"):
            return executor._write_back(
                program, compiled, result, scope, return_numpy,
                getattr(program, "_ckpt_manager", None)
                or getattr(self, "_ckpt_manager", None))

    def _run_repeated(self, executor, feed, fetch_list, steps, scope,
                      return_numpy):
        """`steps` mesh-sharded training steps in ONE dispatch (the
        CompiledProgram face of Executor.run_repeated): state — including
        multi-process global arrays — threads through an on-device
        lax.scan with the same PRNG fold sequence `steps` _run calls
        would use; fetches come back stacked [steps, ...]."""
        import jax.numpy as jnp

        # PADDLE_TPU_CHECK_NAN_INF is rejected by Executor.run_repeated
        # before dispatching here
        scope = scope or global_scope()
        compiled, state, feeds, program = self._prepare_mesh_run(
            executor, feed, fetch_list, scope
        )
        unsettled = sorted(
            n for n, v in state.items()
            if getattr(v, "ndim", None) == 0
            and (not scope.has(n) or scope.get(n) is None)
        )
        if unsettled:
            raise RuntimeError(
                f"persistable vars {unsettled} have no settled value yet "
                "— run the startup program before run_repeated (the scan "
                "carry needs stable shapes)")
        base = program.random_seed or 42
        counter0 = executor._seed_counter + 1

        multi_key = (id(compiled), steps, base)
        multi = executor._multi_cache.get(multi_key)
        if multi is None:
            from .executor import _jit

            # the step's nested jit — see Executor.run_repeated
            step_fn = compiled.nested_fn

            def multi(state, feeds, counter):
                rng0 = jax.random.key(base)

                def body(st, i):
                    fetches, new_state = step_fn(
                        st, feeds, jax.random.fold_in(rng0, counter + i)
                    )
                    return new_state, tuple(fetches)

                final_state, stacked = jax.lax.scan(
                    body, state, jnp.arange(steps)
                )
                return stacked, final_state

            # no donation — see Executor.run_repeated (failure fallback)
            multi = _jit(multi)
            executor._multi_cache[multi_key] = multi

        stacked, new_state = multi(
            state, feeds, jnp.asarray(counter0, jnp.int32)
        )
        executor._seed_counter += steps
        for n, v in new_state.items():
            scope.set(n, v)

        # chaos anchor + heartbeat before the snapshot hook, reporting
        # the window's final step (same ordering as run_repeated)
        from .executor import _trainer_heartbeat, fault_point

        mgr = (getattr(program, "_ckpt_manager", None)
               or getattr(self, "_ckpt_manager", None))
        executor._dispatch_count += 1
        fault_point("trainer.step")
        _trainer_heartbeat(
            None if mgr is None else mgr._auto_step + steps - 1,
            executor._dispatch_count)

        # one dispatch advanced `steps` training steps: the attach-cadence
        # counter advances by all of them, snapshotting the final state if
        # a boundary fell inside the window (intermediate states lived
        # only inside the scan)
        if mgr is not None:
            mgr._on_executor_step(program, scope, executor, steps=steps)

        if return_numpy:
            return [np.asarray(f) for f in stacked]
        return list(stacked)

    def _prepare_mesh_run(self, executor, feed, fetch_list, scope):
        with RecordEvent("pt.exe.prepare"):
            compiled, feed_items, mesh = self._lookup_mesh_step(
                executor, feed, fetch_list, scope)
            feeds = self._mesh_feeds(feed_items, mesh)
        with RecordEvent("pt.exe.state"):
            state = self._mesh_state(compiled, scope, mesh)
        return compiled, state, feeds, self._program

    def _lookup_mesh_step(self, executor, feed, fetch_list, scope):
        """Feed normalization + compile-cache lookup under the mesh.
        Returns (compiled, [(name, host array)], mesh)."""
        from .executor import _as_feed_array
        from .framework import Variable

        feed = feed or {}
        fetch_list = fetch_list or []
        fetch_names = [
            v.name if isinstance(v, Variable) else str(v) for v in fetch_list
        ]
        program = self._program
        block = program.global_block()
        mesh = self._get_mesh()
        if (
            getattr(self, "_pp", 1) > 1
            and self._loss_name
            and getattr(program, "_pipeline_loss", None) is None
        ):
            # with_pipeline(loss_name=...) without PipelineOptimizer: the
            # pipeline executor still needs the loss to seed its vjp
            program._pipeline_loss = self._loss_name

        feed_items = []
        for name in sorted(feed.keys()):
            v = block._find_var_recursive(name)
            dtype = v.dtype if v is not None else None
            feed_items.append((name, _as_feed_array(feed[name], dtype)))
        feed_sig = tuple(
            (name, arr.shape, str(arr.dtype)) for name, arr in feed_items
        )
        from .parallel.mesh import mesh_signature
        from .passes import resolve_pass_names

        key = (
            executor._program_key(program),
            feed_sig,
            tuple(fetch_names),
            id(scope),
            "batch",
            # mesh shape + spec assignment: flipping a shard_parameter
            # annotation (or the zero1 flag) must recompile, not serve
            # the stale executable
            mesh_signature(mesh, program._sharding_specs),
            bool(getattr(self, "_zero1", False)),
            resolve_pass_names(self._build_strategy),
        )
        compiled = executor._cache.get(key)
        if compiled is None:
            # an explicit for_test clone compiles as eval (on pp meshes
            # this folds pp into data parallelism instead of running the
            # microbatch schedule); plain forward-only programs keep
            # train-mode semantics, same as exe.run(program)
            is_test = bool(getattr(program, "_is_test_clone", False))
            with RecordEvent("pt.exe.compile"):
                compiled = executor._compile(
                    program,
                    block,
                    feed_sig,
                    fetch_names,
                    scope,
                    is_test=is_test,
                    mesh=mesh,
                    sharding_specs=program._sharding_specs,
                    build_strategy=self._build_strategy,
                    zero1=bool(getattr(self, "_zero1", False)),
                )
            executor._cache[key] = compiled
        return compiled, feed_items, mesh

    @staticmethod
    def _mesh_feeds(feed_items, mesh):
        import jax.numpy as jnp

        if jax.process_count() == 1:
            return {name: jnp.asarray(arr) for name, arr in feed_items}
        # multi-process (fleet) execution: each trainer feeds its
        # process-LOCAL batch shard (the reference's trainers read
        # disjoint file splits); assemble global arrays spanning all
        # processes
        return {
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

    @staticmethod
    def _mesh_state(compiled, scope, mesh):
        import jax.numpy as jnp

        state = {}
        if jax.process_count() > 1:
            # state is replicated — every process initialized identically
            # from the seeded startup program
            rep = NamedSharding(mesh, P())
            for n in compiled.state_names:
                val = scope.get(n) if scope.has(n) else None
                if isinstance(val, jax.Array) and not val.is_fully_addressable:
                    # already a global (possibly sharded) array from a
                    # previous step — pass through, never fetch to host
                    state[n] = val
                else:
                    state[n] = jax.make_array_from_process_local_data(
                        rep, np.asarray(val if val is not None else 0.0)
                    )
            return state
        state_sh = getattr(compiled, "state_shardings", {}) or {}
        for n in compiled.state_names:
            val = scope.get(n) if scope.has(n) else None
            if not isinstance(val, jax.Array):
                val = jnp.asarray(val if val is not None else 0.0)
            else:
                want = state_sh.get(n)
                if want is not None and val.sharding != want:
                    # one-time reshard: a committed layout from an
                    # earlier compile (different zero1/pipe specs)
                    # moves onto this compile's assignment; steady
                    # state re-enters already matching (out_shardings)
                    val = jax.device_put(val, want)
            state[n] = val
        return state
