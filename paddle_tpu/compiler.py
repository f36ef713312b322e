"""CompiledProgram: data/model-parallel execution via GSPMD.

TPU-native replacement for the reference's ParallelExecutor machinery
(paddle/fluid/framework/parallel_executor.cc:370, details/build_strategy.cc:299,
ir/multi_devices_graph_pass/multi_devices_graph_pass.cc:454): instead of
cloning the graph per device and inserting AllReduce op-handles, the SAME
whole-block XLA computation is jitted over a jax.sharding.Mesh with the batch
dimension sharded — XLA/GSPMD inserts the gradient all-reduces over ICI.
BuildStrategy knobs map to sharding + compiler options. This module holds
the description (strategy, mesh, ZeRO-1, pipeline axes); the Executor, which
imports it, does the compiling and the running.

Tensor-parallel params can carry PartitionSpecs in program._sharding_specs
(set by paddle_tpu.parallel annotations) — GSPMD then partitions the matmuls,
giving TP without graph rewriting (SURVEY.md §2.8: TP "build as first-class").
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh

from .framework import Program

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
        self.num_trainers = 1
        self.trainer_id = 0
        self.sync_batch_norm = False


class ExecutionStrategy:
    def __init__(self):
        self.num_threads = 1  # XLA runtime scheduling; kept for parity
        self.num_iteration_per_drop_scope = 1
        self.use_experimental_executor = False


class CompiledProgram:
    """A Program plus what was decided about running it: the build
    strategy, the mesh (`_get_mesh`), ZeRO-1, the pipeline's axes and an
    attached CheckpointManager. It runs nothing: `Executor.run` and
    `run_repeated` unwrap it and step the Program over the mesh through
    the stages every program takes. reference:
    python/paddle/fluid/compiler.py:65,143."""

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
        # per-HANDLE flag (never stored on the shared Program: another
        # CompiledProgram over the same Program must not flip this one's
        # ZeRO-1 on or off)
        self._zero1 = False
        self._pp = 1
        self._tp = 1
        self._ckpt_manager = None  # resilience: CheckpointManager.attach

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
        if (
            self._pp > 1
            and loss_name
            and getattr(self._program, "_pipeline_loss", None) is None
        ):
            # with_pipeline(loss_name=...) without PipelineOptimizer: the
            # pipeline step still needs the loss to seed its vjp
            self._program._pipeline_loss = loss_name
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
            pp, tp = self._pp, self._tp
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
