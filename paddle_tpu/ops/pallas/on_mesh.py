"""Pallas kernels on a data-parallel mesh.

A Pallas call is a custom call, which GSPMD cannot partition. Pure data
parallelism needs no partitioning of the kernel: a chip's rows of the
batch are a whole problem of the shape the kernel was built for. So where
the mesh shards the `batch` axis alone, a kernel whose every operand is
batch-leading or replicated runs per shard in a manual region over `batch`
(`per_shard`): GSPMD sees stated in and out specs and partitions the rest
of the graph as before. Any other mesh of several devices (tensor or
pipeline parallel, sequence parallel) keeps XLA's lowering.
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

AXIS = "batch"


def _one_device(mesh) -> bool:
    return mesh is None or mesh.devices.size == 1


def batch_shards(mesh, *leading) -> int:
    """How a lowering may call a Pallas kernel whose batch-leading
    operands have the leading dimensions `leading`: 1 directly (no mesh,
    or one device), n > 1 per shard of a mesh that shards `batch` alone n
    ways and divides every one of them, 0 not at all."""
    if _one_device(mesh):
        return 1
    n = mesh.shape.get(AXIS, 1)
    if n == mesh.devices.size and all(d % n == 0 for d in leading):
        return n
    return 0


def per_shard(fn, mesh, split):
    """`fn` over the shards of `batch`; `fn` itself on one device. Operand
    i is split along its leading dimension where `split[i]` and whole on
    every shard where not; every output is the shards' outputs
    concatenated along the leading dimension (a sum over rows leaves as
    one partial per shard, for traced code outside to add up)."""
    if _one_device(mesh):
        return fn
    # JAX 0.9's own manual region over the unified mesh's axis, not the
    # removed experimental one the lint rule was written against
    return jax.shard_map(  # provlint: disable=no-legacy-spmd
        fn, mesh=mesh, in_specs=tuple(P(AXIS) if s else P() for s in split),
        out_specs=P(AXIS),
        # a pallas_call states no replication of its outputs to check
        check_vma=False,
    )


def first_row(mesh, local_rows):
    """Inside `per_shard`'s `fn`: the global index of the shard's first
    row, 0 on one device."""
    if _one_device(mesh):
        return 0
    return jax.lax.axis_index(AXIS) * local_rows
