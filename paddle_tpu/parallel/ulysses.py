"""Ulysses-style sequence parallelism (SURVEY.md §2.8 SP row — the
all-to-all alternative to ring attention; DeepSpeed-Ulysses pattern),
GSPMD-native.

With the sequence axis sharded over `model`, attention needs every key
for every query. Ring attention keeps sequence sharding and streams K/V
chunks (ops/pallas/ring_attention.py); Ulysses instead re-shards so each
device holds the FULL sequence for h/n of the heads, runs ordinary
(flash or XLA-fused) attention locally, and re-shards back. In the
legacy `shard-map` form those re-shards were four hand-written
`lax.all_to_all`s; here they are two `with_sharding_constraint` flips
(sequence-sharded -> head-sharded -> sequence-sharded) and GSPMD emits
the all-to-alls — same wire traffic, chosen and overlapped by the
compiler. Wins when heads are plentiful and sequence chunks are small;
requires num_heads % n == 0.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["ulysses_attention"]


def _constrain(x, spec, mesh):
    if mesh is None:
        return x
    from jax.sharding import NamedSharding

    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def ulysses_attention(q, k, v, axis_name="model", axis_size=None, bias=None,
                      causal=False, sm_scale=None, dropout=0.0,
                      rng_key=None, mesh=None):
    """Attention with Ulysses head/sequence re-sharding, on GLOBAL arrays.

    q/k/v: [b, h, s, d] (full sequence — under GSPMD each device holds a
    sequence chunk when the caller shards dim 2 over `axis_name`);
    optional additive key bias [b, s]. Returns [b, h, s, d] constrained
    back to the sequence sharding. `axis_size` (or the axis size of the
    current mesh) only validates head divisibility — the math is the
    plain attention the all-to-all dance is equivalence-preserving for.
    """
    from jax.sharding import PartitionSpec as P

    from .mesh import canonical_axis, current_mesh

    ax = canonical_axis(axis_name)
    mesh = mesh if mesh is not None else current_mesh()
    n = axis_size
    if n is None and mesh is not None and ax in mesh.axis_names:
        n = mesh.shape[ax]
    n = int(n or 1)
    b, h, s, d = q.shape
    if h % n != 0:
        raise ValueError(
            f"ulysses needs num_heads ({h}) divisible by the {ax} axis "
            f"({n})"
        )

    seq_spec = P(None, None, ax, None)
    head_spec = P(None, ax, None, None)
    # sequence-sharded in; flipping the constraint to head-sharded is the
    # seq->head all-to-all (GSPMD emits it), full attention runs with the
    # whole sequence per head group, and the exit constraint is the
    # head->seq all-to-all back
    qh = _constrain(_constrain(q, seq_spec, mesh), head_spec, mesh)
    kh = _constrain(_constrain(k, seq_spec, mesh), head_spec, mesh)
    vh = _constrain(_constrain(v, seq_spec, mesh), head_spec, mesh)

    from ..ops.fused_ops import _flash_dispatch
    from ..ops.pallas.flash_attention import (
        _reference_attention,
        flash_attention,
    )

    if sm_scale is None:
        sm_scale = 1.0 / float(d) ** 0.5
    if bias is not None:
        bias = jnp.asarray(bias, jnp.float32)
    if _flash_dispatch(qh, kh) == "flash":
        out = flash_attention(qh, kh, vh, bias=bias, causal=causal,
                              sm_scale=sm_scale, dropout=dropout,
                              rng_key=rng_key)
    else:
        out = _reference_attention(qh, kh, vh, bias, causal, sm_scale,
                                   dropout, rng_key)
    return _constrain(out.astype(q.dtype), seq_spec, mesh)
