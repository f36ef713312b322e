"""DLPack zero-copy tensor interop (reference:
paddle/fluid/framework/dlpack_tensor.{h,cc}). jax arrays speak DLPack
natively; these wrappers keep the reference API names."""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["to_dlpack", "from_dlpack"]


def to_dlpack(tensor):
    """Device array -> DLPack capsule (zero-copy where the consumer shares
    the device; falls back to a host copy on backends whose PJRT plugin
    lacks external buffer references)."""
    arr = tensor if isinstance(tensor, jax.Array) else jnp.asarray(tensor)
    try:
        return arr.__dlpack__()
    except Exception:
        import numpy as np

        # own a writable host copy (np views of jax arrays are readonly,
        # which DLPack cannot signal)
        return np.array(arr).__dlpack__()


def from_dlpack(capsule):
    """DLPack capsule / any __dlpack__ exporter (torch, numpy, cupy) ->
    device array."""
    return jnp.from_dlpack(capsule)
