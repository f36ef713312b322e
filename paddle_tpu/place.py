"""Device places (reference: paddle/fluid/platform/place.h).

Fluid dispatches kernels per (Place, dtype, layout); here the whole graph
is one XLA computation on the process's default JAX backend, and a Place
states which backend that must be. `TPUPlace` (and its reference-API
aliases `XLAPlace` / `CUDAPlace`) requires the default backend to be
`tpu`: an Executor built with it on any other backend raises instead of
running on what it found. `CPUPlace` makes no demand — it runs on the
process default, which the tests hold on the CPU with `JAX_PLATFORMS=cpu`
in the environment before the first JAX import.
"""

from __future__ import annotations

__all__ = [
    "CPUPlace", "TPUPlace", "XLAPlace", "CUDAPlace", "is_compiled_with_cuda",
    "peak_bf16_flops",
]

# dense bf16 peak FLOP/s of ONE chip, keyed by the `device_kind` JAX
# reports; the single source every MFU figure divides by
_PEAK_BF16_FLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    "TPU v5 lite": 197e12,
}


def peak_bf16_flops(device_kind: str) -> float:
    """Per-chip bf16 peak for `jax.devices()[0].device_kind`. A device
    that is not in the table is an error, never a default: an MFU over
    the wrong peak is a wrong number with a right-looking name."""
    try:
        return _PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no bf16 peak recorded for device_kind {device_kind!r} "
            f"(known: {sorted(_PEAK_BF16_FLOPS)}); add it to "
            "paddle_tpu/place.py with its source"
        ) from None


class Place:
    # the platform jax.default_backend() must report for an Executor to
    # accept this place; None makes no demand
    platform = None

    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def require_backend(self) -> None:
        """Raise unless the process's default JAX backend is this place's
        platform (initialises the backend, like any first JAX call)."""
        if self.platform is None:
            return
        import jax

        found = jax.default_backend()
        if found != self.platform:
            raise RuntimeError(
                f"{self!r} needs the {self.platform!r} backend but this "
                f"process's default JAX backend is {found!r} "
                f"(devices: {jax.devices()})"
            )

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"


class CPUPlace(Place):
    def __init__(self):
        super().__init__(0)


class TPUPlace(Place):
    """The native device story — one entry per chip; sharded execution uses a
    jax.sharding.Mesh over all chips instead of per-place graphs."""

    platform = "tpu"


# Aliases for reference-API compatibility.
XLAPlace = TPUPlace


class CUDAPlace(TPUPlace):
    pass


class CUDAPinnedPlace(CPUPlace):
    pass


def is_compiled_with_cuda() -> bool:
    return False
