"""Compiled steps on disk: what a warm start finds instead of a trace.

JAX's persistent cache keeps XLA's compile, but finds an executable only
by the hash of the module, so every start still pays Program to jaxpr and
jaxpr to StableHLO (Mosaic's lowering of every kernel) to compute it. This
store sits beside that cache, `<COMPILE_CACHE_DIR>/steps/`, and is asked
at a step's first call (`executor._first_call`) before anything is traced:
a hit loads the executable (`jax.stages.Compiled`: the same pytrees, the
same donation and shardings, the same C++ call path as the jit), a miss
takes the jit's ahead-of-time path once and, under JAX's own rule (trace,
lower and compile together took `jax_persistent_cache_min_compile_time_secs`
or more), writes the entry.

An entry is `steps/<slot>/<rest>.bin`. `slot` hashes what the step is: the
Program before and after the passes (without its seed, which is an
argument; with what rides on it beside its ops, the AMP lists among it:
`executor._store_key`), `Executor._prepare_run`'s key, the jit's keyword
arguments, and
the names, shapes, dtypes, weak types and shardings of the arguments
passed. `rest` hashes what the lowering reads from round it: every
`PADDLE_TPU_*`, `JAX_*`, `XLA_FLAGS` and `LIBTPU_INIT_ARGS` variable, the
`jax`, `jaxlib` and platform versions, the device kind and count, and a
digest of every `.py` file of this package, so a changed lowering rule or
kernel is never served the old step. (A lowering registered from outside
the package, `ops.registry.register_op` in a user's module, is in no
digest: `executor._store_key` gives a step that takes one no key.)
Writing an entry removes its slot's other files. Everything here may be
deleted at any time.

Nothing in here is a reason to fail: an entry that cannot be read, loaded
or written is counted (`step_store_errors.<owner>`) and the step compiles
as it did without the store. A step whose trace leaves something in the
process that the executable does not carry (`PADDLE_TPU_CHECK_NAN_INF`'s
names, a host callback's function) or takes a lowering from outside the
package is never stored, and a fleet of processes keeps to the jit. `PERF.md`, section 3, has the counters."""

from __future__ import annotations

import contextlib
import functools
import hashlib
import logging
import os
import pickle
import time

import numpy as np
# as JAX's cache packs its entries: a TPU executable shrinks fivefold
# (PERF.md, PR 56: 71 MB against 430)
import zstandard

import jax
from jax.experimental import serialize_executable
from jax.sharding import NamedSharding, SingleDeviceSharding

from . import profiler
from .jit_compile import COMPILE_CACHE_DIR

__all__ = ["DIR", "first_call"]

_log = logging.getLogger(__name__)

# Where the entries live; None: no store (the test suite's setting). It
# follows JAX's cache: where that is off, at import, so is this.
DIR = (os.path.join(COMPILE_CACHE_DIR, "steps")
       if jax.config.jax_enable_compilation_cache else None)

_FORMAT = 1
_PACKAGE = os.path.dirname(os.path.abspath(__file__))
# a supervisor's book-keeping of one attempt of one rank: no lowering reads
# them, and with them in the key no restarted trainer would find its step
_NOT_THE_LOWERINGS = ("PADDLE_TPU_PROGRESS_FILE", "PADDLE_TPU_TRAINER_ATTEMPT")
_CONFIG = ("jax_enable_x64", "jax_default_matmul_precision",
           "jax_default_prng_impl", "jax_threefry_partitionable",
           "jax_numpy_dtype_promotion")


# ---------------------------------------------------------------------------
# the key
# ---------------------------------------------------------------------------

@functools.cache
def _source_digest() -> str:
    """Every `.py` file of the package, by relative path and content
    (2 MB, a few milliseconds, once a process)."""
    h = hashlib.sha256()
    for root, dirs, files in os.walk(_PACKAGE):
        dirs.sort()
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(root, name)
                h.update(os.path.relpath(path, _PACKAGE).encode())
                with open(path, "rb") as f:
                    h.update(f.read())
    return h.hexdigest()


def _made_by() -> dict:
    """Who can load what it wrote: an entry's header, and part of `rest`."""
    import jaxlib

    device = jax.devices()[0]
    return {"format": _FORMAT, "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "platform": device.platform,
            "platform_version": device.client.platform_version,
            "device_kind": device.device_kind}


def _surroundings() -> tuple:
    env = sorted(
        (k, v) for k, v in os.environ.items()
        if (k.startswith(("PADDLE_TPU_", "JAX_"))
            or k in ("XLA_FLAGS", "LIBTPU_INIT_ARGS"))
        and k not in _NOT_THE_LOWERINGS)
    return (env, sorted(_made_by().items()), jax.device_count(),
            [(name, str(getattr(jax.config, name, None))) for name in _CONFIG],
            _source_digest())


def _sharding(s):
    if isinstance(s, SingleDeviceSharding):
        (device,) = s.device_set
        return ("single", device.id, s.memory_kind)
    if isinstance(s, NamedSharding):
        mesh = s.mesh
        return ("named", tuple(mesh.axis_names), tuple(mesh.devices.shape),
                tuple(d.id for d in mesh.devices.flat), str(s.spec),
                s.memory_kind)
    return (repr(s), tuple(sorted(d.id for d in s.device_set)))


def _leaf(x):
    if isinstance(x, jax.Array):
        return (x.shape, str(x.dtype), bool(x.aval.weak_type),
                _sharding(x.sharding))
    if isinstance(x, (np.ndarray, np.generic)):
        return (x.shape, str(x.dtype), "host")
    if isinstance(x, jax.sharding.Sharding):
        return _sharding(x)
    return repr(x)


def _described(tree) -> list:
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(path), _leaf(x)) for path, x in leaves]


def _digest(what) -> str:
    return hashlib.sha256(repr(what).encode()).hexdigest()[:32]


def entry_path(directory, key, args) -> str:
    slot = _digest((_described(key), _described(args)))
    return os.path.join(directory, slot, _digest(_surroundings()) + ".bin")


# ---------------------------------------------------------------------------
# an entry
# ---------------------------------------------------------------------------

def _pack(data):
    return zstandard.ZstdCompressor().compress(data)


def _unpack(data):
    return zstandard.ZstdDecompressor().decompress(data)


def _is_a_count(name: str) -> bool:
    """What a trace says about its program, and not what it cost or whom
    it asked: the lowerings' counts and gauges."""
    return not (name.endswith("_us") or "_us." in name
                or name.startswith("compile_"))


def _calls_the_host(lowered) -> bool:
    """A step with a host callback holds a function of this process; where
    JAX does not say, it is taken to."""
    args = getattr(getattr(lowered, "_lowering", None), "compile_args", None)
    if not isinstance(args, dict) or "host_callbacks" not in args:
        return True
    return bool(args["host_callbacks"])


def _load(path, owner):
    """The entry's step, its trace's counts said again; None on a miss."""
    t0 = time.perf_counter()
    try:
        with open(path, "rb") as f:
            entry = pickle.loads(_unpack(f.read()))
        if entry["made_by"] != _made_by():
            raise ValueError(f"made by {entry['made_by']}")
        by_id = {d.id: d for d in jax.devices()}
        step = serialize_executable.deserialize_and_load(
            entry["executable"], entry["in_tree"], entry["out_tree"],
            execution_devices=[by_id[i] for i in entry["devices"]])
        said = entry["counters"]
    except (FileNotFoundError, NotADirectoryError):  # no entry: a miss
        return None
    except Exception:  # noqa: BLE001 — truncated, foreign, unreadable: a miss
        _log.warning("compiled step %s cannot be used; compiling it again",
                     path, exc_info=True)
        profiler.bump_counter(f"step_store_errors.{owner}")
        return None
    # the executable's read, where a warm start's was filed before the store
    us = int((time.perf_counter() - t0) * 1e6)
    profiler.bump_counter(f"compile_backend_us.{owner}", us)
    profiler.bump_counter(f"compile_cache_read_us.{owner}", us)
    profiler.bump_counter(f"step_store_hits.{owner}")
    profiler.replay_counters(said)
    return step


def _write(path, step, said) -> None:
    executable, in_tree, out_tree = serialize_executable.serialize(step)
    (first, *_) = jax.tree_util.tree_leaves(step.input_shardings)
    entry = {"made_by": _made_by(), "executable": executable,
             "in_tree": in_tree, "out_tree": out_tree,
             # the executable's own devices, in its order: loaded over any
             # others it runs nowhere
             "devices": [d.id for d in first._device_assignment],
             "counters": said}
    slot = os.path.dirname(path)
    os.makedirs(slot, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        with open(tmp, "wb") as f:
            f.write(_pack(pickle.dumps(entry, pickle.HIGHEST_PROTOCOL)))
        os.replace(tmp, path)  # a reader sees the whole entry or none
    except BaseException:
        with contextlib.suppress(OSError):
            os.remove(tmp)
        raise
    for name in os.listdir(slot):  # another commit's, another environment's
        if name.endswith(".bin") and name != os.path.basename(path):
            # two environments that take turns over one directory (a flag
            # flipped to and fro, two versions of a fleet) each find this
            _log.info("compiled step %s replaces %s", path, name)
            with contextlib.suppress(OSError):
                os.remove(os.path.join(slot, name))


def _compile_and_keep(jit_fn, args, path, owner):
    """A miss: the jit's ahead-of-time path (the stage events it emits are
    the first call's, so the compile counters read as they did), and the
    entry, if the compile was long enough to be worth one."""
    t0 = time.perf_counter()
    with profiler.recorded_counters() as said:
        lowered = jit_fn.lower(*args)
    step = lowered.compile()
    took = time.perf_counter() - t0
    if (not _calls_the_host(lowered) and took
            >= jax.config.jax_persistent_cache_min_compile_time_secs):
        try:
            _write(path, step, [c for c in said if _is_a_count(c[1])])
            profiler.bump_counter(f"step_store_writes.{owner}")
        except Exception:  # noqa: BLE001 — no directory to write, no way to
            # serialize: the step runs as compiled
            _log.warning("compiled step %s is not kept", path, exc_info=True)
            profiler.bump_counter(f"step_store_errors.{owner}")
    return step


def first_call(jit_fn, args, key, owner):
    """The first call of a step's jit. `key` is what the Executor knows of
    the step without tracing it (a function, asked only where there is a
    store), or None, as the function's answer may be, for a step that is
    not to be stored. Returns (what to call from now on, this call's
    result)."""
    path = None
    if DIR is not None and key is not None and jax.process_count() == 1:
        try:
            described = key()
            if described is not None:
                path = entry_path(DIR, described, args)
        except Exception:  # noqa: BLE001 — no key, no entry
            _log.warning("no key for a compiled step", exc_info=True)
            profiler.bump_counter(f"step_store_errors.{owner}")
    if path is None:
        return jit_fn, jit_fn(*args)
    step = _load(path, owner) or _compile_and_keep(jit_fn, args, path, owner)
    held = [step]

    def stored_step(*args):
        try:
            return held[0](*args)
        except (TypeError, ValueError):
            # other avals or shardings than the step was compiled for,
            # found before anything ran or was donated: the jit retraces,
            # now and from now on
            if held[0] is jit_fn:
                raise
            held[0] = jit_fn
            return jit_fn(*args)

    return stored_step, stored_step(*args)
