"""Shared jax.jit wrapper with PADDLE_TPU_XLA_OPTIONS plumbing.

Both execution modes compile through this single entry point: the static
executor's whole-program step (executor.py) and the dygraph JIT bridge's
traced eager steps (dygraph/jit.py), so XLA compiler tuning set once in
the environment applies to every compiled step in the process — the
tuning surface the reference exposes as FLAGS_* gflags
(platform/flags.cc).

It also owns what a compile is seen to cost: JAX reports each stage of
one (function to jaxpr, jaxpr to StableHLO, backend compile or cache
read) through `jax.monitoring`, and the listeners below file them as
always-on `profiler` counters under the owner that `compile_owner` names
(`PERF.md`, section 3, lists them and the metric each is for).

Beside that cache, under `<COMPILE_CACHE_DIR>/steps/`, live the Executor's
compiled steps (`step_store.py`): one file a step, its executable and the
counts its trace left, found at the step's first call before anything is
traced, so a warm start pays the read and neither of the two stages in
front of it. The file's name holds everything the lowering reads, a digest
of this package's source among it: a changed source file of the package
makes new entries (code registered from outside it is in no digest), and
writing one removes the files it replaces. The directory is
safe to delete at any time."""

from __future__ import annotations

import contextlib
import os
import threading

import jax
import jax.monitoring

from . import profiler

__all__ = ["xla_jit", "parse_xla_options", "COMPILE_CACHE_DIR",
           "compile_owner", "current_owner"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _place_compile_cache() -> str:
    """JAX's persistent compilation cache, placed once at import (JAX
    builds its cache object on the first compile and never looks at the
    directory again). Where JAX_COMPILATION_CACHE_DIR is set JAX reads it
    itself and nothing is set here; otherwise the cache is the fixed
    `<checkout>/.jax_cache` — the path is part of how a later process
    finds the entries, so it is never derived from a pid, a time or a
    temporary name. Child processes (fleet workers, launchers) resolve
    the same directory the same way. JAX keys each entry on the HLO and
    the compile options; its own size and compile-time thresholds stay."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


COMPILE_CACHE_DIR = _place_compile_cache()


# ---------------------------------------------------------------------------
# compile-stage counters
# ---------------------------------------------------------------------------

# JAX's event of each stage (`jax._src.dispatch`) -> the counter it feeds
_BACKEND = "/jax/core/compile/backend_compile_duration"
_STAGE_COUNTERS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile_trace_us",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile_lower_us",
    _BACKEND: "compile_backend_us",
}
_CACHE_LOOKUP = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_WRITE = "/jax/compilation_cache/cache_misses"  # where an entry is written
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"


class _Compiling(threading.local):
    """What this thread is compiling, and for whom."""

    owner = "other"  # who pays: "train", "forward" (`compile_owner`), else
    depth = 0  # stage events open: only the outermost one's time is filed
    missed = False  # the open backend event asked the cache and got nothing


_compiling = _Compiling()


def current_owner() -> str:
    return _compiling.owner


@contextlib.contextmanager
def compile_owner(owner: str):
    """Whatever this thread traces, lowers and compiles inside the body
    is filed under `owner`. The Executor names its steps "train" and
    "forward" round their first call; every compile nobody claimed (an
    eager `jnp` dispatch, the dygraph bridge, a user's own `jax.jit`) is
    "other"."""
    before, _compiling.owner = _compiling.owner, owner
    try:
        yield
    finally:
        _compiling.owner = before


def _stage_opened(event, value, **kwargs):
    # `log_elapsed_time` records the start as a scalar on entry
    if event in _STAGE_COUNTERS:
        _compiling.depth += 1


def _stage_closed(event, duration_secs, **kwargs):
    """A time is filed once: JAX emits a trace event for every inner
    `jit` traced under an outer one (most `jnp` functions are) and again
    for the functions a lowering rule traces, and their durations lie
    inside the open event's. A count is filed always."""
    t = _compiling
    if event == _CACHE_READ:
        if t.depth <= 1:  # inside an outermost backend event
            profiler.bump_counter(f"compile_cache_read_us.{t.owner}",
                                  int(duration_secs * 1e6))
        return
    counter = _STAGE_COUNTERS.get(event)
    if counter is None:
        return
    t.depth = max(t.depth - 1, 0)
    if t.depth == 0:
        profiler.bump_counter(f"{counter}.{t.owner}", int(duration_secs * 1e6))
    if event == _BACKEND:
        profiler.bump_counter(f"compile_requests.{t.owner}")
        if t.missed:
            # compiled anew though the cache was asked; less the writes
            # it is what the cache will never keep (JAX writes no entry
            # under its thresholds of compile time and size)
            profiler.bump_counter(f"compile_cache_compiled.{t.owner}")
            t.missed = False


def _cache_event(event, **kwargs):
    t = _compiling
    if event == _CACHE_LOOKUP:
        t.missed = True  # until a hit says otherwise
    elif event == _CACHE_HIT:
        t.missed = False
        profiler.bump_counter(f"compile_cache_hits.{t.owner}")
    elif event == _CACHE_WRITE:
        profiler.bump_counter(f"compile_cache_writes.{t.owner}")


# registered once, beside the cache's placement; a listener runs only
# when something compiles
jax.monitoring.register_scalar_listener(_stage_opened)
jax.monitoring.register_event_duration_secs_listener(_stage_closed)
jax.monitoring.register_event_listener(_cache_event)


def parse_xla_options(opts: str) -> dict:
    """"k=v,k=v" -> {k: typed v}. XLA validates option TYPES: booleans
    must arrive as bool ("false" as a string is rejected), numbers may
    arrive as strings; coerce the natural spellings."""
    parsed = {}
    for kv in opts.split(","):
        kv = kv.strip()
        if not kv:
            continue
        k, _, v = kv.partition("=")
        v = v.strip()
        if v.lower() in ("true", "false"):
            v = v.lower() == "true"
        elif v.lstrip("-").isdigit():
            v = int(v)
        parsed[k.strip()] = v
    return parsed


def xla_jit(fun, **kwargs):
    """jax.jit with PADDLE_TPU_XLA_OPTIONS plumbed through as XLA
    compiler options ("k=v,k=v" -> env_option_overrides). Backend-
    specific knobs like xla_tpu_scoped_vmem_limit_kib are NOT parseable
    from XLA_FLAGS by the local client, but CompileOptions overrides
    travel with the compile request."""
    opts = os.environ.get("PADDLE_TPU_XLA_OPTIONS", "").strip()
    if opts:
        parsed = parse_xla_options(opts)
        if parsed:
            kwargs["compiler_options"] = parsed
    return jax.jit(fun, **kwargs)
