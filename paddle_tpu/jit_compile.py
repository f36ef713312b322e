"""Shared jax.jit wrapper with PADDLE_TPU_XLA_OPTIONS plumbing.

Both execution modes compile through this single entry point: the static
executor's whole-program step (executor.py) and the dygraph JIT bridge's
traced eager steps (dygraph/jit.py), so XLA compiler tuning set once in
the environment applies to every compiled step in the process — the
tuning surface the reference exposes as FLAGS_* gflags
(platform/flags.cc)."""

from __future__ import annotations

import os

import jax

__all__ = ["xla_jit", "parse_xla_options", "COMPILE_CACHE_DIR"]

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
