"""Count what one KDA chunk asks of the vector units, without a chip: the
jaxprs of `_chunk_fwd` and `_chunk_bwd` (ops/pallas/kda_chunk.py) at the
cell's shapes ([64, 128] float32 operands, bf16 products), every
equation's outputs as [8, 128] registers of 32-bit lanes, products left
out. A count, not a timing: Mosaic folds some `iota`, `broadcast_in_dim`
and `convert_element_type`, so those are given apart.

    JAX_PLATFORMS=cpu python tools/kda_vreg_count.py [path/to/kda_chunk.py]

(the path: another copy of the kernel file, say a parent commit's.)
"""

from __future__ import annotations

import collections
import importlib.util
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

FOLDED = ("iota", "broadcast_in_dim", "convert_element_type")


def registers(aval):
    shape = tuple(aval.shape)
    if not shape:
        return 1
    rows = shape[-2] if len(shape) > 1 else 1
    n = -(-rows // 8) * -(-shape[-1] // 128)
    for d in shape[:-2]:
        n *= d
    return n


def count(jaxpr, into):
    for eqn in jaxpr.eqns:
        inner = [v for v in eqn.params.values() if hasattr(v, "jaxpr")]
        if inner:
            for sub in inner:
                count(sub.jaxpr, into)
        elif eqn.primitive.name != "dot_general":
            into[eqn.primitive.name] += sum(registers(v.aval)
                                            for v in eqn.outvars)
    return into


def main(path=None):
    if path is None:
        from paddle_tpu.ops.pallas import kda_chunk as kernel
    else:
        name = "paddle_tpu.ops.pallas._counted_kda_chunk"
        spec = importlib.util.spec_from_file_location(name, path)
        kernel = sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(kernel)
    x = jax.ShapeDtypeStruct((kernel.CHUNK, 128), jnp.float32)
    beta = jax.ShapeDtypeStruct((kernel.CHUNK, 1), jnp.float32)
    state = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    for fn, args in ((kernel._chunk_fwd, (x, x, x, x, beta, state)),
                     (kernel._chunk_bwd, (x, x, x, x, beta, state, state, x))):
        by = count(jax.make_jaxpr(
            lambda *a: fn(*a, dtype=jnp.bfloat16))(*args).jaxpr,
            collections.Counter())
        total = sum(by.values())
        folded = sum(by[p] for p in FOLDED)
        print(f"{fn.__name__}: {total} register operations, "
              f"{total - folded} without {'/'.join(FOLDED)}; "
              f"{by['exp']} of exp, {by['reduce_sum']} of reduce_sum outputs")
        print("  " + ", ".join(f"{p} {n}" for p, n in by.most_common()))


if __name__ == "__main__":
    main(*sys.argv[1:2])
