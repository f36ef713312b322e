"""Three counts of the KDA chunk kernels (ops/pallas/kda_chunk.py), none
a timing and none needing a chip.

What one chunk asks of the vector units: the jaxprs of a chunk's forward
(`_rows` and `_state_free` over one chunk, then `_chunk_fwd`) and backward
(`_state_free` with Aq transposed, `_chunk_bwd`, `_sweep_tail`) at the
cell's shapes ([64, 128] float32 operands, bf16 products), every
equation's outputs as [8, 128] registers of 32-bit lanes, products left
out. Mosaic folds some `iota`, `broadcast_in_dim` and
`convert_element_type`, so those are given apart.

What a kernel costs the host at every start of a job: the equations of
each kernel's body (`kda_fwd`, `kda_bwd`, `gdn_fwd`, `gdn_bwd`), the
nested jits' bodies counted where they are called, since that is where
Mosaic lowers them. "shared" is what the kernel states once whatever its
width (the step's stacked rows, the masks), "a chunk" what every further
chunk of a grid step adds (`tests/test_kda_kernel.py` holds the second
to 230 forward and 500 backward). In a cell's real step the chip's host
took 0.9 to 1.0 ms an equation of the backward kernel and 0.13 of the
forward's, lowered in a process by themselves 0.15 of either (PERF.md,
PR 54): the count ranks two bodies, it does not predict seconds.

    JAX_PLATFORMS=cpu python tools/kda_vreg_count.py [path/to/kda_chunk.py]

(the path: another copy of the kernel file, say a parent commit's, as long
as it has this tree's functions: a copy from before PR 65, whose kernels
took q, k, the log decay and beta float32 behind XLA's prologue, wants
that commit's copy of this tool.)

The compiler's own schedule of a grid step, `--schedule <dir>`: the pair
at the cells' shapes (one row of 4,096 tokens, 32 heads of 128, q, k, v
and the logits bf16 as the projections write them under AMP; a decay a
channel, gated float32 by XLA, then a decay a head under 16 key heads)
compiled for a described v5e with

    LIBTPU_INIT_ARGS="--xla_jf_dump_to=<dir> --xla_jf_dump_llo_text=true"

which this mode sets itself. libtpu then writes, for each `pallas_call` by
name, `*<name>*final_bundles.txt` (the instruction bundles of a grid
step), `*final_hlo-static-per-bundle-utilization.txt` (the units' slots a
bundle) and `*critical-path.txt` (`Length to end`: the longest chain of
dependent instructions in cycles, the order of a unit of the MXU
included). Printed a kernel: the bundles and that chain. Where the chain
is the longer of the two, a grid step's time is cycles x 0.83 to 0.89 ns
by the wall time of `jax.jit(kda_chunk)` over fourteen builds of the pair
(PERF.md section 7, PR 50), cycles x 0.70 to 0.80 ns by `_call_fwd` and
`_call_bwd` alone or by a trace over ten more (PR 52: the higher figures
where the bundles come near the chain); of two orders of the same
products it named the faster every time it was asked.

    JAX_PLATFORMS=cpu python tools/kda_vreg_count.py --schedule /tmp/llo \\
        [--chunks 4] [path/to/kda_chunk.py]

(`--chunks`: `CHUNKS_PER_STEP` for this compile; `<dir>` fresh, or it
holds an older build's files too. The compile runs in a child process,
because libtpu aborts inside it once the kernels' files are written; one
process loads libtpu at a time.)
"""

from __future__ import annotations

import argparse
import collections
import importlib
import importlib.util
import os
import re
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core

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


def _inner(eqn):
    """The jaxprs an equation holds: a nested jit's, a `cond`'s branches,
    a `pallas_call`'s body."""
    for value in eqn.params.values():
        for item in value if isinstance(value, (tuple, list)) else (value,):
            if isinstance(item, jex_core.ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, jex_core.Jaxpr):
                yield item


def count(jaxpr, into):
    for eqn in jaxpr.eqns:
        inner = list(_inner(eqn))
        if inner:
            for sub in inner:
                count(sub, into)
        elif eqn.primitive.name != "dot_general":
            into[eqn.primitive.name] += sum(registers(v.aval)
                                            for v in eqn.outvars)
    return into


def load(path, module="kda_chunk"):
    """`paddle_tpu.ops.pallas.<module>`, or another copy of its file at
    `path` (say a parent commit's) under a name beside it."""
    if path is None:
        return importlib.import_module(f"paddle_tpu.ops.pallas.{module}")
    name = f"paddle_tpu.ops.pallas._counted_{module}"
    spec = importlib.util.spec_from_file_location(name, path)
    kernel = sys.modules[name] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kernel)
    return kernel


def described_chip():
    """A sharding on one chip of a v5e that is described, not attached:
    what a compile without the chip lowers for."""
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])


def registers_a_chunk(kernel):
    dtype = jnp.bfloat16
    x = jax.ShapeDtypeStruct((kernel.CHUNK, 128), jnp.float32)
    beta = jax.ShapeDtypeStruct((kernel.CHUNK, 1), jnp.float32)
    state = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def state_free(q, k, v, g, beta, backward):
        masks = kernel._pair_masks(kernel.CHUNK)
        free, = kernel._state_free(kernel._rows(q, k, v, g, beta),
                                   masks, dtype, backward)
        return free, masks

    def chunk_fwd(q, k, v, g, beta, St):
        free, _ = state_free(q, k, v, g, beta, False)
        return kernel._chunk_fwd(free, St, dtype=dtype, scale=128 ** -0.5)

    def chunk_bwd(q, k, v, g, beta, St, dSt, dO):
        free, masks = state_free(q, k, v, g, beta, True)
        parts, dbeta, dSt = kernel._chunk_bwd(free, masks, St, dSt, dO,
                                              dtype=dtype)
        return kernel._sweep_tail([parts]), dbeta, dSt

    for fn, args in ((chunk_fwd, (x, x, x, x, beta, state)),
                     (chunk_bwd, (x, x, x, x, beta, state, state, x))):
        by = count(jax.make_jaxpr(fn)(*args).jaxpr, collections.Counter())
        total = sum(by.values())
        folded = sum(by[p] for p in FOLDED)
        print(f"{fn.__name__}: {total} register operations, "
              f"{total - folded} without {'/'.join(FOLDED)}; "
              f"{by['exp']} of exp, {by['reduce_sum']} of reduce_sum outputs")
        print("  " + ", ".join(f"{p} {n}" for p, n in by.most_common()))


def equations(jaxpr):
    """The equations the host lowers for `jaxpr`: a nested jit's body
    counts wherever it is called, as Mosaic lowers it there."""
    total = 0
    for eqn in jaxpr.eqns:
        inner = list(_inner(eqn))
        total += sum(equations(sub) for sub in inner) if inner else 1
    return total


def _pair(kernel, statics, sds, b, s, key_heads, heads, d):
    """The pair under `jax.vjp` from the arrays `kda_mixer_core` hands it
    under AMP (q, k, v and the logits bf16; with a decay a head A_log and
    the decay's bias float32 beside its logits, with a decay a channel
    the float32 log decay that XLA's gate made: since PR 65 the kernels
    make the norms, beta and a head's decay themselves), and those arrays'
    shapes by `sds(shape, dtype)`."""
    def both(*operands):
        o, pull = jax.vjp(lambda *a: kernel._core(*a, statics), *operands)
        return o, pull(o)

    bf16, f32 = jnp.bfloat16, jnp.float32
    keys, values = sds((b, s, key_heads * d), bf16), sds((b, s, heads * d),
                                                         bf16)
    logits, numbers = sds((b, s, heads), bf16), sds((1, heads), f32)
    if statics.per_head:
        return both, (keys, keys, values, logits, logits, (numbers, numbers))
    return both, (keys, keys, values, sds((b, s, heads * d), f32), logits, ())


def kernel_equations(kernel, steps, per_head=False):
    """Kernel name -> `equations` of its body with `steps` chunks a grid
    step: the pair traced once, a row of two grid steps, two heads (under
    one key head where the decay is a head's). Nothing runs."""
    heads, d = 2, 128
    key_heads = 1 if per_head else heads
    s = 2 * steps * kernel.CHUNK
    statics = kernel._Statics(heads, steps, jnp.bfloat16, False, s,
                              heads // key_heads, per_head)
    both, args = _pair(kernel, statics, jax.ShapeDtypeStruct, 1, s,
                       key_heads, heads, d)
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = equations(eqn.params["jaxpr"])
            else:
                for sub in _inner(eqn):
                    walk(sub)

    walk(jax.make_jaxpr(both)(*args).jaxpr)
    return found


def equations_a_start(kernel):
    """Each kernel's equations at `CHUNKS_PER_STEP`, as what it states
    once and what a chunk adds (from the bodies at two and four chunks)."""
    width = kernel.CHUNKS_PER_STEP
    for per_head in (False, True):
        two, four = (kernel_equations(kernel, n, per_head) for n in (2, 4))
        for name in two:
            chunk = (four[name] - two[name]) // 2
            shared = two[name] - 2 * chunk
            print(f"{name}: {shared + width * chunk} equations a grid step "
                  f"of {width} chunks: {shared} shared + {width} x {chunk} "
                  "a chunk")


def compile_for_v5e(kernel, chunks):
    """The two pairs in one jit, compiled for a described v5e: libtpu's
    dump aborts the process inside this compile, after the kernels' files
    are written (it looks for a report template that is not installed)."""
    chip = described_chip()
    b, s, h, d = 1, 4096, 32, 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def pair(hk, per_head):
        statics = kernel._Statics(h, chunks, jnp.bfloat16, False, s, h // hk,
                                  per_head)
        return _pair(kernel, statics, sds, b, s, hk, h, d)

    (kda, kda_args), (gdn, gdn_args) = pair(h, False), pair(h // 2, True)
    jax.jit(lambda a, b: (kda(*a), gdn(*b))).lower(
        kda_args, gdn_args).compile()


def dump_compile(child, into):
    """Run `child` (a command that compiles for a described v5e) with
    libtpu's dump on, into the fresh directory `into`. libtpu aborts the
    child inside the compile once the kernels' files are written, so its
    exit code says nothing; `read_dump` says whether the files are there."""
    os.makedirs(into, exist_ok=True)
    subprocess.run(child, capture_output=True, env=dict(
        os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
        LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={into} "
                         "--xla_jf_dump_llo_text=true"))


def read_dump(into):
    """Kernel name (`<name>_fwd`, `<name>_bwd`) -> what libtpu wrote of a
    grid step: `bundles`, `chain` (the longest chain of dependent
    instructions, cycles) and `slots`, each unit's filled slots summed
    over the final bundles, with `capacity`, its slots a bundle."""
    read = collections.defaultdict(dict)
    for name in sorted(os.listdir(into)):
        found = re.match(r"\d+-(\w+_(?:fwd|bwd))\.\d+-\d+-(.+)\.txt$", name)
        if not found:
            continue
        with open(os.path.join(into, name)) as f:
            text = f.read()
        if found[2] == "critical-path":
            read[found[1]]["chain"] = max(
                int(n) for n in re.findall(r"Length to end: (\d+)", text))
        elif found[2] == "schedule-analysis_final_bundles":
            read[found[1]]["bundles"] = int(re.search(
                r"total scheduled bundles:\s+(\d+)", text)[1])
        elif found[2] == "final_hlo-static-per-bundle-utilization":
            head, rows = text.split("== UTILIZATION:")
            units, capacity = head.strip().splitlines()[1:3]
            units = [u.strip() for u in units.split(",")]
            sums = [sum(col) for col in zip(*(
                map(int, row.split()) for row in rows.strip().splitlines()))]
            read[found[1]]["slots"] = dict(zip(units, sums))
            read[found[1]]["capacity"] = dict(
                zip(units, map(int, capacity.split())))
    if not read:
        sys.exit(f"no kernel's files under {into}: was libtpu free to load?")
    return read


def schedule(path, into, chunks):
    """Compile in a child (`--compile`) with the dump on, then read what
    it wrote whether or not it left in order."""
    dump_compile([sys.executable, os.path.abspath(__file__), "--compile",
                  "--chunks", str(chunks)] + ([path] if path else []), into)
    for name, numbers in read_dump(into).items():
        print(f"{name}: {numbers.get('bundles')} bundles a grid step of "
              f"{chunks} chunks, longest chain {numbers.get('chain')} cycles")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path", nargs="?", help="another copy of kda_chunk.py")
    ap.add_argument("--schedule", metavar="DIR",
                    help="compile for a described v5e and dump into DIR")
    ap.add_argument("--chunks", type=int, help="CHUNKS_PER_STEP to compile")
    ap.add_argument("--compile", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.schedule:
        chunks = args.chunks or load(args.path).CHUNKS_PER_STEP
        schedule(args.path, os.path.abspath(args.schedule), chunks)
    elif args.compile:
        compile_for_v5e(load(args.path), args.chunks)
    else:
        kernel = load(args.path)
        registers_a_chunk(kernel)
        equations_a_start(kernel)


if __name__ == "__main__":
    main()
