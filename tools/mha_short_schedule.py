"""The compiler's schedule of a grid step of the `mha_short` kernel pair
(ops/pallas/mha_short.py): a count, never a device number, and no chip.

`mha_short` under `jax.vjp` (bf16, a key bias) at the three shapes the
benchmark's cells give it, with their attention dropout and without,
compiled for a described v5e with libtpu's dump on (the child-process
compile and the reader are `tools/kda_vreg_count.py`'s). Printed a kernel:
the instruction bundles of a grid step and, of the final bundles, the
filled slots of the MXU, the XLU, the VALU and the one vector store slot
(with how many of its stores are spills), each with the least bundles that
many slots could take (slots over the unit's slots a bundle: MXU 4, XLU 3,
VALU 4, VSTORE 1). The unit whose least comes nearest the bundles is what
the kernel waits on.

    JAX_PLATFORMS=cpu python tools/mha_short_schedule.py <fresh dir> \\
        [path/to/another/mha_short.py] [--shape s512] [--dropout 0.1]

(the path: another copy of the kernel file, say a parent commit's, so one
tool reads both sides; `<dir>` fresh, or it holds an older build's files.)

Against the ledger's PR 57 lines a bundle of this kernel took 0.68 to
0.70 ns on the chip: `bert_base_s512`'s twelve forward and twelve backward
calls a step over a grid of 48 x 6 took 10.3 and 18.35 ms, 2.98 and
5.31 us a grid step of 4,311 and 7,531 bundles; `bert_base_s128`'s (grid
16 x 6) 4.3 and 6.8 us of 6,307 and 10,032. PR 58's bodies read 0.68 to
0.71 at s=512 and 0.74 to 0.76 at s=128 (PERF.md, Findings, PR 58). The count
ranks two bodies of this kernel and predicts no seconds: three orders of
the same operations that it put 3% apart ran at one speed on the chip.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp

import kda_vreg_count as dumps

UNITS = ("VALU", "MXU", "XLU", "VSTORE", "VSTORE:SPILL")

# name -> b, s, heads, causal, the cell's attention dropout. A head is 64
# wide in all three; the block (`_pick_bb`) is 1, 16 and 32 batch rows.
SHAPES = {
    "s512": (48, 512, 12, False, 0.1),   # bert_base_s512
    "s128": (256, 128, 12, False, 0.1),  # bert_base_s128 and its dp4 shard
    "s64": (256, 64, 8, True, 0.1),      # transformer_base_s64's decoder
}


def compile_for_v5e(kernel, shape, dropout):
    """One jit of the pair, compiled for a described v5e: libtpu's dump
    aborts the process inside this compile, after the kernels' files are
    written."""
    chip = dumps.described_chip()
    b, s, heads, causal, _ = SHAPES[shape]
    kernel.require_pallas = lambda name: None  # as on the chip
    x = jax.ShapeDtypeStruct((b, s, heads * 64), jnp.bfloat16, sharding=chip)
    bias = jax.ShapeDtypeStruct((b, s), jnp.float32, sharding=chip)

    def both(q, k, v, bias):
        o, pull = jax.vjp(lambda q, k, v: kernel.mha_short(
            q, k, v, heads, bias=bias, causal=causal, dropout=dropout,
            rng_key=jax.random.key(0)), q, k, v)
        return o, pull(o)

    jax.jit(both).lower(x, x, x, bias).compile()


def schedule(path, into, shape, dropout):
    into = os.path.join(into, f"{shape}_dropout{dropout}")
    dumps.dump_compile(
        [sys.executable, os.path.abspath(__file__), into, "--compile",
         "--shape", shape, "--dropout", str(dropout)]
        + ([path] if path else []), into)
    read = dumps.read_dump(into)
    for name in ("mha_short_fwd", "mha_short_bwd"):
        got = read[name]
        units = ", ".join(
            f"{u} {got['slots'][u]} ({-(-got['slots'][u] // got['capacity'][u])})"
            for u in UNITS)
        print(f"{shape} dropout {dropout} {name}: {got['bundles']} bundles "
              f"a grid step; slots (least bundles): {units}")
    return sum(read[n]["bundles"] for n in read)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("dir", help="a fresh directory for libtpu's dump")
    ap.add_argument("path", nargs="?", help="another copy of mha_short.py")
    ap.add_argument("--shape", choices=sorted(SHAPES), action="append")
    ap.add_argument("--dropout", type=float, action="append",
                    help="default: the cell's rate and 0")
    ap.add_argument("--compile", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.compile:
        compile_for_v5e(dumps.load(args.path, "mha_short"), args.shape[0],
                        args.dropout[0])
        return
    for shape in args.shape or SHAPES:
        for dropout in args.dropout or (SHAPES[shape][4], 0.0):
            pair = schedule(args.path, os.path.abspath(args.dir), shape,
                            dropout)
            print(f"{shape} dropout {dropout} the pair: {pair} bundles")


if __name__ == "__main__":
    main()
