"""Per-op micro-benchmark harness — the reference's
operators/benchmark/op_tester.cc capability, TPU-style: build a one-op
Program, lower it through the registry, jit it, and time executions on
the chip with a host-fetch barrier.

Usage:
    python tools/op_bench.py                      # the default sweep
    python tools/op_bench.py matmul 1024x1024,1024x1024
    python tools/op_bench.py softmax 256x12x128x128 --dtype bfloat16
    python tools/op_bench.py dropout 32768x768 --attr dropout_prob=0.1 \\
        --grad

Prints one line per case: op, shapes, dtype, fwd ms, (fwd+bwd ms),
achieved GB/s over the op's input+output bytes.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _shapes(spec):
    return [tuple(int(d) for d in s.split("x")) for s in spec.split(",")]


def _sync(x):
    leaves = [v for v in (x if isinstance(x, (list, tuple)) else [x])]
    # slice ON DEVICE first — np.asarray of a full output would copy the
    # whole tensor to the host just to synchronize
    np.asarray(leaves[-1].reshape(-1)[:1])


def bench_layer(build, shapes, dtype="float32", steps=30, grad=False,
                rng_seed=0):
    """build(*input_vars) -> output var. Returns (fwd_ms, fwdbwd_ms|None,
    bytes_moved)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    import paddle_tpu.framework as fw

    fw.switch_main_program(fw.Program())
    fw.switch_startup_program(fw.Program())
    fw.unique_name.switch()

    rng = np.random.RandomState(rng_seed)
    ins = []
    feed = {}
    with fluid.unique_name.guard():
        for i, shape in enumerate(shapes):
            v = fluid.layers.data(f"x{i}", list(shape), dtype=dtype,
                                  append_batch_size=False)
            v.stop_gradient = False
            ins.append(v)
            feed[f"x{i}"] = rng.rand(*shape).astype("float32")
        out = build(*ins)
        fetches = [out.name]
        if grad:
            loss = fluid.layers.reduce_sum(out)
            gs = fluid.backward.calc_gradient(loss, ins)
            fetches += [g.name for g in gs if g is not None]

    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {k: jax.device_put(jnp.asarray(v)) for k, v in feed.items()}
    outs = exe.run(feed=feed, fetch_list=fetches, return_numpy=False)
    _sync(outs)

    t0 = time.time()
    for _ in range(steps):
        outs = exe.run(feed=feed, fetch_list=fetches, return_numpy=False)
    _sync(outs)
    dt = (time.time() - t0) / steps

    itemsize = (2 if dtype in ("bfloat16", "float16")
                else np.dtype("float32" if dtype == "float64" else
                              dtype).itemsize)
    nbytes = sum(int(np.prod(s)) for s in shapes) * itemsize
    nbytes += int(np.prod(out.shape)) * itemsize
    if grad:
        # backward re-reads the inputs and writes one grad per input
        nbytes += 2 * sum(int(np.prod(s)) for s in shapes) * itemsize
    return dt * 1e3, nbytes


DEFAULT_SWEEP = [
    # kept short: every case costs a fresh compile
    ("matmul", "4096x1024,1024x4096", {}, "bfloat16"),
    ("softmax", "256x12x128x128", {}, "float32"),
    ("dropout", "32768x3072", {"dropout_prob": 0.1}, "float32"),
    ("layer_norm", "32768x768", {}, "float32"),
]


def _build_fn(op_name, attrs):
    from paddle_tpu import layers

    def build(*ins):
        if op_name == "matmul":
            return layers.matmul(ins[0], ins[1])
        if op_name == "dropout":
            return layers.dropout(
                ins[0], attrs.get("dropout_prob", 0.5),
                dropout_implementation="upscale_in_train",
            )
        if op_name == "layer_norm":
            return layers.layer_norm(ins[0], begin_norm_axis=1)
        if op_name == "reduce_sum":
            return layers.reduce_sum(ins[0], dim=attrs.get("dim"))
        if op_name == "transpose":
            return layers.transpose(ins[0], attrs.get("perm"))
        fn = getattr(layers, op_name, None)
        if fn is None:
            from paddle_tpu.layers import ops as op_layers

            fn = getattr(op_layers, op_name)
        return fn(ins[0], **attrs) if attrs else fn(ins[0])

    return build


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("op", nargs="?", help="layer name (default: sweep)")
    ap.add_argument("shapes", nargs="?",
                    help="comma-separated NxMx... input shapes")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--grad", action="store_true",
                    help="time fwd+bwd instead of fwd only")
    ap.add_argument("--attr", action="append", default=[],
                    help="k=v op attribute (repeatable)")
    args = ap.parse_args()

    cases = []
    if args.op:
        attrs = {}
        for kv in args.attr:
            k, v = kv.split("=", 1)
            try:
                import ast

                attrs[k] = ast.literal_eval(v)
            except (ValueError, SyntaxError):
                attrs[k] = v
        cases.append((args.op, args.shapes, attrs, args.dtype))
    else:
        cases = DEFAULT_SWEEP

    print(f"{'op':<14} {'shapes':<28} {'dtype':<9} "
          f"{'ms' + ('(f+b)' if args.grad else '(fwd)'):<10} GB/s")
    for op_name, shape_spec, attrs, dtype in cases:
        try:
            ms, nbytes = bench_layer(
                _build_fn(op_name, attrs), _shapes(shape_spec),
                dtype=dtype, steps=args.steps, grad=args.grad,
            )
            print(f"{op_name:<14} {shape_spec:<28} {dtype:<9} "
                  f"{ms:<10.3f} {nbytes / ms / 1e6:.1f}")
        except Exception as e:
            print(f"{op_name:<14} {shape_spec:<28} {dtype:<9} "
                  f"FAILED: {type(e).__name__}: {e}")


if __name__ == "__main__":
    main()
