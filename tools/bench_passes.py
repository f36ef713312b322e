"""Measure the Program IR passes (paddle_tpu/passes/): trace/lower wall
time, steady step time and traced-op counts with passes on vs off for
the bench transformer and resnet train programs.

Runs anywhere (CPU included — trace/lower cost is host-side; pass
JAX_PLATFORMS=cpu off-chip). Prints one JSON line per model plus a
summary line.

  python tools/bench_passes.py                   # transformer + resnet
  python tools/bench_passes.py --models transformer
  python tools/bench_passes.py --full            # bench-sized batch/seq

The pass-on/pass-off fetches are compared numerically (rtol 1e-5) from
identical initial state — the same contract tests/test_passes.py pins
at unit scale.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _fresh():
    import paddle_tpu.framework as framework
    import paddle_tpu.scope as scope_mod

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    framework.unique_name.switch()
    scope_mod._scope_stack[:] = [scope_mod.Scope()]


def _build_transformer(full):
    import paddle_tpu as fluid
    from paddle_tpu.models.transformer import (
        TransformerConfig,
        build_transformer,
    )

    cfg = TransformerConfig.base()
    b, s = (64, 64) if full else (4, 16)
    handles = build_transformer(cfg, b, s, s)
    fluid.optimizer.Adam(1e-4).minimize(handles["loss"])
    rng = np.random.RandomState(0)
    pos = np.tile(np.arange(s), (b, 1)).astype("int64")
    feed = {
        "src_ids": rng.randint(1, cfg.src_vocab, (b, s)).astype("int64"),
        "trg_ids": rng.randint(1, cfg.trg_vocab, (b, s)).astype("int64"),
        "lbl_ids": rng.randint(1, cfg.trg_vocab, (b, s)).astype("int64"),
        "src_mask": np.ones((b, s), "float32"),
        "trg_mask": np.ones((b, s), "float32"),
        handles["src_pos_name"]: pos,
        handles["trg_pos_name"]: pos,
    }
    return feed, handles["loss"]


def _build_resnet(full):
    import paddle_tpu as fluid
    from paddle_tpu.models.resnet import resnet50

    b = 32 if full else 2
    img = fluid.layers.data("img", [b, 3, 224, 224],
                            append_batch_size=False)
    label = fluid.layers.data("label", [b, 1], dtype="int64",
                              append_batch_size=False)
    _, loss, _, _ = resnet50(img, label)
    fluid.optimizer.Momentum(0.1, 0.9).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {
        "img": rng.rand(b, 3, 224, 224).astype("float32"),
        "label": rng.randint(0, 1000, (b, 1)).astype("int64"),
    }
    return feed, loss


BUILDERS = {"transformer": _build_transformer, "resnet": _build_resnet}


def bench_model(name, full, steps):
    import paddle_tpu as fluid
    from paddle_tpu import profiler

    result = {"model": name}
    fetches = {}
    for mode in ("none", "all"):
        _fresh()
        fluid.default_main_program().random_seed = 9
        fluid.default_startup_program().random_seed = 9
        os.environ["PADDLE_TPU_PASSES"] = mode
        try:
            feed, loss = BUILDERS[name](full)
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(fluid.default_startup_program())
            profiler.reset_profiler()
            # trace/lower phase alone (the cost that scales with IR op
            # count — what the passes attack), via AOT .lower(): traces
            # the step through every op lowering to StableHLO, no XLA
            import jax

            import paddle_tpu.scope as scope_mod

            scope = scope_mod.global_scope()
            compiled, feeds, _ = exe._prepare_run(
                fluid.default_main_program(), feed, [loss], scope
            )
            state = exe._assemble_state(compiled, scope)
            rng_key = jax.random.key(0)
            t0 = time.perf_counter()
            compiled.jit_fn.lower(state, feeds, rng_key)
            trace_lower_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            (lv,) = exe.run(feed=feed, fetch_list=[loss])
            compile_s = time.perf_counter() - t0
            c = profiler.counters()
            vals = [float(np.asarray(lv).reshape(-1)[0])]
            t0 = time.perf_counter()
            for _ in range(steps):
                (lv,) = exe.run(feed=feed, fetch_list=[loss])
                vals.append(float(np.asarray(lv).reshape(-1)[0]))
            step_ms = (time.perf_counter() - t0) / steps * 1e3
            fetches[mode] = vals
            result[f"passes_{mode}"] = {
                "trace_lower_s": round(trace_lower_s, 3),
                "compile_s": round(compile_s, 3),
                "step_ms": round(step_ms, 2),
                "traced_ops": c.get("program_traced_ops", 0),
                "pass_manager_ms": round(
                    c.get("pass_manager_us", 0) / 1e3, 2
                ),
            }
        finally:
            os.environ.pop("PADDLE_TPU_PASSES", None)
    off, on = result["passes_none"], result["passes_all"]
    result["op_reduction"] = round(
        1.0 - on["traced_ops"] / max(off["traced_ops"], 1), 4
    )
    result["trace_lower_speedup"] = round(
        off["trace_lower_s"] / max(on["trace_lower_s"], 1e-9), 3
    )
    result["compile_speedup"] = round(
        off["compile_s"] / max(on["compile_s"], 1e-9), 3
    )
    result["fetches_match"] = bool(
        np.allclose(fetches["none"], fetches["all"], rtol=1e-5, atol=1e-6)
    )
    if not result["fetches_match"]:
        result["fetches"] = {k: v[:3] for k, v in fetches.items()}
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="transformer,resnet")
    ap.add_argument("--full", action="store_true",
                    help="bench-sized batch/seq (chip-scale)")
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args()

    summary = {"ok": True}
    for name in [m.strip() for m in args.models.split(",") if m.strip()]:
        if name not in BUILDERS:
            log(f"unknown model {name!r}; have {sorted(BUILDERS)}")
            continue
        try:
            r = bench_model(name, args.full, args.steps)
        except Exception as e:  # noqa: BLE001 — per-model isolation
            r = {"model": name, "error": f"{type(e).__name__}: {e}"}
            summary["ok"] = False
        print(json.dumps(r), flush=True)
        if r.get("fetches_match") is False:
            summary["ok"] = False
        summary[name] = {
            k: r.get(k)
            for k in ("op_reduction", "trace_lower_speedup",
                      "compile_speedup", "fetches_match")
        }
    print(json.dumps({"summary": summary}), flush=True)
    sys.exit(0 if summary["ok"] else 1)


if __name__ == "__main__":
    main()
