"""Runner `train_loop`: a training job fed the way users feed one.

    DataLoader.from_generator(...).set_batch_generator(gen)
      -> one Executor.run(feed=batch, fetch_list=[loss],
                          return_numpy=False) per step
      -> the loss read on the host every tenth step

Batches cycle through a pool of distinct batches made from the seed
during set-up, so the host cost inside the window is the program's own
conversion and staging, not NumPy's generator. Nothing here calls
`run_repeated`, and no `PADDLE_TPU_*` variable is set: a cell runs the
program's defaults.

Set-up, in order: build the Program and `minimize`; the startup program
(weights made on the device from the seed); the reference check on the
`for_test` clone; the first train step (trace + compile, or a read from
the persistent cache); warm-up steps. Then the measured window: dispatch
until `seconds` have passed, drain, and divide the rows completed by the
time really elapsed.
"""

from __future__ import annotations

import contextlib
import itertools
import time

import numpy as np

from benchmark.harness import stats

# the end-to-end metrics this runner takes itself, and their units
END_TO_END = {"train_examples_per_s": "examples/s/chip", "setup_s": "s"}
WARMUP_STEPS = 3  # after the first; the loader's double buffer fills too
LOSS_READ_EVERY = 10  # steps: the reference scripts' logging cadence
REFERENCE_ROWS = 32  # rows of the check batch per reference call


class Spans:
    """The benchmark's own spans: each is timed on the host clock and
    written into the profiler's trace, when one is being taken, under the
    same name."""

    def __init__(self):
        import jax

        self.ms: dict[str, list[float]] = {}
        self._annotate = jax.profiler.TraceAnnotation

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        with self._annotate(name):
            yield
        self.ms.setdefault(name, []).append((time.perf_counter() - t0) * 1e3)


def _optimizer(fluid, config: dict):
    spec = dict(config["optimizer"])
    opt = getattr(fluid.optimizer, spec.pop("type"))(**spec)
    if config["precision"] == "bf16_amp":
        from paddle_tpu.contrib import mixed_precision

        opt = mixed_precision.decorate(opt)
    elif config["precision"] != "float32":
        raise ValueError(f"unknown precision {config['precision']!r}")
    return opt


def check_reference(got_loss, got_logits, ref_loss, ref_logits, tol) -> dict:
    """Program against reference: the loss of the whole batch, and the
    logits at the scored positions relative to the reference's own
    root-mean-square."""
    got = np.asarray(got_logits, np.float32).reshape(ref_logits.shape)
    ref = np.asarray(ref_logits, np.float32)
    rel = float(np.sqrt(np.mean((got - ref) ** 2)) / np.sqrt(np.mean(ref ** 2)))
    worst = float(np.max(np.abs(got - ref)))
    loss_err = abs(float(np.asarray(got_loss).reshape(-1)[0]) - float(ref_loss))
    ok = bool(np.isfinite(got).all() and rel <= tol["logits_rel_rms"]
              and loss_err <= tol["loss_abs"])
    return {"ok": ok, "logits_rel_rms": rel, "logits_max_abs": worst,
            "loss_abs": loss_err, "ref_loss": float(ref_loss)}


def reference_outputs(adapter, params, batch, config, rows_scored, **kw):
    """The adapter's plain forward over the whole check batch, some rows
    at a time so that it leaves no mark on the device's peak memory.
    float32 throughout: on a TPU a float32 product runs in lower
    precision unless the precision is set to `highest`."""
    import jax

    fn = jax.jit(lambda p, b: adapter.reference(p, b, config, **kw))
    n = len(next(iter(batch.values())))
    nll = weight = 0.0
    logits = None
    with jax.default_matmul_precision("highest"):
        for lo in range(0, n, REFERENCE_ROWS):
            part = {k: v[lo:lo + REFERENCE_ROWS] for k, v in batch.items()}
            nll_i, w_i, logits_i = fn(params, part)
            nll += float(nll_i)
            weight += float(w_i)
            if logits is None:
                logits = np.asarray(logits_i[:rows_scored])
    return nll / (weight + 1e-6), logits


def build_programs(fluid, adapter, config, traffic, seed):
    """The train program as a user writes it, in the current default
    programs: the adapter's model, the configuration's optimizer and
    precision, and a `for_test` clone (dropout off) for the reference
    check. Weights come from the seed through the startup program."""
    main, startup = fluid.default_main_program(), fluid.default_startup_program()
    main.random_seed = startup.random_seed = 1000 + seed
    built = adapter.build(config, traffic)
    _optimizer(fluid, config).minimize(main.global_block().var(built["loss"]))
    return main, startup, built, main.clone(for_test=True)


def reference_check(fluid, exe, adapter, eval_prog, built, batch, config,
                    traffic, run_as=None, **kw) -> dict:
    """Evaluate the `for_test` clone (through `run_as`, its compiled form
    on a mesh) on one seeded batch with the state as it is in the scope,
    and hold its loss and its scored logits against the adapter's plain
    reference on the same state: every persistable the clone reads, which
    is the parameters and, for batch normalisation, the moving
    statistics."""
    got_loss, got_logits = exe.run(run_as or eval_prog, feed=batch,
                                   fetch_list=built["check"])
    scope, block = fluid.global_scope(), eval_prog.global_block()
    read = {n for op in block.ops for names in op.inputs.values() for n in names}
    params = {n: scope.get(n) for n in read
              if block.has_var(n) and block.var(n).persistable and scope.has(n)}
    scored = min(traffic["batch"], adapter.SCORED_SEQUENCES)
    ref_loss, ref_logits = reference_outputs(
        adapter, params, batch, config, scored, **kw)
    return check_reference(got_loss, got_logits, ref_loss, ref_logits,
                           adapter.TOLERANCE)


def run(ctx) -> dict:
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import profiler
    from paddle_tpu.reader import DataLoader

    from benchmark.harness import spec

    cell, log = ctx.cell, ctx.log
    config, traffic = cell["config"], cell["traffic"]
    adapter = spec.plugin("models", config["adapter"])
    spans = Spans()
    counters0 = profiler.counters()

    # ---- set-up: program, weights, reference check --------------------
    log(f"imports took {time.perf_counter() - ctx.t_start:.2f} s")
    with spans("bench.build_program"):
        main, startup, built, eval_prog = build_programs(
            fluid, adapter, config, traffic, ctx.seed)
    exe = fluid.Executor(fluid.CPUPlace() if ctx.rehearse else fluid.TPUPlace())
    train_prog, eval_as = main, None
    dp = (traffic.get("mesh") or {}).get("data_parallel")
    if dp:
        train_prog = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=built["loss"], places=dp)
        eval_as = fluid.CompiledProgram(eval_prog).with_data_parallel(places=dp)
    with spans("bench.startup_program"):
        exe.run(startup)

    with spans("bench.make_batches"):
        rng = np.random.RandomState(ctx.seed)
        check_batch = adapter.make_batch(rng, config, traffic)
        pool = [[b[n] for n in built["feeds"]] for b in
                (adapter.make_batch(rng, config, traffic)
                 for _ in range(traffic["pool_batches"]))]
    with spans("bench.reference_check"):
        check = reference_check(fluid, exe, adapter, eval_prog, built,
                                check_batch, config, traffic, run_as=eval_as)
    log(f"reference check: {check} (tolerance {adapter.TOLERANCE})")

    # ---- set-up: the user path, first step, warm-up -------------------
    feed_vars = [main.global_block().var(n) for n in built["feeds"]]
    loader = DataLoader.from_generator(feed_list=feed_vars, capacity=8,
                                       use_double_buffer=True)
    loader.set_batch_generator(lambda: itertools.cycle(pool))
    batches = iter(loader)
    fetch = [built["loss"]]

    def step():
        with spans("bench.next_batch"):
            batch = next(batches)
        with spans("bench.exe_run"):
            (loss,) = exe.run(train_prog, feed=batch, fetch_list=fetch,
                              return_numpy=False)
        return loss

    try:
        with spans("bench.first_step"):
            jax.block_until_ready(step())
        for _ in range(WARMUP_STEPS):
            last = step()
        jax.block_until_ready(last)
        for name in ("bench.next_batch", "bench.exe_run"):
            spans.ms[name].clear()  # the window's samples only
        counters1 = profiler.counters()
        compiles = _CompileEvents()

        # ---- the measured window --------------------------------------
        rows = traffic["batch"]
        losses, failed = [], 0
        blocks = []  # (steps, seconds) between two reads of the loss
        traced = None  # what `_traced_blocks` returns, once taken
        trace_at = ctx.seconds * 0.4 if ctx.trace else None
        t0 = t_block = time.perf_counter()
        excluded = 0.0
        n_block = 0
        while time.perf_counter() - t0 < ctx.seconds:
            try:
                losses.append(step())
            except Exception as exc:  # noqa: BLE001 — counted, the run goes on
                failed += 1
                log(f"step {len(losses) + failed} raised "
                    f"{type(exc).__name__}: {exc}")
                continue
            n_block += 1
            if n_block == LOSS_READ_EVERY:
                with spans("bench.read_loss"):
                    float(np.asarray(losses[-1]).reshape(-1)[0])
                now = time.perf_counter()
                blocks.append((n_block, now - t_block))
                t_block, n_block = now, 0
                if trace_at is not None and now - t0 >= trace_at:
                    # the device is idle and the queue empty: trace the
                    # next blocks whole, and leave them out of the rate
                    traced = _traced_blocks(ctx, step, spans, losses)
                    trace_at = None
                    excluded = time.perf_counter() - now
                    t_block = time.perf_counter()
        with spans("bench.drain"):
            jax.block_until_ready(losses[-1])
            jax.block_until_ready(fluid.global_scope().get(
                main.global_block().all_parameters()[0].name))
        elapsed = time.perf_counter() - t0
        counters2 = profiler.counters()
    finally:
        batches.close()

    # ---- what the window showed ---------------------------------------
    values = np.array([float(np.asarray(x).reshape(-1)[0]) for x in losses])
    nonfinite = int((~np.isfinite(values)).sum())
    k = min(10, len(values) // 2)
    fall = (float(np.median(values[:k]) - np.median(values[-k:]))
            if k else float("nan"))
    done = len(losses) - (traced["steps"] if traced else 0)
    rate = done * rows / (elapsed - excluded) / cell["chips"]
    in_window = (counters2.get("program_compile_count", 0)
                 - counters1.get("program_compile_count", 0))
    jax_compiles = compiles.stop()
    margin = config["loss_fall_margin"]
    correct = bool(check["ok"] and nonfinite == 0 and failed == 0
                   and in_window == 0 and not jax_compiles and fall >= margin)

    tokens = adapter.tokens_per_example(config, traffic)
    log(f"set-up {t0 - ctx.t_start:.2f} s, of which: "
        + ", ".join(f"{n[6:]} {sum(spans.ms[n]) / 1e3:.2f}" for n in (
            "bench.build_program", "bench.startup_program",
            "bench.make_batches", "bench.reference_check",
            "bench.first_step")))
    log(f"window: {len(losses)} steps of {rows} rows in {elapsed:.3f} s"
        + (f" ({traced['steps']} traced steps and {excluded:.3f} s around "
           "them left out of the rate)" if traced else "")
        + f"; {rate:.2f} examples/s/chip = {rate * tokens:,.0f} tokens/s/chip")
    log("step time per block of steps between loss reads (ms/step): "
        + stats.summary([1e3 * s / n for n, s in blocks]))
    for name, what in (("bench.next_batch", ""), ("bench.exe_run", ""),
                       ("bench.read_loss", ": the wait for the device, which "
                        "is how far the host's dispatch ran ahead")):
        log(f"host span {name} (ms){what}: "
            + stats.summary(spans.ms.get(name, [])))
    log(f"losses: first {values[:3].round(4).tolist()} last "
        f"{values[-3:].round(4).tolist()}; median of first {k} - median of "
        f"last {k} = {fall:.4f} (margin {margin}); {nonfinite} not finite")
    log(f"compiles in window: program_compile_count +{in_window}; "
        f"JAX compile events {jax_compiles}")

    return {
        "correct": correct, "attempted": len(losses) + failed,
        "failed": failed + nonfinite,
        "end_to_end": {"train_examples_per_s": rate,
                       "setup_s": t0 - ctx.t_start},
        # what the per-layer metric sources read
        "reading": {
            "cell": cell, "adapter": adapter, "spans": spans.ms,
            "counters": {"setup": _delta(counters0, counters1),
                         "window": {**_delta(counters1, counters2),
                                    "jax_compile_events": len(jax_compiles)}},
            "examples_per_s": rate, "traced": traced,
        },
    }


def _delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()}


class _CompileEvents:
    """Every compile JAX itself reports from now on, whoever asked for
    it: the program's own counter sees only `Executor._compile`."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.seen: list[str] = []
        self.on = True
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **kwargs):
        if self.on and event == self.EVENT:
            self.seen.append(f"{kwargs.get('fun_name', '?')}:{duration:.3f}s")

    def stop(self) -> list[str]:
        self.on = False
        return self.seen


def _traced_blocks(ctx, step, spans, losses) -> dict:
    """Two blocks of steps under `jax.profiler`, from an idle device to
    an idle device."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the benchmark's spans are TraceMe's
    options.host_tracer_level = 2
    steps = 2 * LOSS_READ_EVERY
    jax.profiler.start_trace(ctx.trace_dir, profiler_options=options)
    try:
        for i in range(steps):
            losses.append(step())
            if (i + 1) % LOSS_READ_EVERY == 0:
                with spans("bench.read_loss"):
                    float(np.asarray(losses[-1]).reshape(-1)[0])
    finally:
        jax.profiler.stop_trace()
    return {"steps": steps, "dir": ctx.trace_dir}
