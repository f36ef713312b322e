"""The program's own names in a profiler trace: one `phase/op` scope per
Program op on the device side (`ops/registry.py::lower_op`), the
`pt.exe.*` spans inside `Executor.run` / `CompiledProgram._run`, and the
reader's `pt.reader.*` spans (`profiler.RecordEvent`, a TraceMe on the
trace's clock); and the compile path's always-on counters, by stage and
by owner (`jit_compile.py`). `PERF.md` lists which benchmark metric reads
which."""

import contextlib
import glob
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
from jax._src.lib import _jax
import paddle_tpu as fluid
from paddle_tpu import jit_compile, profiler
from paddle_tpu.ops import registry

EXE_SPANS = ("pt.exe.prepare", "pt.exe.state", "pt.exe.dispatch",
             "pt.exe.writeback")


def host_events(trace_dir):
    """[(name, start_ns, end_ns, thread line)] of every host event of the
    trace under `trace_dir`."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, (i, j))
            for i, plane in enumerate(ProfileData.from_file(path).planes)
            if plane.name.startswith("/host:")
            for j, line in enumerate(plane.lines) for ev in line.events]


@contextlib.contextmanager
def traced(trace_dir):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # TraceMe's only: a small, fast trace
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------- device scopes


def _tiny_bert_step():
    """The BERT-tiny pretraining step with Adam under bf16 mixed
    precision, ready to lower: (compiled step, its arguments)."""
    from paddle_tpu.contrib import mixed_precision
    from paddle_tpu.models.bert import BertConfig, build_bert_pretrain

    cfg = BertConfig.tiny()
    cfg.use_flash_attention = False
    b, s, p = 4, 16, 4
    main = fluid.default_main_program()
    main.random_seed = fluid.default_startup_program().random_seed = 7
    handles = build_bert_pretrain(cfg, b, s, mlm_only=True, max_preds=p)
    mixed_precision.decorate(fluid.optimizer.Adam(1e-3)).minimize(
        handles["loss"])
    rng = np.random.RandomState(0)
    feed = {
        "src_ids": rng.randint(0, cfg.vocab_size, (b, s)).astype("int64"),
        "sent_ids": rng.randint(0, 2, (b, s)).astype("int64"),
        "pos_ids": np.tile(np.arange(s), (b, 1)).astype("int64"),
        "input_mask": np.ones((b, s), "float32"),
        "mask_label": rng.randint(0, cfg.vocab_size, (b, p)).astype("int64"),
        "mask_weight": np.ones((b, p), "float32"),
        "mask_pos": np.stack(
            [rng.choice(s, p, False) for _ in range(b)]).astype("int64"),
    }
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    compiled, feeds, _ = exe._prepare_run(main, feed, [handles["loss"]], scope)
    state = exe._assemble_state(compiled, scope)
    return compiled, (state, feeds, jax.random.key(3))


# ops whose lowering is a literal: no instruction to carry a name
NO_INSTRUCTION = {f"{phase}/assign_value" for phase in ("fwd", "bwd", "opt")}


def _lowered_and_run(compiled, args):
    """(StableHLO text with names, optimized HLO without metadata, the
    step's fetches), traced anew so that the scopes in force now are
    the ones it sees."""
    jax.clear_caches()
    lowered = compiled.jit_fn.lower(*args)
    executable = lowered.compile()
    options = _jax.HloPrintOptions()
    options.print_metadata = False
    (module,) = executable.runtime_executable().hlo_modules()
    bare = module.to_string(options)
    state, feeds, key = args  # the state is donated: run on a copy
    fetches, _ = executable(jax.tree.map(jax.numpy.copy, state), feeds, key)
    return (lowered.as_text(debug_info=True), bare,
            [np.asarray(f) for f in fetches])


def test_every_program_op_lowers_under_its_phase_scope_and_names_change_nothing(
        monkeypatch):
    compiled, args = _tiny_bert_step()
    lowered_ops, op_scope = [], registry.op_scope
    monkeypatch.setattr(registry, "op_scope", lambda op: (
        lowered_ops.append(op_scope(op)) or lowered_ops[-1]))
    text, hlo, fetches = _lowered_and_run(compiled, args)
    names = set(re.findall(r'loc\("(jit\([^"]*)"', text))
    scopes = {m[1] for n in names
              if (m := re.match(r"jit\(step\)/((?:fwd|bwd|opt)/[^/]+)", n))}
    assert {s.split("/")[0] for s in scopes} == {"fwd", "bwd", "opt"}
    # every op the step lowered appears under the phase its role gives
    # it, but for those that trace to no instruction (a constant, an alias)
    assert scopes <= set(lowered_ops)
    assert set(lowered_ops) - scopes <= NO_INSTRUCTION, (
        sorted(set(lowered_ops) - scopes))
    # ... in Program vocabulary, a grad op named after its forward op
    assert {"fwd/matmul", "bwd/matmul_grad", "bwd/layer_norm_grad",
            "opt/fused_adam"} <= scopes
    assert not any("__auto_grad__" in s for s in scopes)

    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    text0, hlo0, fetches0 = _lowered_and_run(compiled, args)
    assert "fwd/" not in text0 and "opt/fused_adam" not in text0
    assert hlo == hlo0  # the optimized HLO, metadata stripped
    assert len(fetches) == len(fetches0) == 1
    for got, ref in zip(fetches, fetches0):
        assert got.dtype == ref.dtype and got.tobytes() == ref.tobytes()


@pytest.mark.parametrize("role, phase", [
    (fluid.framework.core_op_role.Forward, "fwd"),
    (fluid.framework.core_op_role.Loss, "fwd"),
    (fluid.framework.core_op_role.Backward, "bwd"),
    (fluid.framework.core_op_role.Backward | fluid.framework.core_op_role.Loss,
     "bwd"),
    (fluid.framework.core_op_role.Optimize, "opt"),
    (fluid.framework.core_op_role.LRSched, "opt"),
    (fluid.framework.core_op_role.Dist, "opt"),
])
def test_op_scope_phase_follows_op_role(role, phase):
    block = fluid.default_main_program().global_block()
    op = block.append_op("scale", {}, {}, {"op_role": role})
    assert registry.op_scope(op) == f"{phase}/scale"


# -------------------------------------------------------------- host spans


def _mlp(places):
    x = fluid.layers.data("x", [16])
    y = fluid.layers.data("y", [1])
    pred = fluid.layers.fc(fluid.layers.fc(x, 32, act="relu"), 1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(0.1).minimize(loss)
    prog = fluid.default_main_program()
    if places:
        prog = fluid.CompiledProgram(prog).with_data_parallel(
            loss_name=loss.name, places=places)
    return prog, loss


@pytest.mark.parametrize("places", [None, 4],
                         ids=["Executor.run", "CompiledProgram.dp4"])
def test_exe_spans_nest_in_the_callers_span_once_a_step(places, tmp_path):
    prog, loss = _mlp(places)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(8, 16).astype("float32"),
            "y": rng.randn(8, 1).astype("float32")}
    with traced(tmp_path):
        for _ in range(3):  # the first compiles: a cold cache
            with jax.profiler.TraceAnnotation("caller.step"):
                exe.run(prog, feed=feed, fetch_list=[loss])
    events = host_events(tmp_path)
    callers = sorted((s, e, line) for n, s, e, line in events
                     if n == "caller.step")
    assert len(callers) == 3
    for name in EXE_SPANS:
        inside = [[(s, e) for n, s, e, line in events
                   if n == name and line == c_line and c_s <= s and e <= c_e]
                  for c_s, c_e, c_line in callers]
        assert [len(found) for found in inside] == [1, 1, 1], (name, inside)
    order = [n for _, n in sorted(
        (s, n) for n, s, e, _ in events
        if n in EXE_SPANS and callers[1][0] <= s <= callers[1][1])]
    assert order == list(EXE_SPANS)
    compiles = [(s, e) for n, s, e, _ in events if n == "pt.exe.compile"]
    prepares = sorted((s, e) for n, s, e, _ in events
                      if n == "pt.exe.prepare")
    assert len(compiles) == 1  # nested in the first step's prepare
    assert prepares[0][0] <= compiles[0][0] and compiles[0][1] <= prepares[0][1]
    # ... and what it built traced, lowered and compiled in the first
    # step's dispatch: a recompile reads `pt.exe.compile`, `pt.exe.first_call`
    first_calls = [(s, e) for n, s, e, _ in events if n == "pt.exe.first_call"]
    dispatches = sorted((s, e) for n, s, e, _ in events
                        if n == "pt.exe.dispatch")
    assert len(first_calls) == 1 and compiles[0][1] <= first_calls[0][0]
    assert (dispatches[0][0] <= first_calls[0][0]
            and first_calls[0][1] <= dispatches[0][1])


def test_reader_stages_once_a_batch_on_the_stagers_thread(tmp_path):
    from paddle_tpu.reader import DataLoader

    x = fluid.layers.data("x", [4])
    loader = DataLoader.from_generator(feed_list=[x], capacity=4,
                                       use_double_buffer=True)
    batches = [[np.full((2, 4), i, "float32")] for i in range(5)]
    loader.set_batch_generator(lambda: iter(batches))
    with traced(tmp_path):
        with jax.profiler.TraceAnnotation("caller.epoch"):
            got = [np.asarray(b["x"] if isinstance(b, dict) else b[0])
                   for b in loader]
    assert [int(g[0, 0]) for g in got] == [0, 1, 2, 3, 4]
    events = host_events(tmp_path)
    (caller_line,) = {line for n, _, _, line in events if n == "caller.epoch"}
    stages = [line for n, _, _, line in events if n == "pt.reader.stage"]
    assert len(stages) == len(batches)
    assert set(stages) != {caller_line}  # the stager's own thread
    waits = [line for n, _, _, line in events if n == "pt.reader.wait"]
    assert len(waits) == len(batches) + 1  # the last one meets the end
    assert set(waits) == {caller_line}


def test_record_event_keeps_the_table_without_a_trace(tmp_path):
    profiler.reset_profiler()
    with profiler.RecordEvent("outside"):  # not started: no row
        pass
    profiler.start_profiler()
    for _ in range(2):
        with profiler.RecordEvent("inside"):
            pass
    rows = profiler.stop_profiler(profile_path=str(tmp_path / "table.txt"))
    assert [(r[0], r[1]) for r in rows] == [("inside", 2)]


# ------------------------------------------- the compile path, by stage

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE, LOWER, BACKEND = jit_compile._STAGE_COUNTERS  # JAX's three events
STAGES = tuple(jit_compile._STAGE_COUNTERS.values())
COMPILE_METRICS = [
    "step_trace_s", "step_lower_s", "step_backend_s", "step_cache_read_s",
    "step_first_call_s", "step_kernel_ops_trace_s",
    "setup_forward_compile_s", "setup_other_compile_s",
    "setup_other_compiles", "setup_uncached_compiles"]


def _reads(metric):
    """The counters the benchmark's metric `metric` adds up."""
    from benchmark.harness import spec

    return spec.load("layer_metrics", metric)["args"]["counters"]


def _filed(prefix=("compile_", "program_first_call_us", "trace_op_us"),
           since=None):
    """The compile path's counters (those with an owner), less `since`."""
    since = since or {}
    return {k: v - since.get(k, 0) for k, v in profiler.counters().items()
            if k.startswith(prefix) and v != since.get(k, 0)}


def _mlp_feed():
    rng = np.random.RandomState(0)
    return {"x": rng.randn(8, 16).astype("float32"),
            "y": rng.randn(8, 1).astype("float32")}


def test_a_train_step_files_each_stage_of_its_compile_under_train():
    prog, loss = _mlp(None)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    before = profiler.counters()
    exe.run(prog, feed=_mlp_feed(), fetch_list=[loss])
    filed = _filed(since=before)
    stages = [filed[f"{stage}.train"] for stage in STAGES]
    assert all(us > 0 for us in stages)
    assert sum(stages) <= filed["program_first_call_us.train"]
    assert filed["compile_requests.train"] == 1
    # what the benchmark's five `step_*` metrics read is what was filed
    for metric in COMPILE_METRICS[:5]:
        (counter,) = _reads(metric)
        assert counter.endswith(".train")
        assert (counter in filed) == (metric != "step_cache_read_s"), metric
    # nothing of another owner's: the feeds went to the device as they were
    assert not any(".forward" in k or ".other" in k for k in filed), filed
    after = profiler.counters()
    exe.run(prog, feed=_mlp_feed(), fetch_list=[loss])
    assert profiler.counters() == after  # the second run compiles nothing


def test_a_forward_only_program_files_under_forward():
    x = fluid.layers.data("x", [16])
    out = fluid.layers.fc(x, 4)
    exe = fluid.Executor(fluid.CPUPlace())
    before = profiler.counters()
    exe.run(fluid.default_startup_program())
    startup = _filed(since=before)
    assert startup["compile_requests.forward"] == 1
    assert startup["program_first_call_us.forward"] > 0
    before = profiler.counters()
    exe.run(fluid.default_main_program().clone(for_test=True),
            feed={"x": np.ones((2, 16), "float32")}, fetch_list=[out])
    clone = _filed(since=before)
    assert clone["compile_requests.forward"] == 1
    assert sum(clone[f"{s}.forward"] for s in STAGES) <= clone[
        "program_first_call_us.forward"]
    assert set(_reads("setup_forward_compile_s")) == {
        f"{s}.forward" for s in STAGES} <= set(clone)
    assert not any(".train" in k for k in {**startup, **clone})
    assert {k for k in clone if k.startswith("trace_op_us.")} == {
        "trace_op_us.forward.fwd/mul", "trace_op_us.forward.fwd/elementwise_add"}


def test_a_bare_jit_outside_any_executor_files_under_other():
    before = profiler.counters()
    jax.jit(lambda a: a * 2 + 1)(np.arange(3, dtype="float32"))
    filed = _filed(since=before)
    assert set(filed) == {f"{s}.other" for s in STAGES} | {
        "compile_requests.other"}
    assert filed["compile_requests.other"] == 1
    assert _reads("setup_other_compiles") == ["compile_requests.other"]
    assert set(_reads("setup_other_compile_s")) == {
        f"{s}.other" for s in STAGES}
    assert jit_compile.current_owner() == "other"


def test_run_repeated_files_its_scan_under_the_steps_owner():
    prog, loss = _mlp(None)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    before = profiler.counters()
    exe.run_repeated(prog, feed=_mlp_feed(), fetch_list=[loss], steps=3)
    filed = _filed(since=before)
    assert filed["compile_requests.train"] == 1
    assert sum(filed[f"{s}.train"] for s in STAGES) <= filed[
        "program_first_call_us.train"]
    assert "compile_requests.other" not in filed
    after = profiler.counters()
    exe.run_repeated(prog, feed=_mlp_feed(), fetch_list=[loss], steps=3)
    assert profiler.counters() == after


def test_a_stage_is_filed_once_whatever_is_traced_inside_it():
    """JAX reports a trace event for every jitted `jnp` function traced
    under the step's and for the functions its lowering rules trace; the
    step's trace stage is the outermost event alone."""
    compiled, args = _tiny_bert_step()
    traces = []

    def listen(event, duration_secs, **kwargs):
        if event == TRACE:
            traces.append(duration_secs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    before = profiler.counters()
    try:
        compiled.fn(*args)  # the wrapper's first call
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    filed = _filed(since=before)
    assert len(traces) > 1 and sum(traces) > max(traces)
    assert filed["compile_trace_us.train"] == int(max(traces) * 1e6)
    assert filed["compile_trace_us.train"] < filed[
        "program_first_call_us.train"]
    assert sum(filed[f"{s}.train"] for s in STAGES) <= filed[
        "program_first_call_us.train"]

    # and by hand: a lowering and a trace inside an open trace add nothing
    record, close = (jax.monitoring.record_scalar,
                     jax.monitoring.record_event_duration_secs)
    before = profiler.counters()
    record(TRACE, 0.0, fun_name="outer")
    record(TRACE, 0.0, fun_name="inner")
    close(TRACE, 5.0, fun_name="inner")
    record(LOWER, 0.0, fun_name="inner")
    close(LOWER, 7.0, fun_name="inner")
    close(TRACE, 1.0, fun_name="outer")
    assert _filed(since=before) == {"compile_trace_us.other": 1_000_000}


@pytest.mark.parametrize("owner", ["train", "forward", "other"])
def test_the_cache_counters_follow_jaxs_events_under_each_owner(owner):
    """The CPU has no usable persistent cache, so JAX's events by hand,
    in the order `compile_or_get_cached` emits them."""
    record, event, close = (jax.monitoring.record_scalar,
                            jax.monitoring.record_event,
                            jax.monitoring.record_event_duration_secs)
    lookup, hit, write, read = (jit_compile._CACHE_LOOKUP,
                                jit_compile._CACHE_HIT,
                                jit_compile._CACHE_WRITE,
                                jit_compile._CACHE_READ)
    before = profiler.counters()
    with (contextlib.nullcontext() if owner == "other"
          else jit_compile.compile_owner(owner)):
        record(BACKEND, 0.0, fun_name="hit")
        event(lookup)
        event(hit)
        close(read, 0.25)
        close(BACKEND, 0.5, fun_name="hit")
        record(BACKEND, 0.0, fun_name="compiled and written")
        event(lookup)
        event(write)
        close(BACKEND, 2.0, fun_name="compiled and written")
        record(BACKEND, 0.0, fun_name="under the thresholds: never kept")
        event(lookup)
        close(BACKEND, 0.125, fun_name="under the thresholds: never kept")
        record(BACKEND, 0.0, fun_name="the cache is off")
        close(BACKEND, 0.0625, fun_name="the cache is off")
    assert jit_compile.current_owner() == "other"
    assert _filed(since=before) == {
        f"compile_backend_us.{owner}": 2_687_500,
        f"compile_cache_read_us.{owner}": 250_000,
        f"compile_requests.{owner}": 4,
        f"compile_cache_hits.{owner}": 1,
        f"compile_cache_compiled.{owner}": 2,
        f"compile_cache_writes.{owner}": 1,
    }
    assert f"compile_cache_compiled.{owner}" in _reads(
        "setup_uncached_compiles")
    assert _reads("step_cache_read_s") == ["compile_cache_read_us.train"]


def test_each_ops_lowering_time_is_filed_under_its_scope(monkeypatch):
    """`trace_op_us.<owner>.<scope>`: the names the device trace carries,
    one key a scope the step lowered, and together inside the trace
    stage, which also holds the jit's own work on 160 state arrays."""
    compiled, args = _tiny_bert_step()
    lowered_ops, op_scope = set(), registry.op_scope
    monkeypatch.setattr(registry, "op_scope", lambda op: (
        lowered_ops.add(op_scope(op)) or op_scope(op)))
    before = profiler.counters()
    compiled.fn(*args)
    filed = _filed("trace_op_us.", since=before)
    assert set(filed) == {f"trace_op_us.train.{s}" for s in lowered_ops}
    trace = _filed("compile_trace_us.", since=before)["compile_trace_us.train"]
    assert 0.5 * trace < sum(filed.values()) <= trace
    # the ops whose lowering can reach a kernel, forward and gradient
    # (this step's attention is written out in matmuls and a softmax)
    kernel_ops = set(_reads("step_kernel_ops_trace_s"))
    assert {"trace_op_us.train.fwd/layer_norm",
            "trace_op_us.train.bwd/layer_norm_grad",
            "trace_op_us.train.fwd/lookup_table",
            "trace_op_us.train.bwd/lookup_table_grad",
            } == kernel_ops & set(filed)


def test_an_op_lowered_inside_another_is_not_counted_twice():
    """A `while` body's ops are lowered through `lower_op` inside the
    `while` op's own lowering: each files its own time, and the sum stays
    inside the trace stage."""
    i = fluid.layers.fill_constant([1], "int64", 0)
    limit = fluid.layers.fill_constant([1], "int64", 5)
    total = fluid.layers.fill_constant([1], "float32", 0.0)
    cond = fluid.layers.less_than(i, limit)
    loop = fluid.layers.While(cond)
    with loop.block():
        fluid.layers.assign(fluid.layers.scale(total, 1.0, bias=2.0), total)
        fluid.layers.increment(i, 1.0, in_place=True)
        fluid.layers.less_than(i, limit, cond=cond)
    exe = fluid.Executor(fluid.CPUPlace())
    before = profiler.counters()
    (got,) = exe.run(fetch_list=[total])
    assert float(got[0]) == 10.0
    filed = _filed(since=before)
    ops = {k: v for k, v in filed.items() if k.startswith("trace_op_us.")}
    assert {"trace_op_us.forward.fwd/while", "trace_op_us.forward.fwd/scale",
            "trace_op_us.forward.fwd/increment"} <= set(ops)
    assert sum(ops.values()) <= filed["compile_trace_us.forward"]


@pytest.mark.parametrize("metric", COMPILE_METRICS)
def test_a_compile_metric_reads_counters_the_program_files(metric):
    """PR 49's ten per-layer metrics: a file each, the same fields in
    `BENCHMARK.json`, and counters of the families `jit_compile`,
    `_first_call` and `lower_op` file (the tests above see each bumped)."""
    from benchmark.harness import spec

    m = spec.load("layer_metrics", metric)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        (declared,) = [x for x in json.load(f)["per_layer"]
                       if x["name"] == metric]
    count = metric.endswith("compiles")
    assert declared == {
        "name": metric, "unit": "count" if count else "s", "better": "lower",
        "source": "program_counter" if count else "program_span",
        "layer": "L0 compile path", "moves": "setup_s"}
    assert {k: m[k] for k in declared} == declared
    assert m["kind"] == "counter_delta" and "where" not in m
    assert m["args"].get("scale", 1) == (1 if count else 1e-6)
    assert m["args"]["phase"] == "setup"
    owners = ("train", "forward", "other")
    families = STAGES + ("compile_cache_read_us", "compile_requests",
                         "compile_cache_compiled", "program_first_call_us")
    filed = {f"{family}.{owner}" for family in families for owner in owners}
    filed |= {f"trace_op_us.train.{phase}/{op}{grad}"
              for op in registry.all_op_types()
              for phase, grad in (("fwd", ""), ("bwd", "_grad"))}
    counters = m["args"]["counters"]
    assert counters and set(counters) <= filed, set(counters) - filed
    assert len(set(counters)) == len(counters)


def test_a_rehearsal_line_carries_the_two_compile_counts():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "bert_base_s128", "--seed", "5", "--seconds", "1",
         "--trace", "1", "--rehearse"],
        cwd=REPO, env=dict(os.environ, PYTHONPATH=REPO), capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    metrics = json.loads(proc.stdout.strip().splitlines()[-1])["metrics"]
    # the reference's jit and the placeholder of a state nothing has set
    assert metrics["setup_other_compiles"]["value"] >= 1
    # the rehearsal turns the persistent cache off: nothing is looked up
    assert metrics["setup_uncached_compiles"] == {"value": 0, "unit": "count"}
    # and with it the store of compiled steps: nothing is found or written
    assert metrics["setup_step_store_hits"] == {"value": 0, "unit": "count"}
    assert metrics["setup_step_store_errors"] == {"value": 0, "unit": "count"}
    assert not any(name.endswith("_s") for name in metrics)  # no time


# ---------------------------------------- what reads the declared costs

FLASH_CALL = ("%flash_fwd.12 = (bf16[32,8192,128]{2,1,0}, f32[32,1,8192]) "
              "custom-call(%seed, %q, %k, %v)")


def _bench():
    from benchmark.harness import spec

    with open(os.path.join(os.path.dirname(spec.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        return json.load(f)


def _selected(where, bench):
    """The cells of `BENCHMARK.json` that a metric's `where` selects, read
    here against each cell's own files: every key names a value of the
    cell, one of the listed ones, or a list (a configuration's
    `mechanisms`) that holds one of them."""
    from benchmark.harness import spec

    def has(cell, key, allowed):
        for part in key.split("."):
            cell = cell.get(part) if isinstance(cell, dict) else None
        held = cell if isinstance(cell, list) else [cell]
        return bool(set(held) & set(allowed))

    return [w["name"] for w in bench["workloads"]
            if all(has(spec.cell(w["name"]), key, allowed)
                   for key, allowed in where.items())]


@pytest.mark.parametrize("metric,layer,key,bound,selects,hits,misses", [
    ("fc_mxu_roofline_pct", "Op lowerings", "scope", "bf16_flops",
     ("config.mechanisms", "fc"),
     ["fwd/mul/dot_general", "bwd/matmul_grad/transpose(jvp())/dot_general"],
     ["fwd/elementwise_mul/mul", "opt/fused_adam/mul", ""]),
    ("conv_roofline_pct", "Op lowerings", "scope", "bf16_flops",
     ("config.adapter", "resnet50_v1_5"),
     ["fwd/conv2d/conv_general_dilated", "bwd/conv2d_grad/transpose(jvp())"],
     ["fwd/batch_norm/mul"]),
    ("nemotron_ssd_roofline_pct", "Op lowerings", "scope", "bf16_flops",
     ("config.adapter", "nemotron_h"),
     ["fwd/ssd_scan/dot_general",
      "bwd/ssd_scan_grad/transpose(jvp())/dot_general"],
     ["fwd/selective_scan/mul", "fwd/short_conv1d/ssd_scan/add"]),
    ("attn_short_roofline_pct", "Pallas kernels", "name", "bf16_flops",
     ("config.adapter", "bert"),
     ["%mha_short_bwd.7 = (bf16[256,128,768]) custom-call(%a)",
      "mha_short_fwd = bf16[256,128,768] custom-call(%a)"],
     ["%copy.1 = bf16[256,128,768] copy(%mha_short_fwd.3)"]),
    ("flash_kernels_roofline_pct", "Pallas kernels", "name", "bf16_flops",
     ("config.mechanisms", "flash"),
     [FLASH_CALL, FLASH_CALL.replace("fwd", "bwd_dkv"),
      FLASH_CALL.replace("fwd", "bwd_dkv_dq")],
     ["%fusion.9 = bf16[8,8] fusion(%flash_fwd.12), kind=kLoop",
      FLASH_CALL.replace("fwd", "fwd_too")]),
    ("delta_rule_roofline_pct", "Pallas kernels", "name", "bf16_flops",
     ("config.mechanisms", "delta_rule"),
     ["%kda_bwd.2 = (f32[1,4096,4096])", "%gdn_fwd.5 = (bf16[1,4096,4096])"],
     ["%fusion.1 = f32[8] fusion(%kda_fwd.2)"]),
    ("qk_prep_hbm_roofline_pct", "Pallas kernels", "name", "hbm_bytes_per_s",
     ("config.mechanisms", "qk_prep"),
     ["%qk_prep_fwd.13 = (bf16[1,32,8192,128])"], [FLASH_CALL]),
])
def test_a_roofline_metric_names_what_it_reads_and_the_cells_that_have_it(
        metric, layer, key, bound, selects, hits, misses):
    """PR 35's per-layer metrics: declared in `BENCHMARK.json` for the
    cells whose configuration has what they read (the mechanism, or the
    adapter where one model alone has it: the file's own `where`, read
    here against the configurations, and no list of cells), read by
    `trace_roofline` from the events' own `flops` or `bytes_accessed`, by
    an expression that finds the kernel (or the Program op's scope) and
    not what reads its output."""
    from benchmark.harness import spec

    bench = _bench()
    m = spec.load("layer_metrics", metric)
    assert m["kind"] == "trace_roofline" and m["args"]["bound"] == bound
    where, word = selects
    assert set(m["where"]) == {where} and word in m["where"][where]
    cells = _selected(m["where"], bench)
    assert cells, metric
    (declared,) = [x for x in bench["per_layer"] if x["name"] == metric]
    assert sorted(declared.pop("workloads")) == sorted(cells)
    assert declared == {
        "name": metric, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": layer,
        "moves": "train_examples_per_s"}
    assert [w["name"] for w in bench["workloads"]
            if metric in {x["name"] for x in spec.layer_metrics(
                spec.cell(w["name"]))}] == cells
    assert all(re.search(m["args"][key], text) for text in hits)
    assert not any(re.search(m["args"][key], text) for text in misses)


def test_every_roofline_metrics_where_selects_a_cell():
    """Whatever files `benchmark/layer_metrics/` holds: a `trace_roofline`
    metric that no cell of `BENCHMARK.json` can read is a file nothing
    measures, and one that `per_layer` lists for other cells than its
    `where` selects is read where it is not declared."""
    from benchmark.harness import spec

    bench = _bench()
    declared = {m["name"]: m for m in bench["per_layer"]}
    files = [spec.load("layer_metrics", n) for n in spec.names("layer_metrics")]
    rooflines = [m for m in files if m["kind"] == "trace_roofline"]
    assert rooflines
    for m in rooflines:
        cells = _selected(m.get("where", {}), bench)
        assert cells, m["name"]
        assert sorted(declared[m["name"]]["workloads"]) == sorted(cells)


# ------------------------------------------- the stages of one Program op

def _stage_reading(tables):
    return {"trace": object(), "scope_ns": tables, "notes": [],
            "traced": {"steps": 2},
            "counters": {"window": {"moe_rows_routed": 960,
                                    "moe_rows_live": 240,
                                    "moe_blocks_run": 10}}}


STAGED = {  # (XLA group, scope): self ns; 1,000 in all
    ("fusion/kCustom", "fwd/moe_experts/moe.sort/sort"): 100.0,
    ("fusion/kLoop",
     "bwd/moe_experts_grad/transpose(jvp(moe.gather))/scatter-add"): 50.0,
    ("fusion/kCustom",
     "fwd/moe_experts/moe.combine/while/body/moe.gather/gather"): 25.0,
    ("fusion/kCustom", "fwd/moe_experts/moe.route/top_k"): 200.0,
    ("convert", "fwd/moe_experts/convert_element_type"): 10.0,
    ("fusion/kOutput", "fwd/matmul/dot_general"): 615.0,
}


@pytest.mark.parametrize("metric", ["moe_dispatch_device_pct",
                                    "moe_held_load_pct"])
def test_the_expert_layers_two_metrics_on_a_hand_made_reading(metric):
    """`trace_stage_share` on a `scope_ns` table with known answers (the
    innermost stage counts, the share is over all busy time, mean over
    the devices, None without a stage), and `moe_held_load_pct` on the
    window's counts; both declared for the nine expert cells."""
    from benchmark.harness import spec
    from benchmark.harness.sources import counter_ratio, trace_stage_share

    with open(os.path.join(os.path.dirname(spec.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    m = spec.load("layer_metrics", metric)
    assert declared[metric]["workloads"] == declared["moe_device_pct"][
        "workloads"] and len(declared[metric]["workloads"]) == 9
    assert m["where"] == {"config.mechanisms": ["experts"]}
    assert (m["unit"], m["layer"], m["source"], m["better"]) == tuple(
        declared[metric][k] for k in ("unit", "layer", "source", "better"))

    other = {key: 2 * ns for key, ns in STAGED.items()}
    other["fusion/kCustom", "fwd/moe_experts/moe.sort/sort"] = 500.0
    r = _stage_reading([STAGED, other])  # 17.5% and 650 of 2,300
    if metric == "moe_held_load_pct":
        assert counter_ratio.read(m["args"], r) == 25.0
        r["counters"]["window"] = {}
        assert counter_ratio.read(m["args"], r) is None  # the parent's
        return
    assert m["kind"] == "trace_stage_share"
    want = 100 * (0.175 + 650 / 2300) / 2
    assert trace_stage_share.read(m["args"], r) == pytest.approx(want)
    notes = "\n".join(r["notes"])
    for line in ("moe.sort: fwd 0.000 bwd 0.000", "moe.route:", "moe.gather:",
                 "(no stage):", "where XLA booked it",
                 "moe_rows_routed 960, moe_rows_live 240, moe_blocks_run 10"):
        assert line in notes, line
    assert "moe.combine:" not in notes  # the loop's body was the gather's
    bare_op = {k: v for k, v in STAGED.items() if k[0] != "convert"}
    staged = _stage_reading([bare_op])
    trace_stage_share.read(m["args"], staged)
    assert ("  (no stage): fwd 0.000 bwd 0.000 (0.00% of busy time); nothing"
            in staged["notes"])
    # once a run, however many metrics read the scope
    noted = len(r["notes"])
    assert trace_stage_share.read(m["args"], r) == pytest.approx(want)
    assert len(r["notes"]) == noted
    # ms a step, forward and backward, and the stage's largest XLA groups
    big = _stage_reading([{k: v * 1e6 for k, v in STAGED.items()}])
    trace_stage_share.read(m["args"], big)
    assert ("  moe.gather: fwd 12.500 bwd 25.000 (7.50% of busy time); "
            "fusion/kLoop (scatter-add) 25.000, fusion/kCustom (gather) "
            "12.500") in big["notes"]
    # a program from before the stages, a trace without scopes, no trace
    bare = {k: v for k, v in STAGED.items() if "moe." not in k[1]}
    assert trace_stage_share.read(m["args"], _stage_reading([bare])) is None
    assert trace_stage_share.read(m["args"], _stage_reading(None)) is None
    assert trace_stage_share.read(m["args"], {"trace": None}) is None
