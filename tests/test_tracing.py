"""The program's own names in a profiler trace: one `phase/op` scope per
Program op on the device side (`ops/registry.py::lower_op`), the
`pt.exe.*` spans inside `Executor.run` / `CompiledProgram._run`, and the
reader's `pt.reader.*` spans (`profiler.RecordEvent`, a TraceMe on the
trace's clock). `PERF.md` lists which benchmark metric reads which."""

import contextlib
import glob
import os
import re

import numpy as np
import pytest

import jax
from jax._src.lib import _jax
import paddle_tpu as fluid
from paddle_tpu import profiler
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


# ---------------------------------------- what reads the declared costs

EXPERT_CELLS = ["kimi_linear_ep32_s4096", "trinity_mini_ep16_s8192"]
FC_CELLS = ["bert_base_s128", "bert_base_s128_dp4", "bert_base_s512",
            "kimi_linear_ep32_s4096", "transformer_base_s64",
            "trinity_mini_ep16_s8192"]
FLASH_CALL = ("%flash_fwd.12 = (bf16[32,8192,128]{2,1,0}, f32[32,1,8192]) "
              "custom-call(%seed, %q, %k, %v)")


@pytest.mark.parametrize("metric,layer,key,bound,cells,hits,misses", [
    ("fc_roofline_pct", "Op lowerings", "scope", "bf16_flops", FC_CELLS,
     ["fwd/mul/dot_general", "bwd/matmul_grad/transpose(jvp())/dot_general"],
     ["fwd/elementwise_mul/mul", "opt/fused_adam/mul", ""]),
    ("conv_roofline_pct", "Op lowerings", "scope", "bf16_flops",
     ["resnet50_b128"],
     ["fwd/conv2d/conv_general_dilated", "bwd/conv2d_grad/transpose(jvp())"],
     ["fwd/batch_norm/mul"]),
    ("moe_grouped_roofline_pct", "Op lowerings", "name", "bf16_flops",
     EXPERT_CELLS, ["%ragged-dot-none.37 = f32[8,2048,1024]{2,1,0}"],
     ["%fusion.3 = f32[8] fusion(%ragged-dot-none.37)"]),
    ("attn_short_roofline_pct", "Pallas kernels", "name", "bf16_flops",
     ["bert_base_s128", "bert_base_s128_dp4", "bert_base_s512",
      "transformer_base_s64"],
     ["%mha_short_bwd.7 = (bf16[256,128,768]) custom-call(%a)",
      "mha_short_fwd = bf16[256,128,768] custom-call(%a)"],
     ["%copy.1 = bf16[256,128,768] copy(%mha_short_fwd.3)"]),
    ("flash_roofline_pct", "Pallas kernels", "name", "bf16_flops",
     EXPERT_CELLS, [FLASH_CALL, FLASH_CALL.replace("fwd", "bwd_dkv")],
     ["%fusion.9 = bf16[8,8] fusion(%flash_fwd.12), kind=kLoop"]),
    ("kda_roofline_pct", "Pallas kernels", "name", "bf16_flops",
     ["kimi_linear_ep32_s4096"], ["%kda_bwd.2 = (f32[1,4096,4096])"],
     ["%fusion.1 = f32[8] fusion(%kda_fwd.2)"]),
    ("qk_prep_hbm_pct", "Pallas kernels", "name", "hbm_bytes_per_s",
     ["trinity_mini_ep16_s8192"], ["%qk_prep_fwd.13 = (bf16[1,32,8192,128])"],
     [FLASH_CALL]),
])
def test_a_roofline_metric_names_what_it_reads_and_the_cells_that_have_it(
        metric, layer, key, bound, cells, hits, misses):
    """PR 35's per-layer metrics: declared in `BENCHMARK.json` for the
    cells whose traces hold what they read, read by `trace_roofline` from
    the events' own `flops` or `bytes_accessed`, by an expression that
    finds the kernel (or the Program op's scope) and not what reads its
    output."""
    import json

    from benchmark.harness import spec

    with open(os.path.join(os.path.dirname(spec.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    (declared,) = [m for m in bench["per_layer"] if m["name"] == metric]
    assert declared == {
        "name": metric, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": layer,
        "moves": "train_examples_per_s", "workloads": cells}
    m = spec.load("layer_metrics", metric)
    assert m["kind"] == "trace_roofline" and m["args"]["bound"] == bound
    assert sorted(
        w["name"] for w in bench["workloads"]
        if metric in {x["name"] for x in spec.layer_metrics(
            spec.cell(w["name"]))}) == cells
    assert all(re.search(m["args"][key], text) for text in hits)
    assert not any(re.search(m["args"][key], text) for text in misses)
