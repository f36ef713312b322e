"""`paddle_tpu/step_store.py`: a step's executable kept on disk beside
JAX's cache and found again at the step's first call, before anything is
traced. These are the only tests that give the store a directory (the
session has none, `conftest.py`), one of their own, with JAX's threshold
of compile time at 0 so that a tiny program is worth an entry."""

import json
import os
import pickle

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import profiler, step_store
from paddle_tpu.scope import Scope

from decoder_suite import settled_counters

STAGES = ("compile_trace_us.train", "compile_lower_us.train")


@pytest.fixture
def store(tmp_path, monkeypatch):
    monkeypatch.setattr(step_store, "DIR", str(tmp_path / "steps"))
    seconds = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    yield tmp_path / "steps"
    jax.config.update("jax_persistent_cache_min_compile_time_secs", seconds)


def entries(store):
    return sorted(str(p.relative_to(store)) for p in store.glob("*/*.bin"))


def read(path):
    return pickle.loads(step_store._unpack(path.read_bytes()))


def since(before, prefix="step_store_"):
    return {k: v - before.get(k, 0) for k, v in profiler.counters().items()
            if k.startswith(prefix) and v != before.get(k, 0)}


def mlp(width=8, scale=1.0, attend=False):
    """A train Program under names of its own: built twice it is the same
    Program, to the fingerprint."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [width])
        y = fluid.layers.data("y", [1])
        h = fluid.layers.fc(x, 16, act="relu")
        if attend:  # two tokens of two heads of four lanes
            qkv = fluid.layers.reshape(h, [-1, 2, 2, 4])
            h = fluid.layers.reshape(fluid.layers.fused_multihead_attention(
                qkv, qkv, qkv, layout="bshd"), [-1, 16])
        out = fluid.layers.scale(fluid.layers.fc(h, 1), scale=scale)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(out, y))
        fluid.optimizer.Adam(1e-2).minimize(loss)
    main.random_seed = startup.random_seed = 7
    return main, startup, loss


def feed(width=8, rows=4):
    rng = np.random.RandomState(0)
    return {"x": rng.randn(rows, width).astype("float32"),
            "y": rng.randn(rows, 1).astype("float32")}


def train(program, startup, loss, batch, steps=3, exe=None, scope=None):
    """A new Executor over a new scope: the start of a process, as far as
    the Executor's own cache goes."""
    exe = exe or fluid.Executor(fluid.CPUPlace())
    scope = scope or Scope()
    exe.run(startup, scope=scope)
    return [exe.run(program, feed=batch, fetch_list=[loss], scope=scope)[0]
            for _ in range(steps)]


def test_a_second_executor_hits_and_fetches_what_the_miss_fetched(store):
    main, startup, loss = mlp()
    before = profiler.counters()
    missed = train(main, startup, loss, feed())
    assert since(before) == {"step_store_writes.forward": 1,
                             "step_store_writes.train": 1}
    assert len(entries(store)) == 2
    before = profiler.counters()
    hit = train(main, startup, loss, feed())
    assert since(before) == {"step_store_hits.forward": 1,
                             "step_store_hits.train": 1}
    # three steps with the state donated, to the last bit
    assert [a.tobytes() for a in hit] == [a.tobytes() for a in missed]
    assert hit[0] != hit[2]
    now = profiler.counters()
    # nothing was traced or lowered; the read is filed where a warm
    # start's read of JAX's cache is
    assert all(now[s] == before[s] for s in STAGES)
    assert now["compile_backend_us.train"] > before["compile_backend_us.train"]
    assert (now["compile_cache_read_us.train"]
            - before.get("compile_cache_read_us.train", 0)
            == now["compile_backend_us.train"]
            - before["compile_backend_us.train"])
    assert now["program_compile_count"] == before["program_compile_count"] + 2


def test_a_loaded_step_hands_its_device_counts_back(store):
    """The counts a step makes on the device are named by the Program
    (`executor._device_count_names`), not by the trace a hit skips: a
    second Executor's loaded step folds the same counts the miss did."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [24, 16], append_batch_size=False)
        out, load = fluid.layers.moe_experts(
            x, experts_total=4, experts_held=2, d_ff=8, k=2)
        loss = fluid.layers.reduce_mean(fluid.layers.square(out))
        fluid.optimizer.SGD(0.1).minimize(loss)
    main.random_seed = startup.random_seed = 7
    batch = {"x": np.random.RandomState(0).randn(24, 16).astype("float32")}
    counts = ("moe_rows_routed", "moe_rows_live", "moe_blocks_run")
    gained = []
    for said in ("step_store_writes.train", "step_store_hits.train"):
        before = settled_counters()
        loads = train(main, startup, load, batch)
        assert since(before)[said] == 1
        now = settled_counters()
        gained.append([now[n] - before.get(n, 0) for n in counts])
        assert gained[-1] == [3 * 24 * 2, sum(int(v.sum()) for v in loads), 3]
    assert gained[0] == gained[1]


def test_another_seed_is_the_same_step(store):
    """`random_seed` reaches the step as an argument: a job restarted
    under another seed finds its step, and draws other numbers."""
    main, startup, loss = mlp()
    first = train(main, startup, loss, feed())
    main.random_seed = startup.random_seed = 8
    before = profiler.counters()
    second = train(main, startup, loss, feed())
    assert since(before) == {"step_store_hits.forward": 1,
                             "step_store_hits.train": 1}
    assert first[0] != second[0]  # other initial weights
    assert [a.tobytes() for a in second] == [
        a.tobytes() for a in jitted(main, startup, loss, feed())]


def jitted(*args, **kwargs):
    """`train` with no store: the jit, as it was."""
    directory, step_store.DIR = step_store.DIR, None
    try:
        return train(*args, **kwargs)
    finally:
        step_store.DIR = directory


def test_the_hit_says_what_the_misss_trace_said_of_the_program(store):
    """The lowerings' counts and gauges, in their order; no time, and no
    count of the compile's own."""
    main, startup, loss = mlp(attend=True)
    with profiler.recorded_counters() as missed:
        train(main, startup, loss, feed(), steps=1)
    said = [c for c in missed if c[1].startswith("attn_")]
    assert ("set", "attn_kv_group", 1) in said
    assert said.count(("bump", "attn_dispatch_xla", 1)) == 2  # and the replay
    profiler.set_counter("attn_kv_group", -1)  # whatever it read in between
    with profiler.recorded_counters() as hit:
        train(main, startup, loss, feed(), steps=1)
    assert [c for c in hit if c[1].startswith("attn_")] == said
    assert profiler.counters()["attn_kv_group"] == 1
    assert ("bump", "step_store_hits.train", 1) in hit
    kept = []
    for path in store.glob("*/*.bin"):
        kept += read(path)["counters"]
    assert [c for c in kept if c[1].startswith("attn_")] == said
    assert not [c for c in kept if "_us" in c[1] or c[1].startswith(
        ("compile_", "step_store_", "program_"))]


def test_a_recording_holds_its_own_threads_bumps_alone():
    """The reader's stager bumps its counters beside a first call: they
    are not the trace's, and a hit must not say them again."""
    import threading

    def stager():
        profiler.bump_counter("reader_staged_batches")

    with profiler.recorded_counters() as outer:
        profiler.bump_counter("attn_dispatch_xla")
        with profiler.recorded_counters() as inner:
            profiler.set_counter("attn_kv_group", 4)
            other = threading.Thread(target=stager)
            other.start()
            other.join(timeout=10)
            assert not other.is_alive()
    assert inner == [("set", "attn_kv_group", 4)]
    assert outer == [("bump", "attn_dispatch_xla", 1)] + inner
    before = profiler.counters()
    profiler.replay_counters(outer)
    assert since(before, "attn_") == {"attn_dispatch_xla": 1}  # the gauge: 4
    profiler.bump_counter("attn_dispatch_xla")  # and nothing records now
    assert len(outer) == 2


def _variant(case, monkeypatch):
    """The MLP's train step with one thing the lowering reads changed."""
    kwargs, batch = {}, feed()
    if case == "an op's attribute":
        kwargs = {"scale": 3.0}
    elif case == "a feed's shape":
        batch = feed(rows=6)
    elif case == "a PADDLE_TPU_ variable":
        monkeypatch.setenv("PADDLE_TPU_JIT_CACHE_CAP", "9")
    elif case == "the source digest":
        monkeypatch.setattr(step_store, "_source_digest", lambda: "edited")
    elif case == "the JAX version string":
        monkeypatch.setattr(jax, "__version__", "0.0.1")
    main, startup, loss = mlp(**kwargs)
    if case == "the AMP dtype":
        main._amp_dtype = "bfloat16"
    return main, startup, loss, batch


def test_an_edit_under_passes_changes_the_source_digest(tmp_path, monkeypatch):
    """The digest in every key is of the package's files as they are: a
    copy reads the same, and an edited pass, a new one and a renamed one
    each read otherwise, where a file that is no source reads the same.
    A pass's rewrite is in no other part of a key."""
    import shutil

    copy = tmp_path / "paddle_tpu"
    shutil.copytree(step_store._PACKAGE, copy,
                    ignore=shutil.ignore_patterns("__pycache__"))

    def digest():
        step_store._source_digest.cache_clear()
        return step_store._source_digest()

    try:
        real = digest()
        monkeypatch.setattr(step_store, "_PACKAGE", str(copy))
        assert digest() == real
        (copy / "passes" / "notes.txt").write_text("no source\n")
        assert digest() == real
        with open(copy / "passes" / "dce.py", "a") as f:
            f.write("# edited\n")
        edited = digest()
        (copy / "passes" / "another.py").write_text("")
        added = digest()
        (copy / "passes" / "another.py").rename(copy / "passes" / "other.py")
        assert len({real, edited, added, digest()}) == 4
        assert step_store._surroundings()[-1] == digest()
    finally:  # the next caller reads the package itself again
        step_store._source_digest.cache_clear()


@pytest.mark.parametrize("case", [
    "an op's attribute", "a feed's shape", "the AMP dtype",
    "a PADDLE_TPU_ variable", "the source digest", "the JAX version string"])
def test_whatever_the_lowering_reads_is_in_the_key(case, store, monkeypatch):
    train(*mlp(), feed())
    there = entries(store)
    before = profiler.counters()
    train(*_variant(case, monkeypatch))
    moved = since(before)
    assert "step_store_hits.train" not in moved, case
    assert moved["step_store_writes.train"] == 1
    assert "step_store_errors.train" not in moved
    new = set(entries(store)) - set(there)
    # what the step is names the slot, what surrounds it the file
    surroundings = case in ("a PADDLE_TPU_ variable", "the source digest",
                            "the JAX version string")
    slots = {e.split("/")[0] for e in there}
    assert all((e.split("/")[0] in slots) == surroundings for e in new), new


def _riding(case, main, loss):
    """A bf16 MLP with one thing changed that rides on the Program
    beside its fingerprint (`executor._THE_LOWERINGS_READ`)."""
    from jax.sharding import PartitionSpec as P

    main._amp_dtype = "bfloat16"
    if case == "the AMP black list":  # as decorate(..., custom_black_list)
        main._amp_black_list = {"mul"}
    elif case == "the AMP white list":
        main._amp_white_list = {"elementwise_add"}
    elif case == "the pipeline's loss":
        main._pipeline_loss = loss.name
    elif case == "a sharding spec":
        name = main.global_block().all_parameters()[0].name
        main._sharding_specs[name] = P(None, "model")
    return main


@pytest.mark.parametrize("case", [
    "the AMP black list", "the AMP white list", "the pipeline's loss",
    "a sharding spec"])
def test_what_rides_on_the_program_beside_its_fingerprint_is_in_the_key(
        case, store):
    """A user who moves an op into `custom_black_list` between two starts
    (to cure an overflow) has the same Program, the same avals (fp32
    master weights) and the same source: the lists themselves are in the
    key, or the restart would run the old precision."""
    main, startup, loss = mlp()
    plain = train(_riding(None, main, loss), startup, loss, feed())
    there = entries(store)
    before = profiler.counters()
    other, startup, loss = mlp()
    assert (_riding(case, other, loss).fingerprint()
            == _riding(None, main, loss).fingerprint())
    moved_to = train(other, startup, loss, feed())
    moved = since(before)
    assert "step_store_hits.train" not in moved, case
    assert moved["step_store_writes.train"] == 1
    new = set(entries(store)) - set(there)
    assert new and not {e.split("/")[0] for e in new} & {
        e.split("/")[0] for e in there}  # another step: another slot
    if case == "the AMP black list":  # and it is another step: fp32 products
        assert [a.tobytes() for a in moved_to] != [a.tobytes() for a in plain]
        assert [a.tobytes() for a in moved_to] == [
            a.tobytes() for a in jitted(other, startup, loss, feed())]


def test_every_private_attribute_of_a_program_is_accounted_for():
    """Whatever the package reads off a Program under a private name is
    either in the key (`_THE_LOWERINGS_READ`) or named as no lowering's
    business (`_NOT_THE_LOWERINGS`): a new one fails here until its
    author says which."""
    import re

    from paddle_tpu import executor

    package = os.path.dirname(os.path.abspath(fluid.__file__))
    a_program = r"(?:\bprogram|\bprog|_program)"
    reads = re.compile(
        a_program + r"\._([a-z]\w*)\b(?!\()"
        r"|[gs]etattr\(\s*[\w.]*" + a_program + r",\s*\"_(\w+)\"")
    found = {}
    for root, _, files in os.walk(package):
        for name in files:
            # framework.py is the Program's own: what it keeps of itself
            # under `self._x` is not read off one
            if name.endswith(".py") and name != "framework.py":
                path = os.path.join(root, name)
                with open(path) as f:
                    for a, b in reads.findall(f.read()):
                        found.setdefault("_" + (a or b), path)
    known = set(executor._THE_LOWERINGS_READ) | set(
        executor._NOT_THE_LOWERINGS)
    assert not {n: p for n, p in found.items() if n not in known}
    # and the ones the lowerings are known to read are found at all
    assert {"_amp_black_list", "_amp_white_list", "_pipeline_loss",
            "_sharding_specs", "_recompute_loss"} <= set(found)
    assert not set(executor._THE_LOWERINGS_READ) & set(
        executor._NOT_THE_LOWERINGS)


def test_this_jax_says_what_the_store_asks_of_it():
    """Two private names of JAX's stand between the store and its
    entries: where a lowering keeps its host callbacks (without it no step
    is written) and a sharding's device assignment (without it every write
    is an error). An upgrade that renames either fails here, and not as
    `setup_step_store_hits` reading 0."""
    x = jax.numpy.ones((4,))
    plain = jax.jit(lambda a: a * 2).lower(x)
    assert "host_callbacks" in plain._lowering.compile_args
    assert step_store._calls_the_host(plain) is False

    def called_back(a):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2, jax.ShapeDtypeStruct(a.shape, a.dtype),
            a)

    assert step_store._calls_the_host(jax.jit(called_back).lower(x)) is True
    (sharding, *_) = jax.tree_util.tree_leaves(
        plain.compile().input_shardings)
    assert [d.id for d in sharding._device_assignment] == [
        jax.devices()[0].id]


def _break(case, store):
    paths = list(store.glob("*/*.bin"))
    for path in paths:
        if case == "a truncated entry":
            path.write_bytes(path.read_bytes()[:200])
        elif case == "a foreign entry":
            entry = read(path)
            entry["made_by"] = {**entry["made_by"], "device_kind": "TPU v9",
                                "jax": "0.0.1"}
            path.write_bytes(step_store._pack(pickle.dumps(entry)))
        elif case == "an entry that does not load":
            entry = read(path)
            entry["executable"] = entry["executable"][:300]
            path.write_bytes(step_store._pack(pickle.dumps(entry)))


@pytest.mark.parametrize("case", [
    "a truncated entry", "a foreign entry", "an entry that does not load"])
def test_an_entry_that_cannot_be_used_is_a_miss_counted_and_rewritten(
        case, store):
    main, startup, loss = mlp()
    missed = train(main, startup, loss, feed())
    _break(case, store)
    before = profiler.counters()
    again = train(main, startup, loss, feed())
    assert since(before) == {
        "step_store_errors.forward": 1, "step_store_errors.train": 1,
        "step_store_writes.forward": 1, "step_store_writes.train": 1}
    assert [a.tobytes() for a in again] == [a.tobytes() for a in missed]
    before = profiler.counters()
    train(main, startup, loss, feed())  # rewritten: whole again
    assert since(before) == {"step_store_hits.forward": 1,
                             "step_store_hits.train": 1}


def test_a_directory_that_cannot_be_written_leaves_the_step_running(
        tmp_path, store, monkeypatch):
    blocked = tmp_path / "a_file"
    blocked.write_text("not a directory")
    monkeypatch.setattr(step_store, "DIR", str(blocked / "steps"))
    main, startup, loss = mlp()
    before = profiler.counters()
    first = train(main, startup, loss, feed())
    assert since(before) == {"step_store_errors.forward": 1,
                             "step_store_errors.train": 1}
    assert [a.tobytes() for a in first] == [
        a.tobytes() for a in jitted(main, startup, loss, feed())]


def test_under_jaxs_threshold_of_compile_time_nothing_is_written(store):
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 3600.0)
    before = profiler.counters()
    losses = train(*mlp(), feed())
    assert since(before) == {} and not store.exists()
    assert losses[0] != losses[2]


def test_a_call_with_other_avals_after_a_hit_is_served_by_the_jit(store):
    """As a retrace is without the store: the startup program's second
    run finds its written-only state settled (no placeholder scalars),
    and a state array of another dtype reaches a train step."""
    main, startup, loss = mlp()
    train(main, startup, loss, feed())

    def twice(run):
        exe, scope = fluid.Executor(fluid.CPUPlace()), Scope()
        first = run(main, startup, loss, feed(), exe=exe, scope=scope)
        # the same steps again, the startup program's state at its avals
        return exe, scope, first + run(main, startup, loss, feed(),
                                       exe=exe, scope=scope)

    before = profiler.counters()
    exe, scope, hit = twice(train)
    assert since(before) == {"step_store_hits.forward": 1,
                             "step_store_hits.train": 1}
    assert [a.tobytes() for a in hit] == [
        a.tobytes() for a in twice(jitted)[2]]
    name = main.global_block().all_parameters()[0].name
    scope.set(name, np.asarray(scope.get(name)).astype("float16"))
    traced = profiler.counters().get("compile_trace_us.other", 0)
    (loss16,) = exe.run(main, feed=feed(), fetch_list=[loss], scope=scope)
    assert np.isfinite(loss16).all()
    # nobody's first call: the jit's retrace, filed as one is without a store
    assert profiler.counters()["compile_trace_us.other"] > traced
    assert "step_store_errors.train" not in since(before)


def test_writing_an_entry_removes_its_slots_stale_sibling(store, monkeypatch):
    main, startup, loss = mlp()
    train(main, startup, loss, feed())
    old = entries(store)
    tmp = store / old[0].split("/")[0] / "half_written.bin.123.tmp"
    tmp.write_bytes(b"another process is writing this")
    monkeypatch.setattr(step_store, "_source_digest", lambda: "next commit")
    train(main, startup, loss, feed())
    new = entries(store)
    assert len(new) == 2 and not set(new) & set(old)
    assert {e.split("/")[0] for e in new} == {e.split("/")[0] for e in old}
    assert tmp.exists()  # not its to remove


def test_a_step_whose_trace_stays_in_the_process_is_not_stored(
        store, monkeypatch):
    """`PADDLE_TPU_CHECK_NAN_INF`'s trace leaves the flags' names in the
    process; a `py_func` step calls a function of it."""
    monkeypatch.setenv("PADDLE_TPU_CHECK_NAN_INF", "1")
    before = profiler.counters()
    losses = train(*mlp(), feed())
    assert since(before) == {} and not entries(store)
    assert np.isfinite(losses).all()
    monkeypatch.delenv("PADDLE_TPU_CHECK_NAN_INF")

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [4])
        out = main.global_block().create_var(name="doubled", shape=[2, 4],
                                             dtype="float32")
        fluid.layers.py_func(lambda a: np.asarray(a) * 2, x, out)
    before = profiler.counters()
    for _ in range(2):
        (got,) = fluid.Executor(fluid.CPUPlace()).run(
            main, feed={"x": np.ones((2, 4), "float32")}, fetch_list=[out],
            scope=Scope())
        assert (got == 2).all()
    assert since(before) == {} and not entries(store)


def test_a_step_lowered_by_code_outside_the_package_is_not_stored(
        store, monkeypatch):
    """A lowering registered from a user's module (`register_op`), or
    patched in place, is in no digest of the package's source: its next
    edit would be served the old step. Forward and, through
    `__auto_grad__`, backward."""
    from paddle_tpu.ops import registry

    ours = registry.get_op("scale").lower

    def scale_from_a_users_module(ctx, op):
        return ours(ctx, op)

    assert not scale_from_a_users_module.__module__.startswith("paddle_tpu")
    monkeypatch.setattr(registry.get_op("scale"), "lower",
                        scale_from_a_users_module)
    main, startup, loss = mlp()
    before = profiler.counters()
    theirs = train(main, startup, loss, feed())
    # the startup program takes no `scale`: stored as ever
    assert since(before) == {"step_store_writes.forward": 1}
    monkeypatch.undo()
    monkeypatch.setattr(step_store, "DIR", str(store))
    before = profiler.counters()
    assert [a.tobytes() for a in train(main, startup, loss, feed())] == [
        a.tobytes() for a in theirs]
    assert since(before) == {"step_store_hits.forward": 1,
                             "step_store_writes.train": 1}


def test_a_mesh_step_is_loaded_with_its_shardings(store):
    main, startup, loss = mlp()
    batch = feed(rows=8)

    def on_four():
        compiled = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, places=4)
        return train(compiled, startup, loss, batch)

    before = profiler.counters()
    missed = on_four()
    assert since(before)["step_store_writes.train"] == 1
    before = profiler.counters()
    hit = on_four()
    assert since(before) == {"step_store_hits.forward": 1,
                             "step_store_hits.train": 1}
    assert [a.tobytes() for a in hit] == [a.tobytes() for a in missed]
    # one device's step is another slot: the shardings are in the key
    before = profiler.counters()
    train(main, startup, loss, batch)
    assert since(before)["step_store_writes.train"] == 1


def test_the_sessions_setting_is_no_store_and_leaves_no_file():
    """tier-1 patches lowerings and kernels in place, which no key can
    see: under the session's setting an Executor asks no store and
    creates nothing under the checkout's cache."""
    assert step_store.DIR is None
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    steps = os.path.join(checkout, ".jax_cache", "steps")
    there = set(os.listdir(steps)) if os.path.isdir(steps) else None
    seconds = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        before = profiler.counters()
        losses = train(*mlp(), feed())
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          seconds)
    assert losses[0] != losses[2] and since(before) == {}
    assert (set(os.listdir(steps)) if os.path.isdir(steps) else None) == there


@pytest.mark.parametrize("metric,better,family", [
    ("setup_step_store_hits", "higher", "step_store_hits"),
    ("setup_step_store_errors", "lower", "step_store_errors")])
def test_the_benchmarks_two_metrics_read_the_stores_counters(
        metric, better, family):
    """Declared as `setup_uncached_compiles` is: a file, the same fields
    in `BENCHMARK.json`, a delta over set-up in every cell."""
    from benchmark.harness import spec

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    m, like = (spec.load("layer_metrics", n)
               for n in (metric, "setup_uncached_compiles"))
    with open(os.path.join(checkout, "BENCHMARK.json")) as f:
        (declared,) = [x for x in json.load(f)["per_layer"]
                       if x["name"] == metric]
    assert declared == {
        "name": metric, "unit": "count", "better": better,
        "source": "program_counter", "layer": "L0 compile path",
        "moves": "setup_s"}
    assert {k: m[k] for k in declared} == declared
    assert {**m, "name": 0, "better": 0, "args": 0} == {
        **like, "name": 0, "better": 0, "args": 0}
    assert m["args"] == {"counters": [f"{family}.train", f"{family}.forward"],
                         "phase": "setup"}
