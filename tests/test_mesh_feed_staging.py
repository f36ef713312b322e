"""A mesh step's per-dispatch arguments arrive laid out on the mesh (PR 30).

The reader's stager puts each batch with the feed shardings of the mesh
step the Executor last ran for the feeds' Program (`reader/stager.py::
stage_feed`, a hint and never a contract), and the step takes its seed as
two words of host memory and folds the key inside. On the chip that is
what lets the dispatch of step N+1 go out while step N runs (PERF.md,
Findings, PR 30); here, on the eight virtual CPU devices of conftest.py,
the tests hold the layout, the counters and the numbers."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

import paddle_tpu as fluid
from paddle_tpu import executor as executor_mod
from paddle_tpu import profiler
from paddle_tpu.reader import DataLoader
from paddle_tpu.reader import stager as stager_mod
from paddle_tpu.resilience import faults

PLACES = 4
ROWS = 8


def _count(name):
    return profiler.counters().get(name, 0)


def _build(seed=7):
    """A small classifier with dropout on; returns (exe, main, loss, x, y)."""
    x = fluid.layers.data("x", [16])
    y = fluid.layers.data("y", [1], dtype="int64")
    h = fluid.layers.dropout(fluid.layers.fc(x, 32, act="relu"), 0.3)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(fluid.layers.fc(h, 4), y))
    fluid.optimizer.SGD(0.1).minimize(loss)
    main = fluid.default_main_program()
    main.random_seed = seed
    fluid.default_startup_program().random_seed = seed
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    return exe, main, loss, x, y


def _pool(n, rows=ROWS, seed=0):
    rng = np.random.RandomState(seed)
    return [[rng.randn(rows, 16).astype("float32"),
             rng.randint(0, 4, (rows, 1)).astype("int64")] for _ in range(n)]


def _on_mesh(main, loss):
    return fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, places=PLACES)


def _loader(x, y, pool):
    loader = DataLoader.from_generator(feed_list=[x, y], capacity=8,
                                       use_double_buffer=True)
    return loader.set_batch_generator(lambda: iter(pool))


def _compile_only(exe, prog, pool, loss):
    """Compile the step for this feed shape without running it (so no
    state and no PRNG tick moves); returns the compiled step."""
    program, cp = exe._unwrap(prog)
    compiled, _, _ = exe._prepare_run(
        program, {"x": pool[0][0], "y": pool[0][1]}, [loss],
        fluid.global_scope(), cp)
    return compiled


def _params(main):
    scope = fluid.global_scope()
    return {p.name: np.asarray(scope.get(p.name))
            for p in main.global_block().all_parameters()}


# ---- (1) the loader stages where the compiled step reads ----------------


def test_loader_batches_carry_the_compiled_feed_shardings():
    exe, main, loss, x, y = _build()
    cp, pool = _on_mesh(main, loss), _pool(6)
    exe.run(cp, feed={"x": pool[0][0], "y": pool[0][1]}, fetch_list=[loss])
    compiled = list(exe._cache.values())[-1]
    assert set(compiled.feed_shardings) == {"x", "y"}
    assert main._feed_shardings is compiled.feed_shardings

    staged0, mesh0 = _count("reader_staged_batches"), _count("reader_staged_on_mesh")
    reshard0 = _count("feed_reshard_at_dispatch")
    for n, batch in enumerate(_loader(x, y, pool), 1):
        for name, arr in batch.items():
            assert arr.sharding == compiled.feed_shardings[name]
            assert len(arr.sharding.device_set) == PLACES
        assert batch["y"].dtype == np.int32  # 64-bit feeds map down as before
        exe.run(cp, feed=batch, fetch_list=[loss])
        assert _count("reader_staged_on_mesh") - mesh0 >= n  # a batch a batch
    assert _count("reader_staged_on_mesh") - mesh0 == len(pool)
    assert _count("reader_staged_batches") - staged0 == len(pool)
    assert _count("feed_reshard_at_dispatch") == reshard0


# ---- (2) however the batch arrives, the numbers are the same ------------

FEEDS = ["staged_on_mesh", "numpy", "user_device_put"]


def _train_five(kind):
    """Five steps with dropout on a four-device mesh, fed `kind`'s way;
    (losses, parameters). Fresh programs and scope, the same names."""
    import paddle_tpu.framework as framework
    import paddle_tpu.scope as scope_mod

    old_main = framework.switch_main_program(framework.Program())
    old_startup = framework.switch_startup_program(framework.Program())
    framework.unique_name.switch()
    try:
        with scope_mod.scope_guard(scope_mod.Scope()):
            exe, main, loss, x, y = _build()
            cp, pool = _on_mesh(main, loss), _pool(5)
            if kind == "staged_on_mesh":
                _compile_only(exe, cp, pool, loss)  # the hint is there
                mesh0 = _count("reader_staged_on_mesh")
                batches = list(_loader(x, y, pool))
                assert _count("reader_staged_on_mesh") - mesh0 == 5
            elif kind == "numpy":
                batches = [{"x": a, "y": b} for a, b in pool]
            else:  # one uncommitted array on the default device
                batches = [{"x": jax.device_put(a), "y": jax.device_put(b)}
                           for a, b in pool]
            losses = [np.asarray(exe.run(cp, feed=b, fetch_list=[loss])[0])
                      for b in batches]
            return np.stack(losses), _params(main)
    finally:
        framework.switch_main_program(old_main)
        framework.switch_startup_program(old_startup)


@pytest.fixture(scope="module")
def numpy_fed():
    return _train_five("numpy")


@pytest.mark.parametrize("kind", FEEDS)
def test_every_way_of_feeding_a_mesh_gives_the_same_bits(kind, numpy_fed):
    losses, params = _train_five(kind)
    want_losses, want_params = numpy_fed
    np.testing.assert_array_equal(losses, want_losses)
    assert losses[-1] != losses[0]
    for name, want in want_params.items():
        np.testing.assert_array_equal(params[name], want, err_msg=name)


# ---- (3) a hint, never a contract ----------------------------------------

MISFITS = ["rows_the_mesh_does_not_divide", "committed_to_one_device",
           "laid_out_for_another_mesh", "staged_before_the_first_compile"]


@pytest.mark.parametrize("misfit", MISFITS)
def test_a_batch_the_hint_does_not_fit_falls_back_and_trains(misfit):
    exe, main, loss, x, y = _build()
    cp, pool = _on_mesh(main, loss), _pool(3)
    mesh0 = _count("reader_staged_on_mesh")
    reshard0 = _count("feed_reshard_at_dispatch")
    if misfit == "staged_before_the_first_compile":
        assert getattr(main, "_feed_shardings", None) is None
        (first, *_) = list(_loader(x, y, pool[:1]))
        assert all(len(a.sharding.device_set) == 1 for a in first.values())
        batches, resharded = [first], 2
    else:
        _compile_only(exe, cp, pool, loss)
        if misfit == "rows_the_mesh_does_not_divide":
            batches = list(_loader(x, y, _pool(2, rows=6)))
            assert all(len(a.sharding.device_set) == 1
                       for b in batches for a in b.values())
            resharded = 4  # the step for six rows reads them replicated
        elif misfit == "committed_to_one_device":
            dev = jax.devices()[1]
            batches = [{"x": jax.device_put(a, dev), "y": jax.device_put(b, dev)}
                       for a, b in pool[:1]]
            resharded = 2
        else:
            other = Mesh(np.array(jax.devices()[:8]), ("batch",))
            batches = [{"x": jax.device_put(a, NamedSharding(other, P("batch"))),
                        "y": jax.device_put(b, NamedSharding(other, P("batch")))}
                       for a, b in pool[:1]]
            resharded = 2
    assert _count("reader_staged_on_mesh") == mesh0
    before = _params(main)
    for b in batches:
        (got,) = exe.run(cp, feed=b, fetch_list=[loss])
        assert np.isfinite(got).all()
    assert _count("feed_reshard_at_dispatch") - reshard0 == resharded
    after = _params(main)
    assert any((before[n] != after[n]).any() for n in before)  # it trained


# ---- (4) the key is folded inside the step -------------------------------

PATHS = ["plain", "mesh"]


def _on(path, main, loss):
    return main if path == "plain" else _on_mesh(main, loss)


def _spy_on_the_key(monkeypatch):
    """Every step compiled from now on can also fetch `__key__`: the bits
    of the key its lowering was handed (as int32, a dtype a Program's
    variable can declare)."""
    real = executor_mod.lower_block

    def spy(ctx, block):
        real(ctx, block)
        ctx.values["__key__"] = jax.lax.bitcast_convert_type(
            jax.random.key_data(ctx.rng_key), np.int32)

    monkeypatch.setattr(executor_mod, "lower_block", spy)
    fluid.default_main_program().global_block().create_var(
        name="__key__", shape=[2], dtype="int32")


def _want_key(seed, n):
    return np.asarray(jax.random.key_data(
        jax.random.fold_in(jax.random.key(seed or 42), n))).view(np.int32)


@pytest.mark.parametrize("seed", [0, 7, 1000 + 2**31 + 11])
@pytest.mark.parametrize("path", PATHS)
def test_the_key_inside_the_step_is_the_fold_of_seed_and_tick(
        monkeypatch, path, seed):
    """`fold_in(key(random_seed or 42), n)` at step n, bit for bit what the
    executor folded on the host's side before PR 30; the benchmark's seeds
    pass 32 signed bits."""
    _spy_on_the_key(monkeypatch)
    exe, main, loss, x, y = _build(seed)
    prog, pool = _on(path, main, loss), _pool(3)
    n0 = exe._seed_counter
    for n, (a, b) in enumerate(pool, 1):
        _, key = exe.run(prog, feed={"x": a, "y": b},
                         fetch_list=[loss, "__key__"])
        np.testing.assert_array_equal(key, _want_key(seed, n0 + n))
    # and the seed words are host memory: nothing is born on device 0
    words = executor_mod._seed_words(main, n0 + 1)
    assert type(words) is np.ndarray and words.dtype == np.uint32


@pytest.mark.parametrize("path", PATHS)
def test_n_runs_equal_one_run_repeated_of_n(path):
    import paddle_tpu.scope as scope_mod

    exe, main, loss, x, y = _build()
    prog = _on(path, main, loss)
    (a, b), = _pool(1)
    feed = {"x": a, "y": b}
    start = _params(main)
    seq = [np.asarray(exe.run(prog, feed=feed, fetch_list=[loss])[0])
           for _ in range(4)]
    seq_params = _params(main)

    scope = scope_mod.Scope()
    for name, value in start.items():
        scope.set(name, value)
    exe2 = fluid.Executor(fluid.CPUPlace())
    exe2._seed_counter = exe._seed_counter - 4
    with scope_mod.scope_guard(scope):
        (stacked,) = exe2.run_repeated(prog, feed=feed, fetch_list=[loss],
                                       steps=4)
        rep_params = _params(main)
    np.testing.assert_allclose(np.asarray(stacked).reshape(4),
                               np.stack(seq).reshape(4), rtol=1e-6)
    assert exe2._seed_counter == exe._seed_counter
    for name, want in seq_params.items():
        np.testing.assert_allclose(rep_params[name], want, rtol=1e-5,
                                   atol=1e-7, err_msg=name)


@pytest.mark.parametrize("path", PATHS)
def test_a_failed_dispatch_replays_the_same_tick(monkeypatch, path):
    _spy_on_the_key(monkeypatch)
    exe, main, loss, x, y = _build()
    prog = _on(path, main, loss)
    (a, b), = _pool(1)
    feed = {"x": a, "y": b}
    exe.run(prog, feed=feed, fetch_list=[loss, "__key__"])
    tick = exe._seed_counter
    with faults.active(faults.FaultPlan().add(
            "executor.dispatch", raises=RuntimeError, nth=1)):
        with pytest.raises(RuntimeError, match="injected fault"):
            exe.run(prog, feed=feed, fetch_list=[loss, "__key__"])
    assert exe._seed_counter == tick
    _, key = exe.run(prog, feed=feed, fetch_list=[loss, "__key__"])
    np.testing.assert_array_equal(key, _want_key(7, tick + 1))
    assert exe._seed_counter == tick + 1


@pytest.mark.parametrize("path", PATHS)
def test_random_seed_changed_between_runs_changes_the_masks(path):
    """The seed is read at each dispatch and is an argument of the step:
    no recompile, and no stale executable either."""
    x = fluid.layers.data("x", [64])
    out = fluid.layers.dropout(x, 0.5)
    main = fluid.default_main_program()
    main.random_seed = 7
    exe = fluid.Executor(fluid.CPUPlace())
    prog = (main if path == "plain" else
            fluid.CompiledProgram(main).with_data_parallel(places=PLACES))
    feed = {"x": np.ones((ROWS, 64), "float32")}

    def mask(seed, tick):
        main.random_seed = seed
        exe._seed_counter = tick
        return np.asarray(exe.run(prog, feed=feed, fetch_list=[out])[0]) != 0

    first = mask(7, 0)
    compiles = _count("program_compile_count")
    other = mask(8, 0)
    assert (first != other).any()
    np.testing.assert_array_equal(mask(7, 0), first)
    assert (mask(7, 1) != first).any()
    assert _count("program_compile_count") == compiles


# ---- (5) with no mesh the stager does what it did ------------------------


@pytest.mark.parametrize("after", ["nothing_compiled", "a_plain_step",
                                   "a_plain_step_after_a_mesh_step"])
def test_with_no_mesh_the_loader_stages_on_the_default_device(after):
    exe, main, loss, x, y = _build()
    pool = _pool(3)
    if after == "a_plain_step_after_a_mesh_step":
        _compile_only(exe, _on_mesh(main, loss), pool, loss)
        assert main._feed_shardings
    if after != "nothing_compiled":
        exe.run(main, feed={"x": pool[0][0], "y": pool[0][1]},
                fetch_list=[loss])
        assert main._feed_shardings == {}
    mesh0 = _count("reader_staged_on_mesh")
    reshard0 = _count("feed_reshard_at_dispatch")
    for (a, b), batch in zip(pool, _loader(x, y, pool)):
        want = {"x": jax.device_put(a), "y": jax.device_put(b)}
        for name, arr in batch.items():
            assert isinstance(arr, jax.Array)
            assert arr.sharding == want[name].sharding  # one default device
            assert not arr.committed
            assert arr.dtype == want[name].dtype  # int64 -> int32, float32
            np.testing.assert_array_equal(np.asarray(arr),
                                          np.asarray(want[name]))
        exe.run(main, feed=batch, fetch_list=[loss])
    assert _count("reader_staged_on_mesh") == mesh0
    assert _count("feed_reshard_at_dispatch") == reshard0


# ---- (6) the dataset path stages through the same helper -----------------


class _Batches:
    """What `_run_dataset` needs of a dataset."""

    def __init__(self, feeds):
        self._feeds = feeds

    def batches(self, num_threads=1):
        yield from self._feeds


@pytest.mark.parametrize("path", PATHS)
def test_run_dataset_stages_through_the_same_helper(monkeypatch, path):
    exe, main, loss, x, y = _build()
    prog, pool = _on(path, main, loss), _pool(4)
    _compile_only(exe, prog, pool, loss)
    seen = []
    real = stager_mod.stage_feed

    def spy(feed, program=None):
        out = real(feed, program)
        seen.append((program, out))
        return out

    monkeypatch.setattr(stager_mod, "stage_feed", spy)
    mesh0 = _count("reader_staged_on_mesh")
    reshard0 = _count("feed_reshard_at_dispatch")
    feeds = [{"x": a, "y": b} for a, b in pool]
    (last,) = exe.train_from_dataset(prog, _Batches(feeds), fetch_list=[loss])
    assert np.isfinite(last).all()
    assert len(seen) == len(pool) and all(p is main for p, _ in seen)
    devices = PLACES if path == "mesh" else 1
    for _, out in seen:
        assert out["y"].dtype == np.int32
        assert all(len(a.sharding.device_set) == devices for a in out.values())
    staged = _count("reader_staged_on_mesh") - mesh0
    assert staged == (len(pool) if path == "mesh" else 0)
    assert _count("feed_reshard_at_dispatch") == reshard0
