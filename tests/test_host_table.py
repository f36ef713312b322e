"""Host-RAM embedding tables (massive-sparse PS capability): the
DownpourWorker pull->run->push loop with tables living outside HBM
(reference fleet_wrapper.h:66,100, device_worker.h:175)."""

import os

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.framework import Program
from paddle_tpu.incubate.fleet.parameter_server.host_table import (
    HostEmbeddingTable,
    HostTableSession,
)

from ctr_model import batch, build_ctr


def test_pull_push_roundtrip():
    t = HostEmbeddingTable(1000, 4, lr=1.0, optimizer="sgd", seed=1)
    ids = np.array([[5, 7], [5, 900]])
    uniq, remapped, block = t.pull(ids, max_unique=8)
    assert list(uniq) == [5, 7, 900]
    np.testing.assert_array_equal(uniq[remapped], ids)
    np.testing.assert_allclose(block[:3], t.rows[[5, 7, 900]])
    before = t.rows[[5, 7, 900]].copy()
    g = np.zeros((8, 4), np.float32)
    g[0] = 1.0  # grad for row 5
    t.push(uniq, g)
    np.testing.assert_allclose(t.rows[5], before[0] - 1.0)
    np.testing.assert_allclose(t.rows[7], before[1])


def test_pull_overflow_raises():
    t = HostEmbeddingTable(100, 4)
    try:
        t.pull(np.arange(50), max_unique=16)
        raise AssertionError("expected overflow error")
    except ValueError as e:
        assert "max_unique" in str(e)


def test_ctr_model_trains_with_host_table():
    main, startup = Program(), Program()
    loss = build_ctr(main, startup)
    table = HostEmbeddingTable(100_000, 8, lr=0.1, optimizer="adagrad",
                               seed=3)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe.run(startup)
        sess = HostTableSession(
            exe, main, {"ctr_table": (table, "ids", 64)}
        )
        # fixed batch: loss must drop as BOTH dense tower and host rows
        # learn
        feed = batch(rng, 100_000)
        losses = [
            float(np.asarray(
                sess.run(feed, fetch_list=[loss])[0]
            ).reshape(-1)[0])
            for _ in range(15)
        ]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.8, losses
    # the touched rows actually moved
    uniq = np.unique(feed["ids"])
    assert np.abs(table.rows[uniq]).max() > 0


def test_memmap_table_beyond_ram(tmp_path):
    """A table whose FULL size exceeds any single chip's HBM (sparse file:
    only touched pages materialize)."""
    vocab, dim = 200_000_000, 32  # 200M x 32 fp32 = 25.6 GB + adagrad state
    t = HostEmbeddingTable(
        vocab, dim, optimizer="adagrad",
        mmap_path=str(tmp_path / "big_table.bin"),
    )
    assert t.nbytes() > 16 * 2**30  # bigger than a v5e chip's HBM
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (16, 2))
    uniq, remapped, block = t.pull(ids, max_unique=64)
    assert np.abs(block[: uniq.size]).max() > 0  # lazily initialized
    # second pull returns the same rows (initialized once)
    _, _, block2 = t.pull(ids, max_unique=64)
    np.testing.assert_allclose(block, block2)
    g = np.ones((64, dim), np.float32)
    before = block[: uniq.size].copy()
    t.push(uniq, g)
    _, _, after = t.pull(ids, max_unique=64)
    assert (after[: uniq.size] < before).all()


def test_pipelined_session_trains():
    """run_pipelined (the DownpourWorker thread model: prefetch pull +
    async push) trains the same CTR model; bounded-staleness updates
    still converge and every batch's rows get pushed."""
    main, startup = Program(), Program()
    loss = build_ctr(main, startup)
    table = HostEmbeddingTable(100_000, 8, lr=0.1, optimizer="adagrad",
                               seed=3)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe.run(startup)
        sess = HostTableSession(
            exe, main, {"ctr_table": (table, "ids", 64)}
        )
        feed = batch(rng, 100_000)
        losses = [
            float(out[0].reshape(-1)[0])
            for out in sess.run_pipelined(
                (dict(feed) for _ in range(15)), fetch_list=[loss]
            )
        ]
    assert len(losses) == 15
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.8, losses
    uniq = np.unique(feed["ids"])
    assert np.abs(table.rows[uniq]).max() > 0


def test_pipelined_session_propagates_errors():
    main, startup = Program(), Program()
    loss = build_ctr(main, startup)
    table = HostEmbeddingTable(1000, 8, seed=1)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe.run(startup)
        sess = HostTableSession(
            exe, main, {"ctr_table": (table, "ids", 64)}
        )

        def bad_feeds():
            feed = batch(rng, 1000)
            yield feed
            bad = dict(feed)
            bad["ids"] = np.full_like(feed["ids"], -5)  # negative ids
            yield bad

        import pytest as _pytest

        with _pytest.raises(ValueError, match="negative feature ids"):
            for _ in sess.run_pipelined(bad_feeds(), fetch_list=[loss]):
                pass


# -- checkpoint/resume (reference checkpoint_notify_op.cc:49-87,
# io.py:306 _save_distributed_persistables) ---------------------------


def test_table_save_load_roundtrip(tmp_path):
    t = HostEmbeddingTable(5000, 4, lr=0.5, optimizer="adagrad", seed=3,
                           lazy_init=True)
    rng = np.random.RandomState(0)
    for _ in range(4):
        ids = rng.randint(0, 5000, (8, 3))
        uniq, _, block = t.pull(ids, max_unique=32)
        t.push(uniq, rng.rand(32, 4).astype("float32"))
    t.save(str(tmp_path), "tbl", num_shards=3)

    t2 = HostEmbeddingTable(5000, 4, lr=0.5, optimizer="adagrad", seed=99,
                            lazy_init=True)
    t2.load(str(tmp_path), "tbl")
    np.testing.assert_array_equal(t._initialized, t2._initialized)
    touched = np.flatnonzero(t._initialized)
    np.testing.assert_array_equal(t.rows[touched], t2.rows[touched])
    np.testing.assert_array_equal(t.g2sum[touched], t2.g2sum[touched])
    # restored rng: lazy-init of a fresh row draws identically
    u1, _, b1 = t.pull(np.array([4321]), 4)
    u2, _, b2 = t2.pull(np.array([4321]), 4)
    np.testing.assert_array_equal(b1, b2)


def test_table_load_rejects_mismatch(tmp_path):
    t = HostEmbeddingTable(100, 4, optimizer="sgd")
    t.save(str(tmp_path), "tbl")
    import pytest as _pytest

    t2 = HostEmbeddingTable(100, 4, optimizer="adagrad")
    with _pytest.raises(ValueError, match="optimizer"):
        t2.load(str(tmp_path), "tbl")
    t3 = HostEmbeddingTable(200, 4, optimizer="sgd")
    with _pytest.raises(ValueError, match="vocab_size"):
        t3.load(str(tmp_path), "tbl")


def test_kill_resume_ctr(tmp_path):
    """Kill a CTR run AFTER its mid-training checkpoint (SIGKILL, the
    reference's pserver-crash story) and resume from the checkpoint:
    the resumed losses must equal the uninterrupted run's exactly."""
    import json as _json
    import signal
    import subprocess
    import sys as _sys

    worker = os.path.join(os.path.dirname(__file__), "ckpt_worker.py")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo
    env.pop("XLA_FLAGS", None)

    def run(workdir, mode, timeout=420):
        return subprocess.run(
            [_sys.executable, worker, str(workdir), mode],
            env=env, capture_output=True, text=True, timeout=timeout,
        )

    def losses(out):
        return {
            _json.loads(l)["step"]: _json.loads(l)["loss"]
            for l in out.splitlines() if l.startswith("{")
        }

    full_dir = tmp_path / "full"
    full_dir.mkdir()
    p = run(full_dir, "full")
    assert p.returncode == 0 and "WORKER_DONE" in p.stdout, p.stdout + p.stderr
    full_losses = losses(p.stdout)

    kill_dir = tmp_path / "kill"
    kill_dir.mkdir()
    proc = subprocess.Popen(
        [_sys.executable, worker, str(kill_dir), "killed"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    seen = []
    try:
        for line in proc.stdout:
            seen.append(line)
            if line.startswith("CKPT_DONE"):
                break
        else:
            raise AssertionError(f"no CKPT_DONE: {''.join(seen)}")
        proc.send_signal(signal.SIGKILL)  # mid-training crash
        proc.wait(timeout=60)
    finally:
        proc.kill()
    assert proc.returncode == -signal.SIGKILL

    p = run(kill_dir, "resume")
    assert p.returncode == 0 and "WORKER_DONE" in p.stdout, p.stdout + p.stderr
    resumed = losses(p.stdout)
    assert sorted(resumed) == list(range(5, 10)), resumed
    for step in range(5, 10):
        np.testing.assert_allclose(
            resumed[step], full_losses[step], rtol=1e-6,
            err_msg=f"step {step} diverged after resume",
        )


def test_table_save_overwrite_is_atomic(tmp_path):
    t = HostEmbeddingTable(500, 4, optimizer="adagrad", seed=2,
                           lazy_init=True)
    t.pull(np.array([1, 2, 3]), 8)
    t.save(str(tmp_path), "tbl")
    t.push(np.array([1, 2, 3]), np.ones((8, 4), np.float32))
    t.pull(np.array([7]), 8)
    t.save(str(tmp_path), "tbl")  # overwrite: swap via @tmp/@old renames
    assert not os.path.isdir(str(tmp_path / "tbl@tmp"))
    assert not os.path.isdir(str(tmp_path / "tbl@old"))
    t2 = HostEmbeddingTable(500, 4, optimizer="adagrad", seed=9,
                            lazy_init=True)
    t2.load(str(tmp_path), "tbl")
    np.testing.assert_array_equal(t.rows[[1, 2, 3, 7]], t2.rows[[1, 2, 3, 7]])
    np.testing.assert_array_equal(t.g2sum[[1, 2, 3]], t2.g2sum[[1, 2, 3]])


# -- native table kernels (table_kernels.cc; GIL-free pull/push) -------


def test_native_table_kernels_match_numpy():
    from paddle_tpu.native import table_kernels as tk

    if not tk.available():
        import pytest

        pytest.skip("native toolchain unavailable")
    rng = np.random.RandomState(0)
    rows = rng.randn(100, 8).astype(np.float32)
    g2 = np.abs(rng.randn(100, 8)).astype(np.float32)
    uniq = np.array([3, 7, 42, 99], np.int64)
    grad = rng.randn(4, 8).astype(np.float32)

    out = np.zeros((4, 8), np.float32)
    assert tk.pull_rows(rows, uniq, out)
    np.testing.assert_array_equal(out, rows[uniq])

    rows_ref = rows.copy()
    rows_sgd = rows.copy()
    assert tk.push_sgd(rows_sgd, uniq, grad, 0.1)
    rows_ref[uniq] -= 0.1 * grad
    np.testing.assert_allclose(rows_sgd, rows_ref, rtol=1e-6)

    rows_ada = rows.copy()
    g2_ada = g2.copy()
    assert tk.push_adagrad(rows_ada, g2_ada, uniq, grad, 0.1, 1e-6)
    rows_ref2 = rows.copy()
    g2_ref = g2.copy()
    g2_ref[uniq] += grad * grad
    rows_ref2[uniq] -= 0.1 * grad / np.sqrt(g2_ref[uniq] + 1e-6)
    np.testing.assert_allclose(rows_ada, rows_ref2, rtol=1e-5)
    np.testing.assert_allclose(g2_ada, g2_ref, rtol=1e-6)


def test_table_uses_native_path_equivalently(tmp_path):
    """The table's pull/push results are identical whether the native
    kernels or the numpy fallback run (memmap variant included)."""
    from paddle_tpu.native import table_kernels as tk

    rng = np.random.RandomState(1)
    ids = rng.randint(0, 500, (8, 3))
    grads = rng.rand(32, 4).astype(np.float32)

    def run_table(force_numpy, mmap_path=None):
        t = HostEmbeddingTable(500, 4, lr=0.2, optimizer="adagrad",
                               seed=7, mmap_path=mmap_path)
        if force_numpy:
            # disable the native path for this table's calls
            orig = tk._lib, tk._tried
            tk._lib, tk._tried = None, True
            try:
                uniq, remap, block = t.pull(ids, 32)
                t.push(uniq, grads[: 32])
            finally:
                tk._lib, tk._tried = orig
        else:
            uniq, remap, block = t.pull(ids, 32)
            t.push(uniq, grads[: 32])
        return uniq, remap, block, np.asarray(t.rows[np.unique(ids)]), \
            np.asarray(t.g2sum[np.unique(ids)])

    a = run_table(force_numpy=False)
    b = run_table(force_numpy=True)
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=1e-5)
    # memmap-backed rows take the same native pointer path (compare
    # against the memmap NUMPY path — lazy init draws rows in touch
    # order, so memmap values legitimately differ from the dense table)
    c = run_table(force_numpy=False, mmap_path=str(tmp_path / "t1.dat"))
    d = run_table(force_numpy=True, mmap_path=str(tmp_path / "t2.dat"))
    for x, y in zip(c, d):
        np.testing.assert_allclose(x, y, rtol=1e-5)


def test_pull_rejects_oob_and_float_ids():
    import pytest

    t = HostEmbeddingTable(100, 4, lazy_init=False)
    with pytest.raises(IndexError, match="vocab_size"):
        t.pull(np.array([5, 100]), 8)
    with pytest.raises(TypeError, match="integers"):
        t.pull(np.array([1.5, 2.0]), 8)
