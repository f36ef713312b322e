"""Elastic training supervisor tests (round 11).

Fast (tier-1): launch.py group semantics (first-nonzero exit code in
death order, kill-survivors, SIGTERM fan-out) against real subprocesses;
TrainSupervisor crash-respawn / hang-watchdog / restart-pacing /
orderly-stop drills against a lightweight simulated trainer (no JAX
import per worker — the drills test SUPERVISION, not training);
DataLoader cursor + seeded shuffle + manager cursor-manifest round trip;
a loader-driven in-process bitwise resume.

Slow (tools/ci.sh elastic-chaos stage): the acceptance gates — a REAL
supervised training job (tests/trainer_worker.py: dropout MLP, cursor-
tracked DataLoader, auto-resume) SIGKILLed at a pinned step via
`fleet.kill_trainer` and wedged at a pinned step via a seed-pinned
`trainer.step:hold=` worker fault; the completed run's per-step
(batch crc, loss) log must be bitwise-identical to an uninterrupted
run — no batch replayed or skipped — with bounded restarts and zero
orphan processes after supervisor exit.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import reader as rdr
from paddle_tpu.distributed.launch import spawn_workers, wait_group
from paddle_tpu.resilience import CheckpointManager, faults
from paddle_tpu.resilience.trainer_fleet import TrainSupervisor

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(TESTS_DIR)
WORKER = os.path.join(TESTS_DIR, "trainer_worker.py")

# -- the simulated trainer (supervision drills need processes that obey
# the progress-file contract, not processes that burn a JAX import) ----

SIM = """\
import json, os, signal, sys, time
att = int(os.environ.get("PADDLE_TPU_TRAINER_ATTEMPT", "0"))
rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
pf = os.environ.get("PADDLE_TPU_PROGRESS_FILE")
wd, mode = sys.argv[1], sys.argv[2]

def on_term(signum, frame):
    open(os.path.join(wd, f"term.{rank}.{att}"), "w").write("1")
    sys.exit(0)

# handler FIRST, ready marker AFTER: the pid file doubles as the "drain
# me" readiness signal — a SIGTERM that lands before the handler is
# installed would die rc -15 instead of draining (the round-12 flake:
# tests synchronizing on anything earlier raced the spawn)
signal.signal(signal.SIGTERM, on_term)
open(os.path.join(wd, f"pid.{rank}.{att}"), "w").write(str(os.getpid()))
open(os.path.join(wd, f"world.{rank}.{att}"), "w").write(
    os.environ.get("PADDLE_TPU_ELASTIC_WORLD", "?") + "/"
    + os.environ.get("PADDLE_TPU_BASE_WORLD", "?"))
if mode == "fail":
    sys.exit(2)
state = os.path.join(wd, f"state.{rank}")
start = int(open(state).read()) + 1 if os.path.exists(state) else 0
steps = int(os.environ.get("SIM_STEPS", "10"))
dt = float(os.environ.get("SIM_DT", "0.05"))
# SIM_HOLD: the step a drill pins. The first attempt writes it and waits
# there to be killed, so the supervisor's poll reads the step however late
# it is scheduled (no tick is long enough under six workers' compiles).
hold = int(os.environ.get("SIM_HOLD", "-1"))
# SIM_SEEN: a file a respawned attempt waits for before it exits; the test
# writes it once the supervisor has read a step of that attempt
seen = os.environ.get("SIM_SEEN")
for step in range(start, steps):
    # the state whole and first: the supervisor may kill on the very read
    # of the progress file, and the respawn resumes from what is on disk
    with open(state + ".tmp", "w") as f:
        f.write(str(step))
    os.replace(state + ".tmp", state)
    if pf:
        tmp = pf + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"step": step, "tick": step + 1,
                       "pid": os.getpid()}, f)
        os.replace(tmp, pf)
    if mode in ("crash", "crashmate") and att == 0 and rank == 0 \\
            and step == 4:
        sys.exit(7)
    if mode == "hang" and att == 0 and rank == 0 and step == 3:
        time.sleep(600)
    if mode == "crashmate" and att == 0 and rank == 1 and step == 2:
        time.sleep(600)
    if att == 0 and step == hold:
        time.sleep(600)
    time.sleep(dt)
if seen and att > 0:
    deadline = time.monotonic() + 60
    while not os.path.exists(seen) and time.monotonic() < deadline:
        time.sleep(0.01)
print("DONE", flush=True)
"""


def _sim(tmp_path):
    path = str(tmp_path / "sim.py")
    with open(path, "w") as f:
        f.write(SIM)
    return path


def _pids(tmp_path):
    out = {}
    for n in os.listdir(tmp_path):
        if n.startswith("pid."):
            try:
                out[n[4:]] = int(open(tmp_path / n).read())
            except (OSError, ValueError):
                pass  # caught the worker mid-write; next poll sees it
    return out


def _assert_no_orphans(tmp_path):
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        alive = [
            (k, p) for k, p in _pids(tmp_path).items() if _alive(p)
        ]
        if not alive:
            return
        time.sleep(0.05)
    raise AssertionError(f"orphan worker processes survived: {alive}")


def _alive(pid):
    try:
        os.kill(pid, 0)
        return True
    except OSError:
        return False


def _sup(tmp_path, argv, **kw):
    kw.setdefault("hang_timeout_s", 8.0)
    kw.setdefault("start_timeout_s", 30.0)
    kw.setdefault("min_uptime_s", 0.05)
    kw.setdefault("respawn_base_delay_s", 0.01)
    kw.setdefault("respawn_max_delay_s", 0.05)
    kw.setdefault("workdir", str(tmp_path / "supwd"))
    return TrainSupervisor(argv, **kw)


def _run_seen(sup, tmp_path):
    """`sup.run()` of a supervisor whose sims were given `_seen_env`: a
    respawned sim stays alive until the supervisor has read a step of it
    (the gauge `trainer_resume_step` is set on that read), so an assertion
    on the resume step or on a time to recover does not race the few ticks
    the respawned attempt has left to run."""
    done = threading.Event()

    def release():
        while ("trainer_resume_step" not in sup.counters.snapshot()
               and not done.wait(0.02)):
            pass
        (tmp_path / "seen").write_text("1")

    releaser = threading.Thread(target=release, daemon=True)
    releaser.start()
    try:
        return sup.run()
    finally:
        done.set()
        releaser.join()


def _seen_env(tmp_path, **env):
    return dict(env, SIM_SEEN=str(tmp_path / "seen"))


# ------------------------------------------------------------- launch.py


def test_launch_cli_propagates_exit_code_and_kills_survivors(tmp_path):
    """Satellite gate: rank 1 exits 3 while rank 0 would run for
    minutes — the launcher must return 3 promptly (first nonzero code
    in DEATH order, not rank order) and leave no surviving rank."""
    script = str(tmp_path / "crash_rank1.py")
    with open(script, "w") as f:
        f.write(
            "import os, sys, time\n"
            "rank = int(os.environ['PADDLE_TRAINER_ID'])\n"
            "open(f'{sys.argv[1]}/pid.{rank}', 'w')"
            ".write(str(os.getpid()))\n"
            "if rank == 1:\n"
            "    time.sleep(0.3); sys.exit(3)\n"
            "time.sleep(600)\n"
        )
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node=2", script, str(tmp_path)],
        env=env, timeout=120)
    elapsed = time.monotonic() - t0
    assert proc.returncode == 3
    assert elapsed < 60  # never waited behind rank 0's sleep(600)
    _assert_no_orphans(tmp_path)


def test_launch_cli_sigterm_fans_out_to_all_ranks(tmp_path):
    script = str(tmp_path / "drain.py")
    with open(script, "w") as f:
        f.write(
            "import os, signal, sys, time\n"
            "rank = int(os.environ['PADDLE_TRAINER_ID'])\n"
            "wd = sys.argv[1]\n"
            "def t(s, f):\n"
            "    open(f'{wd}/term.{rank}', 'w').write('1')\n"
            "    sys.exit(0)\n"
            "signal.signal(signal.SIGTERM, t)\n"
            "open(f'{wd}/pid.{rank}', 'w').write(str(os.getpid()))\n"
            "time.sleep(600)\n"
        )
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node=2", script, str(tmp_path)], env=env)
    deadline = time.monotonic() + 60
    while len(_pids(tmp_path)) < 2:
        assert time.monotonic() < deadline, "ranks never spawned"
        time.sleep(0.05)
    proc.send_signal(signal.SIGTERM)
    assert proc.wait(timeout=60) == 0  # every rank drained cleanly
    assert (tmp_path / "term.0").exists() and (tmp_path / "term.1").exists()
    _assert_no_orphans(tmp_path)


def test_wait_group_first_nonzero_in_death_order(tmp_path):
    """In-process wait_group: the FIRST death's code wins even when a
    lower rank later exits differently."""
    script = str(tmp_path / "w.py")
    with open(script, "w") as f:
        f.write(
            "import os, sys, time\n"
            "rank = int(os.environ['PADDLE_TRAINER_ID'])\n"
            "time.sleep(0.2 if rank == 1 else 5.0)\n"
            "sys.exit(9 if rank == 1 else 4)\n"
        )
    procs = spawn_workers([script], ["h:1", "h:2"], 0, 2)
    try:
        assert wait_group(procs, kill_grace_s=1.0) == 9
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()


# ------------------------------------------------------- TrainSupervisor


def test_supervisor_crash_respawn_resume_and_counters(tmp_path):
    sup = _sup(tmp_path, [_sim(tmp_path), str(tmp_path), "crash"],
               extra_env=_seen_env(tmp_path))
    try:
        assert _run_seen(sup, tmp_path) == 0
    finally:
        sup.close()
    stats = sup.stats()
    assert stats["restarts"] == 1
    c = stats["counters"]
    assert c["trainer_crashes"] == 1 and c["trainer_restarts"] == 1
    # the sim checkpoints each step: the respawn resumed past the crash
    assert c["trainer_resume_step"] >= 4
    assert c["train_mttr_ms"] >= 0
    _assert_no_orphans(tmp_path)


def test_supervisor_watchdog_detects_hang_within_deadline(tmp_path):
    sup = _sup(tmp_path, [_sim(tmp_path), str(tmp_path), "hang"],
               hang_timeout_s=1.0)
    t0 = time.monotonic()
    try:
        assert sup.run() == 0
    finally:
        sup.close()
    elapsed = time.monotonic() - t0
    c = sup.stats()["counters"]
    assert c["trainer_hangs_detected"] == 1
    assert c["trainer_restarts"] == 1
    # wedge at ~0.2s + 1s deadline + respawn + ~0.5s to finish: the
    # watchdog fired within its configured deadline, not at some
    # multiple of it
    assert elapsed < 15, elapsed
    _assert_no_orphans(tmp_path)  # the sleep(600) rank was SIGKILLed


def test_supervisor_coordinated_kill_of_surviving_ranks(tmp_path):
    """2-rank job: rank 0 crashes (exit 7 at step 4) while rank 1 is
    wedged in a fake collective (sleep 600 at step 2). The supervisor
    must SIGKILL the wedged survivor — not wait behind it — then
    respawn BOTH ranks and finish the job."""
    sup = _sup(tmp_path, [_sim(tmp_path), str(tmp_path), "crashmate"],
               nproc_per_node=2, started_port=6270,
               extra_env={"SIM_STEPS": "6"})
    t0 = time.monotonic()
    try:
        assert sup.run() == 0
    finally:
        sup.close()
    c = sup.stats()["counters"]
    assert c["trainer_crashes"] == 1 and c["trainer_restarts"] == 1
    assert time.monotonic() - t0 < 30  # never waited on the sleep(600)
    _assert_no_orphans(tmp_path)


def test_supervisor_max_restarts_and_fast_crash_breaker(tmp_path):
    sup = _sup(tmp_path, [_sim(tmp_path), str(tmp_path), "fail"],
               max_restarts=3, breaker_threshold=2)
    try:
        assert sup.run() == 2  # the workers' code, not a swallowed 0/1
    finally:
        sup.close()
    stats = sup.stats()
    assert stats["restarts"] == 3
    # every attempt died before min_uptime/first heartbeat: the fast-
    # crash breaker tripped and paced the loop
    assert sup.respawn_breaker.open
    _assert_no_orphans(tmp_path)


def test_supervisor_chaos_kill_at_pinned_step(tmp_path):
    """fleet.kill_trainer:nth=N SIGKILLs a trainer when global step N
    is first reached — once, never re-fired by the resumed attempt
    re-crossing old steps."""
    plan = faults.FaultPlan(seed=7).add(
        "fleet.kill_trainer", raises="FaultError", nth=6)
    with faults.active(plan):
        sup = _sup(tmp_path, [_sim(tmp_path), str(tmp_path), "full"],
                   extra_env=_seen_env(tmp_path, SIM_HOLD="6"))
        try:
            assert _run_seen(sup, tmp_path) == 0
        finally:
            sup.close()
    c = sup.stats()["counters"]
    assert c["trainer_chaos_kills"] == 1
    assert plan.fired.get("fleet.kill_trainer") == 1
    assert c["trainer_crashes"] == 1 and c["trainer_restarts"] == 1
    assert c["trainer_resume_step"] >= 6
    _assert_no_orphans(tmp_path)


def test_supervisor_stop_request_drains_without_respawn(tmp_path):
    sup = _sup(tmp_path, [_sim(tmp_path), str(tmp_path), "full"],
               extra_env={"SIM_STEPS": "1000", "SIM_DT": "0.05"},
               term_grace_s=10.0)

    def stop_when_ready():
        # synchronize on the sim's ready marker (written only AFTER its
        # SIGTERM handler is installed) instead of racing the spawn with
        # a fixed timer — on a loaded box the old 0.5 s timer could beat
        # the handler install and the fan-out SIGTERM killed the worker
        # rc -15 (the round-12 known flake)
        deadline = time.monotonic() + 60
        while not _pids(tmp_path):
            if time.monotonic() > deadline:
                break
            time.sleep(0.02)
        sup.request_stop()

    threading.Thread(target=stop_when_ready, daemon=True).start()
    try:
        rc = sup.run()
    finally:
        sup.close()
    assert rc == 0  # SIGTERM fan-out -> sim's handler exits 0
    assert sup.stats()["restarts"] == 0
    assert any(n.startswith("term.") for n in os.listdir(tmp_path))
    _assert_no_orphans(tmp_path)


# ------------------------------------------------- shrink policy (fast)


def test_shrink_candidates_are_proper_divisors():
    from paddle_tpu.distributed.launch import shrink_candidates

    assert shrink_candidates(8) == [4, 2, 1]
    assert shrink_candidates(6) == [3, 2, 1]
    assert shrink_candidates(1) == []
    assert shrink_candidates(7) == [1]  # primes can only collapse to 1


def _world_markers(tmp_path):
    out = {}
    for n in os.listdir(tmp_path):
        if n.startswith("world."):
            _, rank, att = n.split(".")
            out[(int(rank), int(att))] = (tmp_path / n).read_text()
    return out


def test_supervisor_host_loss_shrinks_world(tmp_path):
    """fleet.kill_host at a pinned step: the 2-rank job loses a host,
    and the supervisor relaunches the SURVIVING world at 1 rank instead
    of respawning at full width — env contract re-derived, counters
    account the shrink, the job still completes."""
    plan = faults.FaultPlan(seed=7).add(
        "fleet.kill_host", raises="FaultError", nth=3)
    with faults.active(plan):
        sup = _sup(tmp_path, [_sim(tmp_path), str(tmp_path), "full"],
                   nproc_per_node=2, started_port=6470,
                   allow_shrink=True,
                   extra_env=_seen_env(tmp_path, SIM_STEPS="8",
                                       SIM_HOLD="3"))
        try:
            assert _run_seen(sup, tmp_path) == 0
        finally:
            sup.close()
    stats = sup.stats()
    c = stats["counters"]
    assert plan.fired.get("fleet.kill_host") == 1
    assert c["trainer_host_losses"] == 1
    assert c["trainer_shrinks"] == 1
    assert c["trainer_world_size"] == 1
    assert stats["world_size"] == 1 and stats["base_world"] == 2
    assert stats["restarts"] == 1
    assert c["mesh_shrink_mttr_ms"] >= 0
    # the elastic env contract: attempt 0 ran 2/2, attempt 1 ran 1/2
    worlds = _world_markers(tmp_path)
    assert worlds[(0, 0)] == "2/2" and worlds[(1, 0)] == "2/2"
    assert worlds[(0, 1)] == "1/2"
    assert (1, 1) not in worlds  # rank 1 was not respawned
    _assert_no_orphans(tmp_path)


def test_supervisor_budget_exhaustion_shrinks_then_gives_up(tmp_path):
    """With allow_shrink, exhausting the per-world restart budget steps
    the world down (2 -> 1) with a FRESH budget instead of giving up;
    only when no smaller world remains does the supervisor exit with
    the workers' code."""
    sup = _sup(tmp_path, [_sim(tmp_path), str(tmp_path), "fail"],
               nproc_per_node=2, started_port=6480,
               max_restarts=2, allow_shrink=True, breaker_threshold=100)
    try:
        assert sup.run() == 2
    finally:
        sup.close()
    stats = sup.stats()
    c = stats["counters"]
    # 2 restarts at world 2 exhaust the budget -> shrink -> 2 more at
    # world 1 exhaust it again with nothing smaller left
    assert c["trainer_shrinks"] == 1
    assert stats["world_size"] == 1
    assert stats["restarts"] == 4
    # marker presence per (rank, attempt) is racy — the coordinated
    # kill can beat a sibling's first write — but any marker that DID
    # land must show the width of its attempt: 2/2 before the shrink
    # (attempts 0-2), 1/2 after (attempts 3-4, rank 0 only)
    worlds = _world_markers(tmp_path)
    for (rank, att), marker in worlds.items():
        assert marker == ("2/2" if att <= 2 else "1/2"), (rank, att,
                                                          marker)
    # the post-shrink attempts are single-rank and die FIRST (nothing
    # races their writes): their markers are always observable
    assert worlds[(0, 3)] == "1/2" and worlds[(0, 4)] == "1/2"
    _assert_no_orphans(tmp_path)


def test_supervisor_host_loss_without_shrink_respawns_full(tmp_path):
    """allow_shrink off (the default): fleet.kill_host degrades to a
    plain kill-and-respawn at the original width — existing jobs see no
    behavior change."""
    plan = faults.FaultPlan(seed=7).add(
        "fleet.kill_host", raises="FaultError", nth=3)
    with faults.active(plan):
        sup = _sup(tmp_path, [_sim(tmp_path), str(tmp_path), "full"],
                   nproc_per_node=2, started_port=6490,
                   extra_env={"SIM_STEPS": "6", "SIM_HOLD": "3"})
        try:
            assert sup.run() == 0
        finally:
            sup.close()
    stats = sup.stats()
    c = stats["counters"]
    assert c["trainer_host_losses"] == 1
    assert "trainer_shrinks" not in c
    assert stats["world_size"] == 2
    worlds = _world_markers(tmp_path)
    assert worlds[(0, 1)] == "2/2" and worlds[(1, 1)] == "2/2"
    _assert_no_orphans(tmp_path)


# ---------------------------------------------- exactly-resumable reader


def _mk_loader(on_bad_sample="raise"):
    x = fluid.layers.data("x", [2])

    def samples():
        for i in range(20):
            yield (np.full(2, i, "float32"),)

    loader = rdr.DataLoader.from_generator([x], capacity=4,
                                           on_bad_sample=on_bad_sample)
    loader.set_sample_generator(samples, batch_size=4, shuffle_buf=8,
                                shuffle_seed=5)
    return loader


def test_dataloader_cursor_midepoch_rewind_bitwise():
    loader = _mk_loader()
    epoch0 = [np.asarray(f["x"]).copy() for f in loader()]
    assert loader.state_dict() == {"epoch": 1, "batch": 0,
                                   "shuffle_seed": 5}
    resumed_loader = _mk_loader()
    resumed_loader.set_state_dict({"epoch": 0, "batch": 2,
                                   "shuffle_seed": 5})
    resumed = [np.asarray(f["x"]) for f in resumed_loader()]
    assert len(resumed) == len(epoch0) - 2
    for got, want in zip(resumed, epoch0[2:]):
        np.testing.assert_array_equal(got, want)


def test_dataloader_seeded_shuffle_differs_per_epoch_replays_per_seed():
    a, b = _mk_loader(), _mk_loader()
    ep0_a = [np.asarray(f["x"]).copy() for f in a()]
    ep1_a = [np.asarray(f["x"]).copy() for f in a()]
    ep0_b = [np.asarray(f["x"]).copy() for f in b()]
    # same seed + epoch -> identical permutation across loader instances
    for x, y in zip(ep0_a, ep0_b):
        np.testing.assert_array_equal(x, y)
    # different epoochs -> different permutation (same multiset)
    assert any(not np.array_equal(x, y) for x, y in zip(ep0_a, ep1_a))
    assert (sorted(np.concatenate(ep0_a).ravel().tolist())
            == sorted(np.concatenate(ep1_a).ravel().tolist()))


def test_manager_tracks_reader_cursor_in_manifest_and_rewinds(tmp_path):
    from paddle_tpu.resilience.snapshot import (
        list_snapshots,
        read_manifest,
    )
    from paddle_tpu.scope import Scope

    loader = _mk_loader()
    it = iter(loader)
    next(it), next(it), next(it)  # consume 3 batches
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.track_reader(loader, "train")
    mgr.save(0, state={"w": np.zeros(2, np.float32)})
    manifest = read_manifest(list_snapshots(str(tmp_path))[0][1])
    assert manifest["extra"]["reader_cursors"]["train"] == {
        "epoch": 0, "batch": 3, "shuffle_seed": 5}
    # drain the epoch (cursor moves on) ...
    for _ in it:
        pass
    assert loader.state_dict()["epoch"] == 1
    # ... then restore: the tracked loader rewinds to the manifest
    mgr2 = CheckpointManager(str(tmp_path), async_save=False)
    mgr2.track_reader(loader, "train")
    assert mgr2.restore(scope=Scope()) == 0
    assert loader.state_dict() == {"epoch": 0, "batch": 3,
                                   "shuffle_seed": 5}


def test_loader_driven_training_resume_bitwise(tmp_path):
    """Tier-1 tentpole gate (in-process flavor of the ci.sh chaos
    stage): interrupt a loader-fed dropout training run, resume from
    the snapshot — losses AND batch bytes must continue bitwise, the
    data cursor included."""
    import shutil
    import zlib

    from paddle_tpu import layers
    from paddle_tpu.resilience.snapshot import list_snapshots

    def build():
        main = fluid.default_main_program()
        main.random_seed = 7
        x = layers.data("x", [6])
        y = layers.data("y", [1])
        h = layers.dropout(layers.fc(x, 16, act="relu"), dropout_prob=0.3)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(layers.fc(h, 1), y))
        fluid.optimizer.Adam(1e-2).minimize(loss)

        def samples():
            for i in range(32):
                rs = np.random.RandomState(500 + i)
                xv = rs.rand(6).astype("float32")
                yield (xv, np.asarray([xv.sum()], "float32"))

        loader = rdr.DataLoader.from_generator([x, y], capacity=4)
        loader.set_sample_generator(samples, batch_size=8, drop_last=True,
                                    shuffle_buf=16, shuffle_seed=3)
        return main, loss, loader

    def run(root, upto=None):
        main, loss, loader = build()
        exe = fluid.Executor(fluid.CPUPlace())
        mgr = CheckpointManager(root, save_interval=1, keep=10)
        mgr.track_reader(loader, "train")
        mgr.restore_or_initialize(exe, main,
                                  fluid.default_startup_program())
        mgr.attach(main)
        out, step = [], 0
        for epoch in range(loader.state_dict()["epoch"], 3):
            for feed in loader():
                crc = zlib.crc32(np.asarray(feed["x"]).tobytes())
                (lv,) = exe.run(main, feed=feed, fetch_list=[loss])
                out.append((epoch, loader.state_dict()["batch"] - 1,
                            crc, float(np.asarray(lv).reshape(-1)[0])))
        mgr.drain()
        mgr.close()
        return out

    import paddle_tpu.scope as scope_mod

    full = run(str(tmp_path / "full"))
    assert len(full) == 12  # 3 epochs x 4 batches

    # interrupted flavor: run fully, then throw away everything after
    # step 5's snapshot (epoch 1, batch 1) — the moral SIGKILL — and
    # resume in a FRESH scope/program/loader
    chaos_root = str(tmp_path / "chaos")
    with scope_mod.scope_guard(scope_mod.Scope()):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            with fluid.unique_name.guard():
                first = run(chaos_root)
    assert first == full
    for st, path in list_snapshots(chaos_root):
        if st > 5:
            shutil.rmtree(path)
    with scope_mod.scope_guard(scope_mod.Scope()):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            with fluid.unique_name.guard():
                resumed = run(chaos_root)
    assert resumed == full[6:], (resumed, full[6:])


def test_dygraph_jit_path_heartbeats(tmp_path, monkeypatch):
    """A supervised dygraph-JIT training loop must heartbeat too — the
    watchdog would otherwise read a healthy dygraph job as hung."""
    from paddle_tpu import dygraph
    from paddle_tpu.dygraph import nn, to_variable
    from paddle_tpu.dygraph.jit import TracedLayer

    hb = tmp_path / "hb.json"
    monkeypatch.setenv("PADDLE_TPU_PROGRESS_FILE", str(hb))
    with dygraph.guard():
        layer = nn.Linear(4, 2)
        _, traced = TracedLayer.trace(
            layer, [to_variable(np.ones((2, 4), "float32"))])
        for _ in range(2):
            traced([to_variable(np.ones((2, 4), "float32"))])
        data = json.loads(hb.read_text())
    assert data["tick"] >= 2
    assert "step" not in data  # dygraph has no manager-counted step


def test_compiled_program_mesh_path_heartbeats(tmp_path, monkeypatch):
    """The multi-rank/mesh dispatch path (CompiledProgram._run — the
    TrainSupervisor's main customer) must heartbeat like Executor.run,
    or the watchdog reads a healthy distributed job as hung."""
    hb = tmp_path / "hb.json"
    monkeypatch.setenv("PADDLE_TPU_PROGRESS_FILE", str(hb))
    x = fluid.layers.data("x", [8])
    y = fluid.layers.data("y", [1])
    pred = fluid.layers.fc(x, 1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    main = fluid.default_main_program()
    cp = fluid.CompiledProgram(main).with_data_parallel()
    mgr = CheckpointManager(str(tmp_path / "ck"), save_interval=100)
    mgr.attach(main)
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(16, 8).astype("float32"),
            "y": rng.randn(16, 1).astype("float32")}
    exe.run(cp, feed=feed, fetch_list=[loss])
    data = json.loads(hb.read_text())
    assert data["tick"] >= 1
    assert data["step"] == 0  # the manager-counted training step
    mgr.close()


# --------------------------------------------- the ci.sh elastic gates


def _read_jsonl(path):
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line.endswith("}"):  # a SIGKILL may tear the last line
                out.append(json.loads(line))
    return out


def _assert_bitwise_vs_full(full_path, chaos_path):
    full = _read_jsonl(full_path)
    chaos = _read_jsonl(chaos_path)
    fm = {(r["epoch"], r["batch"]): (r["crc"], r["loss"]) for r in full}
    mismatches = [
        r for r in chaos
        if fm.get((r["epoch"], r["batch"])) != (r["crc"], r["loss"])
    ]
    covered = {(r["epoch"], r["batch"]) for r in chaos}
    assert not mismatches, mismatches[:4]
    assert covered == set(fm), (sorted(set(fm) - covered),
                                sorted(covered - set(fm)))
    return full, chaos


def _run_full(tmp_path):
    """Uninterrupted reference run of tests/trainer_worker.py."""
    result = str(tmp_path / "full.jsonl")
    env = dict(os.environ, ELASTIC_RESULT=result,
               PYTHONPATH=REPO_ROOT, JAX_PLATFORMS="cpu")
    env.pop("PADDLE_TPU_FAULTS", None)
    subprocess.run(
        [sys.executable, WORKER, str(tmp_path / "full_wd")],
        env=env, check=True, timeout=300)
    return result


@pytest.mark.slow
def test_elastic_sigkill_bitwise_resume(tmp_path):
    """Acceptance gate: SIGKILL a supervised trainer when a pinned
    global step is first reached -> the supervisor restarts it from the
    newest valid snapshot and the completed run's per-step fetch log is
    bitwise-equal to an uninterrupted run (data cursor included)."""
    full = _run_full(tmp_path)
    chaos = str(tmp_path / "chaos.jsonl")
    plan = faults.FaultPlan(seed=7).add(
        "fleet.kill_trainer", raises="FaultError", nth=8)
    with faults.active(plan):
        sup = TrainSupervisor(
            [WORKER, str(tmp_path / "chaos_wd")],
            hang_timeout_s=60.0, start_timeout_s=120.0,
            min_uptime_s=0.2, respawn_base_delay_s=0.05,
            respawn_max_delay_s=0.2, started_port=6370,
            workdir=str(tmp_path / "supwd"),
            log_dir=str(tmp_path / "logs"),
            extra_env={"ELASTIC_RESULT": chaos, "JAX_PLATFORMS": "cpu",
                       "PYTHONPATH": REPO_ROOT})
        try:
            rc = sup.run()
        finally:
            sup.close()
    assert rc == 0
    stats = sup.stats()
    c = stats["counters"]
    assert c["trainer_chaos_kills"] == 1
    assert 1 <= stats["restarts"] <= 2  # bounded, not a respawn storm
    assert c["train_mttr_ms"] > 0 and c["trainer_resume_step"] > 0
    _assert_bitwise_vs_full(full, chaos)
    # zero orphan workers after supervisor exit
    for r in stats["ranks"]:
        assert not r["alive"] and not _alive(r["pid"])


@pytest.mark.slow
def test_elastic_hang_watchdog_bitwise(tmp_path):
    """Acceptance gate: a hold-barrier-wedged step (heartbeat for step
    M never lands) is detected by the watchdog within the configured
    deadline and the job restarts to a bitwise-identical completion."""
    full = _run_full(tmp_path)
    chaos = str(tmp_path / "chaos.jsonl")
    never = str(tmp_path / "never-created-barrier")
    # attempt 0 wedges when trainer.step hit 8 holds on a barrier file
    # that never appears (the startup dispatch is hit 1, so training
    # step s is hit s+2: nth=8 wedges training step 6); attempt 1 runs
    # with no faults and must finish the job
    sup = TrainSupervisor(
        [WORKER, str(tmp_path / "chaos_wd")],
        hang_timeout_s=10.0, start_timeout_s=120.0,
        min_uptime_s=0.2, respawn_base_delay_s=0.05,
        respawn_max_delay_s=0.2, started_port=6380,
        workdir=str(tmp_path / "supwd"),
        log_dir=str(tmp_path / "logs"),
        worker_faults={0: f"trainer.step:hold={never}:nth=8"},
        extra_env={"ELASTIC_RESULT": chaos, "JAX_PLATFORMS": "cpu",
                   "PYTHONPATH": REPO_ROOT})
    t0 = time.monotonic()
    try:
        rc = sup.run()
    finally:
        sup.close()
    assert rc == 0
    stats = sup.stats()
    c = stats["counters"]
    assert c["trainer_hangs_detected"] == 1
    assert stats["restarts"] == 1
    # wedge ~ a few s in + 10 s deadline + one restart's import/compile:
    # generous cap proves the watchdog fired on ITS deadline, not the
    # 120 s hold timeout
    assert time.monotonic() - t0 < 90
    _assert_bitwise_vs_full(full, chaos)
    for r in stats["ranks"]:
        assert not r["alive"] and not _alive(r["pid"])
