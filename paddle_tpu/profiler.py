"""Profiler (reference: python/paddle/fluid/profiler.py:225,127,168 and
platform/profiler.h:81 RecordEvent spans, profiler.cc:322 tables).

A span (`RecordEvent`) is a `jax.profiler.TraceAnnotation`: while a
`jax.profiler` trace is being taken it lands in the trace's host plane, on
the clock the device's operations are on; between `start_profiler` and
`stop_profiler` it is also aggregated into the reference-style table.
To trace a running job, wrap some steps in
`profiler.profiler(trace_dir=...)` and open the `.xplane.pb` it leaves
there (TensorBoard, Perfetto, or `benchmark/harness/trace_reduce.py`);
the device side replaces the reference's CUPTI DeviceTracer
(platform/device_tracer.h:41). The `pt.*` spans the Executor and the
reader write, and the `fwd/ bwd/ opt/` scopes on device operations, are
listed in `PERF.md`. Each device operation also carries its FLOPs and
bytes, the Pallas kernels' as they declare them (`ops/pallas/cost.py`):
README, Profiling, says how to read them against the chip's peaks."""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from collections import defaultdict, deque

import numpy as np

import jax

__all__ = [
    "profiler",
    "start_profiler",
    "stop_profiler",
    "reset_profiler",
    "record_event",
    "RecordEvent",
    "bump_counter",
    "set_counter",
    "counters",
    "recorded_counters",
    "replay_counters",
    "time_counter",
    "hold_device_counts",
]

_events: dict[str, list[float]] = defaultdict(list)
_counters: dict[str, int] = defaultdict(int)  # monotonic named counts
# serving handler threads (server + fleet router) bump concurrently:
# the read-modify-write below is not atomic under the GIL, and a lost
# increment would make this global roll-up diverge from the per-
# instance CounterSet totals it promises to equal
_counters_lock = threading.Lock()
_recording = threading.local()  # .log: what `recorded_counters` collects
_active = False
_trace_dir = None


def bump_counter(name: str, amount: int = 1) -> int:
    """Monotonic named counter (always on, unlike spans — cache hit/miss
    accounting must not depend on the profiler being started). The
    dygraph JIT bridge bumps dygraph_jit_cache_hit / _miss /
    _fallback here so the per-op-dispatch-removed speedup is observable
    next to the span table."""
    log = getattr(_recording, "log", None)
    if log is not None:
        log.append(("bump", name, amount))
    with _counters_lock:
        _counters[name] += amount
        return _counters[name]


def set_counter(name: str, value: int) -> int:
    """Gauge-style counter assignment (always on, like bump_counter;
    the compile path's counters by stage and owner, and every counter a
    benchmark metric reads, are listed in `PERF.md`, section 3, not here):
    for values that REPLACE rather than accumulate — resilience sets
    `resume_step` to the step a restore landed on, so observers read the
    resume point, not a meaningless sum of resume points; the inference
    server keeps `serve_queue_depth` here as a live gauge. The bump_
    counter family also carries the resilience counters (ckpt_save_ms /
    ckpt_bytes / ckpt_async_overlap_ms / ckpt_snapshots_committed /
    nan_steps_skipped / nan_rollbacks / preemptions_observed /
    table_rpc_retries), the serving-robustness counters
    (serve_requests / serve_shed / serve_deadline_exceeded /
    serve_breaker_open / serve_breaker_trips / serve_breaker_recovered /
    serve_warmup_ms / serve_drains — kept per server instance and
    rolled up here), the round-14 continuous-batching counters
    (serve_batches via bump = coalesced predictor dispatches;
    serve_batch_members = requests those dispatches carried;
    serve_batch_size_p50 as a gauge = rolling median members/batch;
    serve_coalesce_wait_ms = summed member wait inside the admission
    gate; serve_batch_padded_rows = pad rows dispatched and discarded;
    serve_coalesce_bypass = requests whose deadline could not afford
    the window; serve_bucket_overflow = dispatches beyond the largest
    bucket at exact row count; serve_dispatch_ms_ewma as a gauge = the
    per-dispatch wall EWMA behind the derived Retry-After;
    executor_cache_evictions / dygraph_jit_cache_evictions = LRU
    evictions from the PADDLE_TPU_JIT_CACHE_CAP-bounded executable
    caches; and the KV-cache decode counters kv_slots_inflight as a
    gauge plus kv_slot_acquires / kv_slot_releases / kv_evictions /
    kv_admission_sheds / kv_decode_steps via bump — per RingKVCache
    CounterSet, rolled up here), the serving-fleet counters (fleet_spawns /
    fleet_replica_deaths / fleet_respawns / fleet_respawn_failures /
    fleet_route_requests / fleet_failovers / fleet_replica_503s /
    fleet_route_sheds / fleet_deadline_exceeded /
    fleet_rolling_restarts / fleet_chaos_kills /
    fleet_drain_timeouts — per-fleet dict rolled up the same way; the
    round-22 mixed-class family: fleet_diverts via bump = requests
    routed to the overflow backend class, with a per-reason breakdown
    fleet_diverts.deadline / fleet_diverts.brownout /
    fleet_diverts.tier_loss / fleet_diverts.chaos;
    fleet_brownout_steered / fleet_brownout_sheds = bulk-tenant
    requests steered to the overflow class / shed past the brownout
    shed watermark; fleet_tier_losses = entries into degraded mode
    (every primary-class replica dead or breaker-open); and
    fleet_degraded as a 0/1 gauge mirroring the router's current
    degraded state), the
    elastic-training counters (trainer_restarts / trainer_crashes /
    trainer_hangs_detected / trainer_chaos_kills / trainer_host_losses
    / trainer_shrinks via bump; trainer_resume_step = first step a
    restarted attempt heartbeats, train_mttr_ms =
    kill-to-first-resumed-step, trainer_world_size = the current
    attempt's elastic width and mesh_shrink_mttr_ms = host-loss kill to
    the SHRUNK world's first step as gauges — all per-TrainSupervisor
    CounterSet, rolled up here; the round-13 topology-elastic restore
    counters: restore_place_ms via bump = wall ms of the one batched
    device_put wave a mesh-aware restore issues, restore_resharded_vars
    / restore_degraded_vars as gauges = how many recorded-spec vars the
    last restore re-placed under a different mesh shape / degraded to
    replicated on a divisibility failure; the live-reshard counters
    table_reshards / reshard_rows_moved / table_reshard_ms via bump =
    DistributedEmbeddingTable.reshard invocations, rows streamed K->N,
    and wall ms; reader_bad_samples
    counts DataLoader on_bad_sample="skip" per-sample drops and
    reader_bad_batches whole-batch drops — raw batches, or batches
    with no single offender sample) and the table RPC hardening
    counters (table_shard_breaker_trips / table_shard_breaker_recovered
    / table_conns_reaped / table_malformed_frames), and the unified-mesh
    gauges (mesh_axes = non-trivial axis count, mesh_shape = device
    count, mesh_shape_batch / mesh_shape_model / mesh_shape_pipe,
    collective_bytes_estimate = crude per-step wire-traffic estimate;
    sharding_recompiles rides bump_counter — a program recompiling
    under a different mesh/spec signature), and the round-12 layout/
    dispatch counters (transpose_ops_before / transpose_ops_after as
    gauges = the traced step's activation-transpose count under NCHW IR
    vs after the pass, most recent compile: their difference is what
    layout_opt removed; attn_dispatch_short / _xla / _flash / _ring
    via bump = attention path chosen at trace time, fwd + grad replay
    each count; pallas_on_mesh_calls via bump = lowerings that run
    their Pallas kernel per shard of a data-parallel mesh
    (ops/pallas/on_mesh.py); reader_staged_batches via bump = batches the shared
    DeviceStager converted + device_put ahead of the consumer), and the
    round-15 static-analysis timer (pass_verify_us via time_counter =
    wall time the PADDLE_TPU_VERIFY IR-verifier hook spent across the
    input-program check and every after-pass check of a compile), and
    the round-16 autoshard gauge (autoshard_planned_vars = state vars
    the shard_propagation pass assigned a PartitionSpec on the most
    recent planned compile; 0 / absent when autoshard is off or the
    planner declined), and the round-17 streaming counters (per
    WriteBehindRowCache CounterSet, rolled up here: table_cache_hits /
    table_cache_misses / table_cache_evictions /
    table_cache_refreshed_rows = rows the background refresh-ahead
    re-pulled before they could expire, table_writebehind_flushes =
    applied delta generations / table_writebehind_flush_failures /
    table_writebehind_uncertain_rows = deltas dropped LOUDLY because
    their push outcome was unknowable after retries, via bump;
    table_dirty_rows / table_staleness_p99_ms / table_staleness_max_ms
    as gauges — the measured bounded-staleness contract;
    table_push_dedup_drops via bump = re-sent sequenced pushes the
    shard's (client_id, seq) dedup absorbed — each one is a retry that
    would have been a double-apply under the old protocol; plus the
    OnlineTrainer counters stream_clicks / stream_steps), and the
    round-19 disaggregated-serving counters (per PagedKVCache
    CounterSet, rolled up here: kv_page_allocs / kv_page_evictions =
    pages claimed at admission / reclaimed from LRU-evicted finished
    streams via bump, kv_pages_in_use / kv_decode_streams as live
    gauges of pool occupancy and registered decode jobs — NOTE the
    fleet's worker_counters() SUMS these across replicas, they are
    per-pool occupancies, not rates; the server role counters
    serve_prefill_requests / serve_prefill_dispatches /
    serve_prefill_tokens / serve_decode_requests /
    serve_generate_requests via bump, serve_prefill_queued_tokens as
    the prefill scheduler's queue gauge and serve_prefill_ms_ewma /
    serve_decode_ms_ewma as per-role dispatch-wall EWMAs; and the
    router handoff counters fleet_handoffs via bump,
    fleet_handoff_ms = summed router-side handoff overhead (stage-2
    wall minus the replica's own X-Decode-Ms), fleet_prefill_ms_ewma
    / fleet_decode_ms_ewma as router-observed stage gauges), and the
    round-20 counter (via bump: cross_kv_reuse =
    decoder cross-attention calls that consumed a precomputed
    encoder K/V pair instead of re-projecting it — one per layer per
    decode-step program build), and the round-21 multi-model serving
    counters (registry-side, all via bump: serve_deploys = hot-swap
    attempts a worker's ModelRegistry.deploy started,
    serve_deploy_failures = deploys aborted before cutover — drift
    gate, load failure, injected fault; the old version stayed
    authoritative — and serve_deploy_unloads = old runtimes drained
    and unloaded after a successful cutover; per-MODEL serve_*
    counters live in each ModelRuntime's own locked dict, surfaced on
    worker /healthz under `models` and folded by the fleet into
    `model.<name>.<counter>` families, NOT rolled up globally, so a
    single-model process's global totals stay identical; fleet-side:
    fleet_deploys / fleet_deploy_failures via bump, plus
    fleet_deploy_rollbacks = workers re-deployed back to the old
    version after a mid-fleet-deploy failure)."""
    log = getattr(_recording, "log", None)
    if log is not None:
        log.append(("set", name, int(value)))
    with _counters_lock:
        _counters[name] = int(value)
        return _counters[name]


def counters() -> dict:
    """Every counter as it stands, the device counts of the steps that
    have finished among them; a step still running is not waited for."""
    with _device_counts_lock:
        _fold_device_counts()
    with _counters_lock:
        return dict(_counters)


# Counts a compiled step makes on the device (`LoweringContext.count`:
# data, which `bump_counter`, running while the step is traced, cannot
# see). The Executor hands each dispatch's small int32 array here; it is
# added to the counters above, as exact Python ints, once the step that
# made it has finished: at a later dispatch or at `counters()`, whichever
# comes first, and never by waiting, unless the host has run
# `DEVICE_COUNTS_IN_FLIGHT` steps ahead of the device (the oldest step is
# then waited for: a bounded queue that loses nothing).
DEVICE_COUNTS_IN_FLIGHT = 64
_device_counts: deque = deque()  # (names, array) by dispatch, oldest first
_device_counts_lock = threading.Lock()


def hold_device_counts(names, array) -> None:
    """`array`: int32 `[len(names)]` of one step, or `[steps, len(names)]`
    of a `run_repeated` window, as the dispatch returned it."""
    with _device_counts_lock:
        _device_counts.append((tuple(names), array))
        _fold_device_counts(len(_device_counts) - DEVICE_COUNTS_IN_FLIGHT)


def _fold_device_counts(waiting_for=0) -> None:
    """The finished steps' counts into `_counters`, and the `waiting_for`
    oldest whether finished or not; steps finish in the order they were
    dispatched. Called with `_device_counts_lock` held."""
    while _device_counts and (waiting_for > 0
                              or _device_counts[0][1].is_ready()):
        names, array = _device_counts.popleft()
        waiting_for -= 1
        try:
            # replicated on a mesh, and on a fleet of processes not wholly
            # here: this process's first copy
            sums = np.asarray(array.addressable_data(0), np.int64).reshape(
                -1, len(names)).sum(axis=0)
        except Exception:  # noqa: BLE001 — a step that failed on the device
            # raises where its outputs are read: the training loop's to
            # meet, not a reader of counters'
            logging.getLogger(__name__).warning(
                "a step's device counts %s could not be read", names,
                exc_info=True)
            sums, names = [1], ("device_counts_dropped",)
        with _counters_lock:
            for name, total in zip(names, sums):
                _counters[name] += int(total)


@contextlib.contextmanager
def recorded_counters():
    """Every `bump_counter` and `set_counter` THIS thread makes inside the
    body, in order, as `("bump" | "set", name, value)`: what a trace said
    about its program, which `step_store` keeps beside the executable and
    `replay_counters` says again when the trace is not made. Another
    thread's bumps (the reader's stager runs beside a first call) are not
    in it."""
    before = getattr(_recording, "log", None)
    _recording.log = log = []
    try:
        yield log
    finally:
        _recording.log = before
        if before is not None:
            before.extend(log)


def replay_counters(log) -> None:
    for kind, name, value in log:
        (bump_counter if kind == "bump" else set_counter)(name, value)


class CounterSet:
    """Instance-scoped always-on counters that ALSO roll up into the
    process-global table above. The inference server and the serving
    fleet each own one: co-resident instances (two servers in one
    process, a router + supervisor sharing one) keep separable
    accounting on their own /healthz while existing global observers
    keep working."""

    def __init__(self):
        self._lock = threading.Lock()
        self._data: dict[str, int] = {}

    def bump(self, name: str, amount: int = 1) -> int:
        with self._lock:
            self._data[name] = self._data.get(name, 0) + amount
            out = self._data[name]
        bump_counter(name, amount)
        return out

    def gauge(self, name: str, value: int) -> int:
        with self._lock:
            self._data[name] = int(value)
        set_counter(name, value)
        return int(value)

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._data)


__all__ += ["CounterSet"]


@contextlib.contextmanager
def time_counter(name: str):
    """Always-on wall-time counter: the body's duration lands in the
    monotonic `<name>_us` counter (microseconds). Unlike RecordEvent
    spans this does not require start_profiler — the pass manager and
    the executor's compile path bump these unconditionally, like the
    dygraph_jit_* cache counters."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        bump_counter(name + "_us", int((time.perf_counter() - t0) * 1e6))


class RecordEvent:
    """RAII span (reference: platform/profiler.h:81): a TraceMe in the
    `jax.profiler` trace (an atomic load when none is being taken), and a
    row of the table while the profiler is started."""

    def __init__(self, name):
        self.name = name
        self._t0 = None
        self._annotation = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self._annotation.__exit__(*exc)
        if _active:
            _events[self.name].append(time.perf_counter() - self._t0)


record_event = RecordEvent


def start_profiler(state="All", tracer_option=None, trace_dir=None):
    """reference: profiler.py:127. trace_dir enables the device trace
    (jax.profiler) alongside host spans."""
    global _active, _trace_dir
    _active = True
    if trace_dir:
        _trace_dir = trace_dir
        jax.profiler.start_trace(trace_dir)


def stop_profiler(sorted_key="total", profile_path=None):
    """reference: profiler.py:168 — prints the aggregated span table."""
    global _active, _trace_dir
    _active = False
    if _trace_dir:
        jax.profiler.stop_trace()
        _trace_dir = None
    rows = []
    for name, ts in _events.items():
        total = sum(ts)
        rows.append((name, len(ts), total, total / len(ts), min(ts), max(ts)))
    keyidx = {"total": 2, "calls": 1, "ave": 3, "min": 4, "max": 5}.get(
        sorted_key, 2
    )
    rows.sort(key=lambda r: r[keyidx], reverse=True)
    lines = [
        f"{'Event':<40}{'Calls':>8}{'Total(s)':>12}{'Avg(s)':>12}"
        f"{'Min(s)':>12}{'Max(s)':>12}"
    ]
    for r in rows:
        lines.append(
            f"{r[0]:<40}{r[1]:>8}{r[2]:>12.6f}{r[3]:>12.6f}"
            f"{r[4]:>12.6f}{r[5]:>12.6f}"
        )
    csnap = counters()  # locked snapshot: fleet/server daemon threads
    if csnap:           # may be inserting new keys mid-report
        lines.append(f"{'Counter':<40}{'Count':>8}")
        for name in sorted(csnap):
            lines.append(f"{name:<40}{csnap[name]:>8}")
    table = "\n".join(lines)
    if profile_path:
        with open(profile_path, "w") as f:
            f.write(table)
    else:
        print(table)
    return rows


def reset_profiler():
    """reference: profiler.py:105."""
    _events.clear()
    with _device_counts_lock:
        _device_counts.clear()
    with _counters_lock:
        _counters.clear()


@contextlib.contextmanager
def profiler(state="All", sorted_key="total", profile_path=None,
             trace_dir=None):
    """reference: profiler.py:225 context manager."""
    start_profiler(state, trace_dir=trace_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def cuda_profiler(output_file, output_mode=None, config=None):
    """reference: profiler.py cuda_profiler — CUDA nvprof capture. Ⓝ on
    TPU: the xplane trace (start/stop_profiler + jax.profiler) is the
    device-side profile; this shim warns and runs the body."""
    import warnings

    warnings.warn(
        "cuda_profiler is CUDA-specific; on TPU use profiler.profiler() "
        "or jax.profiler.trace for device profiles", stacklevel=2)
    del output_file, output_mode, config
    yield


__all__ += ["cuda_profiler"]
