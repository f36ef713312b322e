"""Online train-while-serve driver for the streaming CTR scenario.

The reference's async parameter-server mode trains CTR models on a
never-ending click stream while the same tables serve lookups
(DownpourWorker device_worker.h:175 + the geo/async strategies of
fleet/parameter_server). TPU-native shape: ONE process owns the
training loop — clicks stream through the compiled executor step, the
sparse table rides a `WriteBehindRowCache` over the sharded table — and
any number of serving clients (replica processes or threads holding
their own `DistributedEmbeddingTable` / read cache) answer lookups
against the SAME shard servers. Staleness between the two is bounded
and measured by the cache (`table_staleness_p99_ms`).

`OnlineTrainer` wraps `HostTableSession` (the pull -> run -> push device
worker loop) and adds the streaming contract:

- chaos site `stream.click` fires once per click batch BEFORE the train
  step — `raise`/`hold` pin crashes and wedges at exact positions in
  the click stream (the streaming analog of `trainer.step`);
- counters `stream_clicks` (examples consumed) and `stream_steps`
  (train steps) via a profiler.CounterSet, plus the cache's staleness
  gauges surfaced through `stats()`;
- `run()` for synchronous draining and `start()`/`stop()` for the
  train-while-serve arrangement (training on a background thread while
  the caller measures the serving side).

`zipf_ids` is THE seeded Zipf id generator for every streaming drill:
ids are drawn by inverse-CDF
over the truncated zipf(s) mass on [0, vocab), so the same
(seed, vocab, s) always yields the same hot set — rank r has mass
proportional to 1/(r+1)^s, id 0 hottest.
"""

from __future__ import annotations

import threading

import numpy as np

from paddle_tpu import profiler
from paddle_tpu.incubate.fleet.parameter_server.host_table import (
    HostTableSession,
)
from paddle_tpu.resilience.faults import fault_point

__all__ = ["OnlineTrainer", "zipf_ids", "click_stream"]


_ZIPF_CDFS: dict = {}  # (vocab, s) -> cdf; ~400 KB per 50k-vocab entry


def zipf_ids(rng, n, vocab, s=1.1):
    """Draw `n` ids from a truncated Zipf(s) over [0, vocab): seeded,
    vectorized inverse-CDF sampling (np.random.zipf is unbounded and
    cannot be truncated without rejection bias). The CDF is memoized
    per (vocab, s) — recomputing a vocab-sized cumsum per draw batch
    would dwarf the hot-path work the streaming bench measures."""
    vocab = int(vocab)
    key = (vocab, float(s))
    cdf = _ZIPF_CDFS.get(key)
    if cdf is None:
        mass = np.arange(1, vocab + 1, dtype=np.float64) ** (-float(s))
        cdf = np.cumsum(mass)
        cdf /= cdf[-1]
        if len(_ZIPF_CDFS) < 32:  # bound the memo
            _ZIPF_CDFS[key] = cdf
    u = rng.rand(int(n))
    return np.searchsorted(cdf, u, side="left").astype(np.int64)


def click_stream(seed, vocab, batch=64, slots=2, dense_dim=4, s=1.1,
                 max_batches=None, ids_name="ids", dense_name="dense",
                 label_name="label"):
    """Seeded synthetic click generator: Zipf ids + dense features +
    click labels, shaped for the canned CTR program (the `_build_ctr`
    layout the table tests and bench share). Infinite unless
    `max_batches` caps it; bit-identical per (seed, ...) config."""
    rng = np.random.RandomState(seed)
    i = 0
    while max_batches is None or i < max_batches:
        ids = zipf_ids(rng, batch * slots, vocab, s).reshape(batch, slots)
        yield {
            ids_name: ids,
            dense_name: rng.rand(batch, dense_dim).astype("float32"),
            label_name: (rng.rand(batch, 1) > 0.5).astype("float32"),
        }
        i += 1


class OnlineTrainer:
    """Streams click batches through the executor into the sparse table
    (via whatever table/cache object `tables` names) while the serving
    side reads the same shards.

    tables: {table_name: (table_or_cache, ids_feed_name, max_unique)} —
    the HostTableSession spec; pass the WriteBehindRowCache as the
    table to get write-behind + bounded staleness."""

    def __init__(self, exe, program, tables, fetch_list=()):
        self._session = HostTableSession(exe, program, tables)
        self._tables = dict(tables)
        self._fetch = list(fetch_list)
        self._counters = profiler.CounterSet()
        self._stop = threading.Event()
        self._thread = None
        self._error = None
        self.last_fetches = None

    def step(self, feed):
        """One click batch: fault site -> pull -> train step -> push
        (write-behind when the table is a cache). Returns the user
        fetches."""
        fault_point("stream.click")
        first_ids = next(iter(self._tables.values()))[1]
        clicks = int(np.asarray(feed[first_ids]).shape[0])
        outs = self._session.run(feed, fetch_list=self._fetch)
        self._counters.bump("stream_clicks", clicks)
        self._counters.bump("stream_steps")
        self.last_fetches = outs
        return outs

    def run(self, feed_iter, max_steps=None):
        """Drain `feed_iter` synchronously (until exhausted, `max_steps`,
        or stop()); returns the number of steps run."""
        steps = 0
        for feed in feed_iter:
            if self._stop.is_set():
                break
            self.step(feed)
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return steps

    # -- train-while-serve ------------------------------------------------
    def start(self, feed_iter, max_steps=None):
        """Run the stream on a background thread (the caller's thread is
        then free to drive/measure the serving side). stop() + join via
        stop(); a crashed stream re-raises there."""
        if self._thread is not None:
            raise RuntimeError("online trainer already running")
        self._stop.clear()
        self._error = None

        def _loop():
            try:
                self.run(feed_iter, max_steps=max_steps)
            except BaseException as e:  # noqa: BLE001 — re-raised in stop()
                # stop() reads this only after Thread.join establishes
                # the happens-before edge; no lock needed
                self._error = e  # provlint: disable=thread-shared-write-unguarded

        self._thread = threading.Thread(
            target=_loop, daemon=True, name="online_trainer")
        self._thread.start()
        return self

    def wait(self, timeout=None):
        """Block until a start()ed stream exhausts itself (finite
        streams / max_steps) WITHOUT signalling it to stop early; call
        stop() afterwards to drain and surface errors."""
        t = self._thread
        if t is not None:
            t.join(timeout)
        return self

    def stop(self, timeout=60):
        """Signal the stream to stop, join the thread, drain the cache
        (flush) and re-raise any training-thread failure."""
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=timeout)
        for table, _, _ in self._tables.values():
            if getattr(table, "flush", None) is not None:
                table.flush()
        err, self._error = self._error, None  # idempotent re-stop
        if err is not None:
            raise err

    def stats(self):
        snap = self._counters.snapshot()
        for tname, (table, _, _) in self._tables.items():
            if getattr(table, "stats", None) is not None:
                snap[f"{tname}_cache"] = table.stats()
        return snap
