"""The device side of the input pipeline: where a batch's arrays are put,
and the background thread that puts them ahead of the training loop.

`stage_feed` is the one place that decides how a batch gets from host
memory to the device(s) the compiled step reads it from. With no mesh
step compiled for the feeds' Program it is `jax.device_put(v)`: one
array on the default device. Once the Executor has run a mesh step for
that Program it leaves the step's feed shardings on it
(`Program._feed_shardings`, rewritten at every run, `{}` after a step on
one device), and each host array is put with the
sharding of its name: one host-to-device transfer a shard, none of which
touches a device's compute stream, and an array the step's `jit` passes
through. An array put on one device instead is split by `jit` *on device
0, behind the step running there*, so the dispatch of step N+1 waits for
the end of step N (PERF.md, Findings, PR 30). The sharding is a hint,
never a contract: an array it does not fit (a leading dimension the mesh
does not divide, another rank), a name it does not know, or a `jax.Array`
the user put themselves takes the default path, and `Executor.run`
reshards at the dispatch whatever arrives laid out otherwise
(`feed_reshard_at_dispatch` counts those).

`DeviceStager` has the role of the reference's buffered_reader.cc
(pinned-memory double-buffering between the file readers and the
device): items pulled from a source iterator are pushed through a
`stage` function (host convert + `stage_feed`) on a dedicated thread,
keeping up to `depth` STAGED batches ahead of the consumer. Because JAX
transfers are async, the H2D copy for batch N+1 overlaps the device step
for batch N, and because the convert+put runs off the consumer thread,
the Python-side conversion cost overlaps too.

Both input pipelines stage through the two:
  * reader/dataloader.py `DataLoader.__iter__` (the device double
    buffer, `use_double_buffer=True`): every cell of the benchmark;
  * executor._run_dataset (train_from_dataset / infer_from_dataset).

Error/termination contract: a source or stage exception is re-raised in
the consumer (never swallowed, never a fake end-of-stream); `close()`
unblocks and stops the thread no matter what the consumer did
(break/exception mid-iteration included). Items are staged strictly in
source order."""

from __future__ import annotations

import queue as _queue
import threading

import jax
import numpy as np

from .. import profiler

__all__ = ["DeviceStager", "stage_feed"]

_DONE = object()


def _fits(sharding, shape) -> bool:
    """Whether `sharding` can lay out an array of `shape`: every
    partitioned dimension exists and divides."""
    if len(sharding.spec) > len(shape):
        return False
    try:
        sharding.shard_shape(tuple(shape))
    except ValueError:
        return False
    return True


def stage_feed(feed: dict, program=None) -> dict:
    """A batch's arrays put where the step compiled for `program` will
    read them (the module docstring has the rules). Host arrays only: a
    `jax.Array` is somebody's own placement and passes as it is."""
    want = getattr(program, "_feed_shardings", None) or {}
    out, on_mesh = {}, False
    for name, value in feed.items():
        if isinstance(value, jax.Array):
            out[name] = value
            continue
        sharding = want.get(name)
        if sharding is not None and _fits(sharding, np.shape(value)):
            out[name] = jax.device_put(value, sharding)
            on_mesh = True
        else:
            out[name] = jax.device_put(value)
    if on_mesh:
        profiler.bump_counter("reader_staged_on_mesh")
    return out


class _StageError:
    def __init__(self, exc):
        self.exc = exc


class DeviceStager:
    def __init__(self, source, stage, depth: int = 2):
        """source: iterable of raw items; stage: item -> staged item,
        run on the stager thread; depth: staged batches kept ahead."""
        self._source = source
        self._stage = stage
        self._q: _queue.Queue = _queue.Queue(maxsize=max(1, int(depth)))
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _put(self, item) -> bool:
        """Blocking put that aborts when the consumer closed."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.5)
                return True
            except _queue.Full:
                continue
        return False

    def _run(self):
        try:
            for item in self._source:
                if self._stop.is_set():
                    return
                with profiler.RecordEvent("pt.reader.stage"):
                    staged = self._stage(item)
                profiler.bump_counter("reader_staged_batches")
                if not self._put(staged):
                    return
        except BaseException as exc:  # noqa: BLE001 — via the queue
            self._put(_StageError(exc))
        else:
            self._put(_DONE)

    def __iter__(self):
        try:
            while True:
                with profiler.RecordEvent("pt.reader.wait"):
                    item = self._q.get()
                if item is _DONE:
                    return
                if isinstance(item, _StageError):
                    raise item.exc
                yield item
        finally:
            self.close()

    def close(self):
        """Stop the stager thread and drop queued items. Safe to call
        repeatedly; called automatically when iteration ends or the
        consumer abandons the iterator."""
        self._stop.set()
        # drain so a blocked put wakes immediately
        try:
            while True:
                self._q.get_nowait()
        except _queue.Empty:
            pass
