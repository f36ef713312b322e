"""DeviceStager: a background staging thread between a batch source and
the training loop.

The role of the reference's buffered_reader.cc (pinned-memory
double-buffering between the file readers and the device): items pulled
from a source iterator are pushed through a `stage` function (host
convert + `jax.device_put`) on a dedicated thread, keeping up to `depth`
STAGED batches ahead of the consumer. Because JAX transfers are async,
the H2D copy for batch N+1 overlaps the device step for batch N — and
because the convert+put runs off the consumer thread, the Python-side
conversion cost overlaps too (the piece the old in-loop device_put
serialized with the step dispatch).

Shared by the two input pipelines:
  * reader/dataloader.py `DataLoader.__iter__` (prefetch_to_device) —
    ResNet's bench input path;
  * executor._run_dataset (train_from_dataset / infer_from_dataset).

Error/termination contract: a source or stage exception is re-raised in
the consumer (never swallowed, never a fake end-of-stream); `close()`
unblocks and stops the thread no matter what the consumer did
(break/exception mid-iteration included). Items are staged strictly in
source order."""

from __future__ import annotations

import queue as _queue
import threading

from .. import profiler

__all__ = ["DeviceStager"]

_DONE = object()


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
