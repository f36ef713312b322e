"""Keyed accessor for checked-in tuning artifacts.

The repo ships data files that steer backend-specific decisions at
runtime — the serving shape-bucket table, the paged KV cache's page
table, the model registry, the shape-coverage ratchet.
A bare ``json.load`` answers *what does the file say* but never *which
(backend, signature) asked*, so when a deploy drifts from the artifact
(bucket table tuned for one feed set, serving another) nothing observes
the mismatch.

``load_artifact`` is the one sanctioned loader (enforced by the
provlint ``no-unkeyed-artifact-lookup`` rule): every load records the
artifact's content hash plus the caller's (backend, signature) key in a
process-global registry and the profiler counters, so /healthz-style
observers and tests can assert which artifact content actually fed
which backend. Fallback behavior stays with the caller: pass
``default=`` to never raise, omit it to propagate errors (serving refuses to
start on a corrupt bucket table).
"""

from __future__ import annotations

import hashlib
import json
import os
import threading

from ..profiler import bump_counter

_MISSING = object()

_lock = threading.Lock()
_records: dict = {}  # (artifact name, backend) -> provenance dict


def load_artifact(path, *, backend, signature, default=_MISSING):
    """json.load `path`, recording (backend, signature) provenance.

    backend: which execution backend the lookup steers (e.g. the
        JAX_PLATFORMS value, "tpu", "cpu", "serving").
    signature: what was asked of the artifact (a threshold-set name, a
        feed signature, a path) — any short stringable key.
    default: returned (and the fallback recorded) on a missing/corrupt
        file; omit to let OSError/ValueError propagate.
    """
    name = os.path.basename(path)
    error = None
    try:
        with open(path, "rb") as f:
            raw = f.read()
        obj = json.loads(raw.decode("utf-8"))
        sha = hashlib.sha256(raw).hexdigest()[:16]
    except (OSError, ValueError, UnicodeDecodeError) as e:
        error = f"{type(e).__name__}: {e}"
        _record(name, backend, signature, None, error)
        bump_counter("artifact_load_fallbacks")
        if default is _MISSING:
            raise
        return default
    _record(name, backend, signature, sha, error)
    bump_counter("artifact_loads")
    return obj


def _record(name, backend, signature, sha, error):
    key = (name, str(backend))
    with _lock:
        rec = _records.get(key)
        if rec is None:
            rec = _records[key] = {
                "artifact": name, "backend": str(backend),
                "loads": 0, "fallbacks": 0,
            }
        rec["loads"] += 1
        if error is not None:
            rec["fallbacks"] += 1
            rec["last_error"] = error
        else:
            rec["sha256"] = sha
        rec["last_signature"] = str(signature)


def records():
    """Snapshot of every (artifact, backend) lookup seen so far."""
    with _lock:
        return {f"{n}@{b}": dict(r) for (n, b), r in sorted(_records.items())}


def reset_records():
    """Test hook: forget recorded lookups."""
    with _lock:
        _records.clear()
