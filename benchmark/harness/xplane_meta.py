"""What a trace knows about each device operation beyond its timing: the
JAX name stack it was traced under (`tf_op`), its source line, XLA's
category and its cost model's FLOPs and bytes.

`jax.profiler.ProfileData` (which `trace_reduce` reads) gives an event its
name and its timing statistics, and not the event's *metadata*, which is
where the TPU's profiler writes these. They are in the same `.xplane.pb`;
this module reads them with a reader of the protobuf wire format from the
standard library alone (no TensorFlow, no `xprof`). The fields, from
`tsl/profiler/protobuf/xplane.proto`:

    XSpace          planes=1
    XPlane          name=2  event_metadata=4  stat_metadata=5
                    (both maps: an entry has key=1, value=2)
    XEventMetadata  name=2  stats=5
    XStatMetadata   id=1  name=2
    XStat           metadata_id=1  double=2  uint64=3  int64=4  str=5
                    ref=7 (an id into stat_metadata: the value is its name)

`tf_op` is `<name stack>/<primitive>:<type>`, as in
`jit(step)/fwd/matmul/dot_general:`. `paddle_tpu` lowers every Program op
under a scope `fwd/<op>`, `bwd/<op>_grad` or `opt/<op>`
(`paddle_tpu/ops/registry.py::op_scope`), which is what `scope` finds.
"""

from __future__ import annotations

import gzip
import re
import struct

from benchmark.harness import trace_reduce

KEPT = ("tf_op", "source", "hlo_category", "flops", "bytes_accessed")
# the first component of the name stack that is a phase scope, to the end
# of the operation's name: `jit(step)/while/body/bwd/mul_grad/transpose(jvp())`
SCOPE = re.compile(r"(?:^|[/(])((?:fwd|bwd|opt)/[^:]*)")


def fields(buf: bytes):
    """(field number, wire type, value) of one message: a varint as an
    int, a fixed64 or fixed32 as its bytes, a length-delimited field as
    its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield number, wire, value


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def _map_value(entry: bytes) -> bytes:
    return next(v for k, _, v in fields(entry) if k == 2)


def _stat(buf: bytes, stat_names: dict) -> tuple[str, object]:
    name = value = None
    for n, wire, v in fields(buf):
        if n == 1:
            name = stat_names.get(v)
        elif n == 2:
            (value,) = struct.unpack("<d", v)
        elif n == 3:
            value = v
        elif n == 4:
            value = v - (1 << 64) if v >> 63 else v
        elif n == 5:
            value = str(v, "utf-8", "replace")
        elif n == 7:
            value = stat_names.get(v)
    return name, value


def read(path: str) -> dict:
    """plane name -> event name -> the `KEPT` statistics its metadata
    carries, for the device planes of the `.xplane.pb` (or `.pb.gz`) at
    `path`. The event name is the one `ProfileData` gives the event (on
    the TPU the whole HLO instruction), so the two join by name."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = memoryview(f.read())  # slices of it are not copies
    out = {}
    for number, _, plane in fields(space):
        if number != 1:
            continue
        plane_name, stat_metas, event_metas = "", [], []
        for n, _, v in fields(plane):
            if n == 2:
                plane_name = str(v, "utf-8")
            elif n == 4:
                event_metas.append(_map_value(v))
            elif n == 5:
                stat_metas.append(_map_value(v))
        if not trace_reduce.DEVICE_PLANE.match(plane_name):
            continue
        stat_names = {}
        for meta in stat_metas:
            got = {n: v for n, _, v in fields(meta) if n in (1, 2)}
            stat_names[got.get(1, 0)] = str(got.get(2, b""), "utf-8")
        events = {}
        for meta in event_metas:
            name, stats = "", {}
            for n, _, v in fields(meta):
                if n == 2:
                    name = str(v, "utf-8", "replace")
                elif n == 5:
                    key, value = _stat(v, stat_names)
                    if key in KEPT:
                        stats[key] = value
            if stats:
                events[name] = stats
        out[plane_name] = events
    return out


def scope(tf_op: str | None) -> str:
    """The `pt` part of a `tf_op`: from the phase scope on, without the
    primitive's type. "" where the operation was traced under none."""
    found = SCOPE.search(tf_op or "")
    return found[1] if found else ""


def phase_op(scope: str) -> str:
    """`bwd/matmul_grad` of the scope `bwd/matmul_grad/transpose(jvp())/...`."""
    return "/".join(scope.split("/")[:2])


def scopes_by_event(reading: dict) -> dict | None:
    """plane name -> event name -> scope, for the traced run in `reading`
    (read once a run and kept there). None where the trace carries no
    phase scope at all: a program without them, or a compile cache entry
    an earlier program wrote (JAX leaves names out of the cache key)."""
    if "scopes_by_event" not in reading:
        path = trace_reduce.find_xplane(reading["traced"]["dir"])
        by_plane = {plane: {name: scope(stats.get("tf_op"))
                            for name, stats in events.items()}
                    for plane, events in read(path).items()}
        scoped = any(s for events in by_plane.values() for s in events.values())
        reading["scopes_by_event"] = by_plane if scoped else None
    return reading["scopes_by_event"]
