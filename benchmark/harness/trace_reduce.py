"""From a profiler trace to numbers: busy and idle time, idle gaps by
host span, operation and kernel time, and the time the operation stream
spends in collectives.

Reads the `.xplane.pb` that `jax.profiler` writes through
`jax.profiler.ProfileData` (nothing but JAX). The reduction works on
anything shaped like a `ProfileData`: `.planes`, each with `.name` and
`.lines`; each line with `.name` and `.events`; each event with `.name`,
`.start_ns`, `.duration_ns` and `.stats` (pairs of key and value). The
tests hand it such a structure built by hand, with known answers.

Times are nanoseconds on the trace's own clock; host spans written with
`jax.profiler.TraceAnnotation` are on that clock too, which is what lets
an idle gap on the device be attributed to what the host was doing.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
# an op whose whole job is communication between chips
COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")


@dataclass
class Event:
    name: str
    start: float  # ns
    end: float  # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    """Device operations per device plane, and the host's named spans."""

    devices: dict  # plane name -> [Event] sorted by start
    host_spans: list  # [Event] whose name starts with a kept prefix

    def window(self) -> tuple[float, float]:
        """First device operation's start to the last one's end, over all
        devices: the traced steady window, without the profiler's own
        start and stop."""
        evs = [e for ops in self.devices.values() for e in ops]
        if not evs:
            raise ValueError("the trace holds no device operation")
        return min(e.start for e in evs), max(e.end for e in evs)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, host_prefixes=("bench.",)) -> Trace:
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    return from_profile(ProfileData.from_file(path), host_prefixes)


def from_profile(profile, host_prefixes=("bench.",)) -> Trace:
    devices, host = {}, []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(Event(ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
            devices[plane.name] = sorted(ops, key=lambda e: (e.start, -e.end))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(tuple(host_prefixes)):
                        host.append(Event(ev.name, ev.start_ns,
                                          ev.start_ns + ev.duration_ns))
    return Trace(devices, sorted(host, key=lambda e: e.start))


# ------------------------------------------------------------ intervals


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted, non-overlapping intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """The part of merged intervals `a` that merged intervals `b` do not
    cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


# -------------------------------------------------------- busy and idle


def busy(ops) -> list[tuple[float, float]]:
    return union((e.start, e.end) for e in ops)


def idle_share(ops, window) -> float:
    """1 - union of operation intervals / window."""
    lo, hi = window
    return 1.0 - length(clip(busy(ops), lo, hi)) / (hi - lo)


def gaps(ops, window) -> list[tuple[float, float]]:
    return subtract([window], clip(busy(ops), *window))


def attribute_gaps(gap_list, host_spans, none="(no bench span)") -> dict:
    """Idle nanoseconds by the host span that covers most of each gap."""
    by_name: dict[str, float] = {}
    for s, e in gap_list:
        cover: dict[str, float] = {}
        for h in host_spans:
            if h.end <= s:
                continue
            if h.start >= e:
                break
            cover[h.name] = cover.get(h.name, 0.0) + (min(e, h.end)
                                                      - max(s, h.start))
        name = max(cover, key=cover.get) if cover else none
        by_name[name] = by_name.get(name, 0.0) + (e - s)
    return by_name


# ------------------------------------------------ operations and kernels


def self_times(ops) -> list[tuple[Event, float]]:
    """Each event with the part of its interval that no event nested in
    it covers (a `while` or a conditional encloses its body's events), so
    that shares by name add up to the busy time."""
    out, stack = [], []  # stack of [event, time covered by children]

    def close(upto):
        while stack and stack[-1][0].end <= upto:
            ev, covered = stack.pop()
            out.append((ev, max(0.0, ev.dur - covered)))
            if stack:
                stack[-1][1] += ev.dur

    for ev in ops:
        close(ev.start)
        stack.append([ev, 0.0])
    close(float("inf"))
    return out


def stem(name: str) -> str:
    """`%fusion.123 = ...` -> `fusion`; the name without its instance."""
    base = name.split(" = ")[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", base) or base


def group(name: str) -> str:
    """The operation's name without its instance and, for a fusion, its
    kind, which the TPU's trace carries in the name (the whole HLO
    instruction): `divide_subtract_fusion/kOutput`. An output fusion is
    one whose root is a dot or a convolution."""
    kind = re.search(r"\bkind=(k\w+)", name)
    return f"{stem(name)}/{kind[1]}" if kind else stem(name)


def share(ops, name_regex: str) -> float:
    """Self time of the operations whose name (the HLO instruction text)
    `name_regex` finds, over the busy time."""
    total = length(busy(ops))
    hit = sum(t for ev, t in self_times(ops) if re.search(name_regex, ev.name))
    return hit / total if total else 0.0


def kernel(ops, name_regex: str) -> tuple[int, float]:
    """(calls, summed device nanoseconds) of events named like
    `name_regex`."""
    hit = [e for e in ops if re.search(name_regex, e.name)]
    return len(hit), sum(e.dur for e in hit)


def top_groups(ops, n=10) -> list[tuple[str, float]]:
    """Self nanoseconds by `group`, largest first."""
    by: dict[str, float] = {}
    for ev, t in self_times(ops):
        by[group(ev.name)] = by.get(group(ev.name), 0.0) + t
    return sorted(by.items(), key=lambda kv: -kv[1])[:n]


# ---------------------------------------------------------- collectives


def collective(ops) -> float:
    """Nanoseconds this device's operation stream spent in collective
    operations. The stream runs one operation at a time, so this is the
    communication the step waits for: a synchronous collective's whole
    event, and of an asynchronous one its `-start` and its `-done` event,
    where the stream waits for it to finish. What lies between the two
    runs under other work and is hidden; the trace cannot tell how much of
    that span is transfer (on the four-chip BERT step one 4-byte
    collective-permute is open for 56 ms a step because its `-done` is
    scheduled that much later), so the span is not counted. Only events
    that enclose no other are counted, so a `while` around a collective
    adds nothing."""
    return length(union(
        (ev.start, ev.end) for ev, own in self_times(ops)
        if own >= ev.dur and COLLECTIVE.match(ev.name.lstrip("%"))))


# -------------------------------------------------------------- summary


def summarize(trace: Trace) -> dict:
    """What the result line's `device` and `breakdown` need."""
    window = trace.window()
    busy_s = [length(clip(busy(ops), *window)) / 1e9
              for ops in trace.devices.values()]
    worst = max(trace.devices, key=lambda d: idle_share(trace.devices[d], window))
    ops = trace.devices[worst]
    gap_ns = attribute_gaps(gaps(ops, window), trace.host_spans)
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": sum(busy_s) / len(busy_s),
        "worst_device": worst,
        "device_ops": [[k, v / 1e9] for k, v in top_groups(ops)],
        "idle_gaps": [[k, v / 1e9] for k, v in
                      sorted(gap_ns.items(), key=lambda kv: -kv[1])[:10]],
    }
