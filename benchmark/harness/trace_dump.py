"""Look at a trace by hand: which planes are devices, which lines they
hold, how operations are named and what statistics they carry.

    python3 -m benchmark.harness.trace_dump <trace dir or .xplane.pb>
"""

from __future__ import annotations

import collections
import os
import sys

from benchmark.harness import trace_reduce


def main(path: str) -> None:
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    print(path, os.path.getsize(path), "bytes")
    profile = ProfileData.from_file(path)
    for plane in profile.planes:
        print(f"== plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            t0 = min(e.start_ns for e in events)
            t1 = max(e.start_ns + e.duration_ns for e in events)
            print(f"  line {line.name!r}: {len(events)} events, span "
                  f"{(t1 - t0) / 1e6:.3f} ms, from {t0:.0f} ns")
            if not (trace_reduce.DEVICE_PLANE.match(plane.name)
                    or any(e.name.startswith("bench.") for e in events[:2000])):
                continue
            by = collections.Counter()
            for e in events:
                by[e.name] += e.duration_ns
            for name, ns in by.most_common(12):
                print(f"    {ns / 1e6:10.3f} ms  {name[:150]}")
            comm = collections.Counter()
            for e in events:
                if trace_reduce.COLLECTIVE.match(e.name.lstrip("%")):
                    comm[e.name[:110]] += e.duration_ns
            for name, ns in comm.most_common(8):
                print(f"    collective {ns / 1e6:10.3f} ms  {name}")
            for e in events[:3]:
                print(f"    stats of {e.name[:60]!r}: {dict(e.stats)}")
    trace = trace_reduce.from_profile(profile)
    if trace.devices:
        print(trace_reduce.summarize(trace))


if __name__ == "__main__":
    main(sys.argv[1])
