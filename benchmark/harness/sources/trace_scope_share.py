"""Share of device busy time, in %, in operations traced under a Program
op scope that the regular expression `scope` finds; mean over the
devices. The scope is the `pt` part of the operation's `tf_op`
(`fwd/matmul/dot_general`, `bwd/matmul_grad/transpose(jvp())/...`,
`opt/fused_adam/mul`; "" where it was traced under none, which `^$`
finds), read from the trace's event metadata by `harness/xplane_meta`.
A fusion carries one `tf_op`, that of the instruction XLA names it
after, so the shares say where XLA *booked* the time. None where the
trace holds no scope at all.

Once a run it notes the largest `phase/op` groups, under each of the
largest groups of XLA operations the Program ops that make it up, which
XLA operations carry no scope, and how a fusion is booked (`BOOKED`: what
`opt_device_pct` and `bwd_device_pct` may not be read as)."""

import re

from benchmark.harness import trace_reduce, xplane_meta

TOP_SCOPES = 12
TOP_GROUPS, TOP_IN_GROUP = 6, 4
BOOKED = ("  a fusion is booked under the one op XLA names it after: an "
          "optimizer update fused onto a weight-gradient product counts "
          "under the product's bwd/ op, not under opt/")


def _table(r: dict):
    """[{(XLA group, scope): self ns}] per device, once a run."""
    if "scope_ns" not in r:
        by_plane = xplane_meta.scopes_by_event(r)
        r["scope_ns"] = None
        if by_plane is not None:
            r["scope_ns"] = []
            for plane, ops in r["trace"].devices.items():
                scopes, ns = by_plane.get(plane, {}), {}
                for ev, own in trace_reduce.self_times(ops):
                    key = (trace_reduce.group(ev.name), scopes.get(ev.name, ""))
                    ns[key] = ns.get(key, 0.0) + own
                r["scope_ns"].append(ns)
            r["notes"].extend(_notes(r["scope_ns"]))
    return r["scope_ns"]


def _notes(tables: list) -> list[str]:
    by_op, by_group = {}, {}
    for ns in tables:
        for (xla, scope), t in ns.items():
            op = xplane_meta.phase_op(scope) or "(unscoped)"
            s = t / 1e9 / len(tables)  # seconds, mean over the devices
            by_op[op] = by_op.get(op, 0.0) + s
            by_group.setdefault(xla, {})
            by_group[xla][op] = by_group[xla].get(op, 0.0) + s

    def top(d, n):
        return ", ".join(f"{k} {v:.4f}" for k, v in
                         sorted(d.items(), key=lambda kv: -kv[1])[:n])

    notes = [f"device busy seconds by Program op scope: {top(by_op, TOP_SCOPES)}"]
    largest = sorted(by_group, key=lambda g: -sum(by_group[g].values()))
    for xla in largest[:TOP_GROUPS]:
        notes.append(f"  XLA group {xla} {sum(by_group[xla].values()):.4f} s, "
                     f"of which: {top(by_group[xla], TOP_IN_GROUP)}")
    unscoped = {xla: ops["(unscoped)"] for xla, ops in by_group.items()
                if "(unscoped)" in ops}
    notes.append(f"  (unscoped) by XLA group: {top(unscoped, TOP_IN_GROUP)}")
    notes.append(BOOKED)
    return notes


def read(args: dict, r: dict):
    if r.get("trace") is None:
        return None
    tables = _table(r)
    if tables is None:
        return None
    shares = []
    for ns in tables:
        total = sum(ns.values())
        hit = sum(t for (_, scope), t in ns.items()
                  if re.search(args["scope"], scope))
        shares.append(hit / total if total else 0.0)
    return 100.0 * sum(shares) / len(shares)
