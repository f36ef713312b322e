"""Share of device busy time, in %, that XLA booked under chosen *stages*
of one Program op; mean over the devices. A stage is a named scope that
an op's lowering opens below its own (`paddle_tpu/parallel/moe.py::stage`):
a component of the operation's scope with a dot in it,
`fwd/moe_experts/moe.sort/sort`,
`bwd/moe_experts_grad/transpose(jvp(moe.gather))/scatter-add`. Where
stages nest (a loop opened under one, its body's operations under
others: `moe.combine/while/body/moe.gather/gather`) the innermost counts.
args: scope (regular expression on the operation's scope, as
`trace_scope_share` takes it), stages (a list of stage names), counts
(optional: names of counts the step makes on the device, noted beside
the stages as `counters.window` has them). None where no operation under
`scope` carries a stage: a program from before the stages, or a cache
entry it wrote.

Once a run and `scope` it notes every stage found under `scope`, and
"(no stage)" for what lies under `scope` outside all of them: ms a step
under `fwd/` and under `bwd/`, and the largest XLA groups in it, each
with the JAX primitive its operations are named after (the scope's last
component: `fusion/kCustom (scatter-add)`, which is what tells a stage's
sort from its gathers and its scatters). A fusion
is booked under the one instruction XLA names it after, so a stage's time
is where XLA *booked* it; XLA merges the forward op's operations with
those the gradient op replays, so a forward stage's time may be booked
under `bwd/` (which is why the share takes a stage under either phase);
XLA's `ragged-dot` events carry no scope at all and are in no stage.

It reads `trace_scope_share`'s table and parses nothing again."""

import re

from benchmark.harness.sources import trace_scope_share

STAGE = re.compile(r"(?:^|[/(])([A-Za-z_]\w*\.\w+)(?=$|[/)])")
NO_STAGE = "(no stage)"
TOP_IN_STAGE = 4


def stage_of(scope: str) -> str:
    """`moe.gather` of `bwd/moe_experts_grad/transpose(jvp(moe.gather))/add`:
    the innermost stage of a scope, "" where it names none."""
    found = STAGE.findall(scope)
    return found[-1] if found else ""


def _by_stage(ns: dict, scope: str) -> dict:
    """One device's {stage or NO_STAGE: {(phase, XLA group (primitive)):
    ns}} under `scope`."""
    out = {}
    for (xla, s), t in ns.items():
        if re.search(scope, s):
            by = out.setdefault(stage_of(s) or NO_STAGE, {})
            *_, primitive = s.split("/")
            if not STAGE.search(primitive):  # `jvp(moe.products)`: none
                xla = f"{xla} ({primitive})"
            key = (s.split("/")[0], xla)
            by[key] = by.get(key, 0.0) + t
    return out


def _share(devices: list, tables: list, stages) -> float:
    """Busy time in `stages`, in %: each device's share, and their mean."""
    shares = []
    for by_stage, ns in zip(devices, tables):
        busy = sum(ns.values())
        hit = sum(sum(by_stage.get(name, {}).values()) for name in stages)
        shares.append(hit / busy if busy else 0.0)
    return 100.0 * sum(shares) / len(shares)


def _notes(devices: list, tables: list, args: dict, r: dict) -> list[str]:
    steps, n = r["traced"]["steps"], len(devices)
    # "(no stage)" has a line where it reads 0 too: that nothing lies
    # outside the stages is a reading
    share = {name: _share(devices, tables, [name])
             for name in {NO_STAGE}.union(*devices)}
    notes = [f"stages under {args['scope']!r}, ms a step over {steps} traced "
             f"steps ({sum(share.values()):.2f}% of busy time in all):"]
    for name in sorted(share, key=lambda name: -share[name]):
        phases, groups = dict.fromkeys(("fwd", "bwd"), 0.0), {}
        for by_stage in devices:
            for (phase, xla), t in by_stage.get(name, {}).items():
                ms = t / 1e6 / steps / n  # mean over the devices
                phases[phase] = phases.get(phase, 0.0) + ms
                groups[xla] = groups.get(xla, 0.0) + ms
        top = sorted(groups.items(), key=lambda kv: -kv[1])[:TOP_IN_STAGE]
        notes.append(
            f"  {name}: fwd {phases['fwd']:.3f} bwd {phases['bwd']:.3f} "
            f"({share[name]:.2f}% of busy time); "
            + (", ".join(f"{xla} {ms:.3f}" for xla, ms in top) or "nothing"))
    notes.append(
        "  a stage's time is where XLA booked it: a fusion counts under the "
        "one instruction it is named after, the forward op's operations are "
        "merged with the gradient op's replay of them under either phase, "
        "and ragged-dot events carry no scope (no stage has them)")
    if args.get("counts"):
        window = r["counters"]["window"]
        notes.append("  counts the steps made on the device over the window: "
                     + ", ".join(f"{c} {window.get(c, 0)}"
                                 for c in args["counts"]))
    return notes


def read(args: dict, r: dict):
    if r.get("trace") is None:
        return None
    tables = trace_scope_share._table(r)
    if tables is None:
        return None
    devices = [_by_stage(ns, args["scope"]) for ns in tables]
    if not any(set(by_stage) - {NO_STAGE} for by_stage in devices):
        return None
    if args["scope"] not in r.setdefault("stage_notes", set()):
        r["stage_notes"].add(args["scope"])
        r["notes"].extend(_notes(devices, tables, args, r))
    return _share(devices, tables, args["stages"])
