"""Find a cell's data files, and the code they name, by name.

Everything that belongs to one configuration, one traffic mix, one cell
or one per-layer metric is a file of its own under `benchmark/`; a later
PR adds files and edits none. This module is the only place that knows
the directory layout.
"""

from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(kind: str, name: str) -> dict:
    """`benchmark/<kind>/<name>.json` as a dict, with its name added."""
    path = os.path.join(BENCH_DIR, kind, name + ".json")
    try:
        with open(path) as f:
            data = json.load(f)
    except FileNotFoundError:
        raise SystemExit(f"no {kind} named {name!r}: {path} is missing") from None
    data["name"] = name
    return data


def names(kind: str) -> list[str]:
    return sorted(f[:-5] for f in os.listdir(os.path.join(BENCH_DIR, kind))
                  if f.endswith(".json"))


def plugin(package: str, name: str):
    """The module `benchmark.<package>.<name>`: a model adapter, a runner
    or a metric source kind, found by the name a data file gives."""
    return importlib.import_module(f"benchmark.{package}.{name}")


def cell(name: str, rehearse: bool = False) -> dict:
    """A cell with its configuration and traffic mix resolved."""
    return resolve(load("workloads", name), rehearse)


def resolve(c: dict, rehearse: bool = False) -> dict:
    """Put the configuration and the traffic mix that `c` names in their
    names' place. With `rehearse`, each file's `rehearse` block (the tiny
    CPU preset) overrides the keys it names."""
    c["config"] = load("configs", c["config"])
    c["traffic"] = load("traffic", c["traffic"])
    for part in (c["config"], c["traffic"]):
        tiny = part.pop("rehearse", {})
        if rehearse:
            part.update(tiny)
    return c


def layer_metrics(c: dict) -> list[dict]:
    """Every per-layer metric whose `where` clause admits this cell: each
    key of `where` names a key of the cell (`chips`, `config.adapter`,
    `traffic.name`, ...) and lists the values it may have. A metric names
    properties and never a cell, so a new cell edits no metric."""
    def lookup(key):
        value = c
        for part in key.split("."):
            value = value.get(part) if isinstance(value, dict) else None
        return value

    found = []
    for n in names("layer_metrics"):
        m = load("layer_metrics", n)
        if all(lookup(k) in allowed for k, allowed in m.get("where", {}).items()):
            found.append(m)
    return found
