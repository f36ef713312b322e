#!/usr/bin/env python
"""provlint: the repo's pluggable lint framework (pure stdlib, no JAX).

Absorbs the ad-hoc grep gate that lived in tools/ci.sh (the
"no legacy manual-SPMD idioms" check) into a proper rule engine with
AST-based rules, per-line pragma suppression and a path allowlist.

    python tools/provlint.py              # lint the default scopes
    python tools/provlint.py paddle_tpu/  # lint explicit paths
    python tools/provlint.py --list-rules

Suppression: append `# provlint: disable=<rule-name>[,<rule-name>...]`
(or `disable=all`) to the offending line. Suppressions are deliberate
and reviewable — each should explain itself in a nearby comment. The
ALLOWLIST maps rule name -> path substrings exempt from that rule.

Adding a rule: subclass Rule (regex rules override `check_line`,
AST rules override `check_tree`) and add an instance to RULES. Rules
receive every Python file under their scope; `scope` is a tuple of
path prefixes relative to the repo root.

Exit status: 0 = clean, 1 = findings, 2 = usage/internal error.
"""

from __future__ import annotations

import argparse
import ast
import os
import re
import sys
from typing import Iterator, NamedTuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRAGMA = re.compile(r"#\s*provlint:\s*disable=([A-Za-z0-9_,\-\s]+)")


class LintFinding(NamedTuple):
    rule: str
    path: str  # repo-relative
    line: int
    message: str

    def __str__(self):
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


class Rule:
    """One lint rule. name/doc feed --list-rules; scope restricts which
    files the rule sees (path prefixes relative to the repo root)."""

    name = "abstract"
    doc = ""
    scope: tuple = ()

    def applies(self, relpath: str) -> bool:
        return not self.scope or any(
            relpath == s or relpath.startswith(s) for s in self.scope
        )

    def check_line(self, relpath, lineno, line) -> Iterator[str]:
        return iter(())

    def check_tree(self, relpath, tree, lines) -> Iterator[tuple]:
        """Yield (lineno, message) pairs."""
        return iter(())

    def run(self, relpath, text, tree) -> Iterator[LintFinding]:
        lines = text.splitlines()
        for i, line in enumerate(lines, 1):
            for msg in self.check_line(relpath, i, line):
                yield LintFinding(self.name, relpath, i, msg)
        if tree is not None:
            for lineno, msg in self.check_tree(relpath, tree, lines):
                yield LintFinding(self.name, relpath, lineno, msg)


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------


class NoLegacySpmd(Rule):
    """The GSPMD-native rebuild (round 9) deleted every jax.shard_map /
    jax.pmap use — removed from modern JAX; the whole round-5 tier-1
    failure set traced to them. Use the unified mesh
    (paddle_tpu/parallel/mesh.py) instead."""

    name = "no-legacy-spmd"
    doc = "no shard_map/pmap idioms under paddle_tpu/ (use the unified mesh)"
    scope = ("paddle_tpu/",)
    _pat = re.compile(r"shard_map|jax\.pmap|[^a-zA-Z_.]pmap\(")

    def check_line(self, relpath, lineno, line):
        if self._pat.search(line):
            yield (
                "legacy shard_map/pmap idiom — use the unified mesh "
                "(paddle_tpu/parallel/mesh.py)"
            )


class NoHostPullInOps(Rule):
    """Op lowerings run inside a jit trace: np.asarray / jax.device_get
    on a traced value (anything read off the LoweringContext) either
    fails as a TracerError or silently forces a host sync. Sites that
    REQUIRE a static value (shape tensors, top-k K) must say so with a
    pragma."""

    name = "no-host-pull-in-ops"
    doc = ("no jax.device_get / np.asarray on LoweringContext values "
           "inside paddle_tpu/ops/")
    scope = ("paddle_tpu/ops/",)
    _CTX_READS = {"in_", "get", "ins", "get_list"}

    def _is_target_call(self, node):
        f = node.func
        if not isinstance(f, ast.Attribute):
            return None
        base = f.value
        if isinstance(base, ast.Name):
            if f.attr == "asarray" and base.id in ("np", "numpy", "_np"):
                return "np.asarray"
            if f.attr == "device_get" and base.id in ("jax",):
                return "jax.device_get"
        return None

    def _reads_ctx(self, node):
        for sub in ast.walk(node):
            if (
                isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Attribute)
                and sub.func.attr in self._CTX_READS
                and isinstance(sub.func.value, ast.Name)
                and sub.func.value.id in ("ctx", "ictx", "sub")
            ):
                return True
        return False

    def check_tree(self, relpath, tree, lines):
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            kind = self._is_target_call(node)
            if kind is None:
                continue
            # device_get always flags (a lowering has no business
            # pulling to host); np.asarray flags when its argument
            # visibly reads the LoweringContext
            if kind == "jax.device_get" or any(
                self._reads_ctx(a) for a in node.args
            ):
                yield (
                    node.lineno,
                    f"{kind} on a LoweringContext value forces "
                    "concretization inside the trace — if this input "
                    "must be static, say so with a pragma",
                )


class NoBareExcept(Rule):
    """Supervisor / fleet / RPC code paths must never swallow
    KeyboardInterrupt/SystemExit or mask the real failure class: a bare
    `except:` in a respawn loop turns a typo into an infinite crash
    loop. Catch Exception (or narrower)."""

    name = "no-bare-except"
    doc = ("no bare `except:` in supervisor/fleet code paths "
           "(resilience/, inference/, distributed/)")
    scope = (
        "paddle_tpu/resilience/",
        "paddle_tpu/inference/",
        "paddle_tpu/distributed/",
    )

    def check_tree(self, relpath, tree, lines):
        for node in ast.walk(tree):
            if isinstance(node, ast.ExceptHandler) and node.type is None:
                yield (
                    node.lineno,
                    "bare `except:` — catch Exception (or narrower) so "
                    "KeyboardInterrupt/SystemExit propagate",
                )


class NoDeviceInAutoshard(Rule):
    """The placement planner's whole value is that it runs DEVICE-FREE:
    a plan for a 256-chip pod must compute on a chip-less CI box (and
    inside the supervisor's restart path) without probing a backend.
    `jax.devices()` / `jax.local_devices()` / `jax.device_count()`
    initialize the platform (and take the chip from whichever process
    should hold it), `jax.device_put` materializes arrays onto it, and
    any `jnp.*` call builds device arrays. None of them may appear
    under paddle_tpu/autoshard/ — costs are plain Python/numpy
    arithmetic over static VarMetas."""

    name = "no-device-in-autoshard"
    doc = ("no jax.devices/device_put/jnp array materialization under "
           "paddle_tpu/autoshard/ (the planner must run on chip-less "
           "CI boxes)")
    scope = ("paddle_tpu/autoshard/",)
    _JAX_DEVICE_FNS = {
        "devices", "local_devices", "device_count", "local_device_count",
        "device_put", "device_get", "make_mesh",
    }
    _JNP_ALIASES = {"jnp", "jax_numpy"}

    def check_tree(self, relpath, tree, lines):
        # any import of jax.numpy (aliased, dotted or from-imported) is
        # already a materialization hazard, and from-importing a device
        # API unbinds it from the 'jax.' prefix the call check keys on
        # — flag the imports themselves
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "jax.numpy":
                        yield (node.lineno,
                               "import of jax.numpy — planner math is "
                               "numpy/stdlib only")
            elif isinstance(node, ast.ImportFrom):
                if node.module == "jax" and any(
                    a.name == "numpy" for a in node.names
                ):
                    yield (node.lineno,
                           "import of jax.numpy — planner math is "
                           "numpy/stdlib only")
                elif node.module in ("jax", "jax.api") and any(
                    a.name in self._JAX_DEVICE_FNS for a in node.names
                ):
                    names = [a.name for a in node.names
                             if a.name in self._JAX_DEVICE_FNS]
                    yield (node.lineno,
                           f"from jax import {', '.join(names)} — "
                           "placement must not touch a device")
            elif isinstance(node, ast.Call):
                f = node.func
                if not isinstance(f, ast.Attribute):
                    continue
                base = f.value
                if isinstance(base, ast.Name):
                    if base.id == "jax" and f.attr in self._JAX_DEVICE_FNS:
                        yield (node.lineno,
                               f"jax.{f.attr}() in the planner — "
                               "placement must not touch a device")
                    elif base.id in self._JNP_ALIASES:
                        yield (node.lineno,
                               f"jnp.{f.attr}() in the planner — "
                               "device-array materialization")
                elif (
                    isinstance(base, ast.Attribute)
                    and isinstance(base.value, ast.Name)
                    and base.value.id == "jax"
                    and base.attr == "numpy"
                ):
                    # the dotted spelling: jax.numpy.zeros(...)
                    yield (node.lineno,
                           f"jax.numpy.{f.attr}() in the planner — "
                           "device-array materialization")


# ---------------------------------------------------------------------------
# concurrency rules (round 18) — shared AST helpers
# ---------------------------------------------------------------------------


def _ast_dotted(node):
    """'a.b.c' for an Attribute/Name chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _threading_factory(call, kinds=("Lock", "RLock", "Condition")):
    """The factory name if `call` constructs a threading primitive."""
    if not isinstance(call, ast.Call):
        return None
    name = _ast_dotted(call.func)
    if name is None:
        return None
    last = name.rsplit(".", 1)[-1]
    if last in kinds and ("." not in name or name.startswith("threading.")):
        return last
    return None


def _class_sync_attrs(cls):
    """(lock_attrs, alias groups, cond_attrs) for one ClassDef.
    ``self._cv = threading.Condition(self._lock)`` makes {_cv, _lock}
    one alias group: they share a mutex, so holding either IS holding
    the other."""
    lock_attrs, cond_attrs, wraps = set(), set(), {}
    for stmt in ast.walk(cls):
        if not (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1):
            continue
        t = stmt.targets[0]
        if not (isinstance(t, ast.Attribute) and isinstance(t.value, ast.Name)
                and t.value.id == "self"):
            continue
        kind = _threading_factory(stmt.value)
        if kind is None:
            continue
        lock_attrs.add(t.attr)
        if kind == "Condition":
            cond_attrs.add(t.attr)
            v = stmt.value
            if (v.args and isinstance(v.args[0], ast.Attribute)
                    and isinstance(v.args[0].value, ast.Name)
                    and v.args[0].value.id == "self"):
                wraps[t.attr] = v.args[0].attr
    groups = {a: {a} for a in lock_attrs}
    for cv, lk in wraps.items():
        merged = groups.get(cv, {cv}) | groups.get(lk, {lk})
        for a in merged:
            groups[a] = merged
    return lock_attrs, groups, cond_attrs


def _walk_held(fn, on_node):
    """Walk a function body calling on_node(node, held) where held is
    the frozenset of `with self.X:` / `with X:` names lexically held.
    Nested defs/lambdas get a FRESH empty held-set (they usually run on
    another thread)."""

    def visit(node, held):
        if isinstance(node, ast.With):
            h = set(held)
            for item in node.items:
                visit(item.context_expr, frozenset(held))
                d = _ast_dotted(item.context_expr)
                if d is not None:
                    h.add(d.rsplit(".", 1)[-1] if d.startswith("self.")
                          else d)
            for stmt in node.body:
                visit(stmt, frozenset(h))
            return
        if isinstance(node, (ast.Lambda, ast.ClassDef)):
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node is not fn:
                for stmt in node.body:
                    visit(stmt, frozenset())
                return
        on_node(node, held)
        for child in ast.iter_child_nodes(node):
            visit(child, held)

    visit(fn, frozenset())


class CondNotifyOutsideLock(Rule):
    """threading.Condition.notify()/wait() without the owning lock held
    raises RuntimeError at runtime — but only on the path that actually
    races there, so review keeps missing it. Flag lexically-unguarded
    notify/notify_all/wait/wait_for on a class's own condition attrs
    (``Condition(self._lock)`` aliasing understood: holding the wrapped
    lock counts). Helpers named *_locked are trusted to be called with
    the lock held."""

    name = "cond-notify-outside-lock"
    doc = ("notify/wait on a Condition only while lexically holding it "
           "(or its wrapped lock)")
    scope = ("paddle_tpu/",)
    _METHODS = {"notify", "notify_all", "wait", "wait_for"}

    def check_tree(self, relpath, tree, lines):
        out = []
        for cls in ast.walk(tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            _locks, groups, conds = _class_sync_attrs(cls)
            if not conds:
                continue
            for fn in cls.body:
                if not isinstance(fn, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    continue
                if fn.name.endswith("_locked"):
                    continue

                def on_node(node, held, _out=out):
                    if not (isinstance(node, ast.Call)
                            and isinstance(node.func, ast.Attribute)
                            and node.func.attr in self._METHODS):
                        return
                    base = node.func.value
                    if not (isinstance(base, ast.Attribute)
                            and isinstance(base.value, ast.Name)
                            and base.value.id == "self"
                            and base.attr in conds):
                        return
                    if held & groups.get(base.attr, {base.attr}):
                        return
                    _out.append((
                        node.lineno,
                        f"self.{base.attr}.{node.func.attr}() without "
                        f"holding self.{base.attr} — Condition methods "
                        "require the owning lock (RuntimeError on the "
                        "racing path)",
                    ))

                _walk_held(fn, on_node)
        return iter(out)


class CounterRmwOutsideLock(Rule):
    """The process-global profiler counters are a plain dict: a
    read-modify-write outside _counters_lock (or a CounterSet's own
    lock) loses increments under thread interleaving. Go through
    profiler.bump_counter / set_counter / CounterSet instead of
    touching a `*counter*` mapping directly."""

    name = "counter-rmw-outside-lock"
    doc = ("no read-modify-write on `*counter*` mappings outside a "
           "`with <lock>:` block (use profiler.bump_counter/CounterSet)")
    scope = ("paddle_tpu/",)

    def _counter_subscript(self, target):
        if not isinstance(target, ast.Subscript):
            return None
        d = _ast_dotted(target.value)
        if d is not None and "counter" in d.rsplit(".", 1)[-1].lower():
            return d
        return None

    def check_tree(self, relpath, tree, lines):
        out = set()  # nested defs are walked twice; dedup by line
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue

            def on_node(node, held, _out=out):
                target = None
                if isinstance(node, ast.AugAssign):
                    target = self._counter_subscript(node.target)
                elif isinstance(node, ast.Assign) and len(node.targets) == 1:
                    t = self._counter_subscript(node.targets[0])
                    if t is not None and any(
                        _ast_dotted(s.func.value if isinstance(s, ast.Call)
                                    else s.value) == t
                        for s in ast.walk(node.value)
                        if isinstance(s, (ast.Subscript, ast.Attribute))
                        or (isinstance(s, ast.Call)
                            and isinstance(s.func, ast.Attribute))
                    ):
                        target = t
                if target is None:
                    return
                if any("lock" in h.lower() or h.endswith("_cv")
                       for h in held):
                    return
                _out.add((
                    node.lineno,
                    f"read-modify-write on `{target}[...]` outside a "
                    "lock — increments race; use profiler.bump_counter/"
                    "set_counter or a CounterSet",
                ))

            _walk_held(fn, on_node)
        return iter(sorted(out))


class ThreadSharedWriteUnguarded(Rule):
    """An attribute written from a Thread(target=...) body and touched
    from other methods needs ONE common guard — otherwise the write is
    a data race (torn/lost updates, and `deque`/`dict` iteration on the
    reader side can raise mid-flight). Lexical check: both the
    thread-body write and some other-method access are outside any
    `with <lock>:` block. Synchronization primitives themselves and
    pre-start writes in __init__/the spawning method are exempt."""

    name = "thread-shared-write-unguarded"
    doc = ("attrs written by a Thread target and accessed elsewhere "
           "need a common lock")
    scope = ("paddle_tpu/",)

    def _thread_targets(self, cls):
        """{method name: spawning method} for Thread(target=self.X /
        target=<nested def>) calls inside this class."""
        targets = {}
        for fn in cls.body:
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            nested = {n.name for n in ast.walk(fn)
                      if isinstance(n, (ast.FunctionDef,
                                        ast.AsyncFunctionDef))
                      and n is not fn}
            for call in ast.walk(fn):
                if not (isinstance(call, ast.Call)
                        and _ast_dotted(call.func) in (
                            "threading.Thread", "Thread")):
                    continue
                for kw in call.keywords:
                    if kw.arg != "target":
                        continue
                    d = _ast_dotted(kw.value)
                    if d is None:
                        continue
                    if d.startswith("self."):
                        targets[d[5:]] = fn.name
                    elif d in nested:
                        targets[f"{fn.name}.<locals>.{d}"] = fn.name
        return targets

    def _self_stores(self, fn, lock_attrs):
        """[(attr, lineno, guarded)] for self.X assignment targets."""
        out = []

        def on_node(node, held):
            tgts = ()
            if isinstance(node, ast.Assign):
                tgts = node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                tgts = (node.target,)
            for t in tgts:
                els = t.elts if isinstance(t, (ast.Tuple, ast.List)) \
                    else (t,)
                for el in els:
                    if (isinstance(el, ast.Attribute)
                            and isinstance(el.value, ast.Name)
                            and el.value.id == "self"
                            and el.attr not in lock_attrs):
                        out.append((el.attr, node.lineno, bool(held)))

        _walk_held(fn, on_node)
        return out

    def _self_accesses(self, fn, attrs):
        """{attr: any_unguarded} over self.X loads/stores in fn."""
        seen = {}

        def on_node(node, held):
            if (isinstance(node, ast.Attribute)
                    and isinstance(node.value, ast.Name)
                    and node.value.id == "self" and node.attr in attrs):
                seen[node.attr] = seen.get(node.attr, False) or not held

        _walk_held(fn, on_node)
        return seen

    def check_tree(self, relpath, tree, lines):
        out = []
        for cls in ast.walk(tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            targets = self._thread_targets(cls)
            if not targets:
                continue
            lock_attrs, _groups, _conds = _class_sync_attrs(cls)
            # Event/Thread/Queue attrs are themselves synchronization
            for stmt in ast.walk(cls):
                if (isinstance(stmt, ast.Assign)
                        and len(stmt.targets) == 1
                        and isinstance(stmt.targets[0], ast.Attribute)
                        and isinstance(stmt.value, ast.Call)):
                    d = _ast_dotted(stmt.value.func) or ""
                    if d.rsplit(".", 1)[-1] in ("Event", "Thread", "Queue",
                                                "SimpleQueue", "deque"):
                        lock_attrs.add(stmt.targets[0].attr)
            methods = {}
            for fn in cls.body:
                if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    methods[fn.name] = fn
                    for sub in ast.walk(fn):
                        if isinstance(sub, (ast.FunctionDef,
                                            ast.AsyncFunctionDef)) \
                                and sub is not fn:
                            methods[f"{fn.name}.<locals>.{sub.name}"] = sub
            for tname, spawner in targets.items():
                body = methods.get(tname)
                if body is None:
                    continue
                unguarded = [(a, ln) for a, ln, g in
                             self._self_stores(body, lock_attrs) if not g]
                if not unguarded:
                    continue
                exempt = {"__init__", spawner, tname,
                          tname.split(".", 1)[0]}
                for attr, ln in unguarded:
                    for mname, mfn in methods.items():
                        if mname in exempt:
                            continue
                        acc = self._self_accesses(mfn, {attr})
                        if acc.get(attr):
                            out.append((
                                ln,
                                f"self.{attr} written from thread target "
                                f"{tname}() with no lock, and accessed "
                                f"unguarded in {mname}() — guard both "
                                "sides with one lock",
                            ))
                            break
        return iter(out)


class NoUnkeyedArtifactLookup(Rule):
    """Checked-in tuning artifacts (bucket_table.json,
    shape_coverage.json, kv_page_table.json,
    model_registry.json) feed backend-specific
    decisions: a bare json.load answers 'what does the file say' but
    not 'which (backend, signature) asked', so drift between the
    artifact and the deploy goes unobserved. Route loads through
    paddle_tpu/analysis/artifacts.load_artifact, which records the
    (backend, signature) provenance and content hash."""

    name = "no-unkeyed-artifact-lookup"
    doc = ("tuning-artifact json loads must go through "
           "analysis/artifacts.load_artifact (records backend+signature)")
    scope = ("paddle_tpu/",)
    _ARTIFACTS = ("bucket_table.json", "shape_coverage.json",
                  "kv_page_table.json", "model_registry.json")

    def _artifact_consts(self, tree):
        """Module-level names bound to strings mentioning an artifact."""
        names = set()
        for node in tree.body:
            if isinstance(node, ast.Assign):
                for s in ast.walk(node.value):
                    if isinstance(s, ast.Constant) and isinstance(
                            s.value, str) and any(
                            a in s.value for a in self._ARTIFACTS):
                        for t in node.targets:
                            if isinstance(t, ast.Name):
                                names.add(t.id)
        return names

    def check_tree(self, relpath, tree, lines):
        consts = self._artifact_consts(tree)
        out = []
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            mentions = False
            for node in ast.walk(fn):
                if isinstance(node, ast.Constant) and isinstance(
                        node.value, str) and any(
                        a in node.value for a in self._ARTIFACTS):
                    mentions = True
                elif isinstance(node, ast.Name) and node.id in consts:
                    mentions = True
            if not mentions:
                continue
            for node in ast.walk(fn):
                if (isinstance(node, ast.Call)
                        and _ast_dotted(node.func) in (
                            "json.load", "json.loads")):
                    out.append((
                        node.lineno,
                        "bare json.load of a tuning artifact — use "
                        "analysis/artifacts.load_artifact so the "
                        "(backend, signature) lookup is recorded",
                    ))
        return iter(out)


RULES: list[Rule] = [NoLegacySpmd(), NoHostPullInOps(), NoBareExcept(),
                     NoDeviceInAutoshard(), CondNotifyOutsideLock(),
                     CounterRmwOutsideLock(), ThreadSharedWriteUnguarded(),
                     NoUnkeyedArtifactLookup()]

# rule name -> repo-relative path substrings exempt from that rule
# (prefer per-line pragmas; the allowlist is for generated/vendored
# files where editing lines is not an option)
ALLOWLIST: dict[str, tuple] = {
    # the lint framework itself spells the banned idioms in its rules
    "no-legacy-spmd": ("tools/provlint.py",),
    # the keyed accessor is the one legitimate json.load site
    "no-unkeyed-artifact-lookup": ("paddle_tpu/analysis/artifacts.py",),
}


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------


def _suppressed(rule_name, line):
    m = _PRAGMA.search(line)
    if not m:
        return False
    names = {s.strip() for s in m.group(1).split(",")}
    return "all" in names or rule_name in names


def iter_py_files(paths, root=REPO):
    for p in paths:
        ap = os.path.join(root, p) if not os.path.isabs(p) else p
        if os.path.isfile(ap) and ap.endswith(".py"):
            yield ap
            continue
        for dirpath, dirs, files in os.walk(ap):
            dirs[:] = [d for d in dirs
                       if d not in ("__pycache__", ".git", "chip_out")]
            for f in files:
                if f.endswith(".py"):
                    yield os.path.join(dirpath, f)


def lint_paths(paths, rules=None, root=REPO) -> list:
    """`root` anchors rule scopes/allowlists — overridable so tests can
    lint synthetic trees."""
    rules = rules if rules is not None else RULES
    findings: list[LintFinding] = []
    for ap in sorted(set(iter_py_files(paths, root))):
        rel = os.path.relpath(ap, root).replace(os.sep, "/")
        active = [
            r for r in rules
            if r.applies(rel) and not any(
                s in rel for s in ALLOWLIST.get(r.name, ())
            )
        ]
        if not active:
            continue
        try:
            with open(ap, encoding="utf-8") as f:
                text = f.read()
        except OSError as e:
            print(f"provlint: cannot read {rel}: {e}", file=sys.stderr)
            continue
        try:
            tree = ast.parse(text)
        except SyntaxError as e:
            findings.append(LintFinding(
                "syntax", rel, e.lineno or 0, f"file does not parse: {e.msg}"
            ))
            tree = None
        lines = text.splitlines()
        for rule in active:
            for fd in rule.run(rel, text, tree):
                src = lines[fd.line - 1] if 0 < fd.line <= len(lines) else ""
                if not _suppressed(fd.rule, src):
                    findings.append(fd)
    return findings


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to lint (default: every rule's scope)")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--rule", action="append", default=None,
                    help="run only these rules (repeatable)")
    args = ap.parse_args(argv)

    rules = RULES
    if args.rule:
        unknown = set(args.rule) - {r.name for r in RULES}
        if unknown:
            print(f"provlint: unknown rule(s): {sorted(unknown)}",
                  file=sys.stderr)
            return 2
        rules = [r for r in RULES if r.name in args.rule]

    if args.list_rules:
        for r in rules:
            print(f"{r.name}: {r.doc}")
            print(f"    scope: {', '.join(r.scope) or '(repo-wide)'}")
        return 0

    paths = args.paths
    if not paths:
        paths = sorted({s for r in rules for s in r.scope} or {"."})
    findings = lint_paths(paths, rules)
    for fd in findings:
        print(fd)
    if findings:
        print(f"provlint: {len(findings)} finding(s)", file=sys.stderr)
        return 1
    print(f"provlint: clean ({len(rules)} rules)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
