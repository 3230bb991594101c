"""The ten tpuschedlint rules (round 15, ISSUE 10).

Each rule is a small pass over one file's AST producing Findings; the
incident each rule descends from is catalogued in tools/README.md
"Static analysis". Rules are HEURISTIC on purpose: they prove the
cheap lexical property (no `.result()` token under a `with ...lock:`)
rather than the deep semantic one, and every legitimate exception is a
per-line suppression whose mandatory reason documents WHY the line is
exempt — the suppression text is the living review checklist.

Applicability is path-based (repo-relative POSIX paths): most rules
cover product code (tpusched/, tools/) and skip tests;
TPL010 covers ONLY test files. Passing any mix of paths to the engine
is safe — each rule selects its own territory.
"""

from __future__ import annotations

import ast
from typing import TYPE_CHECKING, Iterator

from tpusched.lint import interproc
from tpusched.lint.engine import Finding
from tpusched.lint.kernelflow import KERNEL_RULES

if TYPE_CHECKING:
    from tpusched.lint.engine import LintContext

__all__ = ["RULES", "default_rules", "Rule"]


# ---------------------------------------------------------------------------
# Shared AST helpers.
# ---------------------------------------------------------------------------

def dotted_name(node: ast.AST) -> "str | None":
    """Render a Name/Attribute chain as ``a.b.c``; None for anything
    whose base is not a plain name (calls, subscripts, ...)."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


def terminal_name(node: ast.AST) -> "str | None":
    """The rightmost identifier of a call target: ``x.y.z() -> z``,
    ``f() -> f``."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def is_num(node: ast.AST, value: float) -> bool:
    return (isinstance(node, ast.Constant)
            and type(node.value) in (int, float)
            and float(node.value) == value)


def import_aliases(tree: ast.AST) -> "dict[str, str]":
    """local name -> fully dotted module/object it refers to, from the
    MODULE-LEVEL and function-level import statements of one file."""
    aliases: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                aliases[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0]
                )
        elif isinstance(node, ast.ImportFrom) and node.level == 0 and node.module:
            for a in node.names:
                if a.name == "*":
                    continue
                aliases[a.asname or a.name] = f"{node.module}.{a.name}"
    return aliases


def normalize_call(func: ast.AST, aliases: "dict[str, str]") -> "str | None":
    """Dotted call target with its leading alias expanded:
    ``np.random.rand`` -> ``numpy.random.rand`` under
    ``import numpy as np``."""
    d = dotted_name(func)
    if d is None:
        return None
    head, _, rest = d.partition(".")
    if head in aliases:
        d = aliases[head] + ("." + rest if rest else "")
    return d


def is_test_path(relpath: str) -> bool:
    return (relpath.startswith("tests/")
            or relpath.rsplit("/", 1)[-1].startswith("test_"))


def product_path(relpath: str) -> bool:
    """tpusched/ or tools/ — the non-test gate surface."""
    if is_test_path(relpath):
        return False
    return relpath.startswith("tpusched/") or relpath.startswith("tools/")


class Rule:
    rule_id = "TPL999"
    title = ""
    incident = ""  # the CHANGES.md defect class this rule encodes

    def applies(self, relpath: str) -> bool:
        return product_path(relpath)

    def check(self, tree: ast.Module, src: str, relpath: str,
              ctx: "LintContext",
              parents: "dict[ast.AST, ast.AST]") -> "list[Finding]":
        raise NotImplementedError

    def finding(self, relpath: str, node: ast.AST, message: str) -> Finding:
        return Finding(relpath, getattr(node, "lineno", 1),
                       self.rule_id, message)


# ---------------------------------------------------------------------------
# TPL001 — function-level imports in tpusched/.
# ---------------------------------------------------------------------------

class FunctionLevelImport(Rule):
    """Imports belong at module top. Function-level imports put a
    sys.modules dict probe (or worse, a first-call module init) on
    whatever path calls the function — the exact per-record /
    per-cycle cost PR 5 and PR 7 review passes kept hoisting. Optional
    heavy deps (grpc, yaml: a host-only install must import without
    them) are allowlisted; a deliberate lazy import (cycle break,
    CLI-only dependency) takes a suppression whose reason says so.
    """

    rule_id = "TPL001"
    title = "function-level import in tpusched/"
    incident = ("PR 5/PR 7 review passes: per-cycle `from tpusched import "
                "...` inside host/server hot paths")

    #: Top-level modules a deployment may legitimately lack: importing
    #: them at module top would make the whole package require them.
    OPTIONAL_DEPS = frozenset({"grpc", "yaml"})

    def applies(self, relpath: str) -> bool:
        return relpath.startswith("tpusched/") and not is_test_path(relpath)

    def check(self, tree: ast.Module, src: str, relpath: str,
              ctx: "LintContext",
              parents: "dict[ast.AST, ast.AST]") -> "list[Finding]":
        findings = []
        for node in ast.walk(tree):
            if not isinstance(node, (ast.Import, ast.ImportFrom)):
                continue
            if not self._inside_function(node, parents):
                continue
            mods = self._top_modules(node)
            if mods and mods <= self.OPTIONAL_DEPS:
                continue
            findings.append(self.finding(
                relpath, node,
                f"function-level import of {', '.join(sorted(mods)) or '?'}"
                " — move to module top (or suppress with the cycle/"
                "optional-dep reason)",
            ))
        return findings

    @staticmethod
    def _inside_function(node: ast.AST,
                         parents: "dict[ast.AST, ast.AST]") -> bool:
        p = parents.get(node)
        while p is not None:
            if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                return True
            p = parents.get(p)
        return False

    @staticmethod
    def _top_modules(node: "ast.Import | ast.ImportFrom") -> "set[str]":
        if isinstance(node, ast.Import):
            return {a.name.split(".")[0] for a in node.names}
        if node.module is None or node.level:  # relative import
            return {"."}
        return {node.module.split(".")[0]}


# ---------------------------------------------------------------------------
# TPL002 — unseeded randomness / wall-clock in the hash-pinned sim.
# ---------------------------------------------------------------------------

class UnseededRandomness(Rule):
    """tpusched/sim/, tpusched/kernels/, and faults.py are under the
    determinism contract: same seed -> byte-identical event-log hash
    (PR 5/PR 8 twin harness). Module-level RNG draws (`random.random`,
    `np.random.rand`), zero-arg generator constructions, and wall-clock
    reads (`time.time`, `datetime.now`) all smuggle ambient entropy
    into that hash. Seeded constructions (`random.Random(seed)`,
    `np.random.default_rng(seed)`) and monotonic timers
    (`time.monotonic`, `time.perf_counter`: measurement, not
    timestamps) stay legal.
    """

    rule_id = "TPL002"
    title = "unseeded randomness / wall-clock in deterministic code"
    incident = ("PR 5/PR 8 determinism contract: the event-log hash is "
                "the twin-run equality witness; host.py's demo "
                "rng.uniform() leak took a PR to excise")

    SCOPES = ("tpusched/sim/", "tpusched/kernels/")
    FILES = ("tpusched/faults.py",)
    SEEDED_CTORS = frozenset({
        "Random", "SystemRandom", "default_rng", "RandomState",
        "SeedSequence", "Generator", "PCG64", "Philox",
    })
    WALL_CLOCK = frozenset({"time.time", "time.time_ns"})

    def applies(self, relpath: str) -> bool:
        return (relpath.startswith(self.SCOPES) or relpath in self.FILES)

    def check(self, tree: ast.Module, src: str, relpath: str,
              ctx: "LintContext",
              parents: "dict[ast.AST, ast.AST]") -> "list[Finding]":
        aliases = import_aliases(tree)
        findings = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = normalize_call(node.func, aliases)
            if name is None:
                continue
            msg = self._classify(name, node)
            if msg:
                findings.append(self.finding(relpath, node, msg))
        return findings

    def _classify(self, name: str, call: ast.Call) -> "str | None":
        parts = name.split(".")
        last = parts[-1]
        if name in self.WALL_CLOCK:
            return (f"wall-clock read {name}() in hash-pinned code — "
                    "use the VirtualClock / injected now")
        if parts[0] == "datetime" and last in ("now", "utcnow", "today"):
            return (f"wall-clock read {name}() in hash-pinned code — "
                    "use the VirtualClock / injected now")
        if parts[0] == "random" and len(parts) == 2:
            if last in self.SEEDED_CTORS:
                return self._unseeded_ctor(name, call)
            return (f"global-RNG draw {name}() — construct a seeded "
                    "random.Random / np.random.default_rng(seed)")
        if name.startswith("numpy.random."):
            if last in self.SEEDED_CTORS:
                return self._unseeded_ctor(name, call)
            return (f"module-level numpy RNG draw {name}() — draw from "
                    "a seeded np.random.default_rng(seed) instance")
        return None

    @staticmethod
    def _unseeded_ctor(name: str, call: ast.Call) -> "str | None":
        args = list(call.args) + [k.value for k in call.keywords]
        seedful = [a for a in args
                   if not (isinstance(a, ast.Constant) and a.value is None)]
        if seedful:
            return None
        return (f"{name}() without a seed (or with seed=None) draws OS "
                "entropy — pass an explicit seed")


# ---------------------------------------------------------------------------
# TPL003 — known-cost calls lexically under a lock.
# ---------------------------------------------------------------------------

class WorkUnderLock(Rule):
    """`with <lock>:` bodies must be O(bookkeeping). A call with known
    cost — a fetch join (`.result()`), jit dispatch /
    `block_until_ready`, H2D (`device_put`), byte-store composition,
    sleeps, file/socket I/O — serializes every contender behind work
    that never needed the lock. Lexical heuristic: the call token
    appears inside the with-body (nested `def`/`lambda` bodies are
    excluded — defining a function under a lock is free).
    """

    rule_id = "TPL003"
    title = "known-cost call inside a lock body"
    incident = ("PR 7 review: scheduler_device_bytes scrape summed "
                "store nbytes under _store_lock, stalling Assign "
                "registration behind every Metrics scrape")

    # Shared authority with the whole-program analyses (ISSUE 14):
    # TPL102 propagates the same cost model through the call graph.
    COSTLY = interproc.COSTLY
    COSTLY_BARE = interproc.COSTLY_BARE

    def check(self, tree: ast.Module, src: str, relpath: str,
              ctx: "LintContext",
              parents: "dict[ast.AST, ast.AST]") -> "list[Finding]":
        findings = []
        for node in ast.walk(tree):
            if not isinstance(node, (ast.With, ast.AsyncWith)):
                continue
            lock_expr = self._lock_expr(node)
            if lock_expr is None:
                continue
            for call, name in self._costly_calls(node.body):
                findings.append(self.finding(
                    relpath, call,
                    f"{name}() under `with {lock_expr}:` — hoist the "
                    "work out of the critical section",
                ))
        return findings

    @staticmethod
    def _lock_expr(node: "ast.With | ast.AsyncWith") -> "str | None":
        for item in node.items:
            for sub in ast.walk(item.context_expr):
                t = terminal_name(sub)
                if t and "lock" in t.lower():
                    return dotted_name(item.context_expr) or t
        return None

    def _costly_calls(
            self, body: "list[ast.stmt]",
    ) -> "Iterator[tuple[ast.Call, str]]":
        stack = list(body)
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda, ast.ClassDef)):
                continue  # defined, not executed, under the lock
            if isinstance(node, ast.Call):
                t = terminal_name(node.func)
                if t and (
                    (isinstance(node.func, ast.Attribute) and t in self.COSTLY)
                    or (isinstance(node.func, ast.Name)
                        and t in (self.COSTLY | self.COSTLY_BARE))
                ):
                    yield node, t
            stack.extend(ast.iter_child_nodes(node))


# ---------------------------------------------------------------------------
# TPL004 — inline [0,1] clamps.
# ---------------------------------------------------------------------------

class InlineUnitClamp(Rule):
    """`min(max(v, 0.0), 1.0)` passes NaN straight through (Python
    min/max return the first argument on NaN comparisons), which is
    exactly how a garbage availability annotation once poisoned the
    pressure math — config.clamp01 is the ONE NaN-safe unit-interval
    clamp. Only [0,1]-bounded nestings fire; other min/max range
    clamps (bucket caps, k clamps) are not this bug class.
    """

    rule_id = "TPL004"
    title = "inline [0,1] clamp bypassing config.clamp01"
    incident = ("PR 5 review: NaN slo-target annotations sailed "
                "through naive min/max clamps in kube.py parse paths")

    def check(self, tree: ast.Module, src: str, relpath: str,
              ctx: "LintContext",
              parents: "dict[ast.AST, ast.AST]") -> "list[Finding]":
        findings = []
        for node in ast.walk(tree):
            if self._is_unit_clamp(node):
                findings.append(self.finding(
                    relpath, node,
                    "inline [0,1] clamp — use config.clamp01 "
                    "(NaN-safe, one shared domain contract)",
                ))
        return findings

    @classmethod
    def _is_unit_clamp(cls, node: ast.AST) -> bool:
        outer = cls._minmax(node)
        if outer is None:
            return False
        kind, args = outer
        outer_bound = 1.0 if kind == "min" else 0.0
        inner_kind = "max" if kind == "min" else "min"
        inner_bound = 0.0 if kind == "min" else 1.0
        has_bound = any(is_num(a, outer_bound) for a in args)
        for a in args:
            inner = cls._minmax(a)
            if (inner and inner[0] == inner_kind
                    and any(is_num(ia, inner_bound) for ia in inner[1])
                    and has_bound):
                return True
        return False

    @staticmethod
    def _minmax(node: ast.AST) -> "tuple[str, list[ast.expr]] | None":
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id in ("min", "max") and len(node.args) >= 2
                and not node.keywords):
            return node.func.id, node.args
        return None


# ---------------------------------------------------------------------------
# TPL005 — unnamed threads.
# ---------------------------------------------------------------------------

class UnnamedThread(Rule):
    """tests/conftest.py's thread_leak_check finds leaked workers BY
    NAME ("tpusched" substring): a thread constructed without
    `name="tpusched-..."` is invisible to the leak gate and shows up
    in dumps as `Thread-17 (drive)`. Literal and f-string names must
    prove the prefix; a fully dynamic name expression is accepted
    (can't be proven lexically — the conftest session assertion
    backstops it at runtime).
    """

    rule_id = "TPL005"
    title = "threading.Thread without a tpusched- name"
    incident = ("PR 2/PR 3 thread_leak_check matches by name; unnamed "
                "bench/tool driver threads slipped every leak audit")

    def check(self, tree: ast.Module, src: str, relpath: str,
              ctx: "LintContext",
              parents: "dict[ast.AST, ast.AST]") -> "list[Finding]":
        aliases = import_aliases(tree)
        findings = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = normalize_call(node.func, aliases)
            if name not in ("threading.Thread", "Thread"):
                continue
            if name == "Thread" and aliases.get("Thread") != "threading.Thread":
                continue
            msg = self._check_name_kwarg(node)
            if msg:
                findings.append(self.finding(relpath, node, msg))
        return findings

    @staticmethod
    def _check_name_kwarg(call: ast.Call) -> "str | None":
        kw = next((k for k in call.keywords if k.arg == "name"), None)
        if kw is None:
            return ('threading.Thread(...) without name="tpusched-..." '
                    "— unnamed threads are invisible to thread_leak_check")
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, str):
            if not v.value.startswith("tpusched-"):
                return (f'thread name {v.value!r} lacks the "tpusched-" '
                        "prefix thread_leak_check keys on")
            return None
        if isinstance(v, ast.JoinedStr):
            first = v.values[0] if v.values else None
            if (isinstance(first, ast.Constant)
                    and isinstance(first.value, str)
                    and first.value.startswith("tpusched-")):
                return None
            return ('f-string thread name must start with a literal '
                    '"tpusched-" prefix')
        return None  # dynamic expression: runtime backstop applies


# ---------------------------------------------------------------------------
# TPL007 — dict-order-dependent selection.
# ---------------------------------------------------------------------------

class DictOrderSelection(Rule):
    """`next(reversed(d))` reads "newest entry" but actually reads
    "most recently INSERTED OR MOVED" — an LRU hit-touch reorders the
    dict and the selection silently changes meaning. Select by an
    explicit recency field instead; a genuinely-correct use (any
    element acceptable) takes a suppression saying so.
    """

    rule_id = "TPL007"
    title = "next(reversed(...)) dict-order selection"
    incident = ("PR 6 review: the stale-rebase op picked "
                "next(reversed(_stores)) = most-recently-TOUCHED "
                "store, not the newest registered one")

    def check(self, tree: ast.Module, src: str, relpath: str,
              ctx: "LintContext",
              parents: "dict[ast.AST, ast.AST]") -> "list[Finding]":
        findings = []
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "next" and node.args
                    and isinstance(node.args[0], ast.Call)
                    and isinstance(node.args[0].func, ast.Name)
                    and node.args[0].func.id == "reversed"):
                findings.append(self.finding(
                    relpath, node,
                    "next(reversed(...)) selects by dict/sequence "
                    "order — track the intended element explicitly",
                ))
        return findings


# ---------------------------------------------------------------------------
# TPL008 — string-sorting round/seq-shaped keys.
# ---------------------------------------------------------------------------

class StringSortedRounds(Rule):
    """String order puts r100 before r99: any sorted()/.sort() over a
    collection whose name says round/seq/cycle must pass a numeric
    key. Name-token heuristic — `sorted(rounds)` fires,
    `sorted(rounds, key=round_sort_key)` and `sorted(node_names)`
    don't.
    """

    rule_id = "TPL008"
    title = "sorted() on round/seq-shaped keys without a numeric key"
    incident = ("PR 7 review: benchdiff string-sorted round labels, "
                "diffing r100 against r99's predecessor")

    TOKENS = frozenset({"round", "rounds", "seq", "seqs", "rid",
                        "rids", "cycle", "cycles"})

    def check(self, tree: ast.Module, src: str, relpath: str,
              ctx: "LintContext",
              parents: "dict[ast.AST, ast.AST]") -> "list[Finding]":
        findings = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if any(k.arg == "key" for k in node.keywords):
                continue
            target = None
            if (isinstance(node.func, ast.Name) and node.func.id == "sorted"
                    and node.args):
                target = node.args[0]
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr == "sort" and not node.args):
                target = node.func.value
            if target is None:
                continue
            t = terminal_name(target)
            if t and self.TOKENS & set(t.lower().split("_")):
                findings.append(self.finding(
                    relpath, node,
                    f"sorting {t!r} without key= — string order puts "
                    "r100 < r99; pass a numeric key",
                ))
        return findings


# ---------------------------------------------------------------------------
# TPL009 — trace.DEFAULT / explain.DEFAULT discipline.
# ---------------------------------------------------------------------------

class CollectorDefaultDiscipline(Rule):
    """Injected-collector discipline (PR 4/PR 7 review fixes): spans
    and decision records must land in the collector the caller
    injected, never silently in the process-wide default. The global
    is referenced only (a) in its owning module, (b) as the right arm
    of the documented fallback idiom `injected or MOD.DEFAULT` /
    `x if x is not None else MOD.DEFAULT`, or (c) in the CLI entry
    points that deliberately drive the process default
    (tools/tracez.py, tools/explainz.py).
    """

    rule_id = "TPL009"
    title = "trace/explain DEFAULT outside the fallback idiom"
    incident = ("PR 4 review: make_server(tracer=) spans landed in "
                "trace.DEFAULT instead of the injected ring; PR 7 "
                "mirrored the fix for explain")

    OWNERS = ("tpusched/trace.py", "tpusched/explain.py")
    ENTRY_POINTS = ("tools/tracez.py", "tools/explainz.py")
    MODULES = ("tpusched.trace", "tpusched.explain")

    def applies(self, relpath: str) -> bool:
        return (product_path(relpath)
                and relpath not in self.OWNERS
                and relpath not in self.ENTRY_POINTS)

    def check(self, tree: ast.Module, src: str, relpath: str,
              ctx: "LintContext",
              parents: "dict[ast.AST, ast.AST]") -> "list[Finding]":
        aliases = import_aliases(tree)
        collector_aliases = {
            local for local, full in aliases.items() if full in self.MODULES
        }
        findings = []
        for node in ast.walk(tree):
            if (isinstance(node, ast.ImportFrom) and node.level == 0
                    and node.module in self.MODULES
                    and any(a.name == "DEFAULT" for a in node.names)):
                findings.append(self.finding(
                    relpath, node,
                    f"importing DEFAULT from {node.module} — accept an "
                    "injected collector and fall back with "
                    "`injected or MOD.DEFAULT`",
                ))
                continue
            if not (isinstance(node, ast.Attribute)
                    and node.attr == "DEFAULT"):
                continue
            base = dotted_name(node.value)
            if base is None:
                continue
            head = base.split(".")[0]
            resolved = (base if base in self.MODULES
                        else aliases.get(head) if base == head else None)
            if resolved not in self.MODULES:
                continue
            if self._is_fallback(node, parents):
                continue
            mod = resolved.rsplit(".", 1)[-1]
            findings.append(self.finding(
                relpath, node,
                f"direct {mod}.DEFAULT use — record into the injected "
                "collector (fallback idiom: `injected or "
                f"{mod}.DEFAULT`)",
            ))
        return findings

    @staticmethod
    def _is_fallback(node: ast.AST,
                     parents: "dict[ast.AST, ast.AST]") -> bool:
        p = parents.get(node)
        if isinstance(p, ast.BoolOp) and isinstance(p.op, ast.Or):
            return node in p.values[1:]
        if isinstance(p, ast.IfExp):
            return node is p.orelse
        return False


# ---------------------------------------------------------------------------
# TPL010 — closeable classes must be closed in tests.
# ---------------------------------------------------------------------------

class TestCloseDiscipline(Rule):
    """A test that constructs a closeable tpusched object (Engine,
    HostScheduler, SchedulerClient, ...) and drops it leaks its worker
    threads/channels past the test — the population thread_leak_check
    exists to catch. Heuristic: the bound variable must be close()d,
    enter a `with`, or be handed off to another call in the same test
    function. Tests only; direct-construction assignments only.
    """

    rule_id = "TPL010"
    title = "closeable class never closed in test function"
    incident = ("PR 2 conftest thread_leak_check: leaked fetch "
                "workers from unclosed Engines were the founding "
                "leak class")

    def applies(self, relpath: str) -> bool:
        return (relpath.startswith("tests/")
                and relpath.rsplit("/", 1)[-1].startswith("test_"))

    def check(self, tree: ast.Module, src: str, relpath: str,
              ctx: "LintContext",
              parents: "dict[ast.AST, ast.AST]") -> "list[Finding]":
        closeable = ctx.closeable_classes
        if not closeable:
            return []
        findings = []
        for fn in ast.walk(tree):
            if (isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and fn.name.startswith("test_")):
                findings.extend(self._check_fn(fn, relpath, closeable))
        return findings

    def _check_fn(self, fn: "ast.FunctionDef | ast.AsyncFunctionDef",
                  relpath: str,
                  closeable: "set[str]") -> "list[Finding]":
        candidates = []  # (varname, assign node, class name)
        for node in ast.walk(fn):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and isinstance(node.value, ast.Call)):
                cls = terminal_name(node.value.func)
                if cls in closeable:
                    candidates.append((node.targets[0].id, node, cls))
        out = []
        for var, node, cls in candidates:
            if not self._satisfied(fn, var):
                out.append(self.finding(
                    relpath, node,
                    f"{cls}(...) bound to {var!r} is never closed in "
                    "this test — close() it (try/finally), use a "
                    "context manager, or hand it off",
                ))
        return out

    @staticmethod
    def _satisfied(fn: ast.AST, var: str) -> bool:
        for node in ast.walk(fn):
            # x.close / x.stop referenced anywhere (call, addfinalizer,
            # ExitStack.callback, ...).
            if (isinstance(node, ast.Attribute)
                    and node.attr in ("close", "stop", "shutdown")
                    and isinstance(node.value, ast.Name)
                    and node.value.id == var):
                return True
            # `with x`, `with closing(x)`, `with x.something()` ...
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    for sub in ast.walk(item.context_expr):
                        if isinstance(sub, ast.Name) and sub.id == var:
                            return True
            # handed off as an argument: ownership transferred.
            if isinstance(node, ast.Call):
                for arg in list(node.args) + [k.value for k in node.keywords]:
                    if isinstance(arg, ast.Name) and arg.id == var:
                        return True
        return False


# ---------------------------------------------------------------------------
# TPL011 — carried warm-tableau access discipline.
# ---------------------------------------------------------------------------

class CarriedTableauDiscipline(Rule):
    """The warm-start tableau (kernels.assign.WarmTableau, carried
    across delta cycles as WarmState.tableau inside a DeviceSnapshot
    lineage) is only coherent with the cluster snapshot straight after
    the engine warm path has refreshed its dirty rows — anywhere else
    it is LAST cycle's Filter/Score tables wearing this cycle's shapes,
    and reading it is the stale-state hazard class ISSUE 11 introduces
    (the warm analogue of the TPL007 dict-order bug: silently valid-
    looking, wrong under churn). `.tableau` reads are allowed only in
    the engine warm path and the residency layer; everything else
    consumes SolveResults or the DeviceSnapshot warm counters. A
    deliberate read elsewhere (a debugging tool that accepts staleness)
    takes a suppression whose reason says so.
    """

    rule_id = "TPL011"
    title = "carried warm tableau read outside the engine warm path"
    incident = ("ISSUE 11 (warm-start): tableau cells are only valid "
                "straight after the engine's dirty-row refresh; a "
                "stale read elsewhere solves against last cycle's "
                "Filter/Score tables")

    ALLOWED = frozenset({
        "tpusched/engine.py",
        "tpusched/device_state.py",
        "tpusched/kernels/assign.py",
    })
    ATTRS = frozenset({"tableau"})

    def applies(self, relpath: str) -> bool:
        if relpath in self.ALLOWED:
            return False
        return product_path(relpath) or is_test_path(relpath)

    def check(self, tree: ast.Module, src: str, relpath: str,
              ctx: "LintContext",
              parents: "dict[ast.AST, ast.AST]") -> "list[Finding]":
        findings = []
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) and node.attr in self.ATTRS:
                findings.append(self.finding(
                    relpath, node,
                    f".{node.attr} (the carried warm tableau) read "
                    "outside the engine warm path — consume the "
                    "SolveResult / DeviceSnapshot warm counters "
                    "instead, or suppress with the staleness rationale",
                ))
        return findings


# ---------------------------------------------------------------------------
# TPL1xx — whole-program analyses (round 19, ISSUE 14). These rules run
# over the interprocedural Program index (tpusched/lint/interproc.py):
# per-function summaries + a heuristic call graph with held-lock
# propagation. Each rule reports only findings anchored in the CURRENT
# file, so the engine's per-line suppression/baseline machinery applies
# unchanged, and a cross-module hazard is reported once per involved
# acquisition site.
# ---------------------------------------------------------------------------

class LockOrderCycle(Rule):
    """A cycle in the static lock-order graph is a potential deadlock:
    thread 1 holds A wanting B while thread 2 holds B wanting A — no
    single file shows it, which is why it survives review. Edges come
    from held-lock propagation (a lock acquired anywhere in a function
    transitively callable from a `with`-lock body), so a two-module
    cycle is caught even when neither file nests `with` statements.
    A provably same-instance re-acquisition of a non-reentrant Lock
    (all-self-call chain) is the degenerate one-lock cycle and flags
    too. The checked-in tools/lock_hierarchy.json carries the full
    order; the runtime witness (tpusched/lint/witness.py) cross-checks
    it against observed acquisition orders under tier-1.
    """

    rule_id = "TPL101"
    title = "lock-order cycle (potential deadlock)"
    incident = ("ISSUE 14: ~33 locks across 15 modules; the "
                "_role_lock->_store_lock and session.lock->engine "
                "edges span files no single review pass reads together")

    def check(self, tree: ast.Module, src: str, relpath: str,
              ctx: "LintContext",
              parents: "dict[ast.AST, ast.AST]") -> "list[Finding]":
        prog = ctx.program_view(relpath, src)
        findings = []
        for e in prog.cyclic_edges():
            if e.src_path != relpath:
                continue
            if e.src == e.dst:
                msg = (f"same-instance re-acquisition of non-reentrant "
                       f"{e.src} (via {e.render_chain()}) — guaranteed "
                       "deadlock; split a _locked variant out")
            else:
                cyc = next((c for c in prog.lock_cycles()
                            if e.src in c and e.dst in c), ())
                msg = (f"lock-order cycle: {e.src} -> {e.dst} "
                       f"(via {e.render_chain()}); cycle members: "
                       f"{', '.join(cyc)} — acquire in one global order")
            findings.append(Finding(relpath, e.src_line, self.rule_id, msg))
        return findings


class TransitiveWorkUnderLock(Rule):
    """TPL003 generalized from lexical to whole-program: a known-cost
    call (fetch join, H2D, sleep, I/O, full solve) reached THROUGH a
    function called under a lock serializes every contender exactly
    like a lexical one — it is just invisible to a per-file pass. One
    finding per (rooting call, cost kind), anchored at the call inside
    the `with` body so the suppression (and its mandatory reason)
    lands where the next reader looks.
    """

    rule_id = "TPL102"
    title = "transitive known-cost call under a lock"
    incident = ("ISSUE 14: session.lock delta applies reach device_put "
                "through DeviceSnapshot.apply; PR 7's TPL003 scrape "
                "incident, one call deeper")

    def check(self, tree: ast.Module, src: str, relpath: str,
              ctx: "LintContext",
              parents: "dict[ast.AST, ast.AST]") -> "list[Finding]":
        prog = ctx.program_view(relpath, src)
        findings = []
        seen: "set[tuple[int, str]]" = set()
        for fid in sorted(prog.functions):
            fn = prog.functions[fid]
            if fn.path != relpath:
                continue
            for region in fn.regions:
                lexical = {name for name, _ in region.costly}
                for tfid, (chain, _pure, line) in sorted(
                        prog.region_reach(region).items()):
                    tfn = prog.functions.get(tfid)
                    if tfn is None or len(chain) < 1:
                        continue
                    for cname, _cline in tfn.costly:
                        key = (line, cname)
                        if key in seen or cname in lexical:
                            continue
                        seen.add(key)
                        via = " -> ".join(
                            c.split("::", 1)[-1] for c in chain)
                        findings.append(Finding(
                            relpath, line, self.rule_id,
                            f"call under `with {region.acq.raw}:` "
                            f"transitively reaches {cname}() via {via} "
                            "— hoist the work out of the critical "
                            "section (or suppress with the rationale "
                            "for why the section must cover it)",
                        ))
        return findings


class PerCallJitConstruction(Rule):
    """`jax.jit(...)` constructed inside a per-call function and not
    memoized (module constant, self-attribute, or a memo dict) builds a
    FRESH jit object per invocation: jax's shape-keyed compile cache
    hangs off the jit object, so every call retraces and recompiles —
    the exact compile anomalies ledger.COMPILES attributes
    (`scheduler_cycle_anomalies_total{cause="compile"}`, ROADMAP item
    4). tpusched/ only: tool scripts construct jits per run
    deliberately.
    """

    rule_id = "TPL103"
    title = "per-call jax.jit construction (retrace hazard)"
    incident = ("ROADMAP item 4 / PR 13 sentinel: p99 spikes traced to "
                "retraces; ring_sig_counts_host recompiled per call")

    def applies(self, relpath: str) -> bool:
        return relpath.startswith("tpusched/") and not is_test_path(relpath)

    def check(self, tree: ast.Module, src: str, relpath: str,
              ctx: "LintContext",
              parents: "dict[ast.AST, ast.AST]") -> "list[Finding]":
        prog = ctx.program_view(relpath, src)
        return [
            Finding(relpath, s.line, self.rule_id,
                    "jax.jit constructed per call — memoize it (module "
                    "constant, self-attribute, or a BOUNDED memo dict) "
                    "so the shape-keyed compile cache survives the call")
            for s in prog.jit_sites
            if s.path == relpath and s.kind == "per_call"
        ]


class UnboundedJitFamily(Rule):
    """A memo-dict jit family (`self._topk_jits[k] = jit(...)`) keyed
    by an unbounded value compiles one XLA program PER DISTINCT KEY —
    an adversarial (or merely diverse) request stream turns the cache
    into a compile treadmill and an executable-memory leak. The key
    must provably flow through a bounding helper (pow2/bucket/cap/
    clamp — directly, or one call-hop up like `_warm_inc_fn(cap)`'s
    callers passing `_frontier_bucket(...)`), or the memo must carry an
    explicit size-cap guard (`len(cache) >= N` eviction).
    """

    rule_id = "TPL104"
    title = "unbounded jit family (no bounding bucket on the memo key)"
    incident = ("ISSUE 14 / ROADMAP item 4: _warm_inc_jits' pow2 caps "
                "are the pattern; _topk_jits keyed by raw k was the "
                "counterexample")

    def applies(self, relpath: str) -> bool:
        return relpath.startswith("tpusched/") and not is_test_path(relpath)

    def check(self, tree: ast.Module, src: str, relpath: str,
              ctx: "LintContext",
              parents: "dict[ast.AST, ast.AST]") -> "list[Finding]":
        prog = ctx.program_view(relpath, src)
        return [
            Finding(relpath, s.line, self.rule_id,
                    f"jit family {s.family} keyed by an unbounded value "
                    "— route the key through a pow2/bucket/cap helper "
                    "or add a size-cap eviction to the memo")
            for s in prog.jit_sites
            if s.path == relpath and s.kind == "family"
            and s.bounded is False
        ]


class JitClosureOverMutableState(Rule):
    """A function handed to jax.jit that reads `self.<attr>` bakes the
    attribute's VALUE in at trace time: later mutation of the engine
    state is silently ignored (stale compile) or, worse, flips the
    traced branch and retraces per call. The repo's discipline is to
    hoist instance state into locals at jit-construction time
    (`cfg = self.config`) so the closure is immutable by construction
    — this rule pins that discipline.
    """

    rule_id = "TPL105"
    title = "jit-wrapped closure reads mutable self state"
    incident = ("ISSUE 14: Engine's local-binding discipline (cfg/mesh "
                "hoisted before the jit'd defs) encoded as a rule")

    def applies(self, relpath: str) -> bool:
        return relpath.startswith("tpusched/") and not is_test_path(relpath)

    def check(self, tree: ast.Module, src: str, relpath: str,
              ctx: "LintContext",
              parents: "dict[ast.AST, ast.AST]") -> "list[Finding]":
        aliases = import_aliases(tree)
        local_defs: "dict[str, list[ast.AST]]" = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                local_defs.setdefault(node.name, []).append(node)
        findings = []
        for call, arg_idx in interproc.iter_jit_calls(tree, aliases):
            if len(call.args) <= arg_idx:
                continue
            fn_arg = call.args[arg_idx]
            bodies: "list[ast.AST]" = []
            if isinstance(fn_arg, ast.Lambda):
                bodies = [fn_arg]
            elif isinstance(fn_arg, ast.Name):
                bodies = local_defs.get(fn_arg.id, [])
            for body in bodies:
                hit = self._self_read(body)
                if hit is not None:
                    findings.append(self.finding(
                        relpath, call,
                        f"jit-wrapped {getattr(fn_arg, 'id', 'lambda')} "
                        f"reads self.{hit} — bind it to a local before "
                        "constructing the jit (trace-time snapshot, "
                        "documented)",
                    ))
                    break
        return findings

    @staticmethod
    def _self_read(fn: ast.AST) -> "str | None":
        for node in ast.walk(fn):
            if (isinstance(node, ast.Attribute)
                    and isinstance(node.value, ast.Name)
                    and node.value.id == "self"):
                return node.attr
        return None


RULES = (
    FunctionLevelImport,
    UnseededRandomness,
    WorkUnderLock,
    InlineUnitClamp,
    UnnamedThread,
    DictOrderSelection,
    StringSortedRounds,
    CollectorDefaultDiscipline,
    TestCloseDiscipline,
    CarriedTableauDiscipline,
    LockOrderCycle,
    TransitiveWorkUnderLock,
    PerCallJitConstruction,
    UnboundedJitFamily,
    JitClosureOverMutableState,
    # Kernel dataflow analysis (round 20, ISSUE 15) — defined in
    # kernelflow.py next to the abstract interpreter they read.
    *KERNEL_RULES,
)


def default_rules() -> "list[Rule]":
    return [cls() for cls in RULES]
