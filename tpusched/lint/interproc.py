"""Whole-program analysis substrate for tpuschedlint (round 19, ISSUE 14).

PR 9's rules are lexical and per-file: they prove properties a single
AST shows (a `.result()` token inside a `with ...lock:` body). The
serving stack is now a genuinely concurrent system — ~33 locks across
15 modules — and its real hazards are INTERPROCEDURAL: a blocking call
reached through a function called under a lock, a lock-order cycle
spanning two modules, a jit entry point that silently retraces per
request. This module builds the shared substrate those analyses run on:

  * a per-function summary index over every product file (functions,
    methods, nested defs; the calls they make; the locks they acquire;
    their known-cost calls; their jit construction sites);
  * a heuristic call graph: precise resolution for module functions,
    imports, `self.`/`cls.` methods (through program base classes) and
    locally-inferred receiver types, with a bounded DYNAMIC-DISPATCH
    FALLBACK (an attribute call on an unknown receiver resolves to
    every program function of that name, unless the name is so common
    the resolution would be noise — `_DISPATCH_CAP`);
  * lock identity: every `threading.Lock()`/`Condition()` creation
    site becomes a LockDecl (`path::Class.attr`), and acquisition
    expressions resolve against those decls (self-attr, module global,
    one-hop attribute-type inference, unique-attr fallback);
  * held-lock propagation: for each `with <lock>:` region, the set of
    lock acquisitions and known-cost calls reachable through the call
    graph, each with a shortest witness chain;
  * the lock-order graph (edges + cycles) serialized as the checked-in
    artifact tools/lock_hierarchy.json, which the RUNTIME witness
    (tpusched/lint/witness.py) cross-checks against observed
    acquisition orders under tier-1;
  * jit-boundary analysis: every `jax.jit`/`_traced_jit` site
    classified (module-level / cached attribute / memoized family /
    per-call), with family BOUNDEDNESS proven via bounding-helper key
    flow (pow2/bucket/clamp helpers, one call hop) or an explicit
    size-cap guard on the memo.

Everything is stdlib `ast`, deterministic (sorted outputs, stable
ids), and pure — rules in rules.py turn the results into Findings so
the engine's suppression/baseline machinery applies unchanged.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import re
from pathlib import Path
from typing import Any, Iterator, Optional

__all__ = [
    "CallSite", "FunctionInfo", "JitSite", "LockAcq", "LockDecl",
    "LockEdge", "LockRegion", "Program", "scan_product_sources",
    "COSTLY", "COSTLY_BARE",
]

# Known-cost call names (shared authority with TPL003 in rules.py): a
# fetch join, jit dispatch/sync, H2D, byte-store composition, sleeps,
# file/socket I/O, a full solve. Attribute calls match COSTLY; bare
# names additionally match COSTLY_BARE.
COSTLY = frozenset({
    "result", "block_until_ready", "device_put", "sleep",
    "urlopen", "compose_bytes", "serve_forever", "exec_module",
    "solve", "solve_async", "solve_explained", "score_topk",
    "run_until_idle",
})
COSTLY_BARE = frozenset({"open", "sleep"})

#: Dynamic-dispatch fallback cap: an attribute call on an unknown
#: receiver resolves to every program function of that name — unless
#: more than this many share it, in which case the name is too common
#: to carry signal (`close`, `get`, ...) and the call stays unresolved.
_DISPATCH_CAP = 6

#: Methods of the builtin container/scalar types are excluded from the
#: dynamic-dispatch fallback: `ring.append(...)` on a deque must not
#: resolve to ReplicationLog.append — the analysis cannot distinguish
#: builtin receivers, and these names carry no dispatch signal.
_BUILTIN_METHODS = frozenset(
    name
    for t in (list, dict, set, frozenset, tuple, str, bytes, bytearray)
    for name in dir(t) if not name.startswith("_")
) | {
    # deque / queue / lock / thread / file-protocol names: same
    # reasoning — the receiver is overwhelmingly a stdlib primitive
    # the program cannot shadow meaningfully at a dynamic call site.
    "appendleft", "popleft", "rotate", "extendleft",
    "put", "put_nowait", "get_nowait", "task_done", "qsize",
    "acquire", "release", "locked", "notify", "notify_all", "wait",
    "start", "is_alive", "cancel", "set", "is_set",
    "read", "write", "flush", "seek", "readline", "readlines",
    "writelines", "fileno", "tell",
}

#: Functions whose NAME proves their result is a bounded jit-family
#: key (pow2 buckets, caps, clamps). Used by the TPL104 boundedness
#: proof: a memo key produced by one of these (directly, via a local,
#: or one call-hop up through the family function's parameter) keeps
#: the family's compile set finite.
_BOUNDING_NAME = re.compile(r"(bucket|pow2|cap|clamp)", re.IGNORECASE)

_LOCK_CTORS = frozenset({"Lock", "RLock", "Condition"})

#: CapWords (with optional leading underscores): the class-name
#: convention `_ctor_class_name` keys on — `_OrderedFetchWorker(...)`
#: is a constructor call, `make_server(...)` is not.
_CLASS_LIKE = re.compile(r"^_*[A-Z]")


# ---------------------------------------------------------------------------
# Small AST helpers (kept local: this module must not import rules.py,
# which imports it).
# ---------------------------------------------------------------------------

def _dotted(node: ast.AST) -> Optional[str]:
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


def _terminal(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _module_to_relpath(dotted: str) -> str:
    return dotted.replace(".", "/") + ".py"


def _is_lock_ctor(call: ast.Call, aliases: dict[str, str]) -> Optional[str]:
    """'Lock'/'RLock'/'Condition' when `call` constructs a threading
    primitive (threading.Lock(), Lock() imported from threading, or the
    __import__("threading").Lock() spelling) — else None."""
    func = call.func
    t = _terminal(func)
    if t not in _LOCK_CTORS:
        return None
    if isinstance(func, ast.Name):
        return t if aliases.get(t) == f"threading.{t}" else None
    assert isinstance(func, ast.Attribute)
    base = func.value
    d = _dotted(base)
    if d is not None:
        head = d.split(".")[0]
        if d == "threading" or aliases.get(head, "").startswith("threading"):
            return t
        return None
    # __import__("threading").Lock()
    if (isinstance(base, ast.Call) and isinstance(base.func, ast.Name)
            and base.func.id == "__import__" and base.args
            and isinstance(base.args[0], ast.Constant)
            and base.args[0].value == "threading"):
        return t
    return None


def _file_aliases(tree: ast.Module) -> dict[str, str]:
    """local name -> dotted module/object, module-wide (same contract
    as rules.import_aliases but owned here to avoid an import cycle)."""
    aliases: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                aliases[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0]
                )
        elif isinstance(node, ast.ImportFrom) and node.level == 0 and node.module:
            for a in node.names:
                if a.name != "*":
                    aliases[a.asname or a.name] = f"{node.module}.{a.name}"
    return aliases


def _import_module_names(tree: ast.Module) -> set[str]:
    """Local names that are PROVABLY modules: bound by an `import X`
    / `import X.Y as Z` statement. An attribute chain rooted at one of
    these that does not resolve inside the program is a FOREIGN module
    call (`jnp.linalg.solve`, `subprocess.run`) and must never fall
    through to method dispatch."""
    out: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out.add(a.asname or a.name.split(".")[0])
    return out


# ---------------------------------------------------------------------------
# Summary records.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, order=True)
class LockDecl:
    """One `<target> = threading.Lock()` creation site."""

    lock_id: str    # "tpusched/rpc/server.py::DeviceSession.lock"
    path: str       # repo-relative POSIX path
    line: int       # line of the Lock() call (the witness keys on this)
    attr: str       # attribute / global name
    owner: str      # owning class name, "" for module-level
    kind: str       # "Lock" | "RLock" | "Condition"


@dataclasses.dataclass(frozen=True)
class LockAcq:
    """One resolved `with <lock>:` acquisition."""

    decl: LockDecl
    line: int
    raw: str        # source spelling ("self._store_lock")
    via_self: bool  # receiver is `self` (same-instance provable)


@dataclasses.dataclass(frozen=True)
class CallSite:
    line: int
    raw: str                  # rendered target ("self._engine.solve")
    targets: tuple[str, ...]  # resolved function ids (empty: unresolved)
    kind: str                 # "local"|"module"|"import"|"self"|"class"|
    #                           "typed"|"dynamic"|"unresolved"


@dataclasses.dataclass
class LockRegion:
    """One `with <lock>:` body and what happens inside it (nested defs
    excluded — defining a function under a lock is free)."""

    acq: LockAcq
    calls: list[CallSite]
    inner_acqs: list[LockAcq]           # lexically nested acquisitions
    costly: list[tuple[str, int]]       # lexical known-cost calls


@dataclasses.dataclass
class FunctionInfo:
    fid: str                  # "tpusched/engine.py::Engine.solve"
    path: str
    line: int
    cls: Optional[str]
    name: str
    calls: list[CallSite] = dataclasses.field(default_factory=list)
    regions: list[LockRegion] = dataclasses.field(default_factory=list)
    acquires: list[LockAcq] = dataclasses.field(default_factory=list)
    costly: list[tuple[str, int]] = dataclasses.field(default_factory=list)
    #: lock-ish `with` context exprs the analysis could not name —
    #: invisible to TPL101/TPL102 by construction, so they surface in
    #: graph_doc() as the model's known blind spots (the unmodeled-
    #: edge workflow's static counterpart).
    unresolved_locks: list[tuple[str, int]] = dataclasses.field(
        default_factory=list)


@dataclasses.dataclass(frozen=True)
class LockEdge:
    """src is held when dst is acquired. `chain` is the shortest
    witness call chain (function ids) from the holding region to the
    acquiring function; empty = lexically nested in the same region."""

    src: str
    dst: str
    src_path: str
    src_line: int   # line of the call (or inner with) inside the region
    dst_path: str
    dst_line: int   # line of the dst acquisition
    chain: tuple[str, ...]
    self_pure: bool  # every hop a self-call AND both acqs on `self`

    def render_chain(self) -> str:
        if not self.chain:
            return "nested with"
        return " -> ".join(c.split("::", 1)[-1] for c in self.chain)


@dataclasses.dataclass
class JitSite:
    path: str
    line: int
    func: Optional[str]       # enclosing function id (None: module level)
    kind: str                 # "module"|"decorator"|"attr_cache"|
    #                           "family"|"per_call"
    family: Optional[str] = None      # "Engine._topk_jits"
    bounded: Optional[bool] = None    # families only
    bound_via: str = ""


# ---------------------------------------------------------------------------
# Per-module index (pass 1).
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _ClassInfo:
    name: str
    path: str
    bases: tuple[str, ...]
    methods: dict[str, ast.AST]
    attr_types: dict[str, str]   # self.attr -> program class name
    lock_attrs: dict[str, LockDecl]


@dataclasses.dataclass
class _ModuleInfo:
    path: str
    tree: ast.Module
    aliases: dict[str, str]
    module_aliases: set[str]           # names bound by `import X [as Y]`
    classes: dict[str, _ClassInfo]
    functions: dict[str, ast.AST]      # module-level defs
    global_locks: dict[str, LockDecl]


def scan_product_sources(root: Path) -> dict[str, str]:
    """The whole-program file set: tpusched/** and tools/* — the same
    non-test product surface the per-file rules gate."""
    out: dict[str, str] = {}
    for sub in ("tpusched", "tools"):
        base = root / sub
        if base.is_dir():
            for p in sorted(base.rglob("*.py")):
                out[p.relative_to(root).as_posix()] = p.read_text()
    return out


class Program:
    """The whole-program index + analyses (module docstring)."""

    def __init__(self, sources: dict[str, str]):
        self.sources = dict(sources)
        self.modules: dict[str, _ModuleInfo] = {}
        self.functions: dict[str, FunctionInfo] = {}
        self.locks: dict[str, LockDecl] = {}
        #: attr/global name -> decls sharing it (unique-attr fallback)
        self._locks_by_attr: dict[str, list[LockDecl]] = {}
        #: function name -> fids (any kind; debugging/report surface)
        self._by_name: dict[str, list[str]] = {}
        #: method name -> fids (the dynamic-dispatch fallback index)
        self._methods_by_name: dict[str, list[str]] = {}
        #: fid -> its AST node (return-type inference)
        self._fn_nodes: dict[str, ast.AST] = {}
        #: class name -> _ClassInfo (assumed unique program-wide)
        self._classes: dict[str, _ClassInfo] = {}
        self.jit_sites: list[JitSite] = []
        self._edges: Optional[list[LockEdge]] = None
        for path in sorted(self.sources):
            self._index_module(path, self.sources[path])
        # Name registration is a PRE-pass: dynamic dispatch during
        # summarization must see every program function, not just the
        # alphabetically-earlier modules'.
        for path in sorted(self.modules):
            self._register_names(self.modules[path])
        for path in sorted(self.modules):
            self._summarize_module(self.modules[path])
        self._jit_pass()

    def has(self, relpath: str, src: str) -> bool:
        return self.sources.get(relpath) == src

    # -- pass 1: declarations -------------------------------------------

    def _index_module(self, path: str, src: str) -> None:
        try:
            tree = ast.parse(src, filename=path)
        except SyntaxError:
            return
        aliases = _file_aliases(tree)
        mod = _ModuleInfo(path=path, tree=tree, aliases=aliases,
                          module_aliases=_import_module_names(tree),
                          classes={}, functions={}, global_locks={})
        for node in tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                mod.functions[node.name] = node
            elif isinstance(node, ast.ClassDef):
                mod.classes[node.name] = self._index_class(path, node, aliases)
            elif isinstance(node, ast.Assign) and len(node.targets) == 1:
                tgt, val = node.targets[0], node.value
                if (isinstance(tgt, ast.Name) and isinstance(val, ast.Call)):
                    kind = _is_lock_ctor(val, aliases)
                    if kind:
                        decl = LockDecl(
                            lock_id=f"{path}::{tgt.id}", path=path,
                            line=val.lineno, attr=tgt.id, owner="",
                            kind=kind,
                        )
                        mod.global_locks[tgt.id] = decl
                        self._add_lock(decl)
        self.modules[path] = mod
        for cname, cinfo in mod.classes.items():
            # First definition wins; program class names are unique in
            # practice and determinism beats cleverness here.
            self._classes.setdefault(cname, cinfo)

    def _index_class(self, path: str, node: ast.ClassDef,
                     aliases: dict[str, str]) -> _ClassInfo:
        bases = tuple(b for b in (_terminal(x) for x in node.bases)
                      if b is not None)
        info = _ClassInfo(name=node.name, path=path, bases=bases,
                          methods={}, attr_types={}, lock_attrs={})
        for item in node.body:
            if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                info.methods[item.name] = item
                self._scan_self_assigns(path, info, item, aliases)
        return info

    def _scan_self_assigns(self, path: str, info: _ClassInfo,
                           fn: ast.AST, aliases: dict[str, str]) -> None:
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Assign) and len(node.targets) == 1):
                continue
            tgt, val = node.targets[0], node.value
            if not (isinstance(tgt, ast.Attribute)
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id == "self"):
                continue
            ctor = self._value_ctor(val)
            if ctor is None:
                continue
            val = ctor
            kind = _is_lock_ctor(val, aliases)
            if kind:
                decl = LockDecl(
                    lock_id=f"{path}::{info.name}.{tgt.attr}", path=path,
                    line=val.lineno, attr=tgt.attr, owner=info.name,
                    kind=kind,
                )
                info.lock_attrs[tgt.attr] = decl
                self._add_lock(decl)
                continue
            cls = self._ctor_class_name(val)
            if cls is not None:
                prev = info.attr_types.get(tgt.attr)
                if prev is None:
                    info.attr_types[tgt.attr] = cls
                elif prev != cls:
                    info.attr_types[tgt.attr] = "?"  # conflicting: drop

    @staticmethod
    def _value_ctor(val: ast.AST) -> Optional[ast.Call]:
        """The constructor call inside an assignment value, seeing
        through the injected-or-default idioms: `D(...)`,
        `injected or D(...)`, `x if x is not None else D(...)` — the
        fallback arm pins the type the injected object must share."""
        if isinstance(val, ast.Call):
            return val
        if (isinstance(val, ast.BoolOp) and isinstance(val.op, ast.Or)
                and isinstance(val.values[-1], ast.Call)):
            return val.values[-1]
        if isinstance(val, ast.IfExp):
            arms = [a for a in (val.body, val.orelse)
                    if isinstance(a, ast.Call)]
            if len(arms) == 1:
                return arms[0]
        return None

    @staticmethod
    def _ctor_class_name(call: ast.Call) -> Optional[str]:
        """`D(...)` -> D; `D.from_x(...)` -> D (alternate-constructor
        idiom). Resolution against program classes happens at use."""
        f = call.func
        if isinstance(f, ast.Name) and _CLASS_LIKE.match(f.id):
            return f.id
        if (isinstance(f, ast.Attribute) and f.attr.startswith("from_")
                and isinstance(f.value, ast.Name)
                and _CLASS_LIKE.match(f.value.id)):
            return f.value.id
        return None

    def _add_lock(self, decl: LockDecl) -> None:
        self.locks[decl.lock_id] = decl
        self._locks_by_attr.setdefault(decl.attr, []).append(decl)

    # -- pass 1.5: function-name index ----------------------------------

    def _register_names(self, mod: _ModuleInfo) -> None:
        def reg_tree(fid: str, fn: ast.AST) -> None:
            self._by_name.setdefault(getattr(fn, "name", "?"), []).append(fid)
            self._fn_nodes[fid] = fn
            for n in ast.walk(fn):
                if (isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                        and n is not fn):
                    self._by_name.setdefault(n.name, []).append(
                        f"{fid}.{n.name}")
                    self._fn_nodes.setdefault(f"{fid}.{n.name}", n)

        for name, fn in sorted(mod.functions.items()):
            reg_tree(f"{mod.path}::{name}", fn)
        for cname, cinfo in sorted(mod.classes.items()):
            for mname, meth in sorted(cinfo.methods.items()):
                reg_tree(f"{mod.path}::{cname}.{mname}", meth)
                # Attribute calls can only land on METHODS: the
                # dynamic-dispatch fallback must not resolve `x.f()` to
                # a module function or a nested def.
                self._methods_by_name.setdefault(mname, []).append(
                    f"{mod.path}::{cname}.{mname}")

    # -- pass 2: per-function summaries ---------------------------------

    def _summarize_module(self, mod: _ModuleInfo) -> None:
        for name, fn in sorted(mod.functions.items()):
            self._summarize_function(mod, None, f"{mod.path}::{name}", fn)
        for cname, cinfo in sorted(mod.classes.items()):
            for mname, meth in sorted(cinfo.methods.items()):
                self._summarize_function(
                    mod, cinfo, f"{mod.path}::{cname}.{mname}", meth)

    def _summarize_function(self, mod: _ModuleInfo,
                            cinfo: Optional[_ClassInfo], fid: str,
                            fn: ast.AST) -> None:
        info = FunctionInfo(fid=fid, path=mod.path,
                            line=getattr(fn, "lineno", 1),
                            cls=cinfo.name if cinfo else None,
                            name=getattr(fn, "name", "?"))
        local_types = self._infer_local_types(fn)
        nested = {n.name: f"{fid}.{n.name}"
                  for n in ast.walk(fn)
                  if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                  and n is not fn}
        env = _ResolveEnv(self, mod, cinfo, local_types, nested)

        body: list[ast.AST] = list(ast.iter_child_nodes(fn))
        self._walk_body(body, info, env, region_stack=[])
        self.functions[fid] = info
        # Nested defs become their own (callable-by-name) functions.
        for n in ast.walk(fn):
            if (isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and n is not fn and "." not in getattr(n, "name", "")):
                nfid = nested[n.name]
                if nfid not in self.functions:
                    self._summarize_function(mod, cinfo, nfid, n)

    def _infer_local_types(self, fn: ast.AST) -> dict[str, str]:
        """Single-assignment local var -> program class name (from
        `v = D(...)` / `v = D.from_x(...)`); conflicts drop out."""
        types: dict[str, str] = {}
        for node in ast.walk(fn):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                ctor = self._value_ctor(node.value)
                if ctor is None:
                    continue
                cls = self._ctor_class_name(ctor)
                name = node.targets[0].id
                if cls is not None:
                    types[name] = "?" if types.get(name, cls) != cls else cls
        return {k: v for k, v in types.items() if v != "?"}

    def _walk_body(self, nodes: list[ast.AST], info: FunctionInfo,
                   env: "_ResolveEnv",
                   region_stack: list[LockRegion]) -> None:
        """Collect calls / acquisitions / costly calls, attributing them
        to every enclosing lock region. Nested function/class bodies are
        NOT executed here (their own summaries cover them)."""
        for node in nodes:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda, ast.ClassDef)):
                continue
            if isinstance(node, (ast.With, ast.AsyncWith)):
                self._walk_with(node, info, env, region_stack)
                continue
            if isinstance(node, ast.Call):
                self._note_call(node, info, env, region_stack)
            self._walk_body(list(ast.iter_child_nodes(node)), info, env,
                            region_stack)

    def _walk_with(self, node: ast.AST, info: FunctionInfo,
                   env: "_ResolveEnv",
                   region_stack: list[LockRegion]) -> None:
        opened: list[LockRegion] = []
        for item in node.items:  # type: ignore[attr-defined]
            # The context expression itself runs under the OUTER locks.
            if isinstance(item.context_expr, ast.Call):
                self._note_call(item.context_expr, info, env, region_stack)
            else:
                for sub in ast.walk(item.context_expr):
                    if isinstance(sub, ast.Call):
                        self._note_call(sub, info, env, region_stack)
            acq, raw = env.resolve_lock(item.context_expr)
            if acq is not None:
                info.acquires.append(acq)
                for r in region_stack:
                    r.inner_acqs.append(acq)
                region = LockRegion(acq=acq, calls=[], inner_acqs=[],
                                    costly=[])
                info.regions.append(region)
                region_stack.append(region)
                opened.append(region)
            elif raw is not None:
                info.unresolved_locks.append(
                    (raw, item.context_expr.lineno))
        self._walk_body(list(node.body), info, env,  # type: ignore[attr-defined]
                        region_stack)
        for region in opened:
            region_stack.remove(region)

    def _note_call(self, call: ast.Call, info: FunctionInfo,
                   env: "_ResolveEnv",
                   region_stack: list[LockRegion]) -> None:
        cs = env.resolve_call(call)
        if cs is not None:
            info.calls.append(cs)
            for r in region_stack:
                r.calls.append(cs)
        t = _terminal(call.func)
        if t and ((isinstance(call.func, ast.Attribute) and t in COSTLY)
                  or (isinstance(call.func, ast.Name)
                      and t in (COSTLY | COSTLY_BARE))):
            info.costly.append((t, call.lineno))
            for r in region_stack:
                r.costly.append((t, call.lineno))

    # -- dynamic dispatch -----------------------------------------------

    def dispatch(self, name: str) -> tuple[str, ...]:
        """Dynamic-dispatch fallback: every program METHOD named
        `name`, or () when the name is a builtin/stdlib-protocol method
        or more than _DISPATCH_CAP program methods share it (too common
        to carry signal) or none do."""
        if name in _BUILTIN_METHODS:
            return ()
        fids = self._methods_by_name.get(name, ())
        if 0 < len(fids) <= _DISPATCH_CAP:
            return tuple(sorted(fids))
        return ()

    def class_info(self, name: str) -> Optional[_ClassInfo]:
        return self._classes.get(name)

    def method_of(self, cls: str, name: str) -> Optional[str]:
        """Resolve cls.name through the program base-class chain."""
        seen: set[str] = set()
        stack = [cls]
        while stack:
            c = stack.pop(0)
            if c in seen:
                continue
            seen.add(c)
            ci = self._classes.get(c)
            if ci is None:
                continue
            if name in ci.methods:
                return f"{ci.path}::{ci.name}.{name}"
            stack.extend(ci.bases)
        return None

    def lock_attr_of(self, cls: str, attr: str) -> Optional[LockDecl]:
        seen: set[str] = set()
        stack = [cls]
        while stack:
            c = stack.pop(0)
            if c in seen:
                continue
            seen.add(c)
            ci = self._classes.get(c)
            if ci is None:
                continue
            if attr in ci.lock_attrs:
                return ci.lock_attrs[attr]
            stack.extend(ci.bases)
        return None

    def unique_lock_attr(self, attr: str) -> Optional[LockDecl]:
        decls = self._locks_by_attr.get(attr, [])
        return decls[0] if len(decls) == 1 else None

    # -- held-lock reachability -----------------------------------------

    def _reach(self, roots: tuple[str, ...]) -> dict[
            str, tuple[tuple[str, ...], bool]]:
        """BFS over the call graph from `roots`: fid -> (shortest chain
        of fids ending at fid, chain is all-self-calls). Deterministic:
        sorted expansion, first (shortest) chain wins."""
        out: dict[str, tuple[tuple[str, ...], bool]] = {}
        frontier: list[tuple[str, tuple[str, ...], bool]] = [
            (r, (r,), True) for r in sorted(roots)
        ]
        while frontier:
            nxt: list[tuple[str, tuple[str, ...], bool]] = []
            for fid, chain, pure in frontier:
                if fid in out:
                    continue
                out[fid] = (chain, pure)
                fn = self.functions.get(fid)
                if fn is None:
                    continue
                for cs in fn.calls:
                    hop_pure = pure and cs.kind == "self"
                    for tgt in cs.targets:
                        if tgt not in out:
                            nxt.append((tgt, chain + (tgt,), hop_pure))
            frontier = sorted(nxt)
        return out

    def region_reach(self, region: LockRegion) -> dict[
            str, tuple[tuple[str, ...], bool, int]]:
        """Functions reachable from the region's calls: fid ->
        (chain, self_pure, line of the region call that roots it)."""
        out: dict[str, tuple[tuple[str, ...], bool, int]] = {}
        for cs in sorted(region.calls, key=lambda c: c.line):
            if not cs.targets:
                continue
            reach = self._reach(cs.targets)
            for fid, (chain, pure) in reach.items():
                if fid not in out or len(chain) < len(out[fid][0]):
                    out[fid] = (chain, pure and cs.kind == "self", cs.line)
        return out

    # -- lock-order edges -----------------------------------------------

    def lock_edges(self) -> list[LockEdge]:
        if self._edges is not None:
            return self._edges
        edges: dict[tuple[str, str], LockEdge] = {}

        def consider(e: LockEdge) -> None:
            k = (e.src, e.dst)
            old = edges.get(k)
            if (old is None or len(e.chain) < len(old.chain)
                    or (len(e.chain) == len(old.chain)
                        and (e.src_path, e.src_line)
                        < (old.src_path, old.src_line))):
                edges[k] = e

        for fid in sorted(self.functions):
            fn = self.functions[fid]
            for region in fn.regions:
                src = region.acq.decl
                for inner in region.inner_acqs:
                    consider(LockEdge(
                        src=src.lock_id, dst=inner.decl.lock_id,
                        src_path=fn.path, src_line=inner.line,
                        dst_path=fn.path, dst_line=inner.line,
                        chain=(),
                        self_pure=(region.acq.via_self and inner.via_self
                                   and src.owner == inner.decl.owner),
                    ))
                for tfid, (chain, pure, call_line) in sorted(
                        self.region_reach(region).items()):
                    tfn = self.functions.get(tfid)
                    if tfn is None:
                        continue
                    for acq in tfn.acquires:
                        consider(LockEdge(
                            src=src.lock_id, dst=acq.decl.lock_id,
                            src_path=fn.path, src_line=call_line,
                            dst_path=tfn.path, dst_line=acq.line,
                            chain=chain,
                            self_pure=(pure and region.acq.via_self
                                       and acq.via_self
                                       and src.owner == acq.decl.owner),
                        ))
        self._edges = sorted(
            edges.values(), key=lambda e: (e.src, e.dst))
        return self._edges

    def lock_cycles(self) -> list[tuple[str, ...]]:
        """Cycles in the lock-order graph, as sorted lock-id tuples:
        multi-lock strongly connected components, plus self-edges whose
        witness path proves the SAME instance re-acquires (all-self
        chains on a non-reentrant Lock)."""
        adj: dict[str, set[str]] = {}
        for e in self.lock_edges():
            if e.src != e.dst:
                adj.setdefault(e.src, set()).add(e.dst)
        sccs = _tarjan(adj)
        out = [tuple(sorted(c)) for c in sccs if len(c) > 1]
        for e in self.lock_edges():
            if (e.src == e.dst and e.self_pure
                    and self.locks[e.src].kind == "Lock"):
                out.append((e.src,))
        return sorted(set(out))

    def cyclic_edges(self) -> list[LockEdge]:
        """Edges participating in a cycle (both endpoints in one SCC,
        or a proven self-edge)."""
        in_cycle = {c for cyc in self.lock_cycles() for c in cyc
                    if len(cyc) > 1}
        selfs = {cyc[0] for cyc in self.lock_cycles() if len(cyc) == 1}
        out = []
        for e in self.lock_edges():
            if e.src in in_cycle and e.dst in in_cycle and e.src != e.dst:
                out.append(e)
            elif e.src == e.dst and e.src in selfs and e.self_pure:
                out.append(e)
        return out

    def hierarchy_doc(self) -> dict[str, Any]:
        """The checked-in tools/lock_hierarchy.json payload: every lock
        creation site + every static order edge (with witness chains),
        and any cycles. The runtime witness keys locks by (path, line)
        and checks observed orders against `edges`."""
        return {
            "version": 1,
            "locks": [
                dataclasses.asdict(self.locks[k])
                for k in sorted(self.locks)
            ],
            "edges": [
                {
                    "src": e.src, "dst": e.dst,
                    "via": e.render_chain(),
                    "site": f"{e.src_path}:{e.src_line}",
                    "acquired_at": f"{e.dst_path}:{e.dst_line}",
                }
                for e in self.lock_edges()
            ],
            "cycles": [list(c) for c in self.lock_cycles()],
        }

    # -- jit-boundary analysis ------------------------------------------

    def _jit_pass(self) -> None:
        for path in sorted(self.modules):
            mod = self.modules[path]
            self.jit_sites.extend(_JitScanner(self, mod).scan())
        self.jit_sites.sort(key=lambda s: (s.path, s.line))

    def unbounded_families(self) -> list[JitSite]:
        return [s for s in self.jit_sites
                if s.kind == "family" and s.bounded is False]

    def graph_doc(self) -> dict[str, Any]:
        """`tools/lint.py --graph` payload: per-function call targets +
        held-lock regions, for debugging the analyses."""
        funcs = {}
        for fid in sorted(self.functions):
            fn = self.functions[fid]
            funcs[fid] = {
                "calls": [
                    {"line": c.line, "raw": c.raw, "kind": c.kind,
                     "targets": list(c.targets)}
                    for c in sorted(fn.calls, key=lambda c: c.line)
                ],
                "acquires": [
                    {"line": a.line, "lock": a.decl.lock_id}
                    for a in fn.acquires
                ],
                "regions": [
                    {"lock": r.acq.decl.lock_id, "line": r.acq.line,
                     "reaches": sorted(
                         lk.lock_id for lk in self._region_lock_set(r))}
                    for r in fn.regions
                ],
            }
            if fn.unresolved_locks:
                funcs[fid]["unresolved_locks"] = [
                    {"raw": raw, "line": line}
                    for raw, line in fn.unresolved_locks
                ]
        return {"functions": funcs, "locks": sorted(self.locks),
                "jit_sites": [dataclasses.asdict(s) for s in self.jit_sites]}

    def _region_lock_set(self, region: LockRegion) -> list[LockDecl]:
        out = {a.decl.lock_id: a.decl for a in region.inner_acqs}
        for tfid in self.region_reach(region):
            tfn = self.functions.get(tfid)
            if tfn:
                for a in tfn.acquires:
                    out[a.decl.lock_id] = a.decl
        return [out[k] for k in sorted(out)]


# ---------------------------------------------------------------------------
# Resolution environment (one function's scope).
# ---------------------------------------------------------------------------

class _ResolveEnv:
    def __init__(self, program: Program, mod: _ModuleInfo,
                 cinfo: Optional[_ClassInfo],
                 local_types: dict[str, str],
                 nested: dict[str, str]):
        self.program = program
        self.mod = mod
        self.cinfo = cinfo
        self.local_types = local_types
        self.nested = nested

    # -- calls ----------------------------------------------------------

    def resolve_call(self, call: ast.Call) -> Optional[CallSite]:
        p = self.program
        raw = _dotted(call.func)
        if raw is None:
            # `self._pool().submit(...)`: the receiver is itself a call
            # — try return-type inference, then dynamic dispatch.
            t = _terminal(call.func)
            if t is None or not isinstance(call.func, ast.Attribute):
                return None
            rc = self._receiver_class(call.func.value)
            if rc is not None:
                tgt0 = p.method_of(rc, t)
                if tgt0 is not None:
                    return CallSite(call.lineno, f"(...).{t}", (tgt0,),
                                    "typed")
            dyn0 = p.dispatch(t)
            return CallSite(call.lineno, f"(...).{t}", dyn0,
                            "dynamic" if dyn0 else "unresolved")
        line = call.lineno
        # bare name: nested def, module function, imported object,
        # program class constructor
        if isinstance(call.func, ast.Name):
            name = call.func.id
            if name in self.nested:
                return CallSite(line, raw, (self.nested[name],), "local")
            if name in self.mod.functions:
                return CallSite(line, raw, (f"{self.mod.path}::{name}",),
                                "module")
            if name in self.mod.classes:
                init = p.method_of(name, "__init__")
                return CallSite(line, raw, (init,) if init else (), "class")
            full = self.mod.aliases.get(name)
            if full is not None:
                tgt = self._resolve_imported(full)
                if tgt is not None:
                    return CallSite(line, raw, tgt, "import")
            ci = p.class_info(name)
            if ci is not None:
                init = p.method_of(name, "__init__")
                return CallSite(line, raw, (init,) if init else (), "class")
            return CallSite(line, raw, (), "unresolved")
        # attribute call
        assert isinstance(call.func, ast.Attribute)
        meth = call.func.attr
        recv = call.func.value
        recv_cls = self._receiver_class(recv)
        if recv_cls is not None:
            kind = ("self" if isinstance(recv, ast.Name)
                    and recv.id in ("self", "cls") else "typed")
            tgt2 = p.method_of(recv_cls, meth)
            if tgt2 is not None:
                return CallSite(line, raw, (tgt2,), kind)
            # fall through: a method the class gets dynamically
        d = _dotted(recv)
        if d is not None:
            # module attribute: tpusched.engine.solve_core style
            head = d.split(".")[0]
            full = self.mod.aliases.get(head)
            base = d if head == d else None
            dotted_mod = (full + d[len(head):]) if full else (base or d)
            relpath = _module_to_relpath(dotted_mod)
            m = p.modules.get(relpath)
            if m is not None:
                if meth in m.functions:
                    return CallSite(line, raw, (f"{relpath}::{meth}",),
                                    "import")
                if meth in m.classes:
                    init = p.method_of(meth, "__init__")
                    return CallSite(line, raw, (init,) if init else (),
                                    "class")
                # The receiver IS a module: `tracing.frob(...)` names a
                # module function we don't know — method dispatch must
                # not guess (`subprocess.run` -> SimDriver.run).
                return CallSite(line, raw, (), "unresolved")
            if head in self.mod.module_aliases:
                # The chain is rooted at an `import X`-bound name and
                # did not resolve to a program module above, so the
                # whole receiver subtree is FOREIGN (`jnp.linalg`,
                # `subprocess`) — never method dispatch. Program-module
                # ATTRIBUTES (`tracing.DEFAULT.record` via `from
                # tpusched import trace as tracing`) keep the fallback:
                # their head is not an `import X` binding.
                return CallSite(line, raw, (), "unresolved")
        dyn = p.dispatch(meth)
        if dyn:
            return CallSite(line, raw, dyn, "dynamic")
        return CallSite(line, raw, (), "unresolved")

    def _return_class(self, call: ast.Call) -> Optional[str]:
        """Return type of a single-target program call, when every
        `return` provably yields one program class (`self._pool()` ->
        _OrderedFetchWorker via `return self._fetch_pool`)."""
        cs = self.resolve_call(call)
        if cs is None or len(cs.targets) != 1:
            return None
        fid = cs.targets[0]
        node = self.program._fn_nodes.get(fid)
        if node is None:
            return None
        owner_ci = None
        tail = fid.split("::", 1)[-1]
        if "." in tail:
            owner_ci = self.program.class_info(tail.split(".")[0])
        local_types = self.program._infer_local_types(node)
        classes: set[str] = set()
        for n in ast.walk(node):
            if not isinstance(n, ast.Return) or n.value is None:
                continue
            v = n.value
            if (isinstance(v, ast.Attribute)
                    and isinstance(v.value, ast.Name)
                    and v.value.id == "self" and owner_ci is not None):
                t = owner_ci.attr_types.get(v.attr)
                if t and t != "?":
                    classes.add(t)
                    continue
            elif isinstance(v, ast.Call):
                t2 = Program._ctor_class_name(v)
                if t2 is not None:
                    classes.add(t2)
                    continue
            elif isinstance(v, ast.Name) and v.id in local_types:
                classes.add(local_types[v.id])
                continue
            return None  # a return we can't type: give up
        if len(classes) == 1:
            cls = classes.pop()
            return cls if self.program.class_info(cls) else None
        return None

    def _resolve_imported(self, full: str) -> Optional[tuple[str, ...]]:
        """'tpusched.engine.solve_core' -> the program function, or a
        class -> its __init__."""
        if "." not in full:
            return None
        modpart, _, name = full.rpartition(".")
        relpath = _module_to_relpath(modpart)
        m = self.program.modules.get(relpath)
        if m is None:
            return None
        if name in m.functions:
            return (f"{relpath}::{name}",)
        if name in m.classes:
            init = self.program.method_of(name, "__init__")
            return (init,) if init else ()
        return None

    def _receiver_class(self, recv: ast.AST) -> Optional[str]:
        """Program class of a call/lock receiver expression, when
        inferable: self/cls, a typed local, a class reference, a typed
        self-attribute, or the return type of a typed-returning
        program method (`self._pool().submit`)."""
        if isinstance(recv, ast.Name):
            if recv.id in ("self", "cls") and self.cinfo is not None:
                return self.cinfo.name
            lt = self.local_types.get(recv.id)
            if lt is not None:
                return lt
            # `TraceCollector.record(...)`-style class-attr calls.
            if _CLASS_LIKE.match(recv.id) and (
                    self.program.class_info(recv.id) is not None):
                return recv.id
            return None
        if isinstance(recv, ast.Call):
            return self._return_class(recv)
        if (isinstance(recv, ast.Attribute)
                and isinstance(recv.value, ast.Name)):
            if recv.value.id == "self" and self.cinfo is not None:
                cls = self.cinfo.attr_types.get(recv.attr)
                if cls is not None and cls != "?":
                    return cls if self.program.class_info(cls) else None
            v = self.local_types.get(recv.value.id)
            if v is not None:
                ci = self.program.class_info(v)
                if ci is not None:
                    cls2 = ci.attr_types.get(recv.attr)
                    if cls2 and cls2 != "?":
                        return cls2
        return None

    # -- locks ----------------------------------------------------------

    def resolve_lock(self, expr: ast.AST) -> tuple[
            Optional[LockAcq], Optional[str]]:
        """(resolved acquisition, raw lock-ish spelling). (None, raw)
        for a lock-looking context expr we cannot name; (None, None)
        for non-lock context managers."""
        t = _terminal(expr)
        raw = _dotted(expr) or (t or "?")
        p = self.program
        looks_lockish = t is not None and (
            "lock" in t.lower() or t in ("_cv",)
            or any(d.attr == t for d in p.locks.values()))
        if t is None or not looks_lockish:
            return None, None
        # bare global
        if isinstance(expr, ast.Name):
            decl = self.mod.global_locks.get(t)
            if decl is None:
                decl = p.unique_lock_attr(t)
            if decl is not None:
                return LockAcq(decl, expr.lineno, raw, False), None
            return None, raw
        if not isinstance(expr, ast.Attribute):
            return None, raw
        recv = expr.value
        via_self = isinstance(recv, ast.Name) and recv.id == "self"
        recv_cls = self._receiver_class(recv)
        if recv_cls is not None:
            decl = p.lock_attr_of(recv_cls, t)
            if decl is not None:
                return LockAcq(decl, expr.lineno, raw, via_self), None
        decl = p.unique_lock_attr(t)
        if decl is not None:
            return LockAcq(decl, expr.lineno, raw,
                           via_self and decl.owner != ""
                           and self.cinfo is not None
                           and decl.owner == self.cinfo.name), None
        return None, raw


# ---------------------------------------------------------------------------
# Jit-boundary scanner.
# ---------------------------------------------------------------------------

class _JitScanner:
    """Classify every jax.jit / Engine._traced_jit construction site in
    one module (class docstring of Program; consumed by TPL103/104/105)."""

    def __init__(self, program: Program, mod: _ModuleInfo):
        self.program = program
        self.mod = mod
        self.parents: dict[ast.AST, ast.AST] = {}
        for node in ast.walk(mod.tree):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node

    def _is_jit_call(self, node: ast.AST) -> bool:
        if not isinstance(node, ast.Call):
            return False
        d = _dotted(node.func)
        if d is None:
            return False
        head = d.split(".")[0]
        norm = d
        if head in self.mod.aliases:
            rest = d[len(head):]
            norm = self.mod.aliases[head] + rest
        return (norm == "jax.jit" or norm.endswith("._traced_jit")
                or d.endswith("._traced_jit"))

    def scan(self) -> list[JitSite]:
        out: list[JitSite] = []
        for node in ast.walk(self.mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if self._is_jit_decorator(dec):
                        out.append(JitSite(
                            path=self.mod.path, line=node.lineno,
                            func=None, kind="decorator"))
            if self._is_jit_call(node):
                out.append(self._classify(node))  # type: ignore[arg-type]
        return out

    def _is_jit_decorator(self, dec: ast.AST) -> bool:
        d = _dotted(dec) or (
            _dotted(dec.func) if isinstance(dec, ast.Call) else None)
        if d is None:
            return False
        head = d.split(".")[0]
        if head in self.mod.aliases:
            d = self.mod.aliases[head] + d[len(head):]
        return d == "jax.jit"

    def _enclosing_function(self, node: ast.AST) -> Optional[ast.AST]:
        p = self.parents.get(node)
        while p is not None:
            if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return p
            p = self.parents.get(p)
        return None

    def _enclosing_fid(self, fn: ast.AST) -> Optional[str]:
        name = getattr(fn, "name", None)
        if name is None:
            return None
        p = self.parents.get(fn)
        while p is not None:
            if isinstance(p, ast.ClassDef):
                return f"{self.mod.path}::{p.name}.{name}"
            if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef)):
                outer = self._enclosing_fid(p)
                return f"{outer}.{name}" if outer else None
            p = self.parents.get(p)
        return f"{self.mod.path}::{name}"

    def _classify(self, call: ast.Call) -> JitSite:
        fn = self._enclosing_function(call)
        if fn is None:
            return JitSite(path=self.mod.path, line=call.lineno,
                           func=None, kind="module")
        fid = self._enclosing_fid(fn)
        # What does the jit value land in?
        assign = self.parents.get(call)
        targets: list[ast.AST] = []
        if isinstance(assign, ast.Assign) and assign.value is call:
            targets = list(assign.targets)
        elif (isinstance(assign, ast.AnnAssign)
              and assign.value is call and assign.target is not None):
            targets = [assign.target]
        family_t = next((t for t in targets
                         if isinstance(t, ast.Subscript)), None)
        attr_t = next((t for t in targets
                       if isinstance(t, ast.Attribute)
                       and isinstance(t.value, ast.Name)
                       and t.value.id == "self"), None)
        name_t = next((t for t in targets if isinstance(t, ast.Name)), None)
        if family_t is None and name_t is not None:
            family_t = self._later_store(fn, name_t.id, call.lineno)
        if family_t is not None:
            fam = _dotted(family_t.value) or "?"
            bounded, via = self._family_bounded(fn, family_t)
            return JitSite(path=self.mod.path, line=call.lineno, func=fid,
                           kind="family", family=fam, bounded=bounded,
                           bound_via=via)
        if attr_t is not None:
            return JitSite(path=self.mod.path, line=call.lineno, func=fid,
                           kind="attr_cache",
                           family=f"self.{attr_t.attr}")
        return JitSite(path=self.mod.path, line=call.lineno, func=fid,
                       kind="per_call")

    def _later_store(self, fn: ast.AST, name: str,
                     after_line: int) -> Optional[ast.Subscript]:
        """`f = jax.jit(...); CACHE[key] = f` — find the memo store of a
        locally-bound jit so the site classifies as a family."""
        for node in ast.walk(fn):
            if (isinstance(node, ast.Assign)
                    and node.lineno >= after_line
                    and isinstance(node.value, ast.Name)
                    and node.value.id == name):
                for t in node.targets:
                    if isinstance(t, ast.Subscript):
                        return t
        return None

    def _family_bounded(self, fn: ast.AST,
                        sub: ast.Subscript) -> tuple[bool, str]:
        """A memo-dict jit family is bounded when its key provably comes
        from a bounding helper (pow2/bucket/cap/clamp — directly, via a
        local, or one call-hop up through the enclosing function's
        parameter), or the memo carries an explicit size-cap guard
        (a len(<memo>) comparison in its module)."""
        key = sub.slice
        if self._bounding_expr(fn, key):
            return True, "bounding key"
        # one-hop: key is a parameter; every program caller passes a
        # bounding expression.
        pname = key.id if isinstance(key, ast.Name) else None
        if pname is not None and self._param_bounded(fn, pname):
            return True, "bounded by callers"
        fam = _dotted(sub.value)
        if fam is not None and self._len_capped(fam):
            return True, "len-capped memo"
        return False, ""

    def _bounding_expr(self, fn: ast.AST, expr: ast.AST) -> bool:
        if isinstance(expr, ast.Call):
            t = _terminal(expr.func)
            return bool(t and _BOUNDING_NAME.search(t))
        if isinstance(expr, ast.Name):
            for node in ast.walk(fn):
                if (isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)
                        and node.targets[0].id == expr.id
                        and isinstance(node.value, ast.Call)):
                    t = _terminal(node.value.func)
                    if t and _BOUNDING_NAME.search(t):
                        return True
        return False

    def _param_bounded(self, fn: ast.AST, pname: str) -> bool:
        args = getattr(fn, "args", None)
        if args is None:
            return False
        names = [a.arg for a in args.args if a.arg not in ("self", "cls")]
        if pname not in names:
            return False
        idx = names.index(pname)
        fname = getattr(fn, "name", "")
        callers = 0
        for other in self.program.functions.values():
            for cs in other.calls:
                if cs.raw.split(".")[-1] != fname:
                    continue
                callers += 1
                call = self._find_call(other, cs.line, fname)
                if call is None or len(call.args) <= idx:
                    return False
                caller_fn = self._find_function_node(other)
                if caller_fn is None or not self._bounding_expr(
                        caller_fn, call.args[idx]):
                    return False
        return callers > 0

    def _find_function_node(self, info: FunctionInfo) -> Optional[ast.AST]:
        mod = self.program.modules.get(info.path)
        if mod is None:
            return None
        for node in ast.walk(mod.tree):
            if (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and node.name == info.name
                    and node.lineno == info.line):
                return node
        return None

    def _find_call(self, info: FunctionInfo, line: int,
                   fname: str) -> Optional[ast.Call]:
        fn = self._find_function_node(info)
        if fn is None:
            return None
        for node in ast.walk(fn):
            if (isinstance(node, ast.Call) and node.lineno == line
                    and _terminal(node.func) == fname):
                return node
        return None

    def _len_capped(self, fam: str) -> bool:
        """`if len(<memo>) >= N: <evict>` anywhere in the module — the
        crude-but-honest bound for repr/mesh-keyed caches."""
        tail = fam.split(".")[-1]
        for node in ast.walk(self.mod.tree):
            if not isinstance(node, ast.Compare):
                continue
            exprs = [node.left] + list(node.comparators)
            for e in exprs:
                if (isinstance(e, ast.Call) and isinstance(e.func, ast.Name)
                        and e.func.id == "len" and e.args):
                    d = _dotted(e.args[0])
                    if d is not None and d.split(".")[-1] == tail:
                        return True
        return False


# ---------------------------------------------------------------------------
# Tarjan SCC (iterative: product files can nest call chains deeply).
# ---------------------------------------------------------------------------

def _tarjan(adj: dict[str, set[str]]) -> list[list[str]]:
    index: dict[str, int] = {}
    low: dict[str, int] = {}
    on_stack: set[str] = set()
    stack: list[str] = []
    sccs: list[list[str]] = []
    counter = 0
    nodes = sorted(set(adj) | {v for vs in adj.values() for v in vs})

    for root in nodes:
        if root in index:
            continue
        work: list[tuple[str, Iterator[str]]] = [
            (root, iter(sorted(adj.get(root, ()))))
        ]
        index[root] = low[root] = counter
        counter += 1
        stack.append(root)
        on_stack.add(root)
        while work:
            v, it = work[-1]
            advanced = False
            for w in it:
                if w not in index:
                    index[w] = low[w] = counter
                    counter += 1
                    stack.append(w)
                    on_stack.add(w)
                    work.append((w, iter(sorted(adj.get(w, ())))))
                    advanced = True
                    break
                if w in on_stack:
                    low[v] = min(low[v], index[w])
            if advanced:
                continue
            work.pop()
            if work:
                pv = work[-1][0]
                low[pv] = min(low[pv], low[v])
            if low[v] == index[v]:
                comp = []
                while True:
                    w = stack.pop()
                    on_stack.discard(w)
                    comp.append(w)
                    if w == v:
                        break
                sccs.append(comp)
    return sccs


def iter_jit_calls(tree: ast.AST,
                   aliases: dict[str, str]) -> Iterator[
                       tuple[ast.Call, int]]:
    """(call node, index of the traced-function argument) for every
    jax.jit / _traced_jit CALL in `tree` — jax.jit(fn, ...) carries fn
    at 0, Engine._traced_jit(name, fn) at 1. Rules use this for the
    per-file jit checks (TPL105) without building a Program."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func)
        if d is None:
            continue
        head = d.split(".")[0]
        norm = (aliases[head] + d[len(head):]) if head in aliases else d
        if norm == "jax.jit":
            yield node, 0
        elif norm.endswith("._traced_jit") or d.endswith("._traced_jit"):
            yield node, 1


def write_hierarchy(path: Path, program: Program) -> None:
    path.write_text(
        json.dumps(program.hierarchy_doc(), indent=2, sort_keys=True) + "\n"
    )


def load_hierarchy(path: Path) -> Optional[dict[str, Any]]:
    p = Path(path)
    if not p.exists():
        return None
    doc = json.loads(p.read_text())
    return doc if isinstance(doc, dict) else None
