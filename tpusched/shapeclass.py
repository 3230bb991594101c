"""Shape-class registry and persistent-cache wiring (ROADMAP item 3).

XLA compiles one program per (jit family, argument shape tuple), and every
shape in this codebase is a pure function of the static ``Buckets`` plus a
handful of pow2-bucketed request parameters (top-k, explain-k, the warm
incremental frontier cap). That makes "every program this server will ever
trace" a FINITE, LISTABLE set — which this module formalizes:

* ``ShapeClass`` — one jit family Engine labels through ``_traced_jit``
  (engine.py), with the pow2 parameter that keys it (k / cap) when one
  exists.
* ``ShapeClassRegistry`` — the enumerable, JSON-round-trippable set of
  classes derived from an ``EngineConfig`` + explicit ``Buckets`` + the
  serving toggles (explain on/off, warm bitwise/incremental). The families
  here are exactly the bounded families tpuschedlint TPL104 proves at the
  engine's call sites; ``tools/check.py``'s ``prewarm`` stage cross-checks
  the two by AST.
* ``Engine.prewarm(registry)`` (tpusched/engine.py) traces every class at
  boot, so a promoted standby serves its first request with zero new
  compiles; the canonical per-family workloads live in
  ``prewarm_records`` / ``incremental_unassignable`` here.
* ``enable_persistent_cache`` — jax's persistent compilation cache, so a
  fresh PROCESS (bench round N+1, a restarted sidecar) reuses round N's
  XLA instead of recompiling it.

This module must import without jax (tools/check.py runs its registry
smoke in jax-less environments): everything jax-touching is behind a lazy
import inside ``enable_persistent_cache``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from typing import Any, Iterator

from tpusched.config import Buckets, EngineConfig

# Compile-event attribution causes (ledger.CompileWatcher events carry
# one): boot-time prewarm work must never read as a serving regression —
# the PR 13 cycle sentinel keys "compile" anomalies off per-cycle counter
# deltas, and a prewarm runs before any cycle, but the timeline still
# needs the split for forensics.
CAUSE_SERVE = "serve"
CAUSE_PREWARM = "prewarm"

# Set from outside, JAX reads this itself: enable_persistent_cache()
# then places no cache directory of its own.
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".xla_cache")

REGISTRY_VERSION = 1


def k_bucket(k: int, n: int) -> int:
    """Pow2 compile bucket for a top-k request — MUST mirror
    Engine._k_bucket (pinned by tests/test_prewarm.py): O(log N) programs,
    callers slice the first k columns of the bucketed result."""
    kb = 1 << (max(int(k), 1) - 1).bit_length()
    return min(kb, int(n))


def frontier_caps(pods_bucket: int) -> tuple[int, ...]:
    """Every frontier-compaction width Engine._frontier_bucket can emit
    for a pod bucket of P (pinned against the engine formula by
    tests/test_prewarm.py): pow2 caps from the 64 floor up to (but not
    reaching) P, plus 0 = full-width rounds once the cap would cover the
    pod axis anyway. P <= 64 therefore has exactly one class: cap 0."""
    caps = []
    c = 64
    while c < int(pods_bucket):
        caps.append(c)
        c *= 2
    caps.append(0)
    return tuple(caps)


def topk_buckets(nodes_bucket: int) -> tuple[int, ...]:
    """All pow2 top-k buckets a ScoreBatch request can key (k is
    client-chosen in [1, N], so the reachable set is every pow2 <= N)."""
    out = []
    kb = 1
    while kb <= int(nodes_bucket):
        out.append(kb)
        kb *= 2
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class ShapeClass:
    """One jit family the engine will trace: `family` is the exact label
    Engine._traced_jit attaches (and ledger.COMPILES records), `kind`
    groups it for reporting, `params` carries the pow2 parameter baked
    into parameterized families (k for top-k/probe, cap for incremental)."""

    family: str
    kind: str  # "solve" | "score" | "explain" | "warm"
    params: tuple[tuple[str, int], ...] = ()

    def to_dict(self) -> dict:
        return {"family": self.family, "kind": self.kind,
                "params": dict(self.params)}

    @staticmethod
    def from_dict(d: dict) -> "ShapeClass":
        return ShapeClass(
            family=str(d["family"]), kind=str(d["kind"]),
            params=tuple(sorted(
                (str(k), int(v)) for k, v in dict(d.get("params", {})).items()
            )),
        )


def _config_fingerprint(config: EngineConfig, buckets: Buckets) -> str:
    """Stable digest of everything that keys compiled programs: two
    registries agree iff their engines trace the same program set."""
    blob = json.dumps(
        {"config": dataclasses.asdict(config),
         "buckets": dataclasses.asdict(buckets)},
        sort_keys=True, default=str,
    )
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


@dataclasses.dataclass(frozen=True)
class ShapeClassRegistry:
    """The finite program set of one serving configuration. Frozen and
    JSON-round-trippable so a leader can publish it and a standby can
    prewarm the mirrored set (tpusched/replicate.py)."""

    classes: tuple[ShapeClass, ...]
    buckets: Buckets
    mode: str
    mesh_shape: tuple[int, int]
    explain: bool
    explain_k: int
    warm: str | None
    config_fingerprint: str

    def families(self) -> tuple[str, ...]:
        return tuple(c.family for c in self.classes)

    def __len__(self) -> int:
        return len(self.classes)

    def __iter__(self) -> Iterator[ShapeClass]:
        return iter(self.classes)

    def __contains__(self, family: object) -> bool:
        if isinstance(family, ShapeClass):
            family = family.family
        return any(c.family == family for c in self.classes)

    def to_json(self) -> str:
        return json.dumps({
            "version": REGISTRY_VERSION,
            "config_fingerprint": self.config_fingerprint,
            "buckets": dataclasses.asdict(self.buckets),
            "mode": self.mode,
            "mesh_shape": list(self.mesh_shape),
            "explain": self.explain,
            "explain_k": self.explain_k,
            "warm": self.warm,
            "classes": [c.to_dict() for c in self.classes],
        }, sort_keys=True)

    @staticmethod
    def from_json(s: str) -> "ShapeClassRegistry":
        d = json.loads(s)
        ver = int(d.get("version", 0))
        if ver != REGISTRY_VERSION:
            raise ValueError(
                f"shape-class registry version {ver}: this build reads "
                f"version {REGISTRY_VERSION}"
            )
        return ShapeClassRegistry(
            classes=tuple(ShapeClass.from_dict(c) for c in d["classes"]),
            buckets=Buckets.from_dict(d["buckets"]),
            mode=str(d["mode"]),
            mesh_shape=tuple(int(x) for x in d["mesh_shape"]),  # type: ignore[arg-type]
            explain=bool(d["explain"]),
            explain_k=int(d["explain_k"]),
            warm=(None if d["warm"] is None else str(d["warm"])),
            config_fingerprint=str(d["config_fingerprint"]),
        )


def build_registry(
    config: EngineConfig | None = None,
    buckets: Buckets | None = None,
    *,
    explain: bool = False,
    explain_k: int = 3,
    warm: str | None = None,
    topk: tuple[int, ...] | None = None,
) -> ShapeClassRegistry:
    """Enumerate every jit family a server with this configuration will
    dispatch. `buckets` must be EXPLICIT: without pinned buckets, shapes
    float with content and no finite registry exists (the same caveat
    SnapshotBuilder documents for serving paths).

    topk narrows the score_topk_k{kb} classes to the pow2 buckets of the
    given k values (default: every pow2 <= the node bucket, the full
    client-reachable set).

    The eager "solve" wrapper (Engine._solve_jit) is deliberately ABSENT:
    no public entry point dispatches it, so prewarming it would trace a
    program serving never runs."""
    config = config or EngineConfig()
    if buckets is None:
        raise ValueError(
            "build_registry needs explicit Buckets: shape classes are a "
            "function of pinned bucket sizes (pass Buckets.fit(...) with "
            "floors for everything the cluster might hold)"
        )
    if warm not in (None, "bitwise", "incremental"):
        raise ValueError(
            f"warm={warm!r}: want None, 'bitwise', or 'incremental'"
        )
    N, P = int(buckets.nodes), int(buckets.pods)
    classes: list[ShapeClass] = [
        ShapeClass("solve_packed", "solve"),
        ShapeClass("score", "score"),
        ShapeClass("score_top1", "score"),
    ]
    if topk is None:
        kbs: tuple[int, ...] = topk_buckets(N)
    else:
        kbs = tuple(sorted({k_bucket(k, N) for k in topk}))
    classes.extend(
        ShapeClass(f"score_topk_k{kb}", "score", (("k", kb),)) for kb in kbs
    )
    if explain:
        classes.append(ShapeClass("solve_explained", "explain"))
        kb = k_bucket(min(max(int(explain_k), 1), max(N, 1)), max(N, 1))
        classes.append(
            ShapeClass(f"explain_probe_k{kb}", "explain", (("k", kb),))
        )
    if warm is not None:
        classes.append(ShapeClass("warm_cold_refresh", "warm"))
        classes.append(ShapeClass("warm_refresh", "warm"))
        if warm == "incremental":
            classes.extend(
                ShapeClass(f"warm_incremental_cap{c}", "warm", (("cap", c),))
                for c in frontier_caps(P)
            )
    return ShapeClassRegistry(
        classes=tuple(classes),
        buckets=buckets,
        mode=config.mode,
        mesh_shape=tuple(config.mesh_shape),  # type: ignore[arg-type]
        explain=bool(explain),
        explain_k=int(explain_k),
        warm=warm,
        config_fingerprint=_config_fingerprint(config, buckets),
    )


# ---------------------------------------------------------------------------
# Canonical prewarm workloads.
#
# Leaf shapes are a pure function of Buckets (SnapshotBuilder pads content
# up to explicit buckets), so a TINY synthetic cluster built at the
# registry's buckets compiles exactly the programs real traffic at those
# buckets dispatches. The warm families additionally shape-key on the
# pow2-padded dirty-row lists: the canonical delta is the smallest one
# serving produces — one upserted existing pod (pad (1,), no perms) —
# matching a session delta that touches one pod.
# ---------------------------------------------------------------------------


def incremental_unassignable(cap: int, pods_bucket: int) -> int:
    """How many unassignable filler pods the cap-`cap` representative
    needs: Engine._frontier_bucket picks the cap from
    est = |frontier| + |unassigned carry|, the canonical delta contributes
    1 frontier pod, so `cap//2 - 1` unassigned pods land est exactly at
    cap/2 (-> want == cap). cap 0 means full-width: trivial when the 64
    floor already covers the pod axis (P <= 64), otherwise est must reach
    P/2 so the pow2 bucket meets the axis."""
    P = int(pods_bucket)
    if cap == 0:
        return 0 if P <= 64 else P // 2 - 1
    return max(0, int(cap) // 2 - 1)


def prewarm_records(
    config: EngineConfig, unassignable: int = 0,
) -> tuple[list[dict], list[dict], list[dict]]:
    """Builder-style (nodes, pods, running) record lists for a prewarm
    snapshot: two schedulable nodes, one placeable pod, one running pod,
    plus `unassignable` filler pods whose requests no node can hold
    (their carry stays -1, which is what steers the incremental frontier
    estimate — see incremental_unassignable)."""
    res = config.resources[0]
    nodes = [
        {"name": f"prewarm-n{i}", "allocatable": {res: 1000.0}}
        for i in range(2)
    ]
    pods = [{"name": "prewarm-p0", "requests": {res: 100.0},
             "priority": 1.0}]
    pods.extend(
        {"name": f"prewarm-x{i}", "requests": {res: 1e9}, "priority": 0.0}
        for i in range(int(unassignable))
    )
    running = [{"name": "prewarm-r0", "node": "prewarm-n0",
                "requests": {res: 50.0}}]
    return nodes, pods, running


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.
    Where $JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and
    this sets no directory; otherwise the cache goes to
    <checkout>/.xla_cache (gitignored). The thresholds are dropped to
    zero so even sub-second compiles persist. Call before the first
    compile: JAX fixes the cache at its first use."""
    import jax  # tpl: disable=TPL001(optional dep: this module is stdlib-only so tools/check.py can reason about registries without jax; only cache wiring needs it)

    path = os.environ.get(CACHE_ENV)
    if not path:
        path = DEFAULT_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
