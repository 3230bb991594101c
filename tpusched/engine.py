"""Engine: the solver driver (SURVEY.md §1.3 "Solver driver" layer).

Owns the jitted solve paths and the host<->device boundary: snapshots
come in as numpy pytrees (from SnapshotBuilder or the gRPC decoder),
results come back as numpy. jax.jit's shape-keyed cache handles bucket
changes; EngineConfig is closed over as compile-time constants.
"""

from __future__ import annotations

import dataclasses
import itertools
import logging
import queue
import threading
import time
import weakref
from concurrent.futures import Future
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from tpusched import ledger as ledgering
from tpusched import trace as tracing
from tpusched.config import EngineConfig
from tpusched.faults import NO_FAULTS
from tpusched.kernels import explain as kexplain
from tpusched.kernels.assign import (_PREEMPT_MAX_ROUNDS, INC_AUDIT_LEN,
                                     EXPLAIN_AUCTION_STATS, build_tableau,
                                     finalize_static, refresh_tableau,
                                     score_batch, solve_incremental,
                                     solve_rounds, solve_sequential)
from tpusched.kernels.atoms import atom_sat
from tpusched.kernels.pairwise import member_label_sat_t
from tpusched.mesh import shard_snapshot
from tpusched.ring import ring_sig_counts
from tpusched.shapeclass import (CAUSE_PREWARM, CAUSE_SERVE,
                                 ShapeClassRegistry, incremental_unassignable,
                                 prewarm_records)
from tpusched.shardctx import constrain_replicated
from tpusched.snapshot import ClusterSnapshot, SnapshotBuilder


@dataclasses.dataclass
class SolveResult:
    assignment: np.ndarray     # [P] int32 node index or -1
    chosen_score: np.ndarray   # [P] f32 (-inf where unschedulable)
    final_used: np.ndarray     # [N, R] f32
    order: np.ndarray          # [P] int32 pop order
    # [P] commit key: pods with smaller keys committed strictly earlier
    # (parity: pop-order position; fast: round index). -1 = unplaced.
    commit_key: np.ndarray | None = None
    rounds: int = 0            # commit rounds (fast mode; P for parity)
    # [M] bool: running pods evicted by preemption (cfg.preemption);
    # the host must delete these before binding their preemptors.
    evicted: np.ndarray | None = None
    solve_seconds: float = 0.0
    # Incremental warm solves only (ISSUE 12): the in-kernel validity
    # audit + frontier accounting — keys cap_violations /
    # static_violations / pair_violations / audit_violations (their
    # sum; the validity contract demands 0) / carried / frontier.
    inc_info: "dict | None" = None


@dataclasses.dataclass
class ScoreBatchResult:
    feasible: np.ndarray       # [P, N] bool
    scores: np.ndarray         # [P, N] f32
    solve_seconds: float = 0.0


@dataclasses.dataclass
class ExplainData:
    """Solve-path provenance extras (round 12, decision provenance):
    which gang placements rolled back, and for every evicted running
    pod WHO evicted it and in which commit round. auction_stats is one
    row per fast-mode preemption round (kernels.assign
    EXPLAIN_AUCTION_STATS columns; all-zero rows are untrimmed here —
    tpusched.explain trims when building records)."""

    rolled: np.ndarray         # [P] bool: reverted by gang_rollback
    evictor: np.ndarray        # [M] int32 preemptor pod index (-1)
    evict_round: np.ndarray    # [M] int32 commit-round key (-1)
    auction_stats: np.ndarray  # [rounds_cap, N_STATS] f32


class _OrderedFetchWorker:
    """ONE background fetch thread with strict FIFO order — the
    replacement for the old single-worker ThreadPoolExecutor. Three
    differences that matter for serving:

      * the thread is a DAEMON, so an engine that was never close()d
        cannot wedge interpreter shutdown, and the owning Engine
        registers a GC finalizer that enqueues the shutdown sentinel —
        dropped engines release their thread like the old executor's
        weakref cleanup did;
      * close(wait=True) DRAINS: the shutdown sentinel enqueues behind
        every submitted fetch, so in-flight PendingFetch results
        complete before close returns;
      * submit after close fails loudly instead of queueing into
        nothing.

    Self-healing (ISSUE 3): per-item exceptions relay into the item's
    Future, so the loop itself only dies on something catastrophic
    (interpreter teardown, a corrupted queue item). A dead-but-not-
    closed worker would silently park every later PendingFetch forever;
    submit() detects that state and RESTARTS the thread — the queue
    survives, only the item that killed the loop is lost (its waiter's
    watchdog/timeout converts the loss into an error).
    """

    def __init__(self, name: str = "tpusched-fetch"):
        self._name = name
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._lock = threading.Lock()
        self._thread: threading.Thread | None = None
        self._closed = False
        self.restarts = 0

    def submit(self, fn, *args) -> "Future":
        fut: Future = Future()
        with self._lock:
            if self._closed:
                raise RuntimeError("engine is closed")
            if self._thread is not None and not self._thread.is_alive():
                # The loop died on an unexpected exception (not via the
                # shutdown sentinel — _closed is False). Respawn it.
                logging.getLogger("tpusched.engine").warning(
                    "fetch worker %s died unexpectedly; restarting",
                    self._name,
                )
                self._thread = None
                self.restarts += 1
            if self._thread is None:
                # Lazy start: idle engines pay nothing, and the lock
                # keeps concurrent first-submits from double-starting.
                self._thread = threading.Thread(
                    target=self._run, name=self._name, daemon=True
                )
                self._thread.start()
            self._q.put((fut, fn, args))
        return fut

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            fut, fn, args = item
            if not fut.set_running_or_notify_cancel():
                continue
            try:
                fut.set_result(fn(*args))
            except BaseException as e:  # noqa: BLE001 — relay to waiter
                fut.set_exception(e)

    def close(self, wait: bool = True) -> None:
        """Idempotent and safe to race: the first caller enqueues the
        shutdown sentinel; every waiting caller joins the same thread
        (joining a finished thread is a no-op), so concurrent close vs
        in-flight fetch drains exactly once."""
        with self._lock:
            thread = self._thread
            if not self._closed:
                self._closed = True
                if thread is not None:
                    self._q.put(None)  # behind all pending work: a drain
        if wait and thread is not None:
            thread.join()


@dataclasses.dataclass
class PendingFetch:
    """An in-flight device result: the program is dispatched and its
    packed buffer is being fetched on the engine's background fetch
    thread. `result()` joins and decodes. The point of the split —
    SURVEY.md §2.3 PP, lifted out of pipeline.solve_stream so SERVING
    paths get the same overlap — is that between dispatch and join the
    caller's thread is free for CPU work (the next request's decode,
    response scaffolding) while the background np.asarray waits on the
    device."""

    _unpack: Callable[[np.ndarray, float], Any]
    _fut: Any          # Future[(np buffer, completion perf_counter)]
    _t0: float

    def result(self, timeout: float | None = None):
        """Join the fetch. `timeout` (seconds) raises
        concurrent.futures.TimeoutError when the fetch has not landed
        in time — the sidecar's per-dispatch watchdog uses this to
        convert a hung solve into DEADLINE_EXCEEDED instead of wedging
        the handler thread (the fetch itself keeps running on the
        worker and is simply abandoned)."""
        raw, done_t = self._fut.result(timeout)
        return self._unpack(raw, done_t - self._t0)


def _sat_tables(snap: ClusterSnapshot, mesh=None):
    node_sat_t = atom_sat(
        snap.atoms, snap.nodes.label_pairs, snap.nodes.label_keys,
        snap.nodes.label_nums,
    ).T
    member_sat_t = member_label_sat_t(
        snap, lambda lp, lk: atom_sat(snap.atoms, lp, lk, None), mesh
    )
    return node_sat_t, member_sat_t


def solve_core(cfg: EngineConfig, snap: ClusterSnapshot, mesh=None,
               explain: bool = False, static=None, member_sat_t=None):
    """Mode dispatch shared by Engine and tenants.solve_many: returns
    (assigned, chosen, used, order, commit_key, rounds, evicted) in
    either mode (parity synthesizes commit_key from pop order and
    rounds=P). With cfg.ring_counts and a multi-device mesh, the
    initial pairwise domain counts come from the blockwise ring kernel
    (sig blocks rotating over the 'p' axis via ppermute) instead of the
    dense contraction — bit-identical results, O(S/ndev x members/ndev)
    peak memory (SURVEY.md §2.3 SP/CP row).

    explain=True (decision provenance, round 12) appends one trailing
    tuple (rolled, evictor, evict_round, auction_stats) — see
    solve_rounds/solve_sequential. Placements are IDENTICAL either way
    (the provenance arrays are pure observers; test-pinned).

    static: optional precomputed StaticCtx (the warm path — ROADMAP
    item 3): the sat-table + static-mask/score precompute is skipped and
    `member_sat_t` (the tableau's, needed only by the ring-counts init)
    must ride along."""
    if static is None:
        node_sat_t, member_sat_t = _sat_tables(snap, mesh)
    else:
        node_sat_t = None  # precompute skipped; solve paths take static
    init_counts = None
    if cfg.ring_counts and snap.sigs.key.shape[0]:
        P = snap.pods.valid.shape[0]
        init_counts = ring_sig_counts(
            snap, member_sat_t, jnp.full(P, -1, jnp.int32), mesh
        )
    if cfg.mode == "fast":
        return solve_rounds(cfg, snap, node_sat_t, member_sat_t,
                            init_counts=init_counts, explain=explain,
                            static=static, mesh=mesh)
    seq = solve_sequential(cfg, snap, node_sat_t, member_sat_t,
                           init_counts=init_counts, explain=explain,
                           static=static, mesh=mesh)
    if explain:
        a, c, u, o, ev, extras = seq
    else:
        a, c, u, o, ev = seq
    # parity commit key = position in pop order (strictly serial)
    P = a.shape[0]
    rank = jnp.zeros(P, jnp.int32).at[o].set(
        jnp.arange(P, dtype=jnp.int32)
    )
    base = (a, c, u, o, rank, jnp.int32(P), ev)
    return base + ((extras,) if explain else ())


def _pack_solve(out, mesh=None):
    """Flatten a solve_core output tuple into the ONE f32 result buffer
    (layout authority: Engine.unpack). Shared by the plain, warm, and
    cold-refresh packed programs so the packing cannot drift between
    them. Indices are exact in f32 (< 2^24).

    mesh: the pack concatenates 'p'-sharded pod vectors with replicated
    scalars — the mixed-sharding concat class this jax version's 2D-mesh
    partitioner mis-routes (tpusched/shardctx.py) — so on a mesh the
    result is pinned replicated (it is about to be fetched to the host
    wholesale anyway)."""
    assigned, chosen, used, order, commit_key, rounds, ev = out
    return constrain_replicated(jnp.concatenate([
        assigned.astype(jnp.float32), chosen,
        order.astype(jnp.float32), commit_key.astype(jnp.float32),
        used.reshape(-1), ev.astype(jnp.float32),
        rounds.astype(jnp.float32)[None],
    ]), mesh)


# Per-Engine nonce for compile-watcher keys: jit caches are
# per-instance, so a second engine's first solve at a known shape is a
# NEW compile and must count as one (itertools.count is atomic).
_ENGINE_IDS = itertools.count(1)


def _shape_label(args) -> str:
    """Human shape-class label for the compile timeline: the snapshot's
    bucket dims when one is present, else a leaf-count tag."""
    for a in args:
        if isinstance(a, ClusterSnapshot):
            return (f"P{a.pods.valid.shape[0]}"
                    f"xN{a.nodes.valid.shape[0]}"
                    f"xM{a.running.valid.shape[0]}")
    return f"{len(jax.tree.leaves(args))}leaves"


@dataclasses.dataclass
class WarmState:
    """The carried-state handle of the warm path (ROADMAP item 3): one
    lineage's device-resident WarmTableau plus the identity facts that
    decide whether it may be trusted next cycle. Held by the owning
    DeviceSnapshot (device_state.commit_warm) and consumed only by
    Engine.solve_warm_async — reads of `.tableau` anywhere else are the
    stale-tableau hazard tpuschedlint TPL011 guards."""

    tableau: Any       # device kernels.assign.WarmTableau
    lineage: Any       # DeviceSnapshot.warm_lineage token at build time
    shapes: tuple      # snapshot leaf shapes the tableau was traced at
    engine: Any        # the Engine whose programs built the tableau


class Engine:
    def __init__(self, config: EngineConfig | None = None, mesh=None,
                 faults=None):
        """mesh: optional jax.sharding.Mesh for multi-device solves;
        required when config.ring_counts routes the pairwise counts
        through the ring kernel.

        faults: optional tpusched.faults.FaultPlan; the background
        fetch fires site "engine.fetch" per fetched buffer (a delay
        rule there is a hung solve — what the sidecar watchdog hunts)."""
        self.config = config or EngineConfig()
        self.mesh = mesh
        self._faults = faults if faults is not None else NO_FAULTS
        # Span collector for engine.fetch events; None = the process
        # default at emit time (SchedulerService points this at its own
        # collector so fetch spans land in the same ring — and flight
        # dumps — as the handler spans).
        self.tracer = None
        cfg = self.config
        if cfg.mode not in ("parity", "fast"):
            raise ValueError(f"mode={cfg.mode!r}: want 'parity' or 'fast'")
        if cfg.ring_counts and mesh is None:
            raise ValueError(
                "ring_counts=True needs Engine(mesh=...): the ring "
                "rotates sig blocks over the mesh's 'p' axis"
            )
        if cfg.tie_break not in ("first", "seeded"):
            raise NotImplementedError(
                f"tie_break={cfg.tie_break!r}: want 'first' or 'seeded'"
            )

        def _solve(snap: ClusterSnapshot):
            return solve_core(cfg, snap, mesh=mesh)

        def _solve_packed(snap: ClusterSnapshot):
            # One flat f32 output = ONE device->host fetch: every
            # fetched buffer pays a fixed per-transfer cost on top of
            # its payload (SURVEY.md §7 hard part 6).
            return _pack_solve(_solve(snap), mesh)

        def _score(snap: ClusterSnapshot):
            node_sat_t, member_sat_t = _sat_tables(snap, mesh)
            ic = None
            if cfg.ring_counts and snap.sigs.key.shape[0]:
                ic = ring_sig_counts(
                    snap, member_sat_t,
                    jnp.full(snap.pods.valid.shape[0], -1, jnp.int32),
                    mesh,
                )
            return score_batch(cfg, snap, node_sat_t, member_sat_t,
                               init_counts=ic, mesh=mesh)

        def _score_top1(snap: ClusterSnapshot):
            feasible, scores = _score(snap)
            masked = jnp.where(feasible, scores, -jnp.inf)
            best = jnp.argmax(masked, axis=1).astype(jnp.int32)
            any_feasible = jnp.any(feasible, axis=1)
            best = jnp.where(any_feasible, best, -1)
            return jnp.stack([
                best.astype(jnp.float32), jnp.max(masked, axis=1),
                any_feasible.astype(jnp.float32),
            ])

        # Compile attribution (round 18, ISSUE 13): every jit entry
        # point is wrapped so the first dispatch of a new shape class
        # records one compile event (count + wall time) in
        # ledger.COMPILES — the per-cycle retrace visibility the cycle
        # ledger's sentinel keys "compile" anomalies off.
        self._jit_nonce = next(_ENGINE_IDS)
        # Shape-class registry hook (ROADMAP item 3): prewarm() fills
        # `families` with the registered family set and dispatch then
        # counts (and warns on) any family traced OUTSIDE it; `cause`
        # labels compile events for the ledger ("prewarm" during boot
        # tracing, "serve" otherwise). A plain dict — NOT self — so the
        # dispatch closures hold no strong ref to the engine (the fetch
        # worker's GC finalizer relies on that).
        self._prewarm_meta: dict[str, Any] = {
            "families": None, "unregistered": {}, "cause": CAUSE_SERVE,
        }
        self.registry: ShapeClassRegistry | None = None
        self._solve_jit = self._traced_jit("solve", _solve)
        self._solve_packed_jit = self._traced_jit("solve_packed",
                                                  _solve_packed)
        self._score_jit = self._traced_jit("score", _score)
        self._score_top1_jit = self._traced_jit("score_top1", _score_top1)
        self._score_fn = _score
        self._topk_jits: dict[int, Any] = {}  # k -> jitted top-k path
        # Decision-provenance programs (round 12): compiled LAZILY on
        # the first solve_explained call, so engines that never explain
        # pay neither trace time nor executable memory for them.
        self._explain_solve_jit = None
        self._explain_probe_jits: dict[int, Any] = {}
        # Warm-start programs (ROADMAP item 3): compiled lazily on the
        # first solve_warm_async call. ONE jit each — jax's shape-keyed
        # cache specializes per (snapshot buckets, pow2-padded dirty
        # sizes, perm presence), and the dirty sizes are pow2-bucketed
        # so the compile set stays bounded.
        self._warm_solve_jit = None
        self._cold_refresh_jit = None
        # Incremental (bounded-divergence) warm programs (ISSUE 12):
        # one jit per FRONTIER BUCKET — the commit rounds run on a
        # [cap, N] compacted view whose width is a compile-time
        # constant, so the frontier size pow2-buckets into a small
        # family exactly like the dirty-row scatters.
        self._warm_inc_jits: dict[int, Any] = {}
        # ONE background fetch worker: fetch order == dispatch order,
        # so results complete in the order requests were dispatched.
        # Callers overlap by dispatching the next program while the
        # worker's np.asarray waits on the current one. The finalizer
        # restores the old executor's exit-on-GC: an engine dropped
        # WITHOUT close() enqueues the shutdown sentinel when collected,
        # so its (daemon) thread parks forever in neither case. The
        # finalizer must hold the QUEUE, not the worker or self — a
        # strong ref to either would keep the engine alive.
        self._pool_lock = threading.Lock()  # pool swap vs close vs submit
        self._closing = False               # close() wins over restarts
        self._fetch_pool = _OrderedFetchWorker()
        self._pool_finalizer = weakref.finalize(
            self, self._fetch_pool._q.put, None
        )

    def _traced_jit(self, name: str, fn):
        """jax.jit plus compile/retrace attribution (round 18, ISSUE
        13): the FIRST dispatch of a new (engine, program, arg-shapes)
        class runs trace+lower+compile synchronously, so its wall time
        prices the compile; ledger.COMPILES records one event per
        class and cycle emitters diff its counters around a cycle.
        Steady state costs one set-membership check per dispatch (a
        disabled watcher: one attribute read)."""
        jf = jax.jit(fn)  # tpl: disable=TPL103(the _traced_jit factory IS the cache: every call site stores the wrapper in an attr or bounded memo family, which TPL103/TPL104 enforce at those sites)
        nonce = self._jit_nonce
        meta = self._prewarm_meta  # no self capture (see __init__)

        def dispatch(*args):
            watcher = ledgering.COMPILES
            if not watcher.enabled:
                return jf(*args)
            key = (nonce, name,
                   tuple(np.shape(l) for l in jax.tree.leaves(args)))
            if watcher.known(key):
                return jf(*args)
            t0 = time.perf_counter()
            out = jf(*args)
            watcher.note(key, name, _shape_label(args),
                         time.perf_counter() - t0, cause=meta["cause"])
            fams = meta["families"]
            if fams is not None and name not in fams:
                # Registry strictness (counted, not fatal): a family the
                # registry missed still serves — but a prewarmed server
                # was promised zero request-path traces, so the miss is
                # loud and countable (Engine.unregistered_compiles).
                meta["unregistered"][name] = (
                    meta["unregistered"].get(name, 0) + 1)
                logging.getLogger("tpusched.engine").warning(
                    "jit family %r (%s) traced outside the attached "
                    "shape-class registry — add it to "
                    "shapeclass.build_registry so prewarm covers it",
                    name, _shape_label(args),
                )
            return out

        return dispatch

    # -- public API ---------------------------------------------------------

    @staticmethod
    def unpack(snap: ClusterSnapshot, buf) -> SolveResult:
        """Decode _solve_packed's flat buffer (the single authority on
        its layout — solve() and pipeline.solve_stream both go through
        here, so the packing can't drift between them)."""
        buf = np.asarray(buf)
        P = snap.pods.valid.shape[0]
        N, R = snap.nodes.used.shape
        M = snap.running.valid.shape[0]
        base = 4 * P + N * R
        return SolveResult(
            assignment=buf[:P].astype(np.int32),
            chosen_score=buf[P : 2 * P],
            order=buf[2 * P : 3 * P].astype(np.int32),
            commit_key=buf[3 * P : 4 * P].astype(np.int32),
            final_used=buf[4 * P : base].reshape(N, R),
            evicted=buf[base : base + M] > 0,
            rounds=int(buf[-1]),
        )

    def _pool(self) -> _OrderedFetchWorker:
        with self._pool_lock:
            return self._fetch_pool

    def restart_fetch_worker(self) -> None:
        """Abandon a wedged fetch worker (ISSUE 3 watchdog): a fresh
        worker takes all NEW fetches; the old one keeps draining its
        own queue if it ever unwedges (its in-flight futures still
        complete), and its daemon thread can't block shutdown either
        way. Tradeoff, documented: across the swap, fetch order ==
        dispatch order no longer holds between old and new queues, so
        two D2H reads may briefly run concurrently. A
        worker hung past the watchdog means the device stream is
        already suspect; the ladder quarantines the fast path and this
        swap buys back availability. A no-op once close() has begun:
        swapping a fresh (never-closed) worker in behind a concurrent
        close would void close's drain guarantee and leak the thread."""
        with self._pool_lock:
            if self._closing:
                return
            old = self._fetch_pool
            self._fetch_pool = _OrderedFetchWorker()
            # Detach the abandoned pool's finalizer (its sentinel is
            # enqueued explicitly below): finalizers must not pile up
            # one-per-restart on a persistently wedged device — each
            # would pin a dead worker's queue for the engine's life.
            self._pool_finalizer.detach()
            self._pool_finalizer = weakref.finalize(
                self, self._fetch_pool._q.put, None
            )
        old.close(wait=False)

    def _fetch(self, buf, tctx=None):
        # Completion time measured INSIDE the worker so solve_seconds
        # covers dispatch->fetch-done, not whatever CPU work the caller
        # overlapped with the wait. np.asarray releases the GIL while
        # it waits on the device. tree.map: score_async fetches a
        # (feasible, scores) pair through the same worker.
        # tctx: the dispatching request's trace context (captured on
        # the caller's thread at dispatch time — thread-locals don't
        # cross into the worker); the fetch records one span against
        # it, so the stitched trace shows the device window alongside
        # the handler's fetch.join wait.
        self._faults.fire("engine.fetch")
        t0 = time.perf_counter()
        out = jax.tree.map(np.asarray, buf)
        done = time.perf_counter()
        (self.tracer or tracing.DEFAULT).record(
            "engine.fetch", dur_s=done - t0, cat="engine", ctx=tctx)
        return out, done

    def _submit_fetch(self, buf):
        """Queue the D2H fetch, carrying the caller's trace context."""
        tr = self.tracer or tracing.DEFAULT
        return self._pool().submit(self._fetch, buf, tr.current())

    def solve(self, snap: ClusterSnapshot) -> SolveResult:
        """Full batched scheduling: assign every pending pod (or -1).

        Timing includes the device->host readback: the host shim needs
        the assignments anyway, so the D2H copy is part of the schedule
        cycle."""
        t0 = time.perf_counter()
        out = self.unpack(snap, self._solve_packed_jit(self._placed(snap)))
        out.solve_seconds = time.perf_counter() - t0
        return out

    def _placed(self, snap: ClusterSnapshot) -> ClusterSnapshot:
        """A host snapshot handed to a mesh engine's jit would land
        REPLICATED on every device (the compiler picks no input
        sharding); put it in the canonical sharded layout instead. A
        snapshot already laid out that way passes through untouched."""
        return snap if self.mesh is None else self.put(snap)

    def solve_async(self, snap: ClusterSnapshot) -> PendingFetch:
        """Dispatch the packed solve and fetch its one flat buffer on
        the engine's background worker; `.result()` joins and unpacks.
        The caller's thread is free between dispatch and join — the
        decode<->solve overlap primitive behind pipeline.solve_stream
        and the sidecar's staged request handling (in-request overlap:
        response scaffolding builds while the device runs; cross-
        request: the next request's decode overlaps this solve)."""
        t0 = time.perf_counter()
        buf = self._solve_packed_jit(self._placed(snap))  # async dispatch

        def unpack(raw, seconds):
            res = self.unpack(snap, raw)
            res.solve_seconds = seconds
            return res

        return PendingFetch(unpack, self._submit_fetch(buf), t0)

    # -- O(churn) warm-start solving (ROADMAP item 3) -----------------------

    @staticmethod
    def _pad_idx(idx) -> "np.ndarray | None":
        """Pow2-pad a dirty index list by repeating the first index
        (duplicate scatter writes carry identical recomputed content, so
        order cannot matter) — bounded jit-shape set. None when empty,
        so an all-clean axis skips its scatter at trace time."""
        if idx is None or len(idx) == 0:
            return None
        n = len(idx)
        cap = 1 << max(n - 1, 0).bit_length() if n > 1 else 1
        out = np.full(cap, idx[0], np.int32)
        out[:n] = list(idx)
        return out

    @staticmethod
    def _shape_key(snap: ClusterSnapshot) -> tuple:
        return tuple(np.shape(leaf) for leaf in jax.tree.leaves(snap))

    def _ensure_warm_jits(self) -> None:
        if self._warm_solve_jit is not None:
            return
        cfg, mesh = self.config, self.mesh

        def _cold(snap: ClusterSnapshot):
            node_sat_t, member_sat_t = _sat_tables(snap, mesh)
            tab = build_tableau(cfg, snap, node_sat_t, member_sat_t, mesh)
            static = finalize_static(cfg, snap, tab)
            out = solve_core(cfg, snap, mesh=mesh, static=static,
                             member_sat_t=tab.member_sat_t)
            return _pack_solve(out, mesh), tab

        def _warm(snap: ClusterSnapshot, tab, dp, dn, dm, pperm, nperm,
                  mperm):
            tab = refresh_tableau(cfg, snap, tab, dirty_pods=dp,
                                  dirty_nodes=dn, dirty_members=dm,
                                  pod_perm=pperm, node_perm=nperm,
                                  member_perm=mperm, mesh=mesh)
            static = finalize_static(cfg, snap, tab)
            out = solve_core(cfg, snap, mesh=mesh, static=static,
                             member_sat_t=tab.member_sat_t)
            return _pack_solve(out, mesh), tab

        self._cold_refresh_jit = self._traced_jit("warm_cold_refresh",
                                                  _cold)
        self._warm_solve_jit = self._traced_jit("warm_refresh", _warm)

    def _warm_inc_fn(self, cap: int):
        """The incremental warm program at one frontier-compaction
        bucket (compile-time constant; see _warm_inc_jits)."""
        fn = self._warm_inc_jits.get(cap)
        if fn is None:
            cfg, mesh = self.config, self.mesh

            def _inc(snap: ClusterSnapshot, tab, dp, dn, dm, pperm,
                     nperm, mperm, carry, carry_chosen, frontier, dnode,
                     _cap=cap):
                tab = refresh_tableau(cfg, snap, tab, dirty_pods=dp,
                                      dirty_nodes=dn, dirty_members=dm,
                                      pod_perm=pperm, node_perm=nperm,
                                      member_perm=mperm, mesh=mesh)
                out = solve_incremental(cfg, snap, tab, carry,
                                        carry_chosen, frontier, dnode,
                                        _cap, mesh=mesh)
                return constrain_replicated(jnp.concatenate(
                    [_pack_solve(out[:7], mesh), out[7]]), mesh), tab

            fn = self._warm_inc_jits[cap] = self._traced_jit(
                f"warm_incremental_cap{cap}", _inc)
        return fn

    @staticmethod
    def _k_bucket(k: int, n: int) -> int:
        """Pow2 compile bucket for a top-k request (TPL104, ISSUE 14):
        the top-k jit families are keyed by THIS (O(log N) programs,
        not one per distinct k) and callers slice the first k columns
        — lax.top_k sorts descending, so top-kb's k-prefix IS top-k,
        bitwise. Clamped to n: a bucket past the node axis would pad
        the program for columns that cannot exist."""
        kb = 1 << (max(int(k), 1) - 1).bit_length()
        return min(kb, int(n))

    @staticmethod
    def _frontier_bucket(est: int, P: int) -> int:
        """Pow2 frontier-compaction width for an estimated frontier of
        `est` pods: 2x headroom for closure expansion + revalidation
        spills, floored at 64 (tiny views re-gather more rounds than
        they save), 0 (= full-width rounds) once the bucket would reach
        the pod axis anyway."""
        want = max(64, 2 * max(est, 1))
        cap = 1 << (want - 1).bit_length()
        return 0 if cap >= P else cap

    def unpack_incremental(self, snap: ClusterSnapshot, buf):
        """Decode the incremental program's packed buffer: the standard
        solve layout + the INC_AUDIT_LEN in-kernel audit tail. Returns
        (SolveResult, info dict) — info keys mirror
        SolveResult.inc_info."""
        buf = np.asarray(buf)
        res = Engine.unpack(snap, buf[:-INC_AUDIT_LEN])
        audit = buf[-INC_AUDIT_LEN:]
        info = dict(
            cap_violations=int(audit[0]),
            static_violations=int(audit[1]),
            pair_violations=int(audit[2]),
            audit_violations=int(audit[0] + audit[1] + audit[2]),
            carried=int(audit[3]),
            frontier=int(audit[4]),
        )
        return res, info

    def solve_warm_async(self, device, incremental: bool = False,
                         ) -> PendingFetch:
        """Warm-start solve of a device-resident lineage (ROADMAP item
        3): `device` is a tpusched.device_state.DeviceSnapshot. The
        lineage's accumulated dirty state (device.warm_delta()) decides
        the path:

          * warm — the carried tableau is reordered + scatter-refreshed
            for exactly the dirty pod rows / node columns / member
            columns, then the normal solve runs against it. Per-pod QoS
            weights, score normalizations, pop order, and all pair-state
            counts are recomputed fresh from the CURRENT snapshot every
            solve, so placements are bitwise-identical to a cold solve
            (the twin-parity contract, pinned in tests/test_warm.py).
          * cold — anything the row model cannot express (vocab/bucket
            growth, a rebuild, a foreign or missing tableau) rebuilds
            the tableau from scratch inside the same program; cost is
            the plain solve's, and the lineage is warm again afterwards.

        The handle is committed back into the DeviceSnapshot
        (commit_warm) at DISPATCH time; a caller whose fetch later
        fails should device.invalidate_warm() — the conservative reset.
        Explain mode is not traced on the warm program; use the
        explained (cold) path when provenance is on.

        incremental=True (ISSUE 12, bounded-divergence warm rounds):
        the previous cycle's assignment — committed back into the
        lineage by every solve_warm fetch (DeviceSnapshot.commit_carry)
        — seeds the round loop for clean pods; the dirty set expands to
        its signature-cluster/node closure, carried placements are
        revalidated in batched passes (violations spill), and commit
        rounds run only over the pending frontier on a pow2-bucketed
        compacted view (kernels.assign.solve_incremental). NOT bitwise
        vs cold: governed by the validity contract enforced in-kernel
        (SolveResult.inc_info carries the audit; `python -m
        tpusched.divergence --warm-audit N --incremental` twin-audits
        validity AND placement-quality drift). Falls back to the
        bitwise warm path when the lineage has no carry yet, and to
        cold for everything the row model cannot express — exactly the
        ladder of the plain warm path."""
        self._ensure_warm_jits()
        if incremental and self.config.ring_counts:
            raise NotImplementedError(
                "incremental warm solve does not support ring_counts"
            )
        snap = device.snap
        delta = device.warm_delta()
        warm = device.warm_state
        shapes = self._shape_key(snap)
        reason = None
        if delta.needs_cold:
            reason = delta.reason or "needs_cold"
        elif warm is None:
            reason = "no_tableau"
        elif warm.lineage is not device.warm_lineage:
            # A handle carried across a failover/restore to a DIFFERENT
            # lineage (e.g. a promoted replica) must never be trusted.
            reason = "lineage_mismatch"
        elif warm.engine is not self:
            reason = "engine_mismatch"
        elif warm.shapes != shapes:
            reason = "shape_change"
        carry = device.carry_arrays() if incremental else None
        t0 = time.perf_counter()
        inc_run = False
        if reason is not None:
            buf, tab = self._cold_refresh_jit(snap)
            path, rows = "cold", (0, 0, 0)
        elif incremental and carry is not None:
            carry_arr, chosen_arr = carry
            P = snap.pods.valid.shape[0]
            frontier = np.zeros(P, bool)
            if delta.dirty_pods:
                frontier[np.asarray(delta.dirty_pods, np.int32)] = True
            dnode = None
            if delta.dirty_nodes:
                dnode = np.zeros(snap.nodes.valid.shape[0], bool)
                dnode[np.asarray(delta.dirty_nodes, np.int32)] = True
            # Estimate over REAL rows only (name-sorted reals precede
            # the bucket's padding tail): pad rows read as carry -1 and
            # would inflate the estimate past the pow2 boundary,
            # silently disabling compaction for lineages sitting just
            # above one.
            n_real = len(device.meta.pod_names)
            est = (int(frontier[:n_real].sum())
                   + int((carry_arr[:n_real] < 0).sum()))
            cap = self._frontier_bucket(est, P)
            buf, tab = self._warm_inc_fn(cap)(
                snap, warm.tableau,
                self._pad_idx(delta.dirty_pods),
                self._pad_idx(delta.dirty_nodes),
                self._pad_idx(delta.dirty_members),
                delta.pod_perm, delta.node_perm, delta.member_perm,
                carry_arr, chosen_arr, frontier, dnode,
            )
            path = "incremental"
            inc_run = True
            rows = (len(delta.dirty_pods or ()),
                    len(delta.dirty_nodes or ()),
                    len(delta.dirty_members or ()))
        else:
            buf, tab = self._warm_solve_jit(
                snap, warm.tableau,
                self._pad_idx(delta.dirty_pods),
                self._pad_idx(delta.dirty_nodes),
                self._pad_idx(delta.dirty_members),
                delta.pod_perm, delta.node_perm, delta.member_perm,
            )
            path = "warm"
            rows = (len(delta.dirty_pods or ()),
                    len(delta.dirty_nodes or ()),
                    len(delta.dirty_members or ()))
        device.commit_warm(
            WarmState(tableau=tab, lineage=device.warm_lineage,
                      shapes=shapes, engine=self),
            path=path, reason=reason or "", rows=rows,
        )
        # Name orders of the snapshot THIS dispatch solves, captured
        # now: the carry maps by name, so a concurrent next-cycle
        # apply() shifting rows cannot corrupt it.
        pod_names = list(device.meta.pod_names)
        node_names = list(device.meta.node_names)

        def unpack(raw, seconds):
            if inc_run:
                res, info = self.unpack_incremental(snap, raw)
                res.inc_info = info
            else:
                res = self.unpack(snap, raw)
            res.solve_seconds = seconds
            # Every warm-path result becomes the next incremental
            # cycle's carry (join-thread call — same single-caller
            # discipline as DeviceSnapshot.apply).
            device.commit_carry(pod_names, node_names, res.assignment,
                                np.asarray(res.chosen_score))
            return res

        return PendingFetch(unpack, self._submit_fetch(buf), t0)

    def solve_warm(self, device, incremental: bool = False) -> SolveResult:
        """Blocking form of solve_warm_async."""
        return self.solve_warm_async(device, incremental=incremental).result()

    # -- decision provenance (round 12) -------------------------------------

    def unpack_explained(self, snap: ClusterSnapshot, buf):
        """Decode the explained solve's packed buffer: the standard
        solve layout (Engine.unpack) followed by the provenance extras.
        Returns (SolveResult, ExplainData)."""
        buf = np.asarray(buf)
        P = snap.pods.valid.shape[0]
        N, R = snap.nodes.used.shape
        M = snap.running.valid.shape[0]
        std = 4 * P + N * R + M + 1
        res = Engine.unpack(snap, buf[:std])
        off = std
        rolled = buf[off:off + P] > 0
        off += P
        evictor = buf[off:off + M].astype(np.int32)
        off += M
        evict_round = buf[off:off + M].astype(np.int32)
        off += M
        astats = buf[off:].reshape(
            _PREEMPT_MAX_ROUNDS, len(EXPLAIN_AUCTION_STATS)
        )
        return res, ExplainData(rolled=rolled, evictor=evictor,
                                evict_round=evict_round,
                                auction_stats=astats)

    def solve_explained_async(self, snap: ClusterSnapshot, k: int = 3):
        """Dispatch the EXPLAINED solve plus the provenance probe
        (kernels.explain.explain_probe): returns (pending_solve,
        pending_probe) where the first joins to (SolveResult,
        ExplainData) and the second to a ScoreExplain. Both fetch
        through the engine's ordered worker — no handler-thread D2H.
        Placements are identical to solve(): the explain program only
        ADDS observer arrays (test-pinned). Compiled lazily per shape;
        the unexplained hot path never traces it."""
        cfg = self.config
        mesh = self.mesh
        if self._explain_solve_jit is None:
            def _packed_explained(s: ClusterSnapshot):
                out = solve_core(cfg, s, mesh=mesh, explain=True)
                a, c, u, o, ck, rounds, ev = out[:7]
                rolled, evictor, evict_rd, astats = out[7]
                return jnp.concatenate([
                    a.astype(jnp.float32), c, o.astype(jnp.float32),
                    ck.astype(jnp.float32), u.reshape(-1),
                    ev.astype(jnp.float32),
                    rounds.astype(jnp.float32)[None],
                    rolled.astype(jnp.float32),
                    evictor.astype(jnp.float32),
                    evict_rd.astype(jnp.float32),
                    astats.reshape(-1),
                ])

            self._explain_solve_jit = self._traced_jit(
                "solve_explained", _packed_explained)
        N = snap.nodes.valid.shape[0]
        kk = int(min(max(int(k), 1), max(N, 1)))
        # Compile bucket (TPL104): probe programs are keyed by the pow2
        # bucket of k and unpack slices back — same prefix-stability
        # argument as score_topk_async (lax.top_k sorts descending).
        kb = self._k_bucket(kk, max(N, 1))
        probe_fn = self._explain_probe_jits.get(kb)
        if probe_fn is None:
            def _probe(s: ClusterSnapshot, _k=kb):
                node_sat_t, member_sat_t = _sat_tables(s, mesh)
                ic = None
                if cfg.ring_counts and s.sigs.key.shape[0]:
                    ic = ring_sig_counts(
                        s, member_sat_t,
                        jnp.full(s.pods.valid.shape[0], -1, jnp.int32),
                        mesh,
                    )
                return kexplain.explain_probe(
                    cfg, s, node_sat_t, member_sat_t, _k, init_counts=ic,
                    mesh=mesh,
                )

            probe_fn = self._explain_probe_jits[kb] = self._traced_jit(
                f"explain_probe_k{kb}", _probe)

        t0 = time.perf_counter()
        solve_buf = self._explain_solve_jit(snap)   # async dispatch
        probe_buf = probe_fn(snap)                  # async dispatch

        def unpack_solve(raw, seconds):
            res, exd = self.unpack_explained(snap, raw)
            res.solve_seconds = seconds
            return res, exd

        def unpack_probe(raw, _seconds):
            se = kexplain.unpack_probe(snap, raw, kb)
            if kb == kk:
                return se
            return dataclasses.replace(
                se, k=kk, topk_idx=se.topk_idx[:, :kk],
                topk_score=se.topk_score[:, :kk],
                topk_terms=se.topk_terms[:, :kk, :],
            )

        return (
            PendingFetch(unpack_solve, self._submit_fetch(solve_buf), t0),
            PendingFetch(unpack_probe, self._submit_fetch(probe_buf), t0),
        )

    def solve_explained(self, snap: ClusterSnapshot, k: int = 3):
        """Blocking form: (SolveResult, ExplainData, ScoreExplain)."""
        p_solve, p_probe = self.solve_explained_async(snap, k)
        res, exd = p_solve.result()
        return res, exd, p_probe.result()

    def score(self, snap: ClusterSnapshot) -> ScoreBatchResult:
        """ScoreBatch: [P, N] feasibility + normalized weighted scores,
        no commits (the Score-plugin backend of the north star)."""
        return self.score_async(snap).result()

    def score_async(self, snap: ClusterSnapshot) -> PendingFetch:
        """Async form of score(): both matrices fetched on the engine's
        ordered fetch worker. Serving handlers must use this (or any
        *_async form) rather than fetching on their own thread — a
        handler-thread np.asarray would break the worker's
        dispatch-order completion."""
        def unpack(pair, seconds):
            feasible, scores = pair
            return ScoreBatchResult(
                feasible=feasible, scores=scores, solve_seconds=seconds
            )

        t0 = time.perf_counter()
        out = self._score_jit(snap)  # async dispatch
        return PendingFetch(unpack, self._submit_fetch(out), t0)

    def score_topk(self, snap: ClusterSnapshot, k: int):
        """Top-k of the ScoreBatch matrix computed ON DEVICE: each
        pod's best k feasible nodes (descending) and their scores,
        fetched as one packed [2*P*k] f32 buffer (node indices are
        exact in f32: N < 2^24). This is the O(P) serving form of the
        Score-plugin surface — the [P, N] matrix never leaves the
        device; upstream's percentageOfNodesToScore likewise narrows
        the scored-node set at scale. Returns (idx[P,k] int32 with -1
        where fewer than k feasible, scores[P,k] f32 with 0 at -1
        slots, seconds)."""
        res = self.score_topk_async(snap, k)
        idx, val, seconds = res.result()
        return idx, val, seconds

    def score_topk_async(self, snap: ClusterSnapshot, k: int) -> PendingFetch:
        """Async form of score_topk (same packed buffer, background
        fetch): `.result()` -> (idx, val, seconds). Lets the sidecar's
        ScoreBatch handler build its response name tables while the
        device ranks."""
        k = int(k)
        N = snap.nodes.valid.shape[0]
        if not 1 <= k <= N:
            raise ValueError(
                f"top_k={k} out of range for {N} node slots"
            )
        # Compile bucket (TPL104): the family is keyed by the pow2
        # bucket, the device ranks kb columns, and unpack slices the
        # first k — identical to a direct top-k (descending sort).
        kb = self._k_bucket(k, N)
        fn = self._topk_jits.get(kb)
        if fn is None:
            score = self._score_fn

            def _topk(s: ClusterSnapshot, _kb=kb):
                feasible, scores = score(s)
                masked = jnp.where(feasible, scores, -jnp.inf)
                v, i = jax.lax.top_k(masked, _kb)
                ok = jnp.isfinite(v)
                return jnp.concatenate([
                    jnp.where(ok, i, -1).astype(jnp.float32).ravel(),
                    jnp.where(ok, v, 0.0).ravel(),
                ])

            fn = self._topk_jits[kb] = self._traced_jit(
                f"score_topk_k{kb}", _topk)
        P = snap.pods.valid.shape[0]

        def unpack(buf, seconds):
            half = P * kb
            idx = buf[:half].astype(np.int32).reshape(P, kb)[:, :k]
            val = buf[half:].reshape(P, kb).astype(np.float32)[:, :k]
            return idx, val, seconds

        t0 = time.perf_counter()
        buf = fn(snap)  # async dispatch
        return PendingFetch(unpack, self._submit_fetch(buf), t0)

    def score_top1(self, snap: ClusterSnapshot):
        """Full [P, N] scoring on device, returning only each pod's best
        node, its score, and feasibility — the decision-ready contract
        the host shim binds on (full matrix stays on device)."""
        t0 = time.perf_counter()
        buf = np.asarray(self._score_top1_jit(snap))
        return (
            buf[0].astype(np.int32), buf[1], buf[2] > 0,
            time.perf_counter() - t0,
        )

    @property
    def unregistered_compiles(self) -> dict[str, int]:
        """Per-family count of compiles traced OUTSIDE the attached
        shape-class registry (empty until prewarm() attaches one).
        Counted + warned, never fatal — the miss list is the work item
        for shapeclass.build_registry."""
        return dict(self._prewarm_meta["unregistered"])

    class _PrewarmStop(Exception):
        """Raised between shape classes when a prewarm's should_stop
        callable fires — cooperative cancellation, never an error."""

    def prewarm(self, registry: ShapeClassRegistry,
                should_stop=None) -> dict:
        """Trace every shape class in `registry` (ROADMAP item 3): after
        this returns, a request at the registry's buckets through any
        registered family dispatches an already-compiled program — the
        compile-free failover a promoted standby needs. Also ATTACHES
        the registry: later compiles outside its family set are counted
        in `unregistered_compiles` and logged (not fatal).

        Leaf shapes are a pure function of explicit Buckets, so the tiny
        canonical clusters from shapeclass.prewarm_records compile the
        exact programs real traffic at those buckets hits. Warm families
        are driven through a real DeviceSnapshot lineage with the
        canonical smallest delta (one upserted pod); the incremental
        family needs one lineage per frontier cap, steered by
        unassignable filler pods (shapeclass.incremental_unassignable).

        Compile events recorded during this call carry cause="prewarm"
        in ledger.COMPILES so boot work never reads as a serving
        regression. Returns a report dict (classes / families /
        compiles / compile_s / prewarm_s / cancelled).

        should_stop: optional zero-arg callable polled BETWEEN shape
        classes; returning True abandons the remaining classes (the
        report comes back cancelled=True). A closing server uses this
        so a boot prewarm racing shutdown stops after the in-flight
        compile instead of keeping a thread inside XLA while the
        interpreter tears down."""
        from tpusched.device_state import DeviceSnapshot  # tpl: disable=TPL001(boot-time only: prewarm runs once per process; a top-level import would tax every engine import with the device-state layer it otherwise never needs)

        t0 = time.perf_counter()
        bk = registry.buckets
        fams = frozenset(registry.families())
        self.registry = registry
        self._prewarm_meta["families"] = fams
        before = ledgering.COMPILES.counters()
        prev_cause = self._prewarm_meta["cause"]
        self._prewarm_meta["cause"] = CAUSE_PREWARM
        cancelled = False

        def _ck() -> None:
            if should_stop is not None and should_stop():
                raise Engine._PrewarmStop

        try:
            nodes, pods, running = prewarm_records(self.config)
            b = SnapshotBuilder(self.config, buckets=bk)
            for n in nodes:
                b.add_node(**n)
            for p in pods:
                b.add_pod(**p)
            for r in running:
                b.add_running_pod(**{k: v for k, v in r.items()
                                     if k != "name"})
            snap, _meta = b.build()
            snap = self.put(snap)
            if "solve_packed" in fams:
                _ck()
                self.solve_async(snap).result()
            if "score" in fams:
                _ck()
                self.score_async(snap).result()
            if "score_top1" in fams:
                _ck()
                self.score_top1(snap)
            for cls in registry:
                if cls.family.startswith("score_topk_k"):
                    _ck()
                    self.score_topk_async(
                        snap, dict(cls.params)["k"]).result()
            if registry.explain:
                _ck()
                p_solve, p_probe = self.solve_explained_async(
                    snap, registry.explain_k)
                p_solve.result()
                p_probe.result()
            if registry.warm is not None:
                caps = ([dict(c.params)["cap"] for c in registry
                         if c.family.startswith("warm_incremental_cap")]
                        if registry.warm == "incremental" else [None])
                for cap in caps:
                    _ck()
                    filler = (0 if cap is None else
                              incremental_unassignable(cap, bk.pods))
                    wn, wp, wr = prewarm_records(self.config, filler)
                    ds = DeviceSnapshot(self.config, bk, mesh=self.mesh)
                    ds.full_load(wn, wp, wr)
                    self.solve_warm(ds)                # warm_cold_refresh
                    ds.apply(upsert_pods=[wp[0]])
                    self.solve_warm(ds)                # warm_refresh
                    if cap is not None:
                        ds.apply(upsert_pods=[wp[0]])
                        self.solve_warm(ds, incremental=True)
        except Engine._PrewarmStop:
            cancelled = True
        finally:
            self._prewarm_meta["cause"] = prev_cause
        after = ledgering.COMPILES.counters()
        return dict(
            classes=len(registry),
            families=sorted(fams),
            compiles=after[0] - before[0],
            compile_s=round(after[1] - before[1], 6),
            prewarm_s=round(time.perf_counter() - t0, 6),
            cancelled=cancelled,
        )

    def warmup(self, snap: ClusterSnapshot) -> None:
        """Trigger compilation of the serving paths (solve + score_top1)
        for this snapshot's bucket shapes."""
        self._solve_packed_jit(snap)
        self._score_top1_jit(snap)

    def put(self, snap: ClusterSnapshot) -> ClusterSnapshot:
        """Explicit host->device transfer (otherwise implicit on call).
        On a mesh-backed engine the snapshot lands SHARDED in the
        canonical layout (pods over 'p', nodes over 'n', vocab
        replicated) so the solve consumes it in place — one engine
        serves a cluster no single device holds (ROADMAP item 1)."""
        if self.mesh is not None and self.mesh.devices.size > 1:
            return shard_snapshot(self.mesh, snap)
        return jax.device_put(snap)

    def close(self, wait: bool = True) -> None:
        """Shut down the background fetch worker. wait=True (default)
        DRAINS: every in-flight PendingFetch completes before this
        returns, so multi-client servers can't leak fetch threads or
        half-fetched results across test runs. The worker thread is a
        daemon, so engines that are never closed still can't block
        interpreter shutdown. Idempotent, and safe against a concurrent
        close or restart_fetch_worker: `_closing` is set under the pool
        lock BEFORE the current pool is read, so a racing watchdog
        restart either completed its swap (we close the new pool) or
        becomes a no-op — no fresh never-closed worker can appear
        behind us (worker.close is itself re-entrant)."""
        with self._pool_lock:
            self._closing = True
            pool = self._fetch_pool
        pool.close(wait=wait)
