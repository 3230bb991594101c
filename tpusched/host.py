"""Host scheduler shim + fake API server (SURVEY.md C13, §3.3).

Plays the kube-scheduler role for E2E runs (BASELINE.json:"configs"[0]:
100 pods x 10 nodes): watch pending pods, accumulate a batch, call the
engine (in-process or through the gRPC sidecar, C12), issue Binds and
eviction Deletes against the API server, repeat until the queue drains.

The FakeApiServer stands in for kind/a real API server (neither exists
in this image): it holds spec-level node/pod records, enforces
bind-once-while-pending semantics (the idempotency the reference relies
on for safe retries after a scheduler crash, SURVEY.md §5 "Failure
detection"), and is thread-safe.

Cluster state is the source of truth: the shim keeps no cache between
cycles — each batch re-reads the API server (recovery = replay,
SURVEY.md §5 "Checkpoint / resume").
"""

from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Iterable

import numpy as np

from tpusched import explain as explaining
from tpusched import ledger as ledgering
from tpusched import metrics as pm
from tpusched import trace as tracing
from tpusched.config import (
    DEFAULT_OBSERVED_AVAIL,
    DEFAULT_SLO_TARGET,
    Buckets,
    EngineConfig,
    clamp01,
)
from tpusched.device_state import DeviceQueue, DeviceSnapshot
from tpusched.engine import Engine
from tpusched.qos import observed_availability, slack_of
from tpusched.rpc.codec import decode_snapshot, snapshot_to_proto


class Conflict(Exception):
    """Bind of a pod that is no longer pending (double-bind guard)."""


# Minimum availability drift before a read re-hints a pod into the
# change accumulator (see FakeApiServer._with_avail): large enough that
# wall-clock unit tests reading milliseconds apart see no hint churn,
# small enough (~0.4%) that a sim tick's worth of waiting registers.
AVAIL_REHINT_EPS = 1.0 / 256.0


class FakeApiServer:
    def __init__(self, clock=None):
        # clock: zero-arg callable for pod timestamps (submitted /
        # bound_at). The simulator injects a VirtualClock so lifecycle
        # accounting runs on virtual time; default is wall time.
        self._clock = clock if clock is not None else time.time
        self._lock = threading.Lock()
        self._nodes: dict[str, dict] = {}
        self._pods: dict[str, dict] = {}      # pending + bound
        self.bind_count = 0
        self.delete_count = 0
        # Change log for delta hints (the fake twin of KubeInformer's
        # event accumulator): every mutation records the object name;
        # drain_changed() empties it. First drain returns None ("no
        # baseline"), matching the informer contract.
        self._changed: set[str] = set()
        self._dirty_all = True
        # Last computed observed_avail each pod was served with — the
        # drift baseline for read-time re-hinting (see _with_avail).
        self._avail_served: dict[str, float] = {}
        # Monotone arrival stamp (ISSUE 20): the device queue's
        # deterministic tie-break. Re-queued pods restamp, matching
        # their new dict-insertion position, so seq order == the dict
        # iteration order the host-sorted path batches in.
        self._arrival_seq = 0

    # -- cluster setup ------------------------------------------------------

    def add_node(self, name: str, **spec) -> None:
        with self._lock:
            self._nodes[name] = dict(spec, name=name)
            self._changed.add(name)

    def delete_node(self, name: str) -> bool:
        """Node removal (sim: a node failure); idempotent. Pods bound
        to it are the CALLER's problem — a real apiserver likewise
        keeps orphaned pods until something evicts them."""
        with self._lock:
            if name not in self._nodes:
                return False
            del self._nodes[name]
            self._changed.add(name)
            return True

    def add_pod(self, name: str, **spec) -> None:
        """`submitted` / `run_seconds` may ride in via spec: the sim
        driver re-queues evicted pods with their lifecycle history
        preserved, so availability keeps decaying across requeues
        instead of resetting."""
        with self._lock:
            rec = dict(spec, name=name, phase="Pending", node=None)
            rec.setdefault("submitted", self._clock())
            rec.setdefault("run_seconds", 0.0)
            rec["arrival_seq"] = self._arrival_seq
            self._arrival_seq += 1
            self._pods[name] = rec
            self._changed.add(name)

    def add_bound_pod(self, name: str, node: str, **spec) -> None:
        """A pod already running on a node (pre-existing workload)."""
        with self._lock:
            now = self._clock()
            rec = dict(spec, name=name, phase="Bound", node=node)
            rec.setdefault("submitted", now)
            rec.setdefault("run_seconds", 0.0)
            rec.setdefault("bound_at", now)
            self._pods[name] = rec
            self._changed.add(name)

    def get_pod(self, name: str) -> "dict | None":
        with self._lock:
            p = self._pods.get(name)
            return dict(p) if p is not None else None

    def set_observed_availability(self, name: str, avail: float) -> bool:
        """Pin a pod's observed availability explicitly (the
        FakeApiServer twin of the kube annotation write-back path,
        KubeApiClient.write_observed_availability). An explicit value
        OVERRIDES lifecycle accounting until cleared."""
        with self._lock:
            p = self._pods.get(name)
            if p is None:
                return False
            p["observed_avail"] = clamp01(
                avail, default=DEFAULT_OBSERVED_AVAIL)
            self._changed.add(name)
            return True

    # -- delta hints --------------------------------------------------------

    def drain_changed(self) -> "set[str] | None":
        with self._lock:
            if self._dirty_all:
                self._dirty_all = False
                self._changed.clear()
                return None
            out = self._changed
            self._changed = set()
            return out

    def restore_changed(self, names: "set[str] | None") -> None:
        """Un-drain hints a caller consumed but never shipped (e.g. a
        cycle that returned early): without this, the next delta would
        trust a stale base for these records."""
        with self._lock:
            if names is None:
                self._dirty_all = True
            else:
                self._changed |= names

    # -- watch/list side ----------------------------------------------------

    def list_nodes(self) -> list[dict]:
        with self._lock:
            return [dict(n) for n in self._nodes.values()]

    def _with_avail(self, p: dict, now: float) -> dict:
        """Record copy with lifecycle-accounted observed_avail (ISSUE 5:
        the closed QoS loop). An explicit spec value PINS it (tests,
        annotation write-back); otherwise availability is computed from
        submitted / run_seconds / bound_at at read time, so every
        cycle's snapshot sees pressure that reflects how long the pod
        has actually waited vs run. Never-observed pods (zero age) fall
        back to 1.0 — see tpusched.qos.observed_availability.

        Read-time computation silently mutates a record no api write
        ever touched, which would break the delta codec's changed-hint
        contract ("name everything you touch": delta_between trusts
        un-hinted records as byte-identical) — a waiting pod would ship
        its arrival-time availability forever and the sidecar's
        pressure signal would freeze. So each read re-hints the pod
        into the change accumulator whenever the computed value drifts
        beyond AVAIL_REHINT_EPS from the last value it was served with;
        the hint drains NEXT cycle, so delta/pipeline transports see
        availability one cycle stale — the same lag the real kube
        annotation write-back path has."""
        q = dict(p)
        if "observed_avail" not in q:
            avail = observed_availability(
                q.get("submitted", now), q.get("run_seconds", 0.0),
                q.get("bound_at") if q["phase"] == "Bound" else None, now,
            )
            q["observed_avail"] = avail
            name = q["name"]
            last = self._avail_served.get(name)
            if last is None:
                # First read: the creation hint (add_pod/add_bound_pod)
                # already covers this cycle's value.
                self._avail_served[name] = avail
            elif abs(avail - last) > AVAIL_REHINT_EPS:
                self._avail_served[name] = avail
                self._changed.add(name)
        return q

    def pending_pods(self) -> list[dict]:
        with self._lock:
            now = self._clock()
            return [self._with_avail(p, now) for p in self._pods.values()
                    if p["phase"] == "Pending"]

    def pods_named(self, names: Iterable[str]) -> list[dict]:
        """O(len(names)) read of specific pending pods, with the same
        availability accounting / re-hint side effects as
        pending_pods(). Skips names that are gone or no longer Pending
        — the device-queue cycle (ISSUE 20) reads ONLY its extracted
        window through this, never the full pending set."""
        with self._lock:
            now = self._clock()
            out = []
            for name in names:
                p = self._pods.get(name)
                if p is not None and p["phase"] == "Pending":
                    out.append(self._with_avail(p, now))
            return out

    def bound_pods(self) -> list[dict]:
        with self._lock:
            now = self._clock()
            return [self._with_avail(p, now) for p in self._pods.values()
                    if p["phase"] == "Bound"]

    # -- write side ---------------------------------------------------------

    def bind(self, pod_name: str, node_name: str) -> None:
        with self._lock:
            pod = self._pods.get(pod_name)
            if pod is None:
                raise Conflict(f"bind: pod {pod_name} does not exist")
            if pod["phase"] != "Pending":
                raise Conflict(
                    f"bind: pod {pod_name} is {pod['phase']} on {pod['node']}"
                )
            if node_name not in self._nodes:
                raise Conflict(f"bind: node {node_name} does not exist")
            pod["phase"] = "Bound"
            pod["node"] = node_name
            pod["bound_at"] = self._clock()
            self.bind_count += 1
            self._changed.add(pod_name)

    def delete_pod(self, pod_name: str) -> bool:
        """Eviction; returns False if already gone (idempotent)."""
        with self._lock:
            if pod_name not in self._pods:
                return False
            del self._pods[pod_name]
            self._avail_served.pop(pod_name, None)
            self.delete_count += 1
            self._changed.add(pod_name)
            return True


# ---------------------------------------------------------------------------
# The scheduler host.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CycleStats:
    batch_size: int
    placed: int
    evicted: int
    build_seconds: float
    solve_seconds: float
    bind_seconds: float

    @property
    def total_seconds(self) -> float:
        return self.build_seconds + self.solve_seconds + self.bind_seconds


class HostScheduler:
    """One scheduling host: batches pending pods, solves, binds.

    backend: an Engine (in-process) or a SchedulerClient (gRPC sidecar)
    — both consume the same wire snapshot via the C12 codec, so the
    in-process path exercises exactly what the sidecar decodes.
    """

    def __init__(
        self,
        api: FakeApiServer,
        config: EngineConfig | None = None,
        client=None,
        batch_size: int = 1024,
        buckets: Buckets | None = None,
        engine: Engine | None = None,
        backoff_initial: float = 1.0,
        backoff_max: float = 10.0,
        clock=None,
        use_delta: bool = True,
        transport: str = "delta",
        explain=None,
        refresh_frac: "float | None" = None,
        tracer=None,
        warm: "bool | str" = False,
        ledger=None,
        device_queue: bool = False,
        queue_capacity: int = 1024,
    ):
        """explain (round 12, ISSUE 8): optional
        tpusched.explain.ExplainCollector; None falls back to the
        process default (tpusched.explain.DEFAULT — disabled unless
        explain.set_enabled(True), mirroring trace.DEFAULT). When the
        collector is enabled, the IN-PROCESS engine path runs every
        cycle explained and appends one DecisionRecord per cycle (the
        sim's miss-attribution input; `ts` rides this host's clock, so
        virtual-time drivers get virtual timestamps). gRPC transports
        ignore it — server-side explain (make_server(explain=...))
        owns provenance there.

        tracer: optional tpusched.trace.TraceCollector for the
        per-cycle host.cycle span; None falls back to the process
        default at emit time (injected-collector discipline, TPL009).

        warm (ROADMAP item 3): in-process engines only — maintain ONE
        device-resident DeviceSnapshot lineage across cycles, feed it
        the api's change hints as record deltas, and warm-start each
        solve from the carried tableau (Engine.solve_warm_async).
        Placements are bitwise-identical to the decode-every-cycle path
        (the twin-parity contract); availability freshness follows the
        delta transports' hint contract (FakeApiServer re-hints only
        past AVAIL_REHINT_EPS drift). Any cycle failure invalidates the
        lineage — the next cycle full-loads and solves cold. While the
        explain collector is enabled, cycles fall back to the explained
        decode path (the warm program is never traced with observers).

        warm="incremental" (ISSUE 12): additionally seed each solve
        with the previous cycle's assignment and run commit rounds only
        over the pending frontier (Engine.solve_warm_async(incremental=
        True)) — bounded divergence under the in-kernel validity
        contract instead of bitwise parity; every cycle failure drops
        the carry with the lineage (the same unwind).

        ledger (round 18, ISSUE 13): optional
        tpusched.ledger.CycleLedger; None falls back to the process
        default at emit time (injected-collector discipline). Every
        successful cycle appends one CycleRecord — batch/placed/
        evicted counts, build/solve/bind stage walls, churn (the
        drained change hints), warm path taken, commit rounds, and
        the XLA cache misses the cycle paid (ledger.COMPILES delta).
        The record's `ts` rides this host's clock, so virtual-time
        drivers emit virtual timestamps; `ledger_source` tags the
        emitter ("host"; the sim driver re-tags its host "sim").

        device_queue (ISSUE 20): keep the pending set in a
        device-resident DeviceQueue instead of re-reading and
        re-filtering `pending_pods()` every cycle. Change hints drive
        O(churn) queue upserts/removals, the top-W solve window is
        extracted on device (availability-decay priority recomputed
        in-kernel), and only the window's W records are read back
        through `pods_named` — per-cycle host work is O(arrivals),
        not O(pending). The queue chooses batch MEMBERSHIP only; the
        window is re-ordered by arrival_seq before the solve, so
        whenever every eligible pod fits the batch the solver sees the
        EXACT batch the host-sorted path would have built (the
        pressure_skew bit-parity contract); under overload the window
        is the highest-pressure W instead of the first W by age."""
        self.api = api
        self.tracer = tracer
        self.config = config or EngineConfig()
        # Transport config accepts ADDRESSES, not just a built client
        # (round 11, ISSUE 6): a str or an ordered list/tuple of
        # replica endpoints builds a failover-capable SchedulerClient
        # owned (and closed) by this host.
        self._owns_client = False
        if isinstance(client, (str, list, tuple)):
            from tpusched.rpc.client import SchedulerClient  # tpl: disable=TPL001(grpc transport is optional; the in-process host must import without grpc)

            client = SchedulerClient(client)
            self._owns_client = True
        self.client = client
        self.batch_size = batch_size
        self.buckets = buckets
        # Engine jit caches live per instance: callers running many hosts
        # (benchmarks, replays) should pass a shared engine so compiles
        # amortize the way the long-lived sidecar's do.
        if client is not None:
            self._engine = None
        else:
            self._engine = engine if engine is not None else Engine(self.config)
        if warm and client is not None:
            raise ValueError(
                "warm=True is the in-process device-resident path; gRPC "
                "transports keep their lineage in the sidecar's "
                "DeviceSession"
            )
        if warm not in (False, True, "bitwise", "incremental"):
            raise ValueError(
                f"warm={warm!r}: want False, True/'bitwise', or "
                "'incremental'"
            )
        self._warm = bool(warm)
        self._warm_incremental = warm == "incremental"
        self._warm_ds: "DeviceSnapshot | None" = None
        # Last cycle's snapshot membership per class (node / pending /
        # running names): the solve input is the FILTERED pending list
        # (backoff windows, batch cap), so membership changes without a
        # change hint and the delta must carry the symmetric difference.
        self._warm_members = None
        # Sidecar transport (chosen by `transport`; use_delta=False is
        # the legacy spelling of "full"):
        #   "delta"    — DeltaSession: each cycle ships only churned
        #                records against the previous cycle's base
        #                (SURVEY.md §7 hard part 6), with changed-name
        #                hints from the api's change log making the
        #                diff O(churn);
        #   "pipeline" — AssignPipeline at depth 1: the pinned-base
        #                cumulative-delta discipline plus its retry /
        #                lineage-resync machinery (ISSUE 5: the sim's
        #                gRPC mode rides this, so long simulated runs
        #                heal through sidecar restarts the way the
        #                robustness suite pins);
        #   "full"     — full snapshot every cycle.
        if transport not in ("delta", "pipeline", "full"):
            raise ValueError(
                f"transport={transport!r}: want delta|pipeline|full"
            )
        if not use_delta and transport == "delta":
            transport = "full"
        self._delta = None
        self._pipeline = None
        if client is not None and transport == "delta":
            from tpusched.rpc.client import DeltaSession  # tpl: disable=TPL001(grpc transport is optional; the in-process host must import without grpc)

            self._delta = DeltaSession(client)
        elif client is not None and transport == "pipeline":
            from tpusched.rpc.client import AssignPipeline  # tpl: disable=TPL001(grpc transport is optional; the in-process host must import without grpc)

            # refresh_frac: pin-refresh churn threshold passthrough
            # (None keeps the client default). The simulator threads
            # SimConfig.pipeline_refresh_frac here so long drifting
            # runs can stay on the delta path deliberately.
            kw = {} if refresh_frac is None else dict(
                refresh_frac=refresh_frac)
            self._pipeline = AssignPipeline(client, depth=1, **kw)
        self.cycles: list[CycleStats] = []
        # Queue semantics (SURVEY.md §1.2 L5: activeQ/backoffQ): a pod
        # that fails to place enters backoff with exponentially growing
        # delay (upstream kube-scheduler: initial 1s, cap 10s) and is
        # excluded from batches until its retry time — so one
        # unschedulable pod cannot spin the cycle loop. Success clears
        # its backoff state. `clock` is injectable for tests.
        # GANG members share ONE backoff entry (keyed by the group):
        # per-pod windows would desynchronize and the all-or-nothing
        # gate could then never see the whole group in one batch.
        self.backoff_initial = backoff_initial
        self.backoff_max = backoff_max
        self._clock = clock if clock is not None else time.monotonic
        self._backoff: dict[str, tuple[float, int]] = {}  # key -> (retry_at, attempts)
        self._io_pool: ThreadPoolExecutor | None = None
        # Cycles that died on a transient sidecar failure and were
        # re-driven by run_until_idle (ISSUE 3: the host survives its
        # scheduler backend's failures the way kube-scheduler survives
        # an apiserver hiccup — state is re-read, the cycle re-runs).
        # Round 9 exports the count as a Prometheus counter in the
        # process-default registry (it was in-memory-only state).
        self.failed_cycles = 0
        self._m_failed_cycles = pm.Counter(
            "tpusched_host_failed_cycles_total",
            "scheduling cycles re-driven after a transient rpc failure")
        self.explain = explain if explain is not None \
            else explaining.DEFAULT
        self.ledger = ledger
        self.ledger_source = "host"
        # Device-resident pending queue (ISSUE 20). The side tables map
        # backoff keys to resident member names so gang parking and
        # backoff-book pruning stay O(churn) — the host-sorted path
        # derives both from the full pending read the queue exists to
        # avoid.
        self._devqueue = None
        self._dq_members: dict[str, set[str]] = {}   # backoff key -> names
        self._dq_key_of: dict[str, str] = {}         # name -> backoff key
        if device_queue:
            self._devqueue = DeviceQueue(
                capacity=queue_capacity,
                qos_gain=float(self.config.qos.qos_gain))

    def _io(self) -> ThreadPoolExecutor:
        """Lazy pool for concurrent API-server writes (binds/deletes)."""
        if self._io_pool is None:
            self._io_pool = ThreadPoolExecutor(
                max_workers=8, thread_name_prefix="tpusched-bind"
            )
        return self._io_pool

    def close(self) -> None:
        """Shut down the bind/delete worker pool (idle workers also
        exit when the host is garbage-collected) and any client this
        host built from addresses; long-lived processes cycling many
        hosts should call this."""
        if self._io_pool is not None:
            self._io_pool.shutdown(wait=False)
            self._io_pool = None
        if self._owns_client and self.client is not None:
            self.client.close()
            self.client = None
            self._owns_client = False

    @staticmethod
    def _backoff_key(p: dict) -> str:
        g = p.get("pod_group")
        return f"gang\x00{g}" if g else f"pod\x00{p['name']}"

    def _restore_hints(self, changed) -> None:
        """Un-drain change hints a cycle consumed but never shipped.
        Device-queue mutations already applied from these hints are
        safe to replay — upsert/remove/park are idempotent."""
        if self._delta is not None or self._pipeline is not None \
                or self._warm or self._devqueue is not None:
            restore = getattr(self.api, "restore_changed", None)
            if restore is not None:
                restore(changed)

    # -- device-resident pending queue (ISSUE 20) ----------------------------

    def _dq_now(self) -> float:
        """The queue's single timebase: the API SERVER's clock (pod
        `submitted` stamps ride it), NOT this host's backoff clock —
        mixing the two in one table would corrupt in-kernel ages. Sim
        drivers inject one VirtualClock into both, so there the bases
        coincide."""
        clk = getattr(self.api, "_clock", None)
        return float(clk()) if callable(clk) else time.time()

    def _dq_upsert(self, p: dict) -> None:
        name = p["name"]
        key = self._backoff_key(p)
        old = self._dq_key_of.get(name)
        if old is not None and old != key:
            self._dq_members.get(old, set()).discard(name)
        self._dq_key_of[name] = key
        self._dq_members.setdefault(key, set()).add(name)
        gain = float(self.config.qos.qos_gain)
        pinned = p.get("observed_avail")
        if pinned is not None:
            # Pinned availability (annotation write-back / tests): no
            # in-kernel decay — fold the whole effective priority into
            # the base and zero the SLO leg so the kernel's pressure
            # term vanishes. Re-pins arrive as change hints.
            base = float(p.get("priority", 0.0)) + gain * clamp01(
                float(p.get("slo_target", 0.0)) - float(pinned))
            slo = 0.0
        else:
            base = float(p.get("priority", 0.0))
            slo = float(p.get("slo_target", 0.0))
        retry_at, _ = self._backoff.get(key, (0.0, 0))
        rem = retry_at - self._clock()
        self._devqueue.upsert(
            name, base_priority=base, slo_target=slo,
            submitted=float(p.get("submitted", 0.0)),
            run_seconds=float(p.get("run_seconds", 0.0)),
            parked_until=self._dq_now() + rem if rem > 0 else 0.0,
            seq=p.get("arrival_seq"))

    def _dq_remove(self, names: list[str]) -> None:
        for name in names:
            key = self._dq_key_of.pop(name, None)
            if key is None:
                continue
            members = self._dq_members.get(key)
            if members is not None:
                members.discard(name)
                if not members:
                    # Last resident member gone: the backoff book entry
                    # is dead too (the host-sorted path prunes these
                    # against the full pending read).
                    del self._dq_members[key]
                    self._backoff.pop(key, None)
        self._devqueue.remove(names)

    def _dq_sync(self, changed: "set[str] | None") -> None:
        """Reconcile the device queue with the api: O(churn) per cycle.
        changed=None (first cycle / informer re-list) is the one full
        O(pending) resync; every other cycle touches only the hinted
        names. Hint names are processed in sorted order so internally
        stamped arrival seqs (records without arrival_seq) stay
        deterministic under set-iteration randomization."""
        if changed is None:
            live = self.api.pending_pods()
            live_names = {p["name"] for p in live}
            self._dq_remove([n for n in list(self._dq_key_of)
                             if n not in live_names])
            for p in live:
                self._dq_upsert(p)
            return
        for name in sorted(changed):
            p = self.api.get_pod(name)
            if p is None or p.get("phase") != "Pending":
                if name in self._dq_key_of:
                    self._dq_remove([name])
                continue
            self._dq_upsert(p)

    def _dq_repark(self, failed_keys: dict) -> None:
        """Mirror this cycle's backoff-book updates into the queue's
        parking bits: failed keys park every resident member until the
        key's retry time, cleared keys unpark them (a gang whose member
        placed re-enters the active window NOW, exactly like the
        host-sorted path's book-driven filter)."""
        dq_now = self._dq_now()
        host_now = self._clock()
        for key, fail in failed_keys.items():
            if fail:
                retry_at, _ = self._backoff.get(key, (0.0, 0))
                until = dq_now + max(retry_at - host_now, 0.0)
            else:
                until = 0.0
            for nm in self._dq_members.get(key, ()):
                self._devqueue.park(nm, until)

    @staticmethod
    def _result_names(meta, res):
        """(assignments, evicted) as name pairs from an in-process
        SolveResult + its SnapshotMeta — shared by the warm and decode
        cycle paths so the bind inputs cannot drift between them."""
        assignments = [
            (meta.pod_names[i], meta.node_names[int(n)])
            for i, n in enumerate(res.assignment[: meta.n_pods])
            if n >= 0
        ]
        evicted = []
        if res.evicted is not None and res.evicted.any():
            names = meta.running_names or []
            evicted = [
                names[m] for m in np.argwhere(res.evicted).ravel()
                if m < len(names)
            ]
        return assignments, evicted

    def _warm_reset(self, reason: str) -> None:
        """Drop the warm lineage (ROADMAP item 3): the carried tableau
        must not survive a failed cycle, drain/restore unwind, or an
        explain-mode detour — the next warm cycle full-loads a fresh
        DeviceSnapshot and solves cold."""
        if self._warm_ds is not None:
            self._warm_ds.invalidate_warm(reason)
        self._warm_ds = None
        self._warm_members = None

    def _warm_cycle_solve(self, nodes_r, pods_r, running_r, changed,
                          backlog: int = 0):
        """One in-process warm cycle: reconcile the device-resident
        lineage with this cycle's record snapshot and warm-solve it.
        Deltas come from the api change hints PLUS the membership diff
        per class — backoff windows and the batch cap move pods in and
        out of the solve input without any hint, and a bind moves a pod
        pending -> running under one hint. changed=None (first cycle, or
        an informer re-list) rebuilds the lineage from scratch.

        backlog: total pending pods (pre-batch-cap); the lineage's
        running bucket is floored to current running + backlog so a
        draining queue does not force a row_bucket rebuild (= a cold
        solve) every cycle as binds land."""
        cur = (
            {r["name"] for r in nodes_r},
            {r["name"] for r in pods_r},
            {r["name"] for r in running_r},
        )
        ds = self._warm_ds
        if ds is None or changed is None or self._warm_members is None:
            buckets = self.buckets
            if buckets is None:
                buckets = Buckets.fit(
                    len(pods_r), len(nodes_r),
                    len(running_r) + backlog,
                )
            # The lineage shards over the engine's mesh (if any) so the
            # warm dispatch reads the device arrays in place.
            ds = DeviceSnapshot(self.config, buckets,
                                mesh=self._engine.mesh)
            ds.full_load(nodes_r, pods_r, running_r)
            self._warm_ds = ds
        else:
            prev_n, prev_p, prev_r = self._warm_members
            touch = set(changed)
            ds.apply(
                upsert_nodes=[r for r in nodes_r
                              if r["name"] in touch
                              or r["name"] not in prev_n],
                remove_nodes=sorted(prev_n - cur[0]),
                upsert_pods=[r for r in pods_r
                             if r["name"] in touch
                             or r["name"] not in prev_p],
                remove_pods=sorted(prev_p - cur[1]),
                upsert_running=[r for r in running_r
                                if r["name"] in touch
                                or r["name"] not in prev_r],
                remove_running=sorted(prev_r - cur[2]),
            )
        self._warm_members = cur
        # Path taken, read off the lineage counters around the solve
        # (commit_warm stamps them at dispatch): the ledger's warm-mix
        # must report what actually served, incl. cold fallbacks.
        marker = ds.warm_marker()
        res = self._engine.solve_warm_async(
            ds, incremental=self._warm_incremental
        ).result()
        return res, ds.meta, ds.warm_path_taken(marker)

    # -- snapshot assembly --------------------------------------------------

    @staticmethod
    def _node_record(n: dict) -> dict:
        return dict(
            name=n["name"], allocatable=n.get("allocatable", {}),
            labels=n.get("labels", {}), taints=n.get("taints", []),
            used=n.get("used", {}),
            unschedulable=n.get("unschedulable", False),
        )

    @staticmethod
    def _pending_record(p: dict) -> dict:
        keep = (
            "name", "requests", "priority", "slo_target", "observed_avail",
            "labels", "node_selector", "required_terms", "preferred_terms",
            "tolerations", "topology_spread", "pod_affinity", "pod_group",
            "pod_group_min_member", "namespace",
        )
        return {k: p[k] for k in keep if k in p}

    @staticmethod
    def _running_record(p: dict) -> dict:
        rec = dict(
            name=p["name"], node=p["node"], requests=p.get("requests", {}),
            priority=p.get("priority", 0.0), labels=p.get("labels", {}),
            pod_affinity=p.get("pod_affinity", []),
            namespace=p.get("namespace", "default"),
        )
        if p.get("pdb_group"):
            rec["pdb_group"] = p["pdb_group"]
            rec["pdb_disruptions_allowed"] = p.get("pdb_disruptions_allowed", 0)
        # QoS slack of a running pod: observed availability minus SLO
        # (SURVEY.md C10); specs carry both or a precomputed slack.
        # Defaults live in ONE place (config.py) shared with the kube
        # annotation parser and the wire codec.
        if "slack" in p:
            rec["slack"] = p["slack"]
        else:
            rec["slack"] = slack_of(
                p.get("slo_target", DEFAULT_SLO_TARGET),
                p.get("observed_avail", DEFAULT_OBSERVED_AVAIL),
            )
        return rec

    def _wire_snapshot(self, pending: list[dict]):
        nodes = [self._node_record(n) for n in self.api.list_nodes()]
        running = [self._running_record(p) for p in self.api.bound_pods()]
        pods = [self._pending_record(p) for p in pending]
        return snapshot_to_proto(nodes, pods, running)

    # -- one cycle ----------------------------------------------------------

    def backlogged(self) -> int:
        """Pods currently waiting out a backoff window."""
        now = self._clock()
        return sum(1 for t, _ in self._backoff.values() if t > now)

    def cycle(self) -> CycleStats | None:
        """One batched scheduling cycle; None when nothing is ACTIVE
        (pods in their backoff window don't count — they re-enter the
        active queue when it expires)."""
        now = self._clock()
        # Flight-ledger context (round 18, ISSUE 13): compile counters
        # snapshot BEFORE any solve work so the record attributes
        # exactly the retraces this cycle paid.
        lg = self.ledger or ledgering.DEFAULT
        comp0 = ledgering.COMPILES.counters() if lg.enabled else (0, 0.0)
        warm_path = "cold"
        rounds = frontier = 0
        n_nodes = n_running = 0
        # Drain change hints BEFORE reading cluster state: an event
        # landing between the drain and the reads stays in the
        # accumulator for next cycle (harmless over-inclusion), whereas
        # draining after the reads could consume a hint whose state the
        # snapshot missed — shipping a stale delta record next cycle.
        changed = None
        epoch_fn = e0 = None
        # Warm cycles suspend while the explain collector is on (the
        # warm program carries no provenance observers); the lineage is
        # dropped so it cannot go hint-stale while bypassed.
        warm_cycle = self._warm and not self.explain.enabled
        if self._warm and not warm_cycle and self._warm_ds is not None:
            self._warm_reset("explain_enabled")
        if self._delta is not None or self._pipeline is not None \
                or warm_cycle or self._devqueue is not None:
            drain = getattr(self.api, "drain_changed", None)
            epoch_fn = getattr(self.api, "relist_epoch", None)
            if epoch_fn is not None:
                e0 = epoch_fn()
            if drain is not None:
                changed = drain()
        # EVERYTHING between the drain and a successful send sits under
        # one try: pending_pods() itself can raise (a malformed pod
        # record parsed by an informer-backed api), and a failure after
        # the drain but before the send would otherwise lose the hints —
        # DeltaSession's base only advances on success, so the next
        # delta would trust a stale base for those records forever.
        window_s = 0.0
        queue_depth = 0
        try:
            if self._devqueue is not None:
                # Device-queue path (ISSUE 20): O(churn) hint-driven
                # sync, in-kernel ranking, O(W) window read-back. The
                # full pending set is never read after the first cycle.
                t0 = time.perf_counter()
                self._dq_sync(changed)
                win_names, _n_elig, queue_depth = self._devqueue.window(
                    self._dq_now(), self.batch_size)
                window_s = time.perf_counter() - t0
                reader = getattr(self.api, "pods_named", None)
                if reader is not None:
                    pending = reader(win_names)
                else:
                    want = set(win_names)
                    pending = [p for p in self.api.pending_pods()
                               if p["name"] in want]
                # The queue chose MEMBERSHIP; arrival order feeds the
                # solver so the batch is byte-identical to the
                # host-sorted path's whenever everything eligible fit.
                pending.sort(key=lambda p: p.get("arrival_seq", 0))
                backlog = queue_depth
            else:
                all_pending = self.api.pending_pods()
                queue_depth = len(all_pending)
                # Prune backoff state for pods that vanished (deleted,
                # or bound by another actor) so the book can't grow
                # unbounded.
                live_keys = {self._backoff_key(p) for p in all_pending}
                for k in [k for k in self._backoff if k not in live_keys]:
                    del self._backoff[k]
                pending = [
                    p for p in all_pending
                    if self._backoff.get(
                        self._backoff_key(p), (0.0, 0))[0] <= now
                ]
                pending = pending[: self.batch_size]
                backlog = len(all_pending)
            if not pending:
                # Nothing ships this cycle: un-drain the hints or the
                # next delta would trust a stale base for those records.
                self._restore_hints(changed)
                return None
            t0 = time.perf_counter()
            if warm_cycle:
                # Record-dialect snapshot (the DeviceSnapshot input);
                # the wire proto is never built on the warm path.
                nodes_r = [self._node_record(n)
                           for n in self.api.list_nodes()]
                running_r = [self._running_record(p)
                             for p in self.api.bound_pods()]
                pods_r = [self._pending_record(p) for p in pending]
            else:
                msg = self._wire_snapshot(pending)
            build_s = time.perf_counter() - t0
            # An informer re-list between the drain and these reads
            # replaced the cache with state the drained hints cannot
            # cover (the missed-event window) — diff everything.
            if epoch_fn is not None and epoch_fn() != e0:
                changed = None

            t0 = time.perf_counter()
            if warm_cycle:
                # Inside the try: a failed apply/solve restores the
                # hints AND invalidates the lineage (the unwind below),
                # so the next cycle full-loads and solves cold instead
                # of trusting half-applied warm state.
                try:
                    res, meta, warm_path = self._warm_cycle_solve(
                        nodes_r, pods_r, running_r, changed,
                        backlog=backlog,
                    )
                except BaseException:
                    self._warm_reset("cycle_error")
                    raise
            elif self.client is not None:
                if self._pipeline is not None:
                    # Depth-1 AssignPipeline: submit drains the pipe
                    # before returning, so exactly one response comes
                    # back per cycle while the pinned-base cumulative
                    # delta + resync/retry machinery stays engaged.
                    resp = self._pipeline.submit(msg, changed=changed,
                                                 packed_ok=True)[-1]
                elif self._delta is not None:
                    resp = self._delta.assign(msg, changed=changed,
                                              packed_ok=True)
                else:
                    resp = self.client.assign(msg, packed_ok=True)
        except BaseException:
            self._restore_hints(changed)
            raise
        if warm_cycle:
            assignments, evicted = self._result_names(meta, res)
            solve_s = time.perf_counter() - t0
            rounds = int(res.rounds)
            if res.inc_info:
                frontier = int(res.inc_info.get("frontier", 0))
            n_nodes, n_running = len(nodes_r), len(running_r)
        elif self.client is not None:
            # Packed parallel-array response: three frombuffer reads
            # instead of P Python proto message traversals (~30 ms per
            # 10k-pod cycle on each side of the wire).
            from tpusched.rpc.client import assign_response_arrays  # tpl: disable=TPL001(grpc transport is optional; the in-process host must import without grpc)

            pod_names, node_names, ni, _, _ = assign_response_arrays(resp)
            assignments = [
                (pod_names[i], node_names[int(n)])
                for i, n in enumerate(ni) if n >= 0
            ]
            evicted = list(resp.evicted)
            solve_s = time.perf_counter() - t0
            rounds = int(resp.rounds)
            n_nodes, n_running = len(msg.nodes), len(msg.running)
        else:
            snap, meta = decode_snapshot(msg, self.config, self.buckets)
            # Async dispatch: the window between dispatch and join is
            # where in-cycle CPU work can hide (pipeline.solve_stream's
            # overlap, in-cycle form — one cluster's consecutive CYCLES
            # cannot pipeline, since cycle k's binds feed cycle k+1's
            # snapshot), and the engine's ordered fetch worker drives
            # the device either way.
            ex_col = self.explain
            explain_on = ex_col.enabled
            if explain_on:
                p_solve, p_probe = self._engine.solve_explained_async(
                    snap, ex_col.topk)
                res, exd = p_solve.result()
                probe = p_probe.result()
                ex_col.record(explaining.build_record(
                    self.config, meta, res, exd, probe,
                    rpc="host.cycle", ts=self._clock(),
                ))
            else:
                pending_solve = self._engine.solve_async(snap)
                res = pending_solve.result()
            assignments, evicted = self._result_names(meta, res)
            solve_s = time.perf_counter() - t0
            rounds = int(res.rounds)
            n_nodes, n_running = meta.n_nodes, meta.n_running

        t0 = time.perf_counter()
        # Deletes before binds: a preemptor's room must exist before its
        # bind (upstream issues evictions first, then re-queues). Each
        # call is one API-server write; issue each class CONCURRENTLY
        # (against a real apiserver these are network round trips —
        # hundreds of serial Binding POSTs dominated bind_seconds; the
        # FakeApiServer is lock-bound and unaffected), with a join
        # between the classes so every delete lands before any bind.
        pool = self._io()
        if evicted:
            list(pool.map(self.api.delete_pod, evicted))

        def _try_bind(a):
            try:
                self.api.bind(*a)
                return a[0]
            except Conflict:
                # Another actor bound/removed it; safe to skip — the
                # next cycle re-reads truth (idempotent-bind story).
                return None

        bound_names = {n for n in pool.map(_try_bind, assignments) if n}
        placed = len(bound_names)
        # Queue maintenance: placed pods (or gangs with any member
        # placed) leave the backoff book; unplaced ones back off
        # exponentially — one shared entry per gang.
        now = self._clock()
        failed_keys: dict[str, bool] = {}
        for p in pending:
            key = self._backoff_key(p)
            if p["name"] in bound_names:
                failed_keys[key] = False
            else:
                failed_keys.setdefault(key, True)
        for key, fail in failed_keys.items():
            if not fail:
                self._backoff.pop(key, None)
                continue
            _, attempts = self._backoff.get(key, (0.0, 0))
            delay = min(
                self.backoff_initial * (2 ** min(attempts, 30)),
                self.backoff_max,
            )
            # Stop counting once the delay is capped: 2**attempts would
            # overflow float for a pod that stays unschedulable for long.
            if delay < self.backoff_max:
                attempts += 1
            self._backoff[key] = (now + delay, attempts)
        if self._devqueue is not None:
            self._dq_repark(failed_keys)
        bind_s = time.perf_counter() - t0
        stats = CycleStats(
            batch_size=len(pending), placed=placed, evicted=len(evicted),
            build_seconds=build_s, solve_seconds=solve_s, bind_seconds=bind_s,
        )
        self.cycles.append(stats)
        # One retroactive span per completed cycle: the host-side roof
        # over the per-request client/server traces (the rpc spans
        # carry their own request_ids; this one carries the batch).
        (self.tracer or tracing.DEFAULT).record(
            "host.cycle", dur_s=stats.total_seconds, cat="host",
            batch=stats.batch_size, placed=placed, evicted=len(evicted),
        )
        # One flight-ledger record per completed cycle (round 18,
        # ISSUE 13): the cycle-sequence join of everything above —
        # sizes, stage walls, churn, warm path, rounds, and the
        # retraces this cycle paid. The sentinel inside observe()
        # flags and attributes p99 spikes.
        if lg.enabled:
            c1, s1 = ledgering.COMPILES.counters()
            lg.observe(ledgering.CycleRecord(
                ts=float(now), source=self.ledger_source,
                pods=len(pending), nodes=int(n_nodes),
                running=int(n_running), placed=placed,
                evicted=len(evicted),
                churn=len(changed) if changed else 0,
                frontier=frontier, rounds=rounds, warm_path=warm_path,
                solve_s=solve_s,
                stages=(dict(build=build_s, solve=solve_s, bind=bind_s,
                             window=window_s)
                        if self._devqueue is not None else
                        dict(build=build_s, solve=solve_s, bind=bind_s)),
                compiles=c1 - comp0[0],
                compile_s=round(s1 - comp0[1], 6),
                queue_depth=int(queue_depth),
            ))
        return stats

    @staticmethod
    def _transient_rpc_error(exc: BaseException) -> bool:
        """A sidecar RpcError the host loop may safely re-drive: the
        failed cycle mutated nothing (binds happen after a successful
        response; change hints were restored by cycle()'s unwind), the
        snapshot is rebuilt from API-server truth next cycle, and a
        retried applied-but-unacked delta is deduped server-side by its
        (lineage_id, seq). Retryable statuses (UNAVAILABLE /
        RESOURCE_EXHAUSTED) were already retried inside the client's
        deadline budget, DEADLINE_EXCEEDED means the watchdog killed
        one dispatch (re-submit as a new cycle, exactly what re-driving
        does), and even INTERNAL is worth bounded re-reads —
        kube-scheduler keeps cycling through apiserver hiccups. NOT
        re-driven: statuses the server taxonomy marks as request bugs
        (INVALID_ARGUMENT, UNIMPLEMENTED) — the identical cycle would
        deterministically fail again, and re-drives each paying an
        O(cluster) rebuild would only mask the bug. The
        consecutive-failure cap is the give-up switch for the rest."""
        try:
            import grpc
        except ImportError:  # in-process host: nothing rpc to tolerate
            return False
        if not isinstance(exc, grpc.RpcError):
            return False
        return exc.code() not in (grpc.StatusCode.INVALID_ARGUMENT,
                                  grpc.StatusCode.UNIMPLEMENTED)

    def run_until_idle(self, max_cycles: int = 100,
                       max_consecutive_failures: int = 8) -> int:
        """Cycle until the ACTIVE queue drains (unschedulable pods land
        in backoff and stop participating — a live host would keep
        polling and retry them as windows expire). Returns the number of
        cycles executed (failed transient attempts count toward
        max_cycles so a dead sidecar cannot spin this loop forever).

        Transient sidecar failures (any grpc RpcError — see
        _transient_rpc_error for why re-driving is safe) are tolerated
        up to max_consecutive_failures in a row; the first success
        resets the streak. Anything else propagates immediately."""
        n = 0
        streak = 0
        while n < max_cycles:
            try:
                stats = self.cycle()
            except BaseException as e:
                if streak >= max_consecutive_failures \
                        or not self._transient_rpc_error(e):
                    raise
                streak += 1
                self.failed_cycles += 1
                self._m_failed_cycles.inc()
                n += 1
                continue
            streak = 0
            n += 1 if stats else 0
            if stats is None:
                break
            if stats.placed == 0 and stats.evicted == 0 and self.backlogged():
                break  # everything still pending is in backoff
        return n


# ---------------------------------------------------------------------------
# Synthetic BASELINE.json:"configs"[0] cluster (tests, tools/chaos.py).
# ---------------------------------------------------------------------------


def build_synthetic_cluster(api: FakeApiServer, rng, n_pods: int, n_nodes: int):
    """configs[0]-shaped cluster: QoS-weighted LeastRequested workload."""
    for i in range(n_nodes):
        api.add_node(
            f"node-{i}",
            allocatable={"cpu": 8000.0, "memory": float(32 << 30)},
            labels={"kubernetes.io/hostname": f"node-{i}",
                    "topology.kubernetes.io/zone": f"zone-{i % 3}"},
        )
    for i in range(n_pods):
        slo = float(rng.choice([0.0, 0.9, 0.99]))
        # No observed_avail pin (ISSUE 5): availability comes from the
        # api's lifecycle accounting at read time — a never-scheduled
        # pod starts at the optimistic 1.0 fallback and decays as it
        # waits, so pressure reflects real queueing instead of the old
        # rng.uniform(0.5, 1.0) demo draw that left the QoS loop open.
        api.add_pod(
            f"pod-{i}",
            requests={"cpu": float(rng.integers(100, 500)),
                      "memory": float(rng.integers(1 << 28, 1 << 30))},
            priority=float(rng.integers(0, 100)),
            slo_target=slo,
            labels={"app": ["web", "db", "cache"][int(rng.integers(3))]},
        )


def synthetic_buckets(n_pods: int, n_nodes: int) -> Buckets:
    """Explicit floor buckets covering a build_synthetic_cluster
    workload through a FULL run: running_pods floors at n_pods (every
    pending pod eventually binds), feature axes at the Buckets defaults
    (the synthetic content's labels fit under them). Pinning these on a
    fleet's servers makes every cycle ONE shape class — the finite set
    a prewarmed replica compiles at boot (PR 18: chaos kill-the-leader
    asserts a promoted standby's compile delta is 0), where
    content-derived buckets would grow as pods bind and recompile
    mid-run."""
    return Buckets.fit(n_pods, n_nodes, n_running=n_pods)
