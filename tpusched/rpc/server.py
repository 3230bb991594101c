"""The TPU scheduling sidecar (SURVEY.md C12): a gRPC server wrapping
Engine. This is the process a `--score-backend=tpu` scheduler talks to
(BASELINE.json:"north_star").

Service stubs are hand-wired with grpc generic handlers (the image has
protoc + grpcio but no grpc_tools codegen); the method table mirrors
protos/tpusched.proto's service block.

Request handling is STAGED (round 6, SURVEY.md §2.3 PP in-request):
decode runs outside the serialized dispatch section (concurrent across
handler threads), the dispatch slot is held just long enough to
enqueue the program (Engine.solve_async / score_topk_async — one
ordered background fetch worker), and the response's name tables build
while the device runs. A single pipelined connection (client
AssignPipeline / ScorePipeline, depth 2) therefore overlaps request
k+1's decode with request k's solve, and even a strictly sequential
client gets its response scaffolding for free inside the device window.

Round 7 makes the sidecar MULTI-CLIENT (ISSUE 2 tentpole):

  * DeviceSession keeps each delta lineage's cluster state RESIDENT on
    the device — deltas apply as O(churn) scatter updates
    (tpusched/device_state.py) instead of recompose + full decode +
    full H2D;
  * the dispatch mutex became _DispatchGate, a bounded FAIR queue
    (round-robin across client peers, FIFO within one, admission caps
    -> RESOURCE_EXHAUSTED);
  * _ScoreCoalescer fuses concurrent identical ScoreBatch deltas into
    one padded top-k dispatch, sliced per caller.

Observability (SURVEY.md §5): every batch emits one structured JSON log
line (sizes, rounds, per-phase seconds, placements/sec) on stderr, and
the Metrics rpc serves Prometheus text with upstream-compatible metric
names (scheduler_e2e_scheduling_duration_seconds etc.).

Round 8 (ISSUE 3) gives the sidecar a FAILURE-DOMAIN CONTRACT.

Error taxonomy — every status the sidecar returns falls in one of
three classes, and the client (rpc/client.py RetryPolicy) keys its
behavior off the class, never the message text:

  RETRYABLE (same request may succeed soon; capped backoff + retry)
    UNAVAILABLE          channel down / sidecar restarting
    RESOURCE_EXHAUSTED   dispatch-gate admission refused (queue full)
  RESYNC-REQUIRED (retrying the same delta can NEVER succeed; the
  client must fall back to a full snapshot and re-pin)
    FAILED_PRECONDITION  unknown/expired base_id, seq replayed past the
                         dedupe cache, or stateless degraded mode
  FATAL (a bug in the request or the server; retrying is wrong)
    INVALID_ARGUMENT     malformed delta (no base_id, duplicate names)
    DEADLINE_EXCEEDED    per-dispatch watchdog fired (the REQUEST is
                         dead; the server stays healthy — callers may
                         re-submit as a NEW cycle, not a blind retry)
    INTERNAL             unexpected server exception

Retry-safety: deltas carry (lineage_id, seq); a retried delta whose
first attempt was applied-but-unacked replays the cached response
instead of re-applying (SnapshotDelta proto comment).

Watchdog: every device-result join runs under `watchdog_s`; a hung
solve becomes DEADLINE_EXCEEDED for ITS caller, the wedged fetch
worker is abandoned (Engine.restart_fetch_worker), and the server
keeps serving other clients — a stuck dispatch can no longer wedge
the gate.

Degradation ladder (DegradationLadder): repeated device-path failures
quarantine the fast path one rung at a time —

    delta      device-resident DeviceSessions, O(churn) serving
    rebuild    sessions quarantined: every delta recomposes bytes and
               fully re-decodes (correct, slower)
    stateless  deltas refused (FAILED_PRECONDITION) and snapshot_ids
               withheld: clients full-send every cycle; the sidecar
               holds NO cross-request state a fault could corrupt

with automatic probe-based recovery: after a cooldown with successes,
the ladder promotes one rung on probation — one failure at the
restored rung demotes immediately, a success keeps it. Health reports
the rung and counters; Metrics exports them.

Round 11 (ISSUE 6) makes the sidecar a FLEET MEMBER instead of a
single point of failure. Every store registration (full send or delta)
is appended to a ReplicationLog (tpusched/replicate.py) as an op that
carries the SAME snapshot_id handed to the client; the Replicate rpc
serves that log to standby replicas, whose StandbyFollower applies the
ops into their own byte stores (and warms DeviceSessions), so a
failed-over client's delta against a leader-era base_id resolves on
the standby without a resync storm. Roles: a "leader" serves and
appends; a "standby" follows and serves only Health/Metrics/Debugz/
Replicate until its first Assign/ScoreBatch arrives — which PROMOTES
it (takeover: one trace event + flight dump carrying the hand-off
causal chain; the "replica.takeover" fault site can refuse it with
UNAVAILABLE — the split-brain-attempt guard scenario). Replication is
async: an op in flight when the leader dies is lost SAFELY — the
failed-over client gets FAILED_PRECONDITION and the ISSUE 3 resync
machinery re-sends the full snapshot.

Round 9 (ISSUE 4) makes the whole pipeline OBSERVABLE:

  * every handler roots a trace (tpusched.trace) at the request's
    wire request_id/parent_span (client-minted; absent => server-
    minted) and emits one span per stage — gate.wait, decode,
    delta.apply (+H2D bytes), dispatch, coalesce.lead/coalesce.wait,
    fetch.join, reply.pack — ring-buffered, exported by the Debugz
    rpc and tools/tracez.py as Chrome/Perfetto trace-event JSON;
  * a FlightRecorder snapshots the ring on watchdog trips, ladder
    demotions, and resync storms (>= 4 FAILED_PRECONDITION answers in
    5 s), so every PR-3 degradation event carries its causal trace;
  * _Metrics is a labeled registry (tpusched.metrics): per-rpc
    counters, per-stage log-scale histograms (the old 5s-capped
    buckets parked every real 10k x 5k solve in +Inf), H2D byte and
    fuse-size histograms, and request outcome counts by status code.
"""

from __future__ import annotations

import hashlib
import json
import logging
import sys
import threading
import time
import traceback
import uuid
from collections import deque
from concurrent import futures
from concurrent.futures import TimeoutError as _FutTimeout
from contextlib import contextmanager

import jax
import numpy as np

import grpc

from tpusched import explain as explaining
from tpusched import ledger as ledgering
from tpusched import metrics as pm
from tpusched import shapeclass
from tpusched import trace as tracing
from tpusched import wire as wiring
from tpusched.faults import NO_FAULTS
from tpusched.mesh import make_mesh
from tpusched.config import Buckets, EngineConfig
from tpusched.device_state import DeviceQueue, DeviceSnapshot
from tpusched.ingest import IngestGate
from tpusched.replicate import ReplicationLog
from tpusched.engine import Engine
from tpusched.faults import FaultError
from tpusched.rpc import tpusched_pb2 as pb
from tpusched.rpc import codec
from tpusched.rpc.codec import SnapshotStore, decode_snapshot, delta_safe
from tpusched.trace import FlightRecorder, StormDetector

SERVICE = "tpusched.TpuScheduler"

# Recent snapshot stores kept for delta resolution. Each store holds
# references into decoded request protos (cheap); the cap bounds memory
# and defines how stale a client's base_id may be before it must resend
# a full snapshot. Sized for MULTI-CLIENT fan-in (round 7): K chained
# lineages each need their latest base plus one in flight to survive
# the LRU while the other K-1 register new stores every cycle — 8 was
# borderline at K=4 and forced periodic full resends + device-session
# re-seeds.
STORE_CAP = 32

# Device-resident lineages kept alive concurrently (each holds a full
# cluster's arrays on the accelerator, so the cap is memory, not CPU).
DEVICE_SESSION_CAP = 8

# Per-dispatch watchdog default: how long a handler waits on a device
# result before declaring the solve hung (DEADLINE_EXCEEDED + fetch
# worker abandoned). Generous — a 10k x 5k parity solve on a loaded
# CPU host takes tens of seconds; the watchdog exists for WEDGED
# dispatches (a transport hang, a stuck D2H), not slow ones.
WATCHDOG_S = 120.0

# Replayable responses kept per delta lineage for seq dedupe. A depth-2
# pipeline has at most 2 unacked requests in flight; 4 leaves margin
# for a retry racing a new submit. Responses above REPLAY_MAX_BYTES are
# NOT cached (a full-matrix ScoreBatch at 10k x 5k is ~250 MB; 4 per
# lineage x 32 lineages would be multi-GB): deterministic solves make
# re-processing an uncached retry safe — it re-applies against the
# still-stored base and rebuilds the identical response.
REPLAY_PER_LINEAGE = 4
REPLAY_MAX_BYTES = 8 << 20

# Above this many matrix cells a packed_ok ScoreBatch response switches
# from repeated ScoreRow to the packed-bytes form: the row form costs
# one pure-Python proto setter per cell (5*10^7 floats + bools at
# 10k x 5k — minutes, round-3 verdict missing #2), the packed form two
# ndarray.tobytes() calls.
PACK_CELLS = 1 << 15


class _Metrics:
    """Labeled Prometheus registry for the serving path (round 9,
    ISSUE 4 — replaces four unlabeled counters + one 5s-capped
    histogram). Built on tpusched.metrics: every family gets a `# TYPE`
    line, label values are escaped, histograms emit `_sum`/`_count`,
    and bucket ranges are shape-aware — durations log-scale out past
    the watchdog (a 10k x 5k CPU solve runs far beyond the old 5.0s
    top bucket, which parked every real solve in +Inf), H2D bytes in
    power-of-4 byte buckets, fuse sizes in small linear buckets.

    Upstream-compatible names are kept (scheduler_schedule_attempts_
    total etc.), now labeled by rpc; per-stage serving telemetry lands
    in scheduler_stage_duration_seconds{stage=...} where stage follows
    the trace span names (gate.wait, decode, delta.apply, dispatch,
    fetch.join, reply.pack) so a histogram anomaly points at the same
    name a trace shows."""

    def __init__(self):
        r = self.registry = pm.Registry()
        self.attempts = pm.Counter(
            "scheduler_schedule_attempts_total",
            "pods offered to the solver", ("rpc",), registry=r)
        self.placements = pm.Counter(
            "scheduler_pod_placements_total",
            "pods placed", ("rpc",), registry=r)
        self.evictions = pm.Counter(
            "scheduler_preemption_victims_total",
            "running pods evicted by preemption", ("rpc",), registry=r)
        self.batches = pm.Counter(
            "scheduler_batches_total",
            "request batches served", ("rpc",), registry=r)
        self.requests = pm.Counter(
            "scheduler_requests_total",
            "requests by final grpc status", ("rpc", "code"), registry=r)
        self.resyncs = pm.Counter(
            "scheduler_resync_required_total",
            "FAILED_PRECONDITION answers (client must full-resync)",
            ("rpc",), registry=r)
        self.overloaded = pm.Counter(
            "scheduler_overloaded_total",
            "dispatch-gate admission refusals", ("rpc",), registry=r)
        self.e2e = pm.Histogram(
            "scheduler_e2e_scheduling_duration_seconds",
            "decode + solve wall per batch",
            buckets=pm.DURATION_BUCKETS, labelnames=("rpc",), registry=r)
        self.stage = pm.Histogram(
            "scheduler_stage_duration_seconds",
            "per-stage serving latency (stage == trace span name)",
            buckets=pm.DURATION_BUCKETS, labelnames=("stage",), registry=r)
        self.h2d = pm.Histogram(
            "scheduler_h2d_bytes",
            "host->device bytes shipped per delta cycle",
            buckets=pm.BYTE_BUCKETS, labelnames=("path",), registry=r)
        # Wire ledger (round 19, ISSUE 19): per-direction bytes at the
        # serving boundary plus a reply-size histogram — before this,
        # only H2D bytes had a family and the reply/D2H direction was
        # entirely unaccounted.
        self.wire_bytes = pm.Counter(
            "scheduler_wire_bytes",
            "serialized request/reply bytes at the serving boundary",
            ("direction", "rpc"), registry=r)
        self.reply_bytes = pm.Histogram(
            "scheduler_reply_bytes",
            "serialized reply payload per served request",
            buckets=pm.BYTE_BUCKETS, labelnames=("rpc",), registry=r)
        self.fuse = pm.Histogram(
            "scheduler_coalesced_fuse_size",
            "callers sharing one coalesced ScoreBatch dispatch",
            buckets=(1, 2, 3, 4, 6, 8, 12, 16), registry=r)
        # Decision provenance (round 12): outcome counts and pending
        # causes, incremented per EXPLAINED cycle only (explain=off
        # cycles don't classify — the counters say so in the help).
        self.decisions = pm.Counter(
            "scheduler_decisions_total",
            "pod decision outcomes on explained cycles", ("outcome",),
            registry=r)
        self.pending_reasons = pm.Counter(
            "scheduler_pending_pods_total",
            "pending-pod causes on explained cycles (dominant filter "
            "reason, or outranked when feasible nodes existed)",
            ("reason",), registry=r)
        # Commit-round + warm-path observability (round 17, ISSUE 12):
        # the frontier-compaction win is a ROUND-COUNT story, so rounds
        # get a first-class histogram instead of living only in the
        # per-batch JSON log lines, and every Assign solve is labeled
        # by the path that produced it — cold (the plain packed solve),
        # bitwise (warm tableau, placements == cold), or incremental
        # (bounded-divergence frontier rounds).
        self.solve_rounds = pm.Histogram(
            "scheduler_solve_rounds",
            "commit rounds per solved Assign batch",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256), registry=r)
        self.warm_solves = pm.Counter(
            "scheduler_warm_solves_total",
            "Assign solves by warm path (bitwise|incremental|cold)",
            ("path",), registry=r)

    def observe(self, n_pods: int, n_placed: int, n_evicted: int,
                dur: float, rpc: str = "Assign"):
        self.attempts.labels(rpc).inc(n_pods)
        self.placements.labels(rpc).inc(n_placed)
        self.evictions.labels(rpc).inc(n_evicted)
        self.batches.labels(rpc).inc()
        self.e2e.labels(rpc).observe(dur)

    def observe_stage(self, stage: str, dur_s: float) -> None:
        self.stage.labels(stage).observe(dur_s)

    def count_request(self, rpc: str, code: str) -> None:
        self.requests.labels(rpc, code).inc()

    def render(self) -> str:
        return self.registry.render()


class DegradationLadder:
    """Quarantine state machine for the device fast path (module
    docstring, "Degradation ladder").

    Demotion: `demote_after` CONSECUTIVE failures at the current rung
    (or a single failure while on probation) drop one rung. Recovery:
    once `recover_after_s` has passed since the demotion AND at least
    one success has landed at the degraded rung, the next level() read
    promotes one rung ON PROBATION — the probe. All transitions are
    clock-injectable and deterministic for tests."""

    LEVELS = ("delta", "rebuild", "stateless")

    def __init__(self, demote_after: int = 2, recover_after_s: float = 30.0,
                 clock=time.monotonic):
        self._lock = threading.Lock()
        self._clock = clock
        self.demote_after = int(demote_after)
        self.recover_after_s = float(recover_after_s)
        self._idx = 0
        self._consec_failures = 0
        self._demoted_at: float | None = None
        self._successes_since_demote = 0
        self._probation = False
        self.demotions = 0
        self.recoveries = 0

    def level(self) -> str:
        """Current rung; performs the probe-promotion check."""
        with self._lock:
            self._maybe_promote_locked()
            return self.LEVELS[self._idx]

    def record_success(self) -> None:
        with self._lock:
            self._consec_failures = 0
            self._probation = False  # the probe survived: rung is kept
            self._successes_since_demote += 1

    def record_failure(self) -> bool:
        """One device-path failure; returns True when it demoted."""
        with self._lock:
            self._consec_failures += 1
            trip = (self._probation
                    or self._consec_failures >= self.demote_after)
            if trip and self._idx < len(self.LEVELS) - 1:
                self._idx += 1
                self.demotions += 1
                self._consec_failures = 0
                self._probation = False
                self._demoted_at = self._clock()
                self._successes_since_demote = 0
                return True
            return False

    def _maybe_promote_locked(self) -> None:
        if (
            self._idx > 0
            and self._demoted_at is not None
            and self._successes_since_demote > 0
            and self._clock() - self._demoted_at >= self.recover_after_s
        ):
            self._idx -= 1
            self.recoveries += 1
            self._probation = True
            self._successes_since_demote = 0
            # Still degraded after the promotion: arm the next probe.
            self._demoted_at = self._clock() if self._idx else None

    def snapshot(self) -> dict:
        with self._lock:
            return dict(
                level=self.LEVELS[self._idx],
                demotions=self.demotions,
                recoveries=self.recoveries,
                probation=self._probation,
            )


class _Abort(Exception):
    """Internal abort carrier: raised where the old code called
    context.abort directly, so COALESCED requests can relay the same
    status to every fused caller (each grpc context must abort itself)."""

    def __init__(self, code, details: str):
        super().__init__(details)
        self.code = code
        self.details = details


class _Overloaded(Exception):
    """Dispatch gate admission refused (queue caps hit)."""


class _DispatchGate:
    """Bounded FAIR admission to the device dispatch slot — the
    replacement for the old `_dispatch_lane` mutex.

    A plain lock serializes dispatches but hands the slot to whichever
    gRPC thread the OS wakes first: one chatty client can starve the
    rest, and tail latency under fan-in is whoever loses the race
    longest. The gate keeps one FIFO queue per client (peer string) and
    serves queue HEADS round-robin, so K clients each see every K'th
    slot — Assign streams from distinct clients interleave at round
    granularity — while one client's own requests stay ordered.

    Admission is BOUNDED: beyond `max_waiting_per_client` queued
    entries for one client (a runaway pipeline) or `max_waiting` total,
    acquire raises _Overloaded and the handler answers
    RESOURCE_EXHAUSTED instead of building an unbounded queue.
    """

    def __init__(self, max_waiting_per_client: int = 16,
                 max_waiting: int = 128):
        self._cv = threading.Condition()
        self._queues: dict[str, deque] = {}
        self._order: list[str] = []     # clients with waiters, stable order
        self._last: str | None = None   # round-robin pointer
        self._busy = False
        self._waiting = 0
        self._closed = False
        self.max_waiting_per_client = max_waiting_per_client
        self.max_waiting = max_waiting
        # Observability: served slots + peak depth.
        self.served = 0
        self.peak_waiting = 0

    def _choose(self):
        """(client, head ticket) the slot goes to next, by round-robin
        from the client AFTER the last served one."""
        order = self._order
        if not order:
            return None, None
        start = 0
        if self._last in order:
            start = order.index(self._last) + 1
        for i in range(len(order)):
            c = order[(start + i) % len(order)]
            q = self._queues.get(c)
            if q:
                return c, q[0]
        return None, None

    @contextmanager
    def slot(self, client: str):
        self._acquire(client)
        try:
            yield
        finally:
            self._release()

    def _acquire(self, client: str) -> None:
        me = object()
        with self._cv:
            if self._closed:
                raise _Overloaded("server shutting down")
            q = self._queues.get(client)
            if self._waiting >= self.max_waiting:
                raise _Overloaded(
                    f"dispatch queue full ({self.max_waiting} waiting)"
                )
            if q is not None and len(q) >= self.max_waiting_per_client:
                raise _Overloaded(
                    f"client {client!r} has {len(q)} dispatches queued "
                    f"(cap {self.max_waiting_per_client})"
                )
            if q is None:
                q = self._queues[client] = deque()
                self._order.append(client)
            q.append(me)
            self._waiting += 1
            self.peak_waiting = max(self.peak_waiting, self._waiting)
            while True:
                if self._closed:
                    self._evict(client, me)
                    raise _Overloaded("server shutting down")
                if not self._busy:
                    c, head = self._choose()
                    if head is me:
                        break
                self._cv.wait()
            # Our turn: take the slot and advance the round-robin.
            self._busy = True
            self._last = client
            self._evict(client, me)
            self.served += 1

    def _evict(self, client: str, me) -> None:
        q = self._queues.get(client)
        if q is not None and me in q:
            q.remove(me)
            self._waiting -= 1
            if not q:
                del self._queues[client]
                self._order.remove(client)

    def _release(self) -> None:
        with self._cv:
            self._busy = False
            self._cv.notify_all()

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()


class _Fusion:
    """One coalesced ScoreBatch dispatch: the LEADER resolves, decodes,
    dispatches once with k = max over joined callers, and publishes;
    followers wait and slice their own k columns from the shared
    result. Joining closes when the leader reaches the dispatch slot."""

    def __init__(self, key):
        self.key = key
        self._lock = threading.Lock()
        self._event = threading.Event()
        self._ks: list[int] = []
        self._sealed = False
        self._payload = None
        self._error: tuple | None = None

    def try_join(self, k: int) -> bool:
        with self._lock:
            if self._sealed:
                return False
            self._ks.append(int(k))
            return True

    def seal(self) -> int:
        """Stop admitting joiners; returns the fused k (max)."""
        with self._lock:
            self._sealed = True
            return max(self._ks) if self._ks else 0

    def publish(self, payload) -> None:
        self._payload = payload
        self._event.set()

    def fail(self, code, details: str) -> None:
        self._error = (code, details)
        self._event.set()

    def wait(self, timeout: float):
        if not self._event.wait(timeout):
            raise _Abort(grpc.StatusCode.DEADLINE_EXCEEDED,
                         "coalesced dispatch leader timed out")
        if self._error is not None:
            raise _Abort(self._error[0],
                         f"coalesced leader failed: {self._error[1]}")
        return self._payload


class _ScoreCoalescer:
    """Request-level fusion of concurrent ScoreBatch DELTAS against the
    same store: identical (base_id, delta bytes) means identical
    post-delta cluster state, so N callers' matrices are one padded
    device dispatch — resolve, decode/apply, and rank run ONCE, and
    per-caller top_k differences collapse to a column slice (lax.top_k
    is prefix-stable: the first k_i of top k_max IS top k_i)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._pending: dict = {}
        self.fused_requests = 0   # followers served without a dispatch
        self.lead_requests = 0

    def join(self, key, k: int):
        """(fusion, is_leader)."""
        with self._lock:
            f = self._pending.get(key)
            if f is not None and f.try_join(k):
                self.fused_requests += 1
                return f, False
            f = _Fusion(key)
            f.try_join(k)
            self._pending[key] = f
            self.lead_requests += 1
            return f, True

    def finish(self, fusion) -> None:
        with self._lock:
            if self._pending.get(fusion.key) is fusion:
                del self._pending[fusion.key]


class DeviceSession:
    """One delta lineage's device-resident cluster state (SURVEY.md §7
    hard part 6 + the tentpole of this round): wire deltas apply as
    on-device scatter updates through DeviceSnapshot instead of
    recompose-bytes -> full decode -> full H2D.

    A session answers deltas against TWO base ids:

      * its PIN — the base it was seeded from. Pipelined clients
        (AssignPipeline / ScorePipeline) send CUMULATIVE deltas that
        all name the pin; the session applies cumulative delta k+1 on
        top of cumulative delta k by also RESTORING pin records that
        delta k touched but k+1 no longer mentions (a record mutated
        back to its pin content drops out of the diff).
      * its CURRENT snapshot_id — chain clients (DeltaSession) target
        the previous response's sid; serving that id re-pins the
        session there (shallow record-dict copies, O(records) pointer
        work).

    A fork (a second delta against a base the session has moved past)
    simply misses and takes the decode path."""

    def __init__(self, device: DeviceSnapshot, pin_sid: str):
        self.device = device
        self.lock = threading.Lock()
        self.last_stats = None   # ApplyStats of the latest load/apply
        self._pin_sid = pin_sid
        self._cur_sid = pin_sid
        self._pin = (dict(device._nodes), dict(device._pods),
                     dict(device._running))
        # Names churned since the pin, per collection.
        self._touched: tuple[set, set, set] = (set(), set(), set())

    def keys(self) -> set[str]:
        return {self._pin_sid, self._cur_sid}

    @classmethod
    def from_base_store(cls, store: SnapshotStore, base_id: str,
                        config: EngineConfig,
                        buckets: Buckets | None,
                        mesh=None) -> "DeviceSession":
        """Seed from the BASE (pre-delta) byte store so the pin matches
        what pipelined clients keep diffing against (the one-time
        O(cluster) conversion; every later delta is O(churn)). mesh:
        shard the lineage arrays in the canonical layout so warm
        dispatches on a mesh-backed engine read them in place."""
        def parse(cls_pb, raw):
            return cls_pb.FromString(raw) if isinstance(raw, bytes) else raw

        nodes = [codec.node_kwargs(parse(pb.Node, v))
                 for v in store.nodes.values()]
        pods = [codec.pod_kwargs(parse(pb.PendingPod, v))
                for v in store.pods.values()]
        running = [codec.running_kwargs(parse(pb.RunningPod, v))
                   for v in store.running.values()]
        device = DeviceSnapshot(config, buckets, mesh=mesh)
        stats = device.full_load(nodes, pods, running)
        session = cls(device, pin_sid=base_id)
        session.last_stats = stats
        return session

    def apply_delta(self, base_id: str, delta: "pb.SnapshotDelta",
                    new_sid: str):
        """Advance to base_id + delta. base_id must be one of keys()."""
        if base_id == self._cur_sid and base_id != self._pin_sid:
            # Chain step: the client committed to the current state —
            # re-pin here (shallow copies; record dicts are replaced,
            # never mutated, so sharing them is safe).
            dev = self.device
            self._pin = (dict(dev._nodes), dict(dev._pods),
                         dict(dev._running))
            self._pin_sid = base_id
            self._touched = (set(), set(), set())
        elif base_id != self._pin_sid:
            raise KeyError(f"session cannot serve base {base_id!r}")
        up_n = [codec.node_kwargs(n) for n in delta.upsert_nodes]
        up_p = [codec.pod_kwargs(p) for p in delta.upsert_pods]
        up_r = [codec.running_kwargs(r) for r in delta.upsert_running]
        rm_n = list(delta.remove_nodes)
        rm_p = list(delta.remove_pods)
        rm_r = list(delta.remove_running)
        new_touched = (
            {r["name"] for r in up_n} | set(rm_n),
            {r["name"] for r in up_p} | set(rm_p),
            {r["name"] for r in up_r} | set(rm_r),
        )
        # Restore pin records the previous cumulative delta touched but
        # this one no longer mentions (mutated back to pin content).
        for prev, new, pin, ups, rms in (
            (self._touched[0], new_touched[0], self._pin[0], up_n, rm_n),
            (self._touched[1], new_touched[1], self._pin[1], up_p, rm_p),
            (self._touched[2], new_touched[2], self._pin[2], up_r, rm_r),
        ):
            for name in prev - new:
                if name in pin:
                    ups.append(pin[name])
                else:
                    rms.append(name)
        self.last_stats = self.device.apply(
            upsert_nodes=up_n, remove_nodes=rm_n,
            upsert_pods=up_p, remove_pods=rm_p,
            upsert_running=up_r, remove_running=rm_r,
        )
        self._touched = new_touched
        self._cur_sid = new_sid
        return self.last_stats


class SchedulerService:
    def __init__(
        self,
        config: EngineConfig | None = None,
        buckets: Buckets | None = None,
        log_stream=None,
        audit_stream=None,
        device_sessions: int = DEVICE_SESSION_CAP,
        faults=None,
        watchdog_s: float = WATCHDOG_S,
        ladder: DegradationLadder | None = None,
        tracer: "tracing.TraceCollector | None" = None,
        flight: FlightRecorder | None = None,
        role: str = "leader",
        replication_log: "ReplicationLog | None" = None,
        explain=False,
        explain_k: int = 3,
        warm: "str | None" = None,
        ledger: "ledgering.CycleLedger | None" = None,
        ledger_jsonl: "str | None" = None,
        prewarm: bool = False,
        wire: "wiring.WireLedger | None" = None,
        wire_profile_dir: "str | None" = None,
        ingest=None,
    ):
        """audit_stream: optional file-like; when set, every Assign
        emits one JSON record PER POD (pod, node, score, commit_key —
        the upstream per-pod placement-decision audit, SURVEY.md §5
        'Metrics/observability') plus one per eviction. Off by default:
        at 10k pods a full audit is ~1 MB per batch.

        device_sessions: how many delta lineages keep their cluster
        state RESIDENT on the device (0 disables; every delta then
        recomposes + fully re-decodes as before).

        faults: optional tpusched.faults.FaultPlan, shared with the
        engine — sites "server.decode" and "server.session" here,
        "engine.fetch" inside the fetch worker (chaos harness).

        watchdog_s: per-dispatch result-join budget; a solve that has
        not landed in time becomes DEADLINE_EXCEEDED for its caller and
        the wedged fetch worker is abandoned (module docstring).

        ladder: injectable DegradationLadder (tests pin the clock).

        tracer: span collector (default: the process-wide
        tpusched.trace.DEFAULT, so in-process clients and the sidecar
        share one stitched ring). flight: injectable FlightRecorder.

        role: "leader" (serves + appends every store registration to
        its replication log) or "standby" (follows a leader's log via
        StandbyFollower; the first Assign/ScoreBatch promotes it —
        module docstring, round 11). replication_log: injectable
        ReplicationLog (tests pin capacity to force the rebase path).

        explain (round 12, ISSUE 8): decision provenance. True (or an
        injected tpusched.explain.ExplainCollector) makes every Assign
        an EXPLAINED cycle — the engine additionally runs the lazily-
        compiled provenance programs (per-pod outcome + top-k score
        decomposition + filter tallies + victim chains) and one
        DecisionRecord lands in the collector, served by the Explainz
        rpc and carried in flight-recorder dumps. Off (default) the
        serving path is byte-identical to round 11: one enabled-check
        per Assign. explain_k: candidate depth per pod.

        warm (round 17, ISSUE 12): None (default) keeps every Assign on
        the plain packed solve; "bitwise" routes delta Assigns whose
        lineage has a live DeviceSession through the warm-tableau path
        (placements bitwise == cold); "incremental" through the
        bounded-divergence frontier path (solve time scales with the
        delta's churn — the in-kernel validity audit rides
        SolveResult.inc_info). Either way full-send Assigns, explained
        cycles, forks, and degraded rungs fall back to the plain solve,
        and scheduler_warm_solves_total{path} counts what actually
        served.

        ledger (round 18, ISSUE 13): injectable
        tpusched.ledger.CycleLedger; by default the service builds its
        own, registered in ITS metrics registry (so
        scheduler_cycle_anomalies_total and friends render in this
        server's Metrics rpc) and wired to its flight recorder and
        span ring (an anomaly's flight dump carries the causal trace).
        Every served Assign appends one CycleRecord — stage walls
        joined from the request's spans, delta churn, warm path,
        commit rounds, and the XLA cache misses the request paid —
        served by the Statusz rpc / tools/statusz.py. ledger_jsonl:
        optional path for the JSONL black box (every record appended;
        ignored when `ledger` is injected).

        prewarm (PR 18, ROADMAP item 3): True traces EVERY shape class
        in the registry derived from (config, buckets, explain, warm) on
        a background boot thread — requires explicit `buckets` (no
        finite shape set exists otherwise). `prewarm_complete` flips
        when done (Health field 12; ReplicaSet.wait_caught_up blocks on
        it for standbys, so a promotion serves its first Assign with
        zero new compiles). Compiles traced during boot land in
        ledger.COMPILES with cause="prewarm".

        wire (round 19, ISSUE 19): injectable tpusched.wire.WireLedger;
        by default the service builds its own, registered in ITS
        metrics registry and wired to its flight recorder / span ring
        — the server HOLDS the ledger (Statusz `wire` panel, anomaly
        counters) while clients FEED it: an in-process or loopback
        client constructed with wire=svc.wire assembles each cycle's
        WireRecord from the shared span ring. wire_profile_dir: when
        set, a wire anomaly arms a one-shot jax.profiler device-trace
        capture of the next serving cycle (WireLedger.maybe_profile),
        written under this directory.

        ingest (PR 20, ISSUE 20): the admission-controlled front door
        served by the Enqueue rpc. None (default) leaves Enqueue
        UNIMPLEMENTED. An IngestGate instance is used as-is; any other
        truthy value builds a gate over a fresh DeviceQueue — pass a
        dict of knobs (capacity/bound for the queue; rate/burst/
        tenants/skew for tpusched.ingest.IngestGate) or True for the
        defaults. A built gate registers its families in THIS server's
        metrics registry, ledgers its drain records into THIS server's
        cycle ledger (source="ingest"), shares the fault plan (the
        ``ingest.enqueue`` site), and dedups admitted names so a
        shed-then-retried batch converges exactly-once."""
        self.config = config or EngineConfig()
        # Floor buckets pin compile shapes across requests (a feature
        # first appearing mid-serving would otherwise trigger a full
        # recompile stall; SnapshotBuilder docstring caveat).
        self.buckets = buckets
        self.metrics = _Metrics()
        # A configured mesh shape (or the ring path, which needs a
        # mesh) puts the sidecar's engine on a device mesh — the YAML
        # route to the sharded/ring paths (EngineConfig.mesh_shape).
        mesh = None
        if self.config.ring_counts or tuple(self.config.mesh_shape) != (1, 1):
            shape = tuple(self.config.mesh_shape)
            mesh = make_mesh(None if shape == (1, 1) else shape)
        self._faults = faults if faults is not None else NO_FAULTS
        # Device sessions shard their lineage arrays over the same mesh
        # the engine solves on (ROADMAP item 1: the snapshot a solve
        # reads and the lineage the deltas scatter into share one
        # canonical layout — no per-dispatch reshard).
        self._mesh = mesh
        self._engine = Engine(self.config, mesh=mesh, faults=self._faults)
        self._log = log_stream if log_stream is not None else sys.stderr
        self._audit = audit_stream
        self._audit_lock = threading.Lock()  # handlers run on a pool
        self._store_lock = threading.Lock()
        self._stores: dict[str, SnapshotStore] = {}  # LRU by insertion
        self._next_store = 0
        # Mint EPOCH (round 11): sids carry a per-instance nonce so a
        # promoted standby's own mints can NEVER alias a sid the dead
        # leader handed a client inside the async-replication loss
        # window — an aliased base would silently resolve a failed-over
        # delta against the wrong bytes instead of triggering the
        # FAILED_PRECONDITION -> resync heal path.
        self._mint_nonce = uuid.uuid4().hex[:8]
        self._last_minted: str | None = None  # newest REGISTERED sid
        # Dispatch admission (round 7, replaces the `_dispatch_lane`
        # mutex): handlers still decode OUTSIDE the serialized section
        # and build responses while the engine's ordered fetch worker
        # drives the device — but the slot itself is now a bounded FAIR
        # queue (round-robin across clients, FIFO within one), and
        # concurrent ScoreBatch deltas against the same store fuse into
        # one dispatch (_ScoreCoalescer). Dispatch order == fetch order
        # still holds: only the slot holder dispatches.
        self._gate = _DispatchGate()
        self._coalescer = _ScoreCoalescer()
        # Device-resident lineages: current snapshot_id -> DeviceSession
        # (LRU by insertion, capped — each holds a cluster on device).
        self._session_cap = device_sessions
        if warm not in (None, "bitwise", "incremental"):
            raise ValueError(
                f"warm={warm!r}: want None, 'bitwise', or 'incremental'"
            )
        self._warm = warm
        self._sessions: dict[str, DeviceSession] = {}
        self._seeding: set[str] = set()   # base_ids mid-seed (dedupe)
        self.session_seeds = 0
        self.session_hits = 0
        self.session_misses = 0
        # Failure-domain state (round 8, ISSUE 3): watchdog budget,
        # degradation ladder, and the per-lineage seq replay cache.
        self.watchdog_s = watchdog_s
        self.watchdog_trips = 0
        self._ladder = ladder if ladder is not None else DegradationLadder()
        self._watchdog_lock = threading.Lock()
        self._last_worker_restart = 0.0
        # lineage_id -> {(seq, rpc): response message}; LRU at both
        # levels. Deterministic solves make an evicted entry SAFE to
        # re-process — the replay is an optimization plus the dedupe
        # guarantee for the applied-but-unacked retry window.
        self._replay_lock = threading.Lock()
        self._replay: dict[str, dict] = {}
        self.replayed_requests = 0
        # Observability (round 9, ISSUE 4): span collector, flight
        # recorder (ring snapshots on failure events), and the resync-
        # storm detector feeding it.
        self._trace = tracer if tracer is not None else tracing.DEFAULT
        if tracer is not None:
            # Non-default collector: point the emitters this service
            # owns (engine.fetch, fault.* shots, device.rebuild via
            # DeviceSession seeding below) at the same ring, so Debugz
            # and flight dumps still carry the full causal chain.
            self._engine.tracer = tracer
            self._faults.tracer = tracer
        self.flight = flight if flight is not None else FlightRecorder()
        # Decision provenance (round 12, ISSUE 8): collector + the
        # flight-recorder attachment (dumps carry last-N decisions).
        # ONE source for the candidate depth: an injected collector's
        # topk wins (host-side wiring honors the same field); explain_k
        # only applies when the server builds its own collector.
        if isinstance(explain, explaining.ExplainCollector):
            self.explain = explain
        else:
            self.explain = explaining.ExplainCollector(
                enabled=bool(explain), topk=int(explain_k))
        self._explain_k = int(self.explain.topk)
        self.flight.decisions = self.explain
        # Cycle flight ledger (round 18, ISSUE 13): per-cycle telemetry
        # ring + regression sentinel, families in THIS server's metrics
        # registry, anomaly dumps into THIS server's flight recorder /
        # span ring (docstring). Served by the Statusz rpc.
        if ledger is not None:
            self.ledger = ledger
        else:
            self.ledger = ledgering.CycleLedger(
                registry=self.metrics.registry, flight=self.flight,
                tracer=self._trace, jsonl=ledger_jsonl)
        # Wire ledger (round 19, ISSUE 19): the per-cycle round-trip
        # decomposition's home — same discipline as the cycle ledger
        # above (families in THIS registry, anomaly dumps into THIS
        # flight recorder). Clients observe INTO it (wire=svc.wire).
        if wire is not None:
            self.wire = wire
        else:
            self.wire = wiring.WireLedger(
                registry=self.metrics.registry, flight=self.flight,
                tracer=self._trace, profile_dir=wire_profile_dir)
        # Admission-controlled ingest (PR 20, ISSUE 20): token-bucket
        # front door over a device-resident pending queue, served by
        # the Enqueue rpc (docstring above). Gauges/counters land in
        # THIS registry so Metrics renders queue depth and shed rate.
        if ingest is None:
            self.ingest = None
        elif isinstance(ingest, IngestGate):
            self.ingest = ingest
        else:
            spec = dict(ingest) if isinstance(ingest, dict) else {}
            queue = DeviceQueue(
                capacity=int(spec.pop("capacity", 4096)),
                bound=spec.pop("bound", None),
                qos_gain=float(self.config.qos.qos_gain),
            )
            self.ingest = IngestGate(
                queue, faults=self._faults,
                registry=self.metrics.registry, ledger=self.ledger,
                dedup=True, **spec)
        # Live device/store memory surface (ROADMAP item 1 feeds on
        # this): rendered at scrape time from the authoritative maps.
        pm.CallbackGauge(
            "scheduler_device_bytes",
            "live device-resident and host-retained bytes by kind "
            "(session_arrays: per-lineage DeviceSnapshot arrays on "
            "device; byte_stores: registered snapshot byte stores, "
            "shared records counted once per store)",
            ("kind",), callback=self._device_bytes_by_kind,
            registry=self.metrics.registry)
        self._resync_storm = StormDetector(n=4, window_s=5.0)
        self._closed = False
        # Replication (round 11, ISSUE 6): role, the op log, and the
        # takeover/lag surface Health + Metrics export. Appending is
        # unconditional — a standby promoted to leader keeps the same
        # log, whose mirrored ops already carry the old leader's seqs,
        # so a surviving second standby re-follows without a rebase.
        if role not in ("leader", "standby"):
            raise ValueError(f"role={role!r}: want leader|standby")
        self.role = role
        self._role_lock = threading.Lock()
        self._replog = (replication_log if replication_log is not None
                        else ReplicationLog())
        self.takeovers = 0
        self.replication_lag = 0      # updated by StandbyFollower
        self.replication_applied = 0  # ops applied as a standby
        self.replication_skipped = 0  # delta ops whose base was gone
        # Shape-class prewarm (PR 18, ROADMAP item 3): boot-time tracing
        # of the full registry on a daemon thread, so construction stays
        # fast and a fleet boots its replicas' compiles in parallel.
        # prewarm_complete is True for non-prewarming servers too ("as
        # warm as it will get") — wait_caught_up can gate uniformly.
        self.registry = None
        self.registry_classes = 0
        self.prewarm_classes_done = 0
        self.prewarm_s = 0.0
        self.prewarm_error: "str | None" = None
        self.prewarm_complete = not prewarm
        self._prewarm_thread: "threading.Thread | None" = None
        # close() sets this so a boot prewarm racing shutdown abandons
        # its remaining classes after the in-flight compile — a daemon
        # thread left inside XLA at interpreter exit aborts the process.
        self._prewarm_stop = threading.Event()
        if prewarm:
            if self.buckets is None:
                raise ValueError(
                    "prewarm=True needs explicit buckets=: shape classes "
                    "are a function of pinned bucket sizes "
                    "(tpusched.shapeclass.build_registry)"
                )
            self.registry = shapeclass.build_registry(
                self.config, self.buckets,
                explain=self.explain.enabled, explain_k=self._explain_k,
                warm=self._warm,
            )
            self.registry_classes = len(self.registry)
            self._prewarm_thread = threading.Thread(
                target=self._run_prewarm, name="tpusched-prewarm",
                daemon=True)
            self._prewarm_thread.start()

    def _run_prewarm(self) -> None:
        try:
            report = self._engine.prewarm(
                self.registry, should_stop=self._prewarm_stop.is_set)
            if report["cancelled"]:
                logging.getLogger("tpusched.rpc.server").info(
                    "shape-class prewarm cancelled by close() after "
                    "%.2fs", report["prewarm_s"])
                return
            self.prewarm_classes_done = report["classes"]
            self.prewarm_s = report["prewarm_s"]
            self._trace.record(
                "server.prewarm", dur_s=report["prewarm_s"], cat="server",
                classes=report["classes"], compiles=report["compiles"])
        except Exception:
            # A failed prewarm must not wedge wait_caught_up or boot —
            # the server still serves (compiling on demand); the error
            # is loud here and visible via prewarm_error/statusz.
            self.prewarm_error = traceback.format_exc(limit=5)
            logging.getLogger("tpusched.rpc.server").error(
                "shape-class prewarm failed; serving will compile on "
                "demand:\n%s", self.prewarm_error)
        finally:
            self.prewarm_complete = True

    def wait_prewarmed(self, timeout: "float | None" = None) -> bool:
        """Block until the boot prewarm finishes (immediately True when
        prewarm is off). The chaos/bench harnesses call this before
        measuring so cold-start compile time never leaks into serving
        metrics."""
        t = self._prewarm_thread
        if t is not None:
            t.join(timeout)
        return self.prewarm_complete

    def _store_put_locked(self, sid: str, store: SnapshotStore) -> None:
        """Insert + evict under _store_lock (caller holds it). The ONE
        place retention policy lives: the leader's mint path and the
        replication apply path must evict identically or leader/standby
        store retention drifts and the byte-identity contract breaks."""
        self._stores.pop(sid, None)
        self._stores[sid] = store
        self._last_minted = sid
        while len(self._stores) > STORE_CAP:
            self._stores.pop(next(iter(self._stores)))

    def _register_store(self, store: SnapshotStore, op_kind: str = "",
                        payload: bytes = b"", base_id: str = "") -> str:
        """Mint + register; when op_kind is set, the replication-log
        append happens INSIDE the same critical section — op order must
        equal registration order, or the standby's replayed insertion
        (= eviction) order diverges from the leader's under concurrent
        handlers and the two replicas evict different stores."""
        with self._store_lock:
            sid = f"snap-{self._mint_nonce}-{self._next_store}"
            self._next_store += 1
            self._store_put_locked(sid, store)
            if op_kind:
                self._replog.append(op_kind, sid, payload,
                                    base_id=base_id)
        return sid

    def _register_store_as(self, sid: str, store: SnapshotStore) -> None:
        """Register under a LEADER-minted snapshot_id (replication
        apply path). No mint-collision handling needed: local mints
        carry this instance's nonce, so a replicated (other-nonce) sid
        can never alias one we hand out post-takeover."""
        with self._store_lock:
            self._store_put_locked(sid, store)

    # -- replication (round 11) ---------------------------------------------

    def replica_apply(self, op: "pb.ReplicationOp") -> bool:
        """Apply one leader op on a standby: register the op's store
        under the leader's snapshot_id, warm the device session for
        delta lineages, and mirror the op into our own log. Returns
        False (skipped) for a delta op whose base this replica no
        longer holds — safe: the failed-over client heals through
        FAILED_PRECONDITION + full-snapshot resync.

        Runs under _role_lock with a role RE-CHECK: a takeover promotes
        under the same lock, so an apply in flight when a client's
        request promotes us finishes first and every later op is
        refused — an old-leader op delivered post-promotion can never
        overwrite a store the new leader registered. The O(cluster)
        device-session warm-up runs OUTSIDE the lock: a failed-over
        client's promoting request must not wait behind a session
        seed/compile (promotion latency IS failover recovery time;
        warmth is only an optimization)."""
        with self._role_lock:
            if self.role != "standby":
                return False
            applied, warm = self._replica_apply_locked(op)
        if warm is not None:
            self._replica_warm_session(*warm)
        return applied

    def _replica_apply_locked(self, op: "pb.ReplicationOp"):
        """(applied, warm-args-or-None); caller holds _role_lock."""
        with self._trace.span("replica.apply", cat="replica",
                              kind=op.kind, sid=op.snapshot_id) as sp:
            if op.kind == "full":
                msg = pb.ClusterSnapshot.FromString(op.payload)
                store = SnapshotStore()
                store.set_full_bytes(msg)
                self._register_store_as(op.snapshot_id, store)
                warm = None
            elif op.kind == "delta":
                with self._store_lock:
                    base = self._stores.get(op.base_id)
                    if base is not None:
                        # Mirror the serving path's true-LRU hit-touch
                        # of the delta's base: without it, leader and
                        # standby eviction orders diverge past
                        # STORE_CAP and the standby drops exactly the
                        # hot bases a failed-over client will name.
                        self._stores.pop(op.base_id)
                        self._stores[op.base_id] = base
                if base is None:
                    self.replication_skipped += 1
                    self._replog.mirror(op)
                    sp.attrs["skipped"] = True
                    return False, None
                delta = pb.SnapshotDelta.FromString(op.payload)
                store = base.copy()
                store.apply_delta(delta)
                self._register_store_as(op.snapshot_id, store)
                warm = (op.base_id, delta, op.snapshot_id, base)
            else:
                raise ValueError(f"unknown replication op kind {op.kind!r}")
            self._replog.mirror(op)
            self.replication_applied += 1
            return True, warm

    def replica_rebase(self, op: "pb.ReplicationOp") -> None:
        """Full rebase after falling behind log retention: drop every
        store and session (they chain from history we no longer have)
        and start over from the leader's newest store. Same _role_lock
        discipline as replica_apply — a post-promotion rebase must not
        wipe the new leader's stores."""
        with self._role_lock:
            if self.role != "standby":
                return
            with self._store_lock:
                self._stores.clear()
                self._sessions.clear()
            self._replica_apply_locked(op)  # "full" op: no warm-up args

    def _replica_warm_session(self, base_id: str, delta, sid: str,
                              base: SnapshotStore) -> None:
        """Best-effort device-session warm-up on the standby, mirroring
        the leader's lazy-seed-then-apply discipline so a takeover
        starts with the lineage's cluster already ON device. Failures
        drop the warm state silently — the post-takeover decode path is
        always the correctness floor, and a standby must not burn
        ladder demerits for an optimization."""
        if self._session_cap <= 0 or self._ladder.level() != "delta":
            return
        session = None
        try:
            with self._store_lock:
                session = self._sessions.get(base_id)
            if session is None:
                with self._trace.span("session.seed", cat="replica",
                                      base_id=base_id):
                    session = DeviceSession.from_base_store(
                        base, base_id, self.config, self.buckets,
                        mesh=self._mesh,
                    )
                    session.device.tracer = self._trace
                self.session_seeds += 1
            with session.lock:
                session.apply_delta(base_id, delta, sid)  # tpl: disable=TPL102(the apply IS the critical section: the lineage's device state must not advance past the base this op mirrors, and the H2D scatter is the apply itself)
            self._session_put(session)
        except Exception:
            logging.getLogger("tpusched.rpc.server").warning(
                "standby session warm-up failed; takeover will serve "
                "via decode:\n%s", traceback.format_exc(limit=3),
            )
            if session is not None:
                self._drop_session(session)

    def _maybe_takeover(self, rpc: str) -> None:
        """First serving request on a standby: promote to leader. The
        'replica.takeover' fault site can refuse it (split-brain-
        attempt guard) — the caller sees UNAVAILABLE and fails over to
        the next endpoint. The promotion is the failover event, so it
        snapshots the trace ring: the flight dump carries the hand-off
        causal chain (last replication polls + the promoting request)."""
        with self._role_lock:
            if self.role != "standby":
                return
            try:
                self._faults.fire("replica.takeover")  # tpl: disable=TPL102(a takeover delay shot must hold _role_lock: the simulated slow promotion has to block replication applies exactly like a real one would)
            except FaultError as e:
                raise _Abort(
                    grpc.StatusCode.UNAVAILABLE,
                    f"standby refused takeover (split-brain guard): {e}",
                ) from e
            self.role = "leader"
            self.takeovers += 1
            lag = self.replication_lag
            self.replication_lag = 0
        self._trace.record("replica.takeover", cat="replica", rpc=rpc,
                           lag_at_takeover=lag)
        self.flight.record("replica_takeover", self._trace,
                           rpc=rpc, lag_at_takeover=lag)

    @staticmethod
    def _check_delta_upserts(delta) -> None:
        """Defense-in-depth behind DeltaSession's client-side guard: a
        delta upsert with an empty or duplicate name would silently
        collapse in the name-keyed store and solve a corrupted snapshot.
        Reject loudly instead (INVALID_ARGUMENT — retrying the same
        delta cannot succeed, unlike an expired base)."""
        for coll in (delta.upsert_nodes, delta.upsert_pods,
                     delta.upsert_running):
            seen = set()
            for rec in coll:
                if not rec.name or rec.name in seen:
                    raise _Abort(
                        grpc.StatusCode.INVALID_ARGUMENT,
                        "delta upserts must carry unique non-empty names "
                        f"(offending record name: {rec.name!r})",
                    )
                seen.add(rec.name)

    def _session_put(self, session: DeviceSession) -> None:
        """(Re-)register under the session's current keys; LRU-evict
        whole sessions (not keys) past the cap. Sessions stay SHARED in
        the map while requests use them: a depth-2 pipeline always has
        one request in flight, and cumulative-from-pin applies are
        order-independent (every apply restores relative to the pin),
        so concurrent lineage requests serialize on session.lock
        instead of missing and re-seeding."""
        with self._store_lock:
            self._drop_session_locked(session)
            for k in session.keys():
                self._sessions.pop(k, None)
                self._sessions[k] = session
            distinct = []
            for s in self._sessions.values():
                if s not in distinct:
                    distinct.append(s)
            while len(distinct) > max(self._session_cap, 0):
                victim = distinct.pop(0)
                for k in list(self._sessions):
                    if self._sessions[k] is victim:
                        del self._sessions[k]

    def _drop_session_locked(self, session) -> None:
        """Forget every key mapping to `session` (caller holds
        _store_lock) — the single authority for session eviction, so
        the injected-fault paths and the real-failure heal path cannot
        silently diverge."""
        for k in [k for k, v in self._sessions.items() if v is session]:
            del self._sessions[k]

    def _drop_session(self, session) -> None:
        with self._store_lock:
            self._drop_session_locked(session)

    def _device_bytes_by_kind(self) -> dict:
        """Samples for the scheduler_device_bytes gauge (round 12):
        distinct device-resident sessions' array bytes (a session
        registered under two keys counts once) and the registered byte
        stores' retained payload. Only the REFERENCE snapshot happens
        under _store_lock — nbytes() walks O(records) per store, and a
        scrape must not stall the Assign registration path behind that
        walk. Array nbytes is metadata, no D2H."""
        with self._store_lock:
            distinct = []
            for s in self._sessions.values():
                if s not in distinct:
                    distinct.append(s)
            stores = list(self._stores.values())
        store_bytes = sum(st.nbytes() for st in stores)
        dev_bytes = 0
        for s in distinct:
            try:
                dev_bytes += int(s.device.full_bytes)
            except Exception:  # noqa: BLE001 — a scrape must not abort
                continue
        return {("session_arrays",): dev_bytes,
                ("byte_stores",): store_bytes}

    # -- failure-domain helpers (round 8) -----------------------------------

    @staticmethod
    def _replay_key(request) -> "tuple[str, int] | None":
        if not request.HasField("delta"):
            return None
        d = request.delta
        if not d.lineage_id or not d.seq:
            return None
        return (d.lineage_id, int(d.seq))

    def _replay_lookup(self, rpc: str, request):
        """Cached response for a retried (lineage_id, seq), or None."""
        key = self._replay_key(request)
        if key is None:
            return None
        lineage, seq = key
        with self._replay_lock:
            per = self._replay.get(lineage)
            if per is None:
                return None
            resp = per.get((seq, rpc))
            if resp is not None:
                self.replayed_requests += 1
            return resp

    def _replay_record(self, rpc: str, request, resp) -> None:
        key = self._replay_key(request)
        if key is None or resp.ByteSize() > REPLAY_MAX_BYTES:
            return
        lineage, seq = key
        with self._replay_lock:
            per = self._replay.pop(lineage, None)
            if per is None:
                per = {}
            per[(seq, rpc)] = resp
            while len(per) > REPLAY_PER_LINEAGE:
                per.pop(next(iter(per)))
            self._replay[lineage] = per           # LRU refresh
            while len(self._replay) > STORE_CAP:
                self._replay.pop(next(iter(self._replay)))

    def _stage_done(self, stage: str, t0: float) -> None:
        """A stage that ended NOW and started at perf_counter t0: one
        retroactive trace span + the per-stage histogram observation —
        for stages whose start can't be wrapped (gate wait)."""
        dur = time.perf_counter() - t0
        self._trace.record(stage, dur_s=dur, cat="server")
        self.metrics.observe_stage(stage, dur)

    def _join_guarded(self, pending, what: str):
        """Join a device result under the per-dispatch watchdog. A
        timeout converts the hung solve into DEADLINE_EXCEEDED for THIS
        caller, demotes the ladder, and abandons the wedged fetch
        worker so later dispatches get a live one (throttled: N callers
        waiting on the same wedged worker trigger ONE restart)."""
        t0 = time.perf_counter()
        try:
            with self._trace.span("fetch.join", cat="server", what=what):
                res = pending.result(timeout=self.watchdog_s)
            self.metrics.observe_stage("fetch.join",
                                       time.perf_counter() - t0)
            return res
        except _FutTimeout:
            # The hung join IS the long tail the log-scale buckets exist
            # for — it must land in the stage histogram, not only in the
            # trip counter (the success path above can't record it).
            self.metrics.observe_stage("fetch.join",
                                       time.perf_counter() - t0)
            now = time.monotonic()
            with self._watchdog_lock:
                self.watchdog_trips += 1
                restart = now - self._last_worker_restart > 1.0
                if restart:
                    self._last_worker_restart = now
            if restart:
                # One ladder demerit + one worker swap per hang event:
                # N coalesced callers timing out on the SAME wedged
                # dispatch are one device failure, not N — and ONE
                # flight-recorder dump carries the causal trace of the
                # hang (the spans that led to the wedged dispatch).
                self.flight.record("watchdog_trip", self._trace,
                                   what=what, watchdog_s=self.watchdog_s)
                self._device_failure()
                self._engine.restart_fetch_worker()
            raise _Abort(
                grpc.StatusCode.DEADLINE_EXCEEDED,
                f"{what} result did not land within the "
                f"{self.watchdog_s:.1f}s dispatch watchdog; fetch worker "
                "restarted and the device fast path demoted — the server "
                "keeps serving, re-submit as a new cycle",
            )

    def _device_failure(self, demote_from_delta: bool = True) -> None:
        """Ladder bookkeeping for a device-path failure; on demotion
        out of 'delta', drop resident sessions (their device arrays are
        the state under suspicion, and the memory buys nothing while
        quarantined). Every demotion snapshots the trace ring: the
        operator gets the spans that spent the ladder's patience, not
        just a counter bump."""
        demoted = self._ladder.record_failure()
        if demoted and demote_from_delta:
            with self._store_lock:
                self._sessions.clear()
        if demoted:
            self.flight.record("ladder_demotion", self._trace,
                               level=self._ladder.snapshot()["level"])

    def _resolve_decoded(self, request):
        """Full-or-delta request -> (snap, meta, snapshot_id,
        decode_seconds, device_stats|None, device_session|None) with
        the decoded arrays ready for dispatch; the trailing session is
        non-None exactly when the delta applied through a live
        DeviceSession (the warm-solve routing hook, round 17).

        Delta requests against a lineage with a live DeviceSession skip
        the recompose + full decode + full H2D entirely: the delta
        applies as on-device scatter updates (O(churn) host work) and
        `device_stats` reports what was shipped. The byte store is
        still advanced and registered either way — it is the fallback
        truth for forks, session eviction, and seeding.

        Unknown/expired base_id raises _Abort(FAILED_PRECONDITION) so
        the client falls back to a full snapshot (DeltaSession does).
        Snapshots whose records lack unique non-empty names are served
        but not registered (empty snapshot_id): name-keyed stores would
        collapse them (DeltaSession refuses to delta against those too).

        Degradation (round 8): at the 'rebuild' rung device sessions
        are skipped entirely (every delta recomposes + re-decodes); at
        'stateless' deltas are refused with FAILED_PRECONDITION and
        full sends are served WITHOUT registering a store (empty
        snapshot_id), so clients settle into full-send-per-cycle
        instead of ping-ponging delta attempts off a refusing server.
        """
        self._faults.fire("server.decode")
        level = self._ladder.level()
        if request.HasField("delta"):
            if level == "stateless":
                raise _Abort(
                    grpc.StatusCode.FAILED_PRECONDITION,
                    "sidecar degraded to stateless serving "
                    "(degradation ladder); resend a full snapshot",
                )
            base_id = request.delta.base_id
            if not base_id:
                # Falling through would silently solve the empty default
                # snapshot; a delta without a base cannot be resolved.
                raise _Abort(
                    grpc.StatusCode.INVALID_ARGUMENT,
                    "delta request carries no base_id",
                )
            self._check_delta_upserts(request.delta)
            with self._store_lock:
                base = self._stores.get(base_id)
                if base is not None:
                    # True-LRU refresh: a hit keeps the base alive while
                    # unrelated sessions churn the cap.
                    self._stores.pop(base_id)
                    self._stores[base_id] = base
            if base is None:
                raise _Abort(
                    grpc.StatusCode.FAILED_PRECONDITION,
                    f"unknown snapshot base_id {base_id!r}",
                )
            store = base.copy()
            store.apply_delta(request.delta)
            # Replication op (round 11): ship the delta verbatim; the
            # standby re-applies it against its own copy of base_id and
            # registers the result under this very sid.
            sid = self._register_store(
                store, "delta", request.delta.SerializeToString(),
                base_id=base_id,
            )
            t0 = time.perf_counter()
            seeding = False
            session = None
            with self._store_lock:
                # The 'rebuild' rung quarantines the device-resident
                # path: no lookups, no seeding — pure decode serving.
                if level == "delta":
                    session = self._sessions.get(base_id)
                    if (session is None and self._session_cap > 0
                            and base_id not in self._seeding):
                        self._seeding.add(base_id)
                        seeding = True
            if seeding:
                # Lazy seed on the FIRST delta of a lineage, from the
                # BASE store (so the pin matches what pipelined clients
                # keep diffing against): one O(cluster) record
                # conversion + build + upload buys O(churn) host work
                # for every later delta. Full-send-only clients never
                # pay this; a concurrent second first-delta skips the
                # duplicate build (_seeding guard) and decodes.
                try:
                    with self._trace.span("session.seed", cat="server",
                                          base_id=base_id):
                        session = DeviceSession.from_base_store(
                            base, base_id, self.config, self.buckets,
                            mesh=self._mesh,
                        )
                        session.device.tracer = self._trace
                    self.session_seeds += 1
                except Exception:
                    logging.getLogger("tpusched.rpc.server").warning(
                        "device session seed failed; serving via the "
                        "decode path:\n%s", traceback.format_exc(limit=3),
                    )
                    self._device_failure()
                finally:
                    with self._store_lock:
                        self._seeding.discard(base_id)
            if session is not None:
                try:
                    shot = self._faults.fire("server.session")
                except FaultError:
                    # Injected apply-path failure: same handling as a
                    # real session exception — drop the lineage, demote
                    # the ladder, heal through decode.
                    self._drop_session(session)
                    self._device_failure()
                    session = None
                else:
                    if shot == "drop":
                        # Injected eviction (chaos: DeviceSession LRU
                        # pressure / store-cap fork): forget the
                        # lineage; this request and the lineage's next
                        # delta heal through decode + re-seed — no
                        # ladder demerit, eviction is a normal event.
                        self._drop_session(session)
                        session = None
            if session is not None:
                try:
                    with session.lock:
                        t_a = time.perf_counter()
                        with self._trace.span("delta.apply",
                                              cat="server") as sp:
                            stats = session.apply_delta(  # tpl: disable=TPL102(the apply IS the critical section: a concurrent apply moving the lineage past this request's base must fork, not interleave, and the H2D scatter is the apply itself)
                                base_id, request.delta, sid)
                            sp.attrs.update(h2d_bytes=stats.h2d_bytes,
                                            path=stats.path)
                        apply_s = time.perf_counter() - t_a
                        snap, meta = session.device.snap, session.device.meta
                except KeyError:
                    # Expected fork: the lineage moved past this base
                    # while we waited. Serve via decode; the session is
                    # untouched. (Counted below as a miss, not a hit.)
                    pass
                except Exception:
                    # Heal through the decode path; the session may be
                    # inconsistent, so drop it (loud, like the native-
                    # decoder fallback: silent means a permanent
                    # O(cluster) regression).
                    logging.getLogger("tpusched.rpc.server").warning(
                        "device session apply failed; dropping the "
                        "lineage and re-decoding:\n%s",
                        traceback.format_exc(limit=3),
                    )
                    self._drop_session(session)
                    # Ladder bookkeeping: repeated apply failures
                    # quarantine the whole device-resident path.
                    self._device_failure()
                else:
                    self._session_put(session)
                    self.metrics.observe_stage("delta.apply", apply_s)
                    self.metrics.h2d.labels(stats.path).observe(
                        stats.h2d_bytes)
                    if not seeding:
                        # Counted on SUCCESS only, so a fork's KeyError
                        # (hit-then-decode) is one miss, not hit+miss —
                        # hits + seeds + misses == delta requests.
                        self.session_hits += 1
                    return (snap, meta, sid, time.perf_counter() - t0, stats,
                            session)
            self.session_misses += 1
            # Bytes composition straight into the (native) decoder: no
            # Python ClusterSnapshot is materialized on the delta path.
            with self._trace.span("store.compose", cat="server"):
                raw = store.compose_bytes()
            snap, meta, decode_s = self._decode(raw)
            return snap, meta, sid, decode_s, None, None
        msg = request.snapshot
        if not delta_safe(msg) or level == "stateless":
            snap, meta, decode_s = self._decode(msg)
            return snap, meta, "", decode_s, None, None
        store = SnapshotStore()
        # One serialize pass per record at full-send time so every
        # later delta cycle serializes only its churn (apply_delta) and
        # composes by concatenation.
        store.set_full_bytes(msg)
        sid = self._register_store(store, "full", msg.SerializeToString())
        snap, meta, decode_s = self._decode(msg)
        return snap, meta, sid, decode_s, None, None

    def _decode(self, snapshot_msg):
        t0 = time.perf_counter()
        with self._trace.span("decode", cat="server") as sp:
            snap, meta = decode_snapshot(
                snapshot_msg, self.config, self.buckets
            )
            sp.attrs.update(pods=meta.n_pods, nodes=meta.n_nodes)
        decode_s = time.perf_counter() - t0
        self.metrics.observe_stage("decode", decode_s)
        return snap, meta, decode_s

    def close(self) -> None:
        """Release serving resources: refuse queued dispatches, drain
        the engine's fetch worker (in-flight results complete), drop
        device-resident sessions and the replay cache. Idempotent and
        safe to race with in-flight handlers or a concurrent close
        (every step below is itself re-entrant); call after
        server.stop()."""
        with self._store_lock:
            already = self._closed
            self._closed = True
        # Stop a still-running boot prewarm FIRST (before the engine's
        # fetch worker drains — prewarm dispatches through it): it
        # abandons remaining classes after its in-flight compile. The
        # bounded join keeps close() from hanging on a pathological
        # compile; the thread is a daemon either way.
        self._prewarm_stop.set()
        t = self._prewarm_thread
        if t is not None:
            t.join(timeout=60.0)
        self._gate.close()
        self._engine.close(wait=True)
        self.ledger.close()  # releases the JSONL black box, if any
        self.wire.close()
        with self._store_lock:
            self._sessions.clear()
        if not already:
            with self._replay_lock:
                self._replay.clear()

    def _log_batch(self, rpc: str, meta, decode_s: float, solve_s: float,
                   placed: int, evicted: int, rounds: int,
                   dstats=None, fused: int = 0):
        rec = dict(
            ts=time.time(), rpc=rpc, pods=meta.n_pods, nodes=meta.n_nodes,
            running=meta.n_running, buckets=[meta.buckets.pods, meta.buckets.nodes],
            decode_s=round(decode_s, 6), solve_s=round(solve_s, 6),
            placed=placed, evicted=evicted, rounds=rounds,
            placements_per_sec=round(placed / solve_s, 1) if solve_s > 0 else 0,
        )
        if dstats is not None:
            rec["device_path"] = dstats.path
            rec["h2d_bytes"] = dstats.h2d_bytes
            if dstats.reason:
                rec["device_rebuild_reason"] = dstats.reason
        if fused:
            rec["fused"] = fused
        print(json.dumps(rec), file=self._log, flush=True)

    # -- rpc methods --------------------------------------------------------

    @staticmethod
    def _peer(context) -> str:
        """Gate client identity; in-process callers (tests invoking
        handlers directly) have no grpc context."""
        return context.peer() if context is not None else "in-process"

    @staticmethod
    def _score_key(request: pb.ScoreRequest):
        """Coalescing identity of a ScoreBatch DELTA request: same base
        + byte-identical delta = identical post-delta cluster state.
        Full sends never coalesce (hashing the whole snapshot would
        cost more than it saves), and the form kind separates top-k
        fusions (k merged) from full-matrix fusions (exact dedupe).
        lineage_id/seq are retry bookkeeping, NOT cluster state — they
        are scrubbed before hashing so identical deltas from distinct
        client lineages still fuse."""
        if not request.HasField("delta"):
            return None
        kind = ("topk" if request.top_k > 0
                else f"full-packed{int(bool(request.packed_ok))}")
        d = request.delta
        if d.lineage_id or d.seq:
            scrub = pb.SnapshotDelta()
            scrub.CopyFrom(d)
            scrub.lineage_id = ""
            scrub.seq = 0
            d = scrub
        digest = hashlib.sha1(d.SerializeToString()).hexdigest()
        return (request.delta.base_id, digest, kind)

    @staticmethod
    def _abort(context, code, details):
        """context.abort, or the raw status as an exception for
        in-process callers (context=None — see _peer)."""
        if context is None:
            raise _Abort(code, details)
        context.abort(code, details)

    def _serve(self, rpc: str, request, context, inner):
        """Shared outermost handler path: one trace root span per
        request (rooted at the wire request_id/parent_span; absent id
        => server-minted), replay dedupe, outcome counting by final
        status code, taxonomy conversion, and the flight-recorder
        resync-storm trigger. Aborts raise THROUGH the span, which
        records the error attr on the way out."""
        rid = request.request_id or self._trace.new_trace_id()
        with self._trace.request(rid, int(request.parent_span),
                                 name=f"server.{rpc}", cat="server",
                                 peer=self._peer(context)) as root:
            replay = self._replay_lookup(rpc, request)
            if replay is not None:
                root.attrs["replayed"] = True
                self.metrics.count_request(rpc, "OK")
                self._count_wire_bytes(rpc, request, replay)
                return replay
            try:
                # A serving request reaching a standby IS the failover
                # signal: promote (or refuse — split-brain guard site).
                self._maybe_takeover(rpc)
                resp = inner(request, context)
                # Chaos site for the reply path (round 19): a delay
                # here stalls the response AFTER every server stage
                # completed — the injected wire stall the wire
                # sentinel must attribute to "transfer".
                self._faults.fire("server.reply")
            except _Abort as e:
                self._count_abort(rpc, e.code, root)
                self._abort(context, e.code, e.details)
            except _Overloaded as e:
                self.metrics.overloaded.labels(rpc).inc()
                self._count_abort(rpc, grpc.StatusCode.RESOURCE_EXHAUSTED,
                                  root)
                self._abort(context, grpc.StatusCode.RESOURCE_EXHAUSTED,
                            str(e))
            except Exception as e:  # taxonomy: fatal (a bug, not a retry)
                self._log_internal(rpc, e)
                self._count_abort(rpc, grpc.StatusCode.INTERNAL, root)
                self._abort(context, grpc.StatusCode.INTERNAL,
                            f"unexpected server error: "
                            f"{type(e).__name__}: {e}")
            else:
                self.metrics.count_request(rpc, "OK")
                self._replay_record(rpc, request, resp)
                self._record_ladder_success(request)
                self._count_wire_bytes(rpc, request, resp)
                return resp

    def _count_wire_bytes(self, rpc: str, request, resp) -> None:
        """Per-direction byte accounting at the serving boundary
        (round 19, ISSUE 19): serialized request bytes up, serialized
        reply bytes down, plus the reply-size histogram. ByteSize() is
        the serialized length protobuf already computed (cached) for
        the transport — no second serialization."""
        down = resp.ByteSize()
        self.metrics.wire_bytes.labels("up", rpc).inc(request.ByteSize())
        self.metrics.wire_bytes.labels("down", rpc).inc(down)
        self.metrics.reply_bytes.labels(rpc).observe(down)

    def _count_abort(self, rpc: str, code, root) -> None:
        name = getattr(code, "name", str(code))
        self.metrics.count_request(rpc, name)
        root.attrs["code"] = name
        if code == grpc.StatusCode.FAILED_PRECONDITION:
            self.metrics.resyncs.labels(rpc).inc()
            if self._resync_storm.hit():
                # A resync STORM (every client re-pinning at once —
                # restart fallout, ladder stateless, LRU thrash) gets a
                # causal dump, not just per-request errors.
                self.flight.record(
                    "resync_storm", self._trace, rpc=rpc,
                    n=self._resync_storm.n,
                    window_s=self._resync_storm.window_s,
                )

    def ScoreBatch(self, request: pb.ScoreRequest, context) -> pb.ScoreResponse:
        # maybe_profile: a no-op unless the PREVIOUS cycle's wire
        # anomaly armed a one-shot jax.profiler device-trace capture
        # (WireLedger docstring) — two attribute reads when unarmed.
        with self.wire.maybe_profile():
            return self._serve("ScoreBatch", request, context,
                               self._score_batch)

    def _score_batch(self, request: pb.ScoreRequest, context) -> pb.ScoreResponse:
        key = self._score_key(request)
        fusion = None
        if key is not None:
            fusion, leader = self._coalescer.join(key, int(request.top_k))
            if not leader:
                # A leader is already resolving this exact state: wait
                # for its dispatch and slice our own k from the shared
                # result — no decode, no dispatch, no extra fetch.
                with self._trace.span("coalesce.wait", cat="server"):
                    payload = fusion.wait(timeout=600.0)
                resp, solve_s = self._score_response(payload, request)
                self.metrics.observe(payload["P"], 0, 0, solve_s,
                                     rpc="ScoreBatch")
                return resp
        try:
            payload = self._score_dispatch(request, context, fusion)
        except BaseException as e:
            if fusion is not None:
                # Followers must see the SAME status class the leader
                # got — an _Overloaded leader means the whole fusion was
                # refused admission (retryable), not a server bug.
                if isinstance(e, _Abort):
                    code = e.code
                elif isinstance(e, _Overloaded):
                    code = grpc.StatusCode.RESOURCE_EXHAUSTED
                else:
                    code = grpc.StatusCode.INTERNAL
                fusion.fail(code, str(e))
                self._coalescer.finish(fusion)
            raise
        if fusion is not None:
            fusion.publish(payload)
            self._coalescer.finish(fusion)
            self.metrics.fuse.observe(len(fusion._ks))
        resp, solve_s = self._score_response(payload, request)
        self._log_batch(
            "ScoreBatch", payload["meta"], payload["decode_s"], solve_s,
            0, 0, 0, dstats=payload["dstats"],
            fused=(len(fusion._ks) - 1) if fusion is not None else 0,
        )
        self.metrics.observe(payload["P"], 0, 0, payload["decode_s"] + solve_s,
                             rpc="ScoreBatch")
        return resp

    def _score_dispatch(self, request, context, fusion) -> dict:
        """Leader path: resolve + decode outside the dispatch slot,
        dispatch the requested form once (k = fused max for top-k),
        return the shared payload followers slice from."""
        snap, meta, sid, decode_s, dstats, session = \
            self._resolve_decoded(request)
        P, N = meta.n_pods, meta.n_nodes
        pending_topk = pending_full = None
        k_used = 0
        t_q = time.perf_counter()
        with self._gate.slot(self._peer(context)):
            self._stage_done("gate.wait", t_q)
            # Seal INSIDE the slot: every request that joined while this
            # one queued rides the same dispatch.
            k_fused = fusion.seal() if fusion is not None \
                else int(request.top_k)
            with self._trace.span("dispatch", cat="server",
                                  fused=len(fusion._ks) if fusion else 1):
                if request.top_k > 0:
                    # O(P) response: top-k computed on device, [P,N]
                    # never fetched. A drained cluster (N == 0) has
                    # nothing to rank: k stays 0 with no rows, which
                    # the client decodes as [P, 0] arrays.
                    if N > 0:
                        k_used = min(max(k_fused, 1), N)
                        pending_topk = self._engine.score_topk_async(
                            snap, k_used)
                else:
                    pending_full = self._engine.score_async(snap)
        return dict(sid=sid, meta=meta, P=P, N=N, decode_s=decode_s,
                    dstats=dstats, k_used=k_used,
                    pending_topk=pending_topk, pending_full=pending_full)

    def _score_response(self, payload: dict, request) -> tuple[pb.ScoreResponse, float]:
        """Build ONE caller's response from the (possibly shared)
        payload: name tables now — they ride inside the device window —
        then join the fetch (watchdog-guarded) and pack this caller's
        k columns."""
        meta = payload["meta"]
        P, N = payload["P"], payload["N"]
        resp = pb.ScoreResponse(snapshot_id=payload["sid"])
        with self._trace.span("reply.names", cat="server"):
            resp.pod_names.extend(meta.pod_names)
            resp.node_names.extend(meta.node_names)
        solve_s = 0.0
        t_p = None
        if payload["pending_topk"] is not None:
            idx, val, solve_s = self._join_guarded(
                payload["pending_topk"], "ScoreBatch top-k"
            )
            t_p = time.perf_counter()
            with self._trace.span("reply.pack", cat="server"):
                # lax.top_k is prefix-stable: columns [:k_own] of the
                # fused top-k_used equal a direct top-k_own dispatch, so
                # sliced responses are byte-identical to unfused serving.
                k_own = min(int(request.top_k), N)
                resp.k = k_own
                resp.topk_idx_packed = np.ascontiguousarray(
                    idx[:P, :k_own], dtype="<i4"
                ).tobytes()
                resp.topk_score_packed = np.ascontiguousarray(
                    val[:P, :k_own], dtype="<f4"
                ).tobytes()
        elif payload["pending_full"] is not None:
            res = self._join_guarded(payload["pending_full"],
                                     "ScoreBatch full")
            solve_s = res.solve_seconds
            t_p = time.perf_counter()
            with self._trace.span("reply.pack", cat="server"):
                if request.packed_ok and P * N >= PACK_CELLS:
                    resp.feasible_packed = np.ascontiguousarray(
                        res.feasible[:P, :N], dtype=np.uint8
                    ).tobytes()
                    resp.scores_packed = np.ascontiguousarray(
                        res.scores[:P, :N], dtype="<f4"
                    ).tobytes()
                else:
                    for i in range(P):
                        row = resp.rows.add()
                        row.feasible.extend(res.feasible[i, :N].tolist())
                        row.scores.extend(res.scores[i, :N].tolist())
        if t_p is not None:
            self.metrics.observe_stage("reply.pack",
                                       time.perf_counter() - t_p)
        return resp, solve_s

    def Assign(self, request: pb.AssignRequest, context) -> pb.AssignResponse:
        # See ScoreBatch: one-shot armed device-trace capture site.
        with self.wire.maybe_profile():
            return self._serve("Assign", request, context, self._assign)

    def _record_ladder_success(self, request) -> None:
        """Probe discipline: a success arms/confirms recovery only when
        it exercised the CURRENT rung's serving path. Delta requests do
        (device sessions at 'delta', store+decode at 'rebuild'); full
        sends are rubber stamps at those rungs and must not clear a
        probation the probe never tested — but at 'stateless' full
        sends ARE the serving path (deltas are refused), so they count
        there, or the ladder could never climb back."""
        if request.HasField("delta") or self._ladder.level() == "stateless":
            self._ladder.record_success()

    @staticmethod
    def _log_internal(rpc: str, exc: BaseException) -> None:
        logging.getLogger("tpusched.rpc.server").error(
            "%s failed unexpectedly (INTERNAL):\n%s",
            rpc, traceback.format_exc(limit=5),
        )

    def _assign(self, request: pb.AssignRequest, context) -> pb.AssignResponse:
        # Flight-ledger context (round 18, ISSUE 13): compile counters
        # BEFORE any decode/dispatch so the record attributes exactly
        # the retraces this request paid; churn is the delta's own
        # record count (0 for full sends — a full send is a reload,
        # not churn).
        comp0 = (ledgering.COMPILES.counters()
                 if self.ledger.enabled else (0, 0.0))
        churn = 0
        if request.HasField("delta"):
            d = request.delta
            churn = (len(d.upsert_nodes) + len(d.remove_nodes)
                     + len(d.upsert_pods) + len(d.remove_pods)
                     + len(d.upsert_running) + len(d.remove_running))
        snap, meta, sid, decode_s, dstats, session = \
            self._resolve_decoded(request)
        # Staged handling (round 6): decode runs OUTSIDE the dispatch
        # slot (so a concurrent request's decode overlaps this solve),
        # the slot is held only long enough to enqueue the program, and
        # the response's name tables build while the engine's worker
        # drives the device and fetches the packed buffer. The gate
        # (round 7) additionally keeps concurrent clients' dispatches
        # round-robin fair instead of lock-race ordered.
        explain_on = self.explain.enabled
        pending_probe = None
        warm_path = "cold"
        t_q = time.perf_counter()
        with self._gate.slot(self._peer(context)):
            self._stage_done("gate.wait", t_q)
            with self._trace.span("dispatch", cat="server",
                                  explained=explain_on):
                pending = None
                if explain_on:
                    # Explained cycle (round 12): the solve carries the
                    # provenance extras and a second program decomposes
                    # scores/filters — both fetch on the ordered worker.
                    pending, pending_probe = (
                        self._engine.solve_explained_async(
                            snap, self._explain_k))
                elif self._warm is not None and session is not None:
                    # Warm routing (round 17, ISSUE 12): the delta
                    # already applied on this lineage's DeviceSnapshot,
                    # so the carried tableau (and, incrementally, the
                    # assignment carry) is one dirty-row refresh away.
                    # Under session.lock: dispatch must see the exact
                    # state this request's delta produced — a
                    # concurrent apply having moved the lineage past it
                    # falls back to the plain solve of OUR decoded
                    # arrays (same heal as a fork).
                    try:
                        with session.lock:
                            if session.device.snap is snap:
                                dev = session.device
                                before = (dev.warm_solves,
                                          dev.incremental_solves)
                                pending = self._engine.solve_warm_async(
                                    dev,
                                    incremental=(
                                        self._warm == "incremental"),
                                )
                                if dev.warm_solves > before[0]:
                                    warm_path = "bitwise"
                                elif dev.incremental_solves > before[1]:
                                    warm_path = "incremental"
                    except Exception:
                        # The warm path is an optimization: any failure
                        # heals through the plain solve (loud — silent
                        # means a permanent round-count regression).
                        logging.getLogger("tpusched.rpc.server").warning(
                            "warm solve dispatch failed; serving via "
                            "the plain solve:\n%s",
                            traceback.format_exc(limit=3),
                        )
                        pending = None
                        warm_path = "cold"
                if pending is None and not explain_on:
                    pending = self._engine.solve_async(snap)
        resp = pb.AssignResponse(snapshot_id=sid)
        P = meta.n_pods
        if request.packed_ok:
            # Name tables now, result arrays after the join: the two
            # string extends are the response's CPU-heavy part at 10k
            # pods and ride inside the device window for free.
            with self._trace.span("reply.names", cat="server"):
                resp.pod_names.extend(meta.pod_names)
                # Indices resolve against the DECODER's canonical
                # (sorted) node order, not the request's wire order —
                # ship the table.
                resp.node_names.extend(meta.node_names)
        exd = None
        try:
            if explain_on:
                res, exd = self._join_guarded(pending, "Assign solve")
            else:
                res = self._join_guarded(pending, "Assign solve")
        except BaseException:
            if warm_path != "cold" and session is not None:
                # The conservative reset the warm contract demands: a
                # dispatch whose FETCH failed may have committed a
                # tableau/carry the device never validated — drop them
                # so the lineage's next solve re-anchors cold instead
                # of repeating a poisoned warm state every request.
                session.device.invalidate_warm("fetch_error")
            raise
        t_p = time.perf_counter()
        with self._trace.span("reply.pack", cat="server"):
            ni = np.asarray(res.assignment[:P], dtype=np.int32)
            sc = np.asarray(res.chosen_score[:P], dtype=np.float32).copy()
            sc[~np.isfinite(sc)] = 0.0  # -inf (unplaced/preempted) -> 0
            ck = np.asarray(res.commit_key[:P], dtype=np.int32)
            placed = int((ni >= 0).sum())
            if request.packed_ok:
                # Parallel-array form: three tobytes() instead of P
                # Python message constructions (~30 ms saved at 10k).
                resp.node_idx_packed = ni.astype("<i4").tobytes()
                resp.score_packed = sc.astype("<f4").tobytes()
                resp.commit_key_packed = ck.astype("<i4").tobytes()
            else:
                for i, name in enumerate(meta.pod_names):
                    a = resp.assignments.add()
                    a.pod = name
                    n = int(ni[i])
                    if n >= 0:
                        a.node = meta.node_names[n]
                        a.score = float(sc[i])
                    a.commit_key = int(ck[i])
        self.metrics.observe_stage("reply.pack", time.perf_counter() - t_p)
        n_evicted = 0
        if res.evicted is not None and res.evicted.any():
            running_names = getattr(meta, "running_names", None) or []
            for m in np.argwhere(res.evicted).ravel():
                if m < len(running_names):
                    resp.evicted.append(running_names[m])
                    n_evicted += 1
        if self._audit is not None:
            ts = time.time()
            lines = []
            for i, name in enumerate(meta.pod_names):
                n = int(ni[i])
                lines.append(json.dumps(dict(
                    ts=ts, kind="placement", pod=name,
                    node=meta.node_names[n] if n >= 0 else None,
                    score=round(float(sc[i]), 4),
                    commit_key=int(ck[i]), snapshot_id=sid,
                )))
            for name in resp.evicted:
                lines.append(json.dumps(dict(
                    ts=ts, kind="eviction", pod=name, snapshot_id=sid,
                )))
            # One write per batch under a lock: concurrent handlers must
            # not interleave partial lines into the audit log.
            if lines:
                with self._audit_lock:
                    self._audit.write("\n".join(lines) + "\n")
                    self._audit.flush()
        if explain_on:
            # BEST-EFFORT: the reply is already complete — a failed or
            # wedged provenance probe must not fail a served placement
            # (no _join_guarded here: a trip would also demote the
            # ladder and abandon the fetch worker for an observability-
            # only program). The plain result(timeout=) converts a hang
            # into a skipped record instead.
            try:
                probe = pending_probe.result(timeout=self.watchdog_s)
            except Exception:  # noqa: BLE001 — observability best-effort
                logging.getLogger("tpusched.rpc.server").warning(
                    "explain probe failed; skipping the decision "
                    "record:\n%s", traceback.format_exc(limit=3),
                )
                probe = None
            if probe is not None:
                ctx = self._trace.current()
                rec = explaining.build_record(
                    self.config, meta, res, exd, probe,
                    rid=ctx[0] if ctx else "", snapshot_id=sid,
                    rpc="Assign",
                )
                cyc = self.explain.record(rec)
                # One "decision" event span under the request root: the
                # Perfetto export's args then link the slow cycle to its
                # DecisionRecord by cycle id (tools/tracez.py satellite).
                self._trace.record("decision", cat="explain",
                                   decision=cyc, pods=meta.n_pods,
                                   evictions=n_evicted)
                for oc, n in explaining.outcome_counts(rec).items():
                    if n:
                        self.metrics.decisions.labels(oc).inc(n)
                for reason, n in explaining.pending_reasons(rec).items():
                    if n:
                        self.metrics.pending_reasons.labels(reason).inc(n)
        resp.rounds = res.rounds
        resp.solve_seconds = res.solve_seconds
        self._log_batch("Assign", meta, decode_s, res.solve_seconds,
                        placed, n_evicted, res.rounds, dstats=dstats)
        self.metrics.observe(meta.n_pods, placed, n_evicted,
                             decode_s + res.solve_seconds)
        self.metrics.solve_rounds.observe(res.rounds)
        self.metrics.warm_solves.labels(warm_path).inc()
        # One flight-ledger record per served Assign (round 18, ISSUE
        # 13): stage walls joined from this request's completed spans
        # (same names a trace shows — decode, delta.apply, dispatch,
        # fetch.join, reply.*), falling back to the directly measured
        # walls when tracing is off. The sentinel inside observe()
        # flags p99 spikes and attributes them from the record itself.
        if self.ledger.enabled:
            c1, s1 = ledgering.COMPILES.counters()
            ctx = self._trace.current()
            stages = self._trace.durations(ctx[0]) if ctx else {}
            if not stages:
                stages = {"decode": decode_s,
                          "fetch.join": res.solve_seconds}
            frontier = 0
            if res.inc_info:
                frontier = int(res.inc_info.get("frontier", 0))
            self.ledger.observe(ledgering.CycleRecord(
                ts=time.time(), source="sidecar", pods=meta.n_pods,
                nodes=meta.n_nodes, running=meta.n_running,
                placed=placed, evicted=n_evicted, churn=churn,
                frontier=frontier, rounds=int(res.rounds),
                # The ledger schema's canonical spelling is "warm"
                # (cold|warm|incremental); the warm-solves counter
                # keeps its historical "bitwise" label.
                warm_path=("warm" if warm_path == "bitwise"
                           else warm_path),
                solve_s=res.solve_seconds,
                stages=stages, compiles=c1 - comp0[0],
                compile_s=round(s1 - comp0[1], 6),
            ))
        return resp

    def Health(self, request: pb.HealthRequest, context) -> pb.HealthResponse:
        """Liveness + the failure-domain surface a sidecar watchdog
        (liveness probe, chaos harness, operator) reads: which ladder
        rung is serving, the trip/demotion/recovery/replay counters,
        and (round 11) the replication role / lag / takeover count."""
        lad = self._ladder.snapshot()
        return pb.HealthResponse(
            ok=True, backend=jax.default_backend(),
            devices=len(jax.devices()),
            serving_path=lad["level"],
            watchdog_trips=self.watchdog_trips,
            ladder_demotions=lad["demotions"],
            ladder_recoveries=lad["recoveries"],
            replayed_requests=self.replayed_requests,
            role=self.role,
            replication_lag_seq=self.replication_lag,
            takeovers=self.takeovers,
            prewarm_complete=self.prewarm_complete,
        )

    def Replicate(self, request: pb.ReplicateRequest,
                  context) -> pb.ReplicateResponse:
        """Serve the op log to a follower (round 11). A from_seq that
        predates retention gets resync=true + ONE full-rebase op built
        from the newest registered store (the follower drops its state
        and resumes from end_seq + 1); a caught-up follower gets an
        empty ops list and the current end_seq as its lag reference."""
        ops, end, stale = self._replog.since(int(request.from_seq))
        resp = pb.ReplicateResponse(end_seq=end, resync=stale,
                                    role=self.role)
        if stale:
            with self._store_lock:
                # Newest REGISTERED store — not dict order: the delta
                # serving path's true-LRU hit-touch moves old bases to
                # the end of _stores, and a rebase op built from one of
                # those but stamped seq=end would leave the follower
                # "caught up" on stale state.
                newest = (self._last_minted
                          if self._last_minted in self._stores
                          else next(reversed(self._stores), None))  # tpl: disable=TPL007(deliberate: _last_minted was evicted, so most-recently-TOUCHED is the freshest state a follower can rebase onto)
                store = self._stores.get(newest) if newest else None
            if store is not None:
                op = resp.ops.add()
                op.seq = end
                op.kind = "full"
                op.snapshot_id = newest
                op.payload = store.compose_bytes()
        else:
            resp.ops.extend(ops)
        return resp

    def Metrics(self, request: pb.MetricsRequest, context) -> pb.MetricsResponse:
        lad = self._ladder.snapshot()
        level_idx = DegradationLadder.LEVELS.index(lad["level"])
        # Live service-state families rendered at scrape time (the
        # registry holds observation-fed metrics; these read the
        # authoritative in-memory counters directly).
        extra = [
            "# TYPE scheduler_watchdog_trips_total counter",
            f"scheduler_watchdog_trips_total {self.watchdog_trips}",
            "# TYPE scheduler_ladder_demotions_total counter",
            f"scheduler_ladder_demotions_total {lad['demotions']}",
            "# TYPE scheduler_ladder_recoveries_total counter",
            f"scheduler_ladder_recoveries_total {lad['recoveries']}",
            "# TYPE scheduler_replayed_requests_total counter",
            f"scheduler_replayed_requests_total {self.replayed_requests}",
            "# TYPE scheduler_degradation_level gauge",
            f'scheduler_degradation_level{{path="{lad["level"]}"}} '
            f"{level_idx}",
            "# TYPE scheduler_device_session_events_total counter",
            f'scheduler_device_session_events_total{{event="seed"}} '
            f"{self.session_seeds}",
            f'scheduler_device_session_events_total{{event="hit"}} '
            f"{self.session_hits}",
            f'scheduler_device_session_events_total{{event="miss"}} '
            f"{self.session_misses}",
            "# TYPE scheduler_gate_served_total counter",
            f"scheduler_gate_served_total {self._gate.served}",
            "# TYPE scheduler_gate_peak_waiting gauge",
            f"scheduler_gate_peak_waiting {self._gate.peak_waiting}",
            "# TYPE scheduler_coalesced_requests_total counter",
            f'scheduler_coalesced_requests_total{{role="leader"}} '
            f"{self._coalescer.lead_requests}",
            f'scheduler_coalesced_requests_total{{role="follower"}} '
            f"{self._coalescer.fused_requests}",
            "# TYPE scheduler_flight_dumps_total counter",
            f"scheduler_flight_dumps_total {self.flight.trips}",
            # Replication surface (round 11, ISSUE 6): role as a
            # labeled gauge (value 1 on the current role), lag in ops,
            # takeovers, and the op-log flow counters.
            "# TYPE scheduler_replica_role gauge",
            f'scheduler_replica_role{{role="{self.role}"}} 1',
            "# TYPE scheduler_replication_lag_seq gauge",
            f"scheduler_replication_lag_seq {self.replication_lag}",
            "# TYPE scheduler_replica_takeovers_total counter",
            f"scheduler_replica_takeovers_total {self.takeovers}",
            "# TYPE scheduler_replication_ops_total counter",
            f'scheduler_replication_ops_total{{op="appended"}} '
            f"{self._replog.appended}",
            f'scheduler_replication_ops_total{{op="applied"}} '
            f"{self.replication_applied}",
            f'scheduler_replication_ops_total{{op="skipped"}} '
            f"{self.replication_skipped}",
            # Shape-class prewarm surface (PR 18, ROADMAP item 3): how
            # many of the registry's classes are traced vs registered —
            # done < registry on a scrape means a half-warm standby
            # whose promotion would still pay compiles.
            "# TYPE scheduler_registry_classes gauge",
            f"scheduler_registry_classes {self.registry_classes}",
            "# TYPE scheduler_prewarmed_classes gauge",
            f"scheduler_prewarmed_classes {self.prewarm_classes_done}",
        ]
        return pb.MetricsResponse(
            prometheus_text=self.metrics.render() + "\n".join(extra) + "\n"
        )

    def Debugz(self, request: pb.DebugzRequest, context) -> pb.DebugzResponse:
        """Last-N stitched traces from the span ring (+ flight-recorder
        dumps on request), as JSON — tools/tracez.py converts to
        Chrome/Perfetto trace-event format. A debug surface: span
        records follow tpusched.trace.span_dict, not a stable API."""
        # <= 0 (absent OR a hostile negative) falls back to the default:
        # traces(last=-1) must not become an unbounded response.
        n = int(request.max_traces)
        if n <= 0:
            n = 16
        traces = {
            tid: [tracing.span_dict(s) for s in spans]
            for tid, spans in self._trace.traces(last=n).items()
        }
        flight = ""
        if request.include_flight:
            flight = json.dumps(self.flight.dumps())
        return pb.DebugzResponse(
            trace_json=json.dumps({"traces": traces}), flight_json=flight
        )

    def Statusz(self, request: pb.StatuszRequest,
                context) -> pb.StatuszResponse:
        """The cycle flight ledger (round 18, ISSUE 13): rolling
        p50/p99 per stage, warm-path mix, churn/round aggregates, the
        compile timeline, sentinel anomaly counts, and the last-N
        CycleRecords — plus this replica's identity facts so
        tools/statusz.py's fleet merge can label columns. Served on
        standbys too (observability must not promote), like Health/
        Metrics/Debugz. A debug surface: record JSON follows
        tpusched.ledger.SCHEMA, not a stable API."""
        n = int(request.max_records)
        n = 32 if n <= 0 else min(n, 256)
        payload = self.ledger.statusz(last=n)
        # Wire panel (round 19, ISSUE 19): the per-cycle round-trip
        # decomposition — component quantiles, byte totals, the clock
        # offset, coverage, and last-N WireRecords (tpusched.wire
        # SCHEMA). Raw bucket counts ride along for the fleet merge.
        payload["wire"] = self.wire.statusz(last=n)
        # Ingest panel (PR 20, ISSUE 20): front-door admission counters
        # plus live queue depth/capacity, when this server has a gate.
        if self.ingest is not None:
            payload["ingest"] = self.ingest.stats()
        lad = self._ladder.snapshot()
        payload["role"] = self.role
        payload["serving_path"] = lad["level"]
        payload["watchdog_trips"] = self.watchdog_trips
        payload["flight_dumps"] = self.flight.trips
        return pb.StatuszResponse(statusz_json=json.dumps(payload))

    def Explainz(self, request: pb.ExplainzRequest,
                 context) -> pb.ExplainzResponse:
        """Decision provenance (round 12): last-N DecisionRecords as
        JSON summaries plus targeted queries — `pod` answers "why is P
        pending / why did P land there" (full per-pod decision with the
        score-term breakdown), `victim` answers "who evicted V" (victim
        terms + evictor's decision + the auction round chain). Like
        Debugz, a debug surface: JSON follows tpusched.explain
        record_dict, not a stable API. Record summaries stay bounded
        (per-pod decisions ship only for the requested pod)."""
        col = self.explain
        n = int(request.max_records)
        n = 8 if n <= 0 else min(n, 64)
        payload: dict = dict(
            enabled=col.enabled,
            recorded=col.recorded,
            records=[
                explaining.record_dict(
                    r, include_auction=bool(request.include_auction))
                for r in col.last(n)
            ],
        )
        if request.pod:
            payload["why"] = col.why(request.pod)
        if request.victim:
            payload["who_evicted"] = col.who_evicted(request.victim)
        return pb.ExplainzResponse(explain_json=json.dumps(payload))

    def Enqueue(self, request: pb.EnqueueRequest,
                context) -> pb.EnqueueResponse:
        """The bounded front door (PR 20, ISSUE 20): offer a batch of
        pending pods to the ingest gate. A partially shed batch is a
        SUCCESS carrying the shed names + retry-after hint (the caller
        re-offers just those); a FULLY shed batch aborts
        RESOURCE_EXHAUSTED, which rpc/client.py's RETRYABLE_CODES
        already backs off and re-drives — the PR 3 retry contract is
        the load-shedding protocol. An injected ``ingest.enqueue``
        error surfaces as UNAVAILABLE (same contract). Admission is
        exactly-once across those retries: the gate dedups by name."""
        if self.ingest is None:
            self._abort(context, grpc.StatusCode.UNIMPLEMENTED,
                        "this server has no ingest gate "
                        "(make_server ingest=...)")
        submitted = float(request.submitted) or time.time()
        pods = [
            dict(name=p.name, priority=float(p.priority),
                 slo_target=float(p.slo_target), submitted=submitted)
            for p in request.pods
        ]
        try:
            res = self.ingest.offer(pods, tenant=int(request.tenant))
        except FaultError as e:
            self._abort(context, grpc.StatusCode.UNAVAILABLE,
                        f"ingest fault: {e}")
        if pods and not res["admitted"]:
            self._abort(
                context, grpc.StatusCode.RESOURCE_EXHAUSTED,
                f"ingest shed all {len(pods)} pods; retry after "
                f"{res['retry_after_s']:.3f}s")
        return pb.EnqueueResponse(
            admitted=len(res["admitted"]), shed=len(res["shed"]),
            shed_pods=res["shed"],
            queue_depth=int(res["queue_depth"]),
            retry_after_s=float(res["retry_after_s"]),
        )


def make_server(
    address: str = "127.0.0.1:0",
    config: EngineConfig | None = None,
    buckets: Buckets | None = None,
    max_workers: int = 8,
    log_stream=None,
    audit_stream=None,
    device_sessions: int = DEVICE_SESSION_CAP,
    faults=None,
    watchdog_s: float = WATCHDOG_S,
    ladder: DegradationLadder | None = None,
    tracer=None,
    flight: FlightRecorder | None = None,
    role: str = "leader",
    replication_log: "ReplicationLog | None" = None,
    explain=False,
    explain_k: int = 3,
    warm: "str | None" = None,
    ledger: "ledgering.CycleLedger | None" = None,
    ledger_jsonl: "str | None" = None,
    prewarm: bool = False,
    wire: "wiring.WireLedger | None" = None,
    wire_profile_dir: "str | None" = None,
    ingest=None,
):
    """Build (grpc.Server, bound_port, service). Unlimited message size:
    a 10k-pod snapshot exceeds the 4 MB default. max_workers default 8:
    4 concurrent clients each keeping 2 requests in flight must all get
    a decode thread — the dispatch gate, not the thread pool, is the
    serialization point. Call svc.close() after server.stop() to drain
    the engine's fetch worker and drop device-resident sessions.
    faults/watchdog_s/ladder: failure-domain knobs; tracer/flight:
    observability knobs; role/replication_log: fleet knobs
    (SchedulerService; tpusched/replicate.py ReplicaSet wires a
    standby's follower loop); explain/explain_k: decision provenance
    (round 12 — True or an ExplainCollector makes every Assign an
    explained cycle, served by the Explainz rpc); warm: warm-solve
    routing for session-backed delta Assigns (round 17, ISSUE 12 —
    None | "bitwise" | "incremental"; SchedulerService docstring);
    ledger/ledger_jsonl: the cycle flight ledger + its optional JSONL
    black box (round 18, ISSUE 13 — served by the Statusz rpc /
    tools/statusz.py); prewarm: boot-time tracing of the full
    shape-class registry (PR 18 — needs explicit buckets; the service's
    prewarm_complete / Health field 12 flips when every class is
    compiled, and ReplicaSet.wait_caught_up blocks on it);
    wire/wire_profile_dir: the wire ledger + its optional anomaly-armed
    device-trace capture directory (round 19, ISSUE 19 — clients
    constructed with wire=svc.wire feed the server's Statusz `wire`
    panel; SchedulerService docstring); ingest: the admission-
    controlled front door served by the Enqueue rpc (PR 20, ISSUE 20 —
    None leaves Enqueue UNIMPLEMENTED; an IngestGate, a dict of
    queue/gate knobs, or True builds one; SchedulerService
    docstring)."""
    svc = SchedulerService(config, buckets, log_stream=log_stream,
                           audit_stream=audit_stream,
                           device_sessions=device_sessions,
                           faults=faults, watchdog_s=watchdog_s,
                           ladder=ladder, tracer=tracer, flight=flight,
                           role=role, replication_log=replication_log,
                           explain=explain, explain_k=explain_k,
                           warm=warm, ledger=ledger,
                           ledger_jsonl=ledger_jsonl, prewarm=prewarm,
                           wire=wire, wire_profile_dir=wire_profile_dir,
                           ingest=ingest)

    def handler(fn, req_cls):
        return grpc.unary_unary_rpc_method_handler(
            fn,
            request_deserializer=req_cls.FromString,
            response_serializer=lambda m: m.SerializeToString(),
        )

    table = {
        "ScoreBatch": handler(svc.ScoreBatch, pb.ScoreRequest),
        "Assign": handler(svc.Assign, pb.AssignRequest),
        "Health": handler(svc.Health, pb.HealthRequest),
        "Metrics": handler(svc.Metrics, pb.MetricsRequest),
        "Debugz": handler(svc.Debugz, pb.DebugzRequest),
        "Replicate": handler(svc.Replicate, pb.ReplicateRequest),
        "Explainz": handler(svc.Explainz, pb.ExplainzRequest),
        "Statusz": handler(svc.Statusz, pb.StatuszRequest),
        "Enqueue": handler(svc.Enqueue, pb.EnqueueRequest),
    }
    server = grpc.server(
        futures.ThreadPoolExecutor(max_workers=max_workers),
        options=[
            ("grpc.max_receive_message_length", -1),
            ("grpc.max_send_message_length", -1),
        ],
    )
    server.add_generic_rpc_handlers(
        (grpc.method_handlers_generic_handler(SERVICE, table),)
    )
    port = server.add_insecure_port(address)
    return server, port, svc


def serve(address: str = "127.0.0.1:50051", config: EngineConfig | None = None,
          audit_path: str | None = None, watchdog_s: float = WATCHDOG_S,
          explain: bool = False, ledger_jsonl: str | None = None,
          buckets: Buckets | None = None, prewarm: bool = False):
    """Blocking entry point: python -m tpusched.rpc.server"""
    # Persistent XLA cache first (PR 18): a restarted sidecar then
    # reloads its programs instead of recompiling them — prewarm still
    # traces each class, but the trace hits the on-disk cache.
    shapeclass.enable_persistent_cache()
    audit = open(audit_path, "a") if audit_path else None
    server, port, svc = make_server(address, config, buckets=buckets,
                                    audit_stream=audit,
                                    watchdog_s=watchdog_s, explain=explain,
                                    ledger_jsonl=ledger_jsonl,
                                    prewarm=prewarm)
    server.start()
    print(f"tpusched sidecar listening on port {port}", file=sys.stderr)
    try:
        server.wait_for_termination()
    finally:
        svc.close()


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--address", default="127.0.0.1:50051")
    ap.add_argument("--config", default=None, help="EngineConfig YAML path")
    ap.add_argument("--audit", default=None,
                    help="append per-pod placement audit JSONL to this file")
    ap.add_argument("--watchdog-s", type=float, default=WATCHDOG_S,
                    help="per-dispatch result-join budget before a hung "
                         "solve is aborted as DEADLINE_EXCEEDED")
    ap.add_argument("--explain", action="store_true",
                    help="record decision provenance for every Assign "
                         "(served by the Explainz rpc / tools/explainz.py)")
    ap.add_argument("--ledger-jsonl", default=None,
                    help="append every cycle flight-ledger record to "
                         "this JSONL black box (round 18; the Statusz "
                         "rpc serves the in-memory ring either way)")
    ap.add_argument("--buckets", default=None, metavar="PODSxNODES[xRUN]",
                    help="explicit floor buckets, e.g. 256x64 or "
                         "256x64x512 (Buckets.fit) — pins compile "
                         "shapes; required by --prewarm")
    ap.add_argument("--prewarm", action="store_true",
                    help="trace the full shape-class registry at boot "
                         "(PR 18: zero request-path compiles afterward; "
                         "needs --buckets)")
    args = ap.parse_args()
    cfg = None
    if args.config:
        from tpusched.config import load_config

        cfg = load_config(args.config)
    bk = None
    if args.buckets:
        dims = [int(x) for x in args.buckets.lower().split("x")]
        if len(dims) not in (2, 3):
            ap.error("--buckets wants PODSxNODES or PODSxNODESxRUNNING")
        bk = Buckets.fit(*dims)
    serve(args.address, cfg, audit_path=args.audit,
          watchdog_s=args.watchdog_s, explain=args.explain,
          ledger_jsonl=args.ledger_jsonl, buckets=bk,
          prewarm=args.prewarm)
