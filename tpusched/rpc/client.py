"""Python client for the tpusched sidecar (SURVEY.md C12).

Mirrors what the Go `--score-backend=tpu` plugin would do: serialize the
cluster snapshot, call ScoreBatch (the Score-plugin path) or Assign (the
full batched solve), read back scores/assignments by name.

Failure-domain contract (round 8, ISSUE 3 — the client half of the
taxonomy documented in rpc/server.py):

  * every RPC carries a DEADLINE (the channel-level timeout);
  * RETRYABLE statuses (UNAVAILABLE — sidecar restarting;
    RESOURCE_EXHAUSTED — dispatch-gate admission refused) retry with
    capped exponential backoff + jitter inside the original deadline
    budget (RetryPolicy);
  * RESYNC statuses (FAILED_PRECONDITION — unknown base / degraded
    stateless mode) make DeltaSession fall back to a full send and the
    pipelines transparently re-send the doomed cycles as full
    snapshots recomposed from the pinned store (no lost responses);
  * everything else is FATAL and surfaces to the caller.

Retry-safety: every delta is stamped with (lineage_id, seq); a retry
whose first attempt was applied-but-unacked is deduped server-side and
the cached response replayed (SnapshotDelta proto comment).

Replica failover (round 11, ISSUE 6): SchedulerClient accepts an
ORDERED endpoint list; UNAVAILABLE rotates to the next replica before
the retry re-sends (both the blocking _call loop and the pipelines'
_join_entry re-issues). A warm standby answers the retried delta from
its replicated stores under the same snapshot_ids; a cold one answers
FAILED_PRECONDITION and the resync machinery above takes over — so
failover composes with, rather than replaces, the ISSUE 3 contract.
"""

from __future__ import annotations

import dataclasses
import random
import threading
import time
import uuid

import grpc
import numpy as np

from tpusched import trace as tracing
from tpusched import wire as wiring
from tpusched.rpc import codec
from tpusched.rpc import tpusched_pb2 as pb
from tpusched.rpc.server import SERVICE

# Error taxonomy (rpc/server.py module docstring is the authority).
RETRYABLE_CODES = frozenset({
    grpc.StatusCode.UNAVAILABLE,
    grpc.StatusCode.RESOURCE_EXHAUSTED,
})
RESYNC_CODES = frozenset({grpc.StatusCode.FAILED_PRECONDITION})


def classify_error(code) -> str:
    """'retryable' | 'resync' | 'fatal' for a grpc StatusCode."""
    if code in RETRYABLE_CODES:
        return "retryable"
    if code in RESYNC_CODES:
        return "resync"
    return "fatal"


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Capped exponential backoff + jitter for RETRYABLE statuses.
    Retries always stay inside the caller's original deadline budget —
    the deadline is the contract, the retries are how the budget is
    spent. jitter_frac spreads K clients retrying a restarted sidecar
    so they don't re-arrive in lockstep (the thundering-herd half of
    the kube-scheduler backoff discipline)."""

    max_attempts: int = 6
    initial_backoff_s: float = 0.05
    max_backoff_s: float = 2.0
    multiplier: float = 2.0
    jitter_frac: float = 0.25
    codes: frozenset = RETRYABLE_CODES

    def backoff_s(self, attempt: int, rng: random.Random) -> float:
        """Delay before retry `attempt` (0-based)."""
        base = min(
            self.initial_backoff_s * self.multiplier ** attempt,
            self.max_backoff_s,
        )
        return base * (1.0 + self.jitter_frac * (2.0 * rng.random() - 1.0))


# Retries disabled: surface the first error (tests pin exact statuses).
NO_RETRY = RetryPolicy(max_attempts=1)


class _MethodRef:
    """Stable handle for one rpc method that resolves the CURRENT
    channel's stub at call time, so a failover mid-retry-loop (the
    channel and its stubs are rebuilt) transparently redirects every
    holder — retry loops, pipelines re-issuing futures — without them
    re-reading client attributes."""

    __slots__ = ("_client", "_name")

    def __init__(self, client: "SchedulerClient", name: str):
        self._client = client
        self._name = name

    def __call__(self, request, timeout=None):
        return self._client._stubs[self._name](request, timeout=timeout)

    def future(self, request, timeout=None):
        return self._client._stubs[self._name].future(
            request, timeout=timeout)


def score_response_arrays(resp: pb.ScoreResponse):
    """(feasible[P,N] bool, scores[P,N] f32) from either the row or the
    packed-bytes ScoreResponse form."""
    P, N = len(resp.pod_names), len(resp.node_names)
    if resp.scores_packed:
        feas = np.frombuffer(resp.feasible_packed, np.uint8)
        return (
            feas.reshape(P, N).astype(bool),
            # Zero-copy (read-only) view of the message buffer — an
            # astype here would duplicate 200 MB at 10k x 5k.
            np.frombuffer(resp.scores_packed, "<f4").reshape(P, N),
        )
    if resp.k:
        raise ValueError(
            "response carries the top-k form; use score_topk_arrays"
        )
    feas = np.zeros((P, N), bool)
    scores = np.zeros((P, N), np.float32)
    for i, row in enumerate(resp.rows):
        feas[i] = row.feasible
        scores[i] = row.scores
    return feas, scores


def score_topk_arrays(resp: pb.ScoreResponse):
    """(idx[P,k] int32 node indices with -1 padding, scores[P,k] f32)
    from the top-k ScoreResponse form (request.top_k > 0). Indices
    resolve against resp.node_names (the decoder's canonical sorted
    order). A zero-node snapshot yields [P,0] arrays."""
    P = len(resp.pod_names)
    if not resp.k:
        if not resp.node_names and not resp.rows:
            # top_k requested on a drained cluster: nothing to rank.
            return (np.zeros((P, 0), np.int32), np.zeros((P, 0), np.float32))
        raise ValueError("response carries no top-k form (request had "
                         "top_k unset)")
    k = resp.k
    return (
        np.frombuffer(resp.topk_idx_packed, "<i4").reshape(P, k),
        np.frombuffer(resp.topk_score_packed, "<f4").reshape(P, k),
    )


def assign_response_arrays(resp: pb.AssignResponse):
    """(pod_names, node_names, node_idx[P] int32 (-1 unplaced),
    score[P] f32, commit_key[P] int32) from the packed AssignResponse
    form (request.packed_ok). node_idx values index into the returned
    node_names — the decoder's canonical sorted order, NOT the request
    wire order. The repeated-Assignment form carries node names inline;
    use .assignments for it. A zero-pod response decodes to empty
    arrays (valid for either form)."""
    if resp.assignments:
        raise ValueError(
            "response carries the repeated-Assignment form; read "
            ".assignments (request had packed_ok unset)"
        )
    return (
        list(resp.pod_names),
        list(resp.node_names),
        np.frombuffer(resp.node_idx_packed, "<i4"),
        np.frombuffer(resp.score_packed, "<f4"),
        np.frombuffer(resp.commit_key_packed, "<i4"),
    )


class SchedulerClient:
    def __init__(self, address, timeout: float = 120.0,
                 retry: RetryPolicy | None = None,
                 retry_seed: int | None = None,
                 tracer=None, wire=None):
        """address: one endpoint, or an ORDERED list of replica
        endpoints (round 11, ISSUE 6) — the client talks to the first
        and FAILS OVER to the next on UNAVAILABLE (a dead/restarting
        sidecar), wrapping around; the promoted standby serves the
        failed-over client's deltas from its replicated stores.
        RESOURCE_EXHAUSTED deliberately does NOT rotate: an overloaded
        leader is alive, and stampeding its standby would promote it
        into a split brain.

        timeout: per-RPC deadline budget (seconds) — retries spend
        the SAME budget, they don't extend it. retry: RetryPolicy for
        RETRYABLE statuses (None = defaults; pass NO_RETRY to surface
        first errors). retry_seed pins the backoff jitter for
        deterministic tests/chaos runs.

        wire: the WireLedger every completed Score/Assign cycle is
        ledgered into (round 19, ISSUE 19) — pass the SIDECAR's own
        ledger (svc.wire) when client and server share a process, so
        the cycles land in the server's Statusz wire panel; None falls
        back to the process-default tpusched.wire.DEFAULT."""
        self.timeout = timeout
        self.retry = retry if retry is not None else RetryPolicy()
        self.retries = 0          # observability: attempts beyond the first
        self.failovers = 0        # endpoint rotations (UNAVAILABLE)
        self._retry_rng = random.Random(retry_seed)
        # Trace stitching (round 9, ISSUE 4): every Score/Assign request
        # is stamped with a trace id (request_id) + the caller's active
        # span (parent_span); the sidecar roots its stage spans there,
        # so the client and server rings merge into one causal trace.
        self.tracer = tracer if tracer is not None else tracing.DEFAULT
        # Wire ledger (round 19, ISSUE 19): every completed Score/
        # Assign cycle is assembled from the shared span ring into one
        # WireRecord. Best-effort — assembly must never fail a call.
        self._wire = wire if wire is not None else wiring.DEFAULT
        self.wire_errors = 0
        self.addresses = ([address] if isinstance(address, str)
                          else list(address))
        if not self.addresses:
            raise ValueError("SchedulerClient needs at least one address")
        self._endpoint_idx = 0
        self._channel = None
        self._stubs: dict = {}
        self._parked: list = []   # pre-failover channels, closed in close()
        # Endpoint GENERATION: bumped on every failover. Callers capture
        # it at issue time and pass it to _maybe_failover so a failure
        # observed on an already-abandoned channel (a pipeline sibling
        # future issued pre-rotation) cannot rotate the client BACK onto
        # the dead endpoint it just left.
        self._gen = 0
        self._failover_lock = threading.Lock()
        self._connect()
        self._score = _MethodRef(self, "ScoreBatch")
        self._assign = _MethodRef(self, "Assign")
        self._health = _MethodRef(self, "Health")
        self._metrics = _MethodRef(self, "Metrics")
        self._debugz = _MethodRef(self, "Debugz")
        self._replicate = _MethodRef(self, "Replicate")
        self._explainz = _MethodRef(self, "Explainz")
        self._statusz = _MethodRef(self, "Statusz")
        self._enqueue = _MethodRef(self, "Enqueue")

    _RPCS = (
        ("ScoreBatch", pb.ScoreRequest, pb.ScoreResponse),
        ("Assign", pb.AssignRequest, pb.AssignResponse),
        ("Health", pb.HealthRequest, pb.HealthResponse),
        ("Metrics", pb.MetricsRequest, pb.MetricsResponse),
        ("Debugz", pb.DebugzRequest, pb.DebugzResponse),
        ("Replicate", pb.ReplicateRequest, pb.ReplicateResponse),
        ("Explainz", pb.ExplainzRequest, pb.ExplainzResponse),
        ("Statusz", pb.StatuszRequest, pb.StatuszResponse),
        ("Enqueue", pb.EnqueueRequest, pb.EnqueueResponse),
    )

    def _connect(self) -> None:
        """(Re)build the channel + raw stubs against the current
        endpoint; the _MethodRef handles callers hold resolve through
        self._stubs, so they all pick up the new channel."""
        self._channel = grpc.insecure_channel(
            self.addresses[self._endpoint_idx],
            options=[
                ("grpc.max_receive_message_length", -1),
                ("grpc.max_send_message_length", -1),
            ],
        )
        self._stubs = {
            name: self._channel.unary_unary(
                f"/{SERVICE}/{name}",
                request_serializer=req_cls.SerializeToString,
                response_deserializer=resp_cls.FromString,
            )
            for name, req_cls, resp_cls in self._RPCS
        }

    def endpoint(self) -> str:
        """The endpoint this client currently targets."""
        return self.addresses[self._endpoint_idx]

    def failover(self) -> str:
        """Rotate to the next endpoint in the ordered list (wrapping)
        and rebuild the channel; returns the new endpoint. The old
        channel is NOT closed here — closing would CANCEL a pipeline's
        other in-flight futures (fatal), where letting them fail
        against the dead server yields UNAVAILABLE (retryable, and the
        retry re-issues on the new channel). Parked channels are closed
        by close()."""
        self._parked.append(self._channel)
        # Bound the park lot: a long-lived client on a flapping fleet
        # must not accumulate channels forever. Only the last few
        # generations can still carry live in-flight futures (pipeline
        # joins are FIFO and re-issue promptly on the current channel);
        # closing the oldest beyond that is safe.
        while len(self._parked) > 8:
            self._parked.pop(0).close()
        self._endpoint_idx = (self._endpoint_idx + 1) % len(self.addresses)
        self._gen += 1
        self._connect()
        self.failovers += 1
        self.tracer.record("client.failover", cat="client",
                           to=self.endpoint())
        return self.endpoint()

    def _maybe_failover(self, code, gen: int | None = None) -> bool:
        """Failover trigger (round 11): UNAVAILABLE means the endpoint
        is dead or restarting — with more than one endpoint configured,
        rotate before the retry re-sends. Other retryable statuses stay
        put (see __init__).

        gen: the endpoint generation captured when the failed call was
        ISSUED. If another failure already rotated us off that endpoint
        (gen is stale), stay put — rotating again would point the
        client back at the dead replica and burn retry attempts
        ping-ponging between the corpse and the live standby."""
        if code != grpc.StatusCode.UNAVAILABLE or len(self.addresses) < 2:
            return False
        with self._failover_lock:
            if gen is not None and gen != self._gen:
                return False
            self.failover()
        return True

    def _stamp(self, request, request_id: str = "") -> str:
        """Stamp a Score/Assign request with its trace identity; keeps
        an id the caller (a pipeline re-issue) already minted. With no
        explicit id, an enclosing client span on this thread donates
        its trace: a resync full-send issued under a client.resync span
        parents into the doomed request's trace instead of starting an
        unrelated one."""
        if request_id:
            request.request_id = request_id
        if not request.request_id:
            ctx = self.tracer.current()
            if ctx is not None and ctx[0]:
                request.request_id = ctx[0]
                if not request.parent_span:
                    request.parent_span = ctx[1]
            else:
                request.request_id = self.tracer.new_trace_id()
        elif not request.parent_span:
            ctx = self.tracer.current()
            if ctx is not None and ctx[0] == request.request_id:
                request.parent_span = ctx[1]
        return request.request_id

    def _call(self, method, request, rpc: str = ""):
        """Blocking unary call under the deadline + retry contract:
        RETRYABLE statuses back off (capped, jittered) and re-send
        inside the ORIGINAL deadline budget; a retried delta carries
        its original (lineage_id, seq) so an applied-but-unacked first
        attempt is deduped server-side. Everything else raises.
        _BasePipeline._join_entry is this loop's future-shaped twin —
        keep their retry discipline in lockstep."""
        rid = ""
        ledger = None
        bytes_up = 0
        if "request_id" in type(request).DESCRIPTOR.fields_by_name:
            rid = self._stamp(request)
            if rpc and self._wire.enabled:
                ledger = self._wire
        if ledger is not None:
            # The wire ledger's serialize component: one timed pass
            # over the request (gRPC's own serializer hits protobuf's
            # warmed path right after). Only paid while ledgering; a
            # disabled wire ledger skips it.
            t_ser = time.perf_counter()
            bytes_up = len(request.SerializeToString())
            self.tracer.record(
                "client.serialize", dur_s=time.perf_counter() - t_ser,
                cat="client", ctx=(rid, int(request.parent_span)),
                rpc=rpc, bytes=bytes_up,
            )
        deadline = time.monotonic() + self.timeout
        attempt = 0
        while True:
            remaining = deadline - time.monotonic()
            gen = self._gen
            try:
                if not rid:
                    return method(request, timeout=max(remaining, 1e-3))
                with self.tracer.span("client.send", cat="client",
                                      trace_id=rid,
                                      parent_id=int(request.parent_span),
                                      rpc=rpc, attempt=attempt):
                    resp = method(request, timeout=max(remaining, 1e-3))
                if ledger is not None:
                    self._wire_observe(ledger, rpc, rid, bytes_up,
                                       resp.ByteSize())
                return resp
            except grpc.RpcError as e:
                attempt += 1
                if (e.code() not in self.retry.codes
                        or attempt >= self.retry.max_attempts):
                    raise
                delay = self.retry.backoff_s(attempt - 1, self._retry_rng)
                if deadline - time.monotonic() <= delay:
                    raise
                self.retries += 1
                # Replica failover (round 11): a dead endpoint rotates
                # BEFORE the backoff, so the retry re-sends against the
                # next replica in the ordered list.
                self._maybe_failover(e.code(), gen)
                time.sleep(delay)
                if rid:
                    # The backoff wait, as a span: retries are visible
                    # gaps in the stitched trace, not silent latency.
                    self.tracer.record(
                        "client.retry", dur_s=delay, cat="client",
                        ctx=(rid, int(request.parent_span)),
                        rpc=rpc, code=e.code().name, attempt=attempt,
                    )

    def _wire_observe(self, ledger, rpc: str, rid: str, bytes_up: int,
                      bytes_down: int, source: str = "call") -> None:
        """Assemble + ledger one completed cycle from the shared span
        ring (tpusched.wire.assemble). Best-effort by contract: a
        ledger bug must never fail a call that already succeeded —
        failures count in self.wire_errors instead of raising."""
        try:
            rec = wiring.assemble(
                rid, rpc, self.tracer.spans(rid), ledger.clock,
                bytes_up=bytes_up, bytes_down=bytes_down, source=source,
            )
            if rec is not None:
                ledger.observe(rec)
        except Exception:
            self.wire_errors += 1

    def health(self) -> pb.HealthResponse:
        return self._call(self._health, pb.HealthRequest())

    def replicate(self, from_seq: int,
                  follower_id: str = "") -> pb.ReplicateResponse:
        """Fetch replication ops from the current endpoint (round 11;
        StandbyFollower's poll — see tpusched/replicate.py)."""
        return self._call(
            self._replicate,
            pb.ReplicateRequest(from_seq=int(from_seq),
                                follower_id=follower_id),
        )

    def score_batch(self, snapshot: pb.ClusterSnapshot, *,
                    packed_ok: bool = False, top_k: int = 0,
                    request_id: str = "") -> pb.ScoreResponse:
        return self._call(
            self._score,
            pb.ScoreRequest(snapshot=snapshot, packed_ok=packed_ok,
                            top_k=top_k, request_id=request_id),
            rpc="ScoreBatch",
        )

    def assign(self, snapshot: pb.ClusterSnapshot, *,
               packed_ok: bool = False,
               request_id: str = "") -> pb.AssignResponse:
        return self._call(
            self._assign,
            pb.AssignRequest(snapshot=snapshot, packed_ok=packed_ok,
                             request_id=request_id),
            rpc="Assign",
        )

    def _send_future(self, method, request, rpc: str, request_id: str):
        """Issue a stamped future; the send itself is an instant span
        (the in-flight wait is the caller's join — pipelines record it
        as client.join against the same trace id)."""
        rid = self._stamp(request, request_id)
        self.tracer.record("client.send", cat="client",
                           ctx=(rid, int(request.parent_span)), rpc=rpc)
        return method.future(request, timeout=self.timeout)

    def assign_future(self, snapshot: pb.ClusterSnapshot, *,
                      packed_ok: bool = False, request_id: str = ""):
        """Non-blocking Assign: returns a grpc Future. With the
        sidecar's staged handlers (decode outside the dispatch lane), a
        second in-flight request is what lets ONE client overlap its
        next request's decode with the previous solve — see
        AssignPipeline."""
        return self._send_future(
            self._assign,
            pb.AssignRequest(snapshot=snapshot, packed_ok=packed_ok),
            "Assign", request_id,
        )

    def assign_delta_future(self, delta: pb.SnapshotDelta, *,
                            packed_ok: bool = False, request_id: str = ""):
        return self._send_future(
            self._assign,
            pb.AssignRequest(delta=delta, packed_ok=packed_ok),
            "Assign", request_id,
        )

    def score_batch_delta(self, delta: pb.SnapshotDelta, *,
                          packed_ok: bool = False, top_k: int = 0,
                          request_id: str = "") -> pb.ScoreResponse:
        return self._call(
            self._score,
            pb.ScoreRequest(delta=delta, packed_ok=packed_ok, top_k=top_k,
                            request_id=request_id),
            rpc="ScoreBatch",
        )

    def score_batch_future(self, snapshot: pb.ClusterSnapshot, *,
                           packed_ok: bool = False, top_k: int = 0,
                           request_id: str = ""):
        """Non-blocking ScoreBatch (see assign_future): the second
        in-flight request that lets ONE scoring client overlap its next
        request's decode with the previous ranking — ScorePipeline."""
        return self._send_future(
            self._score,
            pb.ScoreRequest(snapshot=snapshot, packed_ok=packed_ok,
                            top_k=top_k),
            "ScoreBatch", request_id,
        )

    def score_batch_delta_future(self, delta: pb.SnapshotDelta, *,
                                 packed_ok: bool = False, top_k: int = 0,
                                 request_id: str = ""):
        return self._send_future(
            self._score,
            pb.ScoreRequest(delta=delta, packed_ok=packed_ok, top_k=top_k),
            "ScoreBatch", request_id,
        )

    def assign_delta(self, delta: pb.SnapshotDelta, *,
                     packed_ok: bool = False,
                     request_id: str = "") -> pb.AssignResponse:
        return self._call(
            self._assign,
            pb.AssignRequest(delta=delta, packed_ok=packed_ok,
                             request_id=request_id),
            rpc="Assign",
        )

    def metrics_text(self) -> str:
        return self._call(self._metrics, pb.MetricsRequest()).prometheus_text

    def debugz(self, max_traces: int = 16,
               include_flight: bool = False) -> pb.DebugzResponse:
        """Fetch the sidecar's last-N traces (+ flight dumps) — see
        SchedulerService.Debugz and tools/tracez.py."""
        return self._call(
            self._debugz,
            pb.DebugzRequest(max_traces=max_traces,
                             include_flight=include_flight),
        )

    def explainz(self, pod: str = "", victim: str = "",
                 max_records: int = 8,
                 include_auction: bool = False) -> pb.ExplainzResponse:
        """Decision provenance (round 12): last-N DecisionRecord
        summaries plus "why is `pod` pending/placed" and "who evicted
        `victim`" — see SchedulerService.Explainz and
        tools/explainz.py."""
        return self._call(
            self._explainz,
            pb.ExplainzRequest(pod=pod, victim=victim,
                               max_records=max_records,
                               include_auction=include_auction),
        )

    def statusz(self, max_records: int = 32) -> pb.StatuszResponse:
        """Cycle flight ledger (round 18, ISSUE 13): rolling per-stage
        p50/p99, warm-path mix, compile timeline, sentinel anomalies,
        and the last-N CycleRecords as one JSON payload — see
        SchedulerService.Statusz and tools/statusz.py."""
        return self._call(
            self._statusz,
            pb.StatuszRequest(max_records=int(max_records)),
        )

    def enqueue(self, pods, tenant: int = 0,
                submitted: float = 0.0) -> pb.EnqueueResponse:
        """Offer a batch through the admission-controlled front door
        (PR 20, ISSUE 20). `pods` is a list of pb.PendingPod messages
        or builder-style dicts (name / priority / slo_target). A
        FULLY shed batch is RESOURCE_EXHAUSTED — already in
        RETRYABLE_CODES, so this call backs off and re-offers inside
        its deadline budget without new machinery; the server dedups
        admitted names so the retry is exactly-once. A partial shed
        returns OK with resp.shed_pods for the caller to re-offer."""
        req = pb.EnqueueRequest(tenant=int(tenant),
                                submitted=float(submitted))
        for p in pods:
            if isinstance(p, pb.PendingPod):
                req.pods.add().CopyFrom(p)
            else:
                req.pods.add(name=p["name"],
                             priority=float(p.get("priority", 0.0)),
                             slo_target=float(p.get("slo_target", 0.0)))
        return self._call(self._enqueue, req, rpc="Enqueue")

    def close(self):
        self._channel.close()
        for ch in self._parked:
            ch.close()
        self._parked = []

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class DeltaSession:
    """Transparent delta transport over a SchedulerClient (SURVEY.md §7
    hard part 6): callers always pass the FULL wire snapshot; the
    session diffs it against what the sidecar last acknowledged and
    ships only changed records. Falls back to a full send when the
    sidecar no longer holds the base (FAILED_PRECONDITION — e.g. after
    a sidecar restart or LRU eviction), which also makes crash recovery
    automatic: state lives only as an optimization."""

    def __init__(self, client: SchedulerClient):
        self.client = client
        self._base: codec.SnapshotStore | None = None
        self._base_id: str | None = None
        # Retry-safety lineage identity: every delta this session sends
        # carries (lineage_id, seq) so a client-level retry of an
        # applied-but-unacked delta replays server-side (proto comment).
        self._lineage_id = uuid.uuid4().hex[:16]
        self._seq = 0
        # After a fallback (sidecar restart / base evicted from its LRU),
        # skip the delta attempt for exponentially more sends: a client
        # whose base is always evicted (many interleaved sessions) must
        # not pay a failed delta RPC + full resend on every cycle.
        self._skip_delta = 0
        self._consec_fallbacks = 0
        # Wire accounting for benchmarks/observability.
        self.full_sends = 0
        self.delta_sends = 0
        self.fallbacks = 0
        self.bytes_sent = 0

    def _call(self, snapshot: pb.ClusterSnapshot, send_full, send_delta,
              changed: "set[str] | None" = None):
        """One cycle. Its client-side stages are flat spans (check,
        diff, remember) under the cycle's trace id, which the request
        then carries, so the sidecar's spans join the same trace; none
        encloses the send."""
        tracer = self.client.tracer
        ctx = tracer.current()
        rid = ctx[0] if ctx is not None and ctx[0] else \
            tracer.new_trace_id()
        safe = False
        names = None
        if (
            self._base is not None
            and self._base_id is not None
            and self._skip_delta == 0
        ):
            # The NEW snapshot must itself be delta-safe: the server's
            # name-keyed store would silently collapse unnamed/duplicate
            # records arriving as delta upserts and solve a corrupted
            # snapshot for this cycle. (_remember only drops the base
            # for the NEXT cycle — one cycle too late.) On the named
            # path this is the diff's one name pass.
            with tracer.span("client.check", cat="client", trace_id=rid):
                if changed is None:
                    safe = codec.delta_safe(snapshot)
                else:
                    names = codec.record_names(snapshot)
                    safe = names is not None
        if safe:
            # Named: an O(churn) update of the base, applied once the
            # sidecar acknowledges. Unnamed: the next base, rebuilt.
            update: "list[dict] | None" = None if changed is None else []
            new_bytes = codec.SnapshotStore() if changed is None else None
            walk: "list[int]" = []
            with tracer.span("client.diff", cat="client",
                             trace_id=rid) as sp:
                delta = codec.delta_between(
                    self._base, snapshot, self._base_id,
                    new_bytes=new_bytes, changed=changed, walk=walk,
                    names=names, update=update,
                )
                # Free the name lists before the send: alive through it,
                # they tripled the young collections' cost there.
                names = None
                self._seq += 1
                delta.lineage_id = self._lineage_id
                delta.seq = self._seq
                delta_bytes = delta.ByteSize()
                # Records the diff examined, serialized, and shipped.
                sp.attrs.update(
                    visited=walk[0], serialized=walk[1],
                    shipped=len(delta.upsert_nodes) + len(delta.upsert_pods)
                    + len(delta.upsert_running) + len(delta.remove_nodes)
                    + len(delta.remove_pods) + len(delta.remove_running))
            self.bytes_sent += delta_bytes  # transmitted even on reject
            try:
                resp = send_delta(delta, rid)
                self.delta_sends += 1
                self._consec_fallbacks = 0
                with tracer.span("client.remember", cat="client",
                                 trace_id=rid):
                    self._remember(snapshot, resp.snapshot_id, new_bytes,
                                   update=update)
                return resp
            except grpc.RpcError as e:
                if e.code() != grpc.StatusCode.FAILED_PRECONDITION:
                    raise
                self.fallbacks += 1
                self._consec_fallbacks += 1
                if self._consec_fallbacks >= 2:
                    self._skip_delta = min(
                        2 ** (self._consec_fallbacks - 1), 64
                    )
                self._base = self._base_id = None
                # Minted trace id: the span AND the full send under it
                # (which inherits the id via _stamp) group as one trace
                # in Debugz — trace_id=None here would record untraced.
                with tracer.span(
                    "client.resync", cat="client",
                    trace_id=tracer.new_trace_id(),
                    lineage=self._lineage_id, seq=self._seq,
                ):
                    resp = send_full(snapshot, "")
                self.full_sends += 1
                self.bytes_sent += snapshot.ByteSize()
                # delta_safe already verified this cycle (guard above).
                with tracer.span("client.remember", cat="client",
                                 trace_id=rid):
                    self._remember(snapshot, resp.snapshot_id,
                                   verified=True)
                return resp
        elif self._skip_delta > 0:
            self._skip_delta -= 1
        resp = send_full(snapshot, rid)
        self.full_sends += 1
        self.bytes_sent += snapshot.ByteSize()
        with tracer.span("client.remember", cat="client", trace_id=rid):
            self._remember(snapshot, resp.snapshot_id)
        return resp

    def _remember(self, snapshot: pb.ClusterSnapshot, sid: str,
                  prebuilt: "codec.SnapshotStore | None" = None,
                  verified: bool = False,
                  update: "list[dict] | None" = None) -> None:
        """Record what was sent, as per-record BYTES: immune to the
        caller mutating its message in place afterwards, and usable only
        when the snapshot is delta-safe (unique non-empty names — the
        stores key by name). `prebuilt` reuses the bytes delta_between
        already serialized for the diff (no second serialization pass);
        `update` is a named diff's O(churn) update of the current base,
        which the delta was diffed against."""
        # prebuilt/verified/update only arrive from paths that already
        # checked delta safety this cycle — don't re-scan all records.
        if not sid or (prebuilt is None and update is None and not verified
                       and not codec.delta_safe(snapshot)):
            self._base = self._base_id = None
            return
        if update is not None:
            self._base.apply_update(update)
            st = self._base
        elif prebuilt is not None:
            st = prebuilt
        else:
            st = codec.SnapshotStore()
            st.set_full_bytes(snapshot)
        self._base = st
        self._base_id = sid

    def assign(self, snapshot: pb.ClusterSnapshot,
               changed: "set[str] | None" = None,
               **kw) -> pb.AssignResponse:
        """changed: optional names of records the caller knows it
        touched since the last call (watch-event driven); makes the
        diff O(churn) — see codec.delta_between."""
        return self._call(
            snapshot,
            lambda s, rid: self.client.assign(s, request_id=rid, **kw),
            lambda d, rid: self.client.assign_delta(d, request_id=rid,
                                                    **kw),
            changed=changed,
        )

    def score_batch(self, snapshot: pb.ClusterSnapshot,
                    changed: "set[str] | None" = None,
                    **kw) -> pb.ScoreResponse:
        return self._call(
            snapshot,
            lambda s, rid: self.client.score_batch(s, request_id=rid,
                                                   **kw),
            lambda d, rid: self.client.score_batch_delta(
                d, request_id=rid, **kw),
            changed=changed,
        )


class StaleBase(Exception):
    """An in-flight pipelined delta named a base the sidecar no longer
    holds (restart / LRU eviction) and transparent resync is OFF
    (auto_resync=False). The caller still has its current snapshot:
    re-pin by submitting it with changed=None (a full send).
    `completed` carries the responses that HAD already been received
    before the stale request — earlier cycles' assignments are handed
    to the caller, not dropped in the unwind.

    With auto_resync (the default) this never escapes: the pipeline
    recomposes each doomed cycle's FULL snapshot from its pinned store
    plus that cycle's cumulative delta and re-sends it, so every
    submitted cycle still yields exactly one response — the crash-
    resync path with the end-state-identical guarantee (ISSUE 3)."""

    def __init__(self, msg: str, completed=()):
        super().__init__(msg)
        self.completed: list = list(completed)


class _BasePipeline:
    """Single-connection pipelined requests (SURVEY.md §2.3 PP at the
    serving boundary): keep up to `depth` requests in flight on ONE
    channel so the sidecar's staged handlers overlap request k+1's
    decode with request k's device work — the single-scheduler
    deployment gets the overlap the two-session wire bench measured,
    without a second scheduler. Subclasses bind the rpc pair
    (_send_full / _send_delta_future): AssignPipeline for solves,
    ScorePipeline for top-k ScoreBatch.

    Delta discipline: DeltaSession advances its base every response,
    but a pipelined delta k+1 cannot diff against snapshot k — k's
    snapshot_id is unknown until its response arrives. Instead the base
    is PINNED: every in-flight delta names the same pinned base and
    carries the CUMULATIVE churn since the pin (the server's LRU
    refreshes the pinned store on every hit, keeping it alive). The pin
    refreshes with a full send (draining the pipe first — the response
    carries the new id) when cumulative churn passes refresh_frac of
    the record count, bounding delta growth at O(cumulative churn).

    For streams of independent or slowly-churning snapshots (replay,
    bench, many-cluster fan-in, a scheduler pipelining speculative
    cycles). One cluster's strictly serial feedback cycles cannot be
    pipelined — same limit as pipeline.solve_stream documents."""

    # Wire-ledger rpc label (subclasses bind the real method pair).
    _rpc = ""

    def __init__(self, client: SchedulerClient, depth: int = 2,
                 refresh_frac: float = 0.25, auto_resync: bool = True):
        self.client = client
        self.depth = max(1, int(depth))
        self.refresh_frac = refresh_frac
        self.auto_resync = auto_resync
        self._pinned: codec.SnapshotStore | None = None
        self._pinned_id: str | None = None
        self._churn: set = set()
        # In-flight entries: dict(fut, delta, packed_ok) — the delta is
        # retained so a retry re-sends the SAME (lineage_id, seq) and a
        # resync can recompose the cycle's full snapshot from pin+delta.
        self._inflight: list = []
        self._lineage_id = uuid.uuid4().hex[:16]
        self._seq = 0
        self.full_sends = 0
        self.delta_sends = 0
        self.bytes_sent = 0
        self.resyncs = 0      # doomed cycles re-sent as full snapshots
        self.retried = 0      # retryable-status future re-issues

    # -- rpc binding (subclass responsibility) ------------------------------

    def _send_full(self, snapshot: pb.ClusterSnapshot, packed_ok: bool):
        raise NotImplementedError

    def _send_delta_future(self, delta: pb.SnapshotDelta, packed_ok: bool,
                           request_id: str = ""):
        raise NotImplementedError

    def _join_entry(self, entry) -> object:
        """Join one in-flight delta under the taxonomy: RETRYABLE
        statuses re-issue the SAME delta future (same lineage/seq —
        the server dedupes an applied-but-unacked first attempt) with
        capped backoff; FAILED_PRECONDITION resyncs the cycle as a
        full send (auto_resync) or raises StaleBase; the rest raise.

        Like SchedulerClient._call, re-issues spend ONE deadline
        budget (client.timeout, measured from this join): without the
        cutoff, each fresh future carries its own full timeout and a
        blackholed sidecar could stall a join for max_attempts x
        timeout instead of roughly the configured budget (the last
        in-flight future can still run to its own deadline — ~2x
        worst case, not Nx).

        This loop is _call's FUTURE-shaped twin, kept separate because
        the first "attempt" here is joining an already-issued future
        and the resync path has no blocking-call analogue — but the
        retry DISCIPLINE (policy codes, attempt cap, backoff-must-fit-
        the-remaining-budget) must stay in lockstep with _call; change
        them together."""
        policy = self.client.retry
        tracer = self.client.tracer
        rid = entry.get("rid", "")
        deadline = time.monotonic() + self.client.timeout
        attempt = 0
        while True:
            try:
                with tracer.span("client.join", cat="client",
                                 trace_id=rid, attempt=attempt):
                    resp = entry["fut"].result()
                if rid and self.client._wire.enabled:
                    self.client._wire_observe(
                        self.client._wire, self._rpc, rid,
                        entry["delta"].ByteSize(), resp.ByteSize(),
                        source="pipeline",
                    )
                return resp
            except grpc.RpcError as e:
                code = e.code()
                if code in policy.codes and attempt < policy.max_attempts - 1:
                    delay = policy.backoff_s(attempt, self.client._retry_rng)
                    if deadline - time.monotonic() > delay:
                        # Same failover trigger as _call: rotate off a
                        # dead endpoint, then re-issue the SAME delta
                        # (same lineage/seq) against the new replica —
                        # its replicated stores hold the pinned base.
                        # The entry's issue-time generation keeps a
                        # SIBLING future's failure (issued pre-rotation
                        # on the dead channel) from rotating us back.
                        self.client._maybe_failover(code, entry.get("gen"))
                        time.sleep(delay)
                        attempt += 1
                        self.retried += 1
                        # Re-issue keeps the SAME trace id as well as
                        # the same (lineage, seq): the retry lands in
                        # the original request's stitched trace.
                        tracer.record("client.retry", dur_s=delay,
                                      cat="client", ctx=(rid, 0),
                                      code=code.name, attempt=attempt)
                        entry["fut"] = self._send_delta_future(
                            entry["delta"], entry["packed_ok"], rid
                        )
                        entry["gen"] = self.client._gen
                        continue
                if code in RESYNC_CODES:
                    return self._resync_entry(entry, e)
                raise

    def _resync_entry(self, entry, err):
        """The sidecar lost this cycle's base (restart, LRU eviction,
        stateless degrade). The cycle is NOT lost: its cumulative delta
        applied to the pinned store reproduces the cycle's exact full
        snapshot — recompose and re-send it as a full request. The pin
        id is cleared (the next submit re-pins with a full send) but
        the pin STORE is kept so remaining in-flight cycles can resync
        the same way."""
        if not self.auto_resync or self._pinned is None:
            self._pinned = self._pinned_id = None
            self._drop_inflight()
            raise StaleBase(str(err)) from err
        with self.client.tracer.span("client.resync", cat="client",
                                     trace_id=entry.get("rid", "")):
            full = self._pinned.copy()
            full.apply_delta(entry["delta"])
            msg = full.compose()
            resp = self._send_full(msg, entry["packed_ok"])
        self.resyncs += 1
        self.full_sends += 1
        self.bytes_sent += msg.ByteSize()
        self._pinned_id = None
        return resp

    def _drop_inflight(self):
        for entry in self._inflight:
            entry["fut"].cancel()
        self._inflight = []

    def submit(self, snapshot: pb.ClusterSnapshot,
               changed: "set[str] | None" = None,
               packed_ok: bool = True) -> list:
        """Enqueue one cycle; returns the responses this call completed
        (drained oldest-first; possibly empty while the pipe fills).
        changed: names mutated since the LAST submit, or None to force
        a full send (also the re-pin path). The delta is serialized
        BEFORE returning, so the caller may mutate `snapshot` in place
        between submits."""
        n_rec = (len(snapshot.nodes) + len(snapshot.pods)
                 + len(snapshot.running))
        churn_next = (
            self._churn | set(changed) if changed is not None else None
        )
        # One name pass: the safety check on both paths, and the named
        # diff's names on the delta path.
        names = codec.record_names(snapshot)
        if (
            self._pinned is None or self._pinned_id is None
            or churn_next is None
            or len(churn_next) > self.refresh_frac * max(n_rec, 1)
            or names is None
        ):
            done = self.flush()
            resp = self._send_full(snapshot, packed_ok)
            self.full_sends += 1
            self.bytes_sent += snapshot.ByteSize()
            if resp.snapshot_id and names is not None:
                st = codec.SnapshotStore()
                st.set_full_bytes(snapshot)
                self._pinned, self._pinned_id = st, resp.snapshot_id
                self._churn = set()
            else:
                self._pinned = self._pinned_id = None
            done.append(resp)
            return done
        self._churn = churn_next
        delta = codec.delta_between(
            self._pinned, snapshot, self._pinned_id, changed=self._churn,
            names=names,
        )
        names = None  # freed before the send, as in DeltaSession._call
        self._seq += 1
        delta.lineage_id = self._lineage_id
        delta.seq = self._seq
        self.bytes_sent += delta.ByteSize()
        rid = self.client.tracer.new_trace_id()
        self._inflight.append(dict(
            fut=self._send_delta_future(delta, packed_ok, rid),
            delta=delta, packed_ok=packed_ok, rid=rid,
            gen=self.client._gen,
        ))
        self.delta_sends += 1
        done = []
        while len(self._inflight) >= self.depth:
            self._join_into(done)
        return done

    def flush(self) -> list:
        """Drain every in-flight request, oldest first."""
        out: list = []
        while self._inflight:
            self._join_into(out)
        return out

    def _join_into(self, done: list) -> None:
        """Join the oldest in-flight request into `done`; on StaleBase
        (auto_resync off) the already-joined responses ride the
        exception (`completed`) instead of being lost in the unwind."""
        try:
            done.append(self._join_entry(self._inflight.pop(0)))
        except StaleBase as e:
            e.completed = list(done) + e.completed
            raise


class AssignPipeline(_BasePipeline):
    """Pipelined Assign cycles (see _BasePipeline)."""

    _rpc = "Assign"

    def _send_full(self, snapshot, packed_ok):
        return self.client.assign(snapshot, packed_ok=packed_ok)

    def _send_delta_future(self, delta, packed_ok, request_id=""):
        return self.client.assign_delta_future(
            delta, packed_ok=packed_ok, request_id=request_id
        )


class ScorePipeline(_BasePipeline):
    """Pipelined top-k ScoreBatch cycles: the same depth-`depth`
    pinned-base discipline for the Score-plugin surface, closing the
    round-5 verdict's remaining single-stream gap (parity top-8
    ScoreBatch): with two requests in flight on one connection, cycle
    k+1's decode/delta-apply overlaps cycle k's on-device ranking, so
    the per-cycle wall approaches max(decode, rank + fetch) instead of
    their sum. Coalescer interplay: identical deltas submitted by MANY
    such clients fuse server-side into one dispatch."""

    _rpc = "ScoreBatch"

    def __init__(self, client: SchedulerClient, depth: int = 2,
                 refresh_frac: float = 0.25, top_k: int = 8,
                 auto_resync: bool = True):
        super().__init__(client, depth=depth, refresh_frac=refresh_frac,
                         auto_resync=auto_resync)
        self.top_k = int(top_k)

    def _send_full(self, snapshot, packed_ok):
        return self.client.score_batch(snapshot, packed_ok=packed_ok,
                                       top_k=self.top_k)

    def _send_delta_future(self, delta, packed_ok, request_id=""):
        return self.client.score_batch_delta_future(
            delta, packed_ok=packed_ok, top_k=self.top_k,
            request_id=request_id,
        )
