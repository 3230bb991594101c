"""Wire ledger (ISSUE 19): per-cycle round-trip decomposition.

The repo's only wire number used to be a single process-global p50
measured once at bench startup. This module is the PR 13 move applied
to the transport: every client<->server cycle emits
ONE schema-validated `WireRecord` that decomposes the full round trip
into budgeted components, so ROADMAP item 2 (streaming wire +
on-device response pack) has a baseline to beat per component instead
of one opaque wall number.

Three pieces:

  * `ClockOffsetEstimator` — NTP-style offset between the client's and
    the server's wall clocks from the (send, recv, reply, join)
    timestamp quadruple: the client's `client.send` span gives t0/t3,
    the server's `server.<rpc>` request-root span gives t1/t2, joined
    by request_id. offset = ((t1-t0) + (t2-t3)) / 2; the residual path
    asymmetry bounds the error (uncertainty = delay/2 where delay =
    (t3-t0) - (t2-t1)). Candidate (send, root) pairs are validated by
    DURATION arithmetic only (busy <= window), so pairing survives
    arbitrary clock skew, retries that re-issue under the same rid,
    and resync full-sends; the estimator keeps a min-delay window so
    one congested sample never poisons the offset.
  * `assemble()` — joins one cycle's spans (the ledger does NOT
    re-instrument: client.serialize / client.send / client.retry /
    client.join and the server stages — gate.wait, coalesce.wait,
    store.advance, decode, delta.apply, dispatch, fetch.join (device
    solve + D2H), reply.names, reply.pack) into a WireRecord. The two
    one-way gaps are offset-corrected: `send.gap` = client send start
    -> server root start (up transit + server ingress queue) and
    `reply.gap` = server root end -> response in the client's hands
    (down transit + client-side reply decode). Unattributed server
    wall lands in `server.other`, so the component sum reconstructs
    the cycle wall by design and `coverage` genuinely measures how
    well the clock stitching resolved the gaps.
  * `WireLedger` — bounded ring + rolling quantiles (the PR 13
    machinery) + a sentinel: a cycle whose wall exceeds the rolling
    p99 (non-interpolated covering-bucket bound) is attributed in
    order — payload well above the rolling byte p95 -> "bytes_burst";
    else the component group with the largest excess over its rolling
    median: gate/coalesce waits -> "queue", serialize/store/decode/apply ->
    "decode", gaps/fetch/reply -> "transfer"; else "unknown". Each
    anomaly bumps `scheduler_wire_anomalies_total{cause}`, fires the
    attached FlightRecorder with the attributed record, and — when
    `profile_dir` is set — ARMS a one-shot `jax.profiler` device-trace
    capture that the serving path wraps around the next cycle via
    `maybe_profile()` (a capture cannot start retroactively; the next
    cycle in the same regime is the best observable proxy).

Records flow into the Statusz payload as a fleet-mergeable `wire`
panel (raw bucket counts ride along; tools/statusz.py re-derives fleet
quantiles from summed counts) and into tracez's Perfetto export as a
per-cycle breakdown track (`to_chrome`).

Stdlib-only on purpose (like ledger.py/trace.py): importable from
every layer; jax is touched only inside an armed profile capture.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import json
import math
import os
import threading
from collections import deque
from typing import Any, Iterator, TextIO

from tpusched import metrics as pm
from tpusched import trace as tracing

ANOMALY_CAUSES = ("bytes_burst", "queue", "decode", "transfer", "unknown")

# Server-side stage spans the assembler joins (instrumented since PR 4;
# mutually exclusive serving phases, so their sum stays <= the root
# wall). fetch.join includes the device solve AND the D2H result fetch
# (the engine's ordered fetch worker materializes inside it).
# store.advance is the delta branch's byte-store step before the apply.
# Spans nested inside a stage (delta.apply's apply.*) stay out: the sum
# would count their time twice.
SERVER_STAGES = ("gate.wait", "coalesce.wait", "store.advance", "decode",
                 "delta.apply", "dispatch", "fetch.join", "reply.names",
                 "reply.pack")

# Cause-attribution groups (sentinel docstring). server.other is the
# unattributed server residue: store.compose / session.seed / handler
# glue — transfer-adjacent for attribution purposes because it moves
# with the same H2D/device pressure fetch.join does.
_QUEUE = ("gate.wait", "coalesce.wait")
_DECODE = ("serialize", "store.advance", "decode", "delta.apply")
_TRANSFER = ("send.gap", "reply.gap", "fetch.join", "reply.names",
             "reply.pack", "server.other")

# Canonical component order for rendering (statusz panel, the Perfetto
# breakdown track, bench emission): request-path order.
COMPONENT_ORDER = ("serialize", "send.gap", "retry.backoff", "gate.wait",
                   "coalesce.wait", "store.advance", "decode",
                   "delta.apply", "dispatch",
                   "fetch.join", "reply.names", "reply.pack",
                   "server.other", "reply.gap", "unknown")


@dataclasses.dataclass
class WireRecord:
    """One client<->server cycle's wire-ledger entry (module
    docstring). `cycle` is assigned by the ledger at observe() time;
    `anomaly` is written by the sentinel ("" = none). `stages` holds
    per-component wall seconds; component NAMES follow the trace span
    names (plus the derived `send.gap`/`reply.gap`/`server.other`), so
    a wire anomaly points at the same name a trace shows."""

    ts: float = 0.0            # client clock at the first send
    rpc: str = ""              # Assign | ScoreBatch | Score
    rid: str = ""              # request_id == trace_id
    source: str = "call"       # call (blocking) | pipeline (futures)
    attempts: int = 1          # client.send spans under the rid
    resyncs: int = 0           # client.resync re-issues under the rid
    replayed: bool = False     # server answered from the replay cache
    stitched: bool = False     # a server root was joined (gaps real)
    wall_s: float = 0.0        # the quantity the sentinel judges
    offset_s: float = 0.0      # server clock minus client clock
    uncertainty_s: float = 0.0 # half the path asymmetry; -1 = unknown
    bytes_up: int = 0          # serialized request payload
    bytes_down: int = 0        # serialized reply payload
    stages: "dict[str, float]" = dataclasses.field(default_factory=dict)
    coverage: float = 0.0      # sum(stages) / wall_s
    cycle: int = 0
    anomaly: str = ""


# Field name -> accepted types; THE schema authority (ledger.py
# discipline: validate_record is the contract tools/check.py's wirez
# smoke and the statusz fleet merge rely on).
SCHEMA: "dict[str, tuple[type, ...]]" = {
    "cycle": (int,),
    "ts": (int, float),
    "rpc": (str,),
    "rid": (str,),
    "source": (str,),
    "attempts": (int,),
    "resyncs": (int,),
    "replayed": (bool,),
    "stitched": (bool,),
    "wall_s": (int, float),
    "offset_s": (int, float),
    "uncertainty_s": (int, float),
    "bytes_up": (int,),
    "bytes_down": (int,),
    "stages": (dict,),
    "coverage": (int, float),
    "anomaly": (str,),
}


def record_dict(rec: WireRecord) -> "dict[str, Any]":
    """Plain dict in SCHEMA key order (JSONL lines, Statusz payloads)."""
    d = dataclasses.asdict(rec)
    return {k: d[k] for k in SCHEMA}


def validate_record(d: "dict[str, Any]") -> "dict[str, Any]":
    """Schema check for one record dict (the wirez smoke contract).
    Raises ValueError on any drift: missing/extra keys, wrong field
    types (bools are NOT ints outside the declared bool fields),
    non-numeric stage values, an unknown source."""
    missing = [k for k in SCHEMA if k not in d]
    extra = [k for k in d if k not in SCHEMA]
    if missing or extra:
        raise ValueError(
            f"WireRecord schema drift: missing={missing} extra={extra}"
        )
    for k, types in SCHEMA.items():
        if bool in types:
            if not isinstance(d[k], bool):
                raise ValueError(
                    f"WireRecord field {k!r}: {type(d[k]).__name__} "
                    "is not bool"
                )
            continue
        if not isinstance(d[k], types) or isinstance(d[k], bool):
            raise ValueError(
                f"WireRecord field {k!r}: {type(d[k]).__name__} is not "
                f"{'/'.join(t.__name__ for t in types)}"
            )
    for st, v in d["stages"].items():
        if not isinstance(st, str) or isinstance(v, bool) \
                or not isinstance(v, (int, float)):
            raise ValueError(
                f"WireRecord stages entry {st!r}: {v!r} is not a "
                "str -> seconds pair"
            )
    if d["source"] not in ("call", "pipeline"):
        raise ValueError(
            f"WireRecord source {d['source']!r}: want call|pipeline"
        )
    return d


class ClockOffsetEstimator:
    """NTP-style client/server clock-offset estimator (module
    docstring). Thread-safe; keeps a bounded window of (delay, offset)
    samples and answers with the MIN-DELAY sample — the classic NTP
    filter: the tightest round trip bounds the offset best, and a
    congested or retried cycle's loose sample never displaces it."""

    def __init__(self, window: int = 64):
        self._lock = threading.Lock()
        # (delay_s, offset_s); min() keys on delay first by tuple order.
        self._samples: "deque[tuple[float, float]]" = deque(
            maxlen=int(window))

    def add(self, t0: float, t1: float, t2: float,
            t3: float) -> "tuple[float, float] | None":
        """Fold one send/recv/reply/join quadruple (t0/t3 on the client
        clock, t1/t2 on the server clock). Returns (offset_s,
        uncertainty_s) for this sample, or None for an inconsistent
        pairing (server busy exceeding the client window — a retried
        attempt matched against the wrong root). Consistency uses
        DURATIONS only, so it survives arbitrary absolute skew."""
        busy = t2 - t1
        window = t3 - t0
        if busy < 0.0 or window < 0.0 or busy > window:
            return None
        delay = window - busy
        offset = ((t1 - t0) + (t2 - t3)) / 2.0
        with self._lock:
            self._samples.append((delay, offset))
        return offset, delay / 2.0

    def best(self) -> "tuple[float, float] | None":
        """(offset_s, uncertainty_s) of the min-delay sample in the
        window, or None before any consistent sample landed."""
        with self._lock:
            if not self._samples:
                return None
            delay, offset = min(self._samples)
        return offset, delay / 2.0

    def samples(self) -> int:
        with self._lock:
            return len(self._samples)


def _subtree_ids(spans: "list[tracing.Span]", root_id: int) -> "set[int]":
    """Span ids reachable from root_id via parent links (the chosen
    attempt's server-side subtree; a retry's stages parent under a
    DIFFERENT root and must not be double-counted)."""
    children: "dict[int, list[int]]" = {}
    for s in spans:
        children.setdefault(s.parent_id, []).append(s.span_id)
    out = {root_id}
    frontier = [root_id]
    while frontier:
        nxt = []
        for pid in frontier:
            for cid in children.get(pid, ()):
                if cid not in out:
                    out.add(cid)
                    nxt.append(cid)
        frontier = nxt
    return out


def _choose_pair(sends: "list[tracing.Span]",
                 roots: "list[tracing.Span]",
                 t_end: float) -> "tuple[tracing.Span, tracing.Span] | None":
    """The (client send, server root) pairing with the tightest
    duration fit: min over valid pairs of (window - busy). Validity is
    duration-only (skew-proof): the root's busy time must fit inside
    the attempt's client window. For an instant pipeline send (dur 0)
    the window runs to the cycle end t_end."""
    best = None
    best_delay = math.inf
    for send in sends:
        window = send.dur_s if send.dur_s > 0.0 \
            else max(t_end - send.t_wall, 0.0)
        for root in roots:
            delay = window - root.dur_s
            if root.dur_s >= 0.0 and delay >= 0.0 and delay < best_delay:
                best = (send, root)
                best_delay = delay
    return best


def assemble(rid: str, rpc: str, spans: "list[tracing.Span]",
             clock: ClockOffsetEstimator, *,
             bytes_up: int = 0, bytes_down: int = 0,
             source: str = "call") -> "WireRecord | None":
    """One WireRecord from a cycle's spans (module docstring). `spans`
    is the rid's slice of the shared ring — client spans always, the
    server's stage spans whenever the sidecar shares the process ring
    (the in-process sidecar and the loopback-gRPC bench both do).
    Returns None when the rid has no client.send span (nothing was
    sent, or the ring already evicted the cycle)."""
    sends = [s for s in spans if s.name == "client.send"]
    if not sends:
        return None
    sends.sort(key=lambda s: s.t_wall)
    joins = sorted((s for s in spans if s.name == "client.join"),
                   key=lambda s: s.t_wall)
    serializes = [s for s in spans if s.name == "client.serialize"]
    retries = [s for s in spans if s.name == "client.retry"]
    resyncs = sum(1 for s in spans if s.name == "client.resync")
    roots = [s for s in spans
             if s.cat == "server" and s.name == f"server.{rpc}"]

    t0 = sends[0].t_wall
    ser_s = sum(s.dur_s for s in serializes)
    if source == "pipeline" and joins:
        t_end = max(s.end_wall for s in joins)
    else:
        t_end = max(s.end_wall for s in sends)
    # serialize precedes the first send span; it is real cycle wall.
    wall = max(t_end - t0, 0.0) + ser_s

    stages: "dict[str, float]" = {}
    if ser_s > 0.0:
        stages["serialize"] = ser_s
    backoff = sum(s.dur_s for s in retries)
    if backoff > 0.0:
        stages["retry.backoff"] = backoff

    replayed = False
    stitched = False
    offset = 0.0
    uncertainty = -1.0
    pair = _choose_pair(sends, roots, t_end)
    if pair is not None:
        send, root = pair
        stitched = True
        replayed = bool(root.attrs.get("replayed", False))
        p_end = send.end_wall if send.dur_s > 0.0 else t_end
        clock.add(send.t_wall, root.t_wall,
                  root.end_wall, p_end)
        best = clock.best()
        if best is not None:
            offset, uncertainty = best
        subtree = _subtree_ids(spans, root.span_id)
        staged = 0.0
        for s in spans:
            if s.name in SERVER_STAGES and s.span_id in subtree:
                stages[s.name] = stages.get(s.name, 0.0) + s.dur_s
                staged += s.dur_s
        stages["server.other"] = max(root.dur_s - staged, 0.0)
        # Offset-corrected one-way gaps; negative residue (offset error
        # larger than the gap itself) clamps to zero and shows up as a
        # coverage shortfall rather than a negative component.
        stages["send.gap"] = max(root.t_wall - offset - send.t_wall, 0.0)
        stages["reply.gap"] = max(
            p_end - (root.end_wall - offset), 0.0)
    else:
        # No joinable server root (remote sidecar, tracing off there):
        # the middle of the cycle is one unattributed block.
        stages["unknown"] = max(wall - ser_s - backoff, 0.0)
        best = clock.best()
        if best is not None:
            offset, uncertainty = best

    total = sum(stages.values())
    return WireRecord(
        ts=t0, rpc=rpc, rid=rid, source=source,
        attempts=len(sends), resyncs=resyncs,
        replayed=replayed, stitched=stitched,
        wall_s=wall, offset_s=offset, uncertainty_s=uncertainty,
        bytes_up=int(bytes_up), bytes_down=int(bytes_down),
        stages=stages,
        coverage=(total / wall) if wall > 0.0 else 0.0,
    )


class WireLedger:
    """Bounded ring of WireRecords + rolling aggregation + the wire
    sentinel (module docstring).

    registry: where the ledger's metric families live (the sidecar
    passes its per-server registry so wire anomalies render in its
    Metrics rpc). flight/tracer: the FlightRecorder the sentinel fires
    and the span ring it snapshots. min_cycles: rolling-window arming
    threshold. jsonl: optional black-box path. profile_dir: when set,
    an anomaly arms a one-shot jax.profiler device-trace capture for
    the next cycle wrapped in maybe_profile()."""

    def __init__(self, capacity: int = 1024,
                 registry: "pm.Registry | None" = None,
                 flight: "tracing.FlightRecorder | None" = None,
                 tracer: "tracing.TraceCollector | None" = None,
                 min_cycles: int = 32,
                 jsonl: "str | None" = None,
                 profile_dir: "str | None" = None,
                 enabled: bool = True):
        self._lock = threading.Lock()
        self._ring: "deque[WireRecord]" = deque(maxlen=int(capacity))
        self._mint = itertools.count(1)
        self.enabled = enabled
        self.min_cycles = int(min_cycles)
        self.flight = flight
        self.tracer = tracer
        self.clock = ClockOffsetEstimator()
        self._jsonl_path = jsonl
        self._jsonl: "TextIO | None" = None
        self._jsonl_closed = False
        self._io_lock = threading.Lock()
        self._component_names: "set[str]" = set()
        self._bytes_window: "deque[int]" = deque(maxlen=256)
        self.anomalies = 0
        self.bytes_up_total = 0
        self.bytes_down_total = 0
        self.profile_dir = profile_dir
        self._profile_armed = False
        self.profiles: "list[str]" = []
        reg = registry if registry is not None else pm.DEFAULT
        self._h_wall = pm.Histogram(
            "scheduler_wire_wall_seconds",
            "per-cycle client-observed round-trip wall (the wire "
            "sentinel's judged quantity)",
            buckets=pm.DURATION_BUCKETS, registry=reg)
        self._h_comp = pm.Histogram(
            "scheduler_wire_component_seconds",
            "per-cycle wire wall by round-trip component",
            buckets=pm.DURATION_BUCKETS, labelnames=("component",),
            registry=reg)
        self._c_cycles = pm.Counter(
            "scheduler_wire_cycles_total",
            "ledgered wire cycles", ("rpc", "source"), registry=reg)
        self._c_anomalies = pm.Counter(
            "scheduler_wire_anomalies_total",
            "wire-sentinel-flagged cycles by attributed cause",
            ("cause",), registry=reg)

    # -- recording -----------------------------------------------------------

    def observe(self, rec: WireRecord) -> "WireRecord | None":
        """Append one cycle: sentinel check against PRIOR cycles'
        rolling windows, then fold the record into them. Returns the
        (cycle-stamped, anomaly-stamped) record, or None when the
        ledger is disabled."""
        if not self.enabled:
            return None
        cause = self._sentinel(rec)
        rec.anomaly = cause or ""
        rec.cycle = next(self._mint)
        with self._lock:
            self._ring.append(rec)
            self._component_names.update(rec.stages)
            self._bytes_window.append(rec.bytes_up + rec.bytes_down)
            self.bytes_up_total += rec.bytes_up
            self.bytes_down_total += rec.bytes_down
        self._h_wall.observe(rec.wall_s)
        for comp, dur in rec.stages.items():
            self._h_comp.labels(comp).observe(float(dur))
        self._c_cycles.labels(rec.rpc, rec.source).inc()
        if cause:
            self.anomalies += 1
            self._c_anomalies.labels(cause).inc()
            if self.profile_dir is not None:
                self._profile_armed = True
            flight = self.flight
            if flight is not None:
                flight.record("wire_anomaly",
                              self.tracer or tracing.DEFAULT,
                              cause=cause, wire=record_dict(rec),
                              device_trace=(self.profiles[-1]
                                            if self.profiles else None))
        self._write_jsonl(rec)
        return rec

    def _wall_count(self) -> int:
        return int(self._h_wall.labels().count)

    def _sentinel(self, rec: WireRecord) -> "str | None":
        """The wire sentinel (module docstring): None = normal. Wall
        threshold is the NON-interpolated rolling p99 bucket bound;
        attribution is ordered — bytes first (a burst explains every
        downstream component), then the component group with the
        largest excess over its rolling median."""
        if self._wall_count() < self.min_cycles:
            return None
        p99 = self._h_wall.quantile(0.99, interpolate=False)
        if math.isnan(p99) or not rec.wall_s > p99:
            return None
        total_bytes = rec.bytes_up + rec.bytes_down
        with self._lock:
            window = sorted(self._bytes_window)
        if window:
            p95 = window[int(0.95 * (len(window) - 1))]
            # A burst must be SUBSTANTIALLY above the rolling p95 —
            # steady traffic jitters by a few varint bytes per cycle,
            # and that must never out-attribute a real stall.
            if total_bytes > max(1.5 * p95, p95 + 4096):
                return "bytes_burst"
        excess = {"queue": 0.0, "decode": 0.0, "transfer": 0.0}
        for group, comps in (("queue", _QUEUE), ("decode", _DECODE),
                             ("transfer", _TRANSFER)):
            for comp in comps:
                v = rec.stages.get(comp)
                if v is None:
                    continue
                med = self._h_comp.quantile(0.5, comp, interpolate=False)
                if math.isnan(med):
                    med = 0.0
                excess[group] += max(float(v) - med, 0.0)
        # Priority on ties follows the request path: a queue spike
        # usually CAUSES downstream inflation, so it wins equals.
        cause = max(("queue", "decode", "transfer"),
                    key=lambda g: excess[g])
        if excess[cause] <= 0.0:
            return "unknown"
        return cause

    def _write_jsonl(self, rec: WireRecord) -> None:
        if self._jsonl_path is None:
            return
        line = json.dumps(record_dict(rec)) + "\n"
        if self._jsonl is None:
            # Lazy open OUTSIDE the lock (ledger.py discipline): the
            # tiny publish race double-opens at worst; a closed ledger
            # never reopens — late observers drop the line.
            f: "TextIO | None" = open(self._jsonl_path, "a")
            with self._io_lock:
                if self._jsonl is None and not self._jsonl_closed:
                    self._jsonl, f = f, None
            if f is not None:
                f.close()
        with self._io_lock:
            f = self._jsonl
            if f is not None:
                f.write(line)
                f.flush()

    # -- device-trace capture ------------------------------------------------

    @contextlib.contextmanager
    def maybe_profile(self) -> "Iterator[bool]":
        """One-shot jax.profiler device-trace capture armed by the
        previous anomaly (module docstring). Unarmed (the steady
        state) this is two attribute reads; the serving path wraps its
        dispatch region in it unconditionally. Yields whether a
        capture is running so callers can annotate."""
        if not self._profile_armed or self.profile_dir is None:
            yield False
            return
        self._profile_armed = False
        try:
            import jax  # tpl: disable=TPL001(optional dependency: the wire ledger must stay importable from jax-free layers; this import only runs on the one cycle after an armed anomaly)
        except ImportError:
            yield False
            return
        path = os.path.join(self.profile_dir,
                            f"wire_cycle_{next(self._mint)}")
        try:
            jax.profiler.start_trace(path)
        except Exception:
            yield False
            return
        try:
            yield True
        finally:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self.profiles.append(path)
            (self.tracer or tracing.DEFAULT).record(
                "wire.device_trace", cat="wire", path=path)

    # -- reading -------------------------------------------------------------

    def records(self, last: "int | None" = None) -> "list[WireRecord]":
        with self._lock:
            out = list(self._ring)
        if last is not None and last >= 0:
            out = out[len(out) - min(last, len(out)):]
        return out

    def _hist_export(self, hist: pm.Histogram,
                     *labels: Any) -> "dict[str, Any]":
        counts = hist.series_counts(*labels)
        return dict(le=list(hist.buckets), counts=counts)

    def statusz(self, last: int = 32) -> "dict[str, Any]":
        """The Statusz `wire` panel: rolling p50/p99 per component and
        for the cycle wall, byte totals, the current clock offset with
        its uncertainty, mean stitched coverage, anomaly counts, the
        last-N records, and RAW bucket counts (tools/statusz.py merges
        counts across replicas and re-derives fleet quantiles)."""
        recs = self.records(last)
        all_recs = self.records()
        anomalies: "dict[str, int]" = {}
        rpcs: "dict[str, int]" = {}
        for r in all_recs:
            rpcs[r.rpc] = rpcs.get(r.rpc, 0) + 1
            if r.anomaly:
                anomalies[r.anomaly] = anomalies.get(r.anomaly, 0) + 1
        with self._lock:
            comp_names = sorted(self._component_names)
            bytes_up, bytes_down = self.bytes_up_total, self.bytes_down_total
        components: "dict[str, Any]" = {}
        for comp in comp_names:
            components[comp] = dict(
                p50_ms=_ms(self._h_comp.quantile(0.50, comp)),
                p99_ms=_ms(self._h_comp.quantile(0.99, comp)),
                hist=self._hist_export(self._h_comp, comp),
            )
        stitched = [r for r in all_recs if r.stitched]
        best = self.clock.best()
        return dict(
            cycles=self._wall_count(),
            anomalies=anomalies,
            anomalies_total=self.anomalies,
            rpcs=rpcs,
            bytes=dict(up=bytes_up, down=bytes_down),
            offset_ms=_ms(best[0]) if best is not None else None,
            uncertainty_ms=_ms(best[1]) if best is not None else None,
            coverage_frac=(
                round(sum(r.coverage for r in stitched) / len(stitched), 4)
                if stitched else None),
            wall=dict(
                p50_ms=_ms(self._h_wall.quantile(0.50)),
                p99_ms=_ms(self._h_wall.quantile(0.99)),
                hist=self._hist_export(self._h_wall),
            ),
            components=components,
            device_traces=list(self.profiles),
            records=[record_dict(r) for r in recs],
        )

    def close(self) -> None:
        """Release the JSONL black box (idempotent; later observers
        drop their lines instead of reopening)."""
        with self._io_lock:
            f, self._jsonl = self._jsonl, None
            self._jsonl_closed = True
        if f is not None:
            f.close()


def to_chrome(records: "list[WireRecord]",
              pid: int = 9) -> "list[dict[str, Any]]":
    """Perfetto breakdown track: one lane of back-to-back "X" events
    per cycle, components laid out in request-path order from the
    cycle's ts, so the per-cycle decomposition reads as a waterfall
    alongside the span tracks trace.to_chrome emits. Merge the two
    event lists into one traceEvents array."""
    events: "list[dict[str, Any]]" = []
    for rec in records:
        t = rec.ts
        order = [c for c in COMPONENT_ORDER if c in rec.stages]
        order += [c for c in sorted(rec.stages) if c not in order]
        for comp in order:
            dur = rec.stages[comp]
            events.append(dict(
                name=comp, cat="wire", ph="X",
                ts=t * 1e6, dur=max(dur, 0.0) * 1e6,
                pid=pid, tid=f"wire:{rec.rpc}",
                args=dict(cycle=rec.cycle, rid=rec.rid,
                          coverage=round(rec.coverage, 3),
                          anomaly=rec.anomaly),
            ))
            t += max(dur, 0.0)
    return events


def _ms(v: float) -> "float | None":
    return None if math.isnan(v) else round(v * 1e3, 3)


# Process default: clients fall back here unless handed the sidecar's
# own ledger (the server builds one per service so its wire panel and
# anomaly counters render in its own Statusz/Metrics rpcs).
# `set_enabled(False)` is the global off switch.
DEFAULT = WireLedger()


def set_enabled(on: bool) -> None:
    DEFAULT.enabled = bool(on)
