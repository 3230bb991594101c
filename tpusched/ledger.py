"""Cycle flight ledger (round 18, ISSUE 13).

Every serving cycle — a HostScheduler batch, a sidecar Assign, a
`pipeline.warm_cycle_stream` delta cycle, a sim-driver tick's cycle —
emits ONE structured `CycleRecord` into a bounded in-memory ring with
rolling aggregation. The record joins what was previously scattered
across three point-in-time surfaces: per-request spans (trace.py),
per-process counters/histograms (metrics.py), and per-decision explain
records — so a p99 spike finally answers "was that a retrace, a round
blow-up, a churn burst, or a preemption storm?" instead of being a
bare histogram bucket.

Three pieces:

  * `CompileWatcher` — counts XLA cache misses per (engine, program,
    shape-class) with compile wall time. Engine wraps its jit entry
    points (`Engine._traced_jit`): the FIRST dispatch of a new shape
    class runs trace+lower+compile synchronously, so its wall time IS
    the compile cost; later dispatches are one set-membership check.
    Cycle emitters read `COMPILES.counters()` before/after a cycle to
    attribute retraces to the cycle that paid them. Events carry a
    `cause` ("serve" by default; `Engine.prewarm` tags its boot-time
    traces "prewarm" via tpusched.shapeclass.CAUSE_PREWARM) so the
    shape-class prewarm + persistent-cache layer (ROADMAP item 3) never
    reads as a serving regression — a prewarm runs before any cycle, and
    the timeline still shows the split for forensics.
  * `CycleLedger` — the ring + rolling-window aggregation, reusing
    metrics.Histogram buckets plus the bucket-interpolated
    `Histogram.quantile()` for the rolling p50/p99 per stage, churn
    p95, and round median. Optionally persists every record as one
    JSONL line (the black box a postmortem replays).
  * the regression sentinel — a cycle whose solve time exceeds the
    rolling p99 (non-interpolated: the covering bucket bound, so a
    flag means "above everything the layout resolved so far") is
    attributed by correlating the record's OWN fields, in order:
    retrace present -> "compile"; rounds above the rolling median ->
    "round_growth"; churn above its p95 -> "churn_burst"; a
    preemption tranche active -> "preemption"; else "unknown". Each
    anomaly bumps `scheduler_cycle_anomalies_total{cause}` and fires
    the attached FlightRecorder, so the anomaly carries its causal
    trace, not just a counter bump.

Schema discipline: `SCHEMA` is the single authority on a record's
fields; `validate_record` is the twin contract between live serving
and virtual-time sim replays (tests/test_ledger.py pins schema
equality), and what tools/check.py's `statusz` smoke validates against
a real sidecar. Record timestamps ride the EMITTER's clock — wall time
on the sidecar, the host's injected clock in-process, so sim replays
carry virtual timestamps.

Stdlib-only on purpose (like trace.py): the ledger must be importable
from every layer, including ones that never touch jax.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import math
import threading
import time
from collections import deque
from typing import Any, TextIO

from tpusched import metrics as pm
from tpusched import trace as tracing

# Churn (records per cycle) and commit-round bucket layouts: discrete
# pow2-ish bounds so the sentinel's non-interpolated quantiles land on
# values a real cycle can actually have.
CHURN_BUCKETS = tuple(float(1 << i) for i in range(17))      # 1 .. 65536
ROUND_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0)

ANOMALY_CAUSES = ("compile", "round_growth", "churn_burst",
                  "preemption", "unknown")


@dataclasses.dataclass
class CycleRecord:
    """One scheduling cycle's flight-ledger entry (module docstring).
    `cycle` is assigned by the ledger at observe() time; `anomaly` is
    written by the sentinel ("" = none). `stages` holds per-stage wall
    seconds joined from the cycle's spans (decode, delta.apply,
    dispatch, fetch.join, reply.*, engine.fetch on the sidecar;
    build/solve/bind on the host) — stage NAMES follow the trace span
    names so a ledger anomaly points at the same name a trace shows."""

    ts: float = 0.0            # emitter clock (virtual under the sim)
    source: str = ""           # host | sidecar | pipeline | sim | bench
    pods: int = 0              # batch size offered to the solver
    nodes: int = 0
    running: int = 0
    placed: int = 0
    evicted: int = 0
    churn: int = 0             # changed records feeding this cycle
    frontier: int = 0          # incremental warm solves; 0 otherwise
    rounds: int = 0            # commit rounds
    warm_path: str = "cold"    # cold | warm | incremental
    solve_s: float = 0.0       # the quantity the sentinel judges
    stages: "dict[str, float]" = dataclasses.field(default_factory=dict)
    compiles: int = 0          # XLA cache misses paid inside the cycle
    compile_s: float = 0.0     # their compile wall time
    queue_depth: int = 0       # pending-queue depth at cycle start
    cycle: int = 0
    anomaly: str = ""


# Field name -> accepted types; THE schema authority (docstring).
SCHEMA: "dict[str, tuple[type, ...]]" = {
    "cycle": (int,),
    "ts": (int, float),
    "source": (str,),
    "pods": (int,),
    "nodes": (int,),
    "running": (int,),
    "placed": (int,),
    "evicted": (int,),
    "churn": (int,),
    "frontier": (int,),
    "rounds": (int,),
    "warm_path": (str,),
    "solve_s": (int, float),
    "stages": (dict,),
    "compiles": (int,),
    "compile_s": (int, float),
    "queue_depth": (int,),
    "anomaly": (str,),
}


def record_dict(rec: CycleRecord) -> "dict[str, Any]":
    """Plain dict in SCHEMA key order (JSONL lines, Statusz payloads)."""
    d = dataclasses.asdict(rec)
    return {k: d[k] for k in SCHEMA}


def validate_record(d: "dict[str, Any]") -> "dict[str, Any]":
    """Schema check for one record dict (the sim-vs-live twin contract
    and the check.py statusz smoke). Raises ValueError on any drift:
    missing/extra keys, wrong field types, non-numeric stage values."""
    missing = [k for k in SCHEMA if k not in d]
    extra = [k for k in d if k not in SCHEMA]
    if missing or extra:
        raise ValueError(
            f"CycleRecord schema drift: missing={missing} extra={extra}"
        )
    for k, types in SCHEMA.items():
        if not isinstance(d[k], types) or isinstance(d[k], bool):
            raise ValueError(
                f"CycleRecord field {k!r}: {type(d[k]).__name__} is not "
                f"{'/'.join(t.__name__ for t in types)}"
            )
    for st, v in d["stages"].items():
        if not isinstance(st, str) or isinstance(v, bool) \
                or not isinstance(v, (int, float)):
            raise ValueError(
                f"CycleRecord stages entry {st!r}: {v!r} is not a "
                "str -> seconds pair"
            )
    if d["warm_path"] not in ("cold", "warm", "incremental"):
        raise ValueError(
            f"CycleRecord warm_path {d['warm_path']!r}: want "
            "cold|warm|incremental"
        )
    return d


class CompileWatcher:
    """Process-wide XLA cache-miss ledger (module docstring). Keys are
    opaque (the engine builds (engine-nonce, program, shape-tuple));
    `shape` is the human label the Statusz compile timeline shows.
    Lock bodies are O(set-op) only; BOTH stores are bounded — the
    event deque caps the timeline, and the seen-key set evicts
    oldest-first past `seen_cap` so a process that churns through
    engines (chaos fleets, promotion cycles, long test runs) cannot
    leak one key per engine forever. An evicted key's shape re-counts
    as a compile if it ever recurs — at 4096 keys that is far beyond
    any live engine's real shape set."""

    def __init__(self, capacity: int = 256, seen_cap: int = 4096):
        self._lock = threading.Lock()
        self._seen: "dict[Any, None]" = {}  # insertion-ordered key set
        self._seen_cap = int(seen_cap)
        self._events: "deque[dict[str, Any]]" = deque(maxlen=int(capacity))
        self._by_cause: "dict[str, int]" = {}
        self.total = 0
        self.compile_s_total = 0.0
        self.enabled = True

    def known(self, key: Any) -> bool:
        with self._lock:
            return key in self._seen

    def note(self, key: Any, fn: str, shape: str, dur_s: float,
             cause: str = "serve") -> bool:
        """Record one first-dispatch (compile) event; False when a
        racing first caller already recorded this key. `cause` labels
        WHY the program was traced ("serve" for a request-path cache
        miss, "prewarm" for Engine.prewarm boot work) — the split the
        cycle sentinel's "compile" attribution and the prewarm tests
        read back through cause_counts()/timeline()."""
        ev = dict(ts=time.time(), fn=fn, shape=shape,
                  compile_s=round(float(dur_s), 6), cause=str(cause))
        with self._lock:
            if key in self._seen:
                return False
            self._seen[key] = None
            while len(self._seen) > self._seen_cap:
                self._seen.pop(next(iter(self._seen)))
            self.total += 1
            self.compile_s_total += float(dur_s)
            self._by_cause[str(cause)] = self._by_cause.get(str(cause), 0) + 1
            self._events.append(ev)
            return True

    def counters(self) -> "tuple[int, float]":
        """(total compiles, total compile seconds) — cycle emitters
        read this before/after a cycle to attribute retraces."""
        with self._lock:
            return self.total, self.compile_s_total

    def cause_counts(self) -> "dict[str, int]":
        """Monotonic per-cause compile totals (unlike the capped event
        timeline): {"prewarm": boot traces, "serve": request-path
        cache misses, ...}."""
        with self._lock:
            return dict(self._by_cause)

    def timeline(self) -> "list[dict[str, Any]]":
        with self._lock:
            return list(self._events)


class CycleLedger:
    """Bounded ring of CycleRecords + rolling aggregation + the
    regression sentinel (module docstring).

    registry: where the ledger's metric families live (the sidecar
    passes its per-server registry so anomalies render in its Metrics
    rpc; None = the process-default registry). flight/tracer: the
    FlightRecorder the sentinel fires and the span ring it snapshots
    (tracer None = the process default at fire time). min_cycles: how
    many cycles the rolling windows need before the sentinel arms.
    jsonl: optional path — every record appends one JSON line (the
    black box); close() releases the file."""

    def __init__(self, capacity: int = 1024,
                 registry: "pm.Registry | None" = None,
                 flight: "tracing.FlightRecorder | None" = None,
                 tracer: "tracing.TraceCollector | None" = None,
                 min_cycles: int = 32,
                 jsonl: "str | None" = None,
                 watcher: "CompileWatcher | None" = None,
                 enabled: bool = True):
        self._lock = threading.Lock()
        self._ring: "deque[CycleRecord]" = deque(maxlen=int(capacity))
        self._mint = itertools.count(1)
        self.enabled = enabled
        self.min_cycles = int(min_cycles)
        self.flight = flight
        self.tracer = tracer
        self.watcher = watcher if watcher is not None else COMPILES
        self._jsonl_path = jsonl
        self._jsonl: "TextIO | None" = None
        self._jsonl_closed = False
        # Serializes black-box writes (a TextIOWrapper is not safe for
        # concurrent multi-chunk writes) and the close() handoff.
        self._io_lock = threading.Lock()
        self._stage_names: "set[str]" = set()
        self.anomalies = 0
        reg = registry if registry is not None else pm.DEFAULT
        self._h_solve = pm.Histogram(
            "scheduler_cycle_solve_seconds",
            "per-cycle solve wall (the sentinel's judged quantity)",
            buckets=pm.DURATION_BUCKETS, registry=reg)
        self._h_stage = pm.Histogram(
            "scheduler_cycle_stage_seconds",
            "per-cycle stage wall by trace span name",
            buckets=pm.DURATION_BUCKETS, labelnames=("stage",),
            registry=reg)
        self._h_churn = pm.Histogram(
            "scheduler_cycle_churn_records",
            "changed records feeding each cycle",
            buckets=CHURN_BUCKETS, registry=reg)
        self._h_rounds = pm.Histogram(
            "scheduler_cycle_rounds",
            "commit rounds per ledgered cycle",
            buckets=ROUND_BUCKETS, registry=reg)
        self._c_cycles = pm.Counter(
            "scheduler_cycles_total",
            "ledgered scheduling cycles", ("source", "warm_path"),
            registry=reg)
        self._c_anomalies = pm.Counter(
            "scheduler_cycle_anomalies_total",
            "sentinel-flagged cycles by attributed cause", ("cause",),
            registry=reg)
        self._c_compiles = pm.Counter(
            "scheduler_cycle_compiles_total",
            "XLA cache misses attributed to ledgered cycles",
            registry=reg)

    # -- recording -----------------------------------------------------------

    def observe(self, rec: CycleRecord) -> "CycleRecord | None":
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
        self._h_solve.observe(rec.solve_s)
        for stage, dur in rec.stages.items():
            with self._lock:
                self._stage_names.add(stage)
            self._h_stage.labels(stage).observe(float(dur))
        self._h_churn.observe(rec.churn)
        self._h_rounds.observe(rec.rounds)
        self._c_cycles.labels(rec.source, rec.warm_path).inc()
        if rec.compiles:
            self._c_compiles.inc(rec.compiles)
        if cause:
            self.anomalies += 1
            self._c_anomalies.labels(cause).inc()
            flight = self.flight
            if flight is not None:
                flight.record("cycle_anomaly",
                              self.tracer or tracing.DEFAULT,
                              cause=cause, cycle=record_dict(rec))
        self._write_jsonl(rec)
        return rec

    def _solve_count(self) -> int:
        child = self._h_solve.labels()
        return int(child.count)

    def _sentinel(self, rec: CycleRecord) -> "str | None":
        """The regression sentinel (module docstring): None = normal.
        All thresholds are NON-interpolated bucket bounds — exceeding
        one means exceeding everything the layout resolved so far, so
        a flag is never an interpolation artifact."""
        if self._solve_count() < self.min_cycles:
            return None
        p99 = self._h_solve.quantile(0.99, interpolate=False)
        if math.isnan(p99) or not rec.solve_s > p99:
            return None
        if rec.compiles > 0:
            return "compile"
        med_rounds = self._h_rounds.quantile(0.5, interpolate=False)
        if not math.isnan(med_rounds) and rec.rounds > med_rounds:
            return "round_growth"
        churn_p95 = self._h_churn.quantile(0.95, interpolate=False)
        if not math.isnan(churn_p95) and rec.churn > churn_p95:
            return "churn_burst"
        if rec.evicted > 0:
            return "preemption"
        return "unknown"

    def _write_jsonl(self, rec: CycleRecord) -> None:
        if self._jsonl_path is None:
            return
        line = json.dumps(record_dict(rec)) + "\n"
        if self._jsonl is None:
            # Lazy open OUTSIDE the lock (file open must not serialize
            # observers); the tiny publish race double-opens at worst,
            # and the loser's handle is closed immediately. A closed
            # ledger never reopens — late observers drop the line.
            f: "TextIO | None" = open(self._jsonl_path, "a")
            with self._io_lock:
                if self._jsonl is None and not self._jsonl_closed:
                    self._jsonl, f = f, None
            if f is not None:
                f.close()
        # Write under the io lock: concurrent handlers must not
        # interleave partial lines into the black box, and a racing
        # close() must not yank the handle mid-write.
        with self._io_lock:
            f = self._jsonl
            if f is not None:
                f.write(line)
                f.flush()

    # -- reading -------------------------------------------------------------

    def records(self, last: "int | None" = None) -> "list[CycleRecord]":
        with self._lock:
            out = list(self._ring)
        if last is not None and last >= 0:
            out = out[len(out) - min(last, len(out)):]
        return out

    def _hist_export(self, hist: pm.Histogram, *labels: Any) -> "dict[str, Any]":
        counts = hist.series_counts(*labels)
        return dict(le=list(hist.buckets), counts=counts)

    def statusz(self, last: int = 32) -> "dict[str, Any]":
        """The Statusz payload: rolling p50/p99 per stage, warm-path
        mix, churn/round aggregates, the compile timeline, anomaly
        counts, the last-N records, and the RAW bucket counts
        (tools/statusz.py merges counts across replicas and
        re-derives fleet quantiles via metrics.bucket_quantile)."""
        recs = self.records(last)
        all_recs = self.records()
        warm_mix: "dict[str, int]" = {}
        anomalies: "dict[str, int]" = {}
        sources: "dict[str, int]" = {}
        for r in all_recs:
            warm_mix[r.warm_path] = warm_mix.get(r.warm_path, 0) + 1
            sources[r.source] = sources.get(r.source, 0) + 1
            if r.anomaly:
                anomalies[r.anomaly] = anomalies.get(r.anomaly, 0) + 1
        with self._lock:
            stage_names = sorted(self._stage_names)
        stages: "dict[str, Any]" = {}
        for stage in stage_names:
            stages[stage] = dict(
                p50_ms=_ms(self._h_stage.quantile(0.50, stage)),
                p99_ms=_ms(self._h_stage.quantile(0.99, stage)),
                hist=self._hist_export(self._h_stage, stage),
            )
        total, compile_s = self.watcher.counters()
        return dict(
            cycles=self._solve_count(),
            anomalies=anomalies,
            anomalies_total=self.anomalies,
            warm_mix=warm_mix,
            sources=sources,
            solve=dict(
                p50_ms=_ms(self._h_solve.quantile(0.50)),
                p99_ms=_ms(self._h_solve.quantile(0.99)),
                hist=self._hist_export(self._h_solve),
            ),
            churn=dict(
                p50=_r(self._h_churn.quantile(0.50)),
                p95=_r(self._h_churn.quantile(0.95)),
                hist=self._hist_export(self._h_churn),
            ),
            rounds=dict(
                p50=_r(self._h_rounds.quantile(0.50)),
                hist=self._hist_export(self._h_rounds),
            ),
            compiles=dict(total=total,
                          compile_s_total=round(compile_s, 6),
                          timeline=self.watcher.timeline()),
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


def _ms(v: float) -> "float | None":
    return None if math.isnan(v) else round(v * 1e3, 3)


def _r(v: float) -> "float | None":
    return None if math.isnan(v) else round(v, 3)


# Process defaults: the engine's jit wrappers feed COMPILES; host /
# pipeline / sim emitters fall back to DEFAULT unless handed their own
# ledger (the sidecar builds one per service so its anomalies render in
# its own Metrics rpc). `set_enabled(False)` is the global off switch.
COMPILES = CompileWatcher()
DEFAULT = CycleLedger()


def set_enabled(on: bool) -> None:
    DEFAULT.enabled = bool(on)
    COMPILES.enabled = bool(on)
