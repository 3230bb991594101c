"""End-to-end request tracing (round 9, ISSUE 4 tentpole).

A `TraceCollector` is a lock-cheap ring buffer of `Span` records —
named, timed stage intervals attached to a `trace_id` (the wire
`request_id`). Every stage boundary of the serving path emits one:
client send/retry/resync (rpc/client.py), gate queue wait, coalescer
fuse/wait, decode, device delta-apply (+H2D bytes), solve dispatch,
fetch join, reply pack (rpc/server.py), the engine's background fetch
(engine.py), device rebuilds (device_state.py), injected faults
(faults.py), and kube watch reconnects (kube.py).

Design constraints, in order:

  * ZERO overhead when disabled: ``span()`` is one attribute read and
    returns a shared no-op context manager; ``record()`` returns
    immediately. No thread, no allocation, no lock on the disabled
    path — tracing must be safe to leave compiled into every hot path.
  * Lock-cheap when enabled: one short lock around a deque append.
    Spans are immutable-after-finish plain records; readers snapshot
    under the same lock. The collector NEVER spawns threads
    (tests/conftest.py thread_leak_check pins this).
  * Seedable ids: trace ids are ``<seeded-prefix>-<counter>`` so tests
    and chaos twins get reproducible identities; span ids are a
    process-wide monotone counter (itertools.count — atomic in
    CPython).
  * Cross-thread, cross-wire stitching: spans carry an explicit
    trace_id; WITHIN a thread, nested ``span()`` blocks auto-parent
    through a per-collector thread-local stack, and code dispatching
    work to another thread captures ``current()`` and passes it to
    ``record(ctx=...)`` (engine fetch worker). Across the wire the
    client stamps its trace_id into the request's ``request_id`` field
    and its active span id into ``parent_span``; the server roots its
    spans there (absent id => server-minted), so client and server
    rings merge into one causal trace per request.

Export: ``to_chrome(spans)`` renders Chrome/Perfetto trace-event JSON
(``tools/tracez.py``); ``span_dict``/``spans()`` feed the sidecar's
Debugz rpc. The `FlightRecorder` snapshots the ring (plus caller
counters) on failure events — watchdog trips, ladder demotions, resync
storms — so every degradation event carries its causal trace instead
of being a bare counter bump.
"""

from __future__ import annotations

import itertools
import random
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable

# Process-wide span id mint: itertools.count.__next__ is atomic in
# CPython, so span ids need no lock and stay unique across collectors.
_SPAN_IDS = itertools.count(1)


@dataclass
class Span:
    trace_id: str       # wire request_id ("" = untraced event)
    span_id: int
    parent_id: int      # 0 = root
    name: str           # stage name ("decode", "gate.wait", ...)
    cat: str            # "client" | "server" | "engine" | "device" | ...
    t_wall: float       # epoch seconds at span start
    dur_s: float
    thread: str
    attrs: "dict[str, Any]" = field(default_factory=dict)

    @property
    def end_wall(self) -> float:
        """Epoch seconds at span end (the wire ledger stitches cycle
        bounds and one-way gaps from span endpoints — round 19)."""
        return self.t_wall + self.dur_s


def span_dict(s: Span) -> "dict[str, Any]":
    return dict(
        trace_id=s.trace_id, span_id=s.span_id, parent_id=s.parent_id,
        name=s.name, cat=s.cat, t_wall=s.t_wall, dur_s=s.dur_s,
        thread=s.thread, attrs=dict(s.attrs),
    )


class _NoopSpan:
    """Shared disabled-path context manager: supports the same surface
    live spans do (attrs mutation, span_id read) so call sites need no
    enabled-check of their own."""

    __slots__ = ()
    span_id = 0
    attrs: "dict[str, Any]" = {}  # writes land here and are discarded; shared is fine

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc: object) -> bool:
        return False


_NOOP = _NoopSpan()


class _NoopLaps:
    """Disabled-path twin of `Laps`: shared, records nothing."""

    __slots__ = ()

    def lap(self, name: str) -> None:
        pass

    def close(self) -> None:
        pass


_NOOP_LAPS = _NoopLaps()


class Laps:
    """Back-to-back stage spans over straight-line code too long to
    indent under one `with` per stage: `lap(name)` ends the running
    stage and starts `name`, `close()` ends the last one. Each stage is
    recorded when it ends, through `TraceCollector.record`, under this
    thread's innermost open span. A name may recur (one stage whose
    work runs in two stretches)."""

    __slots__ = ("_col", "_cat", "_name", "_t0")

    def __init__(self, col: "TraceCollector", cat: str):
        self._col = col
        self._cat = cat
        self._name: "str | None" = None
        self._t0 = 0.0

    def lap(self, name: str) -> None:
        self.close()
        self._name = name
        self._t0 = time.perf_counter()

    def close(self) -> None:
        if self._name is None:
            return
        self._col.record(self._name, time.perf_counter() - self._t0,
                         cat=self._cat)
        self._name = None


class _LiveSpan:
    """One open span; finishing (context exit) appends the immutable
    record to the collector ring."""

    __slots__ = ("_col", "name", "cat", "trace_id", "parent_id",
                 "span_id", "attrs", "_t_wall", "_t0")

    def __init__(self, col: "TraceCollector", name: str, cat: str,
                 trace_id: "str | None", parent_id: "int | None",
                 attrs: "dict[str, Any]"):
        self._col = col
        self.name = name
        self.cat = cat
        self.trace_id = trace_id
        self.parent_id = parent_id
        self.span_id = next(_SPAN_IDS)
        self.attrs = attrs

    def __enter__(self) -> "_LiveSpan":
        col = self._col
        stack = col._stack()
        if self.trace_id is None:
            # Inherit identity from the enclosing span on this thread;
            # with no enclosure this is an untraced event stream ("").
            if stack:
                self.trace_id = stack[-1][0]
                if self.parent_id is None:
                    self.parent_id = stack[-1][1]
            else:
                self.trace_id = ""
        elif self.parent_id is None and stack \
                and stack[-1][0] == self.trace_id:
            self.parent_id = stack[-1][1]
        if self.parent_id is None:
            self.parent_id = 0
        stack.append((self.trace_id, self.span_id))
        self._t_wall = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, et: Any, ev: Any, tb: Any) -> bool:
        dur = time.perf_counter() - self._t0
        stack = self._col._stack()
        if stack and stack[-1][1] == self.span_id:
            stack.pop()
        if et is not None:
            self.attrs.setdefault("error", f"{et.__name__}: {ev}")
        self._col._append(Span(
            trace_id=self.trace_id, span_id=self.span_id,
            parent_id=self.parent_id, name=self.name, cat=self.cat,
            t_wall=self._t_wall, dur_s=dur,
            thread=threading.current_thread().name, attrs=self.attrs,
        ))
        return False


class TraceCollector:
    """Ring-buffered span collector (module docstring)."""

    def __init__(self, capacity: int = 4096, seed: "int | None" = None,
                 enabled: bool = True):
        self._lock = threading.Lock()
        self._ring: "deque[Span]" = deque(maxlen=int(capacity))
        self._tls = threading.local()
        self.enabled = enabled
        self._prefix = f"{random.Random(seed).getrandbits(32):08x}"
        self._mint = itertools.count(1)

    # -- id minting ----------------------------------------------------------

    def new_trace_id(self) -> str:
        """Seeded-prefix + counter: unique per collector, reproducible
        under a pinned seed."""
        return f"{self._prefix}-{next(self._mint)}"

    # -- recording -----------------------------------------------------------

    def _stack(self) -> "list[tuple[str, int]]":
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _append(self, span: Span) -> None:
        with self._lock:
            self._ring.append(span)

    def span(self, name: str, cat: str = "server",
             trace_id: "str | None" = None,
             parent_id: "int | None" = None,
             **attrs: Any) -> "_LiveSpan | _NoopSpan":
        """Context manager timing a stage. trace_id=None inherits from
        the enclosing span on this thread (or records untraced)."""
        if not self.enabled:
            return _NOOP
        return _LiveSpan(self, name, cat, trace_id, parent_id, attrs)

    def request(self, trace_id: str, parent_id: int = 0,
                name: str = "request", cat: str = "server",
                **attrs: Any) -> "_LiveSpan | _NoopSpan":
        """Root span with explicit wire identity (server handlers)."""
        if not self.enabled:
            return _NOOP
        return _LiveSpan(self, name, cat, trace_id, int(parent_id), attrs)

    def laps(self, cat: str = "server") -> "Laps | _NoopLaps":
        """Consecutive stage spans (see `Laps`); a shared no-op when
        disabled."""
        if not self.enabled:
            return _NOOP_LAPS
        return Laps(self, cat)

    def record(self, name: str, dur_s: float = 0.0, cat: str = "event",
               ctx: "tuple[str, int] | None" = None,
               **attrs: Any) -> None:
        """Retroactive span ending NOW with the given duration — for
        stages whose start wasn't wrapped (gate wait, cross-thread
        fetches). ctx: (trace_id, parent_span_id) captured earlier via
        current(); None inherits from this thread's stack."""
        if not self.enabled:
            return
        if ctx is None:
            stack = self._stack()
            ctx = stack[-1] if stack else ("", 0)
        dur_s = max(float(dur_s), 0.0)
        self._append(Span(
            trace_id=ctx[0], span_id=next(_SPAN_IDS), parent_id=ctx[1],
            name=name, cat=cat, t_wall=time.time() - dur_s, dur_s=dur_s,
            thread=threading.current_thread().name, attrs=attrs,
        ))

    def current(self) -> "tuple[str, int] | None":
        """(trace_id, span_id) of this thread's innermost open span —
        capture before handing work to another thread."""
        stack = self._stack()
        return stack[-1] if stack else None

    # -- reading -------------------------------------------------------------

    def spans(self, trace_id: "str | None" = None) -> "list[Span]":
        """Snapshot of the ring, oldest first; optionally one trace."""
        with self._lock:
            out = list(self._ring)
        if trace_id is not None:
            out = [s for s in out if s.trace_id == trace_id]
        return out

    def durations(self, trace_id: str) -> "dict[str, float]":
        """Total completed-span seconds per stage name for one trace —
        the cycle ledger's stage-timing join (round 18, ISSUE 13): a
        CycleRecord's `stages` dict is this, so a ledger anomaly names
        the same stages a trace shows. Open (unfinished) spans are
        absent by construction; disabled collectors return {}."""
        out: "dict[str, float]" = {}
        for s in self.spans(trace_id):
            out[s.name] = out.get(s.name, 0.0) + s.dur_s
        return out

    def traces(self, last: int = 16) -> "dict[str, list[Span]]":
        """The most recent `last` traces (trace_id -> spans, oldest
        span first within each), by recency of each trace's newest
        span. Untraced events ("") are excluded. last <= 0 returns
        nothing (a negative slice would invert the bound)."""
        if int(last) <= 0:
            return {}
        groups: "dict[str, list[Span]]" = {}
        for s in self.spans():
            if s.trace_id:
                # dict preserves insertion order; re-inserting on every
                # span keeps ids ordered by their NEWEST span.
                groups[s.trace_id] = groups.pop(s.trace_id, [])
                groups[s.trace_id].append(s)
        ids = list(groups)[-int(last):]
        return {t: groups[t] for t in ids}

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()


def to_chrome(spans: "Iterable[Span | dict[str, Any]]",
              pid: int = 1) -> "list[dict[str, Any]]":
    """Chrome/Perfetto trace-event list ("X" complete events, ts/dur in
    microseconds) from spans or span_dicts. Load via chrome://tracing
    or ui.perfetto.dev."""
    events: "list[dict[str, Any]]" = []
    for s in spans:
        d = span_dict(s) if isinstance(s, Span) else s
        args = dict(d["attrs"])
        args["trace_id"] = d["trace_id"]
        args["span_id"] = d["span_id"]
        if d["parent_id"]:
            args["parent_span"] = d["parent_id"]
        events.append(dict(
            name=d["name"], cat=d["cat"] or "span", ph="X",
            ts=d["t_wall"] * 1e6, dur=max(d["dur_s"], 0.0) * 1e6,
            pid=pid, tid=d["thread"], args=args,
        ))
    return events


class FlightRecorder:
    """Snapshots a collector's ring on failure events (watchdog trip,
    ladder demotion, resync storm) so the operator gets the CAUSAL
    trace of a degradation, not just a counter bump. Keeps the last
    `capacity` dumps; thread-safe; spawns no threads."""

    def __init__(self, capacity: int = 8):
        self._lock = threading.Lock()
        self._dumps: "deque[dict[str, Any]]" = deque(maxlen=int(capacity))
        self.trips = 0
        # Optional tpusched.explain.ExplainCollector (round 12): when
        # attached AND enabled, every dump also carries the last-N
        # decision records, so a watchdog trip / ladder demotion ships
        # the DECISIONS in flight alongside the causal trace.
        self.decisions: Any = None
        self.decisions_last = 4

    def record(self, reason: str, collector: TraceCollector,
               **extra: Any) -> "dict[str, Any]":
        dump: "dict[str, Any]" = dict(
            ts=time.time(), reason=reason, extra=extra,
            spans=[span_dict(s) for s in collector.spans()],
        )
        dec = self.decisions
        if dec is not None and getattr(dec, "enabled", False):
            from tpusched import explain as _explain  # tpl: disable=TPL001(trace must stay stdlib-only at import; explain pulls the jax kernels stack)

            dump["decisions"] = [
                _explain.record_dict(r, include_auction=True)
                for r in dec.last(self.decisions_last)
            ]
        with self._lock:
            self._dumps.append(dump)
            self.trips += 1
        return dump

    def dumps(self) -> "list[dict[str, Any]]":
        with self._lock:
            return list(self._dumps)


class StormDetector:
    """Sliding-window event-rate trigger: hit() returns True when the
    `n`th event lands within `window_s` — and then resets, so one storm
    yields ONE flight-recorder dump, not one per event. Clock-injectable
    for deterministic tests."""

    def __init__(self, n: int = 4, window_s: float = 5.0,
                 clock: "Callable[[], float]" = time.monotonic):
        self._lock = threading.Lock()
        self._clock = clock
        self.n = int(n)
        self.window_s = float(window_s)
        self._times: "deque[float]" = deque(maxlen=self.n)
        self.storms = 0

    def hit(self) -> bool:
        now = self._clock()
        with self._lock:
            self._times.append(now)
            if (len(self._times) == self.n
                    and now - self._times[0] <= self.window_s):
                self._times.clear()
                self.storms += 1
                return True
            return False


# Process default: clients, the sidecar, and the event streams
# (device_state rebuilds, faults, kube reconnects) all share this
# collector unless handed their own, so an in-process client+server run
# yields ONE stitched ring. `set_enabled(False)` is the global off switch.
DEFAULT = TraceCollector()


def set_enabled(on: bool) -> None:
    DEFAULT.enabled = bool(on)
