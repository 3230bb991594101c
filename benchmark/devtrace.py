"""Device trace capture and its reduction to numbers.

A traced run captures a stretch of its window with JAX's profiler and
reads the `.xplane.pb` back with `jax.profiler.ProfileData` (nothing
but JAX). What it keeps:

  * device planes (`/device:TPU:<n>`): the "XLA Ops" line gives every
    operation's interval on the chip; "XLA Modules" gives each program
    execution, named after its jit (the engine's packed solve is
    `jit__solve_packed...`);
  * busy time: the union of op intervals on each device, averaged over
    the devices used; idle = stretch - busy;
  * idle gaps: the spaces between busy intervals, each labelled by the
    host stage that covers most of it: the program's own spans (server
    stages such as `delta.apply`, from the run's TraceCollector), the
    benchmark's `bench.generator`, or the client's unspanned work
    inside `bench.assign` (the DeltaSession diff has no span);
  * the clock: a `bench.clock` annotation entered right after the
    capture starts, whose wall time the run notes, ties the program's
    `time.time()` spans to the trace's nanoseconds.

Per-layer metric readers (metrics/<name>.py) take their numbers from
what `reduce` returns and from the run's spans and cycles.
"""

from __future__ import annotations

import contextlib
import glob
import importlib.util
import pathlib
import shutil
import time

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CLOCK = "bench.clock"
TOP = 10


def annotate(name: str):
    """A host annotation in the profiler's trace (free when no capture
    is running)."""
    try:
        import jax

        return jax.profiler.TraceAnnotation(name)
    except ImportError:
        return contextlib.nullcontext()


class Capture:
    """One profiler capture into a fixed directory inside the checkout."""

    def __init__(self, root: pathlib.Path):
        self.root = pathlib.Path(root)
        self.t_start = self.t_stop = None
        self.clock_wall = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.root, ignore_errors=True)
        # No Python function tracer: it multiplies the host path's time
        # several times over (every call is an event). The host tracer
        # stays on for the annotations.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.root), profiler_options=opts)
        with jax.profiler.TraceAnnotation(CLOCK):
            self.clock_wall = time.time()
        self.t_start = time.perf_counter()

    def stop(self) -> None:
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    def path(self) -> str:
        found = glob.glob(str(self.root / "**" / "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise RuntimeError(f"{len(found)} xplane files under {self.root}")
        return found[0]

    def reduce(self, spans) -> dict:
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(self.path())
        return reduce_profile(_planes(pd), self.t_stop - self.t_start,
                              self.clock_wall, spans)

    def cleanup(self) -> None:
        shutil.rmtree(self.root, ignore_errors=True)


def _planes(pd) -> list[dict]:
    """ProfileData -> plain data: [{name, lines: [{name, events:
    [(name, start_ns, dur_ns)]}]}] (what the recorded test trace
    holds, so the reduction is tested without the chip)."""
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append(dict(name=line.name, events=[
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events]))
        out.append(dict(name=plane.name, lines=lines))
    return out


def _short(op: str) -> str:
    """An "XLA Ops" event is named by its HLO text; keep the
    instruction's name ("%fusion.12 = f32[...] ..." -> "fusion.12")."""
    return op.split(" = ", 1)[0].lstrip("%")


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def reduce_profile(planes: list[dict], stretch_s: float,
                   clock_wall: "float | None", spans=()) -> dict:
    """Numbers from one capture (see the module docstring)."""
    devices = [p for p in planes if p["name"].startswith(DEVICE_PREFIX)
               and p["name"][len(DEVICE_PREFIX):].isdigit()]
    if not devices:
        raise RuntimeError("no TPU device plane in the trace")
    host_events = [ev for p in planes if not p["name"].startswith("/device")
                   for ln in p["lines"] for ev in ln["events"]]
    clock_ns = next((s for n, s, _ in host_events if n == CLOCK), None)
    busy_total = 0.0
    ops: dict[str, float] = {}
    modules: dict[str, list[float]] = {}
    merged_first = None
    lo = hi = None
    for dev in devices:
        lines = {ln["name"]: ln["events"] for ln in dev["lines"]}
        evs = lines.get(OPS_LINE, [])
        for name, s, d in evs:
            op = _short(name)
            ops[op] = ops.get(op, 0.0) + d * 1e-9
        for name, s, d in lines.get(MODULES_LINE, []):
            modules.setdefault(name, []).append(d * 1e-9)
        merged = _union([(s, s + d) for _, s, d in evs])
        busy_total += sum(e - s for s, e in merged) * 1e-9
        if merged_first is None:
            merged_first = merged
    busy_s = busy_total / len(devices)
    # Stretch bounds on the trace clock: from the clock annotation to
    # its start plus the capture's host-timed length.
    if clock_ns is not None:
        lo, hi = clock_ns, clock_ns + stretch_s * 1e9
    elif merged_first:
        lo, hi = merged_first[0][0], merged_first[-1][1]
    gaps = []
    if merged_first and lo is not None:
        edges = [(lo, lo)] + [(s, e) for s, e in merged_first
                              if e > lo and s < hi] + [(hi, hi)]
        for (_, e0), (s1, _) in zip(edges, edges[1:]):
            if s1 > e0:
                gaps.append((e0, s1))
    host = _host_intervals(host_events, spans, clock_ns, clock_wall)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return dict(
        busy_s=busy_s, window_s=stretch_s,
        device_ops=sorted(ops.items(), key=lambda kv: -kv[1]),
        modules={k: (len(v), sum(v)) for k, v in modules.items()},
        gaps_s=[(g1 - g0) * 1e-9 for g0, g1 in gaps],
        breakdown=dict(
            device_ops=[[n, s] for n, s in sorted(
                ops.items(), key=lambda kv: -kv[1])[:TOP]],
            idle_gaps=[[_label(g0, g1, host), (g1 - g0) * 1e-9]
                       for g0, g1 in longest],
        ),
    )


def _host_intervals(host_events, spans, clock_ns, clock_wall):
    """(start_ns, end_ns, name) of what the host was doing, on the
    trace clock: the benchmark's annotations and the program's spans."""
    out = [(s, s + d, n) for n, s, d in host_events if n.startswith("bench.")
           and n != CLOCK]
    if clock_ns is not None and clock_wall is not None:
        for sp in spans:
            t = getattr(sp, "t_wall", None)
            if t is None:
                t, dur, name = sp["t_wall"], sp["dur_s"], sp["name"]
            else:
                dur, name = sp.dur_s, sp.name
            s = clock_ns + (t - clock_wall) * 1e9
            out.append((s, s + dur * 1e9, name))
    return out


# Spans that contain a whole cycle's host work: a gap is named after
# what ran inside them, not after them.
CONTAINERS = ("bench.assign", "server.Assign", "client.send")
UNSPANNED = "client (no span: DeltaSession diff, reply read)"


def _length(intervals: list[tuple[float, float]]) -> float:
    return sum(e - s for s, e in _union(intervals))


def _label(g0: float, g1: float, host) -> str:
    """What the host did in the gap [g0, g1): the stage span covering
    most of it; time inside `bench.assign` that no stage span covers
    is the client's own unspanned work. Stage spans nest (`delta.apply`
    holds the `apply.*` stages), so that time is taken against the
    union of the stage spans, each moment counted once."""
    cover: dict[str, float] = {}
    inside, staged = [], []
    for s, e, name in host:
        lo, hi = max(s, g0), min(e, g1)
        if hi <= lo:
            continue
        if name == "bench.assign":
            inside.append((lo, hi))
        elif name not in CONTAINERS:
            cover[name] = cover.get(name, 0.0) + hi - lo
            staged.append((lo, hi))
    rest = _length(inside + staged) - _length(staged)
    if rest > 0:
        cover[UNSPANNED] = cover.get(UNSPANNED, 0.0) + rest
    if not cover:
        return "host (no span open)"
    return max(cover, key=cover.get)


def read_metric(bench_dir: pathlib.Path, name: str, run: dict):
    """Load metrics/<name>.py and read the metric from the run; None
    when the reader finds nothing to read."""
    path = pathlib.Path(bench_dir) / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)
