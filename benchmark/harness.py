"""One run of one benchmark cell, from set-up to the result line.

The system under test is the served path a host shim uses: an
in-process sidecar (`tpusched.rpc.server.make_server`, floor buckets
pinned to the cell's own) driven by `SchedulerClient` + `DeltaSession`
with `packed_ok=True`. The benchmark hands it the wire snapshot its
generator keeps, and keeps for itself a copy of every cycle's input
and reply, which the reference judges once the window has closed
(check.py). The configuration names its generator and its reference
by path; `plugins` loads both and holds them to their protocols
(workload.py's and reference.py's docstrings).

Traffic is one general loop over the mix's parameters:
  * arrival "closed": before each cycle the queue is topped up to
    `backlog` pods (one scheduler leader waiting on each cycle);
  * arrival "poisson": pods arrive at `rate_per_s` on a seeded
    schedule, join the queue up to its bucket, and wait client-side
    beyond it (an open loop: their wait counts).
Before each cycle the generator binds what the last reply placed,
completes running pods (`completions`: a number, or "bound" = as
many as were bound), and gives `qos_churn` of the queue a new
observed availability.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import logging
import math
import os
import pathlib
import sys
import time

import numpy as np

import check
import devtrace

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = pathlib.Path(__file__).resolve().parent
# Fixed paths inside the checkout: the compile cache's path is part of
# its key, and the profiler's capture is read and deleted in the run.
CACHE_DIR = ROOT / ".xla_cache"
TRACE_DIR = ROOT / ".bench_trace"
# The traced run profiles the window's first seconds.
PROFILE_S = 3.0
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


def prepare_env() -> None:
    """Before JAX is imported: the compile cache inside the checkout
    (taken by tpusched.shapeclass.enable_persistent_cache), and no TPU
    runtime logs under /tmp."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))


class NoAccelerator(RuntimeError):
    pass


class PluginError(ValueError):
    """A configuration's generator or reference is missing or does not
    keep its protocol."""


CLUSTER_METHODS = ("add_pending", "bind", "complete", "qos_churn",
                   "static_view", "cycle_view")
CLUSTER_ATTRS = ("msg", "node_index", "pend_names", "pend_slot", "n_pend",
                 "pend_cap", "n_run")
REFERENCE_CALLS = ("sequential", "commit_rank_differs")


def load_module(kind: str, rel: str):
    """The module at `rel` (a path from the repo root), loaded as
    devtrace.read_metric loads metric readers."""
    path = (ROOT / rel).resolve()
    if not (path.is_relative_to(ROOT) and path.is_file()):
        raise PluginError(f"the configuration's {kind} {rel!r} is not a "
                          f"file in the repo ({ROOT})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def plugins(spec: dict):
    """(generator, reference) modules the cell's configuration names,
    each checked for what its protocol needs before anything runs."""
    cfg = spec["config"]
    missing = [k for k in ("generator", "reference") if k not in cfg]
    if missing:
        raise PluginError(f"configuration {cfg.get('name')!r} names no "
                          f"{' and no '.join(missing)}")
    gen = load_module("generator", cfg["generator"])
    ref = load_module("reference", cfg["reference"])
    cls = getattr(gen, "Cluster", None)
    lacks = ["Cluster"] if cls is None else [
        m for m in CLUSTER_METHODS if not callable(getattr(cls, m, None))]
    if lacks:
        raise PluginError(f"generator {cfg['generator']!r} lacks "
                          f"{', '.join(lacks)}")
    calls = list(REFERENCE_CALLS)
    if cfg["guarantee"] == "valid":
        audits = getattr(ref, "VALIDITY_AUDITS", None)
        calls += ["VALIDITY_AUDITS"] if audits is None else list(audits)
    lacks = [c for c in calls if not callable(getattr(ref, c, None))]
    if lacks:
        raise PluginError(f"reference {cfg['reference']!r} lacks "
                          f"{', '.join(lacks)}")
    return gen, ref


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(workload: str) -> dict:
    """The cell's entry, configuration, traffic mix and metric lists,
    found by name from BENCHMARK.json."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    return dict(
        cell=cell,
        config=load_json(ROOT / cfg_entry["file"]),
        traffic=load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)],
    )


def cell_buckets(spec: dict):
    """The sidecar's floor buckets, pinned to the cell's own sizes (and
    to the configuration's further `buckets`, by Buckets field) so that
    nothing in the window grows a bucket (and compiles)."""
    from tpusched.config import Buckets

    mix, cfg = spec["traffic"], spec["config"]
    return dataclasses.replace(
        Buckets.minimal(1, 1, 1), pods=int(mix["pending_bucket"]),
        nodes=int(cfg["node_bucket"]),
        running_pods=int(mix["running_bucket"]),
        **{k: int(v) for k, v in cfg.get("buckets", {}).items()})


class _CompileCounter:
    """JAX's own compile and trace events (every jit, not only the
    engine's), counted while `armed`."""

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _dur, **_kw):
        if self.armed and name in COMPILE_EVENTS:
            self.count += 1


_COUNTER: "_CompileCounter | None" = None


def _compile_counter() -> _CompileCounter:
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = _CompileCounter()
    return _COUNTER


class _Null:
    """Swallows the sidecar's per-batch JSON log."""

    def write(self, _):
        return 0

    def flush(self):
        pass


@dataclasses.dataclass
class Cycle:
    t_send: float
    t_done: float
    placed: int
    record: dict           # what check.py judges
    held: int = 0          # pods held unplaced before it (queue_thirds)


class Driver:
    """Sidecar + client + generator of one run."""

    def __init__(self, spec: dict, seed: int, tracer):
        from tpusched import EngineConfig
        from tpusched.rpc.client import DeltaSession, SchedulerClient
        from tpusched.rpc.server import make_server

        self.spec = spec
        self.mix = spec["traffic"]
        self.cfg = EngineConfig.from_dict(spec["config"]["engine"])
        gen, self.reference = plugins(spec)
        self.cluster = gen.Cluster(spec["config"], self.mix, seed)
        lacks = [a for a in CLUSTER_ATTRS if not hasattr(self.cluster, a)]
        if lacks:
            raise PluginError(f"generator {spec['config']['generator']!r}: "
                              f"its Cluster lacks {', '.join(lacks)}")
        self.static = self.cluster.static_view()
        self.server, port, self.svc = make_server(
            config=self.cfg, buckets=cell_buckets(spec), log_stream=_Null(),
            tracer=tracer)
        self.server.start()
        self.client = SchedulerClient(f"127.0.0.1:{port}", tracer=tracer)
        self.sess = DeltaSession(self.client)
        self.node_index = self.cluster.node_index
        self.last = (np.zeros(0, np.int64), np.zeros(0, np.int64))
        self.arrival_t: dict[str, float] = {}
        # control.py and tests/benchmark: a hook that alters the reply
        self.faults = None

    def close(self) -> None:
        if self.server is None:
            return
        self.client.close()
        self.server.stop(None)
        self.svc.close()
        self.server = None

    # -- one cycle -----------------------------------------------------

    def generate(self, t_clock: float | None, arrivals=None) -> set:
        """Between two cycles: bind, complete, arrive, churn. Returns
        the names the client knows it touched."""
        c, mix = self.cluster, self.mix
        slots, nodes = self.last
        names = [c.pend_names[s] for s in slots]
        bound = c.bind(names, [int(n) for n in nodes])
        comp = mix["completions"]
        c.complete(len(bound) if comp == "bound" else int(comp))
        if mix["arrival"] == "closed":
            new = c.add_pending(int(mix["backlog"]) - c.n_pend)
        else:
            due = arrivals.due(t_clock)
            k = min(due, c.pend_cap - c.n_pend)
            new = c.add_pending(k)
            for j, nm in enumerate(new):
                self.arrival_t[nm] = arrivals.time_of(arrivals.admitted + j)
            arrivals.admitted += k
        qos = c.qos_churn(float(mix["qos_churn"]))
        return set(bound) | set(new) | set(qos)

    def assign(self, changed: "set | None") -> tuple:
        """One Assign through DeltaSession; returns (node by slot, key
        by slot, malformed) once the client holds the placements."""
        from tpusched.rpc.client import assign_response_arrays

        c = self.cluster
        resp = self.sess.assign(c.msg, packed_ok=True, changed=changed)
        if self.faults is not None:
            resp = self.faults(resp)
        pod_names, node_names, node_idx, _, key = assign_response_arrays(resp)
        n = c.n_pend
        node = np.full(n, -1, np.int64)
        keys = np.full(n, np.iinfo(np.int32).max, np.int64)
        malformed = len(pod_names) != n or len(node_idx) != n
        try:
            slot = np.fromiter((c.pend_slot[p] for p in pod_names), np.int64,
                               len(pod_names))
            nmap = np.fromiter((self.node_index[x] for x in node_names),
                               np.int64, len(node_names))
        except KeyError:
            return node, keys, True
        if len(np.unique(slot)) != len(slot):
            malformed = True
        ni = np.asarray(node_idx, np.int64)
        placed = ni >= 0
        node[slot[placed]] = nmap[ni[placed]]
        keys[slot] = key
        return node, keys, malformed

    def cycle(self, changed, keep: bool) -> Cycle:
        rec = self.cluster.cycle_view() if keep else None
        t0 = time.perf_counter()
        node, keys, malformed = self.assign(changed)
        t1 = time.perf_counter()
        placed = np.flatnonzero(node >= 0)
        self.last = (placed, node[placed])
        if keep:
            rec.update(node=node, key=keys, malformed=malformed)
        return Cycle(t0, t1, len(placed), rec)


class Arrivals:
    """Seeded Poisson arrival schedule (open loop), in seconds from the
    start of the arrival clock."""

    def __init__(self, seed: int, rate: float, horizon_s: float):
        rng = np.random.default_rng([seed, 0xA221])
        n = int(rate * horizon_s * 1.2) + 1000
        self.t = np.cumsum(rng.exponential(1.0 / rate, n))
        self.t0 = None
        self.admitted = 0

    def start(self, t: float) -> None:
        self.t0 = t

    def due(self, now: float) -> int:
        arrived = int(np.searchsorted(self.t, now - self.t0, "right"))
        if arrived >= len(self.t):
            raise RuntimeError("arrival schedule exhausted")
        return arrived - self.admitted

    def time_of(self, i: int) -> float:
        return self.t0 + float(self.t[i])


def warm_scatters(svc, trees, min_rows: int, max_rows: int) -> int:
    """Compile the delta path's row scatters of the snapshot's row
    `trees` (the mix's `warm_trees`) for every pow2 size from
    `min_rows` to `max_rows` (the churn sizes the mix can reach in its
    window), on the session's own arrays (identical rows written back),
    so none compiles in the window. Returns how many programs were
    warmed."""
    import jax

    from tpusched.kernels.assign import scatter_rows

    session = _session(svc)
    if session is None:
        raise RuntimeError("want exactly one device session")
    snap = session.device.snap
    n = 0
    for tree in (getattr(snap, name) for name in trees):
        host = jax.tree.map(np.asarray, tree)
        size = min_rows
        while size <= max_rows:
            idx = np.zeros(size, np.int32)
            rows = jax.tree.map(lambda a: a[idx], host)
            jax.block_until_ready(scatter_rows(tree, idx, rows))
            n += 1
            size *= 2
    return n


def _session(svc):
    """The sidecar's one device-resident lineage (it may be keyed
    under more than one snapshot id), or None."""
    sessions = {id(s): s for s in svc._sessions.values()}
    return next(iter(sessions.values())) if len(sessions) == 1 else None


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, t_start: float | None = None,
             faults=None, say=None) -> dict:
    """One run; returns the result object (the contract's last line).
    `faults(driver)` (tests only) may break the timed path."""
    t_start = time.perf_counter() if t_start is None else t_start
    say = say or (lambda **kw: print(json.dumps(kw), flush=True))
    prepare_env()
    import jax

    devs = jax.devices()
    chips = int(spec["cell"]["chips"])
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoAccelerator(
            f"need {chips} TPU chip(s); JAX sees {len(devs)} "
            f"{devs[0].platform!r} device(s)")
    device = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                  count=len(devs))
    say(phase="device", **device)
    from tpusched import ledger
    from tpusched.shapeclass import enable_persistent_cache
    from tpusched.trace import TraceCollector

    enable_persistent_cache()
    # No LRU eviction: it reads an `-atime` file beside every entry, and
    # a cache directory that came without them (the chip machine's)
    # then fails every write, so every run compiled again.
    jax.config.update("jax_compilation_cache_max_size", -1)
    counter = _compile_counter()
    tracer = TraceCollector(capacity=1 << 20, seed=seed)
    say(phase="jax_ready", s=time.perf_counter() - t_start)
    drv = Driver(spec, seed, tracer)
    fallbacks = _NativeFallbacks()
    logging.getLogger("tpusched.native").addHandler(fallbacks)
    try:
        return _run(drv, spec, seed, seconds, trace, device, t_start,
                    counter, tracer, ledger, fallbacks, faults, say)
    finally:
        logging.getLogger("tpusched.native").removeHandler(fallbacks)
        drv.close()


class _GcPauses:
    """Python's garbage collections while `armed`: how many of the
    oldest generation, and the seconds all of them took (a diagnostic
    of the host path's tail)."""

    def __init__(self):
        self.armed = False
        self.full = 0
        self.seconds = 0.0
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if not self.armed:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t
            self.full += info["generation"] == 2

    def close(self):
        gc.callbacks.remove(self._on)


class _NativeFallbacks(logging.Handler):
    def __init__(self):
        super().__init__(logging.WARNING)
        self.count = 0

    def emit(self, record):
        self.count += 1


def setup(drv: Driver, seed: int, seconds: float):
    """Set-up after the cluster and the sidecar: the full send, the
    session seed, the scatter shapes and the warm-up cycles (a closed
    mix until the cluster saturates, an open one for `warmup_s` of
    arrivals at its rate). Returns (stages in seconds, the arrival
    schedule or None, warm-up cycles)."""
    mix = drv.mix
    closed = mix["arrival"] == "closed"
    c = drv.cluster
    stages = {}
    if closed:
        c.add_pending(int(mix["backlog"]))
    t = time.perf_counter()
    drv.cycle(None, keep=False)
    stages["full_send"] = time.perf_counter() - t
    t = time.perf_counter()
    drv.cycle(drv.generate(None, None) if closed else
              set(c.qos_churn(float(mix["qos_churn"]))), keep=False)
    stages["session_seed"] = time.perf_counter() - t
    t = time.perf_counter()
    warm_scatters(drv.svc, mix["warm_trees"], *mix["warm_rows"])
    stages["scatter_warm"] = time.perf_counter() - t
    arrivals = None
    if not closed:
        arrivals = Arrivals(seed, float(mix["rate_per_s"]),
                            float(mix["warmup_s"]) + seconds + 60.0)
        arrivals.start(time.perf_counter())
    n_warm = 0
    t_w = time.perf_counter()
    while True:
        n_warm += 1
        cyc = drv.cycle(drv.generate(time.perf_counter(), arrivals),
                        keep=False)
        if closed:
            if (n_warm >= int(mix["warmup_cycles"])
                    and cyc.placed <= int(mix["saturated_placed"])):
                break
            if n_warm >= int(mix["warmup_cycles_max"]):
                raise RuntimeError("cluster did not saturate in warm-up")
        elif time.perf_counter() - t_w >= float(mix["warmup_s"]):
            break
    stages["warmup_cycles"] = time.perf_counter() - t_w
    return stages, arrivals, n_warm


@dataclasses.dataclass
class Window:
    cycles: list
    gen_s: float           # the generator's time between Assigns
    t0: float
    t_last: float          # the last cycle's end
    paused: float          # the profiler's write-out, not measured

    @property
    def window_s(self) -> float:
        return self.t_last - self.t0 - self.paused


def window(drv: Driver, seconds: float, arrivals, prof=None,
           say=None) -> Window:
    """`seconds` of cycles, each input and reply kept for check.py.
    With `prof` (devtrace.Capture) the first PROFILE_S are profiled."""
    cycles: list[Cycle] = []
    gen_s = 0.0
    prof_stop = None
    t0 = time.perf_counter()
    if prof is not None:
        prof.start()
        prof_stop = t0 + min(PROFILE_S, seconds)
    t_end = t0 + seconds
    paused = 0.0
    while True:
        tg = time.perf_counter()
        if tg >= t_end:
            break
        if prof_stop is not None and tg >= prof_stop:
            # Writing the capture out takes tens of seconds: the window
            # (and the open loop's arrival clock) stands still meanwhile.
            prof.stop()
            prof_stop = None
            pause = time.perf_counter() - tg
            paused += pause
            t_end += pause
            if arrivals is not None:
                arrivals.t0 += pause
            tg = time.perf_counter()
        with devtrace.annotate("bench.generator"):
            changed = drv.generate(tg, arrivals)
        held = drv.cluster.n_pend + (arrivals.due(tg) if arrivals else 0)
        try:
            with devtrace.annotate("bench.assign"):
                cyc = drv.cycle(changed, keep=True)
        except Exception as e:  # noqa: BLE001 — a failed Assign is counted
            if say is not None:
                say(phase="assign_failed", error=f"{type(e).__name__}: {e}")
            cycles.append(Cycle(tg, time.perf_counter(), 0, dict(failed=True),
                                held))
            drv.last = (np.zeros(0, np.int64), np.zeros(0, np.int64))
            continue
        cyc.held = held
        # Generator time: the mutation plus the benchmark's copy of the
        # cycle's input, everything between two Assigns.
        gen_s += cyc.t_send - tg
        cycles.append(cyc)
    if prof_stop is not None:
        prof.stop()
    t_last = cycles[-1].t_done if cycles else time.perf_counter()
    return Window(cycles, gen_s, t0, t_last, paused)


def queue_thirds(cycles: list) -> tuple[float, float]:
    """The pods the client held unplaced before each cycle (queued, and
    waiting at the client for room), averaged over the window's first
    and last thirds of cycles: an open loop the system keeps up with
    holds as many at the end as at the start."""
    held = np.array([x.held for x in cycles], np.float64)
    k = max(1, len(held) // 3)
    return float(held[:k].mean()), float(held[-k:].mean())


def _run(drv: Driver, spec, seed, seconds, trace, device, t_start,
         counter, tracer, ledger, fallbacks, faults, say) -> dict:
    import jax

    mix = spec["traffic"]
    closed = mix["arrival"] == "closed"
    c = drv.cluster
    t_cluster = time.perf_counter() - t_start
    stages, arrivals, n_warm = setup(drv, seed, seconds)
    stages = dict(cluster=t_cluster, **stages)
    setup_s = time.perf_counter() - t_start
    sess0 = (drv.sess.full_sends, drv.sess.delta_sends, drv.sess.fallbacks)
    hits0 = drv.svc.session_hits
    session = _session(drv.svc)
    rebuilds0 = session.device.rebuilds if session else 0
    say(phase="setup", setup_s=setup_s, stages_s=stages,
        warmup_cycles=n_warm, running=c.n_run, pending=c.n_pend)
    if faults is not None:
        faults(drv)

    # The window.
    tracer.clear()
    comp0 = ledger.COMPILES.counters()[0]
    counter.count = 0
    counter.armed = True
    gcp = _GcPauses()
    gcp.armed = True
    prof = devtrace.Capture(TRACE_DIR) if trace else None
    win = window(drv, seconds, arrivals, prof, say)
    cycles, t0, t_last = win.cycles, win.t0, win.t_last
    counter.armed = False
    gcp.armed = False
    gcp.close()
    compiles = counter.count + (ledger.COMPILES.counters()[0] - comp0)
    window_s = win.window_s
    spans = tracer.spans()
    session = _session(drv.svc)
    rebuilds = (session.device.rebuilds - rebuilds0) if session else -1
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:int(spec["cell"]["chips"])])
    health = drv.client.health()
    sends = (drv.sess.full_sends - sess0[0], drv.sess.delta_sends - sess0[1],
             drv.sess.fallbacks - sess0[2])
    held = queue_thirds(cycles) if cycles else (math.nan, math.nan)
    say(phase="window", cycles=len(cycles), window_s=window_s,
        compiles_in_window=compiles, device_state_rebuilds=rebuilds,
        full_sends=sends[0], delta_sends=sends[1], fallbacks=sends[2],
        session_hits=drv.svc.session_hits - hits0,
        native_fallbacks=fallbacks.count,
        gc_full=gcp.full, gc_s=gcp.seconds,
        generator_share=win.gen_s / window_s if window_s > 0 else None,
        held_first_third=held[0], held_last_third=held[1],
        serving_path=health.serving_path, backend=health.backend,
        running=c.n_run, pending=c.n_pend)

    # End-to-end numbers, from the window's cycles.
    ok_cycles = [x for x in cycles if not x.record.get("failed")]
    lat_ms = np.array([(x.t_done - x.t_send) * 1e3 for x in ok_cycles])
    e2e = {"setup_s": setup_s}
    if len(lat_ms):
        e2e["cycle_p90_ms"] = float(np.percentile(lat_ms, 90))
    if window_s > 0:
        e2e["pods_placed_per_s"] = sum(x.placed for x in ok_cycles) / window_s
    if not closed:
        e2e["pod_latency_p95_ms"] = pod_latency_p95(
            drv, arrivals, cycles, t0, t_last)
    say(phase="placed", pods_placed=sum(x.placed for x in ok_cycles),
        cycles_ok=len(ok_cycles), cycle_ms=[round(v, 1) for v in lat_ms])

    run = dict(cycles=ok_cycles, spans=spans, window_s=window_s,
               profile=None)
    breakdown = None
    busy = None
    if prof is not None:
        red = prof.reduce(spans)
        run["profile"] = red
        breakdown = red["breakdown"]
        busy = (red["busy_s"], red["window_s"])
        prof.cleanup()

    # Correctness, once the window has closed, the peak is read and the
    # sidecar's state is freed.
    drv.close()
    t = time.perf_counter()
    checks = check.judge(spec, drv.reference, drv.static, cycles, seed)
    say(phase="check", s=time.perf_counter() - t)
    correct = all(v <= lim for v, lim in checks.values())
    if trace:
        names = [m["name"] for m in spec["per_layer"]]
        values = {}
        for name in names:
            v = devtrace.read_metric(BENCH, name, run)
            if v is not None:
                values[name] = v
        units = {m["name"]: m["unit"] for m in spec["per_layer"]}
    else:
        values = {m["name"]: e2e[m["name"]] for m in spec["end_to_end"]
                  if m["name"] in e2e}
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
    device = dict(device, memory_peak_bytes=peak)
    if busy is not None:
        device.update(busy_s=busy[0], window_s=busy[1])
    out = dict(correct=bool(correct), attempted=len(cycles),
               failed=len(cycles) - len(ok_cycles),
               metrics={k: {"value": v, "unit": units[k]}
                        for k, v in values.items()},
               device=device)
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def pod_latency_p95(drv: Driver, arrivals: Arrivals, cycles, t0: float,
                     t_last: float) -> float:
    """95th percentile over every pod that arrived in the window: from
    its scheduled arrival to the client holding its placement, or to
    the window's close if it was never placed (still queued, or still
    waiting at the client for room in the queue)."""
    done: dict[str, float] = {}
    for x in cycles:
        rec = x.record
        if rec.get("failed"):
            continue
        names = rec["pend"]["names"]
        for s in np.flatnonzero(rec["node"] >= 0):
            done.setdefault(str(names[s]), x.t_done)
    lat = [done.get(nm, t_last) - t for nm, t in drv.arrival_t.items()
           if t >= t0]
    due = arrivals.admitted + arrivals.due(t_last)
    lat += [t_last - arrivals.time_of(i)
            for i in range(arrivals.admitted, due)
            if arrivals.time_of(i) >= t0]
    return float(np.percentile(np.array(lat) * 1e3, 95)) if lat else math.nan
