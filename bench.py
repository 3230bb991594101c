#!/usr/bin/env python
"""Benchmark harness (SURVEY.md C15).

Default run (`python bench.py`) benches ALL BASELINE configs
(BASELINE.json:"configs"[1..4]; config[0] runs through the host shim when
available) plus fast-vs-parity divergence rows, and prints one JSON line
per metric on stdout, diagnostics on stderr. The HEADLINE metric — p99
schedule-cycle latency for the 10k pending-pods x 5k nodes batched solve
(BASELINE.json:"metric") — is printed LAST so a last-line parse reads it.

The headline is PARITY mode: exact stock kube-scheduler semantics (the
north star conjoins "<500 ms p99" with "placement parity"; EngineConfig
defaults to mode="parity" for the same reason). Fast mode — the opt-in
bounded-rounds throughput mode — is emitted alongside with a `_fast`
metric suffix. vs_baseline = 500 ms north-star budget / measured p99
(>1.0 means under budget); it is reported ONLY for metrics at the
10k x 5k headline shape — other shapes have no baseline and emit null.

Usage: python bench.py [--pods N] [--nodes N] [--iters N] [--only NAME]
       [--what score|score_top1|solve] [--mode both|fast|parity]
       [--serve-clients K] [--serve-cycles N]
       [--serve-what both|assign|score]
NAME in {headline, pairwise, gangs, preemption, pipeline, e2e, wire,
serving, divergence, warm, ledger, multichip}. The multichip bench
(sharded serving over the (p,n) device mesh, incl. the 100k x 50k
sharded headline) runs only when >1 device is visible and skips with a
stderr note otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


TARGET_P99_S = 0.5  # BASELINE.json:"north_star": <500 ms p99 @ 10k x 5k

# Per-call floor of this process's backend (dispatch + result fetch of
# a trivial program, and D2H bandwidth), filled by measure_transport()
# before each bench section and attached to every latency line as
# context, so a shift in the floor is not read as an engine change.
TRANSPORT: dict = {}


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def measure_transport(iters: int = 12) -> dict:
    """Fixed per-call round trip (trivial jit call + materialize) and
    D2H bandwidth (fresh 8 MB result) of the current backend."""
    import jax

    x = jax.device_put(np.float32(1.0))
    f = jax.jit(lambda v: v + 1.0)
    np.asarray(f(x))  # compile
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        np.asarray(f(x))
        ts.append(time.perf_counter() - t0)
    rtt_ms = float(np.percentile(ts, 50) * 1e3)
    rtt_p99_ms = float(np.percentile(ts, 99) * 1e3)
    big = jax.jit(
        lambda k: jax.random.uniform(k, (1024, 2048))  # 8 MB fresh result
    )
    key = jax.random.PRNGKey(0)
    out = big(key)
    out.block_until_ready()
    t0 = time.perf_counter()
    a = np.asarray(out)
    dt = time.perf_counter() - t0
    d2h = a.nbytes / 1e6 / max(dt - rtt_ms / 1e3, 1e-6)
    TRANSPORT.update(rtt_ms=round(rtt_ms, 2),
                     rtt_p99_ms=round(rtt_p99_ms, 2),
                     d2h_mbps=round(d2h, 1))
    log(f"transport: result-fetch RTT p50 {rtt_ms:.1f}ms / "
        f"p99 {rtt_p99_ms:.1f}ms, D2H ~{d2h:.0f} MB/s (subtract RTT "
        f"from any p50 below to estimate device compute)")
    return TRANSPORT


def materialize(out):
    """Force completion by reading the results back to the host (the
    host needs them anyway, so the D2H copy is part of the timing)."""
    import jax

    return jax.tree.map(np.asarray, out)


def bench_fn(fn, iters: int, warmup: int = 3, label: str = ""):
    for _ in range(warmup):
        materialize(fn())
    times = []
    for i in range(iters):
        t0 = time.perf_counter()
        materialize(fn())
        dt = time.perf_counter() - t0
        times.append(dt)
        if dt > 1.0:
            log(f"  [{label}] outlier iter {i}: {dt:.2f}s")
    times = np.asarray(times)
    return dict(
        p50=float(np.percentile(times, 50)),
        p90=float(np.percentile(times, 90)),
        p99=float(np.percentile(times, 99)),
        max=float(times.max()),
        mean=float(times.mean()),
        iters=iters,
    )


def emit(metric: str, stats: dict, extra: dict | None = None,
         against_budget: bool = False):
    """One JSON line on stdout; full stats on stderr. Every latency
    metric carries BOTH the wall numbers and floor-subtracted estimates
    (`device_ms` ≈ p50 − rtt, `device_p99_ms` ≈ p99 − rtt, where rtt is
    measure_transport's per-call floor); they are estimates, not device
    time from a trace. vs_baseline is the 500 ms
    north-star budget over the floor-subtracted p99, reported ONLY when
    against_budget (the metric is at the 10k x 5k headline shape the
    budget talks about); other shapes have no baseline and report null
    rather than implying one (round-2 verdict, weak #2)."""
    rtt_ms = TRANSPORT.get("rtt_ms", 0.0)
    device_ms = max(stats["p50"] * 1e3 - rtt_ms, 0.0)
    device_p99_ms = max(stats["p99"] * 1e3 - rtt_ms, 0.0)
    log(f"{metric}: p50={stats['p50']*1e3:.1f}ms p90={stats['p90']*1e3:.1f}ms "
        f"p99={stats['p99']*1e3:.1f}ms max={stats['max']*1e3:.1f}ms "
        f"device~{device_ms:.1f}ms iters={stats['iters']}")
    # A device estimate at (or below) zero means the wall number is
    # within one transport RTT of the floor — the measurement cannot
    # resolve device time, so no ratio is claimed.
    resolvable = device_p99_ms > 0.0
    line = {
        "metric": metric,
        "value": round(stats["p99"] * 1e3, 3),
        "unit": "ms",
        "vs_baseline": (
            round(TARGET_P99_S * 1e3 / device_p99_ms, 3)
            if against_budget and resolvable else None
        ),
        "budget_basis": (
            ("device_p99_ms" if resolvable else "below_rtt_resolution")
            if against_budget else None
        ),
        "p50_ms": round(stats["p50"] * 1e3, 3),
        "device_ms": round(device_ms, 3),
        "device_p99_ms": round(device_p99_ms, 3),
        "iters": stats["iters"],
    }
    if TRANSPORT:
        line["rtt_ms"] = TRANSPORT["rtt_ms"]
        line["rtt_p99_ms"] = TRANSPORT.get("rtt_p99_ms")
    if extra:
        line.update(extra)
    print(json.dumps(line), flush=True)


def _modes(args) -> list[str]:
    """Expand --mode both into [fast, parity]; parity LAST so that when
    the headline bench iterates these, the final stdout line is the
    parity-mode (stock-semantics) headline."""
    return ["fast", "parity"] if args.mode == "both" else [args.mode]


def _build(make, *a, **kw):
    t0 = time.perf_counter()
    snap, meta = make(*a, **kw)
    log(f"  snapshot build {time.perf_counter() - t0:.2f}s "
        f"buckets={meta.buckets.pods}x{meta.buckets.nodes}")
    return snap, meta


def _config_iters(args, mode: str, pods: int) -> int:
    """Iteration budget for the constraint-heavy configs: parity mode
    is a sequential scan whose per-iteration cost grows with P (~5 s at
    10k x 5k), so large shapes get a reduced-but-recorded count rather
    than a multi-hour bench."""
    base = max(20, args.iters // 3)
    if mode == "parity" and pods >= 4000:
        return max(5, args.iters // 40)
    return base


def _prep(engine, snap, what: str):
    """H2D + compile; returns the timed thunk."""
    t0 = time.perf_counter()
    snap = engine.put(snap)
    log(f"  H2D {time.perf_counter() - t0:.2f}s")
    fn = {
        "score": lambda: engine._score_jit(snap),
        "score_top1": lambda: engine._score_top1_jit(snap),
        "solve": lambda: engine._solve_packed_jit(snap),
    }[what]
    t0 = time.perf_counter()
    materialize(fn())
    log(f"  compile+first-run {time.perf_counter() - t0:.1f}s")
    return fn


def _emit_rounds(engine, snap, name: str, mode: str, extra=None):
    """Commit-round count of one solve as a first-class metric line
    (ISSUE 12 satellite): `rounds` used to ride only the sidecar's
    per-batch JSON log, so benchdiff could never flag a round-count
    regression — the very quantity frontier compaction moves. One extra
    (already-compiled) solve per bench; direction explicit per TPL006."""
    res = engine.unpack(snap, engine._solve_packed_jit(snap))
    line = {"metric": name, "value": int(res.rounds), "unit": "rounds",
            "vs_baseline": None, "direction": "lower", "mode": mode}
    if TRANSPORT:
        line["rtt_ms"] = TRANSPORT["rtt_ms"]
    if extra:
        line.update(extra)
    log(f"{name}: rounds={res.rounds}")
    print(json.dumps(line), flush=True)
    return int(res.rounds)


def bench_headline(args):
    """configs[1]: NodeResourcesFit + BalancedAllocation at 10k x 5k.
    With --mode both (default): fast first, then PARITY LAST, both in
    this process (one process holds the chip); exact stock semantics
    under the 500 ms budget is the north-star claim, so the parity
    number is the final (driver-parsed) stdout line."""
    from tpusched import Engine, EngineConfig
    from tpusched.synth import config2_scale

    n_pods, n_nodes = args.pods, args.nodes
    if args.replay:
        from tpusched.dump import load_snapshot

        snap, rmeta = load_snapshot(args.replay)
        if rmeta is not None:  # label by the replayed snapshot's true size
            n_pods, n_nodes = rmeta.n_pods, rmeta.n_nodes
        log(f"  replayed snapshot from {args.replay}: {n_pods}x{n_nodes}")
    else:
        rng = np.random.default_rng(42)
        snap, meta = _build(config2_scale, rng, args.pods, args.nodes,
                            with_qos=True)
        if args.dump:
            from tpusched.dump import save_snapshot

            save_snapshot(args.dump, snap, meta)
            log(f"  dumped snapshot to {args.dump}")
    headline_shape = n_pods == 10_000 and n_nodes == 5_000
    stats = None
    for mode in _modes(args):
        log(f"[headline] {args.what}@{n_pods}x{n_nodes} mode={mode}")
        engine = Engine(EngineConfig(mode=mode))
        fn = _prep(engine, snap, args.what)
        if args.profile:
            import jax

            with jax.profiler.trace(f"{args.profile}-{mode}"):
                stats = bench_fn(fn, min(args.iters, 10), label="headline")
            log(f"  profiler trace written to {args.profile}-{mode}")
        else:
            stats = bench_fn(fn, args.iters, label="headline")
        log(f"  throughput ~{n_pods / stats['p50']:,.0f} placements/sec")
        # The bare headline metric name is reserved for parity mode (the
        # stock-semantics north-star claim); fast-mode numbers always
        # carry the suffix so time series keyed by name never conflate.
        suffix = "" if mode == "parity" else "_fast"
        emit(
            f"{args.what}_p99_latency_{n_pods}x{n_nodes}{suffix}", stats,
            {"placements_per_sec": round(n_pods / stats["p50"], 1),
             "mode": mode},
            against_budget=headline_shape,
        )
        if args.what == "solve" and mode == "fast":
            _emit_rounds(engine, engine.put(snap),
                         f"solve_rounds_count_{n_pods}x{n_nodes}_{mode}",
                         mode)
    return stats


def bench_pairwise(args):
    """configs[2] at the HEADLINE shape (round-3 verdict, missing #4):
    PodTopologySpread + InterPodAffinity pairwise masks at 10k x 5k."""
    from tpusched import Engine, EngineConfig
    from tpusched.synth import config3_pairwise

    pods, nodes = args.pods, args.nodes
    rng = np.random.default_rng(43)
    snap, _ = _build(config3_pairwise, rng, pods, nodes)
    for mode in _modes(args):
        log(f"[pairwise] solve@{pods}x{nodes} spread+interpod mode={mode}")
        engine = Engine(EngineConfig(mode=mode))
        fn = _prep(engine, snap, "solve")
        stats = bench_fn(fn, _config_iters(args, mode, pods),
                         label="pairwise")
        emit(f"pairwise_solve_p99_latency_{pods}x{nodes}_{mode}", stats,
             {"mode": mode},
             against_budget=(pods == 10_000 and nodes == 5_000
                             and mode == "fast"))
        if mode == "fast":
            _emit_rounds(engine, engine.put(snap),
                         f"pairwise_solve_rounds_count_{pods}x{nodes}_{mode}",
                         mode)


def bench_gangs(args):
    """configs[3] at the headline pod count: 2500 pod-groups x 4 =
    10k pods, all-or-nothing, 5k nodes."""
    from tpusched import Engine, EngineConfig
    from tpusched.synth import config4_gangs

    rng = np.random.default_rng(44)
    n_groups, gang_size = max(1000, args.pods // 4), 4
    n_nodes = args.nodes
    snap, _ = _build(config4_gangs, rng, n_groups=n_groups,
                     gang_size=gang_size, n_nodes=n_nodes)
    pods = n_groups * gang_size
    for mode in _modes(args):
        log(f"[gangs] solve@{pods}({n_groups} groups)x{n_nodes} mode={mode}")
        engine = Engine(EngineConfig(mode=mode))
        fn = _prep(engine, snap, "solve")
        stats = bench_fn(fn, _config_iters(args, mode, pods), label="gangs")
        emit(f"gang_solve_p99_latency_{pods}x{n_nodes}_{mode}", stats,
             {"mode": mode})
        if mode == "fast":
            _emit_rounds(engine, engine.put(snap),
                         f"gang_solve_rounds_count_{pods}x{n_nodes}_{mode}",
                         mode)


def bench_preemption(args):
    """configs[4] at the headline shape: near-full cluster, QoS-slack
    eviction costs, 10k pending x 5k nodes."""
    from tpusched import Engine, EngineConfig
    from tpusched.synth import config5_preemption

    rng = np.random.default_rng(45)
    pods, nodes = args.pods, args.nodes
    snap, _ = _build(config5_preemption, rng, n_pods=pods, n_nodes=nodes)
    for mode in _modes(args):
        log(f"[preemption] solve@{pods}x{nodes} @90% util mode={mode}")
        engine = Engine(EngineConfig(mode=mode, preemption=True))
        fn = _prep(engine, snap, "solve")
        stats = bench_fn(fn, _config_iters(args, mode, pods),
                         label="preemption")
        emit(f"preemption_solve_p99_latency_{pods}x{nodes}_{mode}", stats,
             {"mode": mode},
             against_budget=(pods == 10_000 and nodes == 5_000
                             and mode == "fast"))
        if mode == "fast":
            _emit_rounds(engine, engine.put(snap),
                         f"preemption_solve_rounds_count_{pods}x{nodes}_{mode}",
                         mode)


def bench_explain(args):
    """Decision provenance overhead (round 12, ISSUE 8 acceptance).
    explain=off is the SAME serving program as before — one boolean
    check per Assign — so the regular serving/headline benches are the
    off-arm evidence that disabled provenance costs nothing. Here the
    ON-arm is priced: (1) engine-level, explained solve (solve program
    with observer arrays + the score/filter probe, two fetches) vs the
    plain solve on a preemption cluster; (2) wire-level, one Assign
    off vs on including record building."""
    from tpusched import Engine, EngineConfig
    from tpusched.synth import config5_preemption

    pods = min(args.pods, 2000)
    nodes = min(args.nodes, 1000)
    rng = np.random.default_rng(45)
    snap, _ = _build(config5_preemption, rng, n_pods=pods, n_nodes=nodes)
    cfg = EngineConfig(mode="fast", preemption=True)
    engine = Engine(cfg)
    log(f"[explain] solve@{pods}x{nodes} plain vs explained (fast)")
    fn_off = _prep(engine, snap, "solve")
    iters = max(10, args.iters // 10)
    stats_off = bench_fn(fn_off, iters, label="explain-off")
    dsnap = engine.put(snap)

    def fn_on():
        p_solve, p_probe = engine.solve_explained_async(dsnap, k=3)
        p_solve.result()
        p_probe.result()
        return ()

    t0 = time.perf_counter()
    fn_on()
    log(f"  explained compile+first-run {time.perf_counter() - t0:.1f}s")
    stats_on = bench_fn(fn_on, iters, label="explain-on")
    overhead = (stats_on["p50"] - stats_off["p50"]) / max(
        stats_off["p50"], 1e-9)
    emit(f"solve_explained_p99_latency_{pods}x{nodes}_fast", stats_on,
         {"mode": "fast",
          "explain_overhead_frac_p50": round(overhead, 4),
          "plain_p50_ms": round(stats_off["p50"] * 1e3, 3)})
    log(f"  explain overhead p50: {overhead * 100:.1f}% "
        f"(plain {stats_off['p50'] * 1e3:.1f}ms -> explained "
        f"{stats_on['p50'] * 1e3:.1f}ms)")
    engine.close()

    # Wire arm: the full Assign path incl. record building + counters.
    from tpusched.rpc.client import SchedulerClient
    from tpusched.rpc.codec import snapshot_to_proto
    from tpusched.rpc.server import make_server

    wn, wp, wm = 64, 256, 128
    rngw = np.random.default_rng(7)
    nodes_r = [dict(name=f"n{j}",
                    allocatable={"cpu": 8000.0,
                                 "memory": float(32 << 30)})
               for j in range(wn)]
    running_r = [dict(name=f"v{j}", node=f"n{j % wn}",
                      requests={"cpu": 6000.0, "memory": float(1 << 30)},
                      priority=10.0,
                      slack=float(rngw.uniform(0.0, 0.4)))
                 for j in range(wm)]
    pods_r = [dict(name=f"p{j}",
                   requests={"cpu": float(rngw.integers(500, 4000)),
                             "memory": float(1 << 30)},
                   priority=float(rngw.integers(0, 100)),
                   slo_target=0.9,
                   observed_avail=float(rngw.uniform(0.3, 1.0)))
              for j in range(wp)]
    msg = snapshot_to_proto(nodes_r, pods_r, running_r)
    for arm in ("off", "on"):
        server, port, svc = make_server("127.0.0.1:0", config=cfg,
                                        explain=(arm == "on"))
        server.start()
        try:
            with SchedulerClient(f"127.0.0.1:{port}",
                                 timeout=300.0) as c:
                stats = bench_fn(
                    lambda: c.assign(msg, packed_ok=True),
                    max(8, iters // 2), warmup=2,
                    label=f"wire-explain-{arm}",
                )
        finally:
            server.stop(0)
            svc.close()
        emit(f"wire_assign_ms_{wp}x{wn}_explain_{arm}", stats,
             {"explain": arm})


def bench_pipeline(args):
    """SURVEY.md §2.3 PP analogue: decode of batch k+1 overlapped with
    device solve of batch k over a stream of independent snapshots."""
    from tpusched import Engine, EngineConfig
    from tpusched.pipeline import bench_overlap
    from tpusched.synth import config2_scale

    pods, nodes = 5000, 2000
    # Overlap is measured in fast mode: the shorter the solve, the less
    # room there is to hide decode behind it — the harder case.
    mode = "fast" if args.mode == "both" else args.mode
    log(f"[pipeline] stream of 8 batches @{pods}x{nodes} mode={mode}")
    eng = Engine(EngineConfig(mode=mode))

    def decode(seed):
        return config2_scale(np.random.default_rng(seed), pods, nodes,
                             with_qos=True)

    stats = bench_overlap(eng, list(range(8)), decode)
    log(f"  sequential {stats['sequential_s']:.2f}s "
        f"pipelined {stats['pipelined_s']:.2f}s "
        f"speedup {stats['speedup']:.2f}x")
    print(json.dumps({
        "metric": f"pipeline_overlap_speedup_{pods}x{nodes}",
        "value": round(stats["speedup"], 3),
        "unit": "x",
        "direction": "higher",
        "vs_baseline": round(stats["speedup"], 3),
        "sequential_s": round(stats["sequential_s"], 3),
        "pipelined_s": round(stats["pipelined_s"], 3),
        **({"rtt_ms": TRANSPORT["rtt_ms"]} if TRANSPORT else {}),
    }), flush=True)


def bench_wire(args):
    """The FULL serving cycle at the headline shape, through the actual
    sidecar boundary (round-3 verdict, next-step 1b): client-side
    mutate + delta diff + gRPC + server delta resolve + (native) decode
    + H2D + solve + packed response + client array decode. Steady
    state: cycle 1 ships the full snapshot, later cycles mutate ~1% of
    pods and DeltaSession ships deltas. Also benches the O(P) top-k
    ScoreBatch form — the only Score-plugin response shape that scales
    to 10k x 5k (the [P,N] matrix never leaves the device)."""
    from tpusched.config import EngineConfig
    from tpusched.rpc.client import (
        AssignPipeline,
        DeltaSession,
        SchedulerClient,
        ScorePipeline,
        assign_response_arrays,
        score_topk_arrays,
    )
    from tpusched.rpc.codec import snapshot_to_proto
    from tpusched.rpc.server import make_server
    from tpusched.synth import config2_scale

    pods, nodes = args.pods, args.nodes
    rng = np.random.default_rng(46)
    t0 = time.perf_counter()
    nrec, prec, rrec = config2_scale(rng, pods, nodes, with_qos=True,
                                     as_records=True)
    msg = snapshot_to_proto(nrec, prec, rrec)
    log(f"  [wire] snapshot encode {time.perf_counter() - t0:.2f}s "
        f"({msg.ByteSize() / 1e6:.1f} MB on the wire)")
    # Same rationale as the headline's default 200: with too few
    # iterations one outlier lands inside the 99th percentile.
    iters = max(60, args.iters // 2)
    churn = max(1, pods // 100)

    def mutate():
        names = set()
        for j in rng.choice(pods, size=churn, replace=False):
            p = msg.pods[int(j)]
            p.observed_availability = float(rng.uniform(0.5, 1.0))
            names.add(p.name)
        return names

    for mode in _modes(args):
        server, port, svc = make_server(config=EngineConfig(mode=mode))
        server.start()
        # wire=svc.wire (round 19): the client assembles a WireRecord
        # per cycle into the SERVER's ledger, so the breakdown section
        # below can read component percentiles off svc.wire directly.
        client = SchedulerClient(f"127.0.0.1:{port}", wire=svc.wire)
        sess = DeltaSession(client)
        try:
            log(f"[wire] Assign@{pods}x{nodes} mode={mode} "
                f"({churn} pods churned per cycle)")
            t0 = time.perf_counter()
            resp = sess.assign(msg, packed_ok=True)  # full send + compile
            log(f"  full-send + compile cycle {time.perf_counter() - t0:.1f}s")
            times = []
            placed = 0
            for _ in range(iters):
                changed = mutate()
                t0 = time.perf_counter()
                resp = sess.assign(msg, packed_ok=True, changed=changed)
                _, _, ni, _, _ = assign_response_arrays(resp)
                times.append(time.perf_counter() - t0)
                placed = int((ni >= 0).sum())
            ts = np.asarray(times)
            stats = dict(
                p50=float(np.percentile(ts, 50)),
                p90=float(np.percentile(ts, 90)),
                p99=float(np.percentile(ts, 99)),
                max=float(ts.max()), mean=float(ts.mean()), iters=iters,
            )
            suffix = "" if mode == "parity" else f"_{mode}"
            assign_stats = stats  # the ScoreBatch block reuses `stats`
            emit(
                f"wire_assign_p99_latency_{pods}x{nodes}{suffix}", stats,
                {
                    "mode": mode, "placed": placed,
                    "delta_sends": sess.delta_sends,
                    "full_sends": sess.full_sends,
                    "avg_cycle_wire_mb": round(
                        sess.bytes_sent / max(sess.delta_sends
                                              + sess.full_sends, 1) / 1e6, 3
                    ),
                    # Device-residency accounting (round 7): steady-
                    # state delta cycles scatter O(churn) rows instead
                    # of re-uploading the snapshot.
                    **_session_h2d(svc),
                },
                against_budget=(pods == 10_000 and nodes == 5_000),
            )
            # SINGLE-CLIENT pipelined Assign (round 6): the SAME
            # connection keeps depth=2 requests in flight
            # (AssignPipeline pinned-base cumulative deltas), so the
            # sidecar's staged handlers overlap cycle k+1's decode
            # with cycle k's solve for ONE scheduler — the
            # reference-shaped deployment, no second client. Before =
            # the sequential p50 just measured; after = effective
            # per-cycle wall below.
            piters1 = max(20, iters // 2)
            pipe = AssignPipeline(client, depth=2)
            pipe.submit(msg, changed=None)  # pin base + warm
            # Per-cycle latency of a pipelined stream = the interval
            # between successive COMPLETIONS (responses overlap, so
            # per-request walls double-count); percentiles over the
            # intervals keep the budget verdict a real p99 — a flat
            # wall/n mean would hide the rare multi-second
            # stalls that the sequential bench's p99 exists to surface.
            done_ts = []
            t0 = time.perf_counter()
            for _ in range(piters1):
                changed = mutate()
                for r in pipe.submit(msg, changed=changed, packed_ok=True):
                    assign_response_arrays(r)
                    done_ts.append(time.perf_counter())
            for r in pipe.flush():
                assign_response_arrays(r)
                done_ts.append(time.perf_counter())
            wall1 = time.perf_counter() - t0
            n_done = len(done_ts)
            # Intervals BETWEEN completions only: the span from t0 to
            # the first completion is the depth-2 pipe FILLING — one
            # full unoverlapped cycle — and with few samples the p99
            # interpolates at the near-max sample, so including it
            # would pin the judged p99 at sequential latency exactly
            # when overlap works.
            gaps = np.diff(np.asarray(done_ts))
            if gaps.size == 0:
                gaps = np.asarray([wall1])
            stats1 = dict(
                p50=float(np.percentile(gaps, 50)),
                p90=float(np.percentile(gaps, 90)),
                p99=float(np.percentile(gaps, 99)),
                max=float(gaps.max()), mean=float(gaps.mean()),
                iters=n_done,
            )
            eff1_ms = wall1 / max(n_done, 1) * 1e3
            seq_ms = assign_stats["p50"] * 1e3
            log(f"  single-client pipelined: {n_done} cycles in "
                f"{wall1:.1f}s -> {eff1_ms:.1f}ms/cycle effective "
                f"(sequential p50 {seq_ms:.1f}ms, "
                f"{seq_ms / max(eff1_ms, 1e-9):.2f}x)")
            emit(
                f"wire_assign_pipelined1_cycle_ms_{pods}x{nodes}{suffix}",
                stats1,
                {"mode": mode, "concurrency": 1, "depth": 2,
                 "effective_cycle_ms": round(eff1_ms, 1),
                 "sequential_p50_ms": round(seq_ms, 1),
                 "overlap_speedup": round(seq_ms / max(eff1_ms, 1e-9), 2),
                 "delta_sends": pipe.delta_sends,
                 "full_sends": pipe.full_sends},
                against_budget=(pods == 10_000 and nodes == 5_000),
            )
            if mode == _modes(args)[-1]:
                # Wire-ledger breakdown + ledger cost (round 19):
                # measured once, on the last server, before ScoreBatch
                # repoints `sess` traffic at a different RPC.
                _wire_ledger_section(svc, sess, msg, mutate,
                                     pods, nodes, iters)
                # ScoreBatch top-k wire cycle (mode-independent scores;
                # measured once, on the last server).
                k = 8
                log(f"[wire] ScoreBatch top-{k}@{pods}x{nodes}")
                t0 = time.perf_counter()
                resp = sess.score_batch(msg, top_k=k)  # compile
                log(f"  top-k first cycle {time.perf_counter() - t0:.1f}s")
                times = []
                for _ in range(iters):
                    changed = mutate()
                    t0 = time.perf_counter()
                    resp = sess.score_batch(msg, top_k=k, changed=changed)
                    idx, val = score_topk_arrays(resp)
                    times.append(time.perf_counter() - t0)
                ts = np.asarray(times)
                stats = dict(
                    p50=float(np.percentile(ts, 50)),
                    p90=float(np.percentile(ts, 90)),
                    p99=float(np.percentile(ts, 99)),
                    max=float(ts.max()), mean=float(ts.mean()), iters=iters,
                )
                emit(
                    f"wire_scorebatch_top{k}_p99_latency_{pods}x{nodes}",
                    stats,
                    {"k": k,
                     "resp_mb": round(
                         (len(resp.topk_idx_packed)
                          + len(resp.topk_score_packed)) / 1e6, 3)},
                    against_budget=(pods == 10_000 and nodes == 5_000),
                )
                # SINGLE-CLIENT pipelined ScoreBatch (round 7,
                # closing the round-5 "parity top-8 ScoreBatch" wire
                # item): same depth-2 pinned-base discipline as
                # AssignPipeline, for the Score-plugin surface.
                spipe = ScorePipeline(client, depth=2, top_k=k)
                spipe.submit(msg, changed=None)  # pin + warm
                sdone = []
                t0 = time.perf_counter()
                for _ in range(piters1):
                    changed = mutate()
                    for r in spipe.submit(msg, changed=changed):
                        score_topk_arrays(r)
                        sdone.append(time.perf_counter())
                for r in spipe.flush():
                    score_topk_arrays(r)
                    sdone.append(time.perf_counter())
                swall = time.perf_counter() - t0
                sgaps = np.diff(np.asarray(sdone))
                if sgaps.size == 0:
                    sgaps = np.asarray([swall])
                seff_ms = swall / max(len(sdone), 1) * 1e3
                sseq_ms = stats["p50"] * 1e3
                log(f"  single-client pipelined top-{k}: "
                    f"{len(sdone)} cycles in {swall:.1f}s -> "
                    f"{seff_ms:.1f}ms/cycle effective (sequential p50 "
                    f"{sseq_ms:.1f}ms, {sseq_ms / max(seff_ms, 1e-9):.2f}x)")
                emit(
                    f"wire_scorebatch_top{k}_pipelined1_cycle_ms_"
                    f"{pods}x{nodes}",
                    dict(p50=float(np.percentile(sgaps, 50)),
                         p90=float(np.percentile(sgaps, 90)),
                         p99=float(np.percentile(sgaps, 99)),
                         max=float(sgaps.max()), mean=float(sgaps.mean()),
                         iters=len(sdone)),
                    {"k": k, "concurrency": 1, "depth": 2,
                     "effective_cycle_ms": round(seff_ms, 1),
                     "sequential_p50_ms": round(sseq_ms, 1),
                     "overlap_speedup": round(
                         sseq_ms / max(seff_ms, 1e-9), 2),
                     "delta_sends": spipe.delta_sends,
                     "full_sends": spipe.full_sends},
                    against_budget=(pods == 10_000 and nodes == 5_000),
                )
                # PIPELINED serving (round 5, VERDICT #5): two
                # independent schedulers drive the sidecar
                # concurrently. The engine releases the GIL during the
                # device fetch, so handler k+1's decode overlaps
                # handler k's solve+fetch and effective per-cycle wall
                # (total wall / cycles) drops below the sequential p50
                # — the §2.3 PP overlap measured THROUGH the serving
                # boundary, not just in-bench (pipeline.solve_stream).
                import threading

                rng2 = np.random.default_rng(47)
                nr2, pr2, rr2 = config2_scale(
                    rng2, pods, nodes, with_qos=True, as_records=True
                )
                msg2 = snapshot_to_proto(nr2, pr2, rr2)
                sessions = [sess, DeltaSession(client)]
                msgs = [msg, msg2]
                rngs = [rng, rng2]
                sessions[1].assign(msg2, packed_ok=True)  # base + warm
                piters = max(20, iters // 2)

                def drive(i, out):
                    srng = rngs[i]
                    for _ in range(piters):
                        names = set()
                        for j in srng.choice(pods, size=churn,
                                             replace=False):
                            p = msgs[i].pods[int(j)]
                            p.observed_availability = float(
                                srng.uniform(0.5, 1.0)
                            )
                            names.add(p.name)
                        t0 = time.perf_counter()
                        r = sessions[i].assign(
                            msgs[i], packed_ok=True, changed=names
                        )
                        assign_response_arrays(r)
                        out.append(time.perf_counter() - t0)

                outs = [[], []]
                t0 = time.perf_counter()
                threads = [
                    threading.Thread(target=drive, args=(i, outs[i]),
                                     name=f"tpusched-bench-wire-{i}")
                    for i in range(2)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                wall = time.perf_counter() - t0
                eff_ms = wall / (2 * piters) * 1e3
                # Baseline is the SEQUENTIAL ASSIGN p50 (the same RPC
                # the pipelined cycles run), not the ScoreBatch stats
                # that overwrote `stats` above.
                seq_p50_ms = assign_stats["p50"] * 1e3
                log(f"  pipelined: {2 * piters} cycles in {wall:.1f}s -> "
                    f"{eff_ms:.1f}ms/cycle effective "
                    f"(sequential p50 {seq_p50_ms:.1f}ms)")
                emit(
                    f"wire_pipelined_cycle_ms_{pods}x{nodes}",
                    {"p50": eff_ms / 1e3, "p90": eff_ms / 1e3,
                     "p99": eff_ms / 1e3, "max": eff_ms / 1e3,
                     "mean": eff_ms / 1e3, "iters": 2 * piters},
                    {"concurrency": 2,
                     "sequential_p50_ms": round(seq_p50_ms, 1),
                     "overlap_speedup": round(seq_p50_ms / eff_ms, 2)},
                    against_budget=(pods == 10_000 and nodes == 5_000),
                )
        finally:
            client.close()
            server.stop(None)
            svc.close()


def _wire_ledger_section(svc, sess, msg, mutate, pods, nodes, iters):
    """Wire-ledger breakdown + serve-path cost (round 19, ISSUE 19).

    Three acceptance numbers fall out of one OFF/ON pair of
    steady-state delta arms on the ledgered client:

      * ``wire_breakdown_{component}_ms_{p50,p99}`` — per-component
        percentiles of the clock-stitched round-trip decomposition
        (client serialize, one-way send/reply gaps, every server
        stage, D2H fetch.join, server residue);
      * ``wire_breakdown_coverage_frac`` — the sum-vs-wall check: the
        components must explain >= 90% of the measured cycle wall
        over real gRPC (gap clamping + unstitched cycles eat the
        rest, so a low number means the clock-offset estimator or
        span pairing regressed);
      * ``wire_ledger_overhead_pct`` — what ledgering costs the serve
        path (the extra client serialize pass + span assembly): OFF
        p50 vs ON p50, budget <= 1%.
    """
    led = svc.wire
    arm = max(20, iters // 2)
    log(f"[wire] ledger OFF arm ({arm} cycles)")
    led.enabled = False  # client skips serialize span + assembly
    try:
        off_ts = []
        for _ in range(arm):
            changed = mutate()
            t0 = time.perf_counter()
            sess.assign(msg, packed_ok=True, changed=changed)
            off_ts.append(time.perf_counter() - t0)
    finally:
        led.enabled = True
    n_before = len(led.records())
    log(f"[wire] ledger ON arm ({arm} cycles)")
    on_ts = []
    for _ in range(arm):
        changed = mutate()
        t0 = time.perf_counter()
        sess.assign(msg, packed_ok=True, changed=changed)
        on_ts.append(time.perf_counter() - t0)
    recs = led.records()[n_before:]
    off_p50 = float(np.percentile(np.asarray(off_ts), 50))
    on_p50 = float(np.percentile(np.asarray(on_ts), 50))
    overhead_pct = (on_p50 - off_p50) / max(off_p50, 1e-9) * 100.0
    log(f"  ledger overhead: OFF p50 {off_p50 * 1e3:.1f}ms, "
        f"ON p50 {on_p50 * 1e3:.1f}ms -> {overhead_pct:+.2f}% "
        f"(budget <= 1%)")
    print(json.dumps({
        "metric": "wire_ledger_overhead_pct",
        "value": round(overhead_pct, 2),
        "unit": "pct", "direction": "lower",
        "off_p50_ms": round(off_p50 * 1e3, 2),
        "on_p50_ms": round(on_p50 * 1e3, 2),
        "iters": arm, "shape": f"{pods}x{nodes}",
    }), flush=True)
    if not recs:
        log("[wire] ledger produced no records on the ON arm; "
            "skipping breakdown")
        return
    walls = sum(r.wall_s for r in recs)
    staged = sum(sum(r.stages.values()) for r in recs)
    coverage = staged / max(walls, 1e-12)
    stitched = sum(1 for r in recs if r.stitched)
    best = led.clock.best()
    off_ms = round(best[0] * 1e3, 3) if best else None
    unc_ms = round(best[1] * 1e3, 3) if best else None
    ok = coverage >= 0.90
    log(f"  breakdown: {len(recs)} cycles ({stitched} stitched), "
        f"components cover {coverage:.1%} of cycle wall "
        f"({'OK' if ok else 'BELOW the 90% acceptance bar'}), "
        f"clock offset {off_ms}ms +/- {unc_ms}ms")
    print(json.dumps({
        "metric": "wire_breakdown_coverage_frac",
        "value": round(coverage, 4),
        "unit": "frac", "direction": "higher",
        "cycles": len(recs), "stitched": stitched,
        "clock_offset_ms": off_ms, "clock_uncertainty_ms": unc_ms,
        "bytes_up": sum(r.bytes_up for r in recs),
        "bytes_down": sum(r.bytes_down for r in recs),
        "shape": f"{pods}x{nodes}",
    }), flush=True)
    comps: dict = {}
    for r in recs:
        for name, v in r.stages.items():
            comps.setdefault(name, []).append(v * 1e3)
    for name in sorted(comps):
        arr = np.asarray(comps[name])
        slug = name.replace(".", "_")
        p50 = float(np.percentile(arr, 50))
        p99 = float(np.percentile(arr, 99))
        log(f"    {name:<12s} p50 {p50:8.3f}ms  p99 {p99:8.3f}ms "
            f"({arr.size} cycles)")
        for tag, val in (("p50", p50), ("p99", p99)):
            print(json.dumps({
                "metric": f"wire_breakdown_{slug}_ms_{tag}",
                "value": round(val, 3),
                "unit": "ms", "iters": int(arr.size),
                "shape": f"{pods}x{nodes}",
            }), flush=True)


def _session_h2d(svc) -> dict:
    """Steady-state H2D accounting across the sidecar's device-resident
    sessions: bytes shipped per delta cycle vs the full-snapshot upload
    a decode-path cycle pays (the before/after of device residency)."""
    with svc._store_lock:
        sessions = []
        for s in svc._sessions.values():
            if s not in sessions:
                sessions.append(s)
    if not sessions:
        return {}
    deltas = sum(s.device.delta_updates for s in sessions)
    uploads = sum(s.device.full_uploads for s in sessions)
    full = max(s.device.full_bytes for s in sessions)
    per_cycle = (sum(s.device.h2d_bytes_last for s in sessions)
                 / len(sessions))
    return {
        "h2d_full_snapshot_bytes": int(full),
        "h2d_bytes_per_delta_cycle": int(per_cycle),
        "h2d_reduction_x": round(full / max(per_cycle, 1), 1),
        "device_delta_updates": int(deltas),
        "device_full_uploads": int(uploads),
    }


def _stage_breakdown() -> dict:
    """Per-stage latency columns from the process trace ring (ISSUE 4):
    stage name -> {p50_ms, p99_ms, n} over the server/engine spans
    collected since the last ring clear. Empty when tracing is off —
    the columns degrade away instead of breaking the bench."""
    from tpusched import trace as _tr

    by: dict[str, list] = {}
    for s in _tr.DEFAULT.spans():  # tpl: disable=TPL009(bench deliberately reads the process-default ring its --trace knob enables)
        if s.cat in ("server", "engine"):
            by.setdefault(s.name, []).append(s.dur_s)
    return {
        name: {
            "p50_ms": round(float(np.percentile(v, 50)) * 1e3, 2),
            "p99_ms": round(float(np.percentile(v, 99)) * 1e3, 2),
            "n": len(v),
        }
        for name, v in sorted(by.items())
    }


def _serve_score_phase(svc, clients, msgs, rngs, pods, churn, shape,
                       K, cycles):
    """COALESCED scoring fan-in: K replicas ranking the SAME cluster
    state (the Score-plugin north star at fan-in). Each cycle, one
    delta is built once and all K clients fire the byte-identical
    request concurrently; the sidecar fuses them into ONE top-k
    dispatch and slices per caller — device work amortizes across
    callers, so aggregate qps can exceed the Amdahl bound of
    distinct-state fan-in."""
    import threading

    from tpusched.rpc import tpusched_pb2 as _pb
    from tpusched.rpc.client import score_topk_arrays

    kk = 8
    msg0, rng0 = msgs[0], rngs[0]
    log(f"[serving] coalesced top-{kk} @{shape}: warm + compile")
    t0 = time.perf_counter()
    resp = clients[0].score_batch(msg0, top_k=kk)
    base_sid = resp.snapshot_id
    log(f"  first cycle {time.perf_counter() - t0:.1f}s")

    def score_delta():
        delta = _pb.SnapshotDelta(base_id=base_sid)
        for j in rng0.choice(pods, size=churn, replace=False):
            p = msg0.pods[int(j)]
            p.observed_availability = float(rng0.uniform(0.5, 1.0))
            delta.upsert_pods.add().CopyFrom(p)
        return delta

    # Sequential scoring baseline (single client, chained deltas).
    stimes = []
    for _ in range(cycles):
        d = score_delta()
        t0 = time.perf_counter()
        resp = clients[0].score_batch_delta(d, top_k=kk)
        score_topk_arrays(resp)
        stimes.append(time.perf_counter() - t0)
        base_sid = resp.snapshot_id
    sts = np.asarray(stimes)
    seq_score_qps = 1.0 / sts.mean()
    log(f"  sequential scoring: {seq_score_qps:.2f} qps "
        f"(p50 {np.percentile(sts, 50)*1e3:.0f}ms)")
    fused0 = svc._coalescer.fused_requests

    def fire(i, d, sink):
        t0 = time.perf_counter()
        r = clients[i].score_batch_delta(d, top_k=kk)
        score_topk_arrays(r)
        sink.append((time.perf_counter() - t0, r.snapshot_id))

    clat = []
    t0 = time.perf_counter()
    for _ in range(cycles):
        d = score_delta()
        sink = []
        threads = [threading.Thread(target=fire, args=(i, d, sink),
                                    name=f"tpusched-bench-coalesce-{i}")
                   for i in range(K)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        clat += [x[0] for x in sink]
        base_sid = sink[0][1]
    cwall = time.perf_counter() - t0
    cqps = K * cycles / cwall
    fused = svc._coalescer.fused_requests - fused0
    cl = np.asarray(clat)
    log(f"  coalesced top-{kk} fan-in: {cqps:.2f} qps aggregate "
        f"({cqps / seq_score_qps:.2f}x sequential "
        f"{seq_score_qps:.2f} qps), {fused} of {K * cycles} "
        f"requests fused")
    print(json.dumps({
        "metric": f"serve_qps_coalesced_{K}c_{shape}",
        "value": round(cqps, 3), "unit": "qps",
        # The >= 2x acceptance ratio for the shared-store scoring
        # workload: fused dispatches amortize device work across
        # callers.
        "vs_baseline": round(cqps / seq_score_qps, 3),
        "sequential_qps": round(seq_score_qps, 3),
        "clients": K, "k": kk,
        "fused_requests": int(fused),
        "p50_ms": round(float(np.percentile(cl, 50)) * 1e3, 1),
        "p99_ms": round(float(np.percentile(cl, 99)) * 1e3, 1),
        **({"rtt_ms": TRANSPORT["rtt_ms"]} if TRANSPORT else {}),
    }), flush=True)


def bench_serving(args):
    """Round 7: MULTI-CLIENT coalesced serving through the sidecar.
    K concurrent connections (each its own DeltaSession lineage; the
    server keeps each lineage's cluster state device-resident) drive
    Assign cycles:

      serve_qps_seq_*      single-client closed-loop baseline
      serve_qps_{K}c_*     aggregate closed-loop fan-in throughput
                           (the >= 2x acceptance metric)
      serve_p99_ms_{K}c_*  per-request p99 under OPEN-LOOP arrivals at
                           ~80% of measured capacity (queueing delay
                           counts: latency is measured from the
                           scheduled arrival, not the send)

    plus per-cycle H2D bytes (delta scatter vs full upload)."""
    import threading

    from tpusched.config import EngineConfig
    from tpusched.rpc.client import (
        DeltaSession, SchedulerClient, assign_response_arrays,
    )
    from tpusched.rpc.codec import snapshot_to_proto
    from tpusched.rpc.server import make_server
    from tpusched.synth import config2_scale

    pods, nodes = args.pods, args.nodes
    K = args.serve_clients
    cycles = args.serve_cycles
    churn = max(1, pods // 100)
    rng = np.random.default_rng(48)
    nrec, prec, rrec = config2_scale(rng, pods, nodes, with_qos=True,
                                     as_records=True)
    base = snapshot_to_proto(nrec, prec, rrec)
    shape = f"{pods}x{nodes}"
    server, port, svc = make_server(config=EngineConfig(mode="fast"))
    server.start()
    clients = [SchedulerClient(f"127.0.0.1:{port}") for _ in range(K)]
    try:
        msgs = [type(base).FromString(base.SerializeToString())
                for _ in range(K)]
        sessions = [DeltaSession(c) for c in clients]
        rngs = [np.random.default_rng(100 + i) for i in range(K)]

        def mutate(i):
            names = set()
            for j in rngs[i].choice(pods, size=churn, replace=False):
                p = msgs[i].pods[int(j)]
                p.observed_availability = float(rngs[i].uniform(0.5, 1.0))
                names.add(p.name)
            return names

        def one_cycle(i):
            changed = mutate(i)
            resp = sessions[i].assign(msgs[i], packed_ok=True,
                                      changed=changed)
            assign_response_arrays(resp)

        do_assign = args.serve_what in ("both", "assign")
        do_score = args.serve_what in ("both", "score")
        if not do_assign:
            _serve_score_phase(svc, clients, msgs, rngs, pods, churn,
                               shape, K, cycles)
            return
        log(f"[serving] warmup: {K} lineages full-send + first delta "
            f"@{shape}")
        t0 = time.perf_counter()
        for i in range(K):
            sessions[i].assign(msgs[i], packed_ok=True)   # pin + compile
            one_cycle(i)                                  # seed session
        log(f"  warm in {time.perf_counter() - t0:.1f}s")

        # 1. Single-client closed-loop sequential baseline.
        times = []
        for _ in range(cycles):
            t0 = time.perf_counter()
            one_cycle(0)
            times.append(time.perf_counter() - t0)
        ts = np.asarray(times)
        seq_qps = 1.0 / ts.mean()
        log(f"  sequential: {seq_qps:.2f} cycles/s "
            f"(p50 {np.percentile(ts, 50)*1e3:.0f}ms)")
        print(json.dumps({
            "metric": f"serve_qps_seq_{shape}", "value": round(seq_qps, 3),
            "unit": "qps", "vs_baseline": None,
            "p50_ms": round(float(np.percentile(ts, 50)) * 1e3, 1),
            "p99_ms": round(float(np.percentile(ts, 99)) * 1e3, 1),
            **({"rtt_ms": TRANSPORT["rtt_ms"]} if TRANSPORT else {}),
        }), flush=True)

        # 2. Closed-loop fan-in: K clients back-to-back. The trace ring
        # is cleared first so the per-stage breakdown columns cover
        # exactly this phase.
        from tpusched import trace as _tr

        _tr.DEFAULT.clear()  # tpl: disable=TPL009(bench deliberately scopes the process-default ring to this phase)
        lat: list[list[float]] = [[] for _ in range(K)]

        def drive(i):
            for _ in range(cycles):
                t0 = time.perf_counter()
                one_cycle(i)
                lat[i].append(time.perf_counter() - t0)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=drive, args=(i,),
                                    name=f"tpusched-bench-serve-{i}")
                   for i in range(K)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        agg_qps = K * cycles / wall
        alllat = np.asarray([x for l in lat for x in l])
        speedup = agg_qps / seq_qps
        stage_ms = _stage_breakdown()
        log(f"  {K}-client closed loop: {agg_qps:.2f} cycles/s aggregate "
            f"({speedup:.2f}x sequential), per-request p50 "
            f"{np.percentile(alllat, 50)*1e3:.0f}ms")
        if stage_ms:
            # Where each millisecond of a request goes (ISSUE 4): one
            # column per serving stage, p50/p99 over this phase.
            cols = "  ".join(
                f"{name} {v['p50_ms']:.1f}/{v['p99_ms']:.1f}ms"
                for name, v in stage_ms.items()
            )
            log(f"  stage p50/p99: {cols}")
        print(json.dumps({
            "metric": f"serve_qps_{K}c_{shape}", "value": round(agg_qps, 3),
            "unit": "qps",
            # The acceptance ratio: aggregate fan-in throughput over the
            # single-client sequential baseline (>= 2x at the headline
            # shape on CPU).
            "vs_baseline": round(speedup, 3),
            "sequential_qps": round(seq_qps, 3),
            "clients": K,
            "p50_ms": round(float(np.percentile(alllat, 50)) * 1e3, 1),
            "p99_ms": round(float(np.percentile(alllat, 99)) * 1e3, 1),
            "stage_ms": stage_ms,
            **({"rtt_ms": TRANSPORT["rtt_ms"]} if TRANSPORT else {}),
        }), flush=True)

        # 3. Open-loop arrivals at ~80% of measured capacity: latency
        # includes queueing from the scheduled arrival time.
        rate = max(agg_qps * 0.8, 1e-6)
        n_open = K * cycles
        start = time.perf_counter() + 0.05
        arrivals = start + np.arange(n_open) / rate
        open_lat: list[list[float]] = [[] for _ in range(K)]

        def drive_open(i):
            for req in range(i, n_open, K):
                now = time.perf_counter()
                wait = arrivals[req] - now
                if wait > 0:
                    time.sleep(wait)
                one_cycle(i)
                open_lat[i].append(time.perf_counter() - arrivals[req])

        threads = [threading.Thread(target=drive_open, args=(i,),
                                    name=f"tpusched-bench-open-{i}")
                   for i in range(K)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        ol = np.asarray([x for l in open_lat for x in l])
        h2d = _session_h2d(svc)
        log(f"  open loop @{rate:.2f} req/s: p50 "
            f"{np.percentile(ol, 50)*1e3:.0f}ms p99 "
            f"{np.percentile(ol, 99)*1e3:.0f}ms; "
            f"H2D/cycle {h2d.get('h2d_bytes_per_delta_cycle', 0)} B vs "
            f"full {h2d.get('h2d_full_snapshot_bytes', 0)} B")
        print(json.dumps({
            "metric": f"serve_p99_ms_{K}c_{shape}",
            "value": round(float(np.percentile(ol, 99)) * 1e3, 1),
            "unit": "ms", "vs_baseline": None,
            "offered_qps": round(rate, 3), "clients": K,
            "p50_ms": round(float(np.percentile(ol, 50)) * 1e3, 1),
            "gate_peak_waiting": svc._gate.peak_waiting,
            "session_hits": svc.session_hits,
            "session_seeds": svc.session_seeds,
            "session_misses": svc.session_misses,
            **h2d,
            **({"rtt_ms": TRANSPORT["rtt_ms"]} if TRANSPORT else {}),
        }), flush=True)

        if do_score:
            _serve_score_phase(svc, clients, msgs, rngs, pods,
                               churn, shape, K, cycles)
    finally:
        for c in clients:
            c.close()
        server.stop(None)
        svc.close()


def bench_e2e(args):
    """configs[0]: 100 pods x 10 nodes through the host shim."""
    try:
        from tpusched.host import run_e2e_benchmark
    except ImportError:
        log("[e2e] host shim not available; skipping")
        return
    stats = run_e2e_benchmark(n_pods=100, n_nodes=10, iters=max(5, args.iters // 10))
    emit("e2e_p99_latency_100x10", stats,
         {"placements_per_sec": stats.get("placements_per_sec")})


def bench_warm(args):
    """O(churn) warm-start churn sweep (ROADMAP item 3, ISSUE 11): one
    device-resident lineage at the headline shape, value-churned at
    0.1% / 1% / 10% of pods per cycle, each cycle solved through the
    engine warm path (carried tableau + dirty-row refresh). Emits
    solve_warm_ms_{p50,p99} per churn level next to a cold reference
    measured on the SAME snapshot with the plain packed-solve program
    (comparable to the headline fast number), so benchdiff flags
    regressions in either path. The twin-parity contract (warm == cold
    bitwise) is pinned by tests/test_warm.py and auditable with
    `python -m tpusched.divergence --warm-audit N`."""
    from tpusched import EngineConfig
    from tpusched.device_state import DeviceSnapshot
    from tpusched.engine import Engine
    from tpusched.synth import make_cluster

    pods, nodes = args.pods, args.nodes
    rng = np.random.default_rng(46)
    t0 = time.perf_counter()
    nodes_r, pods_r, running_r = make_cluster(
        rng, pods, nodes, n_running_per_node=1, with_qos=True,
        as_records=True,
    )
    log(f"[warm] records build {time.perf_counter() - t0:.2f}s "
        f"@{pods}x{nodes}")
    cfg = EngineConfig(mode="fast")
    ds = DeviceSnapshot(cfg)
    t0 = time.perf_counter()
    ds.full_load(nodes_r, pods_r, running_r)
    log(f"  full_load {time.perf_counter() - t0:.2f}s")
    engine = Engine(cfg)
    iters = max(10, args.iters // 5)
    try:
        # Cold reference: the same packed program the headline bench
        # times, on this lineage's snapshot.
        fn = _prep(engine, ds.snap, "solve")
        cold = bench_fn(fn, iters, label="warm-coldref")
        emit(f"solve_cold_ref_ms_{pods}x{nodes}", cold,
             {"mode": "fast", "direction": "lower"})
        t0 = time.perf_counter()
        engine.solve_warm(ds)  # tableau build + warm-program compile
        log(f"  warm-path first run (cold tableau build) "
            f"{time.perf_counter() - t0:.1f}s")
        P = len(pods_r)
        for frac in (0.001, 0.01, 0.1):
            k = max(1, min(P, int(round(frac * P))))
            rngc = np.random.default_rng(int(frac * 1e6) + 17)

            def one_cycle(k=k, rngc=rngc):
                picks = rngc.choice(P, size=k, replace=False)
                ups = []
                for i in picks:
                    rec = pods_r[int(i)]
                    rec["observed_avail"] = float(rngc.uniform(0.3, 1.0))
                    ups.append(rec)
                ds.apply(upsert_pods=ups)
                return engine.solve_warm_async(ds).result().assignment

            warm_before = ds.warm_solves
            warmup = 3
            stats = bench_fn(one_cycle, iters, warmup=warmup,
                             label=f"warm-{frac:g}")
            pct = ("%g" % (frac * 100)).replace(".", "p")
            # bench_fn's warmup cycles also warm-solve: count them so
            # even ONE cold fallback inside the timed loop is reported.
            warm_got = ds.warm_solves - warm_before
            if warm_got < iters + warmup:
                log(f"  WARNING: {iters + warmup - warm_got} cold "
                    "fallbacks inside the churn loop "
                    f"({ds.warm_cold_reasons[-3:]})")
            emit(f"solve_warm_ms_{pct}pct_{pods}x{nodes}", stats,
                 {"mode": "fast", "direction": "lower",
                  "churn_pods": k,
                  "dirty_rows": list(ds.last_warm_rows),
                  "solve_warm_ms_p50": round(stats["p50"] * 1e3, 3),
                  "solve_warm_ms_p99": round(stats["p99"] * 1e3, 3),
                  "cold_ref_p50_ms": round(cold["p50"] * 1e3, 3),
                  "warm_speedup_p50": round(
                      cold["p50"] / max(stats["p50"], 1e-9), 2)})

        # Bounded-divergence incremental sweep (ISSUE 12): same lineage
        # (its carry is fresh from the bitwise sweep above), same churn
        # levels, commit rounds restricted to the frontier. The target
        # of record: solve_warm_inc_ms_p50 <= 0.25x the cold ref at 1%
        # churn. Every cycle's in-kernel validity audit must be clean.
        cold_rounds = _emit_rounds(
            engine, ds.snap, f"solve_rounds_count_cold_{pods}x{nodes}",
            "fast")
        audit_bad = 0
        last_info = {}
        last_res = [None]

        def inc_cycle(k, rngc):
            nonlocal audit_bad, last_info
            picks = rngc.choice(P, size=k, replace=False)
            ups = []
            for i in picks:
                rec = pods_r[int(i)]
                rec["observed_avail"] = float(rngc.uniform(0.3, 1.0))
                ups.append(rec)
            ds.apply(upsert_pods=ups)
            res = engine.solve_warm_async(ds, incremental=True).result()
            last_res[0] = res
            if res.inc_info:
                last_info = res.inc_info
                audit_bad += res.inc_info["audit_violations"]
            return res.assignment

        for frac in (0.001, 0.01, 0.1):
            k = max(1, min(P, int(round(frac * P))))
            rngc = np.random.default_rng(int(frac * 1e6) + 29)
            inc_before = ds.incremental_solves
            bad_before = audit_bad
            warmup = 3
            stats = bench_fn(lambda k=k, rngc=rngc: inc_cycle(k, rngc),
                             iters, warmup=warmup, label=f"warm-inc-{frac:g}")
            pct = ("%g" % (frac * 100)).replace(".", "p")
            inc_got = ds.incremental_solves - inc_before
            level_bad = audit_bad - bad_before
            if inc_got < iters + warmup:
                log(f"  WARNING: {iters + warmup - inc_got} non-"
                    "incremental fallbacks inside the churn loop "
                    f"({ds.warm_cold_reasons[-3:]})")
            if level_bad:
                log(f"  WARNING: in-kernel validity audit flagged "
                    f"{level_bad} violations at this churn level — "
                    "investigate with divergence --warm-audit "
                    "--incremental")
            emit(f"solve_warm_inc_ms_{pct}pct_{pods}x{nodes}", stats,
                 {"mode": "fast", "direction": "lower",
                  "churn_pods": k,
                  "carried": last_info.get("carried"),
                  "frontier": last_info.get("frontier"),
                  "audit_violations_total": level_bad,
                  "solve_warm_inc_ms_p50": round(stats["p50"] * 1e3, 3),
                  "solve_warm_inc_ms_p99": round(stats["p99"] * 1e3, 3),
                  "cold_ref_p50_ms": round(cold["p50"] * 1e3, 3),
                  "inc_speedup_p50": round(
                      cold["p50"] / max(stats["p50"], 1e-9), 2)})
        # One representative incremental cycle's round count next to
        # the cold one — read from an ACTUAL ~1%-churn cycle's result
        # (a fresh zero-delta solve would measure an idle frontier).
        rngc = np.random.default_rng(97)
        inc_cycle(max(1, P // 100), rngc)
        res = last_res[0]
        line = {"metric": f"solve_rounds_count_warm_inc_{pods}x{nodes}",
                "value": int(res.rounds), "unit": "rounds",
                "vs_baseline": None, "direction": "lower",
                "cold_rounds": cold_rounds}
        if TRANSPORT:
            line["rtt_ms"] = TRANSPORT["rtt_ms"]
        log(f"solve_rounds_count_warm_inc: {res.rounds} (cold "
            f"{cold_rounds})")
        print(json.dumps(line), flush=True)
    finally:
        engine.close()


def bench_multichip(args):
    """MULTICHIP: sharded serving across the (p,n) device mesh (round
    22, ISSUE 17). Runs only when the backend exposes >1 device —
    skipped gracefully (one stderr line, no metric) otherwise, so the
    default single-device run is unchanged.

    Three phases:
      1. serve_qps_sharded_<shape> + solve_p99_latency_<shape>_sharded:
         the packed serving solve on a mesh engine consuming a
         canonically-sharded snapshot (Engine.put) — the
         pipeline.solve_stream cycle, measured end to end.
      2. shard_combine_ms_<shape>: the cross-shard combine in
         isolation — a [P, N] PS('p','n')-sharded tableau reduced to a
         per-pod vector and pinned replicated (the reduce+broadcast
         every sharded commit round pays, per the ledger's
         safe-any-tree routing).
      3. The 100k x 50k headline solve, sharded — the one-engine-serves
         -the-cluster claim. Accelerator backends only: the [P, N]
         working set at that shape is ~10^10 cells, far past what the
         forced-host-device CPU mesh (a debugging topology) can hold,
         so on cpu it logs the skip instead of thrashing.
    """
    import jax

    ndev = len(jax.devices())
    if ndev < 2:
        log("[multichip] 1 jax device — sharded serving bench skipped")
        return
    from tpusched import Engine, EngineConfig
    from tpusched.mesh import make_mesh, matrix_sharding
    from tpusched.shardctx import constrain_replicated
    from tpusched.synth import config2_scale

    mesh_shape = (ndev // 2, 2) if ndev % 2 == 0 else (ndev, 1)
    mesh = make_mesh(mesh_shape)
    on_cpu = jax.default_backend() == "cpu"
    log(f"[multichip] mesh {mesh_shape} over {ndev} "
        f"{jax.default_backend()} device(s)")

    # Phase 1+2 shape: the headline serving shape on accelerators; a
    # small stand-in on a forced-device CPU mesh (where 10k x 5k fast
    # solves take minutes and measure the host, not the sharding).
    pods, nodes = (2000, 1000) if on_cpu else (args.pods, args.nodes)
    shape = f"{pods}x{nodes}"
    cfg = EngineConfig(mode="fast", compact_cap=8)
    eng = Engine(cfg, mesh=mesh)
    try:
        snap, _meta = _build(config2_scale, np.random.default_rng(21),
                             pods, nodes, with_qos=True)
        dev = eng.put(snap)
        fn = lambda: eng._solve_packed_jit(dev)  # noqa: E731
        t0 = time.perf_counter()
        materialize(fn())
        log(f"  compile+first-run {time.perf_counter() - t0:.1f}s")
        iters = min(args.iters, 30 if on_cpu else args.iters)
        stats = bench_fn(fn, iters, label=f"multichip {shape}")
        emit(f"solve_p99_latency_{shape}_sharded", stats,
             {"mesh": list(mesh_shape), "mode": "fast"})
        qline = {"metric": f"serve_qps_sharded_{shape}",
                 "value": round(1.0 / stats["mean"], 3), "unit": "qps",
                 "direction": "higher", "mesh": list(mesh_shape),
                 "iters": stats["iters"]}
        if TRANSPORT:
            qline["rtt_ms"] = TRANSPORT["rtt_ms"]
        log(f"serve_qps_sharded_{shape}: {qline['value']}")
        print(json.dumps(qline), flush=True)

        # Phase 2: the combine tree in isolation, at the engine's real
        # bucket widths.
        Pb = int(np.asarray(dev.pods.valid).shape[0])
        Nb = int(np.asarray(dev.nodes.valid).shape[0])
        mat = jax.device_put(
            np.random.default_rng(3).random((Pb, Nb)).astype(np.float32),
            matrix_sharding(mesh))
        combine = jax.jit(
            lambda m: constrain_replicated(m.sum(axis=1), mesh))
        materialize(combine(mat))  # compile
        cstats = bench_fn(lambda: combine(mat), iters,
                          label=f"combine {shape}")
        emit(f"shard_combine_ms_{shape}", cstats,
             {"mesh": list(mesh_shape), "matrix": [Pb, Nb]})
    finally:
        eng.close()

    # Phase 3: the 100k x 50k headline.
    if on_cpu:
        log("[multichip] cpu backend — the 100000x50000 sharded "
            "headline runs on accelerator meshes only (skipped)")
        return
    bp, bn = 100_000, 50_000
    eng = Engine(cfg, mesh=mesh)
    try:
        snap, _meta = _build(config2_scale, np.random.default_rng(22),
                             bp, bn, with_qos=True)
        dev = eng.put(snap)
        fn = lambda: eng._solve_packed_jit(dev)  # noqa: E731
        t0 = time.perf_counter()
        materialize(fn())
        log(f"  compile+first-run {time.perf_counter() - t0:.1f}s")
        stats = bench_fn(fn, max(5, min(args.iters, 20)), warmup=1,
                         label=f"multichip {bp}x{bn}")
        emit(f"solve_p99_latency_{bp}x{bn}_sharded", stats,
             {"mesh": list(mesh_shape), "mode": "fast",
              "placements_per_sec": round(bp / stats["p50"], 1)})
    finally:
        eng.close()


def bench_ledger(args):
    """Cycle flight-ledger overhead (round 18, ISSUE 13 acceptance):
    the same 2000x1000 fast solve loop run with the ledger OFF (the
    wrapper's one-attribute-read disabled path) and ON (per-dispatch
    shape-class check + one CycleRecord build/append/sentinel per
    cycle), emitted as `ledger_overhead_pct` (p50 delta as a
    percentage — acceptance: <= 1%). `compile_count_total` rides
    along: the XLA cache misses ledger.COMPILES has attributed so far
    this process — the round-over-round retrace budget ROADMAP item 4
    will drive to ~0. Both are registered lower-better in
    tools/benchdiff.py."""
    from tpusched import Engine, EngineConfig
    from tpusched import ledger as ledgermod
    from tpusched import metrics as pmetrics
    from tpusched.synth import config2_scale

    pods, nodes = min(args.pods, 2000), min(args.nodes, 1000)
    rng = np.random.default_rng(49)
    snap, _ = _build(config2_scale, rng, pods, nodes, with_qos=True)
    engine = Engine(EngineConfig(mode="fast"))
    led = ledgermod.CycleLedger(registry=pmetrics.Registry())
    churn = max(1, pods // 100)
    iters = max(20, args.iters // 10)

    def one_cycle():
        # The serving-shaped ledger work a HostScheduler cycle pays:
        # compile-counter diff, record build, ring append + rolling
        # aggregation + sentinel. Identical code both arms; only the
        # enabled flag differs.
        c0 = ledgermod.COMPILES.counters()
        res = engine.solve_async(dsnap).result()
        c1 = ledgermod.COMPILES.counters()
        led.observe(ledgermod.CycleRecord(
            ts=time.monotonic(), source="bench", pods=pods, nodes=nodes,
            running=0, placed=int((res.assignment >= 0).sum()),
            evicted=0, churn=churn, rounds=int(res.rounds),
            warm_path="cold", solve_s=res.solve_seconds,
            stages=dict(solve=res.solve_seconds),
            compiles=c1[0] - c0[0],
            compile_s=round(c1[1] - c0[1], 6),
        ))
        return ()

    try:
        dsnap = engine.put(snap)
        t0 = time.perf_counter()
        materialize(engine._solve_packed_jit(dsnap))
        log(f"  compile+first-run {time.perf_counter() - t0:.1f}s")
        log(f"[ledger] OFF arm @{pods}x{nodes} fast ({iters} iters)")
        was_default, was_watch = (ledgermod.DEFAULT.enabled,
                                  ledgermod.COMPILES.enabled)
        led.enabled = False
        ledgermod.set_enabled(False)
        try:
            off = bench_fn(one_cycle, iters, label="ledger-off")
        finally:
            ledgermod.set_enabled(True)
            ledgermod.DEFAULT.enabled = was_default
            ledgermod.COMPILES.enabled = was_watch
        led.enabled = True
        log(f"[ledger] ON arm @{pods}x{nodes} fast ({iters} iters)")
        on = bench_fn(one_cycle, iters, label="ledger-on")
    finally:
        engine.close()
    overhead_pct = ((on["p50"] - off["p50"]) / max(off["p50"], 1e-9)
                    * 100.0)
    log(f"  ledger overhead p50: {overhead_pct:+.2f}% "
        f"(off {off['p50'] * 1e3:.1f}ms -> on {on['p50'] * 1e3:.1f}ms); "
        f"{len(led.records())} records, {led.anomalies} anomalies")
    line = {
        "metric": "ledger_overhead_pct",
        "value": round(overhead_pct, 3), "unit": "pct",
        "direction": "lower", "vs_baseline": None,
        "ledger_on_p50_ms": round(on["p50"] * 1e3, 3),
        "ledger_off_p50_ms": round(off["p50"] * 1e3, 3),
        "iters": iters, "records": len(led.records()),
    }
    if TRANSPORT:
        line["rtt_ms"] = TRANSPORT["rtt_ms"]
    print(json.dumps(line), flush=True)
    total, compile_s = ledgermod.COMPILES.counters()
    line = {
        "metric": "compile_count_total",
        "value": int(total), "unit": "count",
        "direction": "lower", "vs_baseline": None,
        "compile_s_total": round(compile_s, 3),
    }
    if TRANSPORT:
        line["rtt_ms"] = TRANSPORT["rtt_ms"]
    log(f"compile_count_total: {total} ({compile_s:.1f}s wall so far "
        "this process)")
    print(json.dumps(line), flush=True)


def bench_divergence(args):
    """Fast-vs-parity agreement as NUMBERS per round (round-2 verdict
    next-step #2): identical-placement rate, placed delta, per-seed
    worst-case placed ratio, and the validity-violation count (must stay
    0) for each contention preset."""
    from tpusched import Engine, EngineConfig
    from tpusched.divergence import PRESETS, measure

    engines = (Engine(EngineConfig(mode="fast")),
               Engine(EngineConfig(mode="parity")))
    seeds = 6
    for preset in sorted(PRESETS):
        log(f"[divergence] preset={preset} seeds={seeds} @80x16")
        stats = measure(preset, seeds=seeds, engines=engines)
        row = stats.row()
        log(f"  identical_rate={row['identical_rate']} "
            f"placed_delta={row['placed_delta']} "
            f"min_placed_ratio={row['min_placed_ratio']} "
            f"violations={row['fast_violations']}")
        line = {
            "metric": f"divergence_{preset}",
            "value": row["identical_rate"],
            "unit": "identical_rate",
            "vs_baseline": None,
            "direction": "higher",
        }
        if TRANSPORT:
            line["rtt_ms"] = TRANSPORT["rtt_ms"]
        line.update({k: v for k, v in row.items() if k != "preset"})
        print(json.dumps(line), flush=True)


def bench_robustness(args):
    """Recovery time and goodput under faults (ISSUE 3): the chaos
    harness twin-runs a full host->sidecar workload fault-free and
    under the seeded default plan (sidecar restart mid-lineage with an
    UNAVAILABLE outage window, DeviceSession drop, one hung solve the
    watchdog must kill, one decode error, a kube watch flap) and
    verifies END placements are identical. Emits:

      chaos_recovery_ms     worst fault->next-completed-cycle time
      chaos_goodput_frac    placements/sec vs the fault-free twin

    Round 11 (ISSUE 6) adds the replicated-fleet section — the SAME
    kill-the-leader fault against a tpusched.replicate.ReplicaSet at
    replica counts 1/2/3:

      chaos_goodput_frac_r{1,2,3}   availability under the kill
      failover_recovery_ms_r{2,3}   kill -> next completed cycle

    PR 18 (ROADMAP item 3): fleet arms boot with the shape-class
    registry prewarm (explicit synthetic buckets), which makes the
    old warmup_arm redundant AND turns compile-freeness into harness
    assertions (zero serve-cause compiles in the fault-free twin;
    zero compiles after the kill at r>=2). New headline numbers:

      cold_start_s              fleet boot -> every replica prewarmed
      prewarm_s                 slowest replica's registry prewarm
      failover_first_request_ms kill -> next completed cycle, with a
                                compile-free promotion (no XLA term)
    """
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "tpusched_chaos",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "tools", "chaos.py"),
    )
    chaos = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chaos)
    report = chaos.run_chaos(
        n_pods=min(args.pods, 120), n_nodes=min(args.nodes, 12),
        watchdog_s=2.0, log=log,
    )
    if not report["end_state"]["identical"]:
        raise AssertionError(
            f"chaos end state diverged: {report['end_state']}"
        )
    rec = report["recovery_s"]
    worst = max(rec.values()) if rec else 0.0
    common = dict(
        end_state_identical=report["end_state"]["identical"],
        duplicated_bindings=report["end_state"]["duplicated"],
        watchdog_trips=report["chaos"]["watchdog_trips"],
        client_retries=report["chaos"]["client_retries"],
        failed_cycle_attempts=report["chaos"]["failed_cycle_attempts"],
        faults_fired=len(report["injected"]["fired"]),
    )
    for metric, value, unit, direction, extra in (
        ("chaos_recovery_ms", round(worst * 1e3, 1), "ms", "lower",
         {"recovery_ms": {k: round(v * 1e3, 1) for k, v in rec.items()}}),
        ("chaos_goodput_frac", report["goodput_frac"],
         "frac_of_fault_free", "higher",
         {"fault_free_pps": report["baseline"]["goodput_pps"],
          "chaos_pps": report["chaos"]["goodput_pps"]}),
    ):
        line = {"metric": metric, "value": value, "unit": unit,
                "vs_baseline": None, "direction": direction}
        if TRANSPORT:
            line["rtt_ms"] = TRANSPORT["rtt_ms"]
        line.update(common)
        line.update(extra)
        print(json.dumps(line), flush=True)
        log(f"{metric}: {value} {unit} {extra}")

    # High availability (ISSUE 6): the SAME kill-the-leader fault at
    # replica counts 1/2/3. At r1 the outage is an availability hole
    # the client can only back off into; at r>=2 one failover retry
    # lands on the warm standby — goodput_frac at 2 replicas must sit
    # STRICTLY above the 1-replica number (acceptance criterion).
    # outage_s=6: failover recovery is outage-INDEPENDENT (one retry
    # lands on the standby), so a long outage only degrades r1 —
    # keeping the separation structural, above the per-arm contention
    # noise of these ~15s runs. prewarm=True replaces the old
    # warmup_arm: every arm is born warm (and asserts it), so compile
    # noise is gone from BOTH sides of the goodput fraction.
    goodput_by_r = {}
    for replicas in (1, 2, 3):
        rep = chaos.run_chaos_fleet(
            n_pods=min(args.pods, 120), n_nodes=min(args.nodes, 12),
            batch_size=max(min(args.pods, 120) // 10, 1),
            replicas=replicas, outage_s=6.0, kill_after_cycle=2,
            prewarm=True,
            log=log,
        )
        if not rep["end_state"]["identical"]:
            raise AssertionError(
                f"fleet chaos end state diverged at r{replicas}: "
                f"{rep['end_state']}"
            )
        goodput_by_r[replicas] = rep["goodput_frac"]
        frec = rep["failover_recovery_s"]
        line = {
            "metric": f"chaos_goodput_frac_r{replicas}",
            "value": rep["goodput_frac"], "unit": "frac_of_fault_free",
            "vs_baseline": None,
            "end_state_identical": rep["end_state"]["identical"],
            "duplicated_bindings": rep["end_state"]["duplicated"],
            "client_failovers": rep["chaos"]["client_failovers"],
            "takeovers": rep["chaos"]["takeovers"],
            "delta_fallbacks": rep["chaos"]["delta_fallbacks"],
            "failover_recovery_ms": (round(frec * 1e3, 1)
                                     if frec is not None else None),
            "outage_s": rep["outage_s"],
        }
        if TRANSPORT:
            line["rtt_ms"] = TRANSPORT["rtt_ms"]
        print(json.dumps(line), flush=True)
        log(f"chaos_goodput_frac_r{replicas}: {rep['goodput_frac']} "
            f"(failover_recovery_ms={line['failover_recovery_ms']})")
        if replicas >= 2:
            line = {
                "metric": f"failover_recovery_ms_r{replicas}",
                "value": line["failover_recovery_ms"], "unit": "ms",
                "vs_baseline": None,
                "goodput_frac": rep["goodput_frac"],
            }
            print(json.dumps(line), flush=True)
        if replicas == 2:
            # The r2 run is the headline failover story: surface its
            # boot cost and compile-free first-request latency as
            # first-class metrics (benchdiff trends them lower-better).
            sc = rep["serve_compiles"]
            for metric, value, unit in (
                ("cold_start_s", rep["cold_start_s"], "s"),
                ("prewarm_s", rep["prewarm_s"], "s"),
                ("failover_first_request_ms",
                 rep["failover_first_request_ms"], "ms"),
            ):
                line = {
                    "metric": metric, "value": value, "unit": unit,
                    "vs_baseline": None, "direction": "lower",
                    "serve_compiles_baseline": sc["baseline"],
                    "serve_compiles_after_takeover": sc["after_takeover"],
                }
                print(json.dumps(line), flush=True)
                log(f"{metric}: {value} {unit} (serve compiles "
                    f"baseline={sc['baseline']} "
                    f"after_takeover={sc['after_takeover']})")
    if goodput_by_r[2] <= goodput_by_r[1]:
        log(f"WARNING: goodput at 2 replicas ({goodput_by_r[2]}) did "
            f"not beat 1 replica ({goodput_by_r[1]}) — HA acceptance "
            "criterion not met on this run")


def bench_sim(args):
    """SLO attainment via the virtual-time simulator (ISSUE 5, 9): the
    twin run — same scenario, same seed, QoS-driven vs static-priority
    baseline (qos_gain=0) — reproducing the reference paper's central
    claim as bench numbers:

      slo_attainment_frac         fraction of SLO-carrying pods whose
                                  final observed availability met their
                                  target under QoS-driven scheduling
      attainment_gain_vs_static   that fraction minus the static
                                  baseline's, on an identical timeline

    --sim-scenario all (ISSUE 9) runs the MATRIX instead: twin runs
    across workloads.MATRIX_SCENARIOS (>= 6 Borg/Azure-shaped
    scenarios incl. autoscale + gang pressure), emitting per scenario
    slo_attainment_frac_<sc> / attainment_gain_vs_static_<sc> /
    preemption_churn[_static]_<sc>, each line carrying an explicit
    "direction" annotation so tools/benchdiff.py flags regressions the
    right way (attainment higher-better, churn lower-better).

    Deterministic: the emitted event-log hashes pin both arms' full
    causal chains (arrivals, binds, evictions, completions) for the
    seed, so regressions show as hash changes, not metric wobble.
    """
    import dataclasses as _dc

    from tpusched.sim import report as sim_report
    from tpusched.sim.driver import matrix_run, twin_run
    from tpusched.sim.workloads import SCENARIOS

    if args.sim_scenario == "all":
        matrix = matrix_run(seed=args.sim_seed,
                            horizon_s=args.sim_horizon, log=log)
        log(sim_report.render_matrix(matrix))
        for row in matrix["rows"]:
            name = row["scenario"]
            common = dict(
                scenario=name, seed=args.sim_seed,
                slo_pods=row["slo_pods"],
                hash_qos=row["hash_qos"], hash_static=row["hash_static"],
            )
            for metric, value, direction in (
                (f"slo_attainment_frac_{name}",
                 row["slo_attainment_frac"], "higher"),
                (f"slo_attainment_frac_static_{name}",
                 row["slo_attainment_frac_static"], "higher"),
                (f"attainment_gain_vs_static_{name}",
                 row["attainment_gain_vs_static"], "higher"),
                (f"preemption_churn_{name}",
                 row["preemption_churn"], "lower"),
                (f"preemption_churn_static_{name}",
                 row["preemption_churn_static"], "lower"),
            ):
                line = {"metric": metric, "value": value, "unit": "frac",
                        "vs_baseline": None, "direction": direction}
                line.update(common)
                print(json.dumps(line), flush=True)
            log(f"slo_attainment_frac_{name}: "
                f"{row['slo_attainment_frac']} "
                f"(static {row['slo_attainment_frac_static']}, churn "
                f"{row['preemption_churn']}/"
                f"{row['preemption_churn_static']})")
        return

    sc = SCENARIOS[args.sim_scenario]
    if args.sim_horizon is not None:
        sc = _dc.replace(sc, horizon_s=args.sim_horizon)
    log(f"[sim] twin run: scenario={sc.name} seed={args.sim_seed} "
        f"horizon={sc.horizon_s}s nodes={sc.n_nodes}")
    twin = twin_run(sc, seed=args.sim_seed, log=log)
    log(sim_report.render_twin(twin))
    q, s = twin["qos"], twin["static"]
    common = dict(
        scenario=sc.name, seed=args.sim_seed,
        horizon_s=q["horizon_s"], slo_pods=q["slo_pods"],
        completions_qos=q["completions"], completions_static=s["completions"],
        evictions_qos=q["evicted"], evictions_static=s["evicted"],
        wait_p99_s_qos=q["wait_p99_s"], wait_p99_s_static=s["wait_p99_s"],
        hash_qos=q["event_log_hash"], hash_static=s["event_log_hash"],
    )
    for metric, value in (
        ("slo_attainment_frac", twin["slo_attainment_frac"]),
        ("attainment_gain_vs_static", twin["attainment_gain_vs_static"]),
    ):
        line = {"metric": metric, "value": value, "unit": "frac",
                "vs_baseline": None, "direction": "higher"}
        if TRANSPORT:
            line["rtt_ms"] = TRANSPORT["rtt_ms"]
        line.update(common)
        print(json.dumps(line), flush=True)
        log(f"{metric}: {value}")


class _HostSortedQueue:
    """The baseline arm's pending store: the CLASSIC host path the
    device queue replaces — records in a dict, and every window() pays
    the O(pending) Python recompute (availability decay per pod, the
    host.py `_with_avail` + sort shape) plus a full re-sort. Duck-types
    the DeviceQueue surface IngestGate needs, so BOTH bench arms run
    behind the identical admission gate and differ only in who ranks
    the backlog."""

    def __init__(self, bound=None, qos_gain: float = 1000.0):
        self.bound = bound
        self.qos_gain = float(qos_gain)
        self._recs: dict[str, dict] = {}
        self._seq = 0

    @property
    def capacity(self):
        return self.bound or len(self._recs)

    @property
    def depth(self):
        return len(self._recs)

    def __contains__(self, name):
        return name in self._recs

    def upsert(self, name, *, base_priority=0.0, slo_target=0.0,
               submitted=0.0, run_seconds=0.0, parked_until=0.0,
               tenant=0, seq=None):
        if name not in self._recs and self.bound is not None \
                and len(self._recs) >= self.bound:
            return False
        self._seq += 1
        self._recs[name] = dict(
            priority=float(base_priority), slo_target=float(slo_target),
            submitted=float(submitted), run_seconds=float(run_seconds),
            parked_until=float(parked_until), seq=self._seq)
        return True

    def remove(self, names):
        n = 0
        for nm in names:
            n += self._recs.pop(nm, None) is not None
        return n

    def window(self, now, w):
        # O(pending) every cycle: the cost model under indictment.
        scored = []
        for nm, r in self._recs.items():
            if r["parked_until"] > now:
                continue
            age = now - r["submitted"]
            avail = 1.0 if age < 1e-9 else min(  # tpl: disable=TPL004(baseline arm mirrors the kernel clip op-for-op on bench-generated finite inputs; bench.py defers every tpusched import so the bare CLI stays light)
                max(r["run_seconds"] / age, 0.0), 1.0)
            pressure = min(max(r["slo_target"] - avail, 0.0), 1.0)  # tpl: disable=TPL004(same baseline-arm rationale as avail above)
            scored.append(
                (-(r["priority"] + self.qos_gain * pressure),
                 r["seq"], nm))
        scored.sort()
        return [nm for _, _, nm in scored[:w]], len(scored), \
            len(self._recs)


def bench_ingest(args):
    """Arrival-storm ingest bench (ISSUE 20): an open-loop storm at a
    million-pod-per-sim-day arrival rate, arriving at 2x the drain
    capacity — the firehose regime the admission gate exists for. The
    two arms differ ONLY in how pending pods are held and ranked:

      device arm    IngestGate (token bucket, bounded DeviceQueue):
                    host work is O(arrivals) — dict upserts plus one
                    dirty-row scatter — and the availability-decay
                    rank runs in-kernel over the bounded table; the
                    overflow half of the storm is SHED with a
                    retry-after (re-offered once, then dropped: open
                    loop)
      hostsort arm  the pre-admission-control world: every arrival
                    lands in an UNBOUNDED pending dict and every cycle
                    pays the classic O(pending) Python recompute + full
                    re-sort (_HostSortedQueue). Under sustained
                    overload pending grows without bound and the cycle
                    cost grows with it.

    Both arms are rated on their TERMINAL cycles (the last fifth of
    their run): an open-loop storm has no steady state for the
    hostsort arm — its sustainable arrival rate is wherever it has
    degraded to, not its warm-start average. The hostsort arm is
    cycle-capped (--ingest-host-cycles, logged loudly) because running
    it to the full million is exactly the quadratic meltdown under
    indictment; the cap UNDERSTATES the speedup.

    Emits ingest_pods_per_sec_{device,hostsort} (terminal drain
    throughput), ingest_speedup_x (the >= 10x acceptance ratio),
    queue_depth_{p50,p99} read back from the gate's source="ingest"
    ledger records, admission_latency_ms_{p50,p99} (virtual-clock
    first-offer -> admit, so shed-then-retry waits are priced in), and
    ingest_shed_frac — each stamped with an explicit direction for
    tools/benchdiff.py."""
    from tpusched import ledger as ledgering
    from tpusched.device_state import DeviceQueue
    from tpusched.ingest import IngestGate

    n_pods = int(args.ingest_pods)
    w = 256                     # drain window per cycle
    batch = 2 * w               # arrivals per cycle: 2x overload
    qcap = 16384                # device arm's bounded pending table
    day_s = 86400.0
    n_cycles = max(n_pods // batch, 1)
    dt = day_s / n_cycles       # virtual seconds per cycle
    rng = np.random.default_rng(0)
    prio = rng.uniform(10.0, 100.0, n_pods).astype(np.float32)
    slo = rng.uniform(0.5, 0.999, n_pods).astype(np.float32)
    log(f"[ingest] storm: {n_pods} pods over a virtual day "
        f"({n_pods / day_s * 86400:.0f} pods/sim-day), {n_cycles} "
        f"cycles, {batch} arrivals vs {w} drains per cycle")

    def run_arm(queue, gate, max_cycles):
        # Every offer/drain passes `now` explicitly (the virtual
        # clock); the gate's own clock only seeds the buckets at t=0.
        queue.window(0.0, w)      # compile/warm before the clock starts
        cycle_s, drained = [], []
        retry: list[int] = []
        for c in range(max_cycles):
            vnow = (c + 1) * dt
            lo = c * batch
            offer = retry + list(range(lo, min(lo + batch, n_pods)))
            pods = [dict(name=f"p{i}", priority=float(prio[i]),
                         slo_target=float(slo[i]), submitted=vnow)
                    for i in offer]
            t0 = time.perf_counter()
            res = gate.offer(pods, now=vnow)
            got = gate.take_window(vnow, w=w)
            cycle_s.append(time.perf_counter() - t0)
            # Open loop: one retry round, then the shed pod is dropped
            # (a shed index < lo already had its retry last cycle).
            retry = [i for i in (int(nm[1:]) for nm in res["shed"])
                     if i >= lo]
            drained.append(len(got))
        tail = max(len(cycle_s) // 5, 1)
        rate = sum(drained[-tail:]) / sum(cycle_s[-tail:])
        return rate, sum(cycle_s), sum(drained)

    lg = ledgering.CycleLedger(capacity=n_cycles + 1)
    dev_q = DeviceQueue(capacity=qcap, bound=qcap)
    dev_gate = IngestGate(dev_q, rate=1.05 * w / dt, burst=2.0 * w,
                          clock=lambda: 0.0, ledger=lg)
    host_cycles = min(int(args.ingest_host_cycles), n_cycles)
    host_q = _HostSortedQueue(bound=None)
    host_gate = IngestGate(host_q, rate=1e12, burst=1e12,
                           clock=lambda: 0.0)

    dev_rate, dev_wall, dev_drained = run_arm(dev_q, dev_gate, n_cycles)
    if host_cycles < n_cycles:
        log(f"[ingest] hostsort arm capped at {host_cycles}/{n_cycles} "
            f"cycles — unbounded O(pending) per cycle; its terminal "
            f"rate only falls further with every additional cycle")
    host_rate, host_wall, host_drained = run_arm(
        host_q, host_gate, host_cycles)
    speedup = dev_rate / host_rate if host_rate > 0 else float("inf")

    depths = np.asarray([r.queue_depth for r in lg.records()], float)
    lat_ms = np.asarray(dev_gate.admission_latency_s, float) * 1e3
    stats = dev_gate.stats()
    log(f"[ingest] device {dev_rate:,.0f} pods/s terminal "
        f"({dev_drained} drained in {dev_wall:.2f}s) vs hostsort "
        f"{host_rate:,.0f} pods/s terminal ({host_drained} drained in "
        f"{host_wall:.2f}s, end depth {host_q.depth}) -> "
        f"{speedup:.1f}x; device depth p50/p99 "
        f"{np.percentile(depths, 50):.0f}/{np.percentile(depths, 99):.0f}"
        f"; shed_frac {stats['shed_frac']}")
    common = dict(pods=n_pods, cycles=n_cycles, batch=batch, window=w,
                  queue_capacity=qcap, host_cycles=host_cycles,
                  host_end_depth=host_q.depth,
                  scatters=getattr(dev_gate.queue, "scatters", None))
    for metric, value, unit, direction in (
        ("ingest_pods_per_sec_device", round(dev_rate, 1), "pods/s",
         "higher"),
        ("ingest_pods_per_sec_hostsort", round(host_rate, 1), "pods/s",
         "higher"),
        ("ingest_speedup_x", round(speedup, 2), "x", "higher"),
        ("queue_depth_p50", float(np.percentile(depths, 50)), "pods",
         "lower"),
        ("queue_depth_p99", float(np.percentile(depths, 99)), "pods",
         "lower"),
        ("admission_latency_ms_p50",
         round(float(np.percentile(lat_ms, 50)), 3), "ms", "lower"),
        ("admission_latency_ms_p99",
         round(float(np.percentile(lat_ms, 99)), 3), "ms", "lower"),
        ("ingest_shed_frac", stats["shed_frac"], "frac", "lower"),
    ):
        line = {"metric": metric, "value": value, "unit": unit,
                "vs_baseline": None, "direction": direction}
        line.update(common)
        print(json.dumps(line), flush=True)


BENCHES = {
    "divergence": bench_divergence,
    "pairwise": bench_pairwise,
    "gangs": bench_gangs,
    "preemption": bench_preemption,
    "pipeline": bench_pipeline,
    "e2e": bench_e2e,
    "wire": bench_wire,
    "serving": bench_serving,
    "robustness": bench_robustness,
    "sim": bench_sim,
    "explain": bench_explain,
    "warm": bench_warm,
    "ledger": bench_ledger,
    "multichip": bench_multichip,
    "ingest": bench_ingest,
    # headline runs last so the final stdout line is the headline metric
    # (parity mode last within it — the stock-semantics north-star claim)
    "headline": bench_headline,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pods", type=int, default=10_000)
    ap.add_argument("--nodes", type=int, default=5_000)
    # 200 iterations: at n=100 a single outlier sits at the p99
    # position (99.01 of 100); at n=200 it lies beyond it.
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--what", choices=["score", "score_top1", "solve"],
                    default="solve")
    ap.add_argument("--mode", choices=["both", "fast", "parity"],
                    default="both",
                    help="both = fast then parity (parity last: the "
                         "stock-semantics headline is the final line)")
    ap.add_argument("--only", choices=sorted(BENCHES), default=None,
                    help="run a single bench instead of all")
    ap.add_argument("--dump", default=None,
                    help="save the headline snapshot to this .npz")
    ap.add_argument("--replay", default=None,
                    help="load the headline snapshot from this .npz")
    ap.add_argument("--profile", default=None,
                    help="write a jax.profiler trace to this directory")
    ap.add_argument("--serve-clients", type=int, default=4,
                    help="concurrent connections in the serving bench")
    ap.add_argument("--serve-cycles", type=int, default=30,
                    help="cycles per client per serving phase")
    ap.add_argument("--serve-what", choices=["both", "assign", "score"],
                    default="both",
                    help="serving phases: distinct-lineage Assign "
                         "fan-in, shared-store coalesced scoring, or "
                         "both")
    ap.add_argument("--sim", action="store_true",
                    help="run ONLY the virtual-time simulator bench "
                         "(twin-run SLO attainment; equivalent to "
                         "--only sim)")
    ap.add_argument("--sim-scenario", default="pressure_skew",
                    help="sim bench scenario (tpusched.sim.workloads."
                         "SCENARIOS), or 'all' for the twin-run "
                         "matrix across MATRIX_SCENARIOS")
    ap.add_argument("--sim-seed", type=int, default=0)
    ap.add_argument("--ingest-pods", type=int, default=1_000_000,
                    help="arrival-storm size for --only ingest (the "
                         "storm spans one virtual day, so the default "
                         "is the million-pod/sim-day regime)")
    ap.add_argument("--ingest-host-cycles", type=int, default=300,
                    help="cycle cap for the hostsort baseline arm "
                         "(O(pending) Python per cycle; its rate is "
                         "measured on its own window)")
    ap.add_argument("--sim-horizon", type=float, default=None,
                    help="override the scenario's virtual horizon (s)")
    ap.add_argument("--trace", choices=["on", "off"], default="on",
                    help="span collection (tpusched.trace) during the "
                         "benches; 'off' measures the disabled "
                         "zero-overhead path (ISSUE 4 acceptance: "
                         "serve_qps within noise of traced runs)")
    args = ap.parse_args()

    from tpusched import trace as _tr

    _tr.set_enabled(args.trace == "on")

    # BEFORE any jit: cache config must precede the first compile.
    from tpusched.shapeclass import enable_persistent_cache

    log(f"persistent compile cache: {enable_persistent_cache()}")

    import jax

    log(f"backend={jax.default_backend()} devices={jax.devices()}")
    if args.sim or args.only:
        measure_transport()
        BENCHES["sim" if args.sim else args.only](args)
        return
    failed = []
    for name, fn in BENCHES.items():
        try:
            measure_transport()
            fn(args)
        except Exception as e:  # run the rest, then fail the whole run
            log(f"[{name}] FAILED: {type(e).__name__}: {e}")
            failed.append(name)
    if failed:
        sys.exit(f"bench sections failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
