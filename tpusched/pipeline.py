"""Double-buffered batch pipeline (SURVEY.md §2.3 "Pipeline parallel").

The schedule cycle has three phases with different engines:
  decode  — proto -> SnapshotBuilder -> padded arrays   (host CPU)
  H2D + solve — device compute                          (TPU)
  fetch   — packed result device->host                  (transport)

A sequential loop pays decode_k+1 strictly after fetch_k. Here jax
dispatch is asynchronous, so dispatching batch k and then decoding batch
k+1 overlaps host decode with device compute; batch k's result is
fetched on a background thread (np.asarray releases the GIL while it
waits), so the main thread's GIL-bound decode is never blocked on it.

Wall-clock per batch approaches max(decode, solve + fetch) instead of
their sum — the "double-buffered" overlap SURVEY.md §7 hard part 6 asks
for.

This is for streams of INDEPENDENT snapshots (a sidecar serving many
schedulers, replay/bench pipelines). A single cluster's consecutive
cycles feed back (cycle k's binds change cycle k+1's snapshot), so they
cannot be pipelined — same limit as the reference's one-at-a-time
scheduleOne loop.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Iterable, Iterator

from tpusched import ledger as ledgering
from tpusched.engine import Engine, SolveResult
from tpusched.snapshot import ClusterSnapshot


def solve_stream(
    engine: Engine,
    batches: Iterable[Any],
    decode: Callable[[Any], tuple[ClusterSnapshot, Any]] | None = None,
) -> Iterator[tuple[Any, SolveResult]]:
    """Pipeline a stream of batches through the engine.

    batches: an iterable of raw batch items. decode(item) must return
    (ClusterSnapshot, meta); None means items already ARE
    (snapshot, meta) pairs. Yields (meta, SolveResult) in order.

    The generator keeps exactly one batch in flight on the device while
    the host decodes the next (double buffering): dispatch(k) ->
    decode(k+1) -> fetch(k) -> dispatch(k+1) -> ...

    Round 6: the dispatch + background-fetch mechanics moved INTO the
    engine (Engine.solve_async, one shared ordered fetch worker), so
    this generator is now just the stream-shaped driver and the SAME
    overlap serves single requests in rpc/server.py's staged handlers.
    """
    decode = decode or (lambda item: item)

    in_flight = None  # (meta, PendingFetch)
    for item in batches:
        snap, meta = decode(item)  # overlaps the in-flight fetch
        if in_flight is not None:
            pmeta, pending = in_flight
            yield pmeta, pending.result()
        in_flight = (meta, engine.solve_async(engine.put(snap)))
    if in_flight is not None:
        pmeta, pending = in_flight
        yield pmeta, pending.result()


def warm_cycle_stream(
    engine: Engine,
    device,
    deltas: Iterable[dict],
    incremental: bool = False,
    ledger=None,
) -> Iterator[tuple[Any, SolveResult]]:
    """Pipeline consecutive DELTA CYCLES of one device-resident lineage
    through the warm-start path (ROADMAP item 3): `device` is a
    tpusched.device_state.DeviceSnapshot, each item of `deltas` is a
    dict of DeviceSnapshot.apply kwargs. Yields (ApplyStats,
    SolveResult) in order.

    Unlike solve_stream (independent snapshots), consecutive cycles here
    share one lineage and FEED FORWARD through the carried tableau —
    they cannot be reordered, but the host-side work of cycle k+1
    (apply(): record normalization, dirty-set accounting, scatter-index
    building) still overlaps cycle k's in-flight result fetch, because
    apply() mutates the host mirror and builds NEW device arrays
    functionally while the dispatched program holds the old ones.

    Contract note: the engine commits the refreshed warm handle at
    dispatch time; a caller that abandons the stream mid-flight after a
    fetch error should device.invalidate_warm("stream_error").

    incremental=True (ISSUE 12): route each cycle through the
    bounded-divergence warm path (Engine.solve_warm_async(incremental=
    True)). The assignment CARRY is committed at result-join time, so
    the stream joins cycle k BEFORE dispatching cycle k+1 — apply(k+1)
    (the host-side record work) still overlaps fetch(k), only the
    dispatch is deferred; dispatching early would seed k+1 from the
    k-1 carry and widen the divergence for no latency win (the device
    is serial across cycles of one lineage anyway).

    ledger (round 18, ISSUE 13): optional tpusched.ledger.CycleLedger;
    None falls back to the process default. Each cycle appends one
    CycleRecord (source="pipeline") at its result join — warm path
    taken, churn carried by the delta, commit rounds/frontier, and
    the XLA cache misses its dispatch paid."""
    lg = ledger or ledgering.DEFAULT

    def _join(entry):
        stats, pending, ctx = entry
        res = pending.result()
        if ctx is not None:
            evicted = 0
            if res.evicted is not None:
                evicted = int(res.evicted.sum())
            frontier = 0
            if res.inc_info:
                frontier = int(res.inc_info.get("frontier", 0))
            lg.observe(ledgering.CycleRecord(
                ts=time.time(), source="pipeline", pods=ctx["pods"],
                nodes=ctx["nodes"], running=ctx["running"],
                placed=int((res.assignment[: ctx["pods"]] >= 0).sum()),
                evicted=evicted, churn=ctx["churn"], frontier=frontier,
                rounds=int(res.rounds), warm_path=ctx["path"],
                solve_s=res.solve_seconds,
                stages=dict(solve=res.solve_seconds),
                compiles=ctx["compiles"],
                compile_s=round(ctx["compile_s"], 6),
            ))
        return stats, res

    in_flight = None  # (ApplyStats, PendingFetch, ledger ctx | None)
    for delta in deltas:
        stats = device.apply(**delta)
        marker = device.warm_marker()
        comp0 = ledgering.COMPILES.counters() if lg.enabled else (0, 0.0)
        if incremental:
            if in_flight is not None:
                yield _join(in_flight)
                in_flight = None
            pending = engine.solve_warm_async(device, incremental=True)
        else:
            pending = engine.solve_warm_async(device)
        ctx = None
        if lg.enabled:
            # Captured at dispatch: commit_warm stamped the path
            # counters, the jit wrapper recorded any compile this
            # dispatch paid, and meta still names THIS cycle's rows (a
            # concurrent next apply would shift them before the join).
            comp1 = ledgering.COMPILES.counters()
            meta = device.meta
            ctx = dict(path=device.warm_path_taken(marker),
                       pods=meta.n_pods, nodes=meta.n_nodes,
                       running=meta.n_running,
                       churn=stats.churn_records,
                       compiles=comp1[0] - comp0[0],
                       compile_s=comp1[1] - comp0[1])
        if in_flight is not None:
            yield _join(in_flight)
        in_flight = (stats, pending, ctx)
    if in_flight is not None:
        yield _join(in_flight)
