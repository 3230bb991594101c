"""Deterministic chaos harness for the serving path (ISSUE 3).

Runs the SAME synthetic workload twice through the full boundary
(FakeApiServer -> HostScheduler -> DeltaSession -> gRPC sidecar ->
Engine): once fault-free, once under a seeded fault schedule — then
verifies the END-STATE-IDENTICAL guarantee: every pod lands on the
same node in both runs, no binding lost, none duplicated.

Two fault layers compose:

  * a tpusched.faults.FaultPlan threaded through server + engine
    (in-process faults: a hung solve at "engine.fetch" that the
    watchdog must convert to DEADLINE_EXCEEDED, a DeviceSession drop
    at "server.session", a decode error at "server.decode");
  * DRIVER events between host cycles (process-level faults a plan
    inside the server cannot express): a sidecar restart mid-lineage
    — optionally with an outage window so the client's UNAVAILABLE
    backoff+retry is exercised, not just the FAILED_PRECONDITION
    resync — and a kube watch flap (change hints invalidated, the
    informer-relist contract: the next delta must full-diff).

Determinism: the cluster is seeded, the fault plan is seeded, the
host's per-cycle batches slice a stable pending order, and the solver
is deterministic — so the chaos run must reproduce the fault-free
placements exactly or the harness fails loudly. Recovery time (fault
event -> next completed cycle) and goodput (placements/sec vs the
fault-free run) come out in the report; tests/test_faults.py's chaos
smoke drives this module.

Round 11 (ISSUE 6) adds the FLEET experiment (`run_chaos_fleet`,
--replicas): the same twin-run discipline over an N-replica
tpusched.replicate.ReplicaSet with a kill-the-leader fault — the
client fails over along its ordered endpoint list, the warm standby
promotes, and END placements must still be identical with zero
lost/duplicated binds. goodput_frac at replica counts 1/2/3 under the
SAME kill is the high-availability claim as a bench number.

Round 25 (ISSUE 20) adds the FRONT-DOOR experiment (`run_chaos_ingest`,
--ingest): a shed-heavy Enqueue storm through client -> gRPC ->
IngestGate -> bounded DeviceQueue, twin-run with drop/error shots at
the ``ingest.enqueue`` fault site. Full sheds surface as
RESOURCE_EXHAUSTED and ride the SAME client retry contract as every
other rpc; gate-side dedup makes retries idempotent — the chaos arm
must drain the identical pod set with zero lost/duplicated pods.

Usage:
    JAX_PLATFORMS=cpu python tools/chaos.py --pods 120 --nodes 12
    python tools/chaos.py --seed 7 --json report.json
    python tools/chaos.py --replicas 2 --json fleet.json
    python tools/chaos.py --ingest --pods 240 --json ingest.json
"""

from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import numpy as np

from tpusched.config import EngineConfig
from tpusched.faults import FaultPlan, FaultRule
from tpusched.host import Conflict, FakeApiServer, HostScheduler, \
    build_synthetic_cluster, synthetic_buckets
from tpusched.ledger import COMPILES
from tpusched.shapeclass import CAUSE_PREWARM


def _serve_compiles() -> int:
    """Compiles paid OUTSIDE prewarm so far, process-wide. Prewarm-
    cause traces are boot work by construction (Engine.prewarm tags
    them); everything else — 'serve' — is a request-path cache miss,
    exactly what a prewarmed fleet must never pay. Arms diff this
    around their measured window."""
    return sum(v for cause, v in COMPILES.cause_counts().items()
               if cause != CAUSE_PREWARM)


class _CountingApi(FakeApiServer):
    """FakeApiServer that counts bind conflicts: with a single host
    driving it, every conflict IS a duplicated-binding attempt (nobody
    else binds), so `conflicts` must stay 0 in a correct chaos run."""

    def __init__(self):
        super().__init__()
        self.conflicts = 0

    def bind(self, pod_name: str, node_name: str) -> None:
        try:
            super().bind(pod_name, node_name)
        except Conflict:
            self.conflicts += 1
            raise


class _Sidecar:
    """An in-process sidecar that can be killed and restarted on the
    SAME port (the client's channel reconnects transparently)."""

    def __init__(self, port: int = 0, **make_kw):
        from tpusched.rpc.server import make_server

        self._make_kw = make_kw
        self.server, self.port, self.svc = make_server(
            f"127.0.0.1:{port}", **make_kw
        )
        self.server.start()
        self.restarts = 0
        # Counters survive restarts (the per-service ones die with the
        # killed process image): accumulated at stop() time.
        self.watchdog_trips = 0
        self.replayed_requests = 0
        self._stopped = False

    def stop(self) -> None:
        # Idempotent: a cleanup close racing the outage window must not
        # stop the same service twice (double-counting its counters).
        if self._stopped:
            return
        self._stopped = True
        self.server.stop(0)
        self.svc.close()
        self.watchdog_trips += self.svc.watchdog_trips
        self.replayed_requests += self.svc.replayed_requests

    def start_again(self) -> None:
        from tpusched.rpc.server import make_server

        self.server, port, self.svc = make_server(
            f"127.0.0.1:{self.port}", **self._make_kw
        )
        if port != self.port:
            raise RuntimeError(f"could not rebind port {self.port}")
        self.server.start()
        self.restarts += 1
        self._stopped = False

    def restart(self) -> None:
        self.stop()
        self.start_again()

    def close(self) -> None:
        self.stop()


def make_default_plan(watchdog_s: float, seed: int | None = None,
                      window: int = 8) -> FaultPlan:
    """The canonical chaos plan: one hung solve (2.5x the watchdog —
    it MUST trip), one DeviceSession drop, one decode error. seed=None
    pins the indices (unit-test friendly); a seed draws them from the
    first `window` invocations of each site."""
    if seed is None:
        return FaultPlan([
            FaultRule("engine.fetch", "delay", at={2},
                      delay_s=2.5 * watchdog_s),
            FaultRule("server.session", "drop", at={1}),
            FaultRule("server.decode", "error", at={4},
                      message="chaos: injected decode failure"),
        ])
    return FaultPlan.seeded(seed, {
        "engine.fetch": dict(kind="delay", n=1, window=window,
                             delay_s=2.5 * watchdog_s),
        "server.session": dict(kind="drop", n=1, window=window),
        "server.decode": dict(kind="error", n=1, window=window,
                              message="chaos: injected decode failure"),
    })


def _placements(api: FakeApiServer) -> dict[str, str]:
    return {p["name"]: p["node"] for p in api.bound_pods()}


def _drive(host: HostScheduler, events: dict, max_cycles: int,
           max_failed_attempts: int = 60) -> dict:
    """Run host cycles, applying driver `events` (completed-cycle-count
    -> [(kind, fn), ...]) and measuring per-fault recovery time (event
    -> next COMPLETED cycle). Transient rpc failures re-drive the
    cycle, like HostScheduler.run_until_idle."""
    completed = 0
    failed = 0
    pending_recovery: dict[str, float] = {}
    recovery_s: dict[str, float] = {}
    while completed < max_cycles:
        for kind, fn in events.pop(completed, []):
            fn()
            pending_recovery.setdefault(kind, time.perf_counter())
        try:
            stats = host.cycle()
        except BaseException as e:
            if not host._transient_rpc_error(e):
                raise
            failed += 1
            if failed > max_failed_attempts:
                raise
            continue
        if stats is None:
            if events:
                # Queue drained before a scheduled event: nothing left
                # for it to disturb — fire the stragglers as no-ops so
                # the report shows them (count as instant recovery).
                for evs in events.values():
                    for kind, fn in evs:
                        fn()
                        recovery_s.setdefault(kind, 0.0)
                events.clear()
            break
        completed += 1
        now = time.perf_counter()
        for kind, t0 in pending_recovery.items():
            recovery_s.setdefault(kind, now - t0)
        pending_recovery.clear()
    return dict(cycles=completed, failed_attempts=failed,
                recovery_s={k: round(v, 4) for k, v in recovery_s.items()})


def run_chaos(
    n_pods: int = 120,
    n_nodes: int = 12,
    seed: int = 0,
    batch_size: int | None = None,
    watchdog_s: float = 1.0,
    outage_s: float = 0.4,
    plan: FaultPlan | None = None,
    plan_seed: int | None = None,
    restart_after_cycle: int = 1,
    flap_after_cycle: int = 2,
    log=print,
) -> dict:
    """One full chaos experiment; returns the report dict (see module
    docstring). Faults covered: sidecar restart mid-lineage (with an
    UNAVAILABLE outage window), DeviceSession loss, one hung solve
    (watchdog), one decode error, and a kube watch flap."""
    from tpusched.rpc.client import SchedulerClient

    cfg = EngineConfig(mode="fast")
    batch = batch_size or max(n_pods // 4, 1)

    def fresh_api():
        api = _CountingApi()
        build_synthetic_cluster(api, np.random.default_rng(seed),
                                n_pods, n_nodes)
        return api

    # -- fault-free twin ----------------------------------------------------
    base_side = _Sidecar(config=cfg, watchdog_s=watchdog_s)
    base_client = SchedulerClient(f"127.0.0.1:{base_side.port}",
                                  retry_seed=seed)
    api0 = fresh_api()
    host0 = HostScheduler(api0, cfg, client=base_client, batch_size=batch)
    try:
        t0 = time.perf_counter()
        base_drive = _drive(host0, {}, max_cycles=200)
        base_wall = time.perf_counter() - t0
        base_placements = _placements(api0)
        base_placed = sum(c.placed for c in host0.cycles)
    finally:
        host0.close()
        base_client.close()
        base_side.close()
    log(f"[chaos] fault-free: {base_drive['cycles']} cycles, "
        f"{base_placed} placed in {base_wall:.2f}s")

    # -- chaos run ----------------------------------------------------------
    plan = plan if plan is not None else make_default_plan(
        watchdog_s, seed=plan_seed
    )
    side = _Sidecar(config=cfg, watchdog_s=watchdog_s, faults=plan)
    client = SchedulerClient(f"127.0.0.1:{side.port}", retry_seed=seed)
    api = fresh_api()
    host = HostScheduler(api, cfg, client=client, batch_size=batch)
    timers: list = []

    def restart_with_outage():
        # Stop now; come back only after outage_s — the cycles in the
        # window exercise UNAVAILABLE backoff+retry, then the first
        # delta against the fresh server exercises FAILED_PRECONDITION
        # -> full-snapshot resync (the mid-lineage crash-resync path).
        side.stop()
        import threading

        t = threading.Timer(outage_s, side.start_again)
        t.name = "tpusched-chaos-restart"
        t.daemon = True
        t.start()
        timers.append(t)

    def kube_flap():
        # The FakeApiServer twin of an informer re-list: hints are no
        # longer trustworthy, the next delta must diff everything.
        api.restore_changed(None)

    events: dict[int, list] = {}
    events.setdefault(restart_after_cycle, []).append(
        ("sidecar_restart", restart_with_outage))
    events.setdefault(flap_after_cycle, []).append(
        ("kube_watch_flap", kube_flap))
    try:
        t0 = time.perf_counter()
        chaos_drive = _drive(host, events, max_cycles=400)
        chaos_wall = time.perf_counter() - t0
        chaos_placements = _placements(api)
        chaos_placed = sum(c.placed for c in host.cycles)
        health = client.health()
        delta = host._delta
    finally:
        # An exception mid-run (even inside the outage window) must not
        # leak the server/engine/channel into the caller, which may run
        # more experiments after this. Cancel an unfired restart timer first
        # so it cannot resurrect a server nobody stops (stop() is
        # idempotent, so a FIRED timer's server is simply stopped here).
        for t in timers:
            t.cancel()
            t.join(timeout=outage_s + 5.0)
        host.close()
        client.close()
        side.close()  # folds the final service's counters into side totals

    lost = sorted(set(base_placements) - set(chaos_placements))
    extra = sorted(set(chaos_placements) - set(base_placements))
    moved = sorted(
        p for p in set(base_placements) & set(chaos_placements)
        if base_placements[p] != chaos_placements[p]
    )
    identical = not (lost or extra or moved)
    base_pps = base_placed / max(base_wall, 1e-9)
    chaos_pps = chaos_placed / max(chaos_wall, 1e-9)
    report = dict(
        pods=n_pods, nodes=n_nodes, seed=seed, batch_size=batch,
        watchdog_s=watchdog_s,
        baseline=dict(cycles=base_drive["cycles"], placed=base_placed,
                      wall_s=round(base_wall, 3),
                      goodput_pps=round(base_pps, 2)),
        chaos=dict(
            cycles=chaos_drive["cycles"], placed=chaos_placed,
            wall_s=round(chaos_wall, 3),
            goodput_pps=round(chaos_pps, 2),
            failed_cycle_attempts=chaos_drive["failed_attempts"],
            bind_conflicts=api.conflicts,
            client_retries=client.retries,
            delta_fallbacks=delta.fallbacks if delta else 0,
            watchdog_trips=side.watchdog_trips,
            serving_path=health.serving_path,
            replayed_requests=side.replayed_requests,
            sidecar_restarts=side.restarts,
        ),
        injected=plan.report(),
        recovery_s=chaos_drive["recovery_s"],
        goodput_frac=round(chaos_pps / max(base_pps, 1e-9), 3),
        end_state=dict(
            identical=identical, lost=lost, duplicated=api.conflicts,
            extra=extra, moved=moved,
        ),
    )
    log(f"[chaos] chaos: {chaos_drive['cycles']} cycles "
        f"(+{chaos_drive['failed_attempts']} failed attempts), "
        f"{chaos_placed} placed in {chaos_wall:.2f}s, "
        f"goodput {report['goodput_frac']:.2f}x of fault-free, "
        f"recovery {chaos_drive['recovery_s']}")
    log(f"[chaos] end state identical: {identical} "
        f"(lost={len(lost)} extra={len(extra)} moved={len(moved)} "
        f"conflicts={api.conflicts})")
    return report


def make_ingest_plan(seed: int | None = None, window: int = 10) -> FaultPlan:
    """The canonical front-door chaos plan (ISSUE 20 satellite): two
    drop shots (the gate sheds the whole batch -> the rpc surfaces
    RESOURCE_EXHAUSTED -> the client's retry contract re-drives it) and
    two error shots (FaultError -> UNAVAILABLE -> same contract). All
    four ride the SAME client machinery production retries ride; no
    harness-only recovery path. seed=None pins the indices."""
    if seed is None:
        return FaultPlan([
            FaultRule("ingest.enqueue", "drop", at={1, 4}),
            FaultRule("ingest.enqueue", "error", at={2, 6},
                      message="chaos: injected enqueue failure"),
        ])
    return FaultPlan.seeded(seed, {
        "ingest.enqueue": [
            dict(kind="drop", n=2, window=window),
            dict(kind="error", n=2, window=window,
                 message="chaos: injected enqueue failure"),
        ],
    })


def run_chaos_ingest(
    n_pods: int = 120,
    batch: int = 24,
    seed: int = 0,
    rate: float = 500.0,
    burst: float = 48.0,
    bound: int = 32,
    drain_w: int = 16,
    plan: FaultPlan | None = None,
    plan_seed: int | None = None,
    log=print,
) -> dict:
    """Twin-run chaos at the FRONT DOOR (ISSUE 20): the same seeded pod
    storm is pushed through the full Enqueue boundary (SchedulerClient
    -> gRPC -> IngestGate -> bounded DeviceQueue) twice — fault-free,
    then with drop/error shots at the ``ingest.enqueue`` site — while a
    drain loop pops windows like the solve loop would. The storm is
    deliberately over its admission budget (burst < batch, drain_w <
    batch, tight queue bound) so all three shed reasons fire: rate
    (token drought), capacity (queue full), fault (injected drop).

    Convergence is the claim under test: every shed pod is re-offered
    (driver requeue for partial sheds; the PR 3 client retry contract
    for RESOURCE_EXHAUSTED full sheds and UNAVAILABLE error shots)
    until admitted, and gate-side name dedup makes retries idempotent —
    so the chaos arm must drain EXACTLY the fault-free arm's pod set:
    zero lost, zero duplicated, or the harness fails loudly."""
    import grpc

    from tpusched.rpc.client import SchedulerClient

    rng = np.random.default_rng(seed)
    storm = [dict(name=f"ing-{i:05d}",
                  priority=float(rng.uniform(10.0, 100.0)),
                  slo_target=float(rng.uniform(0.5, 0.999)))
             for i in range(n_pods)]
    batches = [storm[i:i + batch] for i in range(0, n_pods, batch)]
    all_names = {p["name"] for p in storm}

    def run_arm(faults: "FaultPlan | None") -> dict:
        side = _Sidecar(
            ingest=dict(capacity=max(2 * bound, 64), bound=bound,
                        rate=rate, burst=burst),
            faults=faults,
        )
        client = SchedulerClient(f"127.0.0.1:{side.port}",
                                 retry_seed=seed)
        gate = side.svc.ingest
        drained: list = []
        offers = rpc_sheds = 0
        try:
            t0 = time.perf_counter()
            outstanding = list(batches)
            requeue: list = []
            idle = 0
            while outstanding or requeue or gate.queue.depth:
                if requeue:
                    cur, requeue = requeue[:batch], requeue[batch:]
                elif outstanding:
                    cur = outstanding.pop(0)
                else:
                    cur = []
                if cur:
                    offers += 1
                    try:
                        res = client.enqueue(cur)
                        shed = set(res.shed_pods)
                    except grpc.RpcError as e:
                        # The client already retried inside its deadline
                        # budget; a surviving RESOURCE_EXHAUSTED /
                        # UNAVAILABLE means the whole batch is still
                        # unadmitted — requeue it like any other shed.
                        if e.code() not in (
                                grpc.StatusCode.RESOURCE_EXHAUSTED,
                                grpc.StatusCode.UNAVAILABLE):
                            raise
                        rpc_sheds += 1
                        shed = {p["name"] for p in cur}
                    requeue.extend(p for p in cur if p["name"] in shed)
                took = gate.take_window(w=drain_w)
                drained.extend(took)
                idle = idle + 1 if not cur and not took else 0
                if idle > 200:
                    raise RuntimeError(
                        "ingest chaos run failed to drain: "
                        f"{len(requeue)} requeued, depth "
                        f"{gate.queue.depth}")
                if cur and not took and requeue:
                    time.sleep(0.002)   # token drought: let refill run
            wall = time.perf_counter() - t0
            stats = gate.stats()
            retries = client.retries
        finally:
            client.close()
            side.close()
        return dict(drained=drained, stats=stats, retries=retries,
                    offers=offers, rpc_sheds=rpc_sheds, wall=wall)

    base = run_arm(None)
    log(f"[chaos-ingest] fault-free: {len(base['drained'])} drained in "
        f"{base['wall']:.2f}s ({base['offers']} offers, sheds "
        f"rate={base['stats']['shed_rate']} "
        f"capacity={base['stats']['shed_capacity']})")

    plan = plan if plan is not None else make_ingest_plan(seed=plan_seed)
    chaos = run_arm(plan)
    log(f"[chaos-ingest] chaos: {len(chaos['drained'])} drained in "
        f"{chaos['wall']:.2f}s ({chaos['offers']} offers, "
        f"{chaos['retries']} client retries, sheds "
        f"rate={chaos['stats']['shed_rate']} "
        f"capacity={chaos['stats']['shed_capacity']} "
        f"fault={chaos['stats']['shed_fault']})")

    base_set = set(base["drained"])
    chaos_set = set(chaos["drained"])
    lost = sorted(base_set - chaos_set)
    extra = sorted(chaos_set - base_set)
    dup = len(chaos["drained"]) - len(chaos_set)
    missing = sorted(all_names - base_set)
    identical = not (lost or extra or missing
                     or dup or len(base["drained"]) - len(base_set))
    report = dict(
        pods=n_pods, batch=batch, seed=seed, rate=rate, burst=burst,
        bound=bound, drain_w=drain_w,
        baseline=dict(
            drained=len(base["drained"]), offers=base["offers"],
            client_retries=base["retries"], wall_s=round(base["wall"], 3),
            gate=base["stats"],
        ),
        chaos=dict(
            drained=len(chaos["drained"]), offers=chaos["offers"],
            client_retries=chaos["retries"],
            rpc_level_sheds=chaos["rpc_sheds"],
            wall_s=round(chaos["wall"], 3),
            gate=chaos["stats"],
        ),
        injected=plan.report(),
        end_state=dict(
            identical=identical, lost=lost, extra=extra,
            missing_from_storm=missing, duplicated=dup,
        ),
    )
    log(f"[chaos-ingest] end state identical: {identical} "
        f"(lost={len(lost)} extra={len(extra)} duplicated={dup} "
        f"injected={len(report['injected']['fired'])})")
    return report


def run_chaos_fleet(
    n_pods: int = 120,
    n_nodes: int = 12,
    seed: int = 0,
    batch_size: int | None = None,
    replicas: int = 2,
    kill_after_cycle: int = 2,
    outage_s: float = 0.4,
    watchdog_s: float = 30.0,
    poll_s: float = 0.05,
    plan: FaultPlan | None = None,
    warmup_arm: bool = False,
    prewarm: bool = False,
    log=print,
) -> dict:
    """Kill-the-leader twin run over an N-replica fleet (ISSUE 6).

    Both arms run the SAME fleet shape (replicas, followers polling) so
    goodput is comparable; the chaos arm kills the leader after
    `kill_after_cycle` completed cycles (waiting for the standbys to be
    CAUGHT UP first, so 'warm standby' is a property the harness
    controls, not a race) and resurrects it `outage_s` later — as the
    sole leader again at replicas=1 (nothing else can serve), as a
    STANDBY rejoining the fleet at replicas>=2 (the promoted standby
    keeps leading; the ex-leader must not reclaim and split the brain).

    The client rides the ordered endpoint list: at replicas=1 it backs
    off on UNAVAILABLE until the restart (the availability gap IS the
    single-sidecar story); at replicas>=2 its first retry fails over to
    the warm standby, whose replicated stores answer the delta against
    the leader-minted base — failover recovery is one retry, not one
    outage. End state must be IDENTICAL to the fault-free arm either
    way; `goodput_frac` is the availability claim as a number.

    warmup_arm: run one UNMEASURED fault-free arm first. The first
    fleet run in a process pays the XLA compiles for this workload's
    shapes (later arms hit the in-process compile caches); without a
    warmup, a cold fault-free twin can lose to a warm chaos arm and
    invert the goodput fraction. Callers comparing goodput across
    replica counts set it on their FIRST run.

    prewarm (PR 18): boot every replica with explicit synthetic
    buckets + the shape-class registry prewarm, and make the compile-
    free claims ASSERTIONS: the fault-free twin's measured window pays
    zero serve-cause compiles (so warmup_arm is unnecessary — the arm
    is born warm), and at replicas >= 2 the window from kill to end of
    run pays zero too (the promoted standby prewarmed before
    wait_caught_up let the kill proceed). The report gains
    cold_start_s (fleet construction -> every replica prewarmed),
    prewarm_s (slowest replica's prewarm), and
    failover_first_request_ms (kill -> next COMPLETED cycle, which a
    compile-free promotion keeps free of any XLA component)."""
    from tpusched.replicate import ReplicaSet
    from tpusched.rpc.client import SchedulerClient

    cfg = EngineConfig(mode="fast")
    batch = batch_size or max(n_pods // 4, 1)
    make_kw: dict = dict(config=cfg, watchdog_s=watchdog_s)
    if prewarm:
        # Explicit buckets pin ONE solve_packed shape class for the
        # whole run (running-bucket growth included), so prewarm can
        # compile it once at boot and nothing retraces mid-experiment.
        make_kw.update(buckets=synthetic_buckets(n_pods, n_nodes),
                       prewarm=True)

    def fresh_api():
        api = _CountingApi()
        build_synthetic_cluster(api, np.random.default_rng(seed),
                                n_pods, n_nodes)
        return api

    def run_arm(events_fn, faults=None):
        # `faults` lands on the CHAOS arm only — the baseline/warmup
        # fleets must stay genuinely fault-free (and a plan's pinned
        # invocation indices must not be burned in the wrong arm); the
        # single-sidecar run_chaos follows the same discipline.
        t_boot = time.perf_counter()
        fleet = ReplicaSet(replicas, poll_s=poll_s, faults=faults,
                           **make_kw)
        if prewarm:
            # Cold start ends when EVERY replica has compiled its
            # registry — the standbys' warmness is the failover claim.
            for svc in fleet.services:
                if not svc.wait_prewarmed(timeout=120.0):
                    raise RuntimeError(
                        "replica prewarm did not complete within 120s"
                        + (f": {svc.prewarm_error}" if svc.prewarm_error
                           else "")
                    )
        cold_start_s = time.perf_counter() - t_boot
        client = SchedulerClient(fleet.addresses(), retry_seed=seed)
        api = fresh_api()
        host = HostScheduler(api, cfg, client=client, batch_size=batch)
        timers: list = []
        try:
            serve0 = _serve_compiles()
            t0 = time.perf_counter()
            drive = _drive(host, events_fn(fleet, timers), max_cycles=400)
            wall = time.perf_counter() - t0
            placements = _placements(api)
            placed = sum(c.placed for c in host.cycles)
            health = client.health()
            stats = dict(
                drive=drive, wall=wall, placements=placements,
                placed=placed, conflicts=api.conflicts,
                failovers=client.failovers, retries=client.retries,
                fallbacks=host._delta.fallbacks if host._delta else 0,
                takeovers=fleet.takeovers(),
                serving_role=health.role,
                cold_start_s=cold_start_s,
                prewarm_s=max(
                    (svc.prewarm_s or 0.0 for svc in fleet.services),
                    default=0.0,
                ) if prewarm else 0.0,
                serve_compiles=_serve_compiles() - serve0,
                serve_compiles_end=_serve_compiles(),
                replication=[
                    dict(role=svc.role,
                         applied=svc.replication_applied,
                         skipped=svc.replication_skipped,
                         appended=svc._replog.appended)
                    for svc in fleet.services
                ],
            )
        finally:
            for t in timers:
                t.cancel()
                t.join(timeout=outage_s + 5.0)
            host.close()
            client.close()
            fleet.close()
        return stats

    def no_events(fleet, timers):
        return {}

    kill_marks: dict = {}

    def kill_events(fleet, timers):
        def kill_leader():
            # Deterministic warmness: standbys catch up BEFORE the kill.
            # A timeout here is a harness precondition failure — killing
            # a cold standby would silently turn the warm-failover
            # experiment into a resync-storm one (delta_fallbacks > 0,
            # asserted 0 by the tier-1 smoke); fail loudly instead.
            if not fleet.wait_caught_up(timeout=10.0):
                raise RuntimeError(
                    "standbys failed to catch up with the leader's op "
                    "log before the kill (10s): warm-standby "
                    "precondition not met"
                )
            idx = fleet.kill_leader()
            # Everything traced from here to end-of-run is failover
            # work: a prewarmed promotion must add ZERO to this.
            kill_marks["serve_compiles_at_kill"] = _serve_compiles()

            def resurrect():
                fleet.restart(idx, role="leader" if replicas == 1
                              else "standby")

            import threading

            t = threading.Timer(outage_s, resurrect)
            t.name = "tpusched-chaos-restart"
            t.daemon = True
            t.start()
            timers.append(t)

        return {kill_after_cycle: [("leader_kill", kill_leader)]}

    if warmup_arm and prewarm:
        # Prewarm makes the warmup arm's one job (paying the compiles
        # off the measured clock) redundant: every arm is born warm.
        log(f"[chaos-fleet r{replicas}] --prewarm: skipping the "
            f"warmup arm (prewarmed fleets are born warm)")
        warmup_arm = False
    if warmup_arm:
        t0 = time.perf_counter()
        run_arm(no_events)
        log(f"[chaos-fleet r{replicas}] warmup arm (unmeasured, "
            f"compiles): {time.perf_counter() - t0:.2f}s")
    base = run_arm(no_events)
    log(f"[chaos-fleet r{replicas}] fault-free: "
        f"{base['drive']['cycles']} cycles, {base['placed']} placed "
        f"in {base['wall']:.2f}s (cold start {base['cold_start_s']:.2f}s, "
        f"serve compiles {base['serve_compiles']})")
    if prewarm and base["serve_compiles"] != 0:
        raise RuntimeError(
            f"prewarmed fault-free arm paid {base['serve_compiles']} "
            f"serve-cause compile(s): the shape-class registry missed "
            f"a program this workload dispatches"
        )
    chaos = run_arm(kill_events, faults=plan)
    log(f"[chaos-fleet r{replicas}] kill-the-leader: "
        f"{chaos['drive']['cycles']} cycles "
        f"(+{chaos['drive']['failed_attempts']} failed attempts), "
        f"{chaos['placed']} placed in {chaos['wall']:.2f}s, "
        f"failovers={chaos['failovers']} takeovers={chaos['takeovers']} "
        f"fallbacks={chaos['fallbacks']}")

    lost = sorted(set(base["placements"]) - set(chaos["placements"]))
    extra = sorted(set(chaos["placements"]) - set(base["placements"]))
    moved = sorted(
        p for p in set(base["placements"]) & set(chaos["placements"])
        if base["placements"][p] != chaos["placements"][p]
    )
    identical = not (lost or extra or moved)
    base_pps = base["placed"] / max(base["wall"], 1e-9)
    chaos_pps = chaos["placed"] / max(chaos["wall"], 1e-9)
    rec = chaos["drive"]["recovery_s"]
    takeover_compiles = None
    if "serve_compiles_at_kill" in kill_marks:
        takeover_compiles = (chaos["serve_compiles_end"]
                             - kill_marks["serve_compiles_at_kill"])
        if prewarm and replicas >= 2 and takeover_compiles != 0:
            # The headline claim of PR 18: wait_caught_up only let the
            # kill proceed once the standby was prewarmed, so the
            # promotion must serve without tracing anything new. (At
            # replicas == 1 the resurrected leader may legitimately
            # race its own boot prewarm, so no assertion there.)
            raise RuntimeError(
                f"promoted standby paid {takeover_compiles} compile(s) "
                f"after the leader kill: failover was not compile-free"
            )
    failover_ms = (round(rec["leader_kill"] * 1000.0, 1)
                   if rec.get("leader_kill") is not None else None)
    report = dict(
        pods=n_pods, nodes=n_nodes, seed=seed, batch_size=batch,
        replicas=replicas, outage_s=outage_s, prewarm=prewarm,
        cold_start_s=round(base["cold_start_s"], 3),
        prewarm_s=round(base["prewarm_s"], 3),
        serve_compiles=dict(baseline=base["serve_compiles"],
                            chaos=chaos["serve_compiles"],
                            after_takeover=takeover_compiles),
        failover_first_request_ms=failover_ms,
        baseline=dict(cycles=base["drive"]["cycles"],
                      placed=base["placed"],
                      wall_s=round(base["wall"], 3),
                      goodput_pps=round(base_pps, 2)),
        chaos=dict(
            cycles=chaos["drive"]["cycles"], placed=chaos["placed"],
            wall_s=round(chaos["wall"], 3),
            goodput_pps=round(chaos_pps, 2),
            failed_cycle_attempts=chaos["drive"]["failed_attempts"],
            bind_conflicts=chaos["conflicts"],
            client_retries=chaos["retries"],
            client_failovers=chaos["failovers"],
            delta_fallbacks=chaos["fallbacks"],
            takeovers=chaos["takeovers"],
            serving_role=chaos["serving_role"],
            replication=chaos["replication"],
        ),
        recovery_s=rec,
        failover_recovery_s=rec.get("leader_kill"),
        goodput_frac=round(chaos_pps / max(base_pps, 1e-9), 3),
        end_state=dict(
            identical=identical, lost=lost,
            duplicated=chaos["conflicts"], extra=extra, moved=moved,
        ),
    )
    log(f"[chaos-fleet r{replicas}] goodput "
        f"{report['goodput_frac']:.2f}x of fault-free, recovery {rec}; "
        f"end state identical: {identical} "
        f"(lost={len(lost)} extra={len(extra)} moved={len(moved)} "
        f"conflicts={chaos['conflicts']})")
    if prewarm:
        log(f"[chaos-fleet r{replicas}] prewarm: cold start "
            f"{report['cold_start_s']:.2f}s (prewarm "
            f"{report['prewarm_s']:.2f}s), serve compiles "
            f"baseline={base['serve_compiles']} "
            f"after-takeover={takeover_compiles}, failover first "
            f"request {failover_ms} ms")
    return report


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--pods", type=int, default=120)
    ap.add_argument("--nodes", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--watchdog-s", type=float, default=1.0)
    ap.add_argument("--plan-seed", type=int, default=None,
                    help="draw fault indices from this seed instead of "
                         "the pinned defaults")
    ap.add_argument("--replicas", type=int, default=None,
                    help="run the kill-the-leader FLEET experiment at "
                         "this replica count instead of the single-"
                         "sidecar fault plan")
    ap.add_argument("--kill-after-cycle", type=int, default=2)
    ap.add_argument("--outage-s", type=float, default=0.4)
    ap.add_argument("--prewarm", action="store_true",
                    help="fleet experiment only: boot replicas with "
                         "explicit buckets + shape-class prewarm and "
                         "ASSERT compile-free serving and failover")
    ap.add_argument("--ingest", action="store_true",
                    help="run the FRONT-DOOR experiment instead: a "
                         "shed-heavy Enqueue storm with drop/error "
                         "shots at ingest.enqueue must converge to the "
                         "fault-free drain set (zero lost/duplicated)")
    ap.add_argument("--json", default=None,
                    help="write the full report to this path")
    args = ap.parse_args()
    err = lambda *a: print(*a, file=sys.stderr, flush=True)  # noqa: E731
    if args.ingest:
        report = run_chaos_ingest(
            n_pods=args.pods, batch=args.batch or 24, seed=args.seed,
            plan_seed=args.plan_seed, log=err,
        )
    elif args.replicas is not None:
        report = run_chaos_fleet(
            n_pods=args.pods, n_nodes=args.nodes, seed=args.seed,
            batch_size=args.batch, replicas=args.replicas,
            kill_after_cycle=args.kill_after_cycle,
            outage_s=args.outage_s, prewarm=args.prewarm,
            watchdog_s=max(args.watchdog_s, 30.0), log=err,
        )
    else:
        report = run_chaos(
            n_pods=args.pods, n_nodes=args.nodes, seed=args.seed,
            batch_size=args.batch, watchdog_s=args.watchdog_s,
            plan_seed=args.plan_seed, log=err,
        )
    out = json.dumps(report, indent=2)
    if args.json:
        with open(args.json, "w") as f:
            f.write(out + "\n")
    print(out)
    return 0 if report["end_state"]["identical"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
