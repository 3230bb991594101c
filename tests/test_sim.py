"""Virtual-time cluster simulator (ISSUE 5): determinism, lifecycle
accounting, the closed QoS loop through the host, and the headline
twin-run — QoS-driven scheduling must strictly beat static priority on
SLO attainment over an identical seeded timeline.

Tier-1 budget: the twin-run smoke shares one Engine per config arm (jit
caches amortize across the repeat runs) and shortens the horizon; the
full-length scenario runs are marked slow.
"""

import dataclasses

import numpy as np
import pytest

from tpusched.config import EngineConfig, SimConfig
from tpusched.sim import events as sim_events
from tpusched.sim import report as sim_report
from tpusched.sim import workloads
from tpusched.sim.clock import VirtualClock
from tpusched.sim.driver import (
    SimDriver,
    effective_config,
    run_scenario,
    static_baseline,
    twin_run,
)
from tpusched.sim.lifecycle import LifecycleTracker, observed_availability

# ---------------------------------------------------------------------------
# Units: clock, event queue, lifecycle math, workload generation.
# ---------------------------------------------------------------------------


def test_virtual_clock_monotone_and_callable():
    clk = VirtualClock()
    assert clk() == 0.0
    clk.advance(1.5)
    clk.sleep(0.5)          # no real sleep, just time
    assert clk.now() == pytest.approx(2.0)
    clk.advance_to(1.0)     # past target: no-op
    assert clk.now() == pytest.approx(2.0)
    with pytest.raises(ValueError):
        clk.advance(-1.0)


def test_event_queue_orders_by_time_then_push_order():
    q = sim_events.EventQueue()
    q.push(2.0, "b")
    q.push(1.0, "a")
    q.push(2.0, "c")        # same time as "b": push order breaks the tie
    assert [e.kind for e in q.pop_until(2.0)] == ["a", "b", "c"]
    assert len(q) == 0 and q.next_time() is None


def test_event_log_hash_covers_content_and_order():
    def make(kinds):
        q = sim_events.EventQueue()
        for i, k in enumerate(kinds):
            q.note(float(i), k, pod=f"p{i}")
        return q.log_hash()

    assert make(["a", "b"]) == make(["a", "b"])
    assert make(["a", "b"]) != make(["b", "a"])
    assert make(["a"]) != make(["a", "a"])


def test_observed_availability_math():
    # Never observed (zero age): optimistic fallback 1.0.
    assert observed_availability(10.0, 0.0, None, 10.0) == 1.0
    # Waiting without running decays toward 0.
    assert observed_availability(0.0, 0.0, None, 10.0) == 0.0
    # Half the life spent running.
    assert observed_availability(0.0, 5.0, None, 10.0) == pytest.approx(0.5)
    # A live run counts up to `now`.
    assert observed_availability(0.0, 0.0, 5.0, 10.0) == pytest.approx(0.5)
    # Clipped to [0, 1] even if accounting overshoots.
    assert observed_availability(0.0, 20.0, None, 10.0) == 1.0


def test_lifecycle_tracker_credits_runs_across_evictions():
    life = LifecycleTracker()
    life.on_submit("p", 0.0, slo_target=0.9)
    life.on_bind("p", 2.0)
    assert life.on_unbind("p", 6.0) == pytest.approx(4.0)   # evicted
    assert life.availability("p", 8.0) == pytest.approx(0.5)
    life.on_bind("p", 8.0)
    final = life.on_complete("p", 12.0)
    assert final == pytest.approx(8.0 / 12.0)
    assert life.pods["p"].evictions == 1
    # availability frozen at completion
    assert life.availability("p", 100.0) == pytest.approx(8.0 / 12.0)


def test_workload_generation_is_deterministic():
    sc = workloads.SCENARIOS["pressure_skew"]
    a = workloads.generate(sc, seed=7)
    b = workloads.generate(sc, seed=7)
    assert a.specs == b.specs and a.meta == b.meta
    pop = lambda s: [(e.time, e.kind, sorted(e.data.items()))
                     for e in s.queue.pop_until(float("inf"))]
    assert pop(a) == pop(b)
    c = workloads.generate(sc, seed=8)
    assert pop(c) != pop(workloads.generate(sc, seed=7))


def test_workload_prefill_is_filler_class():
    sc = workloads.SCENARIOS["pressure_skew"]
    setup = workloads.generate(sc, seed=0)
    for i in range(sc.prefill):
        assert setup.meta[f"sim-{i}"]["slo"] == 0.0
        d = setup.meta[f"sim-{i}"]["duration_s"]
        lo, hi = sc.prefill_duration_s
        assert lo <= d <= hi


def test_scenario_and_simconfig_validation():
    with pytest.raises(ValueError):
        workloads.generate(
            dataclasses.replace(workloads.SCENARIOS["steady_state"],
                                arrival="nope"), 0)
    with pytest.raises(ValueError):
        SimConfig(tick_s=0.0)
    with pytest.raises(ValueError):
        SimConfig(resolve_every=0)
    with pytest.raises(ValueError):
        twin_run(workloads.SCENARIOS["steady_state"],
                 config=static_baseline(None))


# ---------------------------------------------------------------------------
# The closed loop through the host: FakeApiServer lifecycle accounting.
# ---------------------------------------------------------------------------


def test_fake_api_observed_avail_decays_while_pending():
    from tpusched.host import FakeApiServer

    clk = VirtualClock()
    api = FakeApiServer(clock=clk)
    api.add_node("n0", allocatable={"cpu": 1000.0})
    api.add_pod("p", requests={"cpu": 100.0}, slo_target=0.9)
    # Submission instant: never observed -> optimistic 1.0, no pressure.
    (rec,) = api.pending_pods()
    assert rec["observed_avail"] == 1.0
    # Waiting 10 virtual seconds with zero run time: availability 0.
    clk.advance(10.0)
    (rec,) = api.pending_pods()
    assert rec["observed_avail"] == 0.0
    # Bound and running: availability climbs, slack follows.
    api.bind("p", "n0")
    clk.advance(10.0)
    (rec,) = api.bound_pods()
    assert rec["observed_avail"] == pytest.approx(0.5)
    from tpusched.host import HostScheduler

    run = HostScheduler._running_record(rec)
    assert run["slack"] == pytest.approx(0.5 - 0.9)


def test_fake_api_explicit_observed_avail_pins():
    from tpusched.host import FakeApiServer

    clk = VirtualClock()
    api = FakeApiServer(clock=clk)
    api.add_pod("p", requests={"cpu": 100.0}, observed_avail=0.7)
    clk.advance(100.0)
    (rec,) = api.pending_pods()
    assert rec["observed_avail"] == 0.7, "explicit spec value wins"
    # ... until the write-back path replaces it.
    assert api.set_observed_availability("p", 0.3)
    (rec,) = api.pending_pods()
    assert rec["observed_avail"] == 0.3
    assert not api.set_observed_availability("ghost", 0.5)


def test_fake_api_requeue_preserves_history():
    from tpusched.host import FakeApiServer

    clk = VirtualClock()
    api = FakeApiServer(clock=clk)
    api.add_pod("p", requests={"cpu": 100.0}, submitted=0.0,
                run_seconds=5.0)
    clk.advance(10.0)
    (rec,) = api.pending_pods()
    assert rec["observed_avail"] == pytest.approx(0.5)


def test_fake_api_avail_drift_rehints_for_delta_transport():
    """The delta codec trusts un-hinted records as byte-identical
    ("name everything you touch"), so read-time availability decay must
    re-hint the pod into the change accumulator — otherwise the delta
    and pipeline transports ship a waiting pod's arrival-time
    availability forever and the sidecar's pressure signal freezes."""
    from tpusched.host import FakeApiServer

    clk = VirtualClock()
    api = FakeApiServer(clock=clk)
    api.add_pod("p", requests={"cpu": 100.0}, slo_target=0.9)
    assert api.drain_changed() is None     # first drain: no baseline
    api.pending_pods()                     # first read: creation hint covers it
    assert api.drain_changed() == set()
    clk.advance(5.0)
    api.pending_pods()                     # avail drifted 1.0 -> 0.0
    assert api.drain_changed() == {"p"}, \
        "availability drift must re-hint the pod for the next delta"
    api.pending_pods()                     # no time passed -> no drift
    assert api.drain_changed() == set(), "no drift, no hint churn"
    # Pinned records bypass lifecycle accounting and never re-hint.
    api.set_observed_availability("p", 0.4)
    api.drain_changed()
    clk.advance(50.0)
    api.pending_pods()
    assert api.drain_changed() == set()


# ---------------------------------------------------------------------------
# Tier-1 smoke: the headline twin run, shortened horizon.
# ---------------------------------------------------------------------------


def test_twin_run_pressure_skew_qos_beats_static_deterministically():
    """ISSUE 5 acceptance: on the pressure-skew scenario QoS-driven
    scheduling attains STRICTLY more SLOs than the static-priority
    baseline, and the run is deterministic under a fixed seed (two runs
    with the same seed produce identical event-log hashes)."""
    from tpusched.engine import Engine

    sc = dataclasses.replace(workloads.SCENARIOS["pressure_skew"],
                             horizon_s=100.0)
    cfg = effective_config(sc, None)
    static_cfg = static_baseline(cfg)
    eng_qos, eng_static = Engine(cfg), Engine(static_cfg)
    try:
        q1 = run_scenario(sc, 0, config=cfg, engine=eng_qos)
        q2 = run_scenario(sc, 0, config=cfg, engine=eng_qos)
        s1 = run_scenario(sc, 0, config=static_cfg, engine=eng_static)
    finally:
        eng_qos.close()
        eng_static.close()
    assert q1.event_log_hash == q2.event_log_hash, \
        "same seed, same config: byte-identical event logs"
    sq, ss = sim_report.summarize(q1), sim_report.summarize(s1)
    assert sq["slo_pods"] == ss["slo_pods"] > 0
    assert sq["slo_attainment_frac"] > ss["slo_attainment_frac"], (
        f"QoS-driven must strictly beat static priority: "
        f"{sq['slo_attainment_frac']} vs {ss['slo_attainment_frac']}"
    )
    # Different policies genuinely diverged on the same timeline.
    assert q1.event_log_hash != s1.event_log_hash
    # Pressure was real during the run (the loop actually closed).
    assert sq["pressure_peak"] > 0.0
    # Report plumbing is complete.
    assert sq["attainment_cdf"] and sq["attainment_by_slo"]


def test_sim_preemption_evicts_filler_for_pressured_pod():
    """With preemption on, a waiting SLO pod's pressure buys an
    eviction: the filler is re-queued WITH its lifecycle history and
    the SLO pod completes attained."""
    from tpusched.config import QoSConfig

    sc = workloads.Scenario(
        name="tiny_preempt", n_nodes=1, horizon_s=60.0,
        arrival="poisson", rate=0.05, prefill=1,
        prefill_duration_s=(100.0, 100.0),
        mix=(
            (0.01, 0.0, (100.0, 100.0), (100, 101), (6000.0, 6001.0)),
            (0.99, 0.9, (15.0, 15.0), (0, 1), (6000.0, 6001.0)),
        ),
        preemption=True,
    )
    # Preemption margin 600: a pending pressured pod (eff ~900) clears
    # a filler victim (eff 100 + 600 = 700) but NOT a just-recovering
    # SLO pod (victim boost tracks its shortfall). Seed 12 yields ONE
    # SLO arrival (~t=20) inside the horizon, so the test pins the
    # clean preempt-filler-then-complete trajectory rather than the
    # overload ping-pong measured by the pressure_skew twin run.
    cfg = EngineConfig(
        mode="fast", preemption=True,
        qos=QoSConfig(preemption_margin=600.0),
    )
    res = run_scenario(sc, seed=12, config=cfg)
    assert res.evicted >= 1, "the pressured pod preempted the filler"
    assert res.requeues >= 1
    filler = next(p for p in res.pods if p.slo == 0.0)
    assert filler.evictions >= 1
    slo_pods = [p for p in res.pods if p.slo > 0 and p.completed]
    assert slo_pods and any(p.attained for p in slo_pods)
    summary = sim_report.summarize(res)
    assert summary["requeues"] >= 1


def test_sim_grpc_end_to_end_smoke(thread_leak_check):
    """The full host -> gRPC sidecar path under simulation: the host
    rides AssignPipeline (pinned-base deltas), pods complete, SLOs are
    measured, and every worker thread drains on close."""
    from tpusched.rpc.client import SchedulerClient
    from tpusched.rpc.server import make_server

    sc = dataclasses.replace(workloads.SCENARIOS["steady_state"],
                             horizon_s=40.0)
    cfg = effective_config(sc, None)
    server, port, svc = make_server("127.0.0.1:0", config=cfg)
    server.start()
    client = SchedulerClient(f"127.0.0.1:{port}")
    try:
        d = SimDriver(sc, seed=0, config=cfg, client=client)
        assert d.host._pipeline is not None, "gRPC sim rides AssignPipeline"
        res = d.run()
    finally:
        client.close()
        server.stop(0)
        svc.close()
    assert res.backend == "grpc"
    assert res.completions > 0 and res.placed > 0
    s = sim_report.summarize(res)
    assert 0.0 <= s["slo_attainment_frac"] <= 1.0
    assert s["event_log_hash"]
    # The pipeline actually shipped deltas after the initial full send —
    # a regression that degenerates every cycle to a full rebuild (e.g.
    # drift re-hints pushing churn past refresh_frac) must fail here.
    assert d.host._pipeline.delta_sends > 0


# ---------------------------------------------------------------------------
# ISSUE 9: autoscale + heterogeneous pools, gang arrivals under
# pressure, and the soak composition.
# ---------------------------------------------------------------------------


def test_autoscale_drives_device_rebuilds_grpc(thread_leak_check):
    """Acceptance (ISSUE 9): mid-horizon autoscale events measurably
    exercise the device-resident growth paths — the tainted pool's
    first grow is a brand-new taint vocabulary entry (new_taint
    rebuild) and the staged +1 grow bursts the 8-row node bucket
    (row_bucket rebuild); the session's node bucket provably grew.
    pipeline_refresh_frac pins the delta path so growth arrives as
    session applies, not churn-triggered full-send reseeds."""
    from tpusched.rpc.client import SchedulerClient
    from tpusched.rpc.server import make_server

    sc = dataclasses.replace(
        workloads.SCENARIOS["autoscale_stress"], horizon_s=45.0,
        autoscale=((10.0, "grow", 1, 2), (20.0, "grow", 0, 1),
                   (22.0, "grow", 0, 3), (35.0, "shrink", 0, 2)),
    )
    cfg = effective_config(sc, None)
    server, port, svc = make_server("127.0.0.1:0", config=cfg)
    server.start()
    client = SchedulerClient(f"127.0.0.1:{port}")
    try:
        d = SimDriver(sc, seed=0, config=cfg, client=client,
                      sim=SimConfig(pipeline_refresh_frac=10.0))
        res = d.run()
        # Capture BEFORE close(): svc.close() drops the sessions.
        sessions = list({id(s): s for s in svc._sessions.values()}
                        .values())
        rebuilds = sum(s.device.rebuilds for s in sessions)
        reasons = {r for s in sessions
                   for r in s.device.rebuild_reasons}
        node_bucket = max(s.device.meta.buckets.nodes for s in sessions)
    finally:
        client.close()
        server.stop(0)
        svc.close()
    assert res.autoscale_events == 8, "2+1+3 grows + 2 shrinks applied"
    assert rebuilds > 0, "autoscale must exercise the rebuild path"
    assert "new_taint" in reasons, \
        f"tainted pool growth must force the vocab rebuild: {reasons}"
    assert "row_bucket" in reasons, \
        f"+1 past the 8-row bucket must force bucket growth: {reasons}"
    assert node_bucket >= 16, "the node bucket provably grew"
    assert res.placed > 0 and res.completions > 0
    s = sim_report.summarize(res)
    assert s["autoscale_events"] == 8


def test_autoscale_scale_down_requeues_with_history():
    """Scale-down interrupts running pods like a real node drain: the
    victim re-queues with banked run credit (availability keeps
    decaying from where it was, not from 1.0)."""
    sc = workloads.Scenario(
        name="scale_down_tiny", horizon_s=30.0,
        pools=((2, 1),),
        autoscale=((10.0, "shrink", 0, 1),),
        arrival="poisson", rate=0.0, prefill=6,
        prefill_duration_s=(25.0, 28.0),
        mix=((1.0, 0.0, (25.0, 28.0), (50, 51), (1800.0, 2000.0)),),
    )
    res = run_scenario(sc, seed=0)
    assert res.autoscale_events == 1
    assert res.requeues >= 1, "the drained node's pods re-queued"
    interrupted = [p for p in res.pods if p.evictions > 0]
    assert interrupted, "scale-down interrupted running pods"
    assert all(p.ran_s > 0 for p in interrupted), \
        "run credit survives the autoscale_down requeue"


def test_gang_under_pressure_held_not_partially_bound():
    """ISSUE 9 satellite: a gang that cannot fully place (4 members x
    1500 cpu on one 4000-cpu node) is HELD — no member is ever bound,
    no capacity leaks — and miss_attribution classifies every member
    gang_held (group-propagated past the members that merely read
    'pending' in the rollback cycle)."""
    from tpusched.explain import ExplainCollector

    sc = workloads.Scenario(
        name="gang_held_tiny", n_nodes=1, node_class=0, horizon_s=30.0,
        arrival="poisson", rate=0.05,
        gang_frac=1.0, gang_size=4,
        mix=((1.0, 0.9, (10.0, 10.0), (50, 51), (1500.0, 1600.0)),),
    )
    col = ExplainCollector(capacity=4096, enabled=True)
    res = run_scenario(sc, seed=0, explain=col)   # seed 0: ONE gang
    assert len(res.pods) == 4 and res.placed == 0
    assert all(p.ran_s == 0.0 and p.evictions == 0 for p in res.pods), \
        "held means NEVER partially bound"
    assert all(p.gang for p in res.pods)
    att = sim_report.miss_attribution(res, col.records())
    assert att["misses"] == 4
    assert att["causes"] == {"gang_held": 4}, att["causes"]
    for d in att["pods"].values():
        assert d["cause"] == "gang_held"


def test_interrupted_gang_reforms_quorum_together():
    """A gang member losing its node pulls the WHOLE gang back to
    pending (gang_reform): the solver's minMember quorum is
    batch-local, so a lone requeued member would be held forever.
    Deterministic interrupt via autoscale shrink: 2 nodes, a 2-member
    gang split one-per-node, scale down one node at t=15, grow it back
    at t=25 — the gang re-forms quorum in one batch and completes."""
    sc = workloads.Scenario(
        name="gang_reform_tiny", horizon_s=70.0,
        pools=((2, 0),),                      # 2 x 4000 cpu
        autoscale=((15.0, "shrink", 0, 1), (25.0, "grow", 0, 1)),
        arrival="poisson", rate=0.012,  # seed 34: ONE gang, at t=0.5
        gang_frac=1.0, gang_size=2,
        mix=((1.0, 0.0, (20.0, 20.0), (50, 51), (2500.0, 2600.0)),),
    )
    d = SimDriver(sc, seed=34)
    res = d.run()
    members = [p for p in res.pods if p.gang]
    assert len(members) == 2
    # Both members were interrupted (one by the shrink, one pulled
    # along by gang_reform) and both re-placed and completed.
    assert all(p.evictions >= 1 for p in members), \
        [p.evictions for p in members]
    assert res.requeues >= 2
    assert all(p.completed for p in members), \
        "the gang re-formed quorum and finished (no lone-member " \
        "livelock)"
    kinds = [e["kind"] for e in d.q.log]
    assert "gang_reform" in kinds
    # All-or-nothing held throughout: bind events for the two members
    # come in pairs (same note timestamp), never a lone member bound.
    binds = [e for e in d.q.log if e["kind"] == "bind"
             and e["pod"] in {p.name for p in members}]
    by_t: dict = {}
    for b in binds:
        by_t.setdefault(b["t"], []).append(b["pod"])
    assert all(len(v) == 2 for v in by_t.values()), by_t


def test_colocated_gang_interrupt_counts_once():
    """Gang members CO-LOCATED on the removed node: the first victim's
    gang_reform propagation re-queues the sibling before the victims
    loop reaches it — the second pass must be a no-op, not a second
    banked eviction (evictions [1,1], requeues 2, not [1,2]/3)."""
    sc = workloads.Scenario(
        name="gang_colo_tiny", horizon_s=70.0,
        # ONE node, so the shrink is guaranteed to hit the gang's node
        # (shrink removes the pool's highest-numbered = only node).
        pools=((1, 0),),
        autoscale=((15.0, "shrink", 0, 1), (25.0, "grow", 0, 1)),
        arrival="poisson", rate=0.012,  # seed 34: one gang at t=0.5
        gang_frac=1.0, gang_size=2,
        # 1700 cpu each: BOTH members fit the one 4000-cpu node.
        mix=((1.0, 0.0, (20.0, 20.0), (50, 51), (1700.0, 1750.0)),),
    )
    res = SimDriver(sc, seed=34).run()
    members = [p for p in res.pods if p.gang]
    assert len(members) == 2
    assert all(p.evictions == 1 for p in members), \
        [p.evictions for p in members]
    assert res.requeues == 2, res.requeues
    assert all(p.completed for p in members), \
        "gang re-placed together after the node grew back"


def test_soak_smoke_composes_faults_with_sim_clock():
    """Bounded tier-1 form of the long-horizon soak (ISSUE 9):
    diurnal load + node flaps + autoscale + gangs + a seeded
    engine-fault plan on one timeline. Injected engine.fetch errors
    drop cycles (counted + logged as cycle_failed — part of the
    deterministic hash), and the run still completes work."""
    from tpusched.sim import generators

    sc = generators.soak_smoke(45.0)
    d = SimDriver(sc, seed=0,
                  faults=generators.soak_fault_plan(0, cycles=45))
    res = d.run()
    assert res.failed_cycles >= 1, "the fault plan actually fired"
    assert res.completions > 0 and res.placed > 0
    assert res.autoscale_events > 0 and res.node_failures > 0
    s = sim_report.summarize(res)
    assert s["failed_cycles"] == res.failed_cycles
    # The drops are IN the hash-covered applied log, so the fault
    # schedule is part of the deterministic timeline.
    assert any(e["kind"] == "cycle_failed" for e in d.q.log)


# ---------------------------------------------------------------------------
# Long scenarios (full horizons): excluded from tier-1.
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_failure_storm_full_horizon_deterministic():
    sc = workloads.SCENARIOS["failure_storm"]
    a = run_scenario(sc, seed=0)
    b = run_scenario(sc, seed=0)
    assert a.event_log_hash == b.event_log_hash
    assert a.node_failures > 0
    assert a.requeues > 0, "failures interrupted running pods"
    s = sim_report.summarize(a)
    assert 0.0 <= s["slo_attainment_frac"] <= 1.0


@pytest.mark.slow
def test_burst_twin_full_horizon():
    twin = twin_run(workloads.SCENARIOS["burst"], seed=0)
    assert twin["qos"]["slo_pods"] > 0
    assert twin["qos"]["slo_attainment_frac"] >= \
        twin["static"]["slo_attainment_frac"], \
        "QoS must not LOSE to static under bursts"


@pytest.mark.slow
def test_soak_storm_full_horizon_deterministic():
    """The 600-virtual-second soak (ISSUE 9): diurnal + flaps +
    autoscale + gangs + lognormal tails + injected faults, twice on
    one seed — byte-identical event logs, faults fired both times."""
    from tpusched.sim import generators

    sc = workloads.SCENARIOS["soak_storm"]
    a = run_scenario(sc, seed=0,
                     faults=generators.soak_fault_plan(0, cycles=600))
    b = run_scenario(sc, seed=0,
                     faults=generators.soak_fault_plan(0, cycles=600))
    assert a.event_log_hash == b.event_log_hash
    assert a.failed_cycles >= 1 and a.failed_cycles == b.failed_cycles
    assert a.node_failures > 0 and a.autoscale_events > 0
    assert a.completions > 0
    s = sim_report.summarize(a)
    assert 0.0 <= s["slo_attainment_frac"] <= 1.0


@pytest.mark.slow
def test_soak_twin_with_faults_factory():
    """twin_run(faults_factory=...): both arms get a FRESH seeded
    FaultPlan (plans carry invocation counters), so a faulted soak
    twins deterministically — the same shots drop cycles in each arm."""
    from tpusched.sim import generators

    sc = generators.soak_smoke(60.0)
    twin = twin_run(
        sc, seed=0,
        faults_factory=lambda: generators.soak_fault_plan(0, cycles=60),
    )
    assert twin["qos"]["failed_cycles"] >= 1
    assert twin["static"]["failed_cycles"] >= 1
    assert twin["qos"]["slo_pods"] == twin["static"]["slo_pods"] > 0


@pytest.mark.slow
def test_matrix_run_covers_scenarios():
    """matrix_run (the simulate.py --scenario all surface) produces a
    row per scenario with both arms' attainment + churn + hashes."""
    from tpusched.sim.driver import matrix_run

    out = matrix_run(scenario_names=["steady_state", "gang_pressure"],
                     seed=0, horizon_s=40.0)
    assert [r["scenario"] for r in out["rows"]] == \
        ["steady_state", "gang_pressure"]
    for r in out["rows"]:
        assert 0.0 <= r["slo_attainment_frac"] <= 1.0
        assert 0.0 <= r["slo_attainment_frac_static"] <= 1.0
        assert r["preemption_churn"] >= 0.0
        assert r["hash_qos"] and r["hash_static"]
    text = sim_report.render_matrix(out)
    assert "gang_pressure" in text and "churn" in text
