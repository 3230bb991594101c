"""Scenario library + the ONE workload-synthesis path (ISSUE 5, 9).

Layered on tpusched/synth.py's cluster vocabulary (the same node
classes, zone labels, and app names the snapshot-level generators use)
but producing API-SERVER records plus an event timeline instead of a
prebuilt array snapshot: the simulator exercises the full host path —
watch, batch, solve, bind — not just the kernels.

`generate()` is the single synthesis code path (ISSUE 9): the
Borg/Azure-shaped presets in tpusched/sim/generators.py are plain
Scenario values fed through it, and tpusched/sim/traces.py serializes
its output (a SimSetup) to the on-disk trace format — so a generated
workload and an ingested trace drive SimDriver through identical
machinery and replay to byte-identical event-log hashes.

Scenario axes:

  * arrival process (poisson / burst / diurnal) and rate;
  * workload mix: per-class SLO target, base priority, duration, and
    resource shape, with tenant skew (tenants.zipf_weights — the one
    shared Zipf definition) for multi-tenant pressure;
  * duration distribution: uniform over the mix range, or lognormal
    long-tail (Borg-shaped: the range is read as (median, ~p99));
  * gang arrivals: a fraction of arrivals submit `gang_size` identical
    members under one pod_group with all-or-nothing minMember
    semantics (test_gangs.py is the kernel-level contract);
  * heterogeneous node pools (>= 2 shapes per cluster) and autoscale
    events: pools grow/shrink mid-horizon, which on the gRPC path
    drives the device-resident state's real bucket-growth and
    taint-vocab rebuild paths (device_state.py);
  * node failures (MTBF/MTTR flaps);
  * the pressure-skew twist, expressed in the mix itself: SLO-carrying
    classes get LOW base-priority ranges, SLO-less filler classes get
    HIGH ones — the adversarial mix where static priority starves
    exactly the pods with availability targets, and QoS-driven dynamic
    priority is the only thing that can rescue them. This is the
    twin-run headline scenario: attainment(qos_gain>0) -
    attainment(qos_gain=0) is the paper's central claim as one number.

Everything is drawn from one seeded Generator in generate(): same
(scenario, seed) -> identical specs and timeline. Scenarios that do not
use a new axis (gang_frac=0, uniform durations, no pools) draw the
EXACT same RNG stream as before the axis existed, so preset timelines
are stable across versions.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from tpusched.synth import NODE_CLASSES, ZONES
from tpusched.tenants import zipf_weights

from tpusched.sim import events as ev

APPS = ("web", "db", "cache", "batch")


@dataclasses.dataclass(frozen=True)
class Scenario:
    name: str
    description: str = ""          # one-liner for --list / the matrix
    # cluster (legacy single-pool form; `pools` overrides when set)
    n_nodes: int = 8
    node_class: int = 1            # index into synth.NODE_CLASSES
    # Heterogeneous pools: ((count, node_class[, (taint_k, v, effect)]),
    # ...). Pool p's nodes are named "p{p}-node-{i}" and labeled
    # tpusched.io/pool=p{p}; a pool may start at count 0 and only exist
    # through autoscale growth.
    pools: tuple = ()
    # Autoscale events: ((t, "grow"|"shrink", pool_idx, count), ...).
    # grow appends `count` nodes to the pool at virtual time t; shrink
    # removes the pool's highest-numbered nodes (running pods are
    # interrupted and re-queued with lifecycle history, like a real
    # scale-down eviction).
    autoscale: tuple = ()
    # time
    horizon_s: float = 150.0
    # arrivals
    arrival: str = "poisson"       # poisson | burst | diurnal
    rate: float = 0.3              # pods per virtual second
    burst_every_s: float = 40.0
    burst_size: int = 12
    diurnal_period_s: float = 120.0
    diurnal_amplitude: float = 0.8
    prefill: int = 0               # pods submitted at t=0
    # Prefill pods draw from ONE mix class (index; the filler class by
    # convention) with an optional widened duration range: staggered
    # durations make the warm cluster release slots continuously from
    # early in the run instead of in one cliff at min(duration).
    prefill_class: int = 0
    prefill_duration_s: "tuple | None" = None
    # workload mix: (weight, slo_target, duration range, priority range,
    # cpu range). Weights are normalized. slo_target 0 = no SLO.
    mix: tuple = (
        (0.5, 0.0, (40.0, 80.0), (50, 100), (1500.0, 2500.0)),
        (0.3, 0.7, (20.0, 40.0), (0, 50), (1500.0, 2500.0)),
        (0.2, 0.9, (20.0, 40.0), (0, 50), (1500.0, 2500.0)),
    )
    # Duration distribution over each class's (d_lo, d_hi) range:
    # "uniform", or "lognormal" long-tail where d_lo is the MEDIAN and
    # d_hi sits near the 99th percentile (Borg-style job durations:
    # most short, a heavy tail of long-runners).
    duration_dist: str = "uniform"
    # gang arrivals (coscheduling): fraction of non-prefill arrivals
    # that submit `gang_size` identical members under one pod_group.
    # gang_min_member 0 means all-or-nothing (minMember = gang_size).
    gang_frac: float = 0.0
    gang_size: int = 4
    gang_min_member: int = 0
    # multi-tenancy
    tenants: int = 4
    tenant_skew: float = 0.0       # 0 = uniform; higher = heavier head
    # failures
    node_mtbf_s: float = 0.0       # 0 = no failures
    node_mttr_s: float = 10.0
    # solver
    preemption: bool = False


@dataclasses.dataclass
class SimSetup:
    """generate()'s output: the initial cluster, per-pod specs/meta,
    and the fully-populated event queue. traces.write_trace serializes
    exactly these four members; traces.load_trace rebuilds them."""

    scenario: Scenario
    seed: int
    nodes: list            # api.add_node kwargs, keyed by ["name"]
    specs: dict            # pod name -> api.add_pod spec (wire fields)
    meta: dict             # pod name -> dict(duration_s, slo, tenant, ...)
    queue: ev.EventQueue


def _sample_duration(rng: np.random.Generator, dist: str,
                     d_lo: float, d_hi: float) -> float:
    if dist == "uniform":
        return float(rng.uniform(d_lo, d_hi))
    if dist == "lognormal":
        # d_lo = median, d_hi ~ p99 (z=2.326). The tail is deliberately
        # uncapped above d_hi — the long-runners that outlive several
        # diurnal periods are the point of a Borg-shaped trace.
        sigma = math.log(max(d_hi / max(d_lo, 1e-9), 1.0 + 1e-9)) / 2.326
        return float(max(d_lo * math.exp(sigma * rng.standard_normal()),
                         1e-3))
    raise ValueError(f"unknown duration_dist {dist!r}")


def _effective_pools(sc: Scenario) -> list[tuple]:
    """Pool list as (count, class_idx, taint-or-None); the legacy
    n_nodes/node_class form is one unnamed pool."""
    if not sc.pools:
        return [(int(sc.n_nodes), int(sc.node_class), None)]
    out = []
    for entry in sc.pools:
        if len(entry) == 2:
            count, cls = entry
            taint = None
        elif len(entry) == 3:
            count, cls, taint = entry
        else:
            raise ValueError(
                f"pool entry {entry!r}: want (count, node_class"
                "[, (taint_key, value, effect)])"
            )
        out.append((int(count), int(cls), taint))
    return out


def _node_record(sc: Scenario, pools: list[tuple], pi: int, i: int,
                 global_idx: int) -> dict:
    """Node record for pool pi's i-th node. Legacy single-pool
    scenarios keep the historical 'node-{i}' names (stable preset
    timelines); pooled clusters name 'p{pi}-node-{i}' and carry a pool
    label (and the pool's taint, if any)."""
    _, cls, taint = pools[pi]
    cpu, mem = NODE_CLASSES[cls % len(NODE_CLASSES)]
    if not sc.pools:
        name = f"node-{i}"
        labels = {
            "kubernetes.io/hostname": name,
            "topology.kubernetes.io/zone": ZONES[i % len(ZONES)],
        }
    else:
        name = f"p{pi}-node-{i}"
        labels = {
            "kubernetes.io/hostname": name,
            "topology.kubernetes.io/zone": ZONES[global_idx % len(ZONES)],
            "tpusched.io/pool": f"p{pi}",
        }
    rec = dict(
        name=name,
        allocatable={"cpu": float(cpu), "memory": float(mem)},
        labels=labels,
    )
    if taint is not None:
        rec["taints"] = [tuple(taint)]
    return rec


def _schedule_autoscale(sc: Scenario, pools: list[tuple],
                        counts: list[int], q: ev.EventQueue) -> None:
    """Turn sc.autoscale into node_add / node_remove events. Processed
    in time order so a later shrink sees earlier growth; node specs for
    grown nodes ride IN the event (the driver learns them at apply
    time, and the trace serializes them with the timeline)."""
    global_idx = sum(counts)
    for entry in sorted(sc.autoscale, key=lambda e: (e[0],)):
        t, op, pi, count = entry
        t, pi, count = float(t), int(pi), int(count)
        if not 0 <= pi < len(pools):
            raise ValueError(f"autoscale {entry!r}: no pool {pi}")
        if op == "grow":
            for _ in range(count):
                rec = _node_record(sc, pools, pi, counts[pi], global_idx)
                counts[pi] += 1
                global_idx += 1
                q.push(t, "node_add", node=rec["name"], spec=rec)
        elif op == "shrink":
            if counts[pi] < count:
                raise ValueError(
                    f"autoscale {entry!r}: pool {pi} has only "
                    f"{counts[pi]} nodes at t={t}"
                )
            for _ in range(count):
                counts[pi] -= 1
                rec = _node_record(sc, pools, pi, counts[pi], global_idx)
                q.push(t, "node_remove", node=rec["name"])
        else:
            raise ValueError(
                f"autoscale {entry!r}: op must be grow|shrink"
            )


def generate(sc: Scenario, seed: int) -> SimSetup:
    rng = np.random.default_rng(seed)
    pools = _effective_pools(sc)
    nodes = []
    global_idx = 0
    for pi, (count, _, _) in enumerate(pools):
        for i in range(count):
            nodes.append(_node_record(sc, pools, pi, i, global_idx))
            global_idx += 1

    if sc.arrival == "burst":
        times = ev.bursty_times(rng, sc.rate, sc.horizon_s,
                                sc.burst_every_s, sc.burst_size)
    elif sc.arrival == "diurnal":
        times = ev.diurnal_times(rng, sc.rate, sc.horizon_s,
                                 sc.diurnal_period_s, sc.diurnal_amplitude)
    elif sc.arrival == "poisson":
        times = ev.poisson_times(rng, sc.rate, sc.horizon_s)
    else:
        raise ValueError(f"unknown arrival process {sc.arrival!r}")
    times = [0.0] * sc.prefill + list(times)

    weights = np.asarray([m[0] for m in sc.mix], np.float64)
    weights = weights / weights.sum()
    tenant_p = zipf_weights(sc.tenants, sc.tenant_skew)

    specs: dict[str, dict] = {}
    meta: dict[str, dict] = {}
    q = ev.EventQueue()
    for i, t in enumerate(times):
        is_prefill = i < sc.prefill
        # The gang gate draw only happens when the axis is in use, so
        # gang-less scenarios keep their historical RNG stream.
        is_gang = (sc.gang_frac > 0.0 and not is_prefill
                   and rng.uniform() < sc.gang_frac)
        cls = (sc.prefill_class if is_prefill
               else int(rng.choice(len(sc.mix), p=weights)))
        _, slo, (d_lo, d_hi), (p_lo, p_hi), (c_lo, c_hi) = sc.mix[cls]
        if is_prefill and sc.prefill_duration_s is not None:
            d_lo, d_hi = sc.prefill_duration_s
        duration = _sample_duration(rng, sc.duration_dist, d_lo, d_hi)
        priority = float(rng.integers(p_lo, max(p_hi, p_lo + 1)))
        tenant = int(rng.choice(sc.tenants, p=tenant_p))
        cpu_req = float(rng.uniform(c_lo, c_hi))
        mem_req = float(rng.integers(1 << 28, 1 << 30))
        app = APPS[int(rng.integers(len(APPS)))]
        base = dict(
            requests={"cpu": cpu_req, "memory": mem_req},
            priority=priority,
            slo_target=float(slo),
            labels={"app": app, "tenant": f"tenant-{tenant}"},
            namespace=f"ns-{tenant}",
        )
        if is_gang:
            # One gang = gang_size IDENTICAL members (one Borg job's
            # homogeneous tasks) under one pod_group; one duration, so
            # a placed gang completes together. Members arrive at the
            # same instant and share the host's gang backoff key.
            gname = f"gang-sim-{i}"
            minm = sc.gang_min_member or sc.gang_size
            for j in range(sc.gang_size):
                name = f"sim-{i}g{j}"
                member = dict(base)
                member["labels"] = dict(base["labels"])
                member["pod_group"] = gname
                member["pod_group_min_member"] = minm
                specs[name] = member
                meta[name] = dict(duration_s=duration, slo=float(slo),
                                  tenant=tenant, priority=priority,
                                  gang=gname)
                q.push(t, "arrival", pod=name)
        else:
            name = f"sim-{i}"
            specs[name] = base
            meta[name] = dict(duration_s=duration, slo=float(slo),
                              tenant=tenant, priority=priority)
            q.push(t, "arrival", pod=name)

    for t, kind, node in ev.failure_times(
        rng, [n["name"] for n in nodes], sc.node_mtbf_s, sc.node_mttr_s,
        sc.horizon_s,
    ):
        q.push(t, kind, node=node)

    counts = [count for count, _, _ in pools]
    _schedule_autoscale(sc, pools, counts, q)

    return SimSetup(scenario=sc, seed=seed, nodes=nodes, specs=specs,
                    meta=meta, queue=q)


# ---------------------------------------------------------------------------
# Presets. Capacity intuition (node_class=1: 8000 cpu): each pod asks
# ~2000 cpu, so a node runs ~4 pods; slots = 4 * n_nodes. Service rate
# ~ slots / mean_duration; rates above it build the queues that make
# SLO attainment a real contest.
#
# The Borg/Azure-shaped presets live in tpusched/sim/generators.py and
# are merged into this registry at the bottom of this module; matrix
# consumers (tools/simulate.py --scenario all) iterate
# MATRIX_SCENARIOS.
# ---------------------------------------------------------------------------


SCENARIOS: dict[str, Scenario] = {
    # Comfortable load, no failures: the sanity scenario where both
    # static and QoS-driven scheduling should attain nearly everything.
    "steady_state": Scenario(
        name="steady_state", n_nodes=6, horizon_s=120.0,
        description="comfortable Poisson load, no failures: both "
                    "policies should attain nearly everything",
        arrival="poisson", rate=0.25,
        mix=(
            (0.5, 0.0, (20.0, 40.0), (0, 100), (1500.0, 2500.0)),
            (0.5, 0.8, (20.0, 40.0), (0, 100), (1500.0, 2500.0)),
        ),
    ),
    # Periodic submission spikes over a modest base: queues form during
    # bursts and drain between them.
    "burst": Scenario(
        name="burst", n_nodes=6, horizon_s=180.0,
        description="periodic submission spikes over a modest base: "
                    "queues form during bursts and drain between",
        arrival="burst", rate=0.15, burst_every_s=45.0, burst_size=16,
        mix=(
            (0.5, 0.0, (25.0, 50.0), (20, 100), (1500.0, 2500.0)),
            (0.5, 0.85, (15.0, 30.0), (0, 20), (1500.0, 2500.0)),
        ),
    ),
    # The headline twin-run scenario: a warm, permanently-overloaded
    # cluster of SLO-less fillers with HIGH base priority, plus a
    # stream of SLO pods with LOW base priority whose demand alone
    # would fit comfortably. Static priority hands every released slot
    # to the standing filler backlog and starves the SLO class; QoS
    # pressure lifts waiting SLO pods over the fillers. Preemption is
    # deliberately OFF here: under permanent overload the preemption
    # path equalizes availability across pods (pending pressured pods
    # evict just-recovered runners whose slack crossed zero), which
    # SPREADS the misses instead of concentrating them — a real effect
    # worth measuring, but it muddies the single-number queue-ordering
    # claim this scenario exists to pin.
    "pressure_skew": Scenario(
        name="pressure_skew", n_nodes=6, horizon_s=150.0,
        description="adversarial headline: high-priority SLO-less "
                    "fillers starve low-priority SLO pods unless QoS "
                    "pressure reorders the queue",
        arrival="poisson", rate=0.32, prefill=30,
        prefill_duration_s=(10.0, 90.0),
        mix=(
            # fillers: no SLO, high base priority, long-running
            (0.60, 0.0, (60.0, 90.0), (60, 100), (1800.0, 2400.0)),
            # SLO classes: tight availability targets, LOW base priority
            (0.20, 0.7, (25.0, 40.0), (0, 10), (1800.0, 2400.0)),
            (0.20, 0.9, (30.0, 45.0), (0, 10), (1800.0, 2400.0)),
        ),
        tenants=4, tenant_skew=1.0,
        preemption=False,
    ),
    # Node flaps mid-run: interrupted pods lose availability through no
    # queueing fault; measures how scheduling policy recovers them.
    "failure_storm": Scenario(
        name="failure_storm", n_nodes=8, horizon_s=180.0,
        description="node MTBF/MTTR flaps interrupt running pods; "
                    "measures how policy recovers their availability",
        arrival="poisson", rate=0.25,
        mix=(
            (0.4, 0.0, (30.0, 60.0), (20, 100), (1500.0, 2500.0)),
            (0.6, 0.8, (20.0, 40.0), (0, 40), (1500.0, 2500.0)),
        ),
        node_mtbf_s=60.0, node_mttr_s=15.0,
    ),
}


# Borg/Azure-shaped presets (ISSUE 9): generators.py builds them from
# the Scenario machinery above and MERGES them into SCENARIOS at its
# own import bottom — a bare import here is safe in either import
# order (no attribute access on a possibly-partially-initialized
# module), and either entry module leaves the registry complete.
import tpusched.sim.generators  # noqa: E402,F401  (side effect: merge)

# The simulate.py matrix: every scenario cheap enough to twin-run in
# one invocation (the long-horizon soak is deliberately excluded — run
# it alone, or via its bounded smoke).
MATRIX_SCENARIOS: tuple = (
    "steady_state",
    "burst",
    "pressure_skew",
    "failure_storm",
    "borg_longtail",
    "azure_diurnal",
    "autoscale_stress",
    "gang_pressure",
)
