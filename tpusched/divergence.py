"""Fast-mode divergence measurement (VERDICT weak #7; SURVEY.md §7 hard
part 1) and the warm-start twin audit (ROADMAP item 3).

The north star demands "placement parity with stock kube-scheduler".
Parity mode delivers it exactly (sequential scan == oracle, fuzz-tested
in tests/test_parity.py). Fast mode trades exact ordering under
contention for bounded rounds; its guarantees are:

  * validity — capacity, static predicates, DoNotSchedule spread,
    required (anti-)affinity all hold against commit-time state
    (audited by oracle.validate_assignment);
  * near-equal throughput — the same NUMBER of pods places to within a
    few percent, but not the same SET: measured on 6 seeds/preset
    (round 5, after the small-cluster fallback-depth fix), the `mixed`
    preset nets about -2% placements for fast mode; run this module
    for the current numbers rather than trusting prose;
  * exact node agreement whenever pods' decisions don't interact — note
    that load-balancing scores couple every pod to all earlier commits,
    so on busy clusters node choices differ by design while remaining
    equally valid and equally balanced. Measured: even the `plain`
    preset (no constraints at all) is only ~11% node-identical, because
    per-node agreement collapses once any commit order diverges.

This module puts NUMBERS on the divergence: run both modes over seeded
snapshots and report how often placements differ and by how much.

CLI:  python -m tpusched.divergence [--preset mixed] [--seeds 10]
      python -m tpusched.divergence --warm-audit 50 [--churn 0.05]
      python -m tpusched.divergence --warm-audit 50 --incremental

--warm-audit N runs N delta cycles TWIN — every cycle solved once warm
(carried tableau, dirty rows only) and once cold (full recompute) on the
same device-resident lineage — and reports the first diverging cycle
with the offending pod rows, plus placement-quality drift (placed-count
and chosen-score deltas vs the cold twin). The bitwise warm contract is
byte equality, so this is the debugging tool for when the twin-parity
tests trip: exit code 1 on any divergence. With --incremental the warm
arm is the BOUNDED-DIVERGENCE path (ISSUE 12): placements may legally
drift, so the audit enforces the validity contract instead — the
in-kernel audit and oracle.validate_assignment must both be clean every
cycle — and exit 1 means a validity violation, not mere divergence.
"""

from __future__ import annotations

import argparse
import dataclasses
import json

import numpy as np

from tpusched.config import EngineConfig
from tpusched.device_state import DeviceSnapshot
from tpusched.engine import Engine
from tpusched.oracle import validate_assignment
from tpusched.synth import make_cluster

# Contention presets: fractions chosen so the interesting regimes are
# all covered — no constraints (same placement COUNT; node choices still
# diverge via load-balance coupling), capacity pressure only,
# pairwise-heavy, and everything at once.
PRESETS: dict[str, dict] = {
    "plain": dict(),
    "tight": dict(initial_utilization=0.7, n_running_per_node=4),
    "pairwise": dict(spread_frac=0.6, interpod_frac=0.5, run_anti_frac=0.2),
    "mixed": dict(
        initial_utilization=0.5, n_running_per_node=3, taint_frac=0.2,
        toleration_frac=0.3, selector_frac=0.2, affinity_frac=0.3,
        spread_frac=0.4, interpod_frac=0.4, run_anti_frac=0.15,
        namespace_count=2, cordon_frac=0.15,
    ),
}


@dataclasses.dataclass
class DivergenceStats:
    preset: str
    seeds: int
    pods: int = 0                 # total pods compared
    same_node: int = 0            # identical placement (incl. both -1)
    both_placed_diff_node: int = 0
    fast_only_placed: int = 0
    parity_only_placed: int = 0
    fast_placed: int = 0
    parity_placed: int = 0
    fast_violations: int = 0      # MUST stay 0
    # Worst single-seed fast/parity placed ratio (advisor round 2: track
    # the per-seed worst case as a number so erosion of the fast-mode
    # throughput floor shows up in BENCH output, not just in a loosened
    # test threshold).
    min_placed_ratio: float = 1.0

    @property
    def identical_rate(self) -> float:
        return self.same_node / max(self.pods, 1)

    @property
    def placed_delta(self) -> int:
        """Fast minus parity total placements (0 = same throughput)."""
        return self.fast_placed - self.parity_placed

    def row(self) -> dict:
        return dict(
            preset=self.preset, seeds=self.seeds, pods=self.pods,
            identical_rate=round(self.identical_rate, 4),
            both_placed_diff_node=self.both_placed_diff_node,
            fast_only_placed=self.fast_only_placed,
            parity_only_placed=self.parity_only_placed,
            placed_delta=self.placed_delta,
            fast_violations=self.fast_violations,
            min_placed_ratio=round(self.min_placed_ratio, 4),
        )


def measure(
    preset: str = "mixed",
    seeds: int = 10,
    n_pods: int = 80,
    n_nodes: int = 16,
    base_seed: int = 3000,
    engines: "tuple[Engine, Engine] | None" = None,
) -> DivergenceStats:
    """Run fast and parity over `seeds` random snapshots of a preset and
    accumulate agreement statistics. Every fast assignment is also run
    through the independent validity audit. `engines` = (fast, parity)
    to reuse jit caches across presets."""
    kw = PRESETS[preset]
    if engines is not None:
        fast, parity = engines
    else:
        fast = Engine(EngineConfig(mode="fast"))
        parity = Engine(EngineConfig(mode="parity"))
    out = DivergenceStats(preset=preset, seeds=seeds)
    for s in range(seeds):
        rng = np.random.default_rng(base_seed + s)
        snap, meta = make_cluster(rng, n_pods, n_nodes, **kw)
        fres = fast.solve(snap)
        pres = parity.solve(snap)
        P = meta.n_pods
        fa = fres.assignment[:P]
        pa = pres.assignment[:P]
        out.pods += P
        out.same_node += int((fa == pa).sum())
        out.both_placed_diff_node += int(((fa >= 0) & (pa >= 0) & (fa != pa)).sum())
        out.fast_only_placed += int(((fa >= 0) & (pa < 0)).sum())
        out.parity_only_placed += int(((fa < 0) & (pa >= 0)).sum())
        seed_fast = int((fa >= 0).sum())
        seed_parity = int((pa >= 0).sum())
        out.fast_placed += seed_fast
        out.parity_placed += seed_parity
        if seed_parity > 0:
            out.min_placed_ratio = min(
                out.min_placed_ratio, seed_fast / seed_parity
            )
        violations = validate_assignment(
            snap, fast.config, fres.assignment,
            commit_key=fres.commit_key, evicted=fres.evicted,
        )
        out.fast_violations += len(violations)
    return out


def warm_churn_stream(rng, nodes, pods, running, cycles: int,
                      churn_frac: float = 0.05,
                      structural_every: int = 5):
    """Seeded delta-cycle generator for the warm audit (and bench churn
    sweeps): mutates the record lists IN PLACE and yields
    DeviceSnapshot.apply kwargs. Each cycle value-churns ~churn_frac of
    the pending pods (observed availability / priority — the QoS
    temporal-locality signal the warm path bets on) plus one node
    (allocatable drift); every `structural_every`-th cycle additionally
    exercises the structural paths: a pod add + remove (row reorder), a
    running-pod removal (a completion), and a cordon toggle (the
    all-residents column invalidation)."""
    seq = 0
    for cyc in range(cycles):
        n_churn = max(1, int(round(churn_frac * len(pods))))
        picks = rng.choice(len(pods), size=min(n_churn, len(pods)),
                           replace=False)
        up_pods = []
        for i in picks:
            rec = pods[int(i)]
            rec["observed_avail"] = float(rng.uniform(0.3, 1.0))
            if rng.random() < 0.3:
                rec["priority"] = float(rng.integers(0, 1000))
            up_pods.append(rec)
        ni = int(rng.integers(len(nodes)))
        nrec = nodes[ni]
        alloc = dict(nrec.get("allocatable", {}))
        if "cpu" in alloc:
            alloc["cpu"] = float(max(1000.0, alloc["cpu"]
                                     * float(rng.uniform(0.9, 1.1))))
        nrec["allocatable"] = alloc
        delta = dict(upsert_pods=up_pods, upsert_nodes=[nrec])
        if structural_every and cyc % structural_every == structural_every - 1:
            seq += 1
            newp = dict(
                name=f"warm-audit-{seq:04d}",
                requests={"cpu": float(rng.integers(100, 800))},
                priority=float(rng.integers(0, 1000)),
                observed_avail=float(rng.uniform(0.5, 1.0)),
                labels={"app": "web"},
            )
            pods.append(newp)
            gone = pods.pop(int(rng.integers(len(pods) - 1)))
            delta["upsert_pods"] = [
                r for r in delta["upsert_pods"] if r["name"] != gone["name"]
            ] + [newp]
            delta["remove_pods"] = [gone["name"]]
            if running:
                done = running.pop(int(rng.integers(len(running))))
                delta["remove_running"] = [done["name"]]
            cn = int(rng.integers(len(nodes)))
            crec = nodes[cn]
            crec["unschedulable"] = not crec.get("unschedulable", False)
            if crec["name"] != nrec["name"]:
                delta["upsert_nodes"] = delta["upsert_nodes"] + [crec]
        yield delta


def warm_audit(
    cycles: int = 50,
    preset: str = "mixed",
    n_pods: int = 80,
    n_nodes: int = 16,
    seed: int = 4000,
    churn_frac: float = 0.05,
    mode: str = "fast",
    preemption: bool = False,
    engine: "Engine | None" = None,
    incremental: bool = False,
) -> dict:
    """Twin-run N delta cycles warm vs cold on ONE device-resident
    lineage (the --warm-audit debugging tool). Every cycle: apply a
    seeded churn delta, solve once through the engine warm path
    (Engine.solve_warm: carried tableau + dirty rows), once cold
    (Engine.solve: full recompute of the same arrays).

    Bitwise mode (default): byte-compare assignment / chosen_score /
    evicted and report the first divergence — diverged_cycle (-1 =
    clean) + bad_pods [(row, name, warm_node, cold_node)].

    incremental=True (ISSUE 12): the warm solve is the BOUNDED-
    DIVERGENCE path (solve_warm(incremental=True)); placements may
    legally differ from the cold twin, so the audit instead enforces
    the VALIDITY contract — the in-kernel audit (SolveResult.inc_info)
    must be clean AND oracle.validate_assignment must find nothing —
    and diverged_cycle marks the first validity failure.

    Both modes now also report PLACEMENT-QUALITY drift vs the cold
    twin (trivially zero in a clean bitwise run): placed-count totals
    and per-cycle worst delta, plus the mean |chosen_score| drift over
    pods both twins placed (carried placements keep their
    as-of-placement score, so nonzero drift here is expected churn
    aging, not a bug)."""
    cfg = EngineConfig(mode=mode, preemption=preemption)
    rng = np.random.default_rng(seed)
    nodes, pods, running = make_cluster(
        rng, n_pods, n_nodes, as_records=True, **PRESETS[preset]
    )
    nodes, pods, running = list(nodes), list(pods), list(running)
    ds = DeviceSnapshot(cfg)
    ds.full_load(nodes, pods, running)
    eng = engine if engine is not None else Engine(cfg)
    report = dict(cycles=0, diverged_cycle=-1, bad_pods=[],
                  preset=preset, churn_frac=churn_frac, mode=mode,
                  incremental=incremental, validity_violations=0,
                  placed_warm_total=0, placed_cold_total=0,
                  worst_cycle_placed_delta=0)
    drift = []
    try:
        if incremental:
            # Establish the lineage's carry (the seed the bounded-
            # divergence path starts from) before the audited cycles.
            eng.solve_warm(ds)
        for cyc, delta in enumerate(warm_churn_stream(
                rng, nodes, pods, running, cycles, churn_frac)):
            ds.apply(**delta)
            warm = eng.solve_warm(ds, incremental=incremental)
            cold = eng.solve(ds.snap)
            report["cycles"] = cyc + 1
            pw = int((warm.assignment >= 0).sum())
            pc = int((cold.assignment >= 0).sum())
            report["placed_warm_total"] += pw
            report["placed_cold_total"] += pc
            if abs(pw - pc) > abs(report["worst_cycle_placed_delta"]):
                report["worst_cycle_placed_delta"] = pw - pc
            both = (warm.assignment >= 0) & (cold.assignment >= 0)
            if both.any():
                wsc = np.asarray(warm.chosen_score)[both]
                csc = np.asarray(cold.chosen_score)[both]
                drift.append(float(np.mean(np.abs(wsc - csc))))
            if incremental:
                viol = list(validate_assignment(
                    ds.snap, cfg, warm.assignment,
                    commit_key=warm.commit_key, evicted=warm.evicted,
                ))
                inc_bad = (warm.inc_info or {}).get("audit_violations", 0)
                if viol or inc_bad:
                    report["validity_violations"] += len(viol) + inc_bad
                    if report["diverged_cycle"] < 0:
                        report["diverged_cycle"] = cyc
                        report["bad_pods"] = [
                            (-1, f"<validity: {v}>", -1, -1)
                            for v in viol[:16]
                        ] + ([(-1, f"<in-kernel audit: "
                                   f"{warm.inc_info}>", -1, -1)]
                             if inc_bad else [])
                    break
                continue
            same = (
                np.array_equal(warm.assignment, cold.assignment)
                and np.array_equal(np.asarray(warm.chosen_score),
                                   np.asarray(cold.chosen_score))
                and np.array_equal(warm.evicted, cold.evicted)
            )
            if not same:
                bad = np.nonzero(warm.assignment != cold.assignment)[0]
                names = ds.meta.pod_names
                report["diverged_cycle"] = cyc
                report["bad_pods"] = [
                    (int(i), names[int(i)] if int(i) < len(names) else "<pad>",
                     int(warm.assignment[int(i)]),
                     int(cold.assignment[int(i)]))
                    for i in bad[:32]
                ]
                if not len(bad):
                    report["bad_pods"] = [
                        (-1, "<score-or-eviction-divergence>", -1, -1)
                    ]
                break
    finally:
        if engine is None:
            eng.close()
    report.update(
        warm_solves=ds.warm_solves, cold_solves=ds.cold_solves,
        incremental_solves=ds.incremental_solves,
        cold_reasons=ds.warm_cold_reasons,
        placed_delta_total=(report["placed_warm_total"]
                            - report["placed_cold_total"]),
        mean_abs_score_drift=(round(float(np.mean(drift)), 6)
                              if drift else 0.0),
    )
    return report


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--preset", choices=sorted(PRESETS), default=None,
                    help="default: all presets")
    ap.add_argument("--seeds", type=int, default=10)
    ap.add_argument("--pods", type=int, default=80)
    ap.add_argument("--nodes", type=int, default=16)
    ap.add_argument("--warm-audit", type=int, default=0, metavar="N",
                    help="run N warm-vs-cold twin delta cycles and "
                         "report the first divergence (exit 1)")
    ap.add_argument("--churn", type=float, default=0.05,
                    help="warm-audit per-cycle churned-pod fraction")
    ap.add_argument("--seed", type=int, default=4000)
    ap.add_argument("--preemption", action="store_true",
                    help="warm-audit with the preemption program")
    ap.add_argument("--incremental", action="store_true",
                    help="warm-audit the bounded-divergence incremental "
                         "path: validity contract + quality drift "
                         "instead of bitwise parity")
    args = ap.parse_args(argv)
    if args.incremental and not args.warm_audit:
        ap.error("--incremental requires --warm-audit N")
    if args.warm_audit:
        report = warm_audit(
            cycles=args.warm_audit, preset=args.preset or "mixed",
            n_pods=args.pods, n_nodes=args.nodes, seed=args.seed,
            churn_frac=args.churn, preemption=args.preemption,
            incremental=args.incremental,
        )
        print(json.dumps(report), flush=True)
        if report["diverged_cycle"] >= 0:
            raise SystemExit(1)
        return
    presets = [args.preset] if args.preset else sorted(PRESETS)
    for p in presets:
        stats = measure(p, args.seeds, args.pods, args.nodes)
        print(json.dumps(stats.row()), flush=True)


if __name__ == "__main__":
    main()
