"""Fast-mode (round-based batched commit) tests: validity properties on
contended snapshots, exact sequential parity on non-interacting ones,
and bounded round counts (SURVEY.md §7 hard parts 1/3)."""

import dataclasses

import numpy as np
import pytest

from tpusched import Engine, EngineConfig
from tpusched.oracle import Oracle, validate_assignment
from tpusched.synth import make_cluster
from tpusched.snapshot import (MatchExpression, SnapshotBuilder,
                               TopologySpreadConstraint)


def fast_cfg():
    return EngineConfig(mode="fast")


def check_valid(snap, res, cfg):
    violations = validate_assignment(
        snap, cfg, res.assignment, commit_key=res.commit_key
    )
    assert violations == [], violations


def test_fast_valid_resources_only(rng):
    snap, _ = make_cluster(rng, 60, 12)
    cfg = fast_cfg()
    res = Engine(cfg).solve(snap)
    check_valid(snap, res, cfg)
    assert res.rounds < 20


def test_fast_valid_overcommitted(rng):
    snap, _ = make_cluster(rng, 64, 4, initial_utilization=0.7)
    cfg = fast_cfg()
    res = Engine(cfg).solve(snap)
    check_valid(snap, res, cfg)
    assert (res.assignment == -1).any()


@pytest.mark.parametrize("seed", range(6))
def test_fast_valid_fuzz(seed):
    rng = np.random.default_rng(2000 + seed)
    snap, _ = make_cluster(
        rng,
        n_pods=int(rng.integers(10, 60)),
        n_nodes=int(rng.integers(4, 20)),
        taint_frac=float(rng.uniform(0, 0.5)),
        toleration_frac=float(rng.uniform(0, 0.5)),
        selector_frac=float(rng.uniform(0, 0.4)),
        affinity_frac=float(rng.uniform(0, 0.4)),
        spread_frac=float(rng.uniform(0, 0.5)),
        interpod_frac=float(rng.uniform(0, 0.5)),
    )
    cfg = fast_cfg()
    res = Engine(cfg).solve(snap)
    check_valid(snap, res, cfg)


def test_fast_matches_sequential_when_pinned(rng):
    """Pods pinned to distinct nodes via nodeSelector: decisions cannot
    interact, so fast mode must equal the oracle exactly."""
    cfg = fast_cfg()
    b = SnapshotBuilder(cfg)
    for i in range(8):
        b.add_node(f"n{i}", {"cpu": 4000, "memory": 16 << 30},
                   labels={"slot": str(i)})
    for i in range(8):
        b.add_pod(f"p{i}", {"cpu": 500, "memory": 1 << 30},
                  node_selector={"slot": str(i)})
    snap, _ = b.build()
    res = Engine(cfg).solve(snap)
    ora = Oracle(snap, cfg).solve()
    np.testing.assert_array_equal(res.assignment, ora.assignment)
    assert res.rounds <= 3  # one productive round + termination check


def test_fast_places_as_many_as_oracle(rng):
    """On plain resource workloads fast mode should not lose placements
    vs sequential (it can only reorder who gets which node)."""
    snap, _ = make_cluster(rng, 48, 12)
    cfg = fast_cfg()
    res = Engine(cfg).solve(snap)
    ora = Oracle(snap, cfg).solve()
    assert (res.assignment >= 0).sum() >= (ora.assignment >= 0).sum() - 2


def test_fast_gang_workload_valid(rng):
    # gangs enforced all-or-nothing; fast mode stays valid
    snap, _ = make_cluster(rng, 32, 8, gang_frac=0.5, gang_size=4)
    cfg = fast_cfg()
    res = Engine(cfg).solve(snap)
    check_valid(snap, res, cfg)


def test_fast_required_self_affinity_first_pod():
    """First pod of a self-affine group must schedule (upstream special
    case), and followers co-locate with it — in both modes."""
    for mode in ("parity", "fast"):
        cfg = EngineConfig(mode=mode)
        b = SnapshotBuilder(cfg)
        for i in range(4):
            b.add_node(f"n{i}", {"cpu": 4000, "memory": 16 << 30},
                       labels={"zone": "ab"[i % 2]})
        from tpusched.snapshot import MatchExpression, PodAffinityTerm
        for i in range(3):
            b.add_pod(
                f"w{i}", {"cpu": 100, "memory": 1 << 28},
                labels={"app": "w"},
                pod_affinity=[PodAffinityTerm(
                    "zone", (MatchExpression("app", "In", ("w",)),)
                )],
            )
        snap, _ = b.build()
        res = Engine(cfg).solve(snap)
        zones = np.asarray(snap.nodes.domain)[:, 0]
        placed = res.assignment[:3]
        assert (placed >= 0).all(), f"{mode}: self-affine pods unplaced"
        assert len(set(zones[placed].tolist())) == 1, f"{mode}: not co-located"


def test_ia_ok_at_choice_matches_full_matrix():
    """The chosen-node-only IA validator (round 5; used by the fast
    loop's commit-validation fixpoint) must agree BITWISE with the full
    [P, N] pairwise_from_counts gathered at the chosen column, for any
    committed subset, across constraint-heavy fuzz snapshots."""
    import jax.numpy as jnp

    from tpusched.engine import _sat_tables
    from tpusched.kernels import pairwise as kpair
    from tpusched.kernels.assign import precompute_static
    from tpusched.synth import make_cluster

    for seed in range(4):
        rng = np.random.default_rng(71000 + seed)
        snap, _ = make_cluster(
            rng, 40, 10, spread_frac=0.4, interpod_frac=0.5,
            run_anti_frac=0.3, namespace_count=2,
            initial_utilization=0.4, n_running_per_node=2,
        )
        if int(np.asarray(snap.sigs.key).shape[0]) == 0:
            continue
        cfg = EngineConfig()
        node_sat_t, member_sat_t = _sat_tables(snap)
        static = precompute_static(cfg, snap, node_sat_t, member_sat_t)
        st = kpair.pair_state_init(snap, static.sig_match)
        P = int(np.asarray(snap.pods.valid).shape[0])
        N = int(np.asarray(snap.nodes.valid).shape[0])
        choice = jnp.asarray(
            rng.integers(-1, N, size=P).astype(np.int32)
        )
        kept = jnp.asarray(rng.random(P) < 0.7) & (choice >= 0)
        st2 = kpair.pair_state_commit(
            snap, st, static.sig_match, choice, kept
        )
        esn = jnp.where(kept, choice, -1)
        _, _, ia_full, _ = kpair.pairwise_from_counts(
            snap, st2, static.aff_ok, static.sig_match,
            exclude_self_node=esn,
        )
        want = np.asarray(
            jnp.take_along_axis(
                ia_full, jnp.clip(choice, 0, N - 1)[:, None], axis=1
            )[:, 0]
        )
        got = np.asarray(
            kpair.ia_ok_at_choice(snap, st2, static.sig_match, choice, esn)
        )
        np.testing.assert_array_equal(got, want, err_msg=f"seed {seed}")


@pytest.mark.parametrize("seed", [0, 1])
def test_fast_capacity_exact_past_f32_prefix(seed):
    """Node capacity one millicore short of k+1 identical pods, with
    enough pods that a global request prefix passes 2^24: the commit's
    per-node sums must stay exact, never admitting pod k+1 (the first
    10k x 5k chip run over-filled a node by one millicore this way)."""
    rng = np.random.default_rng(seed)
    cfg = fast_cfg()
    b = SnapshotBuilder(cfg)
    for i in range(40):
        b.add_node(f"n{i}", {"cpu": float(rng.integers(20, 40) * 9999 - 1)})
    for j in range(2000):
        b.add_pod(f"p{j}", {"cpu": 9999.0},
                  priority=float(rng.integers(0, 100)))
    snap, _ = b.build()
    res = Engine(cfg).solve(snap)
    check_valid(snap, res, cfg)



@pytest.mark.parametrize("pending, c_short", [(6, 0), (20, 0), (16, 4)])
def test_fast_places_every_pod_under_host_and_zone_spread(pending, c_short):
    """scheduler_perf's spread template: DoNotSchedule (maxSkew 5) on
    the hostname and on the zone. Zone a holds the emptiest hosts (7
    foo=bar pods each) but 40 more foo=bar pods than zones b and c, whose
    hosts hold 11 or 13: only b's and c's 11s take a pod. The sequential
    scheduler places every pod, and so must the rounds. Water-filled on
    the hostname alone, every member went to zone a, which the
    end-of-round zone skew rejects, round after round, and the rounds
    stopped with nothing placed. c_short: zone c holds that many fewer
    (12s for 13s), so the fill deals c its first members alone, and the
    members after them must go on to c's next hosts, not back to its
    first: one pod a host, nothing reverted."""
    cfg = fast_cfg()
    b = SnapshotBuilder(cfg)
    foo = (MatchExpression("foo", "In", ("bar",)),)
    spread = [TopologySpreadConstraint(key, 5, "DoNotSchedule", foo)
              for key in ("kubernetes.io/hostname",
                          "topology.kubernetes.io/zone")]
    hosts = ([("a", 7)] * 40 + [("b", 11), ("b", 13)] * 10
             + [("c", 11), ("c", 12)] * c_short
             + [("c", 11), ("c", 13)] * (10 - c_short))
    for i, (zone, held) in enumerate(hosts):
        b.add_node(f"n{i}", {"cpu": 4000, "memory": 32 << 30},
                   labels={"kubernetes.io/hostname": f"n{i}",
                           "topology.kubernetes.io/zone": zone})
        for _ in range(held):
            b.add_running_pod(f"n{i}", {"cpu": 100, "memory": 500 << 20},
                              labels={"foo": "bar"})
    for j in range(pending):
        b.add_pod(f"p{j}", {"cpu": 100, "memory": 500 << 20},
                  priority=float(j % 7), labels={"foo": "bar"},
                  topology_spread=spread)
    snap, _ = b.build()
    res = Engine(cfg).solve(snap)
    check_valid(snap, res, cfg)
    ora = Oracle(snap, cfg).solve()
    assert (ora.assignment[:pending] >= 0).all()
    assert (res.assignment[:pending] >= 0).all(), res.assignment[:pending]
    assert (res.rounds, res.reverts) == (1, 0)


def test_fast_backstop_places_what_the_water_fill_misdeals():
    """Three DoNotSchedule keys: the water-fill balances the coarsest
    (zone) and fans out by the finest (hostname), blind to the rack
    between them. The emptiest hosts (7 foo=bar pods) sit in racks a1
    and b1, which hold 20 more than a2 and b2: every water-filled member
    lands in a rack the end-of-round skew rejects. The progress backstop
    must then deal the reverted pods by the start-state filter (a2's
    and b2's 11s) instead of stopping with them pending."""
    cfg = fast_cfg()
    b = SnapshotBuilder(cfg)
    foo = (MatchExpression("foo", "In", ("bar",)),)
    spread = [TopologySpreadConstraint(key, 5, "DoNotSchedule", foo)
              for key in ("kubernetes.io/hostname", "rack",
                          "topology.kubernetes.io/zone")]
    hosts = []
    for zone in "ab":
        hosts += [(zone, zone + "1", 7)] * 20
        hosts += [(zone, zone + "2", 11), (zone, zone + "2", 13)] * 5
    for i, (zone, rack, held) in enumerate(hosts):
        b.add_node(f"n{i}", {"cpu": 4000, "memory": 32 << 30},
                   labels={"kubernetes.io/hostname": f"n{i}", "rack": rack,
                           "topology.kubernetes.io/zone": zone})
        for _ in range(held):
            b.add_running_pod(f"n{i}", {"cpu": 100, "memory": 500 << 20},
                              labels={"foo": "bar"})
    for j in range(6):
        b.add_pod(f"p{j}", {"cpu": 100, "memory": 500 << 20},
                  priority=float(j), labels={"foo": "bar"},
                  topology_spread=spread)
    snap, _ = b.build()
    res = Engine(cfg).solve(snap)
    check_valid(snap, res, cfg)
    assert (Oracle(snap, cfg).solve().assignment[:6] >= 0).all()
    assert (res.assignment[:6] >= 0).all(), res.assignment[:6]
    assert res.reverts > 0


def test_fast_leading_conservative_pod_commits_alone():
    """The progress backstop's conservative pod commits alone in its
    signature clusters: the round it leads, the other pods it could
    interact with sit out, so nothing moves the counts it was filtered
    against. Two pods spread over two empty hosts, the higher-priority
    one optimistic, the other marked conservative in the seeded state:
    the conservative one commits in round 0, the other in round 1."""
    import jax
    import jax.numpy as jnp

    from tpusched.engine import _sat_tables
    from tpusched.kernels import assign as ka
    from tpusched.kernels import pairwise as kpair

    cfg = fast_cfg()
    b = SnapshotBuilder(cfg)
    app = (MatchExpression("app", "In", ("x",)),)
    for i in range(2):
        b.add_node(f"n{i}", {"cpu": 4000, "memory": 16 << 30},
                   labels={"kubernetes.io/hostname": f"n{i}"})
    for name, prio in (("hi", 10.0), ("lo", 1.0)):
        b.add_pod(name, {"cpu": 100, "memory": 1 << 28}, priority=prio,
                  labels={"app": "x"}, topology_spread=[
                      TopologySpreadConstraint("kubernetes.io/hostname", 1,
                                               "DoNotSchedule", app)])
    snap, meta = b.build()
    snap = jax.tree.map(jnp.asarray, snap)
    P = int(snap.pods.valid.shape[0])
    N = int(snap.nodes.valid.shape[0])
    static = ka.precompute_static(cfg, snap, *_sat_tables(snap))
    order = ka.pop_order(cfg, snap)
    rank = jnp.zeros(P, jnp.int32).at[order].set(jnp.arange(P, dtype=jnp.int32))
    st0 = kpair.pair_state_init(snap, static.sig_match)
    invol, has_pair = ka._sig_involvement(snap, static, st0)
    lo = meta.pod_names.index("lo")
    hi = meta.pod_names.index("hi")
    init = (snap.nodes.used, jnp.full(P, -1, jnp.int32), st0,
            jnp.zeros(P, bool).at[lo].set(True),
            jnp.full(P, ka.NEG_INF, jnp.float32), jnp.full(P, -1, jnp.int32),
            jnp.array(True), jnp.int32(0), jnp.int32(0))
    out = ka._solve_rounds_sig(cfg, snap, static, rank, order, invol,
                               has_pair, init, 2 * P + 8,
                               ka._fallback_depth(N), 0)
    assigned, round_of = np.asarray(out[1]), np.asarray(out[5])
    assert assigned[lo] >= 0 and assigned[hi] >= 0
    assert assigned[lo] != assigned[hi]
    assert (round_of[lo], round_of[hi]) == (0, 1)
