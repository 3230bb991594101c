"""Oracle sanity tests: hand-computed fixtures per plugin (the table-driven
style of upstream plugin unit tests, SURVEY.md §4 item 1). The oracle is
the spec — these tests pin its semantics before parity tests compare the
TPU path against it."""

import numpy as np
import pytest

from tpusched import EngineConfig, SnapshotBuilder
from tpusched.config import PluginWeights, QoSConfig
from tpusched.oracle import EFFECT_NO_EXECUTE, EFFECT_NO_SCHEDULE, Oracle
from tpusched.snapshot import (
    MatchExpression,
    NodeSelectorTerm,
    PodAffinityTerm,
    PreferredTerm,
    Toleration,
    TopologySpreadConstraint,
)
import dataclasses


def lr_only_config(**kw):
    return EngineConfig(
        weights=PluginWeights(
            least_requested=1.0, balanced_allocation=0.0, node_affinity=0.0,
            taint_toleration=0.0, topology_spread=0.0, interpod_affinity=0.0,
        ),
        qos=QoSConfig(urgency_reweight=False),
        **kw,
    )


def test_least_requested_prefers_empty_node():
    cfg = lr_only_config()
    b = SnapshotBuilder(cfg)
    b.add_node("busy", {"cpu": 4000, "memory": 8 << 30})
    b.add_node("empty", {"cpu": 4000, "memory": 8 << 30})
    b.add_running_pod("busy", {"cpu": 3000, "memory": 6 << 30})
    b.add_pod("p0", {"cpu": 500, "memory": 1 << 30})
    snap, meta = b.build()
    res = Oracle(snap, cfg).solve()
    assert meta.node_names[res.assignment[0]] == "empty"


def test_resource_fit_excludes_full_node():
    cfg = lr_only_config()
    b = SnapshotBuilder(cfg)
    b.add_node("full", {"cpu": 1000, "memory": 8 << 30})
    b.add_node("fits", {"cpu": 4000, "memory": 8 << 30})
    b.add_running_pod("full", {"cpu": 900, "memory": 1 << 30})
    b.add_pod("p0", {"cpu": 500, "memory": 1 << 30})
    snap, meta = b.build()
    res = Oracle(snap, cfg).solve()
    assert meta.node_names[res.assignment[0]] == "fits"


def test_unschedulable_gets_minus_one():
    cfg = lr_only_config()
    b = SnapshotBuilder(cfg)
    b.add_node("tiny", {"cpu": 100, "memory": 1 << 20})
    b.add_pod("huge", {"cpu": 64000, "memory": 1 << 40})
    snap, _ = b.build()
    res = Oracle(snap, cfg).solve()
    assert res.assignment[0] == -1


def test_sequential_state_update():
    # Two identical pods, two nodes sized so both pods fit on either node
    # individually but not together: the second pod must go elsewhere.
    cfg = lr_only_config()
    b = SnapshotBuilder(cfg)
    b.add_node("n0", {"cpu": 1000, "memory": 4 << 30})
    b.add_node("n1", {"cpu": 1000, "memory": 4 << 30})
    b.add_pod("p0", {"cpu": 700, "memory": 1 << 30}, priority=10)
    b.add_pod("p1", {"cpu": 700, "memory": 1 << 30}, priority=5)
    snap, _ = b.build()
    res = Oracle(snap, cfg).solve()
    assert set(res.assignment.tolist()[:2]) == {0, 1}


def test_taint_filter():
    cfg = lr_only_config()
    b = SnapshotBuilder(cfg)
    b.add_node("tainted", {"cpu": 64000, "memory": 1 << 40},
               taints=[("dedicated", "batch", "NoSchedule")])
    b.add_node("clean", {"cpu": 1000, "memory": 4 << 30})
    b.add_pod("plain", {"cpu": 100, "memory": 1 << 20})
    b.add_pod("tolerant", {"cpu": 100, "memory": 1 << 20},
              tolerations=[Toleration("dedicated", "Equal", "batch")])
    snap, meta = b.build()
    res = Oracle(snap, cfg).solve()
    assert meta.node_names[res.assignment[0]] == "clean"
    # tolerant pod prefers the huge empty tainted node
    assert meta.node_names[res.assignment[1]] == "tainted"


def test_node_selector_and_affinity():
    cfg = lr_only_config()
    b = SnapshotBuilder(cfg)
    b.add_node("ssd", {"cpu": 1000, "memory": 4 << 30}, labels={"disk": "ssd"})
    b.add_node("hdd", {"cpu": 64000, "memory": 1 << 40}, labels={"disk": "hdd"})
    b.add_pod("wants-ssd", {"cpu": 100, "memory": 1 << 20},
              node_selector={"disk": "ssd"})
    b.add_pod("not-hdd", {"cpu": 100, "memory": 1 << 20}, required_terms=[
        NodeSelectorTerm((MatchExpression("disk", "NotIn", ("hdd",)),))
    ])
    b.add_pod("gt", {"cpu": 100, "memory": 1 << 20}, required_terms=[
        NodeSelectorTerm((MatchExpression("gen", "Gt", ("3",)),))
    ])
    snap, meta = b.build()
    res = Oracle(snap, cfg).solve()
    assert meta.node_names[res.assignment[0]] == "ssd"
    assert meta.node_names[res.assignment[1]] == "ssd"
    assert res.assignment[2] == -1  # no node has numeric "gen" label


def test_preferred_affinity_steers():
    cfg = dataclasses.replace(
        lr_only_config(),
        weights=PluginWeights(
            least_requested=0.0, balanced_allocation=0.0, node_affinity=1.0,
            taint_toleration=0.0, topology_spread=0.0, interpod_affinity=0.0,
        ),
    )
    b = SnapshotBuilder(cfg)
    b.add_node("a", {"cpu": 64000, "memory": 1 << 40}, labels={"disk": "hdd"})
    b.add_node("b", {"cpu": 1000, "memory": 4 << 30}, labels={"disk": "ssd"})
    b.add_pod("p", {"cpu": 100, "memory": 1 << 20}, preferred_terms=[
        PreferredTerm(10.0, NodeSelectorTerm((MatchExpression("disk", "In", ("ssd",)),)))
    ])
    snap, meta = b.build()
    res = Oracle(snap, cfg).solve()
    assert meta.node_names[res.assignment[0]] == "b"


def test_qos_priority_order():
    # Lower observed availability vs SLO -> higher dynamic priority ->
    # pops first and takes the only slot.
    cfg = lr_only_config()
    b = SnapshotBuilder(cfg)
    b.add_node("n0", {"cpu": 1000, "memory": 4 << 30})
    b.add_pod("comfortable", {"cpu": 800, "memory": 1 << 30},
              slo_target=0.9, observed_avail=0.95)
    b.add_pod("starved", {"cpu": 800, "memory": 1 << 30},
              slo_target=0.9, observed_avail=0.5)
    snap, _ = b.build()
    res = Oracle(snap, cfg).solve()
    assert res.assignment[1] == 0      # starved pod won the node
    assert res.assignment[0] == -1
    assert res.order[0] == 1


def test_topology_spread_do_not_schedule():
    cfg = lr_only_config()
    b = SnapshotBuilder(cfg)
    for i, zone in enumerate(["a", "a", "b"]):
        b.add_node(f"n{i}", {"cpu": 64000, "memory": 1 << 40},
                   labels={"zone": zone})
    # zone a already has 2 matching pods, zone b has 0
    b.add_running_pod("n0", {"cpu": 1}, labels={"app": "web"})
    b.add_running_pod("n1", {"cpu": 1}, labels={"app": "web"})
    b.add_pod("p", {"cpu": 100, "memory": 1 << 20}, labels={"app": "web"},
              topology_spread=[TopologySpreadConstraint(
                  "zone", 1, "DoNotSchedule",
                  selector=(MatchExpression("app", "In", ("web",)),))])
    snap, meta = b.build()
    res = Oracle(snap, cfg).solve()
    # count(a)+1-min(0) = 3 > 1  -> zones a infeasible; must land in b
    assert meta.node_names[res.assignment[0]] == "n2"


def test_interpod_required_affinity_and_anti():
    cfg = lr_only_config()
    b = SnapshotBuilder(cfg)
    b.add_node("a0", {"cpu": 64000, "memory": 1 << 40}, labels={"zone": "a"})
    b.add_node("b0", {"cpu": 1000, "memory": 4 << 30}, labels={"zone": "b"})
    b.add_running_pod("b0", {"cpu": 1}, labels={"app": "db"})
    b.add_pod("with-db", {"cpu": 100, "memory": 1 << 20}, pod_affinity=[
        PodAffinityTerm("zone", (MatchExpression("app", "In", ("db",)),))
    ])
    b.add_pod("not-with-db", {"cpu": 100, "memory": 1 << 20}, pod_affinity=[
        PodAffinityTerm("zone", (MatchExpression("app", "In", ("db",)),), anti=True)
    ])
    snap, meta = b.build()
    res = Oracle(snap, cfg).solve()
    assert meta.node_names[res.assignment[0]] == "b0"
    assert meta.node_names[res.assignment[1]] == "a0"


def test_interpod_sees_previously_assigned_pending_pods():
    # Sequential semantics: the first pending pod lands somewhere; the
    # second pod's required affinity must see it (SURVEY.md §7 hard part 1).
    cfg = lr_only_config()
    b = SnapshotBuilder(cfg)
    b.add_node("a0", {"cpu": 64000, "memory": 1 << 40}, labels={"zone": "a"})
    b.add_node("b0", {"cpu": 1000, "memory": 4 << 30}, labels={"zone": "b"})
    b.add_pod("leader", {"cpu": 100, "memory": 1 << 20},
              labels={"app": "lead"}, priority=100)
    b.add_pod("follower", {"cpu": 100, "memory": 1 << 20}, priority=1,
              pod_affinity=[
                  PodAffinityTerm("zone", (MatchExpression("app", "In", ("lead",)),))
              ])
    snap, meta = b.build()
    res = Oracle(snap, cfg).solve()
    lead_node = res.assignment[0]
    # follower must be in the same zone as wherever leader went
    zones = snap.nodes.domain[:, 0]
    assert zones[res.assignment[1]] == zones[lead_node]


def _taints_ok_loop(ora, p):
    """The per-node loop Oracle.taints_ok replaced (the reference)."""
    tids = np.asarray(ora.nodes.taint_ids)
    effect = np.asarray(ora.snap.taint_effect)
    tol = np.asarray(ora.pods.tolerated)[p]
    ok = np.ones(tids.shape[0], bool)
    for n in range(tids.shape[0]):
        for t in tids[n]:
            if t >= 0 and effect[t] in (EFFECT_NO_SCHEDULE,
                                        EFFECT_NO_EXECUTE) and not tol[t]:
                ok[n] = False
    return ok


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_taints_ok_matches_loop_reference(seed):
    from tpusched.synth import make_cluster

    snap, _ = make_cluster(np.random.default_rng(seed), 40, 30,
                           taint_frac=0.5, toleration_frac=0.4)
    ora = Oracle(snap, EngineConfig())
    for p in range(snap.pods.valid.shape[0]):
        np.testing.assert_array_equal(ora.taints_ok(p),
                                      _taints_ok_loop(ora, p))
