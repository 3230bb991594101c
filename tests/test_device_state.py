"""Device-resident cluster state (tpusched/device_state.py): delta
scatter updates must equal a fresh SnapshotBuilder build + upload —
array-identical for same-vocabulary churn (including add/remove row
reorders), solve-identical when the vocabulary grows mid-session — and
steady-state cycles must ship O(churn) bytes, never the full snapshot
(the transfer-counter acceptance hook)."""

import jax
import numpy as np
import pytest

from tpusched import Engine, EngineConfig
from tpusched.config import Buckets
from tpusched.device_state import DeviceSnapshot
from tpusched.snapshot import (
    MatchExpression,
    NodeSelectorTerm,
    PodAffinityTerm,
    SnapshotBuilder,
    Toleration,
    TopologySpreadConstraint,
    _snapshot_from_arrays,
)


def _records(n_pods=14, n_nodes=6, n_running=5, seed=0):
    """A constraint-rich cluster touching every row encoder: labels,
    selectors, affinity, spread, tolerations, gangs, PDBs."""
    rng = np.random.default_rng(seed)
    nodes = [
        dict(name=f"n{i:02d}",
             allocatable={"cpu": 8000.0, "memory": float(32 << 30)},
             labels={"zone": "abc"[i % 3], "disktype": "ssd",
                     "kubernetes.io/hostname": f"n{i:02d}"},
             taints=([("dedicated", "batch", "NoSchedule")]
                     if i == 0 else []))
        for i in range(n_nodes)
    ]
    pods = []
    for i in range(n_pods):
        kw = dict(
            name=f"p{i:02d}",
            requests={"cpu": float(rng.integers(100, 600)),
                      "memory": float(rng.integers(1 << 28, 1 << 30))},
            priority=float(rng.integers(0, 100)),
            slo_target=float(rng.choice([0.0, 0.9])),
            observed_avail=float(rng.uniform(0.6, 1.0)),
            labels={"app": ["web", "db", "cache"][i % 3]},
        )
        if i % 4 == 0:
            kw["node_selector"] = {"disktype": "ssd"}
        if i % 5 == 0:
            kw["tolerations"] = [Toleration("dedicated", "Equal", "batch",
                                            "NoSchedule")]
        if i % 6 == 0:
            kw["topology_spread"] = [TopologySpreadConstraint(
                topology_key="zone", max_skew=2,
                when_unsatisfiable="ScheduleAnyway",
                selector=(MatchExpression("app", "In", ("web",)),),
            )]
        if i % 7 == 0:
            kw["pod_affinity"] = [PodAffinityTerm(
                topology_key="zone",
                selector=(MatchExpression("app", "In", ("db",)),),
                anti=True, required=False, weight=2.0,
            )]
        if i >= n_pods - 4:
            kw["pod_group"] = "gang-a"
            kw["pod_group_min_member"] = 2
        pods.append(kw)
    running = [
        dict(name=f"r{i:02d}", node=f"n{i % n_nodes:02d}",
             requests={"cpu": 400.0, "memory": float(1 << 29)},
             priority=float(i), slack=0.1 * i,
             labels={"app": "db" if i % 2 else "web"},
             **({"pdb_group": "pdb-a", "pdb_disruptions_allowed": 1}
                if i < 2 else {}))
        for i in range(n_running)
    ]
    return nodes, pods, running


def _fresh_build(nodes, pods, running, buckets):
    """The reference: a from-scratch name-sorted build at the SAME
    buckets the device state settled on."""
    b = SnapshotBuilder(EngineConfig(), buckets)
    for r in sorted(nodes, key=lambda r: r["name"]):
        b.add_node(**r)
    for r in sorted(pods, key=lambda r: r["name"]):
        b.add_pod(**r)
    for r in sorted(running, key=lambda r: r["name"]):
        b.add_running_pod(**{k: v for k, v in r.items() if k != "name"})
    return b.build()


def _assert_trees_equal(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        assert g.shape == w.shape and g.dtype == w.dtype
        eq = (g == w) | (
            np.isnan(g) & np.isnan(w)
            if np.issubdtype(g.dtype, np.floating) else False
        )
        np.testing.assert_equal(np.asarray(eq).all(), True)


@pytest.fixture
def loaded():
    nodes, pods, running = _records()
    ds = DeviceSnapshot(EngineConfig())
    ds.full_load(nodes, pods, running)
    return ds, nodes, pods, running


def test_value_churn_scatter_equals_rebuild(loaded):
    """Pure value churn (the steady-state serving cycle): scattered
    arrays are BYTE-identical to a fresh build of the same records."""
    ds, nodes, pods, running = loaded
    pods[3]["priority"] = 777.0
    pods[8]["observed_avail"] = 0.42
    nodes[2]["allocatable"] = {"cpu": 5000.0, "memory": float(24 << 30)}
    running[1]["slack"] = 0.9
    stats = ds.apply(upsert_pods=[pods[3], pods[8]],
                     upsert_nodes=[nodes[2]],
                     upsert_running=[running[1]])
    assert stats.path == "delta" and not stats.reordered
    snap, meta = _fresh_build(nodes, pods, running, ds.meta.buckets)
    _assert_trees_equal(ds.snap, snap)
    assert ds.meta.pod_names == meta.pod_names
    assert ds.meta.node_names == meta.node_names


def test_add_remove_reorder_equals_rebuild(loaded):
    """Insertions/removals shift the name-sorted row order: the
    permutation-gather + scatter path must still match a fresh build
    exactly (same vocabulary). Names chosen to land MID-order so rows
    genuinely move, including the running->node index remap."""
    ds, nodes, pods, running = loaded
    pods = [p for p in pods if p["name"] != "p04"]
    pods.append(dict(name="p03a", requests={"cpu": 150.0},
                     labels={"app": "web"}, observed_avail=1.0))
    running = [r for r in running if r["name"] != "r01"]
    running.append(dict(name="r00a", node="n03",
                        requests={"cpu": 100.0}, labels={"app": "db"},
                        slack=0.2))
    # Labels reuse EXISTING (key,value) pairs only: a never-seen value
    # would append to the intern vocabulary, where ids (legitimately)
    # diverge from a fresh build's and only solve-parity holds (covered
    # by test_vocab_append_stays_delta_and_solves_identically).
    nodes.append(dict(name="n01a",
                      allocatable={"cpu": 6000.0,
                                   "memory": float(16 << 30)},
                      labels={"zone": "b", "disktype": "ssd"}))
    stats = ds.apply(
        upsert_pods=[pods[-1]], remove_pods=["p04"],
        upsert_running=[running[-1]], remove_running=["r01"],
        upsert_nodes=[nodes[-1]],
    )
    assert stats.path == "delta" and stats.reordered
    snap, meta = _fresh_build(nodes, pods, running, ds.meta.buckets)
    _assert_trees_equal(ds.snap, snap)
    assert ds.meta.node_names == meta.node_names
    # node used rows re-summed, and running rows point at the REMAPPED
    # node indices (n01a inserted mid-order shifts n02..).
    run_nodes = np.asarray(ds.snap.running.node_idx)[:len(running)]
    names = ds.meta.node_names
    by_name = {r["name"]: r for r in running}
    for m, rname in enumerate(sorted(by_name)):
        assert names[run_nodes[m]] == by_name[rname]["node"]


def test_vocab_append_stays_delta_and_solves_identically(loaded):
    """New label values / selector atoms within bucket capacity append
    to the interner: the apply stays on the delta path, and although
    intern ids may differ from a fresh build's, solve results are
    identical (ids are opaque equality tokens)."""
    nodes, pods, running = _records()
    floors = Buckets.fit(32, 16, 16, atoms=64, atom_values=8, terms=4,
                         term_atoms=4, signatures=16, pod_labels=8,
                         node_labels=16, spread_constraints=4,
                         affinity_terms=4, pref_terms=4)
    ds = DeviceSnapshot(EngineConfig(), floors)
    ds.full_load(nodes, pods, running)
    pods[1]["labels"] = {"app": "brandnew-value"}
    pods[2]["node_selector"] = {"zone": "c"}   # new atom, existing key
    stats = ds.apply(upsert_pods=[pods[1], pods[2]])
    assert stats.path == "delta", stats.reason
    snap, _ = _fresh_build(nodes, pods, running, ds.meta.buckets)
    # One mode suffices: the solver is a pure function of the arrays,
    # so any mode certifies array-equivalence (parity's lax.scan
    # compile would only re-prove the same thing 10x slower).
    eng = Engine(EngineConfig(mode="fast"))
    a = eng.solve(ds.snap)
    b = eng.solve(snap)
    np.testing.assert_array_equal(a.assignment, b.assignment)
    np.testing.assert_array_equal(np.asarray(a.chosen_score),
                                  np.asarray(b.chosen_score))
    eng.close()


def test_growth_falls_back_to_rebuild(loaded):
    """Out-of-model growth (new taint: a [P, VT] column for every pod)
    rebuilds + re-uploads, and the result still equals a fresh build."""
    ds, nodes, pods, running = loaded
    nodes[3]["taints"] = [("gpu", "true", "NoSchedule")]
    stats = ds.apply(upsert_nodes=[nodes[3]])
    assert stats.path == "rebuild" and stats.reason == "new_taint"
    snap, _ = _fresh_build(nodes, pods, running, ds.meta.buckets)
    _assert_trees_equal(ds.snap, snap)
    # Row-bucket overflow rebuilds too (and grows the bucket).
    many = [dict(name=f"q{i:03d}", requests={"cpu": 10.0},
                 observed_avail=1.0)
            for i in range(ds.meta.buckets.pods + 1)]
    stats = ds.apply(upsert_pods=many)
    assert stats.path == "rebuild" and stats.reason == "row_bucket"
    pods2 = pods + many
    snap, _ = _fresh_build(nodes, pods2, running, ds.meta.buckets)
    _assert_trees_equal(ds.snap, snap)


def test_steady_state_ships_no_full_snapshot(loaded):
    """THE acceptance hook: after the first upload, value-churn cycles
    never re-upload the snapshot — full_uploads stays 1 and per-cycle
    H2D bytes stay orders of magnitude under one full upload."""
    ds, nodes, pods, running = loaded
    full = ds.full_bytes
    assert ds.full_uploads == 1
    rng = np.random.default_rng(1)
    for cycle in range(20):
        i = int(rng.integers(len(pods)))
        pods[i]["observed_avail"] = float(rng.uniform(0.5, 1.0))
        stats = ds.apply(upsert_pods=[pods[i]])
        assert stats.path == "delta"
        assert stats.h2d_bytes < full / 10, (
            f"cycle {cycle}: shipped {stats.h2d_bytes} of {full}"
        )
    assert ds.full_uploads == 1 and ds.delta_updates == 20
    assert ds.rebuilds == 0


def test_group_and_pdb_membership_updates(loaded):
    """Gang min-member and PDB allowed-disruption scalars re-derive
    from CURRENT members (max), including on removal."""
    ds, nodes, pods, running = loaded
    # Raise one gang member's min_member: slot takes the new max.
    gang_pods = [p for p in pods if p.get("pod_group") == "gang-a"]
    gang_pods[0]["pod_group_min_member"] = 3
    ds.apply(upsert_pods=[gang_pods[0]])
    gi = ds._state.group_idx["gang-a"]
    assert int(np.asarray(ds.snap.group_min_member)[gi]) == 3
    # Remove that member: max over the remaining members (2).
    pods = [p for p in pods if p["name"] != gang_pods[0]["name"]]
    ds.apply(remove_pods=[gang_pods[0]["name"]])
    assert int(np.asarray(ds.snap.group_min_member)[gi]) == 2
    # PDB: removing one covered running pod keeps the budget's max.
    pi = ds._state.pdb_idx[("default", "pdb-a")]
    assert float(np.asarray(ds.snap.pdb_allowed)[pi]) == 1.0
    running = [r for r in running if r["name"] != "r00"]
    ds.apply(remove_running=["r00"])
    assert float(np.asarray(ds.snap.pdb_allowed)[pi]) == 1.0
    snap, _ = _fresh_build(nodes, pods, running, ds.meta.buckets)
    for mode in ("fast",):
        eng = Engine(EngineConfig(mode=mode))
        np.testing.assert_array_equal(
            eng.solve(ds.snap).assignment, eng.solve(snap).assignment
        )
        eng.close()


@pytest.mark.parametrize("mode", [
    "fast",
    # The parity lax.scan pays two full compiles here for the same
    # masking invariant; keep it in the unfiltered suite only.
    pytest.param("parity", marks=pytest.mark.slow),
])
def test_bucket_padding_invariance(mode):
    """The session keeps its (possibly larger) buckets across churn
    while a fresh decode refits them — results must not depend on
    padding width (the invariant that makes that safe)."""
    nodes, pods, running = _records(n_pods=10, n_nodes=4, n_running=3)
    small, _ = _fresh_build(nodes, pods, running, None)
    big, _ = _fresh_build(nodes, pods, running,
                          Buckets.fit(64, 32, 32))
    eng = Engine(EngineConfig(mode=mode))
    a, b = eng.solve(small), eng.solve(big)
    P = len(pods)
    np.testing.assert_array_equal(a.assignment[:P], b.assignment[:P])
    np.testing.assert_array_equal(
        np.asarray(a.chosen_score)[:P], np.asarray(b.chosen_score)[:P]
    )
    eng.close()


def test_running_pod_missing_node_raises(loaded):
    ds, nodes, pods, running = loaded
    with pytest.raises(ValueError, match="missing node"):
        ds.apply(upsert_running=[dict(name="rX", node="ghost",
                                      requests={"cpu": 1.0})])
    # State untouched: a rebuild-equality still holds.
    snap, _ = _fresh_build(nodes, pods, running, ds.meta.buckets)
    _assert_trees_equal(ds.snap, snap)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reorder_equals_the_general_perm(seed):
    """The churn-driven order merge gives the order a sort gives and the
    permutation and vacated rows `_perm` derives from the two orders."""
    rng = np.random.default_rng(seed)
    old = sorted({f"k{int(v):05d}" for v in rng.integers(0, 99999, 300)})
    gone = set(rng.choice(old, size=40, replace=False).tolist())
    fresh = {f"k{int(v):05d}x" for v in rng.integers(0, 99999, 25)}
    removed = sorted(old.index(nm) for nm in gone)
    new, perm, pads, runs = DeviceSnapshot._reorder(
        old, removed, sorted(fresh), 512)
    assert new == sorted((set(old) - gone) | fresh)
    want_perm, want_pads = DeviceSnapshot._perm(old, new, 512)
    np.testing.assert_array_equal(perm, want_perm)
    assert perm.dtype == want_perm.dtype and pads == want_pads
    # The runs hold every surviving row once, old row -> new row.
    for o, n, length in runs:
        assert old[o:o + length] == new[n:n + length]
    assert sum(length for *_, length in runs) == len(old) - len(gone)
    assert DeviceSnapshot._reorder(old, [], [], 512) == (old, None, [], [])


def _churn_cluster():
    """_records' cluster plus a second gang and a second PDB, so churned
    records can move between slots that never empty."""
    nodes, pods, running = _records()
    pods.append(dict(name="p99", requests={"cpu": 100.0},
                     labels={"app": "web"}, pod_group="gang-b",
                     pod_group_min_member=1))
    running.append(dict(name="r99", node="n00", requests={"cpu": 200.0},
                        labels={"app": "web"}, pdb_group="pdb-b",
                        pdb_disruptions_allowed=2))
    return ({r["name"]: r for r in nodes}, {r["name"]: r for r in pods},
            {r["name"]: r for r in running})


def _random_delta(rng, k, nodes, pods, running, base):
    """One delta over the record dicts (mutated to the post-apply state).
    Churned records reuse vocabulary the base cluster interned first, so
    a fresh build must equal the delta-applied arrays."""
    delta = dict(upsert_nodes=[], remove_nodes=[], upsert_pods=[],
                 remove_pods=[], upsert_running=[], remove_running=[])
    added_nodes = [n for n in nodes if n not in base[0]]
    if k == 1 or (k > 3 and rng.random() < 0.3):
        nm = f"n{int(rng.integers(0, 6)):02d}x{k}"
        nodes[nm] = dict(name=nm, allocatable={
            "cpu": float(rng.integers(4, 16) * 1000),
            "memory": float(16 << 30)},
            labels={"zone": "a", "disktype": "ssd"})
        delta["upsert_nodes"].append(nodes[nm])
    elif added_nodes and (k == 4 or rng.random() < 0.3):
        gone = added_nodes[int(rng.integers(len(added_nodes)))]
        del nodes[gone]
        delta["remove_nodes"].append(gone)
        for r in [r for r in running.values() if r["node"] == gone]:
            if rng.random() < 0.5 and r["name"] not in base[2]:
                del running[r["name"]]
                delta["remove_running"].append(r["name"])
            else:
                running[r["name"]] = dict(r, node="n01")
                delta["upsert_running"].append(running[r["name"]])
    base_node = f"n{int(rng.integers(0, 6)):02d}"
    nodes[base_node] = dict(nodes[base_node], allocatable={
        "cpu": float(rng.integers(6, 12) * 1000), "memory": float(32 << 30)})
    delta["upsert_nodes"].append(nodes[base_node])
    live = sorted(nodes)
    churned_r = ({r["name"] for r in delta["upsert_running"]}
                 | set(delta["remove_running"]))
    for j in range(int(rng.integers(1, 4))):     # bind
        nm = f"r{int(rng.integers(0, 5)):02d}x{k}{j}"
        pdb = rng.choice(["", "pdb-a", "pdb-b"])
        running[nm] = dict(
            name=nm, node=live[int(rng.integers(len(live)))],
            requests={"cpu": float(rng.integers(1, 9) * 50)},
            labels={"app": str(rng.choice(["web", "db", "cache"]))},
            **({"pdb_group": str(pdb),
                "pdb_disruptions_allowed": int(rng.integers(0, 3))}
               if pdb else {}))
        delta["upsert_running"].append(running[nm])
        churned_r.add(nm)
    for nm in sorted(running):                   # complete / move
        if nm in churned_r:
            continue
        u = rng.random()
        if u < 0.15 and nm not in base[2]:
            del running[nm]
            delta["remove_running"].append(nm)
        elif u < 0.3:
            running[nm] = dict(running[nm],
                               node=live[int(rng.integers(len(live)))])
            delta["upsert_running"].append(running[nm])
    for j in range(int(rng.integers(1, 4))):     # arrive
        nm = f"p{int(rng.integers(2, 14)):02d}x{k}{j}"
        g = rng.choice(["", "gang-a", "gang-b"])
        pods[nm] = dict(
            name=nm, requests={"cpu": float(rng.integers(1, 9) * 50)},
            labels={"app": str(rng.choice(["web", "db", "cache"]))},
            priority=float(rng.integers(0, 100)), observed_avail=1.0,
            **({"pod_group": str(g),
                "pod_group_min_member": int(rng.integers(1, 4))}
               if g else {}))
        delta["upsert_pods"].append(pods[nm])
    arrived = {p["name"] for p in delta["upsert_pods"]}
    for nm in sorted(pods):                      # bind away / update
        if nm in arrived:
            continue
        u = rng.random()
        if u < 0.2 and nm not in base[1]:
            del pods[nm]
            delta["remove_pods"].append(nm)
        elif u < 0.35 and nm not in base[1]:
            g = rng.choice(["", "gang-a", "gang-b"])
            rec = {k2: v for k2, v in pods[nm].items()
                   if k2 not in ("pod_group", "pod_group_min_member")}
            if g:
                rec.update(pod_group=str(g), pod_group_min_member=2)
            pods[nm] = rec
            delta["upsert_pods"].append(rec)
        elif u < 0.5:
            pods[nm] = dict(pods[nm], priority=float(rng.integers(0, 100)))
            delta["upsert_pods"].append(pods[nm])
    return delta


DERIVED = ("_run_node_name", "_pod_group_name", "_run_pdb_key",
           "_group_members", "_pdb_members")


def _mirror(ds):
    st = ds._state
    return _snapshot_from_arrays(st.nodes_np, st.pods_np, st.run_np,
                                 st.tables)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_churn_upkeep_equals_rebuild(seed):
    """Random deltas (binds, completions, moves between nodes, arrivals,
    removals, updates, node inserts and removals, gangs and PDBs entered
    and left): after each apply the orders, maps, mirror and device
    arrays the delta path keeps up equal a from-scratch load of the same
    records."""
    rng = np.random.default_rng(seed)
    nodes, pods, running = _churn_cluster()
    base = (set(nodes), set(pods), set(running))
    ds = DeviceSnapshot(EngineConfig(), Buckets.fit(64, 32, 64))
    ds.full_load(nodes.values(), pods.values(), running.values())
    node_moves = 0
    for k in range(1, 9):
        delta = _random_delta(rng, k, nodes, pods, running, base)
        stats = ds.apply(**delta)
        assert stats.path == "delta", stats.reason
        assert 0 < stats.order_visited
        node_moves += bool(delta["remove_nodes"]
                           or len(delta["upsert_nodes"]) > 1)
        assert ds._node_order == sorted(nodes)
        assert ds._pod_order == sorted(pods)
        assert ds._run_order == sorted(running)
        ref = DeviceSnapshot(EngineConfig(), ds.meta.buckets)
        ref.full_load(nodes.values(), pods.values(), running.values())
        for attr in DERIVED:
            assert getattr(ds, attr) == getattr(ref, attr), (k, attr)
        assert np.array_equal(ds._run_counted, ref._run_counted), k
        assert ds._state.node_index == ref._state.node_index
        assert ds.meta == ref.meta
        _assert_trees_equal(_mirror(ds), _mirror(ref))
        _assert_trees_equal(ds.snap, ref.snap)
    assert node_moves >= 2 and ds.rebuilds == 0


def test_order_upkeep_scales_with_churn_not_cluster():
    """At ~20k running pods a 50-record delta examines O(churn × log n)
    names: a whole-cluster pass in the upkeep would count ~20k."""
    n_run = 20_000
    nodes = [dict(name=f"n{i:03d}", allocatable={"cpu": 1e6})
             for i in range(64)]
    pods = [dict(name=f"p{i:03d}", requests={"cpu": 10.0})
            for i in range(16)]
    running = [dict(name=f"r{i:05d}", node=f"n{i % 64:03d}",
                    requests={"cpu": 10.0}) for i in range(n_run)]
    ds = DeviceSnapshot(EngineConfig())
    ds.full_load(nodes, pods, running)
    stats = ds.apply(
        remove_running=[f"r{i:05d}" for i in range(0, n_run, 1000)],
        upsert_running=[dict(name=f"r{i:05d}x", node="n005",
                             requests={"cpu": 20.0})
                        for i in range(500, n_run, 1000)],
        upsert_pods=[dict(pods[i], requests={"cpu": 11.0})
                     for i in range(10)],
    )
    assert stats.path == "delta" and stats.reordered
    assert stats.churn_records == 50
    assert stats.churn_records <= stats.order_visited
    assert stats.order_visited < 2 * stats.churn_records * np.log2(n_run)
    assert ds._run_order == sorted(ds._running)
    ref = DeviceSnapshot(EngineConfig(), ds.meta.buckets)
    ref.full_load(ds._nodes.values(), ds._pods.values(),
                  ds._running.values())
    i = ds._state.node_index["n005"]
    assert np.array_equal(ds._state.nodes_np.used[i],
                          ref._state.nodes_np.used[i])
    # The re-sum folds every member of each dirtied node, not the churn.
    dirty = {f"n{i % 64:03d}" for i in range(0, n_run, 1000)} | {"n005"}
    assert stats.used_nodes == len(dirty)
    assert stats.used_rows == sum(r["node"] in dirty
                                  for r in ds._running.values())


def _sum_order_cluster(rng):
    """A few nodes with their own `used`, each holding many running pods
    whose float32 sums depend on the order of the additions: fractional
    cpu (0.1, 1/3, 1e-3) beside large memory values. About a tenth of
    the members do not count into `used`."""
    nodes = {f"n{i:02d}": dict(
        name=f"n{i:02d}", allocatable={"cpu": 1e6, "memory": 1e13},
        labels={"zone": "ab"[i % 2]},
        used={"cpu": float(rng.choice([0.0, 0.7, 1 / 7])),
              "memory": float(rng.integers(1, 1 << 20))})
        for i in range(0, 12, 2)}
    running = {}
    for j in range(120):
        nm = f"r{j:03d}"
        running[nm] = _sum_order_member(rng, nm, sorted(nodes)[j % 3])
    pods = {f"p{j}": dict(name=f"p{j}", requests={"cpu": 1.0})
            for j in range(4)}
    return nodes, pods, running


def _sum_order_member(rng, name, node):
    return dict(name=name, node=node,
                requests={"cpu": float(rng.choice([0.1, 1 / 3, 1e-3])),
                          "memory": float((3 << 30) + rng.integers(1, 999)
                                          * 7.3)},
                count_into_used=bool(rng.random() < 0.9))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_used_resum_is_bit_identical_to_rebuild(seed):
    """The churned nodes' `used` rows, re-summed on the delta path, are
    bit-identical to a from-scratch load of the same records after
    binds, completions, moves between nodes, count_into_used flips,
    node inserts (which remap every running row's node) and node
    removals, with removals that leave pad rows — on requests whose
    float32 sum differs when the additions are reordered."""
    rng = np.random.default_rng(seed)
    nodes, pods, running = _sum_order_cluster(rng)
    ds = DeviceSnapshot(EngineConfig(), Buckets.fit(8, 16, 256))
    ds.full_load(nodes.values(), pods.values(), running.values())
    # The data tells the orders apart: a node's members summed in
    # reverse order give another float32 row than the build's.
    st = ds._state
    rows = np.flatnonzero(ds._run_counted & (st.run_np.node_idx == 0))
    fwd = np.zeros(len(ds.config.resources), np.float32)
    rev = fwd.copy()
    for k in rows:
        fwd += st.run_np.requests[k]
    for k in rows[::-1]:
        rev += st.run_np.requests[k]
    assert not np.array_equal(fwd, rev)
    pads = 0
    for k in range(8):
        delta = dict(upsert_nodes=[], remove_nodes=[], upsert_running=[],
                     remove_running=[])
        live = sorted(nodes)
        if k % 3 == 0:                       # insert: node rows shift
            nm = f"n{int(rng.integers(0, 10)):02d}x{k}"
            nodes[nm] = dict(name=nm, allocatable={"cpu": 1e6},
                             labels={"zone": "a"}, used={"cpu": 0.3})
            delta["upsert_nodes"].append(nodes[nm])
            live = sorted(nodes)
        elif k % 3 == 1:                     # remove an inserted node
            extra = [n for n in nodes if "x" in n]
            if extra:
                gone = extra[0]
                del nodes[gone]
                delta["remove_nodes"].append(gone)
                live = sorted(nodes)
                for r in [r for r in running.values()
                          if r["node"] == gone]:
                    running[r["name"]] = dict(r, node=live[0])
                    delta["upsert_running"].append(running[r["name"]])
        moved = {r["name"] for r in delta["upsert_running"]}
        for nm in sorted(running):
            if nm in moved:
                continue
            u = rng.random()
            if u < 0.12:                     # complete
                del running[nm]
                delta["remove_running"].append(nm)
            elif u < 0.22:                   # move
                running[nm] = dict(
                    running[nm], node=live[int(rng.integers(len(live)))])
                delta["upsert_running"].append(running[nm])
            elif u < 0.27:                   # flip count_into_used
                running[nm] = dict(
                    running[nm],
                    count_into_used=not running[nm]["count_into_used"])
                delta["upsert_running"].append(running[nm])
        for j in range(int(rng.integers(0, 12))):   # bind
            nm = f"r{int(rng.integers(0, 130)):03d}x{k}{j}"
            running[nm] = _sum_order_member(
                rng, nm, live[int(rng.integers(len(live)))])
            delta["upsert_running"].append(running[nm])
        n_before = len(ds._run_order)
        stats = ds.apply(**delta)
        assert stats.path == "delta", stats.reason
        pads += max(0, n_before - len(running))
        ref = DeviceSnapshot(EngineConfig(), ds.meta.buckets)
        ref.full_load(nodes.values(), pods.values(), running.values())
        assert ds._state.nodes_np.used.dtype == np.float32
        assert np.array_equal(ds._state.nodes_np.used,
                              ref._state.nodes_np.used), k
        assert np.array_equal(np.asarray(ds.snap.nodes.used),
                              np.asarray(ref.snap.nodes.used)), k
        assert np.array_equal(ds._run_counted, ref._run_counted), k
    assert pads > 0 and ds.rebuilds == 0


def test_used_counters_count_the_folded_members():
    """ApplyStats.used_nodes counts the nodes whose `used` row was
    re-summed, and used_rows the counted running rows folded into
    them: every counted member of a dirtied node, not just the churn."""
    nodes = [dict(name=f"n{i}", allocatable={"cpu": 1e4}) for i in range(4)]
    running = [dict(name=f"r{j:02d}", node=f"n{j % 4}",
                    requests={"cpu": 10.0}, count_into_used=j % 5 != 0)
               for j in range(20)]
    ds = DeviceSnapshot(EngineConfig())
    ds.full_load(nodes, [dict(name="p0", requests={"cpu": 1.0})], running)
    # r00 (n0) completes, r01 moves n1 -> n2, r40 binds to n0 uncounted.
    stats = ds.apply(
        remove_running=["r00"],
        upsert_running=[dict(running[1], node="n2"),
                        dict(name="r40", node="n0", requests={"cpu": 1.0},
                             count_into_used=False)])
    assert stats.path == "delta"
    dirty = {"n0", "n1", "n2"}
    assert stats.used_nodes == len(dirty)
    assert stats.used_rows == sum(
        r["node"] in dirty and r.get("count_into_used", True)
        for r in ds._running.values())
    assert stats.used_rows == 4 + 3 + 5   # n0, n1 less r01, n2 with r01
    # A pod-only delta dirties no node: nothing is folded.
    stats = ds.apply(upsert_pods=[dict(name="p1", requests={"cpu": 2.0})])
    assert (stats.used_nodes, stats.used_rows) == (0, 0)
