"""Native wire decoder (native/fastdecode.cc) parity: EXACT equality —
every array, every meta field — with the Python decode path, across the
full feature surface. Any mismatch is a bug in the C++."""

import dataclasses

import numpy as np
import pytest

from tpusched import Engine, EngineConfig
from tpusched.config import Buckets
from tpusched.host import FakeApiServer, HostScheduler, build_synthetic_cluster
from tpusched.rpc.codec import snapshot_from_proto, snapshot_to_proto
from tpusched import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native decoder not built"
)


def _assert_same(snap_py, meta_py, snap_nat, meta_nat):
    import jax

    leaves_py = jax.tree.leaves(snap_py)
    leaves_nat = jax.tree.leaves(snap_nat)
    assert len(leaves_py) == len(leaves_nat)
    paths = jax.tree_util.tree_flatten_with_path(snap_py)[0]
    for (path, a), b in zip(paths, leaves_nat):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape, (path, a.shape, b.shape)
        assert a.dtype == b.dtype, (path, a.dtype, b.dtype)
        if a.dtype.kind == "f":
            np.testing.assert_array_equal(
                np.nan_to_num(a, nan=-777.0), np.nan_to_num(b, nan=-777.0),
                err_msg=str(path),
            )
        else:
            np.testing.assert_array_equal(a, b, err_msg=str(path))
    assert meta_py.node_names == meta_nat.node_names
    assert meta_py.pod_names == meta_nat.pod_names
    assert meta_py.running_names == meta_nat.running_names
    assert meta_py.group_names == meta_nat.group_names
    assert (meta_py.n_nodes, meta_py.n_pods, meta_py.n_running) == (
        meta_nat.n_nodes, meta_nat.n_pods, meta_nat.n_running
    )
    assert dataclasses.asdict(meta_py.buckets) == dataclasses.asdict(
        meta_nat.buckets
    )


def _roundtrip(msg, config=None, buckets=None):
    config = config or EngineConfig()
    snap_py, meta_py = snapshot_from_proto(msg, config, buckets)
    snap_nat, meta_nat = native.decode_snapshot_bytes(
        msg.SerializeToString(), config, buckets
    )
    _assert_same(snap_py, meta_py, snap_nat, meta_nat)
    return snap_nat, meta_nat


def test_empty_snapshot():
    from tpusched.rpc import tpusched_pb2 as pb

    _roundtrip(pb.ClusterSnapshot())


def test_host_cluster_roundtrip():
    rng = np.random.default_rng(0)
    api = FakeApiServer()
    build_synthetic_cluster(api, rng, 40, 8)
    host = HostScheduler(api, EngineConfig())
    try:
        msg = host._wire_snapshot(api.pending_pods())
        _roundtrip(msg)
    finally:
        host.close()


def _rich_records(rng, n_pods=24, n_nodes=8, n_running=10):
    """Wire records exercising every proto feature: labels, taints,
    selectors, affinity (all 6 operators), spread, gangs, PDBs,
    namespaces incl. '*', tolerations, numeric labels, unnamed running
    pods are NOT included here (delta-unsafe but decode-legal — covered
    separately)."""
    from tpusched.snapshot import (
        MatchExpression, NodeSelectorTerm, PodAffinityTerm, PreferredTerm,
        Toleration, TopologySpreadConstraint,
    )

    zones = ["a", "b", "c"]
    nodes = []
    for i in range(n_nodes):
        labels = {
            "topology.kubernetes.io/zone": zones[i % 3],
            "tier": str(rng.integers(0, 4)),
            "disktype": "ssd" if rng.random() < 0.5 else "hdd",
        }
        if rng.random() < 0.2:
            del labels["topology.kubernetes.io/zone"]
        taints = []
        if rng.random() < 0.3:
            taints.append(("dedicated", "batch", "NoSchedule"))
        if rng.random() < 0.2:
            taints.append(("maint", "yes", "PreferNoSchedule"))
        nodes.append(dict(
            name=f"node-{i:02d}",
            allocatable={"cpu": float(rng.integers(4000, 16000)),
                         "memory": float(rng.integers(16 << 30, 64 << 30))},
            labels=labels, taints=taints,
            used={"cpu": float(rng.integers(0, 500))},
        ))
    apps = ["web", "db", "cache"]
    nss = ["default", "team-a", "team-b"]
    running = []
    for i in range(n_running):
        kw = {}
        if rng.random() < 0.4:
            kw["pod_affinity"] = [PodAffinityTerm(
                "topology.kubernetes.io/zone",
                (MatchExpression("app", "In", (apps[int(rng.integers(3))],)),),
                anti=True, required=True,
                namespaces=("*",) if rng.random() < 0.3 else (),
            )]
        if rng.random() < 0.5:
            kw["pdb_group"] = f"pdb-{int(rng.integers(3))}"
            kw["pdb_disruptions_allowed"] = int(rng.integers(0, 3))
        running.append(dict(
            name=f"run-{i:02d}", node=f"node-{int(rng.integers(n_nodes)):02d}",
            requests={"cpu": float(rng.integers(100, 1000))},
            priority=float(rng.integers(0, 100)),
            slack=float(rng.uniform(-0.2, 0.4)),
            labels={"app": apps[int(rng.integers(3))]},
            namespace=nss[int(rng.integers(3))],
            count_into_used=bool(rng.random() < 0.9),
            **kw,
        ))
    pods = []
    for i in range(n_pods):
        app = apps[int(rng.integers(3))]
        kw = {}
        if rng.random() < 0.4:
            kw["node_selector"] = {"disktype": "ssd"}
        if rng.random() < 0.4:
            kw["required_terms"] = [NodeSelectorTerm((
                MatchExpression("tier", "In", ("0", "1")),
                MatchExpression("tier", "NotIn", ("3",)),
            )), NodeSelectorTerm((
                MatchExpression("tier", "Gt", ("0",)),
                MatchExpression("tier", "Lt", ("3",)),
            ))]
        if rng.random() < 0.3:
            kw["preferred_terms"] = [PreferredTerm(
                float(rng.integers(1, 100)),
                NodeSelectorTerm((MatchExpression("disktype", "Exists", ()),)),
            )]
        if rng.random() < 0.3:
            kw["tolerations"] = [
                Toleration("dedicated", "Equal", "batch", "NoSchedule"),
                Toleration("", "Exists", "", ""),
            ][: int(rng.integers(1, 3))]
        if rng.random() < 0.4:
            kw["topology_spread"] = [TopologySpreadConstraint(
                "topology.kubernetes.io/zone", int(rng.integers(1, 3)),
                "DoNotSchedule" if rng.random() < 0.5 else "ScheduleAnyway",
                (MatchExpression("app", "In", (app,)),),
            )]
        if rng.random() < 0.4:
            ns_roll = rng.random()
            term_ns = (
                ("*",) if ns_roll < 0.2
                else tuple(rng.choice(nss, size=2, replace=False))
                if ns_roll < 0.5 else ()
            )
            kw["pod_affinity"] = [PodAffinityTerm(
                "topology.kubernetes.io/zone",
                (MatchExpression("app", "In", ("db",)),),
                anti=bool(rng.random() < 0.5),
                required=bool(rng.random() < 0.5),
                weight=float(rng.integers(1, 100)),
                namespaces=term_ns,
            )]
        if rng.random() < 0.3:
            kw["pod_group"] = f"gang-{int(rng.integers(4))}"
            kw["pod_group_min_member"] = 3
        pods.append(dict(
            name=f"pod-{i:03d}",
            requests={"cpu": float(rng.integers(100, 2000)),
                      "memory": float(rng.integers(1 << 28, 4 << 30))},
            priority=float(rng.integers(0, 1000)),
            slo_target=float(rng.choice([0.0, 0.9, 0.99])),
            observed_avail=float(rng.uniform(0.5, 1.0)),
            labels={"app": app},
            namespace=nss[int(rng.integers(3))],
            **kw,
        ))
    return nodes, pods, running


@pytest.mark.parametrize("seed", range(6))
def test_rich_feature_fuzz(seed):
    rng = np.random.default_rng(7000 + seed)
    nodes, pods, running = _rich_records(rng)
    msg = snapshot_to_proto(nodes, pods, running)
    snap, meta = _roundtrip(msg)
    # And the decoded snapshot actually schedules.
    res = Engine(EngineConfig(mode="fast")).solve(snap)
    assert (res.assignment[: meta.n_pods] >= -1).all()


def test_floor_buckets_respected():
    rng = np.random.default_rng(7100)
    nodes, pods, running = _rich_records(rng, n_pods=10, n_nodes=4)
    msg = snapshot_to_proto(nodes, pods, running)
    floors = Buckets.fit(64, 64, 32, atoms=64, signatures=32,
                         taint_vocab=16, topo_keys=8)
    _roundtrip(msg, buckets=floors)


def test_unsorted_wire_order():
    rng = np.random.default_rng(7200)
    nodes, pods, running = _rich_records(rng)
    msg = snapshot_to_proto(nodes[::-1], pods[::-1], running[::-1])
    _roundtrip(msg)


def test_unnamed_running_pods():
    nodes = [dict(name="n0", allocatable={"cpu": 4000.0})]
    running = [dict(name="", node="n0", requests={"cpu": 100.0}),
               dict(name="", node="n0", requests={"cpu": 200.0})]
    msg = snapshot_to_proto(nodes, [], running)
    _roundtrip(msg)


def test_separator_bytes_in_labels_do_not_collide():
    """Interner keys are length-prefixed: label components containing
    exotic bytes (e.g. 0x1f) must stay distinct pairs, exactly as the
    Python path's tuple-keyed dicts keep them."""
    nodes = [dict(name="n0", allocatable={"cpu": 4000.0},
                  labels={"a\x1fb": "c", "a": "b\x1fc"})]
    pods = [dict(name="p", requests={"cpu": 100.0}, observed_avail=1.0,
                 labels={"x\x1f": "y", "x": "\x1fy"})]
    msg = snapshot_to_proto(nodes, pods, [])
    _roundtrip(msg)


def test_gtlt_whitespace_nan_literals_match_python():
    """float() parity corners: surrounding whitespace and any-case nan
    are legal Gt/Lt literals; interior whitespace is not (both paths
    must reject it)."""
    from tpusched.snapshot import MatchExpression, NodeSelectorTerm

    def pod_with(value):
        return [dict(name="p", requests={"cpu": 100.0}, observed_avail=1.0,
                     required_terms=[NodeSelectorTerm(
                         (MatchExpression("tier", "Gt", (value,)),))])]

    nodes = [dict(name="n0", allocatable={"cpu": 4000.0},
                  labels={"tier": "5"})]
    for ok_value in (" 10 ", "nAn", "1_0"):
        _roundtrip(snapshot_to_proto(nodes, pod_with(ok_value), []))
    bad = snapshot_to_proto(nodes, pod_with("n an"), [])
    with pytest.raises(Exception):
        snapshot_from_proto(bad, EngineConfig())
    with pytest.raises(Exception):
        native.decode_snapshot_bytes(bad.SerializeToString(), EngineConfig())


def test_bad_toleration_behind_match_short_circuits():
    """Python's any(_tolerates(...)) never reaches a bad-operator
    toleration hiding behind an always-matching one — native must
    accept the same input; a bad op in FIRST position must fail on
    both paths."""
    from tpusched.snapshot import Toleration

    nodes = [dict(name="n0", allocatable={"cpu": 4000.0},
                  taints=[("k", "v", "NoSchedule")])]

    def pod(tols):
        return [dict(name="p", requests={"cpu": 100.0}, observed_avail=1.0,
                     tolerations=tols)]

    ok = snapshot_to_proto(
        nodes,
        pod([Toleration("", "Exists", "", ""),
             Toleration("x", "Bogus", "", "")]),
        [],
    )
    _roundtrip(ok)  # both paths accept; arrays equal
    bad = snapshot_to_proto(
        nodes, pod([Toleration("x", "Bogus", "", "")]), []
    )
    with pytest.raises(Exception):
        snapshot_from_proto(bad, EngineConfig())
    with pytest.raises(Exception):
        native.decode_snapshot_bytes(bad.SerializeToString(), EngineConfig())


def test_unknown_node_raises():
    nodes = [dict(name="n0", allocatable={"cpu": 4000.0})]
    running = [dict(name="r", node="ghost", requests={"cpu": 100.0})]
    msg = snapshot_to_proto(nodes, [], running)
    with pytest.raises(Exception):
        native.decode_snapshot_bytes(msg.SerializeToString(), EngineConfig())


def test_locale_independent_float_parse():
    """strtod honors LC_NUMERIC; the decoder must not (round-2 advisor
    finding, fixed round 5 with strtod_l over a cached C locale). Force
    a comma-decimal locale and decode Gt/Lt float literals; auto-skips
    where no such locale is installed (this image ships only C/POSIX)."""
    import locale

    comma_locale = None
    for cand in ("de_DE.UTF-8", "de_DE.utf8", "fr_FR.UTF-8", "fr_FR.utf8"):
        try:
            locale.setlocale(locale.LC_NUMERIC, cand)
            comma_locale = cand
            break
        except locale.Error:
            continue
    if comma_locale is None:
        pytest.skip("no comma-decimal locale installed")
    try:
        assert locale.localeconv()["decimal_point"] == ","
        from tpusched.snapshot import MatchExpression, NodeSelectorTerm

        nodes = [dict(name="n0", allocatable={"cpu": 4000.0},
                      labels={"mem-gb": "1.5"})]
        pods = [dict(
            name="p", requests={"cpu": 100.0}, observed_avail=1.0,
            required_terms=[NodeSelectorTerm(
                (MatchExpression("mem-gb", "Gt", ("1.25",)),)
            )],
        )]
        msg = snapshot_to_proto(nodes, pods, [])
        snap_nat, meta_nat = native.decode_snapshot_bytes(
            msg.SerializeToString(), EngineConfig()
        )
        # 1.25 must parse as 1.25 (not 1): the Gt atom's numeric
        # threshold decides feasibility of the only node.
        res = Engine(EngineConfig()).solve(snap_nat)
        assert res.assignment[0] == 0, (
            "Gt(1.5 > 1.25) must hold under a comma-decimal locale"
        )
    finally:
        locale.setlocale(locale.LC_NUMERIC, "C")


def test_build_is_keyed_by_source_hash(tmp_path, monkeypatch):
    """The loaded .so is named for the SHA-256 of the committed source,
    so a build of any other source (a stale file copied along with the
    tree, whatever its mtime) is never picked up."""
    import os

    src = tmp_path / "fastdecode.cc"
    with open(native._SRC, "rb") as f:
        src.write_bytes(f.read())
    monkeypatch.setattr(native, "_SRC", str(src))
    before = native._so_path()
    assert os.path.basename(before).startswith("_fastdecode-")
    src.write_bytes(src.read_bytes() + b"\n// edited\n")
    assert native._so_path() != before
