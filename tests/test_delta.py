"""Delta snapshots over the gRPC boundary (SURVEY.md §7 hard part 6):
the client ships only changed records against a server-cached base; the
sidecar recomposes, solves, and returns a new snapshot_id. Unknown bases
fall back to a full send (crash recovery = resend)."""

import numpy as np
import pytest

from tpusched import Engine, EngineConfig
from tpusched.rpc import tpusched_pb2 as pb
from tpusched.rpc.client import DeltaSession, SchedulerClient
from tpusched.rpc.codec import (
    SnapshotStore,
    delta_between,
    snapshot_from_proto,
    snapshot_to_proto,
)
from tpusched.rpc.server import make_server


def _cluster_msg(n_pods=8, n_nodes=4, seed=0):
    rng = np.random.default_rng(seed)
    nodes = [
        dict(name=f"n{i}",
             allocatable={"cpu": 8000.0, "memory": float(32 << 30)},
             labels={"topology.kubernetes.io/zone": "ab"[i % 2]})
        for i in range(n_nodes)
    ]
    pods = [
        dict(name=f"p{i}",
             requests={"cpu": float(rng.integers(100, 500)),
                       "memory": float(rng.integers(1 << 28, 1 << 30))},
             priority=float(rng.integers(0, 100)),
             observed_avail=1.0,
             labels={"app": ["web", "db"][i % 2]})
        for i in range(n_pods)
    ]
    running = [
        dict(name="r0", node="n0", requests={"cpu": 500.0},
             labels={"app": "db"})
    ]
    return nodes, pods, running


@pytest.mark.parametrize("named", [False, True])
def test_delta_walk_counts_the_records_examined(named):
    """`walk` reports what the diff examined one by one, then what it
    serialized. Without `changed`: every record of the new snapshot,
    then every base record scanned for removals, each new record
    serialized. With `changed` naming the churn: each record's name,
    read once, plus the touched records serialized — none here, since
    the one named record was removed and removals come from the names."""
    nodes, pods, running = _cluster_msg()
    store = SnapshotStore(snapshot_to_proto(nodes, pods, running))
    pods2 = [p for p in pods if p["name"] != "p0"]
    new = snapshot_to_proto(nodes, pods2, running)
    walk = []
    delta = delta_between(store, new, "snap-0",
                          changed={"p0"} if named else None, walk=walk)
    assert list(delta.remove_pods) == ["p0"]
    n_new = len(nodes) + len(pods2) + len(running)
    if named:
        assert walk == [n_new, 0]
    else:
        assert walk == [len(nodes) * 2 + len(pods2) + len(pods)
                        + len(running) * 2, n_new]


def test_store_delta_roundtrip():
    """delta_between(prev, new) applied to prev's store recomposes new
    exactly (record sets keyed by name)."""
    nodes, pods, running = _cluster_msg()
    base = snapshot_to_proto(nodes, pods, running)
    store = SnapshotStore(base)
    # Mutate: drop a pod (bound), add a running pod, change a node, add a pod.
    nodes2 = [dict(n) for n in nodes]
    nodes2[1] = dict(nodes2[1], labels={"topology.kubernetes.io/zone": "c"})
    pods2 = [p for p in pods if p["name"] != "p0"] + [
        dict(name="p-new", requests={"cpu": 100.0}, observed_avail=1.0)
    ]
    running2 = running + [
        dict(name="p0", node="n1", requests={"cpu": 250.0},
             labels={"app": "web"})
    ]
    new = snapshot_to_proto(nodes2, pods2, running2)
    delta = delta_between(store, new, "snap-0")
    assert len(delta.upsert_nodes) == 1
    assert list(delta.remove_pods) == ["p0"]
    assert len(delta.upsert_pods) == 1
    assert len(delta.upsert_running) == 1
    store2 = store.copy()
    store2.apply_delta(delta)
    composed = store2.compose()
    assert {n.name for n in composed.nodes} == {n["name"] for n in nodes2}
    assert {p.name for p in composed.pods} == {p["name"] for p in pods2}
    assert {r.name for r in composed.running} == {r["name"] for r in running2}
    # Semantics: composed message schedules identically to the fresh one.
    cfg = EngineConfig()
    s1, m1 = snapshot_from_proto(composed, cfg)
    s2, m2 = snapshot_from_proto(new, cfg)
    eng = Engine(cfg)
    try:
        r1, r2 = eng.solve(s1), eng.solve(s2)
        by_name_1 = {m1.pod_names[i]: (m1.node_names[int(n)] if n >= 0 else None)
                     for i, n in enumerate(r1.assignment[: m1.n_pods])}
        by_name_2 = {m2.pod_names[i]: (m2.node_names[int(n)] if n >= 0 else None)
                     for i, n in enumerate(r2.assignment[: m2.n_pods])}
        assert by_name_1 == by_name_2
    finally:
        eng.close()


@pytest.fixture
def sidecar():
    server, port, svc = make_server("127.0.0.1:0", config=EngineConfig(mode="fast"))
    server.start()
    client = SchedulerClient(f"127.0.0.1:{port}")
    yield client, svc
    client.close()
    server.stop(0)


def test_delta_session_over_wire(sidecar):
    """Second cycle ships a delta (smaller payload), and the assignments
    equal a fresh full-snapshot solve of the same state."""
    client, _ = sidecar
    sess = DeltaSession(client)
    nodes, pods, running = _cluster_msg(n_pods=12, n_nodes=4)
    msg1 = snapshot_to_proto(nodes, pods, running)
    resp1 = sess.assign(msg1)
    assert sess.full_sends == 1 and sess.delta_sends == 0
    assert resp1.snapshot_id

    # Bind the first two assignments: pending -> running, plus one new pod.
    bound = {a.pod: a.node for a in resp1.assignments if a.node}
    picked = sorted(bound)[:2]
    pods2 = [p for p in pods if p["name"] not in picked] + [
        dict(name="late", requests={"cpu": 100.0}, observed_avail=1.0)
    ]
    running2 = running + [
        dict(name=nm, node=bound[nm],
             requests=next(p for p in pods if p["name"] == nm)["requests"])
        for nm in picked
    ]
    msg2 = snapshot_to_proto(nodes, pods2, running2)
    resp2 = sess.assign(msg2)
    assert sess.delta_sends == 1, "second cycle must ride the delta path"
    full_bytes = msg1.ByteSize() + msg2.ByteSize()
    assert sess.bytes_sent < full_bytes, "delta must be smaller"

    cfg = EngineConfig(mode="fast")
    snap, meta = snapshot_from_proto(msg2, cfg)
    direct = Engine(cfg).solve(snap)
    direct_by_name = {
        meta.pod_names[i]: (meta.node_names[int(n)] if n >= 0 else "")
        for i, n in enumerate(direct.assignment[: meta.n_pods])
    }
    wire_by_name = {a.pod: a.node for a in resp2.assignments}
    assert wire_by_name == direct_by_name


def test_unknown_base_falls_back(sidecar):
    """A base evicted from the server's LRU (or a restarted sidecar)
    triggers FAILED_PRECONDITION; the session resends in full."""
    client, svc = sidecar
    sess = DeltaSession(client)
    nodes, pods, running = _cluster_msg()
    msg = snapshot_to_proto(nodes, pods, running)
    sess.assign(msg)
    with svc._store_lock:
        svc._stores.clear()  # simulate restart/eviction
    resp = sess.assign(msg)
    assert sess.fallbacks == 1
    assert sess.full_sends == 2
    assert resp.snapshot_id


def test_in_place_mutation_is_not_lost(sidecar):
    """A client that keeps ONE message and mutates it in place between
    cycles must still get its change onto the wire (the session stores
    serialized bytes, not live record references)."""
    client, _ = sidecar
    sess = DeltaSession(client)
    nodes, pods, running = _cluster_msg(n_pods=4, n_nodes=2)
    msg = snapshot_to_proto(nodes, pods, running)
    sess.assign(msg)
    # In-place mutation: double one pod's cpu request.
    for r in msg.pods[0].requests:
        if r.name == "cpu":
            r.quantity = r.quantity * 2
    resp = sess.assign(msg)
    assert sess.delta_sends == 1
    cfg = EngineConfig(mode="fast")
    snap, meta = snapshot_from_proto(msg, cfg)
    direct = Engine(cfg).solve(snap)
    direct_by_name = {
        meta.pod_names[i]: (meta.node_names[int(n)] if n >= 0 else "")
        for i, n in enumerate(direct.assignment[: meta.n_pods])
    }
    assert {a.pod: a.node for a in resp.assignments} == direct_by_name


def test_unnamed_running_pods_disable_delta(sidecar):
    """Unnamed running pods can't be keyed by name: the server returns
    no snapshot_id and the session keeps sending full snapshots, so
    nothing silently collapses."""
    client, _ = sidecar
    sess = DeltaSession(client)
    nodes, pods, running = _cluster_msg()
    running = [dict(r, name="") for r in running] + [
        dict(name="", node="n1", requests={"cpu": 100.0})
    ]
    msg = snapshot_to_proto(nodes, pods, running)
    resp1 = sess.assign(msg)
    assert resp1.snapshot_id == ""
    sess.assign(msg)
    assert sess.full_sends == 2 and sess.delta_sends == 0


def test_unsafe_snapshot_after_safe_base_sends_full(sidecar):
    """Regression (advisor, round 2): a snapshot that turns delta-UNSAFE
    (duplicate/unnamed records) after a safe base was remembered must NOT
    ride the delta path — the server's name-keyed store would silently
    collapse the duplicates and solve a corrupted snapshot for a cycle."""
    client, _ = sidecar
    sess = DeltaSession(client)
    nodes, pods, running = _cluster_msg(n_pods=4, n_nodes=2)
    sess.assign(snapshot_to_proto(nodes, pods, running))
    assert sess.full_sends == 1

    # Three running pods on the wire, two sharing a name: collapsing to
    # two would under-count node usage.
    running2 = running + [
        dict(name="dup", node="n0", requests={"cpu": 300.0}),
        dict(name="dup", node="n1", requests={"cpu": 400.0}),
    ]
    msg2 = snapshot_to_proto(nodes, pods, running2)
    resp2 = sess.assign(msg2)
    assert sess.delta_sends == 0, "unsafe snapshot must not ship as delta"
    assert sess.full_sends == 2
    assert resp2.snapshot_id == "", "server must not register unsafe base"
    # All three running pods reached the engine: solve equals a direct
    # full-snapshot solve of the same (uncollapsed) state.
    cfg = EngineConfig(mode="fast")
    snap, meta = snapshot_from_proto(msg2, cfg)
    assert meta.n_running == 3
    direct = Engine(cfg).solve(snap)
    direct_by_name = {
        meta.pod_names[i]: (meta.node_names[int(n)] if n >= 0 else "")
        for i, n in enumerate(direct.assignment[: meta.n_pods])
    }
    assert {a.pod: a.node for a in resp2.assignments} == direct_by_name


def test_server_rejects_unsafe_delta_upserts(sidecar):
    """Defense-in-depth: a hand-crafted delta whose upserts carry empty
    or duplicate names is rejected INVALID_ARGUMENT, never solved."""
    import grpc

    client, _ = sidecar
    nodes, pods, running = _cluster_msg(n_pods=4, n_nodes=2)
    resp = client.assign(snapshot_to_proto(nodes, pods, running))
    assert resp.snapshot_id

    for bad_running in (
        [dict(name="dup", node="n0", requests={"cpu": 1.0}),
         dict(name="dup", node="n1", requests={"cpu": 2.0})],
        [dict(name="", node="n0", requests={"cpu": 1.0})],
    ):
        delta = pb.SnapshotDelta(base_id=resp.snapshot_id)
        bad = snapshot_to_proto([], [], bad_running)
        delta.upsert_running.extend(bad.running)
        with pytest.raises(grpc.RpcError) as ei:
            client.assign_delta(delta)
        assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT

    # A delta without a base_id can never resolve — rejected loudly
    # rather than silently solving the empty default snapshot.
    with pytest.raises(grpc.RpcError) as ei:
        client.assign_delta(pb.SnapshotDelta())
    assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT


def test_reordered_full_send_schedules_identically(sidecar):
    """Same state, different wire order -> identical placements (codec
    canonicalizes record order by name)."""
    client, _ = sidecar
    nodes, pods, running = _cluster_msg(n_pods=6, n_nodes=3)
    m1 = snapshot_to_proto(nodes, pods, running)
    m2 = snapshot_to_proto(nodes[::-1], pods[::-1], running[::-1])
    r1 = client.assign(m1)
    r2 = client.assign(m2)
    assert {a.pod: a.node for a in r1.assignments} == \
        {a.pod: a.node for a in r2.assignments}


def test_store_lru_cap(sidecar):
    """The server keeps at most STORE_CAP stores."""
    from tpusched.rpc.server import STORE_CAP

    client, svc = sidecar
    nodes, pods, running = _cluster_msg(n_pods=2, n_nodes=2)
    msg = snapshot_to_proto(nodes, pods, running)
    for _ in range(STORE_CAP + 3):
        client.assign(msg)
    with svc._store_lock:
        assert len(svc._stores) <= STORE_CAP
