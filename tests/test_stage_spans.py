"""Stage spans inside the delta cycle: the client's own stages
(client.check / client.diff / client.remember), the sidecar's byte-store
step (store.advance) and the delta apply's stages (apply.records /
apply.encode / apply.order / apply.scatter under delta.apply), driven
through an in-process sidecar and a DeltaSession at a small size."""

import pytest

from tpusched import EngineConfig, trace
from tpusched.rpc.client import DeltaSession, SchedulerClient
from tpusched.rpc.codec import snapshot_to_proto
from tpusched.rpc.server import make_server
from tpusched.snapshot import MatchExpression, TopologySpreadConstraint

CLIENT = ("client.check", "client.diff", "client.remember")
APPLY = ("apply.records", "apply.encode", "apply.order", "apply.scatter")
NEW = CLIENT + ("store.advance",) + APPLY
ATTRS = {"client.diff": {"visited", "serialized", "shipped"}}
EPS = 2e-3   # wall start + perf_counter duration: a clock-mix epsilon


def _snapshot(cycle: int):
    """Four nodes, running pods, pending pods; each cycle binds one pod,
    adds one and bumps a node, so the apply reorders rows."""
    nodes = [dict(name=f"n{j}",
                  allocatable={"cpu": 8000.0 + (cycle if j == 0 else 0),
                               "memory": float(32 << 30)})
             for j in range(4)]
    pods = [dict(name=f"p{j}", requests={"cpu": 200.0})
            for j in range(cycle, 6 + cycle)]
    running = [dict(name=f"r{j}", node=f"n{j % 4}",
                    requests={"cpu": 100.0}) for j in range(3 + cycle)]
    return snapshot_to_proto(nodes, pods, running)


def _drive(col):
    """A full send then two delta cycles; the spans of each cycle."""
    server, port, svc = make_server("127.0.0.1:0",
                                    config=EngineConfig(mode="fast"),
                                    tracer=col)
    server.start()
    client = SchedulerClient(f"127.0.0.1:{port}", tracer=col)
    sess = DeltaSession(client)
    per_cycle = []
    try:
        for k in range(3):
            col.clear()
            sess.assign(_snapshot(k), changed=None if k == 0 else
                        {"n0", f"p{k - 1}", f"p{5 + k}", f"r{2 + k}"},
                        packed_ok=True)
            per_cycle.append(col.spans())
        assert (sess.full_sends, sess.delta_sends) == (1, 2)
        assert svc.session_hits + svc.session_seeds == 2
    finally:
        client.close()
        server.stop(0)
        svc.close()
    return per_cycle


def _end(s):
    return s.t_wall + s.dur_s


def test_delta_cycles_carry_the_stage_spans(thread_leak_check):
    col = trace.TraceCollector(seed=23)
    full, *deltas = _drive(col)
    assert {"client.remember"} <= {s.name for s in full}
    assert not {"client.diff", "store.advance"} & {s.name for s in full}
    for k, spans in enumerate(deltas, 1):
        by_name = {}
        for s in spans:
            by_name.setdefault(s.name, []).append(s)
        for name in NEW:
            assert name in by_name, (name, sorted(by_name))
            attrs = {k for s in by_name[name] for k in s.attrs}
            assert attrs == ATTRS.get(name, set()), (name, attrs)
        # The named diff reads each record's name of the new snapshot
        # once (4 nodes, 6 pods, 3 + k running pods) and serializes only
        # the touched records: the bumped node and the two arrivals. It
        # ships those and the bound pod's removal.
        diff = by_name["client.diff"][0].attrs
        assert diff["serialized"] == 3
        assert diff["visited"] == (4 + 6 + 3 + k) + diff["serialized"]
        assert diff["shipped"] == 4
        # One trace per cycle: the client's stages, the request and every
        # sidecar span share the id the client minted.
        rid = by_name["client.check"][0].trace_id
        assert rid
        for name in NEW + ("client.send", "server.Assign", "delta.apply"):
            assert {s.trace_id for s in by_name[name]} == {rid}, name
        # The apply's stages nest in delta.apply; store.advance is a
        # stage of the request itself.
        (apply,) = by_name["delta.apply"]
        (root,) = by_name["server.Assign"]
        # The apply's counts: a node bump, a bind and an arrival (4
        # records), each name examined by a handful of O(churn) passes.
        assert set(apply.attrs) == {"h2d_bytes", "path", "order_visited",
                                    "churn_records", "used_rows",
                                    "used_nodes"}
        assert apply.attrs["path"] == "delta"
        assert apply.attrs["churn_records"] >= 4
        assert (apply.attrs["churn_records"] <= apply.attrs["order_visited"]
                <= 10 * apply.attrs["churn_records"])
        # The `used` re-sum: n0 (bumped) and the new running pod's node
        # n{(2 + k) % 4}, folded over every running pod on them.
        dirty = {0, (2 + k) % 4}
        assert apply.attrs["used_nodes"] == len(dirty)
        assert apply.attrs["used_rows"] == sum(
            j % 4 in dirty for j in range(3 + k))
        for name in APPLY:
            for s in by_name[name]:
                assert s.parent_id == apply.span_id, name
        assert by_name["store.advance"][0].parent_id == root.span_id
        # The client's stages are flat and lie outside the send.
        (send,) = by_name["client.send"]
        client_ids = {s.span_id for n in CLIENT for s in by_name[n]}
        for name in CLIENT:
            for s in by_name[name]:
                assert s.parent_id not in client_ids
                assert (_end(s) <= send.t_wall + EPS
                        or s.t_wall >= _end(send) - EPS), name
        # None of the new spans encloses the cycle: the cycle runs from
        # the client's first stage to its last.
        lo = min(s.t_wall for s in spans if s.name in CLIENT)
        hi = max(_end(s) for s in spans if s.name in CLIENT)
        for name in NEW:
            for s in by_name[name]:
                assert not (s.t_wall <= lo and _end(s) >= hi), name
                assert s.dur_s < hi - lo, name
        # The apply's stages account for the apply.
        staged = sum(s.dur_s for n in APPLY for s in by_name[n])
        assert staged <= apply.dur_s + EPS


def test_disabled_collector_records_no_stage_span(thread_leak_check):
    trace.DEFAULT.clear()
    col = trace.TraceCollector(seed=23, enabled=False)
    per_cycle = _drive(col)
    assert all(spans == [] for spans in per_cycle)
    leaked = {s.name for s in trace.DEFAULT.spans()}
    assert not leaked & set(NEW), leaked


@pytest.mark.parametrize("cat", ["device", "server"])
def test_laps_record_back_to_back_stages(cat):
    col = trace.TraceCollector(seed=1)
    with col.span("outer", trace_id="t") as outer:
        laps = col.laps(cat)
        laps.lap("a")
        laps.lap("b")
        laps.lap("a")
        laps.close()
        laps.close()
    *stages, last = col.spans()
    assert [s.name for s in stages] == ["a", "b", "a"]
    assert last.name == "outer"
    for s in stages:
        assert s.parent_id == outer.span_id and s.trace_id == "t"
        assert s.cat == cat and s.attrs == {}
    a1, b, a2 = stages
    assert a1.t_wall <= b.t_wall <= a2.t_wall
    off = trace.TraceCollector(enabled=False).laps(cat)
    off.lap("a")
    off.close()


def _spread_contended():
    """Twelve foo=bar pods, each spread with maxSkew 1 over hostname and
    zone, onto five nodes in three zones: the round deals more of them
    than the skews allow, and validation takes the excess back."""
    sel = (MatchExpression("foo", "In", ("bar",)),)
    nodes = [dict(name=f"n{j}", allocatable={"cpu": 8000.0,
                                              "memory": float(32 << 30)},
                  labels={"kubernetes.io/hostname": f"n{j}",
                          "zone": f"z{j % 3}"}) for j in range(5)]
    pods = [dict(name=f"p{i:02d}",
                 requests={"cpu": 100.0 + 10 * (i % 3),
                           "memory": float(1 << 20)},
                 labels={"foo": "bar"},
                 topology_spread=[TopologySpreadConstraint(
                     topology_key=key, max_skew=1,
                     when_unsatisfiable="DoNotSchedule", selector=sel)
                     for key in ("kubernetes.io/hostname", "zone")])
            for i in range(12)]
    return snapshot_to_proto(nodes, pods)


def _joins(col, msg):
    server, port, svc = make_server("127.0.0.1:0",
                                    config=EngineConfig(mode="fast"),
                                    tracer=col)
    server.start()
    client = SchedulerClient(f"127.0.0.1:{port}", tracer=col)
    try:
        col.clear()
        resp = DeltaSession(client).assign(msg, packed_ok=True)
        return resp, [s for s in col.spans() if s.name == "fetch.join"]
    finally:
        client.close()
        server.stop(0)
        svc.close()


def test_fetch_join_carries_rounds_and_no_reverts_without_signatures(
        thread_leak_check):
    col = trace.TraceCollector(seed=29)
    for spans in _drive(col):
        (join,) = [s for s in spans if s.name == "fetch.join"]
        assert join.attrs["rounds"] >= 1
        assert join.attrs["reverts"] == 0


def test_fetch_join_counts_the_pairwise_reverts(thread_leak_check):
    col = trace.TraceCollector(seed=31)
    resp, (join,) = _joins(col, _spread_contended())
    assert join.attrs["rounds"] == resp.rounds > 1
    assert join.attrs["reverts"] > 0
