"""End-to-end request tracing (round 9, ISSUE 4): collector unit
behavior, Chrome export validity, and the tier-1 smoke — a 4-client
run over real gRPC yields stitched client<->server traces whose server
stage spans are non-overlapping and account for ≈ the request wall —
plus the flight recorder capturing a forced watchdog trip."""

import json
import threading
import time

import pytest

from tpusched import trace
from tpusched.rpc.client import DeltaSession, SchedulerClient
from tpusched.rpc.codec import snapshot_to_proto
from tpusched.rpc.server import make_server


# ---------------------------------------------------------------------------
# Collector unit behavior.
# ---------------------------------------------------------------------------


def test_span_nesting_and_explicit_roots():
    t = trace.TraceCollector(seed=0)
    with t.request("rid", 7, name="root") as root:
        with t.span("child") as c1:
            with t.span("grand") as c2:
                pass
        t.record("retro", dur_s=0.001)
    spans = {s.name: s for s in t.spans()}
    assert spans["root"].trace_id == "rid" and spans["root"].parent_id == 7
    assert spans["child"].parent_id == root.span_id
    assert spans["grand"].parent_id == c1.span_id
    assert spans["grand"].trace_id == "rid"
    assert spans["retro"].parent_id == root.span_id
    assert c2.span_id > c1.span_id > root.span_id


def test_ring_capacity_bounds_memory():
    t = trace.TraceCollector(capacity=8)
    for i in range(100):
        t.record(f"e{i}")
    spans = t.spans()
    assert len(spans) == 8
    assert spans[0].name == "e92"  # oldest survivors


def test_disabled_path_is_shared_noop():
    t = trace.TraceCollector(enabled=False)
    s = t.span("x")
    assert s is t.span("y"), "disabled span() must allocate nothing"
    with s as sp:
        sp.attrs["k"] = 1  # same surface as a live span
    t.record("z")
    assert t.spans() == []


def test_seeded_trace_ids_deterministic():
    a, b = trace.TraceCollector(seed=3), trace.TraceCollector(seed=3)
    assert [a.new_trace_id() for _ in range(3)] == \
           [b.new_trace_id() for _ in range(3)]
    assert trace.TraceCollector(seed=4).new_trace_id() != \
           trace.TraceCollector(seed=5).new_trace_id()


def test_traces_groups_by_recency_and_skips_untraced():
    t = trace.TraceCollector()
    t.record("a", ctx=("t1", 0))
    t.record("orphan")                # untraced event
    t.record("b", ctx=("t2", 0))
    t.record("c", ctx=("t1", 0))      # t1 becomes most recent
    tr = t.traces(last=2)
    assert list(tr) == ["t2", "t1"]
    assert [s.name for s in tr["t1"]] == ["a", "c"]


def test_to_chrome_events_valid():
    t = trace.TraceCollector()
    with t.request("rid", name="req"):
        with t.span("stage", pods=3):
            pass
    events = trace.to_chrome(t.spans())
    json.dumps(events)  # serializable
    for e in events:
        assert e["ph"] == "X" and e["ts"] > 0 and e["dur"] >= 0
        assert set(e) >= {"name", "cat", "pid", "tid", "args"}
    by = {e["name"]: e for e in events}
    assert by["stage"]["args"]["parent_span"] == by["req"]["args"]["span_id"]


def test_storm_detector_one_dump_per_storm():
    now = [0.0]
    sd = trace.StormDetector(n=3, window_s=5.0, clock=lambda: now[0])
    assert not sd.hit() and not sd.hit()
    assert sd.hit(), "third event inside the window is the storm"
    assert not sd.hit(), "the trigger resets: one dump per storm"
    now[0] = 100.0
    assert not sd.hit() and not sd.hit(), "stale events don't count"


def test_flight_recorder_snapshots_ring():
    t = trace.TraceCollector()
    t.record("evidence", ctx=("rid", 0))
    fr = trace.FlightRecorder(capacity=2)
    fr.record("watchdog_trip", t, what="solve")
    for _ in range(3):
        fr.record("ladder_demotion", t)
    dumps = fr.dumps()
    assert len(dumps) == 2 and fr.trips == 4
    assert dumps[0]["reason"] == "ladder_demotion"
    names = {s["name"] for d in dumps for s in d["spans"]}
    assert "evidence" in names


def test_stamp_inherits_enclosing_client_span():
    """A send issued under an open client span (the resync path) joins
    that span's trace: request_id inherits the trace id, parent_span
    the span id — a bare send still mints its own."""
    from tpusched.rpc import tpusched_pb2 as pb

    client = SchedulerClient("127.0.0.1:1")  # lazy channel: never dials
    try:
        t = client.tracer = trace.TraceCollector(seed=9)
        req = pb.ScoreRequest()
        with t.span("client.resync", cat="client",
                    trace_id="doomed-1") as sp:
            assert client._stamp(req) == "doomed-1"
            assert req.parent_span == sp.span_id
        req2 = pb.ScoreRequest()
        rid2 = client._stamp(req2)
        assert rid2 and rid2 != "doomed-1" and req2.parent_span == 0
    finally:
        client.close()


# ---------------------------------------------------------------------------
# Tier-1 smoke: stitched multi-client traces over real gRPC.
# ---------------------------------------------------------------------------


def _tiny_snapshot(tag: str, bump: float = 0.0):
    nodes = [dict(name=f"{tag}-n{j}",
                  allocatable={"cpu": 4000.0 + bump,
                               "memory": float(16 << 30)})
             for j in range(3)]
    pods = [dict(name=f"{tag}-p{j}",
                 requests={"cpu": 500.0, "memory": float(1 << 30)})
            for j in range(4)]
    return snapshot_to_proto(nodes, pods, [])


def test_multiclient_traces_stitch_and_account_for_wall(thread_leak_check):
    """4 concurrent DeltaSession clients; every request's trace must
    contain BOTH the client spans and the server stage spans under one
    request_id, the server stage spans must not overlap each other,
    and on the longest request they must account for most of the
    handler wall (the tentpole acceptance: you can see where each
    millisecond goes)."""
    trace.DEFAULT.clear()
    server, port, svc = make_server("127.0.0.1:0")
    server.start()
    clients = [SchedulerClient(f"127.0.0.1:{port}") for _ in range(4)]
    try:
        def drive(i):
            sess = DeltaSession(clients[i])
            for k in range(3):
                sess.assign(_tiny_snapshot(f"c{i}", bump=k),
                            changed={f"c{i}-n0"} if k else None,
                            packed_ok=True)

        threads = [threading.Thread(target=drive, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        for c in clients:
            c.close()
        server.stop(0)
        svc.close()

    traces = trace.DEFAULT.traces(last=64)
    stitched = {
        tid: spans for tid, spans in traces.items()
        if {"client", "server"} <= {s.cat for s in spans}
    }
    assert len(stitched) >= 12, (
        f"want every request's trace stitched client<->server, got "
        f"{len(stitched)} of {len(traces)}"
    )
    roots = 0
    for tid, spans in stitched.items():
        root = next(s for s in spans if s.name.startswith("server."))
        stages = sorted(
            (s for s in spans
             if s.cat == "server" and s is not root),
            key=lambda s: s.t_wall,
        )
        assert stages, f"trace {tid} has no stage spans"
        # Stage spans are sequential handler work: no overlaps (5 ms
        # epsilon for the wall-vs-perf_counter clock mix).
        for a, b in zip(stages, stages[1:]):
            assert b.t_wall >= a.t_wall + a.dur_s - 5e-3, (
                f"{a.name} overlaps {b.name} in {tid}"
            )
        covered = sum(s.dur_s for s in stages)
        assert covered <= root.dur_s * 1.05 + 5e-3, (
            f"stage spans exceed the request wall in {tid}"
        )
        roots += 1
    # Wall accounting on the slowest request (the compile-bearing one:
    # real work, so bookkeeping gaps are relatively tiny).
    tid, spans = max(
        stitched.items(),
        key=lambda kv: max(s.dur_s for s in kv[1]
                           if s.name.startswith("server.")),
    )
    root = next(s for s in spans if s.name.startswith("server."))
    covered = sum(s.dur_s for s in spans
                  if s.cat == "server" and s is not root)
    assert covered >= 0.6 * root.dur_s, (
        f"stage spans cover {covered:.4f}s of {root.dur_s:.4f}s wall "
        f"in {tid}: the trace does not explain the latency"
    )
    assert roots == len(stitched)


def test_injected_tracer_captures_engine_and_fault_spans(thread_leak_check):
    """make_server(tracer=...) must thread the collector through to the
    engine's fetch worker and the fault plan: engine.fetch and fault.*
    spans land in the INJECTED ring (Debugz/flight dumps see them), not
    the process default."""
    from tpusched.faults import FaultPlan, FaultRule

    trace.DEFAULT.clear()
    col = trace.TraceCollector(seed=11)
    plan = FaultPlan([FaultRule(site="server.decode", kind="delay",
                                at=frozenset({0}), delay_s=0.01)])
    server, port, svc = make_server("127.0.0.1:0", tracer=col,
                                    faults=plan)
    server.start()
    client = SchedulerClient(f"127.0.0.1:{port}")
    client.tracer = col
    try:
        client.assign(_tiny_snapshot("inj"), packed_ok=True)
    finally:
        client.close()
        server.stop(0)
        svc.close()
    names = {s.name for s in col.spans()}
    assert {"engine.fetch", "fault.delay", "decode"} <= names, names
    leaked = {s.name for s in trace.DEFAULT.spans()}
    assert "engine.fetch" not in leaked and "fault.delay" not in leaked


def test_delta_session_resync_span_is_traced(thread_leak_check):
    """A DeltaSession resync (sidecar lost the base) must appear in
    traces()/Debugz as one trace grouping the client.resync span with
    the full re-send it covers — not as untraced ring noise."""
    trace.DEFAULT.clear()
    server, port, svc = make_server("127.0.0.1:0")
    server.start()
    client = SchedulerClient(f"127.0.0.1:{port}")
    sess = DeltaSession(client)
    try:
        sess.assign(_tiny_snapshot("rs"), changed=None, packed_ok=True)
        with svc._store_lock:
            svc._stores.clear()  # sidecar "restart": base is gone
        sess.assign(_tiny_snapshot("rs", bump=1.0), changed={"rs-n0"},
                    packed_ok=True)
        assert sess.fallbacks == 1
    finally:
        client.close()
        server.stop(0)
        svc.close()
    groups = trace.DEFAULT.traces(last=64)
    resync = [spans for spans in groups.values()
              if any(s.name == "client.resync" for s in spans)]
    assert resync, "client.resync must land in a grouped trace"
    names = {s.name for s in resync[0]}
    cats = {s.cat for s in resync[0]}
    assert "client.send" in names, names
    assert "server" in cats, "the re-sent full request must stitch"


def test_watchdog_trip_produces_flight_dump(thread_leak_check):
    """A forced hung fetch (faults.py delay past the watchdog) must
    produce a DEADLINE_EXCEEDED for its caller AND a flight-recorder
    dump whose spans explain the trip (the errored fetch.join of the
    doomed request is in the ring it snapshots)."""
    import grpc

    from tpusched.faults import FaultPlan, FaultRule
    from tpusched.rpc.client import NO_RETRY

    trace.DEFAULT.clear()
    plan = FaultPlan([FaultRule(site="engine.fetch", kind="delay",
                                at=frozenset({1}), delay_s=2.0)])
    server, port, svc = make_server("127.0.0.1:0", faults=plan,
                                    watchdog_s=0.5)
    server.start()
    client = SchedulerClient(f"127.0.0.1:{port}", retry=NO_RETRY)
    try:
        client.assign(_tiny_snapshot("wd"), packed_ok=True)  # warm: idx 0
        with pytest.raises(grpc.RpcError) as err:
            client.assign(_tiny_snapshot("wd", bump=1.0), packed_ok=True)
        assert err.value.code() == grpc.StatusCode.DEADLINE_EXCEEDED
        dumps = svc.flight.dumps()
        reasons = [d["reason"] for d in dumps]
        assert "watchdog_trip" in reasons, reasons
        dump = next(d for d in dumps if d["reason"] == "watchdog_trip")
        assert dump["extra"]["what"] == "Assign solve"
        joined = [s for s in dump["spans"]
                  if s["name"] == "fetch.join" and "error" in s["attrs"]]
        assert joined, "the dump must contain the timed-out fetch.join"
        # The doomed request's whole causal chain is in the dump.
        rid = joined[-1]["trace_id"]
        chain = {s["name"] for s in dump["spans"]
                 if s["trace_id"] == rid}
        assert {"decode", "gate.wait", "dispatch"} <= chain, chain
        assert svc.watchdog_trips >= 1
        # The hung join must land in the stage histogram (the long
        # tail the log-scale buckets exist for), not only the counter:
        # warm request + doomed request = 2 observations.
        joins = svc.metrics.stage.labels("fetch.join")
        assert joins.count >= 2 and joins.sum >= 0.5, \
            (joins.count, joins.sum)
    finally:
        client.close()
        server.stop(0)
        svc.close()
        # Let the delayed (abandoned) fetch finish so its worker exits
        # before thread_leak_check sweeps.
        time.sleep(0.1)


def _tracez_tool():
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parents[1] / "tools" / "tracez.py"
    spec = importlib.util.spec_from_file_location("tpusched_tracez_tool", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def test_tracez_demo_exports_stitched_trace(tmp_path, monkeypatch,
                                            thread_leak_check):
    """`tools/tracez.py --demo` writes valid trace-event JSON in which
    each request's client and server spans share its trace_id."""
    tool = _tracez_tool()
    out = tmp_path / "trace.json"
    monkeypatch.setattr("sys.argv", [
        "tracez.py", "--demo", "--clients", "1", "--cycles", "2",
        "--out", str(out)])
    assert tool.main() == 0
    doc = json.loads(out.read_text())
    events = doc["traceEvents"]
    assert events
    for e in events:
        assert e["ph"] == "X" and e["dur"] >= 0 and e["ts"] > 0, e
        assert isinstance(e["name"], str) and "tid" in e, e
    cats: dict = {}
    for e in events:
        tid = e.get("args", {}).get("trace_id")
        if tid:
            cats.setdefault(tid, set()).add(e["cat"])
    assert len(cats) == 2, cats  # one trace per Assign
    for tid, seen in cats.items():
        assert {"client", "server"} <= seen, (tid, seen)


def test_tracez_address_exports_live_sidecar(tmp_path, monkeypatch,
                                             thread_leak_check):
    """`tools/tracez.py --address` pulls a live sidecar's traces over
    Debugz and its wire records over Statusz, and exports both."""
    tool = _tracez_tool()
    trace.DEFAULT.clear()
    server, port, svc = make_server("127.0.0.1:0")
    server.start()
    try:
        nodes = [dict(name="n0", allocatable={"cpu": 4000.0,
                                              "memory": float(16 << 30)})]
        pods = [dict(name="p0", requests={"cpu": 500.0,
                                          "memory": float(1 << 30)})]
        with SchedulerClient(f"127.0.0.1:{port}", wire=svc.wire) as c:
            c.assign(snapshot_to_proto(nodes, pods, []))
        out = tmp_path / "trace.json"
        monkeypatch.setattr("sys.argv", [
            "tracez.py", "--address", f"127.0.0.1:{port}", "--last", "4",
            "--out", str(out)])
        assert tool.main() == 0
    finally:
        server.stop(0)
        svc.close()
    events = json.loads(out.read_text())["traceEvents"]
    server_tids = {e["args"]["trace_id"] for e in events
                   if e["cat"] == "server" and e["args"].get("trace_id")}
    assert len(server_tids) == 1, server_tids
    assert any(e["cat"] == "wire" for e in events), "no wire panel row"
