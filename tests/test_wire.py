"""Wire ledger (round 19, ISSUE 19): WireRecord schema, the NTP-style
clock-offset estimator (skewed clocks, retry re-issues under one rid,
the degenerate zero-wire case), span-pairing assembly, sentinel cause
attribution (bytes_burst/queue/decode/transfer/unknown), flight-
recorder wiring, the Statusz `wire` panel + metric families over a real
loopback server, and the injected-wire-stall acceptance scenario."""

import json

import pytest

from tpusched import metrics as pm
from tpusched import trace as tracing
from tpusched import wire as wiring
from tpusched.trace import Span


def _wrec(**kw):
    """A steady-state baseline cycle: 100 ms wall, fully stitched,
    modest stages, 1 KB up / 500 B down."""
    base = dict(ts=0.0, rpc="Assign", rid="r", source="call", attempts=1,
                resyncs=0, replayed=False, stitched=True, wall_s=0.1,
                offset_s=0.0, uncertainty_s=0.001, bytes_up=1000,
                bytes_down=500,
                stages={"decode": 0.02, "gate.wait": 0.01,
                        "fetch.join": 0.03, "reply.gap": 0.02},
                coverage=0.95)
    base.update(kw)
    return wiring.WireRecord(**base)


# ---------------------------------------------------------------------------
# Schema.
# ---------------------------------------------------------------------------


def test_record_dict_matches_schema_and_validates():
    d = wiring.record_dict(_wrec())
    assert list(d) == list(wiring.SCHEMA)
    wiring.validate_record(d)


@pytest.mark.parametrize("mutate", [
    lambda d: d.pop("wall_s"),                      # missing key
    lambda d: d.update(extra_field=1),              # extra key
    lambda d: d.update(attempts="1"),               # wrong type
    lambda d: d.update(wall_s=True),                # bool is not seconds
    lambda d: d.update(stitched=1),                 # int is not bool
    lambda d: d.update(stages={"decode": "fast"}),  # non-numeric stage
    lambda d: d.update(source="stream"),            # unknown source
])
def test_validate_record_rejects_drift(mutate):
    d = wiring.record_dict(_wrec())
    mutate(d)
    with pytest.raises(ValueError):
        wiring.validate_record(d)


# ---------------------------------------------------------------------------
# Clock-offset estimator (satellite: skew / retries / zero-wire).
# ---------------------------------------------------------------------------


def test_estimator_recovers_exact_offset_under_symmetric_paths():
    """Symmetric up/down transit: offset == the true skew EXACTLY,
    regardless of its magnitude or sign (NTP identity)."""
    for skew in (-3600.0, -0.5, 0.0, 0.25, 1e6):
        est = wiring.ClockOffsetEstimator()
        # client: send at 100, join at 100.5; server (client+skew):
        # recv 0.05 after send, 0.4 busy — 0.05 symmetric transit.
        out = est.add(100.0, 100.05 + skew, 100.45 + skew, 100.5)
        assert out is not None
        offset, unc = out
        assert offset == pytest.approx(skew, abs=1e-9)
        assert unc == pytest.approx(0.05, abs=1e-9)
        assert est.best() == pytest.approx((skew, 0.05))


def test_estimator_uncertainty_bounds_path_asymmetry():
    """Asymmetric transit (80 ms up, 20 ms down): the offset is wrong
    by exactly the asymmetry/2 — and uncertainty covers it."""
    est = wiring.ClockOffsetEstimator()
    offset, unc = est.add(0.0, 0.08, 0.48, 0.5)
    assert abs(offset - 0.0) <= unc + 1e-12
    assert unc == pytest.approx(0.05)


def test_estimator_rejects_inconsistent_pairings():
    """A server busy longer than the client window cannot belong to
    this attempt (a retry matched against the wrong root): duration-
    only validity, so arbitrary skew never masks it."""
    est = wiring.ClockOffsetEstimator()
    assert est.add(0.0, 50.0, 50.9, 0.5) is None     # busy 0.9 > window
    assert est.add(0.0, 50.0, 49.0, 0.5) is None     # busy < 0
    assert est.add(0.5, 50.0, 50.1, 0.2) is None     # window < 0
    assert est.best() is None
    assert est.samples() == 0


def test_estimator_min_delay_sample_wins():
    """A congested round trip (loose delay, poisoned offset) never
    displaces a tight sample — the classic NTP min-delay filter."""
    est = wiring.ClockOffsetEstimator()
    est.add(0.0, 10.4, 10.5, 1.0)     # delay 0.9: offset est 9.95
    tight = est.add(2.0, 12.005, 12.395, 2.4)  # delay 0.01: offset 10.0
    assert tight is not None
    offset, unc = est.best()
    assert offset == pytest.approx(tight[0])
    assert unc == pytest.approx(0.005)


def test_estimator_zero_wire_reports_tight_zero_offset():
    """Degenerate in-process case (client and server share one clock,
    near-zero transit): offset ~ 0 with TIGHT uncertainty."""
    est = wiring.ClockOffsetEstimator()
    for i in range(8):
        t0 = 100.0 + i
        est.add(t0, t0 + 1e-4, t0 + 0.02, t0 + 0.0202)
    offset, unc = est.best()
    assert abs(offset) <= unc + 1e-12
    assert unc < 0.001


# ---------------------------------------------------------------------------
# Assembly: span pairing -> WireRecord.
# ---------------------------------------------------------------------------


def _span(rid, name, cat, t, dur, span_id, parent=0, **attrs):
    return Span(trace_id=rid, span_id=span_id, parent_id=parent,
                name=name, cat=cat, t_wall=t, dur_s=dur, thread="t",
                attrs=attrs)


def test_assemble_stitches_skewed_server_and_reconstructs_wall():
    skew = 7200.0  # server clock two hours ahead
    rid = "cycle-1"
    spans = [
        _span(rid, "client.serialize", "client", 99.99, 0.01, 1),
        _span(rid, "client.send", "client", 100.0, 0.5, 2),
        _span(rid, "server.Assign", "server", 100.05 + skew, 0.4, 3),
        _span(rid, "decode", "server", 100.06 + skew, 0.1, 4, parent=3),
        _span(rid, "fetch.join", "server", 100.2 + skew, 0.2, 5, parent=3),
    ]
    clock = wiring.ClockOffsetEstimator()
    rec = wiring.assemble(rid, "Assign", spans, clock,
                          bytes_up=1234, bytes_down=567)
    assert rec is not None and rec.stitched
    assert rec.offset_s == pytest.approx(skew, abs=1e-6)
    assert rec.wall_s == pytest.approx(0.51, abs=1e-9)
    assert rec.stages["decode"] == pytest.approx(0.1)
    assert rec.stages["fetch.join"] == pytest.approx(0.2)
    # Root residue: 0.4 - 0.3 staged.
    assert rec.stages["server.other"] == pytest.approx(0.1, abs=1e-6)
    # Offset-corrected one-way gaps: 50 ms each way.
    assert rec.stages["send.gap"] == pytest.approx(0.05, abs=1e-6)
    assert rec.stages["reply.gap"] == pytest.approx(0.05, abs=1e-6)
    # Coverage by construction: components reconstruct the wall.
    assert rec.coverage == pytest.approx(1.0, abs=1e-6)
    assert (rec.bytes_up, rec.bytes_down) == (1234, 567)
    wiring.validate_record(wiring.record_dict(rec))


def test_assemble_counts_store_advance_and_skips_nested_spans():
    """store.advance is an exclusive serving phase: it is its own
    component, not server.other. The apply.* spans nested in
    delta.apply and the client's own stage spans are not components
    (summing them beside their parent would count their time twice)."""
    assert "store.advance" in wiring.SERVER_STAGES
    assert not any(s.startswith(("apply.", "client."))
                   for s in wiring.SERVER_STAGES)
    rid = "cycle-delta"
    spans = [
        _span(rid, "client.check", "client", 99.90, 0.02, 1),
        _span(rid, "client.diff", "client", 99.92, 0.07, 2),
        _span(rid, "client.send", "client", 100.0, 0.5, 3),
        _span(rid, "server.Assign", "server", 100.05, 0.4, 4),
        _span(rid, "store.advance", "server", 100.05, 0.01, 5, parent=4),
        _span(rid, "delta.apply", "server", 100.06, 0.1, 6, parent=4),
        _span(rid, "apply.records", "device", 100.06, 0.03, 7, parent=6),
        _span(rid, "apply.order", "device", 100.09, 0.06, 8, parent=6),
        _span(rid, "fetch.join", "server", 100.2, 0.2, 9, parent=4),
        _span(rid, "client.remember", "client", 100.5, 0.01, 10),
    ]
    rec = wiring.assemble(rid, "Assign", spans,
                          wiring.ClockOffsetEstimator())
    assert rec is not None and rec.stitched
    assert rec.stages["store.advance"] == pytest.approx(0.01)
    assert rec.stages["delta.apply"] == pytest.approx(0.1)
    assert not any(k.startswith(("apply.", "client.")) for k in rec.stages)
    # Root residue: 0.4 - (0.01 + 0.1 + 0.2).
    assert rec.stages["server.other"] == pytest.approx(0.09, abs=1e-6)
    assert wiring.COMPONENT_ORDER.index("store.advance") \
        < wiring.COMPONENT_ORDER.index("delta.apply")


def test_assemble_pairs_the_retry_attempt_with_its_own_root():
    """Two sends under one rid (first errored before reaching the
    server): the lone root pairs with the attempt whose window fits
    it; the backoff wait becomes its own component."""
    skew = 5.0
    rid = "cycle-retry"
    spans = [
        _span(rid, "client.send", "client", 0.0, 0.05, 1),     # failed
        _span(rid, "client.retry", "client", 0.05, 0.1, 2),
        _span(rid, "client.send", "client", 0.15, 0.3, 3),
        _span(rid, "server.Assign", "server", 0.2 + skew, 0.2, 4),
    ]
    clock = wiring.ClockOffsetEstimator()
    rec = wiring.assemble(rid, "Assign", spans, clock)
    assert rec.attempts == 2 and rec.stitched
    assert rec.offset_s == pytest.approx(skew, abs=1e-9)
    assert rec.stages["retry.backoff"] == pytest.approx(0.1)
    # Cycle bounds: first send start -> last send end.
    assert rec.wall_s == pytest.approx(0.45)


def test_assemble_counts_resyncs():
    rid = "cycle-resync"
    spans = [
        _span(rid, "client.send", "client", 0.0, 0.2, 1),
        _span(rid, "client.resync", "client", 0.0, 0.19, 2),
        _span(rid, "server.Assign", "server", 0.01, 0.15, 3),
    ]
    rec = wiring.assemble(rid, "Assign", spans,
                          wiring.ClockOffsetEstimator())
    assert rec.resyncs == 1 and rec.stitched


def test_assemble_without_server_root_degrades_to_unknown():
    """Remote sidecar (its spans never reach this ring): the middle of
    the cycle is one honest `unknown` block, stitched=False."""
    rid = "cycle-remote"
    spans = [
        _span(rid, "client.serialize", "client", 0.0, 0.02, 1),
        _span(rid, "client.send", "client", 0.02, 0.3, 2),
    ]
    rec = wiring.assemble(rid, "Assign", spans,
                          wiring.ClockOffsetEstimator())
    assert rec is not None and not rec.stitched
    assert rec.stages["unknown"] == pytest.approx(0.3)
    assert rec.coverage == pytest.approx(1.0)


def test_assemble_returns_none_without_a_send():
    assert wiring.assemble("nope", "Assign", [],
                           wiring.ClockOffsetEstimator()) is None


# ---------------------------------------------------------------------------
# Sentinel attribution.
# ---------------------------------------------------------------------------


def _fed_ledger(registry, n=24, **kw):
    led = wiring.WireLedger(registry=registry, min_cycles=16, **kw)
    for _ in range(n):
        led.observe(_wrec())
    return led


@pytest.mark.parametrize("kw,cause", [
    # Payload burst above the rolling byte p95 wins attribution even
    # when components also inflated (the burst explains them).
    (dict(wall_s=1.0, bytes_up=50_000_000,
          stages={"decode": 0.6, "reply.gap": 0.3}), "bytes_burst"),
    (dict(wall_s=1.0, stages={"gate.wait": 0.8, "decode": 0.02}),
     "queue"),
    (dict(wall_s=1.0, stages={"decode": 0.8, "gate.wait": 0.01}),
     "decode"),
    (dict(wall_s=1.0, stages={"reply.gap": 0.8, "decode": 0.02}),
     "transfer"),
    # Wall spiked but every component sits at baseline: honest unknown.
    (dict(wall_s=1.0), "unknown"),
])
def test_sentinel_attributes_wire_spikes(kw, cause):
    led = _fed_ledger(pm.Registry())
    try:
        rec = led.observe(_wrec(**kw))
        assert rec.anomaly == cause
        assert led.anomalies == 1
    finally:
        led.close()


def test_sentinel_stays_quiet_below_min_cycles_and_at_baseline():
    led = wiring.WireLedger(registry=pm.Registry(), min_cycles=16)
    try:
        for _ in range(8):
            assert led.observe(_wrec(wall_s=5.0)).anomaly == ""
    finally:
        led.close()
    led2 = _fed_ledger(pm.Registry())
    try:
        assert led2.observe(_wrec()).anomaly == ""
        assert led2.anomalies == 0
    finally:
        led2.close()


def test_sentinel_fires_flight_recorder_with_the_wire_record():
    flight = tracing.FlightRecorder()
    tracer = tracing.TraceCollector(seed=7)
    with tracer.span("wire.context", cat="test"):
        pass
    led = _fed_ledger(pm.Registry(), flight=flight, tracer=tracer)
    try:
        led.observe(_wrec(wall_s=1.0,
                          stages={"reply.gap": 0.9, "decode": 0.02}))
        assert flight.trips == 1
        dump = flight.dumps()[0]
        assert dump["reason"] == "wire_anomaly"
        assert dump["extra"]["cause"] == "transfer"
        wiring.validate_record(dump["extra"]["wire"])
        assert any(s["name"] == "wire.context" for s in dump["spans"])
    finally:
        led.close()


def test_disabled_ledger_records_nothing():
    led = wiring.WireLedger(registry=pm.Registry(), enabled=False)
    try:
        assert led.observe(_wrec()) is None
        assert led.records() == []
    finally:
        led.close()


def test_jsonl_black_box_appends_validated_lines(tmp_path):
    path = tmp_path / "wire.jsonl"
    led = wiring.WireLedger(registry=pm.Registry(), jsonl=str(path))
    try:
        for _ in range(3):
            led.observe(_wrec())
    finally:
        led.close()
    lines = path.read_text().strip().splitlines()
    assert len(lines) == 3
    for line in lines:
        wiring.validate_record(json.loads(line))


def test_statusz_panel_and_chrome_export():
    led = _fed_ledger(pm.Registry(), n=20)
    try:
        panel = led.statusz(last=4)
    finally:
        led.close()
    assert panel["cycles"] == 20
    assert panel["bytes"] == {"up": 20_000, "down": 10_000}
    assert panel["wall"]["p50_ms"] > 0
    assert panel["wall"]["hist"]["counts"], "raw counts for fleet merge"
    assert panel["components"]["decode"]["p50_ms"] > 0
    assert len(panel["records"]) == 4
    for rec in panel["records"]:
        wiring.validate_record(rec)
    # JSON-serializable end to end (the Statusz payload contract).
    json.dumps(panel)
    events = wiring.to_chrome(led.records(last=2))
    assert events and all(e["ph"] == "X" for e in events)
    # Components lay out back-to-back from the cycle start.
    starts = [e["ts"] for e in events if e["args"]["cycle"]
              == events[0]["args"]["cycle"]]
    assert starts == sorted(starts)


# ---------------------------------------------------------------------------
# End-to-end over a real loopback server.
# ---------------------------------------------------------------------------


def _mini_snapshot():
    from tpusched.rpc.codec import snapshot_to_proto
    return snapshot_to_proto(
        [dict(name="n0", allocatable={"cpu": 8000.0,
                                      "memory": float(32 << 30)})],
        [dict(name="p0", requests={"cpu": 500.0,
                                   "memory": float(1 << 30)})],
        [],
    )


def test_wire_ledger_end_to_end_over_grpc(thread_leak_check):
    from tpusched.config import EngineConfig
    from tpusched.rpc import tpusched_pb2 as pb
    from tpusched.rpc.client import SchedulerClient
    from tpusched.rpc.server import make_server

    server, port, svc = make_server("127.0.0.1:0",
                                    config=EngineConfig(mode="fast"))
    server.start()
    try:
        with SchedulerClient(f"127.0.0.1:{port}",
                             wire=svc.wire) as client:
            msg = _mini_snapshot()
            resp = client.assign(msg, packed_ok=True)
            delta = pb.SnapshotDelta(base_id=resp.snapshot_id)
            delta.upsert_pods.append(msg.pods[0])
            client.assign_delta(delta, packed_ok=True)
            assert client.wire_errors == 0
            payload = json.loads(client.statusz().statusz_json)
            metrics_text = client.metrics_text()
    finally:
        server.stop(0)
        svc.close()
    recs = svc.wire.records()
    assert len(recs) == 2
    for r in recs:
        wiring.validate_record(wiring.record_dict(r))
        assert r.rpc == "Assign" and r.source == "call"
        # Loopback + shared span ring: every cycle stitches, the
        # offset is ~0 (one clock), and components cover the wall.
        assert r.stitched
        assert abs(r.offset_s) < 0.05
        assert r.coverage >= 0.9
        assert r.bytes_up > 0 and r.bytes_down > 0
        assert "send.gap" in r.stages and "reply.gap" in r.stages
    # Statusz wire panel rides the same payload as the cycle ledger.
    panel = payload["wire"]
    assert panel["cycles"] == 2
    assert panel["coverage_frac"] >= 0.9
    for rec in panel["records"]:
        wiring.validate_record(rec)
    # Ledger + byte families render in THIS server's Metrics rpc.
    assert "# TYPE scheduler_wire_wall_seconds histogram" in metrics_text
    assert "# TYPE scheduler_wire_anomalies_total counter" in metrics_text
    assert 'scheduler_wire_bytes{direction="up",rpc="Assign"}' \
        in metrics_text
    assert 'scheduler_wire_bytes{direction="down",rpc="Assign"}' \
        in metrics_text
    assert 'scheduler_reply_bytes_count{rpc="Assign"} 2' in metrics_text
    assert 'scheduler_wire_cycles_total{rpc="Assign",source="call"} 2' \
        in metrics_text


def test_injected_wire_stall_fires_sentinel_with_flight_dump(
        thread_leak_check):
    """Acceptance scenario (ISSUE 19): a delay fault at the server.reply
    site — every stage completed, the reply stalled on the wire — must
    trip the wire sentinel with cause=transfer and a flight dump
    carrying the attributed WireRecord."""
    from tpusched.config import EngineConfig
    from tpusched.faults import FaultPlan, FaultRule
    from tpusched.rpc.client import SchedulerClient
    from tpusched.rpc.server import make_server

    flight = tracing.FlightRecorder()
    reg = pm.Registry()
    led = wiring.WireLedger(registry=reg, flight=flight, min_cycles=8)
    # The first cycles pay jit tracing/compile (~0.8 s — the same
    # order as the injected stall), which would set the rolling wall
    # p99's covering-bucket bound ABOVE the stall and mask it. Warm up
    # OUTSIDE the ledger, then ledger only steady-state cycles; the
    # fault site counts the warmup fires, so the stall index is offset.
    warmup, baseline = 3, 11
    stall_at = warmup + baseline
    plan = FaultPlan([FaultRule(site="server.reply", kind="delay",
                                at=frozenset({stall_at}), delay_s=0.8)])
    server, port, svc = make_server("127.0.0.1:0",
                                    config=EngineConfig(mode="fast"),
                                    faults=plan, flight=flight, wire=led)
    server.start()
    try:
        with SchedulerClient(f"127.0.0.1:{port}", wire=svc.wire) as client:
            msg = _mini_snapshot()
            led.enabled = False
            for _ in range(warmup):
                client.assign(msg, packed_ok=True)
            led.enabled = True
            for _ in range(baseline + 1):
                client.assign(msg, packed_ok=True)
    finally:
        server.stop(0)
        svc.close()
    stalled = [r for r in led.records() if r.anomaly]
    assert stalled, "the stalled cycle must trip the wire sentinel"
    rec = stalled[-1]
    assert rec.anomaly == "transfer"
    assert rec.wall_s > 0.7
    # The stall happened AFTER every stage inside the root span — it
    # must land in the unattributed server residue, not a stage.
    assert rec.stages["server.other"] > 0.7
    dumps = [d for d in flight.dumps() if d["reason"] == "wire_anomaly"]
    assert dumps
    assert dumps[-1]["extra"]["cause"] == "transfer"
    wiring.validate_record(dumps[-1]["extra"]["wire"])


# ---------------------------------------------------------------------------
# Fleet merge (tools/statusz.py wire panel).
# ---------------------------------------------------------------------------


def test_statusz_tool_merges_and_renders_the_wire_panel():
    """tools/statusz.py fleet merge over the wire panel: counts and
    byte totals sum; wall/component quantiles re-derive from SUMMED
    bucket counts (exact, not quantile averaging); per-replica clock
    offsets do NOT merge (a fleet offset has no referent); replicas
    without the panel propagate None."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "tpusched_statusz_tool",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "statusz.py"),
    )
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)

    def payload(addr, wall_s, n):
        led = wiring.WireLedger(registry=pm.Registry(), min_cycles=10_000)
        for _ in range(n):
            led.observe(_wrec(wall_s=wall_s,
                              stages={"decode": wall_s / 2}))
        p = dict(address=addr, wire=led.statusz(last=4))
        led.close()
        return p

    a = payload("r1:1", 0.01, 10)
    b = payload("r2:1", 0.5, 10)
    merged = tool.merge_fleet([a, b])
    wire = merged["wire"]
    assert wire["cycles"] == 20
    assert wire["rpcs"] == {"Assign": 20}
    assert wire["bytes"] == {"up": 20 * 1000, "down": 20 * 500}
    # Fleet p99 must reflect the SLOW replica's bucket mass; p50 sits
    # between the two replicas' medians.
    assert wire["wall"]["p99_ms"] > 100.0
    assert 5.0 < wire["wall"]["p50_ms"] < 500.0
    assert wire["components"]["decode"]["p99_ms"] > 50.0
    assert wire["offset_ms"] is None
    text = tool.render_text(merged)
    assert "wire: 20 cycles" in text
    assert "decode" in text
    html_doc = tool.render_html([merged])
    assert "wire ledger" in html_doc
    # Pre-panel replicas: no wire key at all in the fleet view.
    old = tool.merge_fleet([dict(address="old:1"), dict(address="old:2")])
    assert "wire" not in old
