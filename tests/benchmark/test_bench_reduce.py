"""The trace reduction (benchmark/devtrace.py) and the per-layer
readers that use it: busy/idle share, the packed solve's device time,
and the breakdown, on a hand-built trace whose answers are known and
on a small trace recorded on the chip (trace_small.json, beside this
file: the device plane and the benchmark's annotations of a stretch
of a k8s5k-fast.stream run)."""

import json
import pathlib

import pytest

import benchtiny
import devtrace

HERE = pathlib.Path(__file__).resolve().parent
MS = 1e6   # ns


def _synthetic():
    # Stretch of 100 ms from the clock mark at t=0. Device busy
    # [10,30] and [25,40] (merged: 30 ms) and [70,80] (10 ms).
    dev = dict(name="/device:TPU:0", lines=[
        dict(name="XLA Modules", events=[
            ("jit__solve_packed(1)", 10 * MS, 30 * MS),
            ("jit_scatter_rows(2)", 70 * MS, 10 * MS)]),
        dict(name="XLA Ops", events=[
            ("fusion.1", 10 * MS, 20 * MS), ("while.2", 25 * MS, 15 * MS),
            ("scatter.3", 70 * MS, 10 * MS)]),
    ])
    host = dict(name="/host:CPU", lines=[dict(name="python", events=[
        (devtrace.CLOCK, 0.0, 0.01 * MS),
        ("bench.assign", 5 * MS, 40 * MS),
        ("bench.generator", 45 * MS, 25 * MS),
    ])])
    return [dev, host]


def test_synthetic_trace():
    # A program span (wall clock) covering 80..100 ms: delta.apply.
    span = dict(t_wall=1000.0 + 0.080, dur_s=0.020, name="delta.apply")
    red = devtrace.reduce_profile(_synthetic(), 0.100, 1000.0, [span])
    assert red["busy_s"] == pytest.approx(0.040)
    run = dict(profile=red)
    idle = devtrace.read_metric(benchtiny.BENCH, "device_idle_pct", run)
    assert idle == pytest.approx(60.0)
    solve = devtrace.read_metric(benchtiny.BENCH, "solve_device_ms", run)
    assert solve == pytest.approx(30.0)
    gaps = red["breakdown"]["idle_gaps"]
    # gaps, longest first: 40-70 (mostly bench.generator), 80-100
    # (delta.apply), 0-10 (inside bench.assign, no stage span)
    assert [g[0] for g in gaps] == ["bench.generator", "delta.apply",
                                    devtrace.UNSPANNED]
    assert [g[1] for g in gaps] == pytest.approx([0.030, 0.020, 0.010])
    ops = red["breakdown"]["device_ops"]
    assert ops[0] == ["fusion.1", pytest.approx(0.020)]


def test_nested_stage_spans_count_once():
    # One idle gap, 10..110 ms, inside one bench.assign: delta.apply
    # covers 40 ms of it and its two apply.* stages the same 40 ms, so
    # 60 ms is the client's own unspanned work.
    dev = dict(name="/device:TPU:0", lines=[dict(name="XLA Ops", events=[
        ("fusion.1", 0.0, 10 * MS), ("fusion.2", 110 * MS, 10 * MS)])])
    host = dict(name="/host:CPU", lines=[dict(name="python", events=[
        (devtrace.CLOCK, 0.0, 0.01 * MS), ("bench.assign", 0.0, 120 * MS)])])
    spans = [dict(t_wall=1000.0 + a, dur_s=d, name=n) for n, a, d in (
        ("server.Assign", 0.005, 0.110), ("delta.apply", 0.010, 0.040),
        ("apply.order", 0.010, 0.020), ("apply.encode", 0.030, 0.020))]
    red = devtrace.reduce_profile([dev, host], 0.120, 1000.0, spans)
    [(label, seconds)] = red["breakdown"]["idle_gaps"]
    assert label == devtrace.UNSPANNED
    assert seconds == pytest.approx(0.100)


def test_no_device_plane_is_an_error():
    with pytest.raises(RuntimeError):
        devtrace.reduce_profile(_synthetic()[1:], 0.1, 0.0)


def test_readers_return_nothing_without_a_trace():
    run = dict(profile=None, cycles=[], spans=[])
    for name in ("device_idle_pct", "solve_device_ms", "client_ms",
                 "apply_ms", "h2d_kb", "solve_wait_ms", "reply_ms"):
        assert devtrace.read_metric(benchtiny.BENCH, name, run) is None


def test_recorded_chip_trace():
    rec = json.loads((HERE / "trace_small.json").read_text())
    red = devtrace.reduce_profile(rec["planes"], rec["slice_s"],
                                  rec["clock_wall"], rec["spans"])
    want = rec["expect"]
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert red["breakdown"] == json.loads(json.dumps(want["breakdown"]))
    run = dict(profile=red)
    idle = devtrace.read_metric(benchtiny.BENCH, "device_idle_pct", run)
    solve = devtrace.read_metric(benchtiny.BENCH, "solve_device_ms", run)
    assert 0.0 < idle < 100.0
    assert solve > 0.0
    n, total = next(v for k, v in red["modules"].items()
                    if "solve_packed" in k)
    assert solve == pytest.approx(total * 1e3 / n)
    assert len(red["breakdown"]["device_ops"]) <= devtrace.TOP
    assert len(red["breakdown"]["idle_gaps"]) <= devtrace.TOP
