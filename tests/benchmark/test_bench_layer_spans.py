"""The per-layer readers of the client's and the sidecar's stage spans
(benchmark/metrics/): their arithmetic on hand-built spans and cycles,
and nothing read where their spans are absent (a program without the
stage spans)."""

from types import SimpleNamespace

import pytest

import benchtiny
import devtrace

STAGE_READERS = {
    "client_diff_ms": ("client.check", "client.diff", "client.remember"),
    "store_advance_ms": ("store.advance",),
    "apply_records_ms": ("apply.records",),
    "apply_encode_ms": ("apply.encode",),
    "apply_order_ms": ("apply.order",),
    "apply_scatter_ms": ("apply.scatter",),
}
ALL = tuple(STAGE_READERS) + ("client_scan_ratio", "sidecar_self_ms")

_ids = iter(range(1, 1 << 30))


def _span(name, t, dur, parent=0, **attrs):
    return SimpleNamespace(name=name, t_wall=t, dur_s=dur,
                           span_id=next(_ids), parent_id=parent,
                           trace_id="t", attrs=attrs)


def _read(name, spans, n_cycles):
    run = dict(cycles=[object()] * n_cycles, spans=spans, profile=None)
    return devtrace.read_metric(benchtiny.BENCH, name, run)


@pytest.mark.parametrize("name", sorted(STAGE_READERS))
def test_stage_reader_sums_its_spans_per_cycle(name):
    wanted = STAGE_READERS[name]
    spans = [_span(n, 10.0 * i, 0.010 * (i + 1))
             for i, n in enumerate(wanted)]
    # A stage recorded twice in one cycle counts both stretches.
    spans.append(_span(wanted[0], 50.0, 0.004))
    # Other stages, and the apply that encloses the apply's stages,
    # are not read.
    spans += [_span("delta.apply", 0.0, 9.0), _span("decode", 1.0, 2.0),
              _span("client.send", 3.0, 4.0)]
    total = sum(0.010 * (i + 1) for i in range(len(wanted))) + 0.004
    assert _read(name, spans, 4) == pytest.approx(total * 1e3 / 4)


def test_scan_ratio_is_records_walked_per_record_shipped():
    spans = [_span("client.diff", 0.0, 0.2, visited=300_000, shipped=600,
                   bytes=90_000),
             _span("client.diff", 1.0, 0.2, visited=300_000, shipped=900,
                   bytes=120_000),
             _span("client.check", 2.0, 0.05, records=150_000)]
    assert _read("client_scan_ratio", spans, 2) == pytest.approx(400.0)
    # Nothing shipped: no ratio.
    empty = [_span("client.diff", 0.0, 0.2, visited=10, shipped=0)]
    assert _read("client_scan_ratio", empty, 1) is None


def test_sidecar_self_time_subtracts_the_union_of_direct_children():
    r1 = _span("server.Assign", 100.0, 1.0)
    r2 = _span("server.Assign", 102.0, 0.5)
    apply = _span("delta.apply", 100.1, 0.3, parent=r1.span_id)
    spans = [
        r1, r2, apply,
        # Overlaps delta.apply: [100.1, 100.5] covered once.
        _span("engine.fetch", 100.3, 0.2, parent=r1.span_id),
        # Runs past the root's end: clipped to [100.9, 101.0].
        _span("reply.pack", 100.9, 0.3, parent=r1.span_id),
        # A grandchild is not a direct child.
        _span("apply.order", 100.6, 0.2, parent=apply.span_id),
        # Another root's child.
        _span("decode", 102.1, 0.1, parent=r2.span_id),
        _span("client.diff", 99.0, 0.5),
    ]
    # r1: 1.0 - (0.4 + 0.1); r2: 0.5 - 0.1; over 2 cycles.
    want = ((1.0 - 0.5) + (0.5 - 0.1)) * 1e3 / 2
    assert _read("sidecar_self_ms", spans, 2) == pytest.approx(want)


@pytest.mark.parametrize("name", ALL)
def test_reader_returns_nothing_without_its_spans(name):
    # The parent's spans only: the cycle, the send, the request, the
    # apply as one span.
    spans = [_span("client.send", 0.0, 1.0),
             _span("delta.apply", 0.2, 0.3, h2d_bytes=7)]
    if name != "sidecar_self_ms":
        spans.append(_span("server.Assign", 0.1, 0.8))
    assert _read(name, spans, 3) is None
    assert _read(name, [], 0) is None
