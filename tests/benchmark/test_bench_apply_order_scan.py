"""The reader of the delta apply's upkeep counts
(benchmark/metrics/apply_order_scan_ratio.py): its arithmetic on
hand-built `delta.apply` spans, and nothing read where the counts are
absent (a program whose apply does not report them) or nothing churned."""

from types import SimpleNamespace

import pytest

import benchtiny
import devtrace

NAME = "apply_order_scan_ratio"

_ids = iter(range(1, 1 << 30))


def _span(name, t, dur, parent=0, **attrs):
    return SimpleNamespace(name=name, t_wall=t, dur_s=dur,
                           span_id=next(_ids), parent_id=parent,
                           trace_id="t", attrs=attrs)


def _read(spans, n_cycles):
    run = dict(cycles=[object()] * n_cycles, spans=spans, profile=None)
    return devtrace.read_metric(benchtiny.BENCH, NAME, run)


def test_order_scan_ratio_is_names_examined_per_record_applied():
    spans = [_span("delta.apply", 0.0, 0.3, h2d_bytes=700_000,
                   path="delta", order_visited=3_000, churn_records=600),
             _span("delta.apply", 1.0, 0.3, h2d_bytes=700_000,
                   path="delta", order_visited=5_000, churn_records=1_000),
             # The apply's stages carry no counts.
             _span("apply.order", 1.1, 0.02)]
    assert _read(spans, 2) == pytest.approx(5.0)


@pytest.mark.parametrize("spans, n_cycles", [
    # An apply that reports no upkeep counts, as one without them does.
    ([_span("client.send", 0.0, 1.0), _span("server.Assign", 0.1, 0.8),
      _span("delta.apply", 0.2, 0.3, h2d_bytes=7)], 3),
    # Nothing churned.
    ([_span("delta.apply", 0.0, 0.1, h2d_bytes=0, path="delta",
            order_visited=0, churn_records=0)], 1),
    # No apply at all.
    ([_span("client.send", 0.0, 1.0)], 1),
    ([], 0),
], ids=["no_counts", "no_churn", "no_apply", "empty"])
def test_order_scan_ratio_returns_nothing_without_counts(spans, n_cycles):
    assert _read(spans, n_cycles) is None
