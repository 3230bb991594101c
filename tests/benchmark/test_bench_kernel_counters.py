"""The kernel counters' readers (benchmark/metrics/solve_rounds.py and
pair_reverts_per_placed.py): the sums over the window's `fetch.join`
spans, and nothing read where there is nothing to read."""

import types

import pytest

import benchtiny
import devtrace


def _run(joins, placed):
    spans = [types.SimpleNamespace(name="fetch.join", attrs=a) for a in joins]
    spans.append(types.SimpleNamespace(name="delta.apply", attrs={}))
    return dict(spans=spans, cycles=[types.SimpleNamespace(placed=n)
                                     for n in placed])


def read(name, run):
    return devtrace.read_metric(benchtiny.BENCH, name, run)


def test_solve_rounds_is_rounds_per_cycle():
    run = _run([dict(rounds=3, reverts=0), dict(rounds=9, reverts=4)],
               [10, 20])
    assert read("solve_rounds", run) == 6.0


def test_pair_reverts_per_placed_is_reverts_over_placed():
    run = _run([dict(rounds=3, reverts=6), dict(rounds=9, reverts=4)],
               [10, 30])
    assert read("pair_reverts_per_placed", run) == 0.25
    run = _run([dict(rounds=1, reverts=0)], [5])
    assert read("pair_reverts_per_placed", run) == 0.0


@pytest.mark.parametrize("name", ["solve_rounds", "pair_reverts_per_placed"])
@pytest.mark.parametrize("joins, placed", [
    ([], [4, 4]),                               # no spans
    ([dict(what="Assign solve")], [4]),         # a program without them
    ([dict(rounds=2, reverts=1), dict(what="x")], [3, 3]),
], ids=["no_spans", "no_counters", "one_lacks_them"])
def test_nothing_to_read(name, joins, placed):
    assert read(name, _run(joins, placed)) is None


@pytest.mark.parametrize("name, placed", [("solve_rounds", []),
                                          ("pair_reverts_per_placed", [0, 0])])
def test_no_cycles_or_no_placements(name, placed):
    assert read(name, _run([dict(rounds=2, reverts=0)] * 2, placed)) is None
