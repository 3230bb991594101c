"""Fast-vs-parity divergence numbers (VERDICT weak #7): constraint-free
snapshots must agree EXACTLY; contended ones must stay valid and close."""

import json

import pytest

from tpusched.divergence import PRESETS, DivergenceStats, main, measure


def test_plain_preset_same_throughput():
    """No constraints: both modes must place (nearly) the same NUMBER of
    pods. Node choices — and at full-cluster margins even which pods
    land — legitimately differ: load-balancing scores couple every
    pod's choice to all earlier commits, so the two orders reach
    different but equally valid packings (tests/test_fast.py pins the
    uncoupled case where agreement is exact)."""
    stats = measure("plain", seeds=4, n_pods=40, n_nodes=12)
    assert stats.fast_violations == 0
    assert abs(stats.placed_delta) <= stats.seeds, stats.row()


def test_mixed_preset_valid_and_close():
    stats = measure("mixed", seeds=4, n_pods=40, n_nodes=12)
    assert stats.fast_violations == 0, stats.row()
    # Under heavy pairwise contention the two orders reach different
    # valid fixpoints; measured gap stays within a few percent of pods
    # (those pods retry next batch in a live cluster). Parity mode is
    # the way out when exact stock placements are required.
    assert stats.placed_delta >= -0.08 * stats.pods, stats.row()


def test_pairwise_preset_valid():
    stats = measure("pairwise", seeds=3, n_pods=40, n_nodes=12)
    assert stats.fast_violations == 0, stats.row()


def test_main_prints_one_row_per_preset(capsys):
    """`python -m tpusched.divergence` with no --preset reports every
    preset, one JSON row each, with DivergenceStats.row()'s keys."""
    main(["--seeds", "1", "--pods", "12", "--nodes", "6"])
    rows = [json.loads(line)
            for line in capsys.readouterr().out.splitlines()]
    assert [r["preset"] for r in rows] == sorted(PRESETS)
    keys = set(DivergenceStats("plain", 1).row())
    for r in rows:
        assert set(r) == keys
        assert r["seeds"] == 1 and r["pods"] == 12
        assert r["fast_violations"] == 0, r


def test_main_incremental_needs_warm_audit(capsys):
    with pytest.raises(SystemExit) as exc:
        main(["--incremental"])
    assert exc.value.code == 2
    assert "--incremental requires --warm-audit" in capsys.readouterr().err
