"""Pipelined batch stream (SURVEY.md §2.3 PP analogue): results must be
identical to unpipelined solves, in order, for independent snapshots."""

import numpy as np

from tpusched import Engine, EngineConfig
from tpusched.pipeline import solve_stream
from tpusched.synth import make_cluster


def _batches(n=4, pods=24, nodes=8):
    out = []
    for seed in range(n):
        rng = np.random.default_rng(500 + seed)
        out.append(make_cluster(rng, pods, nodes, spread_frac=0.3))
    return out


def test_stream_matches_sequential():
    cfg = EngineConfig(mode="fast")
    eng = Engine(cfg)
    batches = _batches()
    expected = [eng.solve(s) for s, _ in batches]
    got = list(solve_stream(eng, batches))
    assert len(got) == len(batches)
    for (meta_in, exp), (meta_out, res) in zip(
        [(m, e) for (_, m), e in zip(batches, expected)], got
    ):
        assert meta_out is meta_in, "metas must come back in order"
        np.testing.assert_array_equal(res.assignment, exp.assignment)
        np.testing.assert_array_equal(res.final_used, exp.final_used)
        assert res.rounds == exp.rounds


def test_stream_with_decode_fn():
    """decode callback path: items are seeds, decoded lazily."""
    cfg = EngineConfig(mode="fast")
    eng = Engine(cfg)

    def decode(seed):
        rng = np.random.default_rng(700 + seed)
        return make_cluster(rng, 16, 8)

    got = list(solve_stream(eng, [0, 1, 2], decode))
    assert len(got) == 3
    for _, res in got:
        assert (res.assignment >= -1).all()


def test_solve_async_matches_sync():
    """Engine.solve_async (round 6: the dispatch+background-fetch
    primitive behind solve_stream AND the sidecar's staged handlers)
    returns exactly Engine.solve's result."""
    import numpy as np

    from tpusched import Engine, EngineConfig
    from tpusched.synth import make_cluster

    rng = np.random.default_rng(9)
    snap, _ = make_cluster(rng, 40, 8)
    eng = Engine(EngineConfig(mode="fast"))
    try:
        snap = eng.put(snap)
        sync = eng.solve(snap)
        pending = eng.solve_async(snap)
        # The caller's thread is free here — that window is the feature.
        async_res = pending.result()
        np.testing.assert_array_equal(sync.assignment, async_res.assignment)
        np.testing.assert_array_equal(sync.commit_key, async_res.commit_key)
        np.testing.assert_allclose(sync.final_used, async_res.final_used)
        assert async_res.solve_seconds > 0
    finally:
        eng.close()
