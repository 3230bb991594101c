"""Shape-class registry + boot prewarm (PR 18, ROADMAP item 3).

The registry (tpusched.shapeclass) makes "every program this server
will ever trace" a finite, serializable set derived from
(EngineConfig, Buckets, explain, warm); Engine.prewarm traces all of
it at boot with cause="prewarm" so serving — and a promoted standby's
FIRST request — pays zero XLA compiles. These tests drive a prewarmed
SchedulerService through every registered dispatch path and assert
the serve-cause compile count never moves; the chaos harness
(tools/chaos.py --prewarm) makes the same claim under kill-the-leader.
"""

import logging
import pathlib

import pytest

from tpusched import ledger as ledgering
from tpusched import shapeclass
from tpusched.config import Buckets, EngineConfig
from tpusched.engine import Engine

BK = Buckets.fit(8, 8, 8)


def _serve_compiles() -> int:
    """Process-wide compile count excluding prewarm-cause boot work."""
    return sum(v for cause, v in ledgering.COMPILES.cause_counts().items()
               if cause != shapeclass.CAUSE_PREWARM)


# ---------------------------------------------------------------------------
# Registry formulas pin against the engine's actual bucketing


def test_k_bucket_matches_engine():
    for n in (1, 3, 8, 16, 64):
        for k in range(1, 20):
            assert shapeclass.k_bucket(k, n) == Engine._k_bucket(k, n), \
                (k, n)


def test_frontier_caps_match_engine():
    """frontier_caps(P) must enumerate exactly the cap values
    Engine._frontier_bucket can emit at pods-bucket P — a missed cap
    is a warm_incremental family prewarm never compiles."""
    for P in (8, 64, 128, 512):
        reachable = {Engine._frontier_bucket(est, P)
                     for est in range(1, P + 1)}
        assert reachable == set(shapeclass.frontier_caps(P)), P


def test_small_pods_bucket_has_only_uncapped_frontier():
    assert shapeclass.frontier_caps(8) == (0,)
    assert shapeclass.frontier_caps(64) == (0,)
    assert 64 in shapeclass.frontier_caps(256)


# ---------------------------------------------------------------------------
# Registry construction + wire format


def test_registry_round_trips_through_json():
    reg = shapeclass.build_registry(
        EngineConfig(mode="fast"), BK,
        explain=True, explain_k=3, warm="incremental",
    )
    back = shapeclass.ShapeClassRegistry.from_json(reg.to_json())
    assert back == reg
    assert back.to_json() == reg.to_json()
    assert len(reg) == len(list(reg))
    fams = set(reg.families())
    # The eager "solve" entry point is deliberately absent: no serving
    # path dispatches it, so prewarming it would compile dead weight.
    assert "solve" not in fams
    for expected in ("solve_packed", "score", "score_top1",
                     "solve_explained", "warm_cold_refresh",
                     "warm_refresh", "warm_incremental_cap0"):
        assert expected in fams, expected


def test_registry_rejects_unknown_version_and_missing_buckets():
    reg = shapeclass.build_registry(EngineConfig(mode="fast"), BK)
    import json as _json

    doc = _json.loads(reg.to_json())
    doc["version"] = 99
    with pytest.raises(ValueError, match="version"):
        shapeclass.ShapeClassRegistry.from_json(_json.dumps(doc))
    with pytest.raises(ValueError, match="Buckets"):
        shapeclass.build_registry(EngineConfig(mode="fast"), None)
    with pytest.raises(ValueError, match="warm"):
        shapeclass.build_registry(EngineConfig(mode="fast"), BK,
                                  warm="sideways")


def test_registry_fingerprint_tracks_config():
    a = shapeclass.build_registry(EngineConfig(mode="fast"), BK)
    b = shapeclass.build_registry(EngineConfig(mode="parity"), BK)
    c = shapeclass.build_registry(EngineConfig(mode="fast"),
                                  Buckets.fit(16, 8, 8))
    assert a.config_fingerprint != b.config_fingerprint
    assert a.config_fingerprint != c.config_fingerprint


# ---------------------------------------------------------------------------
# Prewarmed serving: every registered path, zero post-boot compiles


def _mk_cluster():
    nodes = [dict(name=f"n{i}", allocatable={"cpu": 4000.0})
             for i in range(3)]
    pods = [dict(name=f"p{i}", requests={"cpu": 400.0},
                 priority=float(i)) for i in range(6)]
    return nodes, pods


def _prewarmed_service(**kw):
    from tpusched.rpc.server import SchedulerService

    svc = SchedulerService(EngineConfig(mode=kw.pop("mode", "fast")),
                           buckets=BK, prewarm=True, **kw)
    assert svc.wait_prewarmed(timeout=300.0), svc.prewarm_error
    assert svc.prewarm_error is None
    assert svc.prewarm_classes_done == len(svc.registry)
    return svc


def test_prewarmed_fast_warm_incremental_serves_without_compiles():
    """The widest fast-mode surface: full Assign (solve_packed),
    session deltas through cold/incremental warm refresh, full and
    top-k scoring — all prewarmed, so the serve-cause compile count is
    frozen from the first request on."""
    pytest.importorskip("grpc")
    from tpusched.rpc import tpusched_pb2 as pb
    from tpusched.rpc.codec import snapshot_to_proto

    svc = _prewarmed_service(warm="incremental")
    try:
        serve0 = _serve_compiles()
        nodes, pods = _mk_cluster()
        msg = snapshot_to_proto(nodes, pods, [])
        r1 = svc.Assign(pb.AssignRequest(snapshot=msg, packed_ok=True),
                        None)
        assert r1.snapshot_id
        sid = r1.snapshot_id
        for cyc in range(3):
            pods[0]["priority"] = float(10 + cyc)
            delta = pb.SnapshotDelta(base_id=sid)
            delta.upsert_pods.extend(
                snapshot_to_proto([], [pods[0]], []).pods)
            r = svc.Assign(pb.AssignRequest(delta=delta, packed_ok=True),
                           None)
            sid = r.snapshot_id
        full = svc.ScoreBatch(pb.ScoreRequest(snapshot=msg), None)
        assert full.snapshot_id
        topk = svc.ScoreBatch(pb.ScoreRequest(snapshot=msg, top_k=3),
                              None)
        assert topk.k
        text = svc.Metrics(pb.MetricsRequest(), None).prometheus_text
        assert _serve_compiles() == serve0, (
            "prewarmed server paid a request-path compile")
        assert svc._engine.unregistered_compiles == {}
    finally:
        svc.close()
    assert 'scheduler_warm_solves_total{path="cold"}' in text
    assert f"scheduler_registry_classes {len(svc.registry)}" in text
    assert f"scheduler_prewarmed_classes {len(svc.registry)}" in text


def test_prewarmed_explain_and_parity_bitwise_serve_without_compiles():
    """The other registry axes: explain-on (solve_explained + probe
    families take over the Assign path) and parity mode with bitwise
    warm refresh."""
    pytest.importorskip("grpc")
    from tpusched.rpc import tpusched_pb2 as pb
    from tpusched.rpc.codec import snapshot_to_proto

    nodes, pods = _mk_cluster()
    msg = snapshot_to_proto(nodes, pods, [])

    svc = _prewarmed_service(explain=True, explain_k=3)
    try:
        serve0 = _serve_compiles()
        r = svc.Assign(pb.AssignRequest(snapshot=msg, packed_ok=True),
                       None)
        assert r.snapshot_id
        assert _serve_compiles() == serve0
    finally:
        svc.close()

    svc = _prewarmed_service(mode="parity", warm="bitwise")
    try:
        serve0 = _serve_compiles()
        r1 = svc.Assign(pb.AssignRequest(snapshot=msg, packed_ok=True),
                        None)
        sid = r1.snapshot_id
        for cyc in range(2):
            pods[0]["priority"] = float(20 + cyc)
            delta = pb.SnapshotDelta(base_id=sid)
            delta.upsert_pods.extend(
                snapshot_to_proto([], [pods[0]], []).pods)
            sid = svc.Assign(
                pb.AssignRequest(delta=delta, packed_ok=True), None
            ).snapshot_id
        assert _serve_compiles() == serve0
        assert svc._engine.unregistered_compiles == {}
    finally:
        svc.close()


def test_prewarm_covers_engine_level_entry_points():
    """score_top1 has no rpc of its own but is registered + prewarmed:
    an engine-level dispatch at the registry's buckets after prewarm
    is compile-free too."""
    from tpusched.snapshot import SnapshotBuilder

    cfg = EngineConfig(mode="fast")
    eng = Engine(cfg)
    try:
        reg = shapeclass.build_registry(cfg, BK)
        report = eng.prewarm(reg)
        assert report["cancelled"] is False
        assert report["classes"] == len(reg)
        serve0 = _serve_compiles()
        nodes, pods, running = shapeclass.prewarm_records(cfg)
        b = SnapshotBuilder(cfg, buckets=BK)
        for n in nodes:
            b.add_node(**n)
        for p in pods:
            b.add_pod(**p)
        for r in running:
            b.add_running_pod(**{k: v for k, v in r.items()
                                 if k != "name"})
        snap, _ = b.build()
        snap = eng.put(snap)
        eng.score_top1(snap)
        eng.solve_async(snap).result()
        eng.score_topk_async(snap, 3).result()
        assert _serve_compiles() == serve0
        # Prewarm work is attributed, not hidden: the cause ledger saw
        # this engine's boot traces as "prewarm".
        assert ledgering.COMPILES.cause_counts().get(
            shapeclass.CAUSE_PREWARM, 0) >= report["compiles"] > 0
    finally:
        eng.close()


def test_unregistered_family_is_counted_and_logged_not_fatal(caplog):
    """A program traced OUTSIDE the attached registry (here: the warm
    path, with a warm-less registry attached) still serves — it is
    counted in Engine.unregistered_compiles and logged so the gap gets
    added to build_registry, never turned into an error."""
    from tpusched.device_state import DeviceSnapshot

    cfg = EngineConfig(mode="fast")
    eng = Engine(cfg)
    try:
        eng.prewarm(shapeclass.build_registry(cfg, BK, warm=None))
        assert eng.unregistered_compiles == {}
        nodes, pods, running = shapeclass.prewarm_records(cfg)
        ds = DeviceSnapshot(cfg, BK, mesh=eng.mesh)
        ds.full_load(nodes, pods, running)
        with caplog.at_level(logging.WARNING, "tpusched.engine"):
            result = eng.solve_warm(ds)
        assert result is not None
        assert eng.unregistered_compiles.get("warm_cold_refresh") == 1
        assert any("outside the attached shape-class registry"
                   in r.message for r in caplog.records)
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# Surfaces: Health, ReplicaSet gating, close() cancellation


def test_health_reports_prewarm_complete():
    pytest.importorskip("grpc")
    from tpusched.rpc import tpusched_pb2 as pb
    from tpusched.rpc.server import SchedulerService

    svc = SchedulerService(EngineConfig(mode="fast"))
    try:
        h = svc.Health(pb.HealthRequest(), None)
        # No prewarm configured: the server is as warm as it will ever
        # get, so the field reads True and wait_caught_up gates
        # uniformly across prewarming and plain fleets.
        assert h.prewarm_complete is True
        text = svc.Metrics(pb.MetricsRequest(), None).prometheus_text
        assert "scheduler_registry_classes 0" in text
        assert "scheduler_prewarmed_classes 0" in text
    finally:
        svc.close()


def test_prewarm_requires_explicit_buckets():
    pytest.importorskip("grpc")
    from tpusched.rpc.server import SchedulerService

    with pytest.raises(ValueError, match="buckets"):
        SchedulerService(EngineConfig(mode="fast"), prewarm=True)


def test_replicaset_wait_caught_up_gates_on_prewarm():
    """A standby is only 'caught up' once it is also COMPILED: the
    chaos harness kills the leader right after this returns True, and
    the promotion must serve its first Assign with zero new compiles."""
    pytest.importorskip("grpc")
    from tpusched.replicate import ReplicaSet

    fleet = ReplicaSet(2, config=EngineConfig(mode="fast"),
                       buckets=BK, prewarm=True)
    try:
        assert fleet.wait_caught_up(timeout=300.0)
        assert all(svc.prewarm_complete for svc in fleet.services)
        assert fleet.followers[1].prewarmed
    finally:
        fleet.close()


def test_close_cancels_inflight_prewarm():
    """close() racing the boot prewarm must stop it after the
    in-flight class (a daemon thread left inside XLA at interpreter
    exit aborts the process) — and never wedge prewarm_complete."""
    pytest.importorskip("grpc")
    from tpusched.rpc.server import SchedulerService

    svc = SchedulerService(EngineConfig(mode="fast"), buckets=BK,
                           prewarm=True)
    svc.close()
    assert svc.prewarm_complete
    t = svc._prewarm_thread
    assert t is not None and not t.is_alive()


# ---------------------------------------------------------------------------
# Persistent compilation cache wiring


@pytest.mark.parametrize("from_env", [False, True],
                         ids=["checkout-default", "env-placed"])
def test_enable_persistent_cache_sets_jax_config(tmp_path, monkeypatch,
                                                 from_env):
    """One helper places the cache: $JAX_COMPILATION_CACHE_DIR when set
    (JAX reads it itself, so the code sets no directory), else
    <checkout>/.xla_cache."""
    import jax

    prev = jax.config.jax_compilation_cache_dir
    sentinel = str(tmp_path / "untouched")
    try:
        jax.config.update("jax_compilation_cache_dir", sentinel)
        if from_env:
            env_dir = str(tmp_path / "from-env")
            monkeypatch.setenv(shapeclass.CACHE_ENV, env_dir)
            assert shapeclass.enable_persistent_cache() == env_dir
            assert jax.config.jax_compilation_cache_dir == sentinel
        else:
            monkeypatch.delenv(shapeclass.CACHE_ENV, raising=False)
            want = str(pathlib.Path(__file__).resolve().parents[1]
                       / ".xla_cache")
            assert shapeclass.enable_persistent_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
            assert pathlib.Path(want).is_dir()
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
