"""Config surface (SURVEY.md §5 "Config / flag system"): every live
EngineConfig field round-trips through from_dict/YAML, unknown keys are
rejected, and the sidecar reaches the mesh/ring paths from config alone
(VERDICT round-4 #8: ring_counts was unreachable from YAML and
mesh_shape had zero consumers)."""

import dataclasses

import numpy as np
import pytest

from tpusched import EngineConfig
from tpusched.config import PluginWeights, QoSConfig, load_config


def test_from_dict_round_trips_every_live_field():
    d = {
        "resources": ["cpu", "memory", "pods", "nvidia.com/gpu"],
        "score_resource_weights": {"cpu": 2.0, "memory": 1.0},
        "weights": {"least_requested": 3.0, "topology_spread": 5.0},
        "qos": {"qos_gain": 500.0, "preemption_margin": 1.0},
        "mode": "fast",
        "max_rounds": 17,
        "tie_break": "seeded",
        "tie_seed": 99,
        "preemption": True,
        "ring_counts": True,
        "mesh_shape": [4, 2],
        "compact_cap": 256,
    }
    cfg = EngineConfig.from_dict(d)
    assert cfg.resources == ("cpu", "memory", "pods", "nvidia.com/gpu")
    assert cfg.score_resource_weights["cpu"] == 2.0
    assert cfg.weights.least_requested == 3.0
    assert cfg.weights.topology_spread == 5.0
    assert cfg.qos.qos_gain == 500.0
    assert cfg.mode == "fast"
    assert cfg.max_rounds == 17
    assert cfg.tie_break == "seeded"
    assert cfg.tie_seed == 99
    assert cfg.preemption is True
    assert cfg.ring_counts is True
    assert cfg.mesh_shape == (4, 2)
    assert cfg.compact_cap == 256


def test_from_dict_rejects_unknown_keys():
    with pytest.raises(ValueError, match="typo"):
        EngineConfig.from_dict({"typo": 1})


def test_every_engineconfig_field_is_yaml_reachable():
    """No dead config: every dataclass field either round-trips through
    from_dict or is explicitly exempt (none currently)."""
    settable = {
        "resources", "score_resource_weights", "weights", "qos", "mode",
        "max_rounds", "tie_break", "tie_seed", "preemption",
        "ring_counts", "mesh_shape", "compact_cap",
    }
    fields = {f.name for f in dataclasses.fields(EngineConfig)}
    assert fields == settable, (
        f"EngineConfig fields drifted from from_dict coverage: "
        f"{fields ^ settable}"
    )


def test_load_config_yaml(tmp_path):
    p = tmp_path / "profile.yaml"
    p.write_text(
        "mode: fast\nring_counts: true\nmesh_shape: [8, 1]\n"
        "weights:\n  balanced_allocation: 4.0\n"
    )
    cfg = load_config(str(p))
    assert cfg.mode == "fast"
    assert cfg.ring_counts is True
    assert cfg.mesh_shape == (8, 1)
    assert cfg.weights.balanced_allocation == 4.0


def test_sidecar_builds_mesh_and_ring_from_config():
    """A YAML-shaped config with mesh_shape + ring_counts must produce
    a serving sidecar whose engine runs the mesh/ring path — and its
    Assign must agree with a single-device engine on the same
    snapshot."""
    from tpusched import Engine
    from tpusched.rpc.client import SchedulerClient, assign_response_arrays
    from tpusched.rpc.codec import snapshot_to_proto
    from tpusched.rpc.server import make_server
    from tpusched.synth import make_cluster

    cfg = EngineConfig.from_dict({
        "mode": "parity", "ring_counts": True, "mesh_shape": [4, 2],
    })
    nodes, pods, running = make_cluster(
        np.random.default_rng(77), 24, 8, spread_frac=0.4,
        interpod_frac=0.3, as_records=True,
    )
    msg = snapshot_to_proto(nodes, pods, running)
    server, port, svc = make_server("127.0.0.1:0", config=cfg)
    assert svc._engine.mesh is not None, "config must put the engine on a mesh"
    assert svc._engine.mesh.devices.shape == (4, 2)
    server.start()
    try:
        with SchedulerClient(f"127.0.0.1:{port}") as client:
            resp = client.assign(msg, packed_ok=True)
            _, _, node_idx, _, _ = assign_response_arrays(resp)
        from tpusched.rpc.codec import decode_snapshot

        snap, meta = decode_snapshot(msg, EngineConfig(mode="parity"))
        ref = Engine(EngineConfig(mode="parity")).solve(snap)
        np.testing.assert_array_equal(
            node_idx, ref.assignment[: meta.n_pods]
        )
    finally:
        server.stop(0)
