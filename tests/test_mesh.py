"""Mesh/sharding tests (SURVEY.md C14, §4 item 3): run the full solve on
2D device meshes over the 8 virtual CPU devices provisioned by conftest
and assert the sharded result is identical to the single-device result.
The SPMD partitioner must insert collectives (cross-'n' argmax
reductions, cross-'p' gathers) without changing semantics.

ROADMAP item 1 (the PR 6 quarantine, removed here): true-2D meshes —
both axes > 1 — diverged because this jax version's partitioner
mis-routes the replicated|'p'-sharded member-merge concatenates. The
kernels now thread the mesh down to explicit sharding constraints at
those merges (tpusched/shardctx.py), and every shape is bit-exact.
Each sharded step is a FRESH closure over its mesh: jax caches traced
jaxprs per (function identity, avals) — shardings only enter at
lowering — so reusing one function object across meshes would silently
reuse the first trace's constraints (shardctx module docstring).
"""

import numpy as np
import pytest
import jax

from tpusched import Engine, EngineConfig
from tpusched.engine import _sat_tables
from tpusched.kernels.assign import score_batch, solve_rounds, solve_sequential
from tpusched.mesh import make_mesh, matrix_sharding, shard_snapshot, snapshot_shardings
from tpusched.synth import config2_scale, make_cluster

MESH_SHAPES = [
    (8, 1),
    (4, 2),
    (2, 4),
    (1, 8),
]


def _snap(rng, **kw):
    return make_cluster(
        rng, 24, 16, taint_frac=0.3, toleration_frac=0.3, selector_frac=0.2,
        affinity_frac=0.3, spread_frac=0.3, interpod_frac=0.3, **kw
    )


def test_snapshot_shardings_builds(rng):
    """snapshot_shardings must mirror the snapshot pytree structure
    exactly (regression: it used to crash on the missing sigs field)."""
    snap, _ = _snap(rng)
    mesh = make_mesh((2, 4), devices=jax.devices()[:8])
    spec = snapshot_shardings(mesh, snap)
    flat_snap = jax.tree.leaves(snap)
    flat_spec = jax.tree.leaves(
        spec, is_leaf=lambda x: hasattr(x, "spec")
    )
    assert len(flat_snap) == len(flat_spec)


def _seq_step(cfg, mesh=None):
    def step(s):
        node_sat_t, member_sat_t = _sat_tables(s, mesh)
        return solve_sequential(cfg, s, node_sat_t, member_sat_t,
                                mesh=mesh)
    return step


@pytest.mark.parametrize("shape", MESH_SHAPES)
def test_sharded_sequential_matches_single(rng, shape):
    snap, _ = _snap(rng)
    cfg = EngineConfig()

    single = jax.jit(_seq_step(cfg))(snap)
    mesh = make_mesh(shape, devices=jax.devices()[: shape[0] * shape[1]])
    sharded_in = shard_snapshot(mesh, snap)
    sharded = jax.jit(_seq_step(cfg, mesh))(sharded_in)
    np.testing.assert_array_equal(np.asarray(single[0]), np.asarray(sharded[0]))
    np.testing.assert_allclose(
        np.asarray(single[2]), np.asarray(sharded[2]), rtol=1e-6
    )


@pytest.mark.parametrize("shape", [
    (4, 2),
    (1, 8),
])
def test_sharded_fast_matches_single(rng, shape):
    snap, _ = _snap(rng)
    cfg = EngineConfig(mode="fast")

    def mk(mesh=None):
        def step(s):
            node_sat_t, member_sat_t = _sat_tables(s, mesh)
            return solve_rounds(cfg, s, node_sat_t, member_sat_t,
                                mesh=mesh)
        return step

    single = jax.jit(mk())(snap)
    mesh = make_mesh(shape, devices=jax.devices()[: shape[0] * shape[1]])
    sharded = jax.jit(mk(mesh))(shard_snapshot(mesh, snap))
    np.testing.assert_array_equal(np.asarray(single[0]), np.asarray(sharded[0]))


@pytest.mark.parametrize("shape", [(4, 1), (2, 2)])
def test_sharded_fast_nosig_state_bitwise(shape):
    """No-signature fast rounds (BASELINE config 2): placements AND node
    usage equal the single-device solve bit for bit — every pod-axis f32
    reduction is exact, so sharding cannot reorder its rounding (a
    duplicate scatter-add of `used` once could)."""
    snap, _ = config2_scale(np.random.default_rng(0), 300, 80,
                            with_qos=True)
    cfg = EngineConfig(mode="fast")
    mesh = make_mesh(shape)
    single = Engine(cfg).solve(snap)
    sharded = Engine(cfg, mesh=mesh).solve(snap)
    np.testing.assert_array_equal(single.assignment, sharded.assignment)
    np.testing.assert_array_equal(single.final_used, sharded.final_used)


@pytest.mark.parametrize("shape", [
    (2, 4),
])
def test_sharded_score_batch_matches_single(rng, shape):
    snap, _ = _snap(rng)
    cfg = EngineConfig()

    def mk(mesh=None):
        def step(s):
            node_sat_t, member_sat_t = _sat_tables(s, mesh)
            return score_batch(cfg, s, node_sat_t, member_sat_t,
                               mesh=mesh)
        return step

    f1, s1 = jax.jit(mk())(snap)
    mesh = make_mesh(shape, devices=jax.devices()[:8])
    jitted = jax.jit(
        mk(mesh), out_shardings=(matrix_sharding(mesh), matrix_sharding(mesh))
    )
    f2, s2 = jitted(shard_snapshot(mesh, snap))
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


def test_default_mesh_uses_all_devices():
    mesh = make_mesh()
    assert mesh.devices.size == len(jax.devices())


def test_dryrun_multichip_entry():
    """The driver-facing dryrun must pass in-process (8 devices here)."""
    import __graft_entry__ as g

    g.dryrun_multichip(8)
