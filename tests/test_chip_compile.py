"""Compiles for a described TPU v5e (no chip attached): the served main
path's programs at real width must pass the chip's compiler and fit its
16 GB. Nothing here runs; a pass is not a chip run.

This is the only file that describes the topology: one process at a
time may hold the TPU library, so the description happens inside a
module fixture (never at import), and only the xdist worker given this
file loads it."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, SingleDeviceSharding

from tpusched import EngineConfig
from tpusched.engine import _pack_solve, solve_core
from tpusched.mesh import NODE_AXIS, POD_AXIS, snapshot_shardings
from tpusched.synth import config2_scale

HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out.
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _shapes(snap, shardings):
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        snap, shardings)


def _fits_one_chip(compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes + m.generated_code_size_in_bytes
             - m.alias_size_in_bytes)
    assert 0 < total <= HBM_BYTES, total


@pytest.mark.parametrize("mode", ["parity", "fast"])
def test_packed_solve_compiles_10k_x_5k(topo, mode):
    """BASELINE config 2 at 10k pods x 5k nodes, the sidecar's program."""
    snap, _ = config2_scale(np.random.default_rng(0), 10_000, 5_000,
                            with_qos=True)
    one = SingleDeviceSharding(topo.devices[0])
    cfg = EngineConfig(mode=mode)
    compiled = jax.jit(lambda s: _pack_solve(solve_core(cfg, s))).lower(
        _shapes(snap, jax.tree.map(lambda _: one, snap))).compile()
    _fits_one_chip(compiled)


def test_fast_solve_compiles_on_4x1_mesh(topo):
    snap, _ = config2_scale(np.random.default_rng(0), 2_000, 1_000,
                            with_qos=True)
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1),
                (POD_AXIS, NODE_AXIS))
    cfg = EngineConfig(mode="fast")
    compiled = jax.jit(
        lambda s: _pack_solve(solve_core(cfg, s, mesh=mesh), mesh)
    ).lower(_shapes(snap, snapshot_shardings(mesh, snap))).compile()
    _fits_one_chip(compiled)  # per device
    hlo = compiled.as_text()
    assert "all-reduce" in hlo or "all-gather" in hlo
