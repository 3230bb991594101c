"""Device mesh + sharding layout (SURVEY.md C14).

The 2D mesh maps the problem's two big axes onto hardware
(SURVEY.md §2.3): pending pods shard over the 'p' axis (the DP
analogue), candidate nodes over the 'n' axis (the TP analogue). The
[P, N] feasibility/score matrices shard PS('p','n'); per-pod reductions
over nodes (argmax, NormalizeScore max) become cross-'n' XLA collectives
inserted by the SPMD partitioner; nothing is hand-scheduled.

Multi-host: jax.distributed.initialize() before make_mesh() and the same
code spans slices — ICI within a slice, DCN across (SURVEY.md §5
"Distributed communication backend").
"""

from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from tpusched.snapshot import ClusterSnapshot

POD_AXIS = "p"
NODE_AXIS = "n"


def init_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Multi-host entry (SURVEY.md §5 'Distributed communication
    backend'): initialize jax.distributed so jax.devices() spans every
    host's chips — ICI within a slice, DCN across slices — then build
    meshes as usual; the same solve code runs SPMD with XLA inserting
    the cross-host collectives. With no arguments, relies on the TPU
    environment's auto-detection (GKE/Borg metadata); arguments mirror
    jax.distributed.initialize for manual clusters.

    The reference's analogue is client-go's watch/bind HTTP plumbing —
    its only 'backend' — while compute scaling here rides XLA
    collectives; gRPC stays at the host boundary (SURVEY.md §2.3)."""
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)


def make_mesh(shape: tuple[int, int] | None = None, devices=None) -> Mesh:
    """Mesh of shape (p, n) over the first p*n of `devices` (default
    jax.devices()). Default shape: all devices on the 'p' axis (pod
    sharding scales first; node-axis sharding pays collective cost on
    every per-pod reduction)."""
    devices = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = (len(devices), 1)
    devices = devices[: shape[0] * shape[1]]
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, (POD_AXIS, NODE_AXIS))


def _spec_for(path: str, mesh: Mesh) -> NamedSharding:
    p = PS(POD_AXIS)
    n = PS(NODE_AXIS)
    rep = PS()
    table = {"pods": p, "nodes": n}
    return NamedSharding(mesh, table.get(path, rep))


def snapshot_shardings(mesh: Mesh, snap: ClusterSnapshot) -> ClusterSnapshot:
    """Pytree of NamedShardings matching the snapshot's structure:
    pod-major arrays shard on 'p', node-major on 'n', vocab tables
    (atoms, taint effects, groups, running pods) replicate."""

    def build(sub, path):
        return jax.tree.map(lambda _: _spec_for(path, mesh), sub)

    return ClusterSnapshot(
        nodes=build(snap.nodes, "nodes"),
        pods=build(snap.pods, "pods"),
        running=build(snap.running, "rep"),
        atoms=build(snap.atoms, "rep"),
        sigs=build(snap.sigs, "rep"),
        taint_effect=_spec_for("rep", mesh),
        group_min_member=_spec_for("rep", mesh),
        pdb_allowed=_spec_for("rep", mesh),
    )


def shard_snapshot(mesh: Mesh, snap: ClusterSnapshot) -> ClusterSnapshot:
    """device_put the snapshot with the standard layout."""
    return jax.device_put(snap, snapshot_shardings(mesh, snap))


def matrix_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for [P, N] result matrices."""
    return NamedSharding(mesh, PS(POD_AXIS, NODE_AXIS))


def pod_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PS(POD_AXIS))
