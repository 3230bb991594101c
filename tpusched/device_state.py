"""Device-resident cluster state (SURVEY.md §7 hard part 6, serving
form): after the first upload, delta cycles mutate the ON-DEVICE
snapshot in place instead of rebuilding + re-uploading the cluster.

This is the scheduling analogue of what continuous-batching LLM servers
(Orca-style iteration scheduling, vLLM's paged KV state) do with model
state: keep the big arrays resident on the accelerator, apply each
cycle's churn as scatter updates, and let the host do O(churn) work per
cycle instead of O(cluster).

Per delta cycle the host:
  * normalizes + interns only the CHURNED records against a persistent
    `_Interner` (vocabulary appends; ids already burned into device
    arrays stay valid),
  * re-encodes only the churned rows into the numpy mirror
    (snapshot.py's shared row fills),
  * ships those rows (plus, when insertion/removal shifted the
    name-sorted row order, one int32 permutation per collection) and
    applies them with `kernels.assign.scatter_rows` /
    `permute_rows` — `.at[idx].set` XLA scatters over whole
    struct-of-arrays groups.

Anything the row model cannot express incrementally falls back to a
full SnapshotBuilder rebuild + re-upload, counted and reasoned:
bucket overflow (rows or any feature axis), a NEW taint (the [P, VT]
tolerated matrix gains a column for every pod), or a NEW topology key
(the [N, TK] domain matrix gains a column for every node).

Invariants (the delta-vs-rebuild parity tests pin these):
  * Row order is ALWAYS name-sorted per collection — exactly the
    canonical order the wire decoder uses — so index-based tie-breaks
    are a function of cluster STATE, not of the delta history, and a
    fallback/rebuild produces identical results.
  * Value-only churn produces arrays BYTE-IDENTICAL to a fresh
    `SnapshotBuilder.build()` of the same records (same buckets).
    Vocabulary-growing churn may assign different (opaque) intern ids
    than a fresh build; solve/score results are unaffected.
  * Node `used` rows are re-summed over the node's counted running
    pods in name order on every touch — float-identical to a rebuild,
    never drifting through += / -= pairs.
"""

from __future__ import annotations

import dataclasses
import heapq
import logging
import time
import traceback
from bisect import bisect_left
from typing import Iterable, Mapping

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from tpusched import trace as tracing
from tpusched.config import Buckets, EngineConfig
from tpusched.kernels import queue as queue_kernels
from tpusched.kernels.assign import permute_rows, scatter_rows
from tpusched.mesh import snapshot_shardings
from tpusched.qos import pressure_of
from tpusched.snapshot import (
    ClusterSnapshot,
    SnapshotBuilder,
    SnapshotMeta,
    _fill_node_row,
    _fill_pod_row,
    _fill_running_row,
    _fill_atom_row,
    _fill_sig_row,
    _pad_node_row,
    _pad_pod_row,
    _pad_running_row,
    _snapshot_from_arrays,
)


@dataclasses.dataclass
class ApplyStats:
    """What one apply() did and what it cost on the wire to the device."""

    path: str                 # "delta" | "rebuild"
    reason: str = ""          # rebuild trigger ("" on the delta path)
    h2d_bytes: int = 0        # bytes actually shipped host->device
    rows_scattered: int = 0   # churned+pad rows written across groups
    reordered: bool = False   # a permutation gather ran
    # Wire-level churn: upsert+remove records this apply carried (the
    # cycle ledger's churn field for warm/pipeline cycles, round 18 —
    # rows_scattered counts pad rows and used-resums too, so it
    # overstates what the CLIENT changed).
    churn_records: int = 0
    # Names the Python-level order/index/map upkeep examined: one per
    # churned name per pass plus one per bisect lookup (a rebuild counts
    # every record it re-sorts). O(churn) on the delta path.
    order_visited: int = 0
    # The `used` re-sum: running rows folded into churned nodes' rows,
    # and the nodes re-summed (0 on a rebuild, which sums every row).
    used_rows: int = 0
    used_nodes: int = 0


@dataclasses.dataclass
class WarmDelta:
    """One warm solve's dirty work, derived by DeviceSnapshot.warm_delta
    from everything applied since the last committed tableau (ROADMAP
    item 3). Index lists are positions in the CURRENT name-sorted row
    order; perms map tableau-order rows to current order (None = order
    unchanged). needs_cold forces a full tableau rebuild — vocabulary
    growth, a rebuild, or a never-built lineage."""

    needs_cold: bool = False
    reason: str = ""
    dirty_pods: "list[int] | None" = None     # pod tableau rows
    dirty_nodes: "list[int] | None" = None    # node tableau columns
    dirty_members: "list[int] | None" = None  # [running | pod] columns
    pod_perm: "np.ndarray | None" = None      # int32 [pod bucket]
    node_perm: "np.ndarray | None" = None     # int32 [node bucket]
    member_perm: "np.ndarray | None" = None   # int32 [run+pod buckets]


class _NeedsRebuild(Exception):
    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


def _tree_nbytes(tree) -> int:
    return sum(int(l.nbytes) for l in jax.tree.leaves(tree))


def _pad_pow2(idx: list[int]) -> np.ndarray:
    """Pad a scatter index list to the next power of two by REPEATING
    the first index: bounded jit-shape set, and the duplicate writes
    carry identical row content so scatter order cannot matter."""
    n = len(idx)
    cap = 1 << max(n - 1, 0).bit_length() if n > 1 else 1
    out = np.full(cap, idx[0], np.int32)
    out[:n] = idx
    return out


def _pdb_key(rec: Mapping) -> "tuple | None":
    """The (namespace, name) PDB a running record belongs to, or None."""
    g = rec.get("pdb_group")
    if not g:
        return None
    return (str(rec.get("namespace", "default")) or "default", g)


def _leave(members: dict, key, name: str) -> None:
    """Drop `name` from members[key]; a slot left empty goes, as a
    from-scratch recompute never creates one."""
    m = members.get(key)
    if m is not None:
        m.pop(name, None)
        if not m:
            del members[key]


def _order_churn(old: list[str], names: set[str], live: Mapping,
                 ) -> "tuple[list[int], list[str]]":
    """Rows of the sorted `old` order that the churned `names` vacate
    (ascending) and the names they insert (sorted), given the record
    dict after the apply: one bisect per churned name."""
    removed, inserted = [], []
    for nm in names:
        i = bisect_left(old, nm)
        was = i < len(old) and old[i] == nm
        if nm in live:
            if not was:
                inserted.append(nm)
        elif was:
            removed.append(i)
    removed.sort()
    inserted.sort()
    return removed, inserted


def _row_of(order: list[str], name: str) -> int:
    """Row of `name` in the sorted `order` (present by contract)."""
    i = bisect_left(order, name)
    if i == len(order) or order[i] != name:
        raise KeyError(name)
    return i


class DeviceSnapshot:
    """One snapshot lineage resident on the device.

    `full_load()` takes builder-style record dicts (the kwargs
    SnapshotBuilder.add_* accept, plus 'name'; running records carry
    both 'name' and 'node'), sorts them by name, builds, and uploads.
    `apply()` upserts/removes records and updates the device arrays in
    O(churn); `snap`/`meta` always reflect the latest applied state.

    Not thread-safe: callers (the sidecar's DeviceSession) serialize
    applies per lineage.
    """

    def __init__(self, config: EngineConfig | None = None,
                 buckets: Buckets | None = None, mesh=None):
        self.config = config or EngineConfig()
        self._floor_buckets = buckets
        # Optional jax.sharding.Mesh (ROADMAP item 1): when set, the
        # lineage's device arrays live SHARDED in the canonical layout
        # (mesh.snapshot_shardings: pods over 'p', nodes over 'n', vocab
        # replicated) so one lineage can hold a cluster no single
        # device fits. Delta scatters/permutes run on the sharded
        # arrays; _repin() restores the canonical layout afterwards in
        # case the partitioner chose a different output sharding.
        self.mesh = mesh
        # Span collector for device.rebuild events; None = the process
        # default at emit time (the sidecar points this at its own
        # collector when one was injected).
        self.tracer = None
        # Raw record kwargs by name (rebuild source of truth) and the
        # normalized forms row fills consume.
        self._nodes: dict[str, dict] = {}
        self._pods: dict[str, dict] = {}
        self._running: dict[str, dict] = {}
        self._norm_nodes: dict[str, dict] = {}
        self._norm_pods: dict[str, dict] = {}
        self._norm_running: dict[str, dict] = {}
        self._run_anti: dict[str, list[int]] = {}   # name -> anti sig ids
        self._pod_pc: dict[str, dict] = {}          # name -> compiled pod
        # Name-sorted row orders (the decoder's canonical order).
        self._node_order: list[str] = []
        self._pod_order: list[str] = []
        self._run_order: list[str] = []
        # group name -> {pod name: min_member}; pdb key -> {run name: allowed}
        self._group_members: dict[str, dict[str, int]] = {}
        self._pdb_members: dict[tuple, dict[str, int]] = {}
        # Reverse maps of the PREVIOUS state (see _refresh_prev_maps).
        self._run_node_name: dict[str, str] = {}
        self._pod_group_name: dict[str, str] = {}
        self._run_pdb_key: dict[str, tuple] = {}
        # Host-only column in the running mirror's row order: the row's
        # record has count_into_used (pad rows False). Never shipped.
        self._run_counted = np.zeros(0, bool)
        self._state = None          # snapshot.BuiltState
        self._meta: SnapshotMeta | None = None
        self._device: ClusterSnapshot | None = None
        # Transfer accounting (the test/bench hook for the "no full H2D
        # in steady state" acceptance).
        self.full_uploads = 0
        self.delta_updates = 0
        self.rebuilds = 0
        self.rebuild_reasons: list[str] = []
        self.h2d_bytes_total = 0
        self.h2d_bytes_last = 0
        # Warm-start residency (ROADMAP item 3): the carried tableau
        # handle lives HERE, next to the device snapshot it was built
        # from, so its lifetime is the lineage's. The lineage token is
        # the identity a handle is pinned to — a handle surviving a
        # failover/restore onto a DIFFERENT DeviceSnapshot fails the
        # engine's `is` check and takes the cold path.
        self.warm_lineage: object = object()
        self.warm_state = None            # engine.WarmState (opaque here)
        self._warm_orders = None          # (node, pod, run) orders at sync
        self._warm_vocab = None           # (n_atoms, n_sigs) at sync
        self._warm_pressure = None        # np [pod bucket] pressure at sync
        self._warm_dirty_nodes: set[str] = set()
        self._warm_dirty_pods: set[str] = set()
        self._warm_dirty_runs: set[str] = set()
        self._warm_cold_reason: "str | None" = "never_built"
        # Warm-path accounting (the bench/prof/test hooks).
        self.warm_solves = 0
        self.cold_solves = 0
        self.incremental_solves = 0
        self.warm_cold_reasons: list[str] = []
        self.last_warm_rows = (0, 0, 0)   # (pod, node, member) dirty rows
        # Previous-cycle assignment carry (ISSUE 12, the incremental
        # warm path's seed): name-keyed so row reorders between cycles
        # cannot misroute it. Committed by the engine's warm unpack on
        # the joining thread — the same single-caller serialization
        # discipline apply() relies on.
        self._carry = None  # (pod_names, node_names, assign np, chosen np)
        # Device-resident pending queue (ISSUE 20): attached lazily so
        # lineages that never ingest pay nothing. Lives on the lineage
        # because its lifetime (and failover story) is the lineage's.
        self.pending: "DeviceQueue | None" = None

    def attach_pending(self, capacity: int = 1024,
                       bound: int | None = None) -> "DeviceQueue":
        """Create (or return) this lineage's device pending queue. The
        queue inherits the lineage's qos_gain so in-kernel priorities
        match what the solver would compute host-side."""
        if self.pending is None:
            self.pending = DeviceQueue(
                capacity=capacity, bound=bound,
                qos_gain=float(self.config.qos.qos_gain))
        return self.pending

    # -- views --------------------------------------------------------------

    @property
    def snap(self) -> ClusterSnapshot:
        if self._device is None:
            raise ValueError("DeviceSnapshot: full_load() first")
        return self._device

    @property
    def meta(self) -> SnapshotMeta:
        if self._meta is None:
            raise ValueError("DeviceSnapshot: full_load() first")
        return self._meta

    @property
    def full_bytes(self) -> int:
        """Size of one full snapshot upload at current buckets."""
        return _tree_nbytes(self.snap)

    # -- load / rebuild -----------------------------------------------------

    def full_load(self, nodes: Iterable[Mapping], pods: Iterable[Mapping],
                  running: Iterable[Mapping]) -> ApplyStats:
        """Replace all state with these records and upload."""
        self._nodes = self._keyed(nodes, "node")
        self._pods = self._keyed(pods, "pod")
        self._running = self._keyed(running, "running pod")
        return self._rebuild("full_load")

    @staticmethod
    def _keyed(records: Iterable[Mapping], kind: str) -> dict[str, dict]:
        out: dict[str, dict] = {}
        for rec in records:
            name = rec.get("name")
            if not name or name in out:
                raise ValueError(
                    f"device-resident state needs unique non-empty {kind} "
                    f"names (offending: {name!r})"
                )
            out[name] = dict(rec)
        return out

    def _rebuild_members(self) -> None:
        """Group and PDB member maps from every record (the rebuild's;
        an apply carries them over with _update_members)."""
        self._group_members = {}
        for name, rec in self._pods.items():
            g = rec.get("pod_group")
            if g:
                self._group_members.setdefault(g, {})[name] = int(
                    rec.get("pod_group_min_member", 0)
                )
        self._pdb_members = {}
        for name, rec in self._running.items():
            key = _pdb_key(rec)
            if key is not None:
                self._pdb_members.setdefault(key, {})[name] = int(
                    rec.get("pdb_disruptions_allowed", 0)
                )

    def _update_members(self, pods: set[str], running: set[str]) -> int:
        """Carry the member maps over one apply from the churned names:
        each leaves the slot the previous state's reverse maps name and
        joins the one its record names now. Runs before
        _update_prev_maps overwrites those maps. Returns the names
        examined."""
        for name in pods:
            old = self._pod_group_name.get(name)
            if old is not None:
                _leave(self._group_members, old, name)
            rec = self._pods.get(name)
            g = rec.get("pod_group") if rec is not None else None
            if g:
                self._group_members.setdefault(g, {})[name] = int(
                    rec.get("pod_group_min_member", 0)
                )
        for name in running:
            old = self._run_pdb_key.get(name)
            if old is not None:
                _leave(self._pdb_members, old, name)
            rec = self._running.get(name)
            key = _pdb_key(rec) if rec is not None else None
            if key is not None:
                self._pdb_members.setdefault(key, {})[name] = int(
                    rec.get("pdb_disruptions_allowed", 0)
                )
        return len(pods) + len(running)

    def _refresh_prev_maps(self) -> None:
        """Reverse maps the NEXT apply needs to find what a churned
        record used to reference (old node, old group, old PDB), from
        every record (the rebuild's; an apply carries them over with
        _update_prev_maps)."""
        self._run_node_name = {
            name: rec["node"] for name, rec in self._running.items()
        }
        self._pod_group_name = {
            name: rec.get("pod_group") for name, rec in self._pods.items()
            if rec.get("pod_group")
        }
        self._run_pdb_key = {}
        for key, members in self._pdb_members.items():
            for name in members:
                self._run_pdb_key[name] = key

    def _update_prev_maps(self, pods: set[str], running: set[str]) -> int:
        """_refresh_prev_maps for the churned names only. Returns the
        names examined."""
        for name in running:
            rec = self._running.get(name)
            new = rec["node"] if rec is not None else None
            if new is None:
                self._run_node_name.pop(name, None)
            else:
                self._run_node_name[name] = new
            key = _pdb_key(rec) if rec is not None else None
            if key is None:
                self._run_pdb_key.pop(name, None)
            else:
                self._run_pdb_key[name] = key
        for name in pods:
            rec = self._pods.get(name)
            g = rec.get("pod_group") if rec is not None else None
            if g:
                self._pod_group_name[name] = g
            else:
                self._pod_group_name.pop(name, None)
        return len(pods) + len(running)

    def _put(self, snap_np: ClusterSnapshot) -> ClusterSnapshot:
        """Upload a full snapshot — sharded in the canonical mesh layout
        when this lineage has one, single (default) device otherwise."""
        if self.mesh is not None and self.mesh.devices.size > 1:
            return jax.device_put(
                snap_np, snapshot_shardings(self.mesh, snap_np)
            )
        return jax.device_put(snap_np)

    def _repin(self, dev: ClusterSnapshot) -> ClusterSnapshot:
        """Restore the canonical mesh layout after delta scatters (the
        partitioner may emit a different output sharding for the
        scattered/permuted groups). Leaves already laid out canonically
        are untouched (device_put with a matching sharding is a no-op);
        drifted leaves move device-to-device, never back through the
        host — delta applies stay O(churn) on the H2D wire."""
        if self.mesh is None or self.mesh.devices.size <= 1:
            return dev
        return jax.device_put(dev, snapshot_shardings(self.mesh, dev))

    def _rebuild(self, reason: str) -> ApplyStats:
        """Full host rebuild + device re-upload (the fallback path).
        Buckets floor at the PREVIOUS state's buckets so a lineage's
        compile shapes never shrink mid-session (no recompile churn)."""
        floor = self._state.buckets if self._state is not None \
            else self._floor_buckets
        b = SnapshotBuilder(self.config, floor)
        self._node_order = sorted(self._nodes)
        self._pod_order = sorted(self._pods)
        self._run_order = sorted(self._running)
        for name in self._node_order:
            b.add_node(**self._nodes[name])
        for name in self._pod_order:
            b.add_pod(**self._pods[name])
        for name in self._run_order:
            rec = {k: v for k, v in self._running[name].items()
                   if k != "name"}
            b.add_running_pod(**rec)
        t0 = time.perf_counter()
        snap_np, meta, state = b.build_state()
        meta.running_names = list(self._run_order)
        self._state = state
        self._meta = meta
        # Harvest the builder's normalized records + compiled forms so
        # later incremental row re-encodes match build exactly.
        self._norm_nodes = dict(zip(self._node_order, b._nodes))
        self._norm_pods = dict(zip(self._pod_order, b._pods))
        self._norm_running = dict(zip(self._run_order, b._running))
        self._run_counted = np.zeros(state.buckets.running_pods, bool)
        self._run_counted[:len(b._running)] = [
            bool(r["count_into_used"]) for r in b._running]
        # Compiled forms cache only what churn touches; the build just
        # burned every row, so start empty.
        self._pod_pc = {}
        self._run_anti = {}
        self._rebuild_members()
        self._refresh_prev_maps()
        self._device = self._put(snap_np)
        # A rebuild replaces every device array: any carried warm
        # tableau is built on the OLD arrays (and possibly old buckets/
        # vocab) — drop it so the next warm solve goes cold and
        # re-anchors on this state.
        self.invalidate_warm(reason)
        nbytes = _tree_nbytes(snap_np)
        self.full_uploads += 1
        if reason != "full_load":
            self.rebuilds += 1
            self.rebuild_reasons.append(reason)
        self.h2d_bytes_last = nbytes
        self.h2d_bytes_total += nbytes
        # Event span (round 9): a rebuild is the expensive surprise of
        # the device-resident path — it must be visible in the trace
        # ring (and flight dumps) with its trigger, not just a counter.
        (self.tracer or tracing.DEFAULT).record(
            "device.rebuild", dur_s=time.perf_counter() - t0, cat="device",
            reason=reason, h2d_bytes=nbytes,
        )
        return ApplyStats(path="rebuild", reason=reason, h2d_bytes=nbytes,
                          order_visited=len(self._node_order)
                          + len(self._pod_order) + len(self._run_order))

    # -- incremental apply --------------------------------------------------

    def apply(
        self,
        upsert_nodes: Iterable[Mapping] = (),
        remove_nodes: Iterable[str] = (),
        upsert_pods: Iterable[Mapping] = (),
        remove_pods: Iterable[str] = (),
        upsert_running: Iterable[Mapping] = (),
        remove_running: Iterable[str] = (),
    ) -> ApplyStats:
        if self._device is None:
            raise ValueError("DeviceSnapshot: full_load() first")
        # Stage spans under the caller's open span (the sidecar's
        # delta.apply): records / encode / order / scatter, per stretch.
        laps = (self.tracer or tracing.DEFAULT).laps("device")
        laps.lap("apply.records")
        upsert_nodes = [dict(r) for r in upsert_nodes]
        upsert_pods = [dict(r) for r in upsert_pods]
        upsert_running = [dict(r) for r in upsert_running]
        remove_nodes = list(remove_nodes)
        remove_pods = list(remove_pods)
        remove_running = list(remove_running)
        for coll, kind in ((upsert_nodes, "node"), (upsert_pods, "pod"),
                           (upsert_running, "running pod")):
            seen = set()
            for rec in coll:
                name = rec.get("name")
                if not name or name in seen:
                    raise ValueError(
                        f"delta upserts need unique non-empty {kind} names "
                        f"(offending: {name!r})"
                    )
                seen.add(name)
        # Validate BEFORE committing anything: a running pod whose node
        # is gone cannot be encoded (the fresh decoder raises the same
        # way), and raising mid-apply must not leave records and device
        # arrays disagreeing.
        up_n = {r["name"] for r in upsert_nodes}
        rm_n = set(remove_nodes)
        removed_r = set(remove_running)
        upserted_r = {u["name"] for u in upsert_running}
        check = list(upsert_running)
        if remove_nodes:
            check += [rec for name, rec in self._running.items()
                      if name not in removed_r and name not in upserted_r]
        for rec in check:
            n = rec["node"]
            if (n not in self._nodes and n not in up_n) or n in rm_n:
                raise ValueError(
                    f"running pod {rec.get('name')!r} references missing "
                    f"node {rec['node']!r}"
                )
        # Commit the record store FIRST: if the incremental path cannot
        # express the change, _rebuild() regenerates everything from
        # records, so any surprise below degrades to a correct (slower)
        # cycle instead of corrupt state.
        for rec in upsert_nodes:
            self._nodes[rec["name"]] = rec
        for name in remove_nodes:
            self._nodes.pop(name, None)
        for rec in upsert_pods:
            self._pods[rec["name"]] = rec
        for name in remove_pods:
            self._pods.pop(name, None)
        for rec in upsert_running:
            self._running[rec["name"]] = rec
        for name in remove_running:
            self._running.pop(name, None)
        churn = (len(upsert_nodes) + len(remove_nodes) + len(upsert_pods)
                 + len(remove_pods) + len(upsert_running)
                 + len(remove_running))
        # Every name this apply upserts or removes, per collection.
        churned = (up_n | rm_n,
                   {r["name"] for r in upsert_pods} | set(remove_pods),
                   upserted_r | removed_r)
        try:
            visited = self._update_members(churned[1], churned[2])
            stats = self._apply_incremental(
                upsert_nodes, remove_nodes, upsert_pods, remove_pods,
                upsert_running, remove_running, churned, laps,
            )
            stats.order_visited += visited
        except _NeedsRebuild as e:
            laps.close()
            stats = self._rebuild(e.reason)
        except Exception:  # noqa: BLE001 — heal, then let tests catch it
            laps.close()
            logging.getLogger("tpusched.device_state").warning(
                "incremental delta apply failed; rebuilding this "
                "lineage:\n%s", traceback.format_exc(limit=4),
            )
            stats = self._rebuild("incremental_error")
        stats.churn_records = churn
        return stats

    def _apply_incremental(self, upsert_nodes, remove_nodes, upsert_pods,
                           remove_pods, upsert_running, remove_running,
                           churned, laps) -> ApplyStats:
        st = self._state
        intr = st.interner
        bk = st.buckets
        cfg = self.config
        # O(churn): normalize, intern and check the churned records.
        laps.lap("apply.encode")

        # Row-count capacity.
        if (len(self._pods) > bk.pods or len(self._nodes) > bk.nodes
                or len(self._running) > bk.running_pods):
            raise _NeedsRebuild("row_bucket")

        # Normalize churned records through a scratch builder: identical
        # defaulting (pods resource, namespace fallback, PDB keying) to
        # a full build.
        nb = SnapshotBuilder(cfg)
        for rec in upsert_nodes:
            nb.add_node(**rec)
        for rec in upsert_pods:
            nb.add_pod(**rec)
        for rec in upsert_running:
            nb.add_running_pod(**{k: v for k, v in rec.items()
                                  if k != "name"})
        norm_nodes = dict(zip([r["name"] for r in upsert_nodes], nb._nodes))
        norm_pods = dict(zip([r["name"] for r in upsert_pods], nb._pods))
        norm_running = dict(
            zip([r["name"] for r in upsert_running], nb._running)
        )

        # Vocabulary growth with column-wise blast radius forces a
        # rebuild: a new taint grows pods.tolerated for EVERY pod, a new
        # topology key grows nodes.domain for EVERY node.
        n_topo0 = len(intr.topo_keys)
        for rec in norm_nodes.values():
            for (k, v, e) in rec["taints"]:
                if (k, v, e) not in intr.taint_ids:
                    raise _NeedsRebuild("new_taint")

        n_atoms0, n_sigs0 = len(intr.atoms), len(intr.sigs)
        new_pcs: dict[str, dict] = {}
        for name, rec in norm_pods.items():
            pc = intr.compile_pod(rec)
            intr.intern_labels(rec["labels"])
            intr.nsid(rec["namespace"])
            new_pcs[name] = pc
            if (len(pc["req_terms"]) > bk.terms
                    or len(pc["pref_terms"]) > bk.pref_terms
                    or len(pc["ts"]) > bk.spread_constraints
                    or len(pc["ia"]) > bk.affinity_terms
                    or len(rec["labels"]) > bk.pod_labels
                    or any(len(t) > bk.term_atoms
                           for t in pc["req_terms"])
                    or any(len(t[0]) > bk.term_atoms
                           for t in pc["pref_terms"])):
                raise _NeedsRebuild("pod_feature_bucket")
        new_anti: dict[str, list[int]] = {}
        for name, rec in norm_running.items():
            sigs_of_pod, am = intr.compile_running_anti(rec)
            intr.intern_labels(rec["labels"])
            intr.nsid(rec["namespace"])
            new_anti[name] = sigs_of_pod
            if (len(sigs_of_pod) > bk.affinity_terms or am > bk.term_atoms
                    or len(rec["labels"]) > bk.pod_labels):
                raise _NeedsRebuild("running_feature_bucket")
        for rec in norm_nodes.values():
            intr.intern_labels(rec["labels"])
            if (len(rec["labels"]) > bk.node_labels
                    or len(rec["taints"]) > bk.node_taints):
                raise _NeedsRebuild("node_feature_bucket")
        # Topology-domain ids append FOREVER on a long-lived interner
        # (node relabels keep minting values), but the pairwise kernels
        # scatter domain counts into [S, N] — an id >= the node bucket
        # would be silently dropped by XLA. A fresh build compacts ids
        # to <= #nodes, so rebuild before the bucket is breached.
        new_domains: dict[int, set] = {}
        for rec in norm_nodes.values():
            for ti, tk in enumerate(intr.topo_keys):
                v = rec["labels"].get(tk)
                if v is not None and v not in intr.domain_ids[ti]:
                    new_domains.setdefault(ti, set()).add(v)
        for ti, vals in new_domains.items():
            if len(intr.domain_ids[ti]) + len(vals) > bk.nodes:
                raise _NeedsRebuild("domain_vocab")
        if len(intr.topo_keys) > n_topo0:
            raise _NeedsRebuild("new_topo_key")
        if len(intr.atoms) > bk.atoms or len(intr.sigs) > bk.signatures:
            raise _NeedsRebuild("table_bucket")
        for a in range(n_atoms0, len(intr.atoms)):
            if len(intr.atoms[a][2]) > bk.atom_values:
                raise _NeedsRebuild("atom_values_bucket")
        for s in range(n_sigs0, len(intr.sigs)):
            _, ns_scope, alist = intr.sigs[s]
            if len(alist) > bk.term_atoms or (
                    ns_scope != "*" and len(ns_scope) > bk.sig_namespaces):
                raise _NeedsRebuild("sig_bucket")

        # Groups / PDBs: new ids APPEND (a fresh build sorts names; ids
        # are opaque equality tokens so appending keeps settled pod rows
        # valid). Touched = any slot whose membership a churned record
        # enters or leaves; its value is max over current members.
        touched_groups = set()
        for rec in upsert_pods:
            g = rec.get("pod_group")
            if g:
                touched_groups.add(g)
            old_g = self._pod_group_name.get(rec["name"])
            if old_g:
                touched_groups.add(old_g)
        for name in remove_pods:
            old_g = self._pod_group_name.get(name)
            if old_g:
                touched_groups.add(old_g)
        for g in touched_groups:
            if g in self._group_members and g not in st.group_idx:
                if len(st.group_idx) >= bk.pod_groups:
                    raise _NeedsRebuild("group_bucket")
                st.group_idx[g] = len(st.group_idx)
        touched_groups = {g for g in touched_groups if g in st.group_idx}
        touched_pdbs = set()
        for rec in norm_running.values():
            if rec["pdb_group"] is not None:
                touched_pdbs.add(rec["pdb_group"])
        for rec in upsert_running:
            old_key = self._run_pdb_key.get(rec["name"])
            if old_key:
                touched_pdbs.add(old_key)
        for name in remove_running:
            old_key = self._run_pdb_key.get(name)
            if old_key:
                touched_pdbs.add(old_key)
        for key in touched_pdbs:
            if key in self._pdb_members and key not in st.pdb_idx:
                if len(st.pdb_idx) >= bk.pdb_groups:
                    raise _NeedsRebuild("pdb_bucket")
                st.pdb_idx[key] = len(st.pdb_idx)
        touched_pdbs = {k for k in touched_pdbs if k in st.pdb_idx}

        # Commit normalized forms + compiled caches.
        for name in remove_nodes:
            self._norm_nodes.pop(name, None)
        for name in remove_pods:
            self._norm_pods.pop(name, None)
            self._pod_pc.pop(name, None)
        for name in remove_running:
            self._norm_running.pop(name, None)
            self._run_anti.pop(name, None)
        self._norm_nodes.update(norm_nodes)
        self._norm_pods.update(norm_pods)
        self._norm_running.update(norm_running)
        self._pod_pc.update(new_pcs)
        self._run_anti.update(new_anti)

        # Churn sets. A running upsert/remove dirties its node's `used`
        # row (old node AND new node when the pod moved).
        node_churn = set(norm_nodes)
        run_churn = set(norm_running)
        pod_churn = set(norm_pods)
        for rec in upsert_running:
            node_churn.add(rec["node"])
            old_node = self._run_node_name.get(rec["name"])
            if old_node is not None:
                node_churn.add(old_node)
        for name in remove_running:
            old_node = self._run_node_name.get(name)
            if old_node is not None:
                node_churn.add(old_node)
        node_churn = {nm for nm in node_churn if nm in self._nodes}
        # Name-order upkeep from the churned names alone: bisects into
        # the sorted orders, C-level list and array copies, no pass in
        # Python over the cluster.
        laps.lap("apply.order")
        node_names, pod_names, run_names = churned
        visited = self._update_prev_maps(pod_names, run_names)
        node_rm, node_ins = _order_churn(self._node_order, node_names,
                                         self._nodes)
        pod_rm, pod_ins = _order_churn(self._pod_order, pod_names, self._pods)
        run_rm, run_ins = _order_churn(self._run_order, run_names,
                                       self._running)
        visited += (len(node_names) + len(pod_names) + len(run_names)
                    + len(node_ins) + len(pod_ins) + len(run_ins))

        # Permutations for insertion/removal (None = steady-state
        # value churn, pure scatter).
        new_node_order, node_perm, node_pads, node_runs = self._reorder(
            self._node_order, node_rm, node_ins, bk.nodes)
        new_pod_order, pod_perm, pod_pads, _ = self._reorder(
            self._pod_order, pod_rm, pod_ins, bk.pods)
        new_run_order, run_perm, run_pads, _ = self._reorder(
            self._run_order, run_rm, run_ins, bk.running_pods)
        node_reorder = node_perm is not None
        if node_reorder:
            # Node rows moved: every running row's node_idx needs a
            # remap (one [M] int32 column, not a per-row re-encode).
            remap = np.full(bk.nodes, -1, np.int32)
            for o, n, length in node_runs:
                remap[o:o + length] = np.arange(n, n + length)

        # Reorder the numpy mirror first (the gather allocates fresh
        # arrays), then re-encode churned rows at NEW positions,
        # then pad vacated tail rows.
        for holder, perm in ((st.nodes_np, node_perm),
                             (st.pods_np, pod_perm),
                             (st.run_np, run_perm)):
            if perm is None:
                continue
            rows = perm.astype(np.intp)
            for f, arr in list(vars(holder).items()):
                setattr(holder, f, np.take(arr, rows, axis=0))
        if run_perm is not None:
            self._run_counted = self._run_counted[run_perm]
        if node_reorder:
            ni = st.run_np.node_idx
            st.run_np.node_idx = np.where(
                ni >= 0, remap[ni], ni
            ).astype(np.int32)
            st.node_index = {nm: i for i, nm in enumerate(new_node_order)}
            visited += len(new_node_order)
        mirror = _snapshot_from_arrays(st.nodes_np, st.pods_np, st.run_np,
                                       st.tables)
        pod_row = {nm: _row_of(new_pod_order, nm) for nm in pod_churn}
        run_row = {nm: _row_of(new_run_order, nm) for nm in run_churn}
        visited += len(pod_row) + len(run_row)
        laps.lap("apply.encode")
        for nm in node_churn:
            _fill_node_row(st.nodes_np, st.node_index[nm],
                           self._norm_nodes[nm], intr, cfg)
        for nm in pod_churn:
            _fill_pod_row(st.pods_np, pod_row[nm], self._norm_pods[nm],
                          self._pod_pc[nm], intr, cfg, st.group_idx)
        counted = self._run_counted
        for nm in run_churn:
            rrec = self._norm_running[nm]
            _fill_running_row(st.run_np, run_row[nm], rrec,
                              self._run_anti[nm], intr, cfg, st.node_index,
                              st.pdb_idx)
            counted[run_row[nm]] = bool(rrec["count_into_used"])
        for i in node_pads:
            _pad_node_row(st.nodes_np, i)
        for i in pod_pads:
            _pad_pod_row(st.pods_np, i)
        for i in run_pads:
            _pad_running_row(st.run_np, i)
            counted[i] = False
        # Re-sum the churned nodes' `used` rows from the running rows as
        # they will be scattered: each counted member's float32 requests
        # added one at a time in ascending row (= name) order, the
        # sequence build_state performs, so the row is bit-identical to
        # a rebuild and never drifts through +=/-= pairs. np.add.at is
        # unbuffered: duplicate node indices accumulate in index order.
        # The extra mark slot absorbs pad rows' node_idx of -1.
        mark = np.zeros(bk.nodes + 1, bool)
        mark[[st.node_index[nm] for nm in node_churn]] = True
        ni = st.run_np.node_idx
        used_rows = np.flatnonzero(mark[ni] & counted)
        np.add.at(st.nodes_np.used, ni[used_rows],
                  st.run_np.requests[used_rows])

        # New atom/sig table rows + touched group/pdb scalars.
        for a in range(n_atoms0, len(intr.atoms)):
            _fill_atom_row(st.tables, a, intr.atoms[a])
        for s in range(n_sigs0, len(intr.sigs)):
            _fill_sig_row(st.tables, s, intr.sigs[s])
        for g in touched_groups:
            members = self._group_members.get(g, {})
            st.tables.group_min[st.group_idx[g]] = (
                max(members.values()) if members else 0
            )
        for key in touched_pdbs:
            members = self._pdb_members.get(key, {})
            st.tables.pdb_allowed[st.pdb_idx[key]] = float(
                max(members.values()) if members else 0
            )

        # Device updates: permutation gathers, then row scatters.
        laps.lap("apply.scatter")
        h2d = 0
        rows_written = 0
        dev = self._device
        nodes_dev, pods_dev, run_dev = dev.nodes, dev.pods, dev.running
        if node_perm is not None:
            nodes_dev = permute_rows(nodes_dev, node_perm)
            h2d += node_perm.nbytes
        if pod_perm is not None:
            pods_dev = permute_rows(pods_dev, pod_perm)
            h2d += pod_perm.nbytes
        if run_perm is not None:
            run_dev = permute_rows(run_dev, run_perm)
            h2d += run_perm.nbytes
        if node_reorder:
            # Ship the remapped node_idx column wholesale (int32 [M]).
            # On a mesh it must land replicated across the mesh devices
            # (the canonical running layout) — a plain device_put would
            # commit it to the default device only, and the scatter jit
            # below rejects committed inputs on mismatched device sets.
            if self.mesh is not None and self.mesh.devices.size > 1:
                ni_dev = jax.device_put(
                    st.run_np.node_idx,
                    NamedSharding(self.mesh, PartitionSpec()),
                )
            else:
                ni_dev = jax.device_put(st.run_np.node_idx)
            run_dev = dataclasses.replace(run_dev, node_idx=ni_dev)
            h2d += st.run_np.node_idx.nbytes

        def scatter(dev_tree, mirror_tree, rows):
            nonlocal h2d, rows_written
            rows = sorted(set(rows))
            if not rows:
                return dev_tree
            idx = _pad_pow2(rows)
            row_data = jax.tree.map(lambda a: a[idx], mirror_tree)
            h2d += idx.nbytes + _tree_nbytes(row_data)
            rows_written += len(rows)
            return scatter_rows(dev_tree, idx, row_data)

        nodes_dev = scatter(
            nodes_dev, mirror.nodes,
            [st.node_index[nm] for nm in node_churn] + list(node_pads),
        )
        pods_dev = scatter(
            pods_dev, mirror.pods,
            [pod_row[nm] for nm in pod_churn] + list(pod_pads),
        )
        run_dev = scatter(
            run_dev, mirror.running,
            [run_row[nm] for nm in run_churn] + list(run_pads),
        )
        atoms_dev = scatter(dev.atoms, mirror.atoms,
                            list(range(n_atoms0, len(intr.atoms))))
        sigs_dev = scatter(dev.sigs, mirror.sigs,
                           list(range(n_sigs0, len(intr.sigs))))
        group_dev = scatter(dev.group_min_member, mirror.group_min_member,
                            [st.group_idx[g] for g in touched_groups])
        pdb_dev = scatter(dev.pdb_allowed, mirror.pdb_allowed,
                          [st.pdb_idx[k] for k in touched_pdbs])

        self._device = self._repin(dataclasses.replace(
            dev, nodes=nodes_dev, pods=pods_dev, running=run_dev,
            atoms=atoms_dev, sigs=sigs_dev, group_min_member=group_dev,
            pdb_allowed=pdb_dev,
        ))
        laps.lap("apply.order")
        self._node_order = new_node_order
        self._pod_order = new_pod_order
        self._run_order = new_run_order
        # Warm-start dirty accumulation (ROADMAP item 3): every name
        # whose row this apply re-encoded (including used-resummed
        # nodes) goes stale in the carried tableau. Vacated/pad rows
        # and reorders are derived from the ORDER diff at warm_delta()
        # time, so multiple applies between solves compose. Only while
        # a tableau is actually committed: lineages that never warm-
        # solve (the sidecar's DeviceSessions today) must not grow
        # these sets without bound across a long serving life.
        if self._warm_orders is not None:
            self._warm_dirty_nodes |= node_churn
            self._warm_dirty_pods |= pod_churn
            self._warm_dirty_runs |= run_churn
        # The meta shares the orders: no order list is mutated in place.
        self._meta = SnapshotMeta(
            node_names=new_node_order,
            pod_names=new_pod_order,
            n_nodes=len(new_node_order), n_pods=len(new_pod_order),
            n_running=len(new_run_order), buckets=bk,
            # ID order, not name order: appended mid-session groups get
            # ids past the originally-sorted ones, and group_names[i]
            # must keep naming group id i.
            group_names=[g for g, _ in sorted(st.group_idx.items(),
                                              key=lambda kv: kv[1])],
            running_names=new_run_order,
        )
        laps.close()
        self.delta_updates += 1
        self.h2d_bytes_last = h2d
        self.h2d_bytes_total += h2d
        return ApplyStats(
            path="delta", h2d_bytes=h2d, rows_scattered=rows_written,
            reordered=(node_perm is not None or pod_perm is not None
                       or run_perm is not None),
            order_visited=visited, used_rows=len(used_rows),
            used_nodes=len(node_churn),
        )

    # -- warm-start residency (ROADMAP item 3) ------------------------------

    def invalidate_warm(self, reason: str) -> None:
        """Drop the carried tableau AND the assignment carry: the next
        warm solve goes cold (and re-anchors the lineage), and the next
        incremental solve falls back to the bitwise path until a fresh
        carry lands. Called on every rebuild, by the host on a failed
        cycle (the unwind contract), and available to any owner whose
        fetch errored after dispatch (the conservative reset)."""
        self.warm_state = None
        self._warm_cold_reason = reason
        self._warm_orders = None
        self._warm_dirty_nodes = set()
        self._warm_dirty_pods = set()
        self._warm_dirty_runs = set()
        self._carry = None

    def warm_delta(self) -> WarmDelta:
        """Derive the dirty work accumulated since the last committed
        tableau: churned rows at their CURRENT name-sorted positions,
        rows vacated by shrinkage (now padding), one reorder perm per
        axis (tableau order -> current order, exactly the permutation
        discipline apply() uses for the snapshot arrays), and — the QoS
        temporal-locality guard — pods whose pressure drifted since the
        tableau was committed, found by one vectorized qos.pressure_of
        compare. The pressure compare is DEFENSIVE: the engine
        recomputes every pressure-dependent quantity (plugin weights,
        pop order, preemption priorities) fresh from the snapshot each
        solve, so a pressure change alone never changes tableau cells;
        the compare catches out-of-band mirror edits that bypassed
        apply(). Vocabulary growth (atoms/sigs appended by a delta)
        forces needs_cold: new vocab rows change tableau cells of
        UNCHURNED rows, which the row model cannot express."""
        if self._warm_cold_reason is not None:
            return WarmDelta(needs_cold=True, reason=self._warm_cold_reason)
        st = self._state
        bk = st.buckets
        if (len(st.interner.atoms), len(st.interner.sigs)) != self._warm_vocab:
            return WarmDelta(needs_cold=True, reason="vocab_growth")
        o_nodes, o_pods, o_runs = self._warm_orders
        node_perm, node_pads = self._perm(o_nodes, self._node_order,
                                          bk.nodes)
        pod_perm, pod_pads = self._perm(o_pods, self._pod_order, bk.pods)
        run_perm, run_pads = self._perm(o_runs, self._run_order,
                                        bk.running_pods)
        pod_index = {nm: i for i, nm in enumerate(self._pod_order)}
        run_index = {nm: i for i, nm in enumerate(self._run_order)}
        d_nodes = {st.node_index[nm] for nm in self._warm_dirty_nodes
                   if nm in st.node_index} | set(node_pads)
        d_pods = {pod_index[nm] for nm in self._warm_dirty_pods
                  if nm in pod_index} | set(pod_pads)
        d_runs = {run_index[nm] for nm in self._warm_dirty_runs
                  if nm in run_index} | set(run_pads)
        cur = np.asarray(pressure_of(st.pods_np.slo_target,
                                     st.pods_np.observed_avail))
        prev = self._warm_pressure
        prev_at_cur = prev[pod_perm] if pod_perm is not None else prev
        drift = np.nonzero((cur != prev_at_cur) & st.pods_np.valid)[0]
        d_pods |= {int(i) for i in drift}
        # A pod is both a tableau ROW and a pairwise MEMBER column; a
        # running pod is a member column only. Member axis layout is
        # [running bucket | pod bucket] (kernels.pairwise).
        d_members = {int(i) for i in d_runs} | {
            bk.running_pods + int(i) for i in d_pods
        }
        member_perm = None
        if run_perm is not None or pod_perm is not None:
            rp = run_perm if run_perm is not None else np.arange(
                bk.running_pods, dtype=np.int32)
            pp = pod_perm if pod_perm is not None else np.arange(
                bk.pods, dtype=np.int32)
            member_perm = np.concatenate([rp, bk.running_pods + pp])
        return WarmDelta(
            dirty_pods=sorted(d_pods) or None,
            dirty_nodes=sorted(d_nodes) or None,
            dirty_members=sorted(d_members) or None,
            pod_perm=pod_perm, node_perm=node_perm,
            member_perm=member_perm,
        )

    def warm_marker(self) -> "tuple[int, int]":
        """(warm_solves, incremental_solves) snapshot BEFORE a warm
        dispatch — pair with warm_path_taken to classify what the
        dispatch actually served. One authority (round 18, ISSUE 13):
        the host, the warm stream, and the ledger's warm-mix must all
        read the commit_warm counters the same way."""
        return (self.warm_solves, self.incremental_solves)

    def warm_path_taken(self, marker: "tuple[int, int]") -> str:
        """Path the dispatch since `marker` took (the ledger's
        canonical spelling): incremental | warm | cold."""
        if self.incremental_solves > marker[1]:
            return "incremental"
        if self.warm_solves > marker[0]:
            return "warm"
        return "cold"

    def commit_warm(self, state, path: str, reason: str, rows) -> None:
        """Engine callback at warm/cold dispatch time: store the new
        handle and re-anchor the dirty accumulation on the snapshot
        state the dispatched program reads."""
        st = self._state
        self.warm_state = state
        self._warm_orders = (list(self._node_order),
                             list(self._pod_order),
                             list(self._run_order))
        self._warm_vocab = (len(st.interner.atoms), len(st.interner.sigs))
        self._warm_pressure = np.array(pressure_of(
            st.pods_np.slo_target, st.pods_np.observed_avail))
        self._warm_dirty_nodes = set()
        self._warm_dirty_pods = set()
        self._warm_dirty_runs = set()
        self._warm_cold_reason = None
        self.last_warm_rows = tuple(rows)
        if path == "warm":
            self.warm_solves += 1
        elif path == "incremental":
            self.incremental_solves += 1
        else:
            self.cold_solves += 1
            self.warm_cold_reasons.append(reason)

    def commit_carry(self, pod_names, node_names, assignment, chosen,
                     ) -> None:
        """Store a completed solve's assignment as the next incremental
        cycle's seed (ISSUE 12). `pod_names`/`node_names` are the name
        orders of the snapshot that solve ran against — the carry is
        NAME-keyed, so later applies reordering rows (or a rebuild
        renumbering nodes) reroute rather than corrupt it."""
        self._carry = (list(pod_names), list(node_names),
                       np.asarray(assignment), np.asarray(chosen))

    def carry_arrays(self):
        """Map the stored carry onto the CURRENT name-sorted row order:
        (carry [pod bucket] int32 node index | -1, chosen [pod bucket]
        f32 as-of-placement scores) or None when no carry exists (never
        solved, or invalidated). Pods/nodes that vanished since the
        carried solve simply drop out (-1 = pending)."""
        if self._carry is None:
            return None
        prev_pods, prev_nodes, a, c = self._carry
        bk = self._state.buckets
        # Steady-state fast path: no row churn since the carried solve
        # (same pod AND node name orders, same buckets) means the carry
        # maps identically — skip the O(P) per-name remap loop that
        # would otherwise run on every incremental dispatch.
        if (prev_pods == self._pod_order and prev_nodes == self._node_order
                and a.shape[0] == bk.pods):
            return (np.asarray(a, np.int32).copy(),
                    np.asarray(c, np.float32).copy())
        carry = np.full(bk.pods, -1, np.int32)
        chos = np.full(bk.pods, -np.inf, np.float32)
        prev_idx = {nm: i for i, nm in enumerate(prev_pods)}
        node_now = self._state.node_index
        for i, nm in enumerate(self._pod_order):
            j = prev_idx.get(nm)
            if j is None or j >= len(a):
                continue
            n = int(a[j])
            if n < 0 or n >= len(prev_nodes):
                continue
            ni = node_now.get(prev_nodes[n], -1)
            if ni >= 0:
                carry[i] = ni
                chos[i] = np.float32(c[j])
        return carry, chos

    @staticmethod
    def _reorder(old: list[str], removed: list[int], inserted: list[str],
                 bucket: int):
        """The new name order after vacating the `removed` rows
        (ascending) and inserting the sorted `inserted` names (none in
        `old`): (order, perm, pads, runs) where perm and pads are what
        `_perm(old, order, bucket)` gives and runs lists each stretch of
        surviving rows as (old row, new row, length). Unchanged: (old,
        None, [], []). A new list is built; `old` is never mutated (the
        meta, the warm anchor and the carry hold earlier orders)."""
        if not removed and not inserted:
            return old, None, [], []
        at = [bisect_left(old, nm) for nm in inserted]
        # One merge: an insert lands before the row at its position, a
        # removal skips that row, and the rows between move as a block.
        events = sorted([(a, 0, nm) for a, nm in zip(at, inserted)]
                        + [(r, 1, "") for r in removed])
        order: list[str] = []
        runs = []
        cur = 0
        for pos, gone, nm in events + [(len(old), 1, "")]:
            if pos > cur:
                runs.append((cur, len(order), pos - cur))
                order += old[cur:pos]
            if gone:
                cur = pos + 1
            else:
                order.append(nm)
                cur = pos
        # Inserted rows keep their own index (the placeholder `_perm`
        # gives a name with no old row).
        perm = np.arange(bucket, dtype=np.int32)
        for o, n, length in runs:
            perm[n:n + length] -= n - o
        pads = list(range(len(order), len(old)))
        return order, perm, pads, runs

    @staticmethod
    def _perm(old_order: list[str], new_order: list[str], bucket: int):
        """(perm int32[bucket] | None, vacated-row indices). None when
        the order is unchanged (the steady-state value-churn cycle)."""
        if old_order == new_order:
            return None, []
        old_pos = {nm: i for i, nm in enumerate(old_order)}
        perm = np.arange(bucket, dtype=np.int32)
        for i, nm in enumerate(new_order):
            perm[i] = old_pos.get(nm, i)
        pads = list(range(len(new_order), len(old_order)))
        return perm, pads


# ---------------------------------------------------------------------------
# Device-resident pending queue (ISSUE 20)
# ---------------------------------------------------------------------------


class DeviceQueue:
    """The persistent [Q] pending table: host mirror + device twin.

    The host keeps a numpy struct-of-arrays mirror plus the name<->slot
    map; every mutation (upsert / remove / park) touches ONLY the
    mirror and marks the slot dirty, and `window()` ships the dirty
    rows in one pow2-padded scatter (`_pad_pow2` + `scatter_rows`, the
    PR 2 delta discipline) before ranking — so per-cycle device traffic
    is O(mutations) and per-cycle host work never re-reads or re-sorts
    the pending set. Ranking, availability decay, and the top-W window
    slice all run in-kernel (kernels.queue.window_select).

    Times are rebased against the first-submit epoch so wall clocks
    survive the float32 table (f32 resolution at time.time() magnitudes
    is ~256s; rebased sim/wall offsets are exact to well past a sim
    day). `bound` caps admission: upsert of a NEW name into a full
    bounded queue returns False and the caller sheds (RESOURCE_EXHAUSTED
    at the rpc layer); unbounded queues grow by pow2 doubling, which
    drops the device twin for one full re-upload (bounded compile set:
    one (Q, kb) bucket pair per capacity).

    Not thread-safe: the ingest gate serializes access under its own
    lock; HostScheduler drives it single-threaded from the cycle loop.
    """

    def __init__(self, capacity: int = 1024, bound: int | None = None,
                 qos_gain: float = 1000.0):
        cap = 1 << max(int(capacity) - 1, 0).bit_length()
        self.bound = int(bound) if bound else None
        self.qos_gain = float(qos_gain)
        self._host = queue_kernels.empty_table(cap)
        self._dev = None                    # device twin; None = stale
        self._slot: dict[str, int] = {}     # name -> slot index
        self._names: list[str | None] = [None] * cap
        self._free: list[int] = list(range(cap))  # min-heap (lowest first)
        heapq.heapify(self._free)
        self._dirty: set[int] = set()
        self._epoch: float | None = None    # first-submit time rebase
        self._seq = 0                       # arrival sequence stamp
        # Profiling counters, reported by stats().
        self.scatters = 0
        self.scatter_rows_total = 0
        self.windows = 0

    # -- inspection ------------------------------------------------------

    @property
    def capacity(self) -> int:
        return len(self._names)

    @property
    def depth(self) -> int:
        return len(self._slot)

    def __contains__(self, name: str) -> bool:
        return name in self._slot

    def names(self) -> list[str]:
        return list(self._slot)

    def _rebase(self, t: float) -> np.float32:
        if self._epoch is None:
            self._epoch = float(t)
        return np.float32(t - self._epoch)

    # -- mutation (host mirror only; O(1) each) --------------------------

    def upsert(self, name: str, *, base_priority: float = 0.0,
               slo_target: float = 0.0, submitted: float = 0.0,
               run_seconds: float = 0.0, parked_until: float = 0.0,
               tenant: int = 0, seq: int | None = None) -> bool:
        """Insert or update one pending row. Returns False (and changes
        nothing) when the queue is bounded and full and `name` is new —
        the admission-shed signal."""
        slot = self._slot.get(name)
        if slot is None:
            if self.bound is not None and len(self._slot) >= self.bound:
                return False
            if not self._free:
                self._grow()
            slot = heapq.heappop(self._free)
            self._slot[name] = slot
            self._names[slot] = name
        if seq is None:
            seq = self._seq
        self._seq = max(self._seq, int(seq)) + 1
        h = self._host
        h.valid[slot] = True
        h.base_priority[slot] = np.float32(base_priority)
        h.slo_target[slot] = np.float32(slo_target)
        h.submitted[slot] = self._rebase(submitted)
        h.run_seconds[slot] = np.float32(run_seconds)
        h.parked_until[slot] = self._rebase(parked_until) \
            if parked_until else np.float32(0.0)
        h.tenant[slot] = np.int32(tenant)
        h.seq[slot] = np.uint32(seq)
        self._dirty.add(slot)
        return True

    def remove(self, names: Iterable[str]) -> int:
        """Invalidate slots (bind/delete). Unknown names are ignored —
        removal is idempotent like FakeApiServer.delete_pod."""
        n = 0
        for name in names:
            slot = self._slot.pop(name, None)
            if slot is None:
                continue
            self._host.valid[slot] = False
            self._names[slot] = None
            heapq.heappush(self._free, slot)
            self._dirty.add(slot)
            n += 1
        return n

    def park(self, name: str, until: float) -> bool:
        """Backoff-park one pod: ineligible until `until` (absolute
        time, same clock as upsert/window). The row keeps its place,
        priority keeps decaying — parking masks eligibility only."""
        slot = self._slot.get(name)
        if slot is None:
            return False
        self._host.parked_until[slot] = self._rebase(until)
        self._dirty.add(slot)
        return True

    # -- device sync + window -------------------------------------------

    def _grow(self) -> None:
        old = self._host
        old_cap = len(self._names)
        new_cap = old_cap * 2
        self._host = queue_kernels.empty_table(new_cap)
        for f, arr in zip(self._host._fields, self._host):
            arr[:old_cap] = getattr(old, f)
        self._names.extend([None] * old_cap)
        for s in range(old_cap, new_cap):
            heapq.heappush(self._free, s)
        self._dev = None            # full re-upload on next flush

    def _flush(self) -> None:
        """Ship dirty mirror rows to the device twin: one pow2-padded
        scatter per cycle (or a full device_put after growth)."""
        if self._dev is None:
            self._dev = jax.device_put(
                queue_kernels.QueueTable(*[np.asarray(a) for a in self._host]))
            self._dirty.clear()
            return
        if not self._dirty:
            return
        rows = sorted(self._dirty)
        idx = _pad_pow2(rows)
        row_data = queue_kernels.QueueTable(
            *[np.ascontiguousarray(a[idx]) for a in self._host])
        self._dev = scatter_rows(self._dev, idx, row_data)
        self.scatters += 1
        self.scatter_rows_total += len(rows)
        self._dirty.clear()

    def window(self, now: float, w: int):
        """Extract the top-`w` solve window ON DEVICE: flush dirty
        rows, rank the whole table in-kernel, slice the pow2 window
        bucket, and map the returned slots back to names. Returns
        (names in pop order, n_eligible, depth) with
        len(names) == min(w, n_eligible)."""
        self._flush()
        if self._epoch is None:
            return [], 0, 0
        cap = self.capacity
        kb = queue_kernels.k_bucket(min(max(int(w), 1), cap), cap)
        win, _prio, n_elig, depth = queue_kernels.window_select(
            self._dev, self._rebase(now), self.qos_gain, kb)
        self.windows += 1
        n_elig = int(n_elig)
        take = min(int(w), n_elig, kb)
        names = []
        for s in np.asarray(win)[:take]:
            nm = self._names[int(s)]
            if nm is not None:
                names.append(nm)
        return names, n_elig, int(depth)

    def stats(self) -> dict:
        return {
            "depth": self.depth,
            "capacity": self.capacity,
            "bound": self.bound,
            "scatters": self.scatters,
            "scatter_rows_total": self.scatter_rows_total,
            "windows": self.windows,
        }
