"""Proto <-> SnapshotBuilder codec (SURVEY.md C12).

The wire model is spec-level records; this module is the single place
where they meet the engine's host-side interning (SnapshotBuilder).
snapshot_to_proto exists for clients/tests that already hold builder
-style records (the host shim uses it); a Go scheduler would emit the
proto directly from its cache.
"""

from __future__ import annotations

import logging
import operator
import os
import traceback
from itertools import compress, filterfalse

from tpusched.config import (Buckets, DEFAULT_OBSERVED_AVAIL,
                             DEFAULT_SLO_TARGET, EngineConfig)
from tpusched.rpc import tpusched_pb2 as pb
from tpusched.snapshot import (
    MatchExpression,
    NodeSelectorTerm,
    PodAffinityTerm,
    PreferredTerm,
    SnapshotBuilder,
    Toleration,
    TopologySpreadConstraint,
)


def decode_snapshot(
    msg: "pb.ClusterSnapshot | bytes",
    config: EngineConfig | None = None,
    buckets: Buckets | None = None,
    prefer_native: bool | None = None,
):
    """Decode a wire snapshot, preferring the native C++ decoder
    (tpusched.native, ~8x faster at 10k x 5k and exactly equal to the
    Python path) when it is available. prefer_native=None consults the
    TPUSCHED_NO_NATIVE env toggle; False forces the Python path.

    `msg` may be the parsed message or its serialized BYTES: the
    sidecar's delta path composes snapshots as concatenated per-record
    wire bytes (SnapshotStore.compose_bytes) and hands them straight to
    the native parser — no Python message is ever materialized there.
    For a parsed message, the re-serialization feeding the native
    parser is upb-backed and costs ~5 ms at 10k x 5k (measured) — noise
    next to the ~350 ms of Python decode it replaces.

    A native decode error falls back to the Python path: if the input
    is genuinely bad, Python raises the authoritative error; if it was
    a native-only limitation (e.g. exotic numeric literals), the slow
    path still serves the request."""
    if prefer_native is None:
        prefer_native = os.environ.get("TPUSCHED_NO_NATIVE", "") in ("", "0")
    if prefer_native:
        from tpusched import native  # tpl: disable=TPL001(the native .so is optional and may BUILD on first import; the pure-python path must not pay or risk that at module import)

        if native.available():
            try:
                data = (msg if isinstance(msg, bytes)
                        else msg.SerializeToString())
                return native.decode_snapshot_bytes(data, config, buckets)
            except Exception:
                # The fallback must be LOUD: a native decode failure is
                # either a contract bug (native.py calls it "a bug in
                # this file") or a permanent ~8x decode slowdown.
                logging.getLogger("tpusched.native").warning(
                    "native decode failed; falling back to the Python "
                    "decoder for this request:\n%s",
                    traceback.format_exc(limit=3),
                )
    if isinstance(msg, bytes):
        msg = pb.ClusterSnapshot.FromString(msg)
    return snapshot_from_proto(msg, config, buckets)


def _res_map(resources) -> dict[str, float]:
    return {r.name: r.quantity for r in resources}


def _labels(labels) -> dict[str, str]:
    return {l.key: l.value for l in labels}


def _exprs(msgs) -> tuple[MatchExpression, ...]:
    return tuple(
        MatchExpression(m.key, m.op, tuple(m.values)) for m in msgs
    )


def _affinity(msgs) -> list[PodAffinityTerm]:
    return [
        PodAffinityTerm(
            topology_key=t.topology_key,
            selector=_exprs(t.selector),
            anti=t.anti,
            required=t.required,
            weight=t.weight or 1.0,
            namespaces=tuple(t.namespaces),
        )
        for t in msgs
    ]


def node_kwargs(n: "pb.Node") -> dict:
    """Wire Node -> SnapshotBuilder.add_node kwargs (incl. 'name').
    The single proto->record authority, shared by the full decoder and
    the device-resident delta path (rpc.server.DeviceSession)."""
    return dict(
        name=n.name,
        allocatable=_res_map(n.allocatable),
        labels=_labels(n.labels),
        taints=[(t.key, t.value, t.effect) for t in n.taints],
        used=_res_map(n.used),
        unschedulable=n.unschedulable,
    )


def pod_kwargs(p: "pb.PendingPod") -> dict:
    """Wire PendingPod -> SnapshotBuilder.add_pod kwargs (incl. 'name')."""
    return dict(
        name=p.name,
        requests=_res_map(p.requests),
        priority=p.priority,
        slo_target=p.slo_target,
        # proto3 cannot distinguish unset from 0.0: clients must set
        # observed_availability explicitly (0.0 means 0.0; a pod with
        # no SLO is unaffected either way since pressure clips at 0).
        observed_avail=p.observed_availability,
        labels=_labels(p.labels),
        node_selector=_labels(p.node_selector),
        required_terms=[
            NodeSelectorTerm(_exprs(t.expressions))
            for t in p.required_terms
        ],
        preferred_terms=[
            PreferredTerm(t.weight, NodeSelectorTerm(_exprs(t.term.expressions)))
            for t in p.preferred_terms
        ],
        tolerations=[
            Toleration(t.key, t.operator or "Equal", t.value, t.effect)
            for t in p.tolerations
        ],
        topology_spread=[
            TopologySpreadConstraint(
                topology_key=c.topology_key,
                max_skew=c.max_skew,
                when_unsatisfiable=c.when_unsatisfiable,
                selector=_exprs(c.selector),
            )
            for c in p.topology_spread
        ],
        pod_affinity=_affinity(p.pod_affinity),
        pod_group=p.pod_group or None,
        pod_group_min_member=p.pod_group_min_member,
        namespace=p.namespace or "default",
    )


def running_kwargs(r: "pb.RunningPod") -> dict:
    """Wire RunningPod -> SnapshotBuilder.add_running_pod kwargs, plus
    'name' (the builder doesn't key running pods; delta paths do)."""
    return dict(
        name=r.name,
        node=r.node,
        requests=_res_map(r.requests),
        priority=r.priority,
        slack=r.slack,
        labels=_labels(r.labels),
        count_into_used=not r.exclude_from_used,
        pod_affinity=_affinity(r.pod_affinity),
        namespace=r.namespace or "default",
        pdb_group=r.pdb_group or None,
        pdb_disruptions_allowed=r.pdb_disruptions_allowed,
    )


def snapshot_from_proto(
    msg: pb.ClusterSnapshot,
    config: EngineConfig | None = None,
    buckets: Buckets | None = None,
):
    """Decode a wire snapshot into a built (ClusterSnapshot, SnapshotMeta).

    Records are processed in NAME order, not wire order: index-based
    tie-breaks (lowest node index among score maxima, submission order
    among equal priorities) are then deterministic for a given cluster
    STATE regardless of how the records were transported — a full send
    and a delta-path recompose of the same state schedule identically."""
    config = config or EngineConfig()
    b = SnapshotBuilder(config, buckets)
    for n in _by_name(msg.nodes):
        b.add_node(**node_kwargs(n))
    for p in _by_name(msg.pods):
        b.add_pod(**pod_kwargs(p))
    for r in _by_name(msg.running):
        kw = running_kwargs(r)
        kw.pop("name")
        b.add_running_pod(**kw)
    snap, meta = b.build()
    # Running-pod names travel with meta for eviction responses — in the
    # same name-sorted order the arrays were built in, so evicted[m]
    # resolves to the right pod whatever the wire order was.
    meta.running_names = [
        r.name or f"running-{i}" for i, r in enumerate(_by_name(msg.running))
    ]
    return snap, meta


# ---------------------------------------------------------------------------
# Delta snapshots (SURVEY.md §7 hard part 6).
# ---------------------------------------------------------------------------


_NAME = operator.attrgetter("name")


class UnknownBase(KeyError):
    """Delta referenced a base_id the store no longer holds."""


def _by_name(coll):
    """Canonical record order (see snapshot_from_proto): sort by name
    WITHOUT copying messages (decode is the hot path). Running pods may
    be unnamed; Python's stable sort keeps their relative wire order."""
    return sorted(coll, key=lambda r: r.name)


def record_names(msg: pb.ClusterSnapshot):
    """Each collection's record names (nodes, pods, running), read once
    in message order, as (names, name set) pairs; None when the snapshot
    is not delta-safe (see delta_safe). The named diff takes these
    pairs, so one name pass serves both the safety check and the diff."""
    out = []
    for coll in (msg.nodes, msg.pods, msg.running):
        names = list(map(_NAME, coll))
        name_set = set(names)
        if "" in name_set or len(name_set) != len(names):
            return None
        out.append((names, name_set))
    return tuple(out)


def delta_safe(msg: pb.ClusterSnapshot) -> bool:
    """A snapshot is usable as a delta base only if every record carries
    a unique non-empty name: the stores key by name, so unnamed or
    duplicate records would silently collapse on the delta path."""
    return record_names(msg) is not None


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


class SnapshotStore:
    """Name-keyed record store of one snapshot's proto sub-messages
    (messages OR their serialized bytes), so a SnapshotDelta can be
    applied and the full ClusterSnapshot recomposed server-side. Wire
    savings: the client ships only changed records; the recompose +
    re-intern cost stays on the sidecar host.

    The sidecar stores BYTES (set_full_bytes): applying a delta then
    serializes only the churned records, and compose_bytes() builds the
    full serialized snapshot by pure concatenation — protobuf wire
    format allows a repeated field's entries to appear anywhere in the
    stream — feeding the native decoder with no Python message at all."""

    def __init__(self, msg: pb.ClusterSnapshot | None = None):
        self.nodes: dict[str, "pb.Node | bytes"] = {}
        self.pods: dict[str, "pb.PendingPod | bytes"] = {}
        self.running: dict[str, "pb.RunningPod | bytes"] = {}
        if msg is not None:
            self.set_full(msg)

    def set_full(self, msg: pb.ClusterSnapshot) -> None:
        self.nodes = {n.name: n for n in msg.nodes}
        self.pods = {p.name: p for p in msg.pods}
        self.running = {r.name: r for r in msg.running}

    def set_full_bytes(self, msg: pb.ClusterSnapshot) -> None:
        """Store serialized records (one upb serialize pass per record,
        full sends only); later delta cycles reuse the bytes."""
        self.nodes = {n.name: n.SerializeToString() for n in msg.nodes}
        self.pods = {p.name: p.SerializeToString() for p in msg.pods}
        self.running = {r.name: r.SerializeToString() for r in msg.running}

    def copy(self) -> "SnapshotStore":
        st = SnapshotStore()
        st.nodes, st.pods, st.running = (
            dict(self.nodes), dict(self.pods), dict(self.running)
        )
        return st

    def nbytes(self) -> int:
        """Retained payload bytes (serialized record sizes; message-
        typed entries report ByteSize) — feeds the sidecar's
        scheduler_device_bytes{kind="byte_stores"} gauge (round 12).
        Copies share record objects, so summing every registered
        store OVERCOUNTS shared bytes; the gauge documents that."""
        total = 0
        for coll in (self.nodes, self.pods, self.running):
            for v in coll.values():
                total += (len(v) if isinstance(v, (bytes, bytearray))
                          else v.ByteSize())
        return total

    def apply_delta(self, delta: pb.SnapshotDelta) -> None:
        """Upserts are stored as bytes when the store holds bytes
        (serialize churn only), as messages otherwise."""
        as_bytes = any(
            isinstance(next(iter(d.values()), None), bytes)
            for d in (self.nodes, self.pods, self.running)
        )

        def put(d, rec):
            d[rec.name] = rec.SerializeToString() if as_bytes else rec

        for n in delta.upsert_nodes:
            put(self.nodes, n)
        for name in delta.remove_nodes:
            self.nodes.pop(name, None)
        for p in delta.upsert_pods:
            put(self.pods, p)
        for name in delta.remove_pods:
            self.pods.pop(name, None)
        for r in delta.upsert_running:
            put(self.running, r)
        for name in delta.remove_running:
            self.running.pop(name, None)

    def apply_update(self, update) -> None:
        """Apply a named diff's base update (delta_between's `update`):
        per collection, name -> the record's new bytes, or None for a
        removal. O(churn): the store becomes the diffed snapshot's."""
        for d, upd in zip((self.nodes, self.pods, self.running), update):
            for name, raw in upd.items():
                if raw is None:
                    d.pop(name, None)
                else:
                    d[name] = raw

    def compose(self) -> pb.ClusterSnapshot:
        msg = pb.ClusterSnapshot()
        if any(isinstance(v, bytes) for v in
               (*self.nodes.values(), *self.pods.values(),
                *self.running.values())):
            return pb.ClusterSnapshot.FromString(self.compose_bytes())
        msg.nodes.extend(self.nodes.values())
        msg.pods.extend(self.pods.values())
        msg.running.extend(self.running.values())
        return msg

    # ClusterSnapshot field tags, wire type 2 (length-delimited):
    # (1<<3)|2, (2<<3)|2, (3<<3)|2.
    _TAGS = (b"\x0a", b"\x12", b"\x1a")

    def compose_bytes(self) -> bytes:
        """Serialized ClusterSnapshot by concatenating length-delimited
        record fields — a few ms at 10k x 5k vs ~25 ms for message
        compose + re-serialize. Record order is irrelevant: the decoder
        canonicalizes by name (snapshot_from_proto sorts; the native
        decoder matches it)."""
        parts = []
        for tag, d in zip(self._TAGS,
                          (self.nodes, self.pods, self.running)):
            for rec in d.values():
                raw = _ser(rec)
                parts.append(tag)
                parts.append(_varint(len(raw)))
                parts.append(raw)
        return b"".join(parts)


def _ser(rec) -> bytes:
    return rec if isinstance(rec, bytes) else rec.SerializeToString()


def delta_between(prev: SnapshotStore, msg: pb.ClusterSnapshot,
                  base_id: str,
                  new_bytes: SnapshotStore | None = None,
                  changed: "set[str] | None" = None,
                  walk: "list[int] | None" = None,
                  names=None,
                  update: "list[dict] | None" = None) -> pb.SnapshotDelta:
    """Client-side diff: the SnapshotDelta turning `prev` into `msg`.
    Record equality by serialized bytes. `prev` values may be messages
    or pre-serialized bytes (DeltaSession stores bytes so that a caller
    mutating its snapshot message in place between calls — the records
    would then alias — still diffs against what was actually sent).

    new_bytes: optional empty SnapshotStore; when given, filled with
    msg's per-record serialized bytes so the caller can remember them
    as the next base without serializing everything a second time.

    changed: optional set of record names the caller knows may have
    changed since the base (an informer-driven client knows exactly
    which objects its watch events touched). The diff then reads each
    record's name once and finds additions and removals by set
    operations on the names; it serializes and compares only the
    touched records — additions, and named records the base holds — in
    message order. Base records NOT named are trusted byte-identical,
    making the per-cycle diff O(churn) past the one name pass instead
    of O(cluster) serialization and bookkeeping. Additions and removals
    are still detected by name regardless. CONTRACT: a caller that
    mutates a record without naming it here ships a stale record and
    the sidecar solves a stale snapshot — name everything you touch.
    Upserts keep message order; removes come in name order.

    names: optional record_names(msg), when the caller already read the
    names for its safety check (named path only; read here otherwise).
    The snapshot must be delta-safe on the named path.

    update: optional empty list; on the named path filled with one dict
    per collection (nodes, pods, running): name -> the record's new
    bytes, or None for a removal. `prev.apply_update(update)` turns
    prev into msg's store in O(churn); prev itself is left as it is.

    walk: optional list; when given, two counts are appended to it: the
    records the diff examined one by one (with `changed`: the names
    read plus the records serialized; without: the new records, then
    the base records scanned for removals), then the records it
    serialized and compared — the scan the client pays per cycle."""
    delta = pb.SnapshotDelta(base_id=base_id)
    colls = (
        (prev.nodes, msg.nodes, delta.upsert_nodes, delta.remove_nodes),
        (prev.pods, msg.pods, delta.upsert_pods, delta.remove_pods),
        (prev.running, msg.running, delta.upsert_running,
         delta.remove_running),
    )
    if changed is None:
        walked = serialized = 0
        outs = ((new_bytes.nodes, new_bytes.pods, new_bytes.running)
                if new_bytes is not None else (None,) * 3)
        for (prev_d, coll, upserts, removes), out_d in zip(colls, outs):
            w, n = _full_diff(prev_d, coll, upserts, removes, out_d)
            walked += w
            serialized += n
    else:
        if not isinstance(changed, (set, frozenset)):
            changed = set(changed)
        if names is None:
            names = record_names(msg)
            if names is None:
                raise ValueError("the named diff needs a delta-safe "
                                 "snapshot: unique non-empty names")
        upd: "list[dict]" = []
        walked = serialized = 0
        for (prev_d, coll, upserts, removes), (nm, nm_set) in zip(colls,
                                                                 names):
            u: dict = {}
            n = _named_diff(prev_d, coll, nm, nm_set, changed, upserts,
                            removes, u)
            upd.append(u)
            walked += len(nm) + n
            serialized += n
        if update is not None:
            update.extend(upd)
        if new_bytes is not None:
            for out_d, (prev_d, *_) in zip(
                    (new_bytes.nodes, new_bytes.pods, new_bytes.running),
                    colls):
                out_d.update((k, _ser(v)) for k, v in prev_d.items())
            new_bytes.apply_update(upd)
    if walk is not None:
        walk.extend((walked, serialized))
    return delta


def _full_diff(prev_d, coll, upserts, removes, out_d) -> "tuple[int, int]":
    """Unnamed diff of one collection: serialize and compare every new
    record, then scan the base for removals. Returns (records examined,
    records serialized)."""
    new_names = set()
    n = 0
    for n, rec in enumerate(coll, 1):
        new_names.add(rec.name)
        old = prev_d.get(rec.name)
        raw = rec.SerializeToString()
        if out_d is not None:
            out_d[rec.name] = raw
        if old is None or _ser(old) != raw:
            upserts.append(rec)
    m = 0
    for m, k in enumerate(prev_d, 1):
        if k not in new_names:
            removes.append(k)
    return n + m, n


def _named_diff(prev_d, coll, names, name_set, changed, upserts, removes,
                upd) -> int:
    """Named diff of one collection (see delta_between). Removals come
    from one C-level pass over the base's names; additions from the
    named ones alone when their count accounts for every addition, else
    from a set difference. Only the touched records are serialized, in
    message order. Fills `upd` with the base update; returns the
    records serialized."""
    removed = sorted(filterfalse(name_set.__contains__, prev_d))
    removes.extend(removed)
    upd.update(dict.fromkeys(removed))
    named = changed & name_set
    added = named.difference(prev_d)
    if len(added) != len(name_set) - len(prev_d) + len(removed):
        added = name_set.difference(prev_d)  # an unnamed addition
    touched = named | added
    if not touched:
        return 0
    n = 0
    for i in compress(range(len(names)), map(touched.__contains__, names)):
        rec = coll[i]
        raw = rec.SerializeToString()
        n += 1
        old = prev_d.get(names[i])
        if old is None or _ser(old) != raw:
            upserts.append(rec)
            upd[names[i]] = raw
    return n


# ---------------------------------------------------------------------------
# Encoder (host shim / tests).
# ---------------------------------------------------------------------------


def _set_resources(field, mapping):
    for name, q in mapping.items():
        r = field.add()
        r.name, r.quantity = name, float(q)


def _set_labels(field, mapping):
    for k, v in sorted(mapping.items()):
        l = field.add()
        l.key, l.value = k, v


def _set_exprs(field, exprs):
    for e in exprs:
        m = field.add()
        m.key, m.op = e.key, e.op
        m.values.extend(e.values)


def _set_affinity(field, terms):
    for t in terms:
        m = field.add()
        m.topology_key = t.topology_key
        _set_exprs(m.selector, t.selector)
        m.anti, m.required, m.weight = t.anti, t.required, float(t.weight)
        m.namespaces.extend(t.namespaces)


def snapshot_to_proto(
    nodes: list[dict], pods: list[dict], running: list[dict] | None = None
) -> pb.ClusterSnapshot:
    """Encode builder-style records (the kwargs SnapshotBuilder.add_*
    take, plus 'name'/'node') into a wire snapshot."""
    msg = pb.ClusterSnapshot()
    for n in nodes:
        nm = msg.nodes.add()
        nm.name = n["name"]
        _set_resources(nm.allocatable, n.get("allocatable", {}))
        _set_labels(nm.labels, n.get("labels", {}))
        _set_resources(nm.used, n.get("used", {}))
        for (k, v, e) in n.get("taints", []):
            t = nm.taints.add()
            t.key, t.value, t.effect = k, v, e
        if n.get("unschedulable"):
            nm.unschedulable = True
    for p in pods:
        pm = msg.pods.add()
        pm.name = p["name"]
        _set_resources(pm.requests, p.get("requests", {}))
        pm.priority = float(p.get("priority", 0.0))
        pm.slo_target = float(p.get("slo_target", DEFAULT_SLO_TARGET))
        pm.observed_availability = float(
            p.get("observed_avail", DEFAULT_OBSERVED_AVAIL))
        _set_labels(pm.labels, p.get("labels", {}))
        _set_labels(pm.node_selector, p.get("node_selector", {}))
        for term in p.get("required_terms", []):
            tm = pm.required_terms.add()
            _set_exprs(tm.expressions, term.expressions)
        for pt in p.get("preferred_terms", []):
            tm = pm.preferred_terms.add()
            tm.weight = float(pt.weight)
            _set_exprs(tm.term.expressions, pt.term.expressions)
        for tol in p.get("tolerations", []):
            t = pm.tolerations.add()
            t.key, t.operator, t.value, t.effect = (
                tol.key, tol.operator, tol.value, tol.effect
            )
        for c in p.get("topology_spread", []):
            cm = pm.topology_spread.add()
            cm.topology_key = c.topology_key
            cm.max_skew = int(c.max_skew)
            cm.when_unsatisfiable = c.when_unsatisfiable
            _set_exprs(cm.selector, c.selector)
        _set_affinity(pm.pod_affinity, p.get("pod_affinity", []))
        if p.get("pod_group"):
            pm.pod_group = p["pod_group"]
            pm.pod_group_min_member = int(p.get("pod_group_min_member", 0))
        if p.get("namespace"):
            pm.namespace = p["namespace"]
    for r in running or []:
        rm = msg.running.add()
        rm.name = r.get("name", "")
        rm.node = r["node"]
        _set_resources(rm.requests, r.get("requests", {}))
        rm.priority = float(r.get("priority", 0.0))
        rm.slack = float(r.get("slack", 0.0))
        _set_labels(rm.labels, r.get("labels", {}))
        _set_affinity(rm.pod_affinity, r.get("pod_affinity", []))
        rm.exclude_from_used = not r.get("count_into_used", True)
        if r.get("namespace"):
            rm.namespace = r["namespace"]
        if r.get("pdb_group"):
            rm.pdb_group = r["pdb_group"]
            rm.pdb_disruptions_allowed = int(
                r.get("pdb_disruptions_allowed", 0)
            )
    return msg
