#!/usr/bin/env python
"""Chip smoke: the served scheduling cycle, once, on the local TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # sharded serving only, four chips

One chip: the BASELINE config-2 cluster (10,000 pending pods on 5,000
nodes, QoS on) goes through an in-process sidecar (`make_server`) and
the normal client (`SchedulerClient` + `DeltaSession`), in parity and
in fast mode: one full Assign, then delta Assigns that each change ~1%
of the pods and nodes. Every reply is audited by
`oracle.validate_assignment`; a reduced cluster with every constraint
family and preemption on is compared with `oracle.Oracle` exactly.

Four chips: sidecars on a (4,1) mesh, a (2,2) mesh and a (4,1) mesh
with ring counts serve the same 10k x 5k cluster in fast mode, and a
(4,1) ring sidecar serves a config-3 (pairwise) cluster, where the ring
kernel actually runs; their placements must equal a single-device
Engine's bit for bit, and the device-resident snapshot must span all
four devices.

Earlier stdout lines are one JSON object per phase; the last line is
`{"ok": true, "device": {...}}`. Any failed check raises, so the exit
code is non-zero and that line is never printed. With no TPU visible
the script exits non-zero before doing any work.
"""

from __future__ import annotations

import argparse
import json
import logging
import sys
import time
from typing import NamedTuple

import numpy as np

PODS, NODES = 10_000, 5_000   # BASELINE config 2; 5,000 = upstream node limit
CHURN = 0.01                  # share of pods and nodes each delta changes
DELTAS = 8                    # delta Assigns per mode (first one seeds)
MESHES = (((4, 1), False), ((2, 2), False), ((4, 1), True))
# Config 2 has no pod-affinity signatures, so ring_counts takes the dense
# path there; the ring kernel itself runs on a (4,1) mesh over a BASELINE
# config-3 cluster (spread + inter-pod affinity, synth.config3_pairwise's
# default shape).
RING_PAIRWISE = (2_000, 500)


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


class _Cluster:
    """A wire snapshot plus the seeded churn applied to it between
    Assigns: pods get a new observed availability, nodes a cpu
    allocatable at or above the original (running pods keep fitting)."""

    def __init__(self, seed: int, n_pods: int, n_nodes: int, **kw):
        from tpusched.rpc.codec import snapshot_to_proto
        from tpusched.synth import make_cluster

        self.rng = np.random.default_rng(seed)
        nrec, prec, rrec = make_cluster(self.rng, n_pods, n_nodes,
                                        as_records=True, **kw)
        self.msg = snapshot_to_proto(nrec, prec, rrec)
        self.cpu0 = [next(r.quantity for r in n.allocatable
                          if r.name == "cpu") for n in self.msg.nodes]

    def churn(self) -> set:
        changed = set()
        npods, nnodes = len(self.msg.pods), len(self.msg.nodes)
        for j in self.rng.choice(npods, max(1, round(npods * CHURN)),
                                 replace=False):
            p = self.msg.pods[int(j)]
            p.observed_availability = float(self.rng.uniform(0.5, 1.0))
            changed.add(p.name)
        for j in self.rng.choice(nnodes, max(1, round(nnodes * CHURN)),
                                 replace=False):
            n = self.msg.nodes[int(j)]
            cpu = next(r for r in n.allocatable if r.name == "cpu")
            cpu.quantity = self.cpu0[int(j)] * float(self.rng.uniform(1, 1.1))
            changed.add(n.name)
        return changed

    def decode(self, cfg, buckets=None):
        from tpusched.rpc.codec import decode_snapshot

        return decode_snapshot(self.msg, cfg, buckets)


class _NativeFallbacks(logging.Handler):
    """Counts the codec's native-decoder fallback warnings."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.count = 0

    def emit(self, record):
        self.count += 1


class Reply(NamedTuple):
    """A packed AssignResponse as bucket-width arrays in the locally
    decoded snapshot's canonical order."""
    node: np.ndarray        # [P] int32, -1 = unplaced
    commit_key: np.ndarray  # [P] int32
    evicted: np.ndarray     # [M] bool


def _reply(resp, meta) -> Reply:
    from tpusched.rpc.client import assign_response_arrays

    pod_names, node_names, node_idx, _, ck = assign_response_arrays(resp)
    if pod_names != list(meta.pod_names) or node_names != list(
            meta.node_names):
        raise AssertionError("reply name tables differ from the decoder's")
    P = meta.buckets.pods
    node = np.full(P, -1, np.int32)
    node[: len(node_idx)] = node_idx
    key = np.full(P, np.iinfo(np.int32).max, np.int32)
    key[: len(ck)] = ck
    run = {n: i for i, n in enumerate(meta.running_names)}
    ev = np.zeros(meta.buckets.running_pods, bool)
    ev[[run[n] for n in resp.evicted]] = True
    return Reply(node, key, ev)


def _audit(snap, cfg, r: Reply, what: str) -> None:
    from tpusched.oracle import validate_assignment

    bad = validate_assignment(snap, cfg, r.node, commit_key=r.commit_key,
                              evicted=r.evicted)
    if bad:
        raise AssertionError(f"{what}: {len(bad)} violations, e.g. {bad[:3]}")


def _served(cfg, cluster: _Cluster, deltas: int, platform: str,
            check=None, on_server=None) -> dict:
    """One sidecar at `cfg`, floor buckets pinned to the cluster's own:
    a full Assign then `deltas` delta Assigns through DeltaSession.
    `check(snap, reply, what)` audits each reply;
    `on_server(svc)` runs before shutdown. Returns the timings."""
    from tpusched import ledger, native
    from tpusched.rpc.client import DeltaSession, SchedulerClient
    from tpusched.rpc.server import make_server

    if not native.available():
        raise AssertionError("native wire decoder unavailable")
    fallbacks = _NativeFallbacks()
    logging.getLogger("tpusched.native").addHandler(fallbacks)
    _, meta0 = cluster.decode(cfg)
    server, port, svc = make_server(config=cfg, buckets=meta0.buckets,
                                    log_stream=_Null())
    server.start()
    client = SchedulerClient(f"127.0.0.1:{port}")
    sess = DeltaSession(client)
    try:
        c0, s0 = ledger.COMPILES.counters()
        t0 = time.perf_counter()
        resp = sess.assign(cluster.msg, packed_ok=True)
        first_s = time.perf_counter() - t0
        c1, s1 = ledger.COMPILES.counters()
        times = []
        replies = [(resp, *cluster.decode(cfg, meta0.buckets))]
        for _ in range(deltas):
            changed = cluster.churn()
            t0 = time.perf_counter()
            resp = sess.assign(cluster.msg, packed_ok=True, changed=changed)
            times.append(time.perf_counter() - t0)
            replies.append((resp, *cluster.decode(cfg, meta0.buckets)))
        c2, _ = ledger.COMPILES.counters()
        for k, (resp, snap, meta) in enumerate(replies):
            if check is not None:
                check(snap, _reply(resp, meta), f"{cfg.mode} Assign {k}")
        health = client.health()
        if on_server is not None:
            on_server(svc)
    finally:
        client.close()
        server.stop(None)
        svc.close()
        logging.getLogger("tpusched.native").removeHandler(fallbacks)
    if health.backend != platform:
        raise AssertionError(f"Health.backend={health.backend!r}")
    if health.watchdog_trips or health.ladder_demotions:
        raise AssertionError(
            f"watchdog_trips={health.watchdog_trips} "
            f"ladder_demotions={health.ladder_demotions}")
    if health.serving_path != "delta":
        raise AssertionError(f"serving_path={health.serving_path!r}")
    if (sess.full_sends, sess.delta_sends, sess.fallbacks) != (1, deltas, 0):
        raise AssertionError(
            f"full_sends={sess.full_sends} delta_sends={sess.delta_sends} "
            f"fallbacks={sess.fallbacks}: deltas were resent in full")
    # The first delta seeds the device session; every later one must be
    # applied to it on the device (a hit), not re-decoded.
    if svc.session_seeds != 1 or svc.session_hits != deltas - 1:
        raise AssertionError(
            f"session_seeds={svc.session_seeds} "
            f"session_hits={svc.session_hits}: not served on the delta rung")
    if fallbacks.count:
        raise AssertionError(f"{fallbacks.count} native decode fallbacks")
    return dict(first_assign_ms=first_s * 1e3,
                compile_ms=(s1 - s0) * 1e3, compiles_first=c1 - c0,
                compiles_after_first=c2 - c1,
                first_delta_ms=times[0] * 1e3,
                steady_delta_ms=[t * 1e3 for t in times[1:]])


class _Null:
    """Swallows the sidecar's per-batch JSON log."""

    def write(self, _):
        return 0

    def flush(self):
        pass


def _session(svc):
    """The sidecar's one device-resident lineage (it may be keyed
    under more than one snapshot id)."""
    sessions = {id(s): s for s in svc._sessions.values()}
    if len(sessions) != 1:
        raise AssertionError(f"{len(sessions)} device sessions, want 1")
    return next(iter(sessions.values()))


def bur_probe(svc, reps: int = 5) -> dict:
    """Does block_until_ready on a solve output wait here? Times the
    served engine's (compiled) packed solve on its device-resident
    snapshot: dispatch, then block_until_ready, then the host read. If
    it waits, the read after it is only the copy."""
    import jax

    snap, solve = _session(svc).device.snap, svc._engine._solve_packed_jit
    np.asarray(solve(snap))
    rows = []
    for _ in range(reps):
        t0 = time.perf_counter()
        buf = solve(snap)
        t1 = time.perf_counter()
        jax.block_until_ready(buf)
        t2 = time.perf_counter()
        np.asarray(buf)
        t3 = time.perf_counter()
        rows.append((t1 - t0, t2 - t1, t3 - t2))
    d, b, f = (float(np.median(c)) * 1e3 for c in zip(*rows))
    return dict(dispatch_ms=d, block_ms=b, read_after_block_ms=f,
                block_until_ready_waits=bool(f < 0.25 * (d + b)))


def one_chip(seed: int, platform: str, n_pods: int = PODS,
             n_nodes: int = NODES, deltas: int = DELTAS,
             oracle_shape: tuple = (48, 12)) -> None:
    """The main path at full width in parity and fast mode, then the
    oracle comparison at a reduced shape."""
    import jax

    from tpusched import EngineConfig
    from tpusched.oracle import Oracle

    def audit(snap, r, what):
        _audit(snap, cfg, r, what)

    probe = {}
    for mode in ("parity", "fast"):
        cfg = EngineConfig(mode=mode)
        cluster = _Cluster(seed, n_pods, n_nodes, n_running_per_node=1,
                           with_qos=True)
        t = _served(cfg, cluster, deltas, platform, check=audit,
                    on_server=(lambda svc: probe.update(bur_probe(svc)))
                    if mode == "fast" else None)
        steady = t.pop("steady_delta_ms")
        say(phase="serve", mode=mode, pods=n_pods, nodes=n_nodes,
            deltas=deltas, steady_assign_p50_ms=float(np.median(steady)),
            steady_assign_n=len(steady), **t)
    say(phase="block_until_ready", **probe)
    stats = jax.devices()[0].memory_stats() or {}
    say(phase="memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"))

    # Reduced shape, every constraint family and preemption on (the
    # __graft_entry__ mix): parity placements and evictions == oracle.
    cfg = EngineConfig(mode="parity", preemption=True)
    cluster = _Cluster(
        seed, *oracle_shape, taint_frac=0.3, toleration_frac=0.3,
        selector_frac=0.2, affinity_frac=0.3, spread_frac=0.3,
        interpod_frac=0.3, run_anti_frac=0.1, gang_frac=0.25, gang_size=2,
        namespace_count=2, pdb_frac=0.3, initial_utilization=0.8)
    agree = []

    def vs_oracle(snap, r, what):
        ref = Oracle(snap, cfg).solve()
        if not (ref.assignment == r.node).all():
            raise AssertionError(f"{what}: parity != oracle placements")
        ref_ev = (np.zeros_like(r.evicted) if ref.evicted is None
                  else np.asarray(ref.evicted))
        if not (ref_ev == r.evicted).all():
            raise AssertionError(f"{what}: parity != oracle evictions")
        agree.append((int((r.node >= 0).sum()), int(r.evicted.sum())))

    _served(cfg, cluster, 3, platform, check=vs_oracle)
    say(phase="oracle", pods=oracle_shape[0], nodes=oracle_shape[1],
        assigns=len(agree), placed=[a for a, _ in agree],
        evicted=[e for _, e in agree])


def four_chips(seed: int, platform: str, n_pods: int = PODS,
               n_nodes: int = NODES, deltas: int = 2,
               pairwise: tuple = RING_PAIRWISE) -> None:
    """Sharded serving: every mesh sidecar's placements == one device."""
    import jax

    from tpusched import Engine, EngineConfig

    config2 = dict(n_pods=n_pods, n_nodes=n_nodes, n_running_per_node=1,
                   with_qos=True)
    config3 = dict(n_pods=pairwise[0], n_nodes=pairwise[1],
                   spread_frac=0.5, interpod_frac=0.5)
    cases = [(shape, ring, config2) for shape, ring in MESHES]
    cases.append(((4, 1), True, config3))
    ref = Engine(EngineConfig(mode="fast"))
    for shape, ring, kw in cases:
        cfg = EngineConfig(mode="fast", mesh_shape=shape, ring_counts=ring)
        cluster = _Cluster(seed, **kw)
        spans = []

        def same_as_one_device(snap, r, what):
            _audit(snap, cfg, r, what)
            want = np.asarray(ref.solve(ref.put(snap)).assignment)
            if (want != r.node).any():
                raise AssertionError(
                    f"{what} on {shape}: {int((want != r.node).sum())} "
                    f"placements differ from the single-device solve "
                    f"(placed {int((want >= 0).sum())} vs "
                    f"{int((r.node >= 0).sum())})")

        def snapshot_spans(svc):
            spans.extend(len(leaf.sharding.device_set) for leaf in
                         jax.tree.leaves(_session(svc).device.snap))

        t = _served(cfg, cluster, deltas, platform, check=same_as_one_device,
                    on_server=snapshot_spans)
        if set(spans) != {4}:
            raise AssertionError(f"{shape}: snapshot spans {set(spans)} "
                                 "devices, want 4")
        say(phase="mesh", mesh=list(shape), ring_counts=ring,
            pods=kw["n_pods"], nodes=kw["n_nodes"],
            signatures=int(cluster.decode(cfg)[0].sigs.key.shape[0]),
            assigns=deltas + 1, identical=True,
            first_assign_ms=t["first_assign_ms"],
            compile_ms=t["compile_ms"])
    ref.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU visible (platform "
              f"{devs[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} visible",
              file=sys.stderr)
        return 2
    from tpusched.shapeclass import enable_persistent_cache

    say(phase="device", kind=devs[0].device_kind, count=len(devs),
        compile_cache=enable_persistent_cache())
    if args.chips == 4:
        four_chips(args.seed, devs[0].platform)
    else:
        one_chip(args.seed, devs[0].platform)
    say(ok=True, device={"platform": devs[0].platform,
                         "kind": devs[0].device_kind, "count": len(devs)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
