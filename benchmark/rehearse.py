#!/usr/bin/env python3
"""Rehearsal of a cell's host path at full size on the CPU, untimed.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py --workload <cell> --cycles 12

The full-size solve must not be compiled or run on a CPU, so this
drives everything around it: the cell's generator at its real size
(5,000 nodes, ~140k running pods, the mix's queue), the client diff
(`codec.delta_between`, as `DeltaSession` runs it), and the sidecar's
device-resident session (`DeviceSession.from_base_store` +
`apply_delta`, on CPU arrays). Placements come from the configuration's
reference, so the bind / complete / arrive waves are the ones the
cell will make. It fails unless every cycle stays on the delta rung
with 0 device-state rebuilds and the session's arrays equal a fresh
decode of the records the client sent.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--cycles", type=int, default=12)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import harness

    harness.prepare_env()
    import jax

    from tpusched import EngineConfig
    from tpusched.rpc import codec
    from tpusched.rpc.server import DeviceSession

    spec = harness.cell_spec(args.workload)
    mix, conf = spec["traffic"], spec["config"]
    gen, reference = harness.plugins(spec)
    cfg = EngineConfig.from_dict(conf["engine"])
    c = gen.Cluster(conf, mix, args.seed)
    static = c.static_view()
    bk = harness.cell_buckets(spec)
    if mix["arrival"] == "closed":
        c.add_pending(int(mix["backlog"]))
    else:
        c.add_pending(c.pend_cap // 2)
    base = codec.SnapshotStore()
    base.set_full_bytes(c.msg)
    session = DeviceSession.from_base_store(base, "s0", cfg, bk)
    print(json.dumps(dict(phase="seeded", device=jax.devices()[0].platform,
                          running=c.n_run, pending=c.n_pend,
                          buckets=dataclasses.asdict(session.device.meta.buckets)
                          )), flush=True)
    sid = "s0"
    bad = []
    for k in range(args.cycles):
        node, _ = reference.sequential(conf["engine"], static, c.cycle_view())
        placed = np.flatnonzero(node >= 0)
        names = [c.pend_names[s] for s in placed]
        bound = c.bind(names, [int(node[s]) for s in placed])
        comp = mix["completions"]
        done = c.complete(len(bound) if comp == "bound" else int(comp))
        if mix["arrival"] == "closed":
            new = c.add_pending(int(mix["backlog"]) - c.n_pend)
        else:
            new = c.add_pending(min(len(bound), c.pend_cap - c.n_pend))
        qos = c.qos_churn(float(mix["qos_churn"]))
        nb = codec.SnapshotStore()
        delta = codec.delta_between(base, c.msg, sid, new_bytes=nb,
                                    changed=set(bound) | set(new) | set(qos))
        new_sid = f"s{k + 1}"
        stats = session.apply_delta(sid, delta, new_sid)
        base, sid = nb, new_sid
        row = dict(phase="cycle", k=k, placed=len(bound), completed=len(done),
                   arrived=len(new), qos=len(qos), path=stats.path,
                   reason=stats.reason, h2d_bytes=stats.h2d_bytes,
                   rebuilds=session.device.rebuilds, running=c.n_run,
                   pending=c.n_pend)
        print(json.dumps(row), flush=True)
        if stats.path != "delta" or session.device.rebuilds:
            bad.append(row)
    fresh, meta = codec.decode_snapshot(base.compose_bytes(), cfg, bk)
    dev = session.device.snap
    same = all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(
        jax.tree.leaves((dev.nodes.used, dev.nodes.allocatable,
                         dev.pods.requests, dev.running.requests,
                         dev.running.node_idx)),
        jax.tree.leaves((fresh.nodes.used, fresh.nodes.allocatable,
                         fresh.pods.requests, fresh.running.requests,
                         fresh.running.node_idx))))
    used = c.cycle_view()["used"]
    used_ok = np.array_equal(
        np.asarray(fresh.nodes.used)[:len(used)],
        (used * np.array([1, 1 << 20, 1])).astype(np.float32))
    print(json.dumps(dict(phase="done", delta_rung=not bad,
                          session_equals_fresh_decode=bool(same),
                          used_equals_generator=bool(used_ok))), flush=True)
    return 0 if (not bad and same and used_ok) else 1


if __name__ == "__main__":
    sys.exit(main())
