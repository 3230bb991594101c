#!/usr/bin/env python3
"""The control of `correct`: a cell run with one stated guarantee
broken, which the comparison has to call not correct.

    python3 benchmark/control.py --workload <cell> --seed <n> --seconds <s>

  * guarantee "sequential" (parity): the program's own path that would
    tempt a later PR, fast mode (`EngineConfig.mode = "fast"`), serves
    the cell and is judged against the sequential reference;
  * guarantee "valid" (fast): the reference put in the program's place
    with the capacity guarantee broken — every pod takes its best node
    against the cycle's starting state, nothing committed between pods
    (the configuration's reference, `sequential(commit=False)`), read on
    every cycle's own input (no_commit_answers).

With `--fault <name>` it plants a fault in the timed path instead:
  * half_batch: the reply places only the first half of the queue (the
    upper reading of `placed_net_gap` where the control cannot give
    one: the stream mix, below saturation, where placing without
    commits places the same count);
  * stale_state: the sidecar's device session takes each delta's
    pending pods but not its running-pod changes, so node usage stays
    as the session seed left it;
  * altered_answer: the reply's first placement moves to the node with
    the least cpu room for that pod (read from the views every
    resource-fit generator gives: static `alloc`, the cycle's `used`
    and `pend["cpu"]`).
tests/benchmark plants each at a CPU size.

The benchmark's own runs never run this. Prints the checks and one
JSON line {"control": ..., "correct": ..., "checks": ...}.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

import harness  # noqa: E402


def no_commit_answers(drv):
    """A faults hook: the answer the check reads for each cycle is the
    no-commit reference's on that cycle's own input, while the generator
    still binds what the program placed (the control read at the same
    inputs, as a served model's control is read at the same tokens; fed
    back, its thousands of placements a cycle would overflow the running
    bucket of a saturated cluster)."""
    profile = drv.spec["config"]["engine"]
    cycle = drv.cycle

    def hook(changed, keep):
        cyc = cycle(changed, keep)
        if keep:
            r = cyc.record
            r["node"], _ = drv.reference.sequential(profile, drv.static, r,
                                                    commit=False)
        return cyc

    drv.cycle = hook


def stale_state(drv):
    """A faults hook: the sidecar's one device session drops the
    running-pod upserts and removals of every delta it applies."""
    from tpusched.rpc import tpusched_pb2 as pb

    session = harness._session(drv.svc)
    apply = session.apply_delta

    def stale(base_id, delta, new_sid):
        d = pb.SnapshotDelta()
        d.CopyFrom(delta)
        del d.upsert_running[:]
        del d.remove_running[:]
        return apply(base_id, d, new_sid)

    session.apply_delta = stale


def half_batch(drv):
    """A faults hook: the reply leaves the second half of the queue
    unplaced."""

    def hook(resp):
        node = np.frombuffer(resp.node_idx_packed, "<i4").copy()
        node[len(node) // 2:] = -1
        resp.node_idx_packed = node.astype("<i4").tobytes()
        return resp

    drv.faults = hook


def altered_answer(drv):
    """A faults hook: the reply's first placement moves to the node
    with the least room for that pod."""

    def hook(resp):
        node = np.frombuffer(resp.node_idx_packed, "<i4").copy()
        placed = np.flatnonzero(node >= 0)
        if len(placed):
            c = drv.cluster
            j = int(placed[0])
            slot = c.pend_slot[resp.pod_names[j]]
            rec = c.cycle_view()
            room = (drv.static["alloc"][:, 0] - rec["used"][:, 0]
                    - rec["pend"]["cpu"][slot])
            n = int(np.argmin(room))
            worst = next(nm for nm, i in c.node_index.items() if i == n)
            node[j] = list(resp.node_names).index(worst)
        resp.node_idx_packed = node.astype("<i4").tobytes()
        return resp

    drv.faults = hook


FAULTS = ("half_batch", "stale_state", "altered_answer")


def control_spec(spec: dict) -> tuple[dict, object]:
    """(spec to run, faults hook) for the cell's control."""
    if spec["config"]["guarantee"] == "sequential":
        spec["config"]["engine"] = dict(spec["config"]["engine"],
                                        mode="fast")
        return spec, None
    return spec, no_commit_answers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=FAULTS)
    args = ap.parse_args(argv)
    harness.prepare_env()
    if args.fault:
        spec = harness.cell_spec(args.workload)
        faults = globals()[args.fault]
    else:
        spec, faults = control_spec(harness.cell_spec(args.workload))
    try:
        out = harness.run_cell(spec, args.seed, args.seconds, False,
                               t_start=T_START, faults=faults)
    except harness.NoAccelerator as e:
        print(f"control: {e}; nothing was run", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} value={c['value']} limit={c['limit']}",
              file=sys.stderr)
    print(json.dumps(dict(control=args.workload, fault=args.fault,
                          seed=args.seed,
                          correct=out["correct"], checks=out["checks"])),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
