"""The plain reference of the k8s5k configurations: what a scheduling
cycle must return.

Imports nothing of the program and reads nothing it made: its inputs
are the generator's views (workload.Cluster's `static_view()` and the
`cycle_view()` of each cycle as it was sent), and the configuration
file's scheduler profile. A configuration names its reference by path
(`"reference"`); the check calls this protocol, which every reference
keeps:

  * `sequential(profile, static, rec, commit=True) -> (node, pos)`;
  * `commit_rank_differs(node, key, pos)`;
  * `VALIDITY_AUDITS`: the names of the audits a configuration of
    guarantee "valid" is held to, each a function `(static, rec) ->
    int` of this module counting violations in one cycle's reply
    (`rec["node"]`), with its limit under the same name in the
    configuration's `checks`.

`sequential` is kube-scheduler's `scheduleOne` loop for the profile
the configurations state (NodeResourcesFit filter, LeastAllocated +
BalancedAllocation scores, QoS-weighted priority, no constraints):
pop the highest effective priority (ties: name order), filter every
node, score, take the first best node (ties: node name order), commit.
Its float32 arithmetic is the one `tpusched/oracle.py` states, written
per resource column; multiplying by a resource weight of 1 and adding
the zero-weight "pods" column are exact, so the columns give the same
bits as the oracle's [N, R] form (tests/benchmark checks this).

`overfull_nodes` is the capacity audit every placement must pass.
"""

from __future__ import annotations

import numpy as np

F32 = np.float32
MIB = 1 << 20
VALIDITY_AUDITS = ("overfull_nodes",)
_PLUGINS = ("least_requested", "balanced_allocation", "node_affinity",
            "taint_toleration", "topology_spread", "interpod_affinity")


def _requests(pend: dict) -> tuple[np.ndarray, np.ndarray]:
    return (pend["cpu"].astype(F32),
            (pend["mem"] * MIB).astype(F32))


def sequential(profile: dict, static: dict, rec: dict, commit: bool = True
               ) -> tuple[np.ndarray, np.ndarray]:
    """(node [P] int64, -1 = unplaced; pop position [P] int64) for the
    pending pods `rec["pend"]` (by slot), on nodes in name order.

    static["alloc"], rec["used"]: [N, 3] int64 (cpu millicores, memory
    MiB, pods). commit=False is the control of the fast configurations
    (control.py): every pod takes its best node against the cycle's
    starting state, the capacity guarantee broken."""
    w_plugin = {p: float(profile["weights"][p]) for p in _PLUGINS}
    qos = profile["qos"]
    gain = float(qos["qos_gain"])
    res_w = profile["score_resource_weights"]
    if (float(res_w["cpu"]), float(res_w["memory"]),
            float(res_w.get("pods", 0.0))) != (1.0, 1.0, 0.0):
        raise ValueError("the reference is written for cpu:1 memory:1 "
                         "pods:0 resource weights")
    alloc, used, pend = static["alloc"], rec["used"], rec["pend"]
    a_c, a_m, a_p = (alloc[:, 0].astype(F32), (alloc[:, 1] * MIB).astype(F32),
                     alloc[:, 2].astype(F32))
    u_c, u_m, u_p = (used[:, 0].astype(F32), (used[:, 1] * MIB).astype(F32),
                     used[:, 2].astype(F32))
    r_c, r_m = _requests(pend)
    P = len(r_c)
    # Pods in name order (the decoder's canonical order).
    by_name = np.argsort(pend["names"], kind="stable")
    slo = pend["slo"].astype(F32)[by_name]
    avail = pend["avail"].astype(F32)[by_name]
    pressure = (slo - avail).clip(0.0, 1.0)
    prio = pend["prio"].astype(F32)[by_name] + gain * pressure
    order = np.argsort(-prio, kind="stable")
    node = np.full(P, -1, np.int64)
    pos = np.full(P, -1, np.int64)
    total = sum(w_plugin.values())
    inv_spread = F32(100.0)   # _inverse_normalize of an all-zero penalty
    for k, i in enumerate(order):
        p = int(by_name[i])
        pos[p] = k
        rc, rm = r_c[p], r_m[p]
        feasible = (u_c + rc <= a_c) & (u_m + rm <= a_m) & (u_p + F32(1) <= a_p)
        if not feasible.any():
            continue
        pr = pressure[i]
        if qos["urgency_reweight"]:
            w = {q: (1.0 - pr) * w_plugin[q]
                 + pr * (total if q == "least_requested" else 0.0)
                 for q in _PLUGINS}
        else:
            w = w_plugin
        with np.errstate(divide="ignore", invalid="ignore"):
            lc = (a_c - u_c - rc) * 100.0 / a_c
            lm = (a_m - u_m - rm) * 100.0 / a_m
        lc = np.where(lc < 0, F32(0.0), lc)
        lm = np.where(lm < 0, F32(0.0), lm)
        least = (lc + lm) / F32(2.0)
        fc = ((u_c + rc) / a_c).clip(0.0, 1.0)
        fm = ((u_m + rm) / a_m).clip(0.0, 1.0)
        mean = (fc + fm) / F32(2.0)
        var = ((fc - mean) ** 2 + (fm - mean) ** 2) / F32(2.0)
        balanced = ((1.0 - np.sqrt(var)) * 100.0).astype(F32)
        # NodeAffinity (no preferred terms) scores 0 and TaintToleration
        # (no PreferNoSchedule taints) 100 on every node.
        static = F32(w["node_affinity"] * F32(0.0)
                     + w["taint_toleration"] * F32(100.0))
        score = (w["least_requested"] * least
                 + w["balanced_allocation"] * balanced + static
                 + w["topology_spread"] * inv_spread
                 + w["interpod_affinity"] * F32(0.0)).astype(F32)
        n = int(np.argmax(np.where(feasible, score, F32(-np.inf))))
        node[p] = n
        if not commit:
            continue
        u_c[n] += rc
        u_m[n] += rm
        u_p[n] += F32(1)
    return node, pos


def overfull_nodes(static: dict, rec: dict) -> int:
    """Nodes whose requests exceed allocatable once the placed pods
    `rec["node"]` (by slot, -1 = unplaced) are added (exact integers)."""
    pend, node = rec["pend"], rec["node"]
    placed = node >= 0
    after = rec["used"].copy()
    np.add.at(after, node[placed],
              np.c_[pend["cpu"][placed], pend["mem"][placed],
                    np.ones(int(placed.sum()), np.int64)])
    return int((after > static["alloc"]).any(axis=1).sum())


def commit_rank_differs(node: np.ndarray, key: np.ndarray,
                        ref_pos: np.ndarray) -> int:
    """Placed pods whose commit order (by the reply's commit keys)
    differs from the reference's pop order."""
    placed = np.flatnonzero(node >= 0)
    got = placed[np.argsort(key[placed], kind="stable")]
    want = placed[np.argsort(ref_pos[placed], kind="stable")]
    return int((got != want).sum())
