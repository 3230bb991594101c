"""The plain reference of the sperf5k-pairwise configuration: what a
scheduling cycle must return on `sperf_cluster.py`'s cluster.

Imports nothing of the program and reads nothing it made; keeps the
protocol of reference.py's docstring. Its inputs are the generator's
views: static `alloc`, `zone`, `n_zones`, `max_skew`; per cycle `used`,
`pend` (with `kind`) and `kinds_on_node`, the running pods per node by
kind.

`sequential` is kube-scheduler's `scheduleOne` loop (pop the highest
effective priority, ties by name; filter every node; score; take the
first best node, ties by node name; commit) for the profile
reference.py states, with the two plugins this cluster's pods use:

  * PodTopologySpread. A DoNotSchedule constraint keeps a node when its
    domain's matching count + 1 - the minimum count over the eligible
    domains <= maxSkew (every node is eligible: no pod here has node
    affinity). ScheduleAnyway constraints add their domains' counts into
    a penalty, inverse-normalized over the nodes to [0, 100] (all equal:
    100) and weighted by `topology_spread`.
  * InterPodAffinity. A required affinity keeps the nodes whose domain
    holds a match, or every node when nothing in the cluster matches and
    the pod matches its own selector (the first pod of a self-affine
    group). A required anti-affinity drops the nodes whose domain holds a
    match; symmetrically, a member holding a required anti-affinity
    drops, for the pods its selector matches, the nodes of its domain.
    With no preferred terms the plugin scores 0 on every node.

Members are the running pods and the pods committed earlier in the
cycle. The templates fix who matches what: `foo=bar` (kinds spread and
spread-soft) is what every spread constraint counts, `foo=""`
(affinity) what the affinity term looks for, `color=green` (anti) what
the anti-affinity term repels, held by the green pods themselves; all
pods live in one namespace, inside every term's scope. So the counts are
kept per node and per zone as pods commit, never recounted per pod. The
float32 arithmetic is reference.py's, per resource column, with the
spread score as tpusched/oracle.py states it.

`pairwise_violations` audits a fast reply: placed pods that break a
DoNotSchedule spread, a required affinity or a required (or symmetric)
anti-affinity against the members committed at a commit key <= their
own, themselves excluded (oracle.py's `validate_assignment` contract for
fast mode).
"""

from __future__ import annotations

import numpy as np

import reference as base

F32 = np.float32
MIB = base.MIB
KINDS = ("default", "spread", "spread-soft", "affinity", "anti")
DEFAULT, SPREAD, SOFT, AFFINITY, ANTI = range(len(KINDS))
VALIDITY_AUDITS = ("overfull_nodes", "pairwise_violations")
overfull_nodes = base.overfull_nodes
commit_rank_differs = base.commit_rank_differs


def _fit_and_scores(a_c, a_m, a_p, u_c, u_m, u_p, rc, rm):
    """(fits, LeastAllocated, BalancedAllocation) per node for one
    request, reference.py's arithmetic. Element-wise, so a slice of the
    nodes gives the same bits as the whole."""
    fits = (u_c + rc <= a_c) & (u_m + rm <= a_m) & (u_p + F32(1) <= a_p)
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
    return fits, least, balanced


class _Members:
    """Matching member counts, per node and per zone, of the cycle's
    starting state, kept up as pods commit."""

    def __init__(self, static: dict, kinds_on_node: np.ndarray):
        run = kinds_on_node.astype(F32)
        self.zone = static["zone"]
        self.Z = int(static["n_zones"])
        self.foobar = run[:, SPREAD] + run[:, SOFT]
        self.zone_foobar = self._by_zone(self.foobar)
        self.zone_empty = self._by_zone(run[:, AFFINITY])
        self.green = run[:, ANTI].copy()

    def _by_zone(self, per_node: np.ndarray) -> np.ndarray:
        return np.bincount(self.zone, per_node, self.Z).astype(F32)

    def add(self, node: np.ndarray, kind: np.ndarray) -> None:
        fb = (kind == SPREAD) | (kind == SOFT)
        np.add.at(self.foobar, node[fb], F32(1))
        np.add.at(self.zone_foobar, self.zone[node[fb]], F32(1))
        np.add.at(self.zone_empty, self.zone[node[kind == AFFINITY]], F32(1))
        np.add.at(self.green, node[kind == ANTI], F32(1))


def sequential(profile: dict, static: dict, rec: dict, commit: bool = True
               ) -> tuple[np.ndarray, np.ndarray]:
    """(node [P] int64, -1 = unplaced; pop position [P] int64) for the
    pending pods `rec["pend"]` (by slot), on nodes in name order.
    commit=False is the control of the fast configuration (control.py):
    every pod takes its best node against the cycle's starting state,
    nothing committed between pods (neither capacity nor members)."""
    w_plugin = {p: float(profile["weights"][p]) for p in base._PLUGINS}
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
    r_c, r_m = base._requests(pend)
    kind = pend["kind"]
    P = len(r_c)
    by_name = np.argsort(pend["names"], kind="stable")
    slo = pend["slo"].astype(F32)[by_name]
    avail = pend["avail"].astype(F32)[by_name]
    pressure = (slo - avail).clip(0.0, 1.0)
    prio = pend["prio"].astype(F32)[by_name] + gain * pressure
    order = np.argsort(-prio, kind="stable")
    node = np.full(P, -1, np.int64)
    pos = np.full(P, -1, np.int64)
    total = sum(w_plugin.values())
    mem = _Members(static, rec["kinds_on_node"])
    zone, skew = mem.zone, F32(static["max_skew"])
    # Fit and resource scores of the last request seen; a commit
    # changes one node's, so they are kept up per node while the
    # request stays the same (in this cluster every pod's).
    req = cached = None
    for k, i in enumerate(order):
        p = int(by_name[i])
        pos[p] = k
        rc, rm, kd = r_c[p], r_m[p], kind[p]
        if req != (rc, rm):
            req = (rc, rm)
            cached = _fit_and_scores(a_c, a_m, a_p, u_c, u_m, u_p, rc, rm)
        fits, least, balanced = cached
        feasible = fits
        if kd == SPREAD:
            feasible = (feasible
                        & (mem.foobar + 1 - mem.foobar.min() <= skew)
                        & (mem.zone_foobar + 1 - mem.zone_foobar.min()
                           <= skew)[zone])
        elif kd == AFFINITY and mem.zone_empty.any():
            feasible = feasible & (mem.zone_empty > 0)[zone]
        elif kd == ANTI:
            feasible = feasible & (mem.green == 0)
        if not feasible.any():
            continue
        pr = pressure[i]
        if qos["urgency_reweight"]:
            w = {q: (1.0 - pr) * w_plugin[q]
                 + pr * (total if q == "least_requested" else 0.0)
                 for q in base._PLUGINS}
        else:
            w = w_plugin
        inv_spread = F32(100.0)
        if kd == SOFT:
            penalty = mem.foobar + mem.zone_foobar[zone]
            mx, mn = penalty.max(), penalty.min()
            if mx > mn:
                inv_spread = ((mx - penalty) * 100.0 / (mx - mn)).astype(F32)
        # NodeAffinity (no preferred terms) scores 0 and TaintToleration
        # (no PreferNoSchedule taints) 100 on every node.
        static_s = F32(w["node_affinity"] * F32(0.0)
                       + w["taint_toleration"] * F32(100.0))
        score = (w["least_requested"] * least
                 + w["balanced_allocation"] * balanced + static_s
                 + w["topology_spread"] * inv_spread
                 + w["interpod_affinity"] * F32(0.0)).astype(F32)
        n = int(np.argmax(np.where(feasible, score, F32(-np.inf))))
        node[p] = n
        if not commit:
            continue
        u_c[n] += rc
        u_m[n] += rm
        u_p[n] += F32(1)
        one = slice(n, n + 1)
        for full, part in zip(cached, _fit_and_scores(
                a_c[one], a_m[one], a_p[one], u_c[one], u_m[one], u_p[one],
                rc, rm)):
            full[one] = part
        mem.add(np.array([n]), np.array([kd]))
    return node, pos


def pairwise_violations(static: dict, rec: dict) -> int:
    """Placed pods (`rec["node"]`, by slot) that break a DoNotSchedule
    spread, a required affinity or a required or symmetric
    anti-affinity, each judged against the running pods and the placed
    pods of a commit key (`rec["key"]`) <= its own, itself excluded."""
    pend, node, key = rec["pend"], rec["node"], rec["key"]
    placed = np.flatnonzero(node >= 0)
    placed = placed[np.argsort(key[placed], kind="stable")]
    mem = _Members(static, rec["kinds_on_node"])
    skew = F32(static["max_skew"])
    cuts = np.flatnonzero(np.diff(key[placed])) + 1
    bad = 0
    for group in np.split(placed, cuts):
        if not len(group):
            continue
        n, kd = node[group], pend["kind"][group]
        z = mem.zone[n]
        mem.add(n, kd)
        # Each pod matches its own selector: taking it out lowers its
        # own domain's count by one, and the minimum at most to that.
        host = mem.foobar[n] - 1
        zone = mem.zone_foobar[z] - 1
        spread_bad = ((host + 1 - np.minimum(mem.foobar.min(), host) > skew)
                      | (zone + 1 - np.minimum(mem.zone_foobar.min(), zone)
                         > skew))
        empty = mem.zone_empty[z] - 1
        affinity_bad = (empty <= 0) & (mem.zone_empty.sum() - 1 > 0)
        # A green pod both holds the term and matches it: its own term
        # and a green member's symmetric one bar the same host.
        anti_bad = mem.green[n] - 1 > 0
        bad += int(((kd == SPREAD) & spread_bad).sum()
                   + ((kd == AFFINITY) & affinity_bad).sum()
                   + ((kd == ANTI) & anti_bad).sum())
    return bad
