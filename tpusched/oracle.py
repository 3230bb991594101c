"""Stock-semantics oracle: a deliberately naive per-pod NumPy scheduler.

This is the parity reference demanded by SURVEY.md §4 item 2: it replays
the reference scheduler's one-pod-at-a-time cycle (SURVEY.md §3.1
`scheduleOne`) — pop highest dynamic-priority pod, Filter every node,
Score, NormalizeScore, weighted sum, pick the max, commit to the cache —
in plain NumPy with zero batching tricks. The batched TPU engine must
produce identical placements in parity mode (bit-identical, fuzz-tested).
Fast mode does NOT promise node-identical placements: every commit
couples later pods through load-balancing scores, so node agreement
collapses once commit order diverges (measured ~11% node-identical even
with no constraints; net placed-pod delta about -2% on the mixed preset
as of round 5 — run tpusched/divergence.py for current numbers). Fast
mode's contract is validity (audited) and near-equal placement COUNT,
not the same nodes.

Semantics notes (each mirrors an upstream plugin, SURVEY.md C2-C7):
  * NodeResourcesFit filter: forall r: used_r + req_r <= allocatable_r.
  * TaintToleration filter: every NoSchedule/NoExecute taint tolerated.
  * NodeAffinity filter: OR over nodeSelectorTerms, AND within a term;
    nodeSelector is ANDed into every term. Operators In/NotIn/Exists/
    DoesNotExist/Gt/Lt with apimachinery labels.Requirement semantics
    (NotIn/DoesNotExist match when the key is absent).
  * LeastRequested score: sum_r w_r * (alloc - used - req)*100/alloc / sum w.
  * BalancedAllocation score: (1 - stddev of utilisation fractions) * 100.
  * NodeAffinity score: sum of satisfied preferred-term weights,
    default-normalized to [0,100] per pod across nodes.
  * TaintToleration score: intolerable PreferNoSchedule taint count,
    inverse-normalized to [0,100].
  * PodTopologySpread: DoNotSchedule -> filter (count[dom]+1-min <= maxSkew);
    ScheduleAnyway -> inverse-normalized penalty score. Nodes missing the
    topology key are infeasible for DoNotSchedule constraints.
  * InterPodAffinity: required (anti-)affinity -> filter against running
    AND previously-assigned pending pods; preferred terms -> +-weight,
    upstream-normalized. Symmetric required anti-affinity: an *existing*
    member's (running pod's or earlier-assigned pending pod's) required
    anti-affinity term repels an incoming pod matching its selector
    (SURVEY.md C7).
  * Dynamic QoS priority (C10): effective = base + gain*pressure,
    pressure = clip(slo - observed_avail, 0, 1); pop order is stable
    descending.

Tie-break (SURVEY.md §7 hard part 2): EngineConfig.tie_break "first"
picks the lowest node index among score maxima; "seeded" reproduces
upstream's rand-among-max as a deterministic per-pod hash pick
(qos.tie_hash), implemented bit-identically here (Oracle.solve's
tie-set pick) and on device (kernels.assign.pick_node /
pick_node_batch), so parity holds for any seed in both engine modes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import numpy as np

from tpusched.config import (
    DO_NOT_SCHEDULE,
    EFFECT_NO_EXECUTE,
    EFFECT_NO_SCHEDULE,
    EFFECT_PREFER_NO_SCHEDULE,
    EngineConfig,
    MAX_NODE_SCORE,
    OP_DOES_NOT_EXIST,
    OP_EXISTS,
    OP_GT,
    OP_IN,
    OP_LT,
    OP_NOT_IN,
    SCHEDULE_ANYWAY,
)
from tpusched.qos import (
    effective_priority,
    effective_weights,
    evict_cost_raw,
    pressure_of,
    tie_hash,
    victim_effective_priority,
)
from tpusched.snapshot import ClusterSnapshot


@dataclasses.dataclass
class OracleResult:
    assignment: np.ndarray       # [P] int32 node index or -1
    order: np.ndarray            # [P] int32 pop order (indices into pods)
    chosen_score: np.ndarray     # [P] f32 score of the chosen node (-inf if none)
    final_used: np.ndarray       # [N, R] f32 node used after all commits
    evicted: np.ndarray | None = None  # [M] bool preemption victims


def _np(x: Any) -> np.ndarray:
    return np.asarray(x)


class Oracle:
    def __init__(self, snap: ClusterSnapshot,
                 config: EngineConfig) -> None:
        self.snap = snap
        self.cfg = config
        self.nodes = snap.nodes
        self.pods = snap.pods
        self._atom_sat_nodes: np.ndarray | None = None
        # Preemption state: evicted running pods stop counting as
        # members everywhere (capacity, pairwise counts, anti holders).
        self._evicted = np.zeros(_np(snap.running.valid).shape[0], bool)

    # -- atoms over node labels --------------------------------------------

    def atom_sat_nodes(self) -> np.ndarray:
        """[A, N] bool: does node n satisfy match-expression atom a."""
        if self._atom_sat_nodes is not None:
            return self._atom_sat_nodes
        at = self.snap.atoms
        key, op, pairs, num, avalid = map(_np, (at.key, at.op, at.pairs, at.num, at.valid))
        lp, lk, ln = map(_np, (self.nodes.label_pairs, self.nodes.label_keys,
                               self.nodes.label_nums))
        A, N = key.shape[0], lp.shape[0]
        sat = np.zeros((A, N), bool)
        for a in range(A):
            if not avalid[a]:
                continue
            sat[a] = _atom_sat_row(key[a], op[a], pairs[a], num[a], lp, lk, ln)
        self._atom_sat_nodes = sat
        return sat

    def atom_sat_over(self, lp: np.ndarray, lk: np.ndarray) -> np.ndarray:
        """[A, X] bool atom satisfaction over arbitrary label sets (pods)."""
        at = self.snap.atoms
        key, op, pairs, num, avalid = map(_np, (at.key, at.op, at.pairs, at.num, at.valid))
        A, X = key.shape[0], lp.shape[0]
        sat = np.zeros((A, X), bool)
        ln = np.full(lp.shape, np.nan, np.float32)
        for a in range(A):
            if avalid[a]:
                sat[a] = _atom_sat_row(key[a], op[a], pairs[a], num[a], lp, lk, ln)
        return sat

    # -- filters ------------------------------------------------------------

    def resource_fit(self, p: int, used: np.ndarray) -> np.ndarray:
        alloc = _np(self.nodes.allocatable)
        req = _np(self.pods.requests)[p]
        return np.all(used + req <= alloc, axis=1)

    def taints_ok(self, p: int) -> np.ndarray:
        tids = _np(self.nodes.taint_ids)          # [N, TN]
        effect = _np(self.snap.taint_effect)      # [VT]
        tol = _np(self.pods.tolerated)[p]         # [VT]
        t = np.maximum(tids, 0)
        hard = np.isin(effect, (EFFECT_NO_SCHEDULE, EFFECT_NO_EXECUTE))
        repels = (tids >= 0) & hard[t] & ~tol[t]
        return ~repels.any(axis=1)

    def node_affinity_ok(self, p: int) -> np.ndarray:
        sat = self.atom_sat_nodes()                      # [A, N]
        atoms = _np(self.pods.req_term_atoms)[p]         # [T, AT]
        tvalid = _np(self.pods.req_term_valid)[p]        # [T]
        N = sat.shape[1]
        if not tvalid.any():
            return np.ones(N, bool)
        ok = np.zeros(N, bool)
        for t in range(atoms.shape[0]):
            if not tvalid[t]:
                continue
            term_ok = np.ones(N, bool)
            for a in atoms[t]:
                if a >= 0:
                    term_ok &= sat[a]
            ok |= term_ok
        return ok

    # -- scores (each returns [N] f32 in [0, 100]) --------------------------

    def score_least_requested(self, p: int, used: np.ndarray) -> np.ndarray:
        alloc = _np(self.nodes.allocatable)
        req = _np(self.pods.requests)[p]
        w = np.asarray(self.cfg.score_weights_vector(), np.float32)
        wsum = w.sum()
        with np.errstate(divide="ignore", invalid="ignore"):
            per_r = np.where(
                alloc > 0, (alloc - used - req) * MAX_NODE_SCORE / alloc, 0.0
            )
        per_r = np.where(per_r < 0, 0.0, per_r)  # over-requested -> 0 (upstream)
        return (per_r * w).sum(axis=1).astype(np.float32) / max(wsum, 1e-9)

    def score_balanced(self, p: int, used: np.ndarray) -> np.ndarray:
        alloc = _np(self.nodes.allocatable)
        req = _np(self.pods.requests)[p]
        # Masked-sum formulation identical to kernels/score.py
        # balanced_allocation so parity holds bitwise.
        sel = (np.asarray(self.cfg.score_weights_vector(), np.float32) > 0).astype(np.float32)
        k = max(sel.sum(), 1.0)
        with np.errstate(divide="ignore", invalid="ignore"):
            frac = np.where(alloc > 0, (used + req) / alloc, 1.0)
        frac = np.clip(frac, 0.0, 1.0)
        mean = (frac * sel).sum(axis=1, keepdims=True) / k
        var = (((frac - mean) ** 2) * sel).sum(axis=1) / k
        return ((1.0 - np.sqrt(var)) * MAX_NODE_SCORE).astype(np.float32)

    def score_node_affinity(self, p: int) -> np.ndarray:
        sat = self.atom_sat_nodes()
        atoms = _np(self.pods.pref_term_atoms)[p]
        tvalid = _np(self.pods.pref_term_valid)[p]
        weight = _np(self.pods.pref_weight)[p]
        N = sat.shape[1]
        raw = np.zeros(N, np.float32)
        for t in range(atoms.shape[0]):
            if not tvalid[t]:
                continue
            term_ok = np.ones(N, bool)
            for a in atoms[t]:
                if a >= 0:
                    term_ok &= sat[a]
            raw += weight[t] * term_ok
        return _default_normalize(raw, _np(self.nodes.valid))

    def score_taint_toleration(self, p: int) -> np.ndarray:
        tids = _np(self.nodes.taint_ids)
        effect = _np(self.snap.taint_effect)
        tol = _np(self.pods.tolerated)[p]
        N = tids.shape[0]
        count = np.zeros(N, np.float32)
        for n in range(N):
            for t in tids[n]:
                if t >= 0 and effect[t] == EFFECT_PREFER_NO_SCHEDULE and not tol[t]:
                    count[n] += 1
        nvalid = _np(self.nodes.valid)
        mx = count[nvalid].max() if nvalid.any() else 0.0
        if mx <= 0:
            return np.full(N, MAX_NODE_SCORE, np.float32)
        return ((mx - count) * MAX_NODE_SCORE / mx).astype(np.float32)

    # -- pairwise: topology spread + inter-pod affinity ---------------------

    def _ns_ok(self, sig: int, member_ns: np.ndarray) -> np.ndarray:
        """[X] bool: member namespaces within signature sig's scope
        (upstream podAffinityTerm.namespaces / same-namespace spread)."""
        sigs = self.snap.sigs
        if bool(_np(sigs.ns_all)[sig]):
            return np.ones(member_ns.shape[0], bool)
        allowed = _np(sigs.ns)[sig]
        allowed = allowed[allowed >= 0]
        return np.isin(member_ns, allowed)

    def _match_counts(self, sel_atoms: np.ndarray, sig: int,
                      assigned_pods: list[int]) -> np.ndarray:
        """[X] bool: which of running+assigned pods match the selector
        within the signature's namespace scope. A selector with zero
        atoms matches everything (upstream empty label selector)."""
        run = self.snap.running
        ap = list(assigned_pods)
        plp, plk = _np(self.pods.label_pairs), _np(self.pods.label_keys)
        pns = _np(self.pods.namespace)
        lp = np.concatenate([_np(run.label_pairs), plp[ap]], axis=0)
        lk = np.concatenate([_np(run.label_keys), plk[ap]], axis=0)
        mns = np.concatenate([_np(run.namespace), pns[ap]])
        valid = np.concatenate(
            [_np(run.valid) & ~self._evicted, np.ones(len(ap), bool)]
        )
        sat = self.atom_sat_over(lp, lk)
        match = valid & self._ns_ok(sig, mns)
        for a in sel_atoms:
            if a >= 0:
                match &= sat[a]
        return match

    def spread_ok_and_penalty(
        self, p: int, assigned_nodes: list[int], assigned_pods: list[int]
    ) -> tuple[np.ndarray, np.ndarray]:
        """Returns (feasible [N] bool, penalty [N] f32) for all spread
        constraints of pod p given already-committed pending pods."""
        nodes, pods = self.nodes, self.pods
        dom = _np(nodes.domain)                       # [N, TK]
        nvalid = _np(nodes.valid)
        N = dom.shape[0]
        ok = np.ones(N, bool)
        penalty = np.zeros(N, np.float32)
        tsk = _np(pods.ts_key)[p]
        tsv = _np(pods.ts_valid)[p]
        if not tsv.any():
            return ok, penalty
        run_nodes = _np(self.snap.running.node_idx)
        member_nodes = np.concatenate(
            [run_nodes, np.asarray(assigned_nodes, np.int32)]
        ) if assigned_pods else run_nodes
        # Eligible nodes for domain discovery: honor the pod's own node
        # affinity (upstream NodeAffinityPolicy: Honor default).
        eligible = nvalid & self.node_affinity_ok(p)
        for c in range(tsk.shape[0]):
            if not tsv[c]:
                continue
            key = tsk[c]
            has_key = dom[:, key] >= 0
            match = self._match_counts(
                _np(pods.ts_sel_atoms)[p, c], int(_np(pods.ts_sig)[p, c]),
                assigned_pods,
            )
            # count matching member pods per domain of this topo key
            member_dom = np.where(member_nodes >= 0, dom[member_nodes, key], -1)
            n_dom = int(dom[:, key].max()) + 1 if has_key.any() else 0
            counts = np.zeros(max(n_dom, 1), np.float32)
            for md, m in zip(member_dom, match):
                if m and md >= 0:
                    counts[md] += 1
            elig_doms = np.unique(dom[eligible & has_key, key]) if (eligible & has_key).any() else np.array([], np.int64)
            min_count = counts[elig_doms].min() if elig_doms.size else 0.0
            node_count = np.where(has_key, counts[np.clip(dom[:, key], 0, None)], np.inf)
            if _np(pods.ts_when)[p, c] == DO_NOT_SCHEDULE:
                ok &= has_key & (node_count + 1 - min_count <= _np(pods.ts_max_skew)[p, c])
            else:
                penalty += np.where(has_key, node_count, counts.max() if n_dom else 0.0)
        return ok, penalty

    def interpod_ok_and_raw(
        self, p: int, assigned_nodes: list[int], assigned_pods: list[int]
    ) -> tuple[np.ndarray, np.ndarray]:
        """(feasible [N] bool, preferred raw score [N] f32) over the pod's
        inter-pod (anti-)affinity terms."""
        nodes, pods = self.nodes, self.pods
        dom = _np(nodes.domain)
        N = dom.shape[0]
        ok = np.ones(N, bool)
        raw = np.zeros(N, np.float32)
        iav = _np(pods.ia_valid)[p]
        if not iav.any():
            return ok, raw
        plp, plk = _np(pods.label_pairs), _np(pods.label_keys)
        run_nodes = _np(self.snap.running.node_idx)
        member_nodes = np.concatenate(
            [run_nodes, np.asarray(assigned_nodes, np.int32)]
        ) if assigned_pods else run_nodes
        for t in range(iav.shape[0]):
            if not iav[t]:
                continue
            key = _np(pods.ia_key)[p, t]
            match = self._match_counts(
                _np(pods.ia_sel_atoms)[p, t], int(_np(pods.ia_sig)[p, t]),
                assigned_pods,
            )
            member_dom = np.where(member_nodes >= 0, dom[member_nodes, key], -1)
            # domain -> has matching pod?
            has_key = dom[:, key] >= 0
            n_dom = int(dom[:, key].max()) + 1 if has_key.any() else 0
            dom_has = np.zeros(max(n_dom, 1), bool)
            for md, m in zip(member_dom, match):
                if m and md >= 0:
                    dom_has[md] = True
            node_has = has_key & dom_has[np.clip(dom[:, key], 0, None)]
            anti = _np(pods.ia_anti)[p, t]
            if _np(pods.ia_required)[p, t]:
                # Required affinity: node's domain must contain a match
                # (nodes missing the key fail). Upstream special case: if
                # NO pod in the cluster matches the selector but the
                # incoming pod matches its own selector, the term is
                # satisfied on any node with the key (lets the first pod
                # of a self-affine group schedule). Required
                # anti-affinity: no match in the domain (missing key ok).
                if anti:
                    ok &= ~node_has
                else:
                    self_sat = self.atom_sat_over(
                        plp[p : p + 1], plk[p : p + 1]
                    )[:, 0]
                    self_match = bool(_np(pods.valid)[p]) and bool(
                        self._ns_ok(
                            int(_np(pods.ia_sig)[p, t]),
                            _np(pods.namespace)[p : p + 1],
                        )[0]
                    )
                    for a in _np(pods.ia_sel_atoms)[p, t]:
                        if a >= 0:
                            self_match = self_match and bool(self_sat[a])
                    all_zero = not match.any()
                    ok &= node_has | (all_zero & self_match & has_key)
            else:
                w = _np(pods.ia_weight)[p, t]
                raw += np.where(node_has, -w if anti else w, 0.0)
        return ok, raw

    def symmetric_anti_ok(
        self, p: int, assigned_nodes: list[int], assigned_pods: list[int]
    ) -> np.ndarray:
        """[N] bool: no member (running pod or already-assigned pending
        pod) holds a required anti-affinity term whose selector matches
        pod p with node n inside the holder's topology domain (upstream
        symmetric anti-affinity)."""
        snap, nodes, pods = self.snap, self.nodes, self.pods
        dom = _np(nodes.domain)
        N = dom.shape[0]
        ok = np.ones(N, bool)
        sig_key = _np(snap.sigs.key)
        sig_atoms = _np(snap.sigs.atoms)
        if not _np(snap.sigs.valid).any():
            return ok
        plp = _np(pods.label_pairs)[p : p + 1]
        plk = _np(pods.label_keys)[p : p + 1]
        sat_p = self.atom_sat_over(plp, plk)[:, 0]           # [A]

        holders: list[tuple[int, int]] = []                  # (sig, node)
        run = self.snap.running
        ranti, rnode, rvalid = map(_np, (run.anti_sig, run.node_idx, run.valid))
        for m in range(ranti.shape[0]):
            if not rvalid[m] or rnode[m] < 0 or self._evicted[m]:
                continue
            for s in ranti[m]:
                if s >= 0:
                    holders.append((int(s), int(rnode[m])))
        ia_sig, ia_anti, ia_req, ia_valid = map(
            _np, (pods.ia_sig, pods.ia_anti, pods.ia_required, pods.ia_valid)
        )
        for q, nq in zip(assigned_pods, assigned_nodes):
            for t in range(ia_sig.shape[1]):
                if ia_valid[q, t] and ia_anti[q, t] and ia_req[q, t]:
                    holders.append((int(ia_sig[q, t]), int(nq)))
        for s, hn in holders:
            match = bool(_np(pods.valid)[p]) and bool(
                self._ns_ok(int(s), _np(pods.namespace)[p : p + 1])[0]
            )
            for a in sig_atoms[s]:
                if a >= 0:
                    match = match and bool(sat_p[a])
            if not match:
                continue
            key = sig_key[s]
            hd = dom[hn, key]
            if hd < 0:
                continue  # holder's node lacks the key: no domain to poison
            ok &= dom[:, key] != hd
        return ok

    # -- the per-pod cycle ---------------------------------------------------

    def feasible_and_score(
        self, p: int, used: np.ndarray,
        assigned_nodes: list[int] | None = None,
        assigned_pods: list[int] | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """One scheduling cycle's Filter + Score for pod p: returns
        (feasible [N] bool, total weighted score [N] f32)."""
        assigned_nodes = assigned_nodes or []
        assigned_pods = assigned_pods or []
        nvalid = _np(self.nodes.valid)
        spread_ok, spread_penalty = self.spread_ok_and_penalty(
            p, assigned_nodes, assigned_pods
        )
        ia_ok, ia_raw = self.interpod_ok_and_raw(p, assigned_nodes, assigned_pods)
        feasible = (
            nvalid
            # Cordon filter with the NodeUnschedulable toleration escape.
            & (_np(self.nodes.schedulable)
               | bool(_np(self.pods.tolerates_unsched)[p]))
            & self.resource_fit(p, used)
            & self.taints_ok(p)
            & self.node_affinity_ok(p)
            & spread_ok
            & ia_ok
            & self.symmetric_anti_ok(p, assigned_nodes, assigned_pods)
        )
        w = effective_weights(
            self.cfg,
            pressure_of(_np(self.pods.slo_target)[p], _np(self.pods.observed_avail)[p]),
        )
        # Grouping mirrors kernels/assign.py pod_cycle (static NodeAffinity
        # + TaintToleration term parenthesised together) for f32 parity.
        static = (
            w["node_affinity"] * self.score_node_affinity(p)
            + w["taint_toleration"] * self.score_taint_toleration(p)
        ).astype(np.float32)
        score = (
            w["least_requested"] * self.score_least_requested(p, used)
            + w["balanced_allocation"] * self.score_balanced(p, used)
            + static
            + w["topology_spread"] * _inverse_normalize(spread_penalty, nvalid)
            + w["interpod_affinity"] * _upstream_normalize(ia_raw, nvalid)
        ).astype(np.float32)
        return feasible, score

    def try_preempt(
        self, p: int, p_prio: float, used: np.ndarray,
        assigned_nodes: list[int], assigned_pods: list[int],
    ) -> tuple[int, list[int]]:
        """PostFilter (SURVEY.md C9): find the minimum-cost eligible
        victim prefix per allowed node, pick the cheapest node. Mirrors
        kernels/preempt.py exactly (same cost shift, same stable
        cost-sort, same first-feasible-prefix rule). Returns
        (node or -1, victim running-pod indices)."""
        cfg = self.cfg
        run = self.snap.running
        rvalid, rnode = _np(run.valid), _np(run.node_idx)
        M = rvalid.shape[0]
        if not cfg.preemption or M == 0:
            return -1, []
        if _np(self.pods.group)[p] >= 0:
            # Gang members never preempt: their placement is provisional
            # until quorum, and evicting for a provisional placement
            # would strand the victims (mirrors kernels/assign.py).
            return -1, []
        spread_ok, _ = self.spread_ok_and_penalty(p, assigned_nodes, assigned_pods)
        ia_ok, _ = self.interpod_ok_and_raw(p, assigned_nodes, assigned_pods)
        allowed = (
            _np(self.nodes.valid)
            & (_np(self.nodes.schedulable)
               | bool(_np(self.pods.tolerates_unsched)[p]))
            & self.taints_ok(p)
            & self.node_affinity_ok(p)
            & spread_ok
            & ia_ok
            & self.symmetric_anti_ok(p, assigned_nodes, assigned_pods)
        )
        N = allowed.shape[0]
        vprio = np.asarray(
            victim_effective_priority(cfg, _np(run.priority), _np(run.slack)),
            np.float32,
        )
        raw = np.asarray(
            evict_cost_raw(cfg, _np(run.priority), _np(run.slack)), np.float32
        )
        mn = raw[rvalid].min() if rvalid.any() else np.float32(0.0)
        cost = (raw - mn + 1.0).astype(np.float32)
        elig = (
            rvalid & ~self._evicted & (rnode >= 0)
            & (vprio + cfg.qos.preemption_margin < p_prio)
        )
        alloc = _np(self.nodes.allocatable)
        req_p = _np(self.pods.requests)[p]
        rreq = _np(run.requests)
        # Victim-prefix search, arithmetic mirroring kernels/preempt.py
        # step for step (global f32 cumsum minus segment offset over the
        # same (node, cost) sort) so fit/cost decisions agree with the
        # device to the last ULP the scan association allows.
        node_m = np.where(rvalid & (rnode >= 0), rnode, N)
        perm = np.lexsort((cost, node_m))
        node_s = node_m[perm]
        idx = np.arange(M)
        boundary = np.concatenate([[True], node_s[1:] != node_s[:-1]]) if M else np.zeros(0, bool)
        seg_start = np.maximum.accumulate(np.where(boundary, idx, 0))
        elig_s = elig[perm]
        # PDB violation flags, mirroring kernels/preempt.py: a victim
        # violates its budget when the same-budget count within its
        # node-segment prefix (incl. itself) plus earlier preemptors'
        # evictions exceeds the remaining allowance. Prefixes are ranked
        # lexicographically by (violations, cost) — never summed into
        # one penalty channel, so f32 parity with the device holds.
        pdb_allowed = _np(self.snap.pdb_allowed)
        GP = pdb_allowed.shape[0]
        if GP:
            run_pdb = _np(run.pdb_group)
            pdb_s = run_pdb[perm]
            consumed = np.zeros(GP, np.float32)
            for m in range(M):
                if self._evicted[m] and run_pdb[m] >= 0 and rvalid[m]:
                    consumed[run_pdb[m]] += 1.0
            remaining = pdb_allowed - consumed
            pdb_clip = np.clip(pdb_s, 0, None)
            gsel = (
                (np.arange(GP)[:, None] == pdb_clip[None, :])
                & (elig_s & (pdb_s >= 0))[None, :]
            )
            cum_g = np.cumsum(gsel.astype(np.float32), axis=1)
            my_cum = cum_g[pdb_clip, idx]
            off_g = np.where(
                seg_start > 0,
                cum_g[pdb_clip, np.maximum(seg_start - 1, 0)], 0.0,
            )
            within_cnt = my_cum - off_g
            viol = elig_s & (pdb_s >= 0) & (within_cnt > remaining[pdb_clip])
        else:
            viol = np.zeros(M, bool)
        req_s = np.where(elig_s[:, None], rreq[perm], 0.0).astype(np.float32)
        cum_req = np.cumsum(req_s, axis=0, dtype=np.float32)
        cum_cost = np.cumsum(
            np.where(elig_s, cost[perm], 0.0), dtype=np.float32
        )
        cum_viol = np.cumsum(viol.astype(np.float32), dtype=np.float32)
        off_req = np.where(
            (seg_start > 0)[:, None], cum_req[np.maximum(seg_start - 1, 0)], 0.0
        )
        off_cost = np.where(
            seg_start > 0, cum_cost[np.maximum(seg_start - 1, 0)], 0.0
        )
        off_viol = np.where(
            seg_start > 0, cum_viol[np.maximum(seg_start - 1, 0)], 0.0
        )
        within_req = cum_req - off_req
        within_cost = cum_cost - off_cost
        within_viol = cum_viol - off_viol
        cap_node = np.minimum(node_s, N - 1)
        fits = elig_s & np.all(
            used[cap_node] - within_req + req_p[None, :] <= alloc[cap_node],
            axis=-1,
        )
        # Lexicographic (violations, cost) MIN feasible prefix per node
        # (ties -> first position), mirroring the kernel's two-stage
        # scatter-min + argmin selection exactly.
        node_viol = np.full(N + 1, np.inf, np.float32)
        node_cost = np.full(N + 1, np.inf, np.float32)
        for i in range(M):
            if fits[i]:
                n_i = node_s[i]
                if within_viol[i] < node_viol[n_i]:
                    node_viol[n_i] = within_viol[i]
        for i in range(M):
            if fits[i] and within_viol[i] == node_viol[node_s[i]]:
                n_i = node_s[i]
                if within_cost[i] < node_cost[n_i]:
                    node_cost[n_i] = within_cost[i]
        nvalid = _np(self.nodes.valid)
        ok_node = allowed & nvalid
        viol_total = np.where(ok_node, node_viol[:N], np.inf)
        min_viol = viol_total.min() if N else np.inf
        total = np.where(
            ok_node & (viol_total == min_viol), node_cost[:N], np.inf
        )
        best_n = int(np.argmin(total))
        if not np.isfinite(total[best_n]):
            return -1, []
        cand = fits & (node_s == best_n) & (within_viol == min_viol)
        masked = np.where(cand, within_cost, np.inf)
        fp = int(np.argmin(masked))
        sel_s = (node_s == best_n) & elig_s & (idx <= fp)
        return best_n, [int(perm[i]) for i in range(M) if sel_s[i]]

    def solve(self) -> OracleResult:
        pods, nodes = self.pods, self.nodes
        pvalid = _np(pods.valid)
        P = pvalid.shape[0]
        used = _np(nodes.used).copy()
        prio = effective_priority(
            self.cfg, _np(pods.base_priority), _np(pods.slo_target),
            _np(pods.observed_avail),
        )
        # Stable descending pop order over valid pods (SURVEY.md §3.1
        # queue.Pop of max dynamic priority; ties by submission order =
        # pod index).
        order = np.argsort(-np.where(pvalid, prio, -np.inf), kind="stable")
        order = order[pvalid[order]]
        assignment = np.full(P, -1, np.int32)
        chosen_score = np.full(P, -np.inf, np.float32)
        assigned_nodes: list[int] = []
        assigned_pods: list[int] = []
        self._evicted[:] = False
        rreq = _np(self.snap.running.requests)
        for p in order:
            feasible, score = self.feasible_and_score(
                int(p), used, assigned_nodes, assigned_pods
            )
            if not feasible.any():
                n, victims = self.try_preempt(
                    int(p), float(prio[p]), used, assigned_nodes, assigned_pods
                )
                if n >= 0:
                    for m in victims:
                        used[n] -= rreq[m]
                        self._evicted[m] = True
                    assignment[p] = n  # chosen_score stays -inf (no rescore)
                    used[n] += _np(pods.requests)[p]
                    assigned_nodes.append(n)
                    assigned_pods.append(int(p))
                continue
            masked = np.where(feasible, score, -np.inf)
            if self.cfg.tie_break == "seeded":
                mx = masked.max()
                ties = np.where(masked == mx)[0]
                n = int(ties[tie_hash(self.cfg.tie_seed, int(p)) % len(ties)])
            else:
                n = int(np.argmax(masked))  # first max = tie_break "first"
            assignment[p] = n
            chosen_score[p] = masked[n]
            used[n] += _np(pods.requests)[p]
            assigned_nodes.append(n)
            assigned_pods.append(int(p))
        # Gang all-or-nothing Permit gate (SURVEY.md C8): groups below
        # their minMember quorum unwind entirely (assignments, capacity).
        group = _np(pods.group)
        gmin = _np(self.snap.group_min_member)
        if gmin.shape[0]:
            cnt = np.zeros(gmin.shape[0], np.int64)
            for p in range(P):
                if assignment[p] >= 0 and group[p] >= 0:
                    cnt[group[p]] += 1
            for p in range(P):
                gp = group[p]
                if assignment[p] >= 0 and gp >= 0 and cnt[gp] < gmin[gp]:
                    used[assignment[p]] -= _np(pods.requests)[p]
                    assignment[p] = -1
                    chosen_score[p] = -np.inf
        return OracleResult(
            assignment=assignment,
            order=order.astype(np.int32),
            chosen_score=chosen_score,
            final_used=used,
            evicted=self._evicted.copy(),
        )


def validate_assignment(snap: ClusterSnapshot, cfg: EngineConfig,
                        assignment: np.ndarray,
                        commit_key: np.ndarray | None = None,
                        evicted: np.ndarray | None = None,
                        hard_only: bool = True) -> list[str]:
    """Independent validity audit of any assignment (used to check the
    fast mode's guarantees): capacity respected, static predicates hold,
    and every placed pod's DoNotSchedule-spread / required inter-pod
    constraints hold against its commit-time state.

    commit_key [P]: pods with a strictly smaller key committed earlier.
    A pod is checked against members committed at key <= its own
    (excluding itself) — upstream semantics check only the incoming pod,
    so later commits may legally raise an earlier pod's skew; the fast
    mode additionally guarantees validity against same-key (same-round)
    commits, which this reproduces. With commit_key=None the check is
    against the FINAL state (strictly stronger; holds for parity mode
    only in the absence of retroactive skew).

    GANG-ROLLBACK CAVEAT: a pod whose required affinity (or spread
    headroom) was satisfied at commit time by gang members that the
    all-or-nothing gate later rolled back can be reported as violating
    here, in BOTH modes and in the oracle itself — the audit only sees
    the final placed set. This mirrors upstream optimism: a pod that
    passed Filter using an assumed gang member binds even if that gang
    later un-reserves; nothing re-schedules the dependent. Audits of
    gang-bearing snapshots should treat such reports as the documented
    optimistic-assume edge, not an engine defect (see
    tests/test_gangs.py::test_gang_rollback_audit_caveat).

    Violations consistent with that caveat carry a machine-readable
    " [gang-optimism]" suffix: the constraint flips to satisfied when
    the snapshot's UNPLACED gang members are hypothetically restored to
    the placed set (the audit cannot know their rolled-back provisional
    nodes, so it tries a small greedy family of candidate placements —
    each member alone at each domain-representative node, all members
    at one node, and members round-robin across domains). A flip under
    any tried restoration applies the tag; exotic multi-member cases
    may stay untagged, erring toward reporting a hard violation — the
    tag is never spurious, and gang-free snapshots are never tagged
    (there is nothing to restore).

    hard_only (default True): drop tagged gang-optimism caveats from
    the returned list, so every consumer audits the HARD-violation set
    by default. Pass hard_only=False to also see the tagged caveats
    (opt-in diagnostics; see
    tests/test_gangs.py::test_gang_rollback_audit_caveat).

    EVICTION-TIMING CAVEAT: with `evicted` given, the audit cannot
    know WHEN each eviction happened relative to each commit, so
    pairwise violations are reported only when they hold with the
    evictions applied AND ignored (see the inline note) — faithful
    engine output never yields a false report; a placement valid only
    under a strict subset of the evictions may go unreported.

    Returns human-readable violation strings (empty = valid)."""
    ora = Oracle(snap, cfg)
    pods, nodes = snap.pods, snap.nodes
    assignment = np.asarray(assignment)
    placed = [
        (p, int(n)) for p, n in enumerate(assignment)
        if n >= 0 and _np(pods.valid)[p]
    ]
    out = []
    used = _np(nodes.used).copy()
    if evicted is not None:
        evicted = np.asarray(evicted)
        ora._evicted[:] = evicted  # members stop counting in pairwise checks
        run = snap.running
        rnode, rreq = _np(run.node_idx), _np(run.requests)
        for m in np.argwhere(evicted).ravel():
            if rnode[m] >= 0:
                used[rnode[m]] -= rreq[m]
    for p, n in placed:
        used[n] += _np(pods.requests)[p]
    over = used > _np(nodes.allocatable) + 1e-3
    for n in np.argwhere(over.any(axis=1)).ravel():
        if _np(nodes.valid)[n]:
            out.append(f"node {n}: capacity exceeded {used[n]}")
    # Gang-optimism tagging support (see docstring): the unplaced valid
    # gang members a rollback could have removed, and restoration
    # candidates (one representative node per topology domain). Both
    # lists are CAPPED — the search is a diagnostic aid, and each
    # family costs a full oracle re-check over the placed set; beyond
    # the caps a report simply stays untagged (conservative direction).
    _TAG_MEMBER_CAP, _TAG_CAND_CAP = 32, 16
    group = _np(pods.group)
    gmin = _np(snap.group_min_member)
    pods_valid = _np(pods.valid)
    restorable = (
        [int(q) for q in range(assignment.shape[0])
         if group[q] >= 0 and assignment[q] < 0 and pods_valid[q]]
        [:_TAG_MEMBER_CAP]
        if gmin.shape[0] else []
    )

    def _restore_candidates(n: int) -> list[int]:
        dom = _np(nodes.domain)
        nvalid = _np(nodes.valid)
        cands = {int(n)}
        for k in range(dom.shape[1]):
            seen: set[int] = set()
            for m in np.argwhere(nvalid).ravel():
                d = int(dom[m, k])
                if d >= 0 and d not in seen:
                    seen.add(d)
                    cands.add(int(m))
        return sorted(cands)[:_TAG_CAND_CAP]

    def _gang_tag(p: int, n: int, others: "list[tuple[int, int]]",
                  check: "Callable[[list[int], list[int]], Any]") -> str:
        """' [gang-optimism]' iff some tried hypothetical restoration
        of the unplaced gang members satisfies the constraint."""
        if not restorable:
            return ""
        cands = _restore_candidates(n)
        families = [[(u, c)] for u in restorable for c in cands]
        families += [[(u, c) for u in restorable] for c in cands]
        families.append(
            [(u, cands[i % len(cands)]) for i, u in enumerate(restorable)]
        )
        for fam in families:
            aug = others + fam
            if check([m for _, m in aug], [q for q, _ in aug]):
                return " [gang-optimism]"
        return ""

    # Retroactive-eviction ambiguity (round 5): the audit applies ALL
    # evictions up front, but a pod committed BEFORE a later preemptor's
    # eviction legitimately counted the evicted member in its own
    # check (upstream checks the incoming pod against the cache of its
    # cycle). The audit has no per-eviction timing, so a pairwise
    # violation is reported only if it holds under BOTH timing extremes
    # — evictions applied AND evictions ignored. One-sided: no false
    # reports on faithful engine output; an exotic placement valid only
    # under a strict SUBSET of the evictions could go unreported.
    ora_noev = None
    if evicted is not None and evicted.any() and snap.sigs.key.shape[0]:
        ora_noev = Oracle(snap, cfg)

    def _both(check_fn: "Callable[..., np.ndarray]", p: int,
              on: "list[int]", op: "list[int]", n: int) -> bool:
        """True iff the check FAILS under both eviction timings."""
        if check_fn(ora, p, on, op)[n]:
            return False
        return ora_noev is None or not check_fn(ora_noev, p, on, op)[n]

    sp_fn = lambda o, p, on, op: o.spread_ok_and_penalty(p, on, op)[0]
    ia_fn = lambda o, p, on, op: o.interpod_ok_and_raw(p, on, op)[0]
    sym_fn = lambda o, p, on, op: o.symmetric_anti_ok(p, on, op)

    # No signatures = no spread / inter-pod / symmetric-anti term on any
    # pod or member, so the O(placed^2) pairwise checks are vacuous.
    pairwise = snap.sigs.key.shape[0] > 0
    for p, n in placed:
        if not _np(nodes.valid)[n]:
            out.append(f"pod {p}: placed on invalid node {n}")
            continue
        if not _np(nodes.schedulable)[n] and not _np(
            pods.tolerates_unsched
        )[p]:
            out.append(f"pod {p}: placed on cordoned node {n}")
        if not ora.taints_ok(p)[n]:
            out.append(f"pod {p}: node {n} has untolerated taint")
        if not ora.node_affinity_ok(p)[n]:
            out.append(f"pod {p}: node {n} fails required node affinity")
        if not pairwise:
            continue
        if commit_key is None:
            others = [(q, m) for q, m in placed if q != p]
        else:
            others = [
                (q, m) for q, m in placed
                if q != p and commit_key[q] <= commit_key[p]
            ]
        others_n = [m for _, m in others]
        others_p = [q for q, _ in others]
        if _both(sp_fn, p, others_n, others_p, n):
            tag = _gang_tag(
                p, n, others,
                lambda on, op: ora.spread_ok_and_penalty(p, on, op)[0][n],
            )
            out.append(
                f"pod {p}: node {n} violates DoNotSchedule spread{tag}"
            )
        if _both(ia_fn, p, others_n, others_p, n):
            tag = _gang_tag(
                p, n, others,
                lambda on, op: ora.interpod_ok_and_raw(p, on, op)[0][n],
            )
            out.append(
                f"pod {p}: node {n} violates required pod affinity{tag}"
            )
        if _both(sym_fn, p, others_n, others_p, n):
            # Restoring members can only ADD anti holders, never remove
            # them, so a symmetric-anti violation cannot be
            # gang-optimism: always untagged.
            out.append(
                f"pod {p}: node {n} violates a member's symmetric anti-affinity"
            )
    # Gang all-or-nothing: a group with ANY placed member must have at
    # least minMember placed (SURVEY.md C8).
    if gmin.shape[0]:
        cnt: dict[int, int] = {}
        for p, n in placed:
            if group[p] >= 0:
                cnt[int(group[p])] = cnt.get(int(group[p]), 0) + 1
        for g, c in sorted(cnt.items()):
            if c < gmin[g]:
                out.append(
                    f"group {g}: {c} placed < minMember {gmin[g]} "
                    "(partial gang placement)"
                )
    if hard_only:
        out = [v for v in out if "[gang-optimism]" not in v]
    return out


# ---------------------------------------------------------------------------


def _atom_sat_row(key: int, op: int, pairs: np.ndarray, num: float,
                  lp: np.ndarray, lk: np.ndarray,
                  ln: np.ndarray) -> np.ndarray:
    """Satisfaction of one atom over label arrays lp/lk/ln of shape [X, L]."""
    pair_set = pairs[pairs >= 0]
    any_pair = np.isin(lp, pair_set).any(axis=1) if pair_set.size else np.zeros(lp.shape[0], bool)
    exists = (lk == key).any(axis=1)
    if op == OP_IN:
        return any_pair
    if op == OP_NOT_IN:
        return ~any_pair
    if op == OP_EXISTS:
        return exists
    if op == OP_DOES_NOT_EXIST:
        return ~exists
    # Gt / Lt: numeric value of the matching key; absent or unparsable
    # (NaN) labels never satisfy. Formulation mirrors kernels/atoms.py
    # exactly so oracle and device agree bitwise.
    matched = (lk == key) & np.isfinite(ln)
    has = matched.any(axis=1)
    val = np.where(matched, ln, 0.0).sum(axis=1)
    if op == OP_GT:
        return has & (val > num)
    if op == OP_LT:
        return has & (val < num)
    raise ValueError(f"bad op {op}")


def _default_normalize(raw: np.ndarray, nvalid: np.ndarray) -> np.ndarray:
    """Upstream DefaultNormalizeScore: scale so max becomes 100."""
    mx = raw[nvalid].max() if nvalid.any() else 0.0
    if mx <= 0:
        return np.zeros_like(raw)
    return (raw * MAX_NODE_SCORE / mx).astype(np.float32)


def _inverse_normalize(penalty: np.ndarray, nvalid: np.ndarray) -> np.ndarray:
    """Lower penalty -> higher score; all-equal -> 100."""
    if not nvalid.any():
        return np.zeros_like(penalty)
    mx = penalty[nvalid].max()
    mn = penalty[nvalid].min()
    if mx <= mn:
        return np.full_like(penalty, MAX_NODE_SCORE)
    return ((mx - penalty) * MAX_NODE_SCORE / (mx - mn)).astype(np.float32)


def _upstream_normalize(raw: np.ndarray, nvalid: np.ndarray) -> np.ndarray:
    """Upstream InterPodAffinity normalize: (raw-min)/(max-min)*100,
    all-zero -> 0."""
    if not nvalid.any():
        return np.zeros_like(raw)
    mx = raw[nvalid].max()
    mn = raw[nvalid].min()
    if mx == mn:
        return np.zeros_like(raw)
    return ((raw - mn) * MAX_NODE_SCORE / (mx - mn)).astype(np.float32)
