"""Commit loops (SURVEY.md C11) and the batched score matrix.

The scheduling cycle (Filter + Score + Normalize; the device analogue of
the reference's `scheduleOne` body, SURVEY.md §3.1) splits into:

  * a STATIC part (taints, node affinity, their scores, per-pod QoS
    plugin weights, signature label-match tables) computed once per
    snapshot — StaticCtx;
  * a DYNAMIC part (resource fit, LeastRequested, BalancedAllocation,
    pairwise terms from domain counts) that depends on node `used` and
    the [S, N] signature counts.

Three drivers:
  * solve_sequential — EXACT stock semantics (parity mode): lax.scan
    over pods in dynamic-priority order; each step updates `used` and
    the domain counts before the next pod scores.
  * solve_rounds — fast mode: optimistic batched rounds. Every pending
    pod scores against round-start state; commits are resolved per node
    by a priority-ordered capacity prefix scan; committed pods with
    pairwise constraints are re-validated against end-of-round counts
    (self-excluded) and violators are rolled back and marked
    "conservative" — a conservative pod only commits in a round where it
    is the globally highest-priority pending pod, which makes its check
    state exactly sequential. Terminates when a round makes no progress.
    Matches sequential placements whenever pods' decisions don't interact
    (the common case); under contention it stays *valid* (capacity
    respected; pairwise constraints hold against commit-time state) but
    may order contended pods differently (SURVEY.md §7 hard parts 1/3).
  * score_batch — the ScoreBatch API of the north star: all pods scored
    at once, no commits — what a Go scheduler calls through the gRPC
    boundary for NormalizeScore + Bind.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import struct

from tpusched.config import DO_NOT_SCHEDULE, EngineConfig
from tpusched.kernels.atoms import atom_sat, gather_term_sat
from tpusched.kernels import filter as kfilter
from tpusched.kernels import pairwise as kpair
from tpusched.kernels import preempt as kpreempt
from tpusched.kernels import score as kscore
from tpusched.qos import (
    effective_priority,
    effective_weights,
    pressure_of,
    tie_hash,
)
from tpusched.snapshot import ClusterSnapshot

NEG_INF = -jnp.inf

# Per-round commit/revert tracing via jax.debug.print. Read at TRACE
# time: set it before the Engine's first solve at a given shape — an
# already-compiled executable keeps whatever the flag was when traced.
import os as _os_mod

_DEBUG_ROUNDS = bool(_os_mod.environ.get("TPUSCHED_DEBUG_ROUNDS"))


@struct.dataclass
class StaticCtx:
    """Snapshot-dependent but state-independent precomputation."""

    mask: Any       # [P, N] bool: taints & node affinity & validity
    aff_ok: Any     # [P, N] bool: node-affinity component alone (spread
                    # domain eligibility honors it)
    score: Any      # [P, N] f32: w_na*NodeAffinity + w_tt*TaintToleration
    sig_match: Any  # [S, M+P] bool: signature selector label matches
    w_lr: Any       # [P] f32 per-pod effective plugin weights (QoS)
    w_ba: Any       # [P]
    w_ts: Any       # [P]
    w_ia: Any       # [P]
    rw: Any         # [R] resource score weights


@struct.dataclass
class WarmTableau:
    """The carried warm-start tableau (ROADMAP item 3): every CELL-LOCAL
    static table of the Filter/Score program, resident on device across
    delta cycles inside a lineage. "Cell-local" means cell (p, n) depends
    only on pod p's row, node n's row, and the (vocab-stable) atom/sig
    tables — so a delta cycle can recompute exactly the dirty rows and
    columns and scatter-merge them (refresh_tableau), and the result is
    the same table a from-scratch build would produce. Everything with
    cross-row coupling (per-pod score normalization, QoS weights, pop
    order, pair-state counts) is deliberately EXCLUDED and recomputed
    fresh each solve by finalize_static / the solve drivers — that is
    what makes warm placements bitwise-equal to cold ones.

    Access discipline (tpuschedlint TPL011): the tableau is only valid
    straight after the engine warm path refreshed it against the current
    snapshot; reads outside engine.py / device_state.py / this module
    are the stale-tableau hazard class."""

    node_sat_t: Any    # [A, N] bool  atom satisfaction over node labels
    member_sat_t: Any  # [A, M+P] bool  over member (running|pending) labels
    sig_match: Any     # [S, M+P] bool  signature selector x member
    mask: Any          # [P, N] bool  static feasibility (taints/affinity/cordon)
    aff_ok: Any        # [P, N] bool  node-affinity component alone
    na_raw: Any        # [P, N] f32  pre-normalize preferred-affinity sums
    tt_count: Any      # [P, N] f32  intolerable PreferNoSchedule taint counts


def _tableau_cells(snap: ClusterSnapshot, pods_v, nodes_v, node_sat_v):
    """The cell-local tableau block for any (pods view, nodes view)
    pair: full build passes the whole snapshot, refresh passes gathered
    dirty rows/columns. One shared body so a refreshed cell runs the
    exact op sequence the full build ran (bool ops are exact; the f32
    sums reduce over identical per-cell extents)."""
    aff_ok = kfilter.node_affinity_mask(
        node_sat_v, pods_v.req_term_atoms, pods_v.req_term_valid
    )
    # Cordon (NodeUnschedulable plugin): closed to new pods UNLESS the
    # pod tolerates node.kubernetes.io/unschedulable (DaemonSet pattern).
    cordon_ok = (
        nodes_v.schedulable[None, :] | pods_v.tolerates_unsched[:, None]
    )
    mask = (
        aff_ok
        & kfilter.taint_mask(nodes_v.taint_ids, snap.taint_effect,
                             pods_v.tolerated)
        & nodes_v.valid[None, :]
        & cordon_ok
        & pods_v.valid[:, None]
    )
    na_raw = kscore.node_affinity_raw(
        node_sat_v, pods_v.pref_term_atoms, pods_v.pref_term_valid,
        pods_v.pref_weight,
    )
    tt_count = kscore.taint_intolerable_count(
        nodes_v.taint_ids, snap.taint_effect, pods_v.tolerated
    )
    return mask, aff_ok, na_raw, tt_count


def build_tableau(cfg: EngineConfig, snap: ClusterSnapshot,
                  node_sat_t, member_sat_t, mesh=None) -> WarmTableau:
    """Full (cold) tableau build from the snapshot's sat tables."""
    mask, aff_ok, na_raw, tt_count = _tableau_cells(
        snap, snap.pods, snap.nodes, node_sat_t
    )
    return WarmTableau(
        node_sat_t=node_sat_t, member_sat_t=member_sat_t,
        sig_match=kpair.sig_member_match(snap, member_sat_t, mesh),
        mask=mask, aff_ok=aff_ok, na_raw=na_raw, tt_count=tt_count,
    )


def refresh_tableau(cfg: EngineConfig, snap: ClusterSnapshot,
                    tab: WarmTableau, dirty_pods=None, dirty_nodes=None,
                    dirty_members=None, pod_perm=None, node_perm=None,
                    member_perm=None, mesh=None) -> WarmTableau:
    """O(churn) tableau maintenance: reorder gathers (when record
    insertion/removal shifted the name-sorted row order — exactly the
    permutations device_state applies to the snapshot arrays), then
    recompute and scatter-merge the dirty pod ROWS, node COLUMNS, and
    member columns. Order matters: node sat rows first (the pod-row and
    node-column recomputes read them), then rows, then columns; an
    overlapping (dirty pod, dirty node) cell is written twice with the
    same fresh value. Dirty index arrays may carry repeated indices
    (pow2 padding) — duplicate scatters write identical content.

    Vocabulary growth (new atoms/sigs/taints/topo keys) is NOT
    expressible here — those change rows this function never touches —
    and must force a cold rebuild; device_state.warm_delta() is the
    gatekeeper."""
    nst, mst, sm = tab.node_sat_t, tab.member_sat_t, tab.sig_match
    mask, aff_ok = tab.mask, tab.aff_ok
    na_raw, ttc = tab.na_raw, tab.tt_count
    if node_perm is not None:
        nst = nst[:, node_perm]
        mask = mask[:, node_perm]
        aff_ok = aff_ok[:, node_perm]
        na_raw = na_raw[:, node_perm]
        ttc = ttc[:, node_perm]
    if pod_perm is not None:
        mask = mask[pod_perm]
        aff_ok = aff_ok[pod_perm]
        na_raw = na_raw[pod_perm]
        ttc = ttc[pod_perm]
    if member_perm is not None:
        mst = mst[:, member_perm]
        sm = sm[:, member_perm]
    if dirty_nodes is not None:
        nv = jax.tree.map(lambda a: a[dirty_nodes], snap.nodes)
        sat_rows = atom_sat(snap.atoms, nv.label_pairs, nv.label_keys,
                            nv.label_nums)                   # [D, A]
        nst = nst.at[:, dirty_nodes].set(sat_rows.T)
    if dirty_members is not None:
        lp = kpair.merge_members(
            snap.running.label_pairs, snap.pods.label_pairs, mesh
        )[dirty_members]
        lk = kpair.merge_members(
            snap.running.label_keys, snap.pods.label_keys, mesh
        )[dirty_members]
        mns = kpair.merge_members(
            snap.running.namespace, snap.pods.namespace, mesh
        )[dirty_members]
        sat_cols = atom_sat(snap.atoms, lp, lk, None).T      # [A, D]
        mst = mst.at[:, dirty_members].set(sat_cols)
        match = gather_term_sat(sat_cols, snap.sigs.atoms)   # [S, D]
        ns_ok = kpair.ns_scope_ok(snap.sigs.ns, snap.sigs.ns_all, mns)
        sm = sm.at[:, dirty_members].set(
            match & ns_ok & snap.sigs.valid[:, None]
        )
    if dirty_pods is not None:
        pv = jax.tree.map(lambda a: a[dirty_pods], snap.pods)
        m_r, a_r, n_r, t_r = _tableau_cells(snap, pv, snap.nodes, nst)
        mask = mask.at[dirty_pods].set(m_r)
        aff_ok = aff_ok.at[dirty_pods].set(a_r)
        na_raw = na_raw.at[dirty_pods].set(n_r)
        ttc = ttc.at[dirty_pods].set(t_r)
    if dirty_nodes is not None:
        nv = jax.tree.map(lambda a: a[dirty_nodes], snap.nodes)
        m_c, a_c, n_c, t_c = _tableau_cells(
            snap, snap.pods, nv, nst[:, dirty_nodes]
        )
        mask = mask.at[:, dirty_nodes].set(m_c)
        aff_ok = aff_ok.at[:, dirty_nodes].set(a_c)
        na_raw = na_raw.at[:, dirty_nodes].set(n_c)
        ttc = ttc.at[:, dirty_nodes].set(t_c)
    return WarmTableau(node_sat_t=nst, member_sat_t=mst, sig_match=sm,
                       mask=mask, aff_ok=aff_ok, na_raw=na_raw,
                       tt_count=ttc)


def finalize_static(cfg: EngineConfig, snap: ClusterSnapshot,
                    tab: WarmTableau) -> StaticCtx:
    """StaticCtx from a (fresh or carried) tableau: everything with
    cross-row coupling — per-pod QoS plugin weights (pressure is read
    from the CURRENT snapshot, so a pressure change never needs a dirty
    row) and the per-pod max-normalizations of the NA/TT scores — is
    recomputed here, every solve, warm or cold."""
    nodes, pods = snap.nodes, snap.pods
    w = effective_weights(
        cfg, pressure_of(pods.slo_target, pods.observed_avail)
    )  # dict of [P] arrays
    na = kscore.default_normalize(tab.na_raw, nodes.valid)
    tt = kscore.taint_toleration_from_count(tab.tt_count, nodes.valid)
    static_score = (
        w["node_affinity"][:, None] * na + w["taint_toleration"][:, None] * tt
    ).astype(jnp.float32)
    return StaticCtx(
        mask=tab.mask, aff_ok=tab.aff_ok, score=static_score,
        sig_match=tab.sig_match,
        w_lr=w["least_requested"], w_ba=w["balanced_allocation"],
        w_ts=w["topology_spread"], w_ia=w["interpod_affinity"],
        rw=jnp.asarray(cfg.score_weights_vector(), jnp.float32),
    )


def precompute_static(cfg: EngineConfig, snap: ClusterSnapshot, node_sat_t,
                      member_sat_t, mesh=None) -> StaticCtx:
    return finalize_static(
        cfg, snap, build_tableau(cfg, snap, node_sat_t, member_sat_t, mesh)
    )


def batched_cycle(cfg: EngineConfig, snap: ClusterSnapshot,
                  static: StaticCtx, used, pair_st,
                  exclude_self_node=None, return_relaxed: bool = False):
    """Full [P, N] Filter + Score against the given state. Score-sum
    grouping mirrors oracle.feasible_and_score exactly.

    return_relaxed=True additionally returns the SPREAD-RELAXED
    feasibility (all predicates except the DoNotSchedule skew filter):
    the fast mode's water-fill dealer may target domains whose skew is
    over the bound against ROUND-START counts but legal against
    end-of-round counts (the state its validator — and the fast-mode
    contract — actually checks); see _spread_waterfill_deal."""
    nodes = snap.nodes
    nvalid = nodes.valid
    base_feasible = static.mask & kfilter.resource_fit(
        nodes.allocatable, used, snap.pods.requests
    )
    base_score = (
        static.w_lr[:, None]
        * kscore.least_requested(nodes.allocatable, used, snap.pods.requests, static.rw)
        + static.w_ba[:, None]
        * kscore.balanced_allocation(nodes.allocatable, used, snap.pods.requests, static.rw)
        + static.score
    )
    if snap.sigs.key.shape[0] == 0:
        # No pairwise constraints anywhere (trace-time fact): penalty is
        # 0 everywhere -> inverse_normalize == 100, raw 0 -> minmax == 0,
        # matching the oracle's formulas exactly without [P, N] work.
        score = base_score + static.w_ts[:, None] * 100.0
        if return_relaxed:
            return base_feasible, score.astype(jnp.float32), base_feasible
        return base_feasible, score.astype(jnp.float32)
    spread_ok, spread_pen, ia_ok, ia_raw = kpair.pairwise_from_counts(
        snap, pair_st, static.aff_ok, static.sig_match, exclude_self_node
    )
    feasible = base_feasible & spread_ok & ia_ok
    score = (
        base_score
        + static.w_ts[:, None] * kscore.inverse_normalize(spread_pen, nvalid)
        + static.w_ia[:, None] * kscore.minmax_normalize(ia_raw, nvalid)
    ).astype(jnp.float32)
    if return_relaxed:
        return feasible, score, base_feasible & ia_ok
    return feasible, score


def pod_cycle(cfg: EngineConfig, snap: ClusterSnapshot, static: StaticCtx,
              p, used, pair_st):
    """Single-pod [N] Filter + Score (sequential scan body). Also
    returns the non-resource feasibility (static & pairwise) so the
    preemption branch can reuse it without recomputing pairwise_row."""
    nodes = snap.nodes
    nvalid = nodes.valid
    req = snap.pods.requests[p]
    spread_ok, spread_pen, ia_ok, ia_raw = kpair.pairwise_row(
        snap, pair_st, static.sig_match, p, static.aff_ok[p]
    )
    allowed = static.mask[p] & spread_ok & ia_ok
    feasible = allowed & kfilter.resource_fit(nodes.allocatable, used, req)
    score = (
        static.w_lr[p] * kscore.least_requested(nodes.allocatable, used, req, static.rw)
        + static.w_ba[p] * kscore.balanced_allocation(nodes.allocatable, used, req, static.rw)
        + static.score[p]
        + static.w_ts[p] * kscore.inverse_normalize(spread_pen, nvalid)
        + static.w_ia[p] * kscore.minmax_normalize(ia_raw, nvalid)
    ).astype(jnp.float32)
    return feasible, score, allowed


def gang_rollback(snap: ClusterSnapshot, used, assigned, chosen, pair_st,
                  sig_match):
    """All-or-nothing Permit gate (SURVEY.md C8, coscheduling): a pod
    group with fewer than group_min_member placed members rolls back
    entirely — capacity, pair state, and assignments. minMember is a
    floor, not a cap: extra members above quorum stay placed. Quorum is
    batch-local (running members are not tracked against minMember).
    Returns (used, assigned, chosen, pair_st, rolled_mask)."""
    pods = snap.pods
    P = assigned.shape[0]
    G = snap.group_min_member.shape[0]
    if G == 0:
        return used, assigned, chosen, pair_st, jnp.zeros(P, bool)
    g = pods.group
    placed = (assigned >= 0) & pods.valid & (g >= 0)
    gclip = jnp.clip(g, 0, None)
    cnt = jnp.zeros(G, jnp.float32).at[gclip].add(placed.astype(jnp.float32))
    quorum = cnt >= snap.group_min_member.astype(jnp.float32)
    roll = placed & ~quorum[gclip]
    used = used.at[jnp.clip(assigned, 0, None)].add(  # tpl: disable=TPL203(rollback subtraction order matches the oracle's sequential gang rollback bit-for-bit on the parity contract; co-located rolled members are rare and integer-valued in every workload — conversion to _node_add tracked in the ledger for item 1)
        -jnp.where(roll[:, None], pods.requests, 0.0)
    )
    if snap.sigs.key.shape[0]:
        pair_st = kpair.pair_state_commit(
            snap, pair_st, sig_match, assigned, roll, sign=-1.0
        )
    assigned = jnp.where(roll, -1, assigned)
    chosen = jnp.where(roll, NEG_INF, chosen)
    return used, assigned, chosen, pair_st, roll


def pick_node(cfg: EngineConfig, masked, p):
    """Select among score maxima (C5 'max-score node wins'): lowest
    index ("first") or a seeded uniform pick ("seeded", the upstream
    rand-among-max analogue; oracle mirrors bit-for-bit)."""
    if cfg.tie_break == "first":
        return jnp.argmax(masked)
    mx = jnp.max(masked)
    ties = masked == mx
    cnt = jnp.maximum(jnp.sum(ties), 1).astype(jnp.uint32)
    h = (tie_hash(cfg.tie_seed, p) % cnt).astype(jnp.int32)
    rank = jnp.cumsum(ties) - 1
    return jnp.argmax(ties & (rank == h))


def pick_node_batch(cfg: EngineConfig, masked, pod_idx):
    """Row-wise pick_node over a [P?, N] score block: each row's seeded
    uniform pick among its maxima, hash-keyed by the ORIGINAL pod index
    (so compacted residual views pick identically to full-width rows
    and to the oracle). Returns None for tie_break='first' — callers
    use it as 'no override'."""
    if cfg.tie_break == "first":
        return None
    mx = jnp.max(masked, axis=1, keepdims=True)
    ties = masked == mx
    cnt = jnp.maximum(jnp.sum(ties, axis=1), 1).astype(jnp.uint32)
    h = (tie_hash(cfg.tie_seed, pod_idx) % cnt).astype(jnp.int32)
    rank = jnp.cumsum(ties, axis=1) - 1
    return jnp.argmax(
        ties & (rank == h[:, None]), axis=1
    ).astype(jnp.int32)


def pop_order(cfg: EngineConfig, snap: ClusterSnapshot):
    """Queue order (SURVEY.md C10): stable descending sort by dynamic
    QoS priority; invalid pods sink to the end."""
    pods = snap.pods
    prio = effective_priority(
        cfg, pods.base_priority, pods.slo_target, pods.observed_avail
    )
    key = jnp.where(pods.valid, prio, NEG_INF)
    return jnp.argsort(-key, stable=True)


def _preempt_branch(cfg: EngineConfig, snap: ClusterSnapshot, static,
                    pctx, prio_p, p, allowed, used, st, evicted):
    """PostFilter for one pod: victim search + state updates. `allowed`
    is the pod's non-resource feasibility row from pod_cycle. Returns
    (used, st, evicted, node-or-minus-1)."""
    best_n, can, evict_m, freed = kpreempt.preempt_step(
        cfg, snap, pctx, prio_p, snap.pods.requests[p], allowed, used, evicted
    )
    used = used - freed
    used = used.at[best_n].add(
        jnp.where(can, snap.pods.requests[p], 0.0)
    )
    st = kpair.pair_state_evict(snap, st, static.sig_match, evict_m)
    st = kpair.pair_state_add_pod(snap, st, static.sig_match, p, best_n, can)
    evicted = evicted | evict_m
    return used, st, evicted, jnp.where(can, best_n, -1).astype(jnp.int32)


def solve_sequential(cfg: EngineConfig, snap: ClusterSnapshot,
                     node_sat_t, member_sat_t, init_counts=None,
                     explain: bool = False, static=None, mesh=None):
    """Exact sequential commit: stock scheduleOne semantics on device,
    including inline PostFilter preemption (cfg.preemption) at the exact
    point upstream runs it — immediately after a pod fails Filter.
    Returns (assigned, chosen, used, order, evicted); with explain=True
    an extra trailing tuple (rolled, evictor, evict_round, zeros-shaped
    auction table) — in parity mode "evict_round" is the pop-order step
    at which the eviction committed, and the auction table is all-zero
    (there is no auction; the shape is kept so the engine's packed
    explain layout is mode-independent). static: optional precomputed
    StaticCtx (the warm path's finalize_static output); None computes
    it from the sat tables."""
    if static is None:
        static = precompute_static(cfg, snap, node_sat_t, member_sat_t,
                                   mesh)
    P = snap.pods.valid.shape[0]
    M = snap.running.valid.shape[0]
    order = pop_order(cfg, snap)
    st0 = kpair.pair_state_init(snap, static.sig_match, counts=init_counts,
                                mesh=mesh)
    do_preempt = cfg.preemption and M > 0
    if do_preempt:
        pctx = kpreempt.precompute(cfg, snap)
        prio = effective_priority(
            cfg, snap.pods.base_priority, snap.pods.slo_target,
            snap.pods.observed_avail,
        )

    def body(carry, x):
        if explain:
            p, pos = x
            used, assigned, st, evicted, evictor, evict_rd = carry
        else:
            p = x
            used, assigned, st, evicted = carry
        feasible, score, allowed = pod_cycle(cfg, snap, static, p, used, st)
        masked = jnp.where(feasible, score, NEG_INF)
        n = pick_node(cfg, masked, p)
        commit = jnp.any(feasible)
        used = used.at[n].add(jnp.where(commit, snap.pods.requests[p], 0.0))
        st = kpair.pair_state_add_pod(snap, st, static.sig_match, p, n, commit)
        a_p = jnp.where(commit, n, -1).astype(jnp.int32)
        if do_preempt:
            # Gang members never preempt: their placement is provisional
            # until quorum (gang_rollback), and evicting real workloads
            # for a provisional placement would strand the victims.
            prev_evicted = evicted
            used, st, evicted, pn = jax.lax.cond(
                ~commit & snap.pods.valid[p] & (snap.pods.group[p] < 0),
                lambda ops: _preempt_branch(
                    cfg, snap, static, pctx, prio[p], p, allowed, *ops
                ),
                lambda ops: (*ops, jnp.int32(-1)),
                (used, st, evicted),
            )
            a_p = jnp.where(commit, a_p, pn)
            if explain:
                new_ev = evicted & ~prev_evicted
                evictor = jnp.where(new_ev, p, evictor)
                evict_rd = jnp.where(new_ev, pos, evict_rd)
        assigned = assigned.at[p].set(a_p)
        out = (used, assigned, st, evicted)
        if explain:
            out = out + (evictor, evict_rd)
        # Preempted placements carry no score (upstream nominates without
        # rescoring); chosen stays -inf for them, as in the oracle.
        return out, jnp.where(commit, masked[n], NEG_INF)

    init = (
        snap.nodes.used, jnp.full(P, -1, jnp.int32), st0,
        jnp.zeros(M, bool),
    )
    xs = order
    if explain:
        init = init + (jnp.full(M, -1, jnp.int32),
                       jnp.full(M, -1, jnp.int32))
        xs = (order, jnp.arange(P, dtype=jnp.int32))
    # unroll=4: purely an XLA loop-overhead optimization (4 pod steps
    # per while iteration, same sequential dataflow — placements are
    # bit-identical); ~15% off the 10k-pod scan on v5e.
    final, chosen_in_order = jax.lax.scan(body, init, xs, unroll=4)
    used, assigned, st, evicted = final[:4]
    chosen = jnp.full(P, NEG_INF, jnp.float32).at[order].set(chosen_in_order)
    used, assigned, chosen, _, rolled = gang_rollback(
        snap, used, assigned, chosen, st, static.sig_match
    )
    if explain:
        astats = jnp.zeros(
            (_PREEMPT_MAX_ROUNDS, len(EXPLAIN_AUCTION_STATS)), jnp.float32
        )
        return (assigned, chosen, used, order, evicted,
                (rolled, final[4], final[5], astats))
    return assigned, chosen, used, order, evicted


def score_batch(cfg: EngineConfig, snap: ClusterSnapshot, node_sat_t,
                member_sat_t, init_counts=None, static=None, mesh=None):
    """One-shot [P, N] feasibility + scores against the current snapshot
    (no commits): the ScoreBatch gRPC surface (SURVEY.md C12)."""
    if static is None:
        static = precompute_static(cfg, snap, node_sat_t, member_sat_t,
                                   mesh)
    st0 = kpair.pair_state_init(snap, static.sig_match, counts=init_counts,
                                mesh=mesh)
    return batched_cycle(cfg, snap, static, snap.nodes.used, st0)


# ---------------------------------------------------------------------------
# Delta-update entry points (device-resident cluster state,
# tpusched/device_state.py): one XLA scatter / gather over a whole
# struct-of-arrays group. jit caches per (pytree structure, shapes) —
# callers bucket the churned-row count to powers of two so the compile
# set stays bounded. Duplicate scatter indices are only ever written
# with IDENTICAL row content (idx padding repeats a real row), so the
# unspecified duplicate-write order cannot change the result.
# ---------------------------------------------------------------------------


@jax.jit
def scatter_rows(tree, idx, rows):
    """tree.leaf[idx[j]] = rows.leaf[j] for every leaf of a
    struct-of-arrays pytree (NodeArrays / PodArrays / ... or a bare
    array): the O(churn) device-side write of a delta update."""
    return jax.tree.map(lambda a, r: a.at[idx].set(r), tree, rows)


@jax.jit
def permute_rows(tree, perm):
    """Row gather tree.leaf[perm] over a struct-of-arrays pytree: the
    device-side reorder when record insertion/removal shifts the
    name-sorted row order (host ships one [rows] int32 permutation, not
    the arrays)."""
    return jax.tree.map(lambda a: a[perm], tree)


def _spread_waterfill_deal(snap: ClusterSnapshot, pair_st, used, relaxed,
                           score, allowed, rank, K: int):
    """Domain-balanced dealing for spread-constrained pods (round-4):
    the global capacity dealer sends same-sig members to ADJACENT
    ranked nodes — one topology domain — and the skew validator then
    reverts all but ~maxSkew of them, draining spread-heavy workloads
    at ~(sigs x domains) commits per round (146 rounds at 10k x 5k).
    Instead, each sig's members (rank order) are water-filled across
    its existing domains — member q goes to the domain that keeps the
    per-domain fill levels flattest given current counts — and each
    member gets K+1 candidate nodes INSIDE its assigned domain
    (successive free-capacity rotation positions, so capacity misses
    spill to the domain's next node within the same round instead of
    escaping to a global — wrong-domain — fallback and being reverted).

    `relaxed` is the SPREAD-RELAXED feasibility (batched_cycle
    return_relaxed): the start-state DoNotSchedule filter forbids every
    domain above min_start + maxSkew, which under imbalance is ALL
    domains but the emptiest — upstream's sequential loop escapes this
    because its counts move per pod, and the fast mode escapes it here
    by targeting against end-of-round semantics and letting the skew
    validator (which checks exactly that state) confirm or revert.
    Returns (cand[P, K+1] int32, val[P, K+1] f32 scores at those
    candidates, ok[P] bool); ok=False falls back to the capacity
    dealer's choice (e.g. no relax-feasible node in the domain)."""
    pods = snap.pods
    S = snap.sigs.key.shape[0]
    P = rank.shape[0]
    if pods.ts_valid.shape[1] == 0 or S == 0:
        # No spread-constraint slots in this snapshot (trace-time):
        # nothing to water-fill.
        return (jnp.zeros((P, K + 1), jnp.int32),
                jnp.full((P, K + 1), NEG_INF, jnp.float32),
                jnp.zeros(P, bool))
    BIG = jnp.int32(2**31 - 1)
    LARGE = jnp.float32(1e9)  # finite stand-in for "domain absent"
    dom_s = kpair.sig_domains(snap)                          # [S, N]
    N = dom_s.shape[1]
    # Members are pods with a DoNotSchedule constraint, keyed by their
    # FIRST DNS slot — that is the filter that serializes them.
    # ScheduleAnyway-only pods keep the normal score-driven dealing
    # (their spread score already penalizes crowded domains, and the
    # skew validator never reverts them).
    dns = pods.ts_valid & (pods.ts_when == DO_NOT_SCHEDULE)
    has_dns = jnp.any(dns, axis=1)
    first_c = jnp.argmax(dns, axis=1)
    s_p = jnp.clip(
        pods.ts_sig[jnp.arange(P), first_c], 0, None
    )                                                        # [P]
    member = allowed & has_dns
    # In-sig 0-based rank positions among this round's members.
    gid = jnp.where(member, s_p, S)
    perm = jnp.lexsort((rank, gid))
    gid_sorted = gid[perm]
    mem_sorted = member[perm]
    boundary = jnp.concatenate(
        [jnp.ones(1, bool), gid_sorted[1:] != gid_sorted[:-1]]
    )
    idx = jnp.arange(P, dtype=jnp.int32)
    cum = jnp.cumsum(mem_sorted.astype(jnp.float32))
    seg_start = jax.lax.cummax(jnp.where(boundary, idx, 0))
    q_off = jnp.where(
        seg_start > 0, cum[jnp.clip(seg_start - 1, 0, None)], 0.0
    )
    q_sorted = cum - q_off - 1.0                             # 0-based
    q = jnp.zeros(P, jnp.float32).at[perm].set(q_sorted)
    # Per-sig water-fill tables over the domain-count rows.
    exist = jnp.zeros((S, N), bool).at[
        jnp.arange(S)[:, None], jnp.clip(dom_s, 0, None)
    ].max(dom_s >= 0)
    cnt = jnp.where(exist, pair_st.counts, LARGE)            # [S, N]
    ord_dom = jnp.argsort(cnt, axis=1)                       # [S, N]
    csort = jnp.take_along_axis(cnt, ord_dom, axis=1)
    presum = jnp.concatenate(
        [jnp.zeros((S, 1), jnp.float32),
         jnp.cumsum(csort, axis=1)[:, :-1]], axis=1  # tpl: disable=TPL201(water-fill level table: counts mixed with the LARGE=1e9 absent-domain sentinel do round, but the table only DEALS members to domains — the skew validator (_spread_excess_mask, integer-exact) confirms or reverts every commit)
    )
    js = jnp.arange(N, dtype=jnp.float32)[None, :]
    fill = js * csort - presum                               # [S, N] nondecr.
    fill_p = fill[s_p]                                       # [P, N]
    # searchsorted(fill_p[p], q[p], right) == count of entries <= q[p]:
    # one [P, N] compare+reduce (a vmapped searchsorted lowers to P
    # serial row searches — ~20 ms/round at 10k x 5k).
    j_p = jnp.clip(
        jnp.sum((fill_p <= q[:, None]).astype(jnp.int32), axis=1) - 1,
        0, N - 1,
    )
    r_p = (q - jnp.take_along_axis(fill_p, j_p[:, None], axis=1)[:, 0])
    r_i = r_p.astype(jnp.int32)
    slot = jnp.mod(r_i, j_p + 1)
    dchoice = jnp.take_along_axis(
        ord_dom[s_p], slot[:, None], axis=1
    )[:, 0]                                                  # [P] domain id
    in_dom = dom_s[s_p] == dchoice[:, None]                  # [P, N]
    sel = relaxed & in_dom
    # Within the domain, members must also fan out across NODES: the
    # best-scoring node is nearly the same for every member (the load
    # balancing scores barely separate them), and one node holds only a
    # few pods — argmax here re-creates the herding one level down
    # (observed: the commit rate stayed capacity-capped at ~15/round).
    # Member m of its (sig, domain) takes the (m mod n_feasible)-th
    # feasible domain node in free-capacity order, with the next K
    # rotation positions as its spill candidates.
    m_p = r_i // (j_p + 1)                                   # [P] level offset
    alloc = snap.nodes.allocatable
    free_frac = jnp.mean(  # tpl: disable=TPL201(per-node mean over the FIXED R resource axis — cell-local, never padded or sharded; orders a dealing rotation that the capacity-prefix commit validates)
        jnp.where(alloc > 0, (alloc - used) / jnp.maximum(alloc, 1e-9), 0.0),
        axis=1,
    )                                                        # [N]
    cap_order = jnp.argsort(-free_frac).astype(jnp.int32)    # [N]
    sel_sorted = sel[:, cap_order]                           # [P, N]
    csum = jnp.cumsum(sel_sorted.astype(jnp.float32), axis=1)
    n_feas = csum[:, -1]
    targets = jnp.mod(
        m_p.astype(jnp.float32)[:, None]
        + jnp.arange(K + 1, dtype=jnp.float32)[None, :],
        jnp.maximum(n_feas, 1.0)[:, None],
    ) + 1.0                                                  # [P, K+1]
    # searchsorted(csum[p], t, left) == count of entries < t; K+1 small
    # compare+reduce passes instead of P serial row searches.
    j_node = jnp.stack(
        [
            jnp.sum(
                (csum < targets[:, k][:, None]).astype(jnp.int32), axis=1
            )
            for k in range(K + 1)
        ],
        axis=1,
    )
    cand = cap_order[jnp.clip(j_node, 0, cap_order.shape[0] - 1)]
    ok = member & (n_feas > 0)
    sel_at = jnp.take_along_axis(sel, cand, axis=1)
    val = jnp.where(
        sel_at, jnp.take_along_axis(score, cand, axis=1), NEG_INF
    )
    return cand, val, ok


def _segment_cumsum(x, boundary, width: "int | None" = None):
    """Inclusive prefix sum of x [L, R] along axis 0, restarting at
    every row where boundary [L] is True. Every partial sum stays inside
    one segment (one node's demand), so integer-valued requests stay
    exact in f32; a global cumsum minus a segment offset does not — at
    10k pods its cpu prefix passes 2^24 and the differences round,
    which let the fast commit over-fill a node by a millicore. Rows are
    zero-padded to `width` first: the scan's tree depends only on its
    length, so a view padded to the full width sums exactly like the
    full-width call."""
    L = x.shape[0]
    if width is not None and width > L:
        x = jnp.concatenate([x, jnp.zeros((width - L, x.shape[1]), x.dtype)])
        boundary = jnp.concatenate([boundary, jnp.zeros(width - L, bool)])

    def comb(a, b):
        (av, af), (bv, bf) = a, b
        return jnp.where(bf[..., None], bv, av + bv), af | bf

    out, _ = jax.lax.associative_scan(comb, (x, boundary))  # tpl: disable=TPL202(segmented prefix-SUM: the tree depends only on the length, which every caller fixes — full [P], or padded to `width` — so the result is width-invariant; partial sums never cross a segment)
    return out[:L]


def _node_add(used, node, mask, requests, rank, width: int, sign=1.0):
    """used.at[node[p]].add(sign * requests[p]) for masked rows, as ONE
    unique-index add per node: rows sort by (node, rank), per-node
    request totals come off a segmented prefix sum PADDED to `width`
    rows, and only each segment's last row scatters. Replaces the
    order-unspecified duplicate f32 scatter-add, which made `used`
    depend on the pod-axis layout: the frontier-compaction contract
    (compacted [F, N] rounds bitwise == full-width [P, N] rounds) needs
    every f32 reduction over the pod axis to be width-invariant, and a
    width-padded front-packed cumsum + disjoint single adds is exactly
    that (masked rows sort to the front in the same (node, rank) order
    at any width; the tail is zeros)."""
    P = node.shape[0]
    N = used.shape[0]
    node_m = jnp.where(mask, jnp.clip(node, 0, N - 1), N)
    perm = jnp.lexsort((rank, node_m))
    node_s = node_m[perm]
    mask_s = mask[perm]
    req_s = jnp.where(mask_s[:, None], requests[perm], 0.0)
    boundary = jnp.concatenate(
        [jnp.ones(1, bool), node_s[1:] != node_s[:-1]]
    )
    total = _segment_cumsum(req_s, boundary, width)          # incl. own row
    is_last = jnp.concatenate([node_s[1:] != node_s[:-1], jnp.ones(1, bool)])
    is_last &= mask_s
    # Non-last rows add exact 0.0 at node 0 (a no-op); last rows hit
    # DISTINCT nodes, so the unspecified duplicate-add order never sees
    # two real contributions.
    return used.at[jnp.where(is_last, node_s, 0)].add(
        jnp.where(is_last[:, None], sign * total, 0.0)
    )


def _deal_commit(allocatable, requests, used, feasible, masked, allowed,
                 rank, K: int, dealt_override=None,
                 dealt_override_val=None, dealt_override_ok=None,
                 score_full=None, tie_pick=None, *, cum_width: int):
    """One round's dealing + capacity-prefix conflict resolution +
    rescue, shape-generic over the pod axis (used on the full [P, N]
    matrices and on compacted views — same math per pod). Returns
    (used2, choice, chosen_val); choice[p] = committed node or -1.

    cum_width (>= the full pod width; rank values index it): every f32
    reduction over the pod axis is made WIDTH- and SHARDING-INVARIANT,
    so a compacted [F, N] call is bitwise-identical to the full-width
    [P, N] call it stands in for, and a pod-sharded call to a
    single-device one: node desirability sums go through int32
    fixed-point (integer adds are associativity-exact; f32 column sums
    change with the reduction tree when the row count or the sharding
    changes), demand and per-node capacity prefixes run over arrays
    padded/scattered to `cum_width` rows (identical layouts at any view
    width — real rows front-packed or rank-scattered, zeros elsewhere),
    and `used` updates apply as unique-per-node segment totals
    (_node_add) instead of order-unspecified duplicate scatter-adds.

    Load-balancing scores give every pod nearly the SAME global node
    ranking, so per-pod argmax/top-K concentrates all commits on the
    few best nodes and serializes rounds. Deal pods into the ranked
    node list by cumulative request mass instead: the q-th pending pod
    (by priority) targets the node where the cumulative remaining
    capacity first covers the cumulative demand of pods 0..q, for
    every resource. Pods whose dealt node is infeasible for them fall
    back to their own top-K; the capacity-prefix commit corrects any
    estimate error, and misses retry next round.

    tie_pick: optional [P] seeded argmax per pod (pick_node_batch) —
    the upstream rand-among-max analogue for fast mode (C5). When
    given, it replaces the lowest-index maximum as each pod's OWN top
    choice (the first top-K candidate and the rescue pick); the
    lowest-index maximum stays in the list as a later fallback, so
    under capacity pressure behavior is unchanged and on uncontended
    rows the committed node is exactly the oracle's seeded pick."""
    P = requests.shape[0]
    N = allocatable.shape[0]
    BIG = jnp.int32(2**31 - 1)
    allowed_col = allowed[:, None]
    n_allowed = jnp.maximum(allowed.sum(), 1)
    # Fixed-point desirability (see docstring): 1/16 granularity is
    # ample for a dealing-order heuristic, and clipping bounds the
    # int32 column sums at P * (2^15 - 1) (exact for P <= 64k; the
    # old +-2^15 bound could reach exactly 2^31 and wrap — TPL204.
    # The clip never binds in practice: scores are O(400), so
    # |round(contrib*16)| tops out around 6400).
    contrib = jnp.where(feasible & allowed_col, masked, 0.0)
    iq = jnp.clip(
        jnp.round(contrib * 16.0), -32767.0, 32767.0
    ).astype(jnp.int32)
    desir = jnp.sum(iq, axis=0).astype(jnp.float32) / (
        16.0 * n_allowed.astype(jnp.float32)
    )
    desir = jnp.where(
        jnp.any(feasible & allowed_col, axis=0), desir, NEG_INF
    )
    node_order = jnp.argsort(-desir)                         # [N]
    remaining = jnp.maximum(allocatable - used, 0.0)         # [N, R]
    remaining = jnp.where(
        jnp.isfinite(desir)[:, None], remaining, 0.0
    )
    # Inclusive cumulative demand of allowed pods in rank order,
    # WITHOUT sorting (a [P] argsort per round costs more than the
    # scatter): the view's demand lands at GLOBAL rank positions of a
    # [cum_width, R] array — byte-identical to the full-width
    # rank-major scatter (absent pods demand 0) — so the f32 prefix
    # sums agree bitwise at any view width.
    dem = jnp.where(allowed[:, None], requests, 0.0)
    rm = jnp.zeros((cum_width, dem.shape[1]), dem.dtype).at[rank].set(dem)
    my_dem = jnp.cumsum(rm, axis=0)[rank]                    # [P, R]
    cum_rem = jnp.cumsum(remaining[node_order], axis=0)      # [N, R]  # tpl: disable=TPL201(node-axis capacity prefix at fixed [N] — the node axis is never view-compacted; dealing estimate only, corrected by the capacity-prefix commit and re-tried next round on a miss)
    pos = jnp.zeros(P, jnp.int32)
    for ri in range(cum_rem.shape[1]):
        pos = jnp.maximum(
            pos,
            jnp.searchsorted(
                cum_rem[:, ri], my_dem[:, ri], side="left"
            ).astype(jnp.int32),
        )
    dealt = node_order[jnp.clip(pos, 0, N - 1)].astype(jnp.int32)
    dealt_ok = jnp.take_along_axis(
        feasible, dealt[:, None], axis=1
    )[:, 0]
    # Candidate list: dealt node first (when feasible), then the pod's
    # own top-K by score; K capacity sub-iterations.
    topv, topi = jax.lax.top_k(masked, K)                    # [P, K]
    if tie_pick is not None:
        # The pod's own top choice becomes the seeded pick (same max
        # score by construction; equal to topi[:, 0] when untied).
        tp_val = jnp.take_along_axis(masked, tie_pick[:, None], axis=1)
        topi = topi.at[:, 0].set(tie_pick)
        topv = topv.at[:, 0].set(tp_val[:, 0])
    dealt_score = jnp.take_along_axis(masked, dealt[:, None], axis=1)
    use_dealt = dealt_ok
    if tie_pick is not None:
        # Seeded semantics: when the dealt node merely ties the pod's
        # max score (the dealer's redirect is arbitrary among equals),
        # the hash pick leads — uniform hashes spread ties like the
        # dealer would. A strictly lower-scored dealt node keeps its
        # slot: that redirect is the capacity dealer doing real work.
        use_dealt = dealt_ok & (dealt_score[:, 0] < topv[:, 0])
    topi = jnp.concatenate(
        [jnp.where(use_dealt, dealt, topi[:, 0])[:, None], topi], axis=1
    )
    topv = jnp.concatenate(
        [jnp.where(use_dealt, dealt_score[:, 0], topv[:, 0])[:, None], topv],
        axis=1,
    )
    if dealt_override is not None:
        # Spread water-fill (see _spread_waterfill_deal): a constrained
        # pod's WHOLE candidate list becomes its in-domain rotation —
        # spills stay inside the assigned domain. Values come with the
        # candidates (relaxed placements are -inf in `masked`).
        okc = dealt_override_ok[:, None]
        topi = jnp.where(okc, dealt_override, topi)
        topv = jnp.where(okc, dealt_override_val, topv)

    KC = K + 1  # dealt candidate + top-K fallbacks

    def sub_cond(sub_state):
        used_j, choice_j, ptr = sub_state
        ptr_c = jnp.clip(ptr, 0, KC - 1)
        cand_ok = jnp.take_along_axis(topv, ptr_c[:, None], axis=1)[:, 0] > NEG_INF
        return jnp.any(allowed & (choice_j < 0) & (ptr < KC) & cand_ok)

    def sub(sub_state):
        used_j, choice_j, ptr = sub_state
        ptr_c = jnp.clip(ptr, 0, KC - 1)
        cand = jnp.take_along_axis(topi, ptr_c[:, None], axis=1)[:, 0]
        cand_ok = jnp.take_along_axis(topv, ptr_c[:, None], axis=1)[:, 0] > NEG_INF
        active = allowed & (choice_j < 0) & (ptr < KC) & cand_ok
        # Capacity-prefix conflict resolution per node, in priority
        # order: sort by (candidate node, rank); within each node's
        # segment commit the longest prefix whose cumulative requests
        # fit the node's remaining capacity.
        cand_m = jnp.where(active, cand, N)  # inactive -> sentinel seg
        perm = jnp.lexsort((rank, cand_m))
        cand_s = cand_m[perm]
        act_s = active[perm]
        req_s = jnp.where(act_s[:, None], requests[perm], 0.0)
        idx = jnp.arange(P, dtype=jnp.int32)
        boundary = jnp.concatenate(
            [jnp.ones(1, bool), cand_s[1:] != cand_s[:-1]]
        )
        seg_start = jax.lax.cummax(jnp.where(boundary, idx, 0))
        # Active rows front-pack identically at any width (inactive
        # rows sort to the sentinel tail with zero demand), so the
        # zero-padded scan is bitwise width-invariant.
        within = _segment_cumsum(req_s, boundary, cum_width)  # incl. own
        cap_node = jnp.clip(cand_s, 0, N - 1)
        fits = jnp.all(
            used_j[cap_node] + within <= allocatable[cap_node],
            axis=-1,
        ) & act_s
        bad = act_s & ~fits
        last_bad = jax.lax.cummax(jnp.where(bad, idx, -1))
        prefix_ok = last_bad < seg_start
        commit_s = fits & prefix_ok
        commit_j = jnp.zeros(P, bool).at[perm].set(commit_s)
        nofit = jnp.zeros(P, bool).at[perm].set(bad)
        used_j = _node_add(used_j, cand, commit_j, requests, rank,
                           cum_width)
        choice_j = jnp.where(commit_j, cand, choice_j)
        # Only pods whose own node is full advance their pointer;
        # prefix-blocked pods retry the same node next sub-step.
        # Progress: every sub-step either commits or advances a
        # pointer, and pointers are bounded by KC, so the while
        # terminates; it usually exits after 2-3 steps.
        ptr = jnp.where(
            nofit, ptr + 1, jnp.where(commit_j, KC, ptr)
        )
        return used_j, choice_j, ptr

    used2, choice, _ = jax.lax.while_loop(
        sub_cond, sub,
        (used, jnp.full(P, -1, jnp.int32), jnp.zeros(P, jnp.int32)),
    )
    commit = choice >= 0
    # Rescue: if the dealing pass committed NOTHING while some allowed
    # pod still has a feasible node (its dealt + top-K candidates were
    # all prefix-blocked, but a node further down its row has room),
    # commit the first such pod (by rank) at its best feasible node.
    # Feasibility was computed against round-start state and no other
    # commit landed this round, so the placement is valid; this
    # guarantees every round places at least one pod until nothing
    # pending is placeable — the same drain point as the sequential
    # semantics.
    want = jnp.any(feasible, axis=1)
    can_rescue = ~jnp.any(commit) & jnp.any(allowed & want)
    rk = jnp.where(allowed & want, rank, BIG)
    p_star = jnp.argmin(rk)
    n_star = (
        tie_pick[p_star] if tie_pick is not None
        else jnp.argmax(masked[p_star]).astype(jnp.int32)
    )
    used2 = used2.at[n_star].add(
        jnp.where(can_rescue, requests[p_star], 0.0)
    )
    choice = choice.at[p_star].set(
        jnp.where(can_rescue, n_star, choice[p_star])
    )
    # Relaxed (water-fill) placements are -inf in `masked`; their real
    # score lives in score_full when the caller provides it.
    chosen_val = jnp.take_along_axis(
        masked if score_full is None else score_full,
        jnp.clip(choice, 0, N - 1)[:, None], axis=1
    )[:, 0]
    return used2, choice, chosen_val


def _top_by_rank(pend, order, C: int):
    """Indices of the C lowest-rank True pods of `pend`, in ascending
    rank order, plus the number of True pods — SORTLESS (order is the
    precomputed pop order, i.e. pods by ascending rank). Replaces the
    per-round argsort(where(pend, rank, BIG))[:C] selections, each of
    which paid ~4.5 ms on this TPU's sort path. Requires C <= P: every
    slot gets a DISTINCT pod (callers scatter through the result, and
    duplicate tail indices would race); with C > P distinct fillers
    cannot exist."""
    assert C <= order.shape[0], (C, order.shape)
    pend_rm = pend[order]                                    # rank-major
    cpend = jnp.cumsum(pend_rm.astype(jnp.int32))
    cnon = jnp.cumsum((~pend_rm).astype(jnp.int32))
    n_pend = cpend[-1]
    # Every pod gets a DISTINCT slot (pending pods 0..n_pend-1 by rank,
    # then non-pending by rank): tail slots must not repeat a pod —
    # callers scatter through sel, and duplicate indices make the
    # unkept-slot writes race the kept one.
    slot = jnp.where(pend_rm, cpend - 1, n_pend + cnon - 1)
    take = slot < C
    buf = jnp.zeros(C + 1, order.dtype).at[
        jnp.where(take, slot, C)
    ].set(order)
    return buf[:C], n_pend


def _fallback_depth(N: int) -> int:
    """Per-pod fallback-candidate depth for dealing commits: deeper
    lists on SMALL clusters close most of the fast-mode placement gap
    (mixed-preset placed_delta -20 -> -9 of 480 at K=16; stranded
    large pods' top-8 fill up same-round and 8 of 16 nodes left no
    alternates), while on big clusters a deeper [P, N] top_k costs
    more than it recovers (pairwise fast +150 ms at 10k x 5k)."""
    return min(16, N) if N <= 256 else 8


# Residual compaction width: after the first full round, the few
# still-pending pods are gathered into this many slots and later rounds
# run on the [C, N] view instead of [P, N] (~45 ms -> ~2 ms per round at
# 10k x 5k; headline fast p50 295 -> 185 ms). Semantically equivalent:
# with no pairwise signatures a round's outcome depends only on
# (pending set, node used), both preserved by the view including
# relative rank order. NOT bitwise with full-width rounds: the shared
# node-desirability mean in _deal_commit is taken over the view's
# pods, not every pending pod, so near-tied node rankings can flip
# (all audit-valid).
_RESIDUAL_CAP = 1024

# Bid width and round cap of the fast-mode batched preemption auction
# (_preempt_rounds): per round, the top _PREEMPT_BATCH unplaced pods
# bid in parallel; upstream preempts ONE pod per scheduling cycle, so
# even one round x 512 bids is far past parity behavior. 1024 (round
# 6, was 512): plain-feasible bidders share the same slots, and at
# 90% utilization they crowd out preemptors mid-drain — round-5 traces
# show rounds where ~250 of the 512 slots went to plain bidders,
# halving eviction keeps to ~230-260 (the keeps-per-round collapse in
# VERDICT round 5). The wider batch keeps eviction throughput at
# ~full-width even with plain crowding, roughly halving drain rounds;
# it became affordable when preempt_auction dropped its exact
# [C, N, V] tableau for [N, V] candidate tables + [C, V] claimed-node
# validation (per-round cost now scales with C only through [C, N]
# ranking and [C, V] validation).
_PREEMPT_BATCH = 1024
# Width of the per-round plain drain in _preempt_rounds.
_PREEMPT_DRAIN = 1024
# Round cap; the env override exists for per-round cost profiling
# (slope of solve time vs cap) and emergency latency capping.
_PREEMPT_MAX_ROUNDS = int(
    _os_mod.environ.get("TPUSCHED_PREEMPT_MAX_ROUNDS", 128)
)
# Per-node victim cap of the node-major fast-auction tableau
# (kpreempt.PreemptCtxNV): victims are slotted per node in ascending
# cost order and a fast-mode preemptor can evict at most this many on
# one node. Prefixes needing more fall back to other nodes or stay
# pending (the parity path has no cap). 16 covers every BASELINE
# workload (config 5 runs 8 victims/node).
_PREEMPT_VICTIM_CAP = 16

# Per-round auction provenance columns (decision provenance, round 12):
# with explain=True the preemption loop accumulates one row per auction
# round into a [_PREEMPT_MAX_ROUNDS, len(...)] f32 table. Column order
# is the layout contract with tpusched/explain.py — append only.
EXPLAIN_AUCTION_STATS = (
    "considered",      # pods examined this round
    "plain_feasible",  # of those, feasible without any eviction
    "bids",            # entered the victim auction
    "claimed",         # auction claims surviving exact validation
    "kept_evict",      # eviction bids kept past the PDB budget gate
    "kept_plain",      # plain placements kept (claim scan + capacity)
    "drained",         # plain-drain placements (S == 0 pre-pass)
    "evictions",       # victims newly evicted this round
    "pdb_spent",       # PDB budget consumed by kept eviction bids
    "no_bid",          # pods retired spent (no placement or prefix)
)


def _spread_excess_mask(snap: ClusterSnapshot, aff_ok, rank,
                        choice, kept_v, st_v):
    """[P] bool: kept members to revert so every kept DNS-spread
    constraint holds against st_v's (end-of-round) counts. Per (sig,
    domain) group of revert-eligible members, the highest-priority
    prefix whose size respects every kept member's skew bound survives;
    the excess reverts. Shared by solve_rounds' commit-validation
    fixpoint, _preempt_rounds' round validation (round 6), and the
    incremental warm path's carried-placement revalidation + in-kernel
    audit (ISSUE 12). Shape-generic over the pod axis: pass a view
    snapshot (gathered pods rows) plus the matching aff_ok/rank/choice
    rows and the verdict is row-for-row what the full-width call gives
    (all cross-pod reductions here are integer-exact)."""
    pods, nodes = snap.pods, snap.nodes
    P = pods.valid.shape[0]
    N = nodes.valid.shape[0]
    dom_s_v = kpair.sig_domains(snap)                        # [S, N]
    S_sigs = dom_s_v.shape[0]
    dns_any = pods.ts_valid & (pods.ts_when == DO_NOT_SCHEDULE)  # [P, C]
    counts_v = st_v.counts                                   # [S, N]
    node_cnt = jnp.take_along_axis(
        counts_v, jnp.clip(dom_s_v, 0, None), axis=1
    )                                                        # [S, N]
    node_cnt = jnp.where(dom_s_v >= 0, node_cnt, jnp.inf)
    bad = jnp.zeros(P, bool)
    idx = jnp.arange(P, dtype=jnp.int32)
    for c in range(pods.ts_key.shape[1]):
        s_c = jnp.clip(pods.ts_sig[:, c], 0, None)           # [P]
        d_c = dom_s_v[s_c, jnp.clip(choice, 0, N - 1)]
        member = (
            kept_v & dns_any[:, c] & (choice >= 0) & (d_c >= 0)
        )
        # Per-pod allowance T = min over eligible domains of the
        # END-state count, plus the pod's own maxSkew.
        nc_p = node_cnt[s_c]                                 # [P, N]
        eligible = nodes.valid[None, :] & aff_ok & (
            dom_s_v[s_c] >= 0
        )
        min_end = jnp.min(
            jnp.where(eligible, nc_p, jnp.inf), axis=1
        )
        min_end = jnp.where(jnp.isfinite(min_end), min_end, 0.0)
        T = min_end + pods.ts_max_skew[:, c]                 # [P]
        cnt_total = counts_v[s_c, jnp.clip(d_c, 0, None)]
        # Rank-ordered position within each (sig, domain) group
        # of revert-eligible members, and the group's size.
        gid = jnp.where(
            member, s_c * N + jnp.clip(d_c, 0, None), S_sigs * N
        )
        g_tab = jnp.zeros(S_sigs * N + 1, jnp.float32).at[gid].add(
            member.astype(jnp.float32)
        )
        g_elig = g_tab[gid]                                  # [P]
        b_fixed = cnt_total - g_elig  # non-revertable contribution
        perm2 = jnp.lexsort((rank, gid))
        gid_s = gid[perm2]
        mem_s = member[perm2]
        boundary = jnp.concatenate(
            [jnp.ones(1, bool), gid_s[1:] != gid_s[:-1]]
        )
        q_cum = jnp.cumsum(mem_s.astype(jnp.float32))
        seg_start2 = jax.lax.cummax(jnp.where(boundary, idx, 0))
        q_off = jnp.where(
            seg_start2 > 0,
            q_cum[jnp.clip(seg_start2 - 1, 0, None)], 0.0,
        )
        q_incl = q_cum - q_off                               # 1-based position
        # Segmented prefix-min of T in rank order: the k-member
        # prefix is admissible iff b + k <= min over its
        # members' allowances.
        T_s = jnp.where(mem_s, T[perm2], jnp.inf)

        def comb(a, bpair):
            av, ab = a
            bv, bb = bpair
            return (jnp.where(bb, bv, jnp.minimum(av, bv)), ab | bb)

        pm_s, _ = jax.lax.associative_scan(comb, (T_s, boundary))  # tpl: disable=TPL202(segmented prefix-MIN: comb combines by jnp.minimum, order-free-exact in any tree; operand is inf-masked — the analyzer sees only an opaque f32 scan)
        survive_s = mem_s & (b_fixed[perm2] + q_incl <= pm_s)
        bad_c = jnp.zeros(P, bool).at[perm2].set(mem_s & ~survive_s)
        bad |= bad_c
    return bad


def _compact_cap(cfg: EngineConfig, P: int) -> int:
    """Resolved signature-path frontier-compaction cap (ISSUE 12):
    0 = compaction off (the full-width reference the bitwise twin tests
    compare against), cfg.compact_cap -1 = auto (_RESIDUAL_CAP), else
    the explicit cap. Disabled when P is not meaningfully larger than
    the cap (the gathers would not pay for themselves) — except for an
    EXPLICIT positive cap, which tests use to exercise the compacted
    program on small clusters."""
    cap = _RESIDUAL_CAP if cfg.compact_cap < 0 else cfg.compact_cap
    if cap <= 0:
        return 0
    if cfg.compact_cap < 0 and P <= cap:
        return 0
    return min(cap, P)


def _pods_view(snap: ClusterSnapshot, static: StaticCtx, sel):
    """Compacted pod-axis view (the frontier gather): pod rows, static
    rows, and the sig_match MEMBER columns of the selected pods, as a
    (view snapshot, view StaticCtx) pair every shape-generic kernel in
    this module accepts in place of the full-width pair. Running
    members, nodes, sigs, and all [S, N]/[N, R] state stay full — the
    compaction only narrows the pod axis."""
    M = snap.running.valid.shape[0]
    pods_v = jax.tree.map(lambda a: a[sel], snap.pods)
    snap_v = snap.replace(pods=pods_v)
    sig_v = jnp.concatenate(
        [static.sig_match[:, :M], static.sig_match[:, M + sel]], axis=1
    )
    static_v = StaticCtx(
        mask=static.mask[sel], aff_ok=static.aff_ok[sel],
        score=static.score[sel], sig_match=sig_v,
        w_lr=static.w_lr[sel], w_ba=static.w_ba[sel],
        w_ts=static.w_ts[sel], w_ia=static.w_ia[sel], rw=static.rw,
    )
    return snap_v, static_v


def _preempt_rounds(cfg: EngineConfig, snap: ClusterSnapshot,
                    static: StaticCtx, rank, order, base_rounds,
                    used, assigned, st, evicted, round_of, chosen,
                    has_pair=None, explain: bool = False):
    """Fast-mode PostFilter as BATCHED AUCTION ROUNDS (round-4; replaces
    a sequential per-pod scan that cost ~3 ms per preemptor — 9.6 s for
    2.7k preemptors at 10k x 5k). Each round:

      1. The top _PREEMPT_BATCH still-unplaced pods (dynamic-priority
         order) are evaluated IN PARALLEL against round-start state:
         plain feasibility first (an earlier round's evictions may have
         left room), else the batched victim-prefix auction
         (kpreempt.preempt_auction): bidders rank nodes off
         bidder-INDEPENDENT [N, V] prefix tables (priority-quantile
         buckets of the active bidders; round 6 — the exact [C, N, V]
         tableau was the per-round cost floor), parallel claim
         iterations deal bidders distinct cheap STILL-UNCLAIMED
         nodes — one claimant per node, so same-round victim sets never
         overlap (a bidder unclaimed after the fixed iteration count
         defers to the next round, a retry the old rank-ordered scan
         never needed) — and each claimed node gets an EXACT [C, V]
         victim-prefix validation. Plain bidders WITHOUT pairwise involvement (has_pair
         False) bypass the one-claim-per-node scan entirely: the load-
         balancing scores herd their argmaxes onto the same few nodes,
         which capped keeps at ~one per node per round (a 25-round
         drain tail for ~200 pods, measured round 5); a capacity-
         prefix commit per node (the same rule as _deal_commit's sub-
         step) admits every same-node bidder that fits, on nodes no
         eviction bid claimed this round. Pairwise-involved plain
         bidders stay on the claim scan — node exclusivity bounds
         their same-round interactions.
      2. A rank-ordered claimed-cumulative budget gate (O(1)-depth
         cumsums over [C, GP]) enforces PodDisruptionBudgets as a
         priority prefix over the claimants; a bid whose conservative
         budget accounting overdraws is deferred and re-bids next
         round against exact consumption.
      3. Kept bids apply as BATCHED scatters (evictions, capacity,
         pair state); deferred pods re-bid against the updated state.

    Victim sets of same-round keeps cannot overlap (victims are node-
    local and each node keeps one bid) and every kept bid was feasible
    against its round-start state, so validity matches the sequential
    pass; under contention the ORDER of preemptors can differ — the
    standard fast-mode divergence contract. Terminates when a round
    keeps nothing or the cap hits (leftovers stay unplaced)."""
    pods, nodes = snap.pods, snap.nodes
    P = pods.valid.shape[0]
    N = nodes.valid.shape[0]
    BIG = jnp.int32(2**31 - 1)
    C = min(P, _PREEMPT_BATCH)
    pctx = kpreempt.precompute_nv(cfg, snap, _PREEMPT_VICTIM_CAP)
    prio = effective_priority(
        cfg, pods.base_priority, pods.slo_target, pods.observed_avail
    )
    GP = snap.pdb_allowed.shape[0]
    run_pdb = snap.running.pdb_group
    run_valid = snap.running.valid
    M_run = run_valid.shape[0]
    S = snap.sigs.key.shape[0]
    if has_pair is None:
        has_pair = jnp.zeros(P, bool)

    def cond(carry):
        # Explicit indices: with explain=True the provenance tuple rides
        # at the END of the carry, so -2/-1 would land on it.
        return carry[7] & (carry[8] < _PREEMPT_MAX_ROUNDS)

    def body(carry):
        if explain:
            (used, assigned, st, evicted, round_of, chosen, tried, _, r,
             exp) = carry
            evictor, evict_rd, astats = exp
        else:
            used, assigned, st, evicted, round_of, chosen, tried, _, r = \
                carry
        drained = jnp.array(False)
        drained_n = jnp.float32(0.0)
        if S == 0:
            # Plain drain (round 5): one dealing round over the top
            # _RESIDUAL_CAP pending pods absorbs everything that FITS
            # current capacity (~2 ms) BEFORE the auction, so the C
            # auction slots carry true preemptors — previously
            # plain-feasible pods crowded the slots and eviction
            # throughput collapsed mid-drain. S == 0 only: the dealing
            # view has no pairwise state (exactly the no-sig main-round
            # body); with signatures present the mixed slot path below
            # handles plain bidders under node exclusivity.
            alloc = nodes.allocatable
            pend0 = (assigned < 0) & pods.valid
            dsel, _ = _top_by_rank(
                pend0, order, min(_PREEMPT_DRAIN, P)
            )
            dreal = pend0[dsel]
            feas_d, score_d = _cycle_nosig(
                alloc, used, pods.requests[dsel], static.mask[dsel],
                static.score[dsel], static.w_lr[dsel], static.w_ba[dsel],
                static.w_ts[dsel], static.rw,
            )
            feas_d &= dreal[:, None]
            masked_d = jnp.where(feas_d, score_d, NEG_INF)
            used, choice_d, chosen_d = _deal_commit(
                alloc, pods.requests[dsel], used, feas_d, masked_d,
                jnp.any(feas_d, axis=1), rank[dsel], _fallback_depth(N),
                tie_pick=pick_node_batch(cfg, masked_d, dsel),
                cum_width=P,
            )
            hit_d = choice_d >= 0
            assigned = assigned.at[dsel].set(
                jnp.where(hit_d, choice_d, assigned[dsel])
            )
            chosen = chosen.at[dsel].set(
                jnp.where(hit_d, chosen_d, chosen[dsel])
            )
            # Shared per-round key, like the auction keeps below (the
            # drain is S == 0-only, so only capacity semantics ride on
            # it — validated jointly by _deal_commit's prefix rule).
            round_of = round_of.at[dsel].set(
                jnp.where(hit_d, base_rounds + r, round_of[dsel])
            )
            drained = jnp.any(hit_d)
            if explain:
                drained_n = jnp.sum(hit_d.astype(jnp.float32))
        # Like the sequential pass, each pod gets ONE bid (tried); a bid
        # deferred by the conflict scan is NOT tried — it re-bids
        # against the updated state next round.
        pend = (assigned < 0) & pods.valid & ~tried
        sel, _ = _top_by_rank(pend, order, C)
        real = pend[sel]

        def eval_plain(p):
            feasible, score, allowed = pod_cycle(
                cfg, snap, static, p, used, st
            )
            masked = jnp.where(feasible, score, NEG_INF)
            n_plain = pick_node(cfg, masked, p).astype(jnp.int32)
            return (n_plain, jnp.any(feasible), masked[n_plain], allowed,
                    feasible, masked)

        (n_plain, can_plain, sc_plain, allowed_rows, feas_pl,
         masked_pl) = jax.vmap(eval_plain)(sel)
        can_plain &= real
        # Pairwise-involved plain bidders go through the auction's
        # claim scan (node exclusivity bounds their same-round
        # interactions); free plain bidders take the capacity-prefix
        # commit below instead.
        plain_excl = can_plain & has_pair[sel]
        plain_cap = can_plain & ~has_pair[sel]
        # Gangs never preempt (see solve_sequential); inactive bidders
        # enter the auction with all-False allowed rows.
        pre_active = real & ~can_plain & (pods.group[sel] < 0)
        allowed_rows &= pre_active[:, None]
        (target, claimed, takes_evict, vidx_t, freed_req, usage,
         could_bid) = kpreempt.preempt_auction(
            cfg, snap, pctx, prio[sel], pods.requests[sel],
            allowed_rows, used, evicted, plain_excl, n_plain,
            rank=rank[sel],
        )
        could_bid = could_bid | plain_cap
        if GP:
            consumed0 = jnp.zeros(GP, jnp.float32).at[
                jnp.clip(run_pdb, 0, None)
            ].add(
                (evicted & (run_pdb >= 0) & run_valid).astype(jnp.float32)
            )
            remaining0 = snap.pdb_allowed.astype(jnp.float32) - consumed0
            # Budget-respecting bids parallelize as a rank-ordered
            # prefix (sel IS ascending-rank order): keep while the
            # CLAIMED-cumulative consumption stays inside every touched
            # budget's remaining allowance. Counting claimed (not just
            # kept) bids in the cumulative is conservative — a bid the
            # exact sequential accounting would keep can be deferred —
            # and deferred bids re-bid next round against exact
            # consumption; safety is one-sided (kept subset of claimed,
            # so real consumption never exceeds the bound checked). A
            # bid that DECLARED a violation (its own usage alone
            # overdraws — upstream's evict-PDB-pods-as-last-resort)
            # keeps unconditionally: `remaining` only decreases, so a
            # bid violating against round-start budgets would violate
            # against ANY later sequential state too — the sequential
            # pass would evict it as last resort just the same, and
            # serializing these (the old rule admitted one per budget
            # per round via a no-earlier-toucher check) stretched the
            # drain by ~10 one-keep rounds at 6k x 3k (round-6 trace).
            # This replaces a C-step lax.scan with O(1)-depth cumsums
            # (the scan's sequential steps dominated the round wall).
            usage_cl = jnp.where(claimed[:, None], usage, 0.0)
            cum_usage = jnp.cumsum(usage_cl, axis=0)          # [C, GP]
            # Only budgets the bid itself touches gate it: an earlier
            # (kept or dropped) overdraw on budget g must not block
            # bids that never evict from g.
            fits_budget = jnp.all(
                jnp.where(
                    usage > 0.0,
                    cum_usage <= remaining0[None, :] + 1e-6, True,
                ),
                axis=1,
            )
            alone_viol = jnp.any(usage > remaining0[None, :] + 1e-6, axis=1)
            keep = claimed & (fits_budget | alone_viol)
        else:
            keep = claimed
        keep_evict = keep & takes_evict
        # vidx_t carries M at non-victim slots, so the scatter only
        # marks the kept bidders' actual prefixes.
        ev_round = jnp.zeros(M_run, bool).at[
            jnp.clip(vidx_t, 0, M_run - 1)
        ].max(keep_evict[:, None] & (vidx_t < M_run))
        evicted2 = evicted | ev_round
        tgt_c = jnp.clip(target, 0, N - 1)
        # Pairwise-free plain bidders commit through a full dealing
        # round on the compacted [C, N] view (see docstring): the same
        # _deal_commit the main rounds use — demand-aware dealing
        # across the node list, top-K fallback, capacity-prefix
        # resolution, rescue. Nodes an auction keep claimed this round
        # are excluded (their round-start capacity is stale after
        # evictions/placement), so the two commit families touch
        # disjoint nodes and their capacity deltas compose.
        taken = jnp.zeros(N, bool).at[tgt_c].max(keep)
        req_sel = pods.requests[sel]
        feas_c = feas_pl & plain_cap[:, None] & ~taken[None, :]
        masked_c = jnp.where(feas_c, masked_pl, NEG_INF)
        allowed_c = plain_cap & jnp.any(feas_c, axis=1)
        _, choice_pl, chosen_pl = _deal_commit(
            nodes.allocatable, req_sel, used, feas_c, masked_c,
            allowed_c, rank[sel], _fallback_depth(N),
            tie_pick=pick_node_batch(cfg, masked_c, sel),
            cum_width=P,
        )
        keep_pl = choice_pl >= 0
        keep_all = keep | keep_pl
        target_all = jnp.where(keep_pl, choice_pl, target)
        st2 = st
        if S:
            # Pairwise state stays EVICTION-FREE through the preemption
            # rounds (round 6; pair_state_evict is deliberately NOT
            # applied): a pod validated against an INTERMEDIATE
            # eviction state — some victims gone, later rounds' not
            # yet — can be legal there yet illegal under BOTH timings
            # the external audit accepts (validate_assignment checks
            # with ALL evictions applied and with none; pod counts are
            # key-filtered but the evicted mask is not). Counting
            # still-evicted members keeps every check equal to the
            # audit's no-eviction arm: spread and required-anti only
            # get stricter with more members, and a positive-affinity
            # match on an evicted member is precisely what that arm
            # accepts. The cost is bounded conservatism: a pairwise
            # slot freed only by this batch's evictions opens next
            # batch (the snapshot then has the victims gone), exactly
            # like upstream's nominate-then-requeue.
            # Same-round cross-commit validation (round 6): the claim
            # scan's NODE exclusivity does not bound pairwise
            # interactions — spread constraints are per-DOMAIN (many
            # nodes share a zone, so two same-sig keeps on different
            # nodes can jointly breach a skew bound), and this round's
            # evictions can remove the match another keep's required
            # affinity relied on. Re-check every keep against
            # end-of-round state exactly as solve_rounds' commit
            # validation does (same helpers), reverting violators to
            # PENDING — they re-bid next round against true counts.
            # Their victims stay evicted (the eviction was decided
            # against valid round-start state; upstream's
            # nominate-then-requeue can strand evictions the same way).
            #
            # Frontier compaction (ISSUE 12): every keep is in `sel`,
            # so with compaction on the whole fixpoint runs on the
            # [C]-wide view — pair_state_commit / ia_ok_at_choice /
            # _spread_excess_mask only ever touch exact (integer-
            # valued) reductions, so the view verdicts are bitwise the
            # full-width ones (the compact-off engine keeps the [P]
            # arrays as the twin-test reference).
            compact_pv = _compact_cap(cfg, P) > 0
            if compact_pv:
                snap_pv, static_pv = _pods_view(snap, static, sel)
                choice_pv = jnp.where(keep_all, target_all, -1)
                keep_pv = keep_all
                hp_pv = has_pair[sel]
                rank_pv = rank[sel]
            else:
                snap_pv, static_pv = snap, static
                choice_pv = jnp.full(P, -1, jnp.int32).at[sel].set(
                    jnp.where(keep_all, target_all, -1)
                )
                keep_pv = jnp.zeros(P, bool).at[sel].set(keep_all)
                hp_pv = has_pair
                rank_pv = rank
            st2 = kpair.pair_state_commit(
                snap_pv, st2, static_pv.sig_match, choice_pv, keep_pv
            )

            def pv_cond(vs):
                return vs[-1]

            def pv_body(vs):
                st_v, kept_v, _ = vs
                ia_ok = kpair.ia_ok_at_choice(
                    snap_pv, st_v, static_pv.sig_match, choice_pv,
                    jnp.where(kept_v, choice_pv, -1),
                )
                bad = kept_v & hp_pv & ~ia_ok
                bad = bad | (kept_v & _spread_excess_mask(
                    snap_pv, static_pv.aff_ok, rank_pv, choice_pv,
                    kept_v, st_v
                ))
                st_v = kpair.pair_state_commit(
                    snap_pv, st_v, static_pv.sig_match, choice_pv, bad,
                    sign=-1.0,
                )
                return st_v, kept_v & ~bad, jnp.any(bad)

            st2, kept_final, _ = jax.lax.while_loop(
                pv_cond, pv_body,
                (st2, keep_pv, jnp.any(keep_pv & hp_pv)),
            )
            keep_valid = kept_final if compact_pv else kept_final[sel]
            keep = keep & keep_valid
            keep_pl = keep_pl & keep_valid
            keep_all = keep | keep_pl
        used2 = used.at[tgt_c].add(  # tpl: disable=TPL203(one auction claimant per node: kept rows hit DISTINCT tgt_c, non-kept rows add exact 0.0 at a parked slot — duplicate order never sees two real contributions)
            jnp.where(keep_evict[:, None], -freed_req, 0.0)
        )
        used2 = used2.at[tgt_c].add(  # tpl: disable=TPL203(same claim-exclusivity argument as the eviction add above; keep is a subset of claimed, one per node)
            jnp.where(keep[:, None], req_sel, 0.0)
        )
        # Plain-capacity commits CAN share a node (the capacity-prefix
        # rule admits every same-node bidder that fits), so this add —
        # unlike the claim-exclusive ones above — had real duplicate
        # f32 scatter-adds (TPL203, the class PR 12's _node_add
        # replaced in the main rounds; this was the one commit path it
        # missed). Unique-per-node segment totals; bitwise parity with
        # the old duplicate add pinned by
        # tests/test_kernelflow.py::test_preempt_plain_commit_node_add_parity
        # and the existing preempt/fast suites.
        used2 = _node_add(used2, choice_pl, keep_pl, req_sel, rank[sel], C)
        assigned2 = assigned.at[sel].set(
            jnp.where(keep_all, target_all, assigned[sel])
        )
        # Preempted placements carry no score (upstream nominates
        # without rescoring), matching the sequential path.
        chosen2 = chosen.at[sel].set(
            jnp.where(keep_pl, chosen_pl,
                      jnp.where(keep & can_plain, sc_plain,
                                jnp.where(keep, NEG_INF, chosen[sel])))
        )
        # Commit keys: strictly after the main rounds, one SHARED key
        # per preemption round — same-round keeps did NOT see each
        # other's state (they were all checked against round-start
        # state and then jointly validated against end-of-round state
        # above), so rank-ordered intra-round keys would promise the
        # external audit a sequential consistency the engine never
        # enforced; a shared key makes validate_assignment judge each
        # keep against exactly the end-of-round set the engine
        # validated — the same contract solve_rounds' main rounds use.
        round_of2 = round_of.at[sel].set(
            jnp.where(keep_all, base_rounds + r, round_of[sel])
        )
        # A no-bid pod (nothing feasible, no victim prefix anywhere) is
        # spent; a kept pod is placed; a DEFERRED pod (could bid but
        # lost the node race or the budget prefix) bids again. If a
        # round keeps nothing, the first claimant would have kept, so
        # there were no claims: every real pod was a no-bid and gets
        # marked — progress is monotone and the loop terminates.
        if _DEBUG_ROUNDS:
            jax.debug.print(
                "preempt round {r}: real={re} plain={pl} pre={pr} "
                "claimed={a} keep={k} keep_pl={kp} evicts={e}",
                r=r, re=real.sum(), pl=(real & can_plain).sum(),
                pr=takes_evict.sum(), a=claimed.sum(), k=keep.sum(),
                kp=keep_pl.sum(), e=ev_round.sum(),
            )
        newly_tried = real & (keep_all | ~could_bid)
        tried2 = tried.at[sel].set(tried[sel] | newly_tried)
        # Any keep changes the state (evictions free capacity), so
        # earlier no-bid verdicts are stale: clear them and re-bid.
        # Termination: a keep-less round marks every real pod tried
        # (monotone), and rounds with keeps shrink the pending set.
        tried2 = jnp.where(
            jnp.any(keep_all) | drained, jnp.zeros_like(tried2), tried2
        )
        progress = jnp.any(keep_all) | jnp.any(newly_tried) | drained
        out_state = (used2, assigned2, st2, evicted2, round_of2, chosen2,
                     tried2, progress, r + 1)
        if explain:
            # Provenance accumulation (round 12) — traced ONLY under
            # explain=True, so the unexplained program is unchanged.
            # Victim attribution: keep_evict is pre-validation (a
            # reverted keep's victims stay evicted — see above), which
            # is exactly the evicted2 scatter's mask, so evictor /
            # evict_rd cover the evicted set bit-for-bit. Each victim
            # is evicted at most once, so .max over a -1 init is a
            # masked set.
            f32 = jnp.float32
            if M_run:
                vclip = jnp.clip(vidx_t, 0, M_run - 1)
                vmask = keep_evict[:, None] & (vidx_t < M_run)
                evictor = evictor.at[vclip].max(
                    jnp.where(vmask, sel[:, None], -1))
                evict_rd = evict_rd.at[vclip].max(
                    jnp.where(vmask, base_rounds + r, -1))
            if GP:
                pdb_spent = jnp.sum(
                    jnp.where(keep_evict[:, None], usage, 0.0))
            else:
                pdb_spent = f32(0.0)
            row = jnp.stack([
                jnp.sum(real.astype(f32)),
                jnp.sum((real & can_plain).astype(f32)),
                jnp.sum(pre_active.astype(f32)),
                jnp.sum(claimed.astype(f32)),
                jnp.sum(keep_evict.astype(f32)),
                jnp.sum((keep_all & ~takes_evict).astype(f32)),
                drained_n,
                jnp.sum(ev_round.astype(f32)),
                pdb_spent.astype(f32),
                jnp.sum((real & ~could_bid).astype(f32)),
            ])
            astats = astats.at[jnp.clip(r, 0, astats.shape[0] - 1)].set(row)
            out_state = out_state + ((evictor, evict_rd, astats),)
        return out_state

    init = (used, assigned, st, evicted, round_of, chosen,
            jnp.zeros(P, bool), jnp.array(True), jnp.int32(0))
    if explain:
        init = init + ((
            jnp.full(M_run, -1, jnp.int32),
            jnp.full(M_run, -1, jnp.int32),
            jnp.zeros((_PREEMPT_MAX_ROUNDS, len(EXPLAIN_AUCTION_STATS)),
                      jnp.float32),
        ),)
    out = jax.lax.while_loop(cond, body, init)
    base = out[:6] + (out[8],)
    return base + ((out[9],) if explain else ())


def _cycle_nosig(alloc, used, req, mask, sscore, w_lr, w_ba, w_ts, rw):
    """batched_cycle's no-signature body, shape-generic over the pod
    axis (op order identical to batched_cycle so full-width and
    compacted rounds score bitwise the same)."""
    feasible = mask & kfilter.resource_fit(alloc, used, req)
    score = (
        w_lr[:, None] * kscore.least_requested(alloc, used, req, rw)
        + w_ba[:, None] * kscore.balanced_allocation(alloc, used, req, rw)
        + sscore
        + w_ts[:, None] * 100.0
    )
    return feasible, score.astype(jnp.float32)


def _make_round_nosig(cfg, alloc, req, mask, sscore, valid, rank, pod_ids,
                      w_lr, w_ba, w_ts, rw, max_rounds, K, cum_width,
                      round_cap=None):
    """(cond, body) for the no-signature commit rounds over whatever
    pod-axis width the given arrays carry. pod_ids: original pod
    indices of the rows (seeded tie-break hashes by pod identity, so
    compacted views pick like full-width ones). cum_width: the full
    pod width (rank values index it; _deal_commit). round_cap: optional
    (start_r, n) — stop after n rounds from start_r even with commits
    left (tranche mode: stragglers carry into the next tranche instead
    of dribbling through 1-commit fixpoint rounds). State: (used,
    assigned, chosen, round_of, progress, r)."""

    def cond(st):
        ok = st[4] & (st[5] < max_rounds)
        if round_cap is not None:
            ok = ok & (st[5] < round_cap[0] + round_cap[1])
        return ok

    def body(st):
        used, asg, chosen, rnd, _, r = st
        pending = (asg == -1) & valid
        feasible, score = _cycle_nosig(
            alloc, used, req, mask, sscore, w_lr, w_ba, w_ts, rw
        )
        feasible &= pending[:, None]
        masked = jnp.where(feasible, score, NEG_INF)
        allowed = jnp.any(feasible, axis=1)
        used2, choice, chosen_val = _deal_commit(
            alloc, req, used, feasible, masked, allowed, rank, K,
            tie_pick=pick_node_batch(cfg, masked, pod_ids),
            cum_width=cum_width,
        )
        commit = choice >= 0
        asg2 = jnp.where(commit, choice, asg)
        chosen2 = jnp.where(commit, chosen_val, chosen)
        rnd2 = jnp.where(commit, r, rnd)
        all_done = jnp.all((asg2 >= 0) | ~valid)
        return (used2, asg2, chosen2, rnd2,
                jnp.any(commit) & ~all_done, r + 1)

    return cond, body


def _solve_rounds_nosig(cfg: EngineConfig, snap: ClusterSnapshot,
                        static: StaticCtx, rank, order, max_rounds: int,
                        K: int, init=None, skip_full: bool = False,
                        cap: "int | None" = None):
    """Fast-mode rounds when the snapshot has NO pairwise signatures
    (trace-time fact; the common resource/affinity-only serving case):
    tranches of the top-_RESIDUAL_CAP pending pods by rank run [C, N]
    views to fixpoint (see tranche_path below). Returns
    (used, assigned, chosen, round_of, rounds).

    init: optional seeded (used, assigned, chosen, round_of, progress,
    r) — the incremental warm path enters with carried placements
    already assigned and their capacity applied. skip_full=True also
    skips the full-width round 1 (with a small pending frontier it
    would cost [P, N] to place a handful of pods; the tranche loop is
    strictly cheaper there). cap: explicit tranche width — the
    incremental path passes its pow2 FRONTIER bucket so the [C, N]
    view tracks the frontier, not the residual cap (at 2000 pods the
    default small-P guard would otherwise run full-width rounds and
    hand back the very cost the mode exists to shed)."""
    pods, nodes = snap.pods, snap.nodes
    P = pods.valid.shape[0]
    C = _RESIDUAL_CAP if cap is None else max(1, min(cap, P))
    BIG = jnp.int32(2**31 - 1)
    cond_f, body_f = _make_round_nosig(
        cfg, nodes.allocatable, pods.requests, static.mask, static.score,
        pods.valid, rank, jnp.arange(P, dtype=jnp.int32),
        static.w_lr, static.w_ba, static.w_ts,
        static.rw, max_rounds, K, P,
    )
    if init is None:
        init = (
            nodes.used, jnp.full(P, -1, jnp.int32),
            jnp.full(P, NEG_INF, jnp.float32), jnp.full(P, -1, jnp.int32),
            jnp.array(True), jnp.int32(0),
        )
    if P <= (2 * C if cap is None else C):
        # Too small for compaction to pay for its gathers.
        st = jax.lax.while_loop(cond_f, body_f, init)
        used, assigned, chosen, round_of, _, rounds = st
        return used, assigned, chosen, round_of, rounds

    # Full-width round 1: one deal over all P places the uncontended
    # bulk more cheaply than ~P/C tranches' fixed costs (headline fast
    # regressed ~45 ms device without it). SKIPPED when preemption is
    # enabled — that config exists because the cluster is near
    # capacity, round 1 then places little and costs ~50 ms, and the
    # tranche loop handles a large pending set strictly cheaper.
    state1 = init if (cfg.preemption or skip_full) else body_f(init)

    # TRANCHE processing (round 5; replaces the full-width rounds whose
    # 13 x ~45 ms sweeps dominated the preemption-config solve):
    # capacity only SHRINKS in the no-signature loop, so a pod
    # infeasible against current `used` is infeasible forever — a
    # compacted view run to fixpoint therefore resolves every one of
    # its pods as placed or permanently SPENT (the rescue guarantees
    # fixpoint means no view pod has any feasible node left). Outer
    # loop: take the top-C still-unspent pending pods by rank, run the
    # [C, N] view to fixpoint, mark, repeat — pending strictly shrinks
    # by C per tranche, so ~P/C cheap tranches replace O(rounds) full
    # [P, N] sweeps. Placement parity with the old full path holds
    # because spent pods could never have committed later anyway;
    # rank-ordered tranches track the sequential semantics at least as
    # closely.
    # A positive cfg.max_rounds caps the PER-TRANCHE inner rounds here
    # (each selected pod's view gets up to that many rounds — the
    # closest analogue of the old full-width "every pod considered up
    # to max_rounds times"); gating the OUTER loop on the cumulative
    # counter instead would exhaust the budget on the first few
    # tranches and silently never examine later-ranked pods at all.
    # The outer loop is bounded by its own progress guarantee (every
    # tranche places or spends >= 1 pod) plus a P-sized safety cap.
    # With preemption on the cap drops to 2: the cluster is near
    # capacity (that is why preemption is configured), deep per-tranche
    # fixpoints dribble the last few commits through extra [C, N]
    # rounds (~P/C x cap rounds total — 39 of the 55 rounds at
    # 10k x 5k were main-loop rounds, round-6 trace), and any feasible
    # straggler a capped tranche leaves behind is re-examined every
    # preemption round by _preempt_rounds' plain drain anyway.
    base_cap = 2 if cfg.preemption else 4
    tranche_cap = min(base_cap, max_rounds) if cfg.max_rounds > 0 else base_cap

    def tranche_path(st):
        used, assigned, chosen, round_of, progress, r = st
        alloc, req = nodes.allocatable, pods.requests

        def outer_cond(os):
            _, assigned, _, _, spent, r, t, progress = os
            return (
                progress & (t < P)
                & jnp.any((assigned == -1) & pods.valid & ~spent)
            )

        def outer_body(os):
            used, assigned, chosen, round_of, spent, r, t, _ = os
            pend = (assigned == -1) & pods.valid & ~spent
            sel, _ = _top_by_rank(pend, order, C)
            real = pend[sel]
            cond_c, body_c = _make_round_nosig(
                cfg, alloc, req[sel], static.mask[sel],
                static.score[sel], real, rank[sel], sel,
                static.w_lr[sel], static.w_ba[sel], static.w_ts[sel],
                static.rw, 2**30, K, P, round_cap=(r, tranche_cap),
            )
            init_c = (
                used, jnp.full(C, -1, jnp.int32),
                jnp.full(C, NEG_INF, jnp.float32),
                jnp.full(C, -1, jnp.int32), jnp.array(True), r,
            )
            used_c, asg_c, chosen_c, rnd_c, _, r_c = jax.lax.while_loop(
                cond_c, body_c, init_c
            )
            hit = asg_c >= 0
            assigned = assigned.at[sel].set(
                jnp.where(hit, asg_c, assigned[sel])
            )
            chosen = chosen.at[sel].set(
                jnp.where(hit, chosen_c, chosen[sel])
            )
            round_of = round_of.at[sel].set(
                jnp.where(hit, rnd_c, round_of[sel])
            )
            # With the round cap, an unplaced view pod is spent ONLY if
            # it has no feasible node against the tranche-final state
            # (permanent — capacity never grows here); feasible
            # stragglers stay pending and merge into the next tranche.
            feas_left = static.mask[sel] & kfilter.resource_fit(
                alloc, used_c, req[sel]
            )
            no_node = ~jnp.any(feas_left, axis=1)
            spent = spent.at[sel].set(spent[sel] | (real & ~hit & no_node))
            # Progress: placements, newly-spent pods, or a shrinking...
            # a capped tranche with feasible stragglers and no commits
            # cannot happen (the rescue commits one while any view pod
            # is feasible), so any(real) still implies forward motion.
            return (used_c, assigned, chosen, round_of, spent, r_c,
                    t + 1, jnp.any(real))

        used, assigned, chosen, round_of, _, rounds, _, _ = (
            jax.lax.while_loop(
                outer_cond, outer_body,
                (used, assigned, chosen, round_of,
                 jnp.zeros(P, bool), r, jnp.int32(0), progress),
            )
        )
        return used, assigned, chosen, round_of, rounds

    return tranche_path(state1)


def _sig_involvement(snap: ClusterSnapshot, static: StaticCtx, st0):
    """(invol [P, S] bool | None, has_pair [P] bool).

    has_pair: pods whose pairwise validation can ever fail — own spread
    or inter-pod terms, plus symmetric-anti TARGETS: a pod with NO
    constraints of its own can still be displaced by symmetric
    anti-affinity, so it must revalidate if any live anti term (running
    holders via st0.anti — domain-aware, so key-less holders don't
    count — or pending holders, whose node is unknown yet) has a
    selector matching it.

    invol: signature-involvement — the sigs whose counts a pod's checks
    read (its own constraint sigs) or whose counts its commit writes
    (selectors matching it). Pods with DISJOINT involvement cannot
    affect each other's pairwise validation, so conservative pods may
    commit concurrently one-per-sig-cluster instead of one-per-round
    globally — the difference between O(#conservative) and
    O(#sig-clusters) rounds on spread-heavy workloads. It is also the
    incremental warm path's signature-cluster CLOSURE relation: a dirty
    pod drags every invol-overlapping pod into the re-solve frontier
    (ISSUE 12). None when the snapshot has no signatures."""
    pods = snap.pods
    P = pods.valid.shape[0]
    has_pair = jnp.any(pods.ts_valid, axis=1) | jnp.any(pods.ia_valid, axis=1)
    if snap.sigs.key.shape[0] == 0:
        return None, has_pair
    M = snap.running.valid.shape[0]
    anti_possible = st0.anti.sum(axis=1) > 0
    for t in range(pods.ia_key.shape[1]):
        s_t = jnp.clip(pods.ia_sig[:, t], 0, None)
        hold = kpair._pod_anti_holds(snap, t) & pods.valid
        anti_possible = anti_possible.at[s_t].max(hold)
    sym_target = jnp.any(
        static.sig_match[:, M:] & anti_possible[:, None], axis=0
    )
    has_pair = has_pair | sym_target
    invol = static.sig_match[:, M:].T & pods.valid[:, None]  # [P, S]
    for c in range(pods.ts_key.shape[1]):
        s_c = jnp.clip(pods.ts_sig[:, c], 0, None)
        invol = invol.at[jnp.arange(P), s_c].max(pods.ts_valid[:, c])
    for t in range(pods.ia_key.shape[1]):
        s_t = jnp.clip(pods.ia_sig[:, t], 0, None)
        invol = invol.at[jnp.arange(P), s_t].max(pods.ia_valid[:, t])
    return invol, has_pair


def _solve_rounds_sig(cfg: EngineConfig, snap: ClusterSnapshot,
                      static: StaticCtx, rank, order, invol, has_pair,
                      init, max_rounds: int, K: int, cap: int):
    """The signature-path (S > 0) commit-round loop, frontier-compacted
    (ISSUE 12): full-width [P, N] rounds run only while the pending
    frontier exceeds `cap`; once it fits, each round gathers the WHOLE
    pending frontier (top-`cap` by rank — a superset, so every pod that
    could commit, gate, or validate is in view) into a [cap, N] view via
    _pods_view, runs the identical round math there, and scatters the
    commits back. cap == 0 keeps every round full-width — the reference
    the bitwise twin tests compare against.

    BITWISE CONTRACT: compacted rounds equal full-width rounds on
    assignment/chosen_score/evicted. Every cross-pod reduction in the
    round is width-invariant by construction — integer/boolean/min
    reductions are exact in any tree; the f32 ones go through
    _deal_commit(cum_width=P) and _node_add (fixed-point desirability
    sums, width-padded rank-major cumsums, unique-per-node adds); sorts
    key on globally-unique ranks so view layouts gather to identical
    sequences. Pinned by tests/test_frontier.py across structural-churn
    twins incl. preemption and gang admission.

    init/returns: (used, assigned, pair_st, conservative, chosen,
    round_of, progress, r) — `init` may carry a warm-seeded state (the
    incremental path: carried assignments pre-committed into used and
    pair_st, r starting past the carried commit key)."""
    pods, nodes = snap.pods, snap.nodes
    P = pods.valid.shape[0]
    N = nodes.valid.shape[0]
    BIG = jnp.int32(2**31 - 1)

    def round_math(snap_v, static_v, invol_v, hp_v, rank_v, pod_ids,
                   pending_v, conservative_v, used, pair_st, r):
        """One commit round over a (possibly compacted) pod-axis view.
        Returns (used3, st3, kept, choice, chosen_val, fb_mask)."""
        feasible, score, relaxed = batched_cycle(
            cfg, snap_v, static_v, used, pair_st, return_relaxed=True
        )
        feasible &= pending_v[:, None]
        relaxed &= pending_v[:, None]
        masked = jnp.where(feasible, score, NEG_INF)
        want = jnp.any(feasible, axis=1)
        # Conservative pods commit only when first among wanting pods
        # they could INTERACT with: minimal rank within every signature
        # cluster they touch (pods with disjoint involvement are
        # independent).
        cons_want = want & conservative_v
        rank_or_big = jnp.where(cons_want, rank_v, BIG)         # [F]
        min_rank_sig = jnp.min(
            jnp.where(invol_v, rank_or_big[:, None], BIG), axis=0
        )                                                       # [S]
        ok_cons = jnp.all(
            jnp.where(invol_v, rank_v[:, None] == min_rank_sig[None, :],
                      True),
            axis=1,
        )
        allowed = want & (~conservative_v | ok_cons)

        # Water-fill membership and activation use the RELAXED rows: a
        # DNS pod whose every in-bound domain is skew-blocked against
        # round-start counts can still legally place under end-of-round
        # semantics (the validator's state) — see _spread_waterfill_deal.
        allowed_r = jnp.any(relaxed, axis=1) & (~conservative_v | ok_cons)
        sp_cand, sp_val, sp_ok = _spread_waterfill_deal(
            snap_v, pair_st, used, relaxed, score, allowed_r, rank_v, K
        )
        used2, choice, chosen_val = _deal_commit(
            nodes.allocatable, snap_v.pods.requests, used, feasible,
            masked, allowed | sp_ok, rank_v, K, dealt_override=sp_cand,
            dealt_override_val=sp_val, dealt_override_ok=sp_ok,
            score_full=score,
            tie_pick=pick_node_batch(cfg, masked, pod_ids),
            cum_width=P,
        )
        commit = choice >= 0
        st2 = kpair.pair_state_commit(
            snap_v, pair_st, static_v.sig_match, choice, commit
        )

        # Validate committed pairwise pods against end-of-round counts;
        # roll back violators. Iterated to a fixpoint: a revert can
        # strip the match that satisfied another same-round pod's
        # positive affinity, so each pass re-checks the still-kept pods
        # until no new violations (each pass reverts >= 1 pod, so it
        # terminates). Two violation classes with different policies:
        #   * AFFINITY (required inter-pod / symmetric anti):
        #     rank-ordered partial reverts — the cluster-minimal
        #     violator is protected (its violation is usually induced
        #     by same-round lower-priority commits) and the rest revert
        #     and retry optimistically next round; see vbody. The
        #     conservative one-per-cluster gate survives only as the
        #     zero-progress backstop after the loop.
        #   * DoNotSchedule SPREAD: revert only the EXCESS members per
        #     (sig, domain) — keep the highest-priority prefix whose
        #     size respects every kept member's skew bound. Reverted
        #     pods retry WITHOUT the conservative gate: next round's
        #     start-state counts mask the full domains, so the dealer
        #     redirects them. Reverting ALL violators and serializing
        #     them cost O(pods-with-spread) rounds on spread-heavy
        #     workloads (~141 rounds on BASELINE config 3); excess-only
        #     reverts converge in a handful.
        def vcond(vs):
            return vs[-1]

        def vbody(vs):
            st_v, used_v, kept_v, _ = vs
            # Chosen-node-only IA verdict: the full [P, N]
            # pairwise_from_counts made each validation pass as
            # expensive as a scoring round; the fixpoint only reads
            # the chosen-node column.
            ia_ok_at = kpair.ia_ok_at_choice(
                snap_v, st_v, static_v.sig_match, choice,
                jnp.where(kept_v, choice, -1),
            )
            ia_bad_all = kept_v & hp_v & ~ia_ok_at
            # Rank-ordered partial reverts (round-4): PROTECT the
            # violator that precedes every other violator it could
            # interact with (minimal rank across all its involved
            # sigs): its violation is usually induced by same-round
            # higher-rank commits, which revert first; the fixpoint
            # then re-checks it against the surviving state. If a pass
            # finds only protected violators left, they are genuinely
            # invalid against the kept state — revert them too (also
            # guarantees each pass reverts >= 1, so the loop
            # terminates).
            bad_rank = jnp.where(ia_bad_all, rank_v, BIG)
            min_bad_sig = jnp.min(
                jnp.where(invol_v, bad_rank[:, None], BIG), axis=0
            )                                                   # [S]
            protected = ia_bad_all & jnp.all(
                jnp.where(invol_v,
                          rank_v[:, None] == min_bad_sig[None, :], True),
                axis=1,
            )
            ia_bad = ia_bad_all & ~protected
            sp_bad = _spread_excess_mask(
                snap_v, static_v.aff_ok, rank_v, choice, kept_v, st_v
            ) & ~ia_bad_all
            stuck = ~jnp.any(ia_bad | sp_bad) & jnp.any(ia_bad_all)
            ia_bad = ia_bad | (ia_bad_all & stuck)
            new_viol = ia_bad | sp_bad
            used_v = _node_add(used_v, choice, new_viol,
                               snap_v.pods.requests, rank_v, P, sign=-1.0)
            st_v = kpair.pair_state_commit(
                snap_v, st_v, static_v.sig_match, choice, new_viol,
                sign=-1.0,
            )
            return (st_v, used_v, kept_v & ~new_viol, jnp.any(new_viol))

        st3, used3, kept, _ = jax.lax.while_loop(
            vcond, vbody, (st2, used2, commit, jnp.any(commit & hp_v)),
        )
        viol = commit & ~kept
        if _DEBUG_ROUNDS:
            jax.debug.print(
                "round {r}: allowed={a} commit={c} kept={k} viol={v}",
                r=r, a=allowed.sum(), c=commit.sum(), k=kept.sum(),
                v=viol.sum(),
            )
        # Progress backstop: reverted pods retry optimistically against
        # next round's start-state counts (which now mask the domains
        # they lost), so they normally converge without any gating. But
        # if EVERY commit of this round was reverted, optimism alone
        # proves nothing placed — mark the first reverted pod (by rank)
        # conservative so the ordered one-per-cluster path guarantees
        # progress.
        need_fb = ~jnp.any(kept) & jnp.any(viol)
        fb_first = rank_v == jnp.min(jnp.where(viol, rank_v, BIG))
        fb_mask = viol & fb_first & need_fb
        return used3, st3, kept, choice, chosen_val, fb_mask

    ids = jnp.arange(P, dtype=jnp.int32)

    def full_body(state):
        used, assigned, pair_st, conservative, chosen, round_of, _, r = state
        pending = assigned == -1
        used3, st3, kept, choice, chosen_val, fb_mask = round_math(
            snap, static, invol, has_pair, rank, ids, pending,
            conservative, used, pair_st, r,
        )
        assigned2 = jnp.where(kept, choice, assigned)
        chosen2 = jnp.where(kept, chosen_val, chosen)
        round_of2 = jnp.where(kept, r, round_of)
        new_conservative = fb_mask & ~conservative
        conservative2 = conservative | fb_mask
        all_done = jnp.all((assigned2 >= 0) | ~pods.valid)
        progress = (jnp.any(kept) | jnp.any(new_conservative)) & ~all_done
        return (used3, assigned2, st3, conservative2, chosen2,
                round_of2, progress, r + 1)

    def full_cond(state):
        progress, r = state[-2], state[-1]
        ok = progress & (r < max_rounds)
        if cap:
            # Hand off to the compacted loop once the whole pending
            # frontier fits one view (never before: the view must hold
            # EVERY pending pod for the bitwise contract to hold).
            ok &= jnp.sum(
                ((state[1] == -1) & pods.valid).astype(jnp.int32)
            ) > cap
        return ok

    state = jax.lax.while_loop(full_cond, full_body, init)
    if not cap:
        return state

    def compact_body(state):
        used, assigned, pair_st, conservative, chosen, round_of, _, r = state
        pend = (assigned == -1) & pods.valid
        sel, _ = _top_by_rank(pend, order, cap)
        snap_v, static_v = _pods_view(snap, static, sel)
        used3, st3, kept, choice, chosen_val, fb_mask = round_math(
            snap_v, static_v, invol[sel], has_pair[sel], rank[sel], sel,
            pend[sel], conservative[sel], used, pair_st, r,
        )
        assigned2 = assigned.at[sel].set(
            jnp.where(kept, choice, assigned[sel])
        )
        chosen2 = chosen.at[sel].set(
            jnp.where(kept, chosen_val, chosen[sel])
        )
        round_of2 = round_of.at[sel].set(
            jnp.where(kept, r, round_of[sel])
        )
        new_conservative = fb_mask & ~conservative[sel]
        conservative2 = conservative.at[sel].set(
            conservative[sel] | fb_mask
        )
        all_done = jnp.all((assigned2 >= 0) | ~pods.valid)
        progress = (jnp.any(kept) | jnp.any(new_conservative)) & ~all_done
        return (used3, assigned2, st3, conservative2, chosen2,
                round_of2, progress, r + 1)

    def compact_cond(state):
        progress, r = state[-2], state[-1]
        return progress & (r < max_rounds)

    return jax.lax.while_loop(compact_cond, compact_body, state)


def solve_rounds(cfg: EngineConfig, snap: ClusterSnapshot,
                 node_sat_t, member_sat_t, init_counts=None,
                 explain: bool = False, static=None, mesh=None):
    """Fast mode: optimistic batched rounds with validate-and-rollback.
    Returns (assigned, chosen, used, order, round_of, rounds, evicted);
    with explain=True (decision provenance, round 12) an extra trailing
    tuple (rolled, evictor, evict_round, auction_stats) — gang-rollback
    mask [P], per-victim preemptor pod index / commit-round [M] (-1 =
    not evicted), and the [_PREEMPT_MAX_ROUNDS, EXPLAIN_AUCTION_STATS]
    per-round auction table. The explain accumulation is traced only
    when requested, so the default program is unchanged. static:
    optional precomputed StaticCtx (the warm path)."""
    if static is None:
        static = precompute_static(cfg, snap, node_sat_t, member_sat_t,
                                   mesh)
    pods, nodes = snap.pods, snap.nodes
    P = pods.valid.shape[0]
    N = nodes.valid.shape[0]
    order = pop_order(cfg, snap)
    rank = jnp.zeros(P, jnp.int32).at[order].set(jnp.arange(P, dtype=jnp.int32))
    st0 = kpair.pair_state_init(snap, static.sig_match, counts=init_counts,
                                mesh=mesh)
    S = snap.sigs.key.shape[0]
    invol, has_pair = _sig_involvement(snap, static, st0)
    BIG = jnp.int32(2**31 - 1)
    # Round bound: worst case is one conservative pod committing per
    # round, so the auto bound is O(P); cfg.max_rounds > 0 caps it lower
    # (pods still pending at the cap stay unassigned that batch).
    max_rounds = cfg.max_rounds if cfg.max_rounds > 0 else 2 * P + 8
    K = _fallback_depth(N)

    if S == 0:
        # No pairwise signatures (trace-time): dedicated path with
        # residual compaction after round 1 (the conservative/
        # validation machinery is inert at S == 0).
        used, assigned, chosen, round_of, rounds = _solve_rounds_nosig(
            cfg, snap, static, rank, order, max_rounds, K
        )
        st_f = st0
    else:
        init = (
            nodes.used, jnp.full(P, -1, jnp.int32), st0,
            jnp.zeros(P, bool), jnp.full(P, NEG_INF, jnp.float32),
            jnp.full(P, -1, jnp.int32), jnp.array(True), jnp.int32(0),
        )
        out = _solve_rounds_sig(
            cfg, snap, static, rank, order, invol, has_pair, init,
            max_rounds, K, _compact_cap(cfg, P),
        )
        used, assigned, st_f, _, chosen, round_of, _, rounds = out
    M = snap.running.valid.shape[0]
    evicted = jnp.zeros(M, bool)
    evictor = evict_rd = astats = None
    if explain:
        evictor = jnp.full(M, -1, jnp.int32)
        evict_rd = jnp.full(M, -1, jnp.int32)
        astats = jnp.zeros(
            (_PREEMPT_MAX_ROUNDS, len(EXPLAIN_AUCTION_STATS)), jnp.float32
        )
    if cfg.preemption and M > 0:
        pr_out = _preempt_rounds(
            cfg, snap, static, rank, order, rounds,
            used, assigned, st_f, evicted, round_of, chosen,
            has_pair=has_pair, explain=explain,
        )
        (used, assigned, st_f, evicted, round_of, chosen,
         preempt_r) = pr_out[:7]
        if explain:
            evictor, evict_rd, astats = pr_out[7]
        # Total commit rounds surfaces the preemption drain too (the
        # bench and host logs read SolveResult.rounds).
        rounds = rounds + preempt_r
    used, assigned, chosen, st_f, rolled = gang_rollback(
        snap, used, assigned, chosen, st_f, static.sig_match
    )
    round_of = jnp.where(rolled, -1, round_of)
    # Commit key for external validity audits: pods committed in earlier
    # rounds precede later ones; within a round all commits share a key
    # (the engine validated them against end-of-round state).
    base = (assigned, chosen, used, order, round_of, rounds, evicted)
    if explain:
        return base + ((rolled, evictor, evict_rd, astats),)
    return base


def _capacity_prefix_keep(alloc, used_base, requests, node, rank, active):
    """[P] bool: per node, the longest rank-ordered prefix of `active`
    rows whose cumulative requests fit alloc - used_base — the same
    capacity-prefix rule _deal_commit's sub-step commits by, applied to
    the incremental warm path's CARRIED placements: a node whose
    allocatable shrank (or whose carried demand grew) spills its
    lowest-priority carried pods back into the pending frontier instead
    of overflowing."""
    P = node.shape[0]
    N = alloc.shape[0]
    node_m = jnp.where(active, jnp.clip(node, 0, N - 1), N)
    perm = jnp.lexsort((rank, node_m))
    node_s = node_m[perm]
    act_s = active[perm]
    req_s = jnp.where(act_s[:, None], requests[perm], 0.0)
    idx = jnp.arange(P, dtype=jnp.int32)
    boundary = jnp.concatenate(
        [jnp.ones(1, bool), node_s[1:] != node_s[:-1]]
    )
    seg_start = jax.lax.cummax(jnp.where(boundary, idx, 0))
    within = _segment_cumsum(req_s, boundary)
    cap_node = jnp.clip(node_s, 0, N - 1)
    fits = jnp.all(
        used_base[cap_node] + within <= alloc[cap_node], axis=-1
    ) & act_s
    bad = act_s & ~fits
    last_bad = jax.lax.cummax(jnp.where(bad, idx, -1))
    keep_s = fits & (last_bad < seg_start)
    return jnp.zeros(P, bool).at[perm].set(keep_s)


# Layout of the incremental solve's in-kernel audit vector (appended to
# the packed solve buffer by the engine's incremental program):
#   [cap_violations, carried_static_violations, carried_pair_violations,
#    carried_count, frontier_count]
INC_AUDIT_LEN = 5


def solve_incremental(cfg: EngineConfig, snap: ClusterSnapshot, tab,
                      carry, carry_chosen, frontier0, dirty_node_mask,
                      cap: int, mesh=None):
    """Bounded-divergence warm commit rounds (ISSUE 12, tentpole 2):
    seed the round loop with the previous cycle's assignment for clean
    pods and run commit rounds only over the pending FRONTIER, so solve
    time scales with churn, not the cluster.

      1. The frontier starts from the lineage's dirty pods (frontier0)
         and expands to its SIGNATURE-CLUSTER closure (pods whose invol
         rows overlap a dirty pod's — their counts a dirty commit can
         read or write) and NODE closure (carried pods sitting on a
         dirty node: its capacity/labels may have moved under them).
      2. Every remaining carried placement is revalidated against
         CURRENT state in one batched pass per class: static mask at
         the carried node (taints/affinity/cordon), per-node rank-
         ordered capacity prefix vs current allocatable, and — when
         signatures exist — the pairwise fixpoint (ia_ok_at_choice +
         _spread_excess_mask, the exact validators the cold rounds
         use). Violations SPILL into the frontier.
      3. Survivors pre-commit (capacity + pair state + commit key 0)
         and the normal round machinery — frontier-compacted — places
         the frontier; preemption rounds and the gang Permit gate run
         unchanged on top.

    NOT bitwise vs a cold solve (the round fixpoint is globally
    coupled); governed instead by the validity contract — no capacity
    overflow, no pairwise violation, carried pods still feasible on
    their nodes — enforced by the passes above and re-checked by the
    in-kernel audit appended to the result (INC_AUDIT_LEN tail;
    `divergence --warm-audit --incremental` additionally reports the
    placement-quality drift vs a cold twin). One known soft spot,
    shared with the cold fast path: a post-rollback gang member's
    departure can strip a match another pod's REQUIRED positive
    affinity relied on — the audit reports it rather than masking it.

    carry: [P] int32 previous-cycle node per pod in CURRENT row order
    (-1 = no carry); carry_chosen: [P] f32 their as-of-placement
    scores (carried placements keep them — upstream nominates without
    rescoring); frontier0: [P] bool dirty basis; dirty_node_mask: [N]
    bool or None; cap: frontier-compaction width for the rounds (0 =
    full-width).

    Returns (assigned, chosen, used, order, round_of, rounds, evicted,
    audit[INC_AUDIT_LEN] f32)."""
    static = finalize_static(cfg, snap, tab)
    pods, nodes = snap.pods, snap.nodes
    P = pods.valid.shape[0]
    N = nodes.valid.shape[0]
    order = pop_order(cfg, snap)
    rank = jnp.zeros(P, jnp.int32).at[order].set(
        jnp.arange(P, dtype=jnp.int32)
    )
    st0 = kpair.pair_state_init(snap, static.sig_match, mesh=mesh)
    S = snap.sigs.key.shape[0]
    invol, has_pair = _sig_involvement(snap, static, st0)
    max_rounds = cfg.max_rounds if cfg.max_rounds > 0 else 2 * P + 8
    K = _fallback_depth(N)

    carry = jnp.where(pods.valid, carry, -1)
    fr = frontier0 & pods.valid
    if invol is not None:
        hot = jnp.any(invol & fr[:, None], axis=0)           # [S]
        fr = fr | jnp.any(invol & hot[None, :], axis=1)
    if dirty_node_mask is not None:
        fr = fr | ((carry >= 0)
                   & dirty_node_mask[jnp.clip(carry, 0, None)])
    carried = pods.valid & (carry >= 0) & ~fr
    frontier_n = jnp.sum((pods.valid & (carry < 0) | fr).astype(jnp.float32))
    # Revalidation pass 1: static feasibility at the carried node.
    ok_static = tab.mask[jnp.arange(P), jnp.clip(carry, 0, None)]
    carried &= ok_static
    # Pass 2: per-node capacity prefix vs CURRENT allocatable.
    carried &= _capacity_prefix_keep(
        nodes.allocatable, nodes.used, pods.requests, carry, rank, carried
    )
    used = _node_add(nodes.used, carry, carried, pods.requests, rank, P)
    st = st0
    if S:
        st = kpair.pair_state_commit(
            snap, st, static.sig_match, carry, carried
        )

        # Pass 3: pairwise revalidation to fixpoint — a spill can strip
        # the match another carried pod's positive affinity relied on,
        # so iterate until clean (each pass spills >= 1, so it
        # terminates; in the common cycle it exits after one check).
        def rcond(vs):
            return vs[-1]

        def rbody(vs):
            st_v, used_v, kept_v, _ = vs
            ia = kpair.ia_ok_at_choice(
                snap, st_v, static.sig_match, carry,
                jnp.where(kept_v, carry, -1),
            )
            bad = kept_v & has_pair & ~ia
            bad = bad | (kept_v & _spread_excess_mask(
                snap, tab.aff_ok, rank, carry, kept_v, st_v
            ))
            st_v = kpair.pair_state_commit(
                snap, st_v, static.sig_match, carry, bad, sign=-1.0
            )
            used_v = _node_add(used_v, carry, bad, pods.requests, rank, P,
                               sign=-1.0)
            return st_v, used_v, kept_v & ~bad, jnp.any(bad)

        st, used, carried, _ = jax.lax.while_loop(
            rcond, rbody, (st, used, carried, jnp.any(carried & has_pair))
        )
    assigned = jnp.where(carried, carry, -1)
    chosen = jnp.where(carried, carry_chosen, NEG_INF)
    round_of = jnp.where(carried, 0, -1)
    carried_n = jnp.sum(carried.astype(jnp.float32))
    if S == 0:
        used, assigned, chosen, round_of, rounds = _solve_rounds_nosig(
            cfg, snap, static, rank, order, max_rounds, K,
            init=(used, assigned, chosen, round_of, jnp.array(True),
                  jnp.int32(1)),
            skip_full=True, cap=(cap if cap > 0 else None),
        )
        st_f = st
    else:
        init = (used, assigned, st, jnp.zeros(P, bool), chosen, round_of,
                jnp.array(True), jnp.int32(1))
        out = _solve_rounds_sig(
            cfg, snap, static, rank, order, invol, has_pair, init,
            max_rounds, K, cap,
        )
        used, assigned, st_f, _, chosen, round_of, _, rounds = out
    M = snap.running.valid.shape[0]
    evicted = jnp.zeros(M, bool)
    if cfg.preemption and M > 0:
        pr_out = _preempt_rounds(
            cfg, snap, static, rank, order, rounds,
            used, assigned, st_f, evicted, round_of, chosen,
            has_pair=has_pair,
        )
        (used, assigned, st_f, evicted, round_of, chosen,
         preempt_r) = pr_out[:7]
        rounds = rounds + preempt_r
    used, assigned, chosen, st_f, rolled = gang_rollback(
        snap, used, assigned, chosen, st_f, static.sig_match
    )
    round_of = jnp.where(rolled, -1, round_of)

    # In-kernel validity audit (the contract's enforcement receipt).
    # Relative tolerance: request magnitudes span cpu-millis to memory
    # bytes, so an absolute epsilon would be meaningless at one end.
    alloc = nodes.allocatable
    tol = jnp.maximum(jnp.abs(alloc) * 1e-5, 1e-4)
    cap_bad = (used > alloc + tol) & (used > nodes.used + tol)
    final_carried = carried & (assigned == carry) & (assigned >= 0)
    ok_static_f = tab.mask[jnp.arange(P), jnp.clip(assigned, 0, None)]
    s_viol = jnp.sum((final_carried & ~ok_static_f).astype(jnp.float32))
    if S:
        st_car = kpair.pair_state_seed(
            snap, static.sig_match, carry, final_carried, mesh=mesh
        )
        ia_f = kpair.ia_ok_at_choice(
            snap, st_car, static.sig_match, carry,
            jnp.where(final_carried, carry, -1),
        )
        sp_f = _spread_excess_mask(
            snap, tab.aff_ok, rank, carry, final_carried, st_car
        )
        p_viol = (jnp.sum((final_carried & has_pair & ~ia_f)
                          .astype(jnp.float32))
                  + jnp.sum(sp_f.astype(jnp.float32)))
    else:
        p_viol = jnp.float32(0.0)
    audit = jnp.stack([
        jnp.sum(cap_bad.astype(jnp.float32)), s_viol,
        jnp.asarray(p_viol, jnp.float32), carried_n, frontier_n,
    ])
    return (assigned, chosen, used, order, round_of, rounds, evicted,
            audit)
