"""Preemption (PostFilter) kernel (SURVEY.md C9, §3.4).

The reference scheduler's signature behavior: a pod with no feasible
node searches for nodes where evicting lower-priority victims makes it
fit, choosing the minimum-cost victim set, with eviction cost driven by
the victims' QoS slack (pods running above their SLO are cheap to evict;
see qos.evict_cost_raw and QoSConfig).

TPU formulation: victims are sorted ONCE per snapshot by (node, cost)
(PreemptCtx). A preemptor's step is then a masked segment-prefix scan —
eligible victims' cumulative requests within each node's segment — and
the cheapest feasible prefix per node falls out of the FIRST position
where the preemptor fits (costs ascend within a segment, so the first
feasible prefix is the min-cost one). A scatter-min over segments yields
per-node best costs; argmin picks the node. Everything is fixed-shape
[M]/[N] arithmetic — no Hungarian augmenting paths, no data-dependent
loops (the auction-style "bid per node, pick globally best" recommended
over classical Hungarian by SURVEY.md §7 hard part 4).

PodDisruptionBudgets (SURVEY.md C9 "fewest PDB violations"): each
running pod may belong to a budget (running.pdb_group) with a remaining
disruptions_allowed (snapshot.pdb_allowed). A victim whose eviction
would exceed its budget's remaining allowance — counting earlier
preemptors' evictions AND same-prefix co-victims — is a VIOLATION.
Candidate prefixes are ranked lexicographically by (violation count,
cost), exactly upstream's ordering: any non-violating set beats any
violating one, and violation stays available as the last resort
(upstream evicts PDB-protected pods when nothing else fits). Violation
counts are small integers (exact in f32 under any summation order), so
oracle/device parity survives; a cost PENALTY of ~1e8 would instead
poison the f32 prefix sums, whose rounding depends on the backend's
scan association. With violations in play, costs within a segment no
longer rank prefixes, so the chosen prefix is the lexicographic MIN
over all feasible prefix positions, not the first feasible.

Scope notes (mirrored exactly by the oracle so parity is testable):
  * Only RESOURCE infeasibility is repaired: the preemptor's static
    predicates (taints/affinity) and pairwise constraints must already
    hold on the target node, evaluated against pre-eviction state.
  * The preemptor is assigned immediately (the host shim issues deletes
    then binds; upstream nominates and re-queues instead).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import struct

from tpusched.config import EngineConfig
from tpusched.kernels import pairwise as kpair
from tpusched.qos import evict_cost_raw, victim_effective_priority
from tpusched.snapshot import ClusterSnapshot


@struct.dataclass
class PreemptCtx:
    """Snapshot-static victim ordering and costs."""

    perm: Any        # [M] int32: running pods sorted by (node, cost)
    node_s: Any      # [M] int32 node of sorted victim (N = invalid sentinel)
    seg_start: Any   # [M] int32 index where this node's segment begins
    cost_s: Any      # [M] f32 shifted-positive eviction cost, sorted
    vprio_s: Any     # [M] f32 victim effective priority, sorted
    req_s: Any       # [M, R] f32 victim requests, sorted
    pdb_s: Any       # [M] int32 PDB id of sorted victim (-1 none)


def precompute(cfg: EngineConfig, snap: ClusterSnapshot) -> PreemptCtx:
    run = snap.running
    M = run.valid.shape[0]
    N = snap.nodes.valid.shape[0]
    vprio = victim_effective_priority(cfg, run.priority, run.slack)
    raw = evict_cost_raw(cfg, run.priority, run.slack).astype(jnp.float32)
    # Shift costs positive (+1 per victim): prefix sums then strictly
    # increase, making "first feasible prefix = cheapest" hold and
    # encoding the fewer-victims preference (upstream tie-break).
    mn = jnp.min(jnp.where(run.valid, raw, jnp.inf))
    mn = jnp.where(jnp.isfinite(mn), mn, 0.0)
    cost = raw - mn + 1.0
    node_m = jnp.where(run.valid & (run.node_idx >= 0), run.node_idx, N)
    perm = jnp.lexsort((cost, node_m))
    node_s = node_m[perm]
    idx = jnp.arange(M, dtype=jnp.int32)
    if M:
        boundary = jnp.concatenate(
            [jnp.ones(1, bool), node_s[1:] != node_s[:-1]]
        )
        seg_start = jax.lax.cummax(jnp.where(boundary, idx, 0))
    else:
        seg_start = idx
    return PreemptCtx(
        perm=perm, node_s=node_s, seg_start=seg_start,
        cost_s=cost[perm], vprio_s=vprio[perm].astype(jnp.float32),
        req_s=run.requests[perm],
        pdb_s=run.pdb_group[perm],
    )


@struct.dataclass
class PreemptCtxNV:
    """Node-major victim table for the fast auction (round 5): per node,
    up to V victims in ascending-cost order (the same within-segment
    order as PreemptCtx's global (node, cost) sort). The [C, M] global
    prefix sums of the sorted layout cost ~25 ms/round at 10k x 5k
    (log-depth cumsums over M=40960); in node-major layout every prefix
    is a V-length cumsum and the PDB same-budget counts become one
    [V, V] triangular contraction — MXU work instead of scan passes.
    Victims beyond the per-node cap V are unreachable for fast-mode
    preemption (a documented approximation: a prefix needing > V
    evictions on one node falls back to other nodes or stays pending;
    the sequential/parity path has no cap)."""

    vreq: Any    # [N, V, R] f32 victim requests
    vcost: Any   # [N, V] f32 shifted-positive eviction cost, ascending
    vprio: Any   # [N, V] f32 victim effective priority
    vpdb: Any    # [N, V] int32 PDB id (-1 none/pad)
    vvalid: Any  # [N, V] bool
    vidx: Any    # [N, V] int32 index into running arrays (M = pad)


def precompute_nv(cfg: EngineConfig, snap: ClusterSnapshot,
                  cap: int) -> PreemptCtxNV:
    """Build the node-major victim table (fast-auction counterpart of
    precompute; same sort keys, so victim order within a node matches
    the sequential tableau exactly)."""
    run = snap.running
    M = run.valid.shape[0]
    N = snap.nodes.valid.shape[0]
    V = max(1, min(cap, M))
    vprio = victim_effective_priority(cfg, run.priority, run.slack)
    raw = evict_cost_raw(cfg, run.priority, run.slack).astype(jnp.float32)
    mn = jnp.min(jnp.where(run.valid, raw, jnp.inf))
    mn = jnp.where(jnp.isfinite(mn), mn, 0.0)
    cost = raw - mn + 1.0
    node_m = jnp.where(run.valid & (run.node_idx >= 0), run.node_idx, N)
    perm = jnp.lexsort((cost, node_m))
    node_s = node_m[perm]
    idx = jnp.arange(M, dtype=jnp.int32)
    boundary = jnp.concatenate(
        [jnp.ones(1, bool), node_s[1:] != node_s[:-1]]
    )
    seg_start = jax.lax.cummax(jnp.where(boundary, idx, 0))
    pos = idx - seg_start
    ok = (node_s < N) & (pos < V)
    tn = jnp.where(ok, node_s, N)   # sentinel row N for drops/pads
    tv = jnp.where(ok, pos, 0)

    def scat(vals, fill, dtype):
        shape = (N + 1, V) + vals.shape[1:]
        out = jnp.full(shape, fill, dtype)
        src = jnp.where(
            ok.reshape((M,) + (1,) * (vals.ndim - 1)), vals, fill
        )
        return out.at[tn, tv].set(src.astype(dtype))[:N]

    return PreemptCtxNV(
        vreq=scat(run.requests[perm], 0.0, jnp.float32),
        vcost=scat(cost[perm], 0.0, jnp.float32),
        vprio=scat(vprio[perm].astype(jnp.float32), jnp.inf, jnp.float32),
        vpdb=scat(run.pdb_group[perm], -1, jnp.int32),
        vvalid=jnp.zeros((N + 1, V), bool).at[tn, tv].set(ok)[:N],
        vidx=scat(perm, M, jnp.int32),
    )


def _tableau(cfg: EngineConfig, snap: ClusterSnapshot, ctx: PreemptCtx,
             p_prio, p_req, used, evicted):
    """One preemptor's victim-prefix tableau: everything preempt_step
    derives before node selection. Shared verbatim by the sequential
    step and the batched auction (preempt_auction) so their per-node
    rankings agree exactly. Returns
    (elig [M], within_cost [M], within_viol [M], fits [M],
    node_viol [N], node_cost [N])."""
    nodes = snap.nodes
    M = ctx.perm.shape[0]
    N = nodes.valid.shape[0]
    idx = jnp.arange(M, dtype=jnp.int32)

    elig = (
        (ctx.node_s < N)
        & ~evicted[ctx.perm]
        & (ctx.vprio_s + cfg.qos.preemption_margin < p_prio)
    )
    # PDB violations (see module docstring): a victim violates if the
    # same-budget count within its node-segment prefix (including
    # itself) plus earlier preemptors' evictions exceeds the budget.
    GP = snap.pdb_allowed.shape[0]
    if GP:
        pdb_clip = jnp.clip(ctx.pdb_s, 0, None)
        has_pdb = ctx.pdb_s >= 0
        run_pdb = snap.running.pdb_group
        consumed = jnp.zeros(GP, jnp.float32).at[
            jnp.clip(run_pdb, 0, None)
        ].add((evicted & (run_pdb >= 0) & snap.running.valid).astype(
            jnp.float32
        ))
        remaining = snap.pdb_allowed - consumed              # [GP]
        gsel = (
            (jnp.arange(GP)[:, None] == pdb_clip[None, :])
            & (elig & has_pdb)[None, :]
        )                                                    # [GP, M]
        cum_g = jnp.cumsum(gsel.astype(jnp.float32), axis=1)
        my_cum = cum_g[pdb_clip, idx]                        # [M] incl. self
        off_g = jnp.where(
            ctx.seg_start > 0,
            cum_g[pdb_clip, jnp.clip(ctx.seg_start - 1, 0, None)], 0.0,
        )
        within_cnt = my_cum - off_g
        viol = elig & has_pdb & (within_cnt > remaining[pdb_clip])
    else:
        viol = jnp.zeros(M, bool)
    req_m = jnp.where(elig[:, None], ctx.req_s, 0.0)
    cum_req = jnp.cumsum(req_m, axis=0)                      # [M, R] inclusive  # tpl: disable=TPL201(victim-prefix sums at the snapshot's fixed [M] width, mirrored op-for-op by oracle.py — parity suites pin the verdicts bitwise; the victim axis must stay unsharded, recorded in the ledger sharding column)
    cum_cost = jnp.cumsum(jnp.where(elig, ctx.cost_s, 0.0))  # [M]
    # Violation count per prefix: 0/1 sums are exact in f32 under any
    # summation order (<= M < 2^24), unlike penalty-inflated cost sums.
    cum_viol = jnp.cumsum(viol.astype(jnp.float32))          # [M]
    off_req = jnp.where(
        (ctx.seg_start > 0)[:, None],
        cum_req[jnp.clip(ctx.seg_start - 1, 0, None)], 0.0,
    )
    off_cost = jnp.where(
        ctx.seg_start > 0, cum_cost[jnp.clip(ctx.seg_start - 1, 0, None)], 0.0
    )
    off_viol = jnp.where(
        ctx.seg_start > 0, cum_viol[jnp.clip(ctx.seg_start - 1, 0, None)], 0.0
    )
    within_req = cum_req - off_req                           # [M, R]
    within_cost = cum_cost - off_cost                        # [M]
    within_viol = cum_viol - off_viol                        # [M]
    cap_node = jnp.clip(ctx.node_s, 0, N - 1)
    fits = elig & jnp.all(
        used[cap_node] - within_req + p_req[None, :]
        <= nodes.allocatable[cap_node],
        axis=-1,
    )
    # Lexicographic (violations, cost) MIN over feasible prefixes, in
    # exact two-stage comparisons (never summing the two channels):
    # per node, fewest violations first; among those prefixes, min cost.
    # N index = sentinel bucket.
    node_viol = jnp.full(N + 1, jnp.inf).at[ctx.node_s].min(
        jnp.where(fits, within_viol, jnp.inf)
    )[:N]
    fits_v = fits & (within_viol == node_viol[cap_node])
    node_cost = jnp.full(N + 1, jnp.inf).at[ctx.node_s].min(
        jnp.where(fits_v, within_cost, jnp.inf)
    )[:N]
    return elig, within_cost, within_viol, fits, node_viol, node_cost


def preempt_step(cfg: EngineConfig, snap: ClusterSnapshot, ctx: PreemptCtx,
                 p_prio, p_req, allowed_row, used, evicted):
    """One preemptor's victim search. Returns
    (best_n, can, evict_m, freed) — chosen node (int32), whether
    preemption succeeds (bool), the [M] eviction mask, and the [N, R]
    capacity freed on the chosen node (zeros elsewhere)."""
    nodes = snap.nodes
    M = ctx.perm.shape[0]
    idx = jnp.arange(M, dtype=jnp.int32)
    elig, within_cost, within_viol, fits, node_viol, node_cost = _tableau(
        cfg, snap, ctx, p_prio, p_req, used, evicted
    )
    # Across nodes: global fewest violations, then cheapest. (inf ==
    # inf is True, so the allowed mask must gate `total` as well —
    # otherwise a disallowed node's finite prefix wins when NO allowed
    # node is feasible.)
    ok_node = allowed_row & nodes.valid
    viol_total = jnp.where(ok_node, node_viol, jnp.inf)
    min_viol = jnp.min(viol_total)
    total = jnp.where(ok_node & (viol_total == min_viol), node_cost, jnp.inf)
    best_n = jnp.argmin(total).astype(jnp.int32)
    can = jnp.isfinite(total[best_n])
    best_pos = jnp.argmin(
        jnp.where(
            fits & (ctx.node_s == best_n) & (within_viol == min_viol),
            within_cost, jnp.inf,
        )
    ).astype(jnp.int32)
    sel_s = can & (ctx.node_s == best_n) & elig & (idx <= best_pos)
    evict_m = jnp.zeros(M, bool).at[ctx.perm].set(sel_s)
    freed_on_best = jnp.sum(
        jnp.where(sel_s[:, None], ctx.req_s, 0.0), axis=0
    )                                                        # [R]
    freed = jnp.zeros_like(used).at[best_n].add(
        jnp.where(can, freed_on_best, 0.0)
    )
    return best_n, can, evict_m, freed


# Quantile buckets of active-bidder priority for the candidate tables
# (see preempt_auction). 2: each bucket's table is traced/compiled as
# its own [N, V] subgraph, and 2 buckets + the optimistic lane already
# give the common case (victims below every bidder) exact tables while
# keeping the auction's compile time inside the tier-1 wall budget on
# CPU hosts; boundary bidders fall through to the optimistic lane +
# exact [C, V] validation either way.
_PRIO_BUCKETS = 2


def preempt_auction(cfg: EngineConfig, snap: ClusterSnapshot,
                    ctx: PreemptCtxNV, p_prio, p_req, allowed,
                    used, evicted, can_plain, n_plain,
                    k_cand: int = 256, rank=None, claim_iters: int = 6):
    """Batched bidding for C preemptors at once (the fast mode's
    auction round; SURVEY.md §7 hard part 4 — parallel bids, global
    resolution), restructured (round 6) so the EXACT per-bidder work is
    a [C, V] tableau on the claimed node only, never [C, N, V]:

      1. CANDIDATE RANKING from bidder-independent [N, V] prefix
         tables. Within a node, victims sit in ascending-cost slots,
         prefix-freed capacity / cost / violation count are all
         nondecreasing in prefix length, and feasibility is monotone
         (a longer prefix frees more) — so a bidder's best prefix on a
         node is always the FIRST feasible slot, and ranking nodes
         needs only "where does my demand cross this node's cumulative
         freed capacity" (a searchsorted-style compare+reduce per
         resource, [C, N] out) plus two [C, N] gathers into the
         node-major cumulative cost/violation tables. Eligibility is
         approximated by _PRIO_BUCKETS quantile buckets of the ACTIVE
         bidders' priorities: each bucket's table masks victims
         eligible at the bucket's LOWER bound, a conservative subset
         of every member bidder's true eligible set — so a node the
         bucket table calls feasible is feasible for the bidder (more
         eligibility only frees more), while cost is an upper
         estimate. A single active bidder (the small-cluster unit-test
         shape) gets thresholds equal to its own priority: exact.
         The old path materialized the exact [C, N, V(, R)] tableau —
         ~0.5 GB of f32 cumsums per round at 10k x 5k, the measured
         ~16 ms/round floor of the preemption drain.
      2. An OPTIMISTIC (priority-unaware) table answers "could this
         bidder EVER preempt anywhere": bidders with no bucket-feasible
         node but an optimistic-feasible one bid that node as a single
         candidate (exact validation decides), and could_bid/spent
         marking uses the optimistic answer so no pod is falsely
         retired by the bucket approximation.
      3. PARALLEL claim iterations (unchanged) deal bidders distinct
         still-unclaimed candidate nodes: each iteration every
         unclaimed bidder bids its (active-rank mod available)-th
         cheapest untaken candidate and the lowest-rank bidder per
         node wins (scatter-min) — one claimant per node, so
         same-round victim sets never overlap (victims are
         node-local). Losers re-deal next iteration; bidders still
         unclaimed after claim_iters defer to the next auction round.
         Plain placements (can_plain, from the caller's feasibility
         re-check) claim their scored node through the same iterations
         as single-candidate bidders.
      4. EXACT [C, V] VALIDATION on each bidder's claimed node: true
         priority eligibility, V-length prefix sums, first-feasible
         prefix selection — the same selection rule as preempt_step
         restricted to one node (per-prefix violation counts need no
         separate pass: the first feasible prefix is the lexicographic
         minimum). A claim whose exact check fails (possible only via
         the optimistic fallback lane) is released; the bidder is
         marked tried until the next keep changes the state.

    p_prio/p_req/allowed/can_plain/n_plain: [C]/[C,R]/[C,N]/[C]/[C] in
    descending rank order; inactive bidders must arrive with allowed
    all-False and can_plain False. rank: [C] distinct claim-priority
    keys (defaults to 0..C-1, the descending-rank slot order). Returns
    (target [C] int32 (-1 = no claim), claimed [C] bool,
    takes_evict [C] bool, vidx_t [C, V] int32 — running-pod indices of
    each bidder's victim prefix, M at non-victim slots,
    freed_req [C, R] f32 — capacity the prefix frees,
    usage [C, GP] f32 — prefix evictions per PDB budget,
    could_bid [C] bool — False means the pod has NO placement or
    victim prefix at all (spent), as opposed to losing this round's
    node race (retry))."""
    nodes = snap.nodes
    N = nodes.valid.shape[0]
    M = evicted.shape[0]
    C = p_prio.shape[0]
    V = ctx.vvalid.shape[1]
    R = p_req.shape[1]
    BIG = jnp.int32(2**31 - 1)
    if rank is None:
        rank = jnp.arange(C, dtype=jnp.int32)
    ok_node = allowed & nodes.valid[None, :]

    # -- stage 1/2: bidder-independent tables + [C, N] node ranking ---------
    ev_nv = evicted[jnp.clip(ctx.vidx, 0, M - 1)] & ctx.vvalid
    base_elig = ctx.vvalid & ~ev_nv                          # [N, V]
    active = jnp.any(ok_node, axis=1) & ~can_plain
    # Bucket thresholds: quantiles of the ACTIVE bidders' priorities
    # (lower bounds, so each bidder's bucket is conservative for it).
    # No active bidder -> NaN thresholds -> empty tables -> the
    # optimistic lane (whose threshold is +inf) carries nothing either
    # since ok_node is all-False then.
    qs = jnp.linspace(0.0, 1.0, _PRIO_BUCKETS, endpoint=False)
    thr = jnp.nanquantile(jnp.where(active, p_prio, jnp.nan), qs)
    # Bidder -> bucket: largest b with thr[b] <= p_prio (NaN compares
    # False -> bucket 0, harmless: its table is empty too).
    bk = jnp.clip(
        jnp.sum((thr[None, :] <= p_prio[:, None]).astype(jnp.int32), axis=1)
        - 1, 0, _PRIO_BUCKETS - 1,
    )                                                        # [C]
    GP = snap.pdb_allowed.shape[0]
    if GP:
        run_pdb = snap.running.pdb_group
        consumed = jnp.zeros(GP, jnp.float32).at[
            jnp.clip(run_pdb, 0, None)
        ].add(
            (evicted & (run_pdb >= 0) & snap.running.valid).astype(
                jnp.float32
            )
        )
        remaining = snap.pdb_allowed - consumed              # [GP]
        has_pdb = ctx.vpdb >= 0                              # [N, V]
        tri = (
            jnp.arange(V)[:, None] >= jnp.arange(V)[None, :]
        )
        same_g = (
            (ctx.vpdb[:, :, None] == ctx.vpdb[:, None, :])
            & has_pdb[:, :, None] & tri[None]
        ).astype(jnp.float32)                                # [N, V, V]
        rem_nv = remaining[jnp.clip(ctx.vpdb, 0, None)]      # [N, V]
    else:
        remaining = jnp.zeros(0, jnp.float32)
    # Demand each node must free for each bidder (<= 0 in every
    # resource cannot happen on an allowed node of a non-plain bidder).
    need = used[None] + p_req[:, None, :] - nodes.allocatable[None]

    def node_rank(thr_b):
        """[C, N] (feasible, first-feasible cost, viols) against the
        victim subset eligible at priority threshold thr_b."""
        elig_b = base_elig & (
            ctx.vprio + cfg.qos.preemption_margin < thr_b
        )                                                    # [N, V]
        cum_req = jnp.cumsum(  # tpl: disable=TPL201(bucket-table node RANKING only: every claim gets the exact [C, V] validation below before it commits, so a rounding flip here costs a re-deal, never a bad placement)
            jnp.where(elig_b[..., None], ctx.vreq, 0.0), axis=1
        )                                                    # [N, V, R]
        cum_cost = jnp.cumsum(  # tpl: disable=TPL202(same ranking-only role as cum_req above — cost upper estimates ordering candidates; exact validation arbitrates)
            jnp.where(elig_b, ctx.vcost, 0.0), axis=1
        )                                                    # [N, V]
        if GP:
            eligp = (elig_b & has_pdb).astype(jnp.float32)
            wcnt = jnp.einsum("nvw,nw->nv", same_g, eligp)
            viol_b = elig_b & has_pdb & (wcnt > rem_nv)
        else:
            viol_b = jnp.zeros_like(elig_b)
        cum_viol = jnp.cumsum(viol_b.astype(jnp.float32), axis=1)
        # First-feasible slot: the compare+reduce form of a per-(c, n)
        # searchsorted; [C, N, V] compares fuse into the [C, N] sum
        # without materializing the old [C, N, V, R] f32 tableau.
        pos = jnp.zeros((C, N), jnp.int32)
        for r in range(R):
            pos = jnp.maximum(
                pos,
                jnp.sum(
                    (cum_req[None, :, :, r] < need[:, :, None, r]
                     ).astype(jnp.int32),
                    axis=2,
                ),
            )
        feas = jnp.all(
            need <= cum_req[None, :, V - 1, :], axis=-1
        )                                                    # [C, N]
        posc = jnp.clip(pos, 0, V - 1)
        cost = cum_cost[jnp.arange(N)[None, :], posc]        # [C, N]
        viol = cum_viol[jnp.arange(N)[None, :], posc]        # [C, N]
        return feas, cost, viol

    feas_t, cost_t, viol_t = [], [], []
    for b in range(_PRIO_BUCKETS):
        f, c_, v_ = node_rank(thr[b])
        feas_t.append(f)
        cost_t.append(c_)
        viol_t.append(v_)
    # Optimistic (priority-unaware) lane: thr = +inf admits every
    # victim; used for spent-marking and the fallback candidate.
    feas_opt, cost_opt, viol_opt = node_rank(jnp.float32(jnp.inf))

    def pick_bucket(stacked):
        return jnp.take_along_axis(
            jnp.stack(stacked), bk[None, :, None], axis=0
        )[0]

    feas = pick_bucket(feas_t)
    cost = pick_bucket(cost_t)
    viol = pick_bucket(viol_t)
    # Fallback: bucket tables see no feasible node but the optimistic
    # one does (a bidder whose margin sits between its bucket's lower
    # bound and its own priority) — rank by the optimistic tables and
    # let exact validation arbitrate.
    use_fb = (
        ~jnp.any(ok_node & feas, axis=1)
        & jnp.any(ok_node & feas_opt, axis=1)
    )[:, None]
    feas = jnp.where(use_fb, feas_opt, feas)
    cost = jnp.where(use_fb, cost_opt, cost)
    viol = jnp.where(use_fb, viol_opt, viol)
    viol_total = jnp.where(ok_node & feas, viol, jnp.inf)
    min_viol = jnp.min(viol_total, axis=1, keepdims=True)    # [C, 1]
    total = jnp.where(
        ok_node & feas & (viol_total == min_viol), cost, jnp.inf
    )
    K = min(k_cand, N)
    neg_v, cand_i = jax.lax.top_k(-total, K)                 # [C, K]
    cand_finite = jnp.isfinite(neg_v)
    # Plain bidders carry exactly one candidate: their scored node.
    first_col = (jnp.arange(K) == 0)[None, :]                # [1, K]
    cand_i = jnp.where(
        can_plain[:, None],
        jnp.where(first_col, n_plain[:, None], 0), cand_i,
    )
    cand_finite = jnp.where(can_plain[:, None], first_col, cand_finite)

    # Each iteration DEALS bidders across their candidate lists: the
    # bidder with active-rank r (its position, in rank order, among
    # bidders still unclaimed) bids its (r mod #available)-th cheapest
    # untaken candidate, and the lowest-rank bidder per node wins
    # (scatter-min). When candidate lists coincide — the load-balanced
    # cluster's common case, every bidder pricing the same cheap
    # victim prefixes — the deal hands out DISTINCT nodes and one
    # iteration claims min(C, K) nodes at once, reproducing the old
    # rank-ordered scan's assignment without its C sequential steps
    # (greedy per-iteration variants herded onto the shared-cheapest
    # node and claimed ~one node per iteration). Diverging lists cause
    # collisions; losers re-deal next iteration over the remaining
    # nodes.
    cand_c = jnp.clip(cand_i, 0, N - 1)

    def claim_it(state, _):
        taken, target, claimed = state
        avail = (
            cand_finite & ~taken[cand_c] & ~claimed[:, None]
        )                                                    # [C, K]
        csum = jnp.cumsum(avail.astype(jnp.int32), axis=1)
        navail = csum[:, -1]
        has = ~claimed & (navail > 0)
        r_active = jnp.cumsum(has.astype(jnp.int32)) - 1     # [C]
        tgt_cnt = jnp.mod(r_active, jnp.maximum(navail, 1)) + 1
        # Position of the tgt_cnt-th available candidate: csum is a
        # monotone int prefix count, so the index is just how many
        # prefix counts fall short — a [C, K] compare+reduce (a vmapped
        # searchsorted lowered to 512 tiny serial searches and cost
        # ~1 ms/iteration here).
        j = jnp.sum((csum < tgt_cnt[:, None]).astype(jnp.int32), axis=1)
        j = jnp.clip(j, 0, K - 1)
        want = cand_i[jnp.arange(C), j]
        want_c = jnp.clip(want, 0, N - 1)
        key = jnp.where(has, rank, BIG)
        best = jnp.full(N, BIG, jnp.int32).at[want_c].min(key)
        winner = has & (best[want_c] == rank)
        target = jnp.where(winner, want, target).astype(jnp.int32)
        claimed = claimed | winner
        taken = taken.at[want_c].max(winner)
        return (taken, target, claimed), None

    (_, target, claimed), _ = jax.lax.scan(
        claim_it,
        (jnp.zeros(N, bool), jnp.full(C, -1, jnp.int32),
         jnp.zeros(C, bool)),
        None, length=claim_iters,
    )
    # -- stage 4: EXACT [C, V] validation on the claimed node ---------------
    # True-priority eligibility, V-length prefix sums, first-feasible
    # prefix selection — preempt_step's selection rule restricted to
    # one node per bidder (prefix cost/viol/freed are nondecreasing and
    # fits is monotone in prefix length, so first-feasible IS the
    # lexicographic (viol, cost) minimum and no per-prefix violation
    # pass is needed). Everything downstream is [C, V]-sized off the
    # node-major table — no [C, N, V] or [C, M] materialization.
    tgt = jnp.clip(target, 0, N - 1)
    vvalid_x = ctx.vvalid[tgt]                               # [C, V]
    ev_x = evicted[jnp.clip(ctx.vidx[tgt], 0, M - 1)] & vvalid_x
    elig_x = vvalid_x & ~ev_x & (
        ctx.vprio[tgt] + cfg.qos.preemption_margin < p_prio[:, None]
    )                                                        # [C, V]
    wreq_x = jnp.cumsum(  # tpl: disable=TPL201(exact validation prefix at the FIXED V=16 victim cap, same op order as the sequential _tableau the oracle mirrors — parity-pinned; V is a compile-time constant, never padded)
        jnp.where(elig_x[..., None], ctx.vreq[tgt], 0.0), axis=1
    )                                                        # [C, V, R]
    fits_x = elig_x & jnp.all(
        used[tgt][:, None, :] - wreq_x + p_req[:, None, :]
        <= nodes.allocatable[tgt][:, None, :],
        axis=-1,
    )                                                        # [C, V]
    feas_x = jnp.any(fits_x, axis=1)
    # A claim whose exact check fails (reachable only through the
    # optimistic fallback lane — bucket-table feasibility is a sound
    # subset) is RELEASED.
    released = claimed & ~can_plain & ~feas_x
    claimed = claimed & (can_plain | feas_x)
    target = jnp.where(claimed, target, -1)
    takes_evict = claimed & ~can_plain
    best_pos = jnp.argmax(fits_x, axis=1).astype(jnp.int32)  # first feasible
    sel_v = (
        takes_evict[:, None] & elig_x
        & (jnp.arange(V, dtype=jnp.int32)[None, :] <= best_pos[:, None])
    )
    vidx_t = jnp.where(sel_v, ctx.vidx[tgt], M)              # [C, V]
    freed_req = jnp.sum(  # tpl: disable=TPL202(sum over the fixed V=16 victim cap — a compile-time constant axis, not the compacted pod axis; matches the capacity math of the sequential path)
        jnp.where(sel_v[..., None], ctx.vreq[tgt], 0.0), axis=1
    )                                                        # [C, R]
    if GP:
        vpdb_t = ctx.vpdb[tgt]                               # [C, V]
        usage = jnp.zeros((C, GP), jnp.float32).at[
            jnp.arange(C)[:, None], jnp.clip(vpdb_t, 0, None)
        ].add((sel_v & (vpdb_t >= 0)).astype(jnp.float32))
    else:
        usage = jnp.zeros((C, 0), jnp.float32)
    # Spent-marking uses the OPTIMISTIC answer — a pod the bucket
    # approximation under-serves is a deferral, not a retirement —
    # EXCEPT for a released fallback claim: that bidder's best
    # optimistic node just failed the exact check, and keeping it
    # could_bid would let phantom bidders occupy the C slots round
    # after round (claiming and releasing a node each time) while pods
    # ranked beyond C are never examined. Marking it tried retires it
    # for now; any later keep resets `tried` in _preempt_rounds, so it
    # re-bids as soon as evictions actually change the state.
    could_bid = can_plain | (
        jnp.any(ok_node & feas_opt, axis=1) & ~released
    )
    return target, claimed, takes_evict, vidx_t, freed_req, usage, could_bid
