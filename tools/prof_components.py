"""Round-5 scratch: per-component device cost of the fast preemption
round at the headline shape, measured as fori_loop slope (the per-call
fixed cost cancels out of the slope).

Round 16 (warm-start, ROADMAP item 3): `--warm [churn ...]` profiles the
warm path instead — per-cycle dirty row counts (pods / node columns /
member columns) and warm vs cold solve walls at each churn level, the
numbers a source edit used to be required for (the retained _tableau_nv
slope above serves the same purpose for the preemption tableau).

Round 17 (frontier compaction, ISSUE 12): `--rounds [preset]` profiles
WHERE the commit rounds spend their time — solve with the round cap at
sampled values, diff the walls into per-round cost, and read the
placed/pending (= next round's frontier) counts at each cap, with the
compacted and full-width engines side by side. This is the evidence
trail for the compaction claim the same way `--warm` validated the
tableau: late rounds carry tiny frontiers, so their wall should track
the [cap, N] view, not [P, N]. preset: pairwise (default) | preempt.

Round 25 (ISSUE 20, device queue): `--queue` profiles the pending-queue
cost model — DeviceQueue.window() host wall vs backlog depth Q next to
the host-sorted baseline's O(Q log Q) recompute+sort, plus a cProfile
pass showing WHERE each arm's sort work lives: the device arm's only
Python-level sort is the O(arrivals) dirty-index sort in _flush; the
queue re-sort itself is absent from the host profile (it runs
in-kernel over the bounded table).

    python tools/prof_components.py 10000 5000
    python tools/prof_components.py 10000 5000 --warm
    python tools/prof_components.py 2000 500 --rounds preempt
    PROF_CPU=1 python tools/prof_components.py 2000 1000 --warm
    PROF_CPU=1 python tools/prof_components.py --queue
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import time

import numpy as np

if os.environ.get("PROF_CPU"):
    import jax as _jax

    _jax.config.update("jax_platforms", "cpu")

import jax
import jax.numpy as jnp

from tpusched.config import EngineConfig
from tpusched.kernels import assign as kassign
from tpusched.kernels import preempt as kpreempt
from tpusched.kernels.assign import (
    _deal_commit, pod_cycle, precompute_static, NEG_INF,
)
from tpusched.engine import _sat_tables
from tpusched.kernels import pairwise as kpair
from tpusched.qos import effective_priority
from tpusched.synth import config5_preemption

LO, HI = 2, 18


def slope(label, make_body, used0, reps=3):
    """make_body() -> body(i, used) -> used; time fori(LO) vs fori(HI)."""
    outs = {}
    for n in (LO, HI):
        fn = jax.jit(
            lambda u, n=n: jax.lax.fori_loop(0, n, make_body(), u)
        )
        jax.block_until_ready(fn(used0))
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(used0))
            ts.append(time.perf_counter() - t0)
        outs[n] = min(ts)
    per = (outs[HI] - outs[LO]) / (HI - LO) * 1e3
    print(f"  {label}: {per:.2f}ms/iter  (LO={outs[LO]*1e3:.1f}ms "
          f"HI={outs[HI]*1e3:.1f}ms)")


def prof_warm(pods: int, nodes: int,
              churns=(0.001, 0.01, 0.1), cycles: int = 5):
    """Per-cycle warm-path profile: dirty row counts + warm solve wall
    vs the cold packed solve on the same lineage."""
    from tpusched.device_state import DeviceSnapshot
    from tpusched.engine import Engine
    from tpusched.synth import make_cluster

    rng = np.random.default_rng(11)
    nodes_r, pods_r, running_r = make_cluster(
        rng, pods, nodes, n_running_per_node=1, with_qos=True,
        as_records=True,
    )
    cfg = EngineConfig(mode="fast")
    ds = DeviceSnapshot(cfg)
    ds.full_load(nodes_r, pods_r, running_r)
    eng = Engine(cfg)
    try:
        t0 = time.perf_counter()
        np.asarray(eng._solve_packed_jit(ds.snap))
        print(f"cold compile+first-run {time.perf_counter() - t0:.1f}s")
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(eng._solve_packed_jit(ds.snap))
            ts.append(time.perf_counter() - t0)
        cold_ms = min(ts) * 1e3
        t0 = time.perf_counter()
        eng.solve_warm(ds)
        print(f"warm first run (cold tableau build) "
              f"{time.perf_counter() - t0:.1f}s; cold solve "
              f"{cold_ms:.1f}ms")
        P = len(pods_r)
        for frac in churns:
            k = max(1, min(P, int(round(frac * P))))
            rngc = np.random.default_rng(int(frac * 1e6) + 3)
            print(f"-- churn {frac:g} ({k} pods/cycle)")
            for cyc in range(cycles):
                picks = rngc.choice(P, size=k, replace=False)
                ups = []
                for i in picks:
                    rec = pods_r[int(i)]
                    rec["observed_avail"] = float(rngc.uniform(0.3, 1.0))
                    ups.append(rec)
                t0 = time.perf_counter()
                ds.apply(upsert_pods=ups)
                apply_ms = (time.perf_counter() - t0) * 1e3
                warm_before = ds.warm_solves
                t0 = time.perf_counter()
                eng.solve_warm(ds)
                solve_ms = (time.perf_counter() - t0) * 1e3
                dp, dn, dm = ds.last_warm_rows
                path = "warm" if ds.warm_solves > warm_before else "cold"
                print(f"  cycle {cyc}: rows pods={dp} nodes={dn} "
                      f"members={dm} apply={apply_ms:.1f}ms "
                      f"solve={solve_ms:.1f}ms ({path}; cold ref "
                      f"{cold_ms:.1f}ms)")
        print(f"paths: warm={ds.warm_solves} cold={ds.cold_solves} "
              f"reasons={ds.warm_cold_reasons}")
    finally:
        eng.close()


def prof_rounds(pods: int, nodes: int, preset: str = "pairwise",
                caps=(1, 2, 4, 8, 16, 32, 64), reps: int = 3):
    """Per-round wall / frontier-size profile (see module docstring).
    Each sampled cap is a separate compile (max_rounds is a trace-time
    constant), so this is a profiling tool, not a bench."""
    from tpusched.engine import Engine
    from tpusched.synth import config3_pairwise, config5_preemption

    rng = np.random.default_rng(13)
    if preset == "preempt":
        snap, _ = config5_preemption(rng, n_pods=pods, n_nodes=nodes)
        base = dict(mode="fast", preemption=True)
    else:
        snap, _ = config3_pairwise(rng, pods, nodes)
        base = dict(mode="fast")
    snap = jax.device_put(snap)
    P = int(snap.pods.valid.shape[0])

    def measure(cfg_kw, cap):
        eng = Engine(EngineConfig(max_rounds=cap, **cfg_kw))
        try:
            res = eng.unpack(snap, eng._solve_packed_jit(snap))  # compile
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                np.asarray(eng._solve_packed_jit(snap))
                ts.append(time.perf_counter() - t0)
            placed = int((res.assignment >= 0).sum())
            pend = int((res.assignment < 0).sum())
            return min(ts) * 1e3, placed, pend, int(res.rounds)
        finally:
            eng.close()

    print(f"preset={preset} P={P} N={snap.nodes.valid.shape[0]} "
          f"(walls are min of {reps}; per-round = wall delta / cap "
          "delta; pending@cap is the frontier the NEXT round pays for)")
    print(f"{'cap':>5} {'compact_ms':>11} {'full_ms':>9} {'d_ms/rnd':>9} "
          f"{'placed':>7} {'pending':>8} {'rounds':>7}")
    prev = None
    for cap in caps:
        w_c, placed, pend, rounds = measure(base, cap)
        w_f, _, _, _ = measure({**base, "compact_cap": 0}, cap)
        per = ""
        if prev is not None and cap > prev[0]:
            per = f"{(w_c - prev[1]) / (cap - prev[0]):.2f}"
        print(f"{cap:>5} {w_c:>11.1f} {w_f:>9.1f} {per:>9} "
              f"{placed:>7} {pend:>8} {rounds:>7}")
        prev = (cap, w_c)
        if pend == 0 and rounds < cap:
            print(f"  fixpoint at {rounds} rounds; stopping the sweep")
            break


def prof_queue(depths=(1024, 4096, 16384), w: int = 256,
               batch: int = 256, reps: int = 5):
    """Pending-queue cost-model profile (see module docstring). Walls
    are host-blocking time per window() call: the device arm pays a
    near-flat dispatch+transfer cost (the rank/sort runs in-kernel),
    the host-sorted baseline pays the O(Q) recompute + O(Q log Q)
    sort every cycle."""
    import cProfile
    import pstats

    from bench import _HostSortedQueue
    from tpusched.device_state import DeviceQueue

    def fill(q, n):
        r = np.random.default_rng(5)
        for i in range(n):
            q.upsert(f"q{i:06d}",
                     base_priority=float(r.uniform(10.0, 100.0)),
                     slo_target=float(r.uniform(0.5, 0.999)),
                     submitted=float(i) * 1e-3)

    def tmin(fn):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return min(ts) * 1e3

    now = float(max(depths)) * 1e-3 + 60.0
    print(f"w={w} arrivals/cycle={batch} (walls are min of {reps}; "
          f"arrive+win = {batch} upserts + scatter + window, the real "
          "per-cycle host bill)")
    print(f"{'Q':>7} {'dev_window_ms':>14} {'dev_arrive+win_ms':>18} "
          f"{'host_sort_ms':>13} {'host/dev':>9}")
    last = None
    for Q in depths:
        dq = DeviceQueue(capacity=Q)
        fill(dq, Q - batch)
        dq.window(now, w)      # compile + settle at this capacity
        t_win = tmin(lambda: dq.window(now, w))

        k = [0]

        def cycle():
            k[0] += 1
            names = [f"a{k[0]:03d}-{j:04d}" for j in range(batch)]
            for j, nm in enumerate(names):
                dq.upsert(nm, base_priority=50.0, slo_target=0.9,
                          submitted=now - float(j))
            dq.window(now, w)
            dq.remove(names)

        cycle()                # settle the arrival shapes
        t_cyc = tmin(cycle)

        hq = _HostSortedQueue(bound=None)
        fill(hq, Q)
        t_host = tmin(lambda: hq.window(now, w))
        print(f"{Q:>7} {t_win:>14.2f} {t_cyc:>18.2f} {t_host:>13.2f} "
              f"{t_host / max(t_cyc, 1e-9):>9.2f}")
        last = (dq, hq, cycle)

    # -- where does the sort live? ----------------------------------------
    dq, hq, cycle = last

    def sort_rows(fn, n=5):
        pr = cProfile.Profile()
        pr.enable()
        for _ in range(n):
            fn()
        pr.disable()
        rows = []
        for (f, _l, name), (cc, nc, tt, ct, _cal) in \
                pstats.Stats(pr).stats.items():
            if "sort" in name:
                rows.append((nc, tt * 1e3, name))
        return sorted(rows, key=lambda r: -r[1])

    Q = depths[-1]
    print(f"\ncProfile over 5 cycles at Q={Q}: Python-level sort work")
    for arm, rows in (("device", sort_rows(cycle)),
                      ("hostsort", sort_rows(lambda: hq.window(now, w)))):
        if not rows:
            print(f"  {arm}: none")
        for nc, tt, name in rows:
            print(f"  {arm}: {name}  calls={nc} tottime={tt:.2f}ms")
    print("the device arm's only sort is the O(arrivals) dirty-index "
          "sort in _flush; the O(Q log Q) backlog re-sort exists only "
          "in the hostsort arm's profile")


def main():
    argv = [a for a in sys.argv[1:]
            if a not in ("--warm", "--rounds", "--queue")]
    warm = "--warm" in sys.argv[1:]
    rounds_mode = "--rounds" in sys.argv[1:]
    queue_mode = "--queue" in sys.argv[1:]
    # Integer operands are the shape; float operands (only meaningful
    # with --warm) override the churn sweep levels; a bare word after
    # --rounds picks the preset.
    ints, churns, words = [], [], []
    for a in argv:
        try:
            ints.append(int(a))
        except ValueError:
            try:
                churns.append(float(a))
            except ValueError:
                words.append(a)
    pods = ints[0] if len(ints) > 0 else 10_000
    nodes = ints[1] if len(ints) > 1 else 5_000
    if queue_mode:
        # Integer operands become the depth sweep (default 1k/4k/16k).
        prof_queue(depths=tuple(ints) or (1024, 4096, 16384))
        return
    if rounds_mode:
        prof_rounds(pods, nodes, preset=(words[0] if words else "pairwise"))
        return
    if warm:
        prof_warm(pods, nodes,
                  churns=tuple(churns) or (0.001, 0.01, 0.1))
        return
    rng = np.random.default_rng(7)
    snap, _ = config5_preemption(rng, n_pods=pods, n_nodes=nodes)
    cfg = EngineConfig(mode="fast", preemption=True)
    snap = jax.device_put(snap)
    node_sat_t, member_sat_t = _sat_tables(snap)
    static = precompute_static(cfg, snap, node_sat_t, member_sat_t)
    pctx = jax.jit(lambda s: kpreempt.precompute_nv(cfg, s, kassign._PREEMPT_VICTIM_CAP))(snap)
    P = snap.pods.valid.shape[0]
    N = snap.nodes.valid.shape[0]
    M = snap.running.valid.shape[0]
    C = kassign._PREEMPT_BATCH
    print(f"P={P} N={N} M={M} C={C} GP={snap.pdb_allowed.shape[0]}")
    prio = effective_priority(
        cfg, snap.pods.base_priority, snap.pods.slo_target,
        snap.pods.observed_avail,
    )
    used0 = snap.nodes.used
    st0 = kpair.pair_state_init(snap, static.sig_match)
    evicted = jnp.zeros(M, bool)
    sel = jnp.arange(C, dtype=jnp.int32)
    reqs = snap.pods.requests[sel]

    def tableau_body():
        def body(i, used):
            out = kpreempt._tableau_nv(
                cfg, snap, pctx, prio[sel], reqs, used, evicted
            )
            return used + 1e-12 * out[-1][0, 0]
        return body

    slope("_tableau_nv [C,N,V]", tableau_body, used0)

    def topk_body():
        def body(i, used):
            total = jnp.sum(used, axis=1)[None, :] + prio[sel][:, None]
            neg_v, cand_i = jax.lax.top_k(-total, 256)
            return used + 1e-12 * (neg_v[0, 0] + cand_i[0, 0])
        return body

    slope("top_k k=256 [C,N]", topk_body, used0)

    def podcycle_body():
        def body(i, used):
            def one(p):
                feasible, score, allowed = pod_cycle(
                    cfg, snap, static, p, used, st0
                )
                masked = jnp.where(feasible, score, NEG_INF)
                return jnp.max(masked)
            mx = jax.vmap(one)(sel)
            return used + 1e-12 * mx[0]
        return body

    slope("vmap pod_cycle [C,N]", podcycle_body, used0)

    def auction_body():
        allowed = jnp.ones((C, N), bool) & snap.nodes.valid[None, :]

        def body(i, used):
            can_plain = jnp.zeros(C, bool)
            n_plain = jnp.zeros(C, jnp.int32)
            target, claimed, takes_evict, evict_m, could_bid = (
                kpreempt.preempt_auction(
                    cfg, snap, pctx, prio[sel], reqs, allowed, used,
                    evicted, can_plain, n_plain, rank=sel,
                )
            )
            return used + 1e-12 * target[0]
        return body

    slope("preempt_auction full", auction_body, used0)

    def dc_body():
        feas = jnp.ones((C, N), bool) & snap.nodes.valid[None, :]

        def body(i, used):
            masked = jnp.where(feas, 1.0 + 1e-9 * used[0, 0], NEG_INF)
            u2, choice, val = _deal_commit(
                snap.nodes.allocatable, reqs, used, feas, masked,
                jnp.ones(C, bool), sel, 8, cum_width=C,
            )
            return used + 1e-12 * choice[0]
        return body

    slope("_deal_commit [C,N]", dc_body, used0)


if __name__ == "__main__":
    main()
