"""Round-5 scratch: per-component device cost of the S>0 fast round."""
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
from tpusched.engine import _sat_tables
from tpusched.kernels import pairwise as kpair
from tpusched.kernels.assign import (
    NEG_INF,
    _deal_commit,
    _spread_waterfill_deal,
    batched_cycle,
    pick_node_batch,
    precompute_static,
)
from tpusched.synth import config3_pairwise

LO, HI = 2, 10


def slope(label, make_body, used0, reps=3):
    outs = {}
    for n in (LO, HI):
        fn = jax.jit(lambda u, n=n: jax.lax.fori_loop(0, n, make_body(), u))
        jax.block_until_ready(fn(used0))
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(used0))
            ts.append(time.perf_counter() - t0)
        outs[n] = min(ts)
    per = (outs[HI] - outs[LO]) / (HI - LO) * 1e3
    print(f"  {label}: {per:.2f}ms/iter  (LO={outs[LO]*1e3:.1f} "
          f"HI={outs[HI]*1e3:.1f})")


def main():
    pods = int(sys.argv[1]) if len(sys.argv) > 1 else 10_000
    nodes = int(sys.argv[2]) if len(sys.argv) > 2 else 5_000
    rng = np.random.default_rng(43)
    snap, _ = config3_pairwise(rng, pods, nodes)
    cfg = EngineConfig(mode="fast")
    snap = jax.device_put(snap)
    node_sat_t, member_sat_t = _sat_tables(snap)
    static = precompute_static(cfg, snap, node_sat_t, member_sat_t)
    st0 = kpair.pair_state_init(snap, static.sig_match)
    P = snap.pods.valid.shape[0]
    N = snap.nodes.valid.shape[0]
    print(f"P={P} N={N} S={snap.sigs.key.shape[0]} "
          f"C={snap.pods.ts_key.shape[1]} IT={snap.pods.ia_key.shape[1]}")
    used0 = snap.nodes.used
    rank = jnp.arange(P, dtype=jnp.int32)

    def cyc_body():
        def body(i, used):
            feasible, score, relaxed = batched_cycle(
                cfg, snap, static, used, st0, return_relaxed=True
            )
            return used + 1e-12 * score[0, 0]
        return body

    slope("batched_cycle [P,N]", cyc_body, used0)

    def pw_body():
        def body(i, used):
            sp_ok, sp_pen, ia_ok, ia_raw = kpair.pairwise_from_counts(
                snap, st0, static.aff_ok, sig_match=static.sig_match
            )
            return used + 1e-12 * sp_pen[0, 0] + 1e-12 * ia_raw[0, 0]
        return body

    slope("pairwise_from_counts", pw_body, used0)

    def wf_body():
        feasible, score, relaxed = batched_cycle(
            cfg, snap, static, used0, st0, return_relaxed=True
        )
        masked = jnp.where(feasible, score, NEG_INF)

        def body(i, used):
            cand, val, ok = _spread_waterfill_deal(
                snap, st0, used, relaxed, score,
                jnp.any(relaxed, axis=1), rank, 8,
            )
            return used + 1e-12 * val[0, 0]
        return body

    slope("_spread_waterfill_deal", wf_body, used0)

    def dc_body():
        feasible, score, relaxed = batched_cycle(
            cfg, snap, static, used0, st0, return_relaxed=True
        )
        masked = jnp.where(feasible, score, NEG_INF)
        allowed = jnp.any(feasible, axis=1)

        def body(i, used):
            u2, choice, val = _deal_commit(
                snap.nodes.allocatable, snap.pods.requests, used,
                feasible, masked, allowed, rank, 8, cum_width=P,
            )
            return used + 1e-12 * val[0]
        return body

    slope("_deal_commit [P,N]", dc_body, used0)

    def commit_body():
        def body(i, used):
            choice = jnp.full(P, -1, jnp.int32).at[:64].set(0)
            st2 = kpair.pair_state_commit(
                snap, st0, static.sig_match, choice, choice >= 0
            )
            val = kpair.pairwise_from_counts(
                snap, st2, static.aff_ok, sig_match=static.sig_match,
                exclude_self_node=choice,
            )
            return used + 1e-12 * val[1][0, 0]
        return body

    slope("commit+validate pass", commit_body, used0)


if __name__ == "__main__":
    main()
