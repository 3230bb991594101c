"""Multi-tenant batched solving (SURVEY.md §2.3 "EP" row).

The expert-parallel analogue in this domain is routing INDEPENDENT
scheduling problems to solver shards. A sidecar serving many clusters
(or many isolated tenants of one control plane) holds B snapshots with
no cross-tenant interaction — exactly a batch dimension:

  * stack_snapshots: B bucket-aligned ClusterSnapshots -> one pytree
    with a leading tenant axis;
  * solve_many: jax.vmap of the SAME solve kernels over that axis —
    one compiled program schedules every tenant simultaneously,
    saturating a chip that a single small cluster would leave idle;
  * the tenant axis shards over the mesh's 'p' axis (tenant_sharding),
    routing whole tenants to devices — no cross-device communication at
    all, the cheapest collective there is.

Alignment requirement: all tenants must share identical bucket shapes —
build them with one explicit `Buckets` floor (the same discipline the
serving sidecar already uses to pin compile shapes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as PS

from tpusched.config import EngineConfig
from tpusched.mesh import POD_AXIS
from tpusched.engine import solve_core
from tpusched.snapshot import ClusterSnapshot


def zipf_weights(n: int, skew: float) -> np.ndarray:
    """Normalized Zipf weights over n tenants: w_r ∝ 1 / rank^skew.

    THE tenant-skew definition shared across the codebase — the sim's
    workload generators (tpusched/sim/workloads.py draws each pod's
    tenant from these weights) and any serving-path tenant-fairness
    weighting must read it from here, so "tenant 0 gets X% of traffic"
    means the same thing in a trace-driven sim run and on the serving
    path. skew=0 is uniform; the Borg/Azure trace analyses this
    reproduces (Resource Central, SOSP'17) put subscription skew around
    1.0-1.4."""
    if n < 1:
        raise ValueError(f"zipf_weights: n={n} must be >= 1")
    w = 1.0 / np.power(np.arange(1, n + 1, dtype=np.float64),
                       max(float(skew), 0.0))
    return w / w.sum()


def stack_snapshots(snaps: list[ClusterSnapshot]) -> ClusterSnapshot:
    """Stack bucket-aligned snapshots along a new leading tenant axis.
    Raises if any leaf shapes disagree (different buckets)."""
    if not snaps:
        raise ValueError("no snapshots to stack")
    first = jax.tree.leaves(snaps[0])
    for i, s in enumerate(snaps[1:], 1):
        for a, b in zip(first, jax.tree.leaves(s)):
            if np.shape(a) != np.shape(b):
                raise ValueError(
                    f"tenant {i} bucket shapes differ: {np.shape(b)} vs "
                    f"{np.shape(a)} — build all tenants with one explicit "
                    "Buckets floor"
                )
    return jax.tree.map(lambda *xs: jnp.stack(xs), *snaps)


def _solve_one(cfg: EngineConfig, snap: ClusterSnapshot):
    a, c, u, o, _, rounds, ev, _ = solve_core(cfg, snap)
    return a, c, u, o, rounds, ev


def solve_many(cfg: EngineConfig, stacked: ClusterSnapshot):
    """Solve B independent tenants at once: returns per-tenant
    (assignment [B, P], chosen [B, P], used [B, N, R], order [B, P],
    rounds [B], evicted [B, M]). jit/vmap-compiled; call through
    solve_many_jit for compile caching."""
    return jax.vmap(lambda s: _solve_one(cfg, s))(stacked)


_JIT_CACHE: dict[str, object] = {}
#: Distinct configs the memo holds before OLDEST-FIRST eviction kicks
#: in (TPL104, ISSUE 14): repr-keyed means config churn would
#: otherwise grow one compiled program per variant forever.
_JIT_CACHE_CAP = 8


def solve_many_jit(cfg: EngineConfig):
    """Jitted entry closed over the config (compile-time constants);
    memoized so repeated calls share one jit/compile cache. Keyed by
    repr (EngineConfig is frozen but holds a dict field, so it is not
    hashable; its repr is deterministic and value-complete)."""
    key = repr(cfg)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        while len(_JIT_CACHE) >= _JIT_CACHE_CAP:
            # Evict oldest-first: a wholesale clear() would turn
            # steady-state config diversity just past the cap into a
            # periodic full-recompile storm. Race-tolerant: a
            # concurrent miss may drain the dict between the len
            # check and the pop (default-pop swallows the lost key;
            # StopIteration/RuntimeError mean someone else evicted).
            try:
                _JIT_CACHE.pop(next(iter(_JIT_CACHE)), None)
            except (StopIteration, RuntimeError):
                break
        fn = jax.jit(lambda stacked: solve_many(cfg, stacked))
        _JIT_CACHE[key] = fn
    return fn


def tenant_sharding(mesh, stacked: ClusterSnapshot):
    """NamedShardings putting the TENANT axis on the mesh's 'p' axis:
    whole problems route to devices, zero cross-device collectives."""
    return jax.tree.map(
        lambda _: NamedSharding(mesh, PS(POD_AXIS)), stacked
    )
