#!/usr/bin/env python
"""padcheck: the runtime refuter for the kernel dataflow ledger
(round 20, ISSUE 15 — the PR 14 lock-order-witness pattern applied to
tools/reduction_ledger.json).

The static analysis (tpusched/lint/kernelflow.py) CLAIMS, per
reduction site, whether the result is exact in any reduction tree and
invariant under padding of the reduced axis. This tool checks those
claims against reality: every ledger site's enclosing kernel is
executed differentially — the SAME logical cluster built at the base
bucket widths and at two padded widths (2x and 4x the pod/node/member
buckets: two view widths, two pad amounts) — and the real-row outputs
must agree BITWISE. A divergence in a harness whose reachable ledger
sites are all exact-marked means the analysis mis-marked a site:
padcheck fails. A divergence in a harness that reaches hazard-marked
(suppressed) sites would merely confirm the hazard; no such divergence
occurs on this CPU backend at these shapes, which is also worth
knowing — the hazards are LATENT (tree-shape) risks for sharding, not
live CPU bugs.

Coverage is transitive: a harness declares its entry kernels and the
kernelflow call graph closes over everything they reach, so eight
harnesses cover every site in the ledger. A site whose root no harness
reaches fails the run (no silent coverage holes).

Round 22 (ISSUE 17, sharded serving) adds the MESH differential: the
ledger's SHARDING column claims, per site, which reduction trees stay
exact once an axis is device-sharded. The mesh harness runs the same
ledger-covered kernels through a mesh engine on the (2,1) and (1,2)
device meshes — each snapshot axis actually split across devices, one
at a time — and the real rows must agree BITWISE with the dense
single-device run. It executes in a subprocess with a forced
2-virtual-device CPU platform (the parent may have initialised jax
with one device, and platforms cannot be swapped after init — the same
re-exec trick as __graft_entry__.dryrun_multichip).

Run it:

  python tools/padcheck.py            # all harnesses + coverage gate
                                      # + the mesh differential
  python tools/padcheck.py --self-test  # prove the refuter CAN catch a
                                        # seeded hazardous kernel
  python tools/padcheck.py --list     # harness -> covered roots table
  python tools/padcheck.py --mesh-only  # just the mesh differential
                                        # (needs >= 2 jax devices)

Exits non-zero on any divergence-in-exact, uncovered site, mesh
divergence, or self-test miss. Emits JSON metric lines
(padcheck_sites_total / padcheck_divergences_total /
padcheck_mesh_divergences_total, all lower-better) so analyzer
coverage can be tracked across changes.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

REPO_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO_ROOT))

import numpy as np  # noqa: E402

from tpusched.lint import kernelflow  # noqa: E402
from tpusched.lint.interproc import scan_product_sources  # noqa: E402

#: Pad multipliers: "two view widths / two pad amounts" — the same
#: logical cluster at 2x and 4x the fitted pod/node/member buckets.
PAD_MULTIPLIERS = (2, 4)


# ---------------------------------------------------------------------------
# The differential executor (also the library API the kernelflow tests
# drive against the seeded hazardous fixture).
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DiffResult:
    name: str
    diverged: bool
    detail: str = ""
    #: the multiplier-1 outputs (so callers can run sanity predicates
    #: without paying a fourth full execution).
    base: "Dict[str, np.ndarray] | None" = None


def bitwise_equal(a: np.ndarray, b: np.ndarray) -> bool:
    """Byte-level equality (NaNs equal themselves; -0.0 != 0.0 — the
    ledger's exactness claims are about BITS, not values)."""
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape or a.dtype != b.dtype:
        return False
    return bool(np.array_equal(
        a.view(np.uint8) if a.dtype.kind == "f" else a,
        b.view(np.uint8) if b.dtype.kind == "f" else b,
    ))


def diff_run(name: str,
             run: Callable[[int], Dict[str, np.ndarray]],
             multipliers: Iterable[int] = PAD_MULTIPLIERS) -> DiffResult:
    """Execute `run(multiplier)` at 1 and at each pad multiplier; the
    returned {output name: real-rows array} dicts must agree bitwise.
    `run` is responsible for slicing its outputs down to REAL rows —
    padding must be invisible, that is the whole claim under test."""
    base = {k: np.asarray(v) for k, v in run(1).items()}
    for m in multipliers:
        padded = run(m)
        for key, ref in base.items():
            got = np.asarray(padded[key])
            if not bitwise_equal(ref, got):
                where = ""
                if ref.shape == got.shape and ref.dtype == got.dtype:
                    bad = np.nonzero(
                        ref.reshape(-1) != got.reshape(-1))[0][:4]
                    where = f" first diffs at flat {bad.tolist()}"
                return DiffResult(
                    name, True,
                    f"output {key!r} diverged at pad x{m}{where}",
                    base=base)
    return DiffResult(name, False, base=base)


# ---------------------------------------------------------------------------
# Cluster builders (seeded; the SnapshotBuilder pads to the bucket
# widths, so a multiplier IS the pad amount).
# ---------------------------------------------------------------------------


def _build(kind: str, mult: int, cfg: Any) -> Tuple[Any, Any, int, int]:
    """(snapshot, meta, n_pods, n_running) for one preset at one bucket
    multiplier. Same seed at every multiplier -> same logical cluster,
    different pad widths."""
    import dataclasses as dc

    from tpusched.config import Buckets
    from tpusched.synth import make_cluster

    presets: Dict[str, Dict[str, Any]] = {
        "sig": dict(
            n_pods=28, n_nodes=10, spread_frac=0.4, interpod_frac=0.4,
            run_anti_frac=0.25, taint_frac=0.15, toleration_frac=0.2,
            selector_frac=0.2, cordon_frac=0.1, namespace_count=2,
            gang_frac=0.25, gang_size=2, initial_utilization=0.5,
            n_running_per_node=2,
        ),
        "preempt": dict(
            n_pods=24, n_nodes=8, initial_utilization=0.85,
            n_running_per_node=3, pdb_frac=0.3, tight_utilization=True,
            spread_frac=0.2, interpod_frac=0.2, run_anti_frac=0.1,
        ),
        "plain": dict(
            n_pods=24, n_nodes=10, taint_frac=0.1, toleration_frac=0.2,
            initial_utilization=0.6, n_running_per_node=2,
        ),
    }
    kw = presets[kind]
    seed = {"sig": 11, "preempt": 13, "plain": 17}[kind]
    n_run = kw["n_nodes"] * kw.get("n_running_per_node", 0)
    bk = Buckets.fit(kw["n_pods"], kw["n_nodes"], n_run)
    bk = dc.replace(bk, pods=bk.pods * mult, nodes=bk.nodes * mult,
                    running_pods=bk.running_pods * mult)
    snap, meta = make_cluster(
        np.random.default_rng(seed), config=cfg, buckets=bk, **kw)
    return snap, meta, kw["n_pods"], n_run


def _solve_outputs(res: Any, P: int, M: int, N: int) -> Dict[str, Any]:
    return {
        "assignment": np.asarray(res.assignment)[:P],
        "chosen_score": np.asarray(res.chosen_score)[:P],
        "evicted": np.asarray(res.evicted)[:M],
    }


# ---------------------------------------------------------------------------
# Harnesses. `entries` are the kernel-scope functions the harness
# invokes (directly or through Engine); coverage closes over the
# kernelflow call graph from there.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Harness:
    name: str
    entries: Tuple[str, ...]
    run: Callable[[int], Dict[str, np.ndarray]]
    #: sanity predicate on the BASE run's outputs: a harness that never
    #: exercises its path (no evictions fired) proves nothing.
    sanity: Optional[Callable[[Dict[str, np.ndarray]], str]] = None


def _harnesses() -> List[Harness]:
    from tpusched import Engine, EngineConfig
    from tpusched.engine import _sat_tables
    from tpusched.kernels import assign as kassign
    from tpusched.kernels import explain as kexplain
    from tpusched.kernels import pairwise as kpair
    from tpusched.kernels import preempt as kpreempt

    out: List[Harness] = []

    def solve_runner(kind: str, cfg_kw: Dict[str, Any]):
        def run(mult: int) -> Dict[str, np.ndarray]:
            from tpusched.config import EngineConfig as EC
            cfg = EC(**cfg_kw)
            snap, _meta, P, M = _build(kind, mult, cfg)
            eng = Engine(cfg)
            try:
                res = eng.solve(snap)
            finally:
                eng.close()
            return _solve_outputs(res, P, M, 0)
        return run

    # 1. The sig-path fast solve, compacted program forced (explicit
    # cap) so _pods_view / the compacted round loop execute.
    out.append(Harness(
        "solve_fast_sig",
        ("solve_rounds", "precompute_static", "atom_sat"),
        solve_runner("sig", dict(mode="fast", compact_cap=8)),
        sanity=lambda o: "" if (o["assignment"] >= 0).any()
        else "nothing placed",
    ))
    # 2. The preemption auction rounds (evictions must actually fire).
    out.append(Harness(
        "solve_fast_preempt",
        ("solve_rounds",),
        solve_runner("preempt", dict(mode="fast", preemption=True,
                                     compact_cap=8)),
        sanity=lambda o: "" if o["evicted"].any()
        else "preemption never fired",
    ))
    # 3. The sequential parity path incl. inline PostFilter.
    out.append(Harness(
        "solve_parity_preempt",
        ("solve_sequential",),
        solve_runner("preempt", dict(mode="parity", preemption=True)),
        sanity=lambda o: "" if o["evicted"].any()
        else "preemption never fired",
    ))

    # 4. The ScoreBatch surface: full [P, N] feasibility + scores.
    def run_score(mult: int) -> Dict[str, np.ndarray]:
        import jax
        import jax.numpy as jnp
        from tpusched.config import EngineConfig as EC
        cfg = EC(mode="fast")
        snap, _meta, P, _M = _build("sig", mult, cfg)
        snap = jax.tree.map(jnp.asarray, snap)
        N = 10
        nst, mst = _sat_tables(snap)
        feasible, score = kassign.score_batch(cfg, snap, nst, mst)
        return {"feasible": np.asarray(feasible)[:P, :N],
                "score": np.asarray(score)[:P, :N]}

    out.append(Harness("score_batch", ("score_batch",), run_score))

    # 5. The incremental warm rounds: carry = the cold assignment,
    # a dirty frontier, compacted at an explicit cap.
    def run_inc(mult: int) -> Dict[str, np.ndarray]:
        import jax
        import jax.numpy as jnp
        from tpusched.config import EngineConfig as EC
        cfg = EC(mode="fast", compact_cap=8)
        snap, _meta, P, _M = _build("sig", mult, cfg)
        eng = Engine(cfg)
        try:
            cold = eng.solve(snap)
        finally:
            eng.close()
        snap = jax.tree.map(jnp.asarray, snap)
        nst, mst = _sat_tables(snap)
        tab = kassign.build_tableau(cfg, snap, nst, mst)
        Pb = snap.pods.valid.shape[0]
        carry = np.full(Pb, -1, np.int32)
        carry[:P] = np.asarray(cold.assignment)[:P]
        chosen = np.full(Pb, -np.inf, np.float32)
        chosen[:P] = np.asarray(cold.chosen_score)[:P]
        frontier = np.zeros(Pb, bool)
        frontier[: max(2, P // 8)] = True  # dirty basis: first pods
        res = kassign.solve_incremental(
            cfg, snap, tab, jnp.asarray(carry), jnp.asarray(chosen),
            jnp.asarray(frontier), None, cap=8,
        )
        assigned, chosen_o, _used, _order, _ro, _r, _ev, _rv, audit = res
        return {
            "assignment": np.asarray(assigned)[:P],
            "chosen_score": np.asarray(chosen_o)[:P],
            "audit": np.asarray(audit),
        }

    out.append(Harness("solve_incremental", ("solve_incremental",
                                             "build_tableau"), run_inc))

    # 6. The explain probe (decision provenance buffer).
    def run_explain(mult: int) -> Dict[str, np.ndarray]:
        import jax
        import jax.numpy as jnp
        from tpusched.config import EngineConfig as EC
        cfg = EC(mode="fast")
        snap, _meta, P, _M = _build("sig", mult, cfg)
        snap = jax.tree.map(jnp.asarray, snap)
        nst, mst = _sat_tables(snap)
        buf = kexplain.explain_probe(cfg, snap, nst, mst, k=3)
        arr = np.asarray(buf)
        # The probe layout scales with the BUCKET sizes (sections are
        # [P_bucket]-major), so across widths only the first section's
        # real-pod rows line up at the same offsets — compare those.
        return {"head": arr[:P]}

    out.append(Harness("explain_probe", ("explain_probe",), run_explain))

    # 7. The ring/blockwise pairwise counting vs the dense path, on a
    # single-device 'p' ring (the layout the ring path exists for).
    def run_ring(mult: int) -> Dict[str, np.ndarray]:
        import jax
        import jax.numpy as jnp
        from tpusched.config import EngineConfig as EC
        from tpusched.mesh import make_mesh
        cfg = EC(mode="fast")
        snap, _meta, _P, _M = _build("sig", mult, cfg)
        snap = jax.tree.map(jnp.asarray, snap)
        nst, mst = _sat_tables(snap)
        del nst
        mesh = make_mesh((1, 1))
        Pb = snap.pods.valid.shape[0]
        assigned = jnp.full(Pb, -1, jnp.int32)
        from tpusched.ring import ring_sig_counts
        ring = ring_sig_counts(snap, mst, assigned, mesh)
        sm = kpair.sig_member_match(snap, mst)
        dense = kpair.sig_counts(snap, sm, assigned)
        S, N = 8, 10
        return {"ring": np.asarray(ring)[:S, :N],
                "dense": np.asarray(dense)[:S, :N]}

    out.append(Harness("ring_counts", ("ring_sig_counts", "sig_counts"),
                       run_ring))

    # 8. The device-resident pending queue (ISSUE 20): full ranking,
    # the top-kb window slice, and the numpy host oracle. Padding IS
    # the table's natural regime — a bigger pow2 capacity means more
    # invalid slots — so the pad multiple grows Q while the P real
    # rows stay fixed. Real-row pop order is pad-independent because
    # invalid slots are ineligible (k_elig=1) and the sort is stable:
    # filtering the order array to real indices must be bitwise stable
    # across widths, and the top-kb window (kb <= eligible reals, all
    # of which outrank any pad slot) must be identical outright.
    def run_queue(mult: int) -> Dict[str, np.ndarray]:
        import jax
        import jax.numpy as jnp
        from tpusched.kernels import queue as kq
        P, kb, now, gain = 40, 8, 300.0, 1000.0
        rng = np.random.default_rng(7)
        t = kq.empty_table(64 * mult)
        valid = np.asarray(t.valid).copy()
        valid[:P] = True
        base = np.asarray(t.base_priority).copy()
        base[:P] = rng.uniform(10.0, 100.0, P).astype(np.float32)
        slo = np.asarray(t.slo_target).copy()
        slo[:P] = rng.choice(
            np.asarray([0.0, 0.9, 0.99], np.float32), P)
        sub = np.asarray(t.submitted).copy()
        sub[:P] = rng.uniform(0.0, 250.0, P).astype(np.float32)
        run = np.asarray(t.run_seconds).copy()
        run[:P] = rng.uniform(0.0, 40.0, P).astype(np.float32)
        park = np.asarray(t.parked_until).copy()
        park[:P][rng.random(P) < 0.25] = np.float32(now + 60.0)
        seq = np.asarray(t.seq).copy()
        seq[:P] = rng.permutation(P).astype(np.uint32)
        host = t._replace(valid=valid, base_priority=base,
                          slo_target=slo, submitted=sub,
                          run_seconds=run, parked_until=park, seq=seq)
        dev = jax.tree.map(jnp.asarray, host)
        order, prio, n_elig, depth = kq.rank_full(
            dev, jnp.float32(now), jnp.float32(gain))
        order = np.asarray(order)
        win, wprio, _n2, _d2 = kq.window_select(
            dev, now, gain, kb)
        ref_order, ref_prio, _re, _rd = kq.rank_reference(host, now, gain)
        ref_order = np.asarray(ref_order)
        return {
            "order_real": order[order < P],
            "prio": np.asarray(prio)[:P],
            "win": np.asarray(win),
            "win_prio": np.asarray(wprio),
            "ref_order_real": ref_order[ref_order < P],
            "ref_prio": np.asarray(ref_prio)[:P],
            "counts": np.asarray([int(n_elig), int(depth)]),
        }

    out.append(Harness(
        "queue_rank",
        ("rank_full", "_window_body", "rank_reference"),
        run_queue,
        sanity=lambda o: "" if (
            0 < o["counts"][0] < o["counts"][1]) else
        "parked slots never held (or nothing eligible)",
    ))
    return out


# ---------------------------------------------------------------------------
# The mesh differential (--mesh-only; ISSUE 17). Each case runs once
# dense (mesh=None) and once per MESH_SHAPES through the sharded
# serving stack; real rows must be bitwise-identical. (2,1) splits the
# pod axis across the two devices, (1,2) splits the node axis — so
# every sharded snapshot axis crosses a real device boundary at least
# once, which is exactly the regime the ledger's SHARDING verdicts are
# about. The case entry lists feed tools/shardcheck.py: their
# kernelflow closure must reach every decision-path ledger site whose
# verdict is not safe-any-tree.
# ---------------------------------------------------------------------------

MESH_SHAPES = ((2, 1), (1, 2))

#: Mesh-case -> entry kernels. Module-level (no jax needed) so
#: tools/shardcheck.py can close over the kernelflow call graph from
#: here without executing anything: together these entries must reach
#: every decision-path ledger site whose SHARDING verdict is not
#: safe-any-tree — shardcheck fails otherwise.
MESH_CASE_ENTRIES: Dict[str, Tuple[str, ...]] = {
    "mesh_solve_fast_sig": ("solve_rounds", "precompute_static",
                            "atom_sat"),
    "mesh_solve_fast_preempt": ("solve_rounds",),
    "mesh_solve_parity_preempt": ("solve_sequential",),
    "mesh_score_batch": ("score_batch",),
    "mesh_solve_incremental": ("solve_incremental", "build_tableau"),
}


def mesh_entry_kernels() -> Tuple[str, ...]:
    """Union of mesh-case entry kernels, declaration order, deduped."""
    names: List[str] = []
    for entries in MESH_CASE_ENTRIES.values():
        names.extend(entries)
    return tuple(dict.fromkeys(names))


def _mesh_cases() -> List[Harness]:
    """Mesh cases reuse the Harness shape, but run() takes a MESH
    (None = dense single-device reference), not a pad multiplier."""
    from tpusched import Engine
    from tpusched.engine import _sat_tables

    out: List[Harness] = []

    def solve_case(name, kind, cfg_kw):
        def run(mesh) -> Dict[str, np.ndarray]:
            from tpusched.config import EngineConfig as EC
            cfg = EC(**cfg_kw)
            snap, _meta, P, M = _build(kind, 1, cfg)
            eng = Engine(cfg, mesh=mesh)
            try:
                res = eng.solve(eng.put(snap))
            finally:
                eng.close()
            return _solve_outputs(res, P, M, 0)
        out.append(Harness(name, MESH_CASE_ENTRIES[name], run))

    # 1/2/3: the three solve programs of the pad harness, now through a
    # mesh engine + Engine.put (the pipeline.solve_stream serving path).
    solve_case("mesh_solve_fast_sig", "sig",
               dict(mode="fast", compact_cap=8))
    solve_case("mesh_solve_fast_preempt", "preempt",
               dict(mode="fast", preemption=True, compact_cap=8))
    solve_case("mesh_solve_parity_preempt", "preempt",
               dict(mode="parity", preemption=True))

    # 4: the [P, N] score surface on a sharded snapshot (the matrix is
    # PS('p','n') — both mesh axes live in one output).
    def run_score(mesh) -> Dict[str, np.ndarray]:
        import jax
        import jax.numpy as jnp
        from tpusched.config import EngineConfig as EC
        from tpusched.kernels import assign as kassign
        from tpusched.mesh import shard_snapshot
        cfg = EC(mode="fast")
        snap, _meta, P, _M = _build("sig", 1, cfg)
        snap = (shard_snapshot(mesh, snap) if mesh is not None
                else jax.tree.map(jnp.asarray, snap))
        nst, mst = _sat_tables(snap, mesh)
        feasible, score = kassign.score_batch(cfg, snap, nst, mst,
                                              mesh=mesh)
        return {"feasible": np.asarray(feasible)[:P, :10],
                "score": np.asarray(score)[:P, :10]}

    out.append(Harness("mesh_score_batch",
                       MESH_CASE_ENTRIES["mesh_score_batch"], run_score))

    # 5: the incremental warm rounds on a sharded snapshot (reaches
    # _capacity_prefix_keep, the carried-placement revalidation).
    def run_inc(mesh) -> Dict[str, np.ndarray]:
        import jax
        import jax.numpy as jnp
        from tpusched.config import EngineConfig as EC
        from tpusched.kernels import assign as kassign
        from tpusched.mesh import shard_snapshot
        cfg = EC(mode="fast", compact_cap=8)
        snap, _meta, P, _M = _build("sig", 1, cfg)
        eng = Engine(cfg, mesh=mesh)
        try:
            cold = eng.solve(eng.put(snap))
        finally:
            eng.close()
        snap = (shard_snapshot(mesh, snap) if mesh is not None
                else jax.tree.map(jnp.asarray, snap))
        nst, mst = _sat_tables(snap, mesh)
        tab = kassign.build_tableau(cfg, snap, nst, mst, mesh=mesh)
        Pb = snap.pods.valid.shape[0]
        carry = np.full(Pb, -1, np.int32)
        carry[:P] = np.asarray(cold.assignment)[:P]
        chosen = np.full(Pb, -np.inf, np.float32)
        chosen[:P] = np.asarray(cold.chosen_score)[:P]
        frontier = np.zeros(Pb, bool)
        frontier[: max(2, P // 8)] = True
        if mesh is not None:
            # replicated commit: a single-device-committed carry mixed
            # with mesh-sharded snapshot leaves is a placement error.
            from jax.sharding import NamedSharding, PartitionSpec
            rep = NamedSharding(mesh, PartitionSpec())
            ship = lambda x: jax.device_put(jnp.asarray(x), rep)  # noqa: E731
        else:
            ship = jnp.asarray
        res = kassign.solve_incremental(
            cfg, snap, tab, ship(carry), ship(chosen), ship(frontier),
            None, cap=8, mesh=mesh,
        )
        assigned, chosen_o, _used, _order, _ro, _r, _ev, _rv, audit = res
        return {"assignment": np.asarray(assigned)[:P],
                "chosen_score": np.asarray(chosen_o)[:P],
                "audit": np.asarray(audit)}

    out.append(Harness("mesh_solve_incremental",
                       MESH_CASE_ENTRIES["mesh_solve_incremental"],
                       run_inc))
    assert [c.name for c in out] == list(MESH_CASE_ENTRIES)
    return out


def mesh_main() -> int:
    """--mesh-only body (runs inside the forced-2-device subprocess)."""
    import jax
    ndev = len(jax.devices())
    if ndev < 2:
        print(f"padcheck --mesh-only: {ndev} jax device(s); needs 2 "
              "(run under XLA_FLAGS=--xla_force_host_platform_"
              "device_count=2)", file=sys.stderr)
        return 1
    from tpusched.mesh import make_mesh

    failures: List[str] = []
    for case in _mesh_cases():
        try:
            base = {k: np.asarray(v) for k, v in case.run(None).items()}
            for shape in MESH_SHAPES:
                mesh = make_mesh(shape, devices=jax.devices()[:2])
                got = case.run(mesh)
                bad = [k for k, want in base.items()
                       if not bitwise_equal(want, np.asarray(got[k]))]
                for k in bad:
                    failures.append(
                        f"{case.name}@{shape}: output {k!r} diverged "
                        "from the dense single-device run")
                if not bad:
                    print(f"[+] {case.name}@{shape}: bitwise-identical "
                          "to dense")
        except Exception as e:  # a broken case must not pass silently
            failures.append(f"{case.name}: case crashed: {e!r}")
    for f in failures:
        print(f"[!] {f}", file=sys.stderr)
    print(json.dumps({"mesh_cases": len(_mesh_cases()) * len(MESH_SHAPES),
                      "mesh_divergences": len(failures)}))
    return 1 if failures else 0


def _mesh_subprocess() -> Tuple[Optional[int], str]:
    """Dispatch --mesh-only under a forced 2-virtual-device CPU
    platform; returns (divergence count | None on crash, output)."""
    import os
    import subprocess
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append("--xla_force_host_platform_device_count=2")
    env["XLA_FLAGS"] = " ".join(flags)
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).resolve()), "--mesh-only"],
        capture_output=True, text=True, cwd=REPO_ROOT, env=env)
    out = (proc.stdout + proc.stderr).strip()
    div: Optional[int] = None
    for line in proc.stdout.splitlines():
        try:
            doc = json.loads(line)
        except (json.JSONDecodeError, ValueError):
            continue
        if isinstance(doc, dict) and "mesh_divergences" in doc:
            div = int(doc["mesh_divergences"])
    if proc.returncode != 0 and div == 0:
        div = None  # exit code and summary disagree: treat as crash
    return div, out


# ---------------------------------------------------------------------------
# The seeded hazardous fixture (--self-test): a two-op kernel whose
# result provably moves under zero-padding — threshold against the
# MEAN, whose denominator is the padded width. The refuter must catch
# it, or a green padcheck proves nothing.
# ---------------------------------------------------------------------------


def hazardous_fixture_run(mult: int) -> Dict[str, np.ndarray]:
    import jax.numpy as jnp
    n = 8
    rng = np.random.default_rng(5)
    vals = rng.uniform(1.0, 2.0, n).astype(np.float32)
    width = n * mult
    x = np.zeros(width, np.float32)
    x[:n] = vals
    above = np.asarray(jnp.asarray(x) > jnp.mean(jnp.asarray(x)))
    return {"above": above[:n]}


def self_test() -> bool:
    """True when the refuter catches the seeded hazard."""
    res = diff_run("hazardous_fixture", hazardous_fixture_run)
    return res.diverged


# ---------------------------------------------------------------------------
# Coverage: harness entries -> kernelflow reachability -> ledger sites.
# ---------------------------------------------------------------------------


def coverage(prog: "kernelflow.KernelProgram",
             harnesses: List[Harness],
             ledger: Dict[str, Any]) -> Tuple[Dict[str, List[str]],
                                              List[Dict[str, Any]]]:
    """(harness -> covered roots, uncovered ledger site records)."""
    per_harness: Dict[str, List[str]] = {}
    covered: set = set()
    for h in harnesses:
        roots = prog.reachable_from(h.entries)
        per_harness[h.name] = sorted(roots)
        covered |= roots
    uncovered = [rec for rec in ledger["sites"]
                 if rec["root"] not in covered]
    return per_harness, uncovered


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--self-test", action="store_true",
                    help="only prove the refuter catches the seeded "
                         "hazardous fixture")
    ap.add_argument("--list", action="store_true",
                    help="print the harness -> covered roots table")
    ap.add_argument("--mesh-only", action="store_true",
                    help="run only the mesh differential in-process "
                         "(needs >= 2 jax devices)")
    args = ap.parse_args(argv)

    try:
        import jax  # noqa: F401
    except ImportError:
        print("padcheck: jax not installed — skipping (the static "
              "ledger gate still runs via lint.py --check-ledger)")
        return 0

    if args.mesh_only:
        return mesh_main()

    if args.self_test:
        ok = self_test()
        print("padcheck --self-test:",
              "caught the seeded hazard" if ok
              else "MISSED the seeded hazard")
        return 0 if ok else 1

    prog = kernelflow.KernelProgram(kernelflow.kernel_sources(
        scan_product_sources(REPO_ROOT)))
    prog.classify_rules()
    ledger = prog.ledger_doc()
    harnesses = _harnesses()
    per_harness, uncovered = coverage(prog, harnesses, ledger)

    if args.list:
        for h in harnesses:
            print(f"{h.name}: {', '.join(per_harness[h.name])}")
        for case in _mesh_cases():
            roots = prog.reachable_from(case.entries)
            print(f"{case.name} [mesh]: {', '.join(sorted(roots))}")
        return 0

    # Which roots hold only exact-marked sites? A divergence there
    # falsifies the analysis; a divergence reaching hazard sites would
    # merely confirm them.
    hazard_roots = {rec["root"] for rec in ledger["sites"]
                    if rec["exactness"] == "f32-order-sensitive"
                    and rec["padding"] in ("hazard",)}

    failures: List[str] = []
    divergences = 0
    for h in harnesses:
        try:
            res = diff_run(h.name, h.run)
        except Exception as e:  # a broken harness must not pass silently
            failures.append(f"{h.name}: harness crashed: {e!r}")
            continue
        reaches_hazard = bool(set(per_harness[h.name]) & hazard_roots)
        if res.diverged:
            divergences += 1
            if reaches_hazard:
                print(f"[~] {h.name}: diverged ({res.detail}) — "
                      "reaches suppressed hazard sites; confirms the "
                      "hazard marking")
            else:
                failures.append(
                    f"{h.name}: DIVERGED but every reachable ledger "
                    f"site is exact-marked — the analysis mis-marked "
                    f"one ({res.detail})")
        else:
            note = h.sanity(res.base) if h.sanity else ""
            if note:
                failures.append(f"{h.name}: sanity: {note}")
            else:
                print(f"[+] {h.name}: bitwise-identical at pads "
                      f"x{PAD_MULTIPLIERS[0]}/x{PAD_MULTIPLIERS[1]} "
                      f"({len(per_harness[h.name])} roots)")

    if uncovered:
        for rec in uncovered[:10]:
            failures.append(
                f"uncovered ledger site {rec['path']}:{rec['line']} "
                f"({rec['op']} in {rec['root']}) — add a harness or "
                "extend an entry list")

    if not self_test():
        failures.append("self-test: the refuter MISSED the seeded "
                        "hazardous fixture — a green run proves nothing")

    # The mesh differential, in its own forced-2-device subprocess.
    mesh_div, mesh_out = _mesh_subprocess()
    for ln in mesh_out.splitlines():
        if ln.startswith(("[+]", "[~]")):
            print(ln)
    if mesh_div is None:
        failures.append("mesh differential crashed:\n" +
                        "\n".join(mesh_out.splitlines()[-8:]))
        mesh_div = 0
    elif mesh_div:
        for ln in mesh_out.splitlines():
            if ln.startswith("[!]"):
                failures.append(f"mesh: {ln[4:]}")

    total = len(ledger["sites"])
    print(json.dumps({"metric": "padcheck_sites_total",
                      "value": float(total), "unit": "count",
                      "direction": "lower"}))
    print(json.dumps({"metric": "padcheck_divergences_total",
                      "value": float(divergences), "unit": "count",
                      "direction": "lower"}))
    print(json.dumps({"metric": "padcheck_mesh_divergences_total",
                      "value": float(mesh_div), "unit": "count",
                      "direction": "lower"}))
    for f in failures:
        print(f"[!] {f}", file=sys.stderr)
    print(f"padcheck: {len(harnesses)} harnesses, {total} ledger sites "
          f"covered, {divergences} pad + {mesh_div} mesh "
          f"divergence(s), {len(failures)} failure(s)")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
