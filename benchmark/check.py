"""What decides `correct`: the window's replies against the reference.

The reference is the configuration's own module (harness.plugins),
called through its protocol (reference.py's docstring) on the
generator's views. Every number compared has its limit in the
configuration file (`checks`), set from readings of sound runs and of
the control (PERF.md gives both). The numbers, by the guarantee the
configuration states:

  * every configuration
      failed_cycles      Assign calls that raised (limit 0);
      malformed_replies  replies whose pod table is not the pods sent
                         (limit 0).
  * guarantee "sequential" (parity): on a sample of the window's
      cycles drawn from the seed, with the cycle that placed most in
      it, the reference's `scheduleOne` loop is run on the cycle's own
      input:
      pods_differing     pods placed elsewhere than the reference
                         places them (or placed by one side only), plus
                         placed pods committed out of the reference's
                         pop order (limit 0: an exact comparison).
  * guarantee "valid" (fast), over every cycle of the window:
      each of the reference's VALIDITY_AUDITS, summed over the cycles
      (the k8s5k reference's: overfull_nodes, nodes whose requests
      exceed allocatable once the reply's placements are added, limit
      0);
      placed_net_gap     |sum placed - sum reference placed| / sum
                         reference placed, the reference run on each
                         cycle's own input: the net placed-pod delta
                         that oracle.py's fast contract keeps near 0.
"""

from __future__ import annotations

import numpy as np

UNCHECKED = 10**9


def sample_cycles(cycles: list, seed: int, k: int) -> list[int]:
    """k cycle indices drawn from the seed, always with the cycle that
    placed most (the longest reply to check)."""
    ok = [i for i, c in enumerate(cycles) if not c.record.get("failed")]
    if not ok:
        return []
    top = max(ok, key=lambda i: cycles[i].placed)
    rest = [i for i in ok if i != top]
    rng = np.random.default_rng([seed, 0xC4EC])
    pick = rng.choice(len(rest), min(k - 1, len(rest)), replace=False)
    return sorted([top] + [rest[int(j)] for j in pick])


def judge(spec: dict, reference, static, cycles: list, seed: int) -> dict:
    """{name: (value, limit)} for the run's window, judged by the
    `reference` module on the generator's `static` view and each
    cycle's record."""
    cfg = spec["config"]
    limits = cfg["checks"]
    profile = cfg["engine"]
    out = {}
    recs = [c.record for c in cycles]
    out["failed_cycles"] = (sum(1 for r in recs if r.get("failed")),
                            limits["failed_cycles"])
    good = [r for r in recs if not r.get("failed")]
    out["malformed_replies"] = (sum(1 for r in good if r["malformed"]),
                                limits["malformed_replies"])
    if cfg["guarantee"] == "sequential":
        sample = sample_cycles(cycles, seed,
                               int(spec["traffic"]["check_cycles"]))
        # No cycle to compare is no evidence: the number then reads as
        # far over any limit.
        none = UNCHECKED if not sample else 0
        differing = 0
        for i in sample:
            r = recs[i]
            ref, pos = reference.sequential(profile, static, r)
            differing += int((r["node"] != ref).sum())
            differing += reference.commit_rank_differs(
                np.where(ref == r["node"], r["node"], -1), r["key"], pos)
        out["pods_differing"] = (differing + none, limits["pods_differing"])
    elif cfg["guarantee"] == "valid":
        for name in reference.VALIDITY_AUDITS:
            audit = getattr(reference, name)
            out[name] = (sum(audit(static, r) for r in good), limits[name])
        placed = want = 0
        for r in good:
            ref, _ = reference.sequential(profile, static, r)
            want += int((ref >= 0).sum())
            placed += int((r["node"] >= 0).sum())
        gap = abs(placed - want) / max(want, 1) if good else UNCHECKED
        out["placed_net_gap"] = (gap, limits["placed_net_gap"])
    else:
        raise ValueError(f"unknown guarantee {cfg['guarantee']!r}")
    return out
