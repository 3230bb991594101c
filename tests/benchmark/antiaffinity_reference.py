"""The test-only reference of antiaffinity_cluster.py: kube-scheduler's
`scheduleOne` with the InterPodAffinity filter for its one required
term. A "db" pod may not join a node (its own hostname domain) where a
running pod, or a pod placed earlier in the same cycle, matches
`app=db`; the term adds no score. The symmetric rule (a member that
holds the term repels an incoming db pod) bars the same nodes.

Each pod is placed by benchmark/reference.py on its own, in the pop
order that reference gives the whole queue, with the barred nodes'
pod capacity set to what they hold: that fails NodeResourcesFit there
and changes no score, which reads cpu and memory alone."""

from __future__ import annotations

import numpy as np

import reference as base

commit_rank_differs = base.commit_rank_differs


def sequential(profile: dict, static: dict, rec: dict, commit: bool = True
               ) -> tuple[np.ndarray, np.ndarray]:
    pend = rec["pend"]
    alloc = static["alloc"]
    used = rec["used"].copy()
    held = rec["db_on_node"].copy()
    _, pos = base.sequential(profile, static, rec, commit=False)
    node = np.full(len(pos), -1, np.int64)
    for p in np.argsort(pos):
        one = dict(used=used, pend={k: v[p:p + 1] for k, v in pend.items()})
        cap = alloc
        if pend["db"][p]:
            cap = alloc.copy()
            barred = held > 0
            cap[barred, 2] = used[barred, 2]
        n = int(base.sequential(profile, dict(alloc=cap), one)[0][0])
        node[p] = n
        if n < 0 or not commit:
            continue
        used[n] += (pend["cpu"][p], pend["mem"][p], 1)
        held[n] += bool(pend["db"][p])
    return node, pos
