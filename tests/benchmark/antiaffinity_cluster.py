"""A test-only generator, named by path from a configuration a test
builds: the k8s5k cluster (benchmark/workload.py) where every pending
"db" pod carries a required pod anti-affinity against `app=db` on
`kubernetes.io/hostname`, and keeps it once bound. Its views add what
the filter reads: `pend["db"]` (the pods that hold the term, which are
the pods its selector matches) and `db_on_node`, the running pods per
node that the selector matches, counted from the message as sent."""

from __future__ import annotations

import numpy as np

import workload

APP = "db"
HOSTNAME = "kubernetes.io/hostname"
_DB = workload.APPS.index(APP)


def _add_term(field) -> None:
    term = field.add(topology_key=HOSTNAME, anti=True, required=True)
    term.selector.add(key="app", op="In", values=[APP])


class Cluster(workload.Cluster):

    def add_pending(self, k: int) -> list[str]:
        names = super().add_pending(k)
        for name in names:
            s = self.pend_slot[name]
            if self.pend_app[s] == _DB:
                _add_term(self.msg.pods[s].pod_affinity)
        return names

    def bind(self, pods: list[str], nodes: list[int]) -> list[str]:
        db = [self.pend_app[self.pend_slot[name]] == _DB for name in pods]
        bound = super().bind(pods, nodes)
        first = self.n_run - len(pods)      # bind appends in order
        for j in np.flatnonzero(db):
            _add_term(self.msg.running[first + int(j)].pod_affinity)
        return bound

    def cycle_view(self) -> dict:
        rec = super().cycle_view()
        rec["pend"]["db"] = self.pend_app[:self.n_pend] == _DB
        held = np.zeros(self.n_nodes, np.int64)
        for r in self.msg.running:
            if any(lab.key == "app" and lab.value == APP for lab in r.labels):
                held[self.node_index[r.node]] += 1
        rec["db_on_node"] = held
        return rec
