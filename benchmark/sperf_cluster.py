"""The cluster generator of the sperf5k-pairwise configuration:
kube-scheduler's `scheduler_perf` topology-spread and pod-(anti-)affinity
cluster (test/integration/scheduler_perf/config/performance-config.yaml,
the TopologySpreading, PreferredTopologySpreading, SchedulingPodAffinity
and SchedulingPodAntiAffinity cases) at the large-cluster scale.

Keeps `workload.py`'s protocol and its pod stream, QoS fields and churn;
what it changes is the shapes:

  * nodes are all `node-default` (one class from the configuration),
    labelled with their own name on `kubernetes.io/hostname` and with
    one of the configuration's zones, in turn, on
    `topology.kubernetes.io/zone`;
  * every pod is one of five kinds (`KINDS`), each with its template's
    labels and terms: `default` (none), `spread` (`foo=bar`, two
    DoNotSchedule constraints on `foo=bar`, hostname and zone),
    `spread-soft` (the same, ScheduleAnyway), `affinity` (`foo=""`, a
    required pod affinity to `foo=""` on the zone) and `anti`
    (`color=green`, a required pod anti-affinity to `color=green` on the
    hostname). The pod's kind is drawn from the seed with the
    configuration's `kind_shares`, for the initial running pods and for
    arrivals alike;
  * the initial running pods (`running_pods` of them) are spread evenly:
    per node, the total and the `foo=bar` count differ by at most one
    across nodes, the `foo=bar` count by at most one across zones, no
    node holds two `color=green` pods, and every zone holds `foo=""`
    pods: a fill kube-scheduler could have made;
  * running records carry their kind's labels and affinity terms (the
    wire has no spread field on a running pod), pending records their
    labels, spread constraints and affinity terms.

Records are serialized in bulk (`_records`): every record of a kind has
one layout, so a batch is assembled as numpy byte columns, the initial
140k running pods at set-up and each cycle's wave alike.

The views add what the reference reads: static `zone` (each node's zone
index), `n_zones` and `max_skew`; per cycle `pend["kind"]` and
`kinds_on_node`, the running pods per node by kind, kept in step by
`bind` and `complete` (never recounted from the message).
"""

from __future__ import annotations

import numpy as np

import workload

KINDS = ("default", "spread", "spread-soft", "affinity", "anti")
DEFAULT, SPREAD, SOFT, AFFINITY, ANTI = range(len(KINDS))
HOSTNAME = "kubernetes.io/hostname"
ZONE = "topology.kubernetes.io/zone"
MIB = workload.MIB
RUN_NAME = 7          # digits of an initial running pod's name
POD_NAME = 9          # digits of an arriving pod's name (workload.py's)


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        if not n:
            out.append(b)
            return bytes(out)
        out.append(b | 0x80)


def _selector(field, key: str, value: str) -> None:
    field.add(key=key, op="In", values=[value])


def kind_labels(kind: int) -> dict:
    """The labels of a pod of `kind` (its template's)."""
    return {SPREAD: {"foo": "bar"}, SOFT: {"foo": "bar"},
            AFFINITY: {"foo": ""}, ANTI: {"color": "green"}}.get(kind, {})


def add_spread(field, kind: int, max_skew: int) -> None:
    """The kind's topology spread constraints onto a PendingPod's
    `topology_spread`."""
    if kind not in (SPREAD, SOFT):
        return
    when = "DoNotSchedule" if kind == SPREAD else "ScheduleAnyway"
    for key in (HOSTNAME, ZONE):
        c = field.add(topology_key=key, max_skew=max_skew,
                      when_unsatisfiable=when)
        _selector(c.selector, "foo", "bar")


def add_affinity(field, kind: int, namespaces: list) -> None:
    """The kind's required pod (anti-)affinity term onto a pod's
    `pod_affinity`."""
    if kind == AFFINITY:
        t = field.add(topology_key=ZONE, anti=False, required=True,
                      namespaces=namespaces)
        _selector(t.selector, "foo", "")
    elif kind == ANTI:
        t = field.add(topology_key=HOSTNAME, anti=True, required=True,
                      namespaces=namespaces)
        _selector(t.selector, "color", "green")


class KindStream:
    """The kind of each pod of the seeded pod stream: pod i has the same
    kind in every run of a seed, however arrivals are batched. Drawn in
    chunks, each from its own stream, as workload.PodStream draws."""

    CHUNK = workload.PodStream.CHUNK

    def __init__(self, shares: np.ndarray, seed: int):
        self.shares, self.seed = shares, seed
        self._chunks: list[np.ndarray] = []

    def of(self, ids: np.ndarray) -> np.ndarray:
        if len(ids) == 0:
            return np.zeros(0, np.int64)
        need = int(ids.max()) // self.CHUNK + 1
        while len(self._chunks) < need:
            c = len(self._chunks)
            rng = np.random.default_rng([self.seed, 0x51D, c])
            self._chunks.append(rng.choice(len(KINDS), self.CHUNK,
                                           p=self.shares))
        return np.concatenate(self._chunks)[ids]


class Cluster(workload.Cluster):
    """Nodes, running pods and pending pods of one run."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.zones = list(config["zones"])
        self.zone = np.arange(int(config["nodes"])) % len(self.zones)
        self.max_skew = int(config["max_skew"])
        self.namespace = str(config["namespace"])
        self.term_namespaces = list(config["term_namespaces"])
        shares = np.array([float(config["kind_shares"][k]) for k in KINDS])
        self.shares = shares / shares.sum()
        self._tails: dict = {}
        super().__init__(config, traffic, seed)
        for i, nm in enumerate(self.msg.nodes):
            del nm.labels[:]
            nm.labels.add(key=HOSTNAME, value=nm.name)
            nm.labels.add(key=ZONE, value=self.zones[self.zone[i]])

    # -- bulk records ------------------------------------------------------

    def _tail(self, running: bool, kind: int) -> bytes:
        """The serialized fields every record of the kind shares."""
        key = (running, kind)
        if key not in self._tails:
            pb = workload._pb()
            m = pb.RunningPod() if running else pb.PendingPod()
            for k, v in kind_labels(kind).items():
                m.labels.add(key=k, value=v)
            if not running:
                add_spread(m.topology_spread, kind, self.max_skew)
            add_affinity(m.pod_affinity, kind, self.term_namespaces)
            m.namespace = self.namespace
            self._tails[key] = m.SerializeToString()
        return self._tails[key]

    def _records(self, running: bool, names: np.ndarray, kind: np.ndarray,
                 cols: list) -> bytes:
        """Serialized `ClusterSnapshot.running` (or `.pods`) entries for
        many records at once, grouped by kind (`kind` ascending: records
        of one kind share a layout). `names`: [k, width] ASCII; `cols`:
        (field tag, [k, n] uint8) columns every record carries. Field
        numbers follow protos/tpusched.proto; tests/benchmark checks the
        bytes against records built field by field."""
        k = len(kind)
        if k == 0:
            return b""
        if (np.diff(kind) < 0).any():
            raise ValueError("records must come grouped by kind")

        def const(b: bytes, m: int) -> np.ndarray:
            return np.broadcast_to(np.frombuffer(b, np.uint8), (m, len(b)))

        fixed = [const(b"\x0a" + bytes([names.shape[1]]), k), names]
        for tag, col in cols:
            fixed += [const(tag, k), col]
        fixed = np.concatenate(fixed, axis=1)
        outer = b"\x1a" if running else b"\x12"
        bounds = np.searchsorted(kind, np.arange(len(KINDS) + 1))
        parts = []
        for kd in range(len(KINDS)):
            lo, hi = bounds[kd], bounds[kd + 1]
            if lo == hi:
                continue
            tail = self._tail(running, kd)
            head = outer + _varint(fixed.shape[1] + len(tail))
            parts.append(np.concatenate(
                [const(head, hi - lo), fixed[lo:hi], const(tail, hi - lo)],
                axis=1).tobytes())
        return b"".join(parts)

    @staticmethod
    def _ascii(strings: list) -> np.ndarray:
        width = len(strings[0])
        return np.frombuffer("".join(strings).encode(), np.uint8).reshape(
            len(strings), width)

    def _running_records(self, names, node, cpu, mem, prio, slack,
                         kind) -> bytes:
        f64 = workload._f64
        return self._records(True, names, kind, [
            (b"\x12\x0a", np.c_[np.broadcast_to(
                np.frombuffer(b"node-", np.uint8), (len(node), 5)),
                workload._digits(node, 5)]),
            (b"\x1a\x0e\x0a\x03cpu\x11", f64(cpu)),
            (b"\x1a\x11\x0a\x06memory\x11", f64(mem * float(MIB))),
            (b"\x21", f64(prio)),
            (b"\x29", f64(slack)),
        ])

    # -- running pods ------------------------------------------------------

    def _init_running(self) -> None:
        """`running_pods` pods spread evenly over the nodes (see the
        module docstring), grouped by kind in the message.

        The nodes are dealt round-robin in a seeded order that takes the
        zones in turn; the foo=bar pods take the first slots of the deal,
        then the green pods, the foo="" pods and the default pods: every
        kind's per-node count is then within one of every other node's,
        a run of at most N slots holds each node once, and every prefix
        of the order is spread evenly over the zones."""
        N, Z = self.n_nodes, len(self.zones)
        R = int(self.cfg["running_pods"])
        rng = self.rng
        n = np.round(self.shares * R).astype(np.int64)
        n[DEFAULT] = R - n[1:].sum()
        if n[ANTI] > N:
            raise ValueError("more color=green pods than nodes")
        by_zone = [rng.permutation(np.flatnonzero(self.zone == z))
                   for z in range(Z)]
        deal = np.concatenate([
            [zn[j] for zn in by_zone if j < len(zn)]
            for j in range(max(map(len, by_zone)))]).astype(np.int64)
        foobar = np.repeat([SPREAD, SOFT], [n[SPREAD], n[SOFT]])
        slot_kind = np.concatenate([
            foobar[rng.permutation(len(foobar))],
            np.repeat([ANTI, AFFINITY, DEFAULT],
                      [n[ANTI], n[AFFINITY], n[DEFAULT]])])
        slot_node = np.resize(deal, R)
        by_node = np.zeros((N, len(KINDS)), np.int64)
        np.add.at(by_node, (slot_node, slot_kind), 1)
        # Records grouped by kind, each kind's by node.
        kind = np.repeat(np.arange(len(KINDS)), by_node.sum(0))
        node = np.concatenate([np.repeat(np.arange(N), by_node[:, k])
                               for k in range(len(KINDS))])
        self.run_cap = int(self.mix["running_bucket"])
        if R > self.run_cap:
            raise ValueError(f"{R} initial running pods exceed the "
                             f"running bucket {self.run_cap}")
        cpu, mem = self.draw_sizes(R)
        prio = rng.integers(0, 100, R).astype(np.float64)
        slack = rng.uniform(-0.2, 0.3, R)
        ids = np.arange(R)
        self.run_node = np.zeros(self.run_cap, np.int64)
        self.run_cpu = np.zeros(self.run_cap, np.int64)
        self.run_mem = np.zeros(self.run_cap, np.int64)
        self.run_kind = np.zeros(self.run_cap, np.int64)
        self.run_node[:R], self.run_cpu[:R], self.run_mem[:R] = node, cpu, mem
        self.run_kind[:R] = kind
        self.run_names: list[str] = [f"run-{i:0{RUN_NAME}d}" for i in ids]
        self.n_run = R
        names = np.c_[np.broadcast_to(np.frombuffer(b"run-", np.uint8),
                                      (R, 4)), workload._digits(ids, RUN_NAME)]
        self.msg.MergeFromString(self._running_records(
            names, node, cpu, mem, prio, slack, kind))
        np.add.at(self.used, node, np.c_[cpu, mem, np.ones(R, np.int64)])
        self.kinds_on_node = by_node

    def _run_remove(self, slots: np.ndarray) -> list[str]:
        names = []
        for s in sorted((int(x) for x in slots), reverse=True):
            self.kinds_on_node[self.run_node[s], self.run_kind[s]] -= 1
            self.run_kind[s] = self.run_kind[self.n_run - 1]
            names += super()._run_remove([s])
        return names

    # -- pending pods ------------------------------------------------------

    def _init_pending(self) -> None:
        super()._init_pending()
        self.pend_kind = np.zeros(self.pend_cap, np.int64)
        self.kind_stream = KindStream(self.shares, self.seed)

    def add_pending(self, k: int) -> list[str]:
        """The next k pods of the seeded pod stream join the queue, in
        the message grouped by kind."""
        if self.n_pend + k > self.pend_cap:
            raise ValueError("pending bucket overflow")
        ids, cpu, mem, prio, slo, avail, _ = self.stream.take(k)
        kind = self.kind_stream.of(ids)
        order = np.argsort(kind, kind="stable")
        ids, cpu, mem, prio, slo, avail, kind = (
            a[order] for a in (ids, cpu, mem, prio, slo, avail, kind))
        s0, s1 = self.n_pend, self.n_pend + k
        self.pend_cpu[s0:s1], self.pend_mem[s0:s1] = cpu, mem
        self.pend_prio[s0:s1], self.pend_slo[s0:s1] = prio, slo
        self.pend_avail[s0:s1], self.pend_kind[s0:s1] = avail, kind
        self.pend_app[s0:s1] = 0
        names = [f"pod-{i:0{POD_NAME}d}" for i in ids]
        self.pend_names += names
        self.pend_slot.update(zip(names, range(s0, s1)))
        self.n_pend = s1
        if k:
            f64 = workload._f64
            self.msg.MergeFromString(self._records(
                False, self._ascii(names), kind, [
                    (b"\x12\x0e\x0a\x03cpu\x11", f64(cpu)),
                    (b"\x12\x11\x0a\x06memory\x11", f64(mem * float(MIB))),
                    (b"\x19", f64(prio)),
                    (b"\x21", f64(slo)),
                    (b"\x29", f64(avail)),
                ]))
        return names

    def _pend_remove(self, name: str) -> None:
        s, last = self.pend_slot[name], self.n_pend - 1
        self.pend_kind[s] = self.pend_kind[last]
        super()._pend_remove(name)

    # -- binding and completion -------------------------------------------

    def bind(self, pods: list[str], nodes: list[int]) -> list[str]:
        """Placed pending pods become running pods on their nodes, in
        the message grouped by kind."""
        k = len(pods)
        if k == 0:
            return []
        slots = np.fromiter((self.pend_slot[p] for p in pods), np.int64, k)
        node = np.asarray(nodes, np.int64)
        cpu, mem = self.pend_cpu[slots], self.pend_mem[slots]
        prio = self.pend_prio[slots]
        slack = self.pend_avail[slots] - self.pend_slo[slots]
        kind = self.pend_kind[slots]
        for name in pods:
            self._pend_remove(name)
        m = self.n_run
        if m + k > self.run_cap:
            raise ValueError("running bucket overflow")
        order = np.argsort(kind, kind="stable")
        names = [pods[j] for j in order]
        node, cpu, mem, prio, slack, kind = (
            a[order] for a in (node, cpu, mem, prio, slack, kind))
        self.run_names += names
        self.run_node[m:m + k], self.run_cpu[m:m + k] = node, cpu
        self.run_mem[m:m + k], self.run_kind[m:m + k] = mem, kind
        self.n_run += k
        np.add.at(self.used, node, np.c_[cpu, mem, np.ones(k, np.int64)])
        np.add.at(self.kinds_on_node, (node, kind), 1)
        self.msg.MergeFromString(self._running_records(
            self._ascii(names), node, cpu, mem, prio, slack, kind))
        return list(pods)

    # -- what the reference reads -----------------------------------------

    def static_view(self) -> dict:
        """workload.Cluster's, with `zone` [N] (each node's zone index),
        `n_zones` and `max_skew` (every spread constraint's)."""
        return dict(super().static_view(), zone=self.zone.copy(),
                    n_zones=len(self.zones), max_skew=self.max_skew)

    def cycle_view(self) -> dict:
        """workload.Cluster's, with `pend["kind"]` (index into KINDS) and
        `kinds_on_node` [N, len(KINDS)], the running pods per node by
        kind."""
        rec = super().cycle_view()
        rec["pend"]["kind"] = self.pend_kind[:self.n_pend].copy()
        rec["kinds_on_node"] = self.kinds_on_node.copy()
        return rec
