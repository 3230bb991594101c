"""The cluster generator of the k8s5k configurations.

A configuration names its generator by path (`"generator"` in
`configs/<name>.json`); the harness loads it and drives it through
this protocol, which every generator keeps:

  * `Cluster(config, traffic, seed)`: the configuration file and the
    traffic mix as dicts, and the run's seed;
  * attributes `msg` (the `ClusterSnapshot` the client sends, mutated
    in place), `node_index` (node name -> index), `pend_names` (pending
    pod names by slot), `pend_slot` (name -> slot), `n_pend`,
    `pend_cap` (the queue's bucket) and `n_run`;
  * `add_pending(k)`, `bind(names, nodes)`, `complete(k)` and
    `qos_churn(frac)`, each returning the names it touched, called in
    that order between two cycles;
  * `static_view()`, read once per run, and `cycle_view()`, read for
    every cycle the check keeps: what the configuration's reference
    reads, passed through the harness and the check whole. The cycle
    view is a dict whose `pend["names"]` are the pending pods' names by
    slot (the harness times pods by them) and which uses none of the
    keys the harness adds (`node`, `key`, `malformed`, `failed`).

This one is driven by two data files: a configuration (the cluster's
sizes and the pod size distribution) and a traffic mix
(`traffic/<name>.json`: how pods arrive, how many complete, how much
QoS churn). Everything is drawn from the run's seed, in bulk with
numpy.

The state lives twice, kept in step by every mutation:
  * numpy arrays (the benchmark's truth, which the reference reads);
  * the wire message the client hands to `DeltaSession` (the
    scheduler's input), mutated in place the way an informer-driven
    client mutates its cache.

Record order in the message does not matter (the decoder sorts by
name), so removals swap the last record into the hole.

Sizes are whole MiB and millicores, as Kubernetes requests usually are;
every partial sum of them is exact in float32, so the reference and
the program agree on `used` whatever order they add in.
"""

from __future__ import annotations

import math

import numpy as np

MIB = 1 << 20
ZONES = ("zone-a", "zone-b", "zone-c", "zone-d")
APPS = ("web", "db", "cache", "batch")
NAMESPACE = "ns-0"


def _pb():
    from tpusched.rpc import tpusched_pb2 as pb

    return pb


def _res(field, cpu: float, mem_mib: float, pods: float | None = None):
    r = field.add()
    r.name, r.quantity = "cpu", float(cpu)
    r = field.add()
    r.name, r.quantity = "memory", float(mem_mib * MIB)
    if pods is not None:
        r = field.add()
        r.name, r.quantity = "pods", float(pods)


def _digits(x: np.ndarray, width: int) -> np.ndarray:
    """[M, width] ASCII decimal digits of x, zero-padded."""
    pow10 = 10 ** np.arange(width - 1, -1, -1, dtype=np.int64)
    return ((x[:, None] // pow10) % 10 + 48).astype(np.uint8)


def _f64(x: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(x, "<f8").view(np.uint8).reshape(-1, 8)


def _running_bytes(ids, node, cpu, mem_mib, prio, slack, app) -> bytes:
    """Serialized `ClusterSnapshot.running` entries for many running
    pods at once: every record has a fixed layout per app label, so the
    bytes are assembled as numpy columns (the set-up cost of 140k
    records drops from seconds to milliseconds). Field numbers follow
    protos/tpusched.proto; tests/benchmark checks the bytes against
    records built field by field."""
    parts = []
    for a, value in enumerate(APPS):
        sel = app == a
        m = int(sel.sum())
        if not m:
            continue
        v = value.encode()

        def const(b: bytes) -> np.ndarray:
            return np.broadcast_to(np.frombuffer(b, np.uint8), (m, len(b)))

        label = b"\x0a\x03app\x12" + bytes([len(v)]) + v
        cols = [
            const(b"\x0a\x0brun-"), _digits(ids[sel], 7),        # name
            const(b"\x12\x0anode-"), _digits(node[sel], 5),      # node
            const(b"\x1a\x0e\x0a\x03cpu\x11"), _f64(cpu[sel]),   # requests
            const(b"\x1a\x11\x0a\x06memory\x11"),
            _f64(mem_mib[sel] * float(MIB)),
            const(b"\x21"), _f64(prio[sel]),                      # priority
            const(b"\x29"), _f64(slack[sel]),                     # slack
            const(b"\x32" + bytes([len(label)]) + label),         # labels
            const(b"\x4a\x04" + NAMESPACE.encode()),             # namespace
        ]
        body = np.concatenate(cols, axis=1)
        head = const(b"\x1a" + bytes([body.shape[1]]))          # running
        parts.append(np.concatenate([head, body], axis=1).tobytes())
    return b"".join(parts)


class PodStream:
    """The seeded sequence of pods that join the queue: pod i is the
    same pod in every run of a seed, however the arrivals are batched
    into cycles. Drawn in chunks, each from its own stream."""

    CHUNK = 1 << 14

    def __init__(self, config: dict, seed: int):
        self.cfg, self.seed = config, seed
        self.next = 0
        self._chunks: list[list[np.ndarray]] = []

    def _chunk(self, c: int) -> list[np.ndarray]:
        rng = np.random.default_rng([self.seed, 0xA77, c])
        n = self.CHUNK
        lo_c, hi_c = self.cfg["pod_cpu_millicores"]
        lo_m, hi_m = self.cfg["pod_memory_mib"]
        return [rng.integers(lo_c, hi_c + 1, n), rng.integers(lo_m, hi_m + 1, n),
                rng.integers(0, 1000, n).astype(np.float64),
                rng.choice(np.array([0.0, 0.9, 0.95, 0.99]), n),
                rng.uniform(0.5, 1.0, n), rng.integers(0, len(APPS), n)]

    def take(self, k: int) -> tuple:
        """(ids, cpu, mem MiB, priority, slo, availability, app) of the
        next k pods."""
        ids = np.arange(self.next, self.next + k)
        if k == 0:
            return (ids, *(c[:0] for c in self._chunk(0)))
        need = (self.next + k + self.CHUNK - 1) // self.CHUNK
        while len(self._chunks) < need:
            self._chunks.append(self._chunk(len(self._chunks)))
        cols = [np.concatenate([self._chunks[c][f] for c in range(
            self.next // self.CHUNK, need)]) for f in range(6)]
        off = self.next - (self.next // self.CHUNK) * self.CHUNK
        self.next += k
        return (ids, *(c[off:off + k] for c in cols))


class Cluster:
    """Nodes, running pods and pending pods of one run."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        pb = _pb()
        self.cfg, self.mix, self.seed = config, traffic, seed
        self.rng = np.random.default_rng([seed, 0x5EED])
        rng = self.rng
        N = int(config["nodes"])
        classes = np.asarray(config["node_classes"], np.int64)
        # Every seed holds the same multiset of node classes (N split
        # evenly), in its own order: the seed changes placement, not
        # capacity.
        cls = np.resize(np.arange(len(classes)), N)
        rng.shuffle(cls)
        self.n_nodes = N
        self.node_cpu = classes[cls, 0]
        self.node_mem = classes[cls, 1]
        self.pods_cap = int(config["pods_per_node"])
        self.node_names = [f"node-{i:05d}" for i in range(N)]
        self.node_index = {n: i for i, n in enumerate(self.node_names)}
        disk = rng.random(N) < 0.5
        tier = rng.integers(0, 4, N)
        self.msg = pb.ClusterSnapshot()
        for i in range(N):
            nm = self.msg.nodes.add()
            nm.name = self.node_names[i]
            _res(nm.allocatable, self.node_cpu[i], self.node_mem[i],
                 self.pods_cap)
            for k, v in (("disktype", "ssd" if disk[i] else "hdd"),
                         ("kubernetes.io/hostname", self.node_names[i]),
                         ("tier", str(tier[i])),
                         ("topology.kubernetes.io/zone", ZONES[i % 4])):
                lab = nm.labels.add()
                lab.key, lab.value = k, v
        # used[n] = (cpu millicores, memory MiB, pods), exact integers.
        self.used = np.zeros((N, 3), np.int64)
        self._init_running()
        self._init_pending()

    # -- sizes -------------------------------------------------------------

    def draw_sizes(self, k: int) -> tuple[np.ndarray, np.ndarray]:
        lo_c, hi_c = self.cfg["pod_cpu_millicores"]
        lo_m, hi_m = self.cfg["pod_memory_mib"]
        return (self.rng.integers(lo_c, hi_c + 1, k),
                self.rng.integers(lo_m, hi_m + 1, k))

    # -- running pods ------------------------------------------------------

    def _init_running(self) -> None:
        """Fill each node with a prefix of seeded pods up to the mix's
        `initial_fill` of its cpu and memory (and the pods cap)."""
        N, K = self.n_nodes, self.pods_cap
        cpu, mem = self.draw_sizes(N * K)
        cpu, mem = cpu.reshape(N, K), mem.reshape(N, K)
        f = float(self.mix["initial_fill"])
        ok = ((np.cumsum(cpu, 1) <= f * self.node_cpu[:, None])
              & (np.cumsum(mem, 1) <= f * self.node_mem[:, None]))
        count = np.minimum(np.argmin(np.c_[ok, np.zeros(N, bool)], 1), K)
        keep = np.arange(K)[None, :] < count[:, None]
        node = np.broadcast_to(np.arange(N)[:, None], (N, K))[keep]
        self.run_cap = int(self.mix["running_bucket"])
        M = int(keep.sum())
        if M > self.run_cap:
            raise ValueError(f"{M} initial running pods exceed the "
                             f"running bucket {self.run_cap}")
        prio = self.rng.integers(0, 100, M).astype(np.float64)
        slack = self.rng.uniform(-0.2, 0.3, M)
        app = self.rng.integers(0, len(APPS), M)
        # Slots follow the message's record order, which _running_bytes
        # groups by app label.
        ids = np.argsort(app, kind="stable")
        node, cpu, mem = node[ids], cpu[keep][ids], mem[keep][ids]
        self.run_node = np.zeros(self.run_cap, np.int64)
        self.run_cpu = np.zeros(self.run_cap, np.int64)
        self.run_mem = np.zeros(self.run_cap, np.int64)
        self.run_node[:M], self.run_cpu[:M], self.run_mem[:M] = node, cpu, mem
        self.run_names: list[str] = [f"run-{i:07d}" for i in ids]
        self.n_run = M
        self.msg.MergeFromString(_running_bytes(
            ids, node, cpu, mem, prio[ids], slack[ids], app[ids]))
        np.add.at(self.used, node, np.c_[cpu, mem, np.ones(M, np.int64)])

    def _run_remove(self, slots: np.ndarray) -> list[str]:
        """Remove running pods at `slots` (swap with the last)."""
        names = []
        for s in sorted((int(x) for x in slots), reverse=True):
            last = self.n_run - 1
            names.append(self.run_names[s])
            self.used[self.run_node[s]] -= (self.run_cpu[s], self.run_mem[s], 1)
            if s != last:
                self.run_names[s] = self.run_names[last]
                self.run_node[s] = self.run_node[last]
                self.run_cpu[s] = self.run_cpu[last]
                self.run_mem[s] = self.run_mem[last]
                self.msg.running[s].CopyFrom(self.msg.running[last])
            del self.msg.running[last]
            self.run_names.pop()
            self.n_run = last
        return names

    # -- pending pods ------------------------------------------------------

    def _init_pending(self) -> None:
        self.pend_cap = int(self.mix["pending_bucket"])
        P = self.pend_cap
        self.pend_cpu = np.zeros(P, np.int64)
        self.pend_mem = np.zeros(P, np.int64)
        self.pend_prio = np.zeros(P, np.float64)
        self.pend_slo = np.zeros(P, np.float64)
        self.pend_avail = np.zeros(P, np.float64)
        self.pend_app = np.zeros(P, np.int64)
        self.pend_names: list[str] = []
        self.pend_slot: dict[str, int] = {}
        self.n_pend = 0
        self.stream = PodStream(self.cfg, self.seed)

    def add_pending(self, k: int) -> list[str]:
        """The next k pods of the seeded pod stream join the queue."""
        if self.n_pend + k > self.pend_cap:
            raise ValueError("pending bucket overflow")
        ids, cpu, mem, prio, slo, avail, app = self.stream.take(k)
        names = []
        for j in range(k):
            s = self.n_pend
            name = f"pod-{ids[j]:09d}"
            self.pend_cpu[s], self.pend_mem[s] = cpu[j], mem[j]
            self.pend_prio[s], self.pend_slo[s] = prio[j], slo[j]
            self.pend_avail[s], self.pend_app[s] = avail[j], app[j]
            self.pend_names.append(name)
            self.pend_slot[name] = s
            p = self.msg.pods.add()
            p.name = name
            _res(p.requests, cpu[j], mem[j])
            p.priority = float(prio[j])
            p.slo_target = float(slo[j])
            p.observed_availability = float(avail[j])
            lab = p.labels.add()
            lab.key, lab.value = "app", APPS[app[j]]
            p.namespace = NAMESPACE
            self.n_pend += 1
            names.append(name)
        return names

    def _pend_remove(self, name: str) -> None:
        """Remove one pending pod (swap with the last)."""
        s = self.pend_slot.pop(name)
        last = self.n_pend - 1
        if s != last:
            moved = self.pend_names[last]
            self.pend_names[s] = moved
            self.pend_slot[moved] = s
            for a in (self.pend_cpu, self.pend_mem, self.pend_prio,
                      self.pend_slo, self.pend_avail, self.pend_app):
                a[s] = a[last]
            self.msg.pods[s].CopyFrom(self.msg.pods[last])
        del self.msg.pods[last]
        self.pend_names.pop()
        self.n_pend = last

    def qos_churn(self, frac: float) -> list[str]:
        """A new observed availability for `frac` of the pending pods."""
        k = min(self.n_pend, math.ceil(frac * self.n_pend))
        if k == 0:
            return []
        slots = self.rng.choice(self.n_pend, k, replace=False)
        avail = self.rng.uniform(0.5, 1.0, k)
        names = []
        for s, a in zip(slots, avail):
            s = int(s)
            self.pend_avail[s] = a
            self.msg.pods[s].observed_availability = float(a)
            names.append(self.pend_names[s])
        return names

    # -- binding and completion -------------------------------------------

    def bind(self, pods: list[str], nodes: list[int]) -> list[str]:
        """Placed pending pods become running pods on their nodes."""
        for name, n in zip(pods, nodes):
            s = self.pend_slot[name]
            cpu, mem = int(self.pend_cpu[s]), int(self.pend_mem[s])
            prio = float(self.pend_prio[s])
            slack = float(self.pend_avail[s] - self.pend_slo[s])
            app = int(self.pend_app[s])
            self._pend_remove(name)
            m = self.n_run
            if m >= self.run_cap:
                raise ValueError("running bucket overflow")
            self.run_names.append(name)
            self.run_node[m], self.run_cpu[m], self.run_mem[m] = n, cpu, mem
            self.n_run += 1
            self.used[n] += (cpu, mem, 1)
            r = self.msg.running.add()
            r.name = name
            r.node = self.node_names[n]
            _res(r.requests, cpu, mem)
            r.priority = prio
            r.slack = slack
            lab = r.labels.add()
            lab.key, lab.value = "app", APPS[app]
            r.namespace = NAMESPACE
        return list(pods)

    def complete(self, k: int) -> list[str]:
        """k seeded running pods finish and leave their nodes."""
        k = min(k, self.n_run)
        if k == 0:
            return []
        return self._run_remove(self.rng.choice(self.n_run, k,
                                                replace=False))

    # -- what the reference reads -----------------------------------------

    def static_view(self) -> dict:
        """`alloc`: [N, 3] allocatable (cpu millicores, memory MiB,
        pods)."""
        pods = np.full(self.n_nodes, self.pods_cap, np.int64)
        return dict(alloc=np.c_[self.node_cpu, self.node_mem, pods])

    def cycle_view(self) -> dict:
        """Copy of the cycle's input as sent: `used` [N, 3] like
        `alloc`, and the pending pods by slot in `pend`."""
        n = self.n_pend
        return dict(used=self.used.copy(), pend=dict(
            names=np.array(self.pend_names[:n]),
            cpu=self.pend_cpu[:n].copy(), mem=self.pend_mem[:n].copy(),
            prio=self.pend_prio[:n].copy(), slo=self.pend_slo[:n].copy(),
            avail=self.pend_avail[:n].copy(),
        ))
