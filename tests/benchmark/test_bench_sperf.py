"""The sperf5k-pairwise configuration at a CPU size: its reference
(benchmark/sperf_reference.py) equals the engine's parity mode exactly,
placements and commit order, through the served path; the fast cell is
correct when sound and not correct under each fault the check must
catch; the generator's bulk records and per-node kind counts match the
message it sends; the pairwise audit counts each kind of violation."""

import numpy as np
import pytest

pytest.importorskip("grpc")

import benchtiny  # noqa: E402
import control  # noqa: E402
import harness  # noqa: E402

CELL = "sperf5k-pairwise-fast.sperf-wave"
SEEDS = (benchtiny.SEED, 7, 2**32 + 15)
# Every kind at a share that makes its constraint bind at 24 nodes:
# green pods fill the nodes within a few waves, maxSkew 1 holds the
# spread pods to the emptiest hosts and zones.
SHARES = {"default": 0.2, "spread": 0.3, "spread-soft": 0.15,
          "affinity": 0.15, "anti": 0.2}
# At that stress the fast rounds place 1-12% fewer pods than the
# sequential scheduler (CPU, three seeds); the configuration's own
# limit is set from its full-size readings.
TINY_GAP = 0.3


def tiny_spec(running: int = 72, **config) -> dict:
    spec = harness.cell_spec(CELL)
    spec["config"].update(nodes=24, node_bucket=32, running_pods=running,
                          kind_shares=SHARES, max_skew=1, **config)
    spec["config"]["checks"] = dict(spec["config"]["checks"],
                                    placed_net_gap=TINY_GAP)
    spec["traffic"].update(backlog=64, pending_bucket=64, running_bucket=2048,
                           warm_rows=[1, 128], warmup_cycles=2,
                           saturated_placed=64, check_cycles=1000)
    return spec


def parity_spec(running: int) -> dict:
    spec = tiny_spec(running)
    spec["config"].update(guarantee="sequential",
                          checks=dict(failed_cycles=0, malformed_replies=0,
                                      pods_differing=0))
    spec["config"]["engine"] = dict(spec["config"]["engine"], mode="parity")
    return spec


@pytest.mark.parametrize("seed, running", [(s, 72) for s in SEEDS]
                         + [(benchtiny.SEED, 0)],
                         ids=["seed0", "seed1", "seed2", "empty_cluster"])
def test_reference_equals_the_engine_in_parity_mode(seed, running,
                                                    monkeypatch):
    out, diag = benchtiny.run_tiny(CELL, monkeypatch, spec=parity_spec(running),
                                   seed=seed)
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["pods_differing"]["value"] == 0
    assert out["attempted"] >= 2
    win = diag["window"]
    assert win["compiles_in_window"] == 0
    assert win["device_state_rebuilds"] == 0


def test_fast_cell_is_correct(monkeypatch):
    out, diag = benchtiny.run_tiny(CELL, monkeypatch, spec=tiny_spec())
    assert out["correct"] is True, out["checks"]
    assert list(out["checks"]) == ["failed_cycles", "malformed_replies",
                                   "overfull_nodes", "pairwise_violations",
                                   "placed_net_gap"]
    assert diag["placed"]["pods_placed"] > 0
    win = diag["window"]
    assert win["compiles_in_window"] == 0
    assert win["device_state_rebuilds"] == 0
    assert win["session_hits"] == win["delta_sends"] == out["attempted"]


def _termless(plugins):
    def load(spec):
        gen, ref = plugins(spec)

        class Cluster(gen.Cluster):
            def _tail(self, running, kind):
                # The kind's labels and namespace only: the wire drops
                # every spread constraint and affinity term.
                from tpusched.rpc import tpusched_pb2 as pb

                m = pb.RunningPod() if running else pb.PendingPod()
                for k, v in gen.kind_labels(kind).items():
                    m.labels.add(key=k, value=v)
                m.namespace = self.namespace
                return m.SerializeToString()

        gen.Cluster = Cluster
        return gen, ref

    return load


@pytest.mark.parametrize("case", ["no_commit", "altered_answer",
                                  "terms_dropped"])
def test_fast_cell_fails_what_it_must(case, monkeypatch):
    spec, faults = tiny_spec(), None
    if case == "no_commit":
        faults = control.no_commit_answers
    elif case == "altered_answer":
        # Nodes of five pods, four a node resident: the node with the
        # least room is full, so the moved pod overfills it.
        spec = tiny_spec(96, node_classes=[[500, 32768]])
        faults = control.altered_answer
    else:
        monkeypatch.setattr(harness, "plugins", _termless(harness.plugins))
    out, _ = benchtiny.run_tiny(CELL, monkeypatch, spec=spec, faults=faults)
    assert out["correct"] is False, out["checks"]
    checks = out["checks"]
    if case == "altered_answer":
        assert checks["overfull_nodes"]["value"] > 0
    else:
        assert checks["pairwise_violations"]["value"] > 0
    if case == "no_commit":
        assert checks["overfull_nodes"]["value"] > 0


def _kind_of(labels: dict) -> int:
    return {("foo", "bar"): 1, ("foo", ""): 3, ("color", "green"): 4}.get(
        next(iter(labels.items()), None), 0)


def test_generator_counts_and_records_match_its_message():
    spec = tiny_spec()
    conf, mix = spec["config"], spec["traffic"]
    gen, ref = harness.plugins(spec)
    from tpusched.rpc import tpusched_pb2 as pb

    c = gen.Cluster(conf, mix, benchtiny.SEED)
    c.add_pending(int(mix["backlog"]))
    static = c.static_view()
    for _ in range(3):
        node, _ = ref.sequential(conf["engine"], static, c.cycle_view())
        placed = np.flatnonzero(node >= 0)
        bound = c.bind([c.pend_names[s] for s in placed],
                       [int(node[s]) for s in placed])
        assert bound
        c.complete(len(bound))
        c.add_pending(int(mix["backlog"]) - c.n_pend)
        c.qos_churn(float(mix["qos_churn"]))
    rec = c.cycle_view()
    # Recount from the message: kinds (spread and spread-soft share
    # their labels on a running record), usage, and every record as the
    # templates build it field by field.
    N = c.n_nodes
    count = np.zeros((N, 5), np.int64)
    used = np.zeros((N, 3), np.int64)
    assert len(c.msg.running) == c.n_run
    for s, r in enumerate(c.msg.running):
        labels = {lab.key: lab.value for lab in r.labels}
        n = c.node_index[r.node]
        count[n, _kind_of(labels)] += 1
        used[n] += (100, 500, 1)
        assert r.name == c.run_names[s]
        want = pb.RunningPod(name=r.name, node=r.node, priority=r.priority,
                             slack=r.slack, namespace="sched-0")
        want.requests.add(name="cpu", quantity=100.0)
        want.requests.add(name="memory", quantity=500.0 * (1 << 20))
        for k, v in gen.kind_labels(int(c.run_kind[s])).items():
            want.labels.add(key=k, value=v)
        gen.add_affinity(want.pod_affinity, int(c.run_kind[s]),
                         conf["term_namespaces"])
        assert r == want
    got = rec["kinds_on_node"]
    assert (got[:, 0] == count[:, 0]).all()
    assert (got[:, 1] + got[:, 2] == count[:, 1]).all()
    assert (got[:, 3:] == count[:, 3:]).all()
    assert (rec["used"] == used).all()
    for s, p in enumerate(c.msg.pods):
        kind = int(rec["pend"]["kind"][s])
        assert p.name == rec["pend"]["names"][s]
        want = pb.PendingPod(name=p.name, priority=rec["pend"]["prio"][s],
                             slo_target=rec["pend"]["slo"][s],
                             observed_availability=rec["pend"]["avail"][s],
                             namespace="sched-0")
        want.requests.add(name="cpu", quantity=100.0)
        want.requests.add(name="memory", quantity=500.0 * (1 << 20))
        for k, v in gen.kind_labels(kind).items():
            want.labels.add(key=k, value=v)
        gen.add_spread(want.topology_spread, kind, 1)
        gen.add_affinity(want.pod_affinity, kind, conf["term_namespaces"])
        assert p == want


def test_initial_fill_is_one_kube_scheduler_could_make():
    spec = harness.cell_spec(CELL)
    spec["config"].update(nodes=300, running_pods=8400)
    gen, _ = harness.plugins(spec)
    c = gen.Cluster(spec["config"], spec["traffic"], benchtiny.SEED)
    k = c.kinds_on_node
    foobar = k[:, 1] + k[:, 2]
    assert k.sum() == c.n_run == 8400
    assert np.ptp(k.sum(1)) <= 1 and np.ptp(foobar) <= 1
    assert np.ptp(np.bincount(c.zone, foobar)) <= 1
    assert k[:, 4].max() <= 1
    assert (np.bincount(c.zone, k[:, 3]) > 0).all()
    shares = k.sum(0) / k.sum()
    assert np.allclose(shares, [0.28, 0.3, 0.1, 0.3, 0.02], atol=1e-3)


# -- pairwise_violations, case by case ------------------------------------

def _audit(kinds, running, node, key, skew=1):
    """Three nodes, one a zone; `running` [3, 5] per-node kind counts;
    pending pods of `kinds` placed at `node` with commit keys `key`."""
    import sperf_reference as ref

    P = len(kinds)
    static = dict(alloc=np.full((3, 3), 10**6), zone=np.arange(3),
                  n_zones=3, max_skew=skew)
    rec = dict(kinds_on_node=np.asarray(running, np.int64),
               used=np.zeros((3, 3), np.int64),
               pend=dict(kind=np.asarray(kinds), cpu=np.ones(P, np.int64),
                         mem=np.ones(P, np.int64)),
               node=np.asarray(node), key=np.asarray(key))
    return ref.pairwise_violations(static, rec)


def _run(**per_node):
    out = np.zeros((3, 5), np.int64)
    for kind, counts in per_node.items():
        out[:, ["default", "spread", "soft", "affinity", "anti"].index(kind)
            ] = counts
    return out


@pytest.mark.parametrize("kinds, running, node, key, bad", [
    # maxSkew 1: a second foo=bar pod on a host whose peers hold none
    ([1], _run(spread=[1, 0, 0]), [0], [0], 1),
    ([1], _run(spread=[1, 0, 0]), [1], [0], 0),
    # two green pods on one host: the later commit breaks its own term
    ([4, 4], _run(), [1, 1], [0, 1], 1),
    # a resident green pod's term repels an incoming green pod
    ([4], _run(anti=[0, 0, 1]), [2], [0], 1),
    ([4], _run(anti=[0, 0, 1]), [1], [0], 0),
    # foo="" pods only in zone 0: an affinity pod placed in zone 1
    ([3], _run(affinity=[2, 0, 0]), [1], [0], 1),
    ([3], _run(affinity=[2, 0, 0]), [0], [0], 0),
    # no foo="" pod anywhere: the first of a self-affine group goes
    ([3], _run(), [2], [0], 0),
    # one commit key: each green pod sees the other
    ([4, 4], _run(), [0, 0], [3, 3], 2),
    # one key, the spread pods judged against each other's commit
    ([1, 1], _run(), [0, 0], [0, 0], 2),
    ([1, 1], _run(), [0, 1], [0, 0], 0),
], ids=["spread_excess", "spread_within", "anti_pair", "symmetric_anti",
        "anti_elsewhere", "affinity_no_match", "affinity_match",
        "affinity_first_pod", "same_key_anti", "same_key_spread",
        "same_key_spread_apart"])
def test_pairwise_violations(kinds, running, node, key, bad):
    assert _audit(kinds, running, node, key) == bad
