"""The benchmark's plain reference (benchmark/reference.py) gives the
bits the program's own oracle (tpusched/oracle.py) states for the
configurations' profile, and the audits count what they say."""

import numpy as np
import pytest

import benchtiny  # noqa: F401
import reference
import workload
from tpusched import Engine, EngineConfig
from tpusched.oracle import Oracle
from tpusched.rpc.codec import decode_snapshot


def _cluster(seed, nodes=40, pods=200, fill=0.9):
    spec = benchtiny.tiny_spec("k8s5k-parity.backlog")
    spec["config"].update(nodes=nodes, node_bucket=64, initial_fill=fill)
    mix = dict(spec["traffic"], pending_bucket=256, running_bucket=4096)
    c = workload.Cluster(spec["config"], mix, seed)
    c.add_pending(pods)
    return spec["config"], c


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sequential_equals_oracle_and_parity_engine(seed):
    cfg, c = _cluster(seed)
    ec = EngineConfig.from_dict(cfg["engine"])
    snap, meta = decode_snapshot(c.msg, ec)
    ora = Oracle(snap, ec).solve()
    res = Engine(ec).solve(snap)
    rec = c.cycle_view()
    pend = rec["pend"]
    ref, pos = reference.sequential(cfg["engine"], c.static_view(), rec)
    slot = {n: i for i, n in enumerate(pend["names"])}
    by_name = [slot[n] for n in meta.pod_names]
    node_pos = {n: i for i, n in enumerate(meta.node_names)}
    want = np.array([node_pos[c.node_names[ref[s]]] if ref[s] >= 0 else -1
                     for s in by_name])
    P = len(meta.pod_names)
    assert (want == np.asarray(ora.assignment[:P])).all()
    assert (want == np.asarray(res.assignment[:P])).all()
    placed = want >= 0
    assert 0 < placed.sum() < P      # contended: some pods do not fit
    # Parity commit keys are the pop positions.
    assert (np.asarray(res.commit_key[:P])[placed]
            == pos[by_name][placed]).all()


def test_overfull_nodes_counts_exceeded_capacity():
    static = dict(alloc=np.array([[1000, 1024, 110], [2000, 2048, 110]]))
    rec = dict(used=np.array([[900, 0, 1], [0, 0, 0]]),
               pend=dict(cpu=np.array([100, 101, 50]),
                         mem=np.array([1, 1, 1])))
    assert reference.overfull_nodes(
        static, dict(rec, node=np.array([0, -1, 1]))) == 0
    assert reference.overfull_nodes(
        static, dict(rec, node=np.array([0, 0, 1]))) == 1


def test_commit_rank_differs():
    node = np.array([0, 1, -1, 2])
    pos = np.array([0, 1, 2, 3])
    assert reference.commit_rank_differs(node, np.array([5, 6, 0, 7]),
                                         pos) == 0
    assert reference.commit_rank_differs(node, np.array([6, 5, 0, 7]),
                                         pos) == 2


def test_no_commit_control_overfills():
    cfg, c = _cluster(4)
    static, rec = c.static_view(), c.cycle_view()
    node, _ = reference.sequential(cfg["engine"], static, rec, commit=False)
    assert reference.overfull_nodes(static, dict(rec, node=node)) > 0
