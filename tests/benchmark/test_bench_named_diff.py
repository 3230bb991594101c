"""The client's named diff (`DeltaSession` given `changed`, as the
harness drives it) over the benchmark's generator at a small size: each
cycle it ships the upserts, in order, and the removes that the unnamed
diff of the same base ships, and carries its base forward to the store
a full send of the cycle's message would hold. Then its fault paths: a
failed send keeps the last acknowledged base, records the caller did
not name still ship when they come or go, and a snapshot that is not
delta-safe goes in full."""

import grpc
import numpy as np
import pytest

import benchtiny
import workload
from tpusched import trace
from tpusched.rpc import tpusched_pb2 as pb
from tpusched.rpc.client import DeltaSession
from tpusched.rpc.codec import SnapshotStore, delta_between

UPSERTS = ("upsert_nodes", "upsert_pods", "upsert_running")
REMOVES = ("remove_nodes", "remove_pods", "remove_running")


class _Fault(grpc.RpcError):
    def __init__(self, code):
        self._code = code

    def code(self):
        return self._code


class _Sidecar:
    """The part of SchedulerClient that DeltaSession calls: it keeps
    what was sent, acknowledges with a fresh snapshot id, and raises
    `fail` on the next delta send when one is set."""

    def __init__(self):
        self.tracer = trace.TraceCollector(seed=5)
        self.deltas: list = []
        self.fulls = 0
        self.fail = None
        self._n = 0

    def _ack(self):
        self._n += 1
        return pb.AssignResponse(snapshot_id=f"s{self._n}")

    def assign(self, snapshot, request_id="", **kw):
        self.fulls += 1
        return self._ack()

    def assign_delta(self, delta, request_id="", **kw):
        if self.fail is not None:
            fault, self.fail = self.fail, None
            raise fault
        self.deltas.append(delta)
        return self._ack()


def _cluster(seed=benchtiny.SEED):
    spec = benchtiny.tiny_spec("k8s5k-fast.backlog")
    c = workload.Cluster(spec["config"], spec["traffic"], seed)
    c.add_pending(c.pend_cap // 2)
    return c


def _churn(c, rng) -> set:
    """One cycle of the harness's churn: bind, complete, arrive, QoS.
    The names returned are the harness's: completions go unnamed."""
    names = list(rng.choice(c.pend_names, min(6, c.n_pend), replace=False))
    bound = c.bind(names, [int(n) for n in rng.integers(0, c.n_nodes,
                                                        len(names))])
    c.complete(int(rng.integers(1, 4)))
    new = c.add_pending(c.pend_cap // 2 - c.n_pend)
    qos = c.qos_churn(0.1)
    return set(bound) | set(new) | set(qos)


def _full_store(msg) -> SnapshotStore:
    st = SnapshotStore()
    st.set_full_bytes(msg)
    return st


def _stores(st: SnapshotStore) -> tuple:
    return st.nodes, st.pods, st.running


def _assert_same_delta(got, want):
    for f in UPSERTS:
        assert ([r.SerializeToString() for r in getattr(got, f)]
                == [r.SerializeToString() for r in getattr(want, f)]), f
    for f in REMOVES:
        assert sorted(getattr(got, f)) == sorted(getattr(want, f)), f


def test_named_diff_ships_what_the_unnamed_diff_ships():
    c = _cluster()
    side = _Sidecar()
    sess = DeltaSession(side)
    sess.assign(c.msg)
    rng = np.random.default_rng(11)
    for k in range(24):
        changed = _churn(c, rng)
        want = delta_between(sess._base.copy(), c.msg, "base")
        side.tracer.clear()
        sess.assign(c.msg, changed=changed)
        got = side.deltas[-1]
        _assert_same_delta(got, want)
        assert got.upsert_running and got.remove_running and got.upsert_pods
        assert _stores(sess._base) == _stores(_full_store(c.msg)), k
        (diff,) = [s for s in side.tracer.spans() if s.name == "client.diff"]
        n_rec = len(c.msg.nodes) + len(c.msg.pods) + len(c.msg.running)
        # Each name read once, plus the records serialized, which are
        # the churn: every one of them ships.
        assert diff.attrs["visited"] == n_rec + diff.attrs["serialized"]
        assert 0 < diff.attrs["serialized"] <= diff.attrs["shipped"]
    assert (side.fulls, sess.delta_sends) == (1, 24)


@pytest.mark.parametrize("code", [grpc.StatusCode.UNAVAILABLE,
                                  grpc.StatusCode.FAILED_PRECONDITION])
def test_a_failed_delta_keeps_the_acknowledged_base(code):
    """UNAVAILABLE: the send raises and the base stays what the sidecar
    last acknowledged, so the next delta, named with the failed cycle's
    hints restored (as host.py restores them), carries that cycle's
    churn too. FAILED_PRECONDITION: a full resend, then the base is the
    message's full store."""
    c = _cluster()
    side = _Sidecar()
    sess = DeltaSession(side)
    sess.assign(c.msg)
    rng = np.random.default_rng(3)
    sess.assign(c.msg, changed=_churn(c, rng))
    acked = _stores(sess._base.copy())
    acked_id = sess._base_id
    failed = _churn(c, rng)
    side.fail = _Fault(code)
    if code == grpc.StatusCode.FAILED_PRECONDITION:
        sess.assign(c.msg, changed=failed)
        assert (sess.fallbacks, side.fulls) == (1, 2)
        assert _stores(sess._base) == _stores(_full_store(c.msg))
        return
    failed_msg = pb.ClusterSnapshot()
    failed_msg.CopyFrom(c.msg)
    with pytest.raises(grpc.RpcError):
        sess.assign(c.msg, changed=failed)
    assert _stores(sess._base) == acked and sess._base_id == acked_id
    # What the failed cycle changed against the acknowledged base.
    lost = delta_between(sess._base.copy(), failed_msg, "acked")
    assert lost.upsert_pods and lost.remove_running
    changed = failed | _churn(c, rng)
    want = delta_between(sess._base.copy(), c.msg, "acked")
    sess.assign(c.msg, changed=changed)
    got = side.deltas[-1]
    assert got.base_id == acked_id
    _assert_same_delta(got, want)
    removed = set(got.remove_running) | set(got.remove_pods)
    live = {r.name for r in c.msg.running} | {p.name for p in c.msg.pods}
    for f in REMOVES[1:]:
        assert {n for n in getattr(lost, f) if n not in live} <= removed
    assert _stores(sess._base) == _stores(_full_store(c.msg))


def test_unnamed_additions_and_removals_still_ship():
    """A record that comes or goes without its name in `changed` (the
    host's batch cap and backoff move pods so) still ships, also when an
    unnamed addition and an unnamed removal leave the count unchanged."""
    c = _cluster()
    side = _Sidecar()
    sess = DeltaSession(side)
    sess.assign(c.msg)
    msg = c.msg
    gone_run, gone_pod = msg.running[0].name, msg.pods[1].name
    del msg.running[0]
    del msg.pods[1]
    extra = msg.running.add()
    extra.CopyFrom(msg.running[2])
    extra.name = "unnamed-arrival"
    named = msg.pods.add()
    named.CopyFrom(msg.pods[0])
    named.name = "named-arrival"
    msg.pods[0].priority += 1.0   # a named change
    sess.assign(msg, changed={msg.pods[0].name, "named-arrival"})
    got = side.deltas[-1]
    assert [r.name for r in got.upsert_running] == ["unnamed-arrival"]
    assert ([p.name for p in got.upsert_pods]
            == [msg.pods[0].name, "named-arrival"])
    assert list(got.remove_running) == [gone_run]
    assert list(got.remove_pods) == [gone_pod]
    assert _stores(sess._base) == _stores(_full_store(msg))


@pytest.mark.parametrize("fault", ["duplicate", "empty"])
def test_unsafe_snapshot_on_the_named_path_goes_in_full(fault):
    c = _cluster()
    side = _Sidecar()
    sess = DeltaSession(side)
    sess.assign(c.msg)
    rec = c.msg.running.add()
    rec.CopyFrom(c.msg.running[0])
    if fault == "empty":
        rec.name = ""
    sess.assign(c.msg, changed={rec.name})
    assert (side.fulls, sess.delta_sends) == (2, 0)
    assert not side.deltas
    # The session keeps no base it cannot key by name.
    assert sess._base is None
