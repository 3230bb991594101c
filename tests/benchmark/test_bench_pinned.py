"""The configurations' generator and reference, loaded by the path the
configuration names (harness.plugins), give the same message bytes,
cycle records, placements and check values as the generator and
reference the harness imported by name before configurations named
their own: digests and values recorded on that tree, at each tiny
cell's seed, with no sidecar and no clock (a timed window is not
deterministic)."""

import hashlib

import numpy as np
import pytest

import benchtiny
import check
import harness

FIELDS = ("msg_setup", "ref_setup", "record_steps", "msg_steps",
          "ref_steps")
PINNED = {
    "backlog": (
        "dab7db3439f8a8541e1112abaab519a3d3e73231d53ad5a169d4ba5cbc63c0e6",
        "9bc34ee41728e82d1098883dc42cfb4c45db82c2374faf42f308483cfaf33355",
        "6861c80ea9767e761bd01c969a504d94d01ab46699793c6972ab332d7f1896c6",
        "6cbfadbcc5c889f8719760550ae3b585958573a12859e32cbf72ee4f7eb9122d",
        "d58a5bb1592c63abc0aad341f1693e84a3b3b78f6c7965ae982c84bab54c1ae0",
    ),
    "stream": (
        "7c0203a6d6e9b4ba550826c9d15d1370f0a04a0b671bbfef2545178ac0ecc025",
        "c0e7dea02adfaf9eed7571730d9dd09f47a6e0a4209d0ac09227c67b6f06e89e",
        "f9f2fdc5446d86f73250b51150ce4a91c358dbf6c59f2f021ac2b7219f5f6f6a",
        "bdc35c3811d07dc82582919f7fafdd709fbb49b0204e572b7bb506d93bde05c4",
        "8a7724e5dd3826abb9569342896185c644a0049a56bd58f64a8d7281d8fda6e9",
    ),
}
# check.judge on the three steps' records, each answered by the
# no-commit reference (control.py's answer for the fast cells).
JUDGED = {
    "k8s5k-parity.backlog": dict(failed_cycles=(0, 0),
                                 malformed_replies=(0, 0),
                                 pods_differing=(177, 0)),
    "k8s5k-fast.backlog": dict(failed_cycles=(0, 0), malformed_replies=(0, 0),
                               overfull_nodes=(7, 0),
                               placed_net_gap=(4.5, 0.3)),
    "k8s5k-fast.stream": dict(failed_cycles=(0, 0), malformed_replies=(0, 0),
                              overfull_nodes=(4, 0),
                              placed_net_gap=(0.0, 0.3)),
}


def digest(obj) -> str:
    """SHA-256 of nested dicts (by sorted key), tuples and arrays
    (dtype, shape and bytes)."""
    h = hashlib.sha256()

    def feed(x):
        if isinstance(x, dict):
            for k in sorted(x):
                h.update(k.encode() + b"\0")
                feed(x[k])
        elif isinstance(x, (tuple, list)):
            for v in x:
                feed(v)
        else:
            a = np.ascontiguousarray(x)
            h.update(f"{a.dtype.str}{a.shape}".encode())
            h.update(a.tobytes())

    feed(obj)
    return h.hexdigest()


def _sha(msg) -> str:
    return hashlib.sha256(msg.SerializeToString()).hexdigest()


@pytest.mark.parametrize("cell", benchtiny.CELLS)
def test_generator_and_reference_match_the_pinned_tree(cell):
    spec = benchtiny.tiny_spec(cell)
    conf, mix = spec["config"], spec["traffic"]
    profile = conf["engine"]
    gen, ref = harness.plugins(spec)
    c = gen.Cluster(conf, mix, benchtiny.SEED)
    closed = mix["arrival"] == "closed"
    # Set-up as benchmark/rehearse.py makes it: a full queue (closed),
    # half the bucket (open).
    c.add_pending(int(mix["backlog"]) if closed else c.pend_cap // 2)
    static = c.static_view()
    got = dict(msg_setup=_sha(c.msg),
               ref_setup=digest(ref.sequential(profile, static,
                                               c.cycle_view())))
    # Three generator steps in Driver.generate's order, binding the
    # reference's own placements.
    cycles = []
    for _ in range(3):
        rec = c.cycle_view()
        node, pos = ref.sequential(profile, static, rec)
        nc, _ = ref.sequential(profile, static, rec, commit=False)
        cycles.append(harness.Cycle(0.0, 0.0, int((nc >= 0).sum()), dict(
            rec, node=nc, key=pos, malformed=False)))
        placed = np.flatnonzero(node >= 0)
        bound = c.bind([c.pend_names[s] for s in placed],
                       [int(node[s]) for s in placed])
        comp = mix["completions"]
        c.complete(len(bound) if comp == "bound" else int(comp))
        if closed:
            c.add_pending(int(mix["backlog"]) - c.n_pend)
        else:
            c.add_pending(min(len(bound), c.pend_cap - c.n_pend))
        c.qos_churn(float(mix["qos_churn"]))
    rec = c.cycle_view()
    got.update(record_steps=digest(rec), msg_steps=_sha(c.msg),
               ref_steps=digest(ref.sequential(profile, static, rec)))
    assert got == dict(zip(FIELDS, PINNED[cell.split(".")[1]]))
    judged = check.judge(spec, ref, static, cycles, benchtiny.SEED)
    assert judged == JUDGED[cell]
    assert list(judged) == list(JUDGED[cell])      # the stderr lines' order
