"""Ring/blockwise pairwise counting (SURVEY.md §2.3 "SP/CP" row, §5
"Long-context analogue").

The domain-count state counts[s, d] = members matching signature s in
domain d is the contraction of a [S, M+P] match matrix against member
placement — this domain's analogue of attention's [Q, K] scores. The
sig-table design already keeps it compact, but at extreme scale (many
signatures × hundreds of thousands of members) the full [S, M+P] match
matrix need not materialize on any single device:

  * member blocks (labels' atom-satisfaction columns, namespaces, node,
    validity) stay RESIDENT, sharded over the 'p' mesh axis;
  * signature blocks (selector atoms, topology key, ns scope) ROTATE
    around the ring via lax.ppermute, each carrying its accumulated
    [S_blk, N] counts with it;
  * after ndev hops every signature block has seen every member block
    and returns home with complete counts.

Structurally identical to ring attention (KV blocks rotating past
resident Q blocks, accumulating output) — compute overlaps the ICI
transfer of the next block, and peak memory per device is
O(S/ndev x members/ndev), never O(S x members).

Numerically identical to kernels/pairwise.sig_counts (integer adds in
f32, order-independent below 2^24).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from tpusched.kernels.atoms import gather_term_sat
from tpusched.kernels.pairwise import ns_scope_ok
from tpusched.mesh import POD_AXIS
from tpusched.snapshot import ClusterSnapshot


def _pad_to(x, mult: int, axis: int, fill):
    n = x.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad, constant_values=fill)


def ring_sig_counts(
    snap: ClusterSnapshot,
    member_sat_t,
    assigned,
    mesh: Mesh,
):
    """[S, N] f32 domain counts, computed blockwise around the 'p' ring.

    member_sat_t: [A, M+P] atom satisfaction over member labels (from
    pairwise.member_label_sat_t). assigned: [P] int32 committed node per
    pending pod (-1 = not placed). Returns the same counts as
    kernels/pairwise.sig_counts for snapshots whose selectors' AND-lists
    fit the sig atom bucket (always true by construction).
    """
    ndev = mesh.shape[POD_AXIS]
    run, pods, sigs = snap.running, snap.pods, snap.sigs
    N = snap.nodes.valid.shape[0]
    S = sigs.key.shape[0]

    # Member-axis data (resident, sharded over 'p').
    mnode = jnp.concatenate([run.node_idx, assigned])
    mvalid = jnp.concatenate([run.valid, assigned >= 0])
    mns = jnp.concatenate([run.namespace, pods.namespace])
    msat = member_sat_t  # [A, MP]

    # Pad both the member axis and the signature axis to ndev multiples.
    msat = _pad_to(msat, ndev, 1, False)
    mnode = _pad_to(mnode, ndev, 0, -1)
    mvalid = _pad_to(mvalid, ndev, 0, False)
    mns = _pad_to(mns, ndev, 0, -1)
    skey = _pad_to(sigs.key, ndev, 0, -1)
    satoms = _pad_to(sigs.atoms, ndev, 0, -1)
    sns = _pad_to(sigs.ns, ndev, 0, -1)
    snsall = _pad_to(sigs.ns_all, ndev, 0, False)
    svalid = _pad_to(sigs.valid, ndev, 0, False)
    Sp = skey.shape[0]

    # Domain id of node n under topology key k, replicated: [N, TK].
    ndom = snap.nodes.domain

    def kernel(msat, mnode, mvalid, mns, skey, satoms, sns, snsall, svalid):
        # Shapes inside: member arrays hold this device's block
        # ([A, mblk], [mblk], ...); sig arrays hold the CURRENT sig
        # block ([sblk], [sblk, AT], ...), initially this device's own.
        sblk = skey.shape[0]
        perm = [(i, (i + 1) % ndev) for i in range(ndev)]

        def match_block(skey, satoms, sns, snsall, svalid):
            # [sblk, mblk]: same selector-AND + namespace-scope semantics
            # as pairwise.sig_member_match, via the shared kernels.
            match = gather_term_sat(msat, satoms)     # [sblk, mblk]
            ns_ok = ns_scope_ok(sns, snsall, mns)
            return match & ns_ok & svalid[:, None] & mvalid[None, :]

        def body(carry, _):
            skey, satoms, sns, snsall, svalid, counts = carry
            match = match_block(skey, satoms, sns, snsall, svalid)
            # Domain of each member's node under each sig's key.
            if ndom.shape[1]:
                dom_s = ndom[:, jnp.clip(skey, 0, None)].T    # [sblk, N]
                dom_s = jnp.where((skey >= 0)[:, None], dom_s, -1)
            else:
                dom_s = jnp.full((sblk, N), -1, jnp.int32)
            mdom = jnp.where(
                (mnode >= 0)[None, :],
                dom_s[:, jnp.clip(mnode, 0, None)], -1
            )                                                  # [sblk, mblk]
            contrib = (match & (mdom >= 0)).astype(jnp.float32)
            rows = jnp.broadcast_to(
                jnp.arange(sblk)[:, None], mdom.shape
            )
            counts = counts.at[rows, jnp.clip(mdom, 0, None)].add(contrib)
            # Rotate the sig block AND its accumulated counts to the
            # next device; after ndev hops they are home and complete.
            nxt = [
                jax.lax.ppermute(x, POD_AXIS, perm)
                for x in (skey, satoms, sns, snsall, svalid, counts)
            ]
            return tuple(nxt), None

        init = (skey, satoms, sns, snsall, svalid,
                jnp.zeros((sblk, N), jnp.float32))
        (skey, satoms, sns, snsall, svalid, counts), _ = jax.lax.scan(
            body, init, None, length=ndev
        )
        return counts

    p = PS(POD_AXIS)
    counts = shard_map(
        kernel,
        mesh=mesh,
        in_specs=(
            PS(None, POD_AXIS),  # msat: member axis sharded
            p, p, p,             # mnode, mvalid, mns
            p,                   # skey: sig axis sharded
            PS(POD_AXIS, None),  # satoms
            PS(POD_AXIS, None),  # sns
            p, p,                # snsall, svalid
        ),
        out_specs=PS(POD_AXIS, None),
        check_vma=False,
    )(msat, mnode, mvalid, mns, skey, satoms, sns, snsall, svalid)
    return counts[:S]


# ring_sig_counts_host, the old per-call-jit convenience wrapper, was
# DELETED here (round 19, ISSUE 14): it had no callers anywhere in the
# tree and re-jitted (so retraced) on every invocation — the exact
# TPL103 hazard class. Callers wanting a host-side one-shot should go
# through Engine (whose jit families are cached and bounded) or jit
# `ring_sig_counts` themselves at module scope.
