"""Hierarchical (2-hop) all-to-all over a node-factored axis.

The flat a2a over an axis of R ranks sends (R-1) small messages per rank,
(R - intra) of them over the slow inter-node link.  With ranks node-major
(rank = node * intra + local — how launch/mesh.py lays device grids out),
the same permutation decomposes into two grouped a2a hops:

  hop 1 (intra-node)  ranks of one node exchange blocks keyed by the
                      *destination-local* index, at ICI bandwidth;
  hop 2 (inter-node)  rank (node i, local q) exchanges with its peers
                      (node p, local q) across nodes — (inter-1) large
                      messages instead of (R-intra) small ones.

Derivation, with the wire tensor viewed as x[p, q, ...] (block (p, q)
destined for rank p*intra + q) on source rank (i, j):

  hop 1 (split=concat=q-axis, node groups):   y[p, j'] = x_{(i,j')}[p, q]
  hop 2 (split=concat=p-axis, leader groups): z[i', j'] = x_{(i',j')}[p, q]

i.e. exactly the flat a2a result — pure data movement, so values are
bit-identical to ``all_to_all_bf16`` by construction.  The custom_vjp
backward is the mirrored 2-hop (inter first, then intra): each grouped hop
with split == concat is self-transpose, so F = P2∘P1 transposes to P1∘P2,
and gradients stay bit-faithful to the flat path too (tests/test_comm.py
checks both directions bitwise on 8 forced host devices).

bf16 operands travel as u16 words behind an optimization_barrier, exactly
like comm/collectives.py, so no compiler pass can widen the wire to f32.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.comm.collectives import _raw_a2a
from repro.obs import tracing as obs_tracing
from repro.obs.tracing import phase_scope


def intra_groups(r: int, intra: int):
    """Rank groups sharing a node: [[0..intra-1], [intra..2*intra-1], ...]"""
    return tuple(tuple(n * intra + j for j in range(intra))
                 for n in range(r // intra))


def inter_groups(r: int, intra: int):
    """Rank groups sharing a local index: node leaders for each q."""
    return tuple(tuple(p * intra + q for p in range(r // intra))
                 for q in range(intra))


def _two_hop(x, axis_name, intra, mirrored):
    """x: [R, ...] with block axis 0 ordered by destination rank.  Each hop
    is the shared bf16-pinned grouped a2a primitive (collectives._raw_a2a),
    so wire-pinning fixes there apply to both the flat and 2-hop paths."""
    r = x.shape[0]
    out = x.reshape((r // intra, intra) + x.shape[1:])
    hops = [(1, intra_groups(r, intra)), (0, inter_groups(r, intra))]
    if mirrored:
        hops.reverse()
    for axis, groups in hops:
        out = _raw_a2a(out, axis_name, axis, axis, groups=groups)
    return out.reshape(x.shape)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def hierarchical_all_to_all_bf16(x, axis_name: str, intra: int):
    """2-hop a2a of x: [R, ...] (block axis 0 = destination rank) over the
    named axis of size R = inter * intra; drop-in for
    ``all_to_all_bf16(x, axis_name, 0, 0)`` when ranks are node-major.
    Call inside a shard_map body; ``intra`` must divide R with
    1 < intra < R (the planner degrades to flat otherwise)."""
    return _two_hop(x, axis_name, intra, mirrored=False)


def _hier_fwd(x, axis_name, intra):
    return _two_hop(x, axis_name, intra, mirrored=False), None


def _hier_bwd(axis_name, intra, _, ct):
    return (_two_hop(ct, axis_name, intra, mirrored=True),)


hierarchical_all_to_all_bf16.defvjp(_hier_fwd, _hier_bwd)


def hierarchical_moe_exchange(send, compute_fn, axis_name: str, intra: int):
    """dispatch a2a -> compute -> combine a2a, both hops hierarchical.
    send: [R, e_local, c, H]; compute_fn keeps that shape."""
    with phase_scope(obs_tracing.PH_DISPATCH):
        recv = hierarchical_all_to_all_bf16(send, axis_name, intra)
    out = compute_fn(recv)
    with phase_scope(obs_tracing.PH_COMBINE):
        return hierarchical_all_to_all_bf16(out, axis_name, intra)
