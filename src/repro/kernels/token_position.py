"""Pallas TPU kernel: stable per-expert position assignment (routing).

For each flattened (token, choice) entry f with expert id e_f, computes the
number of earlier entries routed to the same expert — the entry's row in
the [E, C] dispatch buffer — plus the uncapped per-expert totals.  This is
the registry's ``positions_in_expert`` op: the XLA reference builds a
[F, E] one-hot and cumsums over it (O(F·E) memory traffic); the kernel
keeps a running per-expert count in the revisited counts output and turns
the within-tile prefix sum into an MXU matmul against a lower-triangular
mask, so only [E, tile_t] ever lives in VMEM.

Grid: (F/tile_t,), sequential — tile t reads the counts accumulated by
tiles 0..t-1 before adding its own totals.  Ids outside [0, E) match no
one-hot row: they receive position 0 and touch no count (the caller maps
them to the overflow bin; see kernels/dispatch.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(ids_ref, pos_ref, counts_ref, *, num_experts, tile_t):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    ids = ids_ref[0]                                       # [tile_t]
    iota_e = jax.lax.broadcasted_iota(jnp.int32, (num_experts, tile_t), 0)
    onehot = (iota_e == ids[None, :]).astype(jnp.float32)  # [E, tile_t]
    # inclusive within-tile prefix: onehot @ LT, LT[j, i] = (j <= i) — an
    # MXU contraction instead of a serial scan
    j = jax.lax.broadcasted_iota(jnp.int32, (tile_t, tile_t), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (tile_t, tile_t), 1)
    tri = (j <= i).astype(jnp.float32)
    incl = jnp.dot(onehot, tri, preferred_element_type=jnp.float32)
    base = counts_ref[0]                                   # [E] f32, pre-tile
    pos_all = base[:, None] + incl - 1.0                   # [E, tile_t]
    pos = jnp.sum(onehot * pos_all, axis=0)                # select own row
    pos_ref[0] = pos.astype(jnp.int32)
    counts_ref[0] = base + jnp.sum(onehot, axis=1)


@functools.partial(jax.jit,
                   static_argnames=("num_experts", "tile_t", "interpret"))
def positions_in_expert_pallas(expert_ids: jax.Array, *, num_experts: int,
                               tile_t: int = 128, interpret: bool):
    """expert_ids: [F] int32.  Returns (pos [F] int32, counts [E] f32):
    pos[f] = |{g < f : id_g == id_f}| (token-major stability — earlier
    entries win buffer rows), counts[e] = uncapped total routed to e.
    Ids outside [0, num_experts) get pos 0 and are counted nowhere."""
    F = expert_ids.shape[0]
    pad_f = (-F) % tile_t
    ids = expert_ids.reshape(1, F).astype(jnp.int32)
    if pad_f:
        ids = jnp.pad(ids, ((0, 0), (0, pad_f)), constant_values=-1)
    Fp = F + pad_f
    pos, counts = pl.pallas_call(
        functools.partial(_kernel, num_experts=num_experts, tile_t=tile_t),
        grid=(Fp // tile_t,),
        in_specs=[pl.BlockSpec((1, tile_t), lambda t: (0, t))],
        out_specs=(
            pl.BlockSpec((1, tile_t), lambda t: (0, t)),
            pl.BlockSpec((1, num_experts), lambda t: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((1, Fp), jnp.int32),
            jax.ShapeDtypeStruct((1, num_experts), jnp.float32),
        ),
        name="positions_in_expert_pallas",
        interpret=interpret,
    )(ids)
    return pos[0, :F], counts[0]
