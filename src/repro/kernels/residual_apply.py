"""Pallas TPU kernel: fused residual error-compensation gather.

Y[g, c] = E(centroids)[g, slot[g, c]] + residual[g, c]      (paper Eq. 5)

A gather along the slot axis fused with the add, so the reconstructed
tensor is produced in one pass over HBM (the gather operand — the expert
outputs on centroids — stays VMEM-resident per group).

Grid: (G, C/tile_t).  VMEM: expert_out block (S×H), residual tile, out tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def dot_tn(a, b):
    """a^T @ b on the MXU: a [K, M], b [K, N] -> [M, N] f32.  Shared with
    the fused codec kernels (kernels/fused_wire.py)."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _kernel(slots_ref, eout_ref, resid_ref, out_ref, *, num_slots):
    slots = slots_ref[0]                          # [1, tile_t]
    eout = eout_ref[0].astype(jnp.float32)        # [S, H]
    resid = resid_ref[0].astype(jnp.float32)      # [tile_t, H]
    onehot = (jax.lax.broadcasted_iota(jnp.int32,
                                       (num_slots, slots.shape[1]), 0)
              == slots).astype(jnp.float32)       # [S, tile_t]
    gathered = dot_tn(onehot, eout)
    out_ref[0] = (gathered + resid).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_t", "interpret"))
def residual_apply_pallas(slots: jax.Array, expert_out: jax.Array,
                          residual: jax.Array, *, tile_t: int = 128,
                          interpret: bool) -> jax.Array:
    """slots: [G, C] int32; expert_out: [G, S, H]; residual: [G, C, H].
    Returns [G, C, H] = expert_out[g, slots] + residual (f32)."""
    G, C, H = residual.shape
    S = expert_out.shape[1]
    pad_c = (-C) % tile_t
    if pad_c:
        residual = jnp.pad(residual, ((0, 0), (0, pad_c), (0, 0)))
        slots = jnp.pad(slots, ((0, 0), (0, pad_c)))
    Cp = C + pad_c
    out = pl.pallas_call(
        functools.partial(_kernel, num_slots=S),
        grid=(G, Cp // tile_t),
        in_specs=[
            # ids ride as [G, 1, C] so the block's last two dims are whole
            # or (8, 128)-aligned, as Mosaic requires
            pl.BlockSpec((1, 1, tile_t), lambda g, t: (g, 0, t)),
            pl.BlockSpec((1, S, H), lambda g, t: (g, 0, 0)),
            pl.BlockSpec((1, tile_t, H), lambda g, t: (g, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile_t, H), lambda g, t: (g, t, 0)),
        out_shape=jax.ShapeDtypeStruct((G, Cp, H), jnp.float32),
        name="residual_apply_pallas",
        interpret=interpret,
    )(slots.reshape(G, 1, Cp), expert_out, residual)
    return out[:, :C]
