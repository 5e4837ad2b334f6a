"""Pallas TPU kernel: slot-wise centroid accumulation (segment mean) as a
one-hot MXU contraction.

TPU adaptation of the paper's scatter-based clustering: TPUs have no fast
scatter, but onehot(slot)^T @ x is a [S, tile_t] x [tile_t, H] MXU matmul.
The kernel builds the one-hot mask in VREGs (iota compare) and accumulates
sums and counts across token tiles into the same output block (grid
revisiting along the token axis; output initialized at the first step).

Grid: (G, T/tile_t).  VMEM: x tile (tile_t×H), out (S×H) + counts (S×1).
For the production shapes (S=64..256, H<=8192) the output block is
64*8192*4 = 2 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(slots_ref, x_ref, sums_ref, counts_ref, *, num_slots, tile_t):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    slots = slots_ref[0]                                   # [1, tile_t]
    x = x_ref[0].astype(jnp.float32)                       # [tile_t, H]
    iota = jax.lax.broadcasted_iota(jnp.int32, (num_slots, tile_t), 0)
    onehot = (iota == slots).astype(jnp.float32)           # [S, tile_t]
    sums_ref[0] += jnp.dot(onehot, x,
                           preferred_element_type=jnp.float32)
    counts_ref[0] += jnp.sum(onehot, axis=1, keepdims=True)  # [S, 1]


@functools.partial(jax.jit,
                   static_argnames=("num_slots", "tile_t", "interpret"))
def segment_centroid_pallas(slots: jax.Array, x: jax.Array, *,
                            num_slots: int, tile_t: int = 128,
                            interpret: bool):
    """slots: [G, C] int32 in [0, num_slots); x: [G, C, H].
    Returns (centroids [G, S, H] f32, counts [G, S] f32); empty slots have
    centroid 0 (mask invalid tokens by pointing their slot at S-1 and
    weighting 0 upstream, or pre-zeroing their rows)."""
    G, C, H = x.shape
    pad_c = (-C) % tile_t
    if pad_c:
        x = jnp.pad(x, ((0, 0), (0, pad_c), (0, 0)))
        slots = jnp.pad(slots, ((0, 0), (0, pad_c)),
                        constant_values=num_slots + 7)  # out-of-range: no hit
    Cp = C + pad_c
    # ids ride as [G, 1, C] and counts as [G, S, 1] so every block's last
    # two dims are whole or (8, 128)-aligned, as Mosaic requires
    sums, counts = pl.pallas_call(
        functools.partial(_kernel, num_slots=num_slots, tile_t=tile_t),
        grid=(G, Cp // tile_t),
        in_specs=[
            pl.BlockSpec((1, 1, tile_t), lambda g, t: (g, 0, t)),
            pl.BlockSpec((1, tile_t, H), lambda g, t: (g, t, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, num_slots, H), lambda g, t: (g, 0, 0)),
            pl.BlockSpec((1, num_slots, 1), lambda g, t: (g, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((G, num_slots, H), jnp.float32),
            jax.ShapeDtypeStruct((G, num_slots, 1), jnp.float32),
        ),
        name="segment_centroid_pallas",
        interpret=interpret,
    )(slots.reshape(G, 1, Cp), x)
    counts = counts[..., 0]
    centroids = sums / jnp.maximum(counts, 1.0)[..., None]
    return centroids, counts
