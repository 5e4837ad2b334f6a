"""Pallas TPU kernels: dispatch-buffer scatter and its transpose gather.

``dispatch_scatter`` builds the [E, C, H] expert dispatch buffer from the
flattened routed tokens; ``combine_gather`` reads each (token, choice)'s
row back out of a [E, C, H] result buffer and applies its combine weight.
The two are mutual transposes (the same [C, tile_t] selection mask, used
as sel @ src vs sel^T @ buf), which is what lets each serve as the
other's backward pass in kernels/dispatch.py — exactly how
``segment_centroid`` / ``residual_apply`` pair up for the LSH path.

TPUs have no fast scatter: both directions build the selection mask
tile-locally in VREGs (iota compare on position AND expert id) and contract
on the MXU, so no [F, E, C] one-hot ever reaches HBM.

Overflow-bin contract (shared with every registry op): an entry whose
expert id falls outside [0, E) or whose position falls outside [0, C)
matches no mask row — it contributes nothing to the scatter and gathers
exactly zero.

Grids: scatter (E, C/tile_c, F/tile_t) revisiting the [tile_c, H] expert
block along the token axis; gather (F/tile_t, E, C/tile_c) revisiting the
[tile_t, H] output block along the expert and capacity axes.  VMEM per
step: one token tile + one [tile_c, H] expert block (``capacity_tile``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


# A whole [C, H] f32 expert block at C=2048, H=1536 is 12 MiB, 24 MiB
# double-buffered: past v5e's 16 MiB default scoped VMEM.  Blocks over
# _BLOCK_BYTES therefore split the capacity axis into row tiles that
# divide C (multiples of 32 rows, the int8 sublane tile, so the fused
# kernels' quantized blocks stay aligned too).  At C=2048, H=1536 that is
# 512-row (3 MiB) blocks: out 2x3 MiB + dot result 3 MiB + token tile
# 2x0.75 MiB, inside the default limit.
_BLOCK_BYTES = 4 << 20


def capacity_tile(capacity: int, hidden: int) -> int:
    """Capacity rows per kernel block: all of C when the f32 [C, H] block
    fits _BLOCK_BYTES, else the largest 32-row multiple dividing C that
    does (all of C when none does)."""
    if capacity * hidden * 4 <= _BLOCK_BYTES:
        return capacity
    fits = [r for r in range(32, capacity, 32)
            if capacity % r == 0 and r * hidden * 4 <= _BLOCK_BYTES]
    return fits[-1] if fits else capacity


def sel_mask(ids, pos, expert, row0, rows):
    """[rows, tile_t] selection mask between buffer rows [row0, row0 +
    rows) of ``expert`` and a token tile: pos one-hot AND id match, as
    one 2-D int compare.  ids/pos: [1, tile_t] (tokens along lanes).  The
    gather direction contracts it transposed (``dot_tn``).  Shared with
    the fused codec kernels (kernels/fused_wire.py) — ONE mask builder is
    part of what makes fused and composed paths bit-identical."""
    own = jnp.where(ids == expert, pos - row0, -1)         # [1, tile_t]
    iota = jax.lax.broadcasted_iota(jnp.int32, (rows, ids.shape[1]), 0)
    return (iota == own).astype(jnp.float32)


def dot_tn(a, b):
    """a^T @ b on the MXU: a [K, M], b [K, N] -> [M, N] f32."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _scatter_kernel(ids_ref, pos_ref, src_ref, out_ref, *, tile_c):
    e = pl.program_id(0)
    c = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    sel = sel_mask(ids_ref[...], pos_ref[...], e, c * tile_c, tile_c)
    src = src_ref[...].astype(jnp.float32)                 # [tile_t, H]
    out_ref[0] += jnp.dot(sel, src, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("num_experts", "capacity",
                                             "tile_t", "interpret"))
def dispatch_scatter_pallas(expert_ids: jax.Array, pos: jax.Array,
                            src: jax.Array, *, num_experts: int,
                            capacity: int, tile_t: int = 128,
                            interpret: bool) -> jax.Array:
    """expert_ids/pos: [F] int32; src: [F, H].  Returns [E, C, H] f32 with
    buf[e, c] = Σ_{f: id_f == e, pos_f == c} src[f]; out-of-range entries
    contribute nothing (overflow bin)."""
    F, H = src.shape
    pad_f = (-F) % tile_t
    ids = expert_ids.reshape(1, F).astype(jnp.int32)
    p = pos.reshape(1, F).astype(jnp.int32)
    if pad_f:
        ids = jnp.pad(ids, ((0, 0), (0, pad_f)), constant_values=-1)
        p = jnp.pad(p, ((0, 0), (0, pad_f)))
        src = jnp.pad(src, ((0, pad_f), (0, 0)))
    Fp = F + pad_f
    tile_c = capacity_tile(capacity, H)
    return pl.pallas_call(
        functools.partial(_scatter_kernel, tile_c=tile_c),
        grid=(num_experts, capacity // tile_c, Fp // tile_t),
        in_specs=[
            pl.BlockSpec((1, tile_t), lambda e, c, t: (0, t)),
            pl.BlockSpec((1, tile_t), lambda e, c, t: (0, t)),
            pl.BlockSpec((tile_t, H), lambda e, c, t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile_c, H), lambda e, c, t: (e, c, 0)),
        out_shape=jax.ShapeDtypeStruct((num_experts, capacity, H),
                                       jnp.float32),
        name="dispatch_scatter_pallas",
        interpret=interpret,
    )(ids, p, src)


def _gather_kernel(ids_ref, pos_ref, w_ref, buf_ref, out_ref, *, tile_c):
    e = pl.program_id(1)
    c = pl.program_id(2)

    @pl.when((e == 0) & (c == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    sel = sel_mask(ids_ref[...], pos_ref[...], e, c * tile_c, tile_c)
    w = w_ref[...].astype(jnp.float32)                     # [1, tile_t]
    buf = buf_ref[0].astype(jnp.float32)                   # [tile_c, H]
    # one nonzero per token column, so folding the weight into the mask
    # rounds exactly like weighting the gathered row
    out_ref[...] += dot_tn(sel * w, buf)


@functools.partial(jax.jit, static_argnames=("tile_t", "interpret"))
def combine_gather_pallas(expert_ids: jax.Array, pos: jax.Array,
                          buf: jax.Array, weights: jax.Array, *,
                          tile_t: int = 128,
                          interpret: bool) -> jax.Array:
    """expert_ids/pos: [F] int32; buf: [E, C, H]; weights: [F].
    Returns [F, H] f32 = weights[f] * buf[id_f, pos_f]; out-of-range
    entries gather zero (overflow bin)."""
    E, C, H = buf.shape
    F = expert_ids.shape[0]
    pad_f = (-F) % tile_t
    ids = expert_ids.reshape(1, F).astype(jnp.int32)
    p = pos.reshape(1, F).astype(jnp.int32)
    w = weights.reshape(1, F)
    if pad_f:
        ids = jnp.pad(ids, ((0, 0), (0, pad_f)), constant_values=-1)
        p = jnp.pad(p, ((0, 0), (0, pad_f)))
        w = jnp.pad(w, ((0, 0), (0, pad_f)))
    Fp = F + pad_f
    tile_c = capacity_tile(C, H)
    out = pl.pallas_call(
        functools.partial(_gather_kernel, tile_c=tile_c),
        grid=(Fp // tile_t, E, C // tile_c),
        in_specs=[
            pl.BlockSpec((1, tile_t), lambda t, e, c: (0, t)),
            pl.BlockSpec((1, tile_t), lambda t, e, c: (0, t)),
            pl.BlockSpec((1, tile_t), lambda t, e, c: (0, t)),
            pl.BlockSpec((1, tile_c, H), lambda t, e, c: (e, c, 0)),
        ],
        out_specs=pl.BlockSpec((tile_t, H), lambda t, e, c: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((Fp, H), jnp.float32),
        name="combine_gather_pallas",
        interpret=interpret,
    )(ids, p, w, buf)
    return out[:F]
