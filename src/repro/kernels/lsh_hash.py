"""Pallas TPU kernel: fused cross-polytope LSH hashing.

Computes per-(token, hash) cross-polytope vertex ids:
  v      = x @ R_l                     (MXU matmul, [tile_t, Dr])
  idx    = argmax |v|                  (VREG reduction, first maximum)
  vertex = 2*idx + (v[idx] < 0)

fused so the rotated activations (L × [T, Dr]) never round-trip to HBM —
on the GPU reference implementation this is a GEMM + separate argmax kernel.

Grid: (T/tile_t,).  BlockSpecs keep one x tile (tile_t × H) and all L
rotations (L × H × Dr) in VMEM, and each step writes the whole
[tile_t, L] id block (a full-width last dim, as Mosaic requires).
VMEM footprint: tile_t*H*4 + L*H*Dr*4 bytes, double-buffered
(128*1536*4 = 0.75 MiB + 6*1536*64*4 = 2.25 MiB at d_model 1536).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, rot_ref, out_ref, *, num_hashes):
    x = x_ref[...].astype(jnp.float32)            # [tile_t, H]
    tile_t = x.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (tile_t, num_hashes), 1)
    out = jnp.zeros((tile_t, num_hashes), jnp.int32)
    for l in range(num_hashes):
        r = rot_ref[l].astype(jnp.float32)        # [H, Dr]
        v = jnp.dot(x, r, preferred_element_type=jnp.float32)  # [tile_t, Dr]
        av = jnp.abs(v)
        # argmax as a 2-D reduction: the first column holding the maximum
        # (jnp.argmax's tie rule), then the sign of v at that column
        iota = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
        best = jnp.max(av, axis=-1, keepdims=True)
        idx = jnp.min(jnp.where(av == best, iota, v.shape[1]), axis=-1,
                      keepdims=True)                       # [tile_t, 1]
        at_idx = jnp.sum(jnp.where(iota == idx, v, 0.0), axis=-1,
                         keepdims=True)
        vertex = 2 * idx + (at_idx < 0).astype(jnp.int32)
        out = jnp.where(col == l, vertex, out)
    out_ref[...] = out


@functools.partial(jax.jit, static_argnames=("tile_t", "interpret"))
def lsh_hash_pallas(x: jax.Array, rotations: jax.Array, *, tile_t: int = 128,
                    interpret: bool) -> jax.Array:
    """x: [T, H]; rotations: [L, H, Dr] -> per-hash vertex ids [T, L] int32.

    interpret=True executes the kernel body on CPU (validation); on TPU pass
    interpret=False for the compiled Mosaic kernel.
    """
    T, H = x.shape
    L, _, Dr = rotations.shape
    pad_t = (-T) % tile_t
    if pad_t:
        x = jnp.pad(x, ((0, pad_t), (0, 0)))
    Tp = T + pad_t
    out = pl.pallas_call(
        functools.partial(_kernel, num_hashes=L),
        grid=(Tp // tile_t,),
        in_specs=[
            pl.BlockSpec((tile_t, H), lambda t: (t, 0)),
            pl.BlockSpec((L, H, Dr), lambda t: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_t, L), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((Tp, L), jnp.int32),
        name="lsh_hash_pallas",
        interpret=interpret,
    )(x, rotations)
    return out[:T]
