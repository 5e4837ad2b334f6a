"""Pallas TPU kernels: wire codec fused into the dispatch/combine ops.

The quantized wire formats (kernels/wire_quant.py) used to run as
separate registry ops, so the f32 wire tensor made a full extra HBM
round-trip on both legs of the hottest path: scatter wrote [E, C, H] f32
to HBM, quantize read it back; and on the far side dequantize wrote
[G, S, H] f32 that the gather immediately re-read.  These kernels fold
the codec into the routing ops so the intermediate f32 tensor only ever
exists tile-locally in VMEM:

  dispatch_scatter_quantize   selection-mask MXU scatter accumulated in a
                              VMEM scratch block, then per-(expert, row)
                              po2 absmax scale + int8/fp8 encode on the
                              final token-tile visit — the f32 buffer
                              never reaches HBM.
  dequantize_combine_gather   gather reads the quantized buffer + scales
                              and dequantizes in VREGs right before the
                              weighted reduce.
  dequantize_residual_apply   the LSH combine leg: dequantize the received
                              expert outputs, subtract the (optional)
                              centroid base and gather-add the residual
                              compensation, all on the VMEM-resident
                              [S, H] block (clustering.decompress fused
                              with WireCodec.decode).

Bit-identity contract (docs/kernels.md): each op computes EXACTLY the
composition of its unfused parts, so fused and composed paths agree
bit-for-bit on every backend, values and (through the composite VJPs in
comm/wire.py) gradients.  The routing twins keep a one-hot MXU
contraction while the unfused routing ops move rows by index
(kernels/scatter_gather.py); they agree because each gathered output
has one nonzero term, and both add colliding scatter entries in entry
order (along the sequential token-tile axis here).  The po2 scale
arithmetic is shared with kernels/wire_quant.py.  The interpret-mode
suite checks the contract; on a v5e, Mosaic's default precision rounds
the f32 operands of the one-hot contraction to bf16, so there the twins
differ from the exact row moves by that rounding.

Grids: scatter-quantize (E, C/tile_c, F/tile_t) with a [tile_c, H] f32
VMEM scratch accumulator; dequant-gather (F/tile_t, E, C/tile_c);
dequant-residual (G, C/tile_t).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.residual_apply import dot_tn
from repro.kernels.scatter_gather import capacity_tile
from repro.kernels.wire_quant import _encode, po2_scale, qmax, quant_dtype


def sel_mask(ids, pos, expert, row0, rows):
    """[rows, tile_t] selection mask between buffer rows [row0, row0 +
    rows) of ``expert`` and a token tile: pos one-hot AND id match, as
    one 2-D int compare.  ids/pos: [1, tile_t] (tokens along lanes).  The
    gather direction contracts it transposed (``dot_tn``)."""
    own = jnp.where(ids == expert, pos - row0, -1)         # [1, tile_t]
    iota = jax.lax.broadcasted_iota(jnp.int32, (rows, ids.shape[1]), 0)
    return (iota == own).astype(jnp.float32)


# ------------------------------------------- scatter + quantize (fused) --
#
# Scales ride these kernels as [E, C, 1] / [G, S, 1] columns, as in
# kernels/wire_quant.py, and the capacity axis is tiled like the unfused
# routing kernels (``capacity_tile``): each row's absmax spans H only, so
# a [tile_c, H] tile quantizes on its own.

def _scatter_quant_kernel(ids_ref, pos_ref, src_ref, q_ref, scale_ref,
                          acc_ref, *, tile_c, fmt, qmax_val, n_t):
    e = pl.program_id(0)
    c = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    sel = sel_mask(ids_ref[...], pos_ref[...], e, c * tile_c, tile_c)
    src = src_ref[...].astype(jnp.float32)                 # [tile_t, H]
    acc_ref[...] += jnp.dot(sel, src, preferred_element_type=jnp.float32)

    @pl.when(t == n_t - 1)
    def _finish():
        buf = acc_ref[...]                                 # [tile_c, H] f32
        absmax = jnp.max(jnp.abs(buf), axis=-1, keepdims=True)
        scale = po2_scale(absmax, qmax_val)                # [tile_c, 1]
        q_ref[0] = _encode(buf / scale, fmt)
        scale_ref[0] = scale


@functools.partial(jax.jit, static_argnames=("num_experts", "capacity",
                                             "fmt", "tile_t", "interpret"))
def dispatch_scatter_quantize_pallas(expert_ids: jax.Array, pos: jax.Array,
                                     src: jax.Array, *, num_experts: int,
                                     capacity: int, fmt: str,
                                     tile_t: int = 128,
                                     interpret: bool):
    """expert_ids/pos: [F] int32; src: [F, H].  Returns
    (q [E, C, H] int8|fp8, scales [E, C] f32) — bit-identical to
    ``wire_quantize(dispatch_scatter(...))`` with the f32 buffer kept in a
    VMEM scratch accumulator instead of round-tripping HBM.  Out-of-range
    entries contribute nothing; empty rows get scale 1, zero payload."""
    F, H = src.shape
    dt = quant_dtype(fmt)
    pad_f = (-F) % tile_t
    ids = expert_ids.reshape(1, F).astype(jnp.int32)
    p = pos.reshape(1, F).astype(jnp.int32)
    if pad_f:
        ids = jnp.pad(ids, ((0, 0), (0, pad_f)), constant_values=-1)
        p = jnp.pad(p, ((0, 0), (0, pad_f)))
        src = jnp.pad(src, ((0, pad_f), (0, 0)))
    Fp = F + pad_f
    n_t = Fp // tile_t
    tile_c = capacity_tile(capacity, H)
    q, scales = pl.pallas_call(
        functools.partial(_scatter_quant_kernel, tile_c=tile_c,
                          fmt=fmt, qmax_val=qmax(fmt), n_t=n_t),
        grid=(num_experts, capacity // tile_c, n_t),
        in_specs=[
            pl.BlockSpec((1, tile_t), lambda e, c, t: (0, t)),
            pl.BlockSpec((1, tile_t), lambda e, c, t: (0, t)),
            pl.BlockSpec((tile_t, H), lambda e, c, t: (t, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, tile_c, H), lambda e, c, t: (e, c, 0)),
            pl.BlockSpec((1, tile_c, 1), lambda e, c, t: (e, c, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((num_experts, capacity, H), dt),
            jax.ShapeDtypeStruct((num_experts, capacity, 1), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((tile_c, H), jnp.float32)],
        name="dispatch_scatter_quantize_pallas",
        interpret=interpret,
    )(ids, p, src)
    return q, scales[..., 0]


# ------------------------------------------- dequantize + gather (fused) --

def _dequant_gather_kernel(ids_ref, pos_ref, w_ref, q_ref, scale_ref,
                           out_ref, *, tile_c):
    e = pl.program_id(1)
    c = pl.program_id(2)

    @pl.when((e == 0) & (c == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    sel = sel_mask(ids_ref[...], pos_ref[...], e, c * tile_c, tile_c)
    w = w_ref[...].astype(jnp.float32)                     # [1, tile_t]
    # dequantize the [tile_c, H] expert block in VREGs — the f32 buffer
    # the unfused path would have written to HBM never leaves the
    # registers
    buf = q_ref[0].astype(jnp.float32) * scale_ref[0]
    out_ref[...] += dot_tn(sel * w, buf)


@functools.partial(jax.jit, static_argnames=("tile_t", "interpret"))
def dequantize_combine_gather_pallas(expert_ids: jax.Array, pos: jax.Array,
                                     q: jax.Array, scales: jax.Array,
                                     weights: jax.Array, *,
                                     tile_t: int = 128,
                                     interpret: bool) -> jax.Array:
    """expert_ids/pos: [F] int32; q: [E, C, H] int8|fp8; scales: [E, C];
    weights: [F].  Returns [F, H] f32 = weights[f] * (q * scale)[id_f,
    pos_f] — bit-identical to ``combine_gather(ids, pos,
    wire_dequantize(q, scales), weights)``.  Out-of-range entries gather
    zero (overflow bin)."""
    E, C, H = q.shape
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
        functools.partial(_dequant_gather_kernel, tile_c=tile_c),
        grid=(Fp // tile_t, E, C // tile_c),
        in_specs=[
            pl.BlockSpec((1, tile_t), lambda t, e, c: (0, t)),
            pl.BlockSpec((1, tile_t), lambda t, e, c: (0, t)),
            pl.BlockSpec((1, tile_t), lambda t, e, c: (0, t)),
            pl.BlockSpec((1, tile_c, H), lambda t, e, c: (e, c, 0)),
            pl.BlockSpec((1, tile_c, 1), lambda t, e, c: (e, c, 0)),
        ],
        out_specs=pl.BlockSpec((tile_t, H), lambda t, e, c: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((Fp, H), jnp.float32),
        name="dequantize_combine_gather_pallas",
        interpret=interpret,
    )(ids, p, w, q, scales.reshape(E, C, 1))
    return out[:F]


# --------------------------------- dequantize + residual gather (fused) --

def _dq_resid_kernel(slots_ref, q_ref, scale_ref, resid_ref, out_ref, *,
                     num_slots):
    slots = slots_ref[0]                                   # [1, tile_t]
    dq = q_ref[0].astype(jnp.float32) * scale_ref[0]       # [S, H]
    resid = resid_ref[0].astype(jnp.float32)               # [tile_t, H]
    onehot = (jax.lax.broadcasted_iota(jnp.int32,
                                       (num_slots, slots.shape[1]), 0)
              == slots).astype(jnp.float32)                # [S, tile_t]
    out_ref[0] = dot_tn(onehot, dq) + resid


def _dq_resid_base_kernel(slots_ref, q_ref, scale_ref, base_ref, resid_ref,
                          out_ref, *, num_slots):
    slots = slots_ref[0]
    dq = q_ref[0].astype(jnp.float32) * scale_ref[0]
    delta = dq - base_ref[0].astype(jnp.float32)           # [S, H]
    resid = resid_ref[0].astype(jnp.float32)
    onehot = (jax.lax.broadcasted_iota(jnp.int32,
                                       (num_slots, slots.shape[1]), 0)
              == slots).astype(jnp.float32)
    out_ref[0] = dot_tn(onehot, delta) + resid


@functools.partial(jax.jit, static_argnames=("tile_t", "interpret"))
def dequantize_residual_apply_pallas(slots: jax.Array, q: jax.Array,
                                     scales: jax.Array, residual: jax.Array,
                                     base: jax.Array = None, *,
                                     tile_t: int = 128,
                                     interpret: bool) -> jax.Array:
    """slots: [G, C] int32; q: [G, S, H] int8|fp8; scales: [G, S];
    residual: [G, C, H]; base: optional [G, S, H].  Returns [G, C, H] f32
    = ((q * scale) - base)[g, slots] + residual — bit-identical to
    ``residual_apply(slots, wire_dequantize(q, scales) - base, residual)``
    (base omitted when None).  Out-of-range slot ids gather zero."""
    G, C, H = residual.shape
    S = q.shape[1]
    pad_c = (-C) % tile_t
    if pad_c:
        residual = jnp.pad(residual, ((0, 0), (0, pad_c), (0, 0)))
        slots = jnp.pad(slots, ((0, 0), (0, pad_c)), constant_values=-1)
    Cp = C + pad_c
    in_specs = [
        pl.BlockSpec((1, 1, tile_t), lambda g, t: (g, 0, t)),
        pl.BlockSpec((1, S, H), lambda g, t: (g, 0, 0)),
        pl.BlockSpec((1, S, 1), lambda g, t: (g, 0, 0)),
    ]
    operands = [slots.reshape(G, 1, Cp), q, scales.reshape(G, S, 1)]
    if base is not None:
        in_specs.append(pl.BlockSpec((1, S, H), lambda g, t: (g, 0, 0)))
        operands.append(base)
        kernel = _dq_resid_base_kernel
    else:
        kernel = _dq_resid_kernel
    in_specs.append(pl.BlockSpec((1, tile_t, H), lambda g, t: (g, t, 0)))
    operands.append(residual)
    out = pl.pallas_call(
        functools.partial(kernel, num_slots=S),
        grid=(G, Cp // tile_t),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, tile_t, H), lambda g, t: (g, t, 0)),
        out_shape=jax.ShapeDtypeStruct((G, Cp, H), jnp.float32),
        name="dequantize_residual_apply_pallas",
        interpret=interpret,
    )(*operands)
    return out[:, :C]
