"""Pallas TPU kernels: quantized wire format for the LSH all-to-all.

The compressed dispatch/combine exchange ships one H-vector per occupied
(expert, slot); ``wire_quantize`` shrinks each vector to int8 (or
fp8-e4m3) with one f32 scale per (group, slot) riding the a2a as a
sidecar — ~2x fewer wire bytes than the bf16 payload at H >= 64.

Scales are **power-of-two-rounded absmax**: scale = 2^ceil(log2(absmax /
qmax)), computed with exact exponent-bit arithmetic (no log2 rounding).
Power-of-two scales cost < 0.5 bit of extra quantization error vs exact
absmax but buy the property the residual-compensation scheme is built on
(core/clustering.py): quantization is **idempotent on its own output** —
quantize(dequantize(quantize(x))) == quantize(x) bit-for-bit, because
every dequantized value q * 2^k is exact in f32/bf16 and re-deriving the
scale from s * max|q| lands on the same power of two (int8; fp8 may slide
to the equivalent (2q, s/2) encoding when the row max rounded down to
exactly qmax/2 — the dequantized values are still bit-identical).  compress() can
therefore store the dequantized centroids, and the transport can
re-encode them, with zero drift between the residuals computed at the
sender and the values the expert actually sees.

Quantize grid: (G, S/tile_s); the absmax reduction, scale derivation and
rounding all happen on the VMEM-resident [tile_s, H] tile in one pass.
Dequantize is the mirror (one multiply on the tile) and is what
``comm/wire.py`` runs on the received chunk right before the expert MLP,
so the f32 wire tensor never round-trips HBM between dequant and use.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

INT8 = "int8"
FP8 = "fp8"
BF16_FORMAT = "bf16"
QUANT_FORMATS = (INT8, FP8)
WIRE_FORMATS = (BF16_FORMAT,) + QUANT_FORMATS

# fp8 support is version/platform gated: resolve the dtype once.
_FP8_DTYPE = getattr(jnp, "float8_e4m3fn", None)


def validate_wire_format(fmt: str) -> str:
    """One validation for every wire-format entry point
    (clustering._to_wire, comm.wire.make_codec)."""
    if fmt not in WIRE_FORMATS:
        raise ValueError(f"unknown wire format {fmt!r}; "
                         f"available: {sorted(WIRE_FORMATS)}")
    return fmt


def quant_dtype(fmt: str):
    if fmt == INT8:
        return jnp.int8
    if fmt == FP8:
        if _FP8_DTYPE is None:
            raise ValueError(
                "wire format 'fp8' needs jnp.float8_e4m3fn, which this "
                "JAX build does not provide; use 'int8' or 'bf16'")
        return _FP8_DTYPE
    raise ValueError(f"unknown quantized wire format {fmt!r}; "
                     f"available: {sorted(QUANT_FORMATS)}")


def qmax(fmt: str) -> float:
    """Largest representable payload magnitude (127 for int8, 448 for
    fp8-e4m3: 1.75 * 2^8)."""
    quant_dtype(fmt)
    return 127.0 if fmt == INT8 else 448.0


def po2_scale(absmax: jax.Array, qmax_val: float) -> jax.Array:
    """Smallest power of two >= absmax / qmax (f32), via exponent-bit
    arithmetic so the result is exact — ceil(log2(.)) computed in floats
    can flip at power-of-two boundaries and break idempotence.

    absmax == 0 maps to scale 1.0 (all-zero tiles quantize to zero and
    dequantize to exactly zero).  Works identically as XLA ops (the
    reference oracle) and inside a Pallas kernel body.
    """
    v = absmax.astype(jnp.float32) / jnp.float32(qmax_val)
    bits = jax.lax.bitcast_convert_type(v, jnp.int32)
    exp = ((bits >> 23) & 0xFF) - 127                  # floor(log2 v), normals
    frac = ((bits & 0x7FFFFF) != 0).astype(jnp.int32)
    k = jnp.clip(exp + frac, -126, 126)                # ceil(log2 v), exact
    scale = jax.lax.bitcast_convert_type((k + 127) << 23, jnp.float32)
    return jnp.where(absmax > 0, scale, jnp.float32(1.0))


def _encode(y: jax.Array, fmt: str) -> jax.Array:
    """Scaled f32 tile -> payload dtype.  |y| <= qmax by construction of
    the power-of-two scale; the clip guards the boundary ulp."""
    if fmt == INT8:
        return jnp.clip(jnp.round(y), -127.0, 127.0).astype(jnp.int8)
    return jnp.clip(y, -448.0, 448.0).astype(_FP8_DTYPE)


def _quant_kernel(x_ref, q_ref, scale_ref, *, fmt, qmax_val, num_rows,
                  tile_s):
    # Mask rows past the true slot count BEFORE the absmax pass: padded
    # rows never enter the scale derivation (they come out as zero
    # payload, scale 1, whatever the pad values were) instead of having
    # scales computed for them.
    s = pl.program_id(1)
    x = x_ref[0].astype(jnp.float32)                   # [tile_s, H]
    row = s * tile_s + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    x = jnp.where(row < num_rows, x, 0.0)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)   # [tile_s, 1]
    scale = po2_scale(absmax, qmax_val)
    q_ref[0] = _encode(x / scale, fmt)
    scale_ref[0] = scale


def _dequant_kernel(q_ref, scale_ref, out_ref):
    q = q_ref[0].astype(jnp.float32)                   # [tile_s, H]
    out_ref[0] = q * scale_ref[0]                      # scale: [tile_s, 1]


# Scales ride the kernels as [G, S, 1] columns: the per-row reduction
# lands there naturally, and the block's last two dims stay whole or
# (8, 128)-aligned, as Mosaic requires.

@functools.partial(jax.jit, static_argnames=("fmt", "tile_s", "interpret"))
def wire_quantize_pallas(x: jax.Array, *, fmt: str, tile_s: int = 8,
                         interpret: bool):
    """x: [G, S, H] -> (q [G, S, H] int8|fp8, scales [G, S] f32).

    One power-of-two absmax scale per (group, slot) row; all-zero rows get
    scale 1 and an all-zero payload."""
    G, S, H = x.shape
    dt = quant_dtype(fmt)
    pad_s = (-S) % tile_s
    if pad_s:
        x = jnp.pad(x, ((0, 0), (0, pad_s), (0, 0)))
    Sp = S + pad_s
    q, scales = pl.pallas_call(
        functools.partial(_quant_kernel, fmt=fmt, qmax_val=qmax(fmt),
                          num_rows=S, tile_s=tile_s),
        grid=(G, Sp // tile_s),
        in_specs=[pl.BlockSpec((1, tile_s, H), lambda g, s: (g, s, 0))],
        out_specs=(
            pl.BlockSpec((1, tile_s, H), lambda g, s: (g, s, 0)),
            pl.BlockSpec((1, tile_s, 1), lambda g, s: (g, s, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((G, Sp, H), dt),
            jax.ShapeDtypeStruct((G, Sp, 1), jnp.float32),
        ),
        name="wire_quantize_pallas",
        interpret=interpret,
    )(x)
    return q[:, :S], scales[:, :S, 0]


@functools.partial(jax.jit, static_argnames=("tile_s", "interpret"))
def wire_dequantize_pallas(q: jax.Array, scales: jax.Array, *,
                           tile_s: int = 8, interpret: bool):
    """(q [G, S, H], scales [G, S]) -> [G, S, H] f32 = q * scale."""
    G, S, H = q.shape
    pad_s = (-S) % tile_s
    if pad_s:
        q = jnp.pad(q, ((0, 0), (0, pad_s), (0, 0)))
        scales = jnp.pad(scales, ((0, 0), (0, pad_s)))
    Sp = S + pad_s
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(G, Sp // tile_s),
        in_specs=[
            pl.BlockSpec((1, tile_s, H), lambda g, s: (g, s, 0)),
            pl.BlockSpec((1, tile_s, 1), lambda g, s: (g, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile_s, H), lambda g, s: (g, s, 0)),
        out_shape=jax.ShapeDtypeStruct((G, Sp, H), jnp.float32),
        name="wire_dequantize_pallas",
        interpret=interpret,
    )(q, scales.reshape(G, Sp, 1))
    return out[:, :S]
