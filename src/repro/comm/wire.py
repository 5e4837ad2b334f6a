"""Wire codec: the quantized (or bf16) on-wire representation of the MoE
exchange, shared by all three transports (docs/comm.md).

A ``WireCodec`` describes how the [R, e_local, c, H] wire tensor travels:

  "bf16"   one leaf, the payload cast to ``wire_dtype`` (today's format);
  "int8"   two leaves: int8 payload + a [R, e_local, c] f32 power-of-two
  "fp8"    scale sidecar (kernels/wire_quant.py), ~2x fewer bytes.

``coded_transfer`` is ONE planned all-to-all of a float tensor under a
codec: encode -> per-leaf transport -> decode.  It is the custom_vjp
boundary that makes the quantized wire trainable: an int8 payload has no
cotangent (integer primals are float0 in JAX), so instead of
differentiating through the leaves, the backward pass is the transposed
transport of the float cotangent — straight-through across the
encode/transport/decode sandwich, exactly mirroring the bf16 path's
backward program (gradients are never quantized; the backward wire stays
``grad_dtype`` = bf16).

Because quantization is per-(group, slot) row, encode commutes with slot
slicing — the pipelined transport slices the FLOAT tensor and each chunk
transfer carries its own payload+scales, which is what keeps the scales
sidecar in lockstep with slot chunks, and chunked results bit-identical
to the unchunked transfer.  The hierarchical transport runs both of its
hops on every leaf, so the sidecar rides the 2-hop per hop.

Re-encoding is lossless by construction: ``clustering.compress`` already
stores the DEQUANTIZED centroids (power-of-two scales make the quant pair
idempotent on its own output), so encode here reproduces bit-identical
wire values to the ones the residuals were computed against.

The FUSED transfers at the bottom of this module are the composite
custom_vjp boundaries over the fused codec kernels
(kernels/fused_wire.py, docs/kernels.md §fusion): each one spans
float-in -> float-out across encode/scatter + transport +
decode/gather, calls the fused registry op in its forward, and
constructs its backward from the SAME unfused registry ops the composed
path differentiates through — which is what makes fused-path values AND
gradients bit-identical to the unfused composition per backend.  The
pipelined transport keeps the per-chunk coded path (its overlap needs
the float tensor sliced before encode); callers gate on
``CommPlan.leaf_transports`` + ``fused_wire_enabled`` ($REPRO_FUSED_WIRE=0
is the escape hatch the parity suite flips).
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from functools import partial
from typing import Callable, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.comm.collectives import _raw_a2a
from repro.comm.hierarchical import _two_hop
from repro.kernels import dispatch
from repro.kernels.dispatch import _float0_like
from repro.kernels.wire_quant import (BF16_FORMAT, QUANT_FORMATS,
                                      WIRE_FORMATS, validate_wire_format)
from repro.obs import tracing as obs_tracing
from repro.obs.tracing import phase_scope

FUSED_ENV = "REPRO_FUSED_WIRE"


def fused_wire_enabled() -> bool:
    """Trace-time gate for the fused codec transfers ($REPRO_FUSED_WIRE;
    "0" forces the unfused composed path — the bit-parity suite's
    baseline)."""
    return os.environ.get(FUSED_ENV, "1") != "0"


@dataclass(frozen=True)
class WireCodec:
    """Static (hashable) trace-time description of the wire format.

    ``backend`` holds the resolved per-op kernel-backend mapping as sorted
    items so the codec can ride custom_vjp nondiff argnums."""
    fmt: str                              # "bf16" | "int8" | "fp8"
    wire_dtype: str = "bfloat16"          # payload dtype of the bf16 format
    compute_dtype: str = "bfloat16"       # dtype handed to the expert MLP
    backend: Tuple[Tuple[str, str], ...] = ()

    @property
    def quantized(self) -> bool:
        return self.fmt in QUANT_FORMATS

    @property
    def grad_dtype(self):
        """Backward-pass wire dtype: gradients are not quantized — the
        straight-through backward transports bf16 (or the bf16 format's
        own payload dtype)."""
        return jnp.dtype(self.wire_dtype) if self.fmt == BF16_FORMAT \
            else jnp.bfloat16

    def encode(self, x: jax.Array) -> Tuple[jax.Array, ...]:
        """Float wire tensor [..., c, H] -> transport leaves (payload,
        [scales]).  Quantization collapses the leading dims to the
        [G, S, H] kernel contract and restores them on the sidecar."""
        if not self.quantized:
            return (x.astype(jnp.dtype(self.wire_dtype)),)
        lead = x.shape[:-2]
        q, scales = dispatch.wire_quantize(
            x.reshape((-1,) + x.shape[-2:]), self.fmt,
            backend=dict(self.backend) or None)
        return (q.reshape(x.shape),
                scales.reshape(lead + x.shape[-2:-1]))

    def decode(self, leaves: Tuple[jax.Array, ...]) -> jax.Array:
        """Transport leaves -> float tensor in ``compute_dtype``.  Exact
        for the quantized formats: power-of-two-scaled int8/fp8 values are
        representable in bf16."""
        if not self.quantized:
            return leaves[0].astype(jnp.dtype(self.compute_dtype))
        q, scales = leaves
        out = dispatch.wire_dequantize(
            q.reshape((-1,) + q.shape[-2:]),
            scales.reshape(-1, scales.shape[-1]),
            backend=dict(self.backend) or None)
        return out.reshape(q.shape).astype(jnp.dtype(self.compute_dtype))


def make_codec(fmt: str, *, wire_dtype="bfloat16", compute_dtype="bfloat16",
               backend: dispatch.BackendSpec = None) -> WireCodec:
    """Validate the format name and freeze the backend spec — a per-op
    mapping (``dispatch.resolve_backends`` output), a single backend name
    (resolved here), or None (= auto at call time)."""
    validate_wire_format(fmt)
    if isinstance(backend, Mapping):
        items = tuple(sorted(backend.items()))
    elif backend is None:
        items = ()
    else:
        items = (("*", dispatch.resolve_backend(backend)),)
    return WireCodec(fmt=fmt, wire_dtype=jnp.dtype(wire_dtype).name,
                     compute_dtype=jnp.dtype(compute_dtype).name,
                     backend=items)


# ------------------------------------------------------- coded transfer --

@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def coded_transfer(x, codec: WireCodec, fwd_leaf: Callable,
                   bwd_leaf: Callable):
    """One planned a2a of float ``x`` under ``codec``: encode, move every
    leaf with ``fwd_leaf`` (flat / 2-hop / per-chunk — already bound to
    axis and groups), decode.  The backward pass is ``bwd_leaf`` — the
    TRANSPOSE transport — applied straight-through to the float cotangent
    in ``codec.grad_dtype`` (the quant pair contributes identity)."""
    return codec.decode(tuple(fwd_leaf(leaf) for leaf in codec.encode(x)))


def _transfer_fwd(x, codec, fwd_leaf, bwd_leaf):
    # The cotangent must come back in the PRIMAL's dtype, which can differ
    # from the decoded output's compute_dtype (e.g. an f32 expert-MLP
    # output entering a bf16-compute combine leg).
    return coded_transfer(x, codec, fwd_leaf, bwd_leaf), \
        jnp.zeros((), x.dtype)


def _transfer_bwd(codec, fwd_leaf, bwd_leaf, xproto, ct):
    return (bwd_leaf(ct.astype(codec.grad_dtype)).astype(xproto.dtype),)


coded_transfer.defvjp(_transfer_fwd, _transfer_bwd)


# ------------------------------------------------- per-transport leaves --

def flat_leaves(axis_name: str):
    """(fwd, bwd) leaf transports for the flat a2a (self-transpose)."""
    def leaf(v):
        return _raw_a2a(v, axis_name, 0, 0)
    return leaf, leaf


def hierarchical_leaves(axis_name: str, intra: int):
    """(fwd, bwd) for the 2-hop a2a: every leaf — scales sidecar included
    — crosses both hops; the transpose is the mirrored 2-hop."""
    def fwd(v):
        return _two_hop(v, axis_name, intra, mirrored=False)

    def bwd(v):
        return _two_hop(v, axis_name, intra, mirrored=True)
    return fwd, bwd


def transfer_fn(codec: WireCodec, axis_name: str):
    """Bound flat coded transfer — the pipelined transport applies it per
    slot chunk, so payload and scales are sliced in lockstep."""
    fwd, bwd = flat_leaves(axis_name)
    return lambda v: coded_transfer(v, codec, fwd, bwd)


def coded_moe_exchange(send, compute_fn, codec: WireCodec, fwd_leaf,
                       bwd_leaf):
    """dispatch a2a -> compute_fn -> combine a2a, both legs coded.
    ``send``: float [R, e_local, c, H]; ``compute_fn`` maps the decoded
    (``compute_dtype``) tensor to the same shape."""
    with phase_scope(obs_tracing.PH_DISPATCH):
        recv = coded_transfer(send, codec, fwd_leaf, bwd_leaf)
    out = compute_fn(recv)
    with phase_scope(obs_tracing.PH_COMBINE):
        return coded_transfer(out, codec, fwd_leaf, bwd_leaf)


# ------------------------------------------------------ fused transfers --
#
# Composite custom_vjp boundaries over the fused codec kernels.  Shared
# structure: forward calls one fused registry op (no f32 wire tensor in
# HBM); backward is built from the UNFUSED registry ops so its program is
# the composed path's backward, op for op — including every dtype cast the
# composed chain performs (grad_dtype on the wire, compute_dtype at the
# decode boundary), so gradients match bit-for-bit per backend.

def _codec_backend(codec: WireCodec):
    return dict(codec.backend) or None


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def precoded_transfer(x, q, scales, codec: WireCodec, fwd_leaf, bwd_leaf):
    """``coded_transfer`` of ``x`` when the caller ALREADY holds x's wire
    encoding (q, scales) — the LSH dispatch leg, where compress() encoded
    the centroids while computing residuals.  Ships the stored payload
    instead of re-quantizing in transit; po2 idempotence makes the decoded
    values bit-identical to re-encoding ``x`` (kernels/wire_quant.py).
    Backward: straight-through transposed transport to ``x``, exactly the
    ``coded_transfer`` backward; q/scales get no gradient."""
    del x
    return codec.decode((fwd_leaf(q), fwd_leaf(scales)))


def _precoded_fwd(x, q, scales, codec, fwd_leaf, bwd_leaf):
    out = precoded_transfer(x, q, scales, codec, fwd_leaf, bwd_leaf)
    return out, (jnp.zeros((), x.dtype), _float0_like(q),
                 jnp.zeros(scales.shape, scales.dtype))


def _precoded_bwd(codec, fwd_leaf, bwd_leaf, res, ct):
    xproto, dq0, ds0 = res
    dx = bwd_leaf(ct.astype(codec.grad_dtype)).astype(xproto.dtype)
    return dx, dq0, ds0


precoded_transfer.defvjp(_precoded_fwd, _precoded_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def fused_dispatch_transfer(flat_ids, pos, src, codec: WireCodec, fwd_leaf,
                            bwd_leaf, model_r: int, num_experts: int,
                            capacity: int):
    """Fused dispatch leg of the coded (non-LSH) baseline: [F] routing
    entries + [F, H] tokens -> decoded [R, e_local, C, H] on the far
    side, via ``dispatch_scatter_quantize`` (the f32 dispatch buffer
    never reaches HBM) + per-leaf transport + decode.  Bit-identical to
    ``coded_transfer(dispatch_scatter(...))``."""
    be = _codec_backend(codec)
    q, scales = dispatch.dispatch_scatter_quantize(
        flat_ids, pos, src, num_experts, capacity, codec.fmt, backend=be)
    e_local = num_experts // model_r
    H = src.shape[-1]
    leaves = (q.reshape(model_r, e_local, capacity, H),
              scales.reshape(model_r, e_local, capacity))
    return codec.decode(tuple(fwd_leaf(leaf) for leaf in leaves))


def _fused_dispatch_fwd(flat_ids, pos, src, codec, fwd_leaf, bwd_leaf,
                        model_r, num_experts, capacity):
    out = fused_dispatch_transfer(flat_ids, pos, src, codec, fwd_leaf,
                                  bwd_leaf, model_r, num_experts, capacity)
    return out, (flat_ids, pos, jnp.zeros((), src.dtype))


def _fused_dispatch_bwd(codec, fwd_leaf, bwd_leaf, model_r, num_experts,
                        capacity, res, ct):
    flat_ids, pos, sproto = res
    be = _codec_backend(codec)
    # Composed backward: transposed transport of the wire cotangent, cast
    # back to the f32 buffer, then the scatter's transpose — the gather
    # with unit weights (kernels/dispatch._routing_vjp_pair).
    dbuf = bwd_leaf(ct.astype(codec.grad_dtype)).astype(jnp.float32)
    dbuf = dbuf.reshape(num_experts, capacity, ct.shape[-1])
    ones = jnp.ones(flat_ids.shape, jnp.float32)
    dsrc = dispatch.combine_gather(flat_ids, pos, dbuf, ones, backend=be)
    return (_float0_like(flat_ids), _float0_like(pos),
            dsrc.astype(sproto.dtype))


fused_dispatch_transfer.defvjp(_fused_dispatch_fwd, _fused_dispatch_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def fused_combine_transfer(expert_out, flat_ids, pos, weights,
                           codec: WireCodec, fwd_leaf, bwd_leaf,
                           model_r: int):
    """Fused combine leg of the coded (non-LSH) baseline: expert outputs
    [R, e_local, C, H] -> encoded in transit -> ``dequantize_combine_
    gather`` straight off the received quantized buffer + scales.
    Returns the [F, H] f32 weighted per-entry combine (callers reshape to
    [T, k, H] and sum over k).  Bit-identical to
    ``combine_gather(ids, pos, decode(transport(encode(eo))), w)``."""
    be = _codec_backend(codec)
    q, scales = tuple(fwd_leaf(leaf) for leaf in codec.encode(expert_out))
    E = q.shape[0] * q.shape[1]
    qb = q.reshape((E,) + q.shape[2:])
    sb = scales.reshape(E, scales.shape[-1])
    return dispatch.dequantize_combine_gather(flat_ids, pos, qb, sb,
                                              weights, backend=be)


def _fused_combine_fwd(expert_out, flat_ids, pos, weights, codec, fwd_leaf,
                       bwd_leaf, model_r):
    be = _codec_backend(codec)
    q, scales = tuple(fwd_leaf(leaf) for leaf in codec.encode(expert_out))
    E = q.shape[0] * q.shape[1]
    qb = q.reshape((E,) + q.shape[2:])
    sb = scales.reshape(E, scales.shape[-1])
    out = dispatch.dequantize_combine_gather(flat_ids, pos, qb, sb,
                                             weights, backend=be)
    return out, (flat_ids, pos, qb, sb, weights,
                 jnp.zeros((), expert_out.dtype))


def _fused_combine_bwd(codec, fwd_leaf, bwd_leaf, model_r, res, ct):
    flat_ids, pos, qb, sb, weights, eproto = res
    be = _codec_backend(codec)
    E, C, H = qb.shape
    # Composed backward (gather custom-VJP + decode/astype transposes +
    # coded_transfer backward): d_w from the unweighted gather of the
    # RECEIVED dequantized buffer; d_buf the scatter of the weighted
    # cotangent, transported back transposed in grad_dtype.
    ones = jnp.ones(flat_ids.shape, jnp.float32)
    gathered = dispatch.dequantize_combine_gather(flat_ids, pos, qb, sb,
                                                  ones, backend=be)
    dw = jnp.sum(ct * gathered, axis=-1).astype(weights.dtype)
    wct = ct * weights.astype(jnp.float32)[:, None]
    dbuf = dispatch.dispatch_scatter(flat_ids, pos, wct, E, C, backend=be)
    dbuf = dbuf.astype(jnp.dtype(codec.compute_dtype)) \
        .reshape(model_r, E // model_r, C, H)
    d_eo = bwd_leaf(dbuf.astype(codec.grad_dtype)).astype(eproto.dtype)
    return d_eo, _float0_like(flat_ids), _float0_like(pos), dw


fused_combine_transfer.defvjp(_fused_combine_fwd, _fused_combine_bwd)


def _decode_seg_transpose(slots, ct, num_slots: int, be):
    """Transpose of the slot gather w.r.t. its [G, S, H] operand, computed
    as THE registry op's own vjp — XLA autodiff of the oracle on the
    reference backend, the segment-centroid custom-VJP on Pallas — so the
    fused decode backward matches whatever the composed path's
    ``residual_apply`` would have produced, per backend."""
    G, C, H = ct.shape
    zeros_eo = jnp.zeros((G, num_slots, H), jnp.float32)
    zeros_r = jnp.zeros((G, C, H), jnp.float32)
    _, vjp = jax.vjp(lambda eo: dispatch.residual_apply(
        slots, eo, zeros_r, backend=be), zeros_eo)
    return vjp(ct)[0]


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _fused_decode_base(expert_out, slots, base, residual, codec: WireCodec,
                       fwd_leaf, bwd_leaf):
    be = _codec_backend(codec)
    q, scales = tuple(fwd_leaf(leaf) for leaf in codec.encode(expert_out))
    G = q.shape[0] * q.shape[1]
    qb = q.reshape((G,) + q.shape[2:])
    sb = scales.reshape(G, scales.shape[-1])
    return dispatch.dequantize_residual_apply(slots, qb, sb, residual,
                                              base, backend=be)


def _fused_decode_base_fwd(expert_out, slots, base, residual, codec,
                           fwd_leaf, bwd_leaf):
    out = _fused_decode_base(expert_out, slots, base, residual, codec,
                             fwd_leaf, bwd_leaf)
    return out, (slots, jnp.zeros(expert_out.shape, expert_out.dtype),
                 jnp.zeros((), base.dtype), jnp.zeros((), residual.dtype))


def _fused_decode_base_bwd(codec, fwd_leaf, bwd_leaf, res, ct):
    slots, eproto, bproto, rproto = res
    be = _codec_backend(codec)
    R, el, S, H = eproto.shape
    # Composed backward of decompress's delta branch + coded_transfer:
    # Y = (eo - base)[slot] + residual, so d_residual = ct, the gather
    # transpose seg flows +seg to eo (back through the transposed
    # transport in grad_dtype) and -seg to base.
    seg = _decode_seg_transpose(slots, ct, S, be)          # [G, S, H] f32
    d_eo = bwd_leaf(seg.reshape(R, el, S, H)
                    .astype(jnp.dtype(codec.compute_dtype))
                    .astype(codec.grad_dtype)).astype(eproto.dtype)
    return (d_eo, _float0_like(slots), (-seg).astype(bproto.dtype),
            ct.astype(rproto.dtype))


_fused_decode_base.defvjp(_fused_decode_base_fwd, _fused_decode_base_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_decode_nobase(expert_out, slots, residual, codec: WireCodec,
                         fwd_leaf, bwd_leaf):
    be = _codec_backend(codec)
    q, scales = tuple(fwd_leaf(leaf) for leaf in codec.encode(expert_out))
    G = q.shape[0] * q.shape[1]
    qb = q.reshape((G,) + q.shape[2:])
    sb = scales.reshape(G, scales.shape[-1])
    return dispatch.dequantize_residual_apply(slots, qb, sb, residual,
                                              None, backend=be)


def _fused_decode_nobase_fwd(expert_out, slots, residual, codec, fwd_leaf,
                             bwd_leaf):
    out = _fused_decode_nobase(expert_out, slots, residual, codec,
                               fwd_leaf, bwd_leaf)
    return out, (slots, jnp.zeros(expert_out.shape, expert_out.dtype),
                 jnp.zeros((), residual.dtype))


def _fused_decode_nobase_bwd(codec, fwd_leaf, bwd_leaf, res, ct):
    slots, eproto, rproto = res
    be = _codec_backend(codec)
    R, el, S, H = eproto.shape
    seg = _decode_seg_transpose(slots, ct, S, be)
    d_eo = bwd_leaf(seg.reshape(R, el, S, H)
                    .astype(jnp.dtype(codec.compute_dtype))
                    .astype(codec.grad_dtype)).astype(eproto.dtype)
    return d_eo, _float0_like(slots), ct.astype(rproto.dtype)


_fused_decode_nobase.defvjp(_fused_decode_nobase_fwd,
                            _fused_decode_nobase_bwd)


def fused_decode_residual_transfer(expert_out, slots, base, residual,
                                   codec: WireCodec, fwd_leaf, bwd_leaf):
    """Fused combine leg of the LSH path: expert outputs [R, e_local, S,
    H] encoded in transit, then ``dequantize_residual_apply`` fuses
    WireCodec.decode with clustering.decompress on the received quantized
    buffer — Y = ((q * scale) - base)[slot] + residual, all in VMEM.
    ``base`` None is the no-error-compensation branch.  Returns
    [G, C, H] f32, bit-identical to decode -> astype(f32) -> decompress;
    gradients match the composed chain per backend (see
    ``_decode_seg_transpose``)."""
    if base is None:
        return _fused_decode_nobase(expert_out, slots, residual, codec,
                                    fwd_leaf, bwd_leaf)
    return _fused_decode_base(expert_out, slots, base, residual, codec,
                              fwd_leaf, bwd_leaf)
