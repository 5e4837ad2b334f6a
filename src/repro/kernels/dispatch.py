"""Kernel backend registry: one uniform contract per hot-path op, three
interchangeable implementations.

  reference        pure-jnp oracles (kernels/ref.py) — XLA fuses them, and
                   they are the only fully-general path (any platform, any
                   shape, spherical hashing, ...).
  pallas_interpret Pallas kernels executed by the interpreter — bit-faithful
                   to the TPU kernels, runs anywhere; used by the parity
                   suite and for debugging Mosaic lowerings on CPU.
  pallas_tpu       compiled Mosaic kernels (TPU only).

Selection: ``resolve_backend(name)`` with name from config
(``MoEConfig.kernel_backend``) or a call-site override.  ``"auto"`` defers
to the ``REPRO_KERNEL_BACKEND`` env var, then platform autodetect
(``pallas_tpu`` on TPU, ``reference`` elsewhere).  Force
``REPRO_KERNEL_BACKEND=reference`` to take every kernel out of the picture
when bisecting a numerics bug (see docs/kernels.md).

The Pallas ops carry custom VJPs whose backwards are themselves kernel
calls (gather ⟂ segment-sum are mutual transposes), so both training and
inference dispatch through this registry — no [G, C, S] one-hot tensor is
ever materialized on a Pallas backend.

The registry covers the full dispatch/combine hot path, not just LSH
compression: ``positions_in_expert`` / ``dispatch_scatter`` /
``combine_gather`` are the routing ops consumed through
``core.routing.DispatchPlan`` by both MoE paths.  Per-op backend overrides
(``MoEConfig.kernel_backend_overrides``) resolve through
``resolve_backends`` into the mapping form every public op accepts.
"""
from __future__ import annotations

import functools
import os
from typing import Callable, Dict, Iterable, Mapping, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.fused_wire import (dequantize_combine_gather_pallas,
                                      dequantize_residual_apply_pallas,
                                      dispatch_scatter_quantize_pallas)
from repro.kernels.lsh_hash import lsh_hash_pallas
from repro.kernels.residual_apply import residual_apply_pallas
from repro.kernels.scatter_gather import (combine_gather_pallas,
                                          dispatch_scatter_pallas)
from repro.kernels.segment_centroid import segment_centroid_pallas
from repro.kernels.token_position import positions_in_expert_pallas
from repro.kernels.wire_quant import (wire_dequantize_pallas,
                                      wire_quantize_pallas)

REFERENCE = "reference"
PALLAS_INTERPRET = "pallas_interpret"
PALLAS_TPU = "pallas_tpu"
AUTO = "auto"
ENV_VAR = "REPRO_KERNEL_BACKEND"

OPS = ("lsh_hash", "segment_centroid", "residual_apply",
       "positions_in_expert", "dispatch_scatter", "combine_gather",
       "wire_quantize", "wire_dequantize",
       # Fused codec ops (kernels/fused_wire.py): bit-identical to the
       # composition of the routing op and the wire_quantize/dequantize
       # halves, without the f32 wire tensor's HBM round-trip.
       "dispatch_scatter_quantize", "dequantize_combine_gather",
       "dequantize_residual_apply")

# A backend selector: a single name, or a per-op mapping op -> name with a
# "*" default (see resolve_backends / MoEConfig.kernel_backend_overrides).
BackendSpec = Union[str, Mapping[str, str], None]


# ----------------------------------------------------------- tile sizes --
#
# Every Pallas wrapper takes its grid tile sizes (tile_t for the token /
# capacity axis, tile_s for the quantize slot axis) as static kwargs; the
# registry resolves them per call so the fused and unfused ops can be
# tile-tuned without code changes.  Resolution order: config
# (MoEConfig.kernel_tiles, installed via ``set_tiles``) >
# $REPRO_KERNEL_TILE > defaults.  Tile sizes are a PERFORMANCE knob only:
# results are bit-identical across tile choices (accumulation order along
# the grid is fixed by the revisit pattern, not the tile width).

TILE_ENV = "REPRO_KERNEL_TILE"
DEFAULT_TILES = {"tile_t": 128, "tile_s": 8}

_ACTIVE_TILES: Dict[str, int] = {}


def resolve_tiles(overrides: Iterable[Tuple[str, int]] = ()) -> Dict[str, int]:
    """(explicit overrides > $REPRO_KERNEL_TILE > defaults) -> concrete
    tile mapping.  Env format: ``tile_t=256,tile_s=16`` (a bare integer
    means tile_t).  Tiles must be positive multiples of 8 (the f32
    sublane quantum); unknown keys raise."""
    out = dict(DEFAULT_TILES)
    env = os.environ.get(TILE_ENV, "")
    entries = []
    for part in env.split(","):
        part = part.strip()
        if part:
            k, _, v = part.partition("=")
            entries.append(("tile_t", k) if not v else (k.strip(), v))
    entries += list(dict(overrides).items())
    for k, v in entries:
        if k not in DEFAULT_TILES:
            raise ValueError(f"unknown kernel tile {k!r}; "
                             f"known: {sorted(DEFAULT_TILES)}")
        out[k] = int(v)
    for k, v in out.items():
        if v <= 0 or v % 8:
            raise ValueError(f"kernel tile {k}={v} must be a positive "
                             "multiple of 8")
    return out


def set_tiles(overrides: Iterable[Tuple[str, int]] = ()) -> None:
    """Install config-level tile overrides (MoEConfig.kernel_tiles) for
    subsequent registry calls — trace-time state, like the backend env
    var.  An empty ``overrides`` resets to env/default resolution."""
    global _ACTIVE_TILES
    _ACTIVE_TILES = resolve_tiles(overrides) if dict(overrides) else {}


def current_tiles() -> Dict[str, int]:
    """The tile mapping registry lambdas resolve at call (trace) time."""
    return dict(_ACTIVE_TILES) if _ACTIVE_TILES else resolve_tiles()


def _float0_like(x):
    """Zero cotangent for integer primals (slot ids)."""
    return np.zeros(x.shape, jax.dtypes.float0)


# --------------------------------------------------------------------------
# Differentiable Pallas ops.  slots is an integer primal (float0 cotangent);
# num_slots / interpret are static.
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _segment_centroid_pl(slots, x, num_slots, interpret):
    return segment_centroid_pallas(slots, x, num_slots=num_slots,
                                   tile_t=current_tiles()["tile_t"],
                                   interpret=interpret)


def _segment_centroid_fwd(slots, x, num_slots, interpret):
    cent, counts = _segment_centroid_pl(slots, x, num_slots, interpret)
    return (cent, counts), (slots, counts, jnp.zeros((), x.dtype))


def _segment_centroid_bwd(num_slots, interpret, res, cts):
    slots, counts, xproto = res
    d_cent, _ = cts                       # counts do not depend on x
    # centroid_s = Σ_c x_c / count_s  =>  dx_c = d_cent[slot_c] / count
    scaled = d_cent / jnp.maximum(counts, 1.0)[..., None]
    G, C = slots.shape
    H = d_cent.shape[-1]
    zeros = jnp.zeros((G, C, H), jnp.float32)
    dx = residual_apply_pallas(slots, scaled, zeros,
                               tile_t=current_tiles()["tile_t"],
                               interpret=interpret)
    return _float0_like(slots), dx.astype(xproto.dtype)


_segment_centroid_pl.defvjp(_segment_centroid_fwd, _segment_centroid_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _residual_apply_pl(slots, expert_out, residual, num_slots, interpret):
    return residual_apply_pallas(slots, expert_out, residual,
                                 tile_t=current_tiles()["tile_t"],
                                 interpret=interpret)


def _residual_apply_fwd(slots, expert_out, residual, num_slots, interpret):
    out = _residual_apply_pl(slots, expert_out, residual, num_slots,
                             interpret)
    return out, (slots, jnp.zeros((), expert_out.dtype),
                 jnp.zeros((), residual.dtype))


def _residual_apply_bwd(num_slots, interpret, res, ct):
    slots, eproto, rproto = res
    # out = gather(expert_out, slots) + residual: the gather's transpose is
    # a segment-sum over slots — the centroid kernel run on the cotangent.
    cent, counts = segment_centroid_pallas(slots, ct, num_slots=num_slots,
                                           tile_t=current_tiles()["tile_t"],
                                           interpret=interpret)
    d_eout = cent * counts[..., None]     # undo the kernel's mean
    return (_float0_like(slots), d_eout.astype(eproto.dtype),
            ct.astype(rproto.dtype))


_residual_apply_pl.defvjp(_residual_apply_fwd, _residual_apply_bwd)


def _routing_vjp_pair(scatter_impl: Callable, gather_impl: Callable):
    """Build the (dispatch_scatter, combine_gather) custom-VJP pair from a
    backend's raw impls.  The mutual-transpose backward structure is
    defined ONCE here and instantiated for every backend — including
    ``reference``, which deliberately does NOT use XLA autodiff through
    its one-hot einsum: identical backward programs are what make the
    parity suite's bit-for-bit gradient check hold.

    scatter_impl(ids, pos, src, num_experts, capacity) -> [E, C, H];
    gather_impl(ids, pos, buf, weights) -> [F, H]."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
    def scatter(ids, pos, src, num_experts, capacity):
        return scatter_impl(ids, pos, src, num_experts, capacity)

    def scatter_fwd(ids, pos, src, num_experts, capacity):
        buf = scatter(ids, pos, src, num_experts, capacity)
        return buf, (ids, pos, jnp.zeros((), src.dtype))

    def scatter_bwd(num_experts, capacity, res, ct):
        ids, pos, sproto = res
        # buf = scatter(src): the transpose is the gather of the cotangent
        # at each entry's (expert, position) — the combine direction with
        # unit weights
        ones = jnp.ones(ids.shape, jnp.float32)
        dsrc = gather_impl(ids, pos, ct, ones)
        return (_float0_like(ids), _float0_like(pos),
                dsrc.astype(sproto.dtype))

    scatter.defvjp(scatter_fwd, scatter_bwd)

    @jax.custom_vjp
    def gather(ids, pos, buf, weights):
        return gather_impl(ids, pos, buf, weights)

    def gather_fwd(ids, pos, buf, weights):
        return gather(ids, pos, buf, weights), (ids, pos, buf, weights)

    def gather_bwd(res, ct):
        ids, pos, buf, weights = res
        E, C, _ = buf.shape
        # out = w * gather(buf): d_buf is the scatter of the weighted
        # cotangent (mutual transposes), d_w the per-entry inner product
        # with the unweighted gather.
        wct = ct * weights.astype(jnp.float32)[:, None]
        dbuf = scatter_impl(ids, pos, wct, E, C)
        ones = jnp.ones(ids.shape, jnp.float32)
        gathered = gather_impl(ids, pos, buf, ones)
        dw = jnp.sum(ct * gathered, axis=-1)
        return (_float0_like(ids), _float0_like(pos), dbuf.astype(buf.dtype),
                dw.astype(weights.dtype))

    gather.defvjp(gather_fwd, gather_bwd)
    return scatter, gather


def _pallas_routing_impls(interpret: bool):
    return (lambda ids, pos, src, num_experts, capacity:
                dispatch_scatter_pallas(ids, pos, src,
                                        num_experts=num_experts,
                                        capacity=capacity,
                                        tile_t=current_tiles()["tile_t"],
                                        interpret=interpret),
            lambda ids, pos, buf, weights:
                combine_gather_pallas(ids, pos, buf, weights,
                                      tile_t=current_tiles()["tile_t"],
                                      interpret=interpret))


_ROUTING_VJP = {
    REFERENCE: _routing_vjp_pair(ref.dispatch_scatter_ref,
                                 ref.combine_gather_ref),
    PALLAS_INTERPRET: _routing_vjp_pair(*_pallas_routing_impls(True)),
    PALLAS_TPU: _routing_vjp_pair(*_pallas_routing_impls(False)),
}


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

def _pallas_ops(interpret: bool) -> Dict[str, Callable]:
    return {
        "lsh_hash": lambda x, rot: lsh_hash_pallas(
            x, rot, interpret=interpret),
        "segment_centroid": lambda slots, x, num_slots: _segment_centroid_pl(
            slots, x, num_slots, interpret),
        "residual_apply": lambda slots, eout, resid: _residual_apply_pl(
            slots, eout, resid, eout.shape[1], interpret),
        "positions_in_expert": lambda ids, num_experts:
            positions_in_expert_pallas(ids, num_experts=num_experts,
                                       tile_t=current_tiles()["tile_t"],
                                       interpret=interpret),
        "dispatch_scatter": _ROUTING_VJP[
            PALLAS_INTERPRET if interpret else PALLAS_TPU][0],
        "combine_gather": _ROUTING_VJP[
            PALLAS_INTERPRET if interpret else PALLAS_TPU][1],
        "wire_quantize": lambda x, fmt: wire_quantize_pallas(
            x, fmt=fmt, tile_s=current_tiles()["tile_s"],
            interpret=interpret),
        "wire_dequantize": lambda q, scales: wire_dequantize_pallas(
            q, scales, tile_s=current_tiles()["tile_s"],
            interpret=interpret),
        "dispatch_scatter_quantize":
            lambda ids, pos, src, num_experts, capacity, fmt:
                dispatch_scatter_quantize_pallas(
                    ids, pos, src, num_experts=num_experts,
                    capacity=capacity, fmt=fmt,
                    tile_t=current_tiles()["tile_t"], interpret=interpret),
        "dequantize_combine_gather":
            lambda ids, pos, q, scales, weights:
                dequantize_combine_gather_pallas(
                    ids, pos, q, scales, weights,
                    tile_t=current_tiles()["tile_t"], interpret=interpret),
        "dequantize_residual_apply":
            lambda slots, q, scales, residual, base:
                dequantize_residual_apply_pallas(
                    slots, q, scales, residual, base,
                    tile_t=current_tiles()["tile_t"], interpret=interpret),
    }


_REFERENCE_OPS: Dict[str, Callable] = {
    "lsh_hash": ref.lsh_hash_ref,
    "segment_centroid": ref.segment_centroid_ref,
    "residual_apply": ref.residual_apply_ref,
    "positions_in_expert": ref.positions_in_expert_ref,
    "dispatch_scatter": _ROUTING_VJP[REFERENCE][0],
    "combine_gather": _ROUTING_VJP[REFERENCE][1],
    "wire_quantize": ref.wire_quantize_ref,
    "wire_dequantize": ref.wire_dequantize_ref,
    "dispatch_scatter_quantize": ref.dispatch_scatter_quantize_ref,
    "dequantize_combine_gather": ref.dequantize_combine_gather_ref,
    "dequantize_residual_apply": ref.dequantize_residual_apply_ref,
}


_REGISTRY: Dict[str, Dict[str, Callable]] = {
    REFERENCE: _REFERENCE_OPS,
    PALLAS_INTERPRET: _pallas_ops(interpret=True),
    PALLAS_TPU: _pallas_ops(interpret=False),
}


def register_backend(name: str, ops: Dict[str, Callable]) -> None:
    """Extension point (e.g. a future pallas_gpu / triton backend)."""
    missing = set(OPS) - set(ops)
    if missing:
        raise ValueError(f"backend {name!r} missing ops {sorted(missing)}")
    _REGISTRY[name] = dict(ops)


def available_backends():
    return tuple(_REGISTRY)


def resolve_backend(name: str | None = AUTO, *,
                    off_tpu_fallback: str | None = None) -> str:
    """Config/override name -> concrete backend (trace-time resolution).

    Order: explicit name > $REPRO_KERNEL_BACKEND > platform autodetect
    (pallas_tpu on TPU, reference elsewhere).  ``off_tpu_fallback`` names
    a backend to degrade to when the resolution lands on ``pallas_tpu``
    off-TPU, instead of raising — for paths that must still trace a
    TPU-targeted config on CPU hosts (the use_lsh=False baseline, decode).
    Unknown names always raise."""
    name = name or AUTO
    if name == AUTO:
        name = os.environ.get(ENV_VAR, AUTO) or AUTO
    if name == AUTO:
        name = PALLAS_TPU if jax.default_backend() == "tpu" else REFERENCE
    if name not in _REGISTRY:
        raise ValueError(f"unknown kernel backend {name!r}; "
                         f"available: {sorted(_REGISTRY)}")
    if name == PALLAS_TPU and jax.default_backend() != "tpu":
        if off_tpu_fallback is not None:
            return resolve_backend(off_tpu_fallback)
        raise ValueError(
            "kernel backend 'pallas_tpu' requires a TPU (platform is "
            f"{jax.default_backend()!r}); use 'pallas_interpret' to run "
            "the kernel logic off-TPU")
    return name


def resolve_backends(name: BackendSpec = AUTO,
                     overrides: Iterable[Tuple[str, str]] = (), *,
                     off_tpu_fallback: str | None = None) -> Dict[str, str]:
    """Resolve a (default, per-op overrides) config into a concrete per-op
    mapping, at trace time.  ``overrides`` pairs op name -> backend name
    (MoEConfig.kernel_backend_overrides); the "*" key holds the resolved
    default for every op not overridden.  ``off_tpu_fallback`` as in
    ``resolve_backend``; unknown op / backend names always raise."""
    rb = functools.partial(resolve_backend,
                           off_tpu_fallback=off_tpu_fallback)
    if isinstance(name, Mapping):                # already a per-op mapping
        out = {op: rb(b) for op, b in name.items()}
        out.setdefault("*", rb(AUTO))
    else:
        out = {"*": rb(name)}
    for op, b in dict(overrides).items():
        if op not in OPS:
            raise ValueError(f"kernel_backend_overrides names unknown op "
                             f"{op!r}; known ops: {sorted(OPS)}")
        out[op] = rb(b)
    return out


def op_backend(backend: BackendSpec, op: str) -> str:
    """Concrete backend for one op: ``backend`` is a name or a per-op
    mapping from ``resolve_backends`` ("*" = default)."""
    if isinstance(backend, Mapping):
        return resolve_backend(backend.get(op, backend.get("*", AUTO)))
    return resolve_backend(backend)


# ------------------------------------------------------------ public ops --
#
# Shared overflow-bin contract: every integer id argument tolerates values
# outside its valid range.  An out-of-range id CONTRIBUTES NOTHING on the
# scatter direction (segment_centroid, dispatch_scatter) and GATHERS ZERO
# on the gather direction (residual_apply, combine_gather), on every
# backend.  Callers encode "dropped" (invalid token / over-capacity) by
# pointing the id at the overflow bin instead of carrying a separate mask
# through the hot path.

def lsh_hash(x, rotations, *, backend: BackendSpec = AUTO):
    """x: [T, H]; rotations: [L, H, Dr] -> [T, L] int32 vertex ids."""
    return _REGISTRY[op_backend(backend, "lsh_hash")]["lsh_hash"](
        x, rotations)


def segment_centroid(slots, x, num_slots: int, *, backend: BackendSpec = AUTO):
    """slots: [G, C] int32; x: [G, C, H] ->
    (centroids [G, S, H] f32, counts [G, S] f32).  Out-of-range slot ids
    (>= num_slots) contribute to nothing — the overflow bin."""
    return _REGISTRY[op_backend(backend, "segment_centroid")][
        "segment_centroid"](slots, x, num_slots)


def residual_apply(slots, expert_out, residual, *, backend: BackendSpec = AUTO):
    """[G, C] ids, [G, S, H] outputs, [G, C, H] residuals -> [G, C, H] f32
    = expert_out[g, slots] + residual.  Out-of-range slot ids gather zero
    on every backend (the overflow bin)."""
    return _REGISTRY[op_backend(backend, "residual_apply")][
        "residual_apply"](slots, expert_out, residual)


def positions_in_expert(expert_ids, num_experts: int, capacity: int, *,
                        backend: BackendSpec = AUTO):
    """Stable dispatch-buffer row of each flattened (token, choice).

    expert_ids: [F] int32 (token-major => earlier tokens win capacity).
    Returns (pos [F] int32, keep [F] bool, counts [E] int32): pos is the
    entry's row within its expert's buffer; dropped entries land OUTSIDE
    [0, capacity) — over-capacity entries keep their raw rank (>= capacity,
    a useful overflow diagnostic), out-of-range ids get exactly capacity —
    so downstream scatter/gather ignore them without a mask (the overflow
    bin).  keep = landed within capacity; counts = uncapped per-expert
    demand (physical order — the routing load diagnostic)."""
    impl = _REGISTRY[op_backend(backend, "positions_in_expert")][
        "positions_in_expert"]
    pos, counts = impl(expert_ids, num_experts)
    in_range = (expert_ids >= 0) & (expert_ids < num_experts)
    pos = jnp.where(in_range, pos, capacity)
    keep = pos < capacity
    return pos.astype(jnp.int32), keep, counts.astype(jnp.int32)


def dispatch_scatter(expert_ids, pos, src, num_experts: int, capacity: int,
                     *, backend: BackendSpec = AUTO):
    """[F] ids, [F] positions, [F, H] tokens -> [E, C, H] f32 dispatch
    buffer: buf[e, c] = Σ src[f] over entries with (id, pos) == (e, c).
    Entries with id outside [0, E) or position outside [0, C) contribute
    nothing (overflow bin).  Differentiable in ``src`` (the backward pass
    is ``combine_gather`` — mutual transposes)."""
    return _REGISTRY[op_backend(backend, "dispatch_scatter")][
        "dispatch_scatter"](expert_ids, pos, src, num_experts, capacity)


def combine_gather(expert_ids, pos, buf, weights, *,
                   backend: BackendSpec = AUTO):
    """[F] ids, [F] positions, [E, C, H] buffer, [F] weights -> [F, H] f32
    = weights[f] * buf[id_f, pos_f].  Out-of-range entries gather zero
    (overflow bin).  Differentiable in ``buf`` and ``weights`` (the buffer
    backward pass is ``dispatch_scatter`` — mutual transposes)."""
    return _REGISTRY[op_backend(backend, "combine_gather")][
        "combine_gather"](expert_ids, pos, buf, weights)


def wire_quantize(x, fmt: str, *, backend: BackendSpec = AUTO):
    """x: [G, S, H] -> (q [G, S, H] int8|fp8-e4m3, scales [G, S] f32).

    One power-of-two absmax scale per (group, slot) row; all-zero rows
    quantize to zero payload with scale 1 (kernels/wire_quant.py).
    Forward-only: gradients flow through ``wire_roundtrip`` (the
    straight-through quant pair) or comm/wire.py's coded transfer, never
    through the int8 payload itself."""
    return _REGISTRY[op_backend(backend, "wire_quantize")][
        "wire_quantize"](x, fmt)


def wire_dequantize(q, scales, *, backend: BackendSpec = AUTO):
    """(q [G, S, H], scales [G, S]) -> [G, S, H] f32 = q * scale.
    Forward-only, like ``wire_quantize``."""
    return _REGISTRY[op_backend(backend, "wire_dequantize")][
        "wire_dequantize"](q, scales)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _wire_roundtrip(x, fmt, backend_name):
    q, scales = _REGISTRY[backend_name]["wire_quantize"](x, fmt)
    return _REGISTRY[backend_name]["wire_dequantize"](q, scales), q, scales


def _wire_roundtrip_fwd(x, fmt, backend_name):
    return _wire_roundtrip(x, fmt, backend_name), None


def _wire_roundtrip_bwd(fmt, backend_name, _, cts):
    ct_x = cts[0]                         # q / scales carry no gradient
    return (ct_x,)                        # straight-through: d/dx [dq∘q] := I


_wire_roundtrip.defvjp(_wire_roundtrip_fwd, _wire_roundtrip_bwd)


def wire_roundtrip(x, fmt: str, *, backend: BackendSpec = AUTO):
    """The quantize→dequantize pair as one differentiable unit:
    returns (dequantize(quantize(x)) [G, S, H] f32, scales [G, S] f32)
    with a straight-through VJP (d/dx := identity — the pair is a
    rounding, not a transformation).  This is how ``clustering.compress``
    obtains the exact values the expert will see on the far side of the
    wire while keeping centroids on the gradient path.

    Power-of-two scales make the pair idempotent on its own output:
    re-quantizing the returned values (as comm/wire.py's transport encode
    does) dequantizes to bit-identical values again — for int8 the (q,
    scales) representation itself is reproduced; fp8 may re-derive
    (2q, scales/2) when the row max rounded down to exactly qmax/2, an
    equivalent encoding of the same values."""
    dq, _q, scales = _wire_roundtrip(x, fmt,
                                     op_backend(backend, "wire_quantize"))
    return dq, scales


def wire_encode_roundtrip(x, fmt: str, *, backend: BackendSpec = AUTO):
    """``wire_roundtrip`` that also returns the encoded payload:
    (dq [G, S, H] f32, q [G, S, H] int8|fp8, scales [G, S] f32) under the
    same straight-through VJP (gradients flow to ``x`` through ``dq``
    only; ``q``/``scales`` are non-differentiable outputs).  The payload
    is what lets ``clustering.compress`` hand the already-encoded
    centroids to comm/wire.py's precoded transfer, skipping the in-transit
    re-quantize that po2 idempotence makes redundant."""
    return _wire_roundtrip(x, fmt, op_backend(backend, "wire_quantize"))


# ------------------------------------------------------------ fused ops --
#
# Forward-only registry entry points for the fused codec kernels
# (kernels/fused_wire.py).  The int8/fp8 payload output means these cannot
# carry a float cotangent themselves; DIFFERENTIATION lives one level up,
# in comm/wire.py's composite transfers, whose custom VJPs call the
# UNFUSED registry ops (dispatch_scatter / combine_gather /
# residual_apply) so fused-path gradients are bit-identical to the
# composed path's on every backend.

def dispatch_scatter_quantize(expert_ids, pos, src, num_experts: int,
                              capacity: int, fmt: str, *,
                              backend: BackendSpec = AUTO):
    """Fused ``wire_quantize(dispatch_scatter(...))``: [F] ids, [F]
    positions, [F, H] tokens -> (q [E, C, H] int8|fp8-e4m3,
    scales [E, C] f32), bit-identical to the composition but without the
    f32 dispatch buffer's HBM round-trip (the Pallas kernel keeps it in a
    VMEM scratch accumulator).  Out-of-range entries contribute nothing
    (overflow bin); empty rows encode as zero payload with scale 1.
    Forward-only — see the section comment."""
    return _REGISTRY[op_backend(backend, "dispatch_scatter_quantize")][
        "dispatch_scatter_quantize"](expert_ids, pos, src, num_experts,
                                     capacity, fmt)


def dequantize_combine_gather(expert_ids, pos, q, scales, weights, *,
                              backend: BackendSpec = AUTO):
    """Fused ``combine_gather(ids, pos, wire_dequantize(q, scales), w)``:
    [F] ids, [F] positions, (q [E, C, H], scales [E, C]), [F] weights ->
    [F, H] f32 = weights[f] * (q * scale)[id_f, pos_f], dequantized in
    VREGs right before the weighted reduce.  Out-of-range entries gather
    zero (overflow bin).  Forward-only — see the section comment."""
    return _REGISTRY[op_backend(backend, "dequantize_combine_gather")][
        "dequantize_combine_gather"](expert_ids, pos, q, scales, weights)


def dequantize_residual_apply(slots, q, scales, residual, base=None, *,
                              backend: BackendSpec = AUTO):
    """Fused ``residual_apply(slots, wire_dequantize(q, scales) - base,
    residual)`` (base omitted when None): [G, C] slot ids,
    (q [G, S, H], scales [G, S]), [G, C, H] residuals, optional
    [G, S, H] base -> [G, C, H] f32.  This is WireCodec.decode fused with
    the LSH decompress leg — the received expert outputs never exist as an
    f32 tensor in HBM.  Out-of-range slot ids gather zero (overflow bin).
    Forward-only — see the section comment."""
    return _REGISTRY[op_backend(backend, "dequantize_residual_apply")][
        "dequantize_residual_apply"](slots, q, scales, residual, base)
