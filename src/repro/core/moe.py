"""Mixture-of-Experts layers.

Both dispatch paths are thin consumers of the same pipeline —

    top_k_gating -> routing.build_dispatch_plan -> routing.dispatch_tokens
    -> expert MLP -> routing.combine_tokens

with every routing op (position assignment, buffer scatter, weighted
combine) dispatched through the kernel backend registry
(kernels/dispatch.py) via core.routing.DispatchPlan:

1. ``moe_expert_parallel`` — the paper's setting (train / prefill): a
   ``shard_map`` region over the mesh in which the plan's dispatch buffer
   is optionally LSH-compressed (core/clustering), exchanged over the
   `model` axis (= expert parallelism), processed by the local experts,
   exchanged back, and error-compensated.  The *compressed* tensor is the
   only thing crossing the wire — the collective operand shrinks by the
   configured rate.  The transport itself (flat | hierarchical 2-hop |
   chunk-pipelined a2a, plus the FSDP weight gathers) is selected once
   per step by ``comm.planner.plan_collectives`` from mesh topology +
   message size + ``cfg.comm`` — this module never calls a raw collective.

2. ``moe_dense_dispatch`` — decode path: token counts are tiny.  On a
   multi-device mesh with a model axis the exchange now goes through the
   SAME per-step ``CommPlan`` as the training path (tokens replicated
   along `model`, batch sharded over the dp axes), so serving meshes get
   the planner's transport control and the tuner's tiny-message regime
   coverage; on a 1-device model axis the plan is consumed without
   shard_map or collectives (GSPMD partitions the einsums) exactly as
   before.

Expert weights are stored [E, H, F] sharded P(model, data, -): expert dim
over `model` (EP), H over `data` (FSDP); the region all-gathers over `data`
(transpose: psum_scatter of grads => ZeRO-2 gradient sharding for free).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.comm import planner as comm_planner
from repro.comm import wire as wire_lib
from repro.compat import shard_map
from repro.configs.base import MoEConfig
from repro.core import clustering, routing
from repro.core.gating import top_k_gating
from repro.kernels import dispatch
from repro.obs import metrics as obs_metrics
from repro.obs import tracing as obs_tracing
from repro.obs.tracing import phase_scope
from repro.runtime.sharding import axis_size, dp_axes


def padded_num_experts(num_experts: int, mesh: Mesh) -> int:
    r = axis_size(mesh, "model")
    return int(math.ceil(num_experts / r) * r)


def expert_capacity(tokens_per_device: int, num_experts_padded: int,
                    top_k: int, capacity_factor: float) -> int:
    cap = int(math.ceil(tokens_per_device * top_k / num_experts_padded
                        * capacity_factor))
    return max(8, int(math.ceil(cap / 8) * 8))


def num_lsh_slots(capacity: int, rate: float, multiple: int = 1) -> int:
    """Slot count: ceil(rate * capacity) rounded up to lcm(8, multiple).
    ``multiple`` is the configured overlap-chunk count, so a pipelined
    transport always finds a slot axis it can chunk evenly (the planner
    degrades to flat — with a logged reason — only when padding is
    impossible, e.g. the uncompressed capacity axis)."""
    unit = math.lcm(8, max(1, multiple))
    return max(unit, int(math.ceil(capacity * rate / unit) * unit))


def _resolve_moe_backend(cfg: MoEConfig, kernel_backend, *,
                         lsh_active: bool) -> Dict[str, str]:
    """Trace-time resolution of the per-op backend mapping: call-site
    override > cfg.kernel_backend, then cfg.kernel_backend_overrides on
    top (kernels/dispatch.py resolution order).  When LSH is off, a
    TPU-targeted config degrades ``pallas_tpu`` to ``reference`` instead
    of raising, so the use_lsh=False baseline (and decode) still traces
    on CPU hosts; name/op validation applies either way.  Also installs
    the config's Pallas tile overrides (cfg.kernel_tiles) for every
    registry call this trace makes."""
    dispatch.set_tiles(cfg.kernel_tiles)
    return dispatch.resolve_backends(
        kernel_backend or cfg.kernel_backend, cfg.kernel_backend_overrides,
        off_tpu_fallback=None if lsh_active else dispatch.REFERENCE)


def _comm_stats_vector(cplan: Optional[comm_planner.CommPlan],
                       wire_format: Optional[str]):
    """[algorithm_id, degraded, calibrated, wire_format_id] int32 — the
    per-step comm observability record (models/model.py threads it into
    the train metrics; decode with no plan reports UNPLANNED).  Decode
    with ``comm_planner.describe_comm_metrics``."""
    if cplan is None:
        return jnp.array([comm_planner.UNPLANNED, 0, 0,
                          comm_planner.WIRE_FORMAT_IDS[None]], jnp.int32)
    return jnp.array([cplan.algorithm_id, int(cplan.degraded),
                      int(cplan.calibrated),
                      comm_planner.WIRE_FORMAT_IDS.get(wire_format, -1)],
                     jnp.int32)


def _expert_mlp(tok, w_gate, w_up, w_down, mlp_act: str):
    """[E, t, H] tokens through the per-expert MLP stack -> [E, t, H]."""
    h = jnp.einsum("eth,ehf->etf", tok, w_up)
    if mlp_act == "swiglu":
        g = jnp.einsum("eth,ehf->etf", tok, w_gate)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
    elif mlp_act == "relu2":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("etf,efh->eth", h, w_down)


# --------------------------------------------------------------------------
# Path 1: expert-parallel shard_map (train / prefill) — the paper's setting.
# --------------------------------------------------------------------------

def _local_moe(x, router_w, w_gate, w_up, w_down, rot, placement, *,
               cfg: MoEConfig, mesh: Mesh, mlp_act: str, e_pad: int,
               capacity: int, use_lsh: bool, lsh_slots: int, wire_dtype,
               codec, kernel_backend, cplan: comm_planner.CommPlan,
               with_obs: bool = False):
    """Per-device body. x: [B_loc, S_loc, H].  ``with_obs`` additionally
    returns pmean'd slot-occupancy and drop-fraction scalars (the
    in-graph MetricBag inputs — obs/metrics.py); off by default so the
    disabled path keeps today's outputs and HLO byte-identical."""
    model_r = axis_size(mesh, "model")
    e_local = e_pad // model_r
    B_loc, S_loc, H = x.shape
    T = B_loc * S_loc
    xf = x.reshape(T, H)

    # Fused codec path (comm/wire.py, kernels/fused_wire.py): quantized
    # wire + a transport whose leaves move whole — the codec runs INSIDE
    # the scatter/gather kernels and the f32 wire tensor never reaches
    # HBM.  The pipelined transport keeps the per-chunk coded path (its
    # overlap slices the float tensor before encode); $REPRO_FUSED_WIRE=0
    # forces the composed path (bit-identical by contract — the parity
    # suite flips it).
    fused = (codec is not None and codec.quantized
             and cplan.transport != comm_planner.PIPELINED
             and wire_lib.fused_wire_enabled())

    with phase_scope(obs_tracing.PH_GATE):
        gate = top_k_gating(xf, router_w, cfg.top_k, placement)
        plan = routing.build_dispatch_plan(gate.expert_ids, gate.weights,
                                           e_pad, capacity,
                                           backend=kernel_backend)
        # The dispatch scatter executes the plan, with LSH on or off.  The
        # quantized non-LSH baseline (wire_format int8/fp8, LSH off) keeps
        # the f32 buffer — the unfused leg encodes the same buffer the
        # fused kernel quantizes, keeping the two paths bit-identical —
        # and the fused one skips it (the scatter runs inside the
        # transfer).
        disp = None
        if use_lsh or not fused:
            disp = routing.dispatch_tokens(plan, xf, backend=kernel_backend)
            if use_lsh or codec is None:
                disp = disp.astype(xf.dtype)

    if use_lsh:
        with phase_scope(obs_tracing.PH_COMPRESS):
            # Residuals are computed against the DEQUANTIZED wire
            # centroids, so the codec's in-transit encode (comm/wire.py)
            # is exactly loss-transparent at the combine step.
            comp = clustering.compress(disp, plan.occupancy, rot, lsh_slots,
                                       cfg.lsh.hash_type,
                                       cfg.lsh.error_compensation,
                                       backend=kernel_backend,
                                       wire_format=cfg.lsh.wire_format,
                                       wire_dtype=wire_dtype)
        wire, c_wire = comp.centroids, lsh_slots
    else:
        # Without LSH the raw dispatch buffer crosses the wire (coded in
        # transit where a codec is set).
        comp, wire, c_wire = None, disp, capacity

    # ---- wire exchange: dispatch a2a -> expert MLP -> combine a2a, with
    # the transport (flat | hierarchical | pipelined) picked by the plan
    # and the on-wire representation (bf16 | int8+scales | fp8+scales) by
    # the codec.  The compressed tensor is the only thing that crosses
    # the wire; with a codec the cast/quantize happens in transit (or
    # inside the fused kernels).
    data_r = axis_size(mesh, "data")
    # expert weights: FSDP all-gather over `data` (H axis) — hoisted out of
    # the (possibly chunked) exchange so they are gathered exactly once
    wg = None if w_gate is None else cplan.all_gather(w_gate, "data", 1,
                                                      data_r)
    wu = cplan.all_gather(w_up, "data", 1, data_r)
    wd = cplan.all_gather(w_down, "data", 1, data_r)

    def expert_chunk(recv):
        """[R, e_local, ck, H] wire chunk -> same shape, through the local
        experts (per-token MLP — any slot sub-range is valid)."""
        with phase_scope(obs_tracing.PH_EXPERT):
            r_, el, ck, h_ = recv.shape
            tok = recv.transpose(1, 0, 2, 3).reshape(el, r_ * ck, h_)
            out = _expert_mlp(tok.astype(x.dtype), wg, wu, wd, mlp_act)
            out = out.reshape(el, r_, ck, h_).transpose(1, 0, 2, 3)
            return out if codec is not None else out.astype(wire_dtype)

    if fused:
        fwd_leaf, bwd_leaf = cplan.leaf_transports()
        if use_lsh:
            # Dispatch leg: ship the payload compress() already encoded
            # (po2 idempotence == re-encoding the dequantized centroids);
            # combine leg: decode fuses with decompress on the received
            # quantized buffer.
            send = wire.reshape(model_r, e_local, c_wire, H)
            q_send = comp.payload.reshape(model_r, e_local, c_wire, H)
            s_send = comp.scales.reshape(model_r, e_local, c_wire)
            with phase_scope(obs_tracing.PH_DISPATCH):
                recv = wire_lib.precoded_transfer(send, q_send, s_send,
                                                  codec, fwd_leaf, bwd_leaf)
            eo_wire = expert_chunk(recv)
            slots, base, residual = clustering.fused_decompress_operands(
                comp)
            with phase_scope(obs_tracing.PH_COMBINE):
                out_tok = wire_lib.fused_decode_residual_transfer(
                    eo_wire, slots, base, residual, codec, fwd_leaf,
                    bwd_leaf)
            with phase_scope(obs_tracing.PH_DECOMPRESS):
                y = routing.combine_tokens(plan, out_tok,
                                           backend=kernel_backend)
        else:
            # Both legs fused into the routing kernels: scatter+quantize
            # out, dequantize+gather back.
            src = jnp.repeat(xf, cfg.top_k, axis=0)
            with phase_scope(obs_tracing.PH_DISPATCH):
                recv = wire_lib.fused_dispatch_transfer(
                    plan.flat_ids, plan.positions, src, codec, fwd_leaf,
                    bwd_leaf, model_r, e_pad, capacity)
            eo_wire = expert_chunk(recv)
            w_flat = plan.weights.reshape(T * cfg.top_k).astype(jnp.float32)
            with phase_scope(obs_tracing.PH_COMBINE):
                yF = wire_lib.fused_combine_transfer(
                    eo_wire, plan.flat_ids, plan.positions, w_flat, codec,
                    fwd_leaf, bwd_leaf, model_r)
            y = yF.reshape(T, cfg.top_k, H).sum(axis=1)
    else:
        if codec is None:
            wire = wire.astype(wire_dtype)
        send = wire.reshape(model_r, e_local, c_wire, H)
        ret = cplan.moe_exchange(send, expert_chunk, codec=codec)
        expert_out = ret.reshape(e_pad, c_wire, H).astype(jnp.float32)
        with phase_scope(obs_tracing.PH_DECOMPRESS):
            if use_lsh:
                out_tok = clustering.decompress(expert_out, comp,
                                                backend=kernel_backend)
            else:
                out_tok = expert_out
            y = routing.combine_tokens(plan, out_tok,
                                       backend=kernel_backend)

    all_axes = tuple(mesh.axis_names)
    aux = jax.lax.pmean(gate.aux_loss, all_axes)
    z = jax.lax.pmean(gate.z_loss, all_axes)
    load = jax.lax.psum(plan.load(), all_axes)
    y = y.reshape(B_loc, S_loc, H).astype(x.dtype)
    if not with_obs:
        return y, aux, z, load
    # In-graph metric inputs (ObsConfig.in_graph_metrics only): occupied
    # fraction of the LSH slot axis and the capacity-overflow drop
    # fraction, averaged over the mesh like the gate losses.
    occ = jnp.mean((comp.counts > 0).astype(jnp.float32)) if use_lsh \
        else jnp.zeros((), jnp.float32)
    occ = jax.lax.pmean(occ, all_axes)
    dropf = jax.lax.pmean(plan.drop_fraction(), all_axes)
    return y, aux, z, load, occ, dropf


def moe_expert_parallel(x: jax.Array, params: Dict, cfg: MoEConfig,
                        mesh: Mesh, *, mlp_act: str,
                        use_lsh: Optional[bool] = None,
                        kernel_backend: Optional[str] = None
                        ) -> Tuple[jax.Array, Dict]:
    """x: [B, S, H] sharded (batch->(pod,data), seq->model).

    params: router_w [H,E], w_gate/w_up [E_pad,H,F], w_down [E_pad,F,H],
    lsh_rot [L,H,Dr], placement [E].  ``kernel_backend`` overrides
    cfg.kernel_backend (resolved before tracing — a static choice);
    cfg.kernel_backend_overrides selects per-op backends on top.
    """
    B, S, H = x.shape
    dp = dp_axes(mesh)
    n_dp = max(1, math.prod(axis_size(mesh, a) for a in dp))
    model_r = axis_size(mesh, "model")
    e_pad = params["w_up"].shape[0]
    t_loc = (B // n_dp) * (S // model_r)
    capacity = expert_capacity(t_loc, e_pad, cfg.top_k, cfg.capacity_factor)
    use_lsh = cfg.lsh.enabled if use_lsh is None else use_lsh
    wire_dtype = jnp.dtype(cfg.lsh.wire_dtype) if use_lsh else x.dtype
    backend = _resolve_moe_backend(cfg, kernel_backend, lsh_active=use_lsh)
    # Slot count padded so the configured overlap chunking always divides
    # the slot axis (the pipelined transport's plan-time requirement) —
    # but only when pipelined can actually be selected: padding inflates
    # wire bytes AND shifts the hash modulo, so an explicit flat /
    # hierarchical transport must not pay for a chunking it never runs.
    chunk_mult = cfg.comm.overlap_chunks \
        if (cfg.comm.a2a_impl or comm_planner.AUTO) in (
            comm_planner.AUTO, comm_planner.PIPELINED) else 1
    c_wire = num_lsh_slots(capacity, cfg.lsh.compression_rate,
                           multiple=chunk_mult) if use_lsh else capacity
    # On-wire representation: the codec validates cfg.lsh.wire_format and
    # carries the kernel-backend mapping for the quant/dequant ops.  With
    # LSH off, a quantized wire_format (int8/fp8) still builds a codec —
    # the raw dispatch buffer crosses the wire coded (opt-in baseline);
    # the default "bf16" keeps the baseline codec-free (byte-identical to
    # the pre-wire-format path).
    wire_fmt = cfg.lsh.wire_format if (
        use_lsh or cfg.lsh.wire_format in wire_lib.QUANT_FORMATS) else None
    codec = wire_lib.make_codec(wire_fmt, wire_dtype=wire_dtype,
                                compute_dtype=x.dtype,
                                backend=backend) if wire_fmt is not None \
        else None
    # Transport resolution (flat | hierarchical | pipelined) happens HERE,
    # once per traced step — _local_moe only consumes the plan.  The
    # message size feeding transport auto-selection is the TRUE wire
    # bytes, scales sidecar included (clustering.wire_bytes).
    cplan = comm_planner.plan_collectives(
        mesh, cfg.comm, axis_name="model",
        msg_bytes=clustering.wire_bytes(e_pad, c_wire, H, wire_fmt,
                                        wire_dtype=wire_dtype),
        chunk_extent=c_wire)

    tok_spec = P(dp if len(dp) > 1 else (dp[0] if dp else None), "model", None)
    ew_spec = P("model", "data", None)
    rep = P(None)

    obs_on = cfg.obs.in_graph_metrics
    fn = partial(_local_moe, cfg=cfg, mesh=mesh, mlp_act=mlp_act,
                 e_pad=e_pad, capacity=capacity, use_lsh=use_lsh,
                 lsh_slots=c_wire if use_lsh else 0, wire_dtype=wire_dtype,
                 codec=codec, kernel_backend=backend, cplan=cplan,
                 with_obs=obs_on)
    mapped = shard_map(
        fn, mesh=mesh,
        in_specs=(tok_spec, P(None, None),
                  ew_spec if "w_gate" in params else None,
                  ew_spec, ew_spec, P(None, None, None), rep),
        out_specs=(tok_spec, P(), P(), P(), P(), P()) if obs_on
        else (tok_spec, P(), P(), P()),
    )
    # The train step activates the scopes already; this covers the layer
    # traced outside a step (the model's loss alone, prefill).
    with obs_tracing.activate(cfg.obs.phase_tracing):
        out = mapped(x, params["router_w"], params.get("w_gate"),
                     params["w_up"], params["w_down"], params["lsh_rot"],
                     params["placement"])
    if obs_on:
        y, aux, z, load, occ, dropf = out
        # Wire bytes per a2a leg (scales sidecar included) vs the raw
        # uncompressed dispatch buffer — the live Eq. 5 compression rate.
        wire_per_leg = clustering.wire_bytes(e_pad, c_wire, H, wire_fmt,
                                             wire_dtype=wire_dtype)
        raw_per_leg = e_pad * capacity * H * jnp.dtype(x.dtype).itemsize
        ne = min(cfg.num_experts, e_pad)
        real = load[:ne].astype(jnp.float32)
        imb = jnp.max(real) / jnp.maximum(jnp.mean(real), 1e-9)
        bag = obs_metrics.MetricBag.zeros()
        bag = bag.inc("wire_bytes", 2.0 * wire_per_leg)
        bag = bag.inc("raw_bytes", 2.0 * raw_per_leg)
        bag = bag.set("load_imbalance", imb)
        bag = bag.set("drop_fraction", dropf)
        bag = bag.set("slot_occupancy", occ)
        # Plan identity enters as static floats — no extra trace ops.
        bag = bag.set("comm_algorithm", float(cplan.algorithm_id))
        bag = bag.set("comm_degraded", float(int(cplan.degraded)))
        bag = bag.set("comm_calibrated", float(int(cplan.calibrated)))
        bag = bag.set("comm_wire_format",
                      float(comm_planner.WIRE_FORMAT_IDS.get(wire_fmt, -1)))
        comm_stat = bag
    else:
        y, aux, z, load = out
        comm_stat = _comm_stats_vector(cplan, wire_fmt)
    return y, {"aux_loss": aux, "z_loss": z, "expert_load": load,
               "comm": comm_stat}


# --------------------------------------------------------------------------
# Path 2: dense dispatch (decode) — GSPMD partitions everything.
# --------------------------------------------------------------------------

def moe_dense_dispatch(x: jax.Array, params: Dict, cfg: MoEConfig,
                       mesh: Mesh, *, mlp_act: str,
                       kernel_backend: Optional[str] = None
                       ) -> Tuple[jax.Array, Dict]:
    """x: [B, S, H] with tiny B*S (decode).  Same plan pipeline as the
    expert-parallel path, minus compression.  With a model axis of > 1
    devices the dispatch/combine exchange runs through the per-step
    ``CommPlan`` (value parity with the GSPMD path — tests/test_tune.py
    pins it on 8 forced devices); otherwise GSPMD partitions the einsums
    as before."""
    e_pad = params["w_up"].shape[0]
    backend = _resolve_moe_backend(cfg, kernel_backend, lsh_active=False)
    model_r = axis_size(mesh, "model") if mesh is not None else 1
    dp = dp_axes(mesh) if mesh is not None else ()
    n_dp = max(1, math.prod(axis_size(mesh, a) for a in dp))
    if model_r > 1 and x.shape[0] % n_dp == 0:
        return _moe_dense_planned(x, params, cfg, mesh, mlp_act=mlp_act,
                                  backend=backend, e_pad=e_pad, dp=dp,
                                  n_dp=n_dp)
    return _moe_dense_gspmd(x, params, cfg, mlp_act=mlp_act,
                            backend=backend, e_pad=e_pad)


def _moe_dense_gspmd(x, params, cfg: MoEConfig, *, mlp_act: str, backend,
                     e_pad: int) -> Tuple[jax.Array, Dict]:
    """Collective-free dense dispatch (1-device model axis / mesh-less
    local mode): GSPMD partitions the einsums, no wire."""
    B, S, H = x.shape
    xf = x.reshape(B * S, H)
    gate = top_k_gating(xf, params["router_w"], cfg.top_k, params["placement"])
    cap = max(4, int(math.ceil(B * S * cfg.top_k / e_pad * 2)))
    plan = routing.build_dispatch_plan(gate.expert_ids, gate.weights,
                                       e_pad, cap, backend=backend)
    disp = routing.dispatch_tokens(plan, xf, backend=backend).astype(x.dtype)
    eo = _expert_mlp(disp, params.get("w_gate"), params["w_up"],
                     params["w_down"], mlp_act)
    y = routing.combine_tokens(plan, eo.astype(jnp.float32), backend=backend)
    return (y.reshape(B, S, H).astype(x.dtype),
            {"aux_loss": gate.aux_loss, "z_loss": gate.z_loss,
             "expert_load": plan.load(),
             "comm": _comm_stats_vector(None, None)})


def _local_decode(x, router_w, w_gate, w_up, w_down, placement, *,
                  cfg: MoEConfig, mesh: Mesh, mlp_act: str, e_pad: int,
                  capacity: int, kernel_backend,
                  cplan: comm_planner.CommPlan):
    """Per-device decode body.  x: [B_loc, S, H], REPLICATED along the
    `model` axis (decode batches are too small to shard there): every
    model rank builds the same plan and the a2a moves each rank's blocks
    to the peers owning their experts — real planned wire traffic in the
    tiny-message regime the tuner probes."""
    model_r = axis_size(mesh, "model")
    e_local = e_pad // model_r
    B_loc, S_loc, H = x.shape
    xf = x.reshape(B_loc * S_loc, H)
    gate = top_k_gating(xf, router_w, cfg.top_k, placement)
    plan = routing.build_dispatch_plan(gate.expert_ids, gate.weights,
                                       e_pad, capacity,
                                       backend=kernel_backend)
    disp = routing.dispatch_tokens(plan, xf,
                                   backend=kernel_backend).astype(x.dtype)
    send = disp.reshape(model_r, e_local, capacity, H)
    data_r = axis_size(mesh, "data")
    wg = None if w_gate is None else cplan.all_gather(w_gate, "data", 1,
                                                      data_r)
    wu = cplan.all_gather(w_up, "data", 1, data_r)
    wd = cplan.all_gather(w_down, "data", 1, data_r)

    def expert_chunk(recv):
        r_, el, ck, h_ = recv.shape
        tok = recv.transpose(1, 0, 2, 3).reshape(el, r_ * ck, h_)
        out = _expert_mlp(tok.astype(x.dtype), wg, wu, wd, mlp_act)
        return out.reshape(el, r_, ck, h_).transpose(1, 0, 2, 3) \
            .astype(x.dtype)

    ret = cplan.moe_exchange(send, expert_chunk)
    expert_out = ret.reshape(e_pad, capacity, H).astype(jnp.float32)
    y = routing.combine_tokens(plan, expert_out, backend=kernel_backend)
    # Tokens are replicated along `model`: reduce stats over the dp axes
    # only, or every token would be counted model_r times.
    aux, z, load = gate.aux_loss, gate.z_loss, plan.load()
    dp = dp_axes(mesh)
    if dp:
        aux = jax.lax.pmean(aux, dp)
        z = jax.lax.pmean(z, dp)
        load = jax.lax.psum(load, dp)
    return y.reshape(B_loc, S_loc, H).astype(x.dtype), aux, z, load


def _moe_dense_planned(x, params, cfg: MoEConfig, mesh: Mesh, *,
                       mlp_act: str, backend, e_pad: int, dp, n_dp: int
                       ) -> Tuple[jax.Array, Dict]:
    """Decode dispatch with the exchange routed through ``CommPlan`` —
    the same trace-time transport resolution as the training path, fed
    the decode path's (tiny) true message size."""
    B, S, H = x.shape
    t_loc = (B // n_dp) * S
    capacity = expert_capacity(t_loc, e_pad, cfg.top_k, 2.0)
    cplan = comm_planner.plan_collectives(
        mesh, cfg.comm, axis_name="model",
        msg_bytes=e_pad * capacity * H * jnp.dtype(x.dtype).itemsize,
        chunk_extent=capacity)
    tok_spec = P(dp if len(dp) > 1 else (dp[0] if dp else None), None, None)
    ew_spec = P("model", "data", None)
    fn = partial(_local_decode, cfg=cfg, mesh=mesh, mlp_act=mlp_act,
                 e_pad=e_pad, capacity=capacity, kernel_backend=backend,
                 cplan=cplan)
    y, aux, z, load = shard_map(
        fn, mesh=mesh,
        in_specs=(tok_spec, P(None, None),
                  ew_spec if "w_gate" in params else None, ew_spec, ew_spec,
                  P(None)),
        out_specs=(tok_spec, P(), P(), P()),
    )(x, params["router_w"], params.get("w_gate"), params["w_up"],
      params["w_down"], params["placement"])
    return y, {"aux_loss": aux, "z_loss": z, "expert_load": load,
               "comm": _comm_stats_vector(cplan, None)}
