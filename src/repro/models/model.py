"""Model assembly: ModelConfig -> init / train-loss / prefill / decode fns.

The layer stack is a ``lax.scan`` over ``num_super_blocks`` with stacked
parameters (keeps HLO size and compile time flat in depth); each scan step
unrolls the short ``layout``.  Remat policy wraps the scan body.  All
distribution is GSPMD sharding constraints except the MoE block, which is an
explicit shard_map region (core/moe.py).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import (ATTN, DENSE, MAMBA, MLSTM, MOE, NONE, SLSTM,
                                ModelConfig)
from repro.core.lsh_moe import lsh_moe_apply, lsh_moe_init
from repro.models import attention as attn_lib
from repro.models import ssm as ssm_lib
from repro.models import xlstm as xlstm_lib
from repro.models.layers import (embed, embedding_init, fanin_init, mlp_apply,
                                 mlp_init, rmsnorm, rmsnorm_init, unembed)
from repro.obs import metrics as obs_metrics
from repro.obs import tracing as obs_tracing
from repro.obs.tracing import phase_scope
from repro.runtime.sharding import constrain

# ---------------------------------------------------------------- helpers --


def _remat_policy(name: str):
    if name == "nothing":
        return jax.checkpoint_policies.nothing_saveable
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return None


def _sinusoidal(seq: int, d: int) -> jax.Array:
    pos = jnp.arange(seq)[:, None].astype(jnp.float32)
    dim = jnp.arange(0, d, 2)[None, :].astype(jnp.float32)
    ang = pos / jnp.power(10000.0, dim / d)
    out = jnp.zeros((seq, d), jnp.float32)
    out = out.at[:, 0::2].set(jnp.sin(ang)).at[:, 1::2].set(jnp.cos(ang))
    return out


# ------------------------------------------------------------------- init --


def _mixer_init(key, cfg: ModelConfig, mixer: str, dtype):
    h, dh = cfg.d_model, cfg.resolved_head_dim
    if mixer == ATTN:
        return attn_lib.attention_init(key, h, cfg.num_heads,
                                       cfg.num_kv_heads, dh, dtype)
    if mixer == MAMBA:
        return ssm_lib.mamba_init(key, h, cfg.ssm, dtype)
    if mixer == MLSTM:
        return xlstm_lib.mlstm_init(key, h, dh, cfg.xlstm.mlstm_proj_factor,
                                    dtype)
    if mixer == SLSTM:
        return xlstm_lib.slstm_init(key, h, cfg.num_heads,
                                    cfg.xlstm.slstm_proj_factor, dtype)
    raise ValueError(mixer)


def _block_init(key, cfg: ModelConfig, mixer: str, ffn: str, mesh, dtype,
                cross: bool) -> Dict:
    ks = jax.random.split(key, 5)
    p: Dict[str, Any] = {"norm1": rmsnorm_init(cfg.d_model, dtype),
                         "mixer": _mixer_init(ks[0], cfg, mixer, dtype)}
    if cross and mixer == ATTN:
        p["cross_norm"] = rmsnorm_init(cfg.d_model, dtype)
        p["cross"] = attn_lib.attention_init(
            ks[1], cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.resolved_head_dim, dtype)
    if ffn == DENSE:
        p["norm2"] = rmsnorm_init(cfg.d_model, dtype)
        p["ffn"] = mlp_init(ks[2], cfg.d_model, cfg.d_ff, cfg.mlp_act, dtype)
    elif ffn == MOE:
        p["norm2"] = rmsnorm_init(cfg.d_model, dtype)
        p["ffn"] = lsh_moe_init(ks[3], cfg.d_model, cfg.moe, mesh,
                                mlp_act=cfg.mlp_act, dtype=dtype)
    return p


def init_params(key, cfg: ModelConfig, mesh: Mesh) -> Dict:
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 6)
    params: Dict[str, Any] = {
        "embed": embedding_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = {"w": fanin_init(ks[1], (cfg.d_model,
                                                  cfg.vocab_size), dtype)}

    def stack(key, cross):
        entries = []
        for i, (mixer, ffn) in enumerate(cfg.layout):
            sub = jax.random.fold_in(key, i)
            bks = jax.random.split(sub, cfg.num_super_blocks)
            entries.append(jax.vmap(
                lambda k: _block_init(k, cfg, mixer, ffn, mesh, dtype, cross)
            )(bks))
        return entries

    params["blocks"] = stack(ks[2], cross=cfg.encoder_decoder)
    if cfg.encoder_decoder:
        enc_cfg = cfg.replace(layout=((ATTN, DENSE),),
                              num_super_blocks=cfg.num_encoder_super_blocks,
                              encoder_decoder=False)
        enc_blocks = []
        sub = jax.random.fold_in(ks[3], 999)
        bks = jax.random.split(sub, enc_cfg.num_super_blocks)
        enc_blocks.append(jax.vmap(
            lambda k: _block_init(k, enc_cfg, ATTN, DENSE, mesh, dtype, False)
        )(bks))
        params["encoder"] = {"blocks": enc_blocks,
                             "final_norm": rmsnorm_init(cfg.d_model, dtype)}
    return params


# -------------------------------------------------------------- forward ----


def _apply_mixer(p, x, cfg: ModelConfig, mesh, *, causal, kv_chunk,
                 enc_states=None):
    mixer_kind = _infer_mixer_kind(p)
    if mixer_kind == ATTN:
        # the whole attention mixer: projections, the sequence<->head
        # exchanges it issues, and cross-attention where there is one
        with phase_scope(obs_tracing.PH_ATTENTION):
            y = attn_lib.attention_apply(
                p["mixer"], x, num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
                causal=causal, kv_chunk=kv_chunk,
                use_rope=(cfg.pos_emb == "rope"), mesh=mesh)
            if enc_states is not None and "cross" in p:
                xc = x + y
                y2 = attn_lib.attention_apply(
                    p["cross"], rmsnorm(p["cross_norm"], xc, cfg.norm_eps),
                    num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                    head_dim=cfg.resolved_head_dim,
                    rope_theta=cfg.rope_theta, causal=False,
                    kv_chunk=kv_chunk, use_rope=False, kv_x=enc_states,
                    mesh=mesh)
                return y + y2
            return y
    if mixer_kind == MAMBA:
        return ssm_lib.mamba_apply(p["mixer"], x, cfg.ssm, cfg.norm_eps,
                                   mesh=mesh)
    if mixer_kind == MLSTM:
        return xlstm_lib.mlstm_apply(p["mixer"], x, cfg.resolved_head_dim,
                                     cfg.xlstm.chunk_size, cfg.norm_eps,
                                     mesh=mesh)
    if mixer_kind == SLSTM:
        return xlstm_lib.slstm_apply(p["mixer"], x, cfg.norm_eps)
    raise ValueError(mixer_kind)


def _infer_mixer_kind(p) -> str:
    m = p["mixer"]
    if "wq" in m:
        return ATTN
    if "w_dt" in m:
        return MAMBA
    if "w_if" in m:
        return MLSTM
    return SLSTM


def stage_bounds(num_super_blocks: int, stages: int) -> Tuple[Tuple[int, int], ...]:
    """Even partition of the super-block scan into pipeline stages.

    The cut points are chosen at SUPER-BLOCK granularity: ``layout``
    repeats once per super-block, so every stage owns at least one full
    layout repeat and therefore keeps its MoE blocks (the per-stage a2a
    the 1F1B schedule hides in the bubbles).  Earlier stages take the
    remainder so the deepest (last) stage — which also carries the head —
    is never the widest."""
    if stages < 1:
        raise ValueError(f"stages={stages} must be >= 1")
    if stages > num_super_blocks:
        raise ValueError(
            f"stages={stages} > num_super_blocks={num_super_blocks}: every "
            f"stage needs >= 1 super-block (one full layout repeat)")
    base, rem = divmod(num_super_blocks, stages)
    bounds, start = [], 0
    for s in range(stages):
        width = base + (1 if s < rem else 0)
        bounds.append((start, start + width))
        start += width
    return tuple(bounds)


def stage_blocks(blocks, start: int, stop: int):
    """Slice the stacked [NSB, ...] block params down to one stage's
    sub-stack — the per-stage scan operates on the same leaves, so
    splitting one scan into consecutive stage scans is value-identical."""
    return jax.tree.map(lambda a: a[start:stop], blocks)


def _stack_forward(blocks, x, cfg: ModelConfig, mesh, *, layout, causal,
                   use_lsh=None, enc_states=None, moe_mode="train",
                   init_stats=None):
    """Scan over super-blocks. blocks: list of stacked pytrees per entry.
    ``init_stats`` threads the (aux, z, load, comm) carry across stage
    boundaries when the stack is partitioned (pipeline_schedule.py)."""
    policy = _remat_policy(cfg.remat_policy)
    do_remat = policy is not None and cfg.remat_policy != "full"

    def one_block(p, x, mixer, ffn):
        """One (mixer, ffn) block — individually remat'd so only a single
        block's internals are live during the super-block backward."""
        x = constrain(x, mesh, "batch", "seq", None)
        h = rmsnorm(p["norm1"], x, cfg.norm_eps)
        x = x + _apply_mixer(p, h, cfg, mesh, causal=causal,
                             kv_chunk=cfg.kv_chunk, enc_states=enc_states)
        aux = z = jnp.zeros((), jnp.float32)
        load = comm = None
        if ffn == DENSE:
            h = rmsnorm(p["norm2"], x, cfg.norm_eps)
            if mesh is None:            # dp_only local mode: plain matmuls
                x = x + mlp_apply(p["ffn"], h, cfg.mlp_act)
            else:
                from repro.runtime.tp import tp_in_project, tp_project
                # SP->TP explicit bf16 gather+project; TP->SP bf16 RS
                if cfg.mlp_act == "swiglu":
                    hh, g = tp_in_project(
                        h, (p["ffn"]["w_up"], p["ffn"]["w_gate"]), mesh)
                    hh = jax.nn.silu(g.astype(jnp.float32)).astype(
                        hh.dtype) * hh
                else:
                    (hh,) = tp_in_project(h, (p["ffn"]["w_up"],), mesh)
                    hh = jnp.square(jax.nn.relu(hh)) \
                        if cfg.mlp_act == "relu2" else jax.nn.gelu(hh)
                hh = constrain(hh, mesh, "batch", None, "mlp")
                x = x + tp_project(hh, p["ffn"]["w_down"], mesh)
        elif ffn == MOE:
            h = rmsnorm(p["norm2"], x, cfg.norm_eps)
            y, stats = lsh_moe_apply(p["ffn"], h, cfg.moe, mesh,
                                     mlp_act=cfg.mlp_act, mode=moe_mode,
                                     use_lsh=use_lsh)
            x = x + y
            aux, z, load = stats["aux_loss"], stats["z_loss"], \
                stats["expert_load"]
            comm = stats.get("comm")
        return x, aux, z, load, comm

    def body(carry, stacked):
        x, aux, z, load, comm = carry
        for i, (mixer, ffn) in enumerate(layout):
            fn = partial(one_block, mixer=mixer, ffn=ffn)
            if do_remat:
                fn = jax.checkpoint(fn, policy=policy, prevent_cse=False)
            x, a, zz, ld, cm = fn(stacked[i], x)
            aux, z = aux + a, z + zz
            if ld is not None:
                load = load + ld
            if cm is not None:
                # legacy int32 vector: static per-trace (same plan for
                # every MoE layer) — overwrite.  MetricBag (obs on):
                # counters accumulate across layers, gauges overwrite.
                comm = obs_metrics.merge_stat(comm, cm)
        return (x, aux, z, load, comm), None

    if do_remat:
        body = jax.checkpoint(body, policy=policy, prevent_cse=False)
    if init_stats is not None:
        aux0 = init_stats
    else:
        n_moe = sum(1 for _, f in layout if f == MOE)
        e_pad = blocks and _find_epad(blocks, layout)
        aux0 = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
                jnp.zeros((e_pad,), jnp.float32) if n_moe else
                jnp.zeros((1,), jnp.float32),
                initial_comm_stat(cfg, layout))
    (x, aux, z, load, comm), _ = jax.lax.scan(body, (x, *aux0),
                                              tuple(blocks))
    return x, {"aux_loss": aux, "z_loss": z, "expert_load": load,
               "comm": comm}


def _find_epad(blocks, layout) -> int:
    for i, (_, ffn) in enumerate(layout):
        if ffn == MOE:
            return blocks[i]["ffn"]["w_up"].shape[1]  # [NSB, E_pad, H, F]
    return 1


def _embed_inputs(params, cfg: ModelConfig, mesh, batch: Dict) -> jax.Array:
    x = embed(params["embed"], batch["tokens"])
    if cfg.frontend == "patch_stub" and "patch_embeds" in batch:
        x = jnp.concatenate([batch["patch_embeds"].astype(x.dtype), x], axis=1)
    if cfg.pos_emb == "learned":
        S = x.shape[1]
        x = x + _sinusoidal(S, cfg.d_model).astype(x.dtype)[None]
    return constrain(x, mesh, "batch", "seq", None)


def _encode(params, cfg: ModelConfig, mesh, frames: jax.Array):
    """Whisper-style encoder over precomputed frame embeddings (stub)."""
    x = frames.astype(jnp.dtype(cfg.dtype))
    x = x + _sinusoidal(x.shape[1], cfg.d_model).astype(x.dtype)[None]
    x = constrain(x, mesh, "batch", "seq", None)
    enc = params["encoder"]
    x, _ = _stack_forward(enc["blocks"], x, cfg, mesh,
                          layout=((ATTN, DENSE),), causal=False)
    return rmsnorm(enc["final_norm"], x, cfg.norm_eps)


def stats_carry(stats: Dict) -> Tuple:
    """stats dict -> the (aux, z, load, comm) scan carry, for threading a
    partitioned stack across stage boundaries (pipeline_schedule.py)."""
    return (stats["aux_loss"], stats["z_loss"], stats["expert_load"],
            stats["comm"])


def initial_comm_stat(cfg: ModelConfig, layout):
    """Zero element for the stats carry's comm slot: a zeroed
    ``MetricBag`` when in-graph metrics are on and the layout has MoE
    blocks, else the legacy packed int32 sentinel (unplanned
    algorithm/format, flags clear — core/moe._comm_stats_vector layout).
    Shared by the stack scan's init and the pipeline grid's stage-0
    carry so both agree on one treedef."""
    has_moe = any(f == MOE for _, f in layout)
    if has_moe and cfg.moe.obs.in_graph_metrics:
        return obs_metrics.MetricBag.zeros()
    return jnp.array([-1, 0, 0, -1], jnp.int32)


def head_logits(params, cfg: ModelConfig, mesh, x: jax.Array) -> jax.Array:
    """Final norm + (tied) unembedding -> vocab-sharded f32 logits.
    ``params`` needs "final_norm" and "embed"/"head" only — the last
    pipeline stage calls this with just its own slice."""
    with phase_scope(obs_tracing.PH_LM_HEAD):
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        x = constrain(x, mesh, "batch", "seq", None)
        if cfg.tie_embeddings:
            logits = unembed(params["embed"], x)
        else:
            logits = (x @ params["head"]["w"]).astype(jnp.float32)
        return constrain(logits, mesh, "batch", None, "vocab")


def forward(params, cfg: ModelConfig, mesh: Mesh, batch: Dict, *,
            use_lsh: Optional[bool] = None, moe_mode: str = "train"
            ) -> Tuple[jax.Array, Dict]:
    """Full-sequence forward -> (logits [B,S,V] vocab-sharded f32, stats)."""
    enc_states = None
    if cfg.encoder_decoder:
        enc_states = _encode(params, cfg, mesh, batch["frames"])
    x = _embed_inputs(params, cfg, mesh, batch)
    x, stats = _stack_forward(params["blocks"], x, cfg, mesh,
                              layout=cfg.layout, causal=True,
                              use_lsh=use_lsh, enc_states=enc_states,
                              moe_mode=moe_mode)
    return head_logits(params, cfg, mesh, x), stats


def loss_from_logits(cfg: ModelConfig, logits: jax.Array, stats: Dict,
                     batch: Dict) -> Tuple[jax.Array, Dict]:
    """CE + z-loss + MoE aux from already-computed logits — the tail the
    last pipeline stage shares with the monolithic ``loss_fn``."""
    with phase_scope(obs_tracing.PH_LM_HEAD):
        return _loss_from_logits(cfg, logits, stats, batch)


def _loss_from_logits(cfg: ModelConfig, logits: jax.Array, stats: Dict,
                      batch: Dict) -> Tuple[jax.Array, Dict]:
    labels = batch["labels"]
    if cfg.frontend == "patch_stub" and "patch_embeds" in batch:
        npatch = batch["patch_embeds"].shape[1]
        logits = logits[:, npatch:, :]
    lse = jax.nn.logsumexp(logits, axis=-1)
    # label log-prob via mask-and-reduce: partitions over the sharded vocab
    # axis (take_along_axis would all-gather the logits).
    vocab_iota = jnp.arange(logits.shape[-1], dtype=labels.dtype)
    ll = jnp.sum(jnp.where(labels[..., None] == vocab_iota, logits, 0.0),
                 axis=-1)
    mask = (labels >= 0).astype(jnp.float32)
    ce = jnp.sum((lse - ll) * mask) / jnp.maximum(mask.sum(), 1.0)
    zl = cfg.z_loss_weight * jnp.mean(jnp.square(lse))
    moe_aux = (cfg.moe.router_aux_weight * stats["aux_loss"]
               + cfg.moe.router_z_weight * stats["z_loss"])
    total = ce + zl + moe_aux
    metrics = {"ce": ce, "z_loss": zl, "moe_aux": stats["aux_loss"],
               "expert_load": stats["expert_load"], "loss": total}
    comm = stats.get("comm")
    if obs_metrics.is_bag(comm):
        # Structured in-graph metrics (ObsConfig): flatten the bag into
        # obs_* scalars, derive the live Eq. 5 compression rate, and keep
        # the legacy comm_* names aliased to the bag's gauges.
        metrics.update(comm.as_metrics())
        metrics["obs_compression_rate"] = (
            comm.get("wire_bytes")
            / jnp.maximum(comm.get("raw_bytes"), 1.0))
        metrics.update(
            comm_algorithm=comm.get("comm_algorithm"),
            comm_degraded=comm.get("comm_degraded"),
            comm_calibrated=comm.get("comm_calibrated"),
            comm_wire_format=comm.get("comm_wire_format"))
    elif comm is not None and cfg.has_moe():
        # Planned-transport observability (core/moe._comm_stats_vector):
        # which a2a ran this step, whether the planner degraded it,
        # whether calibrated constants ranked it, and the wire format —
        # floats so dp-only pmean over metrics stays well-typed.
        metrics.update(
            comm_algorithm=comm[0].astype(jnp.float32),
            comm_degraded=comm[1].astype(jnp.float32),
            comm_calibrated=comm[2].astype(jnp.float32),
            comm_wire_format=comm[3].astype(jnp.float32))
    return total, metrics


def loss_fn(params, cfg: ModelConfig, mesh: Mesh, batch: Dict, *,
            use_lsh: Optional[bool] = None) -> Tuple[jax.Array, Dict]:
    logits, stats = forward(params, cfg, mesh, batch, use_lsh=use_lsh)
    return loss_from_logits(cfg, logits, stats, batch)


# ---------------------------------------------------------------- decode ----


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                      mesh: Mesh) -> Dict:
    """Per-layout-entry stacked caches/states for the scan-over-blocks."""
    dtype = jnp.dtype(cfg.dtype)
    dh = cfg.resolved_head_dim
    entries = []
    for mixer, _ in cfg.layout:
        if mixer == ATTN:
            st = {"k": jnp.zeros((cfg.num_super_blocks, batch, max_len,
                                  cfg.num_kv_heads, dh), dtype),
                  "v": jnp.zeros((cfg.num_super_blocks, batch, max_len,
                                  cfg.num_kv_heads, dh), dtype)}
            if cfg.encoder_decoder:
                st["cross_k"] = jnp.zeros((cfg.num_super_blocks, batch,
                                           max_len, cfg.num_kv_heads, dh),
                                          dtype)
                st["cross_v"] = jnp.zeros_like(st["cross_k"])
        elif mixer == MAMBA:
            d_inner = cfg.ssm.expand * cfg.d_model
            nh = d_inner // cfg.ssm.head_dim
            st = {"h": jnp.zeros((cfg.num_super_blocks, batch, nh,
                                  cfg.ssm.head_dim, cfg.ssm.d_state),
                                 jnp.float32),
                  "conv": jnp.zeros((cfg.num_super_blocks, batch,
                                     cfg.ssm.conv_width - 1, d_inner), dtype)}
        elif mixer == MLSTM:
            d_in = int(cfg.xlstm.mlstm_proj_factor * cfg.d_model)
            d_in -= d_in % dh
            nh = d_in // dh
            st = {"C": jnp.zeros((cfg.num_super_blocks, batch, nh, dh, dh),
                                 jnp.float32),
                  "n": jnp.zeros((cfg.num_super_blocks, batch, nh, dh),
                                 jnp.float32),
                  "m": jnp.zeros((cfg.num_super_blocks, batch, nh),
                                 jnp.float32)}
        elif mixer == SLSTM:
            st = {n: jnp.zeros((cfg.num_super_blocks, batch, cfg.d_model),
                               jnp.float32) for n in ("c", "n", "h", "m")}
        else:
            st = {}
        entries.append(st)
    return {"entries": entries, "position": jnp.zeros((), jnp.int32)}


def decode_step(params, cfg: ModelConfig, mesh: Mesh, state: Dict,
                tokens: jax.Array) -> Tuple[jax.Array, Dict]:
    """One decode step. tokens: [B, 1] -> (logits [B,1,V], new state)."""
    pos = state["position"]
    x = embed(params["embed"], tokens)
    if cfg.pos_emb == "learned":
        x = x + jax.lax.dynamic_slice_in_dim(
            _sinusoidal(8192, cfg.d_model), pos % 8192, 1, 0)[None].astype(x.dtype)
    x = constrain(x, mesh, "batch", None, None)
    dh = cfg.resolved_head_dim

    def one_block(mixer, ffn, p, s, x):
            h = rmsnorm(p["norm1"], x, cfg.norm_eps)
            if mixer == ATTN:
                y, sc = attn_lib.decode_attention(
                    p["mixer"], h, {"k": s["k"], "v": s["v"]}, pos,
                    num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                    head_dim=dh, rope_theta=cfg.rope_theta,
                    use_rope=(cfg.pos_emb == "rope"))
                s_new = dict(s); s_new.update(sc)
                if "cross" in p:
                    hc = rmsnorm(p["cross_norm"], x + y, cfg.norm_eps)
                    y2, _ = attn_lib.decode_attention(
                        p["cross"], hc, {"k": s["cross_k"], "v": s["cross_v"]},
                        pos, num_heads=cfg.num_heads,
                        num_kv_heads=cfg.num_kv_heads, head_dim=dh,
                        rope_theta=cfg.rope_theta, use_rope=False, cross=True)
                    y = y + y2
            elif mixer == MAMBA:
                y, s_new = ssm_lib.mamba_decode(p["mixer"], h, s, cfg.ssm,
                                                cfg.norm_eps)
            elif mixer == MLSTM:
                y, (C, n, m) = xlstm_lib.mlstm_decode(
                    p["mixer"], h, (s["C"], s["n"], s["m"]), dh, cfg.norm_eps)
                s_new = {"C": C, "n": n, "m": m}
            elif mixer == SLSTM:
                y, (c, n, hh, m) = xlstm_lib.slstm_decode(
                    p["mixer"], h, (s["c"], s["n"], s["h"], s["m"]),
                    cfg.norm_eps)
                s_new = {"c": c, "n": n, "h": hh, "m": m}
            else:
                y, s_new = jnp.zeros_like(x), s
            x = x + y
            if ffn == DENSE:
                x = x + mlp_apply(p["ffn"], rmsnorm(p["norm2"], x,
                                                    cfg.norm_eps), cfg.mlp_act)
            elif ffn == MOE:
                y, _ = lsh_moe_apply(p["ffn"], rmsnorm(p["norm2"], x,
                                                       cfg.norm_eps),
                                     cfg.moe, mesh, mlp_act=cfg.mlp_act,
                                     mode="decode")
                x = x + y
            return x, s_new

    # Scan over super-blocks with the full layout INSIDE each step — block
    # order must match _stack_forward (interleaved), not entry-major.
    def body(x, inp):
        ps, ss = inp
        new_ss = []
        for i, (mixer, ffn) in enumerate(cfg.layout):
            x, s_new = one_block(mixer, ffn, ps[i], ss[i], x)
            new_ss.append(s_new)
        return x, tuple(new_ss)

    x, new_entries = jax.lax.scan(
        body, x, (tuple(params["blocks"]), tuple(state["entries"])))
    new_entries = list(new_entries)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = unembed(params["embed"], x)
    else:
        logits = (x @ params["head"]["w"]).astype(jnp.float32)
    return logits, {"entries": new_entries, "position": pos + 1}


def prefill(params, cfg: ModelConfig, mesh: Mesh, batch: Dict,
            ) -> Tuple[jax.Array, Dict]:
    """Inference prefill: full forward returning last-position logits.
    (Cache construction for subsequent decode is exercised via decode_step's
    dynamic_update_slice path; the dry-run prefill cell lowers this fn.)"""
    logits, _ = forward(params, cfg, mesh, batch, use_lsh=None,
                        moe_mode="prefill")
    return logits[:, -1:, :], {"position": jnp.asarray(batch["tokens"].shape[1],
                                                       jnp.int32)}
