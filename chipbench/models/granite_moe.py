"""granite-3.0 MoE with LSH-MoE: plain float32 reference, FLOP count and
the program's keys.

The reference is written from the configuration file and the paper, in
straightforward ``jax.numpy`` (``chipbench/reference.py`` says how it is
run, stored and rounded).  One step, for a batch [B, S] on ``groups``
chips sharing each layer:

  embed * m_emb -> L x (rmsnorm -> GQA causal attention with RoPE,
                        scores * m_att -> residual * m_res
                        -> rmsnorm -> LSH-MoE -> residual * m_res)
        -> rmsnorm -> LM head / logits_scaling
        -> cross entropy + z-loss + router losses

(the configuration's embedding, attention and residual multipliers and
logits scaling; 1, head_dim^-0.5, 1 and 1 for a plain transformer).

LSH-MoE, per chip group (the tokens a chip holds between blocks: every
row of the batch, its 1/groups slice of the sequence):
  softmax router, top-k, renormalised weights; capacity sized for the
  group's own tokens, earlier (token, choice) entries first; the kept
  tokens of each expert hashed by cross-polytope LSH (argmax |x R_l| with
  the sign as the low bit, L hashes folded into one id, modulo the slot
  count); one centroid per slot (the mean of its tokens); the expert MLP
  on the centroids; each token gets its own input plus its slot's
  expert delta E(c) - c (residual compensation); top-k weighted combine.
Centroids and expert outputs cross the LSH wire rounded to the wire type
the configuration states (bf16).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

from chipbench.program import Refused
from chipbench.reference import F32, _held, _mm

FOLD_MULT = 1000003          # the paper's multi-hash fold: id = id * M + v_l


class Dims(NamedTuple):
    """Sizes and settings the reference reads from the configuration."""
    vocab: int
    hidden: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    top_k: int
    expert_ffn: int
    rope_theta: float
    norm_eps: float
    capacity_factor: float
    router_aux_weight: float
    router_z_weight: float
    z_loss_weight: float
    lsh: bool
    num_hashes: int
    rotation_dim: int
    compression_rate: float
    error_compensation: bool
    wire_dtype: str
    embedding_multiplier: float = 1.0
    attention_multiplier: float = 0.0      # 0: head_dim ** -0.5
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0


def dims(c: Dict, *, use_lsh: bool) -> Dims:
    """Dims from a configuration file's ``config`` and ``lsh`` objects."""
    m, lsh = c["config"], c["lsh"]
    return Dims(vocab=m["vocab_size"], hidden=m["hidden_size"],
                layers=m["num_hidden_layers"],
                heads=m["num_attention_heads"],
                kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
                experts=m["num_local_experts"],
                top_k=m["num_experts_per_tok"],
                expert_ffn=m["intermediate_size"],
                rope_theta=float(m["rope_theta"]),
                norm_eps=float(m["rms_norm_eps"]),
                capacity_factor=float(m["capacity_factor"]),
                router_aux_weight=float(m["router_aux_loss_coef"]),
                router_z_weight=float(m["router_z_loss_coef"]),
                z_loss_weight=float(m["z_loss_coef"]),
                lsh=use_lsh, num_hashes=lsh["num_hashes"],
                rotation_dim=lsh["rotation_dim"],
                compression_rate=float(lsh["compression_rate"]),
                error_compensation=bool(lsh["error_compensation"]),
                wire_dtype=lsh["wire_dtype"],
                embedding_multiplier=float(m.get("embedding_multiplier",
                                                 1.0)),
                attention_multiplier=float(m.get("attention_multiplier",
                                                 m["head_dim"] ** -0.5)),
                residual_multiplier=float(m.get("residual_multiplier", 1.0)),
                logits_scaling=float(m.get("logits_scaling", 1.0)))


# ----------------------------------------------------------- weights ----

def init_params(key, d: Dims) -> Dict:
    """Seeded weights in the layout and types the train state holds:
    bf16 matrices and norms, f32 router, bf16 LSH rotations, int32
    expert placement.  Stacked leaves carry the layer axis first.
    Matrices are N(0, 1/fan_in); the embedding N(0, 0.02^2); rotations
    N(0, 1/hidden)."""
    H, L, E, Fe = d.hidden, d.layers, d.experts, d.expert_ffn
    nq, nkv = d.heads * d.head_dim, d.kv_heads * d.head_dim
    bf = jnp.bfloat16
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std, dtype=bf):
        return (jax.random.normal(next(ks), shape, F32) * std).astype(dtype)

    ffn = {"w_up": normal((L, E, H, Fe), H ** -0.5),
           "w_down": normal((L, E, Fe, H), Fe ** -0.5),
           "w_gate": normal((L, E, H, Fe), H ** -0.5),
           "router_w": normal((L, H, E), H ** -0.5, F32),
           "lsh_rot": normal((L, d.num_hashes, H, min(d.rotation_dim, H)),
                             H ** -0.5),
           "placement": jnp.broadcast_to(jnp.arange(E, dtype=jnp.int32),
                                         (L, E))}
    block = {"norm1": {"scale": jnp.ones((L, H), bf)},
             "mixer": {"wq": normal((L, H, nq), H ** -0.5),
                       "wk": normal((L, H, nkv), H ** -0.5),
                       "wv": normal((L, H, nkv), H ** -0.5),
                       "wo": normal((L, nq, H), nq ** -0.5)},
             "norm2": {"scale": jnp.ones((L, H), bf)},
             "ffn": ffn}
    return {"embed": {"table": normal((d.vocab, H), 0.02)},
            "final_norm": {"scale": jnp.ones((H,), bf)},
            "head": {"w": normal((H, d.vocab), H ** -0.5)},
            "blocks": [block]}


# ------------------------------------------------------ reference ----

def _rmsnorm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(F32)


def _rope(x, theta):
    """x: [B, S, n, dh]; rotate the first half against the second."""
    dh, S = x.shape[-1], x.shape[1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(p, h, d: Dims, precision):
    B, S, _ = h.shape
    dh, g = d.head_dim, d.heads // d.kv_heads
    q = _mm("bsh,hd->bsd", h, p["wq"], precision).reshape(B, S, d.heads, dh)
    k = _mm("bsh,hd->bsd", h, p["wk"], precision).reshape(B, S, d.kv_heads, dh)
    v = _mm("bsh,hd->bsd", h, p["wv"], precision).reshape(B, S, d.kv_heads, dh)
    q, k = _rope(q, d.rope_theta), _rope(k, d.rope_theta)
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scale = d.attention_multiplier or dh ** -0.5

    @jax.checkpoint
    def one_row(qkv):                      # one batch row at a time
        qr, kr, vr = qkv
        s = _mm("qnd,knd->nqk", qr, kr, precision) * scale
        s = jnp.where(causal[None], s, -jnp.inf)
        return _mm("nqk,knd->qnd", jax.nn.softmax(s, axis=-1), vr, precision)

    o = jax.lax.map(one_row, (q, k, v)).reshape(B, S, d.heads * dh)
    return _mm("bsd,dh->bsh", o, p["wo"], precision)


def _fold_hash(x, rot):
    """Cross-polytope LSH ids of rows x [..., H] under rotations [L, H, Dr]."""
    v = jnp.einsum("...h,lhr->...lr", jax.lax.stop_gradient(x),
                   rot.astype(F32), precision=jax.lax.Precision.HIGHEST)
    idx = jnp.argmax(jnp.abs(v), axis=-1)
    neg = jnp.take_along_axis(v, idx[..., None], axis=-1)[..., 0] < 0
    vertex = (2 * idx + neg).astype(jnp.int32)
    out = jnp.zeros(vertex.shape[:-1], jnp.int32)
    for l in range(vertex.shape[-1]):
        out = out * jnp.int32(FOLD_MULT) + vertex[..., l]
    return out


def capacity(tokens: int, d: Dims) -> int:
    """Buffer rows per expert for ``tokens`` routed tokens on one chip."""
    cap = math.ceil(tokens * d.top_k / d.experts * d.capacity_factor)
    return max(8, math.ceil(cap / 8) * 8)


def lsh_slots(cap: int, d: Dims) -> int:
    return max(8, math.ceil(cap * d.compression_rate / 8) * 8)


def _wire(x, d: Dims):
    """x as it crosses the wire: rounded to the configuration's wire type."""
    return x.astype(jnp.dtype(d.wire_dtype)).astype(F32)


def _expert_mlp(p, x, precision):
    """x: [E, n, H] through each expert's SwiGLU MLP."""
    up = _mm("enh,ehf->enf", x, p["w_up"], precision)
    gate = _mm("enh,ehf->enf", x, p["w_gate"], precision)
    return _mm("enf,efh->enh", jax.nn.silu(gate) * up, p["w_down"], precision)


def _moe_group(p, x, d: Dims, precision):
    """One chip's tokens x [T, H] -> (y [T, H], aux, z).  ``p`` holds every
    expert, in the order the router numbers them."""
    T, H = x.shape
    E, k = d.experts, d.top_k
    logits = _mm("th,he->te", x, p["router_w"], precision)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    chosen = jax.nn.one_hot(ids, E, dtype=F32).sum(axis=1)
    aux = E * jnp.sum(chosen.mean(0) * probs.mean(0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))

    C = capacity(T, d)
    flat = ids.reshape(T * k)
    onehot = jax.nn.one_hot(flat, E, dtype=jnp.int32)
    pos = jnp.sum(onehot * (jnp.cumsum(onehot, axis=0) - 1), axis=1)
    keep = pos < C
    row = jnp.where(keep, flat * C + pos, E * C)           # E * C: dropped
    src = jnp.repeat(x, k, axis=0)
    buf = jnp.zeros((E * C + 1, H), F32).at[row].add(src)[:E * C]
    buf = buf.reshape(E, C, H)
    filled = jnp.minimum(onehot.sum(0), C)                 # [E]
    valid = jnp.arange(C)[None, :] < filled[:, None]       # [E, C]

    if d.lsh:
        S = lsh_slots(C, d)
        slot = jnp.abs(_fold_hash(buf, p["lsh_rot"])) % jnp.int32(S)
        slot = jnp.where(valid, slot, S)                   # S: no slot
        seg = jax.nn.one_hot(slot, S, dtype=F32)           # [E, C, S]
        count = seg.sum(axis=1)
        cent = jnp.einsum("ecs,ech->esh", seg, buf,
                          precision=jax.lax.Precision.HIGHEST)
        cent = _wire(cent / jnp.maximum(count, 1.0)[..., None], d)
        out = _wire(_expert_mlp(p, cent, precision), d)
        if d.error_compensation:
            delta = out - cent
            res = buf + jnp.einsum("ecs,esh->ech", seg, delta,
                                   precision=jax.lax.Precision.HIGHEST)
        else:
            res = jnp.einsum("ecs,esh->ech", seg, out,
                             precision=jax.lax.Precision.HIGHEST)
    else:
        res = _expert_mlp(p, buf, precision)

    got = jnp.concatenate([res.reshape(E * C, H), jnp.zeros((1, H), F32)])
    y = (got[row] * w.reshape(T * k, 1)).reshape(T, k, H).sum(axis=1)
    return y, aux, z


def _moe(p, h, d: Dims, groups: int, precision, fault: str = ""):
    """h [B, S, H]: each of ``groups`` chips holds every row's 1/groups
    slice of the sequence and routes it on its own.

    ``fault="no_exchange"`` plants the fault of an exchange left out: each
    chip's buffers for all experts go through its own experts (expert e
    through local expert e mod E/groups) instead of their owners'."""
    B, S, H = h.shape
    xs = h.reshape(B, groups, S // groups, H).transpose(1, 0, 2, 3)
    local = d.experts // groups
    owner = jnp.arange(d.experts) % local

    def group(x, g):
        q = p
        if fault == "no_exchange":
            q = dict(p, **{k: p[k][owner + g * local]
                           for k in ("w_up", "w_gate", "w_down")})
        return _moe_group(q, x.reshape(-1, H), d, precision)

    y, aux, z = jax.vmap(group)(xs, jnp.arange(groups))
    y = y.reshape(groups, B, S // groups, H).transpose(1, 0, 2, 3)
    return y.reshape(B, S, H), aux.mean(), z.mean()


def _layer(x, p, d: Dims, groups, precision, fault):
    r = d.residual_multiplier
    x = _held(x + r * _attention(p["mixer"],
                                 _rmsnorm(x, p["norm1"]["scale"], d.norm_eps),
                                 d, precision), precision)
    y, aux, z = _moe(p["ffn"], _rmsnorm(x, p["norm2"]["scale"], d.norm_eps),
                     d, groups, precision, fault)
    return _held(x + r * y, precision), aux, z


def loss_fn(params, tokens, labels, d: Dims, groups: int,
            precision: str = "f32", fault: str = ""):
    """Mean next-token cross entropy plus the z-loss and the router's
    losses.  ``fault="half_batch"`` plants the fault of half the batch left
    out: the loss is the mean over the first half of the rows."""
    if fault == "half_batch":
        half = tokens.shape[0] // 2
        tokens, labels = tokens[:half], labels[:half]
    x = _held(params["embed"]["table"].astype(F32)[tokens]
              * d.embedding_multiplier, precision)
    layer = jax.checkpoint(partial(_layer, d=d, groups=groups,
                                   precision=precision, fault=fault))

    def body(x, p):
        x, aux, z = layer(x, p)
        return x, (aux, z)

    x, (aux, z) = jax.lax.scan(body, x, params["blocks"][0])
    h = _rmsnorm(x, params["final_norm"]["scale"], d.norm_eps)

    @jax.checkpoint
    def row_loss(hl):                      # one batch row of the LM head
        hr, lr = hl
        logits = _mm("sh,hv->sv", hr, params["head"]["w"],
                     precision) / d.logits_scaling
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lr[:, None], axis=-1)[:, 0]
        return jnp.stack([jnp.sum(lse - ll), jnp.sum(jnp.square(lse))])

    sums = jax.lax.map(row_loss, (h, labels)).sum(axis=0)
    n = labels.size
    return (sums[0] / n + d.z_loss_weight * sums[1] / n
            + d.router_aux_weight * aux.sum() + d.router_z_weight * z.sum())


# ---------------------------------------------------------- FLOPs ----

def active_matmul_params(m: Dict) -> int:
    """Weights one token multiplies by in a forward pass: attention
    projections, router, its top-k experts' SwiGLU matrices and the LM
    head, over every layer.  The embedding is a lookup and is left out."""
    h, dh = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * dh, m["num_key_value_heads"] * dh
    attn = h * q + 2 * h * kv + q * h
    router = h * m["num_local_experts"]
    experts = m["num_experts_per_tok"] * 3 * h * m["intermediate_size"]
    return m["num_hidden_layers"] * (attn + router + experts) \
        + h * m["vocab_size"]


def train_flops_per_token(m: Dict, seq_len: int) -> float:
    """Model FLOPs of one trained token: 6 per active weight (forward and
    backward) plus causal attention, 6 * layers * heads * head_dim * S
    (QK^T and PV over half the sequence on average, times 3).  Every
    token counts its full top-k, with or without LSH; LSH's own work and
    recomputation do not count."""
    attn = 6 * m["num_hidden_layers"] * m["num_attention_heads"] \
        * m["head_dim"] * seq_len
    return 6.0 * active_matmul_params(m) + attn


# ------------------------------------------------ the program's keys ----

# configuration key -> how the program's ModelConfig states it
PROGRAM_KEYS = {
    "hidden_size": lambda c: c.d_model,
    "num_attention_heads": lambda c: c.num_heads,
    "num_key_value_heads": lambda c: c.num_kv_heads,
    "head_dim": lambda c: c.resolved_head_dim,
    "intermediate_size": lambda c: c.moe.expert_ffn_dim,
    "num_local_experts": lambda c: c.moe.num_experts,
    "num_experts_per_tok": lambda c: c.moe.top_k,
    "vocab_size": lambda c: c.vocab_size,
    "num_hidden_layers": lambda c: c.num_layers,
    "rope_theta": lambda c: c.rope_theta,
    "rms_norm_eps": lambda c: c.norm_eps,
    "tie_word_embeddings": lambda c: c.tie_embeddings,
    "capacity_factor": lambda c: c.moe.capacity_factor,
    "router_aux_loss_coef": lambda c: c.moe.router_aux_weight,
    "router_z_loss_coef": lambda c: c.moe.router_z_weight,
    "z_loss_coef": lambda c: c.z_loss_weight,
    "hidden_act": lambda c: {"swiglu": "silu"}.get(c.mlp_act, c.mlp_act),
    "torch_dtype": lambda c: c.dtype,
    # the program has no such fields: a plain transformer's values
    "embedding_multiplier": lambda c: getattr(c, "embedding_multiplier", 1.0),
    "attention_multiplier": lambda c: getattr(
        c, "attention_multiplier", c.resolved_head_dim ** -0.5),
    "residual_multiplier": lambda c: getattr(c, "residual_multiplier", 1.0),
    "logits_scaling": lambda c: getattr(c, "logits_scaling", 1.0),
}


def _set_layers(cfg, n):
    if n % len(cfg.layout):
        raise Refused(f"{n} layers is no whole number of "
                      f"{len(cfg.layout)}-block super-blocks")
    return cfg.replace(num_super_blocks=n // len(cfg.layout))


# configuration keys a cut may change, and how the program's config takes
# them; every other key is only checked
CUTS = {
    "num_hidden_layers": _set_layers,
    "num_local_experts": lambda c, n: c.replace(
        moe=dataclasses.replace(c.moe, num_experts=n)),
}


# configuration keys the program has no field for, and why
FILE_ONLY = {
    "max_position_embeddings": "the program sets no position limit; the "
                               "traffic's seq_len is the cell's context",
}
