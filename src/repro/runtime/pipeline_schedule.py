"""Microbatched 1F1B pipeline schedule over the ``pipe`` mesh axis.

The super-block scan is partitioned into per-stage sub-stacks
(``models/model.stage_bounds`` — cuts at super-block granularity so every
stage keeps one full layout repeat and therefore its MoE blocks), and the
train step is re-expressed as the classic one-forward-one-backward tick
program: warmup forwards, steady-state B/F alternation, cooldown
backwards.  ``build_1f1b`` is a deterministic simulator producing the
exact per-stage timeline; ``Schedule.a2a_slot`` is the bubble-overlap
contract — the LSH dispatch/combine exchange of microbatch *k* issues in
the tick before F(stage, k), where the stage is either idle (a pipeline
bubble) or computing a DIFFERENT microbatch, so the wire time hides
behind compute (docs/pipeline.md).

Numerics contract: the staged step is BIT-IDENTICAL (loss and gradients)
to the monolithic scan with the same microbatch accumulation
(runtime/step.accum_grads).  Splitting one ``lax.scan`` into consecutive
stage scans over param slices preserves the op sequence; the per-stage
``jax.vjp`` chain is the same transposition AD performs internally; and
the gradient accumulator mirrors ``accum_grads`` term-for-term
(``acc + g.astype(f32) / n`` in increasing-microbatch order — which is
exactly the order 1F1B retires stage-0 backwards).

Placement altitude: like the rest of the repo, the pipe axis partitions
the SCHEDULE and the cost model, not device placement — under GSPMD the
stage sub-stacks are replicated over ``pipe`` and the stage hand-off is
the identity resharding of the destination constraint
(``stage_transfer``), priced by ``topology.stage_transfer_cost`` and
recorded via ``planner.plan_stage_transfers``.  Mapping stage compute
onto pipe slices with shard_map is the seeded follow-on (ROADMAP).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.comm import planner as comm_planner
from repro.configs.base import MOE, ModelConfig, OptimizerConfig
from repro.models import model as model_lib
from repro.obs import tracing as obs_tracing
from repro.obs.tracing import phase_scope
from repro.runtime.sharding import constrain

F, B = "F", "B"


# ------------------------------------------------------------- schedule ---


@dataclass(frozen=True)
class Schedule:
    """A 1F1B timetable: ``grid[stage][tick]`` is ("F"|"B", microbatch)
    or None (a bubble).  Forward and backward units take one tick each."""
    stages: int
    microbatches: int
    grid: Tuple[Tuple[Optional[Tuple[str, int]], ...], ...]

    @property
    def ticks(self) -> int:
        return len(self.grid[0])

    def tick_of(self, stage: int, phase: str, mb: int) -> int:
        return self.grid[stage].index((phase, mb))

    def bubbles(self, stage: int) -> Tuple[int, ...]:
        return tuple(t for t, u in enumerate(self.grid[stage]) if u is None)

    def bubble_fraction(self) -> float:
        """Idle fraction of the stage x tick grid; (S-1)/(M+S-1) for the
        canonical 1F1B timetable, 0 for a single stage."""
        idle = sum(len(self.bubbles(s)) for s in range(self.stages))
        return idle / float(self.stages * self.ticks)

    def a2a_slot(self, stage: int, mb: int) -> int:
        """The tick whose compute slot hides microbatch ``mb``'s MoE
        exchange on ``stage``: the tick before F(stage, mb).  By
        construction that slot is a bubble or a different microbatch's
        unit — never (F|B, mb) itself.  -1 for the very first unit of the
        pipeline (stage 0, microbatch 0): the cold start has nothing to
        hide behind."""
        return self.tick_of(stage, F, mb) - 1


def build_1f1b(stages: int, microbatches: int) -> Schedule:
    """Simulate the 1F1B policy tick by tick.  Per stage: issue a forward
    while the in-flight bound (stages - stage) allows and the upstream
    activation arrived; otherwise a backward once the downstream
    cotangent arrived; otherwise idle (a bubble)."""
    S, M = int(stages), int(microbatches)
    if S < 1 or M < 1:
        raise ValueError(f"stages={stages}, microbatches={microbatches} "
                         f"must both be >= 1")
    INF = 1 << 30
    done_f: Dict[Tuple[int, int], int] = {}
    done_b: Dict[Tuple[int, int], int] = {}
    nf, nb = [0] * S, [0] * S
    rows: List[List[Optional[Tuple[str, int]]]] = [[] for _ in range(S)]
    t = 0
    while sum(nb) < S * M:
        if t > 2 * (M + S) + 4:
            raise RuntimeError("1F1B simulator did not converge")
        acts = []
        for s in range(S):
            f_ready = (nf[s] < M and nf[s] - nb[s] < S - s
                       and (s == 0 or done_f.get((s - 1, nf[s]), INF) < t))
            b_ready = nb[s] < nf[s] and (
                done_b.get((s + 1, nb[s]), INF) < t if s < S - 1
                else done_f.get((s, nb[s]), INF) < t)
            acts.append((F, nf[s]) if f_ready
                        else (B, nb[s]) if b_ready else None)
        for s, act in enumerate(acts):
            rows[s].append(act)
            if act is None:
                continue
            ph, mb = act
            if ph == F:
                done_f[(s, mb)] = t
                nf[s] += 1
            else:
                done_b[(s, mb)] = t
                nb[s] += 1
        t += 1
    return Schedule(S, M, tuple(tuple(r) for r in rows))


def bubble_fraction(stages: int, microbatches: int) -> float:
    """Closed form for the canonical 1F1B timetable (benchmarks)."""
    if stages <= 1:
        return 0.0
    return (stages - 1) / float(microbatches + stages - 1)


# ------------------------------------------------------ staged train step --


def stage_transfer(x, mesh):
    """Stage-boundary activation hand-off.  Under GSPMD this is the
    resharding collective XLA inserts for the destination constraint —
    the same logical spec the next block pins, so on today's
    pipe-replicated layout it is the identity (bit-identical stacks);
    the planner records and prices it (plan_stage_transfers)."""
    with phase_scope(obs_tracing.PH_STAGE):
        return constrain(x, mesh, "batch", "seq", None)


def _partition(tree):
    """(differentiable, static) split of a param tree — jax.vjp rejects
    integer-dtype primals (MoE placement tables), so those ride a closure
    instead.  Positions not taken are None in the counterpart."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    diff = treedef.unflatten(
        [x if jnp.issubdtype(x.dtype, jnp.inexact) else None for x in leaves])
    static = treedef.unflatten(
        [None if jnp.issubdtype(x.dtype, jnp.inexact) else x for x in leaves])
    return diff, static


def _combine(diff, static):
    return jax.tree.map(lambda d, s: d if s is None else s, diff, static,
                        is_leaf=lambda x: x is None)


def _stage_params(params, cfg: ModelConfig, bounds, s: int, stages: int):
    """The param slice stage ``s`` owns: its block sub-stack, plus the
    embedding on stage 0 and the head on the last stage (the tied
    embedding appears on both — its two gradient contributions are summed
    per microbatch exactly like monolithic AD does)."""
    start, stop = bounds[s]
    sp: Dict[str, Any] = {
        "blocks": [model_lib.stage_blocks(entry, start, stop)
                   for entry in params["blocks"]]}
    if s == 0:
        sp["embed"] = params["embed"]
    if s == stages - 1:
        sp["final_norm"] = params["final_norm"]
        if cfg.tie_embeddings:
            sp["embed"] = params["embed"]
        elif "head" in params:
            sp["head"] = params["head"]
    return sp


def make_pipeline_grad_fn(cfg: ModelConfig, mesh, *,
                          use_lsh: Optional[bool] = None):
    """grad_fn(params, batch) -> (loss, metrics, grads), the 1F1B staged
    equivalent of ``runtime/step.make_accum_grad_fn`` — bit-identical
    values and gradients, with the stage program laid out tick by tick
    and the MoE a2a planned as the bubble-overlapped variant."""
    if mesh is None or "pipe" not in mesh.axis_names:
        raise ValueError("make_pipeline_grad_fn needs a mesh with a "
                         "'pipe' axis (launch/mesh.make_host_mesh)")
    if cfg.encoder_decoder:
        raise NotImplementedError(
            "pipeline staging of encoder-decoder stacks (the encoder is "
            "not part of the super-block scan)")
    stages = int(mesh.shape["pipe"])
    bounds = model_lib.stage_bounds(cfg.num_super_blocks, stages)
    n_mb = int(cfg.pipeline_microbatches) or stages
    sched = build_1f1b(stages, n_mb)
    n_moe = sum(1 for _, f in cfg.layout if f == MOE)

    def _apply_stage(s, dsp, static_sp, x, carry3, comm_in, b):
        """One stage's forward: (embed ->) stage scan (-> head + loss).
        Returns (differentiable_out, aux) for jax.vjp(has_aux=True); the
        int32 comm vector rides aux / the closure, never a primal."""
        sp = _combine(dsp, static_sp)
        if s == 0:
            x = model_lib._embed_inputs(sp, cfg, mesh, b)
        x, stats = model_lib._stack_forward(
            sp["blocks"], x, cfg, mesh, layout=cfg.layout, causal=True,
            use_lsh=use_lsh, moe_mode="train",
            init_stats=(*carry3, comm_in))
        aux3 = (stats["aux_loss"], stats["z_loss"], stats["expert_load"])
        if s == stages - 1:
            logits = model_lib.head_logits(sp, cfg, mesh, x)
            loss, metrics = model_lib.loss_from_logits(cfg, logits, stats, b)
            return loss, metrics
        return (stage_transfer(x, mesh), aux3), stats["comm"]

    def _assemble(gs, params):
        """Stitch per-stage diff-gradients back into the full-params
        shape: block slices concatenate along the stacked axis (slicing
        commutes with the elementwise accumulate), the tied embedding's
        two uses sum."""
        blocks = []
        for i in range(len(params["blocks"])):
            parts = [g["blocks"][i] for g in gs]
            blocks.append(parts[0] if stages == 1 else jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0), *parts))
        out: Dict[str, Any] = {"blocks": blocks,
                               "final_norm": gs[-1]["final_norm"],
                               "embed": gs[0]["embed"]}
        if cfg.tie_embeddings and stages > 1:
            out["embed"] = jax.tree.map(lambda a, b_: a + b_,
                                        out["embed"], gs[-1]["embed"])
        if "head" in gs[-1]:
            out["head"] = gs[-1]["head"]
        return out

    def _run(params, batch):
        rows = batch["tokens"].shape[0]
        if rows % n_mb:
            raise ValueError(f"batch rows {rows} not divisible by "
                             f"pipeline microbatches {n_mb}")
        per = rows // n_mb
        mbs = [jax.tree.map(
            lambda v: constrain(v[k * per:(k + 1) * per], mesh, "batch",
                                *([None] * (v.ndim - 1))), batch)
            for k in range(n_mb)]
        sps = [_stage_params(params, cfg, bounds, s, stages)
               for s in range(stages)]
        parts = [_partition(sp) for sp in sps]

        e_pad = model_lib._find_epad(params["blocks"], cfg.layout)
        zeros3 = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
                  jnp.zeros((e_pad if n_moe else 1,), jnp.float32))
        comm0 = model_lib.initial_comm_stat(cfg, cfg.layout)

        # accumulators mirror runtime/step.accum_grads term for term
        # (None marks non-floating params; finalized to f32 scalar zeros)
        acc_l = jnp.zeros((), jnp.float32)
        acc = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32)
            if jnp.issubdtype(p.dtype, jnp.floating) else None, params)

        fwd_out: Dict = {}      # (s, mb) -> (x, carry3) leaving stage s
        comm_out: Dict = {}     # (s, mb) -> comm vector leaving stage s
        vjps: Dict = {}
        down: Dict = {}         # (s, mb) -> cotangents for stage s-1's out
        stage_g: Dict = {}
        loss_vals: Dict = {}
        metrics_by_mb: Dict = {}

        def emit_f(s, mb):
            b, (dsp, ssp) = mbs[mb], parts[s]
            if s == 0:
                fn = (lambda _b, _ssp:
                      lambda d: _apply_stage(0, d, _ssp, None, zeros3,
                                             comm0, _b))(b, ssp)
                out, vjp, aux = jax.vjp(fn, dsp, has_aux=True)
            else:
                x_in, c3_in = fwd_out.pop((s - 1, mb))
                cm_in = comm_out.pop((s - 1, mb))
                fn = (lambda _s, _b, _ssp, _cm:
                      lambda d, x, c3: _apply_stage(_s, d, _ssp, x, c3,
                                                    _cm, _b))(s, b, ssp,
                                                              cm_in)
                out, vjp, aux = jax.vjp(fn, dsp, x_in, c3_in, has_aux=True)
            vjps[(s, mb)] = vjp
            if s == stages - 1:
                loss_vals[mb], metrics_by_mb[mb] = out, aux
            else:
                fwd_out[(s, mb)], comm_out[(s, mb)] = out, aux

        def emit_b(s, mb):
            nonlocal acc, acc_l
            vjp = vjps.pop((s, mb))
            ct = (jnp.ones((), loss_vals[mb].dtype) if s == stages - 1
                  else down.pop((s + 1, mb)))
            cts = vjp(ct)
            stage_g[(s, mb)] = cts[0]
            if s > 0:
                down[(s, mb)] = (cts[1], cts[2])
            else:
                # stage-0 backwards retire in increasing-mb order under
                # 1F1B — fold here so the accumulation order matches
                # accum_grads exactly.
                g = _assemble([stage_g.pop((ss, mb))
                               for ss in range(stages)], params)
                acc = jax.tree.map(
                    lambda a, gg: a if a is None
                    else a + gg.astype(jnp.float32) / n_mb,
                    acc, g, is_leaf=lambda x: x is None)
                acc_l = acc_l + loss_vals[mb] / n_mb

        for t in range(sched.ticks):
            for s in range(stages):
                unit = sched.grid[s][t]
                if unit is None:
                    continue
                (emit_f if unit[0] == F else emit_b)(s, unit[1])

        grads = jax.tree.map(
            lambda a: jnp.zeros((), jnp.float32) if a is None else a,
            acc, is_leaf=lambda x: x is None)
        return acc_l, metrics_by_mb[n_mb - 1], grads

    def grad_fn(params, batch):
        act_bytes = (batch["tokens"].shape[0] // n_mb
                     * batch["tokens"].shape[1] * cfg.d_model
                     * jnp.dtype(cfg.dtype).itemsize)
        comm_planner.plan_stage_transfers(mesh, cfg.moe.comm,
                                          msg_bytes=act_bytes)
        with comm_planner.pipeline_context(stages, n_mb,
                                           sched.bubble_fraction()), \
                obs_tracing.activate(cfg.moe.obs.phase_tracing):
            return _run(params, batch)

    return grad_fn


def make_pipeline_train_step(cfg: ModelConfig, opt_cfg: OptimizerConfig,
                             mesh, *, use_lsh: Optional[bool] = None):
    """1F1B train_step(state, batch) -> (state, metrics) for meshes with a
    pipe axis; the optimizer tail is shared with runtime/step."""
    from repro.runtime.step import (apply_chaos_scale, apply_gradients,
                                    split_chaos_scale)
    grad_fn = make_pipeline_grad_fn(cfg, mesh, use_lsh=use_lsh)

    def train_step(state, batch):
        batch, chaos_scale = split_chaos_scale(batch)
        with obs_tracing.activate(cfg.moe.obs.phase_tracing):
            l, metrics, grads = grad_fn(state.params, batch)
            l = apply_chaos_scale(l, chaos_scale)
            return apply_gradients(state, opt_cfg, l, metrics, grads)

    return train_step
