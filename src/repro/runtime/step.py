"""Train / serve step builders (jit-able, mesh-aware)."""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import ModelConfig, OptimizerConfig
from repro.models import model as model_lib
from repro.obs import tracing as obs_tracing
from repro.optim.adam import OptState, adamw_init, adamw_update
from repro.optim.schedule import warmup_cosine


class TrainState(NamedTuple):
    params: Any
    opt: OptState


# Fault injection (resilience.faults): a chaos run attaches this scalar to
# the batch dict; the train step multiplies the loss by it BEFORE the
# non-finite skip check in apply_gradients, so injecting NaN exercises the
# real grad-skip recovery path end to end.  Multiplying by the normal 1.0
# is an IEEE identity (bitwise no-op), and when the key is absent —
# every non-chaos run — the traced program is byte-identical to a build
# without this hook (tests/test_resilience.py pins both).
CHAOS_LOSS_SCALE_KEY = "_chaos_loss_scale"


def split_chaos_scale(batch: Dict) -> Tuple[Dict, Optional[Any]]:
    """Pop the fault-injection loss scale off the batch (None when chaos
    is off — the batch object passes through untouched)."""
    if CHAOS_LOSS_SCALE_KEY not in batch:
        return batch, None
    batch = dict(batch)
    return batch, batch.pop(CHAOS_LOSS_SCALE_KEY)


def apply_chaos_scale(l, scale):
    """Scale the loss used for the skip decision.  Gradients are left
    untouched: the only injected values are 1.0 (identity) and NaN (the
    skip discards the gradients entirely)."""
    if scale is None:
        return l
    return l * jnp.asarray(scale, l.dtype)


def init_train_state(key, cfg: ModelConfig, opt_cfg: OptimizerConfig,
                     mesh: Mesh) -> TrainState:
    params = model_lib.init_params(key, cfg, mesh)
    return TrainState(params, adamw_init(params, opt_cfg))


def apply_gradients(state: TrainState, opt_cfg: OptimizerConfig, l, metrics,
                    grads) -> Tuple[TrainState, Dict]:
    """Shared optimizer tail (lr schedule, NaN-skip, adamw) — used by the
    monolithic step below and the 1F1B pipeline step
    (runtime/pipeline_schedule.py)."""
    with obs_tracing.phase_scope(obs_tracing.PH_OPTIMIZER):
        lr = warmup_cosine(state.opt.step, opt_cfg.lr, opt_cfg.warmup_steps,
                           opt_cfg.total_steps)
        skip = ~jnp.isfinite(l)
        new_params, new_opt = adamw_update(state.params, grads, state.opt,
                                           opt_cfg, lr, skip=skip)
    metrics = dict(metrics, lr=lr, grad_skips=new_opt.grad_skips)
    return TrainState(new_params, new_opt), metrics


def make_accum_grad_fn(cfg: ModelConfig, mesh: Mesh, *,
                       use_lsh: Optional[bool] = None, microbatch: int = 0):
    """accum_grads(params, batch) -> (loss, metrics, grads): monolithic
    (unstaged) forward/backward, with lax.scan gradient accumulation when
    microbatch > 0 — the numerics reference the pipeline schedule must
    match bit for bit (tests/test_pipeline.py)."""

    def loss(params, batch):
        return model_lib.loss_fn(params, cfg, mesh, batch, use_lsh=use_lsh)

    grad_fn = jax.value_and_grad(loss, has_aux=True, allow_int=True)

    def accum_grads(params, batch):
        with obs_tracing.activate(cfg.moe.obs.phase_tracing):
            return _accum_grads(params, batch)

    def _accum_grads(params, batch):
        if not microbatch:
            (l, metrics), grads = grad_fn(params, batch)
            return l, metrics, grads
        B = batch["tokens"].shape[0]
        n = B // microbatch
        from repro.runtime.sharding import constrain
        mb = jax.tree.map(
            lambda x: constrain(x.reshape((n, microbatch) + x.shape[1:]),
                                mesh, None, "batch",
                                *([None] * (x.ndim - 1))), batch)

        def body(carry, b):
            b = jax.tree.map(
                lambda x: constrain(x, mesh, "batch",
                                    *([None] * (x.ndim - 1))), b)
            (l, metrics), grads = grad_fn(params, b)
            acc_l, acc_g = carry
            acc_g = jax.tree.map(
                lambda a, g: a if g.dtype == jax.dtypes.float0
                else a + g.astype(jnp.float32) / n, acc_g, grads)
            return (acc_l + l / n, acc_g), metrics

        zero_g = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32)
            if jnp.issubdtype(p.dtype, jnp.floating) else
            jnp.zeros((), jnp.float32), params)
        (l, grads), metrics = jax.lax.scan(
            lambda c, b: body(c, b), (jnp.zeros((), jnp.float32), zero_g), mb)
        metrics = jax.tree.map(lambda m: m[-1], metrics)
        return l, metrics, grads

    return accum_grads


def make_train_step(cfg: ModelConfig, opt_cfg: OptimizerConfig, mesh: Mesh,
                    *, use_lsh: Optional[bool] = None, microbatch: int = 0):
    """Returns train_step(state, batch) -> (state, metrics).

    microbatch > 0: gradient accumulation over batch splits via lax.scan
    (sequential re-use of the same activation memory).

    A mesh with a ``pipe`` axis of size > 1 dispatches to the 1F1B
    pipeline schedule (runtime/pipeline_schedule.py) — bit-identical
    numerics, stage-partitioned stack, a2a planned into the bubbles.

    cfg.dp_only: pure data parallelism — the whole fwd/bwd runs LOCALLY
    inside one shard_map over every mesh axis (params replicated), with a
    single bf16 gradient pmean at the end.  This is the right profile for
    sub-1B models on a 256-chip mesh: GSPMD TP otherwise inserts per-scan-
    step weight-grad all-reduces (recurrent layers) and activation
    exchanges that dwarf the compute."""
    if mesh is not None and "pipe" in mesh.axis_names \
            and int(mesh.shape["pipe"]) > 1:
        if cfg.dp_only:
            raise NotImplementedError(
                "dp_only and a pipe axis are mutually exclusive profiles")
        from repro.runtime.pipeline_schedule import make_pipeline_train_step
        return make_pipeline_train_step(cfg, opt_cfg, mesh, use_lsh=use_lsh)
    if cfg.dp_only and mesh.devices.size > 1:
        return _make_dp_only_train_step(cfg, opt_cfg, mesh, use_lsh=use_lsh)

    accum_grads = make_accum_grad_fn(cfg, mesh, use_lsh=use_lsh,
                                     microbatch=microbatch)

    def train_step(state: TrainState, batch: Dict) -> Tuple[TrainState, Dict]:
        batch, chaos_scale = split_chaos_scale(batch)
        with obs_tracing.activate(cfg.moe.obs.phase_tracing):
            l, metrics, grads = accum_grads(state.params, batch)
            l = apply_chaos_scale(l, chaos_scale)
            return apply_gradients(state, opt_cfg, l, metrics, grads)

    return train_step


def _make_dp_only_train_step(cfg: ModelConfig, opt_cfg: OptimizerConfig,
                             mesh: Mesh, *, use_lsh: Optional[bool]):
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map
    all_axes = tuple(mesh.axis_names)

    def loss_local(params, batch):
        # mesh=None => all sharding constraints no-op: purely local compute
        return model_lib.loss_fn(params, cfg, None, batch, use_lsh=use_lsh)

    grad_fn = jax.value_and_grad(loss_local, has_aux=True, allow_int=True)

    def local_step(params, batch):
        (l, metrics), grads = grad_fn(params, batch)
        grads = jax.tree.map(
            lambda g: g if g.dtype == jax.dtypes.float0
            else jax.lax.pmean(g, all_axes), grads)
        l = jax.lax.pmean(l, all_axes)
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, all_axes), metrics)
        return l, metrics, grads

    def train_step(state: TrainState, batch: Dict) -> Tuple[TrainState, Dict]:
        batch, chaos_scale = split_chaos_scale(batch)
        # shard batch over as many axes as divide evenly (trim from the
        # right: 256 rows on a 512-chip multi-pod mesh shards over
        # (pod, data) and replicates over model — pmean stays correct)
        def bspec_for(v):
            axes = list(all_axes)
            while axes:
                n = 1
                for a in axes:
                    n *= mesh.shape[a]
                if v.shape[0] % n == 0:
                    break
                axes.pop()
            lead = tuple(axes) if len(axes) > 1 else (axes[0] if axes
                                                      else None)
            return P(lead, *([None] * (v.ndim - 1)))

        bspec = {k: bspec_for(v) for k, v in batch.items()}
        rep = jax.tree.map(lambda _: P(), state.params)
        with obs_tracing.activate(cfg.moe.obs.phase_tracing):
            l, metrics, grads = shard_map(
                local_step, mesh=mesh, in_specs=(rep, bspec),
                out_specs=(P(), P(), P()))(state.params, batch)
            l = apply_chaos_scale(l, chaos_scale)
            return apply_gradients(state, opt_cfg, l, metrics, grads)

    return train_step


def make_prefill_step(cfg: ModelConfig, mesh: Mesh):
    def prefill_step(params, batch):
        return model_lib.prefill(params, cfg, mesh, batch)
    return prefill_step


def make_decode_step(cfg: ModelConfig, mesh: Mesh):
    def decode_step(params, state, tokens):
        return model_lib.decode_step(params, cfg, mesh, state, tokens)
    return decode_step
