"""Host-side step timeline: per-phase wall-time attribution, a live
comm-ratio estimate, and 1F1B grid reconstruction.

Device steps are opaque to host timers — one ``block_until_ready`` wall
interval per step is all the host sees.  ``StepTimeline`` splits that
measured interval across the MoE phases proportionally to a modeled
per-phase cost (``model_phase_seconds``: analytic FLOP counts for the
compute phases, the comm planner's — possibly probe-calibrated —
topology cost model for the a2a legs), so the phase spans tile the step
exactly (coverage is 100% of measured wall time by construction) and
their relative sizes are the cost model's.  The comm share of that
attribution is the LIVE counterpart of the paper's fig3 measurement: the
same ratio ``benchmarks/fig3_comm_ratio.py`` computes offline from
Eq. 6, but fed the planner's actual message sizes and (when tuned)
measured link constants, and multiplied into real step seconds.

For pipe>1 meshes, ``reconstruct_grid`` lays the 1F1B timetable
(``runtime/pipeline_schedule.build_1f1b``) over the measured step
interval — per-(stage, microbatch) F/B unit spans plus one a2a marker
per unit at ``Schedule.a2a_slot``, classified ``bubble`` (the slot is an
idle tick: the exchange hid in a bubble), ``overlap`` (the slot computes
a DIFFERENT microbatch: hidden behind compute), or ``cold_start`` (the
pipeline's very first unit — nothing to hide behind).  The classification
is pure schedule arithmetic, so it matches ``Schedule.a2a_slot`` exactly
(tests/test_obs.py pins it).

Everything here is host-side; nothing touches a trace.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

# Bare phase names (obs/tracing.py's PH_* minus the prefix), in execution
# order, plus the residual bucket.
PHASE_ORDER = ("gate", "hash_compress", "dispatch_a2a", "expert_mlp",
               "combine_a2a", "decompress", "stage_transfer", "other")
COMM_PHASES = ("dispatch_a2a", "combine_a2a", "stage_transfer")

# Default device throughput for the analytic compute model: the TPU v5e's
# published bf16 peak (repro.hw), whatever host runs the model.
from repro import hw

DEVICE_FLOPS = hw.peaks(hw.V5E).flops


@dataclass(frozen=True)
class PhaseSpan:
    name: str
    start: float                        # host wall-clock seconds
    duration: float


@dataclass(frozen=True)
class StepRecord:
    step: int
    start: float
    duration: float
    spans: Tuple[PhaseSpan, ...]

    def phase_seconds(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for sp in self.spans:
            out[sp.name] = out.get(sp.name, 0.0) + sp.duration
        return out


# ----------------------------------------------- modeled phase weights ----


def model_phase_seconds(cfg, mesh, *, batch: int, seq: int,
                        device_flops: float = DEVICE_FLOPS,
                        stage_msg_bytes: int = 0) -> Dict[str, float]:
    """Modeled absolute seconds per phase for one train step of ``cfg``
    on ``mesh`` — the attribution weights ``StepTimeline`` scales into
    each measured step.

    Compute phases price analytic FLOPs (6 * active params * tokens, the
    fig3 convention) against ``device_flops``; the a2a legs price the
    TRUE wire bytes (clustering.wire_bytes — scales sidecar included)
    through the planner's topology cost model, calibrated when a tuning
    cache entry matched (``CommPlan.wire_cost``).  Call after the first
    step so ``comm.planner.last_plan()`` reflects the traced step."""
    import jax.numpy as jnp
    from repro.comm import planner as comm_planner
    from repro.comm import topology as topo_lib
    from repro.configs.base import MOE, active_param_count
    from repro.core import clustering
    from repro.core.moe import (expert_capacity, num_lsh_slots,
                                padded_num_experts)
    from repro.runtime.sharding import axis_size, dp_axes

    n_dev = max(1, math.prod(int(mesh.shape[a]) for a in mesh.axis_names)) \
        if mesh is not None else 1
    tokens = batch * seq
    total_s = 6.0 * active_param_count(cfg) * tokens / (device_flops * n_dev)
    out = {name: 0.0 for name in PHASE_ORDER}

    n_moe = sum(1 for _, f in cfg.layout if f == MOE) * cfg.num_super_blocks
    if n_moe and cfg.moe.num_experts:
        moe, h = cfg.moe, cfg.d_model
        model_r = axis_size(mesh, "model") if mesh is not None else 1
        dp = dp_axes(mesh) if mesh is not None else ()
        n_dp = max(1, math.prod(axis_size(mesh, a) for a in dp)) \
            if mesh is not None else 1
        e_pad = padded_num_experts(moe.num_experts, mesh) \
            if mesh is not None else moe.num_experts
        t_loc = max(1, (batch // n_dp) * (seq // max(1, model_r)))
        capacity = expert_capacity(t_loc, e_pad, moe.top_k,
                                   moe.capacity_factor)
        use_lsh = moe.lsh.enabled
        c_wire = num_lsh_slots(capacity, moe.lsh.compression_rate) \
            if use_lsh else capacity
        wire_fmt = moe.lsh.wire_format if use_lsh else None
        wire_dtype = jnp.dtype(moe.lsh.wire_dtype) if use_lsh \
            else jnp.dtype(cfg.dtype)
        msg = clustering.wire_bytes(e_pad, c_wire, h, wire_fmt,
                                    wire_dtype=wire_dtype)
        plan = comm_planner.last_plan("model")
        if plan is None:
            plan = comm_planner.plan_collectives(
                mesh, moe.comm, axis_name="model", msg_bytes=msg,
                chunk_extent=c_wire)
        leg_s = topo_lib.estimate_seconds(plan.wire_cost(msg))
        out["dispatch_a2a"] = leg_s * n_moe
        out["combine_a2a"] = leg_s * n_moe

        # Analytic FLOPs of the per-token MoE phases (fig3's 6*params
        # convention for matmuls; elementwise phases are 2-flop/element).
        flops = device_flops * n_dev
        n_mat = 3 if cfg.mlp_act == "swiglu" else 2
        out["gate"] = 2.0 * tokens * h * moe.num_experts * n_moe / flops
        if use_lsh:
            rot = 2.0 * tokens * moe.top_k * h * moe.lsh.rotation_dim \
                * moe.lsh.num_hashes
            out["hash_compress"] = rot * n_moe / flops
            out["decompress"] = 2.0 * tokens * moe.top_k * h * n_moe / flops
        out["expert_mlp"] = (2.0 * tokens * moe.top_k
                             * n_mat * h * moe.expert_ffn_dim
                             * n_moe / flops)

    pipe_r = int(mesh.shape.get("pipe", 1)) if mesh is not None else 1
    if pipe_r > 1 and stage_msg_bytes:
        plan = comm_planner.last_plan("pipe")
        topo = plan.topology if plan is not None else topo_lib.build_topology(
            mesh, axis_name="pipe")
        hop = topo_lib.estimate_seconds(
            topo_lib.stage_transfer_cost(topo, stage_msg_bytes))
        out["stage_transfer"] = hop * (pipe_r - 1)

    spent = sum(v for k, v in out.items()
                if k not in COMM_PHASES and k != "other")
    out["other"] = max(0.0, total_s - spent)
    return out


def comm_share(phase_seconds: Dict[str, float]) -> float:
    """Comm fraction of the modeled step — the live fig3 number.  Equals
    ``benchmarks.common.a2a_share_from_ratio(r)`` for r = comm/compute."""
    total = sum(phase_seconds.values())
    if total <= 0.0:
        return 0.0
    return sum(phase_seconds.get(p, 0.0) for p in COMM_PHASES) / total


# ------------------------------------------------------------- timeline ---


class StepTimeline:
    """Start/stop bracket around each host step; attribution happens at
    ``stop`` using the current phase weights (re-settable once the first
    traced step has resolved its comm plan)."""

    def __init__(self, phase_seconds: Optional[Dict[str, float]] = None,
                 clock=time.perf_counter, wall=time.time):
        self._weights: Optional[Dict[str, float]] = None
        self._clock = clock
        self._wall = wall
        self._t0: Optional[float] = None
        self._w0: Optional[float] = None
        self._step: Optional[int] = None
        self.records: List[StepRecord] = []
        if phase_seconds:
            self.set_phase_seconds(phase_seconds)

    def set_phase_seconds(self, phase_seconds: Dict[str, float]) -> None:
        total = sum(max(0.0, v) for v in phase_seconds.values())
        if total <= 0.0:
            self._weights = None
            return
        self._weights = {k: max(0.0, v) / total
                         for k, v in phase_seconds.items() if v > 0.0}

    @property
    def weights(self) -> Optional[Dict[str, float]]:
        return self._weights

    def start(self, step: int) -> None:
        self._step = step
        self._t0 = self._clock()
        self._w0 = self._wall()

    def stop(self, step: Optional[int] = None) -> StepRecord:
        if self._t0 is None:
            raise RuntimeError("StepTimeline.stop() without start()")
        dt = max(1e-9, self._clock() - self._t0)
        start = self._w0
        step = self._step if step is None else step
        spans: List[PhaseSpan] = []
        if self._weights:
            t = start
            ordered = [p for p in PHASE_ORDER if p in self._weights]
            ordered += [p for p in self._weights if p not in PHASE_ORDER]
            for name in ordered:
                d = self._weights[name] * dt
                spans.append(PhaseSpan(name, t, d))
                t += d
        else:
            spans.append(PhaseSpan("step", start, dt))
        rec = StepRecord(step=int(step or 0), start=start, duration=dt,
                         spans=tuple(spans))
        self.records.append(rec)
        self._t0 = self._w0 = self._step = None
        return rec

    def comm_share(self) -> float:
        return comm_share(self._weights or {})

    def comm_seconds(self) -> float:
        """Estimated comm seconds across all recorded steps (share x
        measured wall time — the live-rate counterpart of fig3)."""
        return self.comm_share() * sum(r.duration for r in self.records)

    def mean_step_seconds(self) -> float:
        if not self.records:
            return 0.0
        return sum(r.duration for r in self.records) / len(self.records)

    def summary(self) -> Dict[str, float]:
        out: Dict[str, float] = {
            "steps": float(len(self.records)),
            "mean_step_s": self.mean_step_seconds(),
            "comm_share": self.comm_share(),
            "comm_s": self.comm_seconds(),
        }
        if self._weights:
            for name, w in sorted(self._weights.items()):
                out[f"weight_{name}"] = w
        return out


# ------------------------------------------------- 1F1B reconstruction ----

A2A_BUBBLE = "bubble"                   # slot is an idle tick: hit
A2A_OVERLAP = "overlap"                 # slot computes another microbatch
A2A_COLD_START = "cold_start"           # first unit: nothing to hide behind


@dataclass(frozen=True)
class A2ASlot:
    stage: int
    microbatch: int
    tick: int                           # Schedule.a2a_slot(stage, mb)
    status: str                         # A2A_BUBBLE | A2A_OVERLAP | ...

    @property
    def hidden(self) -> bool:
        return self.status in (A2A_BUBBLE, A2A_OVERLAP)


def classify_a2a(sched) -> List[A2ASlot]:
    """One record per (stage, microbatch) forward unit, classifying the
    tick ``Schedule.a2a_slot`` assigns its MoE exchange to.  By the
    schedule's contract the slot is never the unit's own tick, so the
    only statuses are bubble / other-microbatch-overlap / cold-start."""
    out = []
    for s in range(sched.stages):
        for mb in range(sched.microbatches):
            t = sched.a2a_slot(s, mb)
            if t < 0:
                status = A2A_COLD_START
            elif sched.grid[s][t] is None:
                status = A2A_BUBBLE
            else:
                status = A2A_OVERLAP
            out.append(A2ASlot(s, mb, t, status))
    return out


@dataclass(frozen=True)
class PipelineUnit:
    stage: int
    tick: int
    phase: str                          # "F" | "B"
    microbatch: int
    start: float
    duration: float


def reconstruct_grid(sched, start: float, duration: float
                     ) -> List[PipelineUnit]:
    """Lay the 1F1B timetable over a measured step interval: every
    (stage, tick) unit becomes a span of one tick's width.  Ticks are
    uniform — the reconstruction shows the schedule's shape (bubbles,
    warmup/cooldown ramps) at the measured step's scale, not per-tick
    device timings (invisible to the host)."""
    tick_s = duration / max(1, sched.ticks)
    units = []
    for s in range(sched.stages):
        for t, unit in enumerate(sched.grid[s]):
            if unit is None:
                continue
            ph, mb = unit
            units.append(PipelineUnit(stage=s, tick=t, phase=ph,
                                      microbatch=mb,
                                      start=start + t * tick_s,
                                      duration=tick_s))
    return units
