"""Production training launcher with fault tolerance.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
      --steps 200 --batch 8 --seq 128 --smoke --ckpt /tmp/run1

Features exercised end-to-end: checkpoint/restart (auto-resume from last
committed step), async checkpointing, NaN-skip, step watchdog, straggler
monitor, hot-expert rebalancing, preemption (SIGTERM -> checkpoint ->
exit 42), --auto-restart supervisor loop.

Fault tolerance (docs/resilience.md — every path below is chaos-tested
by tests/test_resilience.py):

 * ``--auto-restart`` supervises via ``resilience.supervisor``: child
   exits are CLASSIFIED — preemption (42) restarts for free, watchdog
   (43) / death-by-signal / crash restart under a rolling budget
   ($MAX_RESTARTS within $RESTART_WINDOW_S, exponential backoff + jitter
   from $RESTART_BACKOFF_S), usage errors (2) never restart.
 * checkpoints carry per-shard sha256 digests; a bit-flipped or
   truncated shard is detected at restore, quarantined
   (``checkpoint_corrupt`` event) and the run resumes from the previous
   committed step.  Failed async saves re-raise from the manager
   (``checkpoint_error`` event) instead of silently looking committed.
 * SIGKILL at an arbitrary step + ``--auto-restart`` resume produces a
   post-resume loss trajectory bitwise identical to an uninterrupted
   run (``ds.batch_at(step)`` is deterministic; the committed-step
   protocol restores exact bytes).
 * ``--chaos SPEC`` / ``$REPRO_CHAOS`` injects deterministic,
   step-addressed faults for rehearsal: ``nan_grads@k`` (grad-skip
   path), ``hang@k[:s]`` (watchdog bait), ``sigterm@k`` / ``sigkill@k``,
   ``ckpt_flip@k`` / ``ckpt_truncate@k`` (shard corruption),
   ``tune_corrupt@k``, ``data_stall@k[:s]``; ``seed=N`` seeds the
   bit-flip positions.  Each injection is a typed ``chaos`` event; with
   chaos off the compiled train step is byte-identical to a build
   without the chaos hook.

Observability (docs/observability.md): every line this launcher prints
is a structured event rendered by ``obs.events.ConsoleSink``;
``--metrics-dir DIR`` additionally turns on the in-graph metrics +
phase tracing (``ObsConfig``), appends every event to
``DIR/events.jsonl``, and writes ``DIR/trace.json`` (Chrome trace-event
JSON, loadable in Perfetto) plus ``DIR/metrics.json`` (live comm-ratio
summary) at exit.  ``--profile N`` captures a ``jax.profiler`` device
trace of the first N steps into ``DIR/jax_trace``, parses it into the
MEASURED per-phase timeline (``obs/profile.py``) and reconciles it
against the modeled attribution — ``model_drift`` events plus
``measured_*`` / ``model_*`` keys in metrics.json, with comm-phase
drift recorded into the tune cache as a stale-calibration signal
(``obs/reconcile.py``).  The rolling anomaly detectors
(``obs/anomaly.py``) watch step time / loss / comm share / load
imbalance / stragglers whenever ``--metrics-dir`` is on;
``--anomaly-exit`` escalates persistent degradation to exit 43 for the
supervisor.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import subprocess
import sys

import numpy as np


def supervise(argv) -> int:
    """--auto-restart: exit-code-aware relaunch loop
    (resilience.supervisor — preemptions restart for free, watchdog /
    crash exits restart under a rolling budget with backoff, usage
    errors don't restart)."""
    from repro.obs import events as obs_events
    from repro.obs import export as obs_export
    from repro.resilience import supervisor as sup
    log = obs_events.global_log()
    sinks = []
    if not log.active:
        sinks.append(log.add_sink(obs_events.ConsoleSink()))
    # restart decisions belong in the run's events.jsonl alongside the
    # child's events (the sink appends; child and supervisor interleave)
    metrics_dir = None
    for i, a in enumerate(argv):
        if a == "--metrics-dir" and i + 1 < len(argv):
            metrics_dir = argv[i + 1]
        elif a.startswith("--metrics-dir="):
            metrics_dir = a.split("=", 1)[1]
    jsonl = None
    if metrics_dir:
        os.makedirs(metrics_dir, exist_ok=True)
        jsonl = obs_events.JsonlSink(
            os.path.join(metrics_dir, obs_export.EVENTS_NAME))
        sinks.append(log.add_sink(jsonl))
    child_args = [a for a in argv if a != "--auto-restart"]

    def run_child() -> int:
        return subprocess.run([sys.executable, "-m", "repro.launch.train",
                               *child_args]).returncode

    try:
        return sup.supervise(run_child)
    finally:
        for s in sinks:
            log.remove_sink(s)
        if jsonl is not None:
            jsonl.close()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-sized)")
    ap.add_argument("--lsh", default=None, choices=("on", "off"))
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--watchdog-s", type=float, default=600.0)
    ap.add_argument("--straggler-factor", type=float, default=2.0,
                    help="flag a step as a straggler when it exceeds this "
                         "multiple of the EMA step time")
    ap.add_argument("--auto-restart", action="store_true")
    ap.add_argument("--chaos", default=os.environ.get("REPRO_CHAOS", ""),
                    help="deterministic fault-injection spec, e.g. "
                         "'nan_grads@3,sigkill@5,hang@7:2.5,seed=1' "
                         "(docs/resilience.md; also $REPRO_CHAOS)")
    ap.add_argument("--mesh-data", type=int, default=1,
                    help="data-axis extent of the training mesh")
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="model-axis extent of the training mesh (EP/TP "
                         "wire axis — needs --mesh-data*--mesh-model "
                         "devices)")
    ap.add_argument("--mesh-pipe", type=int, default=1,
                    help="pipeline-stage axis extent (1 = no pipe axis; "
                         ">1 runs the 1F1B schedule, docs/pipeline.md)")
    ap.add_argument("--pipeline-microbatches", type=int, default=0,
                    help="microbatches per step under --mesh-pipe > 1 "
                         "(0 = one per stage)")
    ap.add_argument("--node-size", type=int, default=0,
                    help="devices per node along the model axis "
                         "(0 = detect; docs/comm.md)")
    ap.add_argument("--autotune", action="store_true",
                    help="probe the mesh and fill the comm tuning cache "
                         "before step 0 (docs/tuning.md; needs a "
                         "multi-device --mesh-model to time transports); "
                         "also enables cache consultation for this run "
                         "unless $REPRO_TUNE is already set")
    ap.add_argument("--metrics-dir", default="",
                    help="write events.jsonl + trace.json (Perfetto) + "
                         "metrics.json here and enable the in-graph "
                         "metrics / phase tracing (docs/observability.md)")
    ap.add_argument("--profile", type=int, default=0,
                    help="capture a jax.profiler trace of N steady-state "
                         "steps (the compile step is skipped) into "
                         "<metrics-dir>/jax_trace, parse it "
                         "into the MEASURED per-phase timeline and "
                         "reconcile it against the modeled one "
                         "(docs/observability.md; requires --metrics-dir)")
    ap.add_argument("--anomaly-exit", action="store_true",
                    help="exit EXIT_WATCHDOG (43) when the anomaly "
                         "detectors see persistent degradation, handing "
                         "the restart decision to --auto-restart's "
                         "budgeted supervisor (docs/resilience.md)")
    args = ap.parse_args()
    if args.profile and not args.metrics_dir:
        ap.error("--profile requires --metrics-dir: the device trace and "
                 "its measured-timeline artifacts land under "
                 "<metrics-dir> (jax_trace/, metrics.json)")
    if args.auto_restart:
        return supervise(sys.argv[1:])

    import jax
    from jax.profiler import StepTraceAnnotation, TraceAnnotation
    from repro.checkpoint.checkpoint import CheckpointManager, load_checkpoint
    from repro.compat import set_mesh
    from repro.configs.base import OptimizerConfig
    from repro.configs.registry import get_config, get_smoke_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.obs import events as obs_events
    from repro.obs import export as obs_export
    from repro.obs import timeline as timeline_lib
    from repro.data.synthetic import SyntheticLMDataset
    from repro.runtime.fault import (EXIT_WATCHDOG, ExpertRebalancer,
                                     PreemptionHandler, StepWatchdog,
                                     StragglerMonitor)
    from repro.runtime.step import (TrainState, init_train_state,
                                    make_train_step)

    enable_compile_cache()
    log = obs_events.global_log()
    log.add_sink(obs_events.ConsoleSink())
    mem = obs_events.MemorySink()
    jsonl = None
    if args.metrics_dir:
        os.makedirs(args.metrics_dir, exist_ok=True)
        jsonl = obs_events.JsonlSink(
            os.path.join(args.metrics_dir, obs_export.EVENTS_NAME))
        log.add_sink(jsonl)
        log.add_sink(mem)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    opt = OptimizerConfig(lr=1e-3, warmup_steps=min(20, args.steps // 5),
                          total_steps=args.steps)
    if args.mesh_pipe > 1:
        cfg = cfg.replace(pipeline_microbatches=args.pipeline_microbatches)
    if args.metrics_dir:
        cfg = cfg.replace(moe=dataclasses.replace(
            cfg.moe, obs=dataclasses.replace(cfg.moe.obs, enabled=True)))
    n_mesh = args.mesh_data * args.mesh_pipe * args.mesh_model
    if len(jax.devices()) < n_mesh:
        obs_events.emit(
            "error", where="train",
            message=(f"mesh {args.mesh_data}x{args.mesh_pipe}x"
                     f"{args.mesh_model} needs {n_mesh} devices, have "
                     f"{len(jax.devices())} (force host devices via "
                     f"XLA_FLAGS)"))
        return 2
    mesh = make_host_mesh(args.mesh_data, args.mesh_pipe, args.mesh_model,
                          node_size=args.node_size)
    use_lsh = None if args.lsh is None else (args.lsh == "on")

    from repro.comm import planner as comm_planner
    from repro.tune import runtime as tune_runtime
    comm_cfg = cfg.moe.comm if cfg.has_moe() else None
    if args.autotune:
        # A fresh cache nobody consults is useless: make this run read it.
        os.environ.setdefault(tune_runtime.ENV_TUNE, "cache")
    if cfg.has_moe() and (args.autotune
                          or tune_runtime.tuning_mode(comm_cfg) == "probe"):
        calib = tune_runtime.ensure_calibrated(mesh, comm_cfg,
                                               probe=args.autotune)
        if calib is not None:
            obs_events.emit("tune_calibrated", fingerprint=calib.key)

    chaos = None
    if args.chaos:
        from repro.resilience.faults import STATE_NAME, FaultPlan
        try:
            chaos = FaultPlan.parse(args.chaos)
        except ValueError as exc:
            obs_events.emit("error", where="chaos", message=str(exc))
            return 2
        state_dir = args.ckpt or args.metrics_dir
        if state_dir:
            # fired-markers must survive the kills the plan itself causes
            os.makedirs(state_dir, exist_ok=True)
            chaos.bind_state(os.path.join(state_dir, STATE_NAME))
        obs_events.emit("chaos_plan", spec=chaos.describe())

    ds = SyntheticLMDataset(cfg.vocab_size, args.seq, args.batch,
                            num_shards=jax.process_count(),
                            shard=jax.process_index())
    preempt = PreemptionHandler()
    watchdog = StepWatchdog(args.watchdog_s)
    straggler = StragglerMonitor(threshold=args.straggler_factor)
    timeline = timeline_lib.StepTimeline()
    mgr = CheckpointManager(args.ckpt, keep=3) if args.ckpt else None
    monitor = None
    escalator = None
    if args.metrics_dir:
        from repro.obs import anomaly as anomaly_lib
        monitor = anomaly_lib.AnomalyMonitor()
        if args.anomaly_exit:
            from repro.resilience.supervisor import AnomalyEscalator
            escalator = AnomalyEscalator()
            monitor.add_consumer(escalator.consume)
    rebalancer = None
    placement = None
    if cfg.has_moe():
        rebalancer = ExpertRebalancer(cfg.moe.num_experts,
                                      mesh.shape.get("model", 1))
        # expert_load arrives in physical slot order; identity until a
        # proposed placement is applied (apply_placement_update)
        placement = np.arange(cfg.moe.num_experts, dtype=np.int32)

    n_mb = (cfg.pipeline_microbatches or args.mesh_pipe) \
        if args.mesh_pipe > 1 else 1
    stage_msg_bytes = 0
    if args.mesh_pipe > 1:
        stage_msg_bytes = (args.batch // max(1, n_mb)) * args.seq \
            * cfg.d_model * jax.numpy.dtype(cfg.dtype).itemsize

    step_hlo_text = None
    modeled_phase_s = None
    steps_profiled = 0
    profile_extra = {}
    profile_analyzed = False

    def analyze_profile():
        """Parse the captured device trace into the MEASURED timeline,
        reconcile it against the modeled phase split, emit model_drift
        events and (when a calibration is in play) record the stale
        signal into the tune cache.  Results land in ``profile_extra``
        for metrics.json."""
        nonlocal profile_analyzed
        if profiling or profile_analyzed or not steps_profiled:
            return
        profile_analyzed = True
        from repro.obs import profile as obs_profile
        from repro.obs import reconcile as obs_reconcile
        measured = obs_profile.parse_jax_trace(
            os.path.join(args.metrics_dir, "jax_trace"),
            hlo_text=step_hlo_text, steps=steps_profiled, n_devices=n_mesh)
        profile_extra.update(measured.summary())
        if not modeled_phase_s:
            return
        report = obs_reconcile.reconcile(modeled_phase_s,
                                         measured.phase_seconds)
        obs_reconcile.emit_drift_events(report)
        profile_extra.update(report.to_metrics())
        if cfg.has_moe() \
                and tune_runtime.tuning_mode(comm_cfg) != "off":
            entry = obs_reconcile.record_stale_calibration(
                mesh, comm_cfg, report)
            if entry is not None and report.stale:
                obs_events.emit("tune_stale", path=entry,
                                comm_drift=report.comm_drift,
                                drift_score=report.drift_score)

    def export_artifacts(final_metrics=None):
        if not args.metrics_dir:
            return
        analyze_profile()
        sched = None
        if args.mesh_pipe > 1:
            from repro.runtime.pipeline_schedule import build_1f1b
            sched = build_1f1b(args.mesh_pipe, n_mb)
        obs_export.write_chrome_trace(
            os.path.join(args.metrics_dir, obs_export.TRACE_NAME),
            timeline, mem.events, schedule=sched)
        extra = {}
        if final_metrics is not None:
            extra = {k: float(v) for k, v in final_metrics.items()
                     if np.ndim(v) == 0}
        extra.update(profile_extra)
        if monitor is not None:
            for det, n in monitor.counts().items():
                extra[f"anomaly_{det}"] = float(n)
        obs_export.write_metrics_json(
            os.path.join(args.metrics_dir, obs_export.METRICS_NAME),
            timeline, extra=extra)

    # The capture starts at the first STEADY-STATE step, not at process
    # start: tracing through init + the compile-dominated first step
    # floods the capture with host events (the CPU backend drops the
    # later device events we actually want) and would measure
    # compilation, not the step.
    profiling = False
    profile_done = False
    profile_requested = bool(args.profile and args.metrics_dir)

    def start_profile():
        nonlocal profiling
        jax.profiler.start_trace(os.path.join(args.metrics_dir, "jax_trace"))
        profiling = True

    def stop_profile():
        nonlocal profiling, profile_done
        if profiling:
            profiling = False
            jax.profiler.stop_trace()
        profile_done = True

    metrics = {}
    loss = float("nan")
    try:
        with set_mesh(mesh):
            state = init_train_state(jax.random.PRNGKey(0), cfg, opt, mesh)
            start = 0
            if mgr and mgr.latest_step() is not None:
                restored, start, _ = load_checkpoint(args.ckpt, state)
                state = TrainState(*restored)
                obs_events.emit("resume", from_step=start)
            step_fn = jax.jit(make_train_step(cfg, opt, mesh,
                                              use_lsh=use_lsh,
                                              microbatch=0))
            if profile_requested:
                # The compiled text's op_name metadata is what lets the
                # trace parser resolve CPU/GPU fusion names back to the
                # obs/ phase scopes (obs/profile.hlo_phase_map).
                step_hlo_text = step_fn.lower(
                    state, ds.batch_at(start)).compile().as_text()
            for s in range(start, args.steps):
                if profile_requested and not profiling and not profile_done \
                        and (s == start + 1
                             or args.steps - start == 1):
                    start_profile()
                # Host spans on the profiler's clock: a --profile trace
                # names each idle gap by them, as chipbench/trace.py does.
                with StepTraceAnnotation("train", step_num=s):
                    with TraceAnnotation("batch"):
                        batch = ds.batch_at(s)
                    watchdog.arm()
                    if chaos is not None:
                        # after arm(): a hang fault must trip the watchdog
                        chaos.on_step_start(s)
                        batch = chaos.chaos_batch(batch, s)
                    timeline.start(s)
                    with TraceAnnotation("dispatch"):
                        state, metrics = step_fn(state, batch)
                    with TraceAnnotation("wait"):
                        # blocks; completes the step
                        loss = float(metrics["loss"])
                    watchdog.disarm()
                    rec = timeline.stop(s)
                    dt = rec.duration
                    if s == start:
                        # The first step traced the real comm plan — derive
                        # the phase attribution weights from it (calibrated
                        # topology costs + analytic FLOPs).
                        modeled_phase_s = timeline_lib.model_phase_seconds(
                            cfg, mesh, batch=args.batch, seq=args.seq,
                            stage_msg_bytes=stage_msg_bytes)
                        timeline.set_phase_seconds(modeled_phase_s)
                    is_straggler = straggler.record(s, dt)
                    if is_straggler:
                        obs_events.emit("straggler", step=s, dt=dt,
                                        ema=straggler.ema,
                                        factor=args.straggler_factor,
                                        phases=rec.phase_seconds())
                    if monitor is not None:
                        signals = {"step_time": dt, "loss": loss,
                                   "comm_share": timeline.comm_share(),
                                   "straggler": 1.0 if is_straggler else 0.0}
                        if "obs_load_imbalance" in metrics:
                            signals["load_imbalance"] = float(
                                metrics["obs_load_imbalance"])
                        monitor.observe(s, signals)
                        if escalator is not None and escalator.should_exit:
                            # persistent degradation: make the run durable and
                            # hand the restart decision to the supervisor
                            if mgr:
                                mgr.save_async(s + 1, state)
                                mgr.wait()
                            stop_profile()
                            export_artifacts(metrics)
                            return EXIT_WATCHDOG
                    if rebalancer is not None:
                        rebalancer.record(np.asarray(metrics["expert_load"]),
                                          placement)
                    if s % args.log_every == 0:
                        comm = ""
                        if "comm_algorithm" in metrics:
                            comm = comm_planner.describe_comm_metrics(
                                int(metrics["comm_algorithm"]),
                                int(metrics["comm_degraded"]),
                                int(metrics["comm_calibrated"]),
                                int(metrics["comm_wire_format"]))
                        obs_events.emit(
                            "step", step=s, loss=loss,
                            ce=float(metrics["ce"]),
                            lr=float(metrics["lr"]), dt=dt,
                            skips=int(metrics["grad_skips"]), comm=comm,
                            comm_share=timeline.comm_share())
                    want_ckpt = mgr and (s + 1) % args.ckpt_every == 0
                    if preempt.requested.is_set():
                        if mgr:
                            mgr.save_async(s + 1, state)
                            mgr.wait()
                        obs_events.emit("preempt", step=s)
                        stop_profile()
                        export_artifacts(metrics)
                        return 42
                    if want_ckpt:
                        with TraceAnnotation("checkpoint"):
                            mgr.save_async(s + 1, state)
                    if chaos is not None:
                        chaos.on_step_end(s, manager=mgr, ckpt_dir=args.ckpt)
                # after the step span closes, so the trace keeps it
                if profiling:
                    steps_profiled += 1
                    if steps_profiled >= args.profile:
                        stop_profile()
            if mgr:
                with TraceAnnotation("checkpoint"):
                    mgr.save_async(args.steps, state)
                    mgr.wait()
        watchdog.stop()
        obs_events.emit("train_done", steps=args.steps, loss=loss,
                        comm_share=timeline.comm_share(),
                        mean_step_s=timeline.mean_step_seconds())
        stop_profile()
        export_artifacts(metrics)
        return 0
    finally:
        stop_profile()
        if jsonl is not None:
            log.remove_sink(jsonl)
            jsonl.close()
        log.remove_sink(mem)


if __name__ == "__main__":
    raise SystemExit(main())
