"""Perf-regression bench harness: train + serve smoke runs -> schema'd
``BENCH_<name>.json`` trajectory rows -> CI regression gate.

  PYTHONPATH=src:. python -m benchmarks.bench --out artifacts/bench \
      --steps 8 --gate

Each invocation appends one row per config to its trajectory file
(``repro.obs.benchrow`` owns the schema) and, with ``--gate``, compares
the new row against the median of the file's previous rows — exit 1 on
regression past the tolerant per-metric thresholds.  Rows carry:

 * ``mean_step_s`` / ``tokens_per_s_device`` — the gated throughput pair;
 * ``comm_share_modeled`` — the live fig3 attribution (planner message
   sizes through the — possibly calibrated — topology cost model);
 * ``comm_share_measured`` + per-phase ``model_err_*`` — ONLY when
   ``--profile`` captured a device trace (obs/profile.py);
 * ``compression_rate`` — the live Eq. 5 wire/raw byte ratio from the
   in-graph counters;
 * serve rows: p50/p99 latency + tokens/sec/device via the same schema
   (``launch/serve.py --bench-json`` writes the identical row shape).

Drift metrics ride along but are never gated: on CPU runners the
analytic model prices a TPU, so model error is structural
(docs/observability.md).  Comm-leg metrics are skipped with a logged
reason on 1-device runs — there is no wire to measure.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time


def _train_smoke(args) -> dict:
    """Train the tiny MoE config with obs enabled; returns bench metrics."""
    import jax
    from benchmarks.common import tiny_moe_config
    from repro.compat import set_mesh
    from repro.configs.base import OptimizerConfig
    from repro.data.synthetic import SyntheticLMDataset
    from repro.launch.mesh import make_host_mesh
    from repro.obs import timeline as timeline_lib
    from repro.runtime.step import init_train_state, make_train_step

    n_model = 2 if len(jax.devices()) >= 2 else 1
    mesh = make_host_mesh(1, 1, n_model)
    cfg = tiny_moe_config()
    cfg = cfg.replace(moe=dataclasses.replace(
        cfg.moe, obs=dataclasses.replace(cfg.moe.obs, enabled=True)))
    opt = OptimizerConfig(lr=1e-3, warmup_steps=2, total_steps=args.steps)
    ds = SyntheticLMDataset(cfg.vocab_size, args.seq, args.batch)
    timeline = timeline_lib.StepTimeline()
    metrics = {}
    profiling = False
    steps_profiled = 0
    hlo_text = None
    trace_dir = os.path.join(args.out, "jax_trace")
    with set_mesh(mesh):
        state = init_train_state(jax.random.PRNGKey(0), cfg, opt, mesh)
        step_fn = jax.jit(make_train_step(cfg, opt, mesh))
        if args.profile:
            try:
                hlo_text = step_fn.lower(
                    state, ds.batch_at(0)).compile().as_text()
            except Exception as exc:
                print(f"bench: HLO capture failed ({exc})", file=sys.stderr)
        for s in range(args.steps):
            if args.profile and s == 1 and not profiling:
                try:
                    jax.profiler.start_trace(trace_dir)
                    profiling = True
                except Exception as exc:
                    print(f"bench: profiler unavailable ({exc})",
                          file=sys.stderr)
            timeline.start(s)
            state, metrics = step_fn(state, ds.batch_at(s))
            loss = float(metrics["loss"])
            timeline.stop(s)
            if s == 0:
                timeline.set_phase_seconds(
                    timeline_lib.model_phase_seconds(
                        cfg, mesh, batch=args.batch, seq=args.seq))
            if profiling:
                steps_profiled += 1
                if steps_profiled >= args.profile:
                    jax.profiler.stop_trace()
                    profiling = False
    if profiling:
        jax.profiler.stop_trace()

    # steady-state step time: drop the compile-dominated first record
    recs = timeline.records[1:] or timeline.records
    mean_step = sum(r.duration for r in recs) / len(recs)
    tokens = args.batch * args.seq
    n_dev = mesh.devices.size
    out = {
        "mean_step_s": mean_step,
        "tokens_per_s_device": tokens / mean_step / n_dev,
        "final_loss": loss,
        "comm_share_modeled": timeline.comm_share(),
        "steps": float(args.steps),
    }
    if "obs_compression_rate" in metrics:
        out["compression_rate"] = float(metrics["obs_compression_rate"])
    if n_model < 2:
        print("bench: skipping comm-leg metrics — 1-device runner has "
              "no wire to measure", file=sys.stderr)
    if steps_profiled:
        from repro.obs import profile as obs_profile
        from repro.obs import reconcile as obs_reconcile
        try:
            measured = obs_profile.parse_jax_trace(
                trace_dir, hlo_text=hlo_text, steps=steps_profiled,
                n_devices=n_dev)
            out["comm_share_measured"] = measured.comm_share()
            out["measured_step_s"] = measured.step_seconds()
            modeled = timeline_lib.model_phase_seconds(
                cfg, mesh, batch=args.batch, seq=args.seq)
            report = obs_reconcile.reconcile(modeled,
                                             measured.phase_seconds)
            for k, v in report.to_metrics().items():
                out[k] = v
        except Exception as exc:
            print(f"bench: trace parse failed ({exc})", file=sys.stderr)
    return out


def _serve_smoke(args) -> str:
    """Run the serve launcher in-process; it appends its own bench row
    (the shared obs/benchrow schema).  Returns the trajectory path."""
    from repro.launch import serve
    from repro.obs import benchrow
    rc = serve.main([
        "--arch", args.serve_arch, "--smoke",
        "--requests", str(args.requests), "--gen", str(args.gen),
        "--bench-json", args.out, "--bench-name", "serve_smoke"])
    if rc != 0:
        raise RuntimeError(f"serve smoke exited {rc}")
    return benchrow.bench_file(args.out, "serve_smoke")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join("artifacts", "bench"),
                    help="directory for BENCH_<name>.json trajectories")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--profile", type=int, default=0,
                    help="capture N steady-state steps with jax.profiler "
                         "and add measured comm share + model error to "
                         "the train row")
    ap.add_argument("--serve", action="store_true",
                    help="also run the serve smoke (launch/serve.py "
                         "writes the row)")
    ap.add_argument("--serve-arch", default="smollm-360m")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--gate", action="store_true",
                    help="exit 1 when the new row regresses past the "
                         "gated thresholds vs the trajectory median")
    args = ap.parse_args()

    from repro.obs import benchrow
    os.makedirs(args.out, exist_ok=True)
    t0 = time.time()
    paths = []

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    train_metrics = _train_smoke(args)
    row = benchrow.bench_row(
        name="train_smoke", kind="train", metrics=train_metrics,
        context={"steps": args.steps, "batch": args.batch,
                 "seq": args.seq, "devices": len(jax.devices()),
                 "profile": args.profile})
    paths.append(benchrow.append_row(args.out, row))

    if args.serve:
        paths.append(_serve_smoke(args))

    failed = False
    for path in paths:
        cmp_ = benchrow.compare(benchrow.load_rows(path))
        print(cmp_.describe())
        if args.gate and not cmp_.ok:
            failed = True
    print(f"bench: wrote {len(paths)} trajectory file(s) to {args.out} "
          f"in {time.time() - t0:.1f}s")
    if failed:
        print("bench: REGRESSION GATE FAILED", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
