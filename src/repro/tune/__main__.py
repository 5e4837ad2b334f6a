"""CLI: probe the mesh and fill the tuning cache.

  PYTHONPATH=src python -m repro.tune --devices 8 --model 8 --node-size 2
  PYTHONPATH=src python -m repro.tune --ladder 65536,4194304 --iters 10

``--devices N`` forces N host platform devices — it MUST be applied
before jax first initializes, which is why this module parses args and
sets XLA_FLAGS before importing anything jax-touching (repro.tune's own
``__init__`` is lazy for the same reason).
"""
from __future__ import annotations

import argparse
import logging
import os


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.tune",
        description="calibrate the comm cost model from live-mesh probes")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host platform devices (0 = use existing)")
    ap.add_argument("--data", type=int, default=1,
                    help="data-axis extent of the probe mesh")
    ap.add_argument("--model", type=int, default=0,
                    help="model-axis extent (0 = all remaining devices)")
    ap.add_argument("--node-size", type=int, default=0,
                    help="devices per node along the model axis "
                         "(0 = detect; see docs/comm.md)")
    ap.add_argument("--ladder", default="",
                    help="comma-separated per-rank message sizes in bytes "
                         "(default 64KiB,512KiB,4MiB)")
    ap.add_argument("--wire-formats", default="bf16,int8",
                    help="comma-separated wire formats to probe")
    ap.add_argument("--chunks", default="2,4",
                    help="comma-separated pipelined chunk candidates")
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--cache-dir", default="",
                    help="override $REPRO_TUNE_CACHE for this run")
    ap.add_argument("--no-store", action="store_true",
                    help="probe and report without writing the cache")
    ap.add_argument("--metrics-dir", default="",
                    help="also write structured events (events.jsonl) here")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", "")).strip()
    if args.cache_dir:
        os.environ["REPRO_TUNE_CACHE"] = args.cache_dir
    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="%(levelname)s %(name)s: %(message)s")

    import jax                            # first jax touch — after XLA_FLAGS

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.obs import events as obs_events
    from repro.obs import export as obs_export
    from repro.tune.autotune import DEFAULT_LADDER, autotune

    enable_compile_cache()
    log = obs_events.global_log()
    log.add_sink(obs_events.ConsoleSink())
    jsonl = None
    if args.metrics_dir:
        os.makedirs(args.metrics_dir, exist_ok=True)
        jsonl = obs_events.JsonlSink(
            os.path.join(args.metrics_dir, obs_export.EVENTS_NAME))
        log.add_sink(jsonl)
    try:
        n = len(jax.devices())
        model = args.model or max(1, n // max(1, args.data))
        if args.data * model > n:
            obs_events.emit(
                "error", where="tune",
                message=(f"mesh {args.data}x{model} needs "
                         f"{args.data * model} devices, have {n}"))
            return 2
        mesh = make_host_mesh(args.data, 1, model, node_size=args.node_size)
        ladder = tuple(int(b) for b in args.ladder.split(",") if b) \
            or DEFAULT_LADDER
        choices = autotune(
            mesh, axis_name="model", ladder=ladder,
            wire_formats=tuple(f for f in args.wire_formats.split(",")
                               if f),
            chunk_candidates=tuple(int(k) for k in args.chunks.split(",")
                                   if k),
            warmup=args.warmup, iters=args.iters, store=not args.no_store,
            verbose=args.verbose)
        print(choices.describe())
        return 0
    finally:
        if jsonl is not None:
            log.remove_sink(jsonl)
            jsonl.close()


if __name__ == "__main__":
    raise SystemExit(main())
