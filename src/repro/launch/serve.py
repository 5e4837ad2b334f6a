"""Batched serving loop: prefill + decode with continuous batching slots.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
      --requests 8 --gen 16

Observability: per-request arrival -> completion latency (arrival = when
the request joined the closed backlog at t0, so latency INCLUDES queueing
behind earlier batches), p50/p99 latency and tokens/sec(/device) in the
final ``serve_summary`` event; ``--metrics-dir DIR`` appends all events
to ``DIR/events.jsonl`` (docs/observability.md).
"""
from __future__ import annotations

import argparse
import os
import time


def _percentile(sorted_vals, q: float) -> float:
    """Nearest-rank percentile on an already-sorted list."""
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1,
            max(0, int(round(q / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[i]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--metrics-dir", default="",
                    help="also write structured events (events.jsonl) here")
    ap.add_argument("--bench-json", default="",
                    help="append a schema'd serve bench row (p50/p99 "
                         "latency, tokens/sec/device) to "
                         "BENCH_<name>.json in this directory "
                         "(obs/benchrow.py; the CI regression gate's "
                         "input)")
    ap.add_argument("--bench-name", default="serve_smoke",
                    help="trajectory name for --bench-json")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from repro.compat import set_mesh
    from repro.configs.registry import get_config, get_smoke_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as model_lib
    from repro.obs import events as obs_events
    from repro.obs import export as obs_export

    enable_compile_cache()
    log = obs_events.global_log()
    log.add_sink(obs_events.ConsoleSink())
    jsonl = None
    if args.metrics_dir:
        os.makedirs(args.metrics_dir, exist_ok=True)
        jsonl = obs_events.JsonlSink(
            os.path.join(args.metrics_dir, obs_export.EVENTS_NAME))
        log.add_sink(jsonl)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_host_mesh(1, 1, 1)
    B = args.batch_slots
    max_len = args.prompt_len + args.gen
    n_dev = max(1, len(jax.devices()))

    try:
        with set_mesh(mesh):
            # one program, so each weight is drawn straight into its dtype:
            # eagerly, granite's 32-layer expert stacks would each pass
            # through two 3.75 GiB f32 temporaries
            params = jax.jit(lambda k: model_lib.init_params(k, cfg, mesh))(
                jax.random.PRNGKey(0))
            decode = jax.jit(
                lambda p, s, t: model_lib.decode_step(p, cfg, mesh, s, t))
            key = jax.random.PRNGKey(1)
            done = 0
            t0 = time.time()          # every request "arrives" at t0
            tokens_out = 0
            latencies = []
            while done < args.requests:
                n = min(B, args.requests - done)
                key, k1 = jax.random.split(key)
                prompts = jax.random.randint(k1, (B, args.prompt_len), 0,
                                             cfg.vocab_size)
                state = model_lib.init_decode_state(cfg, B, max_len, mesh)
                # prefill via teacher-forced decode (exercises the cache
                # path)
                for i in range(args.prompt_len):
                    logits, state = decode(params, state,
                                           prompts[:, i:i + 1])
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
                for _ in range(args.gen):
                    logits, state = decode(params, state, tok)
                    tok = jnp.argmax(logits, -1).astype(jnp.int32)
                    tokens_out += n
                jax.block_until_ready(tok)
                t_done = time.time()
                for r in range(done, done + n):
                    lat = t_done - t0
                    latencies.append(lat)
                    obs_events.emit("serve_request", request=r,
                                    latency_s=lat, tokens=args.gen)
                done += n
            dt = max(1e-9, time.time() - t0)
        latencies.sort()
        p50 = _percentile(latencies, 50)
        p99 = _percentile(latencies, 99)
        obs_events.emit(
            "serve_summary", requests=args.requests, tokens=tokens_out,
            dt=dt, tokens_per_s=tokens_out / dt,
            tokens_per_s_device=tokens_out / dt / n_dev,
            latency_p50_s=p50, latency_p99_s=p99)
        if args.bench_json:
            from repro.obs import benchrow
            row = benchrow.bench_row(
                name=args.bench_name, kind="serve",
                metrics={"latency_p50_s": p50, "latency_p99_s": p99,
                         "tokens_per_s": tokens_out / dt,
                         "tokens_per_s_device": tokens_out / dt / n_dev,
                         "requests": float(args.requests),
                         "tokens": float(tokens_out)},
                context={"arch": args.arch, "smoke": args.smoke,
                         "gen": args.gen, "prompt_len": args.prompt_len,
                         "batch_slots": args.batch_slots,
                         "devices": n_dev})
            path = benchrow.append_row(args.bench_json, row)
            obs_events.emit("bench_row", name=args.bench_name,
                            row_kind="serve", path=path)
        return 0
    finally:
        if jsonl is not None:
            log.remove_sink(jsonl)
            jsonl.close()


if __name__ == "__main__":
    raise SystemExit(main())
