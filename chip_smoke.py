"""Smoke run of the LSH-MoE trainer and server on a TPU.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # expert parallelism over four chips

Model: granite-moe-3b-a800m (hf:ibm-granite/granite-3.0-3b-a800m-base) at
its published widths -- d_model 1536, 24 heads / 8 kv heads of width 64,
40 experts top-8 of width 512, vocab 49155 -- with seeded random weights.
Training cuts the depth from 32 to 4 attention+MoE super-blocks: ~555M
parameters, i.e. bf16 weights plus f32 Adam moments of ~5.2 GiB, and the
same again for the updated state, which with the step's ~2.4 GiB of
temporaries fits one 16 GB v5e chip at a batch of 8 x 1024 tokens.  Decode
serves the full 32-layer model (~3.4B parameters, ~6.3 GiB in bf16).

One chip, through the calls launch/train.py and launch/serve.py make:
  1. every kernel-registry op resolves to ``pallas_tpu``;
  2. step 0 with ``kernel_backend="reference"`` gives the loss to match;
  3. the LSH train step (the config default) compiles with Pallas
     kernels in it (``tpu_custom_call``) and runs STEPS finite steps,
     step 0's loss within LOSS_RTOL of the reference's;
  4. one LSH-off step runs, finite;
  5. decode requests are served through ``repro.launch.serve.main``.

Four chips (``--chips 4``) run only the expert-parallel phase: the cut
model's 40 experts over a 1x4 (data, model) mesh, 10 per chip, with LSH
off and on; the step holds all-to-alls, and the 4-chip LSH-off step 0
loss is compared with the same step on a one-device mesh.

Earlier lines are informational.  The last line is one JSON object naming
the device; any failure, a platform other than TPU, or a directory without
the rest of the repository exits non-zero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

ARCH = "granite-moe-3b-a800m"
TRAIN_DEPTH = 4                 # super-blocks kept for training (of 32)
BATCH, SEQ = 8, 1024
STEPS = 4                       # LSH-on steps on one chip
EP_STEPS = 3                    # steps per 4-chip phase
SEED = 0
# Pallas vs reference, step 0.  Outside the kernel registry both runs
# execute the same program.  Inside it the ops move, select and sum bf16
# activations exactly, and differ only where the TPU rounds f32 matmul
# operands (XLA's default one-pass bf16 for the reference's one-hot
# einsums and hash projection vs Mosaic's f32 passes), i.e. at bf16's
# relative precision 2**-8, which then bounds the loss's relative change.
LOSS_RTOL = 2.0 ** -8
# 4 chips vs 1, LSH off, step 0.  Both run the same math except capacity:
# each chip sizes its expert buffers for its own 2048 tokens, one device
# for all 8192, so the over-capacity tokens that are dropped (and pass
# through on the residual) differ.  At random init the MoE branch moves
# the loss by well under 1%, so a changed drop set stays inside 1%.
EP_LOSS_RTOL = 1e-2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def compiled_text_counts(compiled) -> dict:
    txt = compiled.as_text()
    return {"tpu_custom_call": txt.count('"tpu_custom_call"'),
            "all_to_all": txt.count(" all-to-all(")}


def peak_bytes(devices) -> list:
    return [d.memory_stats().get("peak_bytes_in_use", -1) for d in devices]


def train_config(cfg, *, backend: str = "auto"):
    cfg = cfg.replace(num_super_blocks=TRAIN_DEPTH)
    return cfg.replace(moe=dataclasses.replace(cfg.moe,
                                               kernel_backend=backend))


def train_jit(step):
    """``jax.jit`` of a train step as launch/train.py builds it, with the
    input state donated: a caller's reference to the state it passed in
    then pins no second copy (5.2 GiB at the cut depth) while later steps
    run."""
    import jax
    return jax.jit(step, donate_argnums=0)


def run_steps(step_fn, state, ds, first: int, n: int):
    """n steps from batch ``first``; returns (state, losses, seconds)."""
    import jax
    losses, secs = [], []
    for s in range(first, first + n):
        t0 = time.perf_counter()
        state, metrics = step_fn(state, ds.batch_at(s))
        jax.block_until_ready((state, metrics))
        secs.append(time.perf_counter() - t0)
        losses.append(float(metrics["loss"]))
    return state, losses, secs


def check_finite(name: str, losses) -> None:
    if not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"{name}: non-finite loss in {losses}")


def check_close(name: str, got: float, want: float, rtol: float) -> None:
    rel = abs(got - want) / abs(want)
    log(f"{name}: {got!r} vs {want!r} (rel diff {rel!r}, rtol {rtol!r})")
    if not rel <= rtol:
        raise AssertionError(f"{name}: rel diff {rel!r} > {rtol!r}")


def one_chip(base_cfg, opt) -> None:
    import jax
    from repro.compat import set_mesh
    from repro.data.synthetic import SyntheticLMDataset
    from repro.kernels import dispatch
    from repro.launch import serve
    from repro.launch.mesh import make_host_mesh
    from repro.obs import events as obs_events
    from repro.runtime.step import init_train_state, make_train_step

    cfg = train_config(base_cfg)
    resolved = dispatch.resolve_backends(cfg.moe.kernel_backend,
                                         cfg.moe.kernel_backend_overrides)
    per_op = {op: dispatch.op_backend(resolved, op) for op in dispatch.OPS}
    log(f"kernel backends: {per_op}")
    if set(per_op.values()) != {dispatch.PALLAS_TPU}:
        raise AssertionError(f"not every op resolves to pallas_tpu: {per_op}")

    mesh = make_host_mesh(1, 1, 1)
    dev = list(mesh.devices.flat)
    ds = SyntheticLMDataset(cfg.vocab_size, SEQ, BATCH, seed=SEED)
    with set_mesh(mesh):
        state = init_train_state(jax.random.PRNGKey(SEED), cfg, opt, mesh)

        # the one step that must leave ``state`` intact: no donation
        ref_fn = jax.jit(make_train_step(train_config(base_cfg,
                                                      backend="reference"),
                                         opt, mesh, microbatch=0))
        ref_state, ref_loss, ref_s = run_steps(ref_fn, state, ds, 0, 1)
        check_finite("reference step 0", ref_loss)
        log(f"reference step 0: loss {ref_loss[0]!r}, "
            f"seconds incl. compile {ref_s[0]!r}")
        del ref_fn, ref_state       # 5.2 GiB the later phases need

        step_fn = train_jit(make_train_step(cfg, opt, mesh, microbatch=0))
        t0 = time.perf_counter()
        compiled = step_fn.lower(state, ds.batch_at(0)).compile()
        log(f"LSH step compile seconds {time.perf_counter() - t0!r}")
        counts = compiled_text_counts(compiled)
        log(f"LSH step tpu_custom_call count {counts['tpu_custom_call']}")
        if counts["tpu_custom_call"] == 0:
            raise AssertionError("no Pallas kernel in the compiled step")
        # one device: the step returns the state in the shardings it takes,
        # so the one executable serves every step
        state, losses, secs = run_steps(compiled, state, ds, 0, STEPS)
        check_finite("LSH steps", losses)
        log(f"LSH step losses {losses}")
        log(f"LSH step seconds {secs} (steady mean "
            f"{sum(secs[1:]) / len(secs[1:])!r})")
        check_close("pallas vs reference step-0 loss", losses[0],
                    ref_loss[0], LOSS_RTOL)
        del step_fn, compiled

        off_fn = train_jit(make_train_step(cfg, opt, mesh, use_lsh=False,
                                           microbatch=0))
        state, off_loss, off_s = run_steps(off_fn, state, ds, STEPS, 1)
        check_finite("LSH-off step", off_loss)
        log(f"LSH-off step {STEPS}: loss {off_loss[0]!r}, "
            f"seconds incl. compile {off_s[0]!r}")
    log(f"train peak_bytes_in_use {peak_bytes(dev)}")
    del state, off_fn
    gc.collect()

    n_req, gen = 4, 8
    served = obs_events.MemorySink()
    obs_events.global_log().add_sink(served)
    t0 = time.perf_counter()
    rc = serve.main(["--arch", ARCH, "--requests", str(n_req),
                     "--gen", str(gen), "--prompt-len", "8",
                     "--batch-slots", str(n_req)])
    obs_events.global_log().remove_sink(served)
    done = served.of_kind("serve_request")
    log(f"decode: rc {rc}, {len(done)} of {n_req} requests served in "
        f"{time.perf_counter() - t0!r} s incl. init and compile")
    if rc != 0 or len(done) != n_req:
        raise AssertionError("decode requests were not all served")
    log(f"peak_bytes_in_use {peak_bytes(dev)}")


def four_chips(base_cfg, opt) -> None:
    import jax
    from repro.compat import set_mesh
    from repro.data.synthetic import SyntheticLMDataset
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.step import init_train_state, make_train_step

    if len(jax.devices()) < 4:
        raise AssertionError(f"--chips 4 needs 4 devices, have "
                             f"{len(jax.devices())}")
    cfg = train_config(base_cfg)
    ds = SyntheticLMDataset(cfg.vocab_size, SEQ, BATCH, seed=SEED)

    one = make_host_mesh(1, 1, 1)
    with set_mesh(one):
        state = init_train_state(jax.random.PRNGKey(SEED), cfg, opt, one)
        fn = jax.jit(make_train_step(cfg, opt, one, use_lsh=False,
                                     microbatch=0))
        one_state, one_loss, _ = run_steps(fn, state, ds, 0, 1)
    check_finite("1-device LSH-off step 0", one_loss)
    log(f"1-device LSH-off step 0: loss {one_loss[0]!r}")
    del state, one_state, fn
    gc.collect()

    mesh = make_host_mesh(1, 1, 4)
    devs = list(mesh.devices.flat)
    log(f"mesh {dict(mesh.shape)} over devices {[d.id for d in devs]}")
    with set_mesh(mesh):
        state = init_train_state(jax.random.PRNGKey(SEED), cfg, opt, mesh)
        w_up = state.params["blocks"][0]["ffn"]["w_up"]
        log(f"expert weights {w_up.shape} on "
            f"{sorted(d.id for d in w_up.sharding.device_set)}, "
            f"per-device block {w_up.addressable_shards[0].data.shape}")
        for use_lsh in (False, True):
            name = "LSH-on" if use_lsh else "LSH-off"
            fn = train_jit(make_train_step(cfg, opt, mesh, use_lsh=use_lsh,
                                           microbatch=0))
            # the initial state is replicated and step 0 returns it
            # expert-sharded, so the jit path compiles step 1 again
            first = EP_STEPS if use_lsh else 0
            state, losses, secs = run_steps(fn, state, ds, first, EP_STEPS)
            check_finite(f"4-chip {name}", losses)
            log(f"4-chip {name} losses {losses}, seconds incl. compiles "
                f"{secs}")
            counts = compiled_text_counts(
                fn.lower(state, ds.batch_at(0)).compile())
            log(f"4-chip {name} all-to-all count {counts['all_to_all']}, "
                f"tpu_custom_call count {counts['tpu_custom_call']}")
            if counts["all_to_all"] == 0:
                raise AssertionError(f"4-chip {name} step has no all-to-all")
            if not use_lsh:
                check_close("4-chip vs 1-device LSH-off step-0 loss",
                            losses[0], one_loss[0], EP_LOSS_RTOL)
            del fn
    w_up = state.params["blocks"][0]["ffn"]["w_up"]
    log(f"expert weights after training: per-device block "
        f"{w_up.addressable_shards[0].data.shape}")
    log(f"peak_bytes_in_use per device {peak_bytes(devs)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip phases; 4: only the "
                         "expert-parallel phase over four chips")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as exc:
        print(f"chip_smoke: the repository's src/ is not next to this "
              f"script ({exc})", file=sys.stderr)
        return 1
    backend_env = os.environ.get("REPRO_KERNEL_BACKEND", "")
    if backend_env not in ("", "auto", "pallas_tpu"):
        print(f"chip_smoke: REPRO_KERNEL_BACKEND={backend_env} would take "
              f"the compiled kernels off the chip path; unset it",
              file=sys.stderr)
        return 1

    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r})",
              file=sys.stderr)
        return 1
    log(f"cache {enable_compile_cache()}")
    from repro.configs.base import OptimizerConfig
    from repro.configs.registry import get_config
    base_cfg = get_config(ARCH)
    opt = OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    d = jax.devices()[0]
    log(f"jax {jax.__version__}, {len(jax.devices())} x {d.device_kind}")
    (four_chips if args.chips == 4 else one_chip)(base_cfg, opt)
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
