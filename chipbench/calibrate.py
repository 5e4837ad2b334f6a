"""Readings that the limits of ``correct`` are set from (PERF.md).

  python3 chipbench/calibrate.py --workload <name> --seeds 1,2,3 \\
      [--controls 3] [--out <file.jsonl>]

For each seed, in one process: the program's readings over the check
steps and the plain reference's, and the gaps between them (check.py).
On the first ``--controls`` seeds also the control, the reference at the
precision below the configuration's (for bfloat16: every activation and
gradient it holds in float8 e4m3), and the planted faults: half the
batch left out, and on more than one chip the exchange between chips
left out.  A state left
unchanged reads 1 by construction and needs no run.  Each seed's result
is one JSON line.  Not part of a benchmark run.
"""
import json
import sys
import time
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import check  # noqa: E402
from chipbench.harness import ROOT, Trainer, cell_spec  # noqa: E402
from chipbench.program import refuse_program_env  # noqa: E402
from chipbench.traffic import Traffic  # noqa: E402


def readings(workload, seeds, controls, *, require_tpu=True, root=ROOT,
             emit=print):
    """Yield one dict of gaps per seed (see the module docstring)."""
    import gc
    spec = cell_spec(workload, root)
    refuse_program_env()
    import jax
    if require_tpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate: no TPU")
    trainer = Trainer(spec, phases=False)
    n = int(spec.mix["check_steps"])
    faults = ["half_batch"] + (["no_exchange"] if trainer.groups > 1 else [])
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        batches = Traffic(spec.mix, spec.conf["config"]["vocab_size"],
                          seed).batches(0, n)
        state, dev, _ = trainer.start(seed, trainer.put(batches),
                                      keep_moment=True)
        prog = trainer.readings(dev)
        del state, dev
        gc.collect()
        ref = trainer.reference(seed, batches, keep_moment=True)
        out = {"seed": seed, "program": check.gaps(prog, ref),
               "detail": check.worst_leaves(prog, ref)}
        raw = {"program": prog, "reference": ref}
        if i < controls:
            raw["control"] = trainer.reference(seed, batches,
                                               precision="fp8",
                                               keep_moment=True)
            for f in faults:
                raw[f] = trainer.reference(seed, batches, fault=f,
                                           keep_moment=True)
        for k, r in raw.items():
            if k not in ("program", "reference"):
                out[k] = check.gaps(r, ref)
        moments = {k: r.pop("moment") for k, r in raw.items()}
        out["moment_gaps"] = {k: check.moment_gaps(m, moments["reference"])
                              for k, m in moments.items()
                              if k != "reference"}
        del moments
        out["readings"] = raw
        out["seconds"] = time.perf_counter() - t0
        emit(json.dumps(out))
        yield out


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    sink = open(args.out, "a") if args.out else None
    try:
        def emit(line):
            print(line, flush=True)
            if sink:
                sink.write(line + "\n")
                sink.flush()
        for _ in readings(args.workload, seeds, args.controls, emit=emit):
            pass
    finally:
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
