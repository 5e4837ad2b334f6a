"""One run of one cell: set-up, the measured window, the check.

A cell is a ``workloads`` entry of ``BENCHMARK.json``: a configuration
file (``configs/<name>.json``), a traffic mix (``traffic/<name>.json``)
and a chip count.  The configuration file names its model module
(``"model"``: ``models/<module>.py``), which gives the plain reference,
the FLOP count and the program's keys.  Its per-layer metrics are the
``per_layer`` entries that list it; each is read by
``metrics/<name>.py``.  Nothing here names a cell, a configuration, an
architecture or a metric.

Set-up builds the program's compiled train step (state donated) and its
state around the benchmark's seeded weights, then drives it through the
first ``check_steps`` steps: their losses, the first gradient's leaf
norms and the leaves' change are kept for the check.  The window then
dispatches whole steps back to back, with every batch already on the
device and no host sync, and ends when the last step's outputs are
ready.  After it, peak memory is read, the program's state is freed, and
the plain reference repeats the first steps for the comparison.
"""
from __future__ import annotations

import gc
import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional, Tuple

import numpy as np

from chipbench import check, counts, program, reference
from chipbench.program import Refused
from chipbench.traffic import Traffic

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
GIB = float(1 << 30)
TRACE_MIN_STEPS = 2
TRACE_MIN_SECONDS = 2.0


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(workload: str, root: Path = ROOT) -> SimpleNamespace:
    """Everything one cell needs, found by the names in BENCHMARK.json."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf_path = root / HERE.name / "configs" / f"{w['config']}.json"
    conf = load_json(conf_path)
    mix = load_json(root / HERE.name / "traffic" / f"{w['traffic']}.json")
    moves = {m["name"] for m in bench["end_to_end"]
             if workload in m.get("workloads", [workload])}
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])
                 and m["moves"] in moves]
    return SimpleNamespace(name=workload, chips=int(w["chips"]), conf=conf,
                           model=model_module(conf, conf_path,
                                              root / HERE.name / "models"),
                           metrics_dir=root / HERE.name / "metrics",
                           mix=mix, end_to_end=[m for m in bench["end_to_end"]
                                                if m["name"] in moves],
                           per_layer=per_layer)


def load_file(path: Path, name: str):
    """The Python module in ``path``, imported under ``name``."""
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def model_module(conf: Dict, conf_path: Path, models_dir: Path):
    """The model module a configuration file names (``"model"``); a file
    that names none, or a module that does not exist, is refused."""
    name = conf.get("model")
    if not isinstance(name, str) or not name.isidentifier():
        raise Refused(f"{conf_path} names no model module "
                      f"(\"model\": {name!r})")
    path = models_dir / f"{name}.py"
    if not path.is_file():
        raise Refused(f"{conf_path} names the model module {name!r}, and "
                      f"there is no {path}")
    return load_file(path, f"chipbench.models.{name}")


def seed_key(seed: int):
    """A raw threefry key from any whole number, however wide."""
    import jax.numpy as jnp
    words = np.random.SeedSequence(seed % (1 << 64)).generate_state(2)
    return jnp.asarray(words.astype(np.uint32))


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache: $JAX_COMPILATION_CACHE_DIR
    where set, else the fixed ``<checkout>/.jax_cache``.  Every program is
    kept, however fast it compiled, so a warm run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root /
                                                              ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # The traced step differs from the timed one only in its op_name
    # metadata (the obs/ scopes); without metadata in the key it would
    # load the timed step's executable, which carries no scopes.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path


class Unread(RuntimeError):
    """A per-layer metric listed for the cell found nothing to read: the
    run prints no result."""


class Trainer:
    """The program's compiled step, its state initialiser and the readers of
    its state that the check needs; compiled once, driven per seed."""

    def __init__(self, spec: SimpleNamespace, *, phases: bool):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        conf, mix, self.model = spec.conf, spec.mix, spec.model
        program.import_program()
        self.cfg = program.model_config(conf, self.model, phases=phases)
        self.opt = program.optimizer_config(conf)
        self.mesh = program.mesh(conf)
        self.dims = self.model.dims(conf, use_lsh=mix["use_lsh"])
        self.adam = reference.adam_from_config(conf)
        self.groups = conf["mesh"]["model"]
        dims, opt, init_params = self.dims, self.opt, self.model.init_params
        with program.set_mesh(self.mesh):
            abstract = program.abstract_state(self.cfg, opt, self.mesh)
            ours = jax.eval_shape(lambda k: init_params(k, dims),
                                  seed_key(0))
            if jax.tree.structure(ours) != jax.tree.structure(
                    abstract.params) or any(
                    (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                    zip(jax.tree.leaves(ours),
                        jax.tree.leaves(abstract.params))):
                raise Refused("the program's parameter layout is not the "
                              "one the benchmark's weights fill")
            rows = NamedSharding(self.mesh, P(*program.batch_spec(self.mesh)))
            b = jax.ShapeDtypeStruct((mix["global_batch"], mix["seq_len"]),
                                     np.int32, sharding=rows)
            fn = jax.jit(program.train_step(self.cfg, opt, self.mesh,
                                            use_lsh=mix["use_lsh"]),
                         donate_argnums=0)
            self.step = fn.lower(abstract, {"tokens": b,
                                            "labels": b}).compile()
        state_sh, self.batch_sharding = self.step.input_shardings[0]
        self.init = jax.jit(
            lambda key: program.make_state(init_params(key, dims), opt),
            out_shardings=state_sh)
        b1 = self.adam.b1
        self.grad_norms = jax.jit(
            lambda m: reference.float_leaf_norms(m) / (1.0 - b1))
        self.change_norms = jax.jit(
            lambda params, key: reference.change_norms(
                params, init_params(key, dims)))
        self.leaves = reference.leaf_names(ours)

    def put(self, batches: List[Dict]) -> List[Dict]:
        import jax
        return [jax.device_put(b, self.batch_sharding) for b in batches]

    def start(self, seed: int, batches: List[Dict], *,
              keep_moment: bool = False):
        """State from the seed, driven through the check steps.  Returns
        the state, the program's readings (device arrays; with
        ``keep_moment`` also Adam's first moment after the first step,
        on the host) and the seconds of the last check step."""
        import jax
        key = seed_key(seed)
        state = self.init(key)
        losses, grad, last, extra = [], None, 0.0, {}
        for i, b in enumerate(batches):
            jax.block_until_ready(state)
            t = time.perf_counter()
            state, metrics = self.step(state, b)
            losses.append(metrics["loss"])
            jax.block_until_ready(state)
            last = time.perf_counter() - t
            if i == 0:
                grad = self.grad_norms(state.opt.m)
                if keep_moment:
                    extra["moment"] = reference.host_floats(state.opt.m)
        change = self.change_norms(state.params, key)
        return state, {"losses": losses, "grad_norms": grad,
                       "change_norms": change, **extra}, last

    def readings(self, dev: Dict) -> Dict:
        out = {"losses": [float(x) for x in dev["losses"]],
               "grad_norms": [float(x) for x in dev["grad_norms"]],
               "change_norms": [float(x) for x in dev["change_norms"]],
               "leaves": self.leaves}
        if "moment" in dev:
            out["moment"] = dev["moment"]
        return out

    def reference(self, seed: int, batches: List[Dict],
                  precision: str = "f32", fault: str = "",
                  keep_moment: bool = False) -> Dict:
        """The plain reference's readings on the same seed and batches,
        its weights spread over the cell's chips.  ``precision`` and
        ``fault`` select the control and the planted faults
        (``chipbench/calibrate.py``)."""
        return reference.run(self.model, seed_key(seed), batches, self.dims,
                             self.adam, self.groups, steps=len(batches),
                             precision=precision, fault=fault,
                             keep_moment=keep_moment,
                             devices=list(self.mesh.devices.flat))


def window(trainer: Trainer, state, batches: List[Dict]):
    """Dispatch every step back to back; the window ends when the last
    step's state and every loss are ready."""
    import jax
    from jax.profiler import TraceAnnotation
    losses = []
    with TraceAnnotation("window"):
        t0 = time.perf_counter()
        for b in batches:
            with TraceAnnotation("dispatch"):
                state, metrics = trainer.step(state, b)
                losses.append(metrics["loss"])
        with TraceAnnotation("wait"):
            jax.block_until_ready((state, losses))
        t1 = time.perf_counter()
    return state, losses, t0, t1


def metric_reader(metrics_dir: Path, name: str):
    return load_file(metrics_dir / f"{name}.py",
                     f"chipbench.metrics.{name}").read


def check_lines(gaps: Dict[str, float], limits: Dict[str, float]) -> Dict:
    return {n: {"value": gaps[n], "limit": limits[n]} for n in limits}


def trace_context(tr, meta: Dict) -> SimpleNamespace:
    """What a per-layer metric reader reads: the reduced trace, the traced
    window's steps and tokens, the chips, their peaks and the model FLOPs
    per trained token."""
    return SimpleNamespace(trace=tr, steps=meta["steps"],
                           tokens=meta["tokens"], chips=meta["chips"],
                           peaks=counts.peaks(meta["device_kind"]),
                           flops_per_token=meta["flops_per_token"])


def read_metrics(per_layer: List[Dict], metrics_dir: Path,
                 ctx: SimpleNamespace) -> Dict:
    """Each per-layer metric that its reader finds something to read."""
    out = {}
    for m in per_layer:
        value = metric_reader(metrics_dir, m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def cell_metrics(per_layer: List[Dict], metrics_dir: Path,
                 ctx: SimpleNamespace) -> Dict:
    """Every per-layer metric listed for a cell; one that reads nothing is
    an error, not a gap in the result."""
    out = read_metrics(per_layer, metrics_dir, ctx)
    unread = [m["name"] for m in per_layer if m["name"] not in out]
    if unread:
        raise Unread(f"per-layer metrics listed for the cell read nothing "
                     f"in the trace: {unread}")
    return out


def keep(pb: str, hlo: str, meta: Dict, dest: Path) -> None:
    """Copy a traced run's xplane and compiled HLO text, gzipped, and what
    the readers need besides, to ``dest`` (how the trace under
    ``tests/data`` was recorded)."""
    import gzip
    dest.mkdir(parents=True, exist_ok=True)
    with open(pb, "rb") as f, gzip.open(dest / "trace.xplane.pb.gz",
                                        "wb") as g:
        shutil.copyfileobj(f, g)
    with gzip.open(dest / "step.hlo.txt.gz", "wt") as g:
        g.write(hlo)
    (dest / "meta.json").write_text(json.dumps(meta, indent=1))


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True, root: Path = ROOT,
        keep_trace: str = "") -> Tuple[Dict, Dict]:
    """One run; returns the result object (the caller prints it) and
    notes for standard error: the losses behind the check and the
    window's losses."""
    spec = cell_spec(workload, root)
    program.refuse_program_env()
    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < spec.chips):
        raise Refused(f"the cell needs {spec.chips} TPU chip(s); JAX found "
                      f"{len(devices)} {devices[0].platform} device(s)")
    used = devices[:spec.chips]
    kind = used[0].device_kind
    mix, conf = spec.mix, spec.conf

    trainer = Trainer(spec, phases=trace)
    traffic = Traffic(mix, conf["config"]["vocab_size"], seed)
    n_check = int(mix["check_steps"])
    check_batches = traffic.batches(0, n_check)
    limits = conf["limits"]
    moment = "moment_gap" in limits
    state, dev_readings, step_s = trainer.start(
        seed, trainer.put(check_batches), keep_moment=moment)
    n = max(1, min(10000, round(seconds / max(step_s, 1e-6))))
    if trace:
        # The traced window is the first steps of the full one: enough
        # for per-step averages, few enough that the trace stays small.
        n = min(n, max(TRACE_MIN_STEPS,
                       math.ceil(TRACE_MIN_SECONDS / max(step_s, 1e-6))))
    win_batches = trainer.put(traffic.batches(n_check, n))
    import jax
    jax.block_until_ready(win_batches)

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        hlo = trainer.step.as_text()
        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - t_start
    state, losses, t0, t1 = window(trainer, state, win_batches)
    if trace:
        jax.profiler.stop_trace()
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
               if d.memory_stats() else 0 for d in used)
    window_losses = [float(x) for x in losses]
    failed = sum(1 for x in window_losses if not math.isfinite(x))
    prog = trainer.readings(dev_readings)
    del state, dev_readings, win_batches, losses
    gc.collect()

    ref = trainer.reference(seed, check_batches, keep_moment=moment)
    gaps = check.gaps(prog, ref)
    correct = check.judge(gaps, limits) and failed == 0

    window_s = t1 - t0
    tokens = n * traffic.tokens_per_step
    result = {"correct": correct, "attempted": n, "failed": failed}
    device = {"platform": used[0].platform, "kind": kind,
              "count": len(used), "memory_peak_bytes": int(peak)}
    if trace:
        from chipbench import trace as trace_lib
        pb = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True)[0]
        meta = {"steps": n, "tokens": tokens, "chips": len(used),
                "device_kind": kind,
                "flops_per_token": spec.model.train_flops_per_token(
                    conf["config"], mix["seq_len"])}
        if keep_trace:
            keep(pb, hlo, meta, Path(keep_trace))
        tr = trace_lib.load(pb, hlo)
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = cell_metrics(spec.per_layer, spec.metrics_dir,
                               trace_context(tr, meta))
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s)
        result["breakdown"] = tr.breakdown()
    else:
        e2e = {"tokens_per_s_chip": tokens / window_s / len(used),
               "peak_hbm_gib": peak / GIB, "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec.end_to_end}
    result.update(metrics=metrics, device=device,
                  check=check_lines(gaps, limits))
    notes = {"check-step losses": {"program": prog["losses"],
                                   "reference": ref["losses"]},
             "window losses": window_losses}
    return result, notes


def main(argv=None, *, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="",
                    help="with --trace 1, also copy the trace and the "
                         "step's HLO text to this directory")
    args = ap.parse_args(argv)
    try:
        enable_compile_cache()
        res, notes = run(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=t_start,
                         keep_trace=args.keep_trace)
    except Refused as exc:
        print(f"chipbench: refused: {exc}", file=sys.stderr, flush=True)
        return 2
    except Unread as exc:
        print(f"chipbench: {exc}", file=sys.stderr, flush=True)
        return 3
    for name, value in notes.items():
        print(f"chipbench: {name} {value}", file=sys.stderr)
    for name, c in res["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0
