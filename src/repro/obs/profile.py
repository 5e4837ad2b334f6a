"""Measured per-phase timing parsed from a ``jax.profiler`` device trace.

``obs/timeline.StepTimeline`` *attributes* one host wall interval per
step across phases proportionally to the analytic cost model — by
construction its breakdown can never disagree with the model it came
from.  This module produces the MEASURED half: ``--profile`` already
captures a profiler trace (the Chrome-trace ``*.trace.json.gz`` under
``<dir>/plugins/profile/<ts>/``); ``parse_jax_trace`` turns it into a
``MeasuredTimeline`` whose per-phase durations come from actual device
events, correlated with the ``obs/tracing.py`` named scopes:

 * **TPU/GPU-style rows** name device ops with the full scope path, so
   ``obs/<phase>`` appears directly in the event name (or its
   ``long_name``/``tf_op`` args) — matched by regex.
 * **CPU thunk rows** (the forced-host-device meshes CI runs on) name
   events after the post-optimization HLO instruction and carry
   ``args.hlo_op`` / ``args.hlo_module``; the scope survives only in the
   instruction's ``metadata={op_name="...obs/<phase>/..."}``.
   ``hlo_phase_map(compiled_text)`` recovers instruction -> phase from
   the compiled HLO text (the launcher lowers the train step once when
   profiling), and the parser joins trace events against it.
 * **Collectives** the program issues itself carry their scope (the
   MoE exchange's legs, the attention's exchanges); the ones SPMD
   partitioning inserts or re-attributes may carry none, and those are
   classified structurally by opcode: an unscoped ``all-to-all`` is
   taken for the MoE exchange — its time is split evenly between the
   ``dispatch_a2a`` / ``combine_a2a`` legs (the legs carry symmetric
   payloads, and their SUM — the comm share — is the number that
   matters); ``collective-permute`` is the pipeline ``stage_transfer``
   hop.  Grad all-reduces and resharding all-gathers stay in ``other``:
   they are comm, but not the paper's a2a phases.

The step-level scopes (``attention``, ``lm_head``, ``optimizer``) are
measured the same way and reported beside the MoE phases.  Device
events of the profiled module that match no scope land in ``other``;
events of *other* modules (init, eval jits) are excluded when
the module is known, so the measurement is the train step's.  Durations
are summed per phase across the whole capture and divided by the number
of profiled steps and participating devices — the result has the same
span schema as the modeled timeline (``timeline.StepRecord`` /
``PhaseSpan``), so ``obs/reconcile.py`` can diff them phase by phase.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Tuple

from repro.obs import timeline as timeline_lib
from repro.obs import tracing as tracing_lib
from repro.obs.timeline import PHASE_ORDER, PhaseSpan, StepRecord

OTHER = "other"

# The step-level scopes (attention, LM head, optimizer): measured like the
# MoE phases, reported after them.  The cost model prices them as "other"
# (obs/reconcile.py folds them back before comparing).
STEP_PHASES = tuple(s[len(tracing_lib.PREFIX):]
                    for s in tracing_lib.STEP_SCOPES)
MEASURED_ORDER = PHASE_ORDER[:-1] + STEP_PHASES + (OTHER,)

# "obs/<phase>" anywhere in an op path / scope string.
_PHASE_NAMES = tuple(p for p in MEASURED_ORDER if p != OTHER)
PHASE_RE = re.compile(r"obs/(%s)\b" % "|".join(_PHASE_NAMES))

# One post-optimization HLO instruction with op metadata:
#   %name.0 = f32[...] op(...), ..., metadata={op_name="jit(f)/.../obs/gate/mul" ...}
_HLO_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=.*metadata=\{[^}]*"
    r"op_name=\"([^\"]*)\"", re.M)
_HLO_MODULE_RE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)

# Device-side thread names: CPU thunk executor / TPU-GPU op rows.
_DEVICE_THREAD_RE = re.compile(
    r"(XLA Ops|Stream #|TensorFlow Op)", re.I)
_DEVICE_PROC_RE = re.compile(r"/(device|host):", re.I)

# Structural opcode classification for collectives (scope metadata does
# not survive SPMD partitioning).  A2A is a sentinel: the event splits
# evenly across the dispatch/combine legs.
A2A = "__a2a__"
_A2A_OP_RE = re.compile(r"^%?all-to-all")
_PERMUTE_OP_RE = re.compile(r"^%?collective-permute")


# -------------------------------------------------------- trace loading ---


def find_trace_file(path: str) -> str:
    """Resolve a jax.profiler output directory (the ``--profile``
    ``<metrics-dir>/jax_trace`` root, or any ancestor of the dated
    ``plugins/profile/<ts>/`` dir) to its newest ``*.trace.json[.gz]``;
    a direct file path passes through."""
    if os.path.isfile(path):
        return path
    candidates = []
    for pat in ("*.trace.json.gz", "*.trace.json",
                os.path.join("plugins", "profile", "*", "*.trace.json.gz"),
                os.path.join("plugins", "profile", "*", "*.trace.json"),
                os.path.join("**", "*.trace.json.gz"),
                os.path.join("**", "*.trace.json")):
        candidates = glob.glob(os.path.join(path, pat), recursive=True)
        if candidates:
            break
    if not candidates:
        raise FileNotFoundError(
            f"no *.trace.json[.gz] under {path!r} — did the profiler "
            f"backend write a capture?")
    return max(candidates, key=os.path.getmtime)


def load_trace(path: str) -> Dict:
    """The Chrome-trace JSON dict of ``path`` (a trace file or a
    profiler output directory; ``.gz`` transparently decompressed)."""
    path = find_trace_file(path)
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


# --------------------------------------------------- HLO scope recovery ---


def hlo_module_name(hlo_text: str) -> Optional[str]:
    m = _HLO_MODULE_RE.search(hlo_text)
    return m.group(1) if m else None


def hlo_phase_map(hlo_text: str) -> Dict[str, str]:
    """instruction name -> phase, for every instruction of the compiled
    (post-optimization) HLO whose ``op_name`` metadata carries an
    ``obs/<phase>`` scope.  CPU/GPU trace events reference exactly these
    instruction names (``args.hlo_op``), which is what lets a fusion
    named ``broadcast_multiply_fusion`` resolve to the scope its ops
    were traced under."""
    out: Dict[str, str] = {}
    for name, op_name in _HLO_INSTR_RE.findall(hlo_text):
        m = PHASE_RE.search(op_name)
        if m:
            out[name] = m.group(1)
    return out


# ------------------------------------------------------- event selection --


def _meta_tables(events: Iterable[Dict]):
    """(pid -> process name, (pid, tid) -> thread name) from 'M' events."""
    procs: Dict[int, str] = {}
    threads: Dict[Tuple[int, int], str] = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        name = (e.get("args") or {}).get("name", "")
        if e.get("name") == "process_name":
            procs[e.get("pid")] = name
        elif e.get("name") == "thread_name":
            threads[(e.get("pid"), e.get("tid"))] = name
    return procs, threads


def _classify_event(e: Dict, phase_map: Dict[str, str],
                    module: Optional[str]) -> Optional[str]:
    """Phase of one device event, OTHER for unmatched events of the
    profiled module, None for events to exclude."""
    args = e.get("args") or {}
    hlo_op = args.get("hlo_op")
    hlo_module = args.get("hlo_module")
    if module is not None and hlo_module is not None \
            and hlo_module != module:
        return None                     # some other jit's execution
    # scope path directly in the name / annotation args (TPU-style rows)
    for text in (e.get("name", ""), args.get("long_name", ""),
                 args.get("tf_op", "")):
        m = PHASE_RE.search(str(text))
        if m:
            return m.group(1)
    if hlo_op is not None:
        op = str(hlo_op)
        ph = phase_map.get(op.lstrip("%"))
        if ph is not None:
            return ph
        if _A2A_OP_RE.match(op):
            return A2A
        if _PERMUTE_OP_RE.match(op):
            return "stage_transfer"
        if hlo_module is not None and (module is None
                                       or hlo_module == module):
            return OTHER
        return None
    # nameless-args device event (TPU op rows without hlo_op): count it
    # against the residual only when we cannot scope it better
    return OTHER if phase_map == {} and module is None else None


@dataclass(frozen=True)
class MeasuredTimeline:
    """Per-phase durations measured from the device trace — the same
    span schema as the modeled ``StepTimeline`` (``records`` of
    ``StepRecord``/``PhaseSpan``), but every duration is a sum of real
    device events, not a cost-model attribution."""
    phase_seconds: Dict[str, float]     # per profiled step, per device
    total_phase_seconds: Dict[str, float]   # whole capture, all devices
    steps: int                          # profiled steps totals cover
    n_devices: int                      # device rows that contributed
    n_events: int                       # device events classified
    source: str                         # trace file the events came from
    records: Tuple[StepRecord, ...]

    def comm_share(self) -> float:
        return timeline_lib.comm_share(self.phase_seconds)

    def step_seconds(self) -> float:
        return sum(self.phase_seconds.values())

    def summary(self) -> Dict[str, float]:
        out: Dict[str, float] = {
            "measured_steps": float(self.steps),
            "measured_devices": float(self.n_devices),
            "measured_events": float(self.n_events),
            "measured_step_s": self.step_seconds(),
            "measured_comm_share": self.comm_share(),
        }
        for name in MEASURED_ORDER:
            if name in self.phase_seconds:
                out[f"measured_{name}_s"] = self.phase_seconds[name]
        return out


def _synth_records(phase_seconds: Dict[str, float], steps: int
                   ) -> Tuple[StepRecord, ...]:
    """Synthetic per-step records tiling the measured phase durations in
    execution order (starts are schema filler — the trace's own
    timestamps interleave devices and are not a host timeline)."""
    records = []
    t = 0.0
    for s in range(max(1, steps)):
        spans: List[PhaseSpan] = []
        start = t
        for name in MEASURED_ORDER:
            d = phase_seconds.get(name, 0.0)
            if d > 0.0:
                spans.append(PhaseSpan(name, t, d))
                t += d
        records.append(StepRecord(step=s, start=start, duration=t - start,
                                  spans=tuple(spans)))
    return tuple(records)


def parse_trace_events(trace: Dict, *, hlo_text: Optional[str] = None,
                       steps: int = 1, n_devices: Optional[int] = None,
                       source: str = "<dict>") -> MeasuredTimeline:
    """Correlate a loaded Chrome-trace dict's device events with the
    ``obs/`` phase scopes (see module docstring).  ``n_devices`` is the
    device count the captured module ran on; when omitted it is inferred
    from distinct trace pids — correct for TPU/GPU traces (one process
    row per device) but NOT for CPU thunk traces, where every forced
    host device shares one pid and its events land on shared pool
    threads (the launcher passes the mesh size)."""
    events = trace.get("traceEvents", [])
    phase_map = hlo_phase_map(hlo_text) if hlo_text else {}
    module = hlo_module_name(hlo_text) if hlo_text else None
    procs, threads = _meta_tables(events)

    totals: Dict[str, float] = {}
    pids = set()
    n_events = 0
    for e in events:
        if e.get("ph") != "X":
            continue
        tname = threads.get((e.get("pid"), e.get("tid")), "")
        pname = procs.get(e.get("pid"), "")
        args = e.get("args") or {}
        # device rows only: a recognized device thread, or an event that
        # self-identifies with hlo_op (thunk executors rename threads
        # across TF versions; the args key is the stable signal)
        if not (_DEVICE_THREAD_RE.search(tname) or "hlo_op" in args):
            continue
        if pname and not _DEVICE_PROC_RE.search(pname):
            continue
        phase = _classify_event(e, phase_map, module)
        if phase is None:
            continue
        dur = float(e.get("dur", 0.0)) * 1e-6      # trace unit: us
        if dur <= 0.0:
            continue
        if phase == A2A:
            totals["dispatch_a2a"] = totals.get("dispatch_a2a", 0.0) \
                + dur / 2.0
            totals["combine_a2a"] = totals.get("combine_a2a", 0.0) \
                + dur / 2.0
        else:
            totals[phase] = totals.get(phase, 0.0) + dur
        pids.add((e.get("pid"), e.get("tid")))
        n_events += 1

    n_dev = max(1, int(n_devices) if n_devices
                else len({p for p, _ in pids}))
    steps = max(1, int(steps))
    per_step = {k: v / (steps * n_dev) for k, v in totals.items()}
    return MeasuredTimeline(
        phase_seconds=per_step, total_phase_seconds=totals, steps=steps,
        n_devices=n_dev, n_events=n_events, source=source,
        records=_synth_records(per_step, steps))


def parse_jax_trace(path: str, *, hlo_text: Optional[str] = None,
                    steps: int = 1, n_devices: Optional[int] = None
                    ) -> MeasuredTimeline:
    """Parse the trace a ``--profile`` run wrote under ``path`` (the
    ``jax_trace`` dir or a trace file) into a ``MeasuredTimeline``."""
    trace_file = find_trace_file(path)
    return parse_trace_events(load_trace(trace_file), hlo_text=hlo_text,
                              steps=steps, n_devices=n_devices,
                              source=trace_file)
