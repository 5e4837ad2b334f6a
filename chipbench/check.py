"""The comparison that decides ``correct``.

The numbers, each a gap between the program's readings over the check
steps and the plain reference's on the same weights and batches:

* ``loss_gap``: the largest relative gap of the steps' losses.
* ``grad_gap``: over the float leaves, the largest gap between the
  program's and the reference's norm of the first gradient as the
  optimiser takes it (clipped; recovered from Adam's first moment after
  one step), over the larger of the reference's norm of that leaf and of
  the median leaf.
* ``change_gap``: the same for the norm of each leaf's change over the
  check steps.  Leaves whose reference gradient is under a thousandth of
  the median leaf's move by weight decay and rounding alone (the LSH
  rotations, which take no gradient) and are left out.
* ``moment_gap``: over the float leaves, the largest norm of the
  difference of the first moments after one step, over the same
  denominator; read only where both sides kept their moments.

A configuration's ``limits`` name the numbers it compares and the limit
of each (PERF.md gives the readings each was set from); ``correct``
holds where every one is within its limit.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List

STILL = 1e-3          # a leaf whose reference gradient is under this share
#                       of the median leaf's does not count in change_gap


def _leaf_gaps(prog: List[float], ref: List[float],
               keep: List[bool]) -> List[float]:
    """Each kept leaf's gap of norms over the larger of its reference norm
    and the median kept leaf's (a left-out leaf reads 0)."""
    kept = [r for r, k in zip(ref, keep) if k]
    floor = statistics.median(kept) if kept else 0.0
    out = []
    for p, r, k in zip(prog, ref, keep):
        denom = max(abs(r), floor)
        if not k:
            gap = 0.0
        elif not math.isfinite(p):
            gap = math.inf
        else:
            gap = abs(p - r) / denom if denom > 0 else (0.0 if p == r
                                                         else math.inf)
        out.append(gap)
    return out


def _worst(prog: List[float], ref: List[float], keep: List[bool]) -> float:
    return max(_leaf_gaps(prog, ref, keep), default=0.0)


def _moved(ref: Dict) -> List[bool]:
    g_ref = ref["grad_norms"]
    g_med = statistics.median(g_ref)
    return [g >= STILL * g_med for g in g_ref]


def worst_leaves(prog: Dict, ref: Dict) -> Dict[str, list]:
    """The leaf behind each of grad_gap and change_gap, with its gap."""
    out = {}
    for key, keep in (("grad_norms", [True] * len(ref["grad_norms"])),
                      ("change_norms", _moved(ref))):
        g = _leaf_gaps(prog[key], ref[key], keep)
        i = max(range(len(g)), key=g.__getitem__)
        out[key] = [ref["leaves"][i], g[i]]
    return out


def gaps(prog: Dict, ref: Dict) -> Dict[str, float]:
    """prog/ref: readings with ``losses``, ``grad_norms``,
    ``change_norms`` (float leaves in the same order)."""
    if prog.get("leaves", ref["leaves"]) != ref["leaves"]:
        raise ValueError("program and reference leaves differ")
    loss = max((abs(p - r) / abs(r) if math.isfinite(p) else math.inf)
               for p, r in zip(prog["losses"], ref["losses"]))
    g_ref = ref["grad_norms"]
    out = {"loss_gap": loss,
           "grad_gap": _worst(prog["grad_norms"], g_ref,
                              [True] * len(g_ref)),
           "change_gap": _worst(prog["change_norms"], ref["change_norms"],
                                _moved(ref))}
    if "moment" in prog and "moment" in ref:
        out["moment_gap"] = max(moment_gaps(prog["moment"], ref["moment"]))
    return out


def judge(g: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number the configuration gives a limit is within it."""
    return all(g[n] <= limit for n, limit in limits.items())


def moment_gaps(prog: List, ref: List) -> List[float]:
    """Each float leaf's norm of the difference of the first moments
    after one step (the clipped first gradient, scaled alike on both
    sides), over the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    import numpy as np
    norms = [float(np.linalg.norm(r)) for r in ref]
    floor = statistics.median(norms)
    return [float(np.linalg.norm(p - r)) / max(n, floor, 1e-30)
            for p, r, n in zip(prog, ref, norms)]
