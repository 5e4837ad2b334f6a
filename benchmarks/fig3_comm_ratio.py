"""Paper Figure 3: all-to-all share of training time.

Two estimates per model:
 1. the paper's analytic Eq. 6 with the paper's Table-1 configs mapped to
    TPU v5e constants (197 TFLOP/s, 50 GB/s link);
 2. measured from our dry-run artifacts (collective_s / total) when
    artifacts/dryrun.json exists.
Validates the paper's claim that the share is large (~30-70%) and roughly
scale-invariant in w (Eq. 6's (w-1)/w saturates).

A third, LIVE estimate appears when a training run wrote a metrics
summary (``launch/train.py --metrics-dir``, or $REPRO_METRICS_JSON): the
``comm_share`` the run's step timeline attributed from the planner's
actual message sizes and measured wall time (docs/observability.md).
"""
from __future__ import annotations

import json
import os

from benchmarks.common import a2a_share_from_ratio, paper_comm_ratio
from repro import hw

# Paper Table 1 (hidden size h, activated experts k)
PAPER_MODELS = {
    "roberta-moe": {"h": 768, "k": 2},
    "t5-moe": {"h": 1024, "k": 2},
    "gpt-moe-15b": {"h": 768, "k": 2},
    "gpt-moe-52b": {"h": 1024, "k": 2},
    "swin-moe-l": {"h": 1536, "k": 2},
}
_PEAKS = hw.peaks(hw.V5E)
V5E = {"flops": _PEAKS.flops, "b_inter": _PEAKS.ici_bytes_per_s}


def run(out_rows):
    for name, m in PAPER_MODELS.items():
        for w in (4, 8, 16, 64):
            r = paper_comm_ratio(flops=V5E["flops"], b_inter=V5E["b_inter"],
                                 k=m["k"], w=w, h=m["h"])
            share = a2a_share_from_ratio(r)
            out_rows.append((f"fig3/eq6/{name}/w{w}", share * 1e6,
                             f"a2a_share={share:.3f}"))
    art = os.path.join(os.path.dirname(__file__), "..", "artifacts",
                       "dryrun.json")
    if os.path.exists(art):
        with open(art) as f:
            cells = json.load(f)
        for c in cells:
            if c.get("shape") == "train_4k" and "collective_s" in c \
                    and c.get("mesh_name") == "single":
                tot = c["compute_s"] + c["collective_s"]
                share = c["collective_s"] / tot if tot else 0.0
                out_rows.append(
                    (f"fig3/measured/{c['arch']}", share * 1e6,
                     f"a2a_share={share:.3f},dom={c['dominant']}"))
    live = os.environ.get("REPRO_METRICS_JSON") or os.path.join(
        os.path.dirname(__file__), "..", "artifacts", "obs",
        "metrics.json")
    if os.path.exists(live):
        with open(live) as f:
            summary = json.load(f)
        share = float(summary.get("comm_share", 0.0))
        out_rows.append(
            ("fig3/live/comm_share", share * 1e6,
             f"a2a_share={share:.3f},steps={int(summary.get('steps', 0))},"
             f"mean_step_s={summary.get('mean_step_s', 0.0):.3f}"))
    return out_rows


if __name__ == "__main__":
    rows = run([])
    for r in rows:
        print(",".join(str(x) for x in r))
