"""Test cells on the CPU: a checkout-shaped directory with a
``BENCHMARK.json`` whose workloads use the small configurations under
``tests/data``, and the benchmark's own metric readers and model
modules."""
import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
REPO = BENCH.parent


def make_root(tmp: Path, cells, *, extra_per_layer=()) -> Path:
    """cells: (workload, configuration, traffic, chips) tuples, the
    configuration and traffic files taken from ``tests/data``."""
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    for sub in ("configs", "traffic"):
        (tmp / BENCH.name / sub).mkdir(parents=True, exist_ok=True)
    for sub in ("metrics", "models"):
        shutil.copytree(BENCH / sub, tmp / BENCH.name / sub,
                        dirs_exist_ok=True)
    workloads = []
    for name, conf, mix, chips in cells:
        shutil.copy(DATA / f"{conf}.json",
                    tmp / BENCH.name / "configs" / f"{conf}.json")
        shutil.copy(DATA / f"{mix}.json",
                    tmp / BENCH.name / "traffic" / f"{mix}.json")
        workloads.append({"name": name, "config": conf, "traffic": mix,
                          "chips": chips, "why": "CPU test cell"})
    per_layer = [dict(m, workloads=[w["name"] for w in workloads])
                 for m in real["per_layer"]] + list(extra_per_layer)
    bench = dict(real, workloads=workloads, per_layer=per_layer)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return tmp
