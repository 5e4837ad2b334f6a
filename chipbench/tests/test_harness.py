"""The harness on the CPU: what it refuses, the contract's shapes, and a
cell that needs nothing but files."""
import json
import os
import re
import subprocess
import sys
import time

import pytest

from chipbench import harness, program
from chipbench.program import Refused
from chipbench.tests.cells import BENCH, REPO, make_root

CELL = json.loads((REPO / "BENCHMARK.json").read_text())["workloads"][0][
    "name"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _bench():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def _run_cli(env_extra, timeout=120):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(program.ENV_PREFIX)}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout)


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    p = _run_cli({})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_a_program_switch_in_the_environment_is_refused():
    p = _run_cli({"REPRO_KERNEL_BACKEND": "reference"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "REPRO_KERNEL_BACKEND" in p.stderr


def _conf():
    return json.loads((BENCH / "configs" /
                       "granite-moe-3b-a800m.ep4.json").read_text())


@pytest.mark.parametrize("section,key,value", [
    ("config", "hidden_size", 2048),
    ("config", "num_experts_per_tok", 4),
    ("config", "capacity_factor", 2.0),
    ("lsh", "num_hashes", 4),
    ("lsh", "compression_rate", 0.1),
    ("config", "attention_multiplier", 0.015625),
    ("config", "logits_scaling", 6.0),
])
def test_a_configuration_the_program_does_not_state_is_refused(
        section, key, value):
    conf = _conf()
    conf[section][key] = value
    with pytest.raises(Refused, match=key):
        program.model_config(conf, phases=False)


def test_the_configurations_agree_with_the_program():
    for f in sorted((BENCH / "configs").glob("*.json")):
        conf = json.loads(f.read_text())
        cfg = program.model_config(conf, phases=False)
        assert cfg.num_layers == conf["config"]["num_hidden_layers"]


def test_a_field_the_program_no_longer_has_passes(monkeypatch):
    def dropped(cfg):
        raise AttributeError("gone")
    monkeypatch.setitem(program._MODEL_KEYS, "hidden_size", dropped)
    conf = _conf()
    conf["config"]["hidden_size"] = 2048
    program.model_config(conf, phases=False)


def test_names_and_units_keep_to_the_contract():
    b = _bench()
    names = [c["name"] for c in b["configs"]] + \
        [w["name"] for w in b["workloads"]] + \
        [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in b["workloads"]] + \
            [k for c in b["configs"] for k in c["reduced"]]:
        assert NAME.match(n), n
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    assert {"setup_s"} <= {m["name"] for m in b["end_to_end"]}


def test_every_name_finds_its_file():
    b = _bench()
    cells = {w["name"] for w in b["workloads"]}
    configs = {c["name"]: c for c in b["configs"]}
    for c in configs.values():
        conf = json.loads((REPO / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
    for w in b["workloads"]:
        assert w["config"] in configs
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        spec = harness.cell_spec(w["name"])
        assert {m["name"] for m in spec.end_to_end} == {
            m["name"] for m in b["end_to_end"]}
        assert spec.per_layer
    moves = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in moves
        assert set(m["workloads"]) <= cells
        assert callable(harness.metric_reader(BENCH / "metrics", m["name"]))


NEW_READER = '''
def read(ctx):
    return 1e3 * ctx.trace.window_s / ctx.steps
'''


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A configuration, a traffic mix and a metric reader that no existing
    file names, plus a workloads entry, make a cell that runs."""
    root = make_root(tmp_path, [("tiny.new", "tiny.1dev", "tiny.lsh", 1)],
                     extra_per_layer=[{
                         "name": "step_ms", "unit": "ms", "better": "lower",
                         "source": "device_trace", "layer": "train step",
                         "moves": "tokens_per_s_chip",
                         "workloads": ["tiny.new"]}])
    (root / "chipbench" / "metrics" / "step_ms.py").write_text(NEW_READER)
    spec = harness.cell_spec("tiny.new", root)
    assert "step_ms" in [m["name"] for m in spec.per_layer]
    read = harness.metric_reader(spec.metrics_dir, "step_ms")
    ctx = type("Ctx", (), {"steps": 4,
                           "trace": type("T", (), {"window_s": 2.0})})
    assert read(ctx) == 500.0
    res, notes = harness.run("tiny.new", 2**33 + 5, 0.5, False,
                             t_start=time.perf_counter(),
                             require_tpu=False, root=root)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"tokens_per_s_chip", "peak_hbm_gib",
                                   "setup_s"}
    assert list(res)[-1] == "check"
    assert len(notes["window losses"]) == res["attempted"]
    assert res["device"]["platform"] == "cpu"


def test_an_unknown_workload_is_refused():
    with pytest.raises(Refused, match="no workload"):
        harness.cell_spec("no.such.cell")
