"""The harness on the CPU: what it refuses, the contract's shapes, and a
cell that needs nothing but files."""
import json
import os
import re
import subprocess
import sys
import tempfile
import time

import pytest

from chipbench import harness, program
from chipbench.models import granite_moe
from chipbench.program import Refused
from chipbench.tests.cells import BENCH, DATA, REPO, make_root

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
        program.model_config(conf, granite_moe, phases=False)


def test_the_configurations_agree_with_the_program():
    for f in sorted((BENCH / "configs").glob("*.json")):
        conf = json.loads(f.read_text())
        model = harness.model_module(conf, f, BENCH / "models")
        cfg = program.model_config(conf, model, phases=False)
        assert cfg.num_layers == conf["config"]["num_hidden_layers"]


def test_a_field_the_program_no_longer_has_passes(monkeypatch):
    def dropped(cfg):
        raise AttributeError("gone")
    monkeypatch.setitem(granite_moe.PROGRAM_KEYS, "hidden_size", dropped)
    conf = _conf()
    conf["config"]["hidden_size"] = 2048
    program.model_config(conf, granite_moe, phases=False)


def _configuration_files():
    bench = _bench()
    files = [REPO / c["file"] for c in bench["configs"]]
    return files + sorted(f for f in DATA.glob("*.json")
                          if "config" in json.loads(f.read_text()))


@pytest.mark.parametrize("path", _configuration_files(),
                         ids=lambda p: p.stem)
def test_every_key_of_a_configuration_is_checked_or_stated(path):
    """Each ``config`` key is compared with the program's config by its
    model module's ``PROGRAM_KEYS``, or listed in its ``FILE_ONLY`` with
    the reason the program has no field for it."""
    conf = json.loads(path.read_text())
    model = harness.model_module(conf, path, BENCH / "models")
    assert not set(model.PROGRAM_KEYS) & set(model.FILE_ONLY)
    assert all(isinstance(why, str) and why
               for why in model.FILE_ONLY.values())
    unchecked = set(conf["config"]) - set(model.PROGRAM_KEYS) \
        - set(model.FILE_ONLY)
    assert not unchecked, f"{path.name}: {sorted(unchecked)}"
    assert set(conf["reduced"]) <= set(conf["config"])


@pytest.mark.parametrize("model,match", [
    (None, "names no model module"),
    ("no_such_moe", "names the model module 'no_such_moe'"),
])
def test_a_configuration_without_its_model_module_is_refused(
        tmp_path, model, match):
    root = make_root(tmp_path, [("t", "tiny.1dev", "tiny.lsh", 1)])
    path = root / BENCH.name / "configs" / "tiny.1dev.json"
    conf = json.loads(path.read_text())
    if model is None:
        del conf["model"]
    else:
        conf["model"] = model
    path.write_text(json.dumps(conf))
    with pytest.raises(Refused, match=match) as exc:
        harness.cell_spec("t", root)
    assert str(path) in str(exc.value)


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
SENTINEL_FLOPS = 123456789.0
NEW_MODEL = f'''
"""granite_moe's reference and keys, with a FLOP count of its own."""
from chipbench.models.granite_moe import (  # noqa: F401
    CUTS, FILE_ONLY, PROGRAM_KEYS, dims, init_params, loss_fn)


def train_flops_per_token(config, seq_len):
    return {SENTINEL_FLOPS!r}
'''


class _Kept(Exception):
    """Raised in place of keeping a traced run's files: carries its meta."""


def test_a_new_cell_needs_only_new_files(tmp_path, monkeypatch):
    """A configuration, a traffic mix, a model module and a metric reader
    that no existing file names, plus a workloads entry, make a cell that
    runs, through that model module."""
    root = make_root(tmp_path, [("tiny.new", "tiny.1dev", "tiny.lsh", 1)],
                     extra_per_layer=[{
                         "name": "step_ms", "unit": "ms", "better": "lower",
                         "source": "device_trace", "layer": "train step",
                         "moves": "tokens_per_s_chip",
                         "workloads": ["tiny.new"]}])
    bench_dir = root / BENCH.name
    (bench_dir / "metrics" / "step_ms.py").write_text(NEW_READER)
    (bench_dir / "models" / "sentinel_moe.py").write_text(NEW_MODEL)
    conf = json.loads((DATA / "tiny.1dev.json").read_text())
    conf.update(name="tiny.sentinel", model="sentinel_moe")
    (bench_dir / "configs" / "tiny.sentinel.json").write_text(
        json.dumps(conf))
    (bench_dir / "configs" / "tiny.1dev.json").unlink()
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"][0]["config"] = "tiny.sentinel"
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = harness.cell_spec("tiny.new", root)
    assert spec.model.__file__ == str(bench_dir / "models" /
                                      "sentinel_moe.py")
    assert spec.model.train_flops_per_token(conf["config"], 32) == \
        SENTINEL_FLOPS
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

    # The traced run's meta, which the per-layer readers are given (mfu's
    # FLOPs per token), comes from the cell's model module.  A CPU trace
    # has no TPU plane to reduce, so the run stops where it keeps it.
    def keep(pb, hlo, meta, dest):
        raise _Kept(meta)
    monkeypatch.setattr(harness, "keep", keep)
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    with pytest.raises(_Kept) as kept:
        harness.run("tiny.new", 2**33 + 5, 0.5, True,
                    t_start=time.perf_counter(), require_tpu=False,
                    root=root, keep_trace=str(tmp_path / "kept"))
    assert kept.value.args[0]["flops_per_token"] == SENTINEL_FLOPS


def test_an_unknown_workload_is_refused():
    with pytest.raises(Refused, match="no workload"):
        harness.cell_spec("no.such.cell")
