"""Measured-profile observability (docs/observability.md): trace parsing
against a committed synthetic jax-profiler Chrome trace
(tests/data/synthetic_trace.json — the CPU thunk format with
``args.hlo_op``/``hlo_module`` plus one TPU-style scope-named row),
HLO-metadata scope correlation, the structural collective fallback,
modeled-vs-measured reconciliation math, anomaly detector
trigger/no-trigger, the escalation bridge into the restart supervisor,
the BENCH_* trajectory schema + regression gate, and the drift ->
stale-calibration -> re-probe loop through the tune cache.  Subprocess:
a real ``--profile`` train run on 2 forced host devices must produce a
MeasuredTimeline (not a cost-model attribution), and the bench harness
must append trajectory rows and gate clean."""
import glob
import gzip
import json
import math
import os
import subprocess
import sys

import pytest

from repro.comm import topology
from repro.obs import anomaly as anomaly_lib
from repro.obs import benchrow
from repro.obs import events as events_lib
from repro.obs import profile as profile_lib
from repro.obs import reconcile as reconcile_lib
from repro.resilience import supervisor
from repro.tune import cache, runtime
from repro.tune.fingerprint import fingerprint_for
from repro.tune.model import CalibratedCostModel

_HERE = os.path.dirname(__file__)
_SRC = os.path.join(_HERE, "..", "src")
_ROOT = os.path.join(_HERE, "..")
_FIXTURE = os.path.join(_HERE, "data", "synthetic_trace.json")

# The compiled-HLO text the fixture's hlo_op names resolve against —
# the post-optimization format the launcher captures via
# ``step_fn.lower(...).compile().as_text()``.  ``all-to-all.7`` carries
# a partitioner-mangled op_name (".../while", no obs/ scope) exactly as
# observed on real SPMD traces: only the opcode fallback can place it.
_HLO = """\
HloModule jit_train_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

ENTRY %main.20 (p0.1: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  %gate_fusion.1 = f32[8]{0} fusion(%p0.1), kind=kLoop, calls=%fused_gate, metadata={op_name="jit(train_step)/jit(main)/obs/gate/softmax" source_file="m.py" source_line=10}
  %hash_fusion.2 = f32[8]{0} fusion(%gate_fusion.1), kind=kOutput, calls=%fused_hash, metadata={op_name="jit(train_step)/jit(main)/obs/hash_compress/dot_general" source_file="m.py" source_line=20}
  %mlp.3 = f32[8]{0} multiply(%hash_fusion.2, %hash_fusion.2), metadata={op_name="jit(train_step)/jit(main)/obs/expert_mlp/dot_general" source_file="m.py" source_line=30}
  %all-to-all.7 = f32[8]{0} all-to-all(%mlp.3), replica_groups={{0,1}}, metadata={op_name="jit(train_step)/jit(main)/while" source_file="m.py" source_line=40}
  %unmatched.11 = f32[8]{0} add(%mlp.3, %p0.1), metadata={op_name="jit(train_step)/jit(main)/transpose" source_file="m.py" source_line=50}
  ROOT %decomp.4 = f32[8]{0} add(%all-to-all.7, %unmatched.11), metadata={op_name="jit(train_step)/jit(main)/obs/decompress/add" source_file="m.py" source_line=60}
}
"""


def _fixture_trace() -> dict:
    with open(_FIXTURE) as f:
        return json.load(f)


@pytest.fixture
def mem_log():
    mem = events_lib.MemorySink()
    log = events_lib.global_log()
    log.add_sink(mem)
    yield mem
    log.remove_sink(mem)


@pytest.fixture
def tune_cache(tmp_path, monkeypatch):
    monkeypatch.setenv(cache.ENV_CACHE, str(tmp_path))
    monkeypatch.delenv(runtime.ENV_TUNE, raising=False)
    runtime._MEMO.clear()
    yield tmp_path
    runtime._MEMO.clear()


# ------------------------------------------------- HLO scope recovery --


def test_hlo_phase_map_and_module():
    assert profile_lib.hlo_module_name(_HLO) == "jit_train_step"
    pm = profile_lib.hlo_phase_map(_HLO)
    assert pm == {
        "gate_fusion.1": "gate",
        "hash_fusion.2": "hash_compress",
        "mlp.3": "expert_mlp",
        "decomp.4": "decompress",       # ROOT-prefixed instruction
    }
    # no-scope instructions (partitioner-mangled a2a, plain transpose)
    # must NOT be in the map — they resolve structurally or to "other"
    assert "all-to-all.7" not in pm and "unmatched.11" not in pm


# ----------------------------------------------------- trace parsing --


def test_parse_fixture_with_hlo_correlation():
    mt = profile_lib.parse_trace_events(
        _fixture_trace(), hlo_text=_HLO, steps=2, n_devices=2)
    # whole-capture totals (trace unit is us)
    assert mt.total_phase_seconds == pytest.approx({
        "gate": 2e-3,            # CPU fusion + TPU-style scope-named row
        "hash_compress": 2e-3,   # pool thread: "hlo_op" in args admits it
        "expert_mlp": 8e-3,
        "decompress": 1e-3,      # "%decomp.4" hlo_op: lstrip("%") joins
        "dispatch_a2a": 2e-3,    # all-to-all split evenly across the
        "combine_a2a": 2e-3,     # two MoE exchange legs
        "stage_transfer": 1e-3,  # collective-permute opcode
        "other": 3e-3,           # same-module event with no scope
    })
    # excluded: the jit__normal init event (other module), the zero-dur
    # event, the python host thread, the "C" counter row
    assert mt.n_events == 8
    assert mt.steps == 2 and mt.n_devices == 2
    # per-step per-device = totals / (steps * devices)
    assert mt.phase_seconds["expert_mlp"] == pytest.approx(8e-3 / 4)
    assert mt.step_seconds() == pytest.approx(21e-3 / 4)
    assert mt.comm_share() == pytest.approx(5.0 / 21.0)
    s = mt.summary()
    assert s["measured_steps"] == 2.0 and s["measured_devices"] == 2.0
    assert s["measured_step_s"] == pytest.approx(21e-3 / 4)
    assert s["measured_gate_s"] == pytest.approx(2e-3 / 4)
    assert s["measured_comm_share"] == pytest.approx(5.0 / 21.0)
    # records carry the modeled timeline's span schema
    assert len(mt.records) == 2
    for rec in mt.records:
        assert rec.duration == pytest.approx(mt.step_seconds())
        assert sum(sp.duration for sp in rec.spans) \
            == pytest.approx(mt.step_seconds())


def test_parse_fixture_without_hlo_structural_fallback():
    """No compiled text: named CPU ops fall into ``other`` (and without
    a module name the init-jit event cannot be excluded either), but the
    collectives still classify by opcode and the TPU-style row still
    matches its scope path."""
    mt = profile_lib.parse_trace_events(_fixture_trace())
    assert mt.total_phase_seconds == pytest.approx({
        "gate": 1e-3,                    # scope survives in the name
        "dispatch_a2a": 2e-3,
        "combine_a2a": 2e-3,
        "stage_transfer": 1e-3,
        "other": 65e-3,                  # incl. the 50ms jit__normal op
    })
    # n_devices inferred from distinct pids (TPU-trace layout): 2 here
    assert mt.n_devices == 2 and mt.steps == 1
    assert mt.step_seconds() == pytest.approx(71e-3 / 2)


def test_find_trace_file_and_gz_roundtrip(tmp_path):
    # the jax.profiler on-disk layout: <dir>/plugins/profile/<ts>/*.gz
    d = tmp_path / "jax_trace" / "plugins" / "profile" / "2026_08_07"
    d.mkdir(parents=True)
    with open(_FIXTURE, "rb") as f:
        raw = f.read()
    with gzip.open(d / "host.trace.json.gz", "wb") as f:
        f.write(raw)
    found = profile_lib.find_trace_file(str(tmp_path / "jax_trace"))
    assert found.endswith("host.trace.json.gz")
    mt = profile_lib.parse_jax_trace(
        str(tmp_path / "jax_trace"), hlo_text=_HLO, steps=2, n_devices=2)
    assert mt.source == found
    assert mt.step_seconds() == pytest.approx(21e-3 / 4)
    # a direct file path passes through untouched
    assert profile_lib.find_trace_file(found) == found
    with pytest.raises(FileNotFoundError):
        profile_lib.find_trace_file(str(tmp_path / "empty"))


# ------------------------------------------------------ reconciliation --


def test_reconcile_share_error_is_clock_invariant():
    modeled = {"gate": 0.1, "expert_mlp": 0.6, "dispatch_a2a": 0.15,
               "combine_a2a": 0.15}
    # measured = modeled * 2: absolute clock off 2x, proportions exact
    measured = {k: 2.0 * v for k, v in modeled.items()}
    rep = reconcile_lib.reconcile(modeled, measured)
    assert rep.drift_score == pytest.approx(0.0)
    assert rep.comm_drift == pytest.approx(0.0)
    assert rep.clock_ratio == pytest.approx(0.5)
    assert not rep.stale
    assert rep.comm_share_modeled == pytest.approx(0.3)
    assert rep.comm_share_measured == pytest.approx(0.3)
    assert rep.phase("gate").share_err == pytest.approx(0.0)
    assert rep.phase("gate").rel_err == pytest.approx(-0.5)


def test_reconcile_comm_drift_goes_stale():
    modeled = {"gate": 0.1, "dispatch_a2a": 0.45, "combine_a2a": 0.45}
    measured = {"gate": 0.9, "dispatch_a2a": 0.05, "combine_a2a": 0.05}
    rep = reconcile_lib.reconcile(modeled, measured)
    assert rep.comm_drift > reconcile_lib.STALE_THRESHOLD
    assert rep.stale
    m = rep.to_metrics()
    for key in ("model_drift_score", "model_comm_drift",
                "model_clock_ratio", "model_stale", "comm_share_modeled",
                "comm_share_measured", "model_err_gate",
                "model_err_dispatch_a2a"):
        assert key in m, key
    assert m["model_stale"] == 1.0
    p = rep.to_payload()
    assert p["reprobe_recommended"] is True
    assert p["phases"]["dispatch_a2a"]["share_err"] == pytest.approx(
        (0.45 - 0.05) / 0.45)


def test_reconcile_ignores_insignificant_phases():
    # stage_transfer is <1% on both sides: its ~100% share error must
    # not dominate the scores (only gate's tiny share shift remains)
    modeled = {"gate": 1.0, "stage_transfer": 0.004}
    measured = {"gate": 1.0, "stage_transfer": 1e-9}
    rep = reconcile_lib.reconcile(modeled, measured)
    assert not rep.phase("stage_transfer").significant
    assert rep.phase("stage_transfer").share_err > 0.99
    assert rep.drift_score < 0.01
    assert rep.comm_drift == 0.0 and not rep.stale


def test_emit_drift_events(mem_log):
    modeled = {"gate": 0.1, "dispatch_a2a": 0.45, "combine_a2a": 0.45}
    measured = {"gate": 0.9, "dispatch_a2a": 0.05, "combine_a2a": 0.05}
    rep = reconcile_lib.reconcile(modeled, measured)
    reconcile_lib.emit_drift_events(rep, step=7)
    evs = mem_log.of_kind("model_drift")
    summary = [e for e in evs if e.data["phase"] == "*"]
    assert len(summary) == 1 and summary[0].step == 7
    assert summary[0].data["stale"] is True
    per_phase = {e.data["phase"] for e in evs} - {"*"}
    assert "gate" in per_phase and "dispatch_a2a" in per_phase


# --------------------------------------------------- anomaly detectors --


def test_step_time_regression_fires_and_clamps_baseline():
    det = anomaly_lib.StepTimeRegression()
    # warmup absorbs the compile-dominated steps without polluting stats
    for s in range(3):
        assert det.observe(s, 99.0) is None
    for s in range(3, 9):
        assert det.observe(s, 1.0) is None
    a = det.observe(9, 10.0)
    assert a is not None and a.detector == "step_time_regression"
    assert a.baseline == pytest.approx(1.0)
    assert a.severity == pytest.approx(10.0 / 1.5)
    # the fired sample was clamped: the baseline is not inflated, so a
    # normal step stays quiet and the next hang still fires
    assert det.observe(10, 1.0) is None
    assert det.observe(11, 10.0) is not None


def test_drift_detector_frozen_baseline_and_cooldown():
    det = anomaly_lib.DriftDetector()     # window 20, warmup 3, 25% rel
    for s in range(3):
        assert det.observe(s, 0.5) is None      # warmup
    for s in range(20):
        assert det.observe(100 + s, 0.10) is None   # freezes baseline
    fired = [s for s in range(30)
             if det.observe(200 + s, 0.21) is not None]
    # rolling mean crosses +25% on the 5th drifted sample (mean 0.1275,
    # +27.5%); the cooldown then holds it quiet for 20 observations
    assert fired == [4, 25]


def test_loss_spike_nan_and_robust_z():
    det = anomaly_lib.LossSpike()
    a = det.observe(0, float("nan"))
    assert a is not None and math.isinf(a.severity)
    det = anomaly_lib.LossSpike()
    for s in range(8):
        assert det.observe(s, 1.0 + 1e-4 * s) is None
    a = det.observe(8, 100.0)
    assert a is not None and a.detector == "loss_spike"
    # the spike never entered the window: the next normal loss is quiet
    assert det.observe(9, 1.0) is None


def test_threshold_breach_needs_consecutive_steps():
    det = anomaly_lib.ThresholdBreach()   # threshold 4.0, consecutive 3
    assert det.observe(0, 5.0) is None
    assert det.observe(1, 5.0) is None
    a = det.observe(2, 5.0)
    assert a is not None and a.detector == "load_imbalance"
    assert det.observe(3, 5.0) is None    # fires once per breach run
    assert det.observe(4, 1.0) is None    # streak reset
    assert det.observe(5, 5.0) is None
    assert det.observe(6, 5.0) is None
    assert det.observe(7, 5.0) is not None


def test_persistent_straggler_accumulates_and_resets():
    det = anomaly_lib.PersistentStraggler()   # count 3 in window 50
    flags = [1, 0, 1, 0, 1]
    got = [det.observe(s, v) for s, v in enumerate(flags)]
    assert [a is not None for a in got] == [False] * 4 + [True]
    assert got[-1].value == 3.0
    # the window reset: the next fire needs a fresh accumulation
    assert det.observe(5, 1.0) is None
    assert det.observe(6, 1.0) is None
    assert det.observe(7, 1.0) is not None


def test_monitor_skips_missing_metrics_and_fans_out(mem_log):
    mon = anomaly_lib.AnomalyMonitor(
        [anomaly_lib.ThresholdBreach(threshold=1.0, consecutive=1)])
    seen = []
    mon.add_consumer(seen.append)
    assert mon.observe(0, {}) == []           # metric absent: skipped
    fired = mon.observe(1, {"load_imbalance": 2.0})
    assert len(fired) == 1 and seen == fired
    assert mon.counts() == {"load_imbalance": 1}
    evs = mem_log.of_kind("anomaly")
    assert len(evs) == 1
    assert evs[0].data["detector"] == "load_imbalance"
    assert evs[0].data["severity"] == pytest.approx(2.0)


def _anom(detector, step=0, t=0.0):
    return anomaly_lib.Anomaly(detector=detector, step=step,
                               metric="m", value=2.0, baseline=1.0,
                               severity=2.0, message="test")


def test_anomaly_escalator_persistent_pattern_exits(mem_log):
    now = [0.0]
    hits = []
    esc = supervisor.AnomalyEscalator(
        limit=3, window_s=10.0, on_escalate=hits.append,
        clock=lambda: now[0])
    # non-escalating detectors never count toward the limit
    for _ in range(5):
        assert esc.consume(_anom("loss_spike")) is False
    for t in (0.0, 1.0):
        now[0] = t
        assert esc.consume(_anom("step_time_regression")) is False
    now[0] = 2.0
    assert esc.consume(_anom("persistent_straggler", step=9)) is True
    assert esc.should_exit and len(hits) == 1
    evs = mem_log.of_kind("anomaly_escalation")
    assert len(evs) == 1 and evs[0].step == 9
    assert evs[0].data["exit_code"] == supervisor.EXIT_WATCHDOG
    # escalation fires the event once, even as anomalies keep arriving
    assert esc.consume(_anom("step_time_regression")) is True
    assert len(mem_log.of_kind("anomaly_escalation")) == 1


def test_anomaly_escalator_window_expires_old_marks():
    now = [0.0]
    esc = supervisor.AnomalyEscalator(limit=3, window_s=10.0,
                                      clock=lambda: now[0])
    for t in (0.0, 20.0, 40.0):       # each mark expires before the next
        now[0] = t
        assert esc.consume(_anom("step_time_regression")) is False
    assert not esc.should_exit


# ----------------------------------------------------- bench rows/gate --


def test_bench_row_validation():
    good = benchrow.bench_row(name="t", kind="train",
                              metrics={"mean_step_s": 1.0}, ts=1.0)
    benchrow.validate_row(good)
    with pytest.raises(ValueError, match="name"):
        benchrow.bench_row(name="bad name", kind="train",
                           metrics={"x": 1.0})
    with pytest.raises(ValueError, match="kind"):
        benchrow.bench_row(name="t", kind="decode", metrics={"x": 1.0})
    with pytest.raises(ValueError, match="finite"):
        benchrow.bench_row(name="t", kind="train",
                           metrics={"x": float("nan")})
    with pytest.raises(ValueError, match="metrics"):
        benchrow.bench_row(name="t", kind="train", metrics={})
    with pytest.raises(ValueError, match="ts"):
        benchrow.validate_row(dict(good, ts="yesterday"))


def test_append_load_roundtrip_bounds_and_corruption(tmp_path):
    out = str(tmp_path)
    for i in range(3):
        row = benchrow.bench_row(name="t", kind="train",
                                 metrics={"mean_step_s": float(i)},
                                 ts=float(i))
        path = benchrow.append_row(out, row, max_rows=2)
    assert os.path.basename(path) == "BENCH_t.json"
    assert [f for f in os.listdir(out) if f.startswith(".tmp")] == []
    rows = benchrow.load_rows(path)
    # bounded trajectory: only the newest max_rows survive
    assert [r["metrics"]["mean_step_s"] for r in rows] == [1.0, 2.0]
    # corrupt history restarts rather than raising
    with open(path, "w") as f:
        f.write("{ not json")
    benchrow.append_row(out, benchrow.bench_row(
        name="t", kind="train", metrics={"mean_step_s": 9.0}, ts=9.0))
    assert len(benchrow.load_rows(path)) == 1
    # invalid rows inside a valid doc are dropped, not raised
    with open(path) as f:
        doc = json.load(f)
    doc["rows"].append({"name": "t", "kind": "nope", "ts": 0,
                        "metrics": {"x": 1.0}})
    with open(path, "w") as f:
        json.dump(doc, f)
    assert len(benchrow.load_rows(path)) == 1


def _rows(*metric_dicts):
    return [benchrow.bench_row(name="t", kind="train", metrics=m,
                               ts=float(i))
            for i, m in enumerate(metric_dicts)]


def test_compare_gate_is_direction_aware_and_tolerant():
    base = {"mean_step_s": 1.0, "tokens_per_s_device": 100.0,
            "model_comm_drift": 0.9}
    # within tolerance (+20% step time < 35%): ok
    cmp_ = benchrow.compare(_rows(base, base, dict(
        base, mean_step_s=1.2)))
    assert cmp_.ok and cmp_.n_baseline == 2
    # drift metrics are recorded but never gated
    assert "model_comm_drift" not in {d.metric for d in cmp_.deltas}
    # past tolerance on both gated directions: step time UP and
    # throughput DOWN both read as regressions
    cmp_ = benchrow.compare(_rows(base, base, dict(
        base, mean_step_s=2.0, tokens_per_s_device=50.0)))
    assert not cmp_.ok
    assert {d.metric for d in cmp_.regressions} \
        == {"mean_step_s", "tokens_per_s_device"}
    assert "REGRESSED" in cmp_.describe()
    # a throughput IMPROVEMENT is negative worse-direction change
    cmp_ = benchrow.compare(_rows(base, dict(
        base, tokens_per_s_device=200.0)))
    delta = {d.metric: d for d in cmp_.deltas}["tokens_per_s_device"]
    assert delta.rel_change == pytest.approx(-1.0) and not delta.regressed
    # first recorded run: nothing to gate
    assert benchrow.compare(_rows(base)).ok
    assert "no baseline" in benchrow.compare(_rows(base)).describe()


# ------------------------------------- drift -> stale calibration loop --


def _topo():
    return topology.Topology(axis_sizes=(("data", 2), ("model", 8)),
                             node_size=4)


def _stale_payload(reprobe=True):
    modeled = {"gate": 0.1, "dispatch_a2a": 0.45, "combine_a2a": 0.45}
    measured = {"gate": 0.9, "dispatch_a2a": 0.05, "combine_a2a": 0.05}
    rep = reconcile_lib.reconcile(modeled, measured)
    assert rep.stale is reprobe
    return rep.to_payload()


def test_record_drift_annotates_existing_entry_only(tune_cache):
    fp = fingerprint_for(None, _topo(), "model")
    # nothing calibrated means nothing to go stale
    assert cache.record_drift(fp, _stale_payload()) is None
    cache.store(fp, CalibratedCostModel(key=fp.key(),
                                        intra_bw=1e9).to_payload())
    path = cache.record_drift(fp, _stale_payload())
    assert path == cache.entry_path(fp)
    entry = cache.load(fp)
    assert entry["drift"]["reprobe_recommended"] is True
    assert "recorded_unix" in entry["drift"]
    # the annotated entry still parses as a calibration
    assert CalibratedCostModel.from_payload(fp.key(), entry) is not None


def test_runtime_surfaces_stale_once_per_file_version(tune_cache,
                                                      mem_log):
    fp = fingerprint_for(None, _topo(), "model")
    cache.store(fp, CalibratedCostModel(key=fp.key(),
                                        intra_bw=1e9).to_payload())
    model, stale = runtime._load_entry(fp)
    assert model is not None and not stale
    assert mem_log.of_kind("tune_stale") == []
    cache.record_drift(fp, _stale_payload())
    model, stale = runtime._load_entry(fp)
    # stale means mis-calibrated, not corrupt: still usable
    assert model is not None and stale
    evs = mem_log.of_kind("tune_stale")
    assert len(evs) == 1 and evs[0].data["fingerprint"] == fp.key()
    assert evs[0].data["comm_drift"] > reconcile_lib.STALE_THRESHOLD
    # memoized per file version: no event flood on per-step loads
    runtime._load_entry(fp)
    assert len(mem_log.of_kind("tune_stale")) == 1


def test_ensure_calibrated_keeps_stale_model_without_probe_rights(
        tune_cache, monkeypatch, mesh):
    from repro.comm.topology import build_topology
    monkeypatch.setenv(runtime.ENV_TUNE, "cache")
    topo = build_topology(mesh, axis_name="model")
    fp = fingerprint_for(mesh, topo, "model")
    cache.store(fp, CalibratedCostModel(key=fp.key(),
                                        intra_bw=7e9).to_payload())
    cache.record_drift(fp, _stale_payload())
    runtime._MEMO.clear()
    # mode=cache may not probe: the stale model is still returned
    model = runtime.ensure_calibrated(mesh)
    assert model is not None and model.intra_bw == 7e9


# ------------------------------------------------- subprocess: e2e -----


def test_train_profile_requires_metrics_dir():
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "granite-moe-3b-a800m", "--smoke", "--steps", "2",
         "--profile", "1"],
        capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=_SRC), timeout=120)
    assert out.returncode == 2
    assert "--profile requires --metrics-dir" in out.stderr


def test_train_profile_writes_measured_timeline_2dev(tmp_path):
    """--profile end to end: the trace capture must yield MEASURED
    per-phase seconds (device events, not the cost-model attribution)
    plus the reconciliation metrics and model_drift events."""
    mdir = str(tmp_path / "obs")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=_SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "granite-moe-3b-a800m", "--smoke", "--steps", "3", "--batch",
         "4", "--seq", "32", "--mesh-model", "2", "--log-every", "1",
         "--metrics-dir", mdir, "--profile", "1"],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]

    with open(os.path.join(mdir, "metrics.json")) as f:
        m = json.load(f)
    assert m["measured_steps"] == 1.0
    assert m["measured_devices"] == 2.0
    assert m["measured_events"] > 0
    assert m["measured_step_s"] > 0.0
    assert 0.0 <= m["measured_comm_share"] <= 1.0
    assert m["measured_expert_mlp_s"] > 0.0     # HLO scopes correlated
    # reconciliation against the modeled attribution rode along
    assert "model_drift_score" in m and "model_clock_ratio" in m
    assert m["comm_share_modeled"] != m["comm_share_measured"]

    evs = events_lib.read_jsonl(os.path.join(mdir, "events.jsonl"))
    drift = [e for e in evs if e.kind == "model_drift"]
    assert any(e.data["phase"] == "*" for e in drift)


def test_train_profile_host_spans_and_step_scopes_2dev(tmp_path):
    """--profile 2 over four smoke steps with a checkpoint: the launcher's
    host spans (one step span per step, ``batch``, ``dispatch``, ``wait``
    and ``checkpoint``) sit on the trace's /host:CPU plane, and the
    measured timeline reports the step-level scopes."""
    from jax.profiler import ProfileData

    mdir = str(tmp_path / "obs")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=_SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "granite-moe-3b-a800m", "--smoke", "--steps", "4", "--batch",
         "4", "--seq", "32", "--mesh-model", "2", "--log-every", "1",
         "--metrics-dir", mdir, "--profile", "2",
         "--ckpt", str(tmp_path / "ckpt"), "--ckpt-every", "2"],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]

    pb = profile_lib.find_trace_file(os.path.join(mdir, "jax_trace"))
    pb = glob.glob(os.path.join(os.path.dirname(pb), "*.xplane.pb"))
    spans = {}
    for plane in ProfileData.from_file(pb[0]).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    spans[e.name] = spans.get(e.name, 0) + 1
    # steps 1 and 2 profiled; the checkpoint after step 1
    for name in ("train", "batch", "dispatch", "wait"):
        assert spans.get(name) == 2, (name, spans.get(name))
    assert spans.get("checkpoint") == 1

    with open(os.path.join(mdir, "metrics.json")) as f:
        m = json.load(f)
    for phase in profile_lib.STEP_PHASES:
        assert m[f"measured_{phase}_s"] > 0.0, phase


def test_bench_harness_trajectory_and_gate_2dev(tmp_path):
    """Two harness invocations: rows append to one BENCH_* trajectory,
    and the second run's gate compares against the first and passes."""
    out_dir = str(tmp_path / "bench")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=_SRC + os.pathsep + _ROOT)
    argv = [sys.executable, "-m", "benchmarks.bench", "--out", out_dir,
            "--steps", "3", "--batch", "4", "--seq", "32"]
    for extra in ([], ["--gate"]):
        out = subprocess.run(argv + extra, capture_output=True,
                             text=True, env=env, timeout=900)
        assert out.returncode == 0, out.stderr[-3000:]
    assert "latest vs median of 1 previous run(s)" in out.stdout
    rows = benchrow.load_rows(benchrow.bench_file(out_dir, "train_smoke"))
    assert len(rows) == 2
    for row in rows:
        assert row["kind"] == "train"
        assert row["metrics"]["mean_step_s"] > 0.0
        assert row["metrics"]["tokens_per_s_device"] > 0.0
        assert 0.0 <= row["metrics"]["comm_share_modeled"] <= 1.0
        assert 0.0 < row["metrics"]["compression_rate"] <= 1.0
