"""Structured observability layer (docs/observability.md).

In-process: MetricBag pytree/scan/merge invariants, event JSONL and
Chrome-trace round-trips, 1F1B a2a-slot classification vs
``Schedule.a2a_slot``, planner comm_plan events (incl. degrades), phase
scope gating.  Subprocess on 8 forced host devices (the
tests/test_pipeline.py pattern): bitwise loss/grad parity with obs on vs
off, and the HLO contract — obs off compiles with zero "obs/" metadata
and the same all-to-all population as obs on.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.comm.topology import Topology
from repro.obs import events as events_lib
from repro.obs import export as export_lib
from repro.obs import metrics as metrics_lib
from repro.obs import timeline as timeline_lib
from repro.obs import tracing as tracing_lib
from repro.runtime.pipeline_schedule import build_1f1b

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=_SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ------------------------------------------------------------ MetricBag --


def test_metric_bag_counter_gauge_semantics():
    bag = metrics_lib.MetricBag.zeros()
    assert set(bag.names) == {n for n, _ in metrics_lib.MOE_SCHEMA}
    bag = bag.inc("wire_bytes", 10.0).set("load_imbalance", 2.0)
    assert float(bag.get("wire_bytes")) == 10.0
    with pytest.raises(ValueError):
        bag.inc("load_imbalance", 1.0)      # gauges don't accumulate
    with pytest.raises(KeyError):
        bag.get("nope")
    newer = metrics_lib.MetricBag.zeros() \
        .inc("wire_bytes", 5.0).set("load_imbalance", 3.0)
    merged = bag.merge(newer)
    assert float(merged.get("wire_bytes")) == 15.0      # counter adds
    assert float(merged.get("load_imbalance")) == 3.0   # gauge overwrites
    flat = merged.as_metrics()
    assert flat["obs_wire_bytes"] == merged.get("wire_bytes")


def test_metric_bag_is_stable_pytree():
    import jax
    a = metrics_lib.MetricBag.zeros()
    b = a.inc("raw_bytes", 7.0)
    ta = jax.tree_util.tree_structure(a)
    tb = jax.tree_util.tree_structure(b)
    assert ta == tb                        # same schema -> same treedef
    leaves, treedef = jax.tree_util.tree_flatten(b)
    assert len(leaves) == len(metrics_lib.MOE_SCHEMA)
    rt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert float(rt.get("raw_bytes")) == 7.0
    doubled = jax.tree.map(lambda x: x * 2, b)
    assert float(doubled.get("raw_bytes")) == 14.0


def test_metric_bag_scan_carry():
    """The model-stack scan contract: a bag carried through lax.scan with
    merge per step accumulates counters and keeps the last gauge."""
    import jax
    import jax.numpy as jnp

    def body(carry, x):
        step = metrics_lib.MetricBag.zeros() \
            .inc("wire_bytes", x).set("slot_occupancy", x)
        return metrics_lib.merge_stat(carry, step), None

    out, _ = jax.lax.scan(body, metrics_lib.MetricBag.zeros(),
                          jnp.array([1.0, 2.0, 3.0]))
    assert float(out.get("wire_bytes")) == 6.0
    assert float(out.get("slot_occupancy")) == 3.0


def test_merge_stat_legacy_vector_overwrites():
    import jax.numpy as jnp
    old = jnp.array([-1, 0, 0, -1], jnp.int32)
    new = jnp.array([2, 1, 0, 3], jnp.int32)
    assert (metrics_lib.merge_stat(old, new) == new).all()
    bag = metrics_lib.MetricBag.zeros().inc("wire_bytes", 1.0)
    assert metrics_lib.merge_stat(old, bag) is bag  # bag replaces vector
    assert not metrics_lib.is_bag(new)
    assert metrics_lib.is_bag(bag)


# --------------------------------------------------------------- events --


def test_event_jsonl_round_trip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = events_lib.EventLog(strict=True)
    sink = events_lib.JsonlSink(path)
    log.add_sink(sink)
    log.emit("comm_plan", algorithm="flat", degraded=False, axis="model")
    log.emit("straggler", step=7, dt=3.0, ema=1.0, factor=2.0)
    sink.close()
    evs = events_lib.read_jsonl(path)
    assert [e.kind for e in evs] == ["comm_plan", "straggler"]
    assert evs[0].data["algorithm"] == "flat"
    assert evs[1].step == 7 and evs[1].data["dt"] == 3.0
    # to_json/from_json is loss-free for flat JSON-typed data
    again = events_lib.Event.from_json(evs[0].to_json())
    assert again == evs[0]


def test_event_log_no_sinks_is_noop_and_sink_errors_swallowed():
    log = events_lib.EventLog()
    assert log.emit("anything", x=1) is None
    assert not log.active

    def bad_sink(ev):
        raise RuntimeError("boom")

    log.add_sink(bad_sink)
    assert log.emit("anything", x=1) is not None    # swallowed
    strict = events_lib.EventLog(strict=True)
    strict.add_sink(bad_sink)
    with pytest.raises(RuntimeError):
        strict.emit("anything", x=1)


def test_console_sink_renders_known_kinds(capsys):
    log = events_lib.EventLog(strict=True)
    log.add_sink(events_lib.ConsoleSink())
    log.emit("comm_plan", algorithm="hierarchical", degraded=False,
             axis="model", reason="axis factors (2, 4)")
    log.emit("step", step=3, loss=1.5, ce=1.2, lr=1e-3, dt=0.5, skips=0,
             comm="flat/bf16")
    log.emit("error", message="bad mesh")
    cap = capsys.readouterr()
    assert "[comm] plan: hierarchical" in cap.out
    assert "step 3 loss 1.5000" in cap.out and "comm=flat/bf16" in cap.out
    assert "error: bad mesh" in cap.err


def test_planner_emits_comm_plan_event_on_degrade():
    from repro.comm import planner
    from repro.configs.base import CommConfig
    mem = events_lib.MemorySink()
    log = events_lib.global_log()
    log.add_sink(mem)
    try:
        # a fresh axis name so other tests' plans can't pre-populate the
        # dedup cache; node_size=0 makes hierarchical unfactorable
        topo = Topology(axis_sizes=(("obsx", 4),), node_size=0)
        planner.plan_collectives(
            comm=CommConfig(a2a_impl="hierarchical"), topology=topo,
            msg_bytes=1 << 20, axis_name="obsx")
        degr = [e for e in mem.of_kind("comm_plan") if e.data["degraded"]]
        assert degr, [e.data for e in mem.events]
        assert degr[-1].data["algorithm"] == "flat"
        assert "degraded" in degr[-1].data["reason"]
        # identical re-plan is deduplicated: no new event
        n = len(mem.events)
        planner.plan_collectives(
            comm=CommConfig(a2a_impl="hierarchical"), topology=topo,
            msg_bytes=1 << 20, axis_name="obsx")
        assert len(mem.events) == n
    finally:
        log.remove_sink(mem)


# -------------------------------------------------------------- tracing --


def test_phase_scope_gated():
    import contextlib
    assert not tracing_lib.active()
    assert isinstance(tracing_lib.phase_scope("obs/gate"),
                      contextlib.nullcontext)
    with tracing_lib.activate(True):
        assert tracing_lib.active()
        assert not isinstance(tracing_lib.phase_scope("obs/gate"),
                              contextlib.nullcontext)
        with tracing_lib.activate(False):   # stack: inner wins
            assert not tracing_lib.active()
    assert not tracing_lib.active()


def test_phase_scope_names_land_in_lowered_text_only_when_active():
    import jax
    import jax.numpy as jnp

    def make_f():                      # fresh identity per lowering so
        def f(x):                      # jit's trace cache can't reuse the
            with tracing_lib.phase_scope(tracing_lib.PH_GATE):  # other mode
                return x * 2.0
        return f

    off = jax.jit(make_f()).lower(jnp.ones((4,)))
    assert "obs/" not in off.as_text()
    assert "obs/" not in off.compile().as_text()
    with tracing_lib.activate(True):
        on = jax.jit(make_f()).lower(jnp.ones((4,)))
    # the scope name lands in compiled-HLO op metadata
    assert "obs/gate" in on.compile().as_text()


# ------------------------------------------------------------- timeline --


@pytest.mark.parametrize("S,M", [(2, 2), (2, 4), (3, 5), (4, 8)])
def test_classify_a2a_matches_schedule_slots(S, M):
    sched = build_1f1b(S, M)
    slots = timeline_lib.classify_a2a(sched)
    assert len(slots) == S * M
    for a in slots:
        assert a.tick == sched.a2a_slot(a.stage, a.microbatch)
        if (a.stage, a.microbatch) == (0, 0):
            assert a.status == timeline_lib.A2A_COLD_START
            assert not a.hidden
        elif sched.grid[a.stage][a.tick] is None:
            assert a.status == timeline_lib.A2A_BUBBLE and a.hidden
        else:
            # the schedule contract: never the unit's own microbatch
            assert sched.grid[a.stage][a.tick][1] != a.microbatch
            assert a.status == timeline_lib.A2A_OVERLAP and a.hidden


def test_reconstruct_grid_tiles_the_step():
    sched = build_1f1b(2, 4)
    units = timeline_lib.reconstruct_grid(sched, start=100.0, duration=1.0)
    occupied = sum(1 for s in range(sched.stages)
                   for u in sched.grid[s] if u is not None)
    assert len(units) == occupied == 2 * 2 * 4   # F and B per (stage, mb)
    tick_s = 1.0 / sched.ticks
    for u in units:
        assert u.start == pytest.approx(100.0 + u.tick * tick_s)
        assert u.duration == pytest.approx(tick_s)
        assert 100.0 <= u.start < 101.0


def _fake_timeline(weights, durations):
    """A StepTimeline driven by a deterministic fake clock."""
    t = [0.0]

    def clock():
        return t[0]

    tl = timeline_lib.StepTimeline(phase_seconds=weights, clock=clock,
                                   wall=clock)
    for i, d in enumerate(durations):
        tl.start(i)
        t[0] += d
        tl.stop()
    return tl


def test_step_timeline_attribution_and_summary():
    weights = {"dispatch_a2a": 3.0, "expert_mlp": 6.0, "combine_a2a": 3.0}
    tl = _fake_timeline(weights, [1.0, 2.0])
    assert len(tl.records) == 2
    rec = tl.records[1]
    ps = rec.phase_seconds()
    assert ps["expert_mlp"] == pytest.approx(1.0)
    assert sum(ps.values()) == pytest.approx(rec.duration)  # 100% coverage
    assert tl.comm_share() == pytest.approx(0.5)
    assert tl.comm_seconds() == pytest.approx(1.5)
    assert tl.mean_step_seconds() == pytest.approx(1.5)
    s = tl.summary()
    assert s["steps"] == 2.0 and s["comm_share"] == pytest.approx(0.5)


def test_model_phase_seconds_covers_phases_and_comm_share():
    """The live fig3 weights: every MoE phase priced, comm share in
    (0, 1), and the attribution totals a positive step time."""
    from repro.comm import planner
    from repro.configs.base import CommConfig
    from repro.configs.registry import get_smoke_config
    cfg = get_smoke_config("granite-moe-3b-a800m")
    # seed the "model"-axis plan so the weights don't depend on which
    # tests (if any) planned it earlier in the process
    planner.plan_collectives(
        comm=CommConfig(), msg_bytes=1 << 20, axis_name="model",
        topology=Topology(axis_sizes=(("model", 4),), node_size=0))
    ps = timeline_lib.model_phase_seconds(cfg, None, batch=8, seq=32)
    assert set(ps) == set(timeline_lib.PHASE_ORDER)
    for p in ("gate", "hash_compress", "dispatch_a2a", "expert_mlp",
              "combine_a2a", "decompress"):
        assert ps[p] > 0.0, p
    assert 0.0 < timeline_lib.comm_share(ps) < 1.0
    assert sum(ps.values()) > 0.0


# --------------------------------------------------------------- export --


def test_chrome_trace_round_trip_and_coverage(tmp_path):
    weights = {"dispatch_a2a": 1.0, "expert_mlp": 2.0, "combine_a2a": 1.0}
    tl = _fake_timeline(weights, [1.0, 1.0])
    evs = [events_lib.Event("comm_plan", ts=0.5,
                            data={"algorithm": "flat"})]
    sched = build_1f1b(2, 4)
    path = str(tmp_path / "trace.json")
    export_lib.write_chrome_trace(path, tl, evs, schedule=sched)
    trace = export_lib.load_chrome_trace(path)
    assert export_lib.span_coverage(trace) >= 0.95
    names = {e.get("name") for e in trace["traceEvents"]}
    assert {"step 0", "step 1", "dispatch_a2a", "expert_mlp",
            "combine_a2a", "comm_plan"} <= names
    # pipeline rows: one span per non-bubble unit per step, a2a markers
    # carry the Schedule.a2a_slot classification
    stage_rows = [e for e in trace["traceEvents"]
                  if e.get("tid", 0) >= export_lib.TID_STAGE0]
    units = [e for e in stage_rows if e["ph"] == "X"]
    markers = [e for e in stage_rows if e["ph"] == "i"]
    occupied = sum(1 for s in range(2) for u in sched.grid[s]
                   if u is not None)
    assert len(units) == occupied * len(tl.records)
    assert len(markers) == 2 * 4 * len(tl.records)
    for m in markers:
        a = m["args"]
        assert a["tick"] == sched.a2a_slot(a["stage"], a["microbatch"])
        assert a["status"] in (timeline_lib.A2A_BUBBLE,
                               timeline_lib.A2A_OVERLAP,
                               timeline_lib.A2A_COLD_START)


def test_write_metrics_json(tmp_path):
    tl = _fake_timeline({"dispatch_a2a": 1.0, "expert_mlp": 1.0}, [2.0])
    path = str(tmp_path / "metrics.json")
    export_lib.write_metrics_json(path, tl, extra={"loss": 1.25})
    with open(path) as f:
        m = json.load(f)
    assert m["steps"] == 1.0 and m["loss"] == 1.25
    assert m["comm_share"] == pytest.approx(0.5)
    assert m["weight_expert_mlp"] == pytest.approx(0.5)


# --------------------------------------- multi-device numerics contract --


def test_obs_bitwise_parity_and_hlo_contract_8dev():
    """On a (2 data x 4 model) mesh: enabling ObsConfig leaves loss AND
    gradients bitwise unchanged; disabling it leaves zero "obs/" scope
    metadata in the compiled HLO and the identical all-to-all population
    (the metric outputs add only scalar reductions)."""
    out = _run("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.compat import set_mesh
        from repro.configs.registry import get_smoke_config
        from repro.configs.base import ObsConfig
        from repro.launch import mesh as mesh_lib
        from repro.launch import hlo_structural
        from repro.models import model as model_lib

        cfg = get_smoke_config("granite-moe-3b-a800m")
        mesh = mesh_lib.make_host_mesh(2, 1, 4)
        params = model_lib.init_params(jax.random.PRNGKey(0), cfg, mesh)
        B, S = 8, 32
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                         cfg.vocab_size),
            "labels": jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                         cfg.vocab_size)}

        def grad_fn(c):
            def loss(p):
                return model_lib.loss_fn(p, c, mesh, batch)
            return jax.value_and_grad(loss, has_aux=True, allow_int=True)

        cfg_on = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, obs=ObsConfig(enabled=True)))
        with set_mesh(mesh):
            (l0, m0), g0 = jax.jit(grad_fn(cfg))(params)
            (l1, m1), g1 = jax.jit(grad_fn(cfg_on))(params)
            low_off = jax.jit(grad_fn(cfg)).lower(params)
            low_on = jax.jit(grad_fn(cfg_on)).lower(params)
            hlo_off = low_off.compile().as_text()
            hlo_on = low_on.compile().as_text()
        assert (jnp.asarray(l0) == jnp.asarray(l1)).all(), (l0, l1)
        same = jax.tree_util.tree_all(jax.tree.map(
            lambda a, b: bool((a == b).all()), g0, g1))
        assert same, "gradients differ with obs on"
        for k in ("obs_wire_bytes", "obs_raw_bytes", "obs_load_imbalance",
                  "obs_drop_fraction", "obs_slot_occupancy",
                  "obs_compression_rate"):
            assert k in m1, sorted(m1)
            assert k not in m0
        assert float(m1["obs_wire_bytes"]) > 0.0
        assert 0.0 < float(m1["obs_compression_rate"]) <= 1.0

        assert "obs/" not in low_off.as_text()
        assert "obs/" not in hlo_off
        assert "obs/" in hlo_on        # scope names in HLO op metadata
        st_off = hlo_structural.analyze_text(hlo_off)
        st_on = hlo_structural.analyze_text(hlo_on)
        a2a_off = st_off.collective_counts.get("all-to-all", 0)
        assert a2a_off > 0
        assert st_on.collective_counts.get("all-to-all", 0) == a2a_off
        print("PARITY", float(l0))
    """)
    assert "PARITY" in out


def test_obs_pipeline_parity_and_bubble_grid_8dev():
    """pipe=2 x model=4: bitwise loss/grad parity with obs on, and the
    exported trace's a2a markers match Schedule.a2a_slot on the live
    schedule."""
    out = _run("""
        import dataclasses, json, os, tempfile
        import jax, jax.numpy as jnp
        from repro.compat import set_mesh
        from repro.configs.registry import get_smoke_config
        from repro.configs.base import ObsConfig
        from repro.launch import mesh as mesh_lib
        from repro.models import model as model_lib
        from repro.obs import events as events_lib
        from repro.obs import export as export_lib
        from repro.obs import timeline as timeline_lib
        from repro.runtime import pipeline_schedule as pipe_lib

        cfg = get_smoke_config("granite-moe-3b-a800m")
        cfg = dataclasses.replace(cfg, pipeline_microbatches=4)
        mesh = mesh_lib.make_host_mesh(1, 2, 4)
        params = model_lib.init_params(jax.random.PRNGKey(0), cfg, mesh)
        B, S = 8, 32
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                         cfg.vocab_size),
            "labels": jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                         cfg.vocab_size)}

        def grads_for(c):
            gf = pipe_lib.make_pipeline_grad_fn(c, mesh)
            with set_mesh(mesh):
                return jax.jit(gf)(params, batch)

        l0, m0, g0 = grads_for(cfg)
        cfg_on = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, obs=ObsConfig(enabled=True)))
        l1, m1, g1 = grads_for(cfg_on)
        assert bool(jnp.asarray(l0) == jnp.asarray(l1)), (l0, l1)
        assert jax.tree_util.tree_all(jax.tree.map(
            lambda a, b: bool((a == b).all()), g0, g1))
        assert float(m1["obs_wire_bytes"]) > 0.0

        sched = pipe_lib.build_1f1b(2, 4)
        tl = timeline_lib.StepTimeline(
            {"dispatch_a2a": 1.0, "expert_mlp": 1.0})
        tl.start(0); tl.stop()
        with tempfile.TemporaryDirectory() as d:
            path = export_lib.write_chrome_trace(
                os.path.join(d, "trace.json"), tl, (), schedule=sched)
            trace = export_lib.load_chrome_trace(path)
        markers = [e for e in trace["traceEvents"]
                   if e["ph"] == "i"
                   and e.get("tid", 0) >= export_lib.TID_STAGE0]
        assert len(markers) == sched.stages * sched.microbatches
        hits = 0
        for m in markers:
            a = m["args"]
            assert a["tick"] == sched.a2a_slot(a["stage"],
                                               a["microbatch"])
            hits += bool(a["hidden"])
        # every unit except the cold start has a hiding slot
        assert hits == sched.stages * sched.microbatches - 1
        print("PIPE_PARITY", float(l0))
    """)
    assert "PIPE_PARITY" in out


def test_train_launcher_writes_artifacts_8dev(tmp_path):
    """--metrics-dir end to end: events.jsonl + Perfetto trace with >=95%
    phase coverage + metrics.json whose comm_share is a live fig3-style
    share in [0, 1]."""
    mdir = str(tmp_path / "obs")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=_SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "granite-moe-3b-a800m", "--smoke", "--steps", "3", "--batch", "8",
         "--seq", "32", "--mesh-data", "2", "--mesh-model", "4",
         "--log-every", "1", "--metrics-dir", mdir],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "[comm] plan:" in out.stdout
    assert "[train] done: 3 steps" in out.stdout

    evs = events_lib.read_jsonl(os.path.join(mdir, "events.jsonl"))
    kinds = {e.kind for e in evs}
    assert {"step", "comm_plan", "train_done"} <= kinds
    steps = [e for e in evs if e.kind == "step"]
    assert len(steps) == 3 and all("loss" in e.data for e in steps)

    trace = export_lib.load_chrome_trace(os.path.join(mdir, "trace.json"))
    assert export_lib.span_coverage(trace) >= 0.95

    with open(os.path.join(mdir, "metrics.json")) as f:
        m = json.load(f)
    assert 0.0 <= m["comm_share"] <= 1.0
    assert m["steps"] == 3.0
    assert m["obs_wire_bytes"] > 0.0
    assert m["obs_compression_rate"] == pytest.approx(
        m["obs_wire_bytes"] / m["obs_raw_bytes"])


@pytest.mark.parametrize("wire", ["bf16", "int8"])
@pytest.mark.parametrize("transport", ["flat", "hierarchical", "pipelined"])
def test_step_scopes_and_exchange_legs_in_compiled_hlo_4dev(transport,
                                                           wire):
    """The train step lowered with ObsConfig on, on a 1x4 (data x model)
    mesh like the four-chip benchmark cell, LSH on and off: the
    step-level scopes land in the compiled HLO's op_name metadata; every
    all-to-all sits under one exchange leg (or the attention or LM head
    that issues it) on each transport and wire format; no instruction
    sits under two scopes; and the dispatch scatter runs under obs/gate
    (inside the dispatch leg where the quantized wire fuses it)."""
    out = _run(f"""
        import dataclasses, os, re
        os.environ["REPRO_KERNEL_BACKEND"] = "pallas_interpret"
        import jax, jax.numpy as jnp
        from repro.compat import set_mesh
        from repro.configs.base import ObsConfig, OptimizerConfig
        from repro.configs.registry import get_smoke_config
        from repro.launch import mesh as mesh_lib
        from repro.obs import events as events_lib
        from repro.obs import tracing
        from repro.runtime.step import init_train_state, make_train_step

        transport, wire = {transport!r}, {wire!r}
        base = get_smoke_config("granite-moe-3b-a800m")
        comm = dataclasses.replace(
            base.moe.comm, a2a_impl=transport,
            node_size=2 if transport == "hierarchical" else 0,
            overlap_chunks=2 if transport == "pipelined" else 1)
        cfg = base.replace(moe=dataclasses.replace(
            base.moe, obs=ObsConfig(enabled=True), comm=comm,
            lsh=dataclasses.replace(base.moe.lsh, wire_format=wire)))
        mesh = mesh_lib.make_host_mesh(1, 1, 4)
        opt = OptimizerConfig()
        plans = events_lib.MemorySink()
        events_lib.global_log().add_sink(plans)
        legs = {{tracing.PH_DISPATCH, tracing.PH_COMBINE}}
        issuers = legs | {{tracing.PH_ATTENTION, tracing.PH_LM_HEAD}}
        for use_lsh in (True, False):
            with set_mesh(mesh):
                state = jax.eval_shape(lambda: init_train_state(
                    jax.random.PRNGKey(0), cfg, opt, mesh))
                b = jax.ShapeDtypeStruct((8, 32), jnp.int32)
                hlo = jax.jit(make_train_step(cfg, opt, mesh,
                                              use_lsh=use_lsh)).lower(
                    state, {{"tokens": b, "labels": b}}).compile().as_text()
            assert {{e.data["algorithm"] for e in
                    plans.of_kind("comm_plan")}} == {{transport}}
            names = re.findall(r'op_name="([^"]*)"', hlo)
            scoped = [set(re.findall(r"obs/[a-z0-9_]+", n)) for n in names]
            for s in tracing.STEP_SCOPES:
                assert any(s in sc for sc in scoped), s
            assert all(len(sc) <= 1 for sc in scoped), \\
                [n for n, sc in zip(names, scoped) if len(sc) > 1][:3]
            a2a = [set(re.findall(r"obs/[a-z0-9_]+", line))
                   for line in hlo.splitlines() if " all-to-all(" in line]
            assert a2a and all(len(sc & issuers) == 1 for sc in a2a), a2a
            assert {{s for sc in a2a for s in sc}} >= legs
            # forward and rematerialised calls of a kernel, by their full
            # op_name (the interpreter's inner loops carry relative ones);
            # the backward calls are transposes (the combine gather's)
            def calls(kernel):
                return [sc for n, sc in zip(names, scoped)
                        if n.startswith("jit(train_step)")
                        and "/jit(" + kernel + ")" in n
                        and ("transpose(" not in n
                             or "rematted_computation" in n)]
            fused = wire == "int8" and transport != "pipelined" \\
                and not use_lsh
            scatter = calls("dispatch_scatter_pallas")
            if fused:
                assert not scatter
                fused_scatter = calls("dispatch_scatter_quantize_pallas")
                assert fused_scatter and all(
                    sc == {{tracing.PH_DISPATCH}} for sc in fused_scatter)
            else:
                assert scatter and all(sc == {{tracing.PH_GATE}}
                                       for sc in scatter), scatter
        print("SCOPES", transport, wire)
    """, devices=4)
    assert f"SCOPES {transport} {wire}" in out
