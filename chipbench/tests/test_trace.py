"""The trace reduction on a TPU trace recorded on the chip.

``data/trace`` holds the gzipped ``.xplane.pb`` of two steps of the cut
granite LSH step with the ``obs/`` scopes on, on one v5e, the compiled
HLO text of that executable and the run's facts (``meta.json``).  The
numbers below are what the benchmark's readers give on it; a change to
the reduction that moves them has to say why."""
import gzip
import json

import pytest

from chipbench import harness
from chipbench import trace as trace_lib
from chipbench.tests.cells import BENCH, DATA, REPO

TRACE = DATA / "trace"
PINNED = {
    "mfu": 0.8260153103570084,
    "moe_layer_ms": 5392.0167139999985,
    "expert_mlp_ms": 9.35157049999863,
    "lsh_kernels_ms": 39.98733200000047,
    "routing_ms": 5293.554434499999,
    "routing_roofline": 0.46473889063105206,
    "device_idle_pct": 0.0005952016539967353,
}


@pytest.fixture(scope="module")
def recorded():
    meta = json.loads((TRACE / "meta.json").read_text())
    with gzip.open(TRACE / "step.hlo.txt.gz", "rt") as f:
        hlo = f.read()
    return trace_lib.load(str(TRACE / "trace.xplane.pb.gz"), hlo), meta


def test_per_layer_numbers_of_the_recorded_trace(recorded):
    tr, meta = recorded
    per_layer = json.loads((REPO / "BENCHMARK.json").read_text())[
        "per_layer"]
    got = harness.read_metrics(per_layer, BENCH / "metrics",
                               harness.trace_context(tr, meta))
    assert {k: v["value"] for k, v in got.items()} == pytest.approx(
        PINNED, rel=1e-9)
    # one chip: nothing to read for the all-to-all metrics
    assert "a2a_bytes" not in got and "a2a_exposed_ms" not in got
    for k in ("mfu", "routing_roofline"):
        assert 0 < got[k]["value"] <= 100


def test_device_ops_of_the_recorded_trace(recorded):
    tr, meta = recorded
    assert tr.devices == [0]
    assert 0 < tr.busy_s() <= tr.window_s
    per_step = {}
    for op in tr.ops[0]:
        if op.kernel:
            per_step[op.kernel] = per_step.get(op.kernel, 0) + 1
    per_step = {k: v / meta["steps"] for k, v in per_step.items()}
    # 4 layers: forward, rematerialised forward and backward
    assert per_step == {"positions_in_expert_pallas": 8,
                        "lsh_hash_pallas": 8,
                        "dispatch_scatter_pallas": 12,
                        "combine_gather_pallas": 12,
                        "segment_centroid_pallas": 12,
                        "residual_apply_pallas": 12}
    phases = {op.phase for op in tr.ops[0]} - {None}
    assert phases == {"obs/gate", "obs/hash_compress", "obs/expert_mlp",
                      "obs/decompress"}


def test_breakdown_of_the_recorded_trace(recorded):
    tr, _ = recorded
    b = tr.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    name, seconds = b["device_ops"][0]
    assert name == "combine_gather_pallas [obs/decompress]"
    assert seconds == pytest.approx(5.456888429, rel=1e-9)
    assert all(g[0] in trace_lib.HOST_SPANS + ("host",)
               for g in b["idle_gaps"])


def _op(dev, start, end, opcode, name="x"):
    return trace_lib.Op(dev, start, end, name, opcode, "", "")


def test_exposed_time_counts_only_what_nothing_else_covers():
    ops = {0: [_op(0, 0.0, 1.0, "all-to-all"), _op(0, 0.5, 2.0, "fusion"),
               _op(0, 3.0, 4.0, "all-to-all")],
           1: [_op(1, 0.0, 2.0, "all-to-all")]}
    tr = trace_lib.Trace(ops, [], (0.0, 5.0))
    a2a = lambda op: op.opcode in trace_lib.A2A_OPCODES  # noqa: E731
    assert tr.exposed(a2a) == pytest.approx((1.5 + 2.0) / 2)
    assert tr.busy_s() == pytest.approx((3.0 + 2.0) / 2)
    assert tr.time(a2a) == pytest.approx((2.0 + 2.0) / 2)


TRACE4 = DATA / "trace4"
PINNED4 = {
    "mfu": 2.800994120439449,
    "lsh_kernels_ms": 8.329494850000133,
    "a2a_exposed_ms": 8.987974349999718,
    "a2a_bytes": 810.041344,
    "routing_ms": 328.6055948,
    "routing_roofline": 1.8716362824573365,
    "device_idle_pct": 0.20130811528314974,
}


def test_four_chip_trace():
    """Five steps of the expert-parallel step on four v5e chips, recorded
    by ``run.py --trace 1 --keep-trace``.  Its executable came from a
    compile cache keyed without metadata and so carries no ``obs/``
    scopes: the MoE-scope readers find nothing and report nothing."""
    meta = json.loads((TRACE4 / "meta.json").read_text())
    with gzip.open(TRACE4 / "step.hlo.txt.gz", "rt") as f:
        tr = trace_lib.load(str(TRACE4 / "trace.xplane.pb.gz"), f.read())
    assert tr.devices == [0, 1, 2, 3]
    per_layer = json.loads((REPO / "BENCHMARK.json").read_text())[
        "per_layer"]
    got = harness.read_metrics(per_layer, BENCH / "metrics",
                               harness.trace_context(tr, meta))
    assert {k: v["value"] for k, v in got.items()} == pytest.approx(
        PINNED4, rel=1e-9)
    # per layer: two MoE legs and one attention exchange in the forward,
    # the rematerialised forward and the backward; one for the LM head
    a2a = [op for op in tr.ops[0] if op.opcode in trace_lib.A2A_OPCODES]
    assert len(a2a) == meta["steps"] * (4 * 9 + 1)


def test_a_listed_metric_that_reads_nothing_fails_the_run():
    """The four-chip trace above carries no ``obs/`` scopes: a cell that
    lists the MoE-scope metrics gets an error, not a line without them."""
    meta = json.loads((TRACE4 / "meta.json").read_text())
    with gzip.open(TRACE4 / "step.hlo.txt.gz", "rt") as f:
        tr = trace_lib.load(str(TRACE4 / "trace.xplane.pb.gz"), f.read())
    per_layer = json.loads((REPO / "BENCHMARK.json").read_text())[
        "per_layer"]
    with pytest.raises(harness.Unread, match="moe_layer_ms"):
        harness.cell_metrics(per_layer, BENCH / "metrics",
                             harness.trace_context(tr, meta))
