"""The readers of the step-level scopes and of the MoE exchange: on
hand-built traces, what each counts and where each reads nothing; on a
four-chip trace recorded with every scope on, what each reads."""
import gzip
import json

import pytest

from chipbench import harness
from chipbench import trace as trace_lib
from chipbench.tests.cells import BENCH, DATA, REPO

STEP_METRICS = ("attention_ms", "lm_head_ms", "optimizer_ms")
NEW = ("moe_a2a_ms", "moe_a2a_bytes") + STEP_METRICS + ("unscoped_ms",)
PER_LAYER = json.loads((REPO / "BENCHMARK.json").read_text())["per_layer"]
NEW_ENTRIES = [m for m in PER_LAYER if m["name"] in NEW]
WIRE = "bf16[4,10,104,1536]"             # one MoE leg's operand, 12.78 MB


def _op(start, end, opcode, scope, shape="f32[8]"):
    text = f"%x.1 = {shape} {opcode}({shape} %p.0), metadata={{}}"
    return trace_lib.Op(0, start, end, "x.1", opcode, scope, text)


def _read(ops, steps=1):
    tr = trace_lib.Trace({0: ops}, [], (0.0, 100.0))
    ctx = harness.trace_context(tr, {"steps": steps, "tokens": 1,
                                     "chips": 1, "device_kind": "TPU v5 lite",
                                     "flops_per_token": 1.0})
    names = NEW + ("moe_layer_ms",)
    return {n: harness.metric_reader(BENCH / "metrics", n)(ctx)
            for n in names}, tr


STEP = "jit(train_step)/jvp()/while/body"
SCOPED = [
    _op(0.0, 1.0, "fusion", f"{STEP}/obs/attention/dot_general"),
    _op(1.0, 1.5, "all-to-all", f"{STEP}/obs/attention/all_to_all",
        "bf16[8,4,256,1536]"),
    _op(2.0, 2.5, "fusion", f"{STEP}/obs/gate/dot_general"),
    _op(2.5, 3.0, "all-to-all", f"{STEP}/shard_map/obs/dispatch_a2a/a2a",
        WIRE),
    _op(3.0, 4.0, "fusion", f"{STEP}/obs/expert_mlp/dot_general"),
    _op(4.0, 4.25, "all-to-all-start",
        f"transpose(jvp())/shard_map/obs/combine_a2a/a2a", WIRE),
    _op(4.25, 4.5, "all-to-all-done",
        f"transpose(jvp())/shard_map/obs/combine_a2a/a2a", WIRE),
    _op(5.0, 7.0, "fusion", "jit(train_step)/obs/lm_head/dot_general"),
    _op(7.0, 7.5, "fusion", "jit(train_step)/obs/optimizer/mul"),
    _op(8.0, 8.25, "all-gather", "", "bf16[49155,1536]"),
    _op(8.25, 8.5, "fusion", "jit(train_step)/probs/add"),
]


def test_each_reader_counts_its_own_ops():
    got, _ = _read(SCOPED, steps=2)
    assert got["attention_ms"] == pytest.approx(1e3 * 1.5 / 2)
    assert got["lm_head_ms"] == pytest.approx(1e3 * 2.0 / 2)
    assert got["optimizer_ms"] == pytest.approx(1e3 * 0.5 / 2)
    # the legs' all-to-alls, not the attention's, and no fusion under
    # a leg's scope
    assert got["moe_a2a_ms"] == pytest.approx(1e3 * 1.0 / 2)
    assert got["moe_a2a_bytes"] == pytest.approx(
        2 * 4 * 10 * 104 * 1536 * 2 / 1e6 / 2)
    # no obs/ scope: the partitioner's all-gather and a name that only
    # contains "obs/" inside another word
    assert got["unscoped_ms"] == pytest.approx(1e3 * 0.5 / 2)


def test_the_scopes_partition_the_step():
    got, tr = _read(SCOPED)
    parts = got["moe_layer_ms"] + sum(
        got[n] for n in STEP_METRICS + ("unscoped_ms",))
    assert parts == pytest.approx(1e3 * tr.busy_s())


# the scopes each reader needs; without them it reads nothing
ABSENT = {"moe_a2a_ms": ("dispatch_a2a", "combine_a2a"),
          "moe_a2a_bytes": ("dispatch_a2a", "combine_a2a"),
          "attention_ms": ("attention",), "lm_head_ms": ("lm_head",),
          "optimizer_ms": ("optimizer",),
          # a program that scoped only the MoE layer
          "unscoped_ms": ("attention", "lm_head", "optimizer")}


@pytest.mark.parametrize("name", NEW)
def test_a_reader_whose_scope_is_absent_reads_nothing(name):
    ops = [o for o in SCOPED
           if not any(f"obs/{s}" in o.scope for s in ABSENT[name])]
    got, _ = _read(ops)
    assert got[name] is None


def test_an_attention_all_to_all_is_not_the_moe_exchange():
    ops = [o for o in SCOPED if "a2a" not in o.scope]
    got, _ = _read(ops)
    assert got["moe_a2a_ms"] is None and got["moe_a2a_bytes"] is None
    assert got["attention_ms"] == pytest.approx(1e3 * 1.5)


TRACE4S = DATA / "trace4scoped"
PINNED4S = {
    "mfu": 2.8009097136242267,
    "moe_layer_ms": 351.85543165000075,
    "expert_mlp_ms": 2.2061093999998547,
    "lsh_kernels_ms": 8.330088750000204,
    "a2a_exposed_ms": 8.987660549999674,
    "a2a_bytes": 810.041344,
    "routing_ms": 328.6083186500001,
    "routing_roofline": 1.8716207683750723,
    "device_idle_pct": 0.20322090798863268,
    "moe_a2a_ms": 3.3800476500000123,
    "moe_a2a_bytes": 306.70848,
    "attention_ms": 19.533379600000046,
    "lm_head_ms": 10.321111499999988,
    "optimizer_ms": 5.988441099999986,
    "unscoped_ms": 18.349378700021784,
}


def test_four_chip_trace_with_every_scope():
    """Five steps of ``granite.ep4.train-lsh`` on four v5e chips with the
    step-level and exchange scopes on, recorded by ``run.py --trace 1
    --keep-trace``: every metric listed for the cell reads, as pinned;
    the MoE exchange is 24 all-to-alls a step (two legs, forward,
    rematerialised forward and backward, four layers) of 12.78 MB; and
    the scopes partition the step's busy time."""
    meta = json.loads((TRACE4S / "meta.json").read_text())
    with gzip.open(TRACE4S / "step.hlo.txt.gz", "rt") as f:
        tr = trace_lib.load(str(TRACE4S / "trace.xplane.pb.gz"), f.read())
    assert [m["name"] for m in NEW_ENTRIES] == list(NEW)
    got = harness.cell_metrics(PER_LAYER, BENCH / "metrics",
                               harness.trace_context(tr, meta))
    assert {k: v["value"] for k, v in got.items()} == pytest.approx(
        PINNED4S, rel=1e-9)
    from chipbench.metrics.moe_a2a_bytes import moe_send
    assert tr.count(moe_send) == 24 * meta["steps"]
    parts = got["moe_layer_ms"]["value"] + sum(
        got[n]["value"] for n in STEP_METRICS + ("unscoped_ms",))
    assert parts == pytest.approx(1e3 * tr.busy_s() / meta["steps"],
                                  rel=0.01)


def test_a_program_with_only_the_moe_scopes_reads_none_of_them():
    """The one-chip trace recorded before the step-level scopes existed
    (``data/trace``): what the parent program's traced run gives.  Each
    new reader reads nothing there, and does not raise."""
    meta = json.loads((DATA / "trace" / "meta.json").read_text())
    with gzip.open(DATA / "trace" / "step.hlo.txt.gz", "rt") as f:
        tr = trace_lib.load(str(DATA / "trace" / "trace.xplane.pb.gz"),
                            f.read())
    assert harness.read_metrics(NEW_ENTRIES, BENCH / "metrics",
                                harness.trace_context(tr, meta)) == {}
