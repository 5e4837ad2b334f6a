"""Roofline-term extraction from compiled SPMD executables.

compute   = per-device HLO FLOPs / peak FLOP/s
memory    = per-device HLO bytes accessed / HBM bandwidth
collective = per-device wire bytes (ring formulas per collective) / link bw

Per-device FLOPs/bytes come from ``compiled.cost_analysis()`` (verified
per-device, post-SPMD-partitioning).  Wire bytes are parsed from
``compiled.as_text()`` — the post-partitioning HLO carries one line per
collective with per-device shapes and replica_groups.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from repro import hw

# The dry run prices the production mesh's chip, a TPU v5e, whatever host
# compiles it.
_CHIP = hw.peaks(hw.V5E)
PEAK_FLOPS = _CHIP.flops
HBM_BW = _CHIP.hbm_bytes_per_s
ICI_BW = _CHIP.ici_bytes_per_s

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "token": 0,
}

_COLL_RE = re.compile(
    r"=\s+(?P<shape>\([^)]*\)|\S+)\s+"
    r"(?P<op>all-to-all|all-gather|all-reduce|reduce-scatter|"
    r"collective-permute)(?P<start>-start)?\(")
_ARR_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _array_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _ARR_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


@dataclass
class CollectiveStats:
    counts: Dict[str, int] = field(default_factory=dict)
    result_bytes: Dict[str, int] = field(default_factory=dict)
    wire_bytes: Dict[str, float] = field(default_factory=dict)

    @property
    def total_wire_bytes(self) -> float:
        return sum(self.wire_bytes.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Per-device wire bytes via ring-algorithm accounting:
      all-gather       : out * (g-1)/g        (result = gathered)
      reduce-scatter   : out * (g-1)          (result = scattered shard)
      all-reduce       : 2 * size * (g-1)/g   (RS + AG)
      all-to-all       : size * (g-1)/g
      collective-permute: size
    """
    st = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None:
            continue
        op = m.group("op")
        shape_txt = m.group("shape")
        if shape_txt.startswith("("):
            # async -start returns a tuple (operands..., results...): the
            # result halves double-count the payload — take half the tuple.
            b = _array_bytes(shape_txt) // 2
        else:
            b = _array_bytes(shape_txt)
        g = _group_size(line)
        if g <= 1:
            wire = 0.0
        elif op == "all-gather":
            wire = b * (g - 1) / g
        elif op == "reduce-scatter":
            wire = b * (g - 1)
        elif op == "all-reduce":
            wire = 2 * b * (g - 1) / g
        elif op == "all-to-all":
            wire = b * (g - 1) / g
        else:  # collective-permute
            wire = float(b)
        st.counts[op] = st.counts.get(op, 0) + 1
        st.result_bytes[op] = st.result_bytes.get(op, 0) + b
        st.wire_bytes[op] = st.wire_bytes.get(op, 0.0) + wire
    return st


@dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    wire_bytes_per_device: float
    collectives: Dict[str, float]
    collective_counts: Dict[str, int]
    arg_bytes: int
    temp_bytes: int
    output_bytes: int
    xla_flops: float = 0.0          # cost_analysis (loop bodies counted 1x)
    xla_bytes: float = 0.0

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.wire_bytes_per_device / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def to_dict(self) -> Dict:
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "wire_bytes_per_device": self.wire_bytes_per_device,
            "collectives": self.collectives,
            "collective_counts": self.collective_counts,
            "arg_bytes": self.arg_bytes,
            "temp_bytes": self.temp_bytes,
            "output_bytes": self.output_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "xla_flops": self.xla_flops,
            "xla_bytes": self.xla_bytes,
        }


def analyze(compiled) -> Roofline:
    """Roofline terms from the compiled SPMD artifact.

    FLOPs/bytes/wire come from the LOOP-AWARE structural analyzer
    (hlo_structural): XLA's cost_analysis() counts while bodies once, which
    undercounts scan-over-layers programs by ~depth x.  cost_analysis()
    values are kept as `xla_*` cross-checks.
    """
    from repro.launch import hlo_structural
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    st = hlo_structural.analyze_text(compiled.as_text())
    r = Roofline(
        flops_per_device=st.flops,
        bytes_per_device=st.bytes_accessed,
        wire_bytes_per_device=st.total_wire,
        collectives=st.wire_bytes,
        collective_counts={k: int(v)
                           for k, v in st.collective_counts.items()},
        arg_bytes=ma.argument_size_in_bytes,
        temp_bytes=ma.temp_size_in_bytes,
        output_bytes=ma.output_size_in_bytes,
    )
    r.xla_flops = float(ca.get("flops", 0.0))
    r.xla_bytes = float(ca.get("bytes accessed", 0.0))
    return r
