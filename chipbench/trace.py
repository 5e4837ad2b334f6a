"""Reduce a TPU profiler trace to device op intervals and per-layer sums.

Input: the ``.xplane.pb`` that ``jax.profiler`` writes, and the compiled
HLO text of the traced executable.  What a TPU trace holds (read by hand
from a v5e trace of the cut granite step):

* one plane per chip, ``/device:TPU:<n>``; its line ``XLA Ops`` has one
  event per executed HLO instruction, named with the instruction's full
  text (``%name = <result shapes> <opcode>(<operand shapes> ...)``).
  ``while`` (and ``call``/``conditional``) events span the ops of their
  bodies, so only the other ops count as work.  ``Async XLA Ops`` holds
  the DMA copies the compiler overlaps with compute; they are not ops.
* the plane ``/host:CPU`` holds the harness's own spans
  (``jax.profiler.TraceAnnotation``) on the clock of the device events.
* scope names (``obs/<phase>``) are not in the events; they sit in the
  compiled HLO's ``metadata={op_name="..."}``, joined here by instruction
  name.  A Pallas kernel's instruction is named after its jitted wrapper
  (``combine_gather_pallas.27``) and its op_name ends in
  ``jit(<wrapper>)/pallas_call``.

The rules follow the program's ``obs/profile.py`` (scope regex, collectives
classified by opcode), adapted to xplane events.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Tuple

PHASE_RE = re.compile(r"obs/(gate|hash_compress|dispatch_a2a|expert_mlp|"
                      r"combine_a2a|decompress|stage_transfer)\b")
_EVENT_RE = re.compile(r"^%(?P<name>[^\s=]+) = (?P<result>.*?) "
                       r"(?P<opcode>[a-z][a-z0-9\-]*)\((?P<rest>.*)$", re.S)
_HLO_META_RE = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=.*?metadata=\{[^}]*?"
                          r"op_name=\"([^\"]*)\"", re.M)
_KERNEL_RE = re.compile(r"jit\((\w+)\)/pallas_call")
_SHAPE_RE = re.compile(r"\b(pred|s4|u4|s8|u8|s16|u16|s32|u32|s64|u64|f16|bf16|"
                       r"f32|f64|f8e4m3fn|f8e5m2|f8e4m3|f8e4m3b11fnuz)"
                       r"\[([0-9,]*)\]")
_DEVICE_RE = re.compile(r"^/device:TPU:(\d+)$")
CONTAINERS = ("while", "call", "conditional")
ITEMSIZE = {"pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2,
            "u16": 2, "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f16": 2,
            "bf16": 2, "f32": 4, "f64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
            "f8e4m3": 1, "f8e4m3b11fnuz": 1}
A2A_OPCODES = ("all-to-all", "all-to-all-start", "all-to-all-done")
# What the harness's host was doing, as it names its own spans.
HOST_SPANS = ("batch", "dispatch", "wait")


def shapes_bytes(text: str) -> float:
    """Bytes of every array shape written in ``text`` (``bf16[8,1024]``)."""
    total = 0.0
    for dtype, dims in _SHAPE_RE.findall(text):
        n = math.prod(int(x) for x in dims.split(",") if x) if dims else 1
        total += ITEMSIZE[dtype] * n
    return total


@dataclass(frozen=True)
class Op:
    """One executed HLO instruction on one chip (times in seconds)."""
    device: int
    start: float
    end: float
    name: str            # instruction name, e.g. "combine_gather_pallas.27"
    opcode: str
    scope: str           # op_name metadata ("" where the HLO has none)
    text: str            # the event's instruction text

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def base(self) -> str:
        """Instruction name without its numeric and clone suffixes."""
        return re.sub(r"(\.(\d+|clone))+$", "", self.name)

    @property
    def kernel(self) -> Optional[str]:
        """The Pallas wrapper a ``tpu_custom_call`` came from, else None."""
        if self.opcode != "custom-call" or "tpu_custom_call" not in self.text:
            return None
        m = _KERNEL_RE.search(self.scope)
        return m.group(1) if m else self.base

    @property
    def phase(self) -> Optional[str]:
        m = PHASE_RE.search(self.scope)
        return m.group(0) if m else None

    def _parts(self) -> Tuple[str, str]:
        """(result shapes, operand list) of the instruction text."""
        m = _EVENT_RE.match(self.text)
        if m is None:
            return "", ""
        args = m.group("rest")
        depth, end = 1, len(args)
        for i, ch in enumerate(args):         # operands end at the match
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                end = i
                break
        return m.group("result"), args[:end]

    def operand_bytes(self) -> float:
        return shapes_bytes(self._parts()[1])

    def interface_bytes(self) -> float:
        """Bytes of the op's operands and results, each read or written
        once, from the shapes in its instruction text."""
        result, args = self._parts()
        return shapes_bytes(result) + shapes_bytes(args)


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> op_name metadata of a compiled HLO module."""
    return dict(_HLO_META_RE.findall(hlo_text))


Interval = Tuple[float, float]


def _union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _intersect(a, b) -> float:
    """Length of the overlap of two sorted disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


@dataclass
class Trace:
    """Device ops per chip inside the traced window, and host spans."""
    ops: Dict[int, List[Op]]
    spans: List[Tuple[str, float, float]]
    window: Tuple[float, float]
    _busy: Dict[int, list] = field(default_factory=dict, repr=False)

    @property
    def devices(self) -> List[int]:
        return sorted(self.ops)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy(self, device: int):
        if device not in self._busy:
            self._busy[device] = _union((o.start, o.end)
                                        for o in self.ops[device])
        return self._busy[device]

    def busy_s(self) -> float:
        """Union of op intervals, averaged over chips."""
        return sum(_length(self.busy(d)) for d in self.devices) \
            / len(self.devices)

    def time(self, pred: Callable[[Op], bool]) -> float:
        """Summed duration of the ops that match, averaged over chips."""
        return sum(o.dur for d in self.devices for o in self.ops[d]
                   if pred(o)) / len(self.devices)

    def count(self, pred: Callable[[Op], bool]) -> float:
        return sum(1 for d in self.devices for o in self.ops[d]
                   if pred(o)) / len(self.devices)

    def sum(self, pred: Callable[[Op], bool],
            value: Callable[[Op], float]) -> float:
        return sum(value(o) for d in self.devices for o in self.ops[d]
                   if pred(o)) / len(self.devices)

    def exposed(self, pred: Callable[[Op], bool]) -> float:
        """Time in matching ops during which no other op runs on that
        chip, averaged over chips."""
        total = 0.0
        for d in self.devices:
            mine = _union((o.start, o.end) for o in self.ops[d] if pred(o))
            other = _union((o.start, o.end) for o in self.ops[d]
                           if not pred(o))
            total += _length(mine) - _intersect(mine, other)
        return total / len(self.devices)

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The ops that took most time (by instruction base name, with the
        phase scope where there is one), and the longest idle gaps of any
        chip named by the host span they fall in; seconds over the
        window, op times averaged over chips."""
        per: Dict[str, float] = {}
        for d in self.devices:
            for o in self.ops[d]:
                key = o.kernel or o.base
                if o.phase:
                    key = f"{key} [{o.phase}]"
                per[key] = per.get(key, 0.0) + o.dur / len(self.devices)
        ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        for d in self.devices:
            edges = [self.window[0]] + [t for iv in self.busy(d)
                                        for t in iv] + [self.window[1]]
            for lo, hi in zip(edges[0::2], edges[1::2]):
                if hi > lo:
                    gaps.append((hi - lo, lo, hi))
        gaps.sort(reverse=True)
        named = [[self.host_span(lo, hi), g] for g, lo, hi in gaps[:top]]
        return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": named}

    def host_span(self, lo: float, hi: float) -> str:
        """The harness span (``HOST_SPANS``) that covers most of [lo, hi];
        "host" where none does."""
        best, best_cover = "host", 0.0
        for name, s, e in self.spans:
            cover = min(e, hi) - max(s, lo)
            if name in HOST_SPANS and cover > best_cover:
                best, best_cover = name, cover
        return best


def _leaf_ops(events, device: int, scopes: Dict[str, str],
              window: Tuple[float, float]) -> List[Op]:
    out = []
    for e in events:
        m = _EVENT_RE.match(e.name)
        if m is None or m.group("opcode") in CONTAINERS:
            continue
        s = e.start_ns * 1e-9
        t = s + e.duration_ns * 1e-9
        s, t = max(s, window[0]), min(t, window[1])
        if t <= s and e.duration_ns > 0:
            continue
        name = m.group("name")
        out.append(Op(device, s, max(s, t), name, m.group("opcode"),
                      scopes.get(name, ""), e.name))
    return out


def load(xplane_path: str, hlo_text: str, window_span: str = "window"
         ) -> Trace:
    """Read a trace (``.xplane.pb``, or the same gzipped): device leaf
    ops, clipped to the host span named ``window_span`` where there is
    one (else to the ops' extent), and the host's spans.  A trace with no
    TPU plane raises."""
    import gzip
    from jax.profiler import ProfileData

    if xplane_path.endswith(".gz"):
        with gzip.open(xplane_path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(xplane_path)
    spans: List[Tuple[str, float, float]] = []
    device_lines = {}
    for plane in data.planes:
        m = _DEVICE_RE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device_lines[int(m.group(1))] = list(line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    spans.append((e.name, e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9))
    if not device_lines:
        raise ValueError(f"{xplane_path}: no /device:TPU:<n> plane with "
                         f"XLA Ops")
    win = [s for s in spans if s[0] == window_span]
    if win:
        window = (win[0][1], win[0][2])
    else:
        starts = [e.start_ns for ev in device_lines.values() for e in ev]
        ends = [e.start_ns + e.duration_ns for ev in device_lines.values()
                for e in ev]
        window = (min(starts) * 1e-9, max(ends) * 1e-9)
    scopes = hlo_scopes(hlo_text)
    ops = {d: _leaf_ops(ev, d, scopes, window)
           for d, ev in device_lines.items()}
    return Trace(ops, spans, window)
