"""Reduce a JAX profiler trace to the benchmark's device numbers.

The profiler writes an ``.xplane.pb`` file. Each TPU chip is a plane named
``/device:TPU:<k>`` whose ``XLA Ops`` line holds one event per executed HLO
instruction; the event's name is the instruction's text,
``%<name> = <shape> <opcode>(...)``. The host planes hold the harness's own
``TraceAnnotation`` spans, on the same clock.

From these, over the harness's ``bench.window`` span:

* busy time per chip: the union of its op intervals;
* per-instruction device time (summed over chips), with the control-flow
  containers (``while``, ``conditional``, ``call``) left out, since their
  events span the ops they run;
* the idle gaps between busy intervals, each named by the innermost harness
  span (``bench.*``) that holds its midpoint.

This module imports no TPU library: it reads a file, or a text proto.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_INSTR = re.compile(r"^%([A-Za-z0-9_.\-]+?)(?:\.\d+)? = ")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OPERAND = re.compile(r" %[A-Za-z0-9_.\-]+")
CONTAINERS = ("while", "conditional", "call")


def instruction(event_name: str) -> tuple[str, str]:
    """``(base name, opcode)`` of an op event: ``%fusion.230 = f32[8]
    fusion(...)`` gives ``("fusion", "fusion")``; names that are not HLO
    text come back whole, with an empty opcode."""
    m = _INSTR.match(event_name)
    if m is None:
        return event_name, ""
    rest = event_name[m.end():]
    op = _OPCODE.search(" " + rest)
    return m.group(1), (op.group(1) if op else "")


def _label(event_name: str) -> str:
    """The instruction with its result and operand shapes, without layouts,
    operand names or attributes: ``%fusion.217 = u32[25600,2]
    fusion(u32[8,16,200,2], s32[25600])``."""
    head, eq, rest = event_name.partition(" = ")
    if not eq:
        return event_name[:200]
    rest = _OPERAND.sub("", _LAYOUT.sub("", rest))
    _, op = instruction(event_name)
    i = rest.find(f" {op}(") if op else -1
    if i >= 0:
        j = rest.find("),", i)
        rest = rest[:j + 1] if j >= 0 else rest
    return f"{head} = {rest}"[:200]


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


@dataclasses.dataclass
class TraceSummary:
    """Device numbers of one traced window (times in seconds)."""

    window_s: float
    busy_s: list[float]                  # per chip, in chip order
    op_s: dict[str, float]               # "%name opcode" -> s, all chips
    base_s: dict[str, float]             # base instruction name -> s
    gaps: list[tuple[str, float]]        # (host span, s), longest first

    @property
    def n_devices(self) -> int:
        return len(self.busy_s)

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)

    def kernel_s(self, names) -> float:
        """Device seconds, summed over chips, of the instructions whose
        base name is one of ``names``."""
        return sum(self.base_s.get(n, 0.0) for n in names)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:top]]}


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _spans(profile) -> list[tuple[int, int, str]]:
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    out.append((ev.start_ns, ev.end_ns, ev.name))
    return out


def reduce_profile(profile) -> TraceSummary:
    """Summarize a ``jax.profiler.ProfileData`` (see the module doc)."""
    spans = _spans(profile)
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    w0, w1 = windows[0]

    devices = []
    for plane in profile.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            devices.append((int(m.group(1)), plane))
    devices.sort(key=lambda d: d[0])

    busy, gaps = [], []
    op_s: dict[str, float] = {}
    base_s: dict[str, float] = {}
    names: dict[str, tuple[str, str, str]] = {}   # name -> base, op, label
    for _, plane in devices:
        intervals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if e <= s:
                    continue
                intervals.append((s, e))
                name = ev.name
                if name not in names:
                    names[name] = instruction(name) + (_label(name),)
                base, op, label = names[name]
                if op in CONTAINERS:
                    continue
                sec = (e - s) * 1e-9
                op_s[label] = op_s.get(label, 0.0) + sec
                base_s[base] = base_s.get(base, 0.0) + sec
        if not intervals:
            continue
        merged = _union(intervals)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_holder(spans, (a + b) / 2), (b - a) * 1e-9))
    if not busy:
        raise ValueError("no device operation ran in the traced window")
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(window_s=(w1 - w0) * 1e-9, busy_s=busy, op_s=op_s,
                        base_s=base_s, gaps=gaps)


def _holder(spans, t: float) -> str:
    """The innermost span holding time ``t`` (the shortest one)."""
    best = None
    for s, e, n in spans:
        if s <= t <= e and (best is None or e - s < best[0]):
            best = (e - s, n)
    return best[1] if best else "outside the harness's spans"


def reduce_dir(trace_dir: str) -> TraceSummary:
    import jax

    path = find_xplane(trace_dir)
    return reduce_profile(jax.profiler.ProfileData.from_file(path))
