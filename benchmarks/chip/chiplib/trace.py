"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

The traced window is the host annotation ``bench/traced_window``; everything
is measured inside it, on the trace's one clock:

* device busy time: the union of the intervals of the device's operations
  (line ``XLA Ops`` of each ``/device:...`` plane), averaged over the chips;
* time per operation name, and per module name (line ``XLA Modules``), with
  the number of module runs;
* idle gaps: the stretches of the window with no operation on the device,
  each named by the innermost ``bench/`` span open on the host at its middle;
* compilations whose host events lie inside the window.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from chiplib.spans import ANNOTATION_PREFIX

WINDOW = ANNOTATION_PREFIX + "traced_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:"
HOST_PLANE = "/host:CPU"
COMPILE_MARKS = ("backend_compile", "XlaCompile", "PjitCompile",
                 "compile_or_get_cached")


@dataclass
class Reduction:
    window_s: float
    busy_s: float                                   # averaged over devices
    n_devices: int
    op_s: Dict[str, float] = field(default_factory=dict)
    module_s: Dict[str, float] = field(default_factory=dict)
    module_runs: Dict[str, int] = field(default_factory=dict)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)
    compiles: int = 0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def modules(self, prefix: str) -> Tuple[int, float]:
        """(runs, seconds) of the modules whose name starts with ``prefix``."""
        names = [n for n in self.module_s if n.startswith(prefix)]
        return (sum(self.module_runs[n] for n in names),
                sum(self.module_s[n] for n in names))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:top]]}


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"{len(files)} .xplane.pb files in {trace_dir}")
    return files[0]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: float, b: float, lo: float, hi: float) -> Optional[Tuple[float, float]]:
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def reduce_xplane(path: str) -> Reduction:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host_spans: List[Tuple[float, float, str]] = []
    compile_starts: List[float] = []
    window = None
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name == WINDOW:
                    window = (ev.start_ns, ev.end_ns)
                elif name.startswith(ANNOTATION_PREFIX):
                    host_spans.append((ev.start_ns, ev.end_ns,
                                       name[len(ANNOTATION_PREFIX):]))
                elif any(m in name for m in COMPILE_MARKS):
                    compile_starts.append(ev.start_ns)
    if window is None:
        raise ValueError(f"no {WINDOW} annotation in {path}")
    lo, hi = window
    red = Reduction(window_s=(hi - lo) / 1e9, busy_s=0.0, n_devices=0)
    red.compiles = sum(lo <= t <= hi for t in compile_starts)
    busy_sets = []
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        ops, seen = [], False
        for line in plane.lines:
            if line.name == OPS_LINE:
                seen = True
                for ev in line.events:
                    iv = _clip(ev.start_ns, ev.end_ns, lo, hi)
                    if iv:
                        ops.append(iv)
                        red.op_s[ev.name] = (red.op_s.get(ev.name, 0.0)
                                             + (iv[1] - iv[0]) / 1e9)
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    if lo <= ev.start_ns <= hi:
                        red.module_s[ev.name] = (red.module_s.get(ev.name, 0.0)
                                                 + ev.duration_ns / 1e9)
                        red.module_runs[ev.name] = (
                            red.module_runs.get(ev.name, 0) + 1)
        if seen:
            busy_sets.append(union(ops))
    red.n_devices = len(busy_sets)
    if not busy_sets:
        raise ValueError(f"no device plane with an {OPS_LINE!r} line in {path}")
    red.busy_s = sum(sum(b - a for a, b in u) for u in busy_sets) / 1e9 / len(busy_sets)
    red.idle_gaps = idle_gaps(busy_sets[0], lo, hi, host_spans)
    return red


def idle_gaps(busy: List[Tuple[float, float]], lo: float, hi: float,
              host_spans: List[Tuple[float, float, str]]) -> List[Tuple[str, float]]:
    """Gaps between busy intervals, longest first, each named by the shortest
    host span that contains its middle."""
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        inside = [(e - s, n) for s, e, n in host_spans if s <= mid <= e]
        gaps.append((min(inside)[1] if inside else "no span", (b - a) / 1e9))
    return sorted(gaps, key=lambda g: -g[1])
