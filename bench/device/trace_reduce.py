"""Reduce a JAX profiler trace (``*.xplane.pb``) to device metrics.

    python bench/device/trace_reduce.py <trace dir>     # print a summary

The device planes are those named ``/device:TPU:<n>``.  On each, the ops
are the events of its ``XLA Ops`` line and the jitted calls those of its
``XLA Modules`` line (all lines where a plane has neither).  The measured
window is the host event that the harness names ``bench.window``.

- busy: the union of the op intervals inside the window, per chip, and
  its mean over the chips; idle share = 1 - busy / window.
- kernel time: the summed device duration of the module events whose
  name holds the kernel's jitted name (``window_reduce_fwd``), inside
  the window.
- device ops: total device time per op name, the largest first.
- idle gaps: the gaps between busy intervals, each named after the host
  annotation (``bench.*``) that covers most of it.
"""
from __future__ import annotations

import glob
import json
import os
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_EVENT = "bench.window"
HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]


@dataclass
class Event:
    name: str
    start_ns: float
    end_ns: float


@dataclass
class Plane:
    name: str
    lines: Dict[str, List[Event]] = field(default_factory=dict)


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # mean over the chips
    chips: int
    kernel_s: Dict[str, float]          # jitted name -> device seconds
    kernel_calls: Dict[str, int]
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def load_planes(trace_dir: str) -> List[Plane]:
    """Every plane of the newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    out = []
    for pl in ProfileData.from_file(files[-1]).planes:
        plane = Plane(pl.name)
        for ln in pl.lines:
            plane.lines.setdefault(ln.name, []).extend(
                Event(e.name, float(e.start_ns), float(e.end_ns))
                for e in ln.events)
        out.append(plane)
    return out


def device_planes(planes: Sequence[Plane]) -> List[Plane]:
    return [p for p in planes if p.name.startswith("/device:TPU:")
            and p.name[len("/device:TPU:"):].isdigit()]


def _line(plane: Plane, name: str) -> List[Event]:
    if name in plane.lines:
        return plane.lines[name]
    if OPS_LINE in plane.lines or MODULES_LINE in plane.lines:
        return []
    return [e for evs in plane.lines.values() for e in evs]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def host_events(planes: Sequence[Plane]) -> List[Event]:
    return [e for p in planes if p.name.startswith("/host:")
            for evs in p.lines.values() for e in evs
            if e.name.startswith(HOST_PREFIX)]


def window_of(planes: Sequence[Plane]) -> Interval:
    wins = [e for e in host_events(planes) if e.name == WINDOW_EVENT]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW_EVENT!r} host event, "
                         f"found {len(wins)}")
    return wins[0].start_ns, wins[0].end_ns


def op_name(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``%fusion.3``."""
    return name.split(" = ", 1)[0]


def _name_gap(gap: Interval, annotations: Sequence[Event]) -> str:
    best, cover = "host", 0.0
    for a in annotations:
        c = min(gap[1], a.end_ns) - max(gap[0], a.start_ns)
        if c > cover:
            best, cover = a.name, c
    return best


def summarize(planes: Sequence[Plane], kernels: Sequence[str] = (),
              top: int = 10) -> TraceSummary:
    lo, hi = window_of(planes)
    devs = device_planes(planes)
    if not devs:
        raise ValueError("the trace holds no /device:TPU:<n> plane")
    busy_total = 0.0
    kernel_s = {k: 0.0 for k in kernels}
    kernel_calls = {k: 0 for k in kernels}
    op_time: Dict[str, float] = {}
    gaps: List[Interval] = []
    for p in devs:
        ops = [e for e in _line(p, OPS_LINE)
               if e.end_ns > lo and e.start_ns < hi]
        busy = union(clip([(e.start_ns, e.end_ns) for e in ops], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for e in ops:
            d = min(e.end_ns, hi) - max(e.start_ns, lo)
            name = op_name(e.name)
            op_time[name] = op_time.get(name, 0.0) + d
        for e in _line(p, MODULES_LINE):
            if not (e.end_ns > lo and e.start_ns < hi):
                continue
            for k in kernels:
                if k in e.name:
                    kernel_s[k] += (e.end_ns - e.start_ns) * 1e-9
                    kernel_calls[k] += 1
    annotations = [e for e in host_events(planes) if e.name != WINDOW_EVENT]
    gaps.sort(key=lambda g: g[0] - g[1])
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total * 1e-9 / len(devs),
        chips=len(devs), kernel_s=kernel_s, kernel_calls=kernel_calls,
        device_ops=sorted(((n, t * 1e-9) for n, t in op_time.items()),
                          key=lambda x: -x[1])[:top],
        idle_gaps=[(_name_gap(g, annotations), (g[1] - g[0]) * 1e-9)
                   for g in gaps[:top]])


def describe(planes: Sequence[Plane], per_line: int = 5) -> dict:
    """Planes, lines and their most frequent event names: what to look
    at before trusting the reduction on a new device or JAX."""
    out = {}
    for p in planes:
        lines = {}
        for line, evs in p.lines.items():
            freq: Dict[str, int] = {}
            for e in evs:
                name = op_name(e.name)
                freq[name] = freq.get(name, 0) + 1
            lines[line] = {"events": len(evs), "top": sorted(
                freq.items(), key=lambda x: -x[1])[:per_line]}
        out[p.name] = lines
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    planes = load_planes(args[0])
    print(json.dumps(describe(planes), indent=1))
    try:
        print(json.dumps(vars(summarize(planes, ["window_reduce_fwd"]))))
    except ValueError as e:
        print(f"no summary: {e}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
