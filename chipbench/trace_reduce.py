"""From a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

The benchmark marks the traced part of its window with a host span named
``cb.traced`` and its own calls with further ``cb.`` spans (``cb.submit``,
``cb.wait``, ``cb.register``). On each device plane (``/device:TPU:<i>``)
the reduction takes:

* busy time: the union of the intervals of the ``XLA Ops`` line's events
  inside the traced span, averaged over the devices;
* per-operation device time, summed over the devices and averaged;
* executions of each compiled program, from the ``XLA Modules`` line;
* idle gaps: the stretches inside the span that no operation covers, the
  longest labelled by the benchmark's host spans that overlap them most.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "cb."
WINDOW_SPAN = "cb.traced"


@dataclass
class Line:
    name: str
    events: List[Tuple[str, float, float]]  # (name, start_ns, duration_ns)


@dataclass
class Plane:
    name: str
    lines: List[Line] = field(default_factory=list)


def load(path: str) -> List[Plane]:
    """The planes of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [Plane(p.name, [Line(ln.name, [(e.name, e.start_ns, e.duration_ns)
                                          for e in ln.events])
                           for ln in p.lines])
            for p in data.planes]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, non-overlapping cover of ``(start, end)`` pairs."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def op_name(event: str) -> str:
    """An HLO instruction's name without its text: ``%fusion.3 = f32[..]
    fusion(..)`` becomes ``fusion.3``."""
    return event.split(" = ", 1)[0].lstrip("%")


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def _label(gap, spans) -> str:
    a, b = gap
    overlap: Dict[str, float] = defaultdict(float)
    for name, s, e in spans:
        lo, hi = _clip(s, e, a, b)
        if hi > lo:
            overlap[name] += hi - lo
    if not overlap:
        return "no host span"
    top = sorted(overlap, key=lambda n: (-overlap[n], n))[:2]
    return "+".join(top)


def reduce(planes: Sequence[Plane], *, chips: int, top: int = 10) -> dict:
    """Device numbers of the traced span on devices ``0..chips-1``; raises
    if the span or one of those device planes is missing."""
    spans = [(name, s, s + d) for p in planes if not p.name.startswith("/device:")
             for ln in p.lines for name, s, d in ln.events
             if name.startswith(SPAN_PREFIX)]
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(windows)}")
    w0, w1 = windows[0]
    spans = [x for x in spans if x[0] != WINDOW_SPAN]
    wanted = {f"{DEVICE_PREFIX}{i}" for i in range(chips)}
    devices = [p for p in planes if p.name in wanted]
    if len(devices) != chips:
        raise ValueError(f"device planes {sorted(wanted)} not all in the trace")
    busy = 0.0
    ops: Dict[str, float] = defaultdict(float)
    modules: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    gaps: List[Tuple[float, float]] = []
    for dev in devices:
        lines = {ln.name: ln for ln in dev.lines}
        intervals = []
        for name, s, d in lines[OPS_LINE].events if OPS_LINE in lines else ():
            a, b = _clip(s, s + d, w0, w1)
            if b > a:
                intervals.append((a, b))
                ops[op_name(name)] += (b - a) / 1e9
        merged = union(intervals)
        busy += sum(b - a for a, b in merged) / 1e9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for name, s, d in lines[MODULES_LINE].events if MODULES_LINE in lines else ():
            if w0 <= s and s + d <= w1:
                modules[name][0] += 1
                modules[name][1] += d / 1e9
    n = len(devices)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy / n,
        "devices": n,
        "ops": {k: v / n for k, v in ops.items()},
        "modules": {k: (int(c), s) for k, (c, s) in modules.items()},
        "idle_gaps": [[_label(g, spans), (g[1] - g[0]) / 1e9] for g in longest],
    }


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The ``breakdown`` key of a traced run's result line."""
    ops = sorted(reduced["ops"].items(), key=lambda kv: (-kv[1], kv[0]))[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": reduced["idle_gaps"][:top]}
