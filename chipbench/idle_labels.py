"""Idle gaps of the device put down to what the program's host threads did.

``trace_reduce`` labels each idle gap by the benchmark's ``cb.`` spans that
overlap it, which says only that a caller was waiting. The program marks
its own host work with ``sage.`` spans: a name that starts ``sage.wait.``
marks waiting, every other ``sage.`` name marks work. Here each idle gap of
the traced span is labelled

* by the ``sage.`` work spans that overlap it, ranked by their self time
  inside the gap (time not covered by a span nested inside them on the same
  thread line), the top two joined with ``+``;
* else by the ``sage.wait.`` spans, ranked the same way;
* else, where no ``sage.`` span overlaps the gap, by ``trace_reduce``'s
  rule over the ``cb.`` spans, unchanged.

``attribute()`` gives the longest gaps so labelled and ``idle_by_label``,
the idle seconds of all gaps per label, averaged over the devices (so they
sum to ``window_s - busy_s``). Run one cell as ``run.py --trace 1`` does,
and print the attribution of its trace as one more line:

    python3 chipbench/idle_labels.py --workload <name> --seed <n> --seconds <s>
"""
import run  # first: set-up is timed from run.py's import

import argparse
import json
import sys
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

import trace_reduce as tr

PROGRAM_PREFIX = "sage."
WAIT_PREFIX = "sage.wait."

Span = Tuple[str, float, float]  # (name, start_ns, end_ns)


def host_spans(planes: Sequence[tr.Plane]) -> Dict[Tuple[int, int], List[Span]]:
    """The ``sage.`` and ``cb.`` spans of the host planes, by thread line."""
    out: Dict[Tuple[int, int], List[Span]] = defaultdict(list)
    for pi, plane in enumerate(planes):
        if plane.name.startswith("/device:"):
            continue
        for li, line in enumerate(plane.lines):
            for name, s, d in line.events:
                if name.startswith((PROGRAM_PREFIX, tr.SPAN_PREFIX)):
                    out[(pi, li)].append((name, s, s + d))
    return out


def self_pieces(spans: Sequence[Span]) -> List[Span]:
    """One thread line's spans cut into disjoint pieces, each owned by the
    innermost span open there: summed per name, a span's self time."""
    out: List[Span] = []
    stack: List[Tuple[str, float]] = []  # (name, end) of the open spans
    cursor = 0.0

    def close():
        nonlocal cursor
        name, end = stack.pop()
        if end > cursor:
            out.append((name, cursor, end))
            cursor = end

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            close()
        if stack and s > cursor:
            out.append((stack[-1][0], cursor, s))
        cursor = s
        stack.append((name, e))
    while stack:
        close()
    return out


def _ranked(overlap: Dict[str, float]) -> str:
    return "+".join(sorted(overlap, key=lambda n: (-overlap[n], n))[:2])


def label_gaps(gaps: Sequence[Tuple[float, float]],
               lines: Dict[Tuple[int, int], List[Span]]) -> Dict[tuple, str]:
    """The label of each gap, by the rule in the module's docstring. One
    sweep over the gaps in start order, keeping the pieces that may still
    overlap a later gap."""
    pieces = []  # (start, end, kind, name); kind 0 work, 1 wait, 2 cb
    for spans in lines.values():
        for name, s, e in self_pieces(
                [x for x in spans if x[0].startswith(PROGRAM_PREFIX)]):
            pieces.append((s, e, 1 if name.startswith(WAIT_PREFIX) else 0, name))
        pieces += [(s, e, 2, name) for name, s, e in spans
                   if name.startswith(tr.SPAN_PREFIX) and name != tr.WINDOW_SPAN]
    pieces.sort()
    labels: Dict[tuple, str] = {}
    active: list = []
    k = 0
    for a, b in sorted(set(gaps)):
        while k < len(pieces) and pieces[k][0] < b:
            active.append(pieces[k])
            k += 1
        active = [p for p in active if p[1] > a]
        overlap = (defaultdict(float), defaultdict(float), defaultdict(float))
        for s, e, kind, name in active:
            lo, hi = max(s, a), min(e, b)
            if hi > lo:
                overlap[kind][name] += hi - lo
        work, wait, cb = overlap
        labels[(a, b)] = (_ranked(work) if work else _ranked(wait) if wait
                          else _ranked(cb) if cb else "no host span")
    return labels


def attribute(planes: Sequence[tr.Plane], *, chips: int, top: int = 10) -> dict:
    """``idle_gaps`` (the ``top`` longest, labelled) and ``idle_by_label``
    of the traced span on devices ``0..chips-1``; the gaps are those of
    ``trace_reduce.reduce``, and raise where it raises."""
    lines = host_spans(planes)
    windows = [(s, e) for spans in lines.values() for name, s, e in spans
               if name == tr.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {tr.WINDOW_SPAN} span, found {len(windows)}")
    w0, w1 = windows[0]
    wanted = {f"{tr.DEVICE_PREFIX}{i}" for i in range(chips)}
    devices = [p for p in planes if p.name in wanted]
    if len(devices) != chips:
        raise ValueError(f"device planes {sorted(wanted)} not all in the trace")
    gaps: List[Tuple[float, float]] = []
    for dev in devices:
        ops = [ln for ln in dev.lines if ln.name == tr.OPS_LINE]
        merged = tr.union([(max(s, w0), min(s + d, w1)) for ln in ops
                           for _, s, d in ln.events if min(s + d, w1) > max(s, w0)])
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    labels = label_gaps(gaps, lines)
    by_label: Dict[str, float] = defaultdict(float)
    for g in gaps:
        by_label[labels[g]] += (g[1] - g[0]) / 1e9 / len(devices)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {"idle_gaps": [[labels[g], (g[1] - g[0]) / 1e9] for g in longest],
            "idle_by_label": dict(sorted(by_label.items(), key=lambda kv: -kv[1]))}


def main(argv=None) -> int:
    """``run.py``'s traced run of a cell, then the attribution of its trace
    as one more line; ``idle_by_label`` also goes to standard error."""
    argv = list(sys.argv[1:] if argv is None else argv)
    kept = []  # the planes the harness loads, before it deletes the trace
    load = tr.load
    tr.load = lambda path: kept.append(load(path)) or kept[-1]
    rc = run.main(argv + ["--trace", "1"])
    if rc:
        return rc
    import harness

    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload", required=True)
    cell = harness.load_cell(run.ROOT, ap.parse_known_args(argv)[0].workload)
    out = attribute(kept[0], chips=cell.chips)
    print(f"idle_by_label: {json.dumps(out['idle_by_label'])}",
          file=sys.stderr, flush=True)
    print(json.dumps({"idle_labels": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
