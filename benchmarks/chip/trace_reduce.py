"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is first cut down to a plain dict (:func:`load_xplane`), which is
also the format of the recorded trace the tests read:

* ``ops``: per device, ``[start_ns, end_ns, name]`` of every operation that
  ran on it (the ``XLA Ops`` line of the device's plane);
* ``modules``: per device, the same for whole programs (``XLA Modules``);
* ``host``: ``[start_ns, end_ns, name]`` of the host annotations the
  harness wrote (``jax.profiler.TraceAnnotation``), on the same clock;
* ``window``: ``[start_ns, end_ns]`` of the traced window.

Everything below is plain interval arithmetic on that dict.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench_window"


def load_xplane(path: str, host_names: Iterable[str]) -> Dict:
    """The device and annotation events of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    keep = set(host_names) | {WINDOW}
    out = {"ops": {}, "modules": {}, "host": [], "window": None}
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        dev = re.fullmatch(r"/device:(TPU:\d+)", plane.name)
        for line in plane.lines:
            if dev and line.name in (OPS_LINE, MODULES_LINE):
                key = "ops" if line.name == OPS_LINE else "modules"
                out[key][dev.group(1)] = [
                    [e.start_ns, e.end_ns, e.name] for e in line.events]
            elif plane.name.startswith("/host:"):
                out["host"].extend([e.start_ns, e.end_ns, e.name]
                                   for e in line.events if e.name in keep)
    win = [h for h in out["host"] if h[2] == WINDOW]
    if win:
        out["window"] = [min(h[0] for h in win), max(h[1] for h in win)]
    return out


def merged(intervals: Iterable[Sequence]) -> List[List[float]]:
    """Union of ``[start, end, ...]`` intervals as sorted disjoint pairs."""
    out: List[List[float]] = []
    for s, e, *_ in sorted(intervals, key=lambda x: x[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clipped(intervals, lo: float, hi: float):
    """Intervals cut to ``[lo, hi]``; those outside are dropped."""
    return [[max(s, lo), min(e, hi), *rest] for s, e, *rest in intervals
            if e > lo and s < hi]


def busy_ns(ops, window) -> float:
    """Time in the window during which any operation ran on the device."""
    return sum(e - s for s, e in merged(clipped(ops, *window)))


def idle_gaps(ops, window) -> List[List[float]]:
    """The stretches of the window in which no operation ran."""
    lo, hi = window
    gaps, t = [], lo
    for s, e in merged(clipped(ops, lo, hi)):
        if s > t:
            gaps.append([t, s])
        t = max(t, e)
    if t < hi:
        gaps.append([t, hi])
    return gaps


def label_of(t: float, host) -> str:
    """The innermost host annotation open at time ``t``."""
    best: Optional[Sequence] = None
    for s, e, name in host:
        if name != WINDOW and s <= t < e and (best is None or s >= best[0]):
            best = (s, e, name)
    return best[2] if best else "unannotated"


def gaps_by_label(ops, window, host) -> Dict[str, float]:
    """Idle nanoseconds per host annotation open at each gap's midpoint."""
    out: Dict[str, float] = {}
    for s, e in idle_gaps(ops, window):
        lab = label_of((s + e) / 2, host)
        out[lab] = out.get(lab, 0.0) + (e - s)
    return out


def time_ns(ops, window, pattern: str) -> float:
    """Device nanoseconds of the operations whose name matches ``pattern``
    (a regular expression, searched), cut to the window."""
    rx = re.compile(pattern)
    return sum(e - s for s, e, name in clipped(ops, *window)
               if rx.search(name))


def leaves(ops) -> List[Sequence]:
    """The operations that contain no other: a loop (``while``) is listed
    with the operations of its body nested inside it."""
    ops = sorted(ops, key=lambda x: (x[0], -x[1]))
    return [op for op, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[0] >= op[1]]


def short_name(name: str, width: int = 160) -> str:
    """An HLO operation's text, cut to ``width`` characters."""
    return name if len(name) <= width else name[:width - 3] + "..."


def top_ops(ops, window, k: int = 10):
    """The ``k`` innermost operations that took the most device time."""
    tot: Dict[str, float] = {}
    for s, e, name in clipped(leaves(ops), *window):
        tot[name] = tot.get(name, 0.0) + (e - s)
    return sorted(tot.items(), key=lambda kv: -kv[1])[:k]
