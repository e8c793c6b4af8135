"""The program's own spans, read for the per-layer metrics of a traced run.

The program records its host spans in memory (``repro.core.telemetry``):
name, parent, counters, and ``time.perf_counter_ns`` times. It keeps the
last simulation that started under a profiler session, which in a traced
run is the window's first simulation, the one the trace holds. Every reader
built on this module takes that record; a program without it reads
nothing.

The spans are put on the trace's clock by one anchor: the program's
``setup`` span is opened around the call of ``sim_setup``, which the
harness wraps in its ``simulation_setup`` annotation, so the offset is that
annotation's start minus the span's start (the two differ by the few
microseconds the wrapper takes to open its annotation).
"""
from __future__ import annotations

import sys
from typing import Dict, List, Optional

import numpy as np

from benchmarks.chip import trace_reduce

ANCHOR_HOST = "simulation_setup"
ANCHOR_SPAN = "setup"
ROUTE_HOST = "route"
UNATTRIBUTED = "unattributed"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def traced_run():
    """The program's record of the traced simulation, or None."""
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    last = getattr(telemetry, "last_profiled", None)
    return last() if callable(last) else None


def cycles(rec) -> int:
    return int(rec.named("run")[0].counters["cycles"])


def ms_per_cycle(rec, name: str) -> Optional[float]:
    """Milliseconds a cycle in the spans called ``name``; None without
    one."""
    if not rec.named(name):
        return None
    return rec.seconds(name) * 1e3 / cycles(rec)


def offset_ns(rec, host) -> Optional[float]:
    """Trace time minus program time, from the one anchor of the run."""
    anchors = [h for h in host if h[2] == ANCHOR_HOST]
    setup = rec.named(ANCHOR_SPAN)
    if len(anchors) != 1 or len(setup) != 1:
        return None
    return anchors[0][0] - setup[0].t0_ns


def on_trace_clock(rec, host) -> Optional[List[list]]:
    """``[start_ns, end_ns, name]`` of the run's finished spans other than
    the root, on the trace's clock, in the order they opened."""
    off = offset_ns(rec, host)
    if off is None:
        return None
    spans = [s for s in rec.spans if s.name != "run" and s.t1_ns is not None]
    spans.sort(key=lambda s: (s.t0_ns, -s.t1_ns))
    return [[s.t0_ns + off, s.t1_ns + off, s.name] for s in spans]


def label_points(points, spans) -> List[str]:
    """The innermost span open at each point (the one that opened last),
    or ``UNATTRIBUTED``; ``spans`` are in the order they opened."""
    t = np.asarray(points, np.float64)
    label = np.full(t.shape, -1)
    for i, (s, e, _) in enumerate(spans):
        label[(t >= s) & (t < e)] = i
    return [spans[i][2] if i >= 0 else UNATTRIBUTED for i in label]


def idle_by_span(trace: dict, rec) -> Optional[Dict[str, float]]:
    """Idle seconds of the traced window by the program span open at each
    idle stretch's midpoint, averaged over the devices."""
    spans = on_trace_clock(rec, trace["host"])
    ops, w = trace["ops"], trace["window"]
    if spans is None or not ops or not w:
        return None
    out: Dict[str, float] = {}
    for dev_ops in ops.values():
        gaps = trace_reduce.idle_gaps(dev_ops, w)
        labels = label_points([(s + e) / 2 for s, e in gaps], spans)
        for (s, e), lab in zip(gaps, labels):
            out[lab] = out.get(lab, 0.0) + (e - s) / 1e9 / len(ops)
    return out


def anchor_error_ms(rec, host, name: str = "pack_tables"):
    """How far the mapped spans called ``name`` lie from the harness's
    ``route`` annotations: for each span, the distance from its start to
    the nearest annotation start and from its end to the nearest
    annotation end; the largest over the spans. A ``pack_tables`` span
    holds nothing but the wrapped packing call, so this is the anchor's
    error plus the wrapper's own few microseconds; a ``route_chunk`` span
    also holds the router's bookkeeping around the wrapped calls. None
    without such spans or annotations."""
    spans = on_trace_clock(rec, host)
    route = [h for h in host if h[2] == ROUTE_HOST]
    if spans is None or not route:
        return None
    gaps = [max(min(abs(h[0] - s) for h in route),
                min(abs(h[1] - e) for h in route))
            for s, e, span_name in spans if span_name == name]
    return max(gaps) / 1e6 if gaps else None
