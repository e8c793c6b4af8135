"""Protocol telemetry: spans, metric streams, latency histograms.

The paper's experimental story (Figs. 1-7) is entirely about *observing* a
running protocol — error curves, message economies, convergence under
churn. This module is the one home for that observability, with three
faces:

* **Spans, always on** — :func:`span` records a named interval of host
  code on ``time.perf_counter_ns``'s clock, with its parent (the span open
  when it started) and its counters (bytes copied, messages routed, rows
  served), and writes the same interval into the profiler's trace as the
  annotation ``gossip/<name>``. Every ``run_simulation`` call opens a run
  record (:func:`run`) whose root span ``run`` holds the engine's spans;
  the records of recent runs stay in a ring (:func:`runs`), and the last
  run that started under a profiler session stays reachable on its own
  (:func:`last_profiled`), which is how a reader of a profile finds the
  program's spans. Each XLA backend compile is recorded as a ``compile``
  span with its ``fun_name``, under the span open when it ended.
* **Per-cycle metric streams** — ``METRIC_STREAMS`` is a registered schema
  (same both-ways docs-gate philosophy as ``WIRE_CODECS``/``FAULT_MODELS``:
  the table in docs/OBSERVABILITY.md is cross-checked against this registry
  by ``tools/check_docs.py``) of the series both engines emit identically
  when a :class:`Telemetry` is armed: the message economy (its
  balance invariant, continuously emitted), wire bytes, receiver
  occupancy, fault counters, EF residual RMS and the online fraction.
  Because the reference engine and all three sharded packings emit the
  same numbers, the metric stream itself is a cross-engine parity surface
  (tests/test_telemetry.py). An armed :class:`Telemetry` also keeps the
  run records of its runs and exports them as Chrome trace-event JSON
  (:meth:`Telemetry.export_chrome_trace`), summarized offline by
  ``tools/trace_report.py``.
* **Latency histograms** — :class:`LatencyHistogram` is the fixed-bucket
  log-scale histogram behind every latency percentile in the repo
  (``GossipServer`` batch latency, ``BENCH_serving.json`` p50/p90/p99/p999),
  replacing ad-hoc per-call percentile math.

The hard contract (docs/CONTRACTS.md): **telemetry is a pure read**.
Spans run outside every jitted program and change none. ``telemetry=None``
(the default everywhere) compiles to the exact pre-telemetry engines — the
armed stream paths are statically gated, the same mechanism as the fault
machinery — and an armed :class:`Telemetry` must leave error curves and
all protocol state bitwise identical on both engines. Telemetry never
touches ``jax.random``: spans read the host clock and streams are
integer/float *reads* of state the engines already computed, so the
pinned threefry chain (tools/lint/rng_allowlist.py) cannot shift.
"""
from __future__ import annotations

import itertools
import json
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

# ---------------------------------------------------------------------------
# metric-stream registry
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MetricStream:
    """Schema entry for one registered per-run metric series.

    ``cadence`` is "cycle" (one value per gossip cycle) or "eval" (one
    value per eval point). ``parity`` marks the stream as part of the
    cross-engine parity surface: reference and sharded runs at a matched
    seed must emit it bitwise-identically (integers exactly; floats via
    identical op sequences on bitwise-equal state)."""
    name: str
    cadence: str            # "cycle" | "eval"
    dtype: str              # "int" | "float"
    parity: bool
    description: str


def _stream(name, cadence, dtype, parity, description):
    return name, MetricStream(name, cadence, dtype, parity, description)


# The registered schema. Every stream is emitted by BOTH engines (and by
# every sharded packing) when a run is armed; docs/OBSERVABILITY.md mirrors
# this table and tools/check_docs.py fails when either side drifts.
METRIC_STREAMS: Dict[str, MetricStream] = dict([
    _stream("sent", "cycle", "int", True,
            "messages entering the network this cycle (send_ok senders)"),
    _stream("delivered", "cycle", "int", True,
            "messages accepted by an online node within the K rounds"),
    _stream("lost", "cycle", "int", True,
            "messages due this cycle whose destination was offline"),
    _stream("overflow", "cycle", "int", True,
            "arrivals beyond the K winner rounds (truncated receives)"),
    _stream("in_flight", "cycle", "int", True,
            "messages still in the delay buffer after this cycle "
            "(cumulative sent - delivered - lost - overflow; the PR 1 "
            "balance invariant, continuously emitted)"),
    _stream("wire_bytes", "cycle", "int", True,
            "bytes put on the wire this cycle (sent x per-message bytes "
            "of the run's wire codec)"),
    _stream("recv_nodes", "cycle", "int", True,
            "nodes receiving at least one message (round-1 winners; the "
            "numerator of the router's compaction occupancy)"),
    _stream("multi_nodes", "cycle", "int", True,
            "nodes receiving in round 2 or later (the compact packing's "
            "subset)"),
    _stream("online_nodes", "cycle", "int", True,
            "nodes online this cycle (the churn trace row sum)"),
    _stream("corrupted", "cycle", "int", True,
            "Byzantine sends this cycle (fault model armed and send_ok)"),
    _stream("gated", "cycle", "int", True,
            "receives rejected by the defense screen this cycle"),
    _stream("clipped", "cycle", "int", True,
            "receives rescaled by norm_clip this cycle"),
    _stream("ef_residual_rms", "eval", "float", True,
            "RMS per-node L2 norm of the error-feedback residual at each "
            "eval point (0.0 for codecs without EF state)"),
])


# ---------------------------------------------------------------------------
# spans: always on, on the profiler's clock
# ---------------------------------------------------------------------------

# span tracks become named Perfetto threads in the Chrome export
TRACKS: Tuple[str, ...] = ("host", "control", "device", "serving", "eval")

# span name -> (track, phase). The names are a contract: the chip
# benchmark's readers find spans by them, so span() refuses any other name
SPAN_NAMES: Dict[str, Tuple[str, str]] = {
    "run": ("host", "one run_simulation call, either engine (the root)"),
    "setup": ("host", "churn trace, eval subset, upload of the data"),
    "key_schedule": ("control", "the per-cycle threefry subkeys"),
    "stage_draws": ("control", "device draws of every chunk, copied to "
                               "the host"),
    "route_chunk": ("control", "host winner routing for one chunk"),
    "pack_tables": ("control", "the chosen packing's routing tables"),
    "upload_tables": ("control", "host-to-device copy of one chunk's "
                                 "tables"),
    "dispatch": ("device", "dispatch of one chunk's scan program"),
    "collect_results": ("device", "drain of the deferred eval results "
                                  "(the sync point)"),
    "cycle": ("device", "one reference-engine cycle (dispatch + sync)"),
    "eval": ("eval", "population error at an eval point (reference)"),
    "snapshot": ("serving", "snapshot build + serve_hook call"),
    "snapshot_adopt": ("serving", "GossipServer waits for and adopts a "
                                  "snapshot"),
    "submit": ("serving", "GossipServer.submit"),
    "flush": ("serving", "GossipServer.flush"),
    "serve_batch": ("serving", "assemble + answer one query batch"),
    "serve_device": ("serving", "one batch from dispatch to answers "
                                "ready"),
    "compile": ("host", "one XLA backend compile (or persistent-cache "
                        "load)"),
}

# profiler annotations carry this prefix, so no program span shares a name
# with an annotation written around the program from outside
ANNOTATION_PREFIX = "gossip/"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
RUNS_KEPT = 64          # run records kept (the ring)
LOOSE_KEPT = 4096       # spans recorded outside any run


class Span:
    """One span on ``time.perf_counter_ns``'s clock: ``[t0_ns, t1_ns)``
    (``t1_ns`` is None while it is open), the id of the span open when it
    started (None for a root), the id of its run (None outside any run),
    and its counters."""
    __slots__ = ("id", "parent", "run", "name", "t0_ns", "t1_ns",
                 "counters")

    def __init__(self, name: str, counters: Dict[str, object]):
        self.name, self.counters = name, counters
        self.id = self.parent = self.run = self.t0_ns = self.t1_ns = None

    @property
    def seconds(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9

    @property
    def track(self) -> str:
        return SPAN_NAMES[self.name][0]

    def __repr__(self):
        return (f"Span({self.name!r}, id={self.id}, parent={self.parent}, "
                f"run={self.run}, {self.t0_ns}-{self.t1_ns}, "
                f"{self.counters})")


@dataclass
class RunRecord:
    """What one ``run_simulation`` call recorded: its spans (the root
    ``run`` first, the others in the order they opened, a ``compile`` span
    when its compile ended) and whether a profiler session was on when the
    run started (``profiled``)."""
    id: int
    engine: str
    profiled: bool
    spans: List[Span] = field(default_factory=list)

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def seconds(self, name: str) -> float:
        """Total seconds of the finished spans called ``name``."""
        return sum(s.seconds for s in self.named(name)
                   if s.t1_ns is not None)

    def total(self, name: str, counter: str):
        """Sum of one counter over the spans called ``name``."""
        return sum(s.counters.get(counter, 0) for s in self.named(name))


class _Recorder:
    """The process's span record: a ring of recent run records, the last
    run that ran under a profiler session, the spans opened outside any
    run, and a stack of open spans per thread."""

    def __init__(self):
        self.runs: deque = deque(maxlen=RUNS_KEPT)
        self.loose: deque = deque(maxlen=LOOSE_KEPT)
        self.last_profiled: Optional[RunRecord] = None
        self.ids = itertools.count(1)
        self.local = threading.local()
        self.listening = False

    def stack(self) -> list:
        """Open ``(span, run record)`` pairs of this thread, innermost
        last."""
        try:
            return self.local.stack
        except AttributeError:
            self.local.stack = []
            return self.local.stack

    def place(self, s: Span, record: Optional[RunRecord],
              stack) -> Optional[RunRecord]:
        """Give ``s`` its id, parent and run, file it, and return its run
        record (None outside any run)."""
        parent, rec = stack[-1] if stack else (None, None)
        if record is not None:
            parent, rec = None, record
        s.id = next(self.ids)
        s.parent = parent.id if parent is not None else None
        s.run = rec.id if rec is not None else None
        (rec.spans if rec is not None else self.loose).append(s)
        return rec

    def listen(self) -> None:
        """Record every backend compile as a ``compile`` span from now on."""
        if not self.listening:
            self.listening = True
            jax.monitoring.register_event_duration_secs_listener(
                self.on_duration)

    def on_duration(self, event: str, duration: float, **kw) -> None:
        if event != COMPILE_EVENT:
            return
        t1 = time.perf_counter_ns()
        s = Span("compile", {"fun_name": str(kw.get("fun_name", "")),
                             "seconds": float(duration)})
        self.place(s, None, self.stack())
        s.t0_ns, s.t1_ns = t1 - int(duration * 1e9), t1


_REC = _Recorder()


class _Open:
    """The context of one span: opens it on the span stack and in the
    profiler (``TraceAnnotation("gossip/<name>")``), closes both."""
    __slots__ = ("span", "record", "ann")

    def __init__(self, name: str, counters, record=None):
        if name not in SPAN_NAMES:
            raise ValueError(f"unregistered span name {name!r} "
                             f"(registered: {sorted(SPAN_NAMES)})")
        self.span = Span(name, counters)
        self.record = record

    def __enter__(self) -> Span:
        _REC.listen()
        stack = _REC.stack()
        s = self.span
        rec = _REC.place(s, self.record, stack)
        stack.append((s, rec))
        self.ann = jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + s.name)
        self.ann.__enter__()
        s.t0_ns = time.perf_counter_ns()
        return s

    def __exit__(self, *exc):
        s = self.span
        s.t1_ns = time.perf_counter_ns()
        if s.counters and jax.profiler.TraceAnnotation.is_enabled():
            self.ann.set_metadata(**{k: v for k, v in s.counters.items()
                                     if isinstance(v, (int, float, str))})
        self.ann.__exit__(*exc)
        stack = _REC.stack()
        while stack and stack.pop()[0] is not s:
            pass
        return False


def span(name: str, **counters) -> _Open:
    """Record ``name`` around a block, always: ``with span("route_chunk",
    chunk=i) as s: ...; s.counters["mode"] = mode``. The span's parent is
    the span open when it started; inside a run (:func:`run`) it joins
    that run's record. The same interval is a profiler annotation named
    ``gossip/<name>``. Counters are numbers or strings, set at the start
    or while the span is open."""
    return _Open(name, counters)


@contextmanager
def run(engine: str, telemetry: Optional["Telemetry"] = None, **counters):
    """Open a run record and its root span ``run``; yields the record.

    The record joins the ring (:func:`runs`); a run that starts under a
    profiler session becomes :func:`last_profiled` too, and an armed
    ``telemetry`` keeps it for its reports."""
    rec = RunRecord(next(_REC.ids), engine,
                    jax.profiler.TraceAnnotation.is_enabled())
    _REC.runs.append(rec)
    if rec.profiled:
        _REC.last_profiled = rec
    if telemetry is not None:
        telemetry.runs.append(rec)
    with _Open("run", counters, record=rec):
        yield rec


def runs() -> List[RunRecord]:
    """The recent run records, oldest first (at most ``RUNS_KEPT``)."""
    return list(_REC.runs)


def last_profiled() -> Optional[RunRecord]:
    """The last run that started while a profiler session was on."""
    return _REC.last_profiled


def loose_spans() -> List[Span]:
    """Spans recorded outside any run, oldest first."""
    return list(_REC.loose)


# ---------------------------------------------------------------------------
# latency histogram
# ---------------------------------------------------------------------------


class LatencyHistogram:
    """Fixed-bucket log-scale latency histogram (seconds).

    64 buckets, 8 per decade from 1 microsecond to 100 seconds, plus an
    underflow and an overflow bucket — the same fixed edges everywhere, so
    histograms from different runs/servers merge exactly (bucket-wise
    addition) and bucket dumps in BENCH_serving.json stay comparable
    across PRs. Percentiles interpolate linearly inside the owning bucket
    and are clamped to the exact observed [min, max], so single-sample and
    constant-sample histograms report exact values."""

    EDGES = np.logspace(-6.0, 2.0, 8 * 8 + 1)     # 65 edges, 64 buckets

    def __init__(self):
        self.counts = np.zeros(self.EDGES.size + 1, np.int64)
        self.count = 0
        self.total = 0.0
        self.min_value = float("inf")
        self.max_value = 0.0

    def record(self, seconds: float) -> None:
        self.record_many([seconds])

    def record_many(self, seconds) -> None:
        v = np.asarray(seconds, np.float64).ravel()
        if v.size == 0:
            return
        idx = np.searchsorted(self.EDGES, v, side="right")
        np.add.at(self.counts, idx, 1)
        self.count += int(v.size)
        self.total += float(v.sum())
        self.min_value = min(self.min_value, float(v.min()))
        self.max_value = max(self.max_value, float(v.max()))

    def merge(self, other: "LatencyHistogram") -> "LatencyHistogram":
        self.counts += other.counts
        self.count += other.count
        self.total += other.total
        self.min_value = min(self.min_value, other.min_value)
        self.max_value = max(self.max_value, other.max_value)
        return self

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def percentile(self, q: float) -> float:
        """q in [0, 100] -> seconds (0.0 on an empty histogram)."""
        if self.count == 0:
            return 0.0
        target = q / 100.0 * self.count
        cum = 0.0
        for i, c in enumerate(self.counts):
            if c == 0:
                continue
            if cum + c >= target:
                lo = self.EDGES[i - 1] if i > 0 else self.min_value
                hi = (self.EDGES[i] if i < self.EDGES.size
                      else self.max_value)
                frac = (target - cum) / c
                v = lo + frac * (hi - lo)
                return float(min(max(v, self.min_value), self.max_value))
            cum += c
        return self.max_value

    @property
    def p50(self) -> float:
        return self.percentile(50.0)

    @property
    def p90(self) -> float:
        return self.percentile(90.0)

    @property
    def p99(self) -> float:
        return self.percentile(99.0)

    @property
    def p999(self) -> float:
        return self.percentile(99.9)

    def to_dict(self) -> dict:
        """JSON-ready dump: only the occupied buckets (sparse — the fixed
        edge grid is implied by ``bucket_le``, each bucket's upper edge)."""
        nz = np.nonzero(self.counts)[0]
        return dict(
            count=self.count,
            mean_s=self.mean,
            min_s=self.min_value if self.count else 0.0,
            max_s=self.max_value,
            p50_s=self.p50, p90_s=self.p90, p99_s=self.p99,
            p999_s=self.p999,
            bucket_le=[(float(self.EDGES[i]) if i < self.EDGES.size
                        else float("inf")) for i in nz],
            bucket_counts=[int(self.counts[i]) for i in nz],
        )


# ---------------------------------------------------------------------------
# the Telemetry object
# ---------------------------------------------------------------------------


class Telemetry:
    """Armed observability for one (or several back-to-back) runs.

    Pass as ``run_simulation(..., telemetry=tel)`` (either engine) and/or
    ``GossipServer(telemetry=tel)``. Collects the registered metric
    streams and latency histograms and keeps the run records of the runs
    it was armed for (their spans: :attr:`spans`); export with
    :meth:`export_chrome_trace`, summarize with :meth:`phase_report` or
    ``tools/trace_report.py`` on the exported file.

    Arming one Telemetry across several sequential runs is supported —
    spans share one clock and stream segments concatenate in run order
    (each run's ``in_flight`` balance restarts from zero at its own first
    cycle)."""

    def __init__(self, label: str = ""):
        self.label = label
        self.streams: Dict[str, List] = {n: [] for n in METRIC_STREAMS}
        self.runs: List[RunRecord] = []
        self.histograms: Dict[str, LatencyHistogram] = {}
        self.annotations: Dict[str, object] = {}

    # ------------------------------------------------------------- streams
    def emit(self, name: str, values) -> None:
        """Append value(s) to a registered stream (scalar or sequence)."""
        if name not in METRIC_STREAMS:
            raise KeyError(f"unregistered metric stream {name!r} "
                           f"(registered: {sorted(METRIC_STREAMS)})")
        if np.ndim(values) == 0:
            self.streams[name].append(
                float(values) if METRIC_STREAMS[name].dtype == "float"
                else int(values))
        else:
            kind = METRIC_STREAMS[name].dtype
            self.streams[name].extend(
                float(v) if kind == "float" else int(v) for v in values)

    def emit_row(self, **values) -> None:
        """Emit one value into several streams at once."""
        for name, v in values.items():
            self.emit(name, v)

    def stream_array(self, name: str) -> np.ndarray:
        kind = METRIC_STREAMS[name].dtype
        return np.asarray(self.streams[name],
                          np.float64 if kind == "float" else np.int64)

    def histogram(self, name: str) -> LatencyHistogram:
        return self.histograms.setdefault(name, LatencyHistogram())

    # --------------------------------------------------------------- spans
    @property
    def spans(self) -> List[Span]:
        """The finished spans of the armed runs, run by run."""
        return [s for r in self.runs for s in r.spans if s.t1_ns is not None]

    def phase_seconds(self) -> Dict[str, float]:
        """Total span seconds per span name (the per-phase summary)."""
        out: Dict[str, float] = {}
        for s in self.spans:
            out[s.name] = out.get(s.name, 0.0) + s.seconds
        return out

    def compile_total(self) -> int:
        return sum(s.name == "compile" for s in self.spans)

    def wall_seconds(self) -> float:
        spans = self.spans
        if not spans:
            return 0.0
        return (max(s.t1_ns for s in spans)
                - min(s.t0_ns for s in spans)) / 1e9

    # ------------------------------------------------------------ reports
    def phase_report(self) -> str:
        """Printable per-phase table (what the ``--trace`` example flags
        show; the standalone equivalent over an exported file is
        ``tools/trace_report.py``)."""
        wall = self.wall_seconds()
        spans = self.spans
        lines = [f"telemetry: {len(spans)} spans, "
                 f"{self.compile_total()} jit compiles, "
                 f"{wall:.3f}s spanned wall clock"]
        counts: Dict[str, int] = {}
        for s in spans:
            counts[s.name] = counts.get(s.name, 0) + 1
        for name, secs in sorted(self.phase_seconds().items(),
                                 key=lambda kv: -kv[1]):
            pct = 100.0 * secs / wall if wall > 0 else 0.0
            lines.append(f"  {name:<16} {secs:>9.3f}s {pct:>5.1f}%  "
                         f"x{counts[name]}")
        by_id = {s.id: s for s in spans}
        for s in spans:
            if s.name == "compile":
                under = by_id.get(s.parent)
                lines.append(f"  compile {s.counters['fun_name']} "
                             f"{s.seconds * 1e3:.1f}ms under "
                             f"{under.name if under else '-'}")
        sent = self.stream_array("sent")
        wb = self.stream_array("wire_bytes")
        if sent.size:
            lines.append(f"  streams: {sent.size} cycles, "
                         f"{sent.mean():,.0f} msgs/cycle sent, "
                         f"{wb.mean():,.0f} wire B/cycle")
        for name, h in sorted(self.histograms.items()):
            if h.count:
                lines.append(
                    f"  hist {name}: n={h.count} p50={h.p50 * 1e3:.3f}ms "
                    f"p99={h.p99 * 1e3:.3f}ms p999={h.p999 * 1e3:.3f}ms")
        return "\n".join(lines)

    # ------------------------------------------------------- chrome export
    def export_chrome_trace(self, path) -> Path:
        """Write Chrome trace-event JSON (the ``chrome://tracing`` /
        Perfetto "JSON" flavor): one complete ("X") event per span on a
        named thread per track (a ``compile`` span carries its
        ``fun_name``), and the per-cycle metric streams as counter ("C")
        events on a synthetic pid=1 timeline where 1 cycle == 1
        microsecond (protocol time, not wall time — labeled as such).
        Streams, histograms and annotations ride in ``otherData`` so
        ``tools/trace_report.py`` can rebuild the full summary from the
        file alone."""
        events: List[dict] = [
            {"ph": "M", "pid": 0, "tid": 0, "name": "process_name",
             "args": {"name": f"gossip host{' ' + self.label if self.label else ''}"}},
            {"ph": "M", "pid": 1, "tid": 0, "name": "process_name",
             "args": {"name": "protocol streams (1 cycle = 1 us)"}},
        ]
        for tid, track in enumerate(TRACKS):
            events.append({"ph": "M", "pid": 0, "tid": tid,
                           "name": "thread_name", "args": {"name": track}})
        spans = self.spans
        origin = min((s.t0_ns for s in spans), default=0)
        for s in spans:
            args = {k: (v if isinstance(v, (int, float, str, bool))
                        else str(v)) for k, v in s.counters.items()}
            args.update(id=s.id, parent=s.parent, run=s.run)
            events.append({"ph": "X", "pid": 0,
                           "tid": TRACKS.index(s.track), "name": s.name,
                           "ts": (s.t0_ns - origin) / 1e3,
                           "dur": (s.t1_ns - s.t0_ns) / 1e3,
                           "args": args, "cat": s.track})
        for name, spec in METRIC_STREAMS.items():
            if spec.cadence != "cycle":
                continue
            vals = self.streams[name]
            for c, v in enumerate(vals):
                events.append({"ph": "C", "pid": 1, "tid": 0, "name": name,
                               "ts": float(c), "args": {name: v}})
        payload = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "label": self.label,
                "annotations": self.annotations,
                "streams": {n: self.streams[n] for n in METRIC_STREAMS},
                "histograms": {n: h.to_dict()
                               for n, h in self.histograms.items()},
                "compile_total": self.compile_total(),
            },
        }
        fp = Path(path)
        fp.write_text(json.dumps(payload) + "\n")
        return fp
