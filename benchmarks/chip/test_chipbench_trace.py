"""The trace reduction: busy and idle time, kernel time by name, idle gaps
by host annotation; on a made-up trace checked by hand, and on a small
trace recorded on a TPU v5e checked against a rasterised count."""
import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import run, trace_reduce as tr

RECORDED = Path(__file__).parent / "fixtures" / "trace_v5e_small.json.gz"

OPS = [[10, 20, "a"], [15, 30, "b"], [40, 50, "a"], [45, 47, "c"],
       [90, 120, "b"]]
HOST = [[0, 100, tr.WINDOW], [25, 45, "route"], [30, 38, "serve_batch"],
        [60, 95, "snapshot_wait"]]
WIN = [0, 100]


def test_union_of_overlapping_ops():
    assert tr.merged(OPS) == [[10, 30], [40, 50], [90, 120]]
    assert tr.busy_ns(OPS, WIN) == 20 + 10 + 10      # the last op is cut


def test_idle_gaps_cover_the_rest_of_the_window():
    gaps = tr.idle_gaps(OPS, WIN)
    assert gaps == [[0, 10], [30, 40], [50, 90]]
    assert sum(e - s for s, e in gaps) + tr.busy_ns(OPS, WIN) == 100


def test_gaps_are_labelled_by_the_innermost_annotation():
    # [0,10] mid 5: none; [30,40] mid 35: serve_batch inside route;
    # [50,90] mid 70: snapshot_wait
    assert tr.gaps_by_label(OPS, WIN, HOST) == {
        "unannotated": 10, "serve_batch": 10, "snapshot_wait": 40}


def test_time_by_name():
    assert tr.time_ns(OPS, WIN, r"^a$") == 20
    assert tr.time_ns(OPS, WIN, r"b") == 15 + 10
    assert tr.time_ns(OPS, [0, 35], r"b") == 15 + 0


def test_loops_are_not_counted_twice_in_the_top_ops():
    # a loop whose body ran "a" and "b": the innermost operations are listed
    nested = [[0, 50, "loop"], [5, 20, "a"], [25, 40, "b"], [60, 70, "a"]]
    assert tr.leaves(nested) == [[5, 20, "a"], [25, 40, "b"], [60, 70, "a"]]
    assert tr.top_ops(nested, WIN) == [("a", 25), ("b", 15)]
    assert tr.busy_ns(nested, WIN) == 60


def test_breakdown_averages_devices():
    trace = dict(ops={"TPU:0": [[40, 50, "a"]], "TPU:1": [[0, 100, "a"]]},
                 modules={}, host=HOST, window=WIN)
    b = run.breakdown(trace)
    assert dict(b["device_ops"])["a"] == pytest.approx((10 + 100) / 2e9)
    # TPU:0 idles in [0,40] (route at 20) and [50,100] (snapshot_wait at 75)
    assert dict(b["idle_gaps"]) == pytest.approx(
        {"unannotated": 40 / 2e9, "snapshot_wait": 50 / 2e9})
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def _raster(ops, window):
    """Busy nanoseconds by marking every nanosecond: an independent count."""
    lo, hi = (int(round(x)) for x in window)
    busy = np.zeros(hi - lo, bool)
    for s, e, _ in ops:
        busy[max(int(round(s)) - lo, 0):max(int(round(e)) - lo, 0)] = True
    return int(busy.sum())


def test_recorded_trace_reduces_like_a_raster():
    with gzip.open(RECORDED, "rt") as f:
        trace = json.load(f)
    w = trace["window"]
    ops = trace["ops"]["TPU:0"]
    assert ops, "the recorded trace has device operations"
    busy = tr.busy_ns(ops, w)
    assert busy == pytest.approx(_raster(ops, w), abs=len(ops) + 2)
    gaps = tr.gaps_by_label(ops, w, trace["host"])
    assert sum(gaps.values()) + busy == pytest.approx(w[1] - w[0])
    # the receive kernel and the chunk program are where the trace says
    assert tr.time_ns(ops, w, r"^%fused_receive_apply[.\d]* = ") > 0
    assert tr.time_ns(trace["modules"]["TPU:0"], w, r"chunk_fn") > 0
