"""The host control plane's readers of the ``chunk_in_flight`` counter on
the program's ``route_chunk`` spans: ``route_hidden_share`` and
``route_wait_ms_per_cycle``. On a traced CPU run at 2048 nodes, on a
made-up record and trace, and without the program's span record (a
program older than it)."""
import pytest

from benchmarks.chip import program_spans, run
from benchmarks.chip.test_chipbench_check import small_cell
from benchmarks.chip.test_chipbench_program_spans import (  # noqa: F401
    _record, traced)
from repro.core import telemetry

ROUTE = ("route_hidden_share", "route_wait_ms_per_cycle")
CELLS = ("spambase-1m.extreme.serve", "spambase-1m.sparse",
         "spambase-4m.extreme.mesh")


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_lists_the_route_metrics(cell):
    names = {m["name"] for m in run.load_cell(cell)["per_layer"]}
    assert set(ROUTE) <= names, sorted(names)


@pytest.mark.parametrize("cell,metric", [
    (cell, metric) for cell in CELLS[:2] for metric in ROUTE])
def test_route_reader_reads_a_number_on_a_cpu_run(traced, cell, metric):
    line, trace, rec = traced[cell]
    routes = rec.named("route_chunk")
    assert routes and "chunk_in_flight" not in routes[0].counters
    assert all(s.counters["chunk_in_flight"] in (0, 1) for s in routes[1:])
    if metric == "route_hidden_share":
        assert metric in line["metrics"], sorted(line["metrics"])
        value = line["metrics"][metric]["value"]
        assert 0 <= value <= 100
    else:
        # the CPU has no device plane: plant one operation per program
        # dispatch, where the device would run the chunk, on the trace's
        # own host annotations and window
        assert metric not in line["metrics"]
        spans = program_spans.on_trace_clock(rec, trace["host"])
        ops = [[s, e, "chunk_fn"] for s, e, n in spans if n == "dispatch"]
        trace = dict(trace, ops={"TPU:0": ops})
        ctx = dict(trace=trace, window=trace["window"],
                   cycles=small_cell(cell)["traffic"]["cycles"])
        value = run.load_reader(metric)(ctx)
    assert isinstance(value, (int, float)) and value >= 0, value


def test_route_hidden_share_on_a_synthetic_record(monkeypatch):
    """The share of the routings with a chunk ahead of them that ended
    while that chunk was on the device; nothing from a program whose
    ``route_chunk`` spans carry no ``chunk_in_flight`` (the first routing
    of a run never does)."""
    rec = _record([("route_chunk", 0, 1), ("route_chunk", 2, 3),
                   ("route_chunk", 4, 5), ("route_chunk", 6, 7)])
    monkeypatch.setattr(telemetry, "last_profiled", lambda: rec)
    read = lambda: run.load_reader("route_hidden_share")({})
    assert read() is None
    for s, flag in zip(rec.named("route_chunk")[1:], (1, 0, 1)):
        s.counters["chunk_in_flight"] = flag
    assert read() == pytest.approx(100.0 * 2 / 3)
    for s in rec.named("route_chunk")[1:]:
        s.counters["chunk_in_flight"] = 1
    assert read() == 100.0


def test_route_wait_on_a_synthetic_trace(monkeypatch):
    """The device's idle time under the routings that carry
    ``chunk_in_flight``, cut at the spans' edges, children included, over
    the devices and the cycles. The spans map to trace time t - base + 10.
    The first routing, [210, 310), lies in idle time but carries no
    counter; the second, [410, 710), holds idle [520, 700) on one device
    and [600, 700) on the other; the third, [810, 910), none."""
    base = 5 * 10**12
    rec = _record([("setup", base + 10, base + 150),
                   ("route_chunk", base + 200, base + 300),
                   ("route_chunk", base + 400, base + 700),
                   ("pack_tables", base + 650, base + 690),
                   ("route_chunk", base + 800, base + 900)], cycles=10)
    host = [[0, 1000, "bench_window"], [20, 140, "simulation_setup"]]
    ops = {"TPU:0": [[160, 200], [320, 520], [700, 950]],
           "TPU:1": [[160, 200], [320, 600], [700, 950]]}
    trace = dict(ops=ops, modules={}, host=host, window=[0, 1000])
    monkeypatch.setattr(telemetry, "last_profiled", lambda: rec)
    read = lambda: run.load_reader("route_wait_ms_per_cycle")(
        dict(trace=trace, window=trace["window"]))
    assert read() is None
    for s, flag in zip(rec.named("route_chunk")[1:], (0, 1)):
        s.counters["chunk_in_flight"] = flag
    assert read() == pytest.approx((180 + 100) / 2 / 1e6 / 10)
    assert run.load_reader("route_wait_ms_per_cycle")(
        dict(trace=dict(trace, host=[]), window=trace["window"])) is None


@pytest.mark.parametrize("metric", ROUTE)
def test_route_reader_reads_nothing_without_the_span_record(monkeypatch,
                                                             metric):
    """Against a program that keeps no span record the readers give None
    and raise nothing."""
    monkeypatch.delattr(telemetry, "last_profiled")
    ctx = dict(trace=dict(ops={"TPU:0": [[0, 1, "a"]]}, modules={},
                          host=[], window=[0, 10]), window=[0, 10],
               cycles=20)
    assert run.load_reader(metric)(ctx) is None
