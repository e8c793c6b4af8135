"""The per-layer metrics that read the program's own spans: on a traced CPU
run at 2048 nodes, on a made-up trace with a planted clock offset, and
without the program's span record (a program older than it). Also guards
the names the trace readers find the program by."""
import gzip
import json
import re

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import program_spans, run
from benchmarks.chip.test_chipbench_check import small_cell
from repro.core import telemetry

NEW = {"draw_ms_per_cycle", "table_upload_ms_per_cycle",
       "table_upload_mb_per_cycle", "compiles_per_simulation",
       "snapshot_wait_ms_per_cycle", "serve_host_ms_per_batch",
       "device_idle_unattributed_share"}
DEVICE = {"device_idle_unattributed_share"}
CASES = [(cell, m["name"])
         for cell in ("spambase-1m.extreme.serve", "spambase-1m.sparse")
         for m in run.load_cell(cell)["per_layer"] if m["name"] in NEW]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Per cell: the result line of a traced run on the CPU, its reduced
    trace, and the program's record of the traced simulation."""
    out = {}
    for cell in ("spambase-1m.extreme.serve", "spambase-1m.sparse"):
        path = tmp_path_factory.mktemp("trace") / "trace.json.gz"
        line = run.run_cell(small_cell(cell), 2**33 + 11, 1.0, True,
                            devs=jax.devices()[:1], interpret=True,
                            trace_out=str(path))
        with gzip.open(path, "rt") as f:
            trace = json.load(f)
        out[cell] = (line, trace, telemetry.last_profiled())
    return out


def test_every_cell_lists_the_new_metrics():
    assert {m for _, m in CASES} == NEW
    assert {c for c, m in CASES if m == "serve_host_ms_per_batch"} == {
        "spambase-1m.extreme.serve"}


@pytest.mark.parametrize("cell,metric", CASES)
def test_reader_reads_a_number_on_a_cpu_run(traced, cell, metric):
    line, trace, rec = traced[cell]
    assert rec.profiled and rec.named("setup")
    if metric not in DEVICE:
        assert metric in line["metrics"], sorted(line["metrics"])
        value = line["metrics"][metric]["value"]
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
    if metric == "compiles_per_simulation":
        assert value == len(rec.named("compile")) >= 1
    if metric == "table_upload_mb_per_cycle":
        # the 2048-node cell's dense (5, 4, 2048) int32 table a chunk
        assert value == pytest.approx(5 * 4 * 2048 * 4 / 1e6 / 5)


def test_spans_land_on_the_harness_route_annotations(traced):
    """The anchor puts each routing span next to the harness's own
    ``route`` annotations of the same calls. (On the chip the criterion is
    0.1 ms; a loaded CPU runner can stall any thread for longer, so the
    test asks for the millisecond a broken mapping would miss by far.)"""
    for cell, (line, trace, rec) in traced.items():
        for name in ("pack_tables", "route_chunk"):
            err = program_spans.anchor_error_ms(rec, trace["host"], name)
            assert err is not None and err < 1.0, (cell, name, err)


def _record(spans, cycles=10):
    """A made-up run record: ``spans`` are ``(name, t0_ns, t1_ns)``; the
    root covers them all."""
    rec = telemetry.RunRecord(1, "sharded", True)
    root = telemetry.Span("run", {"n": 8, "cycles": cycles})
    root.id, root.t0_ns, root.t1_ns = 1, 0, 10**9
    rec.spans.append(root)
    for i, (name, t0, t1) in enumerate(spans, start=2):
        s = telemetry.Span(name, {})
        s.id, s.parent, s.run, s.t0_ns, s.t1_ns = i, 1, 1, t0, t1
        rec.spans.append(s)
    return rec


def test_unattributed_share_on_a_synthetic_trace(monkeypatch):
    """Program times start near 5*10^12 ns, the trace's near 0: the offset
    comes from the one ``simulation_setup`` annotation. Device idle in
    [0, 100) lies under ``setup``, [300, 400) under ``route_chunk``,
    [600, 1000) under nothing but the root: 400 of 600 idle ns."""
    base = 5 * 10**12
    rec = _record([("setup", base + 10, base + 150),
                   ("route_chunk", base + 250, base + 450),
                   ("pack_tables", base + 420, base + 440)])
    host = [[0, 1000, "bench_window"], [20, 140, "simulation_setup"],
            [270, 400, "route"], [434, 446, "route"]]
    ops = [[100, 300, "a"], [400, 600, "b"]]
    trace = dict(ops={"TPU:0": ops, "TPU:1": ops}, modules={}, host=host,
                 window=[0, 1000])
    idle = program_spans.idle_by_span(trace, rec)
    assert idle == pytest.approx({"setup": 100e-9, "route_chunk": 100e-9,
                                  "unattributed": 400e-9})
    monkeypatch.setattr(telemetry, "last_profiled", lambda: rec)
    reader = run.load_reader("device_idle_unattributed_share")
    assert reader(dict(trace=trace, window=trace["window"])) == \
        pytest.approx(100.0 * 400 / 600)
    # route_chunk maps to [260, 460): the nearest annotation starts 10 ns
    # after it and ends 14 ns before it; pack_tables maps to [430, 450)
    # around [434, 446)
    assert program_spans.anchor_error_ms(rec, host, "route_chunk") == \
        pytest.approx(14e-6)
    assert program_spans.anchor_error_ms(rec, host) == pytest.approx(4e-6)
    assert program_spans.anchor_error_ms(rec, host, "dispatch") is None


def test_span_readers_on_a_synthetic_record(monkeypatch):
    rec = _record([("stage_draws", 0, 40 * 10**6),
                   ("upload_tables", 50 * 10**6, 52 * 10**6),
                   ("upload_tables", 60 * 10**6, 63 * 10**6),
                   ("snapshot_adopt", 70 * 10**6, 71 * 10**6),
                   ("submit", 80 * 10**6, 90 * 10**6),
                   ("serve_batch", 81 * 10**6, 89 * 10**6),
                   ("serve_device", 82 * 10**6, 86 * 10**6),
                   ("flush", 90 * 10**6, 91 * 10**6),
                   ("compile", 1, 2)], cycles=10)
    for s in rec.named("upload_tables"):
        s.counters["h2d_bytes"] = 4 * 10**6
    rec.named("compile")[0].counters.update(fun_name="jit(scan)",
                                            seconds=1e-9)
    monkeypatch.setattr(telemetry, "last_profiled", lambda: rec)
    read = lambda m: run.load_reader(m)({})
    assert read("draw_ms_per_cycle") == pytest.approx(4.0)
    assert read("table_upload_ms_per_cycle") == pytest.approx(0.5)
    assert read("table_upload_mb_per_cycle") == pytest.approx(0.8)
    assert read("snapshot_wait_ms_per_cycle") == pytest.approx(0.1)
    assert read("serve_host_ms_per_batch") == pytest.approx(11 - 4)
    assert read("compiles_per_simulation") == 1


@pytest.mark.parametrize("metric", sorted(NEW))
def test_reader_reads_nothing_without_the_span_record(monkeypatch, metric):
    """Against a program that keeps no span record the readers give None
    and raise nothing."""
    monkeypatch.delattr(telemetry, "last_profiled")
    ctx = dict(trace=dict(ops={"TPU:0": [[0, 1, "a"]]}, modules={},
                          host=[], window=[0, 10]), window=[0, 10],
               cycles=20)
    assert run.load_reader(metric)(ctx) is None


def test_program_names_the_readers_find():
    """The trace readers find the chunk program, the receive kernel's call
    and the serving program by name: ``jit_chunk_fn`` with a
    ``fused_receive_apply`` function, and ``jit_serve_voted_kernel``."""
    from repro.core import serving, sharded_engine as se

    n, t, d, c, k, f32, i32 = 512, 2, 57, 10, 4, jnp.float32, jnp.int32
    S = jax.ShapeDtypeStruct
    fn = se._build_chunk_fn("mu", "pegasos", 1e-3, 0.0, 10, True, True,
                            None, None, "dense", None, False, None, "none",
                            False)
    carry = (S((n, d), f32), S((n,), i32), S((n, d), f32), S((n,), i32),
             S((n, c, d), f32), S((n, c), i32), S((n,), i32), S((n,), i32),
             S((10, n, d), f32), S((10, n), i32), S((0, 0), jnp.float16),
             S((0, 0), jnp.float16), S((0, 0), f32), S((), i32))
    text = fn.lower(carry, (S((t, k, n), i32),), S((t, 2), jnp.uint32),
                    S((n, d), f32), S((n,), f32), S((64, d), f32),
                    S((64,), f32), S((8,), i32), None).as_text()
    assert re.search(r"module @jit_chunk_fn\b", text)
    assert re.search(r"func\.func private @fused_receive_apply\(", text)
    serve = serving.serve_voted_kernel.lower(
        S((n, c, d), f32), S((n,), i32), S((64, d), f32), S((64,), i32),
        interpret=True).as_text()
    assert re.search(r"module @jit_serve_voted_kernel\b", serve)
