"""Telemetry subsystem: the always-on span record, bitwise invisibility,
stream parity, histograms.

The contract under test (docs/CONTRACTS.md + docs/OBSERVABILITY.md):
telemetry is a **pure read**. Arming a :class:`repro.core.telemetry.
Telemetry` must leave error curves and all protocol totals bitwise
identical on BOTH engines, across failure scenarios and wire codecs; and
because the streams are reads of the same protocol, the reference engine
and the sharded engine must emit bitwise-equal metric streams at a
matched seed — the metric stream is itself a cross-engine parity surface.
The message-economy balance invariant (PR 1's run-total identity) must
hold per cycle from the streams alone. Every run records its span tree
(``SimResult.trace``): the spans, their parents and counters, and the
compiles, and under a profiler session the same spans reach the trace."""
from __future__ import annotations

import dataclasses
import json

import numpy as np
import pytest

from repro.configs.gossip_linear import (GossipLinearConfig,
                                         with_failure_scenario)
from repro.core import telemetry
from repro.core.simulation import message_wire_bytes, run_simulation
from repro.core.telemetry import (METRIC_STREAMS, SPAN_NAMES, TRACKS,
                                  LatencyHistogram, Telemetry)


def toy(n=256, d=8, seed=0):
    from repro.data.synthetic import make_linear_dataset
    rng = np.random.default_rng(seed)
    X, y = make_linear_dataset(rng, n + 64, d, noise=0.05, separation=3.0)
    return X[:n], y[:n], X[n:], y[n:]


def small_cfg(n_nodes=256, scenario="clean", **kw):
    base = dict(name="telemetry-toy", dim=8, n_nodes=n_nodes, n_test=64,
                class_ratio=(1, 1), lam=1e-3, variant="mu", cache_size=4)
    base.update(kw)
    return with_failure_scenario(GossipLinearConfig(**base), scenario)


def totals(res):
    return (res.err_fresh, res.err_voted, res.sent_total,
            res.delivered_total, res.lost_total, res.overflow_total,
            res.wire_bytes_total)


KW = dict(cycles=25, eval_every=10, seed=0, k_rounds=2)


# ---------------------------------------------------------------- invisibility


@pytest.mark.parametrize("engine", ["reference", "sharded"])
@pytest.mark.parametrize("scenario", ["clean", "extreme"])
@pytest.mark.parametrize("wire", [None, "int4"])
def test_armed_run_is_bitwise_invisible(engine, scenario, wire):
    """telemetry=None vs an armed Telemetry: identical curves + totals."""
    X, y, Xt, yt = toy()
    cfg = small_cfg(scenario=scenario, wire_dtype=wire)
    plain = run_simulation(cfg, X, y, Xt, yt, engine=engine, **KW)
    armed = run_simulation(cfg, X, y, Xt, yt, engine=engine,
                           telemetry=Telemetry(), **KW)
    assert totals(plain) == totals(armed)
    assert plain.ef_residual_norm == armed.ef_residual_norm


# --------------------------------------------------------------- stream parity


@pytest.mark.parametrize("scenario", ["clean", "extreme"])
def test_reference_and_sharded_emit_equal_streams(scenario):
    """Every registered parity stream: reference == sharded, bitwise."""
    X, y, Xt, yt = toy()
    cfg = small_cfg(scenario=scenario)
    tels = {}
    for engine in ("reference", "sharded"):
        tels[engine] = Telemetry(label=engine)
        run_simulation(cfg, X, y, Xt, yt, engine=engine,
                       telemetry=tels[engine], **KW)
    for name, spec in METRIC_STREAMS.items():
        a = tels["reference"].stream_array(name)
        b = tels["sharded"].stream_array(name)
        assert spec.parity, name
        assert np.array_equal(a, b), (name, a, b)
        # eval points: every eval_every cycles plus the final cycle
        n_evals = KW["cycles"] // KW["eval_every"] + (
            1 if KW["cycles"] % KW["eval_every"] else 0)
        expect = KW["cycles"] if spec.cadence == "cycle" else n_evals
        assert a.size == expect, (name, a.size)


def test_stream_parity_under_faults_and_defense():
    """fault_stats streams (corrupted/gated/clipped) agree cross-engine
    under a 10% sign_flip adversary with the norm_clip defense, and sum
    to the run totals both engines report."""
    X, y, Xt, yt = toy()
    cfg = small_cfg(scenario="extreme", fault_model="sign_flip",
                    byzantine_frac=0.1, defense="norm_clip")
    tels, results = {}, {}
    for engine in ("reference", "sharded"):
        tels[engine] = Telemetry()
        results[engine] = run_simulation(cfg, X, y, Xt, yt, engine=engine,
                                         telemetry=tels[engine], **KW)
    for name in ("corrupted", "gated", "clipped"):
        a = tels["reference"].stream_array(name)
        b = tels["sharded"].stream_array(name)
        assert np.array_equal(a, b), name
        assert a.sum() == results["reference"].fault_stats[name]
    assert tels["reference"].stream_array("corrupted").sum() > 0


def test_ef_residual_stream_matches_result():
    """EF codecs: the eval-cadence residual stream's last value equals the
    result's terminal ef_residual_norm; non-EF codecs emit zeros."""
    X, y, Xt, yt = toy()
    for wire, has_ef in [("int4_ef", True), (None, False)]:
        tel = Telemetry()
        res = run_simulation(small_cfg(wire_dtype=wire), X, y, Xt, yt,
                             engine="sharded", telemetry=tel, **KW)
        ef = tel.stream_array("ef_residual_rms")
        assert ef.size == 3
        if has_ef:
            assert ef[-1] == res.ef_residual_norm > 0.0
        else:
            assert not ef.any()


# ------------------------------------------------------------ balance invariant


@pytest.mark.parametrize("engine", ["reference", "sharded"])
def test_message_economy_balance_from_streams(engine):
    """Per cycle: cumsum(sent - delivered - lost - overflow) == in_flight,
    in_flight stays non-negative and ends at the undelivered remainder;
    wire_bytes == sent x per-message bytes."""
    X, y, Xt, yt = toy()
    cfg = small_cfg(scenario="extreme")
    tel = Telemetry()
    res = run_simulation(cfg, X, y, Xt, yt, engine=engine, telemetry=tel,
                         **KW)
    sent = tel.stream_array("sent")
    flow = np.cumsum(sent - tel.stream_array("delivered")
                     - tel.stream_array("lost")
                     - tel.stream_array("overflow"))
    in_flight = tel.stream_array("in_flight")
    assert np.array_equal(flow, in_flight)
    assert (in_flight >= 0).all()
    assert sent.sum() == res.sent_total
    assert in_flight[-1] == (res.sent_total - res.delivered_total
                             - res.lost_total - res.overflow_total)
    bytes_per_msg = message_wire_bytes(cfg.dim, cfg.wire_dtype)
    assert np.array_equal(tel.stream_array("wire_bytes"),
                          sent * bytes_per_msg)


def test_emit_rejects_unregistered_stream():
    tel = Telemetry()
    with pytest.raises(KeyError):
        tel.emit("not_a_stream", 1)


# ------------------------------------------------------------------- histogram


def test_histogram_percentiles_exact_on_constant_samples():
    h = LatencyHistogram()
    for _ in range(100):
        h.record(0.004)
    assert h.count == 100
    assert h.p50 == h.p99 == h.p999 == 0.004
    assert h.mean == pytest.approx(0.004)


def test_histogram_percentiles_ordered_and_bounded():
    rng = np.random.default_rng(3)
    vals = rng.lognormal(-6.0, 1.5, 5000)
    h = LatencyHistogram()
    h.record_many(vals)
    assert h.min_value == vals.min() and h.max_value == vals.max()
    assert (h.min_value <= h.p50 <= h.p90 <= h.p99 <= h.p999
            <= h.max_value)
    # fixed log buckets: the p50 estimate lands within one bucket (~33%
    # relative width at 8 buckets/decade) of the exact percentile
    exact = np.percentile(vals, 50)
    assert abs(h.p50 - exact) / exact < 0.4


def test_histogram_merge_is_exact_bucket_addition():
    rng = np.random.default_rng(4)
    a, b = LatencyHistogram(), LatencyHistogram()
    va, vb = rng.uniform(1e-5, 1e-2, 200), rng.uniform(1e-4, 1e-1, 300)
    a.record_many(va)
    b.record_many(vb)
    both = LatencyHistogram()
    both.record_many(np.concatenate([va, vb]))
    a.merge(b)
    assert np.array_equal(a.counts, both.counts)
    assert a.count == both.count == 500
    assert a.p99 == both.p99

    empty = LatencyHistogram()
    assert empty.p50 == 0.0 and empty.mean == 0.0


def test_best_of_returns_min_and_result():
    from benchmarks.common import best_of
    calls = []
    best, secs, result = best_of(lambda: calls.append(0) or len(calls),
                                 repeats=3)
    assert result == 3 and len(secs) == 3 and best == min(secs)


# ------------------------------------------------------------------ spans/trace


def test_maybe_span_unarmed_is_noop():
    """With no Telemetry and no run, a span is still recorded — among the
    loose spans, with its counters and nesting — and a nested span's
    parent is the one open when it started."""
    with telemetry.span("submit", rows=3) as outer:
        with telemetry.span("serve_batch", queries=3) as inner:
            inner.counters["padded"] = 1
    loose = telemetry.loose_spans()
    assert loose[-2:] == [outer, inner]
    assert outer.run is None and outer.parent is None
    assert inner.parent == outer.id
    assert inner.counters == {"queries": 3, "padded": 1}
    assert outer.t0_ns <= inner.t0_ns <= inner.t1_ns <= outer.t1_ns
    assert outer.track == "serving"


def test_span_track_validation():
    """A span's name fixes its track; a name outside ``SPAN_NAMES`` (the
    names the benchmark's readers find spans by) is refused."""
    with pytest.raises(ValueError):
        telemetry.span("not_a_span")
    assert all(track in TRACKS for track, _ in SPAN_NAMES.values())


def test_chrome_trace_schema(tmp_path):
    """Exported traces are valid Chrome trace-event JSON: every span is a
    complete event on a named track thread, the streams ride as counter
    events and in otherData, and tools/trace_report.py summarizes it."""
    X, y, Xt, yt = toy()
    tel = Telemetry(label="schema-test")
    run_simulation(small_cfg(scenario="extreme"), X, y, Xt, yt,
                   engine="sharded", telemetry=tel, **KW)
    fp = tel.export_chrome_trace(tmp_path / "trace.json")
    payload = json.loads(fp.read_text())

    events = payload["traceEvents"]
    assert payload["displayTimeUnit"] == "ms"
    thread_names = {e["args"]["name"] for e in events
                    if e["ph"] == "M" and e["name"] == "thread_name"}
    assert thread_names == set(TRACKS)
    spans = [e for e in events if e["ph"] == "X"]
    assert spans and len(spans) == len(tel.spans)
    ids = {e["args"]["id"] for e in spans}
    for e in spans:
        assert e["name"] in SPAN_NAMES
        assert e["dur"] >= 0 and e["cat"] == SPAN_NAMES[e["name"]][0]
        assert e["args"]["parent"] is None or e["args"]["parent"] in ids
    # compile spans carry the compiled function's name
    for e in spans:
        if e["name"] == "compile":
            assert e["args"]["fun_name"].startswith("jit(")
    assert payload["otherData"]["compile_total"] == sum(
        e["name"] == "compile" for e in spans)
    counters = {e["name"] for e in events if e["ph"] == "C"}
    cycle_streams = {n for n, s in METRIC_STREAMS.items()
                     if s.cadence == "cycle"}
    assert counters == cycle_streams

    other = payload["otherData"]
    assert set(other["streams"]) == set(METRIC_STREAMS)
    assert other["annotations"]["runs"][0]["engine"] == "sharded"

    import subprocess
    import sys
    from pathlib import Path
    repo = Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, str(repo / "tools" / "trace_report.py"), str(fp)],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    assert "balance invariant OK" in proc.stdout


def test_span_names_documented():
    """Every span name the engines emit is in the SPAN_NAMES convention
    table (the docs/OBSERVABILITY.md naming contract)."""
    X, y, Xt, yt = toy()
    from repro.launch.gossip_serve import GossipServer
    tel = Telemetry()
    srv = GossipServer(batch_size=16, telemetry=tel)

    def hook(cycle, snap):
        srv.serve_hook(cycle, snap)
        srv.submit(Xt[:16])

    for engine in ("reference", "sharded"):
        run_simulation(small_cfg(), X, y, Xt, yt, engine=engine,
                       serve_hook=hook, telemetry=tel, **KW)
    srv.flush()
    emitted = {s.name for s in tel.spans}
    assert emitted <= set(SPAN_NAMES)
    assert {"run", "setup", "cycle", "eval", "key_schedule", "stage_draws",
            "route_chunk", "pack_tables", "upload_tables", "dispatch",
            "collect_results", "snapshot", "snapshot_adopt", "submit",
            "serve_batch", "serve_device"} <= emitted
    # the serving histogram is shared into the telemetry object
    assert tel.histograms["serve_batch_latency"].count == len(srv.batches)


def test_serve_stats_histogram_backed():
    """GossipServer.stats() derives its percentiles from the shared
    LatencyHistogram and carries the sparse bucket dump."""
    X, y, Xt, yt = toy()
    from repro.launch.gossip_serve import GossipServer
    srv = GossipServer(batch_size=16)

    def hook(cycle, snap):
        srv.serve_hook(cycle, snap)
        srv.submit(Xt[:16])

    run_simulation(small_cfg(), X, y, Xt, yt, engine="sharded",
                   serve_hook=hook, **KW)
    srv.flush()
    s = srv.stats()
    assert s.batches == srv.hist.count > 0
    assert s.p50_latency_s == srv.hist.p50
    assert s.p90_latency_s == srv.hist.p90
    assert s.p999_latency_s == srv.hist.p999
    assert s.latency_hist["count"] == s.batches
    assert sum(s.latency_hist["bucket_counts"]) == s.batches


def test_multi_run_arming_concatenates_streams():
    """One Telemetry across two sequential runs (the robustness-sweep
    --trace mode): streams concatenate in run order."""
    X, y, Xt, yt = toy()
    tel = Telemetry()
    for scenario in ("clean", "extreme"):
        run_simulation(small_cfg(scenario=scenario), X, y, Xt, yt,
                       engine="sharded", telemetry=tel, **KW)
    assert tel.stream_array("sent").size == 2 * KW["cycles"]
    assert len(tel.annotations["runs"]) == 2


# ------------------------------------------------------- the span record


def _served_run(engine="sharded", telemetry_obj=None, cycles=25, **kw):
    """A run with a GossipServer on its hook (two full batches and a tail
    at every eval point); returns the result and the final cache rows."""
    from repro.launch.gossip_serve import GossipServer
    X, y, Xt, yt = toy()
    srv = GossipServer(batch_size=16, compare_fresh=False)
    final = {}

    def hook(cycle, snap):
        srv.serve_hook(cycle, snap)
        srv.submit(Xt[:40])
        srv.flush()
        final.update(w=np.asarray(snap.w), count=np.asarray(snap.count))

    res = run_simulation(small_cfg(scenario="extreme"), X, y, Xt, yt,
                         engine=engine, serve_hook=hook,
                         telemetry=telemetry_obj,
                         **dict(KW, cycles=cycles, **kw))
    return res, final


def test_unarmed_run_records_the_span_tree():
    """An unarmed sharded run records the engine's and the server's spans
    under its root, each under the right parent, with exact counters: the
    tables' bytes, the draws' bytes, the data's bytes and the router's
    message counts."""
    X, y, Xt, yt = toy()
    res, _ = _served_run(compact_rounds=False)
    rec = res.trace
    root = rec.spans[0]
    assert root.name == "run" and root.parent is None
    assert root.counters == {"n": 256, "cycles": KW["cycles"]}
    by_id = {s.id: s for s in rec.spans}
    parent = lambda s: by_id[s.parent].name
    for s in rec.spans:
        assert s.run == rec.id and s.t1_ns is not None
        assert root.t0_ns <= s.t0_ns <= s.t1_ns <= root.t1_ns
    want = {"setup": {"run"}, "key_schedule": {"run"},
            "stage_draws": {"run"}, "route_chunk": {"run"},
            "pack_tables": {"route_chunk"}, "upload_tables": {"run"},
            "dispatch": {"run"}, "snapshot": {"run"},
            "snapshot_adopt": {"snapshot"}, "submit": {"snapshot"},
            "flush": {"snapshot"}, "serve_batch": {"submit", "flush"},
            "serve_device": {"serve_batch"}, "collect_results": {"run"}}
    for name, parents in want.items():
        assert rec.named(name), name
        assert {parent(s) for s in rec.named(name)} == parents, name
    chunks = [10, 10, 5]                   # eval every 10 over 25 cycles
    for name in ("route_chunk", "upload_tables", "dispatch", "snapshot"):
        assert len(rec.named(name)) == len(chunks), name
    assert len(rec.named("serve_batch")) == 3 * len(chunks)
    # dense tables: (T, K, N) int32 a chunk
    k = KW["k_rounds"]
    assert rec.total("upload_tables", "h2d_bytes") == sum(
        t * k * 256 * 4 for t in chunks)
    assert rec.total("stage_draws", "d2h_bytes") == 2 * 4 * 256 * 25
    assert rec.total("setup", "h2d_bytes") == (
        4 * (X.size + y.size + Xt.size + yt.size) + 4 * 100)
    assert rec.total("route_chunk", "sends") == res.sent_total
    assert rec.total("route_chunk", "deliveries") == res.delivered_total
    assert {s.counters["mode"] for s in rec.named("route_chunk")} == {
        "dense"}
    assert [s.counters["queries"] for s in rec.named("serve_batch")] == [
        16, 16, 8] * len(chunks)
    assert telemetry.runs()[-1] is rec


@pytest.mark.parametrize("compact_mode", ["dense", "compact_all"])
def test_next_chunk_is_routed_before_the_snapshot(compact_mode):
    """The sharded engine routes chunk i+1 right after it dispatches chunk
    i, before it builds chunk i's snapshot and calls the hook; each routing
    with a chunk dispatched ahead of it says whether that chunk was still
    on the device when routing ended (``chunk_in_flight``), the first
    routing says nothing."""
    res, _ = _served_run(compact_mode=compact_mode)
    rec = res.trace
    route = sorted(rec.named("route_chunk"), key=lambda s: s.counters["chunk"])
    dispatch = sorted(rec.named("dispatch"), key=lambda s: s.counters["chunk"])
    snap = sorted(rec.named("snapshot"), key=lambda s: s.counters["cycle"])
    assert len(route) == len(dispatch) == len(snap) == 3
    assert route[0].t1_ns <= dispatch[0].t0_ns
    for i in range(len(snap) - 1):
        assert dispatch[i].t1_ns <= route[i + 1].t0_ns
        assert route[i + 1].t1_ns <= snap[i].t0_ns
        assert snap[i].t1_ns <= dispatch[i + 1].t0_ns
    assert "chunk_in_flight" not in route[0].counters
    for s in route[1:]:
        assert s.counters["chunk_in_flight"] in (0, 1)
    assert {s.counters["mode"] for s in route} == {compact_mode}


def test_reference_run_records_its_spans():
    res, _ = _served_run(engine="reference")
    rec = res.trace
    assert rec.engine == "reference"
    by_id = {s.id: s for s in rec.spans}
    assert len(rec.named("cycle")) == KW["cycles"]
    assert len(rec.named("eval")) == len(rec.named("snapshot")) == 3
    for name in ("setup", "cycle", "eval", "snapshot"):
        assert {by_id[s.parent].name for s in rec.named(name)} == {"run"}


def test_span_record_is_bitwise_invisible():
    """The spans change nothing: an unarmed run's curves, totals and final
    cache equal the armed run's bit for bit, and an identical unarmed
    rerun compiles no new engine signature."""
    from repro.core.sharded_engine import retrace_counts
    plain, plain_cache = _served_run()
    counts = retrace_counts()
    again, again_cache = _served_run()
    assert retrace_counts() == counts
    armed, armed_cache = _served_run(telemetry_obj=Telemetry())
    for other, cache in ((again, again_cache), (armed, armed_cache)):
        assert totals(plain) == totals(other)
        for key in ("w", "count"):
            assert np.array_equal(plain_cache[key], cache[key])


def test_key_schedule_compile_is_its_child():
    """The compile of the key schedule (an eager scan, traced anew each
    run) is recorded as a ``compile`` span under ``key_schedule``, with
    the compiled function's name."""
    res, _ = _served_run(cycles=13)
    rec = res.trace
    ks = rec.named("key_schedule")
    assert len(ks) == 1
    under = [s for s in rec.named("compile") if s.parent == ks[0].id]
    assert under, [s.counters for s in rec.named("compile")]
    for s in under:
        assert s.counters["fun_name"].startswith("jit(")
        assert 0 < s.counters["seconds"] and s.t0_ns <= s.t1_ns
        assert ks[0].t0_ns <= s.t1_ns <= ks[0].t1_ns


def test_profiled_run_reaches_the_trace(tmp_path):
    """Inside a profiler session the run is marked ``profiled``, stays the
    last profiled run after later runs, and its spans are in the
    ``.xplane.pb`` as ``gossip/<name>`` annotations."""
    import glob
    import jax
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        res, _ = _served_run(cycles=10)
    later, _ = _served_run(cycles=10)
    assert res.trace.profiled and not later.trace.profiled
    assert telemetry.last_profiled() is res.trace
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = set()
    for plane in ProfileData.from_file(path[0]).planes:
        for line in plane.lines:
            names.update(e.name for e in line.events
                         if e.name.startswith(telemetry.ANNOTATION_PREFIX))
    assert {"gossip/run", "gossip/setup", "gossip/route_chunk",
            "gossip/dispatch", "gossip/snapshot_adopt",
            "gossip/serve_device"} <= names


def test_run_ring_is_bounded_and_keeps_the_profiled_run(tmp_path):
    import jax

    with jax.profiler.trace(str(tmp_path)):
        with telemetry.run("reference", n=1, cycles=1) as profiled:
            pass
    for _ in range(telemetry.RUNS_KEPT + 3):
        with telemetry.run("reference", n=1, cycles=1):
            pass
    assert len(telemetry.runs()) == telemetry.RUNS_KEPT
    assert profiled not in telemetry.runs()
    assert telemetry.last_profiled() is profiled
