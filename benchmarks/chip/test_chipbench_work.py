"""Work counts of the roofline metrics against hand counts at a small size,
the metric readers on a made-up trace, and the peaks table's refusal of an
unknown device."""
import pytest

from benchmarks.chip import run, work

PEAKS = {"peak_flops_bf16": 100.0, "peak_hbm_bytes_per_s": 1000.0}


def test_model_and_message_bytes_by_hand():
    # d=3: three f32 coefficients and an int32 counter
    assert work.model_bytes(3) == 16
    assert work.message_bytes(3) == 16
    assert work.message_bytes(3, wire_bytes_per_coef=1) == 7


def test_send_and_delivery_bytes_by_hand():
    # a send reads a model (16) and writes a message (16)
    assert work.send_bytes(2, 3) == 64
    # a delivery: message 16 + lastModel 16 + example (x and y) 16
    # + cache slot, lastModel and freshest out 3 * 16
    assert work.delivery_bytes(1, 3) == 96
    assert work.delivery_bytes(5, 3) == 480


def test_flops_by_hand():
    assert work.delivery_flops(1, 3) == 21
    # 1 eval point, 2 nodes, 5 rows, (C=2 cached + freshest) models, d=3,
    # a multiply-add each
    assert work.eval_flops(1, 2, 5, 2, 3) == 2 * 5 * 3 * 3 * 2


def test_vote_bytes_by_hand():
    # 4 queries, C=2, d=3: 6 cached coefficients, 3 query, count, answer
    assert work.vote_bytes(4, 2, 3) == 4 * (6 + 3 + 2) * 4


def test_roofline_takes_the_larger_bound():
    assert work.roofline_s(200.0, 10.0, 100.0, 1000.0) == 2.0
    assert work.roofline_s(1.0, 5000.0, 100.0, 1000.0) == 5.0


def test_unknown_device_kind_is_an_error():
    peaks = run.load_cell("spambase-1m.sparse")["peaks"]
    assert run.peaks_for(peaks, "TPU v5 lite")["peak_hbm_bytes_per_s"] \
        == 819e9
    with pytest.raises(KeyError, match="TPU v9"):
        run.peaks_for(peaks, "TPU v9")


def _ctx(**kw):
    # one device, a 1 s window; the receive kernel runs 0.5 s, the vote
    # kernel 0.1 s, the chunk program 0.8 s, the serving program 0.1 s
    ops = [[0, 500_000_000, "%fused_receive_apply.9 = (...) custom-call()"],
           [500_000_000, 600_000_000,
            "%voted_predict_batched.1 = f32[1,4096] custom-call()"],
           [700_000_000, 800_000_000, "%fusion.1 = f32[8] fusion()"]]
    ctx = dict(trace=dict(ops={"TPU:0": ops},
                          modules={"TPU:0": [[0, 800_000_000,
                                              "jit_chunk_fn(1)"],
                                             [800_000_000, 900_000_000,
                                              "jit_serve_voted_kernel(2)"]]},
                          host=[]),
               window=[0, 1_000_000_000], peaks=PEAKS, spans={"route": 0.2},
               cycles=4, n=10, d=3, c=2, n_test=5, eval_nodes=2,
               eval_points=1, sends=2, deliveries=5,
               batches=[{"q": [0, 1, 2, 3], "latency_s": 0.01},
                        {"q": [4], "latency_s": 0.03},
                        {"q": [5, 6], "latency_s": 0.02}], batch=4)
    ctx.update(kw)
    return ctx


def test_metric_readers_by_hand():
    ctx = _ctx()
    read = lambda m: run.load_reader(m)(ctx)
    assert read("route_ms_per_cycle") == pytest.approx(50.0)
    # busy 0.7 s of 1 s
    assert read("device_idle_share") == pytest.approx(30.0)
    assert read("chunk_device_ms_per_cycle") == pytest.approx(200.0)
    # 480 bytes of deliveries in 0.5 s at 1000 B/s: 96%
    assert read("receive_roofline") == pytest.approx(96.0)
    # 7 queries * 11 words * 4 B = 308 B in 0.1 s at 1000 B/s
    assert read("serve_program_roofline") == pytest.approx(308.0)
    assert read("serve_batch_ms") == pytest.approx(20.0)
    flops = 5 * 21 + 1 * 2 * 5 * 3 * 3 * 2
    nbytes = 64 + 480 + (2 * 3 * 16 + 5 * 4 * 4)
    assert read("cycle_mfu") == pytest.approx(
        100.0 * max(flops / 100.0, nbytes / 1000.0) / 1.0)


def test_readers_return_nothing_when_the_trace_has_nothing():
    ctx = _ctx(trace=dict(ops={}, modules={}, host=[]), batches=[],
               spans={})
    for m in ("route_ms_per_cycle", "device_idle_share",
              "chunk_device_ms_per_cycle", "cycle_mfu", "receive_roofline",
              "serve_program_roofline", "serve_batch_ms",
              "collective_ms_per_cycle"):
        assert run.load_reader(m)(ctx) is None, m


def test_renamed_kernel_reads_nothing():
    ctx = _ctx()
    ctx["trace"]["ops"]["TPU:0"][0][2] = "%receive_v2.1 = custom-call()"
    ctx["trace"]["modules"]["TPU:0"][1][2] = "jit_serve_v2(2)"
    assert run.load_reader("receive_roofline")(ctx) is None
    assert run.load_reader("serve_program_roofline")(ctx) is None


def test_collective_reader_by_hand():
    # two devices of a mesh, 4 cycles: the ring's permutes and the eval's
    # all-reduce take 40 ms on one and 20 ms on the other; other ops do not
    # count
    dev = lambda ms: [[0, ms * 500_000, "%collective-permute-start.3 = s32[2]"
                                        " collective-permute-start()"],
                      [ms * 500_000, ms * 1_000_000,
                       "%all-reduce.1 = s32[4] all-reduce()"],
                      [900_000_000, 950_000_000,
                       "%fusion.7 = f32[8] fusion()"]]
    ctx = _ctx(trace=dict(ops={"TPU:0": dev(40), "TPU:1": dev(20)},
                          modules={}, host=[]))
    assert run.load_reader("collective_ms_per_cycle")(ctx) == \
        pytest.approx(30.0 / 4)
    # one chip: no collective, nothing read
    assert run.load_reader("collective_ms_per_cycle")(_ctx()) is None
