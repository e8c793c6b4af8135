"""The readers of the receive pass added with the Reuters cell:
``dtiled_receive_roofline`` (device trace) and
``receive_moved_mb_per_cycle`` (the ``receive_bytes`` counters of the
program's ``dispatch`` spans). On traced CPU runs of the two new cells at a
size a test run holds, on a made-up trace and record, and without what
they read (a program older than them)."""
import jax
import pytest

from benchmarks.chip import run, work
from benchmarks.chip.test_chipbench_check import small_cell
from benchmarks.chip.test_chipbench_program_spans import _record
from repro.core import telemetry
from repro.kernels import gossip_cycle

NEW = {"reuters-6k.extreme": {"dtiled_receive_roofline",
                              "receive_moved_mb_per_cycle"},
       "spambase-1m.clean": {"receive_moved_mb_per_cycle"}}


def tiny_cell(name: str) -> dict:
    """``small_cell``; the Reuters cell also at d=160 and 10 cycles, where
    512 nodes still take the d-tiled kernel (its interpret mode at d=9947
    would take minutes)."""
    cell = small_cell(name)
    if name == "reuters-6k.extreme":
        cell["config"].update(n_nodes=512, dim=160, n_test=100)
        cell["traffic"].update(cycles=10, eval_every=5)
    return cell


@pytest.mark.parametrize("cell", sorted(NEW))
def test_the_new_cells_list_their_metrics(cell):
    names = {m["name"] for m in run.load_cell(cell)["per_layer"]}
    assert names == NEW[cell]


@pytest.mark.parametrize("cell", sorted(NEW))
def test_receive_moved_reads_the_kernels_bytes_on_a_cpu_run(cell):
    c = tiny_cell(cell)
    line = run.run_cell(c, 2**33 + 21, 1.0, True, devs=jax.devices()[:1],
                        interpret=True)
    assert line["correct"], line["checks"]
    cfg = c["config"]
    shape = (cfg["n_nodes"], cfg["dim"], cfg["cache_size"], cfg["k_rounds"])
    tile = gossip_cycle.receive_d_tile(*shape)
    assert (tile < cfg["dim"]) == (cell == "reuters-6k.extreme")
    per_cycle = gossip_cycle.receive_call_bytes(*shape, None, tile)
    value = line["metrics"]["receive_moved_mb_per_cycle"]["value"]
    assert value == pytest.approx(per_cycle / 1e6)
    # the CPU has no device plane: no kernel time to read
    assert "dtiled_receive_roofline" not in line["metrics"]


def test_receive_moved_on_a_synthetic_record(monkeypatch):
    """The dispatch spans' bytes over the run's cycles; nothing from a
    program whose dispatch spans carry no ``receive_bytes``."""
    rec = _record([("dispatch", 0, 1), ("dispatch", 2, 3)], cycles=10)
    monkeypatch.setattr(telemetry, "last_profiled", lambda: rec)
    read = lambda: run.load_reader("receive_moved_mb_per_cycle")({})
    assert read() is None
    for s, b in zip(rec.named("dispatch"), (3 * 10**8, 2 * 10**8)):
        s.counters["receive_bytes"] = b
    assert read() == pytest.approx(50.0)
    monkeypatch.delattr(telemetry, "last_profiled")
    assert read() is None


def _ctx(ops):
    peaks = {"peak_hbm_bytes_per_s": 819e9}
    trace = dict(ops=ops, modules={}, host=[], window=[0, 10**6])
    return dict(trace=trace, window=trace["window"], peaks=peaks,
                deliveries=1000, d=9947)


def test_dtiled_roofline_on_a_synthetic_trace():
    """The deliveries' bytes over the tiled kernel's device time, averaged
    over the devices; the whole-d kernel's ops and others do not count."""
    tiled = "%fused_receive_apply_dtiled.7 = (f32[9947,6144]) custom-call()"
    whole = "%fused_receive_apply.3 = (f32[57,512]) custom-call()"
    ops = {"TPU:0": [[0, 400, tiled], [500, 600, whole], [600, 700, "x"]],
           "TPU:1": [[0, 200, tiled], [300, 500, tiled]]}
    need = work.delivery_bytes(1000, 9947) / 2
    read = run.load_reader("dtiled_receive_roofline")
    assert read(_ctx(ops)) == pytest.approx(
        100.0 * need / 819e9 / (400 / 1e9))
    assert run.load_reader("receive_roofline")(
        _ctx({"TPU:0": ops["TPU:1"]})) is None
    assert read(_ctx({"TPU:0": [[0, 400, whole]]})) is None
    assert read(_ctx({})) is None
