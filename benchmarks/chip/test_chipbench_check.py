"""The check that decides ``correct``, at a size a test run holds, on the
CPU with the Pallas kernels in interpret mode: a sound run passes, the
control (the reference in bfloat16) fails, and a run with a fault planted
underneath the timed path fails."""
import jax
import pytest

from benchmarks.chip import check, faults, run
from benchmarks.chip import reference as ref_mod

CELLS = ("spambase-1m.extreme.serve", "spambase-1m.sparse")


def small_cell(name: str) -> dict:
    """The cell at 2048 nodes, 200 test rows and 2000 queries/s in
    batches of 256, with the cell's own limits; the CPU stands in for the
    chip's peaks."""
    cell = run.load_cell(name)
    cell["config"].update(n_nodes=2048, n_test=200)
    if cell["traffic"].get("queries"):
        cell["traffic"]["queries"].update(rate_per_s=2000, batch=256)
    kind = jax.devices()[0].device_kind
    cell["peaks"]["devices"][kind] = cell["peaks"]["devices"]["TPU v5 lite"]
    return cell


def run_small(name: str, seed: int, control: bool = False) -> dict:
    return run.run_cell(small_cell(name), seed, 1.0, False,
                        devs=jax.devices()[:1], interpret=True,
                        control=control)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct_and_control_is_not(name):
    out = run_small(name, 2**32 + 17, control=True)
    limits = small_cell(name)["limits"]
    assert out["correct"], out["checks"]
    assert not check.verdict(out["control"], limits), out["control"]
    assert set(out["metrics"]) == {m["name"] for m in
                                   run.load_cell(name)["end_to_end"]}
    assert list(out)[-1] == "checks"
    assert out["failed"] == 0


@pytest.mark.parametrize("name,fault", [
    ("spambase-1m.extreme.serve", "frozen"),
    ("spambase-1m.extreme.serve", "half"),
    ("spambase-1m.extreme.serve", "answer"),
    ("spambase-1m.sparse", "frozen"),
    ("spambase-1m.sparse", "half"),
    ("spambase-1m.extreme.serve", "eval"),
    ("spambase-1m.sparse", "eval"),
])
def test_planted_fault_is_not_correct(name, fault):
    undo = faults.plant(fault)
    try:
        out = run_small(name, 99)
    finally:
        undo()
    assert not out["correct"], out["checks"]


def test_control_fails_at_a_larger_population():
    """The bfloat16 reference against the f32 one, with no program: at 4096
    nodes of the extreme scenario the gap of the models passes the limit."""
    p = ref_mod.Protocol(n=4096, d=57, cache_size=10, k_rounds=4, lam=1e-3,
                         drop=0.5, delay_max=10, online_fraction=0.9,
                         cycles=20, eval_every=5, eval_nodes=100)
    from benchmarks.chip import traffic
    data = traffic.make_dataset(3, 4096, 200, 57, noise=0.1, separation=2.5,
                                class_ratio=(1813, 2788))
    nodes = check.sample_nodes(4096, 5)
    want = check.outcome_from_reference(p, *data, 11, nodes, [])
    low = check.outcome_from_reference(p, *data, 11, nodes, [],
                                       precision="bf16")
    same = check.outcome_from_reference(p, *data, 11, nodes, [])
    limits = {k: v for k, v in run.load_cell(CELLS[0])["limits"].items()
              if k != "answer_mismatch"}     # no queries here
    assert check.verdict(check.compare(same, want, False), limits)
    assert not check.verdict(check.compare(low, want, False), limits)


def test_eval_bounds_count_unclear_scores_both_ways():
    """Three eval nodes and one test row labelled -1: a clear positive
    score, one within EVAL_DECISIVE of zero, and the zero model (exactly 0,
    so +1 at any precision). All three are wrong in f32; the unclear one
    may come out right in a lower-precision eval."""
    import numpy as np
    p = ref_mod.Protocol(n=3, d=2, cache_size=2, k_rounds=1, lam=1e-3,
                         drop=0.0, delay_max=1, online_fraction=1.0,
                         cycles=1, eval_every=1, eval_nodes=3)
    s = ref_mod.State(p)
    s.fresh_w[:] = [[1.0, 1.0], [1.0, -0.999], [0.0, 0.0]]
    s.cache_w[:, 0] = s.fresh_w          # count 1: the vote is slot 0's
    X, y = np.array([[1.0, 1.0]], np.float32), np.array([-1.0], np.float32)
    err_f, err_v, b_f, b_v = ref_mod.errors(s, np.arange(3), X, y)
    assert (err_f, err_v) == (1.0, 1.0)
    assert b_f == b_v == (2, 3)
