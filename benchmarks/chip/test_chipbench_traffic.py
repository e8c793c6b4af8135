"""The query generator, the window's accounting and the refusal to run
without a TPU."""
import numpy as np
import pytest

from benchmarks.chip import run, traffic

MIX = {"rate_per_s": 2000, "batch": 256, "assign": "uniform"}


def test_arrivals_come_from_the_seed():
    a = traffic.QueryStream.from_mix(MIX, 2**33 + 5, 3.0, 100)
    b = traffic.QueryStream.from_mix(MIX, 2**33 + 5, 3.0, 100)
    c = traffic.QueryStream.from_mix(MIX, 2**33 + 6, 3.0, 100)
    assert np.array_equal(a.arrival_s, b.arrival_s)
    assert np.array_equal(a.rows, b.rows)
    assert not np.array_equal(a.arrival_s[:50], c.arrival_s[:50])
    assert np.all(np.diff(a.arrival_s) > 0)
    assert a.arrival_s[0] >= 0 and a.arrival_s[-1] < 3.0
    # Poisson count: 6000 expected, sd about 77
    assert abs(a.arrival_s.size - 6000) < 6 * 77
    assert a.rows.min() >= 0 and a.rows.max() < 100
    assert a.due(0.0) == 0 and a.due(3.0) == a.arrival_s.size


def test_derived_seeds_fit_32_bits_and_differ_by_stream():
    s = traffic.derived_seeds(2**40 + 123, 8, 1)
    assert s.shape == (8,) and s.max() < 2**31 and s.min() >= 0
    assert len(set(s)) == 8
    assert not np.array_equal(s, traffic.derived_seeds(2**40 + 123, 8, 2))
    assert np.array_equal(s, traffic.derived_seeds(2**40 + 123, 8, 1))


def test_dataset_comes_from_the_seed():
    a = traffic.make_dataset(7, 50, 10, 5, noise=0.1, separation=2.5,
                             class_ratio=(1, 1))
    b = traffic.make_dataset(7, 50, 10, 5, noise=0.1, separation=2.5,
                             class_ratio=(1, 1))
    assert all(np.array_equal(x, z) for x, z in zip(a, b))
    assert a[0].shape == (50, 5) and a[2].shape == (10, 5)
    assert a[0].dtype == np.float32 and set(np.unique(a[1])) <= {-1.0, 1.0}


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _window(arrivals, seconds_per_snapshot=1.0, tail_s=0.0, n=64):
    """A window over a stand-in engine: every simulation has snapshots at
    cycles 5, 10, 15, 20, one ``seconds_per_snapshot`` apart, and ends
    ``tail_s`` after the last."""
    import jax.numpy as jnp

    from repro.core.serving import QuerySnapshot

    clock = _Clock()
    rng = np.random.default_rng(0)
    snap = QuerySnapshot(
        w=jnp.asarray(rng.normal(size=(n, 2, 3)).astype(np.float32)),
        t=jnp.ones((n, 2), jnp.int32), count=jnp.full((n,), 2, jnp.int32),
        fresh_w=jnp.zeros((n, 3)), fresh_t=jnp.zeros((n,), jnp.int32),
        clock=jnp.int32(0))
    sims = []

    def simulate(seed, hook):
        for cycle in (5, 10, 15, 20):
            clock.t += seconds_per_snapshot
            hook(cycle, snap)
        clock.t += tail_s
        sims.append(seed)
        return {"seed": seed}

    cell = {"traffic": {"cycles": 20}, "config": {"n_nodes": n}}
    x_test = rng.normal(size=(10, 3)).astype(np.float32)
    q = traffic.QueryStream(np.asarray(arrivals, float),
                            np.arange(len(arrivals)) % 10, 2, "uniform")
    win = run.Window(cell, simulate, (None, None, x_test, None), q,
                     np.arange(4), interpret=True, annotated=False,
                     clock=clock)
    return win, clock, sims


def test_latency_runs_from_the_scheduled_arrival():
    win, clock, _ = _window([0.1, 0.2, 1.5, 3.9])
    win.run(2.5, [11, 12, 13])
    # batch of 2 at the 1 s snapshot, a padded batch of 1 at 2 s, and one
    # at 4 s
    lat = win.answered_s - win.queries.arrival_s
    assert lat == pytest.approx([0.9, 0.8, 0.5, 0.1])
    b = win.sims[0]["batches"]
    assert [len(x["q"]) for x in b] == [2, 1, 1]
    assert [x["cycle"] for x in b] == [5, 10, 20]
    assert [x["offset"] for x in b] == [0, 2, 3]
    # the padded tail batch is answered, with one answer per real query
    assert all(len(x["preds"]) == len(x["q"]) for x in b)
    assert set(np.concatenate([x["preds"] for x in b])) <= {-1.0, 1.0}


def test_window_runs_whole_simulations_and_counts_all_of_them():
    win, clock, sims = _window([], seconds_per_snapshot=1.0)
    window_s = win.run(4.5, [11, 12, 13])
    # the first simulation ends at 4 s, under 4.5 s, so a second one runs
    # to its end; none is cut
    assert sims == [11, 12] and len(win.sims) == 2
    assert window_s == pytest.approx(8.0)
    # node-cycles of both simulations: 2 x 20 cycles x 64 nodes
    assert win.node_cycles == 2 * 20 * 64
    assert all(s["sample"] is not None for s in win.sims)


def test_queries_left_after_the_last_snapshot_are_answered():
    # the query due at 4.2 s arrives after the last snapshot (4 s) and
    # before the simulation ends (4.5 s): it is answered on that snapshot
    win, clock, _ = _window([0.5, 4.2], tail_s=0.5)
    win.run(4.3, [11, 12])
    assert len(win.sims) == 1
    lat = win.answered_s - win.queries.arrival_s
    assert lat == pytest.approx([0.5, 0.3])
    assert [x["cycle"] for x in win.sims[0]["batches"]] == [5, 20]


def test_refuses_to_run_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "spambase-1m.sparse", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert "TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""
