"""Inputs of a run, all drawn from ``--seed``: the data, the seed of every
simulation in the window, and the open-loop query stream.

One generator for every traffic mix: a mix is a JSON file under
``traffic/`` whose keys this module reads (the failure scenario, the
simulation length and eval cadence, and an optional ``queries`` block).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


def make_dataset(seed: int, n: int, n_test: int, d: int, *, noise: float,
                 separation: float, class_ratio):
    """Linearly separable classes with label noise, one record per node:
    ``(X, y, X_test, y_test)``.

    The Table I surrogate of ``repro.data.synthetic.make_linear_dataset``
    (unit-norm direction, Gaussian cloud of per-coordinate scale 1/sqrt(d),
    the class shifted by separation/sqrt(d) along it, a ``noise`` share of
    labels flipped), copied so that the benchmark makes its own inputs."""
    rng = np.random.default_rng(seed)
    m = n + n_test
    w_true = rng.normal(size=d)
    w_true /= np.linalg.norm(w_true)
    X = rng.normal(size=(m, d)).astype(np.float32) / np.sqrt(d)
    first, second = class_ratio           # +1 with the first one's share
    y = np.where(rng.random(m) < first / (first + second), 1.0,
                 -1.0).astype(np.float32)
    X = (X + (separation / np.sqrt(d)) * y[:, None] * w_true[None, :]
         ).astype(np.float32)
    flip = rng.random(m) < noise
    y[flip] = -y[flip]
    return X[:n], y[:n], X[n:], y[n:]


def derived_seeds(seed: int, count: int, stream: int) -> np.ndarray:
    """``count`` seeds below 2**31 from the run's seed, one stream per use
    (0: the data, 1: the simulations, 2: the queries, 3: the check)."""
    ss = np.random.SeedSequence([stream, seed & 0xFFFFFFFF, seed >> 32])
    return (ss.generate_state(count, np.uint32) & 0x7FFFFFFF).astype(np.int64)


@dataclass
class QueryStream:
    """Open-loop queries: Poisson arrivals at a fixed rate over the window,
    each a test row. ``arrival_s`` is the time since the window opened at
    which a query is due; a query's latency runs from then."""
    arrival_s: np.ndarray        # (Q,) ascending
    rows: np.ndarray             # (Q,) index into the test rows
    batch: int
    assign: str

    @classmethod
    def from_mix(cls, spec: dict, seed: int, seconds: float, n_test: int):
        rng = np.random.default_rng(int(derived_seeds(seed, 1, 2)[0]))
        rate = float(spec["rate_per_s"])
        # enough gaps for the window at any plausible draw; the tail is cut
        m = int(rate * seconds + 10 * np.sqrt(rate * seconds) + 100)
        t = np.cumsum(rng.exponential(1.0 / rate, m))
        t = t[t < seconds]
        rows = rng.integers(0, n_test, t.size)
        return cls(t, rows, int(spec["batch"]), spec["assign"])

    def due(self, now_s: float) -> int:
        """How many queries are due by ``now_s``."""
        return int(np.searchsorted(self.arrival_s, now_s, side="right"))
