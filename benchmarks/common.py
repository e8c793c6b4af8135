"""Shared benchmark utilities: wall-clock timing, CSV/JSON output, dataset
cache. ``LatencyHistogram`` is re-exported from ``repro.core.telemetry``
so benches keep one import."""
from __future__ import annotations

import functools
import json
import time
from pathlib import Path
from typing import List

from repro.core.telemetry import LatencyHistogram

__all__ = ["REPO_ROOT", "OUT_DIR", "write_csv", "write_bench_json",
           "dataset", "Timer", "best_of", "LatencyHistogram"]

REPO_ROOT = Path(__file__).resolve().parent.parent
OUT_DIR = REPO_ROOT / "results" / "benchmarks"


class Timer:
    """Context-manager wall clock; ``.s`` holds elapsed seconds."""

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.s = time.perf_counter() - self.t0
        return False


def best_of(fn, repeats: int = 2):
    """Min-time estimator: call ``fn()`` ``repeats`` times.

    Returns ``(best_seconds, all_seconds, last_result)``. Shared-container
    noise is strictly additive, so the minimum is the estimator every
    bench uses."""
    secs: List[float] = []
    result = None
    for _ in range(max(repeats, 1)):
        with Timer() as t:
            result = fn()
        secs.append(t.s)
    return min(secs), secs, result


def write_csv(name: str, header: str, rows) -> Path:
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    fp = OUT_DIR / f"{name}.csv"
    with fp.open("w") as f:
        f.write(header + "\n")
        for r in rows:
            f.write(",".join(str(x) for x in r) + "\n")
    return fp


def write_bench_json(name: str, payload: dict) -> Path:
    """Machine-readable perf trajectory: BENCH_<name>.json at the repo root
    (the CSVs under results/ are per-run; the JSON is the one CI and future
    sessions diff for regressions)."""
    fp = REPO_ROOT / f"BENCH_{name}.json"
    fp.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    return fp


@functools.lru_cache(maxsize=None)
def dataset(name: str, seed: int = 0):
    from repro.data.synthetic import paper_dataset
    return paper_dataset(name, seed)
