#!/usr/bin/env python
"""Retrace budget gate: fail when the engines compile more than they should.

The engines are built so that compilation cost is *bounded*: the reference
engine jits one cycle fn and one eval fn; the sharded engine compiles one
``_draw_chunk`` signature per (chunk length, N, scenario statics) and one
``chunk_fn`` signature per (chunk length, packed widths, N) — with the
sticky power-of-two width bucketing keeping the widths axis at O(log N)
signatures. A change that breaks any of this (chunk-length churn, widths
that never go sticky, a dtype flapping between chunks, a host scalar
smuggled into a traced signature) does not fail a test — every run still
converges — it just silently recompiles every chunk, and shows up weeks
later as a bench regression.

This gate makes that failure loud. It runs a small reference + sharded
suite (dense f32 / compact_all int8, the two packing extremes crossed with
the widest dtype gap), reads the jit compile-cache sizes via
``sharded_engine.retrace_counts()`` and ``_cache_size()`` on the reference
fns, and fails if

* any compile source exceeds its pinned ``BUDGETS`` entry (cold check),
* any compile source is missing from ``BUDGETS`` entirely (a new jitted fn
  must declare its budget here), or
* an identical warm rerun compiles *anything* (steady state must be
  zero-compile — the property the benchmarks' min-of-two timing relies on).

    PYTHONPATH=src python tools/lint/retrace_guard.py            # gate
    PYTHONPATH=src python tools/lint/retrace_guard.py --print-counts

Run by ``tools/run_tests.sh --bench-smoke`` next to the bench-regression
check; the contract is documented in docs/CONTRACTS.md.
"""
from __future__ import annotations

import argparse
import re
import sys
from pathlib import Path
from typing import Dict, List

REPO = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(REPO / "src"))

# chunk_fn labels carry a creation index ("3:scan/pegasos/dense/f32") so
# retrace_counts() never collides; budgets are pinned on the stable part
_IDX = re.compile(r"\[\d+:")

# Pinned compile budgets for the mini-suite below, keyed by normalized
# compile source. Raising a number here is an explicit, reviewed act —
# justify it in the commit message (e.g. a new chunk signature axis).
BUDGETS: Dict[str, int] = {
    # reference engine: one cycle signature per emit_streams static — the
    # suite runs unarmed AND telemetry-armed legs, so exactly 2; eval
    # shapes are shared with the sharded engine (and unchanged by arming),
    # so _eval compiles once across the whole suite
    "simulation.simulate_cycle": 2,
    "simulation._eval": 1,
    # sharded control plane: one signature per scenario statics
    # (drop/delay/sampler) x chunk length — the suite uses one scenario
    # and one chunk length
    "sharded_engine._draw_chunk": 1,
    # data plane: one signature per chunk length; the f32 dense config
    "sharded_engine.chunk_fn[mu/pegasos/dense/f32]": 1,
    # ... the telemetry-armed variant of the same config (distinct label:
    # armed chunk fns return per-cycle stream arrays, unarmed runs never
    # build it — the bitwise-invisibility contract of docs/CONTRACTS.md)
    "sharded_engine.chunk_fn[mu/pegasos/dense/f32/telem]": 1,
    # ... and the int8 compact_all config: packed widths are sticky
    # power-of-two buckets, so a short run sees at most 2 width buckets
    # before sticking
    "sharded_engine.chunk_fn[mu/pegasos/compact_all/int8]": 2,
    # serving tier: one signature per (N, batch, d) — the suite serves one
    # fixed-shape batch from both engines' snapshots (identical shapes, so
    # each path compiles once); the Pallas voted_predict_batched cache is
    # counted separately from its serve_voted_kernel wrapper
    "serving.serve_fresh": 1,
    "serving.serve_voted": 1,
    "serving.serve_voted_kernel": 1,
    "kernels.voted_predict_batched": 1,
}


def normalize(key: str) -> str:
    """Strip the per-instance index from chunk_fn labels."""
    return _IDX.sub("[", key)


def check_budgets(counts: Dict[str, int],
                  budgets: Dict[str, int]) -> List[str]:
    """Compare observed compile counts against pinned budgets.

    Returns human-readable error strings: over-budget sources, and sources
    with no budget entry at all (every jitted hot-path fn must be pinned).
    Pure function of its arguments — unit-tested in tests/test_lint.py."""
    errors: List[str] = []
    totals: Dict[str, int] = {}
    for key, n in counts.items():
        norm = normalize(key)
        totals[norm] = totals.get(norm, 0) + n
    for key in sorted(totals):
        n = totals[key]
        if key not in budgets:
            if n:
                errors.append(
                    f"{key}: {n} compile(s) from an unbudgeted source — "
                    f"add a pinned entry to retrace_guard.BUDGETS")
        elif n > budgets[key]:
            errors.append(f"{key}: {n} compile(s) > budget {budgets[key]} "
                          f"— the hot path is retracing")
    return errors


def diff_counts(cold: Dict[str, int], warm: Dict[str, int]) -> List[str]:
    """Error strings for every source that compiled during the warm rerun."""
    errors = []
    for key in sorted(warm):
        grew = warm[key] - cold.get(key, 0)
        if grew > 0:
            errors.append(f"{key}: {grew} new compile(s) on an identical "
                          f"warm rerun — steady state must be zero-compile")
    return errors


def snapshot() -> Dict[str, int]:
    """Current compile-cache sizes of every budgeted hot-path fn."""
    from repro.core import serving, sharded_engine, simulation
    from repro.kernels import voted_predict
    counts = dict(sharded_engine.retrace_counts())
    counts["simulation.simulate_cycle"] = \
        simulation.simulate_cycle._cache_size()
    counts["simulation._eval"] = simulation._eval._cache_size()
    counts["serving.serve_fresh"] = serving.serve_fresh._cache_size()
    counts["serving.serve_voted"] = serving.serve_voted._cache_size()
    counts["serving.serve_voted_kernel"] = \
        serving.serve_voted_kernel._cache_size()
    counts["kernels.voted_predict_batched"] = \
        voted_predict.voted_predict_batched._cache_size()
    return counts


def _mini_suite():
    """One reference run + the two sharded packing extremes, tiny sizes."""
    import dataclasses

    import numpy as np

    from repro.configs.gossip_linear import (GossipLinearConfig,
                                             with_failure_scenario)
    from repro.core.simulation import run_simulation
    from repro.data.synthetic import make_linear_dataset

    n, d = 256, 8
    rng = np.random.default_rng(0)
    X, y = make_linear_dataset(rng, n + 128, d, noise=0.07, separation=2.5)
    X, Xt, y, yt = X[:n], X[n:], y[:n], y[n:]
    cfg = with_failure_scenario(
        GossipLinearConfig(name="retrace-guard", dim=d, n_nodes=n,
                           n_test=128, class_ratio=(1, 1), lam=1e-3,
                           variant="mu", cache_size=4),
        "sparse-d0.5-o0.3")
    kw = dict(cycles=20, eval_every=10, seed=0, k_rounds=2)
    # serving tier rides on the reference + dense-sharded runs: both
    # engines' snapshots have identical shapes, and the query batch is
    # fixed-shape, so each serve path must compile exactly once
    from repro.launch.gossip_serve import GossipServer
    srv = GossipServer(batch_size=16, use_kernel=False)
    srv_k = GossipServer(batch_size=16, use_kernel=True, interpret=True)
    Xq = Xt[:16]

    def serve_hook(cycle, snap):
        for s in (srv, srv_k):
            s.serve_hook(cycle, snap)
            s.submit(Xq)

    run_simulation(cfg, X, y, Xt, yt, serve_hook=serve_hook, **kw)
    run_simulation(cfg, X, y, Xt, yt, engine="sharded",
                   compact_rounds=False, serve_hook=serve_hook, **kw)
    cfg_q = dataclasses.replace(cfg, wire_dtype="int8")
    run_simulation(cfg_q, X, y, Xt, yt, engine="sharded",
                   compact_mode="compact_all", **kw)
    # telemetry-armed legs: arming is a compile-time static (emit_streams
    # on the reference cycle fn, a "/telem" chunk-fn variant on the
    # sharded engine), so each armed config costs exactly one extra
    # signature — and a warm armed rerun must compile nothing, like every
    # other leg
    from repro.core.telemetry import Telemetry
    run_simulation(cfg, X, y, Xt, yt, telemetry=Telemetry(), **kw)
    run_simulation(cfg, X, y, Xt, yt, engine="sharded",
                   compact_rounds=False, telemetry=Telemetry(), **kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--print-counts", action="store_true",
                    help="show observed compile counts and exit")
    args = ap.parse_args(argv)

    _mini_suite()
    cold = snapshot()
    if args.print_counts:
        for key in sorted(cold):
            print(f"{cold[key]:4d}  {key}")
        return 0

    errors = check_budgets(cold, BUDGETS)
    _mini_suite()                      # identical rerun: must not compile
    errors += diff_counts(cold, snapshot())

    for e in errors:
        print(f"retrace-guard: {e}")
    if errors:
        print(f"retrace-guard: {len(errors)} violation(s)")
        return 1
    total = sum(cold.values())
    print(f"retrace-guard: OK ({total} compiles across "
          f"{len(cold)} sources, all within budget; warm rerun clean)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
