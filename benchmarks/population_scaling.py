"""Population-scaling sweep: N = 10^3 .. 10^6 nodes, reference vs sharded.

The paper's PeerSim runs stop near N ~ 10^4; related work ("On the Limit
Performance of Floating Gossip") analyzes exactly the N→∞ regime. This bench
measures node-cycles/sec over the sweep on two scenario families:

* **extreme** (Fig. 1 lower row): 50% drop, delays uniform in [Δ, 10Δ],
  90%-online churn — for ``reference`` (up to ``REF_MAX_N``),
  ``sharded-dense`` (PR 1's dense K-round apply), ``sharded`` (occupancy-
  chosen compacted rounds) and ``sharded-bf16`` (+ bf16 wire dtype);
* **sparse delivery** (the Fig. 5–7 robustness regimes): online fraction
  0.1/0.3 crossed with drop 0.5/0.8 under the 10Δ delay — where only a few
  percent of the population receives per cycle. Here ``sharded-r1dense``
  pins the PR 3 packing (round 1 applied densely, ``compact_mode=
  "compact"``) against ``sharded`` (free to pick the delivery-proportional
  ``compact_all`` packing), so the JSON's ``derived`` speedups record
  exactly what round-1 compaction buys.

    PYTHONPATH=src python -m benchmarks.population_scaling [--quick]
    PYTHONPATH=src python -m benchmarks.run --only population_scaling

Output: CSV rows (results/benchmarks/) plus the machine-readable perf
trajectory ``BENCH_population_scaling.json`` at the repo root — per-row
node-cycles/sec, buffer/wire bytes, compaction telemetry (chunk modes,
round-1 occupancy), the sparse-vs-dense ``derived`` speedups, a bitwise
cross-engine parity probe per wire dtype, and the N=10^6 churn-trace
generation time. ``tools/check_bench_regression.py`` compares a fresh run
against the committed JSON and fails loudly on perf regressions.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import Timer, best_of, write_bench_json, write_csv

REF_MAX_N = 100_000            # reference engine measured up to here
SPEEDUP_AT_N = 100_000         # the acceptance-criterion comparison point
CHURN_TRACE_N = 1_000_000      # churn-trace generation is timed at this N
PARITY_N = 2_000               # cross-engine bitwise probe population


def _retrace_total() -> int:
    """Total jit compile-cache entries across both engines' hot-path fns
    (tools/lint/retrace_guard.py is the hard gate; the bench records the
    per-row delta so retrace churn shows up in the perf trajectory)."""
    from repro.core import sharded_engine, simulation
    return (sum(sharded_engine.retrace_counts().values())
            + simulation.simulate_cycle._cache_size()
            + simulation._eval._cache_size())


def _dataset(n: int, d: int, seed: int = 0):
    from repro.data.synthetic import make_linear_dataset
    rng = np.random.default_rng(seed)
    X, y = make_linear_dataset(rng, n + 512, d, noise=0.07, separation=2.5)
    return X[:n], y[:n], X[n:], y[n:]


def _cfg(n: int, d: int, scenario: str, wire_dtype=None):
    from repro.configs.gossip_linear import (GossipLinearConfig,
                                             with_failure_scenario)
    # cache_size 4 keeps the (N, C, d) cache at 160 MB for N=10^6.
    base = GossipLinearConfig(name=f"scale-{n}", dim=d, n_nodes=n,
                              n_test=512, class_ratio=(1, 1), lam=1e-3,
                              variant="mu", cache_size=4,
                              wire_dtype=wire_dtype)
    return with_failure_scenario(base, scenario)


# label -> (cfg wire_dtype, run_simulation engine kwargs)
EXTREME_VARIANTS = [
    ("reference", None, dict(engine="reference")),
    ("sharded-dense", None, dict(engine="sharded", compact_rounds=False)),
    ("sharded", None, dict(engine="sharded", compact_rounds=True)),
    ("sharded-bf16", "bf16", dict(engine="sharded", compact_rounds=True)),
]

# sparse family: the PR 3 packing (round 1 dense) vs the free engine
SPARSE_VARIANTS = [
    ("sharded-r1dense", None, dict(engine="sharded",
                                   compact_mode="compact")),
    ("sharded", None, dict(engine="sharded")),
]

SPARSE_SCENARIOS = ["sparse-d0.5-o0.3", "sparse-d0.5-o0.1",
                    "sparse-d0.8-o0.3", "sparse-d0.8-o0.1"]


def _parity_probe(d: int, cycles: int, k_rounds: int) -> dict:
    """Bitwise cross-engine probe on the hardest sparse scenario: for every
    wire dtype, reference == sharded-auto == sharded-dense error curves."""
    from repro.core.simulation import run_simulation

    X, y, Xt, yt = _dataset(PARITY_N, d)
    out = {}
    for wire in [None, "bf16", "f16", "int8", "int8_sr"]:
        cfg = _cfg(PARITY_N, d, "sparse-d0.8-o0.1", wire_dtype=wire)
        kw = dict(cycles=cycles, eval_every=10, seed=0, k_rounds=k_rounds)
        ref = run_simulation(cfg, X, y, Xt, yt, **kw)
        auto = run_simulation(cfg, X, y, Xt, yt, engine="sharded", **kw)
        dense = run_simulation(cfg, X, y, Xt, yt, engine="sharded",
                               compact_rounds=False, **kw)
        out[wire or "f32"] = bool(
            ref.err_fresh == auto.err_fresh == dense.err_fresh
            and ref.err_voted == auto.err_voted == dense.err_voted
            and ref.sent_total == auto.sent_total == dense.sent_total)
    return out


def run(quick: bool = False) -> dict:
    from repro.core.simulation import (CHURN_TRACE_VERSION, churn_trace,
                                       run_simulation)

    d = 10                                      # malicious-urls-sized features
    cycles = 20 if quick else 50
    # k_rounds=8 bounds per-cycle receive truncation to ~zero (overflow≈0),
    # matching the paper's event simulator, which never drops simultaneous
    # arrivals; all engines run the identical protocol parameters.
    k_rounds = 8
    sweep = [1_000, 10_000, 100_000] if quick else [
        1_000, 10_000, 100_000, 1_000_000]
    sparse_sweep = [100_000] if quick else [100_000, 1_000_000]
    ref_max = 10_000 if quick else REF_MAX_N

    rows = []
    json_rows = []
    rates: dict = {}
    results: dict = {}

    def measure(label, scenario, n, wire, kw, X, y, Xt, yt):
        cfg = _cfg(n, d, scenario, wire_dtype=wire)
        # warm-up run compiles (same chunk length as the timed run); the
        # timed runs measure steady state via the shared min-time estimator
        # (common.best_of) — the shared 2-core container's noise is
        # strictly additive. eval_every=10 gives paper-style curves and
        # lets the sharded engine pipeline host routing against the
        # in-flight device scan.
        traces0 = _retrace_total()
        run_simulation(cfg, X, y, Xt, yt, cycles=cycles,
                       eval_every=10, seed=0, k_rounds=k_rounds, **kw)
        best, secs, res = best_of(
            lambda: run_simulation(cfg, X, y, Xt, yt, cycles=cycles,
                                   eval_every=10, seed=0,
                                   k_rounds=k_rounds, **kw))
        rate = n * cycles / best
        rates[(label, scenario, n)] = rate
        results[(label, scenario, n)] = res
        rows.append((label, scenario, n, cycles, f"{best:.3f}",
                     f"{rate:.0f}", f"{res.err_fresh[-1]:.4f}",
                     wire or "f32", res.buf_payload_bytes,
                     res.wire_bytes_total))
        dpc = np.asarray(res.delivered_per_cycle, dtype=np.float64)
        json_rows.append(dict(
            engine=label, scenario=scenario, n_nodes=n, cycles=cycles,
            seconds=best, seconds_all=secs, node_cycles_per_sec=rate,
            err_fresh=res.err_fresh[-1], wire_dtype=wire or "f32",
            buf_payload_bytes=res.buf_payload_bytes,
            wire_bytes_total=res.wire_bytes_total,
            sent_total=res.sent_total,
            delivered_total=res.delivered_total,
            delivered_per_cycle_mean=float(dpc.mean()) if dpc.size else 0.0,
            # compiles this row triggered (warm-up included; the timed
            # runs reuse the warm-up's traces, so steady state adds zero)
            retraces=_retrace_total() - traces0,
            compaction=res.compaction))
        print("population_scaling," + ",".join(str(x) for x in rows[-1]))

    for n in sweep:
        X, y, Xt, yt = _dataset(n, d)
        for label, wire, kw in EXTREME_VARIANTS:
            if label == "reference" and n > ref_max:
                continue
            measure(label, "extreme", n, wire, kw, X, y, Xt, yt)

    for n in sparse_sweep:
        X, y, Xt, yt = _dataset(n, d)
        for scenario in SPARSE_SCENARIOS:
            for label, wire, kw in SPARSE_VARIANTS:
                measure(label, scenario, n, wire, kw, X, y, Xt, yt)

    # churn-trace generation cost at mega-population scale (acceptance:
    # the v2 vectorized sampler stays well under ~2 s at N=10^6)
    with Timer() as t_trace:
        churn_trace(np.random.default_rng(0), CHURN_TRACE_N, cycles, 0.9)
    print(f"population_scaling,churn_trace,v{CHURN_TRACE_VERSION},"
          f"n={CHURN_TRACE_N},cycles={cycles},{t_trace.s:.3f}s")

    parity = _parity_probe(d, cycles=20, k_rounds=k_rounds)
    print("population_scaling,parity," + ",".join(
        f"{k}={'bitwise' if v else 'MISMATCH'}" for k, v in parity.items()))

    derived: dict = {}
    cmp_n = min(SPEEDUP_AT_N, ref_max)
    if (("reference", "extreme", cmp_n) in rates
            and ("sharded", "extreme", cmp_n) in rates):
        speedup = (rates[("sharded", "extreme", cmp_n)]
                   / rates[("reference", "extreme", cmp_n)])
        derived[f"sharded_vs_reference_speedup_at_{cmp_n}"] = speedup
        print(f"population_scaling,speedup@N={cmp_n},{speedup:.1f}x")
    top_n = sweep[-1]
    if ("sharded-dense", "extreme", top_n) in rates:
        compact_speedup = (rates[("sharded", "extreme", top_n)]
                           / rates[("sharded-dense", "extreme", top_n)])
        derived[f"compact_vs_dense_speedup_at_{top_n}"] = compact_speedup
        print(f"population_scaling,compact_speedup@N={top_n},"
              f"{compact_speedup:.2f}x")
    if ("sharded-bf16", "extreme", top_n) in results:
        ratio = (results[("sharded-bf16", "extreme", top_n)].buf_payload_bytes
                 / results[("sharded", "extreme", top_n)].buf_payload_bytes)
        derived[f"bf16_payload_buffer_ratio_at_{top_n}"] = ratio
        print(f"population_scaling,bf16_buffer_ratio@N={top_n},{ratio:.2f}")
    sparse_top = sparse_sweep[-1]
    for scenario in SPARSE_SCENARIOS:
        a = rates.get(("sharded", scenario, sparse_top))
        b = rates.get(("sharded-r1dense", scenario, sparse_top))
        if a and b:
            key = f"r1compact_vs_r1dense_speedup_at_{sparse_top}_{scenario}"
            derived[key] = a / b
            print(f"population_scaling,r1compact_speedup@N={sparse_top},"
                  f"{scenario},{a / b:.2f}x")

    write_csv("population_scaling",
              "engine,scenario,n_nodes,cycles,seconds,node_cycles_per_sec,"
              "err_fresh,wire_dtype,buf_payload_bytes,wire_bytes_total",
              rows)
    from repro.configs.gossip_linear import FAILURE_SCENARIOS
    write_bench_json("population_scaling", dict(
        bench="population_scaling",
        quick=quick,
        protocol=dict(k_rounds=k_rounds, dim=d, cycles=cycles,
                      variant="mu", cache_size=4),
        scenarios={name: FAILURE_SCENARIOS[name]
                   for name in ["extreme"] + SPARSE_SCENARIOS},
        rows=json_rows,
        parity_bitwise=parity,
        churn_trace=dict(version=CHURN_TRACE_VERSION, n_nodes=CHURN_TRACE_N,
                         cycles=cycles, seconds=t_trace.s),
        derived=derived,
    ))
    return rates


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    run(ap.parse_args().quick)
