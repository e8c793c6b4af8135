"""Batched serving example: answer live queries from a running gossip run.

The serving tier end to end (docs/SERVING.md): a gossip protocol runs
underneath (either engine), a ``GossipServer`` adopts a fresh
``QuerySnapshot`` at every eval point, and a stream of feature-vector
queries — drawn from the held-out test set, so every answer has a label —
is batched up and answered with the cache majority vote (Algorithm 4 /
Eq. 8 as a service). Prints queries/s, p50/p99 batch latency and the
fresh-vs-voted accuracy of the *served* answers.

    PYTHONPATH=src python examples/serve_batched.py
    PYTHONPATH=src python examples/serve_batched.py --engine sharded \
        --nodes 100000 --scenario extreme --wire-dtype int4 --use-kernel
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.configs.gossip_linear import FAILURE_SCENARIOS

# same short spellings as examples/million_nodes.py; every registered
# FAILURE_SCENARIOS key is also accepted verbatim
SCENARIO_ALIASES = {"sparse": "sparse-d0.8-o0.1"}
SCENARIO_CHOICES = sorted(SCENARIO_ALIASES) + sorted(FAILURE_SCENARIOS)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=10_000)
    ap.add_argument("--cycles", type=int, default=50)
    ap.add_argument("--dim", type=int, default=57)
    ap.add_argument("--engine", choices=["reference", "sharded"],
                    default="sharded")
    ap.add_argument("--scenario", choices=SCENARIO_CHOICES, default="clean",
                    help="failure operating point the protocol runs under "
                         "while serving: clean, extreme (drop=0.5, 10 cycle "
                         "delays, 90%% online), sparse (alias for "
                         "sparse-d0.8-o0.1), or any FAILURE_SCENARIOS key")
    ap.add_argument("--wire-dtype",
                    choices=["f32", "bf16", "f16", "int8", "int8_sr",
                             "int4", "int4_ef", "ternary", "ternary_ef"],
                    default=None,
                    help="wire codec for the protocol's transmitted models "
                         "(serving reads snapshots after decode; merge math "
                         "stays f32)")
    ap.add_argument("--batch", type=int, default=256,
                    help="serving batch size (one compiled predict "
                         "signature; tail batches are padded to it)")
    ap.add_argument("--queries", type=int, default=2048,
                    help="queries submitted per eval-point snapshot")
    ap.add_argument("--policy", choices=["uniform", "round_robin"],
                    default="uniform",
                    help="node-assignment policy: which node answers each "
                         "query")
    ap.add_argument("--use-kernel", action="store_true",
                    help="answer with the fused Pallas voted_predict_batched "
                         "path instead of the jnp einsum path; answers are "
                         "bitwise identical")
    ap.add_argument("--interpret", action="store_true",
                    help="run the Pallas kernel in interpret mode (for a "
                         "machine without a TPU)")
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="arm telemetry across the protocol AND the server "
                         "(bitwise invisible): print the per-phase span "
                         "summary — including snapshot_adopt/serve_batch "
                         "serving spans and the batch-latency histogram — "
                         "and export a Chrome trace to this path")
    args = ap.parse_args()
    scenario = SCENARIO_ALIASES.get(args.scenario, args.scenario)

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.configs.gossip_linear import (GossipLinearConfig,
                                             with_failure_scenario)
    from repro.core.simulation import run_simulation
    from repro.data.synthetic import make_linear_dataset
    from repro.launch.gossip_serve import GossipServer

    n, d = args.nodes, args.dim
    wire = None if args.wire_dtype == "f32" else args.wire_dtype
    n_test = max(args.queries, 512)
    rng = np.random.default_rng(0)
    X, y = make_linear_dataset(rng, n + n_test, d, noise=0.07,
                               separation=2.5)
    cfg = with_failure_scenario(
        GossipLinearConfig(name=f"serve-{n}", dim=d, n_nodes=n,
                           n_test=n_test, class_ratio=(1, 1), lam=1e-3,
                           variant="mu", cache_size=4, wire_dtype=wire),
        scenario)
    X_test, y_test = X[n:], y[n:]

    tel = None
    if args.trace:
        from repro.core.telemetry import Telemetry
        tel = Telemetry(label=f"serve_batched N={n} {scenario}")
    srv = GossipServer(batch_size=args.batch, policy=args.policy,
                       use_kernel=args.use_kernel,
                       interpret=args.interpret, telemetry=tel)
    qrng = np.random.default_rng(7)
    labels = []

    def serve_hook(cycle, snapshot):
        srv.serve_hook(cycle, snapshot)
        idx = qrng.integers(0, n_test, args.queries)
        labels.append(y_test[idx])
        srv.submit(X_test[idx])

    print(f"N={n:,} peers, d={d}, {args.cycles} cycles, "
          f"engine={args.engine}, scenario={scenario}, "
          f"wire={wire or 'f32'}; serving {args.queries} queries per eval "
          f"point in batches of {args.batch} "
          f"({'Pallas kernel' if args.use_kernel else 'jnp einsum'} path, "
          f"{args.policy} assignment)")
    res = run_simulation(cfg, X[:n], y[:n], X_test, y_test,
                         cycles=args.cycles,
                         eval_every=max(args.cycles // 5, 1), seed=0,
                         engine=args.engine, serve_hook=serve_hook,
                         telemetry=tel)
    srv.flush()

    y_served = np.concatenate(labels)
    acc_voted = float(np.mean(srv.answers() == y_served))
    acc_fresh = float(np.mean(srv.answers_fresh() == y_served))
    s = srv.stats()

    print(f"\n  {'cycle':>6} {'err(fresh)':>11} {'err(voted)':>11} "
          f"{'served batches':>15}")
    per_cycle = {}
    for b in srv.batches:
        per_cycle[b.cycle] = per_cycle.get(b.cycle, 0) + 1
    for cyc, ef, ev in zip(res.cycles, res.err_fresh, res.err_voted):
        print(f"  {cyc:>6} {ef:>11.4f} {ev:>11.4f} "
              f"{per_cycle.get(int(cyc), 0):>15}")
    print(f"\nserved {s.queries:,} queries in {s.batches} batches: "
          f"{s.queries_per_sec:,.0f} queries/s, "
          f"p50 {s.p50_latency_s * 1e3:.2f} ms / "
          f"p99 {s.p99_latency_s * 1e3:.2f} ms per batch")
    print(f"accuracy of served answers: voted {acc_voted:.4f} "
          f"vs fresh {acc_fresh:.4f} "
          f"(voted - fresh = {acc_voted - acc_fresh:+.4f})")

    if tel is not None:
        print("\n" + tel.phase_report())
        fp = tel.export_chrome_trace(args.trace)
        print(f"trace written to {fp} — open at https://ui.perfetto.dev "
              f"or summarize with: python tools/trace_report.py {fp}")


if __name__ == "__main__":
    main()
