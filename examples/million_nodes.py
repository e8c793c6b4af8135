"""Gossip learning at a million nodes — 100× beyond the paper's PeerSim runs.

The paper's convergence claims are population-level: merged models random-
walk over *many* nodes, and related work ("On the Limit Performance of
Floating Gossip") analyzes exactly the N→∞ regime. The sharded engine makes
that regime reachable on one machine: the control plane (routing, failures)
is resolved host-side per chunk, the data plane (merge+update+cache) runs as
one ``lax.scan`` between eval points.

    PYTHONPATH=src python examples/million_nodes.py                # 10^6 nodes
    PYTHONPATH=src python examples/million_nodes.py --nodes 100000 # smaller
    PYTHONPATH=src python examples/million_nodes.py --scenario sparse

``--scenario sparse`` runs the paper's Fig. 5–7 robustness regime (80% drop,
10% online, 10Δ delays): only a fraction of a percent of the population
receives per cycle, and the engine's occupancy-based packing switches to the
delivery-proportional ``compact_all`` path — the printed compaction report
shows the chunk modes and receiver occupancy the router observed. Any key
of ``FAILURE_SCENARIOS`` is also accepted directly.

``--fault-model sign_flip --byzantine-frac 0.1 --defense norm_clip`` layers
the adversarial regime (repro.core.faults) on top: a seed-chosen Byzantine
subset corrupts every send, the receive path screens each payload per merge
round, and the run prints the engine's fault counters (corrupted sends,
gated + clipped receives). Measured trade-offs: BENCH_robustness.json.

Expected: the error curve tracks the paper's Fig. 1 shape — at fixed cycle
count the per-cycle error is population-size-invariant (each node still sees
one message per cycle), so convergence *speed per cycle* matches the 10^4
runs while the system processes 100× the node-cycles.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

# config-layer import only (no jax): the scenario choices come from the
# single registry in repro.configs.gossip_linear instead of a local copy
from repro.configs.gossip_linear import FAILURE_SCENARIOS

# short spellings for the most-used operating points; every registered
# FAILURE_SCENARIOS key is also accepted verbatim
SCENARIO_ALIASES = {"sparse": "sparse-d0.8-o0.1"}
SCENARIO_CHOICES = sorted(SCENARIO_ALIASES) + sorted(FAILURE_SCENARIOS)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=1_000_000)
    ap.add_argument("--cycles", type=int, default=50)
    ap.add_argument("--dim", type=int, default=10)
    ap.add_argument("--scenario", choices=SCENARIO_CHOICES, default=None,
                    help="failure operating point: clean (no failures), "
                         "extreme (drop=0.5, 10 cycle delays, 90%% online), "
                         "sparse (alias for sparse-d0.8-o0.1 — the "
                         "delivery-proportional compact_all regime), or any "
                         "registered FAILURE_SCENARIOS key")
    ap.add_argument("--extreme", action="store_true",
                    help="alias for --scenario extreme")
    ap.add_argument("--wire-dtype",
                    choices=["f32", "bf16", "f16", "int8", "int8_sr",
                             "int4", "int4_ef", "ternary", "ternary_ef"],
                    default=None,
                    help="wire codec for the transmitted models (and the "
                         "in-flight buffer — the engine's dominant memory): "
                         "float casts, affine int8, packed int4 (2 "
                         "codes/byte) or base-3 ternary (5 codes/byte); "
                         "the _ef variants add sender-side error-feedback "
                         "residuals. Merge math stays f32")
    ap.add_argument("--fault-model",
                    choices=["sign_flip", "amplify", "zero",
                             "random_payload", "stale_replay", "bitflip"],
                    default=None,
                    help="adversarial fault model (repro.core.faults): a "
                         "seed-chosen Byzantine subset corrupts every model "
                         "it sends (bitflip corrupts the encoded wire "
                         "bytes instead). Default: no fault injection")
    ap.add_argument("--byzantine-frac", type=float, default=0.1,
                    help="fraction of nodes applying the fault "
                         "(only with --fault-model; default 0.1)")
    ap.add_argument("--defense",
                    choices=["none", "norm_clip", "cosine_gate"],
                    default="none",
                    help="receive-side payload screen, applied per merge "
                         "round: norm_clip rescales oversized payloads to "
                         "a multiple of the receiver's own norm, "
                         "cosine_gate rejects anti-aligned ones")
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="arm telemetry (bitwise invisible to the run): "
                         "print the per-phase span summary and export a "
                         "Chrome trace with the per-cycle metric streams "
                         "to this path (view at ui.perfetto.dev; "
                         "summarize with tools/trace_report.py)")
    args = ap.parse_args()
    scenario = args.scenario or ("extreme" if args.extreme else "clean")
    scenario = SCENARIO_ALIASES.get(scenario, scenario)

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.configs.gossip_linear import (GossipLinearConfig,
                                             with_failure_scenario)
    from repro.core.simulation import message_wire_bytes, run_simulation
    from repro.core.wire_codec import get_codec
    from repro.data.synthetic import make_linear_dataset

    n, d = args.nodes, args.dim
    wire = None if args.wire_dtype == "f32" else args.wire_dtype
    codec = get_codec(wire)
    rng = np.random.default_rng(0)
    X, y = make_linear_dataset(rng, n + 1000, d, noise=0.07, separation=2.5)
    cfg = with_failure_scenario(
        GossipLinearConfig(
            name=f"million-{n}", dim=d, n_nodes=n, n_test=1000,
            class_ratio=(1, 1), lam=1e-3, variant="mu", cache_size=4,
            wire_dtype=wire, fault_model=args.fault_model,
            byzantine_frac=args.byzantine_frac if args.fault_model else 0.0,
            defense=args.defense),
        scenario)

    print(f"N={n:,} peers (one record each), d={d}, "
          f"{args.cycles} cycles, variant=MU, "
          f"wire={codec.name} ({message_wire_bytes(d, wire)} B/msg"
          f"{', error feedback' if codec.ef else ''}), "
          f"scenario={scenario} "
          f"(drop={cfg.drop_prob}, delay<= {cfg.delay_max_cycles} cycles, "
          f"online={cfg.online_fraction:.0%})")
    if cfg.fault_model:
        print(f"adversary: {cfg.fault_model} from "
              f"{cfg.byzantine_frac:.0%} Byzantine nodes, "
              f"defense={cfg.defense}")
    tel = None
    if args.trace:
        from repro.core.telemetry import Telemetry
        tel = Telemetry(label=f"million_nodes N={n} {scenario}")
    t0 = time.time()
    res = run_simulation(cfg, X[:n], y[:n], X[n:], y[n:],
                         cycles=args.cycles,
                         eval_every=max(args.cycles // 5, 1), seed=0,
                         engine="sharded", telemetry=tel)
    dt = time.time() - t0
    print(f"\n  {'cycle':>6} {'err(fresh)':>11} {'err(voted)':>11}")
    for cyc, ef, ev in zip(res.cycles, res.err_fresh, res.err_voted):
        print(f"  {cyc:>6} {ef:>11.4f} {ev:>11.4f}")
    print(f"\n{n * args.cycles / dt:,.0f} node-cycles/sec "
          f"({dt:.1f}s wall; {res.sent_total:,} messages sent, "
          f"{res.delivered_total:,} delivered, {res.lost_total:,} lost)")
    print(f"bandwidth: {res.wire_bytes_total / 1e9:.3f} GB on the wire "
          f"({message_wire_bytes(d, wire)} B/msg), "
          f"in-flight payload buffer {res.buf_payload_bytes / 1e6:.1f} MB")
    if codec.ef:
        print(f"error feedback: terminal EF-residual norm "
              f"{res.ef_residual_norm:.4f} (RMS per-node L2; the residual "
              f"each sender still owes the wire)")
    if cfg.fault_model:
        fs = res.fault_stats
        print(f"fault stats: {fs['corrupted']:,} corrupted sends, "
              f"{fs['gated']:,} receives gated, "
              f"{fs['clipped']:,} receives clipped by {cfg.defense}")

    # compaction observability: what the router saw, what the engine chose
    dpc = np.asarray(res.delivered_per_cycle, dtype=np.float64)
    comp = res.compaction
    modes = comp.get("chunk_modes", {})
    print(f"delivered/cycle: mean {dpc.mean():,.0f}, max {dpc.max():,.0f} "
          f"({dpc.mean() / n:.2%} of the population)")
    print("chunk packing: "
          + ", ".join(f"{k}={v}" for k, v in modes.items() if v)
          + f"; round-1 occupancy mean {comp['round1_occupancy_mean']:.2%} "
          f"max {comp['round1_occupancy_max']:.2%}, multi-receive mean "
          f"{comp['multi_occupancy_mean']:.2%}")

    if tel is not None:
        print("\n" + tel.phase_report())
        fp = tel.export_chrome_trace(args.trace)
        print(f"trace written to {fp} — open at https://ui.perfetto.dev "
              f"or summarize with: python tools/trace_report.py {fp}")


if __name__ == "__main__":
    main()
