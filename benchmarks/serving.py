"""Serving-tier bench: live query throughput + latency under a running run.

Measures the gossip serving tier (repro.core.serving + the GossipServer
request loop) end to end: the sharded engine gossips underneath while a
query stream — drawn from the held-out test set, so every served answer has
a label — is batched and answered from eval-point snapshots with the cache
majority vote. Per (scenario, N) the rows record co-serving protocol
throughput (node-cycles/s over the full wall clock, serving included),
queries/s, histogram-backed p50/p90/p99/p999 batch latency (the shared
fixed-bucket ``repro.core.telemetry.LatencyHistogram`` — rows also carry
the sparse bucket dump, comparable across PRs) and the fresh-vs-voted
accuracy of the *served* answers, at N = 10^4..10^6 (quick: 10^4) under
the clean and the paper's extreme (50% drop, 10Δ delays, 90% online)
scenarios.

Bitwise probes ride along at a fixed PROBE_N (the robustness-bench
precedent — the reference engine cannot reach 10^6): per scenario × wire
(f32 + int4), (a) ``snapshot/...`` — eval-point QuerySnapshots are bitwise
identical across engines, and every row carries its scenario's verdict as
``snapshot_parity``; (b) ``kernel/...`` — the Pallas
``voted_predict_batched`` path answers bitwise == the jnp ``serve_voted``
path; (c) ``no_perturb/...`` — a hooked-and-serving run reproduces the
unhooked error curves bit for bit. All three are no-baseline hard gates in
tools/check_bench_regression.py.

    PYTHONPATH=src python -m benchmarks.serving [--quick]
    PYTHONPATH=src python -m benchmarks.run --only serving

Output: CSV rows (results/benchmarks/) plus the machine-readable
``BENCH_serving.json`` at the repo root (guarded as the fourth pair of
tools/run_tests.sh --bench-smoke).
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import Timer, write_bench_json, write_csv

DIM = 57                       # spambase-sized models (paper Table I)
PROBE_N = 2_000                # bitwise parity probes run at this N
BATCH = 256                    # serving batch size (one compiled signature)
SCENARIOS = ("clean", "extreme")
PROBE_WIRES = (None, "int4")   # full-precision + a packed-codec wire


def _dataset(n: int, d: int, seed: int = 0):
    from repro.data.synthetic import make_linear_dataset
    rng = np.random.default_rng(seed)
    X, y = make_linear_dataset(rng, n + 2048, d, noise=0.07, separation=2.5)
    return X[:n], y[:n], X[n:], y[n:]


def _cfg(n: int, scenario: str, wire=None):
    from repro.configs.gossip_linear import (GossipLinearConfig,
                                             with_failure_scenario)
    return with_failure_scenario(
        GossipLinearConfig(name=f"serve-{n}", dim=DIM, n_nodes=n,
                           n_test=2048, class_ratio=(1, 1), lam=1e-3,
                           variant="mu", cache_size=4, wire_dtype=wire),
        scenario)


def _serving_run(cfg, data, *, cycles, queries_per_eval, use_kernel=False,
                 seed=0, engine="sharded"):
    """One hooked run: returns (SimResult, GossipServer, per-query labels)."""
    from repro.core.simulation import run_simulation
    from repro.launch.gossip_serve import GossipServer

    X, y, Xt, yt = data
    srv = GossipServer(batch_size=BATCH, policy="uniform",
                       use_kernel=use_kernel, interpret=use_kernel)
    qrng = np.random.default_rng(17)
    labels = []

    def hook(cycle, snapshot):
        srv.serve_hook(cycle, snapshot)
        idx = qrng.integers(0, len(Xt), queries_per_eval)
        labels.append(yt[idx])
        srv.submit(Xt[idx])

    res = run_simulation(cfg, X, y, Xt, yt, cycles=cycles, eval_every=10,
                         seed=seed, engine=engine, serve_hook=hook)
    srv.flush()
    return res, srv, np.concatenate(labels) if labels else np.zeros(0)


def _parity_probes(cycles: int) -> dict:
    """The fixed-N bitwise gates: snapshot engine-parity, kernel-vs-jnp
    served answers, and the serving-never-perturbs property."""
    import jax
    import jax.numpy as jnp

    from repro.core import serving
    from repro.core.simulation import run_simulation
    from repro.kernels.voted_predict import voted_predict_batched

    parity = {}
    data = _dataset(PROBE_N, DIM, seed=1)
    X, y, Xt, yt = data
    kw = dict(cycles=cycles, eval_every=10, seed=3)
    for scenario in SCENARIOS:
        for wire in PROBE_WIRES:
            cfg = _cfg(PROBE_N, scenario, wire=wire)
            tag = f"{scenario}/{wire or 'f32'}"

            snaps = {"reference": {}, "sharded": {}}

            def collect(store):
                def hook(cycle, snap):
                    store[cycle] = jax.tree.map(np.array, snap)
                return hook

            hooked = {}
            for engine in ("reference", "sharded"):
                hooked[engine] = run_simulation(
                    cfg, X, y, Xt, yt, engine=engine,
                    serve_hook=collect(snaps[engine]), **kw)
            ok = sorted(snaps["reference"]) == sorted(snaps["sharded"])
            for cyc, ref_snap in snaps["reference"].items():
                sh_snap = snaps["sharded"].get(cyc)
                ok = ok and sh_snap is not None and all(
                    np.array_equal(a, b)
                    for a, b in zip(ref_snap, sh_snap))
            parity[f"snapshot/{tag}"] = bool(ok)

            # serving must not perturb: the hooked curves == unhooked
            clean = run_simulation(cfg, X, y, Xt, yt, engine="sharded", **kw)
            parity[f"no_perturb/{tag}"] = bool(
                hooked["sharded"].err_fresh == clean.err_fresh
                and hooked["sharded"].err_voted == clean.err_voted)

            # kernel path == jnp path on the terminal sharded snapshot
            last = snaps["sharded"][max(snaps["sharded"])]
            w, count = jnp.asarray(last.w), jnp.asarray(last.count)
            Xq = jnp.asarray(Xt[:512], jnp.float32)
            assign = jnp.asarray(serving.assign_queries(512, PROBE_N,
                                                        seed=9))
            exp = serving.serve_voted(w, count, Xq, assign)
            got = voted_predict_batched(w[assign], count[assign], Xq,
                                        interpret=True)
            parity[f"kernel/{tag}"] = bool(
                np.array_equal(np.asarray(got), np.asarray(exp)))
            print(f"serving,parity,{tag},"
                  f"snapshot={parity[f'snapshot/{tag}']},"
                  f"kernel={parity[f'kernel/{tag}']},"
                  f"no_perturb={parity[f'no_perturb/{tag}']}")
    return parity


def run(quick: bool = False) -> dict:
    cycles = 20 if quick else 50
    queries_per_eval = 512 if quick else 2048
    n_sweep = [10_000] if quick else [10_000, 100_000, 1_000_000]

    parity = _parity_probes(20)

    rows, json_rows = [], []
    results: dict = {}
    for scenario in SCENARIOS:
        for n in n_sweep:
            data = _dataset(n, DIM)
            cfg = _cfg(n, scenario)
            # warm-up: compiles the chunk fn AND the serve fns at the
            # (N, BATCH) signatures the timed run uses
            _serving_run(cfg, data, cycles=10,
                         queries_per_eval=queries_per_eval)
            with Timer() as t:
                res, srv, y_served = _serving_run(
                    cfg, data, cycles=cycles,
                    queries_per_eval=queries_per_eval)
            s = srv.stats()
            rate = n * cycles / t.s
            acc_voted = float(np.mean(srv.answers() == y_served))
            acc_fresh = float(np.mean(srv.answers_fresh() == y_served))
            results[(scenario, n)] = (res, s, acc_voted, acc_fresh)
            snap_ok = all(parity[f"snapshot/{scenario}/{w or 'f32'}"]
                          for w in PROBE_WIRES)
            rows.append((scenario, n, cycles, f"{t.s:.3f}", f"{rate:.0f}",
                         s.queries, f"{s.queries_per_sec:.0f}",
                         f"{s.p50_latency_s * 1e3:.3f}",
                         f"{s.p99_latency_s * 1e3:.3f}",
                         f"{acc_voted:.4f}", f"{acc_fresh:.4f}", snap_ok))
            json_rows.append(dict(
                engine="sharded", scenario=scenario, n_nodes=n,
                cycles=cycles, seconds=t.s, node_cycles_per_sec=rate,
                queries=s.queries, queries_per_sec=s.queries_per_sec,
                p50_latency_s=s.p50_latency_s,
                p99_latency_s=s.p99_latency_s,
                p90_latency_s=s.p90_latency_s,
                p999_latency_s=s.p999_latency_s,
                latency_hist=s.latency_hist,
                acc_voted=acc_voted, acc_fresh=acc_fresh,
                snapshot_parity=snap_ok))
            print("serving," + ",".join(str(x) for x in rows[-1]))

    derived: dict = {}
    base = results.get(("clean", 10_000))
    if base:
        _, s, acc_voted, acc_fresh = base
        derived["clean_10k_queries_per_sec"] = s.queries_per_sec
        derived["clean_10k_acc_voted"] = acc_voted
        derived["clean_10k_acc_fresh"] = acc_fresh
        derived["voted_minus_fresh_acc"] = acc_voted - acc_fresh
    derived["all_snapshot_probes_bitwise"] = all(
        v for k, v in parity.items() if k.startswith("snapshot/"))
    derived["all_kernel_probes_bitwise"] = all(
        v for k, v in parity.items() if k.startswith("kernel/"))
    derived["all_runs_unperturbed"] = all(
        v for k, v in parity.items() if k.startswith("no_perturb/"))

    write_csv("serving",
              "scenario,n_nodes,cycles,seconds,node_cycles_per_sec,"
              "queries,queries_per_sec,p50_latency_ms,p99_latency_ms,"
              "acc_voted,acc_fresh,snapshot_parity", rows)
    write_bench_json("serving", dict(
        bench="serving",
        quick=quick,
        setup=dict(dim=DIM, variant="mu", cache_size=4, batch=BATCH,
                   queries_per_eval=queries_per_eval, eval_every=10,
                   policy="uniform", probe_n=PROBE_N,
                   probe_wires=[w or "f32" for w in PROBE_WIRES],
                   engine="sharded"),
        rows=json_rows,
        parity_bitwise=parity,
        derived=derived,
    ))
    return results


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    run(ap.parse_args().quick)
