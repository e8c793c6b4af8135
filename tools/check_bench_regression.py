#!/usr/bin/env python
"""Perf-regression guard: compare fresh benchmark runs against the
committed ``BENCH_*.json`` baselines.

Usage (what ``tools/run_tests.sh --bench-smoke`` does):

    cp BENCH_population_scaling.json /tmp/pop.json     # before the bench
    cp BENCH_wire_quantization.json /tmp/wire.json
    cp BENCH_serving.json /tmp/serving.json
    python -m benchmarks.run --quick \
        --only population_scaling,wire_quantization,serving
    python tools/check_bench_regression.py \
        --pair /tmp/pop.json BENCH_population_scaling.json \
        --pair /tmp/wire.json BENCH_wire_quantization.json \
        --pair /tmp/serving.json BENCH_serving.json [--tolerance 0.4]

``--pair BASELINE CURRENT`` may repeat; the legacy single
``--baseline``/``--current`` spelling still works. Rows are matched on
(engine, scenario, n_nodes, wire_dtype, fault_model, byzantine_frac,
defense) — the wire-quantization rows carry no engine/scenario/fault
columns and match on (N, codec) alone. Cycle counts may differ
between --quick and full runs, but node-cycles/sec is a rate, so the
comparison stays meaningful. A current rate below ``tolerance`` × the
baseline rate fails loudly (exit 1) listing every regressed row; rows only
present on one side are reported but never fail (the sweeps differ between
quick and full mode). The tolerance band is deliberately wide: it catches
"the engine got 2.5× slower" regressions, not CPU-container noise.

Rows whose measured work (``n_nodes × cycles``) falls below
``MIN_NODE_CYCLES`` on either side are reported but never fail: at small N
the "rate" is fixed per-run overhead (host routing, dispatch, eval), and a
20-cycle quick run legitimately amortizes it ~2.5× worse than the 50-cycle
full baseline — a rate mismatch there says nothing about the engine.

Also guards every file's ``parity_bitwise`` probe: any wire codec whose
cross-engine curves stopped being bitwise-identical fails regardless of
speed — for the wire bench that covers the full codec registry, and for
the serving bench the snapshot engine-parity / Pallas-kernel-vs-jnp /
serving-never-perturbs probes. Rows
carrying a ``retraces`` field (compiles triggered per bench row) are
diffed informationally — the hard compile-count gate is
``tools/lint/retrace_guard.py``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path


# rate comparisons need the run to be throughput-dominated, not
# overhead-dominated: below ~10^6 node-cycles a run is mostly fixed cost
MIN_NODE_CYCLES = 1_000_000


def row_key(row: dict):
    # fault_model is null for fault-free rows — normalize so mixed keys
    # stay sortable
    return (row.get("engine"), row.get("scenario", "extreme"),
            row.get("n_nodes"), row.get("wire_dtype") or "f32",
            row.get("fault_model") or "none",
            row.get("byzantine_frac") or 0.0,
            row.get("defense") or "none")


def node_cycles(row: dict) -> int:
    return int(row.get("n_nodes") or 0) * int(row.get("cycles") or 0)


def check_pair(base_fp: Path, cur_fp: Path, tolerance: float,
               failures: list) -> None:
    label = cur_fp.name
    cur = json.loads(cur_fp.read_text())    # a broken CURRENT run is an error

    # the parity probes need no baseline — a broken cross-engine bit
    # pattern in the CURRENT run fails even on a fresh tree
    for dtype, ok in cur.get("parity_bitwise", {}).items():
        if not ok:
            failures.append(f"  [{label}] parity_bitwise[{dtype}]: "
                            "cross-engine curves are no longer "
                            "bitwise-identical")

    base = None
    if not base_fp.is_file():
        print(f"check_bench_regression: no baseline at {base_fp} — skipping "
              f"{label} rate comparison (first run on a fresh tree)")
        return
    try:
        base = json.loads(base_fp.read_text())
    except ValueError:
        print(f"check_bench_regression: unparsable baseline at {base_fp} — "
              "treating as missing, skipping rate comparison")
        return

    base_rows = {row_key(r): r for r in base.get("rows", [])}
    cur_rows = {row_key(r): r for r in cur.get("rows", [])}

    compared = 0
    small = 0
    for key, crow in sorted(cur_rows.items()):
        brow = base_rows.get(key)
        if brow is None:
            continue
        b, c = brow["node_cycles_per_sec"], crow["node_cycles_per_sec"]
        if min(node_cycles(brow), node_cycles(crow)) < MIN_NODE_CYCLES:
            small += 1
            print(f"check_bench_regression: [{label}] "
                  f"{'/'.join(str(k) for k in key)}: "
                  f"{c / b:.2f}x baseline (overhead-dominated run — "
                  "informational)")
            continue
        compared += 1
        verdict = "ok"
        if c < tolerance * b:
            verdict = "REGRESSED"
            failures.append(
                f"  [{label}] {'/'.join(str(k) for k in key)}: "
                f"{c:,.0f} node-cycles/s vs baseline {b:,.0f} "
                f"(ratio {c / b:.2f} < tolerance {tolerance})")
        print(f"check_bench_regression: [{label}] "
              f"{'/'.join(str(k) for k in key)}: "
              f"{c / b:.2f}x baseline ({verdict})")
    # retrace counts are informational here (quick vs full sweeps warm
    # different caches); the hard gate is tools/lint/retrace_guard.py
    for key, crow in sorted(cur_rows.items()):
        brow = base_rows.get(key)
        rb = (brow or {}).get("retraces")
        rc = crow.get("retraces")
        if rb is not None and rc is not None and rc > rb:
            print(f"check_bench_regression: [{label}] "
                  f"{'/'.join(str(k) for k in key)}: retraces {rb} -> {rc} "
                  "(informational — see tools/lint/retrace_guard.py)")
    skipped = len(cur_rows) - compared - small
    if skipped:
        print(f"check_bench_regression: [{label}] {skipped} row(s) without "
              "a baseline counterpart (sweep mismatch) — informational only")

    if compared == 0:
        print(f"check_bench_regression: [{label}] WARNING — no comparable "
              "rows between baseline and current run")
    else:
        print(f"check_bench_regression: [{label}] {compared} rows compared")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", nargs=2, action="append", default=[],
                    metavar=("BASELINE", "CURRENT"),
                    help="baseline/current JSON pair; may repeat")
    ap.add_argument("--baseline", help="legacy single-pair spelling")
    ap.add_argument("--current", default="BENCH_population_scaling.json")
    ap.add_argument("--tolerance", type=float, default=0.4,
                    help="fail when current rate < tolerance * baseline")
    args = ap.parse_args()

    pairs = [(Path(b), Path(c)) for b, c in args.pair]
    if args.baseline:
        pairs.append((Path(args.baseline), Path(args.current)))
    if not pairs:
        ap.error("need --pair (or --baseline/--current)")

    failures: list = []
    for base_fp, cur_fp in pairs:
        check_pair(base_fp, cur_fp, args.tolerance, failures)

    if failures:
        print("check_bench_regression: PERF REGRESSION DETECTED:")
        for f in failures:
            print(f)
        return 1
    print("check_bench_regression: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
