"""Benchmark harness — one module per paper table/figure + system benches.

Usage:  PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig1,table1]
Prints ``name,...`` CSV lines and writes results/benchmarks/*.csv.
"""
from __future__ import annotations

import argparse
import time

SUITES = ["table1", "fig1", "fig2", "fig3", "theory", "kernels",
          "gossip_vs_allreduce", "roofline", "population_scaling",
          "wire_quantization", "robustness", "serving"]


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true",
                   help="reduced cycles/iters (CI-sized)")
    p.add_argument("--only", default="",
                   help="comma-separated subset of: " + ",".join(SUITES))
    args = p.parse_args()
    only = set(args.only.split(",")) if args.only else set(SUITES)

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    t0 = time.time()
    if "table1" in only:
        from benchmarks import paper_table1
        paper_table1.run(args.quick)
    if "fig1" in only:
        from benchmarks import paper_fig1
        paper_fig1.run(args.quick)
    if "fig2" in only:
        from benchmarks import paper_fig2
        paper_fig2.run(args.quick)
    if "fig3" in only:
        from benchmarks import paper_fig3
        paper_fig3.run(args.quick)
    if "theory" in only:
        from benchmarks import paper_theory
        paper_theory.run(args.quick)
    if "kernels" in only:
        from benchmarks import kernel_bench
        kernel_bench.run(args.quick)
    if "gossip_vs_allreduce" in only:
        from benchmarks import gossip_vs_allreduce
        gossip_vs_allreduce.run(args.quick)
    if "roofline" in only:
        from benchmarks import roofline_table
        roofline_table.run(args.quick)
    if "population_scaling" in only:
        from benchmarks import population_scaling
        population_scaling.run(args.quick)
    if "wire_quantization" in only:
        from benchmarks import wire_quantization
        wire_quantization.run(args.quick)
    if "robustness" in only:
        from benchmarks import robustness
        robustness.run(args.quick)
    if "serving" in only:
        from benchmarks import serving
        serving.run(args.quick)
    print(f"benchmarks done in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
