#!/usr/bin/env python
"""Summarize an exported Chrome trace (Telemetry.export_chrome_trace).

Standalone — reads only the JSON file (no engine imports, no jax), so a
trace exported on one machine can be summarized anywhere:

    PYTHONPATH=src python tools/trace_report.py results/trace.json

Prints the per-phase wall-clock table (time, %, span counts), the
compile spans (``fun_name``, seconds, the span they ran under), and the
per-cycle stream summary
(messages + wire bytes per cycle, message-economy balance check) that
``otherData.streams`` carries. The same numbers an armed run prints live
via ``Telemetry.phase_report()`` — this is the offline twin for committed
trace files. View the trace itself at https://ui.perfetto.dev (open the
JSON file directly).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path


def summarize(payload: dict) -> str:
    events = payload.get("traceEvents", [])
    other = payload.get("otherData", {})
    lines = []

    label = other.get("label") or "(unlabeled)"
    spans = [e for e in events if e.get("ph") == "X"]
    compiles = [e for e in spans if e["name"] == "compile"]
    lines.append(f"trace: {label} — {len(spans)} spans, "
                 f"{other.get('compile_total', 0)} jit compiles")

    # per-phase table (complete events carry ts/dur in microseconds)
    if spans:
        t0 = min(e["ts"] for e in spans)
        t1 = max(e["ts"] + e["dur"] for e in spans)
        wall = (t1 - t0) / 1e6
        per: dict = {}
        for e in spans:
            secs, cnt = per.get(e["name"], (0.0, 0))
            per[e["name"]] = (secs + e["dur"] / 1e6, cnt + 1)
        lines.append(f"phases ({wall:.3f}s spanned wall clock):")
        for name, (secs, cnt) in sorted(per.items(),
                                        key=lambda kv: -kv[1][0]):
            pct = 100.0 * secs / wall if wall > 0 else 0.0
            lines.append(f"  {name:<16} {secs:>9.3f}s {pct:>5.1f}%  "
                         f"x{cnt}")
    if compiles:
        names = {e.get("args", {}).get("id"): e["name"] for e in spans}
        lines.append(f"compile spans: {len(compiles)}")
        for e in compiles:
            a = e.get("args", {})
            lines.append(f"  {e['ts'] / 1e6:>9.3f}s  {a.get('fun_name', '?')}"
                         f" {e['dur'] / 1e3:.1f}ms under "
                         f"{names.get(a.get('parent'), '-')}")

    # stream summary + the continuously-emitted balance invariant
    streams = other.get("streams", {})
    sent = streams.get("sent", [])
    if sent:
        cycles = len(sent)
        delivered = streams.get("delivered", [])
        wire = streams.get("wire_bytes", [])
        in_flight = streams.get("in_flight", [])
        lines.append(
            f"streams: {cycles} cycles, "
            f"{sum(sent):,} sent ({sum(sent) / cycles:,.0f}/cycle), "
            f"{sum(delivered):,} delivered, "
            f"{sum(wire) / cycles:,.0f} wire B/cycle")
        balance = 0
        ok = True
        for c in range(cycles):
            balance += (sent[c] - delivered[c] - streams["lost"][c]
                        - streams["overflow"][c])
            ok = ok and balance == in_flight[c] and in_flight[c] >= 0
        lines.append(
            f"message economy: in_flight ends at {in_flight[-1]:,}; "
            f"balance invariant {'OK' if ok else 'VIOLATED'}")
        if not ok:
            lines.append("  ERROR: cumulative sent-delivered-lost-overflow "
                         "disagrees with the in_flight stream")
    ef = streams.get("ef_residual_rms", [])
    if ef and any(ef):
        lines.append(f"ef_residual_rms: {ef[0]:.3e} -> {ef[-1]:.3e} "
                     f"over {len(ef)} eval points")

    for name, h in sorted(other.get("histograms", {}).items()):
        if h.get("count"):
            lines.append(
                f"hist {name}: n={h['count']} "
                f"p50={h['p50_s'] * 1e3:.3f}ms p90={h['p90_s'] * 1e3:.3f}ms "
                f"p99={h['p99_s'] * 1e3:.3f}ms "
                f"p999={h['p999_s'] * 1e3:.3f}ms "
                f"({len(h.get('bucket_counts', []))} occupied buckets)")

    for run in other.get("annotations", {}).get("runs", []):
        lines.append("run: " + ", ".join(
            f"{k}={v}" for k, v in run.items()))
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="Chrome trace JSON exported by "
                                  "Telemetry.export_chrome_trace")
    args = ap.parse_args(argv)
    fp = Path(args.trace)
    if not fp.exists():
        print(f"trace_report: no such file: {fp}", file=sys.stderr)
        return 2
    payload = json.loads(fp.read_text())
    if "traceEvents" not in payload:
        print(f"trace_report: {fp} is not a Chrome trace "
              f"(no traceEvents key)", file=sys.stderr)
        return 2
    print(summarize(payload))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
