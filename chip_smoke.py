#!/usr/bin/env python3
"""Smoke run of the gossip-learning stack on a TPU, through its entry points.

    python chip_smoke.py              # one chip: protocol, parity, serving
    python chip_smoke.py --chips 4    # a 2x2 host: the node-mesh runs only

The deployment is the paper's own: the SPAMBASE geometry (d=57, Pegasos,
the "mu" CREATEMODEL variant, a 10-model cache) under the ``extreme``
failure scenario (drop 0.5, delay U[1, 10] cycles, 90% online churn), at
N = 10^6 nodes. Data is the SPAMBASE surrogate of ``repro.data.synthetic``,
made from a seed.

One chip, three phases:

* **protocol** — ``run_simulation(engine="sharded")`` at N=10^6 with the
  fused Pallas receive kernel (``use_pallas=True, interpret=False``), once
  with f32 wire payloads and once with ``int8_sr`` (which adds the Pallas
  send kernel and its in-kernel threefry). Checks: the message economy
  balances exactly, the error curve is finite and falls, and the chunk
  programs that ran are the Pallas ones (never interpret mode).
* **parity** — the reference engine against the sharded engine with the
  Pallas kernels at N=2048 and the same seed: message totals must be equal,
  error curves within ``CURVE_TOL``; whether they are bitwise equal is
  printed, not assumed.
* **serving** — ``GossipServer(use_kernel=True)`` answers query batches on
  the live N=10^6 protocol's snapshots; every answer must equal the jnp
  ``serving.serve_voted`` answer on the same snapshot.

``--chips 4`` runs only the node mesh: the sharded engine over the four
devices at N=10^6 (compared with the same run on one chip) and at
N=4x10^6, whose state does not fit one chip, checking that the state is
spread over all four devices.

Times printed are smoke timings (compilation included), not benchmark
results. The last line of standard output is one JSON object naming the
device; the script exits non-zero, printing no such line, when a check
fails or JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_NODES = 1_000_000          # the README's headline population
N_MESH = 4_000_000           # four chips' worth of f32 protocol state
N_PARITY = 2048
N_TEST = 1000
CYCLES, EVAL_EVERY = 20, 5
SERVE_BATCH = 4096
# error-curve tolerance of the cross-engine comparisons: the one the CPU
# suite applies to non-bitwise engine pairs (assert_curves_close)
CURVE_TOL = 0.02


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def deployment(n: int, wire=None):
    from repro.configs.gossip_linear import SPAMBASE, with_failure_scenario
    cfg = dataclasses.replace(SPAMBASE, name=f"spambase-{n}", n_nodes=n,
                              n_test=N_TEST, wire_dtype=wire)
    return with_failure_scenario(cfg, "extreme")


def dataset(n: int, seed: int = 0):
    """The SPAMBASE surrogate (repro.data.synthetic's settings) at n nodes:
    (X, y, X_test, y_test)."""
    import numpy as np

    from repro.configs.gossip_linear import SPAMBASE
    from repro.data.synthetic import make_linear_dataset
    X, y = make_linear_dataset(np.random.default_rng(seed), n + N_TEST,
                               SPAMBASE.dim, noise=0.10, separation=2.5,
                               class_ratio=SPAMBASE.class_ratio)
    return X[:n], y[:n], X[n:], y[n:]


def economy(res) -> str:
    return (f"sent={res.sent_total} delivered={res.delivered_total} "
            f"lost={res.lost_total} overflow={res.overflow_total} "
            f"in_flight={res.in_flight}")


def run_protocol(n, data, wire=None, *, interpret=False, **kw):
    """One sharded-engine run with the Pallas kernels; checks the economy
    and the error curve, returns (SimResult, seconds)."""
    from repro.core.simulation import run_simulation
    X, y, Xt, yt = data
    t0 = time.perf_counter()
    res = run_simulation(deployment(n, wire), X, y, Xt, yt, cycles=CYCLES,
                         eval_every=EVAL_EVERY, seed=0, engine="sharded",
                         use_pallas=True, interpret=interpret, **kw)
    dt = time.perf_counter() - t0
    tag = f"N={n} wire={wire or 'f32'}"
    log(f"protocol {tag}: err_fresh={res.err_fresh} "
        f"err_voted={res.err_voted} {economy(res)} "
        f"chunk_modes={res.compaction['chunk_modes']} "
        f"[smoke timing, not a benchmark: {dt:.1f} s incl. compile]")
    require(res.sent_total == res.delivered_total + res.lost_total
            + res.overflow_total + res.in_flight,
            f"{tag}: message economy does not balance ({economy(res)})")
    require(res.sent_total > 0 and res.delivered_total > 0,
            f"{tag}: no message was delivered")
    curve = res.err_fresh + res.err_voted
    require(all(math.isfinite(e) for e in curve),
            f"{tag}: error curve is not finite")
    require(res.err_fresh[-1] < res.err_fresh[0],
            f"{tag}: error curve does not fall ({res.err_fresh})")
    return res, dt


def kernel_paths(*, interpret=False):
    """Chunk-program labels that compiled, split by kernel path."""
    from repro.core.sharded_engine import retrace_counts
    ran = [k for k, v in retrace_counts().items()
           if v and k.startswith("sharded_engine.chunk_fn")]
    pallas = [k for k in ran if "/pallas" in k]
    require(pallas, f"no Pallas chunk program compiled ({ran})")
    if not interpret:
        require(not [k for k in ran if "/interp" in k],
                f"a chunk program ran in interpret mode ({ran})")
    return ran


def compare(a, b, what: str) -> bool:
    """Totals must be equal; curves within CURVE_TOL. Returns bitwise."""
    totals = lambda r: (r.sent_total, r.delivered_total, r.lost_total,
                        r.overflow_total)
    require(totals(a) == totals(b),
            f"{what}: message totals differ ({economy(a)} vs {economy(b)})")
    diff = max(abs(p - q) for p, q in zip(a.err_fresh + a.err_voted,
                                          b.err_fresh + b.err_voted))
    bitwise = (a.err_fresh == b.err_fresh and a.err_voted == b.err_voted)
    log(f"{what}: curves bitwise equal={bitwise}, max |diff|={diff!r} "
        f"(tolerance {CURVE_TOL}), message totals equal=True")
    require(diff <= CURVE_TOL, f"{what}: error curves differ by {diff}")
    return bitwise


def protocol_and_serving(*, n=N_NODES, batch=SERVE_BATCH, interpret=False):
    """Phases 1 and 3: the f32 run carries the server, then int8_sr."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import serving
    from repro.launch.gossip_serve import GossipServer

    data = dataset(n)
    srv = GossipServer(batch_size=batch, use_kernel=True,
                       interpret=interpret, compare_fresh=False)
    test_x = data[2]
    queries = np.asarray(test_x[np.arange(batch) % len(test_x)], np.float32)
    agree = []

    def serve_hook(cycle, snap):
        srv.serve_hook(cycle, snap)
        srv.submit(queries)
        b = srv.batches[-1]
        want = serving.serve_voted(snap.w, snap.count, jnp.asarray(queries),
                                   jnp.asarray(b.assign))
        agree.append(bool(np.array_equal(np.asarray(want), b.preds)))
        log(f"serving cycle={cycle}: {b.size} queries, kernel == jnp "
            f"answers: {agree[-1]} [smoke timing, not a benchmark: "
            f"{b.latency_s * 1e3:.2f} ms per batch]")

    run_protocol(n, data, serve_hook=serve_hook, interpret=interpret)
    require(agree and all(agree),
            "serving: the kernel's answers differ from serve_voted's")
    run_protocol(n, data, "int8_sr", interpret=interpret)
    ran = kernel_paths(interpret=interpret)
    require([k for k in ran if "int8_sr" in k and "/sendk" in k],
            f"the int8_sr run did not use the Pallas send kernel ({ran})")


def parity(*, n=N_PARITY, interpret=False):
    """Phase 2: reference engine vs the sharded engine's Pallas path."""
    from repro.core.simulation import run_simulation
    data = dataset(n, seed=1)
    for wire in (None, "int8_sr"):
        kw = dict(cycles=CYCLES, eval_every=EVAL_EVERY, seed=3)
        ref = run_simulation(deployment(n, wire), *data, **kw)
        pal = run_simulation(deployment(n, wire), *data, engine="sharded",
                             use_pallas=True, interpret=interpret, **kw)
        compare(ref, pal, f"parity N={n} wire={wire or 'f32'} reference vs "
                          f"sharded+Pallas")


def mesh_runs(*, n_one=N_NODES, n_big=N_MESH, interpret=False):
    """The four-chip option: the node mesh at n_one (against one chip) and
    at n_big, whose state does not fit one chip."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    mesh = Mesh(np.asarray(devices), ("nodes",))
    spread = {}

    def holder(tag):
        def hook(cycle, snap):
            spread[tag] = len({s.device for s in snap.w.addressable_shards})
        return hook

    data = dataset(n_one)
    one, _ = run_protocol(n_one, data, interpret=interpret)
    on_mesh, _ = run_protocol(n_one, data, mesh=mesh, node_axis="nodes",
                              interpret=interpret,
                              serve_hook=holder("one"))
    compare(one, on_mesh, f"mesh N={n_one}: one chip vs {len(devices)}-device "
                          f"node mesh")
    del data
    big, _ = run_protocol(n_big, dataset(n_big), mesh=mesh,
                          node_axis="nodes", interpret=interpret,
                          serve_hook=holder("big"))
    kernel_paths(interpret=interpret)
    log(f"mesh: cache state on {spread} distinct devices")
    require(all(v == len(devices) for v in spread.values()),
            f"mesh state is not spread over {len(devices)} devices: {spread}")
    return big


def peak_memory(devices) -> list:
    peaks = []
    for dev in devices:
        stats = dev.memory_stats()
        peaks.append(stats["peak_bytes_in_use"])
        log(f"device {dev.id}: peak_bytes_in_use={peaks[-1]} "
            f"bytes_limit={stats['bytes_limit']}")
    return peaks


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): protocol, parity and serving on one "
                         "chip; 4: the node-mesh runs only")
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found {platform!r}")
    require(len(devices) >= args.chips,
            f"--chips {args.chips} needs {args.chips} devices, found "
            f"{len(devices)}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}; compile "
        f"cache: {cache_dir}")

    t0 = time.perf_counter()
    if args.chips == 4:
        big = mesh_runs()
        # each device must have held its quarter of the payload buffer:
        # a mesh that left the state on one device would not have
        share = big.buf_payload_bytes // len(devices)
        peaks = peak_memory(devices)
        require(min(peaks) >= share,
                f"a device peaked below its {share}-byte share of the "
                f"payload buffer: {peaks}")
    else:
        protocol_and_serving()
        peak_memory(devices[:1])
        parity()
    log(f"chip_smoke: all checks passed [smoke timing, not a benchmark: "
        f"{time.perf_counter() - t0:.1f} s total]")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
