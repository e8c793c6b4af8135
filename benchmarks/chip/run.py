#!/usr/bin/env python3
"""Chip benchmark of the gossip-learning stack: one run of one cell.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<mix>.json``); its
limits are in ``limits/<cell>.json`` and each per-layer metric is read by
``metrics/<metric>.py``. Nothing here names a cell, a mix or a metric.

A run makes its data from the seed, runs one warm-up simulation of the
cell's shapes (set-up), then runs whole simulations back to back through
``run_simulation(engine="sharded", use_pallas=True)`` until ``--seconds``
have passed, serving open-loop queries at every snapshot where the mix has
them. One simulation of the window, drawn from the seed, is then compared
with the plain reference. With ``--trace 1`` the first simulation of the
window is traced and the per-layer metrics are read from that trace.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared with its limit.
Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

_T_START = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import gzip  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
if __name__ == "__main__":
    # run as a script: the checkout's root, not this directory, leads the path
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from benchmarks.chip import check, traffic, trace_reduce  # noqa: E402
from benchmarks.chip.reference import Protocol  # noqa: E402

ANNOTATIONS = ("simulation_setup", "route", "serve_batch", "snapshot_wait")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------


def load_cell(name: str, root: Path = ROOT) -> dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", ())
                 or ("workloads" not in m and m["moves"] in e2e_names)]
    here = root / "benchmarks" / "chip"
    return dict(
        name=name, chips=cell["chips"],
        config=json.loads((root / cfg_entry["file"]).read_text()),
        traffic=json.loads((here / "traffic" / f"{cell['traffic']}.json")
                           .read_text()),
        limits=json.loads((here / "limits" / f"{name}.json").read_text()),
        end_to_end=e2e, per_layer=per_layer,
        peaks=json.loads((here / "peaks.json").read_text()))


def peaks_for(peaks: dict, device_kind: str) -> dict:
    """The peak rates of a device, by ``device_kind``; no default."""
    table = peaks["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def protocol(cell: dict) -> Protocol:
    c, t = cell["config"], cell["traffic"]
    s = t["scenario"]
    return Protocol(n=c["n_nodes"], d=c["dim"], cache_size=c["cache_size"],
                    k_rounds=c["k_rounds"], lam=c["lam"],
                    drop=s["drop_prob"], delay_max=s["delay_max_cycles"],
                    online_fraction=s["online_fraction"],
                    cycles=t["cycles"], eval_every=t["eval_every"],
                    eval_nodes=c["eval_nodes"])


def load_reader(metric: str, here: Path = HERE):
    """``metrics/<metric>.py``'s ``read(ctx)``."""
    path = here / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the chip
# ---------------------------------------------------------------------------


def tpu_devices(chips: int):
    """The first ``chips`` TPU devices; exits when JAX finds fewer."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"benchmark needs a TPU; JAX found "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), for every program."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class CompileCounter:
    """Backend compilations since :meth:`reset` (cache loads included)."""

    def __init__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


@contextlib.contextmanager
def annotate(name: str, on: bool):
    if on:
        import jax
        with jax.profiler.TraceAnnotation(name):
            yield
    else:
        yield


def instrument_router(spans: dict):
    """Time the host control plane (routing and table packing) and mark it
    in the trace. Returns a function that undoes it."""
    import jax
    from repro.core import sharded_engine as se

    saved = {}

    def wrap(owner, attr, label):
        fn = getattr(owner, attr)
        saved[(owner, attr)] = fn

        def timed(*a, **k):
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation(label):
                out = fn(*a, **k)
            spans[label] = spans.get(label, 0.0) + time.perf_counter() - t
            return out
        setattr(owner, attr, timed)

    wrap(se._HostRouter, "route_chunk", "route")
    for name in ("dense_table", "pack_compact_rounds", "pack_compact_all"):
        wrap(se, name, "route")
    wrap(se, "sim_setup", "simulation_setup")

    def undo():
        for (owner, attr), fn in saved.items():
            setattr(owner, attr, fn)
    return undo


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------


def build_program(cell: dict, devs, seed: int, interpret: bool):
    """The data, and ``simulate(sim_seed, hook)`` through the entry point."""
    import jax
    from repro.configs.gossip_linear import GossipLinearConfig
    from repro.core.simulation import run_simulation

    c, t = cell["config"], cell["traffic"]
    data = traffic.make_dataset(int(traffic.derived_seeds(seed, 1, 0)[0]),
                                c["n_nodes"], c["n_test"], c["dim"],
                                **c["data"])
    gcfg = GossipLinearConfig(
        name=c["name"], dim=c["dim"], n_nodes=c["n_nodes"],
        n_test=c["n_test"], class_ratio=tuple(c["data"]["class_ratio"]),
        learner=c["learner"], lam=c["lam"], cache_size=c["cache_size"],
        variant=c["variant"], wire_dtype=None, **t["scenario"])
    kw = {}
    if c.get("mesh"):
        from jax.sharding import Mesh
        axis = c["mesh"]["axis"]
        kw = dict(mesh=Mesh(np.asarray(devs), (axis,)), node_axis=axis)

    def simulate(sim_seed: int, hook):
        return run_simulation(
            gcfg, *data, cycles=t["cycles"], eval_every=t["eval_every"],
            seed=int(sim_seed), eval_nodes=c["eval_nodes"],
            k_rounds=c["k_rounds"], sampler="uniform", engine="sharded",
            use_pallas=True, interpret=interpret, serve_hook=hook, **kw)
    return data, simulate


def row_gather():
    """``capture(nodes, arrays)``: the rows ``nodes`` of node-major arrays,
    taken on each device from its own shard of the node axis by dynamic
    slices (no relayout, no collective, nothing waited for); ``assemble``
    turns what it returns into host arrays once the window has closed."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def take(idx, a):
        def body(k, out):
            row = lax.dynamic_index_in_dim(a, idx[k], 0, keepdims=False)
            return lax.dynamic_update_index_in_dim(out, row, k, 0)
        return lax.fori_loop(0, idx.shape[0], body,
                             jnp.zeros(idx.shape + a.shape[1:], a.dtype))

    def capture(nodes, arrays):
        out = []
        for a in arrays:
            parts = []
            for sh in a.addressable_shards:
                rows = sh.index[0]
                lo = rows.start or 0
                hi = a.shape[0] if rows.stop is None else rows.stop
                mine = (nodes >= lo) & (nodes < hi)
                idx = jax.device_put(np.where(mine, nodes - lo, 0)
                                     .astype(np.int32), sh.device)
                parts.append((mine, take(idx, sh.data)))
            out.append(parts)
        return out
    return capture


def assemble(captured):
    """Host arrays from :func:`row_gather`'s per-shard parts."""
    out = []
    for parts in captured:
        first = np.asarray(parts[0][1])
        a = np.zeros_like(first)
        for mine, dev in parts:
            a[mine] = np.asarray(dev)[mine]
        out.append(a)
    return out


class Window:
    """Runs simulations back to back and serves the queries due at every
    snapshot. Keeps, per simulation, what the check compares."""

    def __init__(self, cell, simulate, data, queries, nodes, *,
                 interpret: bool, annotated: bool,
                 clock=time.perf_counter):
        self.cell, self.simulate, self.data = cell, simulate, data
        self.queries, self.nodes = queries, nodes
        self.interpret, self.annotated, self.clock = interpret, annotated, \
            clock
        self.cycles = cell["traffic"]["cycles"]
        self.take = row_gather()
        self.sims = []
        self.t_open = None
        self.next_q = 0
        self.answered_s = (np.full(queries.arrival_s.size, np.nan)
                           if queries is not None else None)

    def server(self, sim_seed: int):
        from repro.launch.gossip_serve import GossipServer
        q = self.queries
        return GossipServer(batch_size=q.batch, policy=q.assign,
                            seed=int(sim_seed), use_kernel=True,
                            interpret=self.interpret, compare_fresh=False)

    def serve(self, rec, srv, cycle, rows_idx):
        """Answer the given queries now, in full batches and a padded tail."""
        q, X_test = self.queries, self.data[2]
        for a in range(0, rows_idx.size, q.batch):
            part = rows_idx[a:a + q.batch]
            offset = rec["served"]
            with annotate("serve_batch", self.annotated):
                srv.submit(X_test[q.rows[part]])
                if part.size < q.batch:
                    srv.flush()
            done = self.clock()
            if self.answered_s is not None and self.t_open is not None:
                self.answered_s[part] = done - self.t_open
            b = srv.batches[-1]
            rec["batches"].append(dict(cycle=cycle, q=part, preds=b.preds,
                                       offset=offset, latency_s=b.latency_s))
            rec["served"] += part.size

    def run_one(self, sim_seed: int, *, warm: bool = False):
        rec = dict(seed=int(sim_seed), batches=[], served=0, sample=None)
        srv = self.server(sim_seed) if self.queries is not None else None

        def hook(cycle, snap):
            if srv is not None:
                with annotate("snapshot_wait", self.annotated):
                    srv.serve_hook(cycle, snap)
                if warm:          # compile the batch shape: a padded batch
                    self.serve(rec, srv, cycle, np.arange(1))
                else:
                    hi = self.queries.due(self.clock() - self.t_open)
                    self.serve(rec, srv, cycle, np.arange(self.next_q, hi))
                    self.next_q = hi
            if cycle == self.cycles:
                rec["sample"] = self.take(self.nodes,
                                          (snap.w, snap.t, snap.count))

        rec["result"] = self.simulate(sim_seed, hook)
        rec["server"] = srv
        return rec

    def run(self, seconds: float, sim_seeds, on_first=None):
        """Whole simulations until ``seconds`` have passed; the queries
        still due after the last one are answered on its final snapshot."""
        self.t_open = self.clock()
        for i, s in enumerate(sim_seeds):
            ctx = on_first() if (i == 0 and on_first) else \
                contextlib.nullcontext()
            if self.sims:
                # only the last snapshot may answer queries after the
                # window; an older one would keep its population on device
                self.sims[-1]["server"] = None
            with ctx:
                self.sims.append(self.run_one(s))
            if self.clock() - self.t_open >= seconds:
                break
        self.t_close = self.clock()
        self.node_cycles = (len(self.sims) * self.cycles
                            * self.cell["config"]["n_nodes"])
        if self.queries is not None:
            last = self.sims[-1]
            rest = np.arange(self.next_q, self.queries.arrival_s.size)
            self.serve(last, last["server"], self.cycles, rest)
            self.next_q = self.queries.arrival_s.size
        return self.t_close - self.t_open


def program_outcome(rec: dict) -> dict:
    r = rec["result"]
    w, t, cnt = assemble(rec["sample"])
    return dict(
        economy=[r.sent_total, r.delivered_total, r.lost_total,
                 r.overflow_total, r.in_flight],
        delivered_per_cycle=list(r.delivered_per_cycle),
        err=list(r.err_fresh) + list(r.err_voted),
        sample=dict(w=w, t=t, count=cnt),
        answers=[b["preds"] for b in rec["batches"]])


def reference_outcome(cell, data, rec, nodes, queries, precision="f32"):
    batches = [(b["cycle"], queries.rows[b["q"]], rec["seed"], b["offset"],
                queries.batch) for b in rec["batches"]] if queries else []
    return check.outcome_from_reference(protocol(cell), *data, rec["seed"],
                                        nodes, batches, precision=precision)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             devs=None, interpret: bool = False, t_start: float = None,
             trace_out: str = None, control: bool = False) -> dict:
    """Set-up, window, check; returns the result line as a dict.

    ``control`` also reads the control: the reference computed in bfloat16
    put in the program's place, compared in the same way (``"control"``)."""
    import jax

    t_start = _T_START if t_start is None else t_start
    devs = tpu_devices(cell["chips"]) if devs is None else devs
    kind = devs[0].device_kind
    peaks = peaks_for(cell["peaks"], kind)
    compiles = CompileCounter()
    c, t = cell["config"], cell["traffic"]
    data, simulate = build_program(cell, devs, seed, interpret)
    qspec = t.get("queries")
    queries = (traffic.QueryStream.from_mix(qspec, seed, seconds, c["n_test"])
               if qspec else None)
    check_seed = int(traffic.derived_seeds(seed, 1, 3)[0])
    nodes = check.sample_nodes(c["n_nodes"], check_seed)
    spans: dict = {}
    undo = instrument_router(spans) if trace else (lambda: None)
    win = Window(cell, simulate, data, queries, nodes, interpret=interpret,
                 annotated=trace)

    # set-up: one warm-up simulation of the cell's shapes
    warm_seed = int(traffic.derived_seeds(seed, 1, 4)[0])
    win.run_one(warm_seed, warm=True)
    jax.block_until_ready(jax.live_arrays())
    setup_s = time.time() - t_start
    log(f"set-up {setup_s:.3f} s; window opens")

    tracedir = tempfile.TemporaryDirectory() if trace else None

    traced_spans: dict = {}

    @contextlib.contextmanager
    def traced_first():
        spans.clear()
        jax.profiler.start_trace(tracedir.name)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                yield
                jax.block_until_ready(jax.live_arrays())
        finally:
            jax.profiler.stop_trace()
            traced_spans.update(spans)

    compiles.count = 0
    sim_seeds = traffic.derived_seeds(seed, 4096, 1)
    window_s = win.run(seconds, sim_seeds,
                       on_first=traced_first if trace else None)
    window_compiles = compiles.count
    undo()
    sims = win.sims
    log(f"window {window_s:.3f} s, {len(sims)} simulations, compiles in "
        f"window: {window_compiles}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)

    # the check, once the window has closed and the peak has been read
    pick = int(np.random.default_rng(check_seed).integers(len(sims)))
    rec = sims[pick]
    prog = program_outcome(rec)
    for s in sims:
        s["server"] = None
    t0 = time.perf_counter()
    ref = reference_outcome(cell, data, rec, nodes, queries)
    numbers = check.compare(prog, ref, serving=queries is not None)
    log(f"reference {time.perf_counter() - t0:.3f} s for simulation {pick}; "
        f"eval pairs it leaves open: {sum(b - a for a, b in ref['wrong'])} "
        f"of {ref['eval_pairs'] * len(ref['wrong'])}")
    correct = check.verdict(numbers, cell["limits"])
    if control:
        low = reference_outcome(cell, data, rec, nodes, queries, "bf16")
        control_numbers = check.compare(low, ref, serving=queries is not None)

    failed = 0
    attempted = len(sims)
    lat_s = None
    if queries is not None:
        attempted += queries.arrival_s.size
        failed += int(np.isnan(win.answered_s).sum())
        lat_s = win.answered_s - queries.arrival_s

    device = dict(platform=devs[0].platform, kind=kind, count=len(devs),
                  memory_peak_bytes=int(peak))
    out = dict(correct=bool(correct), attempted=int(attempted),
               failed=int(failed))
    if not trace:
        values = dict(
            node_cycles_per_s=win.node_cycles / window_s,
            peak_hbm_gb=peak / 1e9, setup_s=setup_s)
        if lat_s is not None:
            # a query never answered misses any limit
            values["query_p95_ms"] = float(np.percentile(
                np.where(np.isnan(lat_s), np.inf, lat_s), 95)) * 1e3
        out["metrics"] = {m["name"]: dict(value=values[m["name"]],
                                          unit=m["unit"])
                          for m in cell["end_to_end"]}
        out["device"] = device
    else:
        p = glob.glob(os.path.join(tracedir.name, "**", "*.xplane.pb"),
                      recursive=True)
        tr = trace_reduce.load_xplane(p[0], ANNOTATIONS)
        tracedir.cleanup()
        if trace_out:
            with (gzip.open if trace_out.endswith(".gz") else open)(
                    trace_out, "wt") as f:
                json.dump(tr, f)
        first = sims[0]
        r = first["result"]
        ctx = dict(
            trace=tr, window=tr["window"], peaks=peaks, spans=traced_spans,
            cycles=t["cycles"], n=c["n_nodes"], d=c["dim"],
            c=c["cache_size"], n_test=c["n_test"],
            eval_nodes=c["eval_nodes"], eval_points=len(r.cycles),
            sends=r.sent_total, deliveries=r.delivered_total,
            batches=first["batches"],
            batch=queries.batch if queries else None)
        metrics = {}
        for m in cell["per_layer"]:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = dict(value=float(v), unit=m["unit"])
        out["metrics"] = metrics
        w = tr["window"]
        busy = [trace_reduce.busy_ns(ops, w) for ops in tr["ops"].values()]
        device.update(busy_s=float(np.mean(busy)) / 1e9 if busy else 0.0,
                      window_s=(w[1] - w[0]) / 1e9)
        out["device"] = device
        out["breakdown"] = breakdown(tr)
    out["window_compiles"] = window_compiles
    if control:
        out["control"] = control_numbers
    out["checks"] = {k: dict(value=numbers.get(k), limit=lim)
                     for k, lim in cell["limits"].items()}
    return out


def breakdown(tr: dict) -> dict:
    """Top device operations and the longest idle stretches by host label,
    in seconds averaged over the devices."""
    w, devs = tr["window"], list(tr["ops"])
    ops: dict = {}
    gaps: dict = {}
    for dev in devs:
        for name, ns in trace_reduce.top_ops(tr["ops"][dev], w, 10):
            name = trace_reduce.short_name(name)
            ops[name] = ops.get(name, 0.0) + ns / 1e9 / len(devs)
        for name, ns in trace_reduce.gaps_by_label(tr["ops"][dev], w,
                                                   tr["host"]).items():
            gaps[name] = gaps.get(name, 0.0) + ns / 1e9 / len(devs)
    top = lambda d: [[k, v] for k, v in sorted(d.items(),
                                                key=lambda kv: -kv[1])[:10]]
    return dict(device_ops=top(ops), idle_gaps=top(gaps))


def print_result(out: dict) -> None:
    for k, v in out["checks"].items():
        log(f"check {k} = {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-out", default=None,
                    help="also write the reduced trace to this JSON file")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    devs = tpu_devices(cell["chips"])
    compile_cache()
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   devs=devs, trace_out=args.trace_out)
    print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
