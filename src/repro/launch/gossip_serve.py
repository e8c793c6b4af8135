"""Gossip-ensemble request loop: batch accumulation over live snapshots.

The gossip rework of the ``DecodeServer`` seed shape (``launch/serve.py``):
instead of a KV cache fed by prefill, the server holds the latest
:class:`repro.core.serving.QuerySnapshot` of a *running* protocol and
answers batches of feature-vector queries with the cache majority vote
(Algorithm 4 / Eq. 8 as a service). Wire it to an engine by passing
``server.serve_hook`` as the ``serve_hook=`` of
``repro.core.simulation.run_simulation`` — the hook refreshes the snapshot
at every eval point while the protocol keeps gossiping underneath.

Request path: ``submit()`` accumulates queries; every full ``batch_size``
batch is answered immediately (node assignment by the configured policy,
then ``serve_voted`` / ``serve_voted_kernel``, optionally ``serve_fresh``
alongside for the fresh-vs-voted comparison); ``flush()`` pads the tail to
the batch shape — one compiled signature per (N, batch) — and slices the
answers back. Per-batch latency is the ``serve_device`` span around the
predict dispatch with the answer blocked to completion, recorded into the
shared fixed-bucket :class:`repro.core.telemetry.LatencyHistogram`;
``stats()`` aggregates queries/s and p50/p90/p99/p999 batch latency from
it (the same histogram BENCH_serving.json dumps bucket-wise). Every call
records its span (``snapshot_adopt``, ``submit``, ``flush``,
``serve_batch``, ``serve_device``: ``repro.core.telemetry.span``), inside
the engine's run record when a hook calls it; ``telemetry=`` shares the
histogram into an armed :class:`repro.core.telemetry.Telemetry`.

    PYTHONPATH=src python examples/serve_batched.py    # end-to-end driver
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import serving
from repro.core import telemetry as telemetry_mod
from repro.core.telemetry import LatencyHistogram


@dataclass
class ServedBatch:
    """One answered batch: which snapshot served it and how fast."""
    cycle: int                 # protocol cycle of the serving snapshot
    size: int                  # real queries (the tail batch is padded)
    latency_s: float           # dispatch -> answers materialized
    query_ids: np.ndarray      # (size,) submission order ids
    assign: np.ndarray         # (size,) serving node per query
    preds: np.ndarray          # (size,) ±1 voted answers
    preds_fresh: Optional[np.ndarray] = None   # (size,) PREDICT answers


@dataclass
class ServeStats:
    queries: int
    batches: int
    queries_per_sec: float
    p50_latency_s: float
    p99_latency_s: float
    serve_seconds: float
    # histogram-backed tail percentiles (same fixed buckets everywhere)
    p90_latency_s: float = 0.0
    p999_latency_s: float = 0.0
    latency_hist: Optional[dict] = None


@dataclass
class GossipServer:
    """Holds the live snapshot + compiled batched vote; serves query batches.

    ``policy``: node assignment for incoming queries
    (``serving.ASSIGN_POLICIES``). ``use_kernel`` answers with the fused
    Pallas ``voted_predict_batched`` path instead of the jnp einsum path —
    the two are bitwise-interchangeable; ``interpret`` runs that kernel in
    Pallas interpret mode (CPU tests), never implicitly. ``compare_fresh`` additionally
    answers every batch with the freshest-model PREDICT (outside the
    latency window) for the fresh-vs-voted accuracy comparison. For a
    fixed ``seed`` and submission order the served answers are
    reproducible bit for bit."""
    batch_size: int = 256
    policy: str = "uniform"
    seed: int = 0
    use_kernel: bool = False
    interpret: bool = False
    compare_fresh: bool = True
    telemetry: Optional[telemetry_mod.Telemetry] = None

    snapshot: Optional[serving.QuerySnapshot] = None
    snapshot_cycle: int = -1
    batches: List[ServedBatch] = field(default_factory=list)
    hist: LatencyHistogram = field(default_factory=LatencyHistogram)
    _pending_x: List[np.ndarray] = field(default_factory=list)
    _pending_ids: List[int] = field(default_factory=list)
    _next_id: int = 0
    _served: int = 0           # assignment-policy offset across batches

    def __post_init__(self):
        if self.telemetry is not None:
            # share the server's histogram so it rides in the trace export
            self.telemetry.histograms["serve_batch_latency"] = self.hist

    # ------------------------------------------------------------------ hook
    def serve_hook(self, cycle: int, snapshot: serving.QuerySnapshot):
        """The ``serve_hook`` for ``run_simulation``: adopt the fresh
        snapshot, blocking until the engine materialized EVERY leaf (the
        cache tensor dominates at large N) — so the batch latency below
        measures serving, not leftover simulation compute."""
        with telemetry_mod.span("snapshot_adopt", cycle=int(cycle)):
            jax.block_until_ready(snapshot)
            self.snapshot = snapshot
            self.snapshot_cycle = int(cycle)

    # --------------------------------------------------------------- queries
    def submit(self, X) -> None:
        """Accumulate queries (rows of X); answer every full batch."""
        X = np.asarray(X, np.float32)
        with telemetry_mod.span("submit", rows=len(X)):
            for row in X:
                self._pending_x.append(row)
                self._pending_ids.append(self._next_id)
                self._next_id += 1
                if len(self._pending_x) >= self.batch_size:
                    self._serve_pending()

    def flush(self) -> None:
        """Answer the partial tail batch (padded to the compiled shape)."""
        with telemetry_mod.span("flush", rows=len(self._pending_x)):
            if self._pending_x:
                self._serve_pending()

    def _serve_pending(self) -> None:
        if self.snapshot is None:
            raise RuntimeError("no snapshot yet — wire serve_hook into "
                               "run_simulation before submitting queries")
        k = min(len(self._pending_x), self.batch_size)
        with telemetry_mod.span("serve_batch", queries=k,
                                padded=self.batch_size - k,
                                batch=len(self.batches)):
            self._serve_batch(k)

    def _serve_batch(self, k: int) -> None:
        xb = np.stack(self._pending_x[:k])
        ids = np.asarray(self._pending_ids[:k])
        del self._pending_x[:k], self._pending_ids[:k]
        if k < self.batch_size:                  # tail: pad, serve, slice
            xb = np.concatenate(
                [xb, np.zeros((self.batch_size - k, xb.shape[1]),
                              np.float32)])

        snap = self.snapshot
        n_nodes = snap.count.shape[0]
        assign = serving.assign_queries(
            self.batch_size, n_nodes, policy=self.policy, seed=self.seed,
            offset=self._served)
        self._served += k
        xj = jnp.asarray(xb)
        aj = jnp.asarray(assign)

        with telemetry_mod.span("serve_device") as dev:
            if self.use_kernel:
                preds = serving.serve_voted_kernel(
                    snap.w, snap.count, xj, aj, interpret=self.interpret)
            else:
                preds = serving.serve_voted(snap.w, snap.count, xj, aj)
            preds.block_until_ready()
        dt = dev.seconds
        self.hist.record(dt)

        fresh = None
        if self.compare_fresh:
            fresh = np.asarray(
                serving.serve_fresh(snap.fresh_w, xj, aj))[:k]
        self.batches.append(ServedBatch(
            cycle=self.snapshot_cycle, size=k, latency_s=dt,
            query_ids=ids, assign=assign[:k],
            preds=np.asarray(preds)[:k], preds_fresh=fresh))

    # ----------------------------------------------------------------- stats
    def answers(self) -> np.ndarray:
        """All voted answers in submission order."""
        out = np.zeros(self._next_id, np.float32)
        for b in self.batches:
            out[b.query_ids] = b.preds
        return out

    def answers_fresh(self) -> np.ndarray:
        out = np.zeros(self._next_id, np.float32)
        for b in self.batches:
            if b.preds_fresh is not None:
                out[b.query_ids] = b.preds_fresh
        return out

    def stats(self) -> ServeStats:
        """Aggregate from the shared fixed-bucket histogram — the same
        p50/p90/p99/p999 estimator every latency number in the repo uses
        (``repro.core.telemetry.LatencyHistogram``; the previous inline
        ``np.percentile`` copy is gone)."""
        h = self.hist
        total = h.total
        q = int(sum(b.size for b in self.batches))
        return ServeStats(
            queries=q, batches=len(self.batches),
            queries_per_sec=q / total if total > 0 else 0.0,
            p50_latency_s=h.p50, p99_latency_s=h.p99,
            serve_seconds=total, p90_latency_s=h.p90,
            p999_latency_s=h.p999,
            latency_hist=h.to_dict() if h.count else None)
