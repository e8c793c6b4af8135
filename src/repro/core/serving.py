"""Live serving tier: query snapshots + batched voted prediction (Eq. 8).

The paper's headline object is the *virtual weighted vote* over an
exponential ensemble (Eq. 8, Algorithm 4) — this module serves predictions
from it while the protocol runs. A :class:`QuerySnapshot` is a pure read
of the live engine state (node-local freshest model + the cache ring
buffer), taken without stopping the protocol: jax arrays are immutable, so
snapshotting perturbs nothing — the post-serve error curves are bitwise
identical to a no-serving run (pinned by tests/test_serving.py).

Query flow (docs/SERVING.md has the full diagram):

    engine eval point ── take_snapshot / snapshot_from_carry
                              │
    incoming queries ── assign_queries (node-assignment policy, host rng)
                              │
    batched answer   ── serve_voted (jnp einsum path) or
                        serve_voted_kernel (fused Pallas
                        voted_predict_batched) / serve_fresh (PREDICT)

Both engines hand snapshots to a ``serve_hook(cycle, snapshot)`` passed to
``run_simulation`` — at each eval point, built from the reference engine's
``SimState`` or the sharded engine's scan carry, so a snapshot is bitwise
identical across engines for the same seed (the serving-tier extension of
the parity contract). Consume the snapshot before the engine's next chunk:
the sharded scan donates its carry buffers, so a snapshot held across
chunk boundaries must be copied out (``np.asarray``) first.

Node assignment draws from a host-side ``numpy.random.default_rng`` stream
— deliberately NOT ``jax.random``: the protocol's pinned per-cycle
threefry split sequence (tools/lint/rng_allowlist.py) stays untouched, so
serving cannot shift a draw counter and break cross-engine parity.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cache as cache_mod
from repro.core.cache import ModelCache
from repro.kernels.voted_predict import voted_predict_batched

ASSIGN_POLICIES = ("uniform", "round_robin")


class QuerySnapshot(NamedTuple):
    """Read-only view of the serving-relevant protocol state at one cycle:
    the cache ring buffer (VOTEDPREDICT state) and the freshest model per
    node (PREDICT state), plus the engine clock for answer attribution."""
    w: jnp.ndarray        # (N, C, d) cache ring-buffer weights
    t: jnp.ndarray        # (N, C) int32 per-slot update counters
    count: jnp.ndarray    # (N,) int32 valid slots per node
    fresh_w: jnp.ndarray  # (N, d) freshest model per node
    fresh_t: jnp.ndarray  # (N,) int32
    clock: jnp.ndarray    # () int32 engine clock at snapshot time


def _snapshot(cache: ModelCache, clock) -> QuerySnapshot:
    fresh_w, fresh_t = cache_mod.freshest(cache)
    return QuerySnapshot(cache.w, cache.t, cache.count, fresh_w, fresh_t,
                         clock)


def take_snapshot(state) -> QuerySnapshot:
    """Snapshot from the reference engine's live ``SimState`` (anything
    with ``.cache`` and ``.clock``) — a pure read, no protocol mutation."""
    return _snapshot(state.cache, state.clock)


def snapshot_from_carry(carry) -> QuerySnapshot:
    """Snapshot from the sharded engine's scan carry (the 14-lane tuple:
    cache lanes 4–7, clock lane 13) — bitwise identical to
    :func:`take_snapshot` of the reference engine at the same cycle."""
    cache = ModelCache(carry[4], carry[5], carry[6], carry[7])
    return _snapshot(cache, carry[13])


def assign_queries(n_queries: int, n_nodes: int, *,
                   policy: str = "uniform", seed: int = 0,
                   offset: int = 0) -> np.ndarray:
    """Node assignment for a query batch: which node answers each query.

    ``"uniform"`` models users landing on arbitrary nodes (the Floating
    Gossip service picture); ``"round_robin"`` is the deterministic
    load-balanced front end (``offset`` carries the position across
    batches). Host-side numpy stream — see the module docstring for why
    this is not ``jax.random``."""
    if policy == "uniform":
        rng = np.random.default_rng((seed, offset))
        return rng.integers(0, n_nodes, n_queries).astype(np.int32)
    if policy == "round_robin":
        return ((offset + np.arange(n_queries)) % n_nodes).astype(np.int32)
    raise ValueError(f"unknown assignment policy {policy!r} "
                     f"(expected one of {ASSIGN_POLICIES})")


@jax.jit
def serve_fresh(fresh_w, X, assign):
    """PREDICT for a query batch: sign of <w_freshest, x> at the assigned
    node — op-for-op the gathered form of ``cache.predict_fresh``."""
    w = cache_mod.take_nodes(fresh_w, assign)   # (M, d)
    return jnp.where(jnp.einsum("md,md->m", w, X) >= 0, 1.0, -1.0)


@jax.jit
def serve_voted(w, count, X, assign):
    """VOTEDPREDICT for a query batch — the jnp einsum path.

    Mirrors ``cache.voted_predict`` op for op on the gathered (query,
    assigned node) pairs: same ``score >= 0`` sign convention, same
    valid-slot mask, same ``p_ratio - 0.5 >= 0`` tie-break. ``w``:
    (N, C, d) snapshot cache; ``count``: (N,); ``X``: (M, d); ``assign``:
    (M,) int32. Returns (M,) ±1 predictions."""
    c = w.shape[1]
    w_sel = cache_mod.take_nodes(w, assign)      # (M, C, d)
    cnt = cache_mod.take_nodes(count, assign)    # (M,)
    scores = jnp.einsum("mcd,md->mc", w_sel, X)
    votes = (scores >= 0).astype(jnp.float32)
    valid = (jnp.arange(c)[None, :] < cnt[:, None]).astype(jnp.float32)
    p_ratio = jnp.einsum("mc,mc->m", votes, valid) / cnt
    return jnp.where(p_ratio - 0.5 >= 0, 1.0, -1.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def serve_voted_kernel(w, count, X, assign, *, interpret: bool = False):
    """VOTEDPREDICT for a query batch — the fused Pallas path
    (``repro.kernels.voted_predict.voted_predict_batched``; ``interpret``
    runs it in Pallas interpret mode, for CPU tests): gathers the assigned
    cache rows, then one VMEM pass scores, votes and reduces. Predictions
    are bitwise equal to :func:`serve_voted` (tests/test_serving.py + the
    BENCH_serving.json parity probes)."""
    return voted_predict_batched(cache_mod.take_nodes(w, assign),
                                 cache_mod.take_nodes(count, assign), X,
                                 interpret=interpret)
