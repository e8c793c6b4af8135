"""Bounded model cache + local prediction (Algorithm 1 state, Algorithm 4).

Each node keeps the ``cache_size`` most recent models that passed through it
(a ring buffer). Prediction is free locally: PREDICT uses the freshest
model; VOTEDPREDICT majority-votes the cache — the paper's Fig. 3 shows this
significantly accelerates RW and slightly accelerates MU.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
from jax import lax


class ModelCache(NamedTuple):
    w: jnp.ndarray        # (N, C, d)
    t: jnp.ndarray        # (N, C) int32
    ptr: jnp.ndarray      # (N,) int32 — next write slot
    count: jnp.ndarray    # (N,) int32 — number of valid entries


def init_cache(n: int, c: int, d: int) -> ModelCache:
    """Cache initialized with the zero model (INITMODEL adds it)."""
    return ModelCache(
        w=jnp.zeros((n, c, d), jnp.float32),
        t=jnp.zeros((n, c), jnp.int32),
        ptr=jnp.ones((n,), jnp.int32),   # slot 0 holds the init model
        count=jnp.ones((n,), jnp.int32),
    )


def cache_add(cache: ModelCache, node_mask, w_new, t_new) -> ModelCache:
    """Vectorized ``modelCache.add`` on the subset ``node_mask`` of nodes.

    w_new: (N, d); nodes where node_mask is False are untouched."""
    n, c, d = cache.w.shape
    rows = jnp.arange(n)
    slot = cache.ptr % c
    w = cache.w.at[rows, slot].set(
        jnp.where(node_mask[:, None], w_new, cache.w[rows, slot]))
    t = cache.t.at[rows, slot].set(
        jnp.where(node_mask, t_new, cache.t[rows, slot]))
    ptr = jnp.where(node_mask, cache.ptr + 1, cache.ptr)
    count = jnp.where(node_mask, jnp.minimum(cache.count + 1, c), cache.count)
    return ModelCache(w, t, ptr, count)


def _slot_select(cache: ModelCache, slot):
    """``(cache.w[rows, slot], cache.t[rows, slot])`` as a chain of selects
    over the C slots: the same values as the gather, without it. XLA's TPU
    layout keeps the node axis of the (N, C, d) cache minor, and a gather
    there would first copy the whole cache into a node-major layout (3.6x
    its size at d=57)."""
    w, t = cache.w[:, 0], cache.t[:, 0]
    for k in range(1, cache.w.shape[1]):
        hit = slot == k
        w = jnp.where(hit[:, None], cache.w[:, k], w)
        t = jnp.where(hit, cache.t[:, k], t)
    return w, t


def freshest(cache: ModelCache):
    """``modelCache.freshest()`` — the most recently added model per node."""
    return _slot_select(cache, (cache.ptr - 1) % cache.w.shape[1])


def cache_oldest(cache: ModelCache):
    """The oldest still-valid model per node (slot ``ptr - count``) — what
    a ``stale_replay`` Byzantine node retransmits: its model from
    ~``cache_size`` receives ago, with the stale counter riding along."""
    return _slot_select(cache, (cache.ptr - cache.count) % cache.w.shape[1])


def take_nodes(a, idx):
    """``a[idx]`` for a few node ids ``idx``: one dynamic slice per node
    along the node axis. An XLA gather on TPU first copies the whole array
    into a node-major layout (the engines' arrays keep the node axis
    minor); the slices read only the selected nodes."""
    at = jnp.moveaxis(a, 0, -1)

    def body(k, out):
        col = lax.dynamic_slice_in_dim(at, idx[k], 1, axis=-1)
        return lax.dynamic_update_slice_in_dim(out, col, k, axis=-1)

    out = lax.fori_loop(0, idx.shape[0], body,
                        jnp.zeros(at.shape[:-1] + idx.shape, a.dtype))
    return jnp.moveaxis(out, -1, 0)


def predict_fresh(cache: ModelCache, X):
    """PREDICT for every node over a test matrix X (m, d) -> (N, m) signs."""
    w, _ = freshest(cache)                      # (N, d)
    return jnp.where(X @ w.T >= 0, 1.0, -1.0).T


def voted_predict(cache: ModelCache, X):
    """VOTEDPREDICT (Algorithm 4): majority vote over the valid cache slots.

    Returns (N, m) predictions for every node on test matrix X (m, d)."""
    n, c, d = cache.w.shape
    scores = jnp.einsum("ncd,md->ncm", cache.w, X)
    votes = (scores >= 0).astype(jnp.float32)   # (N, C, m)
    valid = (jnp.arange(c)[None, :] < cache.count[:, None]).astype(jnp.float32)
    p_ratio = jnp.einsum("ncm,nc->nm", votes, valid) / cache.count[:, None]
    return jnp.where(p_ratio - 0.5 >= 0, 1.0, -1.0)
