"""Pallas TPU kernel: batched VOTEDPREDICT over gathered cache rows.

The serving tier's hot path (Algorithm 4 as a *service*): a batch of M
queries, each routed to one node, answered by a majority vote over that
node's cache ring buffer — ``(queries × cached models)`` scores, votes and
the vote reduction fused into ONE pass over VMEM-resident tiles. The jnp
oracle is :func:`repro.core.cache.voted_predict` restricted to the
(query, assigned node) pairs; the kernel reproduces its ±1 predictions
bitwise (the vote counts are exact small-integer sums, and the tie-break
``p_ratio - 0.5 >= 0`` and the ``score >= 0`` sign convention are applied
identically — pinned by tests/test_serving.py).

TPU adaptation: node-minor, like ``kernels/gossip_cycle.py``. Queries sit
on the 128-wide lane axis: the gathered cache rows are read as (C, d, M),
the query points as (d, M) and the counts as (1, M) — XLA's own TPU
layouts for the (M, C, d) / (M, d) gathers, so the transposes are free.
Every other block dimension is the full array dimension, so nothing is
padded; a ragged last block computes garbage lanes that are never written
back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.gossip_cycle import _lanes, _node_block


def _voted_kernel(w_ref, x_ref, cnt_ref, out_ref):
    w = w_ref[...].astype(jnp.float32)          # (C, d, BLK)
    x = x_ref[...].astype(jnp.float32)          # (d, BLK)
    cnt = cnt_ref[...]                          # (1, BLK) int32
    c, _, blk = w.shape

    # score each (cache slot, query) pair over the coefficient axis
    scores = jnp.sum(w * x[None], axis=1)       # (C, BLK)

    # Algorithm 4 vote: score >= 0 counts positive (the score == 0 sign
    # convention of cache.voted_predict); only the first `count` ring
    # slots are valid
    votes = (scores >= 0).astype(jnp.float32)
    slot = lax.broadcasted_iota(jnp.int32, (c, blk), 0)
    pos = jnp.sum(jnp.where(slot < cnt, votes, 0.0), axis=0, keepdims=True)
    # real queries always have count >= 1 (init_cache seeds one);
    # max(cnt, 1) only guards the garbage lanes of a ragged last block
    p_ratio = pos / jnp.maximum(cnt, 1).astype(jnp.float32)
    out_ref[...] = jnp.where(p_ratio - 0.5 >= 0, 1.0, -1.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def voted_predict_batched(w, count, X, *, interpret: bool = False):
    """w: (M, C, d) per-query gathered cache weights; count: (M,) int32
    valid-slot counts; X: (M, d) query points. Returns (M,) ±1 f32
    predictions — the majority vote of each query's assigned cache."""
    m, c, d = w.shape
    blk = _node_block(m)
    out = pl.pallas_call(
        _voted_kernel,
        grid=(pl.cdiv(m, blk),),
        in_specs=[_lanes(blk, c, d), _lanes(blk, d), _lanes(blk, 1)],
        out_specs=_lanes(blk, 1),
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.float32),
        interpret=interpret,
    )(w.transpose(1, 2, 0), X.T, count.astype(jnp.int32).reshape(1, m))
    return out[0]
