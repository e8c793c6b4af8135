"""Pytree arithmetic helpers used by optimizers and the gossip merge ops.

All helpers are jit-friendly (pure jax) and operate leaf-wise. They are the
pytree generalization of the paper's vector operations on linear models: the
gossip ``merge`` (Algorithm 3) is :func:`tree_average`, the SGD steps are
:func:`tree_axpy`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def tree_leaves_with_path(tree):
    """``jax.tree.leaves_with_path``: (key path, leaf) pairs."""
    return jax.tree.leaves_with_path(tree)


def tree_add(a, b):
    """Leaf-wise ``a + b``."""
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a, b):
    """Leaf-wise ``a - b``."""
    return jax.tree.map(jnp.subtract, a, b)


def tree_scale(s, a):
    """Leaf-wise ``s * a`` for a scalar ``s``."""
    return jax.tree.map(lambda x: s * x, a)


def tree_axpy(alpha, x, y):
    """Leaf-wise ``alpha * x + y`` (the SGD update shape)."""
    return jax.tree.map(lambda xi, yi: alpha * xi + yi, x, y)


def tree_average(*trees, weights=None):
    """Average of a list of pytrees — the paper's MERGE for arbitrary models.

    ``merge(m1, m2).w = (m1.w + m2.w) / 2`` (Algorithm 3, line 24) generalized
    to n-way, optionally weighted, averaging over parameter pytrees.
    """
    n = len(trees)
    if weights is None:
        return jax.tree.map(lambda *xs: sum(xs) / n, *trees)
    wsum = sum(weights)
    return jax.tree.map(lambda *xs: sum(w * x for w, x in zip(weights, xs)) / wsum, *trees)


def tree_dot(a, b):
    """Inner product over all leaves (float32 accumulation)."""
    parts = jax.tree.leaves(jax.tree.map(
        lambda x, y: jnp.vdot(x.astype(jnp.float32), y.astype(jnp.float32)), a, b))
    return jnp.sum(jnp.stack(parts)) if parts else jnp.float32(0.0)


def tree_norm(a):
    """Global L2 norm over all leaves."""
    return jnp.sqrt(tree_dot(a, a))


def tree_zeros_like(a):
    return jax.tree.map(jnp.zeros_like, a)


def tree_cast(a, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), a)


def tree_size(a):
    """Total number of elements across all leaves."""
    return sum(x.size for x in jax.tree.leaves(a))


def tree_bytes(a):
    """Total bytes across all leaves (works on ShapeDtypeStruct too)."""
    total = 0
    for x in jax.tree.leaves(a):
        total += int(jnp.prod(jnp.array(x.shape))) * jnp.dtype(x.dtype).itemsize if x.shape else jnp.dtype(x.dtype).itemsize
    return total


def tree_random_like(key, a, scale=1.0):
    """Random-normal pytree with the same structure/shapes as ``a``."""
    leaves, treedef = jax.tree.flatten(a)
    keys = jax.random.split(key, len(leaves))
    new = [scale * jax.random.normal(k, x.shape, x.dtype if jnp.issubdtype(x.dtype, jnp.floating) else jnp.float32)
           for k, x in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, new)
