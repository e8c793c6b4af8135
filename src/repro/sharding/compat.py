"""One wrapper for ``jax.shard_map``.

Every call site in this repo goes through :func:`shard_map_compat`, so the
keyword spelling (``check_vma``/``axis_names``) lives in one place and the
``shardmap-spec-arity`` lint rule has one call to look for.
"""
from __future__ import annotations

import jax


def shard_map_compat(f, *, mesh, in_specs, out_specs, manual_axes=None,
                     check: bool = False):
    """``jax.shard_map(f, mesh, in_specs, out_specs)``.

    ``manual_axes``: mesh axes the body handles manually; the remaining
    axes stay auto-sharded (partial-manual mode, ``axis_names``). ``None``
    means fully manual over every mesh axis.

    ``check=False`` disables varying-manual-axes checking (``check_vma``)
    — the call sites here permute or act element-wise per shard, which the
    checker cannot always verify."""
    kw = {} if manual_axes is None else {"axis_names": set(manual_axes)}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check, **kw)
