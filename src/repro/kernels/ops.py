"""Public jit'd wrappers for the Pallas kernels.

On a TPU backend the kernels compile to Mosaic. ``interpret=True`` runs the
kernel body as traced JAX ops instead (Pallas interpret mode), which is how
the CPU tests validate the tiling / masking / accumulation logic against
``ref.py``; it is never chosen implicitly, so a caller that asked for the
chip does not quietly run on the CPU.
"""
from __future__ import annotations

from repro.kernels import flash_attention as _fa
from repro.kernels import gossip_merge as _gm
from repro.kernels import pegasos_update as _pu


def pegasos_update(w, t, x, y, *, lam: float, interpret: bool = False):
    return _pu.pegasos_update(w, t, x, y, lam=lam, interpret=interpret)


def merge_update(w1, t1, w2, t2, x, y, *, lam: float,
                 interpret: bool = False):
    return _gm.merge_update(w1, t1, w2, t2, x, y, lam=lam,
                            interpret=interpret)


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    blk_q: int = _fa.DEFAULT_BLK_Q,
                    blk_k: int = _fa.DEFAULT_BLK_K, interpret: bool = False):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               blk_q=blk_q, blk_k=blk_k, interpret=interpret)
