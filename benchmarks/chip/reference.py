"""Plain reference of the gossip-learning protocol, in numpy on the host.

Algorithm 1 of Ormandi, Hegedus and Jelasity (CCPE 2013) with the P2Pegasos
"mu" CREATEMODEL (merge, then one Pegasos step), a ring cache of C models per
node, VOTEDPREDICT over that cache, and the failure model of Sec. VI-A:
i.i.d. message drop, a delay of 1..D whole cycles, lognormal churn.

It imports nothing of the program under test. What it shares with the
program is the definition of a run: the seed fixes the churn trace and the
eval nodes (numpy ``default_rng(seed)``, the generator copied in
:func:`churn_trace`), and the per-cycle peer, delay and drop draws
(``jax.random`` threefry from ``key(seed)``, split once per cycle and then
four ways). Simultaneous arrivals at a node are taken in descending order
of their flat buffer slot ``(send_cycle % D) * N + sender``, at most K a
cycle; the rest overflow. Every receive is applied in full, one round after
the other, on plain arrays: no kernel, no packing, no compaction.

``precision="bf16"`` rounds every floating-point result to bfloat16: the
control, which the comparison has to refuse.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

import numpy as np


@dataclass(frozen=True)
class Protocol:
    """The protocol and scenario a run simulates."""
    n: int
    d: int
    cache_size: int
    k_rounds: int
    lam: float
    drop: float
    delay_max: int
    online_fraction: float
    cycles: int
    eval_every: int
    eval_nodes: int


def churn_trace(rng: np.random.Generator, n: int, cycles: int,
                online_fraction: float, mean_online: float = 50.0,
                sigma: float = 1.5) -> np.ndarray:
    """(cycles, n) online matrix from alternating lognormal sessions.

    The scenario generator of ``repro.core.simulation.churn_trace``
    (version 2), copied so that the reference draws the same trace from the
    same seed without importing the program."""
    if online_fraction >= 1.0:
        return np.ones((cycles, n), dtype=bool)
    if cycles == 0:
        return np.zeros((0, n), dtype=bool)
    mean_off = mean_online * (1.0 - online_fraction) / online_fraction
    mu_on = np.log(mean_online) - sigma ** 2 / 2
    mu_off = np.log(max(mean_off, 1e-9)) - sigma ** 2 / 2
    phase = rng.integers(0, max(int(mean_online), 1), size=n)
    state0 = rng.random(n) < online_fraction
    med_pair = np.exp(mu_on) + np.exp(mu_off)
    horizon = cycles + int(mean_online)
    step = int(np.clip(np.ceil(horizon / max(med_pair, 1.0)) + 2, 4, 4096))

    def draw_sessions(cols_done, m, init_state):
        j = cols_done + np.arange(m)
        on = init_state[:, None] ^ (j[None, :] % 2 == 1)
        mu = np.where(on, np.float32(mu_on), np.float32(mu_off))
        z = rng.standard_normal((init_state.size, m), dtype=np.float32)
        return np.maximum(np.exp(mu + np.float32(sigma) * z).astype(np.int32),
                          1)

    counts = np.zeros((cycles, n), np.int16)
    flip0 = np.zeros(n, bool)

    def scatter_boundaries(node_ids, bounds):
        r, c = np.nonzero((bounds > 0) & (bounds < cycles))
        np.add.at(counts, (bounds[r, c], node_ids[r]), 1)
        flip0[node_ids] ^= ((bounds <= 0).sum(axis=1) & 1).astype(bool)

    bounds = draw_sessions(0, step, state0).cumsum(axis=1) - phase[:, None]
    scatter_boundaries(np.arange(n), bounds)
    last = bounds[:, -1]
    sub = np.flatnonzero(last < cycles)
    lsub = last[sub]
    cols = step
    while sub.size:
        bounds = (lsub[:, None]
                  + draw_sessions(cols, step, state0[sub]).cumsum(axis=1))
        scatter_boundaries(sub, bounds)
        cols += step
        lsub = bounds[:, -1]
        keep = lsub < cycles
        sub, lsub = sub[keep], lsub[keep]

    parity = counts.cumsum(axis=0, dtype=np.int16) & 1
    return (state0 ^ flip0)[None, :] ^ parity.astype(bool)


def scenario(p: Protocol, seed: int):
    """(online (cycles, n) bool, eval node ids): one numpy stream, in order."""
    rng = np.random.default_rng(seed)
    online = churn_trace(rng, p.n, p.cycles, p.online_fraction)
    eval_idx = rng.choice(p.n, size=min(p.eval_nodes, p.n), replace=False)
    return online, eval_idx


class _Draws:
    """Per-cycle (dst, delay, dropped) of ``key(seed)``'s split chain."""

    def __init__(self, p: Protocol, seed: int):
        import jax
        import jax.numpy as jnp
        n, D, drop = p.n, p.delay_max, p.drop

        def one(sub):
            _, k_dst, k_delay, k_drop = jax.random.split(sub, 4)
            r = jax.random.randint(k_dst, (n,), 0, n - 1)
            dst = jnp.where(r >= jnp.arange(n), r + 1, r)
            delay = (jax.random.randint(k_delay, (n,), 1, D + 1) if D > 1
                     else jnp.ones((n,), jnp.int32))
            dropped = (jax.random.bernoulli(k_drop, drop, (n,)) if drop > 0
                       else jnp.zeros((n,), bool))
            return dst, delay, dropped

        self._one = jax.jit(one)
        self._split = jax.jit(jax.random.split)
        self._key = jax.random.key(seed)

    def next(self):
        self._key, sub = self._split(self._key)
        return tuple(np.asarray(a) for a in self._one(sub))


def round_bf16(a: np.ndarray) -> np.ndarray:
    """f32 values rounded to the nearest bfloat16, ties to even, held in f32."""
    a = np.asarray(a, np.float32)
    b = a.view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return b.view(np.float32)


def _exact(a):
    return a


class State:
    """The whole population's protocol state at one cycle."""

    def __init__(self, p: Protocol):
        n, d, C, D = p.n, p.d, p.cache_size, p.delay_max
        self.last_w = np.zeros((n, d), np.float32)
        self.last_t = np.zeros(n, np.int32)
        self.cache_w = np.zeros((n, C, d), np.float32)
        self.cache_t = np.zeros((n, C), np.int32)
        self.ptr = np.ones(n, np.int32)        # slot 0 holds the zero model
        self.count = np.ones(n, np.int32)
        self.fresh_w = np.zeros((n, d), np.float32)
        self.fresh_t = np.zeros(n, np.int32)
        self.buf_w = np.zeros((D * n, d), np.float32)
        self.buf_t = np.zeros(D * n, np.int32)
        # messages in flight by arrival cycle: [(flat slots, destinations)]
        self.pending: Dict[int, List] = {}


# a vote is decisive when every valid nonzero model scores the query
# farther from zero than this share of |w| |x|: far above f32 rounding
DECISIVE = 1e-4


def vote(cache_w, count, nodes, X):
    """VOTEDPREDICT (Algorithm 4) of query rows X at the given nodes: the
    majority of ``<w, x> >= 0`` over the node's first ``count`` ring slots,
    a tie counting as +1. Returns (±1 as float32, decisive): ``decisive``
    marks the queries whose answer no rounding of the models can turn."""
    C = cache_w.shape[1]
    w = cache_w[nodes]                                   # (Q, C, d)
    scores = np.einsum("qcd,qd->qc", w, X)
    cnt = count[nodes]
    valid = np.arange(C)[None, :] < cnt[:, None]
    pos = ((scores >= 0) & valid).sum(1)
    scale = np.linalg.norm(w, axis=-1) * np.linalg.norm(X, axis=-1)[:, None]
    # the zero model (the cache's first entry) scores exactly 0 everywhere
    clear = (np.abs(scores) > DECISIVE * scale) | (scale == 0) | ~valid
    return (np.where(pos / cnt - 0.5 >= 0, 1.0, -1.0).astype(np.float32),
            clear.all(1))


# an eval score is clear when it lies farther from zero than this share of
# sum_i |w_i x_i|: an eval at the chip's default matmul precision (one
# bfloat16 pass) puts each product within 2^-8 of its size, half of this
EVAL_DECISIVE = 2.0 ** -7


def _clear(W, X):
    """Scores ``W @ X.T`` over the last axis, and which are clear of zero by
    :data:`EVAL_DECISIVE` (a zero model scores exactly 0 at any precision)."""
    scores = W @ X.T
    scale = np.abs(W) @ np.abs(X).T
    return scores, (np.abs(scores) > EVAL_DECISIVE * scale) | (scale == 0)


def errors(state: State, eval_idx, X_test, y_test):
    """PREDICT and VOTEDPREDICT errors averaged over the eval nodes, and for
    each the least and the most (eval node, test row) pairs that any eval
    whose scores are within :data:`EVAL_DECISIVE` can count as wrong."""
    y = y_test[None, :]
    scores, clear = _clear(state.fresh_w[eval_idx], X_test)
    fresh = np.where(scores >= 0, 1.0, -1.0)
    err_f = np.mean(np.mean(fresh != y, axis=1))
    lo = int(np.sum((fresh != y) & clear))
    bounds_f = (lo, lo + int(np.sum(~clear)))
    C = state.cache_w.shape[1]
    scores, clear = _clear(state.cache_w[eval_idx], X_test)      # (E, C, m)
    cnt = state.count[eval_idx][:, None]
    valid = np.arange(C)[None, :, None] < cnt[:, :, None]
    pos = ((scores >= 0) & valid).sum(1)
    voted = np.where(pos / cnt - 0.5 >= 0, 1.0, -1.0)
    err_v = np.mean(np.mean(voted != y, axis=1))
    # the vote with every unclear score taken as negative, and as positive
    sure = ((scores >= 0) & clear & valid).sum(1)
    maybe = (~clear & valid).sum(1)
    decided = (sure / cnt - 0.5 >= 0) == ((sure + maybe) / cnt - 0.5 >= 0)
    lo = int(np.sum((voted != y) & decided))
    bounds_v = (lo, lo + int(np.sum(~decided)))
    return float(err_f), float(err_v), bounds_f, bounds_v


def _receive(s: State, nodes, slots, X, y, q, lam, C: int) -> None:
    """One receive round at ``nodes`` (distinct), each taking the message in
    flat buffer slot ``slots``: CREATEMODELMU, a Pegasos step on the merge
    of message and lastModel, added to the ring cache; lastModel <- message.
    """
    mw, mt = s.buf_w[slots], s.buf_t[slots]
    lw, lt = s.last_w[nodes], s.last_t[nodes]
    xn, yn = X[nodes], y[nodes]
    w = q(q(mw + lw) / np.float32(2.0))
    t = np.maximum(mt, lt) + 1
    eta = q(np.float32(1.0) / q(lam * t.astype(np.float32)))
    margin = q(yn * q(np.sum(q(w * xn), axis=1, dtype=np.float32)))
    decay = q(np.float32(1.0) - q(eta * lam))
    step = q(eta[:, None] * q(yn[:, None] * xn))
    w = q(q(decay[:, None] * w)
          + np.where((margin < 1.0)[:, None], step, np.float32(0)))
    slot = s.ptr[nodes] % C
    s.cache_w[nodes, slot] = w
    s.cache_t[nodes, slot] = t
    s.ptr[nodes] += 1
    s.count[nodes] = np.minimum(s.count[nodes] + 1, C)
    s.fresh_w[nodes], s.fresh_t[nodes] = w, t
    s.last_w[nodes], s.last_t[nodes] = mw, mt


# numpy releases the interpreter lock in its array loops, so the rows of one
# round, which touch distinct nodes, are split over a few threads
_THREADS = max(1, min(8, (os.cpu_count() or 1) - 1))


def run(p: Protocol, X, y, X_test, y_test, seed: int, *,
        precision: str = "f32",
        on_eval: Optional[Callable[[int, State], None]] = None) -> Dict:
    """Simulate ``p.cycles`` cycles from ``seed``; returns the error curves,
    the bounds of their wrong-pair counts (:func:`errors`) and the message
    economy, and calls ``on_eval(cycle, state)`` at every
    eval point (after the cycle's sends)."""
    q = {"f32": _exact, "bf16": round_bf16}[precision]
    n, C, D, K = p.n, p.cache_size, p.delay_max, p.k_rounds
    lam = np.float32(p.lam)
    X = np.asarray(X, np.float32)
    y = np.asarray(y, np.float32)
    online, eval_idx = scenario(p, seed)
    draws = _Draws(p, seed)
    s = State(p)
    pool = ThreadPoolExecutor(_THREADS)
    out = dict(cycles=[], err_fresh=[], err_voted=[], wrong_fresh=[],
               wrong_voted=[], eval_pairs=eval_idx.size * len(y_test),
               sent=0, delivered=0, lost=0, overflow=0,
               delivered_per_cycle=[])
    for c in range(p.cycles):
        dst, delay, dropped = draws.next()
        # ---- deliveries due this cycle
        due = s.pending.pop(c, [])
        m_slot = np.concatenate([a for a, _ in due] + [np.empty(0, np.int64)])
        m_dst = np.concatenate([b for _, b in due] + [np.empty(0, np.int64)])
        on = online[c, m_dst]
        out["lost"] += int((~on).sum())
        m_slot, m_dst = m_slot[on], m_dst[on]
        order = np.lexsort((-m_slot, m_dst))       # by node, newest slot first
        m_slot, m_dst = m_slot[order], m_dst[order]
        pos = np.arange(m_dst.size)
        first = np.ones(m_dst.size, bool)
        first[1:] = m_dst[1:] != m_dst[:-1]
        rank = pos - np.maximum.accumulate(np.where(first, pos, 0))
        delivered = int((rank < K).sum())
        out["delivered"] += delivered
        out["overflow"] += int(m_dst.size - delivered)
        out["delivered_per_cycle"].append(delivered)
        for k in range(K):
            sel = rank == k
            if not sel.any():
                break
            nodes, slots = m_dst[sel], m_slot[sel]
            parts = np.array_split(np.arange(nodes.size), _THREADS)
            list(pool.map(lambda i: _receive(s, nodes[i], slots[i], X, y,
                                             q, lam, C), parts))
        # ---- sends: every online node that keeps its message sends its
        # freshest model into buffer row c % D
        row = c % D
        s.buf_w[row * n:(row + 1) * n] = s.fresh_w
        s.buf_t[row * n:(row + 1) * n] = s.fresh_t
        senders = np.flatnonzero(online[c] & ~dropped
                                 & (dst != np.arange(n)))
        out["sent"] += int(senders.size)
        when = delay[senders]
        for dl in np.unique(when):
            who = senders[when == dl]
            s.pending.setdefault(c + int(dl), []).append(
                (row * n + who.astype(np.int64), dst[who].astype(np.int64)))
        if (c + 1) % p.eval_every == 0 or c == p.cycles - 1:
            e_f, e_v, b_f, b_v = errors(s, eval_idx, X_test, y_test)
            out["cycles"].append(c + 1)
            out["err_fresh"].append(e_f)
            out["err_voted"].append(e_v)
            out["wrong_fresh"].append(b_f)
            out["wrong_voted"].append(b_v)
            if on_eval is not None:
                on_eval(c + 1, s)
    pool.shutdown()
    out["in_flight"] = sum(a.size for msgs in s.pending.values()
                           for a, _ in msgs)
    return out


def assign_uniform(batch: int, n: int, seed: int, offset: int) -> np.ndarray:
    """The ``uniform`` front end's node for each query of a batch: the
    serving node is drawn from ``default_rng((seed, offset))``, where
    ``offset`` counts the queries answered before the batch."""
    rng = np.random.default_rng((seed, offset))
    return rng.integers(0, n, batch).astype(np.int32)


def economy(res: Dict) -> List[int]:
    return [res["sent"], res["delivered"], res["lost"], res["overflow"],
            res["in_flight"]]
