"""Sharded mega-population gossip engine (``run_simulation(engine="sharded")``).

The reference driver tops out around N ~ 10^4: it re-enters Python every
cycle (one jitted call + host syncs for the stats scalars), and — worse on
CPU backends — every cycle pays XLA scatter ops (the winner-per-destination
scatter-max and the cache ring-buffer scatter-writes) that lower to serial
per-element loops. This engine splits the protocol the way a router splits a
network:

* **control plane on the host** — which message reaches which node in which
  round is *payload-independent* (it depends only on the PRNG draws, the
  churn matrix and the delay/drop outcomes). Per chunk, the engine draws the
  per-cycle destinations/delays/drops on-device with the *same* threefry
  calls as the reference engine (bitwise-identical), pulls the integer
  tables to the host, and resolves the K winner rounds with vectorized
  numpy fancy-index assignments (no XLA scatters); routing for the next
  chunk overlaps the in-flight device scan. The message economy stats
  (sent/delivered/lost/overflow) fall out of the same pass.
* **data plane in one ``lax.scan``** — all cycles between two eval points
  run as ONE XLA program over the precomputed routing tables: gather the
  winning payloads, apply the K receives (merge + update + cache-write,
  scatter-free one-hot ring-buffer writes), refresh the in-flight payload
  buffer. Population error is evaluated on-device at each ``eval_every``
  boundary; host round-trips drop from O(cycles) to O(cycles/eval_every).
* **node-axis sharding** — the receive application (everything that scales
  with N·d) runs under ``shard_map`` with the node axis split over a device
  mesh, reusing the peer-axis machinery proven in
  ``gossip_optimizer.gossip_merge``; messages reach receivers on other
  shards through an exact ring gather (``_mesh_take_slots``).
* **fused cycle kernel** — on TPU the receive application lowers to the
  Pallas ``kernels/gossip_cycle.py`` kernel: deliver→merge→update→
  cache-write in one VMEM-resident pass per node block (``interpret=True``
  for the CPU parity tests).
* **delivery-proportional compacted rounds** — winner rounds nest
  (round-k receivers ⊆ round-(k-1) receivers), so the router's per-cycle
  receiver lists bound the real work. Per chunk the driver picks the
  cheapest packing from the observed occupancy: ``dense`` (K rounds over
  all N), ``compact`` (round 1 dense, rounds ≥ 2 gathered/applied/
  scattered over the multi-receivers — ~a quarter of the population in the
  extreme scenario) or ``compact_all`` (every round over the round-1
  receiver subset — in sparse-delivery regimes a few percent of N, the
  regime of the paper's Fig. 5–7). Under a node mesh the tables are packed
  per shard, so the compact paths run inside ``shard_map`` too; the dense
  fallback is kept for near-full subsets.
* **wire-codec payloads** — ``cfg.wire_dtype`` names a codec from
  ``repro.core.wire_codec`` and stores the in-flight ``buf_w`` (the
  engine's dominant memory: ``(D, N, P)`` with P the codec's packed width)
  in the codec's payload representation; messages are encoded at send time
  and all merge math runs in f32, the exact contract of ``gossip_merge``'s
  ``exchange_dtype``. Quantized codecs carry a per-message f16 scale lane
  (``buf_scale``; the affine int8 family adds ``buf_zp``) and decode at
  delivery — in-kernel for the Pallas path (including the packed int4/
  ternary unpack); "int8_sr" rounds stochastically with the same per-cycle
  ``k_recv`` threefry slot as the reference engine. The ``_ef`` codecs
  (int4_ef/ternary_ef) add the (N, d) f32 error-feedback residual to the
  carry: senders transmit ``fresh + ef`` and refresh the residual only on
  cycles they actually send — the dense/compact bodies scan the router's
  per-cycle send mask, ``compact_all`` refreshes the sender subset — which
  keeps all packings bitwise-equal to the reference engine. With
  ``use_send_kernel`` the send-side quantization runs as the fused Pallas
  ``quantize_send`` kernel (in-kernel threefry for the SR draw; in-kernel
  pack + fused EF-residual output for the sub-4-bit codecs), closing the
  last full-population f32 pass per cycle. ``SimResult`` reports
  ``wire_bytes_total``/``buf_payload_bytes``/``ef_residual_norm``.

Determinism contract: for a given seed the engine consumes the *same* host
RNG stream (churn trace, eval subset) and the *same* per-cycle threefry
draws as the reference engine, and resolves winners with the same
descending-slot-id semantics — so the error curves reproduce the reference
engine's (bitwise, up to XLA fusion-level float reassociation).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as PS

from repro.configs.gossip_linear import GossipLinearConfig
from repro.core import cache as cache_mod
from repro.core import faults as faults_mod
from repro.core import peer_sampling
from repro.core.cache import ModelCache
from repro.core.learners import LinearModel, make_update
from repro.core.merge import create_model
from repro.core import telemetry as telemetry_mod
from repro.core.simulation import (SimResult, _eval, ef_residual_norm,
                                   eval_points, eval_subset,
                                   message_wire_bytes, payload_buffer_bytes,
                                   setup_h2d_bytes, sim_setup)
from repro.core.wire_codec import get_codec, sr_noise_for_rows
from repro.sharding.compat import shard_map_compat


def key_schedule(seed: int, cycles: int):
    """The reference driver's per-cycle subkeys, as one stacked array.

    Bitwise-identical to ``for c: key, sub = split(key)`` — the sharded
    engine scans over this array instead of splitting on the host."""
    def body(k, _):
        k, sub = jax.random.split(k)
        return k, sub
    _, subs = lax.scan(body, jax.random.key(seed), None, length=max(cycles, 1))
    return subs[:cycles]


# ---------------------------------------------------------------------------
# control plane: per-cycle draws (device, bitwise = reference) + host routing
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n", "drop", "delay_max",
                                             "sampler"))
def _draw_chunk(keys, onlines, clock0, *, n: int, drop: float,
                delay_max: int, sampler: str):
    """(T,) keys -> (T, n) destination / arrival tables.

    Scans the exact per-cycle draw sequence of ``cycle_core`` (split into 4,
    then dst / delay / drop in that order), so every threefry value matches
    the reference engine bit for bit."""
    D = delay_max

    def body(clock, inp):
        key, online = inp
        k_recv, k_dst, k_delay, k_drop = jax.random.split(key, 4)
        if sampler == "matching":
            dst = peer_sampling.perfect_matching(k_dst, n)
        else:
            dst = peer_sampling.uniform_peers(k_dst, n)
        delay = (jax.random.randint(k_delay, (n,), 1, D + 1)
                 if D > 1 else jnp.ones((n,), jnp.int32))
        dropped = (jax.random.bernoulli(k_drop, drop, (n,))
                   if drop > 0 else jnp.zeros((n,), bool))
        idle = dst == jnp.arange(n, dtype=dst.dtype)
        send_ok = online & ~dropped & ~idle
        arrival = jnp.where(send_ok, clock + delay, -1)
        return clock + 1, (dst.astype(jnp.int32), arrival.astype(jnp.int32))

    _, (dsts, arrivals) = lax.scan(body, clock0, (keys, onlines))
    return dsts, arrivals


class _HostRouter:
    """Host-side control-plane state: which flat buffer slot holds a message
    for which destination, carried between chunks as flat pending arrays.

    The router is the "control plane" half of the engine split (diagrammed
    in docs/ARCHITECTURE.md): routing is *payload-independent* — it depends
    only on the PRNG draws, the churn matrix and the delay/drop outcomes —
    so it runs on the host in numpy while the device scans the previous
    chunk's payload math. Payload-blindness is also why every wire dtype
    (f32 through int8_sr) sees the identical delivery schedule, which the
    accounting tests pin via ``sent_total`` equality across dtypes.

    The pending set is three parallel int32 arrays — flat slot id
    (row*n + sender), destination, absolute arrival cycle — snapshotted at
    send time. Storing the destination with the message (instead of reading
    the buffer's dst lane at delivery, as the device-side oracle does) is
    equivalent: the slot row a message occupies is provably not overwritten
    before its arrival cycle's deliveries run."""

    def __init__(self, n: int, delay_max: int):
        self.n = n
        self.delay_max = delay_max
        self.p_slot = _EMPTY_I32
        self.p_dst = _EMPTY_I32
        self.p_arr = _EMPTY_I32

    def route_chunk(self, dsts, arrivals, online_rows, clock0: int,
                    k_rounds: int, per_cycle_stats: bool = False):
        """Resolve winner-per-destination rounds for a chunk of cycles.

        Reproduces ``select_receivers``'s semantics exactly: in round k a
        node accepts the due message with the k-th largest flat slot id.
        The whole chunk is resolved in ONE batched numpy pass (no per-cycle
        Python loop): every candidate message arriving inside the chunk is
        ranked within its (cycle, destination) group by descending flat
        slot id — one lexsort — and rank r < K receives in round r.
        Distinct candidates in a group never share a slot id (a slot row is
        delivered before it is reused), so the ranking is total.

        Returns ``(win, stats, multi, recv)``:

        * ``win`` — the winner tuple ``(t, round, dst, slot)`` of parallel
          int32 arrays, ascending in (t, dst). The router deliberately does
          NOT materialize the dense (T, K, n) table: at N=10^6 that is a
          ~320 MB memset per chunk, pure waste whenever a compact packing
          is chosen. ``dense_table``/``pack_compact_rounds``/
          ``pack_compact_all`` build exactly the representation the chosen
          chunk fn consumes;
        * ``stats`` — the chunk message economy, plus ``delivered_cycles``
          (T,) per-cycle delivered counts for ``SimResult`` observability;
        * ``multi`` — one ascending int32 array per cycle listing the nodes
          that receive in round 2 or later (winner rounds fill in order, so
          round-k receivers ⊆ round-(k-1) receivers);
        * ``recv`` — one ascending int32 array per cycle listing ALL
          receiving nodes (the round-1 winners), which is what the fully
          compacted data-plane path gathers/scatters in sparse-delivery
          regimes.

        ``per_cycle_stats`` (armed telemetry only) adds ``lost_cycles`` and
        ``overflow_cycles`` (T,) bincounts to ``stats`` — the per-cycle
        message-economy streams. Both count at the ARRIVAL cycle, exactly
        like the reference engine's per-cycle stats. Off by default so the
        unarmed hot path pays nothing."""
        T, n = dsts.shape
        D, K = self.delay_max, k_rounds

        # sends of this chunk -> (slot, dst, arrival) triples, merged with
        # the pending carry; arrivals beyond the chunk become the new carry
        t_send, senders = np.nonzero(arrivals >= 0)
        slot = (((clock0 + t_send) % D) * n + senders).astype(np.int32)
        sent = int(senders.size)
        cand_slot = np.concatenate([self.p_slot, slot])
        cand_dst = np.concatenate([self.p_dst,
                                   dsts[t_send, senders].astype(np.int32)])
        cand_arr = np.concatenate([self.p_arr,
                                   arrivals[t_send, senders].astype(np.int32)])
        future = cand_arr >= clock0 + T
        self.p_slot = cand_slot[future]
        self.p_dst = cand_dst[future]
        self.p_arr = cand_arr[future]
        due = ~future
        c_slot = cand_slot[due]
        c_dst = cand_dst[due]
        c_t = cand_arr[due] - clock0

        # a message due while its destination is offline leaves the system
        on = online_rows[c_t, c_dst]
        lost = int(c_slot.size - int(on.sum()))
        lost_t = c_t[~on] if per_cycle_stats else None
        c_slot, c_dst, c_t = c_slot[on], c_dst[on], c_t[on]

        # winner ranks: sort by (cycle, dst) group, ascending slot id inside
        # each group => rank-from-group-end r is the r-th largest slot id
        group = c_t.astype(np.int64) * n + c_dst
        order = np.lexsort((c_slot, group))
        g_s = group[order]
        slot_s = c_slot[order]
        t_s = c_t[order]
        dst_s = c_dst[order]
        rank = np.searchsorted(g_s, g_s, side="right") - 1 \
            - np.arange(g_s.size)
        wm = rank < K
        win = (t_s[wm].astype(np.int32), rank[wm].astype(np.int32),
               dst_s[wm], slot_s[wm])
        delivered = int(wm.sum())
        overflow = int(g_s.size - delivered)

        def per_cycle(mask):
            # group order is (cycle, dst) ascending => each selected list is
            # ascending in node id; split at the cycle boundaries
            tm, dm = t_s[mask], dst_s[mask]
            return [a.astype(np.int32, copy=False) for a in
                    np.split(dm, np.searchsorted(tm, np.arange(1, T)))]

        recv = per_cycle(rank == 0)               # every receiver (round 1)
        multi = per_cycle(rank == 1) if K > 1 else [_EMPTY_I32] * T
        stats = dict(sent=sent, delivered=delivered, lost=lost,
                     overflow=overflow,
                     delivered_cycles=np.bincount(
                         win[0], minlength=T).astype(np.int64))
        if per_cycle_stats:
            stats["lost_cycles"] = np.bincount(
                lost_t, minlength=T).astype(np.int64)
            stats["overflow_cycles"] = np.bincount(
                t_s[~wm], minlength=T).astype(np.int64)
        return win, stats, multi, recv


_EMPTY_I32 = np.empty(0, np.int32)


def shard_list_width(lists, n: int, shards: int) -> int:
    """Smallest per-shard width that fits every per-cycle index list.

    With ``shards == 1`` this is just the longest list. With a node mesh the
    compacted tables must stay node-sharded, so receivers are packed
    per shard of the node axis (shard s owns nodes [s*n/S, (s+1)*n/S)) and
    the width is the largest per-shard receiver count over the chunk."""
    if shards == 1:
        return max((r.size for r in lists), default=0)
    bounds = np.arange(1, shards) * (n // shards)
    w = 0
    for r in lists:
        if r.size:
            w = max(w, int(np.max(np.diff(np.searchsorted(
                r, np.concatenate([[0], bounds, [n]]))))))
    return w


def _pack_index_lists(lists, n: int, width: int, shards: int):
    """(T,) ascending index lists -> (T, shards*width) int32, -1 padded.

    Shard s's entries land in columns [s*width, (s+1)*width): under a node
    mesh the packed axis is sharded like the node axis, and each device's
    slice references only its own nodes — the gather/apply/scatter of the
    compact path stays shard-local."""
    T = len(lists)
    ridx = np.full((T, shards * width), -1, np.int32)
    if shards == 1:
        for t, r in enumerate(lists):
            ridx[t, :r.size] = r
        return ridx
    bounds = np.arange(1, shards) * (n // shards)
    for t, r in enumerate(lists):
        cuts = np.searchsorted(r, np.concatenate([[0], bounds, [n]]))
        for s in range(shards):
            seg = r[cuts[s]:cuts[s + 1]]
            ridx[t, s * width:s * width + seg.size] = seg
    return ridx


def dense_table(win, T: int, K: int, n: int) -> np.ndarray:
    """The dense (T, K, n) routing table from a winner tuple: entry
    [t, r, dst] holds the flat slot id of dst's round-r receive at cycle
    t, -1 = no receive. Built only when the dense chunk fn actually runs —
    at N=10^6 this is the router's single biggest allocation."""
    t_w, r_w, dst_w, slot_w = win
    src_slot = np.full((T, K, n), -1, np.int32)
    src_slot[t_w, r_w, dst_w] = slot_w
    return src_slot


def _packed_columns(lists, t_w, dst_w, n: int, width: int, shards: int):
    """Packed-table column of each winner: the position of ``dst_w[i]``
    inside its cycle's (shard-grouped) index list. ``t_w`` must be
    ascending; every dst must be present in its cycle's list (winner
    rounds nest, so receiver lists cover all deeper rounds)."""
    cols = np.empty(t_w.size, np.int64)
    bounds = np.searchsorted(t_w, np.arange(len(lists) + 1))
    shard_size = n // shards
    for t, r in enumerate(lists):
        lo, hi = bounds[t], bounds[t + 1]
        if hi == lo:
            continue
        d = dst_w[lo:hi]
        pos = np.searchsorted(r, d)
        if shards == 1:
            cols[lo:hi] = pos
        else:
            s = d // shard_size
            cuts = np.searchsorted(r, np.arange(shards) * shard_size)
            cols[lo:hi] = s * width + (pos - cuts[s])
    return cols


def pack_compact_rounds(win, multi, T: int, K: int, n: int, width: int,
                        shards: int = 1):
    """Compact the routing of rounds >= 2 (round 1 stays dense).

    Rounds beyond the first touch only the ``multi`` nodes (about a quarter
    of the population in the paper's extreme scenario) — the dense table
    makes the data plane compute them over all N anyway. This builds:

    * ``src0``  (T, n)        round-1 slots (dense — most nodes receive);
    * ``ridx``  (T, S*M)      receiver node ids, -1 padded, grouped by node
                              shard (S = ``shards``) so meshes stay local;
    * ``rslot`` (T, K-1, S*M) per-round slots for those nodes, -1 = none.

    ``width`` caps the per-shard M; the caller buckets it (powers of two)
    so the jitted chunk fn recompiles O(log n) times, and falls back to the
    dense table when a round is near-full (see ``run_sharded_simulation``)."""
    t_w, r_w, dst_w, slot_w = win
    m0 = r_w == 0
    src0 = np.full((T, n), -1, np.int32)
    src0[t_w[m0], dst_w[m0]] = slot_w[m0]
    ridx = _pack_index_lists(multi, n, width, shards)
    rslot = np.full((T, K - 1, ridx.shape[1]), -1, np.int32)
    mk = ~m0
    cols = _packed_columns(multi, t_w[mk], dst_w[mk], n, width, shards)
    rslot[t_w[mk], r_w[mk] - 1, cols] = slot_w[mk]
    return src0, ridx, rslot


def pack_compact_all(win, recv, T: int, K: int, n: int, width: int,
                     shards: int = 1):
    """Compact ALL receive rounds over the round-1 receiver set.

    In sparse-delivery regimes (high drop, low online fraction, long
    delays) even round 1 touches only a few percent of the population —
    the ``compact`` packing still pays a dense O(N) round-1 apply. Winner
    rounds nest, so the round-1 receiver list ``recv`` covers every round;
    this packs the full K-round chain for just those nodes:

    * ``ridx``  (T, S*M)     receiving node ids, -1 padded, shard-grouped;
    * ``rslot`` (T, K, S*M)  per-round slots for those nodes, -1 = none.

    The data plane gathers the subset state, runs the same K-round apply
    the dense path runs on all N, and scatters back — per-cycle apply cost
    tracks delivered messages instead of population size."""
    t_w, r_w, dst_w, slot_w = win
    ridx = _pack_index_lists(recv, n, width, shards)
    rslot = np.full((T, K, ridx.shape[1]), -1, np.int32)
    cols = _packed_columns(recv, t_w, dst_w, n, width, shards)
    rslot[t_w, r_w, cols] = slot_w
    return ridx, rslot


# ---------------------------------------------------------------------------
# data plane: scatter-free K-receive application
# ---------------------------------------------------------------------------


def _vector_apply(last_w, last_t, fresh_w, fresh_t, cache: ModelCache,
                  msg_w, msg_t, valid, X, y, *, variant: str, update,
                  defense: str = "none"):
    """Scatter-free receive application (Algorithm 1 ON RECEIVE, K rounds).

    Bitwise-equal to ``simulation.apply_receives`` but restructured for
    dense backends: the K CREATEMODEL calls run as ONE batched update over
    (K·N, d) — the merge partner of round k is the round-(k-1) message
    (``lastModel <- m`` stores the *received* model, so the chain is known
    upfront) — and the K ring-buffer writes collapse into one one-hot
    combine instead of K scatter row-writes. Tracks the freshest model in
    the carry so the send step needs no cache gather.

    Payloads arrive in the wire dtype (bf16/f16 when ``cfg.wire_dtype`` is
    set); all merge/update arithmetic runs in f32 — the same contract as
    ``gossip_merge``'s ``exchange_dtype``. A no-op for f32 payloads.

    ``defense`` screens each round against the receiver's current chain
    model (``faults.apply_defense``, same semantics and op order as the
    reference ``apply_receives``): a rejected message drops out of the
    round's ``vm`` mask, a clipped one continues the chain rescaled.
    Returns ``(last_w, last_t, fresh_w, fresh_t, cache, gated, clipped)``
    with (N,) int32 per-node screen counts (zeros under ``"none"``)."""
    msg_w = msg_w.astype(jnp.float32)
    K, n, d = msg_w.shape
    C = cache.w.shape[1]
    rows = jnp.arange(n)
    iota_c = jnp.arange(C, dtype=jnp.int32)[None, :]

    # The round chain: CREATEMODEL(m_k, lastModel) then lastModel <- m_k —
    # the merge partner of round k is the round-(k-1) *message*, so the
    # chain advances with cheap wheres (no gathers, no scatter).
    prev_w, prev_t = last_w, last_t
    off = jnp.zeros((n,), jnp.int32)
    sel = jnp.full((n, C), -1, jnp.int32)
    last_k = jnp.zeros((n,), jnp.int32)
    gated = jnp.zeros((n,), jnp.int32)
    clipped = jnp.zeros((n,), jnp.int32)
    new_ws, new_ts = [], []
    for k in range(K):
        mw, vm, g, cl = faults_mod.apply_defense(
            defense, msg_w[k], valid[k], prev_w)
        gated = gated + g.astype(jnp.int32)
        clipped = clipped + cl.astype(jnp.int32)
        new = create_model(variant, update, LinearModel(mw, msg_t[k]),
                           LinearModel(prev_w, prev_t), X, y)
        new_ws.append(new.w)
        new_ts.append(new.t)
        # round k writes slot (ptr + #valid rounds before k) % C; later
        # rounds win on collision (only when K > C), like sequential adds
        slot_k = (cache.ptr + off) % C
        sel = jnp.where((iota_c == slot_k[:, None]) & vm[:, None], k, sel)
        off = off + vm.astype(jnp.int32)
        last_k = jnp.where(vm, k, last_k)
        prev_w = jnp.where(vm[:, None], mw, prev_w)
        prev_t = jnp.where(vm, msg_t[k], prev_t)

    new_w = jnp.stack(new_ws)                           # (K, n, d)
    new_t = jnp.stack(new_ts)
    hit = sel >= 0
    selc = jnp.maximum(sel, 0)
    cw = jnp.where(hit[:, :, None], new_w[selc, rows[:, None]], cache.w)
    ct = jnp.where(hit, new_t[selc, rows[:, None]], cache.t)
    new_cache = ModelCache(cw, ct, cache.ptr + off,
                           jnp.minimum(cache.count + off, C))

    got_any = off > 0
    fw = jnp.where(got_any[:, None], new_w[last_k, rows], fresh_w)
    ft = jnp.where(got_any, new_t[last_k, rows], fresh_t)
    return prev_w, prev_t, fw, ft, new_cache, gated, clipped


def _pallas_apply(lam: float, interpret: bool, wire, defense: str = "none"):
    """Receive application backed by the fused Pallas gossip-cycle kernel.

    Quantized wire payloads pass straight through: ``msg_w`` stays in the
    codec's packed representation and the per-message f16 ``msg_scale``
    (plus ``msg_zp`` for the affine int8 family) ride along — the kernel
    decodes in VMEM (affine dequant, int4 nibble unpack, base-3 ternary
    unpack), so HBM message traffic is paid at wire precision. The
    ``defense`` screen runs in-kernel between the decode and the merge
    (same round-chain placement as the jnp paths)."""
    from repro.kernels.gossip_cycle import fused_receive_apply

    def apply_fn(last_w, last_t, fresh_w, fresh_t, cache, msg_w, msg_t,
                 valid, X, y, *, variant, update, msg_scale=None,
                 msg_zp=None):
        del update  # the kernel implements the Pegasos step itself
        lw, lt, fw, ft, cw, ct, ptr, cnt, gated, clipped = \
            fused_receive_apply(
                last_w, last_t, fresh_w, fresh_t, cache.w, cache.t,
                cache.ptr, cache.count, msg_w, msg_t,
                valid.astype(jnp.int32), X, y, msg_scale=msg_scale,
                msg_zp=msg_zp, wire=wire, variant=variant, lam=lam,
                interpret=interpret, defense=defense)
        return lw, lt, fw, ft, ModelCache(cw, ct, ptr, cnt), gated, clipped

    return apply_fn


def _shard_apply(base_apply, mesh, axis: str):
    """Wrap a receive application in shard_map over the node axis.

    Every operand carries the node dimension (leading for state/example
    arrays, second for the (K, N, ...) message stack) and the computation is
    purely per-node, so the body needs no collectives. The optional
    ``msg_scale``/``msg_zp`` metadata of the quantized Pallas path shards
    like the message stack (scale-only codecs pass no ``msg_zp``)."""
    ps_n, ps_kn = PS(axis), PS(None, axis)

    def apply_fn(last_w, last_t, fresh_w, fresh_t, cache, msg_w, msg_t,
                 valid, X, y, *, variant, update, msg_scale=None,
                 msg_zp=None):
        meta = [(k, v) for k, v in (("msg_scale", msg_scale),
                                    ("msg_zp", msg_zp)) if v is not None]

        def inner(lw, lt, fw, ft, cw, ct, cp, cc, mw, mt, vl, Xs, ys,
                  *meta_vals):
            kw = dict(zip((k for k, _ in meta), meta_vals))
            lw2, lt2, fw2, ft2, c2, g2, cl2 = base_apply(
                lw, lt, fw, ft, ModelCache(cw, ct, cp, cc), mw, mt, vl,
                Xs, ys, variant=variant, update=update, **kw)
            return (lw2, lt2, fw2, ft2, c2.w, c2.t, c2.ptr, c2.count,
                    g2, cl2)

        in_specs = (ps_n,) * 8 + (ps_kn,) * 3 + (ps_n,) * 2 \
            + (ps_kn,) * len(meta)
        args = [last_w, last_t, fresh_w, fresh_t, cache.w, cache.t,
                cache.ptr, cache.count, msg_w, msg_t, valid, X, y] \
            + [v for _, v in meta]
        f = shard_map_compat(inner, mesh=mesh, in_specs=in_specs,
                             out_specs=(ps_n,) * 10)
        lw2, lt2, fw2, ft2, cw, ct, cp, cc, g2, cl2 = f(*args)
        return lw2, lt2, fw2, ft2, ModelCache(cw, ct, cp, cc), g2, cl2

    return apply_fn


def _take_slots(buf, slots):
    """The messages at flat slot ids (row·N + sender) of a (D, N, ...)
    buffer lane: ``buf.reshape(D*N, ...)[slots]``."""
    return buf.reshape((-1,) + buf.shape[2:])[slots]


def _as_i32(x):
    """Payload bits widened to int32 (exact, and inverted by _from_i32)."""
    if x.dtype.itemsize == 4:
        return lax.bitcast_convert_type(x, jnp.int32)
    if jnp.issubdtype(x.dtype, jnp.floating):
        x = lax.bitcast_convert_type(x, jnp.uint16)
    return x.astype(jnp.int32)


def _from_i32(x, dtype):
    dtype = jnp.dtype(dtype)
    if dtype.itemsize == 4:
        return lax.bitcast_convert_type(x, dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return lax.bitcast_convert_type(x.astype(jnp.uint16), dtype)
    return x.astype(dtype)


def _mesh_take_slots(mesh, axis: str):
    """:func:`_take_slots` for a buffer whose node axis is sharded over
    ``axis``, with the slot columns sharded the same way.

    Senders and receivers sit on different shards, and a gather with
    global slot ids from a node-sharded buffer makes XLA all-gather the
    whole buffer onto every device. Instead each shard's block of messages
    travels round a ring: the block bound for shard j starts on shard j+1
    and moves one shard on per step, and every shard it visits adds the
    messages of the senders it holds (zeros for the others); after a full
    turn it is home. Each sum has one non-zero term, taken over the
    payload's bits widened to int32, so the messages arrive bit for bit.
    Rows and ring steps are loops, so a device holds one row's block and
    its one contribution at a time."""
    shards = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    ring = [(i, (i + 1) % shards) for i in range(shards)]

    def take(buf, slots):
        n, rest = buf.shape[1], buf.shape[2:]
        m = n // shards
        rows = slots.reshape(-1, slots.shape[-1])
        w = rows.shape[1] // shards          # slot columns per shard

        def local(buf_l, rows):
            flat = buf_l.reshape((-1,) + rest)
            me = lax.axis_index(axis)

            def part(row, dst):
                """This shard's messages for the columns of shard dst."""
                cols = lax.dynamic_slice_in_dim(row, dst * w, w)
                off = cols % n - me * m
                mine = (off >= 0) & (off < m)
                got = flat[jnp.where(mine, cols // n * m + off, 0)]
                keep = mine.reshape(mine.shape + (1,) * len(rest))
                return jnp.where(keep, _as_i32(got), 0)

            def one_row(_, row):
                # after step t this shard holds the block for shard me-t-1
                def step(t, acc):
                    acc = lax.ppermute(acc, axis, ring)
                    return acc + part(row, (me - t - 1) % shards)
                return None, lax.fori_loop(1, shards, step,
                                           part(row, (me - 1) % shards))

            with jax.named_scope("ring"):
                _, out = lax.scan(one_row, None, rows)
            return _from_i32(out, buf.dtype)

        out = shard_map_compat(local, mesh=mesh, in_specs=(PS(None, axis),
                                                           PS()),
                               out_specs=PS(None, axis))(buf, rows)
        return out.reshape(slots.shape + rest)

    return take


def _mesh_eval(mesh, axis: str):
    """``simulation._eval`` for a node-sharded cache: every device takes the
    eval nodes it holds (zeros elsewhere) and one all-reduce over the int32
    bits assembles the subset on every device — exact, and without the
    all-gather of the cache that slicing a sharded array would cost."""
    def take(a, idx):
        def local(a_l, idx):
            m = a_l.shape[0]
            off = idx - lax.axis_index(axis) * m
            mine = (off >= 0) & (off < m)
            got = cache_mod.take_nodes(a_l, jnp.clip(off, 0, m - 1))
            keep = mine.reshape(mine.shape + (1,) * (a_l.ndim - 1))
            return _from_i32(lax.psum(jnp.where(keep, _as_i32(got), 0),
                                      axis), a.dtype)
        return shard_map_compat(local, mesh=mesh, in_specs=(PS(axis), PS()),
                                out_specs=PS())(a, idx)

    def evaluate(cache, eval_idx, X_test, y_test):
        sub = ModelCache(*(take(a, eval_idx) for a in cache))
        return eval_subset(sub, X_test, y_test)

    return evaluate


# jitted chunk runners by config label, for compile-count accounting:
# tools/lint/retrace_guard.py reads these via retrace_counts() and fails
# when a run compiles more signatures than its pinned budget
_CHUNK_FNS: Dict[str, Any] = {}


def retrace_counts() -> Dict[str, int]:
    """Compile-cache entry counts of the engine's jitted hot-path fns.

    One entry per distinct traced signature (shape/dtype/static-arg
    combination); a run that keeps compiling — chunk-length churn, packed
    widths that never go sticky, a dtype flapping between chunks — shows up
    here long before it shows up as a bench rate."""
    counts = {"sharded_engine._draw_chunk": _draw_chunk._cache_size()}
    for label, fn in _CHUNK_FNS.items():
        counts[f"sharded_engine.chunk_fn[{label}]"] = fn._cache_size()
    return counts


@functools.lru_cache(maxsize=64)
def _build_chunk_fn(variant: str, learner: str, lam: float, eta: float,
                    delay_max: int, use_pallas: bool, interpret: bool,
                    mesh, axis: Optional[str], mode: str,
                    wire: Optional[str], use_send_kernel: bool,
                    fault_model: Optional[str] = None,
                    defense: str = "none", emit_streams: bool = False):
    """Jitted data-plane chunk runner, cached per configuration.

    Caching the jitted callable (rather than rebuilding the closure per
    ``run_sharded_simulation`` call) lets XLA's compile cache hit across
    runs — a benchmark sweep compiles each (chunk-length, N) combination
    once, not once per call.

    ``mode`` selects the receive-apply packing (chosen per chunk by the
    driver from the router's observed occupancy — see
    ``run_sharded_simulation``):

    * ``"dense"``       — the (T, K, n) table, K-round apply over all N;
    * ``"compact"``     — round 1 dense, rounds >= 2 gathered/applied/
                          scattered over the multi-receiver subset;
    * ``"compact_all"`` — ALL rounds over the gathered round-1 receiver
                          subset: per-cycle apply cost tracks delivered
                          messages, the sparse-delivery hot path.

    Both compact modes run under a node mesh too: the router packs the
    subset tables per node shard (``pack_compact_rounds``/
    ``pack_compact_all`` with ``shards`` = mesh axis size), so the packed
    axis shards like the node axis and the subset apply stays inside
    ``shard_map``. Only the Pallas *receive* kernel still requires the
    dense table (its grid covers all node blocks).

    ``wire`` is the wire-codec name. Quantized codecs encode at send
    (per-message f16 scale — plus a zero-point for the affine int8 family —
    written into the buf_scale/buf_zp carry lanes) and decode at delivery —
    in the scan body for the jnp paths, in VMEM for the Pallas kernel.
    "int8_sr" derives its per-cycle stochastic-rounding key from the
    scanned key stream exactly like the reference engine's ``k_recv``
    (first slot of the 4-way split), so cross-engine parity stays bitwise.
    The ``_ef`` codecs carry the (N, d) f32 error-feedback residual and
    scan the router's per-cycle send mask (dense/compact modes) or refresh
    the sender subset (``compact_all``) — the residual updates exactly
    where the reference engine's ``send_ok`` holds. ``use_send_kernel``
    routes the send-side quantization through the fused Pallas
    ``quantize_send`` kernel (in-kernel threefry for the SR draw; fused
    pack + EF-residual output for the sub-4-bit codecs) instead of the jnp
    codec ops — bitwise-identical by contract.

    ``fault_model``/``defense`` (static, from ``cfg``) thread
    ``repro.core.faults`` through the data plane: model-kind faults
    corrupt the Byzantine rows' ``(send_w, send_t)`` before the wire
    encode (``compact_all`` corrupts only the sender subset,
    ``rows=``-exact), the wire-kind "bitflip" rewrites the encoded
    payload after the EF-residual update, and the defense screen runs
    per round inside every apply path. The fault key is the reference
    engine's ``fault_key`` fold-in from the scanned cycle key, so both
    engines draw identical corruption — and fault-free chunk fns are
    built with ``fault_model=None``, leaving their traces unchanged.

    ``emit_streams`` (static, set by an armed ``telemetry=``) makes the
    chunk fn return the scan's per-cycle (T,) gated/clipped int32 arrays
    instead of their jitted sums — the driver sums on the host (exact for
    integers) and emits them as per-cycle streams. Unarmed runs keep the
    pre-telemetry program byte for byte; armed fns get a distinct
    "/telem" label (and their own retrace budget).

    The scan body's stages run under ``jax.named_scope``s — ``gather``,
    ``receive``, ``send``, ``eval``, and ``ring`` in the mesh's message
    gather — which name them in a device profile's op metadata and change
    nothing else in the compiled program."""
    update = make_update(learner, lam=lam, eta=eta)
    fault = faults_mod.get_fault(fault_model)
    apply_fn = (_pallas_apply(lam, interpret, wire, defense) if use_pallas
                else functools.partial(_vector_apply, defense=defense))
    if mesh is not None and axis is not None:
        apply_fn = _shard_apply(apply_fn, mesh, axis)
    if mode != "dense" and use_pallas:
        raise ValueError("compacted rounds require the vector apply "
                         "(the Pallas receive kernel is dense)")
    D = delay_max
    codec = get_codec(wire)
    sharded = mesh is not None and axis is not None
    take = _mesh_take_slots(mesh, axis) if sharded else _take_slots
    evaluate = _mesh_eval(mesh, axis) if sharded else _eval
    if use_send_kernel:
        from repro.kernels.gossip_cycle import quantize_send

    def chunk_fn(carry, tables, keydata, X, y, X_test, y_test, eval_idx,
                 byz):
        def records(clock):
            if X.ndim == 3:                   # multi-record nodes
                rec = clock % X.shape[1]
                return X[:, rec, :], y[:, rec]
            return X, y

        def gather(buf_w, buf_scale, buf_zp, idx, d):
            """Winning payloads for slot table ``idx``, decoded for the
            jnp apply paths; the Pallas path gets the raw packed codes plus
            their scale (and zero-point when the codec carries one) as
            kwargs — in-kernel decode."""
            msg_w = take(buf_w, idx)
            if not codec.quantized:
                return msg_w, {}
            msc = take(buf_scale, idx)
            mzp = take(buf_zp, idx) if codec.has_zp else None
            if use_pallas:
                extra = dict(msg_scale=msc)
                if codec.has_zp:
                    extra["msg_zp"] = mzp
                return msg_w, extra
            return codec.decode(msg_w, msc, mzp, d), {}

        def send(buf_w, buf_scale, buf_zp, ef, send_w, clock, kd, smask):
            """Refresh this cycle's buffer row (encoding on the way in).

            ``send_w`` is the (possibly fault-corrupted) transmitted model;
            ``smask`` (the router's per-cycle ``arrival >= 0`` == the
            reference engine's ``send_ok``) gates the EF-residual refresh;
            it is only scanned when the codec keeps EF state. The
            wire-kind "bitflip" fault rewrites the payload AFTER the
            EF-residual update — the honest sender's bookkeeping is
            computed from what it encoded, not what the channel delivers
            (same ordering as ``cycle_core``)."""
            row = clock % D
            x = send_w + ef if codec.ef else send_w
            if not codec.quantized:
                q, sc, zp = x.astype(buf_w.dtype), None, None
            else:
                key = None
                if codec.stochastic:
                    # k_recv: slot 0 of the reference per-cycle split
                    key = jax.random.split(
                        jax.random.wrap_key_data(kd), 4)[0]
                if use_send_kernel:
                    outs = quantize_send(
                        send_w, wire,
                        key_data=(jax.random.key_data(key)
                                  if codec.stochastic else None),
                        ef=ef if codec.ef else None, interpret=interpret)
                    if codec.has_zp:
                        q, sc, zp = outs
                    elif codec.ef:
                        (q, sc), zp = outs[:2], None
                        resid = outs[2]
                    else:
                        (q, sc), zp = outs, None
                else:
                    q, sc, zp = codec.encode(x, key=key)
                    if codec.ef:
                        resid = x - codec.decode(q, sc, zp,
                                                 send_w.shape[-1])
                if codec.ef:
                    ef = jnp.where(smask[:, None], resid, ef)
            if fault is not None and fault.kind == "wire":
                q = faults_mod.bitflip_payload(
                    byz, faults_mod.fault_key(jax.random.wrap_key_data(kd)),
                    q)
            if not codec.quantized:
                return buf_w.at[row].set(q), buf_scale, buf_zp, ef
            return (buf_w.at[row].set(q), buf_scale.at[row].set(sc),
                    buf_zp.at[row].set(zp) if codec.has_zp else buf_zp, ef)

        def corrupt_send(fresh_w, fresh_t, cache, kd):
            """Model-kind fault: the Byzantine rows' transmitted model —
            a static no-op when faults are off or wire-kind."""
            if fault is None or fault.kind != "model":
                return fresh_w, fresh_t
            old_w = old_t = None
            if fault.name == "stale_replay":
                old_w, old_t = cache_mod.cache_oldest(cache)
            return faults_mod.corrupt_model(
                fault, byz, faults_mod.fault_key(
                    jax.random.wrap_key_data(kd)),
                fresh_w, fresh_t, old_w, old_t)

        def dense_body(carry, inp):
            (last_w, last_t, fresh_w, fresh_t, cw, ct, ptr, cnt,
             buf_w, buf_t, buf_scale, buf_zp, ef, clock) = carry
            (src_slot, *sm), kd = inp
            valid = src_slot >= 0             # (K, n); -1 = no receive
            idx = jnp.maximum(src_slot, 0)
            n, d = last_w.shape
            Xc, yc = records(clock)
            with jax.named_scope("gather"):
                msg_w, extra = gather(buf_w, buf_scale, buf_zp, idx, d)
                msg_t = take(buf_t, idx)
            with jax.named_scope("receive"):
                last_w, last_t, fresh_w, fresh_t, cache, gated, clipped = \
                    apply_fn(
                        last_w, last_t, fresh_w, fresh_t,
                        ModelCache(cw, ct, ptr, cnt), msg_w, msg_t, valid,
                        Xc, yc, variant=variant, update=update, **extra)
            with jax.named_scope("send"):
                send_w, send_t = corrupt_send(fresh_w, fresh_t, cache, kd)
                buf_w, buf_scale, buf_zp, ef = send(
                    buf_w, buf_scale, buf_zp, ef, send_w, clock, kd,
                    sm[0] if sm else None)
                buf_t = buf_t.at[clock % D].set(send_t)
            return (last_w, last_t, fresh_w, fresh_t, cache.w, cache.t,
                    cache.ptr, cache.count, buf_w, buf_t, buf_scale, buf_zp,
                    ef, clock + 1), (jnp.sum(gated), jnp.sum(clipped))

        def subset_apply(state, ridx, rslot, Xc, yc, buf_w, buf_scale,
                         buf_zp, buf_t):
            """Gather the ``ridx`` subset, run the (K', W)-round chain on
            it, scatter back — the shared core of both compact modes. Work
            tracks the packed width, not N; padding (-1) gathers node 0
            with an all-False valid mask and scatters out of bounds
            (dropped), so it is inert."""
            last_w, last_t, fresh_w, fresh_t, cache = state
            n, d = last_w.shape
            pad = ridx < 0
            gi = jnp.maximum(ridx, 0)
            vc = (rslot >= 0) & (~pad)[None, :]
            sc = jnp.maximum(rslot, 0)
            sub = ModelCache(cache.w[gi], cache.t[gi], cache.ptr[gi],
                             cache.count[gi])
            with jax.named_scope("gather"):
                msg_w, _ = gather(buf_w, buf_scale, buf_zp, sc, d)
            with jax.named_scope("receive"):
                lw2, lt2, fw2, ft2, sub2, g2, cl2 = apply_fn(
                    last_w[gi], last_t[gi], fresh_w[gi], fresh_t[gi], sub,
                    msg_w, take(buf_t, sc), vc, Xc[gi], yc[gi],
                    variant=variant, update=update)
            si = jnp.where(pad, n, gi)        # out of bounds => dropped
            last_w = last_w.at[si].set(lw2, mode="drop")
            last_t = last_t.at[si].set(lt2, mode="drop")
            fresh_w = fresh_w.at[si].set(fw2, mode="drop")
            fresh_t = fresh_t.at[si].set(ft2, mode="drop")
            cache = ModelCache(cache.w.at[si].set(sub2.w, mode="drop"),
                               cache.t.at[si].set(sub2.t, mode="drop"),
                               cache.ptr.at[si].set(sub2.ptr, mode="drop"),
                               cache.count.at[si].set(sub2.count,
                                                      mode="drop"))
            # pad rows carry valid=False everywhere, so their screen
            # counts are structurally zero — a plain sum is exact
            return (last_w, last_t, fresh_w, fresh_t, cache,
                    jnp.sum(g2), jnp.sum(cl2))

        def compact_body(carry, inp):
            (last_w, last_t, fresh_w, fresh_t, cw, ct, ptr, cnt,
             buf_w, buf_t, buf_scale, buf_zp, ef, clock) = carry
            (src0, ridx, rslot, *sm), kd = inp
            n, d = last_w.shape
            Xc, yc = records(clock)
            # round 1, dense over all nodes (same math as a K=1 dense apply)
            i0 = jnp.maximum(src0, 0)
            with jax.named_scope("gather"):
                msg_w0, _ = gather(buf_w, buf_scale, buf_zp, i0[None], d)
            with jax.named_scope("receive"):
                last_w, last_t, fresh_w, fresh_t, cache, g0, cl0 = apply_fn(
                    last_w, last_t, fresh_w, fresh_t,
                    ModelCache(cw, ct, ptr, cnt), msg_w0,
                    take(buf_t, i0[None]), (src0 >= 0)[None], Xc, yc,
                    variant=variant, update=update)
            # rounds >= 2: continue the chain on the multi-receiver subset
            # (their lastModel already holds the round-1 message)
            last_w, last_t, fresh_w, fresh_t, cache, g2, cl2 = subset_apply(
                (last_w, last_t, fresh_w, fresh_t, cache), ridx, rslot,
                Xc, yc, buf_w, buf_scale, buf_zp, buf_t)
            with jax.named_scope("send"):
                send_w, send_t = corrupt_send(fresh_w, fresh_t, cache, kd)
                buf_w, buf_scale, buf_zp, ef = send(
                    buf_w, buf_scale, buf_zp, ef, send_w, clock, kd,
                    sm[0] if sm else None)
                buf_t = buf_t.at[clock % D].set(send_t)
            return (last_w, last_t, fresh_w, fresh_t, cache.w, cache.t,
                    cache.ptr, cache.count, buf_w, buf_t, buf_scale, buf_zp,
                    ef, clock + 1), (jnp.sum(g0) + g2, jnp.sum(cl0) + cl2)

        def send_compact(buf_w, buf_t, buf_scale, buf_zp, ef, fresh_w,
                         fresh_t, clock, kd, sidx, cache):
            """Refresh only the SENDERS' slots of this cycle's buffer row.

            In sparse regimes most nodes are offline or drop their send;
            their slots keep stale payloads that the router provably never
            routes (only ``arrival >= 0`` messages enter the pending set),
            so writing — and for the quantized codecs, encoding — just the
            ``sidx`` subset is exact. The "int8_sr" noise is regenerated at
            the senders' positions (``sr_noise_for_rows``), bitwise-equal
            to the dense ``jax.random.uniform`` draw at those rows; the
            ``_ef`` codecs gather/refresh/scatter only the senders'
            residual rows — exactly the rows the reference engine's
            ``send_ok`` mask refreshes. Fault corruption stays
            sender-proportional too: model-kind faults corrupt the
            gathered subset (``rows=gi`` regenerates the dense
            ``random_payload`` draw at the senders' global rows), the
            wire-kind bitflip flips the subset's encoded payloads — both
            bitwise-equal to the reference engine at the routed rows."""
            n, d = fresh_w.shape
            pad = sidx < 0
            gi = jnp.maximum(sidx, 0)
            si = jnp.where(pad, n, gi)        # out of bounds => dropped
            row = clock % D
            send_w, send_t = fresh_w[gi], fresh_t[gi]
            if fault is not None and fault.kind == "model":
                old_w = old_t = None
                if fault.name == "stale_replay":
                    old_w, old_t = cache_mod.cache_oldest(ModelCache(
                        cache.w[gi], cache.t[gi], cache.ptr[gi],
                        cache.count[gi]))
                send_w, send_t = faults_mod.corrupt_model(
                    fault, byz[gi], faults_mod.fault_key(
                        jax.random.wrap_key_data(kd)),
                    send_w, send_t, old_w, old_t, rows=gi)
            sub_x = send_w + ef[gi] if codec.ef else send_w
            if not codec.quantized:
                q = sub_x.astype(buf_w.dtype)
            else:
                noise = None
                if codec.stochastic:
                    key = jax.random.split(
                        jax.random.wrap_key_data(kd), 4)[0]
                    noise = sr_noise_for_rows(key, gi, d)
                q, sc, zp = codec.encode(sub_x, noise=noise)
                if codec.ef:
                    resid = sub_x - codec.decode(q, sc, zp, d)
                    ef = ef.at[si].set(resid, mode="drop")
            if fault is not None and fault.kind == "wire":
                q = faults_mod.bitflip_payload(
                    byz[gi], faults_mod.fault_key(
                        jax.random.wrap_key_data(kd)),
                    q, rows=gi)
            buf_w = buf_w.at[row, si].set(q, mode="drop")
            if codec.quantized:
                buf_scale = buf_scale.at[row, si].set(sc, mode="drop")
                if codec.has_zp:
                    buf_zp = buf_zp.at[row, si].set(zp, mode="drop")
            buf_t = buf_t.at[row, si].set(send_t, mode="drop")
            return buf_w, buf_t, buf_scale, buf_zp, ef

        def compact_all_body(carry, inp):
            (last_w, last_t, fresh_w, fresh_t, cw, ct, ptr, cnt,
             buf_w, buf_t, buf_scale, buf_zp, ef, clock) = carry
            (ridx, rslot, sidx), kd = inp
            Xc, yc = records(clock)
            # every round over the round-1 receiver subset: non-receivers
            # are never touched, so per-cycle apply cost is
            # delivery-proportional (the sparse-delivery hot path) — and
            # the send refresh (buffer slots AND EF residuals) is
            # sender-proportional to match
            last_w, last_t, fresh_w, fresh_t, cache, g2, cl2 = subset_apply(
                (last_w, last_t, fresh_w, fresh_t,
                 ModelCache(cw, ct, ptr, cnt)), ridx, rslot,
                Xc, yc, buf_w, buf_scale, buf_zp, buf_t)
            with jax.named_scope("send"):
                buf_w, buf_t, buf_scale, buf_zp, ef = send_compact(
                    buf_w, buf_t, buf_scale, buf_zp, ef, fresh_w, fresh_t,
                    clock, kd, sidx, cache)
            return (last_w, last_t, fresh_w, fresh_t, cache.w, cache.t,
                    cache.ptr, cache.count, buf_w, buf_t, buf_scale, buf_zp,
                    ef, clock + 1), (g2, cl2)

        body = {"dense": dense_body, "compact": compact_body,
                "compact_all": compact_all_body}[mode]
        carry, (g_cycles, cl_cycles) = lax.scan(body, carry,
                                                (tables, keydata))
        cache = ModelCache(carry[4], carry[5], carry[6], carry[7])
        with jax.named_scope("eval"):
            errs = evaluate(cache, eval_idx, X_test, y_test)
        if emit_streams:
            return carry, (errs, (g_cycles, cl_cycles))
        return carry, (errs, (jnp.sum(g_cycles), jnp.sum(cl_cycles)))

    jitted = jax.jit(chunk_fn, donate_argnums=(0,))
    # the index prefix keeps labels unique when configs differ only in a
    # field the label omits (lam, eta, mesh, ...); the fault/defense
    # suffixes appear only when active, so fault-free runs keep their
    # pre-fault labels (and retrace budgets); "/interp" marks Pallas
    # kernels run in interpret mode, so a chip run can assert it ran none
    label = (f"{len(_CHUNK_FNS)}:{variant}/{learner}/{mode}/{wire or 'f32'}"
             + ("/pallas" if use_pallas else "")
             + ("/sendk" if use_send_kernel else "")
             + ("/interp" if interpret and (use_pallas or use_send_kernel)
                else "")
             + (f"/fault:{fault_model}" if fault_model else "")
             + (f"/def:{defense}" if defense != "none" else "")
             + ("/telem" if emit_streams else ""))
    _CHUNK_FNS[label] = jitted
    return jitted


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run_sharded_simulation(cfg: GossipLinearConfig, X, y, X_test, y_test, *,
                           cycles: int = 200, eval_every: int = 10,
                           seed: int = 0, eval_nodes: int = 100,
                           sampler: str = "uniform", k_rounds: int = 4,
                           mesh=None, node_axis: Optional[str] = None,
                           use_pallas: Optional[bool] = None,
                           interpret: bool = False,
                           compact_rounds: Optional[bool] = None,
                           compact_mode: Optional[str] = None,
                           use_send_kernel: Optional[bool] = None,
                           serve_hook=None, telemetry=None
                           ) -> SimResult:
    """Run the protocol with the sharded mega-population engine.

    ``mesh``: optional ``jax.sharding.Mesh``; the node axis is split over
    ``node_axis`` (default: the mesh's first axis) — N must be divisible by
    that axis size. ``use_pallas`` selects the fused cycle kernel (default:
    on a TPU backend with the Pegasos learner, the only one the kernel
    implements; asking for it with another learner is an error);
    ``interpret`` runs the Pallas kernels in interpret mode, for CPU
    tests. Neither is switched on or off behind the caller's back: Pallas
    without ``interpret`` on a backend that is not a TPU fails to lower.

    ``compact_rounds`` allows the compacted receive paths (default: on
    whenever the vector apply runs, i.e. no Pallas — meshes included, via
    per-shard packed tables). Per chunk the driver picks the cheapest of
    three packings from the router's observed occupancy — ``"dense"``
    (K rounds over all N), ``"compact"`` (round 1 dense, rounds >= 2 over
    the multi-receiver subset) and ``"compact_all"`` (every round over the
    round-1 receiver subset; in sparse-delivery regimes per-cycle apply
    cost tracks delivered messages instead of N) — falling back to dense
    when a subset is near-full (> N/2). ``compact_mode`` forces one packing
    for every chunk (benchmarks pin the PR 3 behavior with
    ``compact_mode="compact"``).

    ``cfg.wire_dtype`` names a wire codec (``repro.core.wire_codec``:
    "bf16"/"f16"/"int8"/"int8_sr"/"int4"/"int4_ef"/"ternary"/"ternary_ef")
    and stores the in-flight payload buffer — the engine's dominant memory
    — in the codec's packed representation (quantized codecs add the
    (D, N) f16 scale lane, the affine int8 family a zero-point lane, the
    ``_ef`` codecs the (N, d) f32 error-feedback residual); merge math
    stays f32 and the identical encoding is applied by the reference
    engine, so cross-engine parity holds under quantization too, including
    the stochastic-rounding noise (both engines draw it from the same
    per-cycle ``k_recv`` threefry slot) and the EF residual chain (updated
    exactly on the reference engine's ``send_ok`` cycles — the
    dense/compact bodies scan the router's send mask, ``compact_all``
    refreshes the sender subset). ``use_send_kernel`` fuses the send-side
    quantization into the Pallas ``quantize_send`` kernel (default: with
    ``use_pallas`` on quantized codecs, no mesh) — the kernel reproduces
    the jnp codec bitwise, including the in-kernel threefry draw of the
    "int8_sr" noise and the packed codecs' EF residual. Chunks running the
    ``compact_all`` packing go one step further regardless of the flag:
    they encode only the sender subset (``sr_noise_for_rows`` keeps the
    noise positionally identical), which strictly dominates a
    full-population kernel pass.

    ``serve_hook``: optional ``hook(cycle, snapshot)`` — the live serving
    surface. Called at every eval point (chunk boundary) with a
    ``repro.core.serving.QuerySnapshot`` built from the scan carry, a pure
    read of the live cache lanes: bitwise identical to the reference
    engine's snapshot at the same cycle, and provably non-perturbing (the
    scan never observes the hook). The hook must consume the snapshot
    before the next chunk runs — the chunk fn donates its carry.

    Host spans (``repro.core.telemetry.span``), always recorded: ``setup``
    (``h2d_bytes``), ``key_schedule``, ``stage_draws`` (``d2h_bytes``),
    per chunk ``route_chunk`` (``sends``, ``deliveries``, ``mode``) with
    its child ``pack_tables``, ``upload_tables`` (``h2d_bytes``),
    ``dispatch`` and ``snapshot``, and the final ``collect_results``.

    ``telemetry``: optional ``repro.core.telemetry.Telemetry`` — same
    pure-read discipline as ``serve_hook``. Armed runs emit the registered
    per-cycle metric streams (the router's per-cycle message economy, the
    scan's per-cycle gated/clipped counts via the "/telem" chunk-fn
    variant) bitwise-equal to the reference engine's streams under every
    packing. One armed cost is paid eagerly: the ``_ef`` codecs sync the
    EF-residual RMS at each eval point (the float read must happen before
    the next chunk donates the carry); everything else stays pipelined."""
    n, d = X.shape[0], X.shape[-1]
    D = max(cfg.delay_max_cycles, 1)
    codec = get_codec(cfg.wire_dtype)
    with telemetry_mod.span("setup") as sp:
        setup = sim_setup(cfg, X, y, X_test, y_test, cycles=cycles,
                          seed=seed, eval_nodes=eval_nodes)
        sp.counters["h2d_bytes"] = setup_h2d_bytes((X, y, X_test, y_test),
                                                   setup)
    online_mat, eval_idx, X, y, X_test, y_test = setup

    if use_pallas is None:
        use_pallas = (jax.default_backend() == "tpu"
                      and cfg.learner == "pegasos")
    elif use_pallas and cfg.learner != "pegasos":
        raise ValueError(f"the Pallas receive kernel implements the Pegasos "
                         f"update only, not {cfg.learner!r}")

    node_sharding = None
    axis = None
    shards = 1
    if mesh is not None:
        axis = node_axis or mesh.axis_names[0]
        axis_size = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
        if axis_size > 1:
            if n % axis_size != 0:
                raise ValueError(
                    f"sharded engine needs N divisible by the '{axis}' mesh "
                    f"axis ({n} % {axis_size} != 0)")
            node_sharding = NamedSharding(mesh, PS(axis))
            shards = axis_size
        else:
            mesh = axis = None

    if compact_rounds is None:
        compact_rounds = not use_pallas
    if compact_mode is not None:
        if compact_mode not in ("dense", "compact", "compact_all"):
            raise ValueError(f"unknown compact_mode {compact_mode!r}")
        if compact_mode == "compact" and k_rounds == 1:
            raise ValueError("compact_mode='compact' needs k_rounds > 1 "
                             "(there are no rounds >= 2 to compact)")
        if compact_mode != "dense" and use_pallas:
            raise ValueError("compacted rounds require the vector apply "
                             "(the Pallas receive kernel is dense)")
        compact_rounds = compact_mode != "dense"
    if use_send_kernel is None:
        use_send_kernel = use_pallas and codec.quantized and mesh is None
    elif use_send_kernel:
        if not codec.quantized:
            raise ValueError("use_send_kernel needs a quantized (int8 or "
                             "sub-4-bit) wire dtype — float wire dtypes "
                             "send a plain cast")
        if mesh is not None:
            raise ValueError("the Pallas send kernel does not run under a "
                             "node mesh")

    faults_mod.check_defense(cfg.defense)
    byz = byz_np = None
    if cfg.fault_model is not None:
        faults_mod.get_fault(cfg.fault_model)   # fail fast on unknown names
        byz_np = faults_mod.byzantine_mask(seed, n, cfg.byzantine_frac)
        byz = jnp.asarray(byz_np)

    tel = telemetry
    armed = tel is not None

    def get_chunk_fn(mode: str):
        return _build_chunk_fn(cfg.variant, cfg.learner, cfg.lam, cfg.eta,
                               D, use_pallas, interpret, mesh, axis, mode,
                               cfg.wire_dtype, use_send_kernel,
                               cfg.fault_model, cfg.defense, armed)

    # data-plane carry: models + cache + payload lanes of the buffer (the
    # quantized codecs add the (D, N) f16 scale lane — plus a zero-point
    # lane for the affine int8 family — and the _ef codecs the (N, d) f32
    # error-feedback residual; empty (0, 0) arrays otherwise so the float
    # paths carry nothing extra)
    sc_shape = (D, n) if codec.has_scale else (0, 0)
    zp_shape = (D, n) if codec.has_zp else (0, 0)

    def init_carry():
        return (jnp.zeros((n, d), jnp.float32), jnp.zeros((n,), jnp.int32),
                jnp.zeros((n, d), jnp.float32), jnp.zeros((n,), jnp.int32),
                *cache_mod.init_cache(n, cfg.cache_size, d),
                jnp.zeros((D, n, codec.payload_cols(d)),
                          codec.payload_dtype),
                jnp.zeros((D, n), jnp.int32),
                jnp.zeros(sc_shape, jnp.float16),
                jnp.zeros(zp_shape, jnp.float16),
                jnp.zeros((n, d) if codec.ef else (0, 0), jnp.float32),
                jnp.zeros((), jnp.int32))

    if node_sharding is None:
        carry = init_carry()
    else:
        # every lane is made on its shards: made whole on one device and
        # then split, the state of a population that needs the mesh would
        # not fit that device
        put_n = lambda a: jax.device_put(a, node_sharding) if a.size else a
        dn, rep = NamedSharding(mesh, PS(None, axis)), NamedSharding(mesh, PS())
        lanes = (node_sharding,) * 8 + (dn,) * 4 + (node_sharding, rep)
        carry = jax.jit(init_carry, out_shardings=tuple(
            s if a.size else rep
            for a, s in zip(jax.eval_shape(init_carry), lanes)))()
        X, y = put_n(X), put_n(y)
        if byz is not None:
            byz = put_n(byz)

    res = SimResult([], [], [], [], 0, cfg)
    res.buf_payload_bytes = payload_buffer_bytes(D, n, d, cfg.wire_dtype)
    res.fault_stats = {"corrupted": 0, "gated": 0, "clipped": 0}
    pts = eval_points(cycles, eval_every)
    if not pts:                       # cycles == 0: nothing to simulate
        return res

    with telemetry_mod.span("key_schedule"):
        keys = key_schedule(seed, cycles)
        # raw uint32 key data for the scan body (the SR quantizer re-derives
        # the reference engine's per-cycle k_recv from it; DCE'd when not
        # needed)
        keydata = jnp.asarray(jax.random.key_data(keys))
    router = _HostRouter(n, D)
    bounds = list(zip([0] + pts[:-1], pts))

    def draw(lo, hi):
        dsts, arrivals = _draw_chunk(
            keys[lo:hi], jnp.asarray(online_mat[lo:hi]), jnp.int32(lo), n=n,
            drop=cfg.drop_prob, delay_max=D, sampler=sampler)
        return np.asarray(dsts), np.asarray(arrivals)

    # With all integer draws staged upfront (bounded: 8 bytes/node-cycle),
    # chunk i+1's host routing overlaps chunk i's device scan — the scan is
    # dispatched asynchronously and only the eval results are fetched, once,
    # after the last chunk. Each staged entry is released right after it is
    # routed, so host memory stays bounded by ~one chunk of draw tables.
    prefetch = cycles * n <= 250_000_000
    if prefetch:
        with telemetry_mod.span("stage_draws", chunks=len(bounds)) as sp:
            staged = [draw(lo, hi) for lo, hi in bounds]
            sp.counters["d2h_bytes"] = sum(a.nbytes + b.nbytes
                                           for a, b in staged)

    # compacted-table widths, sticky across chunks (monotone powers of two)
    # so the jitted chunk fn compiles O(log n) times per run, not per chunk
    widths = {"compact": 8, "compact_all": 8, "send": 8}
    mode_counts = {"dense": 0, "compact": 0, "compact_all": 0}
    occ_recv: list = []
    occ_multi: list = []

    def bucket(kind: str, need: int) -> int:
        w = widths[kind]
        while w < need:
            w *= 2
        return w

    def route(i):
        """Route chunk i and pick its packing from the observed occupancy.

        Candidate per-cycle work estimates (in node-row units): dense =
        K·N + N, compact = N + (K+1)·W_multi + N,
        compact_all = (K+4)·W_recv + 5·W_send. The trailing terms charge
        the send-side buffer refresh (dense row write for dense/compact,
        sender-proportional scatter+quantize for compact_all); the +1/+4
        constants charge the subset gather/scatter overhead, calibrated on
        the 2-core bench container so the chooser declines compact_all
        near ~5% occupancy, where the measured crossover sits
        (BENCH_population_scaling.json ``derived`` rows). Estimates use
        the sticky bucketed widths so the choice matches what would
        actually compile; a subset over N/2 disqualifies its packing (the
        dense fallback of PR 2, now per mode)."""
        lo, hi = bounds[i]
        if prefetch:
            dn, an = staged[i]
            staged[i] = None          # satellite fix: bound prefetch memory
        else:
            dn, an = draw(lo, hi)
        win, stats, multi, recv = router.route_chunk(
            dn, an, online_mat[lo:hi], lo, k_rounds, per_cycle_stats=armed)
        stats["recv_sizes"] = np.array([r.size for r in recv], np.int64)
        stats["multi_sizes"] = np.array([r.size for r in multi], np.int64)
        # corrupted = Byzantine senders with send_ok (an >= 0 == the
        # reference engine's send_ok) — pure control-plane info, so the
        # host counts it while the device scan runs payload math
        stats["corrupted"] = (int(byz_np[np.nonzero(an >= 0)[1]].sum())
                              if byz_np is not None else 0)
        T = hi - lo
        if armed:
            # per-cycle sends (and Byzantine sends) straight off the
            # arrival table — armed-only host reductions for the streams
            send_mask = an >= 0
            stats["sent_cycles"] = send_mask.sum(axis=1).astype(np.int64)
            stats["corrupted_cycles"] = (
                (send_mask & byz_np[None, :]).sum(axis=1).astype(np.int64)
                if byz_np is not None else np.zeros(T, np.int64))

        # sender lists cost T flatnonzero passes over (T, N) — build them
        # only when a compact_all packing is actually on the table
        sender_cache: list = []

        def senders():
            if not sender_cache:
                sender_cache.append([np.flatnonzero(an[t] >= 0)
                                     .astype(np.int32) for t in range(T)])
            return sender_cache[0]

        cand = {"dense": k_rounds * n + n}
        wm = w1 = ws = None
        if compact_rounds:
            wm = bucket("compact", shard_list_width(multi, n, shards))
            w1 = bucket("compact_all", shard_list_width(recv, n, shards))
            if k_rounds > 1 and int(stats["multi_sizes"].max(initial=0)) \
                    <= n // 2:
                cand["compact"] = n + (k_rounds + 1) * shards * wm + n
            if int(stats["recv_sizes"].max(initial=0)) <= n // 2:
                ws = bucket("send", shard_list_width(senders(), n, shards))
                cand["compact_all"] = ((k_rounds + 4) * shards * w1
                                       + 5 * shards * ws)
        mode = (compact_mode if compact_mode is not None
                else min(cand, key=cand.get))
        with telemetry_mod.span("pack_tables", mode=mode):
            if mode == "compact":
                widths["compact"] = wm
                tables = pack_compact_rounds(win, multi, T, k_rounds, n, wm,
                                             shards)
            elif mode == "compact_all":
                if ws is None:        # forced mode past the near-full gate
                    ws = bucket("send",
                                shard_list_width(senders(), n, shards))
                widths["compact_all"] = w1
                widths["send"] = ws
                tables = (*pack_compact_all(win, recv, T, k_rounds, n, w1,
                                            shards),
                          _pack_index_lists(senders(), n, ws, shards))
            else:
                tables = (dense_table(win, T, k_rounds, n),)
        if codec.ef and mode != "compact_all":
            # the EF residual refreshes exactly where the reference
            # engine's send_ok holds == where a message entered the pending
            # set; compact_all carries the same information as the packed
            # sender list instead of a dense mask
            tables = (*tables, an >= 0)
        return mode, tables, stats

    def routed(i):
        """``route(i)`` in its span, with the chunk's message counts."""
        with telemetry_mod.span("route_chunk", chunk=i) as sp:
            out = route(i)
            sp.counters.update(sends=out[2]["sent"],
                               deliveries=out[2]["delivered"], mode=out[0])
        return out

    msg_bytes = message_wire_bytes(d, cfg.wire_dtype)
    in_flight = 0
    errs_pending = []
    pending = routed(0)
    for i, p in enumerate(pts):
        lo, hi = bounds[i]
        mode, tables, stats = pending
        with telemetry_mod.span("upload_tables", chunk=i) as sp:
            dev_tables = tuple(jnp.asarray(a) for a in tables)
            sp.counters["h2d_bytes"] = sum(int(a.nbytes) for a in tables)
        with telemetry_mod.span("dispatch", chunk=i, cycles=hi - lo):
            carry, (errs, fstats) = get_chunk_fn(mode)(
                carry, dev_tables, keydata[lo:hi], X, y, X_test, y_test,
                eval_idx, byz)
        # no reference past the dispatch: the tables are freed as soon as
        # the chunk is done with them, not when the next chunk's arrive
        del dev_tables
        if serve_hook is not None:
            # pure read of the fresh carry, dispatched before the next
            # chunk donates it; the scan never observes the hook, so the
            # run is bitwise identical with or without serving
            from repro.core import serving
            with telemetry_mod.span("snapshot", cycle=p):
                serve_hook(p, serving.snapshot_from_carry(carry))
        if armed:
            # the one eager armed read: the EF-residual RMS must be taken
            # before the next chunk donates the carry (a no-op float for
            # non-EF codecs — carry[12] is the empty (0, 0) lane)
            tel.emit("ef_residual_rms", ef_residual_norm(carry[12]))
        if i + 1 < len(pts):
            pending = routed(i + 1)   # overlaps the in-flight device scan
        res.sent_total += stats["sent"]
        res.delivered_total += stats["delivered"]
        res.lost_total += stats["lost"]
        res.overflow_total += stats["overflow"]
        res.fault_stats["corrupted"] += stats["corrupted"]
        res.delivered_per_cycle.extend(
            int(x) for x in stats["delivered_cycles"])
        mode_counts[mode] += 1
        occ_recv.append(stats["recv_sizes"])
        occ_multi.append(stats["multi_sizes"])
        res.cycles.append(p)
        errs_pending.append((errs, fstats))
        if armed:
            # per-cycle streams for this chunk, all host-side numpy on the
            # router's tables — identical numbers under every packing, and
            # bitwise-equal to the reference engine's streams
            sc = stats["sent_cycles"]
            dc = stats["delivered_cycles"]
            flow = np.cumsum(sc - dc - stats["lost_cycles"]
                             - stats["overflow_cycles"]) + in_flight
            in_flight = int(flow[-1])
            tel.emit_row(
                sent=sc, delivered=dc, lost=stats["lost_cycles"],
                overflow=stats["overflow_cycles"], in_flight=flow,
                wire_bytes=sc * msg_bytes,
                recv_nodes=stats["recv_sizes"],
                multi_nodes=stats["multi_sizes"],
                online_nodes=online_mat[lo:hi].sum(axis=1),
                corrupted=stats["corrupted_cycles"])
    with telemetry_mod.span("collect_results", chunks=len(errs_pending)):
        for (err_f, err_v, sim), (g, cl) in errs_pending:
            res.err_fresh.append(float(err_f))
            res.err_voted.append(float(err_v))
            res.similarity.append(float(sim))
            # armed chunk fns return per-cycle (T,) arrays (host-summed —
            # exact for ints); unarmed return the jit-summed scalars
            res.fault_stats["gated"] += int(np.sum(g))
            res.fault_stats["clipped"] += int(np.sum(cl))
            if armed:
                tel.emit("gated", np.asarray(g).reshape(-1))
                tel.emit("clipped", np.asarray(cl).reshape(-1))
    r1 = np.concatenate(occ_recv) / n
    mr = np.concatenate(occ_multi) / n
    res.compaction = dict(
        chunk_modes=dict(mode_counts),
        round1_occupancy_mean=float(r1.mean()),
        round1_occupancy_max=float(r1.max()),
        multi_occupancy_mean=float(mr.mean()),
        multi_occupancy_max=float(mr.max()),
        packed_widths=dict(widths), shards=shards)
    res.wire_bytes_total = res.sent_total * message_wire_bytes(d, cfg.wire_dtype)
    res.ef_residual_norm = ef_residual_norm(carry[12])
    res.in_flight = int(router.p_slot.size)
    if armed:
        tel.annotations.setdefault("runs", []).append(dict(
            engine="sharded", n_nodes=n, cycles=cycles,
            wire_dtype=cfg.wire_dtype or "f32", message_bytes=msg_bytes,
            chunk_modes=dict(mode_counts)))
    return res
