"""Pallas TPU kernel: fused gossip-cycle receive path (deliver -> merge ->
update -> cache-write).

One gossip cycle delivers up to K messages to every node; for each the
protocol runs ``modelCache.add(createModel(m, lastModel)); lastModel <- m``
(Algorithm 1). Executed as separate XLA ops that is, per round: read the
message and the last model, write the merged+updated model, then read-modify-
write the whole (N, C, d) cache — the cache traffic alone is C× the model
traffic. This kernel keeps a node block's last model, its K winning messages,
its local example AND its cache slice resident in VMEM and applies all K
sequential receives in one pass: HBM traffic per node drops from
O(K·(C+3)·d) to the minimal read-once/write-once O((K+C+2)·d).

Supports the three CREATEMODEL variants (RW / MU / UM, Algorithm 2) with the
Pegasos update — the paper's P2Pegasos hot path. Message operands may arrive
in any wire codec (f32/bf16/f16 upcast in VMEM; affine int8 dequantized
in VMEM from per-message f16 scale/zero-point; packed int4 nibbles and
base-3 ternary trits unpacked AND dequantized in VMEM from a per-message
f16 scale), so HBM message traffic is paid at wire precision — half a byte
(int4) or a fifth of a byte (ternary) per coefficient. The pure-jnp oracle
is ``repro.core.simulation.apply_receives``; parity is tested in interpret
mode on CPU (tests/test_sharded_engine.py, tests/test_wire_codec.py).

Where a whole node block does not fit VMEM (the paper's Reuters width,
d=9947), ``fused_receive_apply_dtiled`` does the same receive with the
coefficient axis tiled: a first pass sums each round's Pegasos margin over
the d tiles, a second applies merge, step and cache write tile by tile.
``receive_d_tile`` is the rule that picks between the two from the shapes
(tests/test_receive_dtiled.py).

This module also holds the send-side counterpart, ``quantize_send``: the
per-message encode of any quantized wire codec
(``repro.core.wire_codec``) as one fused pass per node block — affine int8
with the "int8_sr" stochastic-rounding uniform generated *in kernel* by an
op-exact threefry-2x32 (bitwise equal to the ``jax.random.uniform`` draw
of the jnp path, which the engines' parity contract requires), and the
packed sub-4-bit codecs with the code packing and the error-feedback
residual update (``(w + ef) - decode(encode(w + ef))``) fused into the
same pass (tests/test_send_kernel.py).
"""
from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The threefry-2x32 cipher, the counter-at-position uniform it feeds and
# the wire decode/encode constants live in repro.core.wire_codec (shared
# with the jnp codecs and the compacted send path); they are pure jnp
# integer ops, so they trace inside the kernel body too — and integer ops
# are exact, so kernel and jnp paths agree bitwise by construction.
#
# Why not ``pltpu.prng_random_bits``: the TPU-native PRNG is a *different*
# generator — its stream cannot match the ``jax.random.uniform`` draw the
# reference engine (and the jnp ``quantize_wire`` path) consume, and the
# engines' parity contract requires bitwise-identical stochastic-rounding
# noise everywhere. Threefry is 20 rounds of uint32 add/rotate/xor on the
# VPU — cheap relative to the (N, d) HBM traffic this kernel saves.
#
# Layout: node-minor. XLA's TPU layout for the engine's (N, d), (N, C, d)
# and (D, N, P) arrays already keeps the node axis minor (on the 128-wide
# lanes, e.g. f32[N,C,d]{0,2,1}), so the kernels take the transposed views
# — (d, N), (C, d, N), (K, P, N), per-node scalars as (1, N) — which are
# bitcasts of the engine's state, not copies. A grid step owns ``blk``
# nodes on the lane axis; every other block dimension is the array's full
# dimension (the d-tiled kernel's coefficient axis aside), so nothing is
# padded (the tiling rule accepts a block dimension equal to the array's). A population that is not a multiple of
# ``blk`` leaves a ragged last block: its out-of-range lanes compute
# garbage that Pallas never writes back, and every operation is per node,
# so no garbage reaches a real node.
from repro.core.faults import apply_defense
from repro.core.wire_codec import (_F16_MAX, INT8_QMAX, get_codec,
                                   uniform_at)

BLK = 512          # nodes per grid step (a multiple of the 128-lane width)


def _node_block(n: int) -> int:
    """Lane-axis block: ``BLK`` nodes, or the whole population when it is
    smaller (a block equal to the full dimension is always legal)."""
    return n if n <= BLK else BLK


def _lanes(blk: int, *lead: int):
    """BlockSpec of a node-minor operand: full leading dims, ``blk`` nodes."""
    zeros = (0,) * len(lead)
    return pl.BlockSpec((*lead, blk), lambda i: (*zeros, i))


def _sat_f16(v):
    """``wire_codec._sat_f16(v)`` (clip, then round to f16 ties-to-even)
    held in f32, from integer ops: Mosaic has no f16 conversion on v5e.
    The caller casts the exact f16 value to f16 outside the kernel."""
    v = jnp.clip(v, -_F16_MAX, _F16_MAX)
    bits = lax.bitcast_convert_type(v, jnp.uint32)
    mag = bits & jnp.uint32(0x7FFFFFFF)
    # normal f16 range: keep 10 of the 23 mantissa bits, ties to even
    lsb = (mag >> jnp.uint32(13)) & jnp.uint32(1)
    normal = (mag + jnp.uint32(0xFFF) + lsb) & jnp.uint32(0xFFFFE000)
    # subnormal f16 range (|v| < 2^-14): a multiple of 2^-24, ties to even
    a = lax.bitcast_convert_type(mag, jnp.float32)
    sub = lax.bitcast_convert_type(
        jnp.round(a * 2.0 ** 24) * 2.0 ** -24, jnp.uint32)
    out = (jnp.where(mag < jnp.uint32(0x38800000), sub, normal)
           | (bits & jnp.uint32(0x80000000)))
    return lax.bitcast_convert_type(out, jnp.float32)


def _pegasos(w, t, x, y, lam: float):
    """(d, BLK) Pegasos step in f32 — ``learners.pegasos_update`` op for op
    (Algorithm 3 lines 1-10), one node per lane."""
    t = t + 1
    eta = 1.0 / (lam * t.astype(jnp.float32))
    margin = y * jnp.sum(w * x, axis=0, keepdims=True)
    decay = 1.0 - eta * lam
    return decay * w + jnp.where(margin < 1.0, eta * (y * x), 0.0), t


def _select(rows: int, cols: int, stride: int, k: int, transpose: bool):
    """0/1 bf16 matrix moving row ``stride*j + k`` of a (d, BLK) code block
    to row ``j`` of a (P, BLK) one (``transpose`` for the way back). It
    (de)interleaves packed codes along the sublane axis on the MXU: one
    non-zero product per output, small integers, f32 accumulation — exact."""
    r = lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    c = lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    hit = (r == stride * c + k) if transpose else (c == stride * r + k)
    return hit.astype(jnp.bfloat16)


def _interleave(groups, d: int):
    """Packed digit planes -> codes: ``groups[k]`` (P, BLK) holds the digit
    of coefficient ``g*j + k`` in row j; returns the (d, BLK) f32 codes."""
    g, p = len(groups), groups[0].shape[0]
    out = None
    for k, q in enumerate(groups):
        part = jnp.dot(_select(d, p, g, k, True), q.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        out = part if out is None else out + part
    return out


def _decode_msg(raw, msc, mzp, d: int, wire_mode: str):
    """In-VMEM wire decode of one round's (P, BLK) payload block (float
    cast, int8 codes, or packed uint8 bytes) to (d, BLK) f32 coefficients.
    The float expressions repeat ``wire_codec``'s decode op order exactly
    (cast-then-multiply-then-add), and the sub-4-bit unpacks are the
    ``unpack_int4``/``unpack_ternary`` integer expressions per digit plane
    (exact), so kernel and jnp paths agree bitwise."""
    if wire_mode == "float":
        return raw.astype(jnp.float32)
    if wire_mode == "affine8":
        return raw.astype(jnp.float32) * msc + mzp
    b = raw.astype(jnp.int32)
    if wire_mode == "int4":
        sign = lambda nib: ((nib + 8) & 0xF) - 8
        planes = [sign(b & 0xF), sign((b >> 4) & 0xF)]
    else:
        planes = [(b // (3 ** k)) % 3 - 1 for k in range(5)]
    return _interleave(planes, d) * msc


def _cycle_kernel(msg_w_ref, msg_t_ref, msc_ref, mzp_ref, valid_ref, x_ref,
                  y_ref, last_w_ref, last_t_ref, fw_ref, ft_ref, cw_ref,
                  ct_ref, ptr_ref, cnt_ref, out_lw, out_lt, out_fw, out_ft,
                  out_cw, out_ct, out_ptr, out_cnt, out_gated, out_clipped,
                  *, variant: str, lam: float,
                  k_rounds: int, wire_mode: str = "float",
                  defense: str = "none", d: int = 0):
    """One node block, node-minor: models (d, BLK), cache (C, d, BLK),
    per-node scalars (1, BLK), messages (K, P, BLK).

    ``msc_ref``/``mzp_ref`` are the per-message scale/zero-point of the
    quantized wire codecs (upcast to f32 by the caller; None when the codec
    does not carry them): messages stream into VMEM at wire precision and
    are decoded by :func:`_decode_msg`.

    ``defense`` runs ``faults.apply_defense`` between the decode and the
    merge of every round (the jnp round-chain placement), reducing over
    the coefficient axis. ``out_gated``/``out_clipped`` accumulate the
    per-node screen counts (zeros under ``"none"``). ``fw``/``ft`` track
    the freshest cached model (the last one written), so the caller needs
    no gather from the cache."""
    lw = last_w_ref[...].astype(jnp.float32)
    lt = last_t_ref[...]
    fw = fw_ref[...].astype(jnp.float32)
    ft = ft_ref[...]
    cw = cw_ref[...].astype(jnp.float32)
    ct = ct_ref[...]
    ptr = ptr_ref[...]
    cnt = cnt_ref[...]
    x = x_ref[...].astype(jnp.float32)
    y = y_ref[...].astype(jnp.float32)
    c, blk = ct.shape
    gated = jnp.zeros((1, blk), jnp.int32)
    clipped = jnp.zeros((1, blk), jnp.int32)
    slots = lax.broadcasted_iota(jnp.int32, (c, blk), 0)

    for kk in range(k_rounds):
        row = slice(kk, kk + 1)
        vm = valid_ref[row, :] > 0                 # (1, BLK) receives
        mw = _decode_msg(msg_w_ref[kk],
                         msc_ref[row, :] if msc_ref is not None else None,
                         mzp_ref[row, :] if mzp_ref is not None else None,
                         d, wire_mode)
        mw, vm, g, cl = apply_defense(defense, mw, vm, lw, axis=0)
        gated = gated + g.astype(jnp.int32)
        clipped = clipped + cl.astype(jnp.int32)
        mt = msg_t_ref[row, :]
        if variant == "mu":                        # update(merge(m, last))
            nw, nt = _pegasos((mw + lw) / 2.0, jnp.maximum(mt, lt), x, y, lam)
        elif variant == "um":                      # merge(update(m), update(last))
            w1, t1 = _pegasos(mw, mt, x, y, lam)
            w2, t2 = _pegasos(lw, lt, x, y, lam)
            nw, nt = (w1 + w2) / 2.0, jnp.maximum(t1, t2)
        else:                                      # rw: update(m)
            nw, nt = _pegasos(mw, mt, x, y, lam)
        # cache_add on the vm subset: one-hot write at slot ptr % C
        onehot = (slots == ptr % c) & vm           # (C, BLK)
        cw = jnp.where(onehot[:, None, :], nw[None], cw)
        ct = jnp.where(onehot, nt, ct)
        fw = jnp.where(vm, nw, fw)
        ft = jnp.where(vm, nt, ft)
        ptr = ptr + vm.astype(jnp.int32)
        cnt = jnp.minimum(cnt + vm.astype(jnp.int32), c)
        # lastModel <- received model
        lw = jnp.where(vm, mw, lw)
        lt = jnp.where(vm, mt, lt)

    out_lw[...] = lw.astype(out_lw.dtype)
    out_lt[...] = lt
    out_fw[...] = fw.astype(out_fw.dtype)
    out_ft[...] = ft
    out_cw[...] = cw.astype(out_cw.dtype)
    out_ct[...] = ct
    out_ptr[...] = ptr
    out_cnt[...] = cnt
    out_gated[...] = gated
    out_clipped[...] = clipped


def _kernel_no_meta(msg_w_ref, msg_t_ref, valid_ref, *rest, **kw):
    """Adapter for float payloads: no scale/zero-point operands."""
    _cycle_kernel(msg_w_ref, msg_t_ref, None, None, valid_ref, *rest, **kw)


def _kernel_scale_only(msg_w_ref, msg_t_ref, msc_ref, valid_ref, *rest,
                       **kw):
    """Adapter for the packed symmetric codecs: scale lane, no zero-point."""
    _cycle_kernel(msg_w_ref, msg_t_ref, msc_ref, None, valid_ref, *rest,
                  **kw)


def _wire_mode(wire, msg_scale, msg_zp) -> str:
    """The kernel's static decode mode for a wire-codec name (legacy
    callers that pass scale/zero-point without a name mean affine int8;
    a scale WITHOUT a name or zero-point is ambiguous — the packed codecs
    must name themselves, so silently decoding as float would corrupt the
    merge: refuse instead)."""
    if wire is not None:
        codec = get_codec(wire)
        if not codec.quantized:
            return "float"
        if codec.has_zp:
            return "affine8"
        return "int4" if codec.group == 2 else "ternary"
    if msg_scale is not None and msg_zp is not None:
        return "affine8"
    if msg_scale is not None:
        raise ValueError("msg_scale without msg_zp needs an explicit "
                         "wire= codec name (scale-only codecs are packed)")
    return "float"


def _receive_layout(n: int, d: int, c: int, k: int, p: int, msg_dtype,
                    n_meta: int, d_tile: int | None = None):
    """Grid, and ``(BlockSpec, array)`` of each operand and each output of a
    receive kernel in call order, the arrays as node-minor
    ``ShapeDtypeStruct``s (f32 models). ``d_tile=None`` is the whole-d
    kernel: one grid step a node block. Otherwise the d-tiled kernel, whose
    grid is (node block, pass, d tile): the operands both passes read
    follow the tile; those only the second pass reads, and the tiled
    outputs, stay on tile 0 through the first pass, so the pipeline moves
    them once; the per-node rows stay put for the whole node block."""
    blk = _node_block(n)
    f32, i32 = jnp.float32, jnp.int32
    arr = jax.ShapeDtypeStruct
    if d_tile is None:
        grid = (pl.cdiv(n, blk),)
        node = functools.partial(_lanes, blk)
        both = second = lambda *lead: _lanes(blk, *lead, d)
        msg = _lanes(blk, k, p)
    else:
        grid = (pl.cdiv(n, blk), 2, pl.cdiv(d, d_tile))

        def spec(lead, tiled, every_pass=True):
            z = (0,) * len(lead)
            if not tiled:
                return pl.BlockSpec((*lead, blk), lambda i, q, j: (*z, i))
            return pl.BlockSpec(
                (*lead, d_tile, blk),
                lambda i, q, j: (*z, j if every_pass else j * q, i))
        node = lambda *lead: spec(lead, False)
        both = lambda *lead: spec(lead, True)
        second = lambda *lead: spec(lead, True, every_pass=False)
        msg = both(k)
    ins = [(msg, arr((k, p, n), msg_dtype)),
           (node(k), arr((k, n), i32)),                    # msg_t
           *[(node(k), arr((k, n), f32))] * n_meta,        # scale, zp
           (node(k), arr((k, n), i32)),                    # valid
           (both(), arr((d, n), f32)),                     # x
           (node(1), arr((1, n), f32)),                    # y
           (both(), arr((d, n), f32)),                     # last_w
           (node(1), arr((1, n), i32)),                    # last_t
           (second(), arr((d, n), f32)),                   # fresh_w
           (node(1), arr((1, n), i32)),                    # fresh_t
           (second(c), arr((c, d, n), f32)),               # cache_w
           (node(c), arr((c, n), i32)),                    # cache_t
           (node(1), arr((1, n), i32)),                    # ptr
           (node(1), arr((1, n), i32))]                    # count
    # the state from last_w on comes back in place, then gated, clipped;
    # the tiled outputs are written in the second pass only
    outs = [(second(), arr((d, n), f32)), ins[n_meta + 6],
            (second(), arr((d, n), f32)), ins[n_meta + 8],
            (second(c), arr((c, d, n), f32)), *ins[n_meta + 10:],
            *[(node(1), arr((1, n), i32))] * 2]
    return grid, ins, outs


def _vmem_bytes(shape, itemsize: int) -> int:
    """VMEM a block takes: the second-minor dimension padded to the
    sublane tile (8 rows of 32-bit words), the minor one to 128 lanes."""
    *lead, r, cols = shape
    sub = 8 * 4 // itemsize
    return (math.prod(lead) * -(-r // sub) * sub * -(-cols // 128) * 128
            * itemsize)


def _window_bytes(layout) -> int:
    """VMEM of a kernel's block windows, each double-buffered."""
    _, ins, outs = layout
    return 2 * sum(_vmem_bytes(s.block_shape, a.dtype.itemsize)
                   for s, a in ins + outs)


def _moved_bytes(layout) -> int:
    """HBM bytes the kernel's pipeline moves over its grid: a block is
    read (operand) or written (output) each time its block index differs
    from the previous grid step's, clipped to the array's bounds."""
    grid, ins, outs = layout
    steps = list(itertools.product(*map(range, grid)))
    total = 0
    for spec, a in ins + outs:
        prev = None
        for ids in steps:
            at = spec.index_map(*ids)
            if at != prev:
                total += a.dtype.itemsize * math.prod(
                    min(b, size - i * b)
                    for b, i, size in zip(spec.block_shape, at, a.shape))
                prev = at
    return total


# Mosaic grants a kernel 16 MiB of scoped VMEM on v5e. The receive kernel's
# double-buffered block windows may take WINDOW_BUDGET of it; the rest holds
# the body's intermediates. Compiled for a described v5e at C=10, K=4,
# N=6144, the whole-d kernel fits at d=104 (windows 12.9 MB) and is refused
# at d=112 (windows 13.9 MB; scoped allocation 16.16M against 16.00M).
WINDOW_BUDGET = 12 * 2 ** 20


def _payload_operands(wire):
    """(payload dtype as the kernel reads it, scale/zero-point lanes)."""
    codec = get_codec(wire)
    dtype = (jnp.float32 if codec.payload_dtype == jnp.float16
             else codec.payload_dtype)       # upcast before the kernel
    return dtype, (int(codec.has_scale) + int(codec.has_zp)
                   if codec.quantized else 0)


@functools.lru_cache(maxsize=None)
def receive_d_tile(n: int, d: int, c: int, k: int, wire=None) -> int:
    """Coefficients a grid step of the receive kernel holds: ``d`` when the
    whole-d kernel's windows fit :data:`WINDOW_BUDGET` (then the engine
    runs :func:`fused_receive_apply`), else the largest multiple of 8
    whose tiled windows do (:func:`fused_receive_apply_dtiled`)."""
    dtype, n_meta = _payload_operands(wire)
    p = get_codec(wire).payload_cols(d)
    if _window_bytes(_receive_layout(n, d, c, k, p, dtype,
                                     n_meta)) <= WINDOW_BUDGET:
        return d
    fits = lambda t: _window_bytes(_receive_layout(
        n, d, c, k, d, jnp.float32, 0, t)) <= WINDOW_BUDGET
    if not fits(8):
        raise ValueError(f"no d tile of the receive kernel fits "
                         f"{WINDOW_BUDGET} bytes of VMEM at C={c}, K={k}")
    t = 8
    while t + 8 < d and fits(t + 8):
        t += 8
    return t


@functools.lru_cache(maxsize=None)
def receive_call_bytes(n: int, d: int, c: int, k: int, wire, d_tile: int
                       ) -> int:
    """HBM bytes one call of the receive kernel reads and writes, as its
    BlockSpecs move them (:func:`_moved_bytes`): the whole-d kernel for
    ``d_tile == d``, else the d-tiled one, both passes."""
    if d_tile != d:
        return _moved_bytes(_receive_layout(n, d, c, k, d, jnp.float32, 0,
                                            d_tile))
    dtype, n_meta = _payload_operands(wire)
    return _moved_bytes(_receive_layout(
        n, d, c, k, get_codec(wire).payload_cols(d), dtype, n_meta))


@functools.partial(jax.jit, static_argnames=("variant", "lam", "interpret",
                                             "wire", "defense"))
def fused_receive_apply(last_w, last_t, fresh_w, fresh_t, cache_w, cache_t,
                        ptr, count, msg_w, msg_t, valid, x, y, *,
                        msg_scale=None,
                        msg_zp=None, wire=None, variant: str, lam: float,
                        interpret: bool = False, defense: str = "none"):
    """Fused K-receive apply for one cycle.

    last_w, fresh_w, x: (N, d); cache_w: (N, C, d); msg_w: (K, N, P);
    msg_t, valid: (K, N) int32; returns the updated (last_w, last_t,
    fresh_w, fresh_t, cache_w, cache_t, ptr, count, gated, clipped) —
    ``fresh_*`` is the freshest cached model (``cache.freshest``, given
    that the passed ``fresh_*`` was), and the trailing (N,) int32 pair
    counts the messages the static ``defense`` screen rejected/rescaled
    in-kernel (zeros for "none").

    ``msg_w`` may arrive in any wire codec's payload representation (the
    simulator's in-flight buffer under ``cfg.wire_dtype``, named by the
    static ``wire``): bf16/f16 are upcast in VMEM; int8 payloads pass their
    per-message f16 ``msg_scale``/``msg_zp`` (K, N) and are
    affine-dequantized in VMEM; packed int4/ternary payloads (P = the
    codec's packed byte width) pass ``msg_scale`` only and are
    unpacked-and-dequantized in VMEM. Either way HBM message traffic is
    paid at wire precision (the (K, N) scale lanes are upcast to f32
    outside the kernel: 8 bytes a message). The kernel runs on the
    node-minor views of every operand (module comment); the cache, model
    and counter outputs alias their inputs, so the update is in place."""
    n, d = last_w.shape
    c = cache_w.shape[1]
    k, _, p = msg_w.shape
    mode = _wire_mode(wire, msg_scale, msg_zp)
    if msg_w.dtype == jnp.float16:      # Mosaic on v5e cannot convert f16
        msg_w = msg_w.astype(jnp.float32)
    row = lambda a: a.reshape(1, n)

    kw = dict(variant=variant, lam=lam, k_rounds=k, wire_mode=mode,
              defense=defense, d=d)
    meta = [m.astype(jnp.float32) for m in (msg_scale, msg_zp)
            if m is not None and mode != "float"]
    kernel = {0: _kernel_no_meta, 1: _kernel_scale_only,
              2: _cycle_kernel}[len(meta)]
    grid, in_blocks, out_blocks = _receive_layout(n, d, c, k, p, msg_w.dtype,
                                                  len(meta))
    n_in = 3 + len(meta)                 # index of x among the operands
    outs = pl.pallas_call(
        functools.partial(kernel, **kw),
        grid=grid,
        in_specs=[spec for spec, _ in in_blocks],
        out_specs=[spec for spec, _ in out_blocks],
        out_shape=[
            jax.ShapeDtypeStruct((d, n), last_w.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((d, n), fresh_w.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((c, d, n), cache_w.dtype),
            jax.ShapeDtypeStruct((c, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
        ],
        # the model, cache and counter state updates in place
        input_output_aliases={n_in + 2 + i: i for i in range(8)},
        interpret=interpret,
    )(msg_w.transpose(0, 2, 1), msg_t, *meta, valid, x.T, row(y),
      last_w.T, row(last_t), fresh_w.T, row(fresh_t),
      cache_w.transpose(1, 2, 0), cache_t.T, row(ptr), row(count))
    lw, lt, fw, ft, cw, ct, ptr, cnt, gated, clipped = outs
    return (lw.T, lt[0], fw.T, ft[0], cw.transpose(2, 0, 1), ct.T, ptr[0],
            cnt[0], gated[0], clipped[0])


def _dtiled_kernel(msg_w_ref, msg_t_ref, valid_ref, x_ref, y_ref,
                  last_w_ref, last_t_ref, fw_ref, ft_ref, cw_ref, ct_ref,
                  ptr_ref, cnt_ref, out_lw, out_lt, out_fw, out_ft, out_cw,
                  out_ct, out_ptr, out_cnt, out_gated, out_clipped, acc_ref,
                  eta_ref, slot_ref, *, lam: float, k_rounds: int, d: int,
                  d_tile: int):
    """One grid step of :func:`fused_receive_apply_dtiled`: node block i,
    pass q, d tile j; models (d_tile, BLK), cache (C, d_tile, BLK),
    messages (K, d_tile, BLK), per-node rows (1, BLK) or (K, BLK).

    Round k's merge ``(m_k + last_k) / 2`` is known from the operands
    alone (``last_k`` is the previous valid message), so pass 0 sums each
    round's margin over the tiles into ``acc_ref`` (K, BLK), and pass 1
    applies the merge, the Pegasos step with that margin, the cache write
    and the freshest and last model, tile by tile. The per-node counters
    are computed once, at the node block's first step, where ``eta_ref``
    and ``slot_ref`` (the cache slot round k writes, -1 for no receive)
    are kept for pass 1. The ops are ``_cycle_kernel``'s for the "mu"
    variant; only the margin's summation order differs."""
    q, j = pl.program_id(1), pl.program_id(2)
    rounds = [slice(kk, kk + 1) for kk in range(k_rounds)]

    @pl.when((q == 0) & (j == 0))
    def _counters():
        lt, ft, ct = last_t_ref[...], ft_ref[...], ct_ref[...]
        ptr, cnt = ptr_ref[...], cnt_ref[...]
        c, blk = ct.shape
        slots = lax.broadcasted_iota(jnp.int32, (c, blk), 0)
        for row in rounds:
            vm = valid_ref[row, :] > 0
            mt = msg_t_ref[row, :]
            t = jnp.maximum(mt, lt) + 1
            eta_ref[row, :] = 1.0 / (lam * t.astype(jnp.float32))
            slot_ref[row, :] = jnp.where(vm, ptr % c, -1)
            ct = jnp.where((slots == ptr % c) & vm, t, ct)
            ft = jnp.where(vm, t, ft)
            ptr = ptr + vm.astype(jnp.int32)
            cnt = jnp.minimum(cnt + vm.astype(jnp.int32), c)
            lt = jnp.where(vm, mt, lt)
        out_lt[...] = lt
        out_ft[...] = ft
        out_ct[...] = ct
        out_ptr[...] = ptr
        out_cnt[...] = cnt
        out_gated[...] = jnp.zeros_like(ptr)
        out_clipped[...] = jnp.zeros_like(ptr)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def merges():
        """``[(round, receives, merge)]`` of this tile in round order, and
        the last model after the rounds."""
        lw = last_w_ref[...].astype(jnp.float32)
        out = []
        for kk, row in enumerate(rounds):
            vm = valid_ref[row, :] > 0
            mw = msg_w_ref[kk].astype(jnp.float32)
            out.append((row, vm, (mw + lw) / 2.0))
            lw = jnp.where(vm, mw, lw)
        return out, lw

    x = x_ref[...].astype(jnp.float32)
    y = y_ref[...].astype(jnp.float32)

    @pl.when(q == 0)
    def _margins():
        # rows past d in the ragged last tile hold no coefficient
        coef = lax.broadcasted_iota(jnp.int32, (d_tile, 1), 0) + j * d_tile
        for row, _, w in merges()[0]:
            acc_ref[row, :] += jnp.sum(jnp.where(coef < d, w * x, 0.0),
                                       axis=0, keepdims=True)

    @pl.when(q == 1)
    def _apply():
        fw = fw_ref[...].astype(jnp.float32)
        rounds_w, lw = merges()
        new = []
        for row, vm, w in rounds_w:
            eta = eta_ref[row, :]
            margin = y * acc_ref[row, :]
            decay = 1.0 - eta * lam
            nw = decay * w + jnp.where(margin < 1.0, eta * (y * x), 0.0)
            fw = jnp.where(vm, nw, fw)
            new.append(nw)
        out_lw[...] = lw.astype(out_lw.dtype)
        out_fw[...] = fw.astype(out_fw.dtype)
        # slot by slot, so one (d_tile, BLK) slab is live at a time; a
        # later round wins a slot, as in the sequential cache adds
        for s in range(cw_ref.shape[0]):
            v = cw_ref[s].astype(jnp.float32)
            for row, nw in zip(rounds, new):
                v = jnp.where(slot_ref[row, :] == s, nw, v)
            out_cw[s] = v.astype(out_cw.dtype)


@functools.partial(jax.jit, static_argnames=("variant", "lam", "interpret",
                                             "wire", "defense", "d_tile"))
def fused_receive_apply_dtiled(last_w, last_t, fresh_w, fresh_t, cache_w,
                               cache_t, ptr, count, msg_w, msg_t, valid, x,
                               y, *, msg_scale=None, msg_zp=None, wire=None,
                               variant: str, lam: float,
                               interpret: bool = False,
                               defense: str = "none", d_tile: int):
    """:func:`fused_receive_apply` with the coefficient axis tiled, for a
    d at which a whole node block does not fit VMEM (the engine picks it
    by :func:`receive_d_tile`). Same operands, outputs and in-place
    aliasing, in the same node-minor views; the grid is (node block, pass,
    d tile) and each round's margin is summed over the tiles before any
    coefficient changes (:func:`_dtiled_kernel`). ``d_tile``: coefficients
    a step, a multiple of 8 (the engine's is :func:`receive_d_tile`'s);
    the last tile may be ragged.

    It implements the f32 wire, the "mu" variant (the paper's P2Pegasos)
    and no defense; anything else is refused, not computed otherwise."""
    n, d = last_w.shape
    c = cache_w.shape[1]
    k = msg_w.shape[0]
    if (_wire_mode(wire, msg_scale, msg_zp) != "float"
            or msg_w.dtype != jnp.float32 or variant != "mu"
            or defense != "none"):
        raise ValueError(
            f"the d-tiled receive kernel (d={d}: a whole node block does not "
            f"fit VMEM) implements the f32 wire, the 'mu' variant and no "
            f"defense; got wire={wire!r} ({msg_w.dtype} payload), "
            f"variant={variant!r}, defense={defense!r}")
    if d_tile % 8:
        raise ValueError(f"d_tile must be a multiple of 8, got {d_tile}")
    row = lambda a: a.reshape(1, n)
    grid, in_blocks, out_blocks = _receive_layout(n, d, c, k, d, msg_w.dtype,
                                                  0, d_tile)
    blk = _node_block(n)
    outs = pl.pallas_call(
        functools.partial(_dtiled_kernel, lam=lam, k_rounds=k, d=d,
                          d_tile=d_tile),
        grid=grid,
        in_specs=[spec for spec, _ in in_blocks],
        out_specs=[spec for spec, _ in out_blocks],
        out_shape=[a for _, a in out_blocks],
        scratch_shapes=[pltpu.VMEM((k, blk), jnp.float32),
                        pltpu.VMEM((k, blk), jnp.float32),
                        pltpu.VMEM((k, blk), jnp.int32)],
        # the model, cache and counter state updates in place
        input_output_aliases={5 + i: i for i in range(8)},
        interpret=interpret,
    )(msg_w.transpose(0, 2, 1), msg_t, valid, x.T, row(y), last_w.T,
      row(last_t), fresh_w.T, row(fresh_t), cache_w.transpose(1, 2, 0),
      cache_t.T, row(ptr), row(count))
    lw, lt, fw, ft, cw, ct, ptr, cnt, gated, clipped = outs
    return (lw.T, lt[0], fw.T, ft[0], cw.transpose(2, 0, 1), ct.T, ptr[0],
            cnt[0], gated[0], clipped[0])


# ---------------------------------------------------------------------------
# send-side quantization kernel
# ---------------------------------------------------------------------------


def _send_kernel(key_ref, w_ref, q_out, sc_out, zp_out, *, d: int,
                 qmax: int, stochastic: bool):
    """Per-message affine int8 quantization of a (d, BLK) node block — the
    same op sequence (and order) as ``wire_codec.quantize_wire``, reduced
    over the coefficient axis, so kernel and jnp sends agree bitwise."""
    w = w_ref[...].astype(jnp.float32)
    blk = w.shape[1]
    lo = jnp.min(w, axis=0, keepdims=True)
    hi = jnp.max(w, axis=0, keepdims=True)
    zp = _sat_f16((hi + lo) * 0.5)
    scale = _sat_f16(jnp.maximum(hi - zp, zp - lo) / qmax)
    sf = jnp.where(scale > 0, scale, 1.0)
    u = (w - zp) / sf
    if stochastic:
        node = (pl.program_id(0) * blk
                + lax.broadcasted_iota(jnp.int32, (d, blk), 1))
        coef = lax.broadcasted_iota(jnp.int32, (d, blk), 0)
        u = jnp.floor(u + uniform_at(key_ref[0], key_ref[1], node * d + coef))
    else:
        u = jnp.round(u)
    q_out[...] = jnp.clip(u, -127, 127).astype(jnp.int8)
    sc_out[...] = scale
    zp_out[...] = zp


def _pack_rows(q, group: int, cols: int):
    """(d, BLK) int32 codes -> (cols, BLK) uint8, the byte layout of the
    ``wire_codec`` pack helpers: int4 two's-complement nibbles (low nibble
    = even coefficient), ternary base-3 digits (code+1)·3^k; pad codes are
    0 (pad digit 1 for ternary), byte for byte the jnp pack."""
    d = q.shape[0]
    pad = lax.broadcasted_iota(jnp.int32, (cols, 1), 0) * group
    planes = []
    for k in range(group):
        v = q + 1 if group != 2 else q
        plane = jnp.dot(_select(cols, d, group, k, False),
                        v.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32).astype(jnp.int32)
        if group != 2:                 # ternary pad digit: code 0 -> 1
            plane = plane + (pad + k >= d).astype(jnp.int32)
        planes.append(plane)
    if group == 2:
        b = (planes[0] & 0xF) | ((planes[1] & 0xF) << 4)
    else:
        b = planes[0]
        for k in range(1, group):
            b = b + planes[k] * (3 ** k)
    return b.astype(jnp.uint8)


def _pack_send_kernel(w_ref, ef_ref, q_out, sc_out, res_out, *, qmax: int,
                      group: int):
    """Packed symmetric send of a (d, BLK) node block: symmetric f16 scale
    over the coefficient axis, round-to-nearest codes, in-kernel packing
    (:func:`_pack_rows`, integer-exact, so kernel bytes == jnp bytes), and
    the fused error-feedback residual ``x - q·scale`` when ``ef_ref``/
    ``res_out`` are wired."""
    x = w_ref[...].astype(jnp.float32)
    if ef_ref is not None:
        x = x + ef_ref[...].astype(jnp.float32)
    # wire_codec.symmetric_scale over the coefficient axis
    scale = _sat_f16(jnp.max(jnp.abs(x), axis=0, keepdims=True) / qmax)
    sf = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / sf), -qmax, qmax).astype(jnp.int32)
    q_out[...] = _pack_rows(q, group, q_out.shape[0])
    sc_out[...] = scale
    if res_out is not None:
        res_out[...] = x - q.astype(jnp.float32) * scale


def _pack_send_ef(w_ref, ef_ref, q_out, sc_out, res_out, **kw):
    _pack_send_kernel(w_ref, ef_ref, q_out, sc_out, res_out, **kw)


def _pack_send_plain(w_ref, q_out, sc_out, **kw):
    _pack_send_kernel(w_ref, None, q_out, sc_out, None, **kw)


@functools.partial(jax.jit, static_argnames=("name", "interpret"))
def quantize_send(w, name: str, key_data=None, ef=None, *,
                  interpret: bool = False):
    """Fused send-side quantization: the wire codec's encode as one Pallas
    pass per node block.

    ``w``: (N, d) f32 fresh models. For the affine int8 codecs returns
    ``(q, scale, zp)`` bitwise equal to ``quantize_wire(w, name, key)`` —
    including the "int8_sr" stochastic-rounding draw, whose threefry
    uniform is generated *inside* the kernel from ``key_data``
    (= ``jax.random.key_data(k_recv)``, the same per-cycle key slot both
    engines use). For the packed sub-4-bit codecs returns
    ``(payload, scale)`` — or ``(payload, scale, resid)`` when ``ef`` (the
    (N, d) f32 error-feedback accumulator) is passed: the kernel encodes
    ``w + ef``, packs the codes in VMEM and emits the EF residual
    ``(w + ef) - decode(...)`` from the same pass, bitwise equal to the
    jnp ``codec.encode``/``decode`` chain (the caller applies the
    send-mask ``where`` to the residual). The jnp encoder materializes the
    range reductions, the scaled quotient and the noise/codes as separate
    (N, d) HBM-resident intermediates; the kernel streams each node block
    through VMEM once and writes the packed codes + f16 scalars. Node-minor
    like the receive kernel: it reads ``w.T`` and returns transposed views."""
    n, d = w.shape
    codec = get_codec(name)
    blk = _node_block(n)
    grid = (pl.cdiv(n, blk),)
    wt = w.astype(jnp.float32).T

    if codec.has_zp:                  # affine int8 family
        if ef is not None:
            raise ValueError(f"{name!r} keeps no error-feedback state — "
                             "ef is only accepted by the _ef codecs")
        stochastic = codec.stochastic
        if stochastic and key_data is None:
            raise ValueError("int8_sr quantization needs key_data")
        kd = (jnp.asarray(key_data, jnp.uint32).reshape(2) if stochastic
              else jnp.zeros((2,), jnp.uint32))

        lanes = lambda *lead: pl.BlockSpec(
            (*lead, blk), lambda i, *_: (*(0,) * len(lead), i))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=[lanes(d)],
            out_specs=[lanes(d), lanes(1), lanes(1)])
        q, sc, zp = pl.pallas_call(
            functools.partial(_send_kernel, d=d, qmax=INT8_QMAX,
                              stochastic=stochastic),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((d, n), jnp.int8),
                       jax.ShapeDtypeStruct((1, n), jnp.float32),
                       jax.ShapeDtypeStruct((1, n), jnp.float32)],
            interpret=interpret,
        )(kd, wt)
        return q.T, sc[0].astype(jnp.float16), zp[0].astype(jnp.float16)

    if not codec.quantized:
        raise ValueError(f"quantize_send needs a quantized wire codec, "
                         f"got {name!r}")

    cols = codec.payload_cols(d)
    kw = dict(qmax=codec.qmax, group=codec.group)
    vec, qvec, sca = _lanes(blk, d), _lanes(blk, cols), _lanes(blk, 1)
    packed = jax.ShapeDtypeStruct((cols, n), jnp.uint8)
    scales = jax.ShapeDtypeStruct((1, n), jnp.float32)     # f16 values
    if ef is not None:
        q, sc, resid = pl.pallas_call(
            functools.partial(_pack_send_ef, **kw),
            grid=grid,
            in_specs=[vec, vec],
            out_specs=[qvec, sca, vec],
            out_shape=[packed, scales,
                       jax.ShapeDtypeStruct((d, n), jnp.float32)],
            interpret=interpret,
        )(wt, ef.astype(jnp.float32).T)
        return q.T, sc[0].astype(jnp.float16), resid.T
    q, sc = pl.pallas_call(
        functools.partial(_pack_send_plain, **kw),
        grid=grid,
        in_specs=[vec],
        out_specs=[qvec, sca],
        out_shape=[packed, scales],
        interpret=interpret,
    )(wt)
    return q.T, sc[0].astype(jnp.float16)
