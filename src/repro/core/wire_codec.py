"""Pluggable wire-codec subsystem: how a transmitted model rides the wire.

The paper's central cost axis is communication — one model per message,
random walks instead of raw-data movement — so the wire representation of
the transmitted model is a first-class protocol knob. This module owns it:
a registry of :class:`WireCodec` objects, each declaring its payload buffer
lane (dtype + packed width), its per-message wire bytes, its encode/decode
functions, and whether the *sender* keeps error-feedback state.

Registered codecs (``WIRE_CODECS``; ``GossipLinearConfig.wire_dtype`` and
``gossip_merge``'s ``exchange_dtype`` accept any name):

* ``f32`` (alias ``None``) — full precision, 4 B/coefficient;
* ``bf16`` / ``f16`` — plain dtype cast, 2 B/coefficient;
* ``int8`` / ``int8_sr`` — per-message *affine* int8: each message carries
  an f16 (scale, zero-point) pair computed from its coefficient range;
  ``int8_sr`` rounds stochastically (unbiased) from a counter-based
  threefry key so runs stay bitwise-reproducible;
* ``int4`` / ``int4_ef`` — per-message *symmetric* int4: codes in
  [-7, 7] packed two per byte (0.5 B/coefficient), one f16 scale
  (``max|w| / INT4_QMAX``), no zero-point;
* ``ternary`` / ``ternary_ef`` — sign+scale codes in {-1, 0, +1} packed
  five per byte base-3 (0.2 B/coefficient), one f16 scale (``max|w|``).

The ``_ef`` variants enable **sender-side error feedback** (the EF-SGD
residual trick): the sender keeps a per-node f32 residual ``e``, transmits
``encode(w + e)`` and stores ``e' = (w + e) - decode(encode(w + e))`` — the
part the coarse code lost this cycle rides along on the *next* send instead
of being dropped. The residual updates only on cycles the node actually
transmits, which is what lets the sharded engine's sender-subset compaction
stay bitwise-equal to the reference engine. The accumulator is bounded by
one half quantization step of the running scale (property-tested), and the
merge-DAG averaging of the protocol absorbs the remaining bias — measured
per codec in ``BENCH_wire_quantization.json``.

Sub-byte codes change the *protocol state* (packed payload lanes, scale
lanes without zero-points, the EF residual lane), which is why the codec —
not a dtype string — is the unit the engines thread through ``SimState``,
the sharded carry, the ``shard_map`` specs and the Pallas kernels. Merge
arithmetic is always f32 regardless of codec.

This module is dependency-free within ``repro`` (pure jnp), so the
engines, the on-mesh optimizer and the Pallas kernels can all import from
it — the affine/symmetric quantization constants and pack/unpack helpers
live here and nowhere else.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# quantization constants — single home (satellite: the affine logic used to
# be copied across gossip_optimizer / gossip_cycle / gossip_merge)
# ---------------------------------------------------------------------------

# int8 payloads target [-126, 126]: one code of headroom keeps the clip at
# ±127 inert even after the scale is rounded to its f16 wire representation
INT8_QMAX = 126
# int4 codes target [-7, 7] (the symmetric subset of the two's-complement
# nibble range [-8, 7]); f16 scale rounding moves |w|/scale by < 0.1%, far
# inside the 1/14 relative headroom of round-to-nearest, so the clip at ±7
# never distorts
INT4_QMAX = 7
# codes packed per byte: two int4 nibbles, five base-3 trits (3^5 = 243)
INT4_GROUP = 2
TERNARY_GROUP = 5

_F16_MAX = float(jnp.finfo(jnp.float16).max)


def _sat_f16(v):
    """f16 cast that saturates instead of overflowing to inf — a divergent
    learner stays finite on the wire (grossly quantized) rather than
    flooding every downstream merge with NaNs."""
    return jnp.clip(v, -_F16_MAX, _F16_MAX).astype(jnp.float16)


# ---------------------------------------------------------------------------
# reproducible stochastic-rounding noise (threefry, op-exact vs jax.random)
# ---------------------------------------------------------------------------


def threefry2x32(k0, k1, x0, x1):
    """Threefry-2x32 block cipher on uint32 arrays — op-for-op the unrolled
    lowering of JAX's ``threefry2x32_p`` (jax._src.prng), so the bits are
    identical to what ``jax.random`` produces for the same key/counters.
    Pure jnp integer ops: usable under jit, inside ``lax.scan`` bodies and
    inside Pallas kernels alike."""
    def rotl(v, r):
        return (v << jnp.uint32(r)) | (v >> jnp.uint32(32 - r))

    rot = ((13, 15, 26, 6), (17, 29, 16, 24))
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(0x1BD11BDA))
    x = [x0 + ks[0], x1 + ks[1]]
    for i in range(5):
        for r in rot[i % 2]:
            x[0] = x[0] + x[1]
            x[1] = rotl(x[1], r)
            x[1] = x[0] ^ x[1]
        x[0] = x[0] + ks[(i + 1) % 3]
        x[1] = x[1] + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x[0], x[1]


def uniform_at(k0, k1, p):
    """``jax.random.uniform(key, shape)`` evaluated at flat positions ``p``
    (non-negative int32, so < 2^31) of the drawn array.

    Reproduces JAX's partitionable threefry counter scheme
    (``jax._src.prng._threefry_random_bits_partitionable``, the default of
    the installed JAX) bit for bit: element p is one 20-round block on the
    64-bit counter (hi32(p), lo32(p)) = (0, p), and its 32 random bits are
    ``y0 ^ y1``. Each element depends on its own position only, not on the
    array size. The uint32 bits map to [0, 1) floats with the same
    mantissa-fill transform ``jax.random.uniform`` applies.

    This is what lets both the Pallas send kernel and the compacted
    send path regenerate the "int8_sr" noise for an arbitrary *subset* of
    messages without a dense (N, d) draw, bitwise-equal to the full-array
    ``jax.random.uniform`` the reference engine consumes."""
    lo = p.astype(jnp.uint32)
    y0, y1 = threefry2x32(k0, k1, jnp.zeros_like(lo), lo)
    fbits = ((y0 ^ y1) >> jnp.uint32(9)) | jnp.uint32(0x3F800000)
    return jax.lax.bitcast_convert_type(fbits, jnp.float32) - 1.0


def sr_noise_for_rows(key, rows, d: int):
    """The ``jax.random.uniform(key, (n, d))`` noise of a full-array
    "int8_sr" quantization, evaluated only at the given ``rows``:
    ``sr_noise_for_rows(key, rows, d)`` ==
    ``jax.random.uniform(key, (n, d))[rows]`` bitwise for any n, at
    O(len(rows)·d) threefry work. ``key`` is a typed threefry key (the
    per-cycle ``k_recv`` slot)."""
    kd = jax.random.key_data(key).astype(jnp.uint32)
    p = rows[:, None] * d + jnp.arange(d, dtype=rows.dtype)[None, :]
    return uniform_at(kd[0], kd[1], p)


# ---------------------------------------------------------------------------
# affine int8 quantization (the int8 / int8_sr codecs; also the one
# implementation behind gossip_merge's int8 exchange path)
# ---------------------------------------------------------------------------


def quantize_wire(w, name, key=None, noise=None):
    """Per-message affine int8 quantization of a batch of models.

    ``w``: (..., d) f32 — each slice along the last axis is one transmitted
    model (one message). Returns ``(q, scale, zp)`` with ``q`` int8 of
    ``w.shape`` and ``scale``/``zp`` f16 of ``w.shape[:-1]`` — the f16
    values are exactly what rides the wire, and the SAME rounded values are
    used by the quantizer itself, so the round-trip error is bounded by one
    quantization step of the *transmitted* scale:

      |w - dequantize(q, scale, zp)| <= scale      (per coordinate)

    (<= scale/2 for round-to-nearest; stochastic rounding is unbiased but
    may land a full step away). ``zp`` is the f16-rounded range midpoint and
    ``scale`` covers the residual range ``max(hi-zp, zp-lo)`` over
    ``INT8_QMAX`` codes, so codes stay within ±127 even after f16 rounding —
    the defensive clip never distorts.

    ``name``: "int8" rounds to nearest (deterministic); "int8_sr" adds
    uniform [0, 1) noise before the floor — ``key`` (threefry) is required
    and makes the draw reproducible: both simulator engines feed the same
    per-cycle ``k_recv`` key here, keeping cross-engine parity bitwise.
    ``noise`` (optional, "int8_sr" only) supplies the uniform draw directly
    instead of ``key`` — the compacted send path passes
    :func:`sr_noise_for_rows` values so a subset quantization consumes
    exactly the noise the full-array draw would have given those rows.

    Precondition: coefficients are expected inside the f16-representable
    range (|w| ≲ 6.5e4 — far beyond any non-divergent linear model here;
    Pegasos is bounded by 1/sqrt(lam)). Outside it the f16 scale/zero-point
    SATURATE at the f16 max instead of overflowing to inf, so a divergent
    run stays finite on the wire (grossly quantized) rather than flooding
    every merge with NaNs."""
    w = w.astype(jnp.float32)
    lo = jnp.min(w, axis=-1)
    hi = jnp.max(w, axis=-1)
    zp = _sat_f16((hi + lo) * 0.5)
    zpf = zp.astype(jnp.float32)
    scale = _sat_f16(jnp.maximum(hi - zpf, zpf - lo) / INT8_QMAX)
    # guarded divisor: a constant message (hi == lo, scale 0) maps every
    # coordinate to code 0 and dequantizes to exactly zp
    sf = jnp.where(scale > 0, scale, jnp.float16(1)).astype(jnp.float32)
    u = (w - zpf[..., None]) / sf[..., None]
    if name == "int8_sr":
        if noise is None:
            if key is None:
                raise ValueError("int8_sr quantization needs a PRNG key")
            noise = jax.random.uniform(key, w.shape)
        u = jnp.floor(u + noise)
    else:
        u = jnp.round(u)
    q = jnp.clip(u, -127, 127).astype(jnp.int8)
    return q, scale, zp


def dequantize_wire(q, scale, zp):
    """Inverse of :func:`quantize_wire`: ``q * scale + zp`` in f32.

    The Pallas ``gossip_cycle`` kernel applies this same expression in-VMEM
    (same op order), so kernel and jnp paths agree bitwise."""
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]
            + zp.astype(jnp.float32)[..., None])


# ---------------------------------------------------------------------------
# sub-4-bit code packing (shared by the jnp codecs and the Pallas kernels —
# integer-exact, so every implementation that uses them agrees bitwise)
# ---------------------------------------------------------------------------


def pack_int4(q):
    """(..., d) int codes in [-8, 7] -> (..., ceil(d/2)) uint8.

    Two's-complement nibbles, low nibble = even coordinate; odd d pads one
    0 code into the final byte's high nibble."""
    d = q.shape[-1]
    pad = -d % INT4_GROUP
    qi = q.astype(jnp.int32)
    if pad:
        qi = jnp.concatenate(
            [qi, jnp.zeros(qi.shape[:-1] + (pad,), jnp.int32)], axis=-1)
    pairs = qi.reshape(qi.shape[:-1] + ((d + pad) // INT4_GROUP, INT4_GROUP))
    return ((pairs[..., 0] & 0xF)
            | ((pairs[..., 1] & 0xF) << 4)).astype(jnp.uint8)


def unpack_int4(b, d: int):
    """(..., P) uint8 -> (..., d) int32 sign-extended nibble codes.

    Exact inverse of :func:`pack_int4` on the first ``d`` coordinates
    (requires 2·P >= d)."""
    bi = b.astype(jnp.int32)
    nib = jnp.stack([bi & 0xF, (bi >> 4) & 0xF], axis=-1)
    nib = nib.reshape(b.shape[:-1] + (b.shape[-1] * INT4_GROUP,))[..., :d]
    return ((nib + 8) & 0xF) - 8


def pack_ternary(q):
    """(..., d) codes in {-1, 0, +1} -> (..., ceil(d/5)) uint8, base-3.

    Byte value = sum of (code+1)·3^k over the five trits it carries
    (0..242); pad trits are code 0 (digit 1), matching the Pallas send
    kernel's padded lanes byte for byte."""
    d = q.shape[-1]
    pad = -d % TERNARY_GROUP
    g = q.astype(jnp.int32) + 1
    if pad:
        g = jnp.concatenate(
            [g, jnp.ones(g.shape[:-1] + (pad,), jnp.int32)], axis=-1)
    g = g.reshape(g.shape[:-1] + ((d + pad) // TERNARY_GROUP, TERNARY_GROUP))
    b = g[..., 0]
    for k in range(1, TERNARY_GROUP):
        b = b + g[..., k] * (3 ** k)
    return b.astype(jnp.uint8)


def unpack_ternary(b, d: int):
    """(..., P) uint8 -> (..., d) int32 codes in {-1, 0, +1}.

    Exact inverse of :func:`pack_ternary` on the first ``d`` coordinates
    (requires 5·P >= d)."""
    bi = b.astype(jnp.int32)
    digs = jnp.stack([(bi // (3 ** k)) % 3 for k in range(TERNARY_GROUP)],
                     axis=-1)
    return digs.reshape(
        b.shape[:-1] + (b.shape[-1] * TERNARY_GROUP,))[..., :d] - 1


def symmetric_scale(w, qmax: int):
    """The shared scale rule of the packed symmetric codecs: one f16
    ``max|w| / qmax`` per message (saturating like the affine path), plus
    the zero-guarded f32 divisor. Returns ``(scale_f16, divisor_f32)``."""
    amax = jnp.max(jnp.abs(w), axis=-1)
    scale = _sat_f16(amax / qmax)
    sf = jnp.where(scale > 0, scale, jnp.float16(1)).astype(jnp.float32)
    return scale, sf


# ---------------------------------------------------------------------------
# codec objects
# ---------------------------------------------------------------------------


class WireCodec:
    """One wire representation of a transmitted model.

    Attributes (fixed per codec):

    * ``name`` — registry key (``GossipLinearConfig.wire_dtype`` value);
    * ``payload_dtype`` — storage dtype of the in-flight payload buffer;
    * ``bits_per_coeff`` — wire bits per model coefficient;
    * ``overhead_bytes`` — per-message metadata beyond the coefficients
      (f16 scale, optionally + f16 zero-point);
    * ``has_scale`` / ``has_zp`` — which metadata lanes the buffer carries
      (``quantized`` is an alias for ``has_scale``);
    * ``ef`` — sender-side error-feedback residual state ((N, d) f32 in
      ``SimState.ef`` / the sharded carry, updated on actual sends only);
    * ``stochastic`` — encode consumes a per-cycle PRNG key (``k_recv``).

    ``encode(w, key=, noise=)`` maps (..., d) f32 models to
    ``(payload, scale, zp)`` (``scale``/``zp`` are None for lanes the codec
    does not carry); ``decode(payload, scale, zp, d)`` inverts it to f32.
    Both are pure jnp and jit/scan/shard_map-safe; the Pallas kernels
    restate them op for op (pinned bitwise in tests)."""

    name: str
    payload_dtype = jnp.float32
    bits_per_coeff = 32
    overhead_bytes = 0
    has_scale = False
    has_zp = False
    ef = False
    stochastic = False

    def __init__(self, name: str):
        self.name = name

    @property
    def quantized(self) -> bool:
        return self.has_scale

    def payload_cols(self, d: int) -> int:
        """Last-axis width of the payload buffer for d-coefficient models."""
        return d

    def payload_bytes(self, d: int) -> int:
        """Wire bytes of the packed coefficients of one message."""
        return self.payload_cols(d) * jnp.dtype(self.payload_dtype).itemsize

    def encode(self, w, key=None, noise=None):
        raise NotImplementedError

    def decode(self, payload, scale, zp, d: int):
        raise NotImplementedError

    def roundtrip(self, w, key=None, noise=None):
        """decode(encode(w)) — the receiver's view of a transmitted model
        (what ``gossip_merge``'s exchange path averages against)."""
        payload, scale, zp = self.encode(w, key=key, noise=noise)
        return self.decode(payload, scale, zp, w.shape[-1])

    def __repr__(self):
        return f"<WireCodec {self.name}>"


class FloatCodec(WireCodec):
    """Plain dtype cast (f32 / bf16 / f16): no metadata, no state."""

    def __init__(self, name: str, dtype, bits: int):
        super().__init__(name)
        self.payload_dtype = dtype
        self.bits_per_coeff = bits

    def encode(self, w, key=None, noise=None):
        return w.astype(self.payload_dtype), None, None

    def decode(self, payload, scale, zp, d: int):
        return payload.astype(jnp.float32)


class AffineInt8Codec(WireCodec):
    """Per-message affine int8 (:func:`quantize_wire`): f16 scale +
    zero-point ride with every message; "int8_sr" rounds stochastically."""

    payload_dtype = jnp.int8
    bits_per_coeff = 8
    overhead_bytes = 4            # f16 scale + f16 zero-point
    has_scale = True
    has_zp = True

    def __init__(self, name: str, stochastic: bool):
        super().__init__(name)
        self.stochastic = stochastic

    def encode(self, w, key=None, noise=None):
        return quantize_wire(w, self.name, key=key, noise=noise)

    def decode(self, payload, scale, zp, d: int):
        return dequantize_wire(payload, scale, zp)


class PackedSymmetricCodec(WireCodec):
    """Sub-4-bit symmetric codes packed several per byte, one f16 scale
    per message, no zero-point. ``int4``/``int4_ef``: codes round(w/scale)
    in [-7, 7], two per byte. ``ternary``/``ternary_ef``: codes in
    {-1, 0, +1} (scale = max|w|), five per byte base-3 — sign+scale on the
    wire. Rounding is deterministic (round-to-nearest): the ``_ef``
    variants rely on the error-feedback residual, not on unbiased noise,
    to kill the quantization bias."""

    payload_dtype = jnp.uint8
    overhead_bytes = 2                      # f16 scale only
    has_scale = True

    def __init__(self, name: str, qmax: int, group: int, pack, unpack,
                 ef: bool):
        super().__init__(name)
        self.qmax = qmax
        self.group = group
        self._pack = pack
        self._unpack = unpack
        self.ef = ef
        self.bits_per_coeff = 8 / group     # 4 for int4, 1.6 for ternary

    def payload_cols(self, d: int) -> int:
        return -(-d // self.group)          # ceil(d / codes-per-byte)

    def quantize_codes(self, w):
        """(codes int32 in [-qmax, qmax], scale f16) before packing — the
        Pallas send kernel re-enters here on its padded block."""
        w = w.astype(jnp.float32)
        scale, sf = symmetric_scale(w, self.qmax)
        q = jnp.clip(jnp.round(w / sf[..., None]),
                     -self.qmax, self.qmax).astype(jnp.int32)
        return q, scale

    def encode(self, w, key=None, noise=None):
        q, scale = self.quantize_codes(w)
        return self._pack(q), scale, None

    def decode(self, payload, scale, zp, d: int):
        q = self._unpack(payload, d)
        return q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

WIRE_CODECS: Dict[str, WireCodec] = {}


def _register(codec: WireCodec) -> WireCodec:
    assert codec.name not in WIRE_CODECS, codec.name
    WIRE_CODECS[codec.name] = codec
    return codec


_register(FloatCodec("f32", jnp.float32, 32))
_register(FloatCodec("bf16", jnp.bfloat16, 16))
_register(FloatCodec("f16", jnp.float16, 16))
_register(AffineInt8Codec("int8", stochastic=False))
_register(AffineInt8Codec("int8_sr", stochastic=True))
_register(PackedSymmetricCodec("int4", INT4_QMAX, INT4_GROUP,
                               pack_int4, unpack_int4, ef=False))
_register(PackedSymmetricCodec("int4_ef", INT4_QMAX, INT4_GROUP,
                               pack_int4, unpack_int4, ef=True))
_register(PackedSymmetricCodec("ternary", 1, TERNARY_GROUP,
                               pack_ternary, unpack_ternary, ef=False))
_register(PackedSymmetricCodec("ternary_ef", 1, TERNARY_GROUP,
                               pack_ternary, unpack_ternary, ef=True))


def get_codec(name: Optional[str]) -> WireCodec:
    """Wire-codec registry lookup; ``None``/``""`` alias the f32 codec."""
    if not name:
        return WIRE_CODECS["f32"]
    try:
        return WIRE_CODECS[name]
    except KeyError:
        raise ValueError(f"unknown wire dtype {name!r} "
                         f"(expected one of {sorted(WIRE_CODECS)})") from None


def deterministic_codec(codec: WireCodec) -> WireCodec:
    """The round-to-nearest sibling of a stochastic codec (int8_sr ->
    int8); identity otherwise. The on-mesh optimizer path uses it: a train
    step threads no per-step key for SR noise."""
    if not codec.stochastic:
        return codec
    base = codec.name.replace("_sr", "")
    return WIRE_CODECS[base]


# ---------------------------------------------------------------------------
# legacy helpers (the pre-registry WIRE_DTYPES API, kept for callers/tests)
# ---------------------------------------------------------------------------

WIRE_DTYPES = {name: c.payload_dtype for name, c in WIRE_CODECS.items()}

# wire-dtype names that use per-message affine int8 quantization
INT8_WIRE_DTYPES = frozenset({"int8", "int8_sr"})


def resolve_wire_dtype(name):
    """Wire-dtype name -> payload storage dtype, or None for full precision
    (``None``/``""``/``"f32"``). Packed sub-4-bit codecs store multiple
    codes per uint8 element — per-coefficient accounting must go through
    ``get_codec(name).payload_bytes(d)``, not this dtype's itemsize."""
    if not name or name == "f32":
        return None
    return get_codec(name).payload_dtype


def is_quantized_wire(name) -> bool:
    """True when the codec carries a per-message scale (int8 and below)."""
    return bool(name) and get_codec(name).quantized


def is_stochastic_wire(name) -> bool:
    """True when the wire codec rounds stochastically (needs a PRNG key)."""
    return bool(name) and get_codec(name).stochastic


def wire_itemsize(name) -> int:
    """Bytes per payload *storage element* for a wire-dtype name (1 for
    every sub-byte codec — a uint8 element packs ``group`` codes)."""
    dt = resolve_wire_dtype(name)
    return 4 if dt is None else jnp.dtype(dt).itemsize


def wire_overhead_bytes(name) -> int:
    """Per-message metadata bytes beyond the coefficients: f16 scale +
    zero-point for the affine int8 codecs, f16 scale for the packed
    symmetric codecs, nothing for float casts."""
    return get_codec(name).overhead_bytes if name else 0
