"""Adversarial-node fault injection + robust merge defenses.

The paper's failure model is *honest*: messages vanish (drop), arrive late
(delay) or find their destination offline (churn) — they never lie. This
module adds the Byzantine axis the ROADMAP calls for: a seed-chosen subset
of nodes corrupts every model it transmits, and the merge path may defend.

Three pieces, mirroring the ``WIRE_CODECS`` registry pattern:

* ``FAULT_MODELS`` — named send-side corruptions. The *model-kind* faults
  (``sign_flip``, ``amplify``, ``zero``, ``random_payload``,
  ``stale_replay``) rewrite the transmitted model BEFORE the wire encode
  (a Byzantine node lies about its weights, then encodes the lie honestly);
  the *wire-kind* fault (``bitflip``) corrupts the encoded payload bytes
  AFTER ``WireCodec.encode`` — an honest sender behind a flaky link —
  exercising decode robustness for every registered codec.
* ``DEFENSES`` — receive-side payload screens applied per merge round,
  against the receiver's *current* ``lastModel`` (the Algorithm-1 chain
  ``lastModel <- m`` makes round k's defense depend on round k-1's
  accepted message, so the defense runs inside the K-round apply loop of
  every engine path, including the Pallas kernel). ``norm_clip`` rescales
  an oversized payload's L2 norm down to a multiple of the receiver's own;
  ``cosine_gate`` rejects payloads anti-aligned with the local model. Both
  reject non-finite payloads (the ``bitflip`` fault on float wire codecs).
* the ``k_fault`` key contract — fault draws ride a key derived by
  ``jax.random.fold_in`` from the per-cycle key (``fault_key``). fold_in
  derives without consuming from the parent counter, so the pinned
  ``split(key, 4)`` sequence of docs/CONTRACTS.md — and therefore every
  fault-free run — stays bitwise identical to the pre-fault engines.

Cross-engine bitwise parity: the subset variants (``rows=`` arguments)
regenerate exactly the dense draws at the given global rows via
``sr_noise_for_rows`` (the mechanism proven by the "int8_sr" compacted
send path), so the sharded engine's sender-subset ``compact_all`` packing
corrupts bit-for-bit like the reference engine.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.wire_codec import sr_noise_for_rows

# ``fold_in`` tag of the per-cycle fault key (k_fault): an arbitrary fixed
# constant, pinned forever — changing it changes every faulty run's draws
FAULT_FOLD = 0x0FA17
# host-side stream tag of the Byzantine subset draw: a SEPARATE
# np.random stream from ``_host_scenario``'s (churn trace + eval subset),
# so enabling faults cannot shift the eval-node draw of a fault-free run
BYZANTINE_STREAM_TAG = 0xB12A

SIGN_FLIP_GAMMA = 4.0     # sign_flip transmits -gamma * w: a *scaled* sign
#                           reversal (gradient-reversal attack). The scaling
#                           is deliberate: a pure -w preserves the norm, so
#                           no norm screen could ever catch it — the
#                           amplified variant is both the stronger attack
#                           and the one norm_clip can provably bound.
AMPLIFY_GAMMA = 8.0       # amplify transmits +gamma * w

NORM_CLIP_MULT = 2.0      # clip ||msg|| to MULT * ||recv|| ...
NORM_CLIP_FLOOR = 1.0     # ... but never below FLOOR (the zero-init phase
#                           has ||recv|| = 0; a floor keeps honest early
#                           messages flowing instead of clipping them away)
COSINE_GATE_THRESHOLD = -0.2   # reject when cos(msg, recv) < threshold
COSINE_GATE_MIN_NORM = 1e-3    # ... but only once ||recv|| is established

DEFENSES = ("none", "norm_clip", "cosine_gate")


@dataclass(frozen=True)
class FaultModel:
    """One registered adversarial behavior.

    ``kind`` places the corruption on the send path: ``"model"`` faults
    rewrite ``(send_w, send_t)`` before the wire encode, ``"wire"`` faults
    rewrite the encoded payload bytes after it."""
    name: str
    kind: str                 # "model" | "wire"
    description: str


FAULT_MODELS: Dict[str, FaultModel] = {}


def _register(fault: FaultModel) -> FaultModel:
    assert fault.name not in FAULT_MODELS, fault.name
    assert fault.kind in ("model", "wire"), fault.kind
    FAULT_MODELS[fault.name] = fault
    return fault


_register(FaultModel("sign_flip", "model",
                     f"transmit -{SIGN_FLIP_GAMMA:g}*w (scaled sign "
                     "reversal / gradient-reversal attack)"))
_register(FaultModel("amplify", "model",
                     f"transmit {AMPLIFY_GAMMA:g}*w (model amplification)"))
_register(FaultModel("zero", "model",
                     "transmit the zero model (knowledge erasure)"))
_register(FaultModel("random_payload", "model",
                     "transmit uniform noise at the sender's own "
                     "coefficient scale"))
_register(FaultModel("stale_replay", "model",
                     "retransmit the node's oldest cached model "
                     "(tau ~ cache_size receives ago)"))
_register(FaultModel("bitflip", "wire",
                     "flip one uniform random bit of the encoded wire "
                     "payload (honest fault, post-encode)"))


def get_fault(name: Optional[str]) -> Optional[FaultModel]:
    """Resolve a fault-model name; ``None``/"" = no fault injection."""
    if name is None or name == "":
        return None
    try:
        return FAULT_MODELS[name]
    except KeyError:
        raise ValueError(f"unknown fault model {name!r} "
                         f"(expected one of {sorted(FAULT_MODELS)})"
                         ) from None


def check_defense(name: str) -> str:
    if name not in DEFENSES:
        raise ValueError(f"unknown defense {name!r} "
                         f"(expected one of {list(DEFENSES)})")
    return name


def byzantine_mask(seed: int, n: int, frac: float) -> np.ndarray:
    """The static per-run Byzantine node subset: ``round(frac * n)`` nodes
    chosen without replacement from a host stream keyed by
    ``(seed, BYZANTINE_STREAM_TAG)`` — deliberately NOT the
    ``_host_scenario`` stream, whose churn-trace/eval-subset draws must
    not shift when faults turn on. Shared by both engines."""
    if not 0.0 <= frac <= 1.0:
        raise ValueError(f"byzantine_frac must be in [0, 1], got {frac}")
    mask = np.zeros(n, bool)
    k = int(round(frac * n))
    if k:
        rng = np.random.default_rng(
            np.random.SeedSequence([seed, BYZANTINE_STREAM_TAG]))
        mask[rng.choice(n, size=k, replace=False)] = True
    return mask


def fault_key(key):
    """The per-cycle fault key: ``fold_in`` DERIVES a new key from the
    cycle key without consuming from the pinned ``split(key, 4)`` draw
    sequence — the k_fault contract of docs/CONTRACTS.md that keeps
    fault-free runs bitwise identical to the pre-fault engines."""
    return jax.random.fold_in(key, FAULT_FOLD)


def corrupt_model(fault: FaultModel, byz, key, w, t, old_w=None, old_t=None,
                  rows=None):
    """Apply a model-kind fault on the Byzantine rows of a send batch.

    ``w``: (m, d) f32 models about to be transmitted; ``t``: (m,) int32
    counters; ``byz``: (m,) bool. ``old_w``/``old_t`` are the stale models
    (``cache.cache_oldest``), required by ``stale_replay`` only. ``key`` is
    the per-cycle ``fault_key``; ``random_payload`` draws its noise from it
    — dense callers (m == n) leave ``rows=None`` and draw the full
    ``(n, d)`` uniform block, subset callers pass the global row ids
    so ``sr_noise_for_rows`` regenerates the identical values at those
    positions (bitwise — the compact_all parity mechanism)."""
    name = fault.name
    if name == "sign_flip":
        cw, ct = -SIGN_FLIP_GAMMA * w, t
    elif name == "amplify":
        cw, ct = AMPLIFY_GAMMA * w, t
    elif name == "zero":
        cw, ct = jnp.zeros_like(w), t
    elif name == "random_payload":
        scale = jnp.max(jnp.abs(w), axis=-1, keepdims=True)
        if rows is None:
            u = jax.random.uniform(key, w.shape)
        else:
            u = sr_noise_for_rows(key, rows, w.shape[-1])
        cw, ct = (2.0 * u - 1.0) * scale, t
    elif name == "stale_replay":
        cw, ct = old_w, old_t
    else:
        raise ValueError(f"{name!r} is not a model-kind fault")
    return (jnp.where(byz[:, None], cw, w), jnp.where(byz, ct, t))


def bitflip_payload(byz, key, payload, rows=None):
    """Flip ONE uniformly drawn bit in each Byzantine row of an encoded
    payload block — wire-level corruption applied after
    ``WireCodec.encode`` (the sender's EF residual, computed from the
    pre-flip bytes, stays honest). Works for every registered codec's
    payload dtype by bitcasting to the matching unsigned integer lane.

    The bit position comes from one uniform per message; dense callers
    draw ``uniform(key, (n, 1))``, subset callers regenerate the
    same values at their global ``rows`` via ``sr_noise_for_rows`` —
    positionally bitwise-equal, like the "int8_sr" compacted send."""
    m, p = payload.shape
    itemsize = jnp.dtype(payload.dtype).itemsize
    nbits = p * itemsize * 8
    if rows is None:
        u = jax.random.uniform(key, (m, 1))[:, 0]
    else:
        u = sr_noise_for_rows(key, rows, 1)[:, 0]
    bit = jnp.minimum((u * nbits).astype(jnp.uint32), nbits - 1)
    uint = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[itemsize]
    col = (bit // (itemsize * 8)).astype(jnp.int32)
    pos = bit % (itemsize * 8)
    lane = jnp.arange(p, dtype=jnp.int32)[None, :]
    flip = jnp.where(lane == col[:, None],
                     jnp.left_shift(jnp.uint32(1), pos)[:, None],
                     jnp.uint32(0)).astype(uint)
    raw = jax.lax.bitcast_convert_type(payload, uint)
    flipped = jax.lax.bitcast_convert_type(raw ^ flip, payload.dtype)
    return jnp.where(byz[:, None], flipped, payload)


def apply_defense(defense: str, msg_w, valid, recv_w, axis: int = -1):
    """Screen one receive round's payloads against the receiver's state.

    ``msg_w``: (m, d) decoded f32 payloads; ``valid``: (m,) bool;
    ``recv_w``: (m, d) the receiver's CURRENT lastModel (the round chain's
    ``prev``). ``axis`` is the coefficient axis: the node-minor Pallas
    receive kernel passes (d, m) payloads with ``axis=0`` and a (1, m)
    ``valid``; the reductions keep their dimension, so the per-message
    results broadcast against the payloads either way.

    Returns ``(msg_w, valid, gated, clipped)``: the (possibly rescaled)
    payloads, the surviving-valid mask, and per-node bool indicators of a
    rejected (``gated``) / rescaled (``clipped``) message, all shaped like
    ``valid``. ``"none"`` is a static no-op so undefended traces stay
    structurally identical."""
    if defense == "none":
        zeros = jnp.zeros(valid.shape, bool)
        return msg_w, valid, zeros, zeros
    red = lambda a: jnp.sum(a, axis=axis, keepdims=True)
    sq = red(msg_w * msg_w)
    rn = red(recv_w * recv_w)
    finite = jnp.isfinite(sq)          # NaN/inf anywhere poisons the sum
    per_msg = lambda a: a.reshape(valid.shape)
    if defense == "norm_clip":
        thr = jnp.maximum(NORM_CLIP_MULT ** 2 * rn, NORM_CLIP_FLOOR ** 2)
        clip = finite & (sq > thr)
        scale = jnp.sqrt(thr / jnp.maximum(sq, 1e-30))
        msg_w = jnp.where(clip, msg_w * scale, msg_w)
        finite, clip = per_msg(finite), per_msg(clip)
        return (msg_w, valid & finite, valid & ~finite, valid & clip)
    if defense == "cosine_gate":
        dot = red(msg_w * recv_w)
        anti = (rn > COSINE_GATE_MIN_NORM ** 2) \
            & (dot < COSINE_GATE_THRESHOLD * jnp.sqrt(sq * rn))
        reject = per_msg(~finite | anti)
        return (msg_w, valid & ~reject, valid & reject,
                jnp.zeros(valid.shape, bool))
    raise ValueError(f"unknown defense {defense!r} "
                     f"(expected one of {list(DEFENSES)})")
