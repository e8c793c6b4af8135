"""Sanctioned ``jax.random`` call sites for the rng-discipline rule.

Both engines replay ONE pinned threefry draw sequence (docs/CONTRACTS.md):
per cycle, ``split(key, 4) -> (k_recv, k_dst, k_delay, k_drop)``, then the
destination draw from ``k_dst``, the delay draw from ``k_delay`` and the
drop draw from ``k_drop`` — in that order, with ``k_recv`` reserved for the
stochastic-rounding wire noise. An extra (or re-ordered) draw anywhere in
the hot path shifts every later threefry counter and breaks cross-engine
bitwise parity *silently* — the run still converges, just not identically.
So every draw inside ``src/repro/core`` and ``src/repro/kernels`` must be
registered here, keyed by ``(path relative to src/repro, def-qualname)``
with the set of ``jax.random`` functions that site may call; the comment on
each entry names the draw-sequence contract it belongs to.
"""
from __future__ import annotations

from typing import Dict, FrozenSet, Tuple

RNG_ALLOWED: Dict[Tuple[str, str], FrozenSet[str]] = {
    # reference engine, per-cycle sequence: the 4-way split and the
    # dst/delay/drop draws — THE sequence every other engine replays
    ("core/simulation.py", "cycle_core"):
        frozenset({"split", "randint", "bernoulli"}),
    # reference driver key chain: key, sub = split(key) once per cycle;
    # key_schedule replays it bitwise on device
    ("core/simulation.py", "_run_reference"): frozenset({"split"}),
    # sharded engine: device-side replay of the driver key chain
    ("core/sharded_engine.py", "key_schedule.body"): frozenset({"split"}),
    # sharded engine control plane: scanned replica of cycle_core's
    # split/dst/delay/drop order (bit-for-bit, see _draw_chunk docstring)
    ("core/sharded_engine.py", "_draw_chunk.body"):
        frozenset({"split", "randint", "bernoulli"}),
    # send-side SR noise: re-derives the reference engine's k_recv
    # (slot 0 of the per-cycle 4-way split) from the scanned key data
    ("core/sharded_engine.py", "_build_chunk_fn.chunk_fn.send"):
        frozenset({"split"}),
    # same k_recv derivation on the sender-subset (compact_all) path
    ("core/sharded_engine.py", "_build_chunk_fn.chunk_fn.send_compact"):
        frozenset({"split"}),
    # peer sampling consumes the per-cycle k_dst slot — one draw, no more
    ("core/peer_sampling.py", "uniform_peers"): frozenset({"randint"}),
    ("core/peer_sampling.py", "perfect_matching"): frozenset({"permutation"}),
    # int8_sr wire noise from k_recv (the slot the float codecs leave
    # unused), uniform over the full (N, d) block; under the partitionable
    # counter scheme element p is threefry(k_recv, (0, p)), which is how
    # wire_codec.uniform_at (no jax.random call) regenerates any subset
    ("core/wire_codec.py", "quantize_wire"): frozenset({"uniform"}),
    # adversarial fault stream: k_fault = fold_in(cycle key, FAULT_FOLD)
    # DERIVES a side key without consuming from the pinned 4-way split, so
    # fault-free runs keep the exact pre-fault threefry counters
    ("core/faults.py", "fault_key"): frozenset({"fold_in"}),
    # random_payload resample from k_fault; the subset path goes through
    # sr_noise_for_rows so sender-subset draws match the dense gather
    ("core/faults.py", "corrupt_model"): frozenset({"uniform"}),
    # one uniform per message from k_fault picks the wire bit to flip
    ("core/faults.py", "bitflip_payload"): frozenset({"uniform"}),
    # centralized baselines (Section V): their own key chains, not part of
    # the gossip draw sequence but pinned for reproducibility all the same
    ("core/ensemble.py", "run_weighted_bagging"):
        frozenset({"split", "randint"}),
    ("core/ensemble.py", "run_sequential_pegasos"):
        frozenset({"split", "randint"}),
    # core/serving.py has NO entry on purpose: the serving tier draws its
    # query-assignment randomness from a host-side numpy stream
    # (serving.assign_queries), never from jax.random — a serving draw in
    # the threefry chain would shift the pinned per-cycle counters and
    # break cross-engine parity exactly the way this allowlist exists to
    # prevent. Keep it that way: a jax.random call appearing in
    # core/serving.py should fail this rule, not get registered here.
    # core/telemetry.py likewise has NO entry on purpose: telemetry is a
    # pure read (docs/CONTRACTS.md) — spans and histograms are
    # perf_counter host timing, metric streams re-read state the engines
    # already computed, and the armed collection paths in
    # core/simulation.py / core/sharded_engine.py add zero draws (the
    # emit_streams statics only widen what the existing fns RETURN). Any
    # jax.random call appearing in core/telemetry.py should fail this
    # rule, not get registered here.
}
