"""Work the protocol requires, counted from a run's own counters and the
configuration's shapes: the bytes and operations behind ``cycle_mfu``,
``receive_roofline`` and ``serve_program_roofline``.

The counts are of protocol work, not of what an implementation happens to
do: a send reads the sender's freshest model and writes one message at the
wire width; a delivery reads the message, the receiver's lastModel and its
local example, and writes one cache slot, the lastModel and the freshest
model; an eval point scores every eval node's C cached models and its
freshest one on every test row. A pass over nodes that receive nothing is
not required work, so an implementation that drops it cannot push a share
over 100%.
"""
from __future__ import annotations

F32 = 4


def model_bytes(d: int) -> int:
    """A model: d f32 coefficients and its int32 update counter."""
    return d * F32 + 4


def message_bytes(d: int, wire_bytes_per_coef: int = F32) -> int:
    """A message on the wire: the coefficients at the wire width and the
    counter."""
    return d * wire_bytes_per_coef + 4


def send_bytes(sends: int, d: int) -> int:
    return sends * (model_bytes(d) + message_bytes(d))


def delivery_bytes(deliveries: int, d: int) -> int:
    """Message in, lastModel and example (d + 1 words) in, and the cache
    slot, lastModel and freshest model out."""
    per = message_bytes(d) + model_bytes(d) + (d + 1) * F32 \
        + 3 * model_bytes(d)
    return deliveries * per


def delivery_flops(deliveries: int, d: int) -> int:
    """Merge (add, halve), margin (multiply-add), decay and hinge step
    (multiply y·x, scale by eta, scale w, add): 7 operations a coefficient."""
    return deliveries * 7 * d


def eval_flops(eval_points: int, eval_nodes: int, n_test: int, c: int,
               d: int) -> int:
    """Scores of the C cached models and the freshest one, a multiply-add
    per coefficient, for every eval node and test row."""
    return eval_points * eval_nodes * n_test * (c + 1) * d * 2


def eval_bytes(eval_points: int, eval_nodes: int, n_test: int, c: int,
               d: int) -> int:
    return eval_points * (eval_nodes * (c + 1) * model_bytes(d)
                          + n_test * (d + 1) * F32)


def vote_bytes(queries: int, c: int, d: int) -> int:
    """VOTEDPREDICT of one query: the node's C cached models and the query
    row in, the valid-slot count in and the answer out (one word each)."""
    return queries * (c * d + d + 2) * F32


def roofline_s(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak_flops, nbytes / peak_bw)
