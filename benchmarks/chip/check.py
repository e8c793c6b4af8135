"""The comparison that decides ``correct``: what the timed path produced in
one simulation of the window, drawn from the seed, against the plain
reference (``reference.py``) run from the same seed on the same data.

Numbers compared, each against its limit in ``limits/<workload>.json``:

* ``economy_mismatch`` — messages sent, delivered, lost, overflowed and in
  flight at the end, and delivered per cycle: the sum of the absolute
  differences. Integer work of the host router; exact, limit 0.
* ``counter_mismatch`` — the update counters and valid-slot counts of the
  sampled nodes' caches after the last cycle: entries that differ. They
  depend on the routing alone; exact, limit 0.
* ``model_gap`` — the sampled nodes' cached models after the last cycle:
  the widest ``|w - w_ref|`` of one model over the larger of ``|w_ref|``
  and the median ``|w_ref|``. Covers the receive kernel, merge, Pegasos
  update, cache write and the wire's round trip.
* ``answer_mismatch`` (serving cells) — served answers that differ from
  the reference's vote at the same snapshot, among the queries whose vote
  is decisive (``reference.DECISIVE``): every one of the node's models
  scores the query clear of zero, so no rounding can turn the answer.
  Exact, limit 0.
* ``eval_excess`` — the eval points' error curves (PREDICT and
  VOTEDPREDICT over the eval nodes and test rows), as counts of wrong
  (eval node, test row) pairs: by how many pairs, summed over the points,
  the program's count lies outside the range the reference allows, whose
  ends count every pair the reference cannot decide (``reference.
  EVAL_DECISIVE``: the program scores at the chip's default matmul
  precision) as right and as wrong. Exact, limit 0.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmarks.chip import reference as ref_mod

SAMPLE_NODES = 512


def sample_nodes(n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.sort(rng.choice(n, size=min(SAMPLE_NODES, n), replace=False))


def outcome_from_reference(p: ref_mod.Protocol, X, y, X_test, y_test,
                           sim_seed: int, nodes: np.ndarray, batches,
                           precision: str = "f32") -> Dict:
    """Run the reference for one simulation and read from it what the
    program's outcome holds: the economy, the error curves, the sampled
    caches after the last cycle and the answer to every served query.

    ``batches``: ``(cycle, rows, assign_seed, offset, size)`` of every
    answered batch: the snapshot's cycle, the test rows of its real
    queries, the front end's seed, the queries it answered before this
    batch, and the batch size the assignment is drawn for."""
    by_cycle: Dict[int, List] = {}
    for i, (cycle, *rest) in enumerate(batches):
        by_cycle.setdefault(cycle, []).append((i, *rest))
    answers: List = [None] * len(batches)
    decisive: List = [None] * len(batches)
    sample = {}

    def on_eval(cycle, s):
        for i, rows, a_seed, offset, size in by_cycle.get(cycle, []):
            assign = ref_mod.assign_uniform(size, p.n, a_seed,
                                            offset)[:len(rows)]
            answers[i], decisive[i] = ref_mod.vote(s.cache_w, s.count,
                                                   assign, X_test[rows])
        if cycle == p.cycles:
            sample.update(w=s.cache_w[nodes].copy(), t=s.cache_t[nodes].copy(),
                          count=s.count[nodes].copy())

    res = ref_mod.run(p, X, y, X_test, y_test, sim_seed, precision=precision,
                      on_eval=on_eval)
    return dict(economy=ref_mod.economy(res),
                delivered_per_cycle=res["delivered_per_cycle"],
                err=res["err_fresh"] + res["err_voted"],
                wrong=res["wrong_fresh"] + res["wrong_voted"],
                eval_pairs=res["eval_pairs"], sample=sample,
                answers=answers, decisive=decisive)


def compare(prog: Dict, ref: Dict, serving: bool) -> Dict[str, float]:
    """The numbers compared, program outcome against reference outcome."""
    out = {}
    pe = list(prog["economy"]) + list(prog["delivered_per_cycle"])
    re_ = list(ref["economy"]) + list(ref["delivered_per_cycle"])
    out["economy_mismatch"] = float(
        sum(abs(a - b) for a, b in zip(pe, re_)) + abs(len(pe) - len(re_)))
    ps, rs = prog["sample"], ref["sample"]
    out["counter_mismatch"] = float(np.sum(ps["t"] != rs["t"])
                                    + np.sum(ps["count"] != rs["count"]))
    diff = np.linalg.norm(ps["w"].astype(np.float64) - rs["w"], axis=-1)
    norm = np.linalg.norm(rs["w"].astype(np.float64), axis=-1)
    floor = max(float(np.median(norm)), np.finfo(np.float32).tiny)
    out["model_gap"] = float(np.max(diff / np.maximum(norm, floor)))
    # an error rate is a count of wrong pairs over eval_pairs (10^5 at most
    # here): f32 carries it to well under half a pair
    wrong = np.rint(np.asarray(prog["err"], np.float64) * ref["eval_pairs"])
    lo, hi = np.asarray(ref["wrong"], np.float64).reshape(-1, 2).T
    out["eval_excess"] = (
        float(np.sum(np.maximum(0.0, np.maximum(lo - wrong, wrong - hi))))
        if wrong.size == lo.size else float("inf"))
    if serving:
        cat = lambda parts: np.concatenate(parts) if parts else np.zeros(0)
        got, want = cat(prog["answers"]), cat(ref["answers"])
        sure = cat(ref["decisive"]).astype(bool)
        out["answer_mismatch"] = (
            float(np.sum((got != want) & sure)) if got.size == want.size
            else float("inf"))
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Correct when every number is within its limit (a missing number, or
    one that is not finite, is not)."""
    return all(k in numbers and np.isfinite(numbers[k])
               and numbers[k] <= lim for k, lim in limits.items())
