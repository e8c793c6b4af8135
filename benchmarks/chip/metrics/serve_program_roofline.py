"""Serving program ``jit_serve_voted_kernel`` (``cache.take_nodes``, which
gathers a batch's cached models, then the ``voted_predict_batched``
kernel): the queries' required bytes (``work.vote_bytes``: C models, the
query row, the count and the answer a query) over the program's device
time (the ``XLA Modules`` line), as a share of the HBM bandwidth; bytes
bound it. Padding of a tail batch is not required work.

It times the whole program, not the Pallas call alone: the kernel reads
the models from the on-chip memory the gather filled, so the kernel's own
time leaves out the reading from HBM that the vote requires. A renamed
serving program reads nothing. Moves ``query_p95_ms``."""
from benchmarks.chip import trace_reduce, work

PROGRAM = r"^jit_serve_voted_kernel\("


def read(ctx):
    mods, w = ctx["trace"]["modules"], ctx["window"]
    q = sum(len(b["q"]) for b in ctx["batches"])
    if not mods or not w or not q:
        return None
    t = [trace_reduce.time_ns(m, w, PROGRAM) for m in mods.values()]
    if not any(t):
        return None
    return 100.0 * work.vote_bytes(q, ctx["c"], ctx["d"]) / sum(t) / 1e-9 \
        / ctx["peaks"]["peak_hbm_bytes_per_s"]
