"""Chunk program (the ``_build_chunk_fn`` scan: payload gather, receive,
send, eval): device milliseconds of its executions a cycle, from the
``XLA Modules`` line of the trace, averaged over the devices. Moves
``node_cycles_per_s``."""
from benchmarks.chip import trace_reduce

MODULE = r"chunk_fn"


def read(ctx):
    mods, w = ctx["trace"]["modules"], ctx["window"]
    if not mods or not w:
        return None
    t = [trace_reduce.time_ns(m, w, MODULE) for m in mods.values()]
    if not any(t):
        return None
    return sum(t) / len(t) / 1e6 / ctx["cycles"]
