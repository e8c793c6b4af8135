"""Device: the share of the traced window in which no operation ran,
1 - (union of operation intervals) / window, averaged over the devices.
Moves ``node_cycles_per_s``."""
from benchmarks.chip import trace_reduce


def read(ctx):
    ops, w = ctx["trace"]["ops"], ctx["window"]
    if not ops or not w:
        return None
    busy = [trace_reduce.busy_ns(o, w) for o in ops.values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (w[1] - w[0]))
