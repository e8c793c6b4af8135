"""Node-mesh collectives (the ring of ``ppermute`` steps that gathers the
messages, ``_mesh_take_slots``, and the eval's ``psum``): device
milliseconds of the collective operations a cycle, from the ``XLA Ops``
line of the trace, averaged over the devices. A run on one chip has none
and reads nothing. Moves ``node_cycles_per_s``."""
from benchmarks.chip import trace_reduce

COLLECTIVE = (r"^%(collective-permute|all-reduce|all-gather|reduce-scatter"
              r"|all-to-all)[-a-z]*[.\d]* = ")


def read(ctx):
    ops, w = ctx["trace"]["ops"], ctx["window"]
    if not ops or not w:
        return None
    t = [trace_reduce.time_ns(o, w, COLLECTIVE) for o in ops.values()]
    if not any(t):
        return None
    return sum(t) / len(t) / 1e6 / ctx["cycles"]
