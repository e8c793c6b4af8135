"""Chunk program: megabytes (10^6 bytes) a cycle that the receive kernel's
pipeline moves between HBM and on-chip memory, in the traced simulation:
the ``receive_bytes`` counters of the program's ``dispatch`` spans, counted
from the kernel's grid and block shapes (both passes of the d-tiled
kernel; the one pass of the whole-d kernel). Set against the deliveries'
required bytes, it says how far the receive pass is from tracking
deliveries. A program whose dispatch spans carry no such counter reads
nothing. Moves ``node_cycles_per_s``."""
from benchmarks.chip import program_spans


def read(ctx):
    rec = program_spans.traced_run()
    if rec is None:
        return None
    spans = rec.named("dispatch")
    if not spans or any("receive_bytes" not in s.counters for s in spans):
        return None
    return (rec.total("dispatch", "receive_bytes") / 1e6
            / program_spans.cycles(rec))
