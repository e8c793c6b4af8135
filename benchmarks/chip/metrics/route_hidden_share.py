"""Host control plane: the share, in %, of the program's ``route_chunk``
spans with a chunk dispatched ahead of them that ended while that chunk
was still on the device (their ``chunk_in_flight`` counter), in the traced
simulation. 100: the host's routing was hidden under the device's chunk
every time; 0: the device waited for the host every time. A program whose
spans carry no such counter reads nothing. Moves ``node_cycles_per_s``."""
from benchmarks.chip import program_spans


def read(ctx):
    rec = program_spans.traced_run()
    if rec is None:
        return None
    flags = [s.counters["chunk_in_flight"] for s in rec.named("route_chunk")
             if "chunk_in_flight" in s.counters]
    return 100.0 * sum(flags) / len(flags) if flags else None
