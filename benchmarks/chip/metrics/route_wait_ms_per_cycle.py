"""Host control plane: milliseconds a cycle that the device idles under the
program's ``route_chunk`` spans with a chunk dispatched ahead of them (those
that carry ``chunk_in_flight``), averaged over the devices, in the traced
simulation. It is the part of the host's routing of chunk i+1 that outlasts
chunk i on the device: 0 where every such routing is hidden, and it shrinks
with any speed-up of the routing, where ``route_hidden_share`` moves only
once a routing fits under its chunk. The first routing of a simulation,
which nothing can hide, is left out. The idle stretches are cut at the
spans' edges, not labelled by their midpoints. A program whose spans carry
no such counter reads nothing. Moves ``node_cycles_per_s``."""
from benchmarks.chip import program_spans, trace_reduce


def read(ctx):
    rec = program_spans.traced_run()
    if rec is None:
        return None
    trace = ctx["trace"]
    off = program_spans.offset_ns(rec, trace["host"])
    routes = [(s.t0_ns + off, s.t1_ns + off) for s in rec.named("route_chunk")
              if "chunk_in_flight" in s.counters and s.t1_ns is not None] \
        if off is not None else []
    ops, w = trace["ops"], trace["window"]
    if not routes or not ops or not w:
        return None
    idle_ns = sum(max(0.0, min(e, r1) - max(s, r0))
                  for dev_ops in ops.values()
                  for s, e in trace_reduce.idle_gaps(dev_ops, w)
                  for r0, r1 in routes)
    return idle_ns / len(ops) / 1e6 / program_spans.cycles(rec)
