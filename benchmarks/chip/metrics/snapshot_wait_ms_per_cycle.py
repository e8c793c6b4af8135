"""Serving: milliseconds a cycle in the program's ``snapshot_adopt`` spans,
where ``GossipServer.serve_hook`` waits until the engine has written the
snapshot (``block_until_ready``), in the traced simulation. The engine
routes the next chunk only after the hook returns; a wait for the chunk
that the snapshot's build already paid (the rest of the ``snapshot`` span,
before the hook) does not show here. Moves ``node_cycles_per_s``."""
from benchmarks.chip import program_spans


def read(ctx):
    rec = program_spans.traced_run()
    return None if rec is None else program_spans.ms_per_cycle(
        rec, "snapshot_adopt")
