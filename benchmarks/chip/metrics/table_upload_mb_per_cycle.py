"""Host control plane: megabytes (10^6 bytes) a cycle that the program's
``upload_tables`` spans copy host to device (their ``h2d_bytes``
counters, the tables' exact sizes), in the traced simulation. Moves
``node_cycles_per_s``."""
from benchmarks.chip import program_spans


def read(ctx):
    rec = program_spans.traced_run()
    if rec is None or not rec.named("upload_tables"):
        return None
    return (rec.total("upload_tables", "h2d_bytes") / 1e6
            / program_spans.cycles(rec))
