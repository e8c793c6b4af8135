"""Host control plane: milliseconds a cycle in the program's
``upload_tables`` spans, the host-to-device copy of each chunk's routing
tables, in the traced simulation. Moves ``node_cycles_per_s``."""
from benchmarks.chip import program_spans


def read(ctx):
    rec = program_spans.traced_run()
    return None if rec is None else program_spans.ms_per_cycle(
        rec, "upload_tables")
