"""Host control plane: milliseconds a cycle in the program's
``stage_draws`` span, where the engine draws every chunk's destinations and
arrivals on the device and copies them to the host, in the traced
simulation. Moves ``node_cycles_per_s``."""
from benchmarks.chip import program_spans


def read(ctx):
    rec = program_spans.traced_run()
    return None if rec is None else program_spans.ms_per_cycle(
        rec, "stage_draws")
