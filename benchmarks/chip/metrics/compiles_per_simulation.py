"""Simulation set-up: the backend compiles (persistent-cache loads
included) that the program recorded as ``compile`` spans in the traced
simulation. Each one's ``fun_name``, seconds and enclosing span go to
standard error. Moves ``node_cycles_per_s``."""
from benchmarks.chip import program_spans


def read(ctx):
    rec = program_spans.traced_run()
    if rec is None:
        return None
    by_id = {s.id: s for s in rec.spans}
    compiles = rec.named("compile")
    for s in compiles:
        under = by_id.get(s.parent)
        program_spans.log(
            f"compile in the traced simulation: {s.counters['fun_name']} "
            f"{s.counters['seconds']:.6f} s under "
            f"{under.name if under else '-'}")
    return len(compiles)
