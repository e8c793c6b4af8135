"""Device: the share of the traced window's device idle time that no
program span accounts for. The program's spans of the traced simulation
are put on the trace's clock (``program_spans``), each idle stretch is
labelled by the innermost span open at its midpoint, and the share is the
idle time under no span but the root ``run``, averaged over the devices.
The whole labelling and the gaps between the mapped routing spans and the
harness's ``route`` annotations go to standard error. Moves ``node_cycles_per_s``."""
from benchmarks.chip import program_spans


def read(ctx):
    rec = program_spans.traced_run()
    if rec is None:
        return None
    idle = program_spans.idle_by_span(ctx["trace"], rec)
    if idle is None:
        return None
    for name, s in sorted(idle.items(), key=lambda kv: -kv[1]):
        program_spans.log(f"device idle under program span {name}: {s} s")
    for name in ("pack_tables", "route_chunk"):
        err = program_spans.anchor_error_ms(rec, ctx["trace"]["host"], name)
        program_spans.log(f"program spans on the trace clock: largest gap "
                          f"between {name} and the route annotations in "
                          f"it: {err} ms")
    total = sum(idle.values())
    return (100.0 * idle.get(program_spans.UNATTRIBUTED, 0.0) / total
            if total > 0 else 0.0)
