"""Serving: host milliseconds a served batch, outside the device's part:
the program's ``submit`` and ``flush`` spans less their ``serve_device``
spans (dispatch to answers ready), over the ``serve_batch`` spans, in the
traced simulation. It holds the per-row queueing of ``submit``, the batch's
assembly and padding, node assignment and the copy-out of the answers.
Moves ``query_p95_ms``."""
from benchmarks.chip import program_spans


def read(ctx):
    rec = program_spans.traced_run()
    if rec is None or not rec.named("serve_batch"):
        return None
    host = (rec.seconds("submit") + rec.seconds("flush")
            - rec.seconds("serve_device"))
    return host * 1e3 / len(rec.named("serve_batch"))
