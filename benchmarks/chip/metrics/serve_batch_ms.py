"""Serving (``GossipServer._serve_pending``: ``cache.take_nodes`` and
``serve_voted_kernel``): the median of the server's own per-batch latency,
dispatch to answers ready, over the traced simulation's batches. Moves
``query_p95_ms``."""
import statistics


def read(ctx):
    lat = [b["latency_s"] for b in ctx["batches"]]
    return statistics.median(lat) * 1e3 if lat else None
