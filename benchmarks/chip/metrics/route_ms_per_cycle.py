"""Host control plane: milliseconds a cycle in routing and table packing
(``_HostRouter.route_chunk``, ``dense_table``, ``pack_compact_*``), timed by
the harness around those calls in the traced simulation. Moves
``node_cycles_per_s``."""


def read(ctx):
    s = ctx["spans"].get("route")
    return None if s is None else s * 1e3 / ctx["cycles"]
