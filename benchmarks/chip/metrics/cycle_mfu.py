"""Whole cycle: the share of the chip's roofline that the protocol's
required work fills over the traced window. Required time is the larger of
FLOPs over the bf16 peak and bytes over the HBM bandwidth (bytes bound it
here), for the sends, deliveries and eval points the traced simulation
counted (``work.py``); the share is that time over the window's length,
per device. Moves ``node_cycles_per_s``."""
from benchmarks.chip import work


def read(ctx):
    w, pk = ctx["window"], ctx["peaks"]
    if not w or not ctx["trace"]["ops"]:
        return None
    d, c = ctx["d"], ctx["c"]
    ev = (ctx["eval_points"], ctx["eval_nodes"], ctx["n_test"], c, d)
    flops = work.delivery_flops(ctx["deliveries"], d) + work.eval_flops(*ev)
    nbytes = (work.send_bytes(ctx["sends"], d)
              + work.delivery_bytes(ctx["deliveries"], d)
              + work.eval_bytes(*ev))
    chips = len(ctx["trace"]["ops"])
    need = work.roofline_s(flops / chips, nbytes / chips,
                           pk["peak_flops_bf16"], pk["peak_hbm_bytes_per_s"])
    return 100.0 * need / ((w[1] - w[0]) / 1e9)
