"""Kernel ``gossip_cycle.fused_receive_apply``: the deliveries' required
bytes (``work.delivery_bytes``) over the kernel's device time, as a share
of the HBM bandwidth. Bytes bound it: about 7 operations for 4 bytes a
coefficient. The kernel is found by the name the trace gives its Pallas
call, that of the jitted function around it (``%fused_receive_apply.N``);
a renamed kernel reads nothing. Moves ``node_cycles_per_s``."""
from benchmarks.chip import trace_reduce, work

KERNEL = r"^%fused_receive_apply[.\d]* = "


def read(ctx):
    ops, w = ctx["trace"]["ops"], ctx["window"]
    if not ops or not w:
        return None
    t = [trace_reduce.time_ns(o, w, KERNEL) for o in ops.values()]
    if not all(t):
        return None
    need = work.delivery_bytes(ctx["deliveries"], ctx["d"]) / len(t)
    return 100.0 * need / ctx["peaks"]["peak_hbm_bytes_per_s"] / (
        sum(t) / len(t) / 1e9)
