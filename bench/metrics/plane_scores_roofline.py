"""Share of its roofline that the ``plane_scores`` kernel (the
approximate oracle, one call per block of an approximate pass) reaches:
for each call the least time the chip could take, the larger of its
operations over the peak FLOP/s and its bytes over the peak HBM
bandwidth (``benchkit.flops.plane_scores``), summed over the calls the
trace holds, over their summed device time.  In percent."""
from benchkit import flops, trace

KERNEL = "plane_scores"


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    events = trace.kernel_events(tr, KERNEL)
    seconds = sum(e[2] for e in events) / 1e9
    if not events or seconds <= 0:
        return None
    cfg, peaks = ctx["config"], ctx["peaks"]
    ops, nbytes = flops.plane_scores(int(cfg["cap"]),
                                     ctx["task_dim"])
    least = max(ops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * len(events) * least / seconds
