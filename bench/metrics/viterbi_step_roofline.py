"""Share of its roofline that the ``viterbi_step`` kernel (one max-plus
step of a served batch) reaches: per call the larger of its operations
over the peak FLOP/s and its bytes over the peak HBM bandwidth for the
batch ``B`` and the ``C`` labels (``benchkit.flops.viterbi_step``),
summed over the calls the trace holds, over their summed device time.
In percent."""
from benchkit import flops, trace

KERNEL = "viterbi_step"


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    events = trace.kernel_events(tr, KERNEL)
    seconds = sum(e[2] for e in events) / 1e9
    if not events or seconds <= 0:
        return None
    peaks = ctx["peaks"]
    ops, nbytes = flops.viterbi_step(int(ctx["batch_size"]),
                                     int(ctx["config"]["num_labels"]))
    least = max(ops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * len(events) * least / seconds
