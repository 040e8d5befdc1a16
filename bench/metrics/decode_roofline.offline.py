"""The decode step's share of its roofline: the least time the chip
could take, max(operations / peak, bytes / bandwidth), over the measured
decode device time.  Operations and bytes are what the step must do,
from shapes (``cost/<attention>.py``): every weight matrix it multiplies
by and the cached keys/values of live positions, with attention in its
cheapest (for MLA, absorbed) form; the same work whatever leg runs it.
Live rows per step come from the engine's counters over the traced
window, their mean context from the requests' progress."""
from metrics_lib import decode_positions, tp_of, trace_window_mono


def read(ctx):
    tr = ctx.trace
    if tr is None or ctx.peaks is None or not ctx.trace_counts:
        return None
    t, n = tr.module_time_mean({"jit_decode_step"})
    c0, c1 = ctx.trace_counts
    ticks = c1["ticks"] - c0["ticks"]
    pos = decode_positions(ctx, *trace_window_mono(ctx))
    if not n or not ticks or not len(pos):
        return None
    rows = (c1["decode_tokens"] - c0["decode_tokens"]) / ticks
    flops, nbytes = ctx.cost.decode_step(ctx.model, rows,
                                         rows * float(pos.mean()), tp_of(ctx))
    least = n * max(flops / ctx.peaks["bf16_flops_per_s"],
                    nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
