"""Device time of the prefill programs per 1,000 prompt tokens of the
requests whose first token came inside the traced window."""
from metrics_lib import prompts_prefilled, trace_window_mono


def read(ctx):
    if ctx.trace is None:
        return None
    t, n = ctx.trace.module_time_mean({"jit_prefill_step", "jit_chunk_step"})
    toks = sum(prompts_prefilled(ctx, *trace_window_mono(ctx)))
    return t * 1e3 / (toks / 1e3) if n and toks else None
