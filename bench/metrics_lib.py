"""Arithmetic the metric readers share (``metrics/<name>.py``).  A reader
gets the run's context (``harness.run_cell``) and returns a number, or
None where it finds nothing to read."""
from __future__ import annotations

import math

import numpy as np


def p90(values):
    """90th percentile, linear between order statistics; a failed
    request counts as missing every limit (infinite)."""
    if not values:
        return None
    v = float(np.percentile(np.asarray(values, np.float64), 90))
    return v if math.isfinite(v) else None


def ttft_s(ctx):
    return [r.t_first - r.t_due if r.t_first is not None and not r.late
            else math.inf for r in ctx.measured]


def tpot_s(ctx):
    out = []
    for r in ctx.measured:
        if r.out is None or r.late or len(r.out) < 2:
            out.append(math.inf)
        else:
            out.append((r.t_done - r.t_first) / (len(r.out) - 1))
    return out


def decode_positions(ctx, a: float, b: float):
    """Decode tokens emitted between monotonic times ``a`` and ``b``, each
    as the number of cached positions it read.  A request's tokens after
    its first are taken as evenly spread between its first token and its
    completion."""
    out = []
    for r in ctx.records:
        if r.out is None or len(r.out) < 2:
            continue
        n, p = len(r.out), len(r.prompt)
        j = np.arange(1, n)
        t = r.t_first + (r.t_done - r.t_first) * j / (n - 1)
        out.append(p + j[(t >= a) & (t < b)])
    return np.concatenate(out) if out else np.zeros((0,), np.int64)


def prompts_prefilled(ctx, a: float, b: float):
    """Prompt lengths of requests whose first token came in [a, b)."""
    return [len(r.prompt) for r in ctx.records
            if r.t_first is not None and a <= r.t_first < b]


def trace_window_mono(ctx):
    """The traced window on the monotonic clock (seconds)."""
    tr = ctx.trace
    return ((tr.window[0] - tr.offset_ns) / 1e9,
            (tr.window[1] - tr.offset_ns) / 1e9)


def decode_step_ms(ctx):
    if ctx.trace is None:
        return None
    t, n = ctx.trace.module_time_mean({"jit_decode_step"})
    return t / n * 1e3 if n else None


def idle_pct(ctx):
    return None if ctx.trace is None else 100.0 * ctx.trace.idle_share()


def tp_of(ctx):
    return ctx.config["mesh"][-1]
