"""Time per output token after the first, p90 over the requests due in
the window: (completion - first token) / (tokens - 1)."""
from metrics_lib import p90, tpot_s


def read(ctx):
    v = p90(tpot_s(ctx))
    return None if v is None else v * 1e3
