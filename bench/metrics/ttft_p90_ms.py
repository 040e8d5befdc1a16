"""Time to first token, p90 over the requests due in the window, from
when each was due (the generator's schedule) to its first token."""
from metrics_lib import p90, ttft_s


def read(ctx):
    v = p90(ttft_s(ctx))
    return None if v is None else v * 1e3
