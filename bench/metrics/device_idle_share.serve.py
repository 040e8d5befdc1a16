"""Share of the traced window in which no op ran on the device (the
union of op intervals, averaged over the chips)."""
from metrics_lib import idle_pct


def read(ctx):
    return idle_pct(ctx)
