"""Device time per execution of the decode step (traced window)."""
from metrics_lib import decode_step_ms


def read(ctx):
    return decode_step_ms(ctx)
