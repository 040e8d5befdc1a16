"""Share of the runtime's core-seconds in the window in which a core had
a runnable worker (the UMT runtime tracer's events)."""


def read(ctx):
    v = ctx.host_core_busy
    return None if v is None else 100.0 * v
