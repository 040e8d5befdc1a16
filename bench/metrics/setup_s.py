"""Process start to window start: imports, weights, engine, warm-up
(compilation or loading from the persistent cache) and the ramp."""


def read(ctx):
    return ctx.setup_s
