"""Live slots per decode tick over the window, as a share of the slots
(window deltas of the engine's occupancy sum and tick count)."""


def read(ctx):
    o, c = ctx.edges["open"], ctx.edges["close"]
    ticks = c["ticks"] - o["ticks"]
    if ticks <= 0:
        return None
    return 100.0 * (c["occupancy_sum"] - o["occupancy_sum"]) / ticks
