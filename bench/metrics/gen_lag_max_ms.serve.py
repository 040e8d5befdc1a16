"""How late the load generator ran: the largest gap between a request's
due time and its submission (the engine's queue stamp)."""


def read(ctx):
    lags = [r.t_submit - r.t_due for r in ctx.measured
            if r.t_submit is not None]
    return max(lags) * 1e3 if lags else None
