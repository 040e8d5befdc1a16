"""Output tokens the engine emitted in the window, over the window.
Decode tokens come from the engine's counter, read at each edge when an
op queued behind every dispatch so far has run (so each counted token
was computed inside the window); first tokens from their host stamps."""


def read(ctx):
    o, c = ctx.edges["open"], ctx.edges["close"]
    a, b = o["t_device"], c["t_device"]
    first = sum(1 for r in ctx.records
                if r.t_first is not None and a <= r.t_first < b)
    return (c["decode_tokens"] - o["decode_tokens"] + first) / (b - a)
