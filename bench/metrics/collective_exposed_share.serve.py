"""Collective-op time with no other op running on the device, over the
decode step's device time; the largest over the chips."""


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    worst = None
    for dev in tr.devices:
        t, n = tr.module_time(dev, {"jit_decode_step"})
        if n and t > 0:
            v = 100.0 * tr.exposed_collective_s(dev) / t
            worst = v if worst is None else max(worst, v)
    return worst
