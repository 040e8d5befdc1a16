"""Model FLOP/s utilisation of the whole window: the model operations of
the tokens decoded in the window (the engine's counter, read as for
``output_tokens_per_s``; matrix products plus attention over their mean
context) and of the prompts prefilled in it, over window x chips x
peak."""
from metrics_lib import decode_positions, prompts_prefilled, tp_of


def read(ctx):
    if ctx.peaks is None:
        return None
    o, c = ctx.edges["open"], ctx.edges["close"]
    a, b = o["t_device"], c["t_device"]
    m, cost, tp = ctx.model, ctx.cost, tp_of(ctx)
    pos = decode_positions(ctx, a, b)
    mean = float(pos.mean()) if len(pos) else 0.0
    flops = (c["decode_tokens"] - o["decode_tokens"]) * (
        cost.matmul_flops(m, tp) + cost.decode_attn_flops(m, mean, tp))
    for p in prompts_prefilled(ctx, a, b):
        # token i of a prompt attends i + 1 positions
        flops += p * cost.matmul_flops(m, tp) + cost.prefill_attn_flops(
            m, p * (p + 1) / 2, tp)
    return 100.0 * flops / ((b - a) * ctx.peaks["bf16_flops_per_s"])
