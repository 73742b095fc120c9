"""Model FLOPs of the traced steps' unique tokens over the traced window,
as a share of the chips' bf16 peak (counts.flops_per_token's convention:
the coded step's recomputation is not counted)."""


def read(ctx):
    t = ctx.trace
    if not t.chips or t.steps == 0:
        return None
    flops = ctx.flops_per_token * ctx.tokens_per_step * t.steps
    return 100.0 * flops / (t.window_s * ctx.n_chips
                            * ctx.peak["bf16_flops_per_s"])
