"""Device time per step of latent attention (named scopes ``mla.*``: the
projections and the attention kernel), mean over chips; None where the
trace holds no such scope."""


def read(ctx):
    return ctx.time_in_scope("mla")
