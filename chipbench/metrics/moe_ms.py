"""Device time per step of the expert layer (named scopes ``moe.*``:
routing, dispatch, the held experts, the shared experts, combine), mean
over chips; None where the trace holds no such scope."""


def read(ctx):
    return ctx.time_in_scope("moe")
