"""Device time per step of every operation that is neither a codec kernel
nor a collective (the subset scan's forward and backward, the optimizer
update, layout copies), mean over chips."""


def read(ctx):
    t = ctx.trace
    if not t.chips or t.steps == 0:
        return None
    total = sum(c.by_kind["compute"] for c in t.chips) / len(t.chips)
    return 1e3 * total / t.steps
