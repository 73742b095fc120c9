"""Device time per step of the collectives (all-gather of the encodings,
all-reduce of the loss and small leaves), mean over chips; nothing where
the trace holds no collective."""


def read(ctx):
    t = ctx.trace
    times = [c.by_kind["collective"] for c in t.chips]
    if not times or max(times) == 0 or t.steps == 0:
        return None
    return 1e3 * sum(times) / len(times) / t.steps
