"""Share of the traced window in which no operation ran, on the busiest
chip."""


def read(ctx):
    t = ctx.trace
    if not t.chips or t.window_s <= 0:
        return None
    busiest = max(c.busy_s for c in t.chips)
    return 100.0 * (1.0 - busiest / t.window_s)
