"""Mean idle time on the device between one step program's end and the
next one's start, over the traced steps and chips: the trainer's host path
(batch placement, decode-weight solve, dispatch, metrics sync)."""


def read(ctx):
    gaps = [g for c in ctx.trace.chips for g in c.step_gaps_s]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
