"""Share of its roofline the encode kernel reaches: the least time the
chip needs for its bytes (or flops) per step (counts.kernel_work) over the
kernel's measured device time, mean over chips."""


def read(ctx):
    return ctx.roofline("encode")
