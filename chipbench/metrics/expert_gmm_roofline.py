"""Share of their roofline of the held experts' grouped matmuls: least time
for the work the model type's ``counts.kernels`` declares as
``expert_gmm``, over the time of the ops that implement it; None where the
model declares no such kernel or the trace holds none of its ops."""


def read(ctx):
    return ctx.roofline_of("expert_gmm")
