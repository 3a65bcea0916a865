"""A host-clock span the driver took, by name."""


def reduce(ctx, span):
    return ctx.spans.get(span)
