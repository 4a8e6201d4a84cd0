"""A value the runner computed from its own per-request records."""


def read(ctx, args):
    return ctx.results.get(args["key"])
