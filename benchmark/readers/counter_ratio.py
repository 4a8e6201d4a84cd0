"""scale * counters[num] / counters[den] over the window's deltas."""


def read(ctx, args):
    num, den = ctx.counters.get(args["num"]), ctx.counters.get(args["den"])
    if num is None or not den:
        return None
    return float(args.get("scale", 1.0)) * num / den
