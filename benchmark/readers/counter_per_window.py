"""scale * counters[num] / the window's seconds."""


def read(ctx, args):
    num, w = ctx.counters.get(args["num"]), ctx.results.get("window_s")
    if num is None or not w:
        return None
    return float(args.get("scale", 1.0)) * num / w
