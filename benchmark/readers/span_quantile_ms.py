"""A quantile (``q``, in percent; default the median) of a benchmark-side
span, in milliseconds."""
import numpy as np


def read(ctx, args):
    vals = ctx.spans.get(args["span"])
    return 1e3 * float(np.percentile(vals, float(args.get("q", 50)))) if vals else None
