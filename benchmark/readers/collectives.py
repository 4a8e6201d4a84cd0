"""Share of the traced slice in collective operations (``part`` =
"total") or in the part of them no compute hides ("exposed")."""
from .. import trace_reduce


def read(ctx, args):
    if not ctx.norm_trace:
        return None
    got = trace_reduce.collectives(ctx.norm_trace)
    if got is None:
        return None
    return got["collective_pct" if args.get("part", "total") == "total" else "exposed_pct"]
