"""Share of the traced slice in which no operation ran on the device."""
from .. import trace_reduce


def read(ctx, args):
    if not ctx.norm_trace:
        return None
    return trace_reduce.device_busy(ctx.norm_trace).get("idle_pct")
