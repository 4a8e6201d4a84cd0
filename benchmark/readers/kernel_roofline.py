"""A kernel family's share of its roofline: the least time one chip could
take for the work the algorithm needs in one step (benchmark/flops.py),
over the device time of the family's events per traced step."""
from .. import flops, trace_reduce


def read(ctx, args):
    steps = ctx.trace_facts.get("steps")
    if not ctx.norm_trace or not steps or ctx.peaks is None:
        return None
    hit = trace_reduce.op_time(ctx.norm_trace, args["pattern"])
    if hit is None:
        return None
    tr = ctx.cell["traffic"]
    work = getattr(flops, args["work"])(ctx.cell["config"], int(tr["global_batch"]) // ctx.cell["chips"],
                                        int(tr["seq"]))
    roof = flops.roofline_seconds(work["flops"], work["bytes"], ctx.peaks)
    ctx.results.setdefault("rooflines", {})[args["work"]] = {
        "bound": roof["bound"], "least_ms_per_step": 1e3 * roof["seconds"],
        "device_ms_per_step": 1e3 * hit["seconds"] / steps, "events_per_step": hit["events"] / steps}
    return 100.0 * roof["seconds"] / (hit["seconds"] / steps)
