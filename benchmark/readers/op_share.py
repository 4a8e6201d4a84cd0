"""Share of the slice's device busy time in the operations whose name
matches ``pattern`` (a kernel by its name: ``^paged_attention``). Self
times, as moe_experts_share counts them, so an enclosing ``while`` or
fusion is not counted with its body. None where nothing matches."""
import re

from .. import trace_reduce


def read(ctx, args):
    tr = ctx.norm_trace
    if not tr or not tr.get("devices"):
        return None
    rx = re.compile(args["pattern"])
    hit = total = 0.0
    events = 0
    for name, _detail, secs in trace_reduce.self_times(next(iter(tr["devices"].values()))):
        total += secs
        if rx.search(name):
            hit, events = hit + secs, events + 1
    if not events or total <= 0:
        return None
    ctx.results.setdefault("op_share", {})[args["pattern"]] = {"events": events, "seconds": hit}
    return 100.0 * hit / total
