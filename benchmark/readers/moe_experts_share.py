"""Share of the slice's device time spent in the expert layer.

No kernel of its own computes the experts, so their operations are found
by what they read: an event belongs to the expert layer when its HLO text
names a stacked expert weight (benchmark/arch/<model_type>.py
``expert_shapes``: ``[64,2048,1024]`` and ``[64,1024,2048]`` for OLMoE)
as its result or among its operands: the matmuls over the stack, and
any copy or conversion of the stack that feeds them. Their self times
(a fusion around its body is not counted twice) over the device's busy
time of the slice. The report lists what matched, by label
(``moe_experts_ops``). None where nothing matches."""
from .. import arch as arch_modules
from .. import trace_reduce


def read(ctx, args):
    tr, c = ctx.norm_trace, ctx.cell["config"]
    if not tr or not tr.get("devices") or "model_type" not in c:
        return None
    shapes = arch_modules.of(c).expert_shapes(c)
    evs = next(iter(tr["devices"].values()))
    matched, total = {}, 0.0
    for name, detail, secs in trace_reduce.self_times(evs):
        total += secs
        if any(s in detail for s in shapes):
            label = trace_reduce.op_label(name, detail)
            n, t = matched.get(label, (0, 0.0))
            matched[label] = (n + 1, t + secs)
    if not matched or total <= 0:
        return None
    ctx.results["moe_experts_ops"] = {k: {"events": n, "seconds": t} for k, (n, t) in
                                      sorted(matched.items(), key=lambda kv: -kv[1][1])}
    return 100.0 * sum(t for _, t in matched.values()) / total
