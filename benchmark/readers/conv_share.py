"""Share of the slice's device time spent in the conv layers' own
operations (the gated short convolution: input projection, gates and taps,
state shift, output projection).

No kernel of its own computes them, so they are found by what they read,
as moe_experts_share finds the experts': an event belongs to the conv
layers when its HLO text names one of the shapes of
benchmark/arch/<model_type>.py ``conv_shapes``, counted by the share that
function gives the shape (1 for a weight only a conv layer has; the conv
layers' part of a shape they share with another kind of layer). An event
that names several counts once, by the largest. Self times over the
device's busy time of the slice; the report lists what matched, by label
(``conv_ops``). None where nothing matches or the architecture has no
``conv_shapes``: a program or a model without such layers."""
from .. import arch as arch_modules
from .. import trace_reduce


def read(ctx, args):
    tr, c = ctx.norm_trace, ctx.cell["config"]
    if not tr or not tr.get("devices") or "model_type" not in c:
        return None
    shapes = getattr(arch_modules.of(c), "conv_shapes", None)
    if shapes is None:
        return None
    shapes = shapes(c)
    evs = next(iter(tr["devices"].values()))
    matched, total = {}, 0.0
    for name, detail, secs in trace_reduce.self_times(evs):
        total += secs
        share = max((w for s, w in shapes.items() if s in detail), default=0.0)
        if share:
            label = trace_reduce.op_label(name, detail)
            n, t = matched.get(label, (0, 0.0))
            matched[label] = (n + 1, t + share * secs)
    if not matched or total <= 0:
        return None
    ctx.results["conv_ops"] = {k: {"events": n, "seconds": t} for k, (n, t) in
                               sorted(matched.items(), key=lambda kv: -kv[1][1])}
    return 100.0 * sum(t for _, t in matched.values()) / total
