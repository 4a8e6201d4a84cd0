"""The program's own spans in a normalised slice (``engine/*``, ``tick/*``,
``executor/*``: ``paddle_tpu.profiler.span`` writes them into the
profiler's trace as host events), for the readers that look at them."""
import re

PROGRAM_SPAN = re.compile(r"^(engine|tick|executor)/")


def program_spans(trace):
    """[name, start_ns, end_ns, thread], by thread, start, longest first.
    A name ends where its attributes begin (``#``)."""
    return sorted(([r[0].partition("#")[0], r[1], r[1] + r[2], r[3]] for r in (trace or {}).get("host") or []
                   if PROGRAM_SPAN.match(r[0])), key=lambda r: (r[3], r[1], -r[2]))


def note_span_means(ctx):
    """Count and mean duration of every program span of the slice, into the
    report (``program_span_ms``): what a counter of the program (a tick's
    wall, a run's host part) is checked against."""
    if "program_span_ms" in ctx.results or not ctx.norm_trace:
        return
    acc = {}
    for name, t0, t1, _ in program_spans(ctx.norm_trace):
        n, s = acc.get(name, (0, 0.0))
        acc[name] = (n + 1, s + (t1 - t0))
    if acc:
        ctx.results["program_span_ms"] = {k: {"count": n, "mean_ms": s / n / 1e6} for k, (n, s) in sorted(acc.items())}
