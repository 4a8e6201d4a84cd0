"""Mean of a histogram of the program's monitor registry, in milliseconds
(sum / count over every observation the process made: warm-up, window and
traced slice alike). None where the program has no such family, as before
the PR that added it, or where it observed nothing. The mean of a mixed
population moves with the mix (runs that found room in the in-flight
queue, runs that blocked for a step), so the report gets the populated
buckets too (``le``: upper bound in seconds -> runs). In a traced run the
report also gets the count and mean duration of the program's spans in
the slice (``program_span_ms``): the same intervals, seen by the
profiler."""
from ._spans import note_span_means


def read(ctx, args):
    from paddle_tpu import monitor

    family = monitor.default_registry().get(args["family"])
    if family is None:
        return None
    h = family.labels()
    if not getattr(h, "count", 0):
        return None
    note_span_means(ctx)
    bounds = [str(b) for b in family.buckets] + ["inf"]
    ctx.results.setdefault("monitor_hists", {})[args["family"]] = {
        "count": h.count, "sum_s": h.sum,
        "le": {b: n for b, n in zip(bounds, h.counts) if n}}
    return 1e3 * h.sum / h.count
