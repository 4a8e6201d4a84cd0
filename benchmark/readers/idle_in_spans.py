"""Share of the slice's device idle time that a span of the program owns.

The idle time is every gap between operations on the first chip. A gap is
cut where a program span (``engine/*``, ``tick/*``, ``executor/*``:
``paddle_tpu.profiler.span`` writes them into the profiler's trace, on
the clock of the device planes) opens or closes, and each piece goes to
the innermost span that holds it. The metric counts the pieces whose
owner is a LEAF span, one with no program span inside it: idle time that
only ``engine/step`` or ``executor/run`` holds is host work no phase
accounts for. The table
of idle seconds by owner goes to the report (``idle_by_span``; the part
outside every span is ``(no program span)``), and so do the count and
mean duration of every program span of the slice (``program_span_ms``). None where the trace holds
no program span, as before the PR that added them."""
import time

import numpy as np

from .. import trace_reduce
from ._spans import note_span_means, program_spans


def read(ctx, args):
    tr = ctx.norm_trace
    if not tr or not tr.get("devices"):
        return None
    t = time.perf_counter()
    spans = program_spans(tr)
    evs = next(iter(tr["devices"].values()))
    if not spans or not evs:
        return None
    # a span is a parent if the next span of its thread starts inside it
    parents = {a[0] for a, b in zip(spans, spans[1:]) if a[3] == b[3] and b[1] < a[2]}
    busy = trace_reduce.merged((r[1], r[1] + r[2]) for r in evs)
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]
    starts = np.asarray([s[1] for s in spans], np.float64)
    ends = np.asarray([s[2] for s in spans], np.float64)
    edges = np.unique(np.concatenate([starts, ends]))
    table, total, in_leaf = {}, 0.0, 0.0
    for g0, g1 in gaps:
        # cut the gap where a span opens or closes: one owner per piece
        cuts = [g0, *edges[np.searchsorted(edges, g0, "right"):np.searchsorted(edges, g1, "left")], g1]
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            inside = np.nonzero((starts <= mid) & (ends >= mid))[0]
            owner = "(no program span)"
            if inside.size:
                owner = spans[inside[np.argmin(ends[inside] - starts[inside])]][0]
            dur = (b - a) / 1e9
            table[owner] = table.get(owner, 0.0) + dur
            total += dur
            if inside.size and owner not in parents:
                in_leaf += dur
    ctx.results["idle_by_span"] = {"idle_s": total, "gaps": len(gaps), "parents": sorted(parents),
                                   "by_span_s": dict(sorted(table.items(), key=lambda kv: -kv[1]))}
    note_span_means(ctx)
    ctx.results.setdefault("reader_s", {})["idle_in_spans"] = time.perf_counter() - t
    return 100.0 * in_leaf / total if total > 0 else None
