"""A percentile (``q``, default 99) of the engine ledger's sample of
inter-token gaps, in milliseconds: engine clock, per request, from the
end of its prefill and the ends of its decode ticks; every request
retired since the runner's ``ledger.reset()``. Engine-side only: a
client's gap also holds the router and the wire. The sample is bounded
(the newest gaps): the report says how many gaps the run made (``seen``)
and whether the sample dropped any (``truncated``). None where the
ledger keeps no such sample."""
import numpy as np


def read(ctx, args):
    from paddle_tpu.serving import ledger

    doc = ledger.totals()
    gaps = doc.get("itl_gaps_s")
    if not gaps:
        return None
    g = np.asarray(gaps, np.float64)
    seen = int(doc.get("itl_gaps_seen", g.size))
    ctx.results["engine_itl_ms"] = {"gaps": int(g.size), "seen": seen, "truncated": seen > g.size, "p50": 1e3 * float(np.percentile(g, 50)),
                                    "p99": 1e3 * float(np.percentile(g, 99)), "max": 1e3 * float(g.max())}
    return 1e3 * float(np.percentile(g, float(args.get("q", 99))))
