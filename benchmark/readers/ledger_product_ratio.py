"""scale * product(num) / product(den), each factor a counter of the
engine ledger (``ledger:<key>``, over every tick since the runner's
``ledger.reset()``, read after the run as ledger_tick_host_ms does) or a
number of the cell's configuration (``config:<key>``). None where the
ledger lacks a counter it names or the denominator is 0: a program
without that counter, as the parent of the PR that added it."""
import math


def read(ctx, args):
    from paddle_tpu.serving import ledger

    doc = ledger.totals()

    def factor(term):
        source, _, key = term.partition(":")
        return (doc if source == "ledger" else ctx.cell["config"]).get(key)

    num, den = [factor(t) for t in args["num"]], [factor(t) for t in args["den"]]
    if any(v is None for v in num + den) or not math.prod(den):
        return None
    return float(args.get("scale", 1.0)) * math.prod(num) / math.prod(den)
