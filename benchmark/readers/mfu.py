"""Model FLOP/s utilisation: the benchmark's own 6N + 12LTd per token
times the measured tokens per second, over chips times the peak."""
from .. import flops


def read(ctx, args):
    tps = ctx.results.get(args.get("rate", "train_tokens_per_s"))
    if tps is None or ctx.peaks is None:
        return None
    return 100.0 * flops.mfu(tps, ctx.cell["config"], int(ctx.cell["traffic"]["seq"]),
                             ctx.cell["chips"], ctx.peaks["bf16_flops_per_s"])
