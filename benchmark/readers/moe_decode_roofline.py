"""Decode tick of a model with experts against the memory roofline: the
bytes a tick MUST stream at the chip's peak bandwidth, over the median
device time of ``jit_decode_tick`` in the slice.

The bytes (benchmark/arch/<model_type>.py ``decode_tick_bytes``): the
weights of the experts that were HIT, from the ledger's ``moe_experts_hit``
a decode tick (every tick since the runner's ``ledger.reset()``); every
other weight once; K and V of the live context, from the ledger's KV use
over the window, as decode_roofline takes it. Program-level on purpose:
XLA brings weights in through asynchronous copies that overlap other
operations, so a share taken over one family of fusions can read over
100 %. The parts go to the report (``moe_decode_program``). None where the
trace has no ``jit_decode_tick``, the ledger no routing counters, or the
configuration's architecture no byte count."""
import statistics

from .. import arch as arch_modules


def read(ctx, args):
    from paddle_tpu.serving import ledger

    tr, c = ctx.norm_trace, ctx.cell["config"]
    if not tr or ctx.peaks is None or not tr.get("modules") or "model_type" not in c:
        return None
    durs = [dur for evs in tr["modules"].values() for name, _s, dur, _f in evs
            if name == "jit_decode_tick"]
    doc = ledger.totals()
    ticks, hit = doc.get("decode_ticks"), doc.get("moe_experts_hit")
    util, wall = ctx.counters.get("ledger.kv_util_weight"), ctx.counters.get("ledger.weighted_wall")
    if not durs or not ticks or hit is None or util is None or not wall:
        return None
    eng = ctx.cell["traffic"]["engine"]
    live = util / wall * (int(eng["n_blocks"]) - 1) * int(eng["block_size"])
    parts = arch_modules.of(c).decode_tick_bytes(c, int(eng["max_batch"]), live, hit / ticks)
    tick_s = statistics.median(durs) / 1e9
    need = sum(parts.values())
    ctx.results["moe_decode_program"] = {
        "runs_in_slice": len(durs), "device_ms": 1e3 * tick_s, "bytes_needed": need,
        "bytes_by_part": parts, "experts_hit_a_tick": hit / ticks, "live_kv_tokens": live,
        "least_ms": 1e3 * need / ctx.peaks["hbm_bytes_per_s"], "bound": "memory"}
    return 100.0 * (need / ctx.peaks["hbm_bytes_per_s"]) / tick_s
