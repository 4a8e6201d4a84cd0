"""The decode attention kernel against the memory roofline: the K and V
bytes a tick MUST read at the chip's peak bandwidth, over the device time
of the kernel's events a decode tick.

The bytes (benchmark/arch/<model_type>.py ``paged_attention_bytes``): K
and V of every live context position in every layer that attends, from
the ledger's KV use over the window, as moe_decode_roofline takes it. The
time: the ``paged_attention`` events of the slice (one a layer that
attends a tick), summed, over the runs of ``jit_decode_tick`` in the
slice. The kernel copies whole pages, so the part of each context's last
page that holds nothing yet is moved and not counted: a share under 100
by that much even at the peak. None where the trace has no such kernel or
program, or the architecture no byte count."""
from .. import arch as arch_modules
from .. import trace_reduce


def read(ctx, args):
    tr, c = ctx.norm_trace, ctx.cell["config"]
    if not tr or ctx.peaks is None or not tr.get("modules") or "model_type" not in c:
        return None
    need = getattr(arch_modules.of(c), "paged_attention_bytes", None)
    ticks = sum(1 for evs in tr["modules"].values() for name, *_ in evs if name == "jit_decode_tick")
    hit = trace_reduce.op_time(tr, r"^paged_attention")
    util, wall = ctx.counters.get("ledger.kv_util_weight"), ctx.counters.get("ledger.weighted_wall")
    if need is None or not ticks or hit is None or util is None or not wall:
        return None
    eng = ctx.cell["traffic"]["engine"]
    live = util / wall * (int(eng["n_blocks"]) - 1) * int(eng["block_size"])
    ticks /= max(1, len(tr["modules"]))
    least_s, tick_s = need(c, live) / ctx.peaks["hbm_bytes_per_s"], hit["seconds"] / ticks
    ctx.results["paged_attention_kernel"] = {
        "ticks_in_slice": ticks, "events_a_tick": hit["events"] / ticks, "device_ms_a_tick": 1e3 * tick_s,
        "bytes_needed": need(c, live), "live_kv_tokens": live, "least_ms": 1e3 * least_s, "bound": "memory"}
    return 100.0 * least_s / tick_s
