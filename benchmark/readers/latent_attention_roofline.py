"""The latent mode of the decode attention kernel against its roofline:
the larger of two least times a tick, over the device time of the kernel's
events a decode tick.

Sixty-four heads score against ONE shared row a position, so the kernel
is bound by operations as much as by bytes (benchmark/arch/<model_type>.py
``latent_attention_bytes`` and ``latent_attention_flops``): the latent
rows of every live context position in every layer at the chip's peak
bandwidth, and the absorbed scores and weighted sums over them at its peak
matmul rate. The live positions come from the ledger's KV use over the
window, as paged_attention_roofline takes them; the time is that of the
``paged_latent_attention`` events of the slice (one a layer a tick),
summed, over the runs of ``jit_decode_tick`` in the slice. The kernel
copies and multiplies whole pages, so the part of each context's last page
that holds nothing yet is moved and not counted: a share under 100 by that
much even at the peak. None where the trace has no such kernel or program
(as on a program without latent attention), or the architecture no
counts."""
from .. import arch as arch_modules
from .. import trace_reduce


def read(ctx, args):
    tr, c = ctx.norm_trace, ctx.cell["config"]
    if not tr or ctx.peaks is None or not tr.get("modules") or "model_type" not in c:
        return None
    mod = arch_modules.of(c)
    n_bytes = getattr(mod, "latent_attention_bytes", None)
    n_flops = getattr(mod, "latent_attention_flops", None)
    ticks = sum(1 for evs in tr["modules"].values() for name, *_ in evs if name == "jit_decode_tick")
    hit = trace_reduce.op_time(tr, r"^paged_latent_attention")
    util, wall = ctx.counters.get("ledger.kv_util_weight"), ctx.counters.get("ledger.weighted_wall")
    if n_bytes is None or n_flops is None or not ticks or hit is None or util is None or not wall:
        return None
    eng = ctx.cell["traffic"]["engine"]
    live = util / wall * (int(eng["n_blocks"]) - 1) * int(eng["block_size"])
    ticks /= max(1, len(tr["modules"]))
    legs = {"memory": n_bytes(c, live) / ctx.peaks["hbm_bytes_per_s"],
            "compute": n_flops(c, live) / ctx.peaks["bf16_flops_per_s"]}
    bound = max(legs, key=legs.get)
    tick_s = hit["seconds"] / ticks
    ctx.results["latent_attention_kernel"] = {
        "ticks_in_slice": ticks, "events_a_tick": hit["events"] / ticks, "device_ms_a_tick": 1e3 * tick_s,
        "bytes_needed": n_bytes(c, live), "flops_needed": n_flops(c, live), "live_kv_tokens": live,
        "least_ms": {k: 1e3 * v for k, v in legs.items()}, "bound": bound}
    return 100.0 * legs[bound] / tick_s
