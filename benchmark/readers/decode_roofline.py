"""Decode tick against the memory roofline: the bytes a tick must stream
(every weight once + K and V of the live context, benchmark/flops.py) at
the chip's peak bandwidth, over the device time of one decode program.

The programs carry no names yet (prefill and decode are both ``jit_fn``),
so the decode program is taken to be the module that ran most often in
the slice; PERF.md lists the named_scope that would replace this."""
import statistics

from .. import flops


def read(ctx, args):
    tr = ctx.norm_trace
    if not tr or ctx.peaks is None or not tr.get("modules"):
        return None
    groups = {}
    for evs in tr["modules"].values():
        for name, _s, dur, _d in evs:
            groups.setdefault(name, []).append(dur)
    if not groups:
        return None
    name, durs = max(groups.items(), key=lambda kv: len(kv[1]))
    tick_s = statistics.median(durs) / 1e9
    util = ctx.counters.get("ledger.kv_util_weight")
    wall = ctx.counters.get("ledger.weighted_wall")
    eng = ctx.cell["traffic"]["engine"]
    live = (util / wall if util is not None and wall else 0.0) * (int(eng["n_blocks"]) - 1) * int(eng["block_size"])
    need = flops.decode_tick_bytes(ctx.cell["config"], live)
    ctx.results["decode_program"] = {"module": name, "runs_in_slice": len(durs),
                                     "device_ms": 1e3 * tick_s, "bytes_needed": need,
                                     "live_kv_tokens": live, "bound": "memory"}
    return 100.0 * (need / ctx.peaks["hbm_bytes_per_s"]) / tick_s
