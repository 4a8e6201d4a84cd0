"""Median device duration, in milliseconds, of the programs (the trace's
module line) whose name matches ``pattern``: ``^jit_decode_tick$``,
``^jit_prefill_``. Programs are named by the function they were jitted
from, so the name holds for any cell. The report gets runs and median per
matching module (``module_device_ms``). None where no module matches, as
in a program whose modules are all ``jit_fn``."""
import re
import statistics
import time


def read(ctx, args):
    tr = ctx.norm_trace
    if not tr or not tr.get("modules"):
        return None
    t = time.perf_counter()
    rx = re.compile(args["pattern"])
    groups = {}
    for evs in tr["modules"].values():
        for name, _s, dur, _full in evs:
            if rx.search(name):
                groups.setdefault(name, []).append(dur)
    if not groups:
        return None
    ctx.results.setdefault("module_device_ms", {}).update(
        {k: {"runs_in_slice": len(v), "median_ms": statistics.median(v) / 1e6}
         for k, v in sorted(groups.items())})
    ctx.results.setdefault("reader_s", {})["module_device_ms"] = time.perf_counter() - t
    return statistics.median([d for v in groups.values() for d in v]) / 1e6
