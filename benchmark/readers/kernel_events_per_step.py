"""How often a named kernel runs in one traced step: device events whose
instruction name matches ``pattern`` (a Mosaic kernel is named by its
``pl.pallas_call(name=...)``: ``flash_fwd.3``), per step, over the
configuration's ``per`` (``n_layer``) where given. A count, so it repeats
exactly. The report gets the events per step of every Mosaic kernel by
name (``kernel_events_per_step``). None where nothing matches, as in a
program whose kernels carry no name."""
import re
import time

STEM = re.compile(r"[.\d]+$")


def read(ctx, args):
    steps = ctx.trace_facts.get("steps")
    tr = ctx.norm_trace
    if not tr or not steps or not tr.get("devices"):
        return None
    t = time.perf_counter()
    rx = re.compile(args["pattern"])
    chips = len(tr["devices"])
    hits = 0
    by_name = {}
    for evs in tr["devices"].values():
        for name, _s, _d, detail in evs:
            if rx.search(name):
                hits += 1
            if "tpu_custom_call" in detail:
                stem = STEM.sub("", name)
                by_name[stem] = by_name.get(stem, 0) + 1
    ctx.results["kernel_events_per_step"] = {k: v / chips / steps for k, v in sorted(by_name.items())}
    ctx.results.setdefault("reader_s", {})["kernel_events_per_step"] = time.perf_counter() - t
    if not hits:
        return None
    per = int(ctx.cell["config"][args["per"]]) if args.get("per") else 1
    return hits / chips / steps / per
