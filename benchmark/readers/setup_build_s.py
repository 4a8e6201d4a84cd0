"""``setup_s`` split by what the program's build log says of it
(``paddle_tpu.framework.xla_insight.build_log``: one record for every
trace, lowering and compile-or-cache-load of every jit of the process, on
``time.perf_counter``'s clock, which is also the clock of ``ctx.t0``).

``args["stage"]`` is ``trace``, ``lower``, ``compile`` or ``rest``. A
stage's value is the ``self_s`` of its records that ended before the
window opened (``ctx.t0 + setup_s``: the reference check and the traced
slice build after it), with what fell off the log's end, which is older
than anything in it. ``rest`` is ``setup_s`` less the three: imports,
backend start and chip hand-over, weights made and placed, the device
running the startup program, the first step and the warm-up. It is
clamped at 0 (records of two threads can overlap) and the report says if
the clamp fired. None where the program keeps no such log, as before the
PR that added it. The first call of a run writes ``setup_timeline`` into
the report."""
STAGES = ("trace", "lower", "compile")
TOP_NAMES = 12


def read(ctx, args):
    tl = ctx.results.get("setup_timeline") or _timeline(ctx)
    if tl is None:
        return None
    ctx.results["setup_timeline"] = tl
    return tl["rest_s"] if args["stage"] == "rest" else tl["stage_s"][args["stage"]]


def _gauge(name, **labels):
    from paddle_tpu import monitor

    family = monitor.default_registry().get(name)
    if family is None:
        return None
    return float((family.labels(**labels) if labels else family).value)


def _timeline(ctx):
    from paddle_tpu.framework import xla_insight

    setup_s = ctx.results.get("setup_s")
    if setup_s is None or not hasattr(xla_insight, "build_log"):
        return None
    return split(xla_insight.build_log(), ctx.t0 + float(setup_s), float(setup_s), extras={
        "import_s": _gauge("paddle_tpu_import_seconds"),
        "serve_boot_s": {ph: _gauge("serve_boot_seconds", phase=ph) for ph in ("load", "warm")},
        "programs": {i.program or i.label: {"cache": i.cache, "analyze_s": i.build_s.get("analyze")}
                     for i in xla_insight.recent()}})


def split(log: dict, t_cut: float, setup_s: float, extras: dict | None = None) -> dict:
    """The timeline of one run from a build log, the window's opening on
    the log's clock and ``setup_s`` (a function of its arguments: the
    tests call it on a log made by hand). ``extras["programs"]`` are the
    programs the system under test names (module name -> cache, analyze_s):
    each keeps its line whatever its size, and one with two ``compile``
    records is listed under ``compiled_twice`` (a helper such as ``copy``
    compiles once a shape under one name, and is not)."""
    from paddle_tpu.framework.xla_insight import program_of

    extras = extras or {}
    programs = extras.get("programs") or {}
    before = [r for r in log["records"] if r["t_end"] <= t_cut]
    stage_s = {st: float(log.get("dropped", {}).get(st, {}).get("seconds", 0.0)) for st in STAGES}
    names: dict = {}
    cache = {"hit": 0, "miss": 0, "off": 0, "hit_s": 0.0, "miss_s": 0.0, "off_s": 0.0}
    for r in before:
        n = r.get("count", 1)
        stage_s[r["stage"]] += r["self_s"]
        # JAX names the trace by the function and the other two by the
        # module: the log's ``program`` is either without its jit wrapper
        row = names.setdefault(r["program"], {"self_s": 0.0})
        row["self_s"] += r["self_s"]
        cell = row.setdefault(r["stage"], {"s": 0.0, "n": 0})
        cell["s"] += r["self_s"]
        cell["n"] += n
        if r["stage"] == "compile":
            kind = r.get("cache", "off")
            by_kind = cell.setdefault("cache", {})
            by_kind[kind] = by_kind.get(kind, 0) + n
            cache[kind] += n
            cache[kind + "_s"] += r["self_s"]
    named = {program_of(p) for p in programs}
    ranked = sorted(names.items(), key=lambda kv: -kv[1]["self_s"])
    shown = [kv for i, kv in enumerate(ranked) if i < TOP_NAMES or kv[0] in named]
    built = sum(stage_s.values())
    rest = setup_s - built
    owned = sum(v or 0.0 for v in (extras.get("import_s"), (extras.get("serve_boot_s") or {}).get("load"),
                                   *(p.get("analyze_s") for p in programs.values())))
    return {"setup_s": setup_s, "stage_s": stage_s, "rest_s": max(0.0, rest), "clamped": rest < 0.0,
            "by_name": dict(shown),
            "others": {"names": len(ranked) - len(shown),
                       "self_s": sum(v["self_s"] for _, v in ranked) - sum(v["self_s"] for _, v in shown)},
            "compiled_twice": sorted(n for n in named if names.get(n, {}).get("compile", {}).get("n", 0) > 1),
            "cache_before_opening": cache, "cache_whole_process": log.get("cache"),
            "records_before_opening": len(before), "records_after": len(log["records"]) - len(before),
            "dropped": log.get("dropped"), **extras,
            # what of the rest no span or gauge owns: the import, the load
            # and the analysis after each compile are taken off it
            "rest_unowned_s": max(0.0, rest - owned)}
