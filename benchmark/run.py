#!/usr/bin/env python3
"""One run of one benchmark cell on the machine it is started on.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process. Fails (non-zero, no result line) unless JAX finds a TPU whose
``device_kind`` is in benchmark/peaks.json, with at least the chips the
cell asks for. ``--rehearse`` is the one exception: the same control flow
at a tiny size on whatever JAX finds (CPU, virtual devices), printing no
device metric. The last line of stdout is the result, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` in a traced run); the line before it is the run's full
report (every end-to-end and per-layer value it has, and the facts behind
``correct``), for PERF.md and for debugging. Progress goes to stderr.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; prints no device metric")
    args = ap.parse_args()

    from benchmark import common, flops, manifest, trace_reduce

    common.T0 = T0
    man = manifest.load()
    cell = manifest.cell(man, args.workload)
    if args.rehearse:
        _shrink(cell)
    seconds = float(args.seconds if args.seconds is not None else man["run_seconds"])

    if args.rehearse and cell["chips"] > 1:
        os.environ.setdefault(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={cell['chips']}")
    import jax

    devs = jax.devices()
    peaks = None
    if not args.rehearse:
        if devs[0].platform != "tpu":
            raise SystemExit(f"benchmark: no TPU. JAX found {devs[0].platform!r} "
                             f"({devs[0].device_kind!r} x{len(devs)}); only --rehearse runs here")
        peaks = flops.load_peaks(devs[0].device_kind)  # unknown kind: KeyError, no default
    if len(devs) < cell["chips"]:
        raise SystemExit(f"benchmark: {cell['name']} needs {cell['chips']} chips, "
                         f"JAX found {len(devs)}")
    if len(devs) != cell["chips"] and not args.rehearse:
        raise SystemExit(f"benchmark: {cell['name']} is a {cell['chips']}-chip cell, this "
                         f"machine has {len(devs)}; the programs under test take every device")

    from paddle_tpu import compile_cache

    cache_dir = compile_cache.enable()
    common.log(f"{cell['name']}: {devs[0].device_kind} x{len(devs)}, cache {cache_dir}, "
               f"seed {args.seed}, {seconds:g}s, trace {args.trace}")

    ctx = common.Ctx(cell=cell, seed=args.seed, seconds=seconds, trace=bool(args.trace),
                     rehearse=args.rehearse, devices=devs[:cell["chips"]], peaks=peaks, t0=T0)
    runner = importlib.import_module(f"benchmark.runners.{cell['traffic']['kind']}")
    runner.run(ctx)

    device = common.device_facts(ctx.devices, ctx.results.get("program_temp_bytes", 0))
    ctx.results["memory_peak_gb"] = (device["memory_peak_bytes"] or 0) / 1e9
    ctx.results["allocator_stats"] = {k: v for k, v in (ctx.devices[0].memory_stats() or {}).items()
                                      if isinstance(v, (int, float))}
    metrics, left_out = {}, []
    e2e = {m["name"]: ctx.results.get(m["name"]) for m in cell["end_to_end"]}
    layer = {}
    for m in cell["per_layer"]:
        spec = manifest.layer_metric(m["name"])
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        layer[m["name"]] = reader.read(ctx, spec.get("args", {}))
    chosen, specs = (layer, cell["per_layer"]) if args.trace else (e2e, cell["end_to_end"])
    for m in specs:
        v = chosen.get(m["name"])
        if v is None:
            left_out.append(m["name"])
        else:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    line = {"correct": bool(ctx.results.get("correct")),
            "attempted": int(ctx.results.get("attempted", 0)),
            "failed": int(ctx.results.get("failed", 0)), "metrics": metrics, "device": device}
    if args.trace and ctx.norm_trace:
        busy = trace_reduce.device_busy(ctx.norm_trace)
        if busy:
            device["busy_s"], device["window_s"] = busy["busy_s"], busy["window_s"]
        line["breakdown"] = {"device_ops": trace_reduce.top_ops(ctx.norm_trace),
                             "idle_gaps": trace_reduce.idle_gaps(ctx.norm_trace)}
        _keep_trace(ctx)
    report = {"report": cell["name"], "seed": args.seed, "seconds": seconds,
              "trace": args.trace, "rehearsal": args.rehearse, "end_to_end": e2e,
              "per_layer": layer, "left_out": left_out, "trace_facts": ctx.trace_facts,
              "facts": {k: v for k, v in ctx.results.items() if k not in e2e},
              "total_s": time.perf_counter() - T0}
    if args.rehearse:
        # a rehearsal shows control flow and counts; its times are the CPU's
        # and are never printed under the name of a device metric
        for part in ("end_to_end", "per_layer"):
            report[part] = {k: "not measured (rehearsal)" for k in report[part]}
        named = {m["name"] for m in man["end_to_end"] + man["per_layer"]}
        report["facts"] = {k: v for k, v in report["facts"].items() if k not in named}
        line["metrics"] = {}
        line["rehearsal"] = True
    print(json.dumps(report, default=str), flush=True)
    print(json.dumps(line), flush=True)
    return 0


def _shrink(cell: dict) -> None:
    """Tiny sizes for --rehearse, from benchmark/rehearse.json."""
    with open(os.path.join(ROOT, "benchmark", "rehearse.json")) as f:
        tiny = json.load(f)
    cell["config"].update(tiny["config"])
    cell["config"].pop("published", None)
    over = tiny["traffic"].get(cell["traffic"]["kind"], {})
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(cell["traffic"].get(k), dict):
            cell["traffic"][k].update(v)
        else:
            cell["traffic"][k] = v


def _keep_trace(ctx) -> None:
    """The normalised slice, gzipped, where the chip tool brings it back
    (and where nothing is committed): enough to re-run any reader offline."""
    import gzip

    out = os.path.join(ROOT, "chiprun_out", "traces")
    try:
        os.makedirs(out, exist_ok=True)
        tr = dict(ctx.norm_trace)
        tr["host"] = [r for r in tr["host"] if r[0].startswith("bench/")][:20000]
        first = sorted(tr["devices"])[:1]  # one chip's plane is enough to re-run a reader
        tr["devices"] = {k: [[n, s, d, det[:400]] for n, s, d, det in tr["devices"][k]] for k in first}
        tr["modules"] = {k: v for k, v in tr.get("modules", {}).items() if k in first}
        with gzip.open(os.path.join(out, f"{ctx.cell['name']}.s{ctx.seed}.json.gz"), "wt") as f:
            json.dump(tr, f)
    except OSError as e:  # a read-only checkout loses the copy, not the run
        from benchmark import common

        common.log(f"trace copy not kept: {e}")


if __name__ == "__main__":
    sys.exit(main())
