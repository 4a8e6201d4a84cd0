#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, on the chip: the same engine,
one process, a short window at each of a few fixed rates.

    python3 benchmark/tools/knee_sweep.py --workload gpt2xl-serve-chat \
        --rates 2,3,4,5,6,7 --seconds 15 --seed 0

Prints one JSON line per rate: offered and completed requests/s, the
normalised latency median and 95th percentile, the queue's growth (mean
latency of the last third of the window over the first third) and the
engine's occupancy. The knee is the highest rate the system sustains: the
last one before latency grows through the window. The cell's rate (0.8 x
knee) is then written into its traffic file as a number; the benchmark
itself never searches.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import numpy as np

    from benchmark import common, flops, manifest
    from benchmark.runners import serve

    cell = manifest.cell(manifest.load(), args.workload)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit("knee_sweep: no TPU")
    peaks = flops.load_peaks(devs[0].device_kind)
    from paddle_tpu import compile_cache
    from paddle_tpu.serving import ledger

    compile_cache.enable()
    ctx = common.Ctx(cell=cell, seed=args.seed, seconds=args.seconds, trace=False,
                     rehearse=False, devices=devs[:1], peaks=peaks, t0=time.perf_counter())
    _params, _dm, engine, router = serve._engine(ctx)
    vocab = int(cell["config"].get("published", {}).get("vocab_size", cell["config"]["vocab_size"]))
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            cell["traffic"]["rate_rps"] = rate
            ctx.seed = args.seed + i
            clients = serve._Clients(router, 600.0)
            ledger.reset()
            # request ids must differ between rates: the engine replays a finished id
            t_open, t_close, state = serve._open_loop(ctx, clients, vocab, rid_prefix=f"r{i}-")
            recs = [r for r in clients.records if t_open <= r["due"] < t_close]
            ok = [r for r in recs if r["ok"]]
            norm = [1e3 * (r["t1"] - r["due"]) / r["n_out"] for r in ok]
            third = args.seconds / 3
            first = [r["t1"] - r["due"] for r in ok if r["due"] < t_open + third]
            last = [r["t1"] - r["due"] for r in ok if r["due"] >= t_close - third]
            d = {k: state[1][k] - state[0][k] for k in state[0]}
            print(json.dumps({
                "rate_rps": rate, "due": len(recs), "ok": len(ok),
                "completed_rps_by_close": sum(1 for r in ok if r["t1"] <= t_close) / args.seconds,
                "norm_p50_ms": float(np.percentile(norm, 50)) if norm else None,
                "norm_p95_ms": float(np.percentile(norm, 95)) if norm else None,
                "latency_first_third_s": float(np.mean(first)) if first else None,
                "latency_last_third_s": float(np.mean(last)) if last else None,
                "drain_s": max((r["t1"] for r in ok), default=t_close) - t_close,
                "occupancy": d["occupancy_weight"] / max(d["weighted_wall"], 1e-9),
                "tick_ms": 1e3 * d["decode_compute_s"] / max(d["ticks"], 1),
                "prefill_share": d["prefill_compute_s"] / args.seconds,
                "lateness_p99_ms": float(np.percentile([1e3 * (r["t0"] - r["due"]) for r in ok], 99)) if ok else None,
                "errors": sorted({str(r["error"])[:100] for r in recs if not r["ok"]})[:3],
            }), flush=True)
            time.sleep(1.0)
    finally:
        router.stop()
        engine.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
