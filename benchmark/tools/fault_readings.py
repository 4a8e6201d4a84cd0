#!/usr/bin/env python3
"""The faults a serving cell's ``correct`` has to catch, each made on
purpose in the program and read by the cell's own check, on the cell's own
seed-made weights (no weight is touched here).

A model is served with one plausible mistake in it: K written to the pool
before RoPE, the decode position off by one, the top-k routing weights
rescaled to sum to 1, no norm over q and k. Its answers are scored like a
run's, by ``runners/serve_arch.reference_gaps``: the float32 reference's
one full forward over prompt + answer, the served token's logit against
the reference's best. Each fault has to read beyond the architecture's
``LOGIT_TOL`` and the sound program inside it; this prints one JSON line
per fault and exits 1 if one does not.

    python3 benchmark/tools/fault_readings.py --workload olmoe-serve-batch [--seed 7] [--requests 4] [--max-new 256]

The cell's widths and depth want the chip; tests/test_olmoe_serving.py
runs the same faults on the CPU at the published hidden width with fewer
layers, experts and vocabulary rows.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import threading

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

FAULTS = ("k_before_rope", "position_off_by_one", "renormalised_top_k", "no_qk_norm")


@contextlib.contextmanager
def made(fault, cfg, params):
    """``(cfg, params)`` to build the model from, with the program's own
    functions patched until the block is left: a program is traced when it
    is first compiled, so build, warm AND serve inside."""
    from paddle_tpu import serving
    from paddle_tpu.ops import moe
    from paddle_tpu.serving import model as M

    saved = (M._rope, serving.DecodeModel._rot, moe.route)
    real_rope, real_rot, real_route = saved
    try:
        if fault == "k_before_rope":
            calls = []

            def rope_q_only(x, rot):  # _qkv turns q, then k: every second call is a k
                calls.append(0)
                return real_rope(x, rot) if len(calls) % 2 else x
            M._rope = rope_q_only
        elif fault == "position_off_by_one":  # decode hands _rot [B] positions, prefill [1, L]
            serving.DecodeModel._rot = lambda self, pos: real_rot(self, pos + 1 if pos.ndim == 1 else pos)
        elif fault == "renormalised_top_k":
            def route(x, router_w, k):
                dense, idx = real_route(x, router_w, k)
                return dense / dense.sum(-1, keepdims=True), idx
            moe.route = route
        elif fault == "no_qk_norm":
            cfg = dataclasses.replace(cfg, qk_norm=False)
            params = {k: v for k, v in params.items() if "_norm." not in k}
        elif fault is not None:
            raise ValueError(f"no fault {fault!r}: one of {FAULTS}")
        yield cfg, params
    finally:
        M._rope, serving.DecodeModel._rot, moe.route = saved


def served(fault, cfg, params, engine_args: dict, requests, max_new: int):
    """``requests`` (token lists) through Router -> ServingEngine ->
    DecodeModel built with ``fault``, all in flight together: the records
    ``reference_gaps`` reads, and the model."""
    from paddle_tpu import serving

    with made(fault, cfg, params) as (cfg, params):
        dm = serving.DecodeModel(cfg, params=params, **engine_args)
        dm.warm(full=True)
        engine = serving.ServingEngine(dm)
        engine.start()
        router = serving.Router([serving.LocalReplica("r0", engine)])
        out = [None] * len(requests)

        def one(i):
            out[i] = router.dispatch(list(requests[i]), max_new_tokens=max_new, deadline_s=600,
                                     request_id=f"fault-{i}")
        try:
            threads = [threading.Thread(target=one, args=(i,)) for i in range(len(requests))]
            [t.start() for t in threads]
            [t.join() for t in threads]
        finally:
            router.stop()
            engine.stop()
        engine.pages = None  # the pool's memory is the reference's to use now
    bad = [r.get("error") for r in out if not r["ok"]]
    if bad:
        raise RuntimeError(f"fault {fault}: requests failed: {bad}")
    return [{"prompt": list(p), "tokens": r["tokens"]} for p, r in zip(requests, out)], dm


def reading(fault, arch, c: dict, cfg, params, engine_args: dict, requests, max_new: int,
            window: int = 0) -> dict:
    """One fault (None: the sound program) served and scored."""
    from benchmark.runners import serve_arch

    records, dm = served(fault, cfg, params, engine_args, requests, max_new)
    facts = serve_arch.reference_gaps(arch, c, params, dm, records, window)
    return dict(facts, fault=fault or "none", logit_tol=arch.LOGIT_TOL,
                caught=facts["max_logit_gap"] > arch.LOGIT_TOL)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="olmoe-serve-batch")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--requests", type=int, default=4)    # what a run checks: N_CHECKED answers
    ap.add_argument("--max-new", type=int, default=256)
    a = ap.parse_args()

    from benchmark import arch as arch_modules
    from benchmark import manifest, traffic_gen
    from paddle_tpu import compile_cache, serving

    compile_cache.enable()
    cell = manifest.cell(manifest.load(), a.workload)
    c, tr = cell["config"], cell["traffic"]
    arch = arch_modules.of(c)
    cfg = serving.GPTConfig(**arch.gpt_config(c, tr["engine"]))
    params = arch.make_params(c, a.seed, cfg.dtype)
    rng = traffic_gen.rng_for(a.seed, "faults")
    lo, hi = tr["prompt_len"]["lo"], tr["prompt_len"]["hi"]
    requests = [traffic_gen.draw_tokens(tr["tokens"], rng, (int(n),), c["vocab_size"]).tolist()
                for n in rng.integers(lo, hi + 1, size=a.requests)]
    window = -(-(hi + a.max_new) // 128) * 128
    ok = True
    for fault in (None, *FAULTS):
        r = reading(fault, arch, c, cfg, params, arch_modules.engine_args(tr["engine"]), requests,
                    a.max_new, window)
        print(json.dumps(r), flush=True)
        ok = ok and r["caught"] == (fault is not None)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
