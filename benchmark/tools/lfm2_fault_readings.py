#!/usr/bin/env python3
"""The faults ``correct`` has to catch in a cell of the LFM2-MoE block, each
made on purpose in the program and read by the cell's own check, on the
cell's own seed-made weights (no weight is touched here).

The model is served with one plausible mistake in it:

- ``state_not_carried``: a prefill leaves zeros where the prompt's last
  gated inputs belong, so the first decode ticks convolve over nothing;
- ``state_shifted_wrong_way``: a decode tick writes the new gated input in
  front of the state instead of behind it;
- ``state_of_last_tenant``: a prefill writes no state, so a slot's first
  ticks convolve over what its last tenant left;
- ``no_selection_bias``: the top-4 are chosen by the bare scores;
- ``top_k_not_normalised``: the four weights are used as they are;
- ``kv_head_modulo``: query head ``i`` reads K|V head ``i % 8``, not ``i // 4``;
- ``no_qk_norm``: no norm over q and k;
- ``position_off_by_one``: the decode tick rotates by the next position.

Before the checked requests a round of others passes through every slot,
so that every slot has had a tenant. The answers are scored like a run's,
by ``runners/serve_arch.reference_gaps``: the float32 reference's one full
forward over prompt + answer, the served token's logit against the
reference's best. Each fault has to read beyond the architecture's
``LOGIT_TOL`` and the sound program inside it; this prints one JSON line
per fault and exits 1 if one does not.

    python3 benchmark/tools/lfm2_fault_readings.py --workload lfm2-serve-reason [--seed 7] [--requests 4] [--max-new 256]

The cell's widths and depth want the chip; tests/test_lfm2_serving.py runs
the same faults on the CPU at the published hidden width with fewer
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

FAULTS = ("state_not_carried", "state_shifted_wrong_way", "state_of_last_tenant", "no_selection_bias",
          "top_k_not_normalised", "kv_head_modulo", "no_qk_norm", "position_off_by_one")


@contextlib.contextmanager
def made(fault, cfg, params):
    """``(cfg, params)`` to build the model from, with the program's own
    functions patched until the block is left: a program is traced when it
    is first compiled, so build, warm AND serve inside."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import serving
    from paddle_tpu.ops import moe
    from paddle_tpu.ops.pallas import paged_attention as pa
    from paddle_tpu.serving import model as M

    DM = serving.DecodeModel
    saved = (DM._conv_prompt, DM._conv_step, DM._grouped, DM._rot, moe.route, pa.paged_attention)
    real_prompt, _, _, real_rot, real_route, real_paged = saved
    try:
        if fault in ("state_not_carried", "state_of_last_tenant"):
            def conv_prompt(self, lp, i, h, L, state_dest):
                y, _ = real_prompt(self, lp, i, h, L, None)  # nothing written
                if state_dest is not None and fault == "state_not_carried":
                    state, length, slot = state_dest
                    zeros = jnp.zeros((1, state.shape[1], 1, state.shape[3]), state.dtype)
                    state_dest = (jax.lax.dynamic_update_slice(state, zeros, (i, 0, slot, 0)),
                                  length, slot)
                return y, state_dest
            DM._conv_prompt = conv_prompt
        elif fault == "state_shifted_wrong_way":
            def conv_step(self, lp, i, h, state):
                K = self.cfg.conv_kernel
                b, c, x = self._conv_in(lp, h)
                z = b * x
                taps = lp[f"{M._LAYER}.conv.taps.w"].astype(jnp.float32)
                past = jax.lax.dynamic_index_in_dim(state, i, keepdims=False)
                mixed = taps[K - 1] * z.astype(jnp.float32) + sum(
                    taps[j] * past[j].astype(jnp.float32) for j in range(K - 1))
                shifted = jnp.concatenate([z[None].astype(state.dtype), past[:-1]], axis=0)
                return (self._conv_out(lp, c, mixed),
                        jax.lax.dynamic_update_slice(state, shifted[None], (i, 0, 0, 0)))
            DM._conv_step = conv_step
        elif fault == "no_selection_bias":
            moe.route = lambda x, w, k, **how: real_route(x, w, k, **dict(how, bias=None))
        elif fault == "top_k_not_normalised":
            moe.route = lambda x, w, k, **how: real_route(x, w, k, **dict(how, norm_topk=False))
        elif fault == "kv_head_modulo":
            kv, group = cfg.kv_heads, cfg.n_head // cfg.kv_heads
            # line j * group + r of the kernel reads K|V head j: hand it head r * kv + j
            perm = jnp.asarray([r * kv + j for j in range(kv) for r in range(group)])

            def paged(q, pool, tables, lens, scale, interpret=None):
                o = real_paged(q[:, perm], pool, tables, lens, scale, interpret)
                return o.reshape(q.shape)[:, jnp.argsort(perm)].reshape(o.shape)
            pa.paged_attention = paged
            DM._grouped = lambda self, a: jnp.tile(a, (1,) * (a.ndim - 2) + (group, 1))
        elif fault == "no_qk_norm":
            cfg = dataclasses.replace(cfg, qk_norm=False)
            params = {k: v for k, v in params.items() if "_norm." not in k}
        elif fault == "position_off_by_one":  # decode hands _rot [B] positions, prefill [1, L]
            DM._rot = lambda self, pos: real_rot(self, pos + 1 if pos.ndim == 1 else pos)
        elif fault is not None:
            raise ValueError(f"no fault {fault!r}: one of {FAULTS}")
        yield cfg, params
    finally:
        (DM._conv_prompt, DM._conv_step, DM._grouped, DM._rot, moe.route, pa.paged_attention) = saved


def served(fault, cfg, params, engine_args: dict, requests, max_new: int, tenants_before):
    """``requests`` (token lists) through Router -> ServingEngine ->
    DecodeModel built with ``fault``, all in flight together, after
    ``tenants_before`` (one request a slot, a few tokens each) have come
    and gone: the records ``reference_gaps`` reads, and the model."""
    from paddle_tpu import serving

    with made(fault, cfg, params) as (cfg, params):
        dm = serving.DecodeModel(cfg, params=params, **engine_args)
        dm.warm(full=True)
        engine = serving.ServingEngine(dm)
        engine.start()
        router = serving.Router([serving.LocalReplica("r0", engine)])

        def wave(prompts, n_new, tag):
            out = [None] * len(prompts)

            def one(i):
                out[i] = router.dispatch(list(prompts[i]), max_new_tokens=n_new, deadline_s=600,
                                         request_id=f"{tag}-{i}")
            threads = [threading.Thread(target=one, args=(i,)) for i in range(len(prompts))]
            [t.start() for t in threads]
            [t.join() for t in threads]
            bad = [r.get("error") for r in out if not r["ok"]]
            if bad:
                raise RuntimeError(f"fault {fault}: requests failed: {bad}")
            return out
        try:
            wave(tenants_before, 6, "before")
            out = wave(requests, max_new, "fault")
        finally:
            router.stop()
            engine.stop()
        engine.pages = None  # the pool's memory is the reference's to use now
    return [{"prompt": list(p), "tokens": r["tokens"]} for p, r in zip(requests, out)], dm


def reading(fault, arch, c: dict, cfg, params, engine_args: dict, requests, max_new: int,
            tenants_before, window: int = 0) -> dict:
    """One fault (None: the sound program) served and scored."""
    from benchmark.runners import serve_arch

    records, dm = served(fault, cfg, params, engine_args, requests, max_new, tenants_before)
    facts = serve_arch.reference_gaps(arch, c, params, dm, records, window)
    return dict(facts, fault=fault or "none", logit_tol=arch.LOGIT_TOL,
                caught=facts["max_logit_gap"] > arch.LOGIT_TOL)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="lfm2-serve-reason")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--requests", type=int, default=4)    # what a run checks: N_CHECKED answers
    ap.add_argument("--max-new", type=int, default=256)
    ap.add_argument("--faults", default=",".join(FAULTS), help="comma-separated; default all")
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

    def prompts(n):
        return [traffic_gen.draw_tokens(tr["tokens"], rng, (int(k),), c["vocab_size"]).tolist()
                for k in rng.integers(lo, hi + 1, size=n)]
    requests, before = prompts(a.requests), prompts(int(tr["engine"]["max_batch"]))
    window = -(-(hi + a.max_new) // 512) * 512
    ok = True
    for fault in (None, *[f for f in a.faults.split(",") if f]):
        r = reading(fault, arch, c, cfg, params, arch_modules.engine_args(tr["engine"]), requests,
                    a.max_new, before, window)
        print(json.dumps(r), flush=True)
        ok = ok and r["caught"] == (fault is not None)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
