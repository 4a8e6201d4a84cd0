#!/usr/bin/env python3
"""The faults ``correct`` has to catch in a cell of the A.X-K1 block, each
made on purpose in the program and read by the cell's own check, on the
cell's own seed-made weights (no weight is touched here).

The model is served with one plausible mistake in it:

- ``m2_dropped``: the softmax scale is ``192^-0.5`` without YaRN's ``m^2``;
- ``ckv_stored_before_norm``: the pool keeps the K|V latent as it leaves the
  down-projection, before its norm (a prefill's own attention is right, every
  decode tick scores and weighs the wrong rows);
- ``krope_stored_unrotated``: the pool keeps the rotated key lanes unrotated;
- ``rope_half_split``: lane ``i`` turns with lane ``i + 32``, not ``2i`` with
  ``2i + 1``;
- ``experts_offset_one_share``: the held stack is weighed with the routing
  weights of the NEXT share's experts (12..23 for 0..11);
- ``shared_expert_dropped``: the routed part alone;
- ``groups_not_limited``: plain top-8 over all 192 experts;
- ``scores_in_bfloat16``: the router's logits and sigmoid in bfloat16;
- ``position_off_by_one``: the decode tick rotates by the next position.

The serving, the tenants that pass through every slot first and the scoring
are benchmark/tools/lfm2_fault_readings.py's (``served_with`` below is its
``served`` with the fault's context handed in): the float32 reference's one
full forward over prompt + answer, the served token's logit against the
reference's best, by ``runners/serve_arch.reference_gaps``. Each fault has to
read beyond the architecture's ``LOGIT_TOL`` and the sound program inside it;
``--float8`` adds the reference with its matmul operands rounded to float8
(e4m3) scored against the sound program's tokens, which has to read beyond it
too. One JSON line a reading; exits 1 if one is on the wrong side.

Two of the nine are NOT seen by that check on this configuration's share of
the experts (``UNSEEN_ON_A_SHARE``; PERF.md section 6, PR 50, has the chip
readings): ``groups_not_limited`` and ``scores_in_bfloat16`` change WHICH
experts a token takes, and a chip that holds 12 of 192 computes a sixteenth of
what the router decides; the bfloat16 program itself picks another top-8 set
than float32 in a quarter of the (position, layer) pairs, so these two read
inside the sound program's own range (0.43 and 0.38 beside 0.41 on the same
tokens). ``routing_agreement_share`` of the report shows the first (0.09
beside 0.74); ``correct`` does not read it. They are served and printed with
``"seen": false`` and do not decide the exit code.

    python3 benchmark/tools/axk1_fault_readings.py --workload axk1-serve-reason [--seed 7] [--requests 4] [--max-new 256] [--float8]

The cell's widths and depth want the chip; tests/test_axk1_faults.py runs
the same faults on the CPU at a cut size.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

UNSEEN_ON_A_SHARE = ("groups_not_limited", "scores_in_bfloat16")
FAULTS = ("m2_dropped", "ckv_stored_before_norm", "krope_stored_unrotated", "rope_half_split",
          "experts_offset_one_share", "shared_expert_dropped", "groups_not_limited", "scores_in_bfloat16",
          "position_off_by_one")


@contextlib.contextmanager
def made(fault, cfg, params):
    """``(cfg, params)`` to build the model from, with the program's own
    functions patched until the block is left: a program is traced when it
    is first compiled, so build, warm AND serve inside."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import serving
    from paddle_tpu.ops import moe
    from paddle_tpu.serving import model as M

    DM = serving.DecodeModel
    saved = (DM._latent_scale, DM._latent_qkv, DM._rot, M._latent_rows, M._rope_pairs, moe._scores)
    real_scale, real_qkv, real_rot, real_rows, _, _ = saved
    try:
        if fault == "m2_dropped":
            DM._latent_scale = lambda self: 1.0 / (self.cfg.qk_nope_dim + self.cfg.qk_rope_dim) ** 0.5
        elif fault in ("ckv_stored_before_norm", "krope_stored_unrotated"):
            raw = {}  # what the down-projection gave, from the layer's own trace to its row write

            def latent_qkv(self, lp, h, rot, lead):
                ckr = self._linear(lp, h, f"{M._LAYER}.attn.kv_down")
                raw["c_kv"], raw["k_rope"] = ckr[..., :self.cfg.kv_lora_rank], ckr[..., self.cfg.kv_lora_rank:]
                return real_qkv(self, lp, h, rot, lead)

            def latent_rows(c_kv, k_rope, lanes):
                if fault == "ckv_stored_before_norm":
                    return real_rows(raw["c_kv"].reshape(c_kv.shape), k_rope, lanes)
                return real_rows(c_kv, raw["k_rope"].reshape(k_rope.shape), lanes)
            DM._latent_qkv, M._latent_rows = latent_qkv, latent_rows
        elif fault == "rope_half_split":
            M._rope_pairs = M._rope
        elif fault == "experts_offset_one_share":
            first, held = cfg.experts_held
            cfg = dataclasses.replace(cfg, experts_held=((first + held) % cfg.n_experts, held))
        elif fault == "shared_expert_dropped":
            cfg = dataclasses.replace(cfg, d_ff_shared=0)
        elif fault == "groups_not_limited":
            cfg = dataclasses.replace(cfg, router_groups=1, router_keep_groups=1)
        elif fault == "scores_in_bfloat16":
            def scores(x, router_w, score):
                low = jnp.dot(x.astype(jnp.bfloat16), router_w.astype(jnp.bfloat16))
                return jax.nn.sigmoid(low).astype(jnp.float32)
            moe._scores = scores
        elif fault == "position_off_by_one":  # decode hands _rot [B] positions, prefill [1, L]
            DM._rot = lambda self, pos: real_rot(self, pos + 1 if pos.ndim == 1 else pos)
        elif fault is not None:
            raise ValueError(f"no fault {fault!r}: one of {FAULTS}")
        yield cfg, params
    finally:
        (DM._latent_scale, DM._latent_qkv, DM._rot, M._latent_rows, M._rope_pairs, moe._scores) = saved


def served_with(fault, cfg, params, engine_args: dict, requests, max_new: int, tenants_before):
    """lfm2_fault_readings.served, the model built and served under THIS
    module's ``made``."""
    from benchmark.tools import lfm2_fault_readings as shared

    theirs, shared.made = shared.made, made
    try:
        return shared.served(fault, cfg, params, engine_args, requests, max_new, tenants_before)
    finally:
        shared.made = theirs


def reading(fault, arch, c: dict, cfg, params, engine_args: dict, requests, max_new: int,
            tenants_before, window: int = 0, float8: bool = False) -> list:
    """One fault (None: the sound program) served and scored; with
    ``float8`` a second reading of the same tokens by the reference with
    its matmul operands rounded to float8."""
    from benchmark.runners import serve_arch

    records, dm = served_with(fault, cfg, params, engine_args, requests, max_new, tenants_before)
    facts = serve_arch.reference_gaps(arch, c, params, dm, records, window)
    out = [dict(facts, fault=fault or "none", logit_tol=arch.LOGIT_TOL,
                caught=facts["max_logit_gap"] > arch.LOGIT_TOL)]
    if float8:
        import types

        import jax.numpy as jnp

        low = types.SimpleNamespace(reference_logits=lambda *a: arch.reference_logits(
            *a, matmul_dtype=jnp.float8_e4m3fn))
        facts = serve_arch.reference_gaps(low, c, params, dm, records, window)
        out.append(dict(facts, fault="reference_in_float8", logit_tol=arch.LOGIT_TOL,
                        caught=facts["max_logit_gap"] > arch.LOGIT_TOL))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="axk1-serve-reason")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--requests", type=int, default=4)    # what a run checks: N_CHECKED answers
    ap.add_argument("--max-new", type=int, default=256)
    ap.add_argument("--faults", default=",".join(FAULTS), help="comma-separated; default all")
    ap.add_argument("--float8", action="store_true", help="also the reference in float8 on the sound tokens")
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
    # the cell's engine with its largest prefill bucket alone: one program fewer to compile a fault
    engine = dict(arch_modules.engine_args(tr["engine"]), prefill_buckets=[max(tr["engine"]["prefill_buckets"])])
    ok = True
    for fault in (None, *[f for f in a.faults.split(",") if f]):
        for r in reading(fault, arch, c, cfg, params, engine, requests,
                         a.max_new, before, window, float8=a.float8 and fault is None):
            seen = r["fault"] not in UNSEEN_ON_A_SHARE
            print(json.dumps(dict(r, seen=seen)), flush=True)
            ok = ok and (not seen or r["caught"] == (r["fault"] != "none"))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
