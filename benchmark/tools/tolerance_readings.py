#!/usr/bin/env python3
"""The two readings a serving tolerance is set between, at a configuration's
own widths, on whatever backend JAX finds (arithmetic only: no time comes
out of this tool, so the CPU will do).

For a seeded sequence the float32 reference gives the logits at every
position. A forward "in precision X" is the same reference with the
operands of every matmul rounded to X (``matmul_dtype``); its greedy token
at each position is then scored by the float32 logits, as the runner scores
a served token: gap = best logit - logit of the chosen token. Printed: the
largest gap and the share of exact argmax for X = bfloat16 (what the
configuration states: should pass LOGIT_TOL) and X = float8_e4m3fn (the
nearest precision below it: has to FAIL it), and the routing agreement of
each with float32.

    python3 benchmark/tools/tolerance_readings.py --config olmoe-1b-7b [--tokens 192] [--seed 7]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="olmoe-1b-7b")
    ap.add_argument("--tokens", type=int, default=192)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--n-layer", type=int, default=None, help="fewer layers for a quick look")
    a = ap.parse_args()

    import jax.numpy as jnp
    import numpy as np

    from benchmark import arch, manifest, traffic_gen

    man = manifest.load()
    c = json.load(open(os.path.join(ROOT, next(x["file"] for x in man["configs"] if x["name"] == a.config))))
    if a.n_layer:
        c["n_layer"] = a.n_layer
    mod = arch.of(c)
    params = mod.make_params(c, a.seed, "bfloat16")
    rng = traffic_gen.rng_for(a.seed, "tolerance")
    seq = traffic_gen.draw_tokens({"dist": "zipf", "a": 1.1}, rng, (1, a.tokens), c["vocab_size"]).astype(np.int32)
    pos = np.arange(a.tokens, dtype=np.int32)[None]

    def forward(dtype):
        logits, routing = mod.reference_logits(lambda n: params[n], jnp.asarray(seq), jnp.asarray(pos), c,
                                               matmul_dtype=dtype)
        return np.asarray(logits)[0], np.sort(np.asarray(routing)[0], axis=-1)

    exact, exact_routing = forward(None)
    out = {"config": a.config, "n_layer": c["n_layer"], "tokens": a.tokens, "seed": a.seed,
           "logit_tol": mod.LOGIT_TOL, "logit_std": float(exact.std())}
    for name, dtype in (("bfloat16", jnp.bfloat16), ("float8_e4m3fn", jnp.float8_e4m3fn)):
        got, routing = forward(dtype)
        gaps = exact.max(-1) - exact[np.arange(a.tokens), got.argmax(-1)]
        out[name] = {"max_logit_gap": float(gaps.max()), "exact_argmax_share": float((gaps == 0).mean()),
                     "routing_agreement_share": float((routing == exact_routing).all(-1).mean()),
                     "correct": bool(gaps.max() <= mod.LOGIT_TOL)}
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
