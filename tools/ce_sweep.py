"""Fused lm-head CE tile sweep on the real chip.

The measurement rules of tools/flash_sweep.py, for the kernel family of
ops/pallas/fused_lmhead_ce.py (PR 40 swept a TPU v5e at this tool's
defaults, the shape of the benchmark cell gpt2s-train-1k; PERF.md
section 6 has the numbers, and the candidates that lost):

- loop INSIDE one jitted program (lax.fori_loop, each iteration chained
  on the last), so per-call dispatch latency is amortized;
- scalar-only host fetch;
- every output of the call under test is CONSUMED by the next iteration
  (nll and lse forward; dx and dw backward);
- medians of 3 reruns, and candidates compared within one call: an
  iteration carries the padded copy of w, the picked-logit row dots and
  the chain's own elementwise pass besides the kernel, so differences
  carry over to the step and ratios do not.

Usage: python tools/ce_sweep.py MODE [--tokens 32768 --width 768
           --vocab 50304] [--tiles "bn,bv bn,bv ..."]
  ce_stats   the forward (lmhead_ce_stats + what runs beside it) per tile
  ce_bwd     the backward (lmhead_ce_dw: dx and dW from one tile) per tile
  step       the full GPT-2 small train step at (batch, seq) per tile, one
             process each, through the op's block_n / block_v attributes
             ("-" is the dispatcher's own choice): the number that
             decides, since kernel-local wins can lose end to end.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_DEFAULT_TILES = {  # PR 40's candidates at the defaults' shape
    "ce_stats": "- 2048,512 1024,512 512,512 256,512 512,1536 1024,1536",
    "ce_bwd": "- 1024,512 512,1536 512,768 512,512 2048,256 1024,1536 2048,512",
    "step": "- 1024,512 512,512",
}


def _timed(many, args, label, flops, iters):
    try:
        out = many(*args)  # warmup/compile
        assert np.isfinite(float(np.asarray(out)))
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            out = many(*args)
            assert np.isfinite(float(np.asarray(out)))
            times.append((time.perf_counter() - t0) / iters * 1000)
    except Exception as e:  # a tiling Mosaic refuses is a result, not a crash
        print(f"{label}: FAILED {type(e).__name__}: {str(e)[:300]}", flush=True)
        return
    med = sorted(times)[1]
    print(f"{label}: {med:.3f} ms  ({flops / med / 1e9:.1f} TF/s needed work)", flush=True)


def _operands(a):
    import jax.numpy as jnp

    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(a.tokens, a.width) * 0.5, jnp.bfloat16)
    w = jnp.asarray(r.randn(a.vocab, a.width) * 0.05, jnp.bfloat16)
    lbl = jnp.asarray(r.randint(0, a.vocab, (a.tokens,)), jnp.int32)
    return x, w, lbl


def _blocks(ce, a, tile):
    return ce.tiles(a.tokens, a.width, a.vocab, 2) if tile == "-" else tuple(int(t) for t in tile.split(","))


def sweep(a):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import fused_lmhead_ce as ce

    x, w, lbl = _operands(a)
    interp = not ce.on_tpu()  # the CPU rehearsal runs the interpreter: no time of it means anything
    matmul = 2.0 * a.tokens * a.vocab * a.width
    for tile in a.tiles:
        bn, bv = _blocks(ce, a, tile)
        if a.mode == "ce_stats":
            @jax.jit
            def many(xx, ww, ll, bn=bn, bv=bv):
                def body(_, xc):
                    nll, lse = ce._run_fwd(xc, ww, ll, None, bn, bv, interp)
                    return (xc.astype(jnp.float32) + 1e-6 * (nll - lse)[:, None]).astype(xc.dtype)
                return jnp.mean(jax.lax.fori_loop(0, a.iters, body, xx).astype(jnp.float32))

            _timed(many, (x, w, lbl), f"ce_stats bn={bn} bv={bv}", matmul, a.iters)
        else:
            lse = jax.jit(lambda xx, ww, ll: ce._run_fwd(xx, ww, ll, None, bn, bv, interp)[1])(x, w, lbl)
            g = jnp.full((a.tokens,), 1.0 / a.tokens, jnp.float32)

            @jax.jit
            def many(xx, ww, ll, ls, gg, bn=bn, bv=bv):
                def body(_, c):
                    xc, wc = c
                    dx, dw = ce._run_bwd(xc, wc, ll, ls, gg, None, bn, bv, interp)
                    return ((xc.astype(jnp.float32) + 1e-3 * dx).astype(xc.dtype),
                            (wc.astype(jnp.float32) + 1e-3 * dw).astype(wc.dtype))
                xc, wc = jax.lax.fori_loop(0, a.iters, body, (xx, ww))
                return jnp.mean(xc.astype(jnp.float32)) + jnp.mean(wc.astype(jnp.float32))

            _timed(many, (x, w, lbl, lse, g), f"ce_bwd bn={bn} bv={bv}", 3 * matmul, a.iters)


def sweep_step(a):
    """Full train step per tile, one process each: the judge of record."""
    for tile in a.tiles:
        cmd = [sys.executable, os.path.abspath(__file__), "_one_step", "--tiles", tile,
               "--batch", str(a.batch), "--seq", str(a.seq), "--steps", str(a.steps)]
        try:
            out = subprocess.run(cmd, capture_output=True, text=True, timeout=900)
        except subprocess.TimeoutExpired:
            print(f"step {tile}: TIMEOUT", flush=True)
            continue
        lines = [ln for ln in out.stdout.splitlines() if ln.startswith("STEP ")]
        print(f"step {tile}: {lines[-1][5:] if lines else 'FAILED ' + out.stderr[-500:]}", flush=True)


def one_step(a):
    """tools/flash_sweep.py's train step (GPT-2 small, Adam) with the CE
    op's block_n / block_v attributes set to the tile."""
    import flash_sweep

    (tile,) = a.tiles

    def set_tiles(main):
        bn, bv = (int(t) for t in tile.split(","))
        for op in main.global_block().ops:
            if op.type == "fused_lm_head_ce":
                op._set_attr("block_n", bn)
                op._set_attr("block_v", bv)

    a.heads, a.head_dim = 12, 64
    flash_sweep.one_step(a, prepare=None if tile == "-" else set_tiles)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=["ce_stats", "ce_bwd", "step", "_one_step"])
    ap.add_argument("--tokens", type=int, default=32768)
    ap.add_argument("--width", type=int, default=768)
    ap.add_argument("--vocab", type=int, default=50304)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--tiles", default=None)
    a = ap.parse_args(argv)
    a.tiles = (a.tiles or _DEFAULT_TILES.get(a.mode, "-")).split()
    {"ce_stats": sweep, "ce_bwd": sweep, "step": sweep_step, "_one_step": one_step}[a.mode](a)


if __name__ == "__main__":
    main()
