"""Flash-attention kernel block sweep on the real chip.

The measurement rules that produced the round-5 block table (see
ops/attention.py dispatch comments and tools/op_bench.py):

- loop INSIDE one jitted program (lax.fori_loop, each iteration chained
  on the last), so per-call dispatch latency is amortized;
- scalar-only host fetch;
- for backward timings, CONSUME dq+dk+dv: an unused gradient's kernel
  is dead-code-eliminated and you silently time half the backward;
- compare medians across reruns (the kernel sweeps below use
  median-of-3).

Usage: python tools/flash_sweep.py [fwd|bwd|step]
  fwd/bwd sweep kernel tilings at B=8,H=12,T=2048,D=64;
  step runs the full GPT train step per config via PADDLE_TPU_FLASH_*
  env knobs (the number that actually matters — kernel-local wins can
  lose end-to-end, as the round-4 bwd-tiling sweep showed).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

B, H, T, D = 8, 12, 2048, 64
ITERS = 40


def _timed(many, args, label, flops=None):
    import jax

    out = many(*args)  # warmup/compile
    assert np.isfinite(float(np.asarray(out)))
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = many(*args)
        assert np.isfinite(float(np.asarray(out)))
        times.append((time.perf_counter() - t0) / ITERS * 1000)
    med = sorted(times)[1]
    msg = f"{label}: {med:.2f} ms"
    if flops:
        msg += f"  ({flops / med / 1e9:.1f} TF/s)"
    print(msg, flush=True)


def sweep_fwd():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(B, T, H, D), jnp.bfloat16) * 0.1
    k = jnp.asarray(r.randn(B, T, H, D), jnp.bfloat16) * 0.1
    v = jnp.asarray(r.randn(B, T, H, D), jnp.bfloat16) * 0.1
    flops = 4 * B * H * T * T * D * 0.5  # causal-adjusted

    for bq, bk in [(256, 512), (256, 1024), (512, 512), (128, 512)]:
        @jax.jit
        def many(qq, kk, vv, bq=bq, bk=bk):
            def body(_, acc):
                o = flash_attention(acc, kk, vv, causal=True, block_q=bq,
                                    block_k=bk, layout="BTHD")
                return o.astype(acc.dtype)
            return jnp.mean(
                jax.lax.fori_loop(0, ITERS, body, qq).astype(jnp.float32))

        try:
            _timed(many, (q, k, v), f"fwd bq={bq} bk={bk}", flops)
        except Exception as e:
            print(f"fwd bq={bq} bk={bk} FAILED: {type(e).__name__}")


def sweep_bwd():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(B, T, H, D), jnp.bfloat16) * 0.1
    k = jnp.asarray(r.randn(B, T, H, D), jnp.bfloat16) * 0.1
    v = jnp.asarray(r.randn(B, T, H, D), jnp.bfloat16) * 0.1
    flops = 4 * B * H * T * T * D * 0.5 * 2.5

    for blocks in [(256, 512, 256, 512), (512, 512, 512, 512),
                   (256, 1024, 512, 512)]:
        def f(qq, kk, vv, blocks=blocks):
            return flash_attention(qq, kk, vv, causal=True, block_q=256,
                                   block_k=1024, layout="BTHD",
                                   bwd_blocks=blocks)

        @jax.jit
        def many(qq, kk, vv, f=f):
            out, vjp = jax.vjp(f, qq, kk, vv)

            def body(_, do):
                dq, dk, dv = vjp(do)  # ALL consumed: nothing DCE'd
                return ((dq + dk + dv) * 1e-3 + do * 0.5).astype(do.dtype)

            do = jax.lax.fori_loop(0, ITERS, body, out)
            return jnp.mean(do.astype(jnp.float32))

        try:
            _timed(many, (q, k, v), f"bwd dq/dkv={blocks}", flops)
        except Exception as e:
            print(f"bwd {blocks} FAILED: {type(e).__name__}")


def sweep_step():
    """Full train step per config — the judge of record."""
    configs = [
        ("256;1024", "512,512;512,512"),
        ("256;512", ""),
        ("256;1024", "256,512;256,512"),
    ]
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "..", "bench.py")
    for fwd, bwd in configs:
        env = dict(os.environ)
        env["PADDLE_TPU_FLASH_BLOCKS"] = fwd
        if bwd:
            env["PADDLE_TPU_FLASH_BWD_BLOCKS"] = bwd
        else:  # a leftover knob from the caller's shell must not leak in
            env.pop("PADDLE_TPU_FLASH_BWD_BLOCKS", None)
        try:
            out = subprocess.run([sys.executable, script], env=env,
                                 capture_output=True, text=True, timeout=600)
        except subprocess.TimeoutExpired:
            print(f"fwd={fwd} bwd={bwd or 'fwd-tied'}: TIMEOUT", flush=True)
            continue
        lines = out.stdout.strip().splitlines()
        try:
            d = json.loads(lines[-1]) if lines else {}
            print(f"fwd={fwd} bwd={bwd or 'fwd-tied'}: "
                  f"long_seq {d['long_seq']['tokens_per_sec']} tok/s, "
                  f"headline {d['tokens_per_sec']} tok/s", flush=True)
        except (json.JSONDecodeError, KeyError, IndexError):
            print(f"fwd={fwd} bwd={bwd or 'fwd-tied'}: FAILED\n"
                  f"{out.stderr[-500:]}", flush=True)


if __name__ == "__main__":
    mode = sys.argv[1] if len(sys.argv) > 1 else "fwd"
    {"fwd": sweep_fwd, "bwd": sweep_bwd, "step": sweep_step}[mode]()
