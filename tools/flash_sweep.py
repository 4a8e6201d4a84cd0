"""Flash-attention tile sweep on the real chip.

The measurement rules behind the tile table of ops/attention.py
(`_FLASH_TILES`; PR 35 swept a TPU v5e at this tool's defaults, the
shape of the benchmark cell gpt2s-train-1k; PERF.md section 6 has the
numbers, and the candidates that lost):

- loop INSIDE one jitted program (lax.fori_loop, each iteration chained
  on the last), so per-call dispatch latency is amortized;
- scalar-only host fetch;
- for backward timings, CONSUME every gradient of the kernel under test:
  an unused gradient's kernel is dead-code-eliminated. `dq` consumes dq
  alone and `dkv` dk + dv alone, so each times ONE kernel (plus the
  delta reduction both share); `bwd` consumes all three;
- compare medians across reruns (median of 3), and kernels within one
  call of one --iters: a loop iteration carries ~0.5 ms that is not the
  kernel (PR 35: 2.41 ms here where the train step's trace reads 1.86),
  so differences carry over to the step and ratios do not.

Usage: python tools/flash_sweep.py MODE [--batch 32 --heads 12 --seq 1024
           --head-dim 64 --kv-seq SEQ --layout BTHD --no-causal]
           [--tiles "256,256 256,512 ..."]
  fwd / dq / dkv   one kernel per (bq,bk) of --tiles
  bwd              both backward kernels per "bq_dq,bk_dq,bq_dkv,bk_dkv"
  step             the full GPT-2 small train step at (batch, seq) per
                   "bq,bk;bq_dq,bk_dq;bq_dkv,bk_dkv" of --tiles through
                   the PADDLE_TPU_FLASH_* knobs, one process each ("-" is
                   the dispatcher's own table): the number that decides,
                   since kernel-local wins can lose end to end.
Every mode prints the share of the score square each kernel computes
(the monitor's flash_tiles_total) beside the time.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_DEFAULT_TILES = {  # PR 35's candidates at the defaults' shape; the table took the first of each
    "fwd": "256,1024 128,1024 512,1024 1024,1024 256,512 512,512 256,256",
    "dq": "128,1024 256,1024 512,1024 512,512 256,512 256,256",
    "dkv": "512,256 256,256 512,512 1024,256 1024,512 128,256",
    "bwd": "128,1024,512,256 512,512,512,512",
    "step": "- 256;1024/512,512;512,512",
}


def computed_share(kernel=None):
    """{kernel: share of the score tiles traced so far that are computed}."""
    fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]  # the package attribute is the function
    out = {k: round((n["interior"] + n["diagonal"]) / sum(n.values()), 4)
           for k, n in fa.tile_counts().items() if sum(n.values())}
    return out if kernel is None else out.get(kernel)


def _timed(many, args, label, flops, iters):
    from paddle_tpu import monitor

    monitor.reset_metrics()
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
        print(f"{label}: FAILED {type(e).__name__}: {str(e)[:200]}", flush=True)
        return
    med = sorted(times)[1]
    print(f"{label}: {med:.3f} ms  ({flops / med / 1e9:.1f} TF/s needed work; "
          f"computed share {computed_share()})", flush=True)


def _operands(a):
    import jax.numpy as jnp

    r = np.random.RandomState(0)
    shape = lambda t: ((a.batch, t, a.heads, a.head_dim) if a.layout == "BTHD"  # noqa: E731
                       else (a.batch, a.heads, t, a.head_dim))
    mk = lambda t: jnp.asarray(r.randn(*shape(t)), jnp.bfloat16) * 0.1  # noqa: E731
    return mk(a.seq), mk(a.kv_seq), mk(a.kv_seq)


def _needed_flops(a, matmuls):
    """`matmuls` products of 2*T*Tk*hd a head, halved under the mask."""
    return matmuls * 2.0 * a.batch * a.heads * a.seq * a.kv_seq * a.head_dim * (0.5 if a.causal else 1.0)


def sweep_fwd(a):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    q, k, v = _operands(a)
    for bq, bk in a.tiles:
        @jax.jit
        def many(qq, kk, vv, bq=bq, bk=bk):
            def body(_, acc):
                o = flash_attention(acc, kk, vv, causal=a.causal, block_q=bq,
                                    block_k=bk, layout=a.layout)
                return o.astype(acc.dtype)
            return jnp.mean(
                jax.lax.fori_loop(0, a.iters, body, qq).astype(jnp.float32))

        _timed(many, (q, k, v), f"fwd bq={bq} bk={bk}", _needed_flops(a, 2), a.iters)


def sweep_bwd(a, consume):
    """consume: which gradients the loop reads ("dq", "dkv" or "bwd")."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    q, k, v = _operands(a)
    if a.kv_seq != a.seq and consume != "dq":
        raise SystemExit("dkv / bwd chain dk + dv into dO: they need --kv-seq == --seq")
    for tiles in a.tiles:
        blocks = tiles if len(tiles) == 4 else tiles * 2

        def f(qq, kk, vv, blocks=blocks):
            return flash_attention(qq, kk, vv, causal=a.causal, block_q=blocks[0],
                                   block_k=blocks[1], layout=a.layout,
                                   bwd_blocks=blocks)

        @jax.jit
        def many(qq, kk, vv, f=f):
            out, vjp = jax.vjp(f, qq, kk, vv)

            def body(_, do):
                dq, dk, dv = vjp(do)
                g = {"dq": dq, "dkv": dk + dv, "bwd": dq + dk + dv}[consume]
                return (g * 1e-3 + do * 0.5).astype(do.dtype)

            do = jax.lax.fori_loop(0, a.iters, body, out)
            return jnp.mean(do.astype(jnp.float32))

        label = (f"{consume} bq={tiles[0]} bk={tiles[1]}" if len(tiles) == 2
                 else f"bwd dq/dkv={tiles}")
        _timed(many, (q, k, v), label,
               _needed_flops(a, {"dq": 2, "dkv": 2, "bwd": 4}[consume]), a.iters)


def sweep_step(a):
    """Full train step per config, one process each: the judge of record."""
    for spec in a.tiles:
        env = {k: v for k, v in os.environ.items()
               if k not in ("PADDLE_TPU_FLASH_BLOCKS", "PADDLE_TPU_FLASH_BWD_BLOCKS")}
        if spec != "-":
            fwd, _, bwd = spec.partition("/")
            env["PADDLE_TPU_FLASH_BLOCKS"] = fwd
            if bwd:
                env["PADDLE_TPU_FLASH_BWD_BLOCKS"] = bwd
        cmd = [sys.executable, os.path.abspath(__file__), "_one_step", "--batch", str(a.batch),
               "--heads", str(a.heads), "--seq", str(a.seq), "--head-dim", str(a.head_dim)]
        try:
            out = subprocess.run(cmd, env=env, capture_output=True, text=True, timeout=900)
        except subprocess.TimeoutExpired:
            print(f"step {spec}: TIMEOUT", flush=True)
            continue
        lines = [ln for ln in out.stdout.splitlines() if ln.startswith("STEP ")]
        print(f"step {spec}: {lines[-1][5:] if lines else 'FAILED ' + out.stderr[-500:]}", flush=True)


def one_step(a, prepare=None):
    """GPT-2 small (12 layers, vocab 50304, Adam) at (batch, seq) under
    whatever PADDLE_TPU_FLASH_* the environment holds: mean step time.
    ``prepare(main)`` may edit the forward program before the optimizer
    appends its backward (tools/ce_sweep.py sets the CE op's tiles)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import compile_cache
    from paddle_tpu.framework import Executor, Scope, program_guard
    from paddle_tpu.models.gpt import GPTConfig, build_train_program
    from paddle_tpu.optimizer import Adam

    compile_cache.enable()
    paddle.enable_static()
    cfg = GPTConfig(vocab_size=50304, n_layer=12, n_head=a.heads, d_model=a.heads * a.head_dim,
                    max_seq_len=max(a.seq, 1024), dropout=0.0, dtype="bfloat16")
    main, startup, io = build_train_program(cfg, batch=a.batch, seq=a.seq)
    if prepare is not None:
        prepare(main)
    with program_guard(main, startup):
        Adam(learning_rate=1e-4).minimize(io["loss"])
    scope, exe = Scope(), Executor()
    exe.run(startup, scope=scope)
    r = np.random.RandomState(0)
    tok = r.randint(0, 50257, (a.batch, a.seq + 1)).astype("int32")
    feed = {"tokens": tok[:, :-1], "labels": tok[:, 1:]}
    run = lambda: exe.run(main, feed=feed, fetch_list=[io["loss"]], scope=scope,  # noqa: E731
                          return_numpy=False)[0]
    jax.block_until_ready(run())
    jax.block_until_ready(run())
    n = a.steps
    t0 = time.perf_counter()
    for _ in range(n):
        last = run()
    loss = float(np.asarray(last))
    ms = (time.perf_counter() - t0) / n * 1e3
    assert np.isfinite(loss)
    print(f"STEP {ms:.2f} ms  {a.batch * a.seq / ms * 1e3:.0f} tokens/s  loss {loss:.4f}  "
          f"computed share {computed_share()}", flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=["fwd", "dq", "dkv", "bwd", "step", "_one_step"])
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--kv-seq", type=int, default=None)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--layout", choices=["BTHD", "BHTD"], default="BTHD")
    ap.add_argument("--no-causal", dest="causal", action="store_false")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--iters", type=int, default=40)
    ap.add_argument("--tiles", default=None)
    a = ap.parse_args(argv)
    a.kv_seq = a.kv_seq or a.seq
    tiles = (a.tiles or _DEFAULT_TILES.get(a.mode, "")).split()
    a.tiles = tiles if a.mode in ("step", "_one_step") else [
        tuple(int(x) for x in t.split(",")) for t in tiles]
    {"fwd": sweep_fwd, "dq": lambda a: sweep_bwd(a, "dq"), "dkv": lambda a: sweep_bwd(a, "dkv"),
     "bwd": lambda a: sweep_bwd(a, "bwd"), "step": sweep_step, "_one_step": one_step}[a.mode](a)


if __name__ == "__main__":
    main()
