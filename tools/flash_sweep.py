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
  bwd              the whole backward, all three gradients consumed: both
                   kernels per "bq_dq,bk_dq,bq_dkv,bk_dkv", or the ONE
                   fused kernel per "fused,bk" (its kv tile; its q tile is
                   the sequence), so one call ranks them
  check            the forward's output and lse, then dq, dk, dv of each
                   "bwd" tiling, against XLA's through materialised scores,
                   at the call's shape
  step             the full GPT-2 small train step at (batch, seq) per
                   "bq,bk" (the backward on the forward's tiles),
                   "bq,bk/bq_dq,bk_dq,bq_dkv,bk_dkv", "bq,bk/fused,bk" or
                   "/fused,bk" (the table's forward) of --tiles, set on
                   every fused_attention_tpu op as its block_q / block_k /
                   bwd_blocks attributes, one process each ("-" is the
                   dispatcher's own table): the number that decides,
                   since kernel-local wins can lose end to end.
Every mode prints the share of the score square each kernel computes
(the monitor's flash_tiles_total) beside the time, and `fwd` which body the
forward took (flash_fwd_calls_total: looped over head groups, or unrolled).
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
    # PR 53 swept the first four again once the one-step forward looped over its
    # heads: PR 35's long tiles (512, 1024 rows) had lost to the size of an
    # unrolled kernel's code before they lost to the area they compute
    "fwd": "256,1024 512,1024 1024,1024 128,1024 256,512 512,512 256,256",
    "dq": "128,1024 256,1024 512,1024 512,512 256,512 256,256",
    "dkv": "512,256 256,256 512,512 1024,256 1024,512 128,256",
    # PR 43: the fused kernel against the two it replaces
    "bwd": "fused,256 fused,512 128,1024,512,256",
    "check": "fused,256 128,1024,512,256",
    "step": "- 256,1024/128,1024,512,256",
}
_FWD_TILE = (256, 1024)  # the forward beside a fused backward: the table's at the defaults' shape


def computed_share(kernel=None):
    """{kernel: share of the score tiles traced so far that are computed}."""
    fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]  # the package attribute is the function
    out = {k: round((n["interior"] + n["diagonal"]) / sum(n.values()), 4)
           for k, n in fa.tile_counts().items() if sum(n.values())}
    return out if kernel is None else out.get(kernel)


def _fwd_body():
    """Which body the forward calls traced since the last reset took."""
    fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]
    return {b: int(n) for b, n in fa.fwd_body_counts().items() if n}


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
        print(f"    body {_fwd_body()}", flush=True)


def _attend(a, tiles):
    """The call under one --tiles entry: (bq, bk) for every kernel, the two
    backward kernels' four, or the fused backward's ("fused", bk)."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    fused = tiles[0] == "fused"
    fwd = _FWD_TILE if fused else tiles[:2]
    return lambda q, k, v: flash_attention(
        q, k, v, causal=a.causal, block_q=fwd[0], block_k=fwd[1], layout=a.layout,
        bwd_blocks=tiles if fused or len(tiles) > 2 else tiles * 2)


def scores_reference(q, k, v, causal=True, layout="BTHD"):
    """(out, lse) of attention through materialised scores in float32 at the
    highest matmul precision (bottom-right aligned mask, as the kernels');
    out in the inputs' layout, lse (B, H, T)."""
    import jax
    import jax.numpy as jnp

    bthd = layout == "BTHD"
    with jax.default_matmul_precision("highest"):
        q, k, v = (t.astype(jnp.float32) if bthd else t.astype(jnp.float32).transpose(0, 2, 1, 3) for t in (q, k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
        if causal:
            t, tk = s.shape[-2:]
            s = jnp.where(jnp.tril(jnp.ones((t, tk), bool), tk - t), s, -jnp.inf)
        out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        return (out if bthd else out.transpose(0, 2, 1, 3)), jax.nn.logsumexp(s, axis=-1)


def check_fwd(a, q, k, v):
    """Largest |difference| of the forward's output (over the largest
    |XLA output|) and of its lse from scores_reference's (eight batch
    elements at a time), on the dispatcher's tiles for the call."""
    import importlib

    import jax

    from paddle_tpu.ops import attention

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")  # the package attribute is the function
    bthd = a.layout == "BTHD"
    bq, bk, _ = attention._flash_tiles(a.seq, a.kv_seq, a.layout, a.causal, heads=a.heads, head_dim=a.head_dim)
    out, lse = fa._fwd(q, k, v, causal=a.causal, scale=1.0 / np.sqrt(a.head_dim), block_q=bq, block_k=bk,
                       interpret=not fa.on_tpu(), bthd=bthd)
    lse = np.asarray(lse if bthd else lse[..., 0])
    ref = jax.jit(lambda *t: scores_reference(*t, causal=a.causal, layout=a.layout))
    errs = {"out": 0.0, "lse": 0.0}
    for b in range(0, a.batch, 8):
        ro, rl = (np.asarray(x) for x in ref(q[b:b + 8], k[b:b + 8], v[b:b + 8]))
        errs["out"] = max(errs["out"], float(np.abs(np.asarray(out[b:b + 8], np.float32) - ro).max() / np.abs(ro).max()))
        errs["lse"] = max(errs["lse"], float(np.abs(lse[b:b + 8] - rl).max()))
    print(f"check fwd ({bq}, {bk}): max |out - xla f32| / max |xla f32|, max |lse - xla f32| {errs}", flush=True)
    assert all(np.isfinite(e) for e in errs.values())


def check_bwd(a):
    """Largest |difference| of each gradient from XLA's through
    materialised scores, in float32 at the highest matmul precision (eight
    batch elements at a time: the scores are B x H x T x Tk floats), over
    the largest |XLA gradient|, per tiling."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.attention import _sdpa_xla

    q, k, v = _operands(a)
    cot = (q * 2.0).astype(q.dtype)

    @jax.jit
    def ref_grads(q, k, v, cot):
        with jax.default_matmul_precision("highest"):
            f32 = [t.astype(jnp.float32) for t in (q, k, v)]
            return jax.vjp(lambda *t: _sdpa_xla(*t, is_causal=a.causal, layout=a.layout), *f32)[1](
                cot.astype(jnp.float32))

    chunks = [ref_grads(*(t[b:b + 8] for t in (q, k, v, cot))) for b in range(0, a.batch, 8)]
    ref = [np.concatenate([np.asarray(c[i]) for c in chunks]) for i in range(3)]
    check_fwd(a, q, k, v)
    for tiles in a.tiles:
        got = jax.jit(lambda q, k, v, f=_attend(a, tiles): jax.vjp(f, q, k, v)[1](cot))(q, k, v)
        errs = {n: float(np.abs(np.asarray(g, np.float32) - r).max() / np.abs(r).max())
                for n, g, r in zip(("dq", "dk", "dv"), got, ref)}
        print(f"check {tiles}: max |got - xla f32| / max |xla f32| {errs}", flush=True)
        assert all(np.isfinite(e) for e in errs.values())


def sweep_bwd(a, consume):
    """consume: which gradients the loop reads ("dq", "dkv" or "bwd")."""
    import jax
    import jax.numpy as jnp

    q, k, v = _operands(a)
    if a.kv_seq != a.seq and consume != "dq":
        raise SystemExit("dkv / bwd chain dk + dv into dO: they need --kv-seq == --seq")
    for tiles in a.tiles:
        f = _attend(a, tiles)

        @jax.jit
        def many(qq, kk, vv, f=f):
            out, vjp = jax.vjp(f, qq, kk, vv)

            def body(_, do):
                dq, dk, dv = vjp(do)
                g = {"dq": dq, "dkv": dk + dv, "bwd": dq + dk + dv}[consume]
                return (g * 1e-3 + do * 0.5).astype(do.dtype)

            do = jax.lax.fori_loop(0, a.iters, body, out)
            return jnp.mean(do.astype(jnp.float32))

        label = (f"bwd {tiles}" if tiles[0] == "fused" else f"bwd dq/dkv={tiles}" if len(tiles) > 2
                 else f"{consume} bq={tiles[0]} bk={tiles[1]}")
        _timed(many, (q, k, v), label,
               _needed_flops(a, {"dq": 2, "dkv": 2, "bwd": 4}[consume]), a.iters)


def step_attrs(spec):
    """One --tiles entry of `step` as the attributes it sets on every
    fused_attention_tpu op ("-": none, the dispatcher's own table); a
    malformed entry is refused here, before a process is spent on it."""
    if spec == "-":
        return {}
    fwd, _, bwd = spec.partition("/")
    fused = bwd.startswith("fused,")
    try:
        fwd = [int(x) for x in fwd.split(",") if fwd]
        bwd = [int(x) for x in bwd.removeprefix("fused,").split(",") if bwd]
    except ValueError:
        fwd = bwd = ()
    if (len(fwd), len(bwd), fused) not in {(2, 0, False), (2, 4, False), (0, 4, False), (2, 1, True), (0, 1, True)}:
        raise ValueError(f"--tiles {spec!r}: expected '-', 'bq,bk', 'bq,bk/bq_dq,bk_dq,bq_dkv,bk_dkv', "
                         f"'bq,bk/fused,bk' or '/fused,bk'")
    attrs = dict(zip(("block_q", "block_k"), fwd))
    if bwd:
        attrs["bwd_blocks"] = bwd  # one kv tile: the ONE fused kernel; four: dq's and dkv's
    return attrs


def sweep_step(a):
    """Full train step per config, one process each: the judge of record."""
    for spec in a.tiles:
        cmd = [sys.executable, os.path.abspath(__file__), "_one_step", "--tiles", spec, "--batch", str(a.batch),
               "--heads", str(a.heads), "--seq", str(a.seq), "--head-dim", str(a.head_dim), "--steps", str(a.steps)]
        try:
            out = subprocess.run(cmd, capture_output=True, text=True, timeout=900)
        except subprocess.TimeoutExpired:
            print(f"step {spec}: TIMEOUT", flush=True)
            continue
        lines = [ln for ln in out.stdout.splitlines() if ln.startswith("STEP ")]
        print(f"step {spec}: {lines[-1][5:] if lines else 'FAILED ' + out.stderr[-500:]}", flush=True)


def _one_step(a):
    """One `step` entry in this process: its attributes on every attention op."""
    (spec,) = a.tiles
    attrs = step_attrs(spec)

    def set_tiles(main):
        for op in main.global_block().ops:
            if op.type == "fused_attention_tpu":
                for name, value in attrs.items():
                    op._set_attr(name, value)

    one_step(a, prepare=set_tiles)


def one_step(a, prepare=None):
    """GPT-2 small (12 layers, vocab 50304, Adam) at (batch, seq): mean
    step time. ``prepare(main)`` may edit the forward program before the
    optimizer appends its backward (`step` here sets the attention ops'
    tiles, tools/ce_sweep.py the CE op's)."""
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
    ap.add_argument("mode", choices=["fwd", "dq", "dkv", "bwd", "check", "step", "_one_step"])
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
    if a.mode in ("step", "_one_step"):
        for spec in tiles:
            step_attrs(spec)
        a.tiles = tiles
    else:
        a.tiles = [tuple(x if x == "fused" else int(x) for x in t.split(",")) for t in tiles]
    {"fwd": sweep_fwd, "dq": lambda a: sweep_bwd(a, "dq"), "dkv": lambda a: sweep_bwd(a, "dkv"),
     "bwd": lambda a: sweep_bwd(a, "bwd"), "check": check_bwd, "step": sweep_step,
     "_one_step": _one_step}[a.mode](a)


if __name__ == "__main__":
    main()
