"""Per-op micro-benchmark harness.

Counterpart of /root/reference/paddle/fluid/operators/benchmark/
op_tester.cc (config-driven standalone per-op latency runner).
Measurement rules baked in:

- the iteration loop lives INSIDE one jitted program (lax.fori_loop), so
  per-dispatch host latency is amortized;
- every iteration's inputs are perturbed by the previous iteration's
  output (a carry-dependent epsilon scale), so no iteration can be
  elided as a repeat;
- only a scalar crosses back to the host;
- each op is compiled ONCE through the AOT stages (trace -> lower ->
  compile), so the same compile that produces the timed executable also
  yields ``memory_analysis()`` — per-op peak bytes
  (arguments+outputs+temps) land next to the latency in the output
  (``peak_bytes`` / ``temp_bytes``), the memory half of the hot-op
  ranking the raw-speed round works from.

Usage:
  python tools/op_bench.py                 # the built-in hot-op set
  python tools/op_bench.py --config f.json # op_tester-style config list
  python tools/op_bench.py --out OPBENCH.json

Config entry: {"op": type, "inputs": {slot: {"shape": [...], "dtype":
"float32", "int_max": 100}}, "attrs": {...}, "iters": 50}
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, List

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])


# GPT-2s + ResNet-50-flavored shapes: the ops a profile of the two
# flagship configs spends its time in
DEFAULT_CONFIG: List[Dict] = [
    {"op": "matmul", "inputs": {
        "X": {"shape": [8, 512, 768], "dtype": "bfloat16"},
        "Y": {"shape": [768, 3072], "dtype": "bfloat16"}}, "iters": 100},
    {"op": "matmul", "inputs": {
        "X": {"shape": [16384, 768], "dtype": "bfloat16"},
        "Y": {"shape": [768, 32768], "dtype": "bfloat16"}},
     "iters": 40, "label": "matmul_lmhead"},
    {"op": "fused_attention_tpu", "inputs": {
        "Q": {"shape": [8, 512, 12, 64], "dtype": "bfloat16"},
        "K": {"shape": [8, 512, 12, 64], "dtype": "bfloat16"},
        "V": {"shape": [8, 512, 12, 64], "dtype": "bfloat16"}},
     "attrs": {"is_causal": True, "layout": "BTHD", "is_test": True},
     "iters": 50, "label": "attention_512"},
    {"op": "fused_attention_tpu", "inputs": {
        "Q": {"shape": [8, 2048, 12, 64], "dtype": "bfloat16"},
        "K": {"shape": [8, 2048, 12, 64], "dtype": "bfloat16"},
        "V": {"shape": [8, 2048, 12, 64], "dtype": "bfloat16"}},
     "attrs": {"is_causal": True, "layout": "BTHD", "is_test": True},
     "iters": 30, "label": "attention_2048_flash"},
    {"op": "layer_norm", "inputs": {
        "X": {"shape": [8, 2048, 768], "dtype": "bfloat16"},
        "Scale": {"shape": [768], "dtype": "float32"},
        "Bias": {"shape": [768], "dtype": "float32"}},
     "attrs": {"begin_norm_axis": 2}, "iters": 100},
    {"op": "softmax_with_cross_entropy", "inputs": {
        "Logits": {"shape": [4096, 32768], "dtype": "bfloat16"},
        "Label": {"shape": [4096, 1], "dtype": "int64", "int_max": 32768}},
     "iters": 40},
    {"op": "lookup_table_v2", "inputs": {
        "W": {"shape": [32768, 768], "dtype": "bfloat16"},
        "Ids": {"shape": [8, 2048], "dtype": "int64", "int_max": 32768}},
     "iters": 100},
    {"op": "elementwise_add", "inputs": {
        "X": {"shape": [8, 2048, 768], "dtype": "bfloat16"},
        "Y": {"shape": [8, 2048, 768], "dtype": "bfloat16"}}, "iters": 100},
    {"op": "gelu", "inputs": {
        "X": {"shape": [8, 2048, 3072], "dtype": "bfloat16"}}, "iters": 100},
    {"op": "softmax", "inputs": {
        "X": {"shape": [8, 12, 512, 512], "dtype": "bfloat16"}},
     "attrs": {"axis": -1}, "iters": 100},
    {"op": "transpose2", "inputs": {
        "X": {"shape": [8, 2048, 12, 64], "dtype": "bfloat16"}},
     "attrs": {"axis": [0, 2, 1, 3]}, "iters": 100},
    {"op": "conv2d", "inputs": {
        "Input": {"shape": [32, 64, 56, 56], "dtype": "bfloat16"},
        "Filter": {"shape": [64, 64, 3, 3], "dtype": "bfloat16"}},
     "attrs": {"strides": [1, 1], "paddings": [1, 1]}, "iters": 50},
    {"op": "conv2d", "inputs": {
        "Input": {"shape": [32, 256, 14, 14], "dtype": "bfloat16"},
        "Filter": {"shape": [1024, 256, 1, 1], "dtype": "bfloat16"}},
     "attrs": {"strides": [1, 1], "paddings": [0, 0]},
     "iters": 50, "label": "conv2d_1x1"},
    {"op": "batch_norm", "inputs": {
        "X": {"shape": [32, 256, 28, 28], "dtype": "float32"},
        "Scale": {"shape": [256], "dtype": "float32"},
        "Bias": {"shape": [256], "dtype": "float32"},
        "Mean": {"shape": [256], "dtype": "float32"},
        "Variance": {"shape": [256], "dtype": "float32", "min": 0.5}},
     "attrs": {"is_test": True}, "iters": 100},
    {"op": "pool2d", "inputs": {
        "X": {"shape": [32, 64, 112, 112], "dtype": "bfloat16"}},
     "attrs": {"pooling_type": "max", "ksize": [3, 3], "strides": [2, 2],
               "paddings": [1, 1]}, "iters": 50},
    {"op": "relu", "inputs": {
        "X": {"shape": [32, 256, 56, 56], "dtype": "bfloat16"}}, "iters": 100},
    {"op": "adam", "inputs": {
        "Param": {"shape": [768, 3072], "dtype": "float32"},
        "Grad": {"shape": [768, 3072], "dtype": "float32"},
        "Moment1": {"shape": [768, 3072], "dtype": "float32"},
        "Moment2": {"shape": [768, 3072], "dtype": "float32", "min": 1.0},
        "LearningRate": {"shape": [1], "dtype": "float32", "min": 1e-4},
        "Beta1Pow": {"shape": [1], "dtype": "float32", "min": 0.9},
        "Beta2Pow": {"shape": [1], "dtype": "float32", "min": 0.999}},
     "iters": 100},
    {"op": "reduce_mean", "inputs": {
        "X": {"shape": [8, 2048, 768], "dtype": "float32"}},
     "attrs": {"dim": [2], "keep_dim": False}, "iters": 100},
    {"op": "dropout", "inputs": {
        "X": {"shape": [8, 2048, 3072], "dtype": "bfloat16"}},
     "attrs": {"dropout_prob": 0.1, "is_test": False}, "iters": 100},
    {"op": "concat", "inputs": {
        "X": [{"shape": [8, 2048, 768], "dtype": "bfloat16"},
              {"shape": [8, 2048, 768], "dtype": "bfloat16"}]},
     "attrs": {"axis": 2}, "iters": 100},
    # DP comms microbenches (distributed/comms.py): the device-side cost
    # of one ~25MB gradient bucket's reduce math over a simulated 2-rank
    # stacked payload — fp32 exact sum vs blockwise-int8
    # quantize/allgather-dequant-sum. Tracks the compute component of the
    # collective alongside the compute ops OPBENCH already ranks (the
    # network leg is the MULTICHIP harness's job).
    {"op": "allreduce_bucket_fp32", "synthetic": "allreduce_bucket",
     "quantize": "none", "mb": 25, "iters": 20,
     "label": "allreduce_bucket_fp32"},
    {"op": "allreduce_bucket_int8", "synthetic": "allreduce_bucket",
     "quantize": "int8", "mb": 25, "iters": 20,
     "label": "allreduce_bucket_int8"},
    # the lm-head + cross-entropy family at the seq-2048 bench shapes
    # (tokens = 8*2048): the raw-speed round's target. All three rows
    # compute the SAME per-token NLL forward; what differs is the
    # [tokens, vocab] logits story — `naive` materializes them in HBM
    # (the r05 matmul_lmhead + softmax_with_cross_entropy pair in one
    # row), `chunked` holds one [C, vocab] tile per lax-loop step, and
    # `fused_pallas` keeps the logits tile in VMEM only. The harness's
    # AOT `peak_bytes` lands next to `kernel_ms` per row, so the memory
    # claim (no [tokens, vocab] buffer on the pallas row) is measured,
    # not advertised.
    {"op": "lmhead_ce_naive", "synthetic": "lmhead_ce", "impl": "naive",
     "tokens": 16384, "d_model": 768, "vocab": 32768, "iters": 10,
     "label": "lmhead_ce_naive"},
    {"op": "lmhead_ce_chunked", "synthetic": "lmhead_ce",
     "impl": "chunked", "tokens": 16384, "d_model": 768, "vocab": 32768,
     "iters": 10, "label": "lmhead_ce_chunked"},
    {"op": "lmhead_ce_fused_pallas", "synthetic": "lmhead_ce",
     "impl": "pallas", "tokens": 16384, "d_model": 768, "vocab": 32768,
     "iters": 10, "label": "lmhead_ce_fused_pallas"},
]


def _make_array(rng, spec):
    shape = spec["shape"]
    dtype = spec.get("dtype", "float32")
    import jax.numpy as jnp

    if dtype.startswith("int"):
        hi = int(spec.get("int_max", 100))
        return jnp.asarray(rng.randint(0, hi, shape), dtype)
    lo = float(spec.get("min", 0.0))
    return jnp.asarray(rng.randn(*shape) * 0.1 + lo, dtype)


# the per-round dispatch/harness floor: OPBENCH_r05 showed nearly every
# small op clocking ~0.9ms (relu 0.928 ≈ matmul 0.894) — that plateau is
# the per-iteration cost of the measurement harness + dispatch, not
# kernel time. A null body (the loop, the carry add, the
# perturbation scaffolding, a scalar reduce over 8 elements — and no
# kernel) is timed once per round, and every op row records both raw
# ``ms`` and ``kernel_ms = ms - null_dispatch_ms`` so a raw-speed round
# ranks real kernel time instead of the shared floor.
NULL_ENTRY = {"op": "null_dispatch", "synthetic": "null_dispatch",
              "iters": 100, "label": "null_dispatch"}


def _synthetic_null_dispatch(entry):
    """(slots, base arrays, run_once) measuring the harness floor: the
    run_once body carries only the scaffolding every other entry pays
    (perturbation multiply, tiny reduce, carry add)."""
    import jax.numpy as jnp

    base = [jnp.ones((8,), jnp.float32)]

    def run_once(arrs, tick):
        return jnp.sum(arrs[0] * (1.0 + tick * 1e-12)) * 1e-12

    return [("X", 1)], base, run_once


def _synthetic_allreduce_bucket(entry):
    """(slots, base arrays, run_once) for the DP-comms bucket microbench:
    a [2, n] stacked fp32 payload stands in for a 2-rank allgather result
    and the measured body is exactly the reduce math the comms layer
    dispatches per bucket (pack is a reshape; quantize/dequant dominate
    the int8 path)."""
    import jax.numpy as jnp

    from paddle_tpu.distributed import comms

    numel = int(float(entry.get("mb", 25)) * 1024 * 1024 // 4)
    block = int(entry.get("block", comms.DEFAULT_BLOCK))
    numel -= numel % block
    quantize = entry.get("quantize", "none")
    rng = np.random.RandomState(0)
    stacked = jnp.asarray(rng.randn(2, numel) * 0.01, jnp.float32)

    def run_once(arrs, tick):
        payload = arrs[0] * (1.0 + tick * 1e-12)
        if quantize == "int8":
            qs = [comms.quantize_blockwise(payload[r], block)
                  for r in range(2)]
            red = sum(
                comms.dequantize_blockwise(q, s, numel, block)
                for q, s in qs)
        else:
            red = payload.sum(axis=0)
        return jnp.sum(red * 1e-12)

    return [("X", 1)], [stacked], run_once


def _synthetic_lmhead_ce(entry):
    """(slots, base arrays, run_once) for the lm-head+CE family: one
    bf16 (tokens, d) activation against a bf16 (vocab, d) tied
    embedding, int32 labels; the scalar out is the summed NLL. Forward
    only — comparable with the r05 matmul_lmhead/softmax rows."""
    import jax
    import jax.numpy as jnp

    n = int(entry.get("tokens", 16384))
    d = int(entry.get("d_model", 768))
    v = int(entry.get("vocab", 32768))
    impl = entry.get("impl", "pallas")
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n, d) * 0.02, jnp.bfloat16)
    w = jnp.asarray(rng.randn(v, d) * 0.02, jnp.bfloat16)
    lbl = jnp.asarray(rng.randint(0, v, (n,)), jnp.int32)

    def run_once(arrs, tick):
        xv = arrs[0] * (1.0 + tick * 1e-12).astype(arrs[0].dtype)
        wv, lv = arrs[1], arrs[2]
        if impl == "naive":
            # the materialized-logits path: bf16 [tokens, vocab] logits
            # out of the matmul, fp32 logsumexp over them (exactly the
            # model's softmax_with_cross_entropy numerics)
            logits = jax.lax.dot_general(
                xv, wv, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32).astype(jnp.bfloat16)
            lf = logits.astype(jnp.float32)
            lse = jax.nn.logsumexp(lf, axis=-1)
            picked = jnp.take_along_axis(lf, lv[:, None], axis=1)[:, 0]
            nll = lse - picked
        elif impl == "chunked":
            from paddle_tpu.ops import fused_ops as _fo

            padded, n_chunks = _fo._lmhead_pad_and_chunks(n, 4096)
            xp, lp = xv, lv
            if padded != n:
                xp = jnp.pad(xp, ((0, padded - n), (0, 0)))
                lp = jnp.pad(lp, (0, padded - n))
            nll = _fo._lm_head_ce(xp, wv, lp, n_chunks)[:n]
        else:
            from paddle_tpu.ops.pallas import fused_lmhead_ce as _plc

            nll = _plc.lmhead_ce(xv, wv, lv)
        return jnp.sum(nll * 1e-12)

    return [("X", 1), ("W", 1), ("Label", 1)], [x, w, lbl], run_once


def bench_op(entry, warmup=True):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.framework.registry import (LoweringContext, get_op_def,
                                               run_lowering)

    op_type = entry["op"]
    attrs = dict(entry.get("attrs", {}))
    iters = int(entry.get("iters", 50))
    rng = np.random.RandomState(0)

    if entry.get("synthetic") == "allreduce_bucket":
        slots, base, run_once = _synthetic_allreduce_bucket(entry)
    elif entry.get("synthetic") == "lmhead_ce":
        slots, base, run_once = _synthetic_lmhead_ce(entry)
    elif entry.get("synthetic") == "null_dispatch":
        slots, base, run_once = _synthetic_null_dispatch(entry)
    else:
        opdef = get_op_def(op_type)

        slots, base = [], []
        for slot, spec in entry["inputs"].items():
            specs = spec if isinstance(spec, list) else [spec]
            for k, sp in enumerate(specs):
                slots.append((slot, len(specs)))
                base.append(_make_array(rng, sp))

        def run_once(arrs, tick):
            ins: Dict[str, List] = {}
            for (slot, _), a in zip(slots, arrs):
                # carry-dependent perturbation: float inputs scale by
                # (1 + tick*1e-12) so no two dispatches are identical
                if jnp.issubdtype(a.dtype, jnp.inexact):
                    a = a * (1.0 + tick * 1e-12).astype(a.dtype)
                ins.setdefault(slot, []).append(a)
            ctx = LoweringContext(training=True)
            outs = run_lowering(opdef, ctx, ins, attrs)
            first = next(v[0] for v in outs.values() if v)
            return jnp.sum(first.astype(jnp.float32) * 1e-12)

    @jax.jit
    def many(arrs):
        def body(i, acc):
            return acc + run_once(arrs, acc)
        return jax.lax.fori_loop(0, iters, body, jnp.float32(0.0))

    # AOT-compile once: the executable is what gets timed AND what
    # answers memory_analysis() — no second compile, and the peak-bytes
    # number belongs to exactly the program measured (one shared
    # attr-table + peak convention: xla_insight.memory_analysis_bytes)
    from paddle_tpu.framework import xla_insight

    fn, mem = many, None
    try:
        executable = many.trace(base).lower().compile()
        m = xla_insight.memory_analysis_bytes(executable)
        if m.get("peak_bytes"):
            mem = m
        fn = executable
    except Exception:
        fn, mem = many, None  # plain jit dispatch; latency still measured

    out = fn(base)
    assert np.isfinite(float(np.asarray(out)))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = fn(base)
        assert np.isfinite(float(np.asarray(out)))
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e3, mem  # ms, memory analysis (or None)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=None,
                    help="JSON list of op entries (op_tester-style)")
    ap.add_argument("--out", default=None, help="write results JSON here")
    ap.add_argument("--filter", default=None, help="only ops containing this")
    args = ap.parse_args()

    config = DEFAULT_CONFIG
    if args.config:
        with open(args.config) as f:
            config = json.load(f)

    import jax

    results = {
        "device": jax.devices()[0].device_kind,
        "ops": [],
    }
    # the per-round dispatch floor every op's kernel_ms subtracts; a
    # failed null measurement degrades to raw-only rows, never a crash
    null_ms = None
    try:
        null_ms, _ = bench_op(NULL_ENTRY)
        results["null_dispatch_ms"] = round(null_ms, 4)
        print(json.dumps({"op": "null_dispatch",
                          "ms": results["null_dispatch_ms"]}), flush=True)
    except Exception as e:
        results["null_dispatch_error"] = (
            f"{type(e).__name__}: {str(e)[:120]}")
    for entry in config:
        label = entry.get("label", entry["op"])
        if args.filter and args.filter not in label:
            continue
        try:
            ms, mem = bench_op(entry)
            row = {"op": label, "ms": round(ms, 4)}
            if null_ms is not None:
                # overhead-subtracted kernel time: what the next
                # raw-speed round should rank ops by (the raw ms keeps
                # the historical meaning for OPBENCH comparisons)
                row["kernel_ms"] = round(max(0.0, ms - null_ms), 4)
            if mem is not None:
                # per-op peak memory next to latency (the memory
                # observability round): args+outputs+temps of the
                # compiled loop body
                row["peak_bytes"] = mem["peak_bytes"]
                if mem.get("temp_bytes") is not None:
                    row["temp_bytes"] = mem["temp_bytes"]
        except Exception as e:  # per-op failure must not kill the sweep
            row = {"op": label, "error": f"{type(e).__name__}: {str(e)[:120]}"}
        results["ops"].append(row)
        print(json.dumps(row), flush=True)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
