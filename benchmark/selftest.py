#!/usr/bin/env python3
"""Self-test of the yardstick, on CPU, in seconds:

    JAX_PLATFORMS=cpu python3 benchmark/selftest.py

1. BENCHMARK.json and every data file load and cross-reference (each
   cell's configuration, traffic, runner, metrics and readers exist; names
   and units use only the characters the contract allows);
2. the trace reduction gives hand-computed numbers on a hand-made trace
   and the stored numbers on the trace recorded on the chip
   (benchmark/testdata/);
3. the operation and byte functions match GPT-2 small values worked out
   by hand;
4. the traffic generator is a function of the seed;
5. the float32 reference agrees with ``DecodeModel.full_logits`` at a tiny
   size;
6. the training runner's loss tolerance is tight enough to fail a leaky
   causal mask and a skipped layer at GPT-2 small's real width and depth
   (the slowest part, ~25 s).
Exits non-zero on the first thing that does not hold.
"""
from __future__ import annotations

import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
HERE = os.path.join(ROOT, "benchmark")


def check(cond, what):
    if not cond:
        raise SystemExit(f"selftest FAILED: {what}")
    print(f"ok  {what}")


def close(a, b, rel=1e-9):
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-30)


def test_manifest():
    from benchmark import manifest

    man = manifest.load()
    probs = manifest.problems(man)
    check(not probs, f"manifest and data files agree {probs or ''}")
    for sub in ("configs", "traffic", "layer_metrics"):
        for f in sorted(os.listdir(os.path.join(HERE, sub))):
            check(f.endswith(".json") and manifest.NAME.match(f[:-5]), f"{sub}/{f} is named from a name")
            with open(os.path.join(HERE, sub, f)) as fh:
                json.load(fh)
    for w in man["workloads"]:
        c = manifest.cell(man, w["name"])
        check(c["config"]["n_embd"] % c["config"]["n_head"] == 0, f"{w['name']}: heads divide the width")
    for c in man["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        check(cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"],
              f"config {c['name']}: source and reduced agree between file and manifest")
        explained = {k for keys in cfg["changed"] for k in keys.split(", ")}
        for k, v in cfg["published"].items():
            check(k in explained and cfg[k] != v, f"config {c['name']}: changed key {k} is explained")
            check((k in c["reduced"]) == isinstance(v, (int, float)),
                  f"config {c['name']}: {k} is under 'reduced' if and only if it is a number")


def test_trace_reduce():
    from benchmark import trace_reduce as tr

    # hand-made: one chip, times in ns
    ops = [["fusion.1", 0.0, 100.0, "loop fusion"],
           ["while.2", 150.0, 300.0, ""],             # encloses the next two
           ["fusion.3", 160.0, 100.0, "convolution fusion"],
           ["fn.4", 300.0, 100.0, "%fn.4 = (bf16[32,1024,768]{2,1,0}, f32[32,12,1024]{2,1,0}) custom-call(bf16[32,1024,768]{2,1,0} "
                                    "%bitcast.1, bf16[32,1024,768]{2,1,0} %bitcast.2), custom_call_target=\"tpu_custom_call\""],
           ["all-gather-start.5", 500.0, 10.0, ""],
           ["fusion.6", 510.0, 190.0, "loop fusion"],
           ["all-gather-done.5", 700.0, 100.0, ""],
           ["all-reduce.7", 900.0, 100.0, ""]]
    trace = {"devices": {"/device:TPU:0": ops}, "modules": {},
             "host": [["bench/exe_run", 790.0, 120.0, "main"], ["$x.py:1 prepare", 830.0, 40.0, "main"],
                      ["$t.py:9 wait", 0.0, 1000.0, "other"]]}
    b = tr.device_busy(trace)
    # busy: [0,100] [150,450] [500,800] [900,1000] = 800 of 1000
    check(close(b["busy_s"], 800e-9) and close(b["window_s"], 1000e-9) and close(b["idle_pct"], 20.0),
          "busy union and idle share on the hand-made trace")
    st = {n: s for n, _d, s in tr.self_times(ops)}
    check(close(st["while.2"], 100e-9) and close(st["fusion.3"], 100e-9), "self time takes nested children out")
    hit = tr.op_time(trace, r"custom-call\(bf16\[32,1024,768\].*tpu_custom_call")
    check(hit and close(hit["seconds"], 100e-9) and hit["events"] == 1, "op time by operand-shape pattern")
    check(tr.op_time(trace, "no_such_kernel") is None, "a pattern that matches nothing reads nothing")
    col = tr.collectives(trace)
    # exposed: start 10 + done 100 + all-reduce 100 = 210; in flight [500,800] + [900,1000] = 400
    check(close(col["exposed_pct"], 21.0) and close(col["collective_pct"], 40.0),
          "collective share and its exposed part")
    gaps = tr.idle_gaps(trace)
    check(gaps[0][0] == "bench/exe_run > $x.py:1 prepare" and close(gaps[0][1], 100e-9),
          f"idle gap named by the enclosing benchmark span and host frame {gaps[:1]}")
    check(tr.op_label("fn.4", ops[3][3]) == "pallas:fn bf16[32,1024,768] <- bf16[32,1024,768]", "kernel label")
    check(tr.split_hlo("%copy.4 = bf16[8,2]{1,0} copy(bf16[8,2]{0,1} %p.1)")[0] == "copy.4"
          and tr.op_label("copy.4", "%copy.4 = bf16[8,2]{1,0} copy(bf16[8,2]{0,1} %p.1)") == "copy bf16[8,2]",
          "HLO text splits into a short name and a label")
    c = tr.clip(trace, 50.0, 200.0)
    check(close(tr.device_busy(c)["busy_s"], 100e-9), "clip cuts events at the edges")

    # recorded on the chip
    rec = os.path.join(HERE, "testdata", "recorded_trace.json.gz")
    exp = os.path.join(HERE, "testdata", "recorded_trace.expected.json")
    with gzip.open(rec, "rt") as f:
        recorded = json.load(f)
    with open(exp) as f:
        want = json.load(f)
    got = summarise(recorded)
    for k, v in want["numbers"].items():
        check(close(got[k], v, 1e-6), f"recorded trace: {k} = {v}")
    check([r[0] for r in tr.top_ops(recorded, 5)] == want["top_ops"], "recorded trace: top operations")


def summarise(trace) -> dict:
    """The numbers stored beside the recorded trace."""
    from benchmark import trace_reduce as tr

    b = tr.device_busy(trace)
    out = {"busy_s": b["busy_s"], "window_s": b["window_s"], "idle_pct": b["idle_pct"],
           "events": float(sum(len(v) for v in trace["devices"].values()))}
    from benchmark import manifest

    for name, metric in (("flash_s", "flash_roofline"), ("lmhead_ce_s", "lmhead_ce_roofline")):
        pat = manifest.layer_metric(metric)["args"]["pattern"]
        hit = tr.op_time(trace, pat)
        out[name] = hit["seconds"] if hit else 0.0
    return out


def test_flops():
    from benchmark import flops

    cfg = json.load(open(os.path.join(HERE, "configs", "gpt2-small.json")))
    # by hand: 50304*768 + 1024*768 + 12*(4*768*768+4*768 + 2*768*3072+3072+768 + 4*768) + 2*768
    check(flops.n_params(cfg) == 124_475_904 == cfg["assumed"]["parameters"], "GPT-2 small parameters")
    xl = json.load(open(os.path.join(HERE, "configs", "gpt2-xl.json")))
    check(flops.n_params(xl) == 1_557_686_400 == xl["assumed"]["parameters"], "GPT-2 XL parameters")
    # 6*124475904 + 12*12*1024*768
    check(flops.train_flops_per_token(cfg, 1024) == 746_855_424 + 113_246_208, "train operations per token")
    check(close(flops.mfu(100_000, cfg, 1024, 1, 197e12), 100_000 * 860_101_632 / 197e12), "MFU arithmetic")
    fa = flops.flash_attention_step(cfg, 32, 1024)
    # 6 matmuls * 2*1024*1024*64, halved (causal), * 12 layers * 32 sequences * 12 heads
    check(fa["flops"] == 6 * 2 * 1024 * 1024 * 64 * 0.5 * 12 * 32 * 12, "flash attention operations")
    check(fa["bytes"] == 12 * 12 * (32 * 1024 * 768 * 2), "flash attention bytes (12 tensor passes a layer)")
    ce = flops.lmhead_ce_step(cfg, 32, 1024)
    check(ce["flops"] == 3 * 2 * 32768 * 768 * 50304, "lm-head CE operations")
    check(ce["bytes"] == 2 * (4 * 32768 * 768 + 4 * 50304 * 768), "lm-head CE bytes")
    r = flops.roofline_seconds(fa["flops"], fa["bytes"], flops.load_peaks("TPU v5 lite"))
    check(r["bound"] == "compute" and close(r["seconds"], fa["flops"] / 197e12), "flash is compute bound at T=1024")
    check(flops.decode_tick_bytes(xl, 0) == 2 * 1_557_686_400, "decode tick streams every weight once")
    check(flops.decode_tick_bytes(xl, 1000) - flops.decode_tick_bytes(xl, 0) == 2 * 48 * 1600 * 2 * 1000,
          "decode tick streams K and V of the live context")
    try:
        flops.load_peaks("TPU v9")
    except KeyError:
        check(True, "an unknown device kind is an error, not a default")
    else:
        check(False, "an unknown device kind is an error, not a default")


def test_traffic():
    import numpy as np

    from benchmark import manifest, traffic_gen as tg

    a = tg.train_batches(manifest.traffic("train-1k-b32"), 50257, 7)
    b = tg.train_batches(manifest.traffic("train-1k-b32"), 50257, 7)
    c = tg.train_batches(manifest.traffic("train-1k-b32"), 50257, 8)
    check(len(a) == 8 and a[0]["tokens"].shape == (32, 1024), "eight host batches of (32, 1024)")
    check(all(np.array_equal(x["tokens"], y["tokens"]) for x, y in zip(a, b)), "same seed, same batches")
    check(not np.array_equal(a[0]["tokens"], c[0]["tokens"]), "another seed, other batches")
    check(np.array_equal(a[0]["tokens"][:, 1:], a[0]["labels"][:, :-1]), "labels are the next tokens")
    check(int(max(x["tokens"].max() for x in a)) < 50257, "no token from the padded rows")
    t = manifest.traffic("batch-decode-c12")
    plan = tg.closed_loop_plan(t, 50257, 3, 8)
    check(len(plan) == t["clients"] and all(64 <= len(r["prompt"]) <= 256 for p in plan for r in p),
          "closed loop: one plan per client, prompts inside their bounds")
    check(all(128 <= r["max_new_tokens"] <= 384 for p in plan for r in p[1:]), "answers inside their bounds")
    check(plan == tg.closed_loop_plan(t, 50257, 3, 8), "closed loop plan is a function of the seed")
    t = manifest.traffic("chat-open-r80")
    plan = tg.open_loop_plan(t, 50257, 5, 33.0)
    due = [r["due_s"] for r in plan]
    check(due == sorted(due) and len(due) == round(33.0 * t["rate_rps"]),
          f"open loop: {len(due)} arrivals in 33 s at {t['rate_rps']}/s (count fixed)")
    win = [[r for r in tg.open_loop_plan(t, 50257, s, 40.0) if 10.0 <= r["due_s"] < 40.0] for s in range(8)]
    check({len(w) for w in win} == {round(30 * t["rate_rps"])}, "every seed puts the same number of arrivals into a 30 s window")
    tot = [sum(r["max_new_tokens"] for r in w) for w in win]
    check((max(tot) - min(tot)) / np.mean(tot) < 0.03, f"stratified lengths: the window's answer tokens vary {min(tot)}..{max(tot)}")
    check(all(len(r["prompt"]) + r["max_new_tokens"] <= t["max_total"] for r in plan), "prompt + answer fit the positions")


def test_reference():
    import numpy as np

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax.numpy as jnp

    from benchmark import weights
    from benchmark.reference import gpt2
    from paddle_tpu import serving

    cfg = serving.GPTConfig(vocab_size=96, n_layer=2, n_head=4, d_model=32, max_seq_len=32, dtype="float32")
    table = weights.param_table(2, 32, 128, 96, 32)
    params = weights.make_params(table, 11, "float32")
    check(set(params) == set(serving.model.init_params(cfg)), "benchmark weights carry the program's names")
    dm = serving.DecodeModel(cfg, params=params, max_batch=2, n_blocks=8, block_size=8, prefill_buckets=[32])
    toks = np.random.default_rng(0).integers(0, 96, size=(1, 20)).astype(np.int32)
    want = dm.full_logits(toks)[0]
    pos = jnp.arange(20, dtype=jnp.int32)[None]
    got = np.asarray(gpt2.logits_at(lambda n: params[n], jnp.asarray(toks), pos, n_layer=2, n_head=4))[0]
    err = float(np.max(np.abs(got - want)))
    check(err < 2e-4, f"reference == DecodeModel.full_logits at a tiny float32 size (max abs diff {err:.2e})")
    nll = gpt2.mean_nll(lambda n: params[n], jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:]), 2, 4, chunk=1)
    lp = want[:-1] - np.log(np.sum(np.exp(want[:-1]), axis=-1, keepdims=True))
    check(abs(nll + float(np.mean(lp[np.arange(19), toks[0, 1:]]))) < 1e-4, "reference loss is the mean NLL of those logits")


def test_loss_tolerance():
    """What ``correct`` can see in a training cell. At a random start the
    mean NLL sits near ln(vocab) whatever the model does, so the first
    step's loss moves little under a defect: the tolerance has to be
    tight. GPT-2 small as run (12 layers, 768 wide), 4 x 1024 tokens (what
    the XL cell checks; the small cell checks 32 x 1024)."""
    import jax.numpy as jnp

    from benchmark import manifest, traffic_gen, weights
    from benchmark.reference import gpt2
    from benchmark.runners.train import LOSS_TOL

    c = json.load(open(os.path.join(HERE, "configs", "gpt2-small.json")))
    table = weights.param_table(c["n_layer"], c["n_embd"], c["n_inner"], c["vocab_size"], c["n_positions"])
    params = weights.make_params(table, 7, "bfloat16")
    tr = dict(manifest.traffic("train-1k-b32"), global_batch=4, n_host_batches=1)
    b = traffic_gen.train_batches(tr, c["published"]["vocab_size"], 7)[0]
    tok, lbl = jnp.asarray(b["tokens"], jnp.int32), jnp.asarray(b["labels"], jnp.int32)

    def loss(n_layer):
        return gpt2.mean_nll(lambda n: params[n], tok, lbl, n_layer, c["n_head"], chunk=4)

    base = loss(c["n_layer"])
    skipped = loss(c["n_layer"] - 1)
    gpt2._NEG = 0.0  # masked scores become 0, not -inf: the future leaks in
    gpt2._block.clear_cache()
    try:
        leaky = loss(c["n_layer"])
    finally:
        gpt2._NEG = -1e30
        gpt2._block.clear_cache()
    check(abs(skipped - base) > LOSS_TOL,
          f"a skipped layer moves the first loss by {skipped - base:+.4f}, beyond the tolerance {LOSS_TOL}")
    check(abs(leaky - base) > LOSS_TOL,
          f"a leaky causal mask moves the first loss by {leaky - base:+.4f}, beyond the tolerance {LOSS_TOL}")


if __name__ == "__main__":
    test_manifest()
    test_flops()
    test_traffic()
    test_trace_reduce()
    test_reference()
    test_loss_tolerance()
    print("selftest passed")
