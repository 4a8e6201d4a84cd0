#!/usr/bin/env python3
"""The quickest proof that paddle-tpu still starts on the chip.

``python chip_smoke.py`` (no arguments) drives the two main paths once at
the full width of GPT-2s (12 layers, 12 heads, 768 wide, vocab 32768,
bf16), through the entry points a user calls, with random weights made
from a seed:

  0  identity  what JAX found; anything but a TPU whose device_kind is in
               paddle_tpu.device.DEVICE_PEAKS ends the run
  1  kernels   the three pallas families, Mosaic-compiled, against the
               repo's own float32 references at real inner widths
  2  trainer   build_train_program -> Adam.minimize -> Executor, 8 steps
               at (8, 2048) (flash + fused CE + fused Adam in one
               program) and at (8, 512) (XLA attention path)
  3  server    DecodeModel behind ServingEngine.start() and
               Router([LocalReplica]): 8 concurrent requests over several
               prefill buckets, each replayed alone bit-for-bit
  4  4 chips   (when jax.device_count() >= 4) the dp / fsdp / tp recipes
               through fleet, then tp-sharded decode

Process layout: ONE process. A chip belongs to the process that touched
JAX first, so every phase, phase 4 included, runs here in the process
that holds the device(s), and nothing is ever spawned. The script sets
no platform: it uses what JAX finds and fails when that is not a TPU.

Every check is a hard failure: the first one that does not hold raises,
the exit code is non-zero and no result line is printed. On success the
last two lines of stdout are one JSON object each: first the summary
(per-phase facts and compile seconds, ``"claim": null`` — this script
measures nothing), then, LAST, the result line with exactly these keys,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
the device as JAX reports it. Seconds it prints are set-up facts (compile
time, cache state), not rates.

The compile cache follows paddle_tpu.compile_cache: the directory in
JAX_COMPILATION_CACHE_DIR when set, else the checkout's ``.jax_cache``.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

T0 = time.perf_counter()

GPT2S = dict(vocab_size=32768, n_layer=12, n_head=12, d_model=768)

# bf16 keeps 8 significant bits, so one rounding is off by up to 2**-8
# relative; every tolerance below is a small multiple of that. Errors are
# measured against a float32 reference evaluated at "highest" matmul
# precision and normalised by the reference's largest magnitude.
BF16_U = 2.0 ** -8
TOL = {
    "flash_out": 4 * BF16_U,      # p and out each rounded to bf16
    "flash_grad": 8 * BF16_U,     # + ds rounded, two chained matmuls
    "ce_nll": BF16_U,             # f32 stats over exact bf16 products
    "ce_grad": 8 * BF16_U,        # d_logits rounded to bf16, long sums
    "adam_param": 1.25 * BF16_U,  # per element: the one output rounding
    "adam_moment": 1e-5,          # f32 in, f32 out: a few ulps
    "recipe_loss": 2e-2,          # step-1 loss across dp / fsdp / tp
}


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.perf_counter() - T0:7.1f}s] {msg}", flush=True)


def check(cond, what: str) -> None:
    """A check that survives ``python -O`` and names what failed."""
    if not cond:
        raise SmokeFailure(what)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-30))


def counter(name: str, **labels) -> int:
    """Current value of one series of a paddle_tpu.monitor counter."""
    from paddle_tpu import monitor

    family = monitor.default_registry().get(name)
    return int((family.labels(**labels) if labels else family).value)


# -- facts only a chip run can show (kept apart so a CPU rehearsal of the
# control flow can stub exactly these) --------------------------------------


def require_mosaic(hlo_text: str, what: str, at_least: int = 1) -> int:
    """The compiled program carries Mosaic kernels: an interpreted pallas
    kernel lowers to plain HLO and leaves no tpu_custom_call behind."""
    n = hlo_text.count("tpu_custom_call")
    check(n >= at_least,
          f"{what}: {n} tpu_custom_call in the compiled HLO, expected "
          f">= {at_least} (a kernel ran interpreted or gave way to XLA)")
    return n


def require_on_tpu(array, what: str) -> None:
    plats = sorted({d.platform for d in array.devices()})
    check(plats == ["tpu"], f"{what} lives on {plats}, not on a tpu device")


def require_device_memory(devices) -> list:
    """The allocator of every device answers (device.memory_stats raises
    on a TPU that would need the synthetic fallback) and holds bytes."""
    from paddle_tpu import device as pdevice

    used = []
    for d in devices:
        stats = pdevice.memory_stats(d)
        check(stats["source"] == "device",
              f"memory_stats({d}) source is {stats['source']!r}")
        check(stats["bytes_in_use"] > 0, f"{d} holds no bytes")
        used.append(int(stats["bytes_in_use"]))
    return used


def require_no_recompiles(where: str) -> None:
    from paddle_tpu import monitor
    from paddle_tpu.framework import xla_insight

    check(monitor.enabled(), "metrics are off: recompiles would not count")
    counts = xla_insight.failure_counts()
    check(counts == {"capture_errors": 0, "aot_fallbacks": 0},
          f"{where}: xla_insight lost an AOT executable: {counts}")


# -- phase 0 -----------------------------------------------------------------


def phase_identity() -> dict:
    import jax

    devs = jax.devices()
    log(f"jax {jax.__version__}: platform={devs[0].platform} "
        f"device_kind={devs[0].device_kind!r} count={len(devs)}")
    from paddle_tpu.device import require_tpu

    d = require_tpu("chip_smoke")  # exits unless a TPU in the peaks table
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# -- phase 1 -----------------------------------------------------------------


def _compile_run(fn, *args):
    """jit + AOT-compile ``fn`` here, return (outputs, hlo text, compile
    seconds): the same executable is inspected and run."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    dt = time.perf_counter() - t0
    out = jax.block_until_ready(compiled(*args))
    return out, compiled.as_text(), dt


def phase_kernels(T: int = 1024, H: int = 12, hd: int = 64,
                  N: int = 1024, D: int = 768, V: int = 32768) -> dict:
    import jax
    import jax.numpy as jnp

    from paddle_tpu.framework.registry import LoweringContext, get_op_def
    from paddle_tpu.ops import attention, optimizer_ops
    from paddle_tpu.ops.attention import _sdpa_xla
    from paddle_tpu.ops.pallas import backend, fused_adam
    from paddle_tpu.ops.pallas.fused_lmhead_ce import lmhead_ce

    check(backend.on_tpu(), "pallas kernels would run interpreted "
          "(ops.pallas.backend.on_tpu() is False)")
    r = np.random.RandomState(0)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    facts: dict = {}

    # flash attention through the op's own dispatcher (its block choice)
    def rnd(*shape, scale=1.0):
        return jnp.asarray(r.randn(*shape) * scale, jnp.bfloat16)

    q, k, v, do = (rnd(2, T, H, hd) for _ in range(4))
    attn = get_op_def("fused_attention_tpu").lower
    attrs = {"is_causal": True, "layout": "BTHD"}

    def flash(q, k, v, do):
        out, vjp = jax.vjp(
            lambda q, k, v: attn(LoweringContext(),
                                 {"Q": [q], "K": [k], "V": [v]},
                                 attrs)["Out"], q, k, v)
        return (out,) + vjp(do)

    def flash_ref(q, k, v, do):
        out, vjp = jax.vjp(
            lambda q, k, v: _sdpa_xla(q, k, v, is_causal=True,
                                      layout="BTHD"), q, k, v)
        return (out,) + vjp(do)

    before = attention.FLASH_DISPATCH_COUNT
    got, hlo, dt = _compile_run(flash, q, k, v, do)
    check(attention.FLASH_DISPATCH_COUNT > before,
          f"fused_attention_tpu did not pick the flash kernel at T={T}")
    # forward and, at T <= 1024, ONE fused backward (dq, dk, dv); three past it
    n_calls = require_mosaic(hlo, "flash fwd+bwd", at_least=2 if T <= 1024 else 3)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(flash_ref)(f32(q), f32(k), f32(v), f32(do))
    errs = {n: rel_err(g, w) for n, g, w in
            zip(("out", "dq", "dk", "dv"), got, want)}
    check(errs["out"] <= TOL["flash_out"], f"flash out error {errs}")
    check(max(errs[n] for n in ("dq", "dk", "dv")) <= TOL["flash_grad"],
          f"flash grad error {errs}")
    facts["flash"] = {"shape": [2, T, H, hd], "tpu_custom_calls": n_calls,
                      "compile_seconds": round(dt, 2),
                      "rel_err": {n: round(e, 5) for n, e in errs.items()}}
    log(f"flash fwd+bwd parity ok {errs}")

    # fused lm-head + CE: value and both gradients vs materialized logits
    x, w = rnd(N, D), rnd(V, D, scale=0.02)
    lbl = jnp.asarray(r.randint(0, V, (N,)), jnp.int32)
    g = jnp.asarray(r.randn(N) / N, jnp.float32)

    def ref_nll(x, w, lbl):
        logits = jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        picked = jnp.take_along_axis(logits, lbl[:, None], axis=1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    def value_and_grads(nll_fn):
        def run(x, w, lbl, g):
            nll, vjp = jax.vjp(lambda x, w: nll_fn(x, w, lbl), x, w)
            return (nll,) + vjp(g)
        return run

    got, hlo, dt = _compile_run(value_and_grads(lmhead_ce), x, w, lbl, g)
    n_calls = require_mosaic(hlo, "lmhead_ce fwd+bwd", at_least=2)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(value_and_grads(ref_nll))(f32(x), f32(w), lbl, g)
    errs = {n: rel_err(a, b) for n, a, b in
            zip(("nll", "dx", "dw"), got, want)}
    check(errs["nll"] <= TOL["ce_nll"], f"lmhead_ce value error {errs}")
    check(max(errs["dx"], errs["dw"]) <= TOL["ce_grad"],
          f"lmhead_ce grad error {errs}")
    facts["lmhead_ce"] = {"shape": [N, D, V], "tpu_custom_calls": n_calls,
                          "compile_seconds": round(dt, 2),
                          "rel_err": {n: round(e, 6)
                                      for n, e in errs.items()}}
    log(f"lmhead_ce value+grad parity ok {errs}")

    # fused Adam vs the plain jnp rule of the same op
    p, grad = rnd(V, D, scale=0.02), rnd(V, D, scale=0.01)
    m = jnp.asarray(r.randn(V, D) * 1e-3, jnp.float32)
    v2 = jnp.asarray(np.abs(r.randn(V, D)) * 1e-5, jnp.float32)
    check(fused_adam.supported(p, grad, m, v2), "fused_adam refuses (V, D)")
    lr, b1p, b2p = (jnp.float32(s) for s in (1e-2, 0.9 ** 3, 0.999 ** 3))
    ins = {"Param": [f32(p)], "Grad": [f32(grad)], "Moment1": [m],
           "Moment2": [v2], "LearningRate": [lr], "Beta1Pow": [b1p],
           "Beta2Pow": [b2p]}
    want = jax.jit(lambda ins: optimizer_ops._adam.__wrapped__(
        None, ins, {}))(ins)
    want = [np.asarray(want[s]) for s in
            ("ParamOut", "Moment1Out", "Moment2Out")]
    # last: the kernel updates p, m and v in place
    got, hlo, dt = _compile_run(fused_adam.fused_adam, p, grad, m, v2,
                                lr, b1p, b2p)
    n_calls = require_mosaic(hlo, "fused_adam")
    got = [np.asarray(a, np.float32) for a in got]
    p_err = float(np.max(np.abs(got[0] - want[0])
                         / (np.abs(want[0]) + 1e-6)))
    m_err, v_err = rel_err(got[1], want[1]), rel_err(got[2], want[2])
    check(p_err <= TOL["adam_param"], f"fused_adam param error {p_err}")
    check(max(m_err, v_err) <= TOL["adam_moment"],
          f"fused_adam moment error {m_err} {v_err}")
    facts["fused_adam"] = {"shape": [V, D], "tpu_custom_calls": n_calls,
                           "compile_seconds": round(dt, 2),
                           "rel_err": {"param": round(p_err, 6),
                                       "m": m_err, "v": v_err}}
    log(f"fused_adam parity ok param={p_err:.2e} m={m_err:.1e} "
        f"v={v_err:.1e}")
    return facts


# -- phase 2 -----------------------------------------------------------------


def _train_insight(exe) -> dict:
    insights = exe.compiled_insights()
    check(insights, "no xla_insight capture for the train program "
          "(the AOT path was not taken)")
    return max(insights, key=lambda c: c.get("flops") or 0)


def _hlo_of(insight: dict) -> str:
    path = (insight.get("artifacts") or {}).get("hlo")
    check(path and os.path.exists(path),
          f"program {insight.get('key_hash')} dumped no HLO text")
    with open(path) as f:
        return f.read()


def _run_steps(exe, main, feed, loss_var, scope, steps: int):
    """``steps`` runs of one program on one batch: losses, first-run and
    steady seconds, and the executor's own cache counters over them."""
    import jax

    def lookups():
        return {r: counter("executor_cache_lookups_total", result=r)
                for r in ("miss", "hit")}

    before = lookups()
    losses, walls, last = [], [], None
    for _ in range(steps):
        t0 = time.perf_counter()
        last = exe.run(main, feed=feed, fetch_list=[loss_var], scope=scope,
                       return_numpy=False)[0]
        jax.block_until_ready(last)
        walls.append(time.perf_counter() - t0)
        losses.append(float(np.asarray(last)))
    delta = {r: n - before[r] for r, n in lookups().items()}
    check(delta == {"miss": 1, "hit": steps - 1},
          f"executor cache over {steps} steps of one shape: {delta} "
          f"(want one miss, then hits)")
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    return losses, walls, last


def phase_trainer(seq: int, batch: int = 8, steps: int = 8,
                  model: dict = GPT2S) -> dict:
    import jax

    from paddle_tpu.framework import Executor, Scope, program_guard
    from paddle_tpu.models.gpt import GPTConfig, build_train_program
    from paddle_tpu.ops import attention
    from paddle_tpu.optimizer import Adam

    # exactly as bench.py builds it
    cfg = GPTConfig(max_seq_len=seq, dtype="bfloat16", **model)
    flash0 = attention.FLASH_DISPATCH_COUNT
    main, startup, io = build_train_program(cfg, batch=batch, seq=seq)
    with program_guard(main, startup):
        Adam(learning_rate=1e-4).minimize(io["loss"])
    check(io["lm_head_impl"] == "pallas",
          f"default loss path is {io['lm_head_impl']!r}, not the kernel")
    scope, exe = Scope(), Executor()
    exe.run(startup, scope=scope)

    r = np.random.RandomState(0)
    feed = {n: jax.device_put(
        r.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64))
        for n in ("tokens", "labels")}
    losses, walls, last = _run_steps(exe, main, feed, io["loss"], scope,
                                     steps)
    flash_dispatches = attention.FLASH_DISPATCH_COUNT - flash0

    ln_v = float(np.log(cfg.vocab_size))
    check(abs(losses[0] - ln_v) <= 0.5,
          f"first loss {losses[0]:.3f} is not ln(vocab) = {ln_v:.3f} +- 0.5")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    require_on_tpu(last, "the loss array")
    require_device_memory(jax.devices()[:1])
    require_no_recompiles(f"train seq {seq}")
    insight = _train_insight(exe)
    # per 2-D aligned parameter one fused Adam, two CE kernels, and at
    # flash lengths the attention kernels of each layer: forward and ONE
    # fused backward up to seq 1024, forward, dq and dkv past it
    n_adam = sum(1 for p in main.all_parameters()
                 if len(p.shape) == 2 and p.shape[0] % 8 == 0
                 and p.shape[1] % 128 == 0)
    flash_expected = seq >= 1024
    check((flash_dispatches > 0) == flash_expected,
          f"seq {seq}: {flash_dispatches} flash dispatches")
    want_calls = n_adam + 2 + ((2 if seq <= 1024 else 3) * cfg.n_layer if flash_expected else 0)
    n_calls = require_mosaic(_hlo_of(insight), f"train step seq {seq}",
                             at_least=want_calls)
    log(f"train ({batch}, {seq}): loss {losses[0]:.4f} -> {losses[-1]:.4f}, "
        f"first run {walls[0]:.1f}s, {n_calls} tpu_custom_call")
    return {
        "batch": batch, "seq": seq, "steps": steps,
        "loss_first": round(losses[0], 4), "loss_last": round(losses[-1], 4),
        "compile_seconds": round(walls[0] - float(np.median(walls[1:])), 2),
        "tpu_custom_calls": n_calls, "flash_dispatches": flash_dispatches,
        "program_peak_bytes": insight.get("peak_bytes"),
    }


# -- phase 3 -----------------------------------------------------------------


def _serve(router, prompts, new_tokens: int, tag: str, concurrent: bool):
    """Dispatch every prompt through the router; returns the records in
    prompt order. Failures raise: a request the engine swallowed shows
    as a record that is not ok."""
    records = [None] * len(prompts)

    def one(i):
        records[i] = router.dispatch(
            prompts[i], max_new_tokens=new_tokens, deadline_s=600.0,
            request_id=f"{tag}-{i}")

    if concurrent:
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        check(not any(t.is_alive() for t in threads),
              f"{tag}: a request is still pending after 600s")
    else:
        for i in range(len(prompts)):
            one(i)
    for i, rec in enumerate(records):
        check(rec is not None and rec["ok"],
              f"{tag}-{i} failed: {rec and rec.get('error')}")
        check(len(rec["tokens"]) == new_tokens,
              f"{tag}-{i} returned {len(rec['tokens'])} tokens, "
              f"not {new_tokens}")
    return records


def phase_server(prompt_lens=(12, 24, 40, 90, 120, 200, 300, 500),
                 new_tokens: int = 32, max_seq_len: int = 1024,
                 recipe=None, model: dict = GPT2S) -> dict:
    from paddle_tpu import serving
    from paddle_tpu.serving import ledger
    from paddle_tpu.serving.kv_cache import BLOCK_SIZE, blocks_for_tokens

    cfg = serving.GPTConfig(max_seq_len=max_seq_len, dtype="bfloat16",
                            **model)
    # the whole batch resident at its final length, plus scratch block 0
    n_blocks = 1 + sum(blocks_for_tokens(n + new_tokens + 1, BLOCK_SIZE)
                       for n in prompt_lens)
    t0 = time.perf_counter()
    dm = serving.DecodeModel(cfg, recipe=recipe, max_batch=len(prompt_lens),
                             n_blocks=n_blocks, seed=0)
    engine = serving.ServingEngine(dm)
    dm.warm(full=True)  # the replica boot path: every bucket before traffic
    warm_s = time.perf_counter() - t0
    buckets = sorted({dm.bucket_for(n) for n in prompt_lens})
    check(None not in buckets and len(buckets) >= (2 if len(prompt_lens) > 1
                                                   else 1),
          f"prompts {prompt_lens} span buckets {buckets}")
    ledger.reset()
    r = np.random.RandomState(1)
    prompts = [r.randint(0, cfg.vocab_size, (n,)).tolist()
               for n in prompt_lens]
    engine.start()
    router = serving.Router([serving.LocalReplica("chip0", engine)])
    try:
        batched = _serve(router, prompts, new_tokens, "batched", True)
        alone = _serve(router, prompts, new_tokens, "alone", False)
    finally:
        router.stop()
        engine.stop()
    for i, (a, b) in enumerate(zip(batched, alone)):
        check(not b["cached"], f"alone-{i} was an idempotency replay")
        check(a["tokens"] == b["tokens"],
              f"request {i} (prompt {prompt_lens[i]}) decoded differently "
              f"in the batch and alone:\n  {a['tokens']}\n  {b['tokens']}")
    requests = ledger.totals()["requests"]
    not_ok = {k: v for k, v in requests.items() if k != "ok" and v}
    check(requests.get("ok") == 2 * len(prompts) and not not_ok,
          f"serving ledger requests: {requests}")
    require_no_recompiles("serving")
    log(f"server: {len(prompts)} requests x {new_tokens} tokens over "
        f"buckets {buckets}, batched == alone; warm-up {warm_s:.1f}s")
    return {"model": dm, "engine": engine, "facts": {
        "requests": len(prompts), "new_tokens": new_tokens,
        "prefill_buckets": buckets, "kv_blocks": n_blocks,
        "block_size": dm.block_size, "bit_identical_alone": True,
        "compile_seconds": round(warm_s, 2)}}


# -- phase 4 -----------------------------------------------------------------


def phase_recipe(recipe: str, batch: int = 32, seq: int = 512,
                 steps: int = 4, model: dict = GPT2S) -> dict:
    """One recipe through fleet, the call sequence of tools/mesh_bench.py."""
    import jax

    from paddle_tpu.distributed import fleet
    from paddle_tpu.framework import Executor, Scope, program_guard
    from paddle_tpu.framework import shard_insight
    from paddle_tpu.models.gpt import GPTConfig, build_train_program
    from paddle_tpu.optimizer import Adam

    n = jax.device_count()
    cfg = GPTConfig(max_seq_len=seq, dtype="bfloat16", **model)
    main, startup, io = build_train_program(cfg, batch=batch, seq=seq)
    with program_guard(main, startup):
        strat = fleet.DistributedStrategy()
        strat.sharding_recipe = recipe
        fleet.init(is_collective=True, strategy=strat)
        fleet.distributed_optimizer(
            Adam(learning_rate=1e-4)).minimize(io["loss"])
    resolved, mesh = main._sharding_recipe, main._mesh
    check(resolved is not None and resolved.n_devices == n,
          f"recipe {recipe!r} resolved to {resolved}")
    check(set(mesh.devices.flat) == set(jax.devices())
          and mesh.devices.size == n,
          f"{recipe}: program._mesh does not span the {n} devices")

    check(shard_insight.verify_enabled(), "PADDLE_TPU_SHARD_VERIFY is off")
    mismatch0 = counter("sharding_mismatch_total")
    scope, exe = Scope(), Executor()
    exe.run(startup, scope=scope)
    r = np.random.RandomState(0)
    feed = {k: r.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
            for k in ("tokens", "labels")}
    losses, walls, last = _run_steps(exe, main, feed, io["loss"], scope,
                                     steps)
    check(counter("sharding_mismatch_total") == mismatch0,
          f"{recipe}: placement drifted from the recipe's rules")
    require_on_tpu(last, f"{recipe} loss")
    require_no_recompiles(f"recipe {recipe}")

    wte = scope.get("gpt.wte")
    share = wte.addressable_shards[0].data.nbytes / wte.nbytes
    want_share = 1.0 if recipe == "dp" else 1.0 / n
    check(len(wte.sharding.device_set) == n and share == want_share,
          f"{recipe}: gpt.wte shard is {share} of the table on "
          f"{len(wte.sharding.device_set)} devices, want {want_share}")
    used = require_device_memory(jax.devices())

    insight = _train_insight(exe)
    comms = insight.get("collectives") or {}
    by_kind = {k: int(v.get("payload_bytes", 0))
               for k, v in (comms.get("by_kind") or {}).items()}
    params = [(p.name, tuple(int(s) for s in p.shape),
               np.dtype(p.dtype).itemsize) for p in main.all_parameters()]
    plan = resolved.predicted_collectives(
        params, batch=batch, seq=seq, d_model=cfg.d_model,
        n_layer=cfg.n_layer, dtype_bytes=2,  # bf16 activations
        lmhead=str(io["lm_head_impl"]))
    rec = shard_insight.license_kinds(
        shard_insight.reconcile(
            plan["payload_bytes_total"],
            measured_bytes=int(comms.get("payload_bytes_total") or 0)),
        by_kind, plan["planned_kinds"])
    check(rec["verdict"] in ("within_bound", "outside_bound"),
          f"{recipe}: recipe plan vs compiled HLO collectives: {rec}, "
          f"HLO by kind {by_kind}")
    n_calls = require_mosaic(_hlo_of(insight), f"{recipe} train step",
                             at_least=3)
    log(f"recipe {recipe}: loss {losses[0]:.4f} -> {losses[-1]:.4f}, "
        f"wte share {share}, HLO collectives {by_kind}, "
        f"reconciliation {rec['verdict']} (ratio {rec.get('ratio')})")
    return {
        "axes": dict(resolved.axes), "loss_first": round(losses[0], 4),
        "loss_last": round(losses[-1], 4), "wte_shard_share": share,
        "bytes_in_use": used, "hlo_collective_bytes": by_kind,
        "reconciliation": rec["verdict"], "reconciliation_ratio":
        rec.get("ratio"), "tpu_custom_calls": n_calls,
        "compile_seconds": round(walls[0] - float(np.median(walls[1:])), 2),
    }


def phase_four_chips() -> dict:
    import gc

    import jax

    n = jax.device_count()
    facts = {}
    for recipe in ("dp", "fsdp", "tp"):
        facts[recipe] = phase_recipe(recipe)
        gc.collect()  # the previous recipe's state leaves the devices
    first = [facts[r]["loss_first"] for r in ("dp", "fsdp", "tp")]
    check(max(first) - min(first) <= TOL["recipe_loss"],
          f"step-1 loss differs across recipes: {first}")

    served = phase_server(prompt_lens=(20, 60, 150, 400), recipe="tp")
    dm, engine = served["model"], served["engine"]
    for what, arr in (("KV pages", engine.pages),
                      ("gpt.h0.attn.q.w", dm.params["gpt.h0.attn.q.w"])):
        share = arr.addressable_shards[0].data.nbytes / arr.nbytes
        check(len(arr.sharding.device_set) == n and share == 1.0 / n,
              f"tp decode: {what} holds {share} per device on "
              f"{len(arr.sharding.device_set)} devices")
    facts["serve_tp"] = served["facts"]
    return facts


# -- main --------------------------------------------------------------------


def main() -> int:
    # sharding verification armed before paddle_tpu reads its flags; the
    # HLO text of each compiled step is wanted for the Mosaic checks, so
    # xla_insight dumps it (to a throwaway directory unless one is set)
    os.environ.setdefault("PADDLE_TPU_SHARD_VERIFY", "1")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_xla_") as tmp:
        os.environ.setdefault("PADDLE_TPU_XLA_DUMP_DIR", tmp)
        summary = run()
    print(json.dumps(summary))
    # the result line: these keys and no others, last on stdout
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)
    return 0


def run() -> dict:
    import gc

    device = phase_identity()

    import jax

    import paddle_tpu as paddle
    from paddle_tpu import compile_cache
    from paddle_tpu.framework import native

    cache_dir = compile_cache.enable()
    cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"compile cache {cache_dir}: {cached} entries at start; "
        f"native core (csrc): {'built' if native.available() else 'absent, pure-Python fallbacks'}")
    paddle.enable_static()
    phases: dict = {"identity": {"ok": True, "jax": jax.__version__}}

    phases["kernels"] = {"ok": True, **phase_kernels()}
    gc.collect()
    phases["trainer"] = {"ok": True,
                         "seq2048": phase_trainer(2048),
                         "seq512": phase_trainer(512)}
    gc.collect()
    phases["server"] = {"ok": True, **phase_server()["facts"]}
    gc.collect()
    if device["count"] >= 4:
        phases["four_chips"] = {"ok": True, **phase_four_chips()}
    else:
        phases["four_chips"] = {"ok": None, "skipped": "fewer than 4 devices"}
    return {
        "ok": True,
        "device": device,
        "native_core": bool(native.available()),
        "compile_cache": {"dir": cache_dir, "entries_at_start": cached},
        "phases": phases,
        "total_seconds": round(time.perf_counter() - T0, 1),
        "claim": None,
    }


if __name__ == "__main__":
    sys.exit(main())
