"""Runner for traffic of kind ``train``: whole training steps through
``build_train_program -> Adam.minimize (or fleet.distributed_optimizer)
-> Executor.run``, fed host batches as a user feeds them.

The window opens after a ``block_until_ready`` and closes on the fetch of
the last step's loss; the number of steps in it is fixed before it opens
(``--seconds`` over the warm-up's step time), so the measured work does
not depend on how far the host runs ahead of the device.
"""
from __future__ import annotations

import time

import numpy as np

from .. import traffic_gen
from ..common import CompileCounter, Ctx, TraceSlice, log
from ..reference import gpt2 as reference

# |program loss - float32 reference loss| on the first step. The program
# computes in bfloat16; the loss is a mean over >= 4,096 tokens of values
# near ln(vocab) ~ 10.8, so random rounding averages out: measured on the
# chip, 3.5e-5 for GPT-2 small and 2.7e-4 for XL under fsdp (PERF.md;
# every run reports its own as ``loss_abs_err``). The tolerance is 4x the
# worst of those and no wider, because at a random start the mean NLL
# sits near ln(vocab) whatever the model does: the float32 reference with
# the last layer skipped moves it by +0.0047 (small, 32 x 1024 tokens)
# and +0.0051 (XL, 4 x 1024), with a causal mask that leaks by -0.0083
# and -0.0194 (CPU arithmetic, PR 23; benchmark/selftest.py repeats it
# at a smaller batch). 1e-2 would pass three of those four.
LOSS_TOL = 1e-3


def _program_counter(name: str, **labels) -> int:
    from paddle_tpu import monitor

    family = monitor.default_registry().get(name)
    return int((family.labels(**labels) if labels else family).value)


def run(ctx: Ctx) -> None:
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.framework import Executor, Scope, program_guard, xla_insight
    from paddle_tpu.models.gpt import GPTConfig, build_train_program
    from paddle_tpu.optimizer import Adam

    c, tr = ctx.cell["config"], ctx.cell["traffic"]
    B, T = int(tr["global_batch"]), int(tr["seq"])
    compiles = CompileCounter()
    paddle.enable_static()
    cfg = GPTConfig(vocab_size=c["vocab_size"], n_layer=c["n_layer"], n_head=c["n_head"],
                    d_model=c["n_embd"], d_ff=c.get("n_inner"), max_seq_len=c["n_positions"],
                    dropout=float(c.get("resid_pdrop", 0.0)), dtype=tr.get("dtype", "bfloat16"))
    main, startup, io = build_train_program(cfg, batch=B, seq=T)
    main.random_seed = startup.random_seed = int(ctx.seed) + 1  # 0 means "unset" to the executor
    recipe = tr.get("recipe")
    lr = float(tr["optimizer"]["lr"])
    with program_guard(main, startup):
        if recipe:
            from paddle_tpu.distributed import fleet

            strat = fleet.DistributedStrategy()
            strat.sharding_recipe = recipe
            fleet.init(is_collective=True, strategy=strat)
            fleet.distributed_optimizer(Adam(learning_rate=lr)).minimize(io["loss"])
        else:
            Adam(learning_rate=lr).minimize(io["loss"])
    if recipe and getattr(main, "_sharding_recipe", None) is None:
        raise RuntimeError(f"recipe {recipe!r} was not applied (fleet saw "
                           f"{jax.device_count()} device(s))")
    if recipe:
        # fleet lays out the MAIN program only: the startup program would
        # create every weight and moment (15.6 GB for GPT-2 XL) on chip 0
        # before the first step reshards them. The benchmark attaches the
        # same recipe to the startup program, so state is born sharded.
        # See PERF.md, Open questions ("fleet: startup program unsharded").
        from paddle_tpu.parallel.recipes import apply_to_program

        apply_to_program(startup, main._sharding_recipe)
    ctx.results["lm_head_impl"] = io["lm_head_impl"]
    scope, exe = Scope(), Executor()
    exe.run(startup, scope=scope)
    names = [p.name for p in main.all_parameters()]
    snapshot = {n: jax.numpy.copy(scope.get(n)) for n in names}
    jax.block_until_ready(snapshot)
    wte = scope.get("gpt.wte")
    ctx.results["wte_shard_share_at_birth"] = wte.addressable_shards[0].data.nbytes / wte.nbytes
    log(f"startup done: {len(names)} parameters on {len(ctx.devices)} device(s), "
        f"wte shard share {ctx.results['wte_shard_share_at_birth']}")

    vocab = int(c.get("published", {}).get("vocab_size", c["vocab_size"]))
    batches = traffic_gen.train_batches(tr, vocab, ctx.seed)
    loss_name = io["loss"]

    def step(i):
        return exe.run(main, feed=batches[i % len(batches)], fetch_list=[loss_name],
                       scope=scope, return_numpy=False)[0]

    # warm-up: the first step compiles (or loads from the cache); the
    # next ones are timed with a sync each to size the window
    first = step(0)
    jax.block_until_ready(first)
    loss_first = float(np.asarray(first))
    log(f"first step done, loss {loss_first:.4f}")
    n_warm = int(tr.get("warmup_steps", 3))
    t = time.perf_counter()
    for i in range(1, 1 + n_warm):
        last = step(i)
    jax.block_until_ready(last)
    step_s = (time.perf_counter() - t) / n_warm
    n_steps = max(4, int(ctx.seconds / step_s))
    ctx.results["setup_s"] = time.perf_counter() - ctx.t0
    log(f"warm step {step_s * 1e3:.1f} ms; window of {n_steps} steps")

    # -- the measured window ------------------------------------------------
    miss0 = _program_counter("executor_cache_lookups_total", result="miss")
    comp0 = compiles.snapshot()
    losses = []
    jax.block_until_ready(last)
    t_open = time.perf_counter()
    for i in range(n_steps):
        with ctx.span("exe_run"):
            losses.append(step(1 + n_warm + i))
    with ctx.span("fetch_loss"):
        loss_last = float(np.asarray(losses[-1]))
    t_close = time.perf_counter()
    window = t_close - t_open
    tokens_per_s = n_steps * B * T / window
    miss = _program_counter("executor_cache_lookups_total", result="miss") - miss0
    comp = {k: v - comp0[k] for k, v in compiles.snapshot().items()}

    # -- the traced slice (own steps, after the window) --------------------
    if ctx.trace:
        n_tr = int(tr.get("trace_steps", 3))
        jax.block_until_ready(losses[-1])
        sl = TraceSlice(ctx)
        sl.start()
        with ctx.span("trace_slice"):
            for i in range(n_tr):
                with jax.profiler.TraceAnnotation("bench/exe_run"):
                    out = step(1 + n_warm + n_steps + i)
            with jax.profiler.TraceAnnotation("bench/fetch_loss"):
                float(np.asarray(out))
        sl.stop()
        ctx.trace_facts["steps"] = n_tr

    # -- correctness, outside the window -----------------------------------
    all_losses = [float(np.asarray(x)) for x in losses]
    finite = bool(np.all(np.isfinite(all_losses)) and np.isfinite(loss_first))
    insight_failures = xla_insight.failure_counts()
    ref = _reference_loss(ctx, snapshot, batches[0], c)
    err = abs(ref - loss_first)
    no_compile = (miss == 0 and comp["compiles"] == 0
                  and not any(insight_failures.values()))
    ctx.results.update({
        "train_tokens_per_s": tokens_per_s,
        "window_s": window, "steps": n_steps, "tokens_per_step": B * T,
        "batch": B, "seq": T, "step_ms": 1e3 * window / n_steps,
        "loss_first": loss_first, "loss_window_first": all_losses[0],
        "loss_window_last": loss_last, "loss_reference": ref, "loss_abs_err": err,
        "loss_tol": LOSS_TOL, "executor_cache_misses_in_window": miss,
        "jax_compiles_in_window": comp["compiles"], "compile_events_total": compiles.snapshot(),
        "insight_failures": insight_failures,
        "program_temp_bytes": max([int(i.get("temp_bytes") or 0)
                                   for i in exe.compiled_insights()] or [0]),
        "attempted": n_steps, "failed": 0 if finite else n_steps,
        "correct": bool(finite and err <= LOSS_TOL and loss_last < all_losses[0]
                        and no_compile),
    })


def _reference_loss(ctx: Ctx, snapshot: dict, batch: dict, c: dict) -> float:
    """Mean NLL of the first batch under the float32 reference on the
    snapshot of the seed-made weights, a few sequences at a time, the
    chunk spread over the cell's chips."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.asarray(ctx.devices), ("b",))
    rep = NamedSharding(mesh, PartitionSpec())
    by_batch = NamedSharding(mesh, PartitionSpec("b"))
    cache: dict = {}

    def get(name):
        # weights gathered whole onto every chip, one array at a time
        if name not in cache:
            if len(cache) > 20:
                cache.clear()
            cache[name] = jax.device_put(snapshot[name], rep)
        return cache[name]

    per_chip = 2 if ctx.rehearse else int(ctx.cell["traffic"].get("reference_chunk_per_chip", 4))
    chunk = per_chip * len(ctx.devices)
    t = time.perf_counter()
    out = reference.mean_nll(
        get, jnp.asarray(batch["tokens"], jnp.int32), jnp.asarray(batch["labels"], jnp.int32),
        n_layer=c["n_layer"], n_head=c["n_head"], eps=c["layer_norm_epsilon"],
        chunk=chunk, place=lambda a: jax.device_put(a, by_batch))
    log(f"reference loss {out:.5f} in {time.perf_counter() - t:.1f}s")
    return out
