"""Traffic of kind ``serve_arch``: the serving runner for any architecture
that has a module under ``benchmark/arch/``.

The clients, the closed loop, the traced slice, the reduction and the
ledger's deltas are serve.py's own functions. What differs is looked up
by the configuration's ``model_type`` (benchmark/arch/<model_type>.py):
the ``serving.GPTConfig`` the engine is built from, the seed-made weights
on the device, and the float32 reference the served tokens are checked
against. The traffic file says ``"loop": "closed"`` (the only loop here
so far); serve.py's reduction is handed the kind it knows.

After the window, outside every timed interval, a seeded sample of the
window's requests is checked three ways at the cell's own widths:

- every served token (prefill, then decode through the paged cache) is
  scored by the reference's ONE full forward over prompt + answer, at its
  own position, within the architecture's ``LOGIT_TOL`` of the
  reference's best logit there (``max_logit_gap``, ``exact_argmax_share``);
- where the architecture routes, the share of (position, layer) top-k
  sets in which the program's own arithmetic (the model's non-paged
  forward, in the serving dtype) chose the reference's experts
  (``routing_agreement_share``);
- the ledger's routing counters of the run (``moe_*``) go to the report.
"""
from __future__ import annotations

import time

import numpy as np

from .. import arch as arch_modules
from .. import traffic_gen
from ..common import CompileCounter, Ctx, log
from . import serve


def _engine(ctx: Ctx, arch):
    from paddle_tpu import serving

    c, e = ctx.cell["config"], ctx.cell["traffic"]["engine"]
    cfg = serving.GPTConfig(**arch.gpt_config(c, e))
    t = time.perf_counter()
    params = arch.make_params(c, ctx.seed, cfg.dtype)
    n_bytes = sum(a.nbytes for a in params.values())
    log(f"weights: {len(params)} arrays, {n_bytes / 1e9:.2f} GB on the device "
        f"({time.perf_counter() - t:.1f}s, dispatch)")
    dm = serving.DecodeModel(cfg, params=params, **arch_modules.engine_args(e))
    engine = serving.ServingEngine(dm)
    dm.warm(full=True)  # every bucket this envelope has, and no other shape
    engine.start()
    router = serving.Router([serving.LocalReplica("chip0", engine)])
    ctx.results["weight_bytes"] = n_bytes
    return params, dm, engine, router


def run(ctx: Ctx) -> None:
    from paddle_tpu.framework import xla_insight
    from paddle_tpu.serving import ledger

    c = ctx.cell["config"]
    arch = arch_modules.of(c)
    # serve.py's loop and reduction read the kind they know
    tr = ctx.cell["traffic"] = dict(ctx.cell["traffic"], kind=f"serve_{ctx.cell['traffic']['loop']}")
    if tr["kind"] != "serve_closed":
        raise SystemExit(f"serve_arch: no loop {tr['loop']!r} yet (closed only)")
    compiles = CompileCounter()
    params, dm, engine, router = _engine(ctx, arch)
    vocab = int(c["vocab_size"])
    clients = serve._Clients(router, float(tr.get("deadline_s", 600.0)))
    try:
        # one request through every prefill bucket and a few decode ticks:
        # the first execution of a loaded program is not a steady one
        rng = traffic_gen.rng_for(ctx.seed, "warm")
        for b in dm.prefill_buckets:
            toks = traffic_gen.draw_tokens(tr["tokens"], rng, (min(b, dm.cfg.max_seq_len - 8),),
                                           vocab).tolist()
            w = clients.send({"prompt": toks, "max_new_tokens": 4}, f"warm-{b}")
            if not w["ok"]:
                raise RuntimeError(f"warm-up request at bucket {b} failed: {w['error']}")
        clients.records.clear()
        ledger.reset()
        ctx.results["setup_s"] = time.perf_counter() - ctx.t0
        log(f"engine warm: buckets {dm.prefill_buckets}, max_batch {dm.max_batch}, "
            f"{dm.n_blocks} KV blocks, window {dm.cfg.max_seq_len}")
        comp0 = compiles.snapshot()
        t_open, t_close, state = serve._closed_loop(ctx, clients, engine, vocab)
        comp = {k: v - comp0[k] for k, v in compiles.snapshot().items()}
    finally:
        router.stop()
        engine.stop()
    serve._reduce(ctx, clients.records, t_open, t_close, state, comp, compiles,
                  xla_insight.failure_counts(), dm)
    doc = ledger.totals()
    ctx.results["routing"] = {k: doc.get(k) for k in ("decode_ticks", *ledger.MOE_COUNTERS)}
    engine.pages = None  # the pool's memory is the reference's to use now
    _check_outputs(ctx, arch, params, dm, clients.records, t_open, t_close)


def _check_outputs(ctx: Ctx, arch, params: dict, dm, records, t_open, t_close) -> None:
    ok = sorted((r for r in records if r["ok"] and t_open <= r["t0"] < t_close),
                key=lambda r: r["rid"])
    if not ok:
        ctx.results["correct"] = False
        return
    rng = traffic_gen.rng_for(ctx.seed, "check")
    picks = [ok[i] for i in rng.choice(len(ok), size=min(arch.N_CHECKED, len(ok)), replace=False)]
    t = time.perf_counter()
    facts = reference_gaps(arch, ctx.cell["config"], params, dm, picks)
    log(f"reference check: {facts['checked_requests']} requests, {facts['checked_tokens']} tokens, "
        f"max gap {facts['max_logit_gap']:.4f}, exact argmax {facts['exact_argmax_share']:.4f}, "
        f"routing sets alike {facts['routing_agreement_share']}, {time.perf_counter() - t:.1f}s")
    ctx.results.update(facts, logit_tol=arch.LOGIT_TOL)
    if facts["max_logit_gap"] > arch.LOGIT_TOL:
        ctx.results["correct"] = False


def reference_gaps(arch, c: dict, params: dict, dm, picks, window: int = 0) -> dict:
    """Served requests ``picks`` (``prompt``, ``tokens``) against the
    float32 reference's one full forward over prompt + answer: how far
    below the reference's best logit each served token lies, and how often
    the program's own arithmetic routes as the reference does."""
    import jax.numpy as jnp

    # every sequence padded to one length, the engine's window unless told
    # (causal: what follows a position does not reach it), so each program
    # below compiles once
    T, P = window or dm.cfg.max_seq_len, max(len(r["tokens"]) for r in picks)
    worst, exact, total, same_sets, sets = 0.0, 0, 0, 0, 0
    for r in picks:
        n_p, n_o = len(r["prompt"]), len(r["tokens"])
        seq = np.zeros((1, T), np.int32)
        seq[0, :n_p + n_o] = list(r["prompt"]) + list(r["tokens"])
        pos = np.minimum(n_p - 1 + np.arange(P), T - 1)[None].astype(np.int32)
        logits, routing = arch.reference_logits(lambda name: params[name], jnp.asarray(seq),
                                                jnp.asarray(pos), c)
        logits = np.asarray(logits)[0, :n_o]
        gaps = logits.max(axis=-1) - logits[np.arange(n_o), r["tokens"]]
        worst = max(worst, float(gaps.max()))
        exact += int((gaps == 0.0).sum())
        total += n_o
        if routing is not None:
            n = n_p + n_o
            ref = np.sort(np.asarray(routing)[0, :n], axis=-1)                    # [n, L, k]
            got = np.sort(dm.full_logits(seq, with_routing=True)[1][:n], axis=-1)
            same_sets += int((ref == got).all(axis=-1).sum())
            sets += ref.shape[0] * ref.shape[1]
    return {"max_logit_gap": worst, "exact_argmax_share": exact / max(total, 1),
            "routing_agreement_share": same_sets / sets if sets else None,
            "checked_requests": len(picks), "checked_tokens": total}
