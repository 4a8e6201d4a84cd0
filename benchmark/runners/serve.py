"""Runner shared by traffic of kind ``serve_closed`` and ``serve_open``:
requests through ``Router([LocalReplica(engine)]).dispatch`` from client
threads, the entry point a caller of the serving plane uses.

Closed loop: N clients, each sending its own seed-made sequence of
requests, the next one as soon as the last returned. Open loop: a seeded
arrival schedule at a fixed rate; latency runs from the time a request
was DUE, and how late the generator ran is reported.

The window opens in steady state and closes ``--seconds`` later; requests
in flight at the close are drained so their completion times are known.
Nothing due or dispatched after the close is counted.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .. import traffic_gen, weights
from ..common import CompileCounter, Ctx, TraceSlice, log, percentile
from ..reference import gpt2 as reference

# A greedy token is accepted when the float32 reference, teacher-forced
# on the same prefix, scores it within LOGIT_TOL of its own best token.
# Reason: the program computes in bfloat16; with seed-made N(0, 0.02)
# weights the best two of 50k logits are often closer than bf16 can
# resolve after 48 layers, so exact argmax equality is not a property of
# a correct bf16 implementation (on the chip 98.5% of ~950 tokens a run
# were the reference's argmax and the largest gap was 0.035; each run
# reports its own as ``max_logit_gap``). 0.15 is four times that. A wrong
# cache block, mask or position picks a token 2 to 4 below the best.
LOGIT_TOL = 0.15
N_CHECKED = 4


def _engine(ctx: Ctx):
    from paddle_tpu import serving

    c, e = ctx.cell["config"], ctx.cell["traffic"]["engine"]
    cfg = serving.GPTConfig(vocab_size=c["vocab_size"], n_layer=c["n_layer"],
                            n_head=c["n_head"], d_model=c["n_embd"], d_ff=c.get("n_inner"),
                            max_seq_len=c["n_positions"], dtype=e.get("dtype", "bfloat16"))
    table = weights.param_table(c["n_layer"], c["n_embd"], cfg.ffn_dim, c["vocab_size"],
                                c["n_positions"])
    params = weights.make_params(table, ctx.seed, cfg.dtype)
    dm = serving.DecodeModel(cfg, params=params, max_batch=int(e["max_batch"]),
                             n_blocks=int(e["n_blocks"]), block_size=int(e["block_size"]),
                             prefill_buckets=[int(b) for b in e["prefill_buckets"]])
    engine = serving.ServingEngine(dm)
    dm.warm(full=True)  # every bucket this envelope has, and no other shape
    engine.start()
    router = serving.Router([serving.LocalReplica("chip0", engine)])
    return params, dm, engine, router


class _Clients:
    """Sends requests and keeps one record per request, on one clock."""

    def __init__(self, router, deadline_s: float):
        self.router, self.deadline_s = router, deadline_s
        self.records: list = []
        self._lock = threading.Lock()

    def send(self, req: dict, rid: str, due: float | None = None) -> dict:
        t0 = time.perf_counter()
        rec = self.router.dispatch(req["prompt"], max_new_tokens=req["max_new_tokens"],
                                   deadline_s=self.deadline_s, request_id=rid)
        t1 = time.perf_counter()
        toks = rec.get("tokens") or []
        att = rec.get("attribution") or {}
        engine_e2e = sum(att.get(k, 0.0) for k in
                         ("admission_queue", "prefill_compute", "decode_compute",
                          "postprocess", "batch_wait"))
        out = {"rid": rid, "due": due if due is not None else t0, "t0": t0, "t1": t1,
               "ok": bool(rec.get("ok")) and len(toks) == req["max_new_tokens"],
               "n_out": len(toks), "want": req["max_new_tokens"], "tokens": toks,
               "prompt": req["prompt"], "engine_e2e": engine_e2e,
               "engine_ttft": att.get("admission_queue", 0.0) + att.get("prefill_compute", 0.0),
               "error": rec.get("error")}
        with self._lock:
            self.records.append(out)
        return out


def _ledger_state() -> dict:
    from paddle_tpu.serving import ledger

    doc = ledger.totals()
    return {"decode_tokens": doc["decode_tokens"], "ticks": doc["ticks"],
            "decode_compute_s": doc["buckets"]["decode_compute"],
            "prefill_compute_s": doc["buckets"]["prefill_compute"],
            "occupancy_weight": doc["occupancy_weight"], "kv_util_weight": doc["kv_util_weight"],
            "weighted_wall": doc["weighted_wall"], "wall_seconds": doc["wall_seconds"],
            "requests_ok": doc["requests"].get("ok", 0),
            "requests_failed": sum(v for k, v in doc["requests"].items() if k != "ok")}


def run(ctx: Ctx) -> None:
    from paddle_tpu.framework import xla_insight
    from paddle_tpu.serving import ledger

    tr, c = ctx.cell["traffic"], ctx.cell["config"]
    compiles = CompileCounter()
    params, dm, engine, router = _engine(ctx)
    vocab = int(c.get("published", {}).get("vocab_size", c["vocab_size"]))
    clients = _Clients(router, float(tr.get("deadline_s", 600.0)))
    try:
        # one request through every prefill bucket and a few decode ticks:
        # the first execution of a loaded program is not a steady one
        rng = traffic_gen.rng_for(ctx.seed, "warm")
        for b in dm.prefill_buckets:
            n = min(b, c["n_positions"] - 8)
            toks = traffic_gen.draw_tokens(tr["tokens"], rng, (n,), vocab).tolist()
            w = clients.send({"prompt": toks, "max_new_tokens": 4}, f"warm-{b}")
            if not w["ok"]:
                raise RuntimeError(f"warm-up request at bucket {b} failed: {w['error']}")
        clients.records.clear()
        ledger.reset()
        ctx.results["setup_s"] = time.perf_counter() - ctx.t0
        log(f"engine warm: buckets {dm.prefill_buckets}, max_batch {dm.max_batch}, "
            f"{dm.n_blocks} KV blocks")
        comp0 = compiles.snapshot()
        if tr["kind"] == "serve_closed":
            t_open, t_close, state = _closed_loop(ctx, clients, engine, vocab)
        else:
            t_open, t_close, state = _open_loop(ctx, clients, vocab)
        comp = {k: v - comp0[k] for k, v in compiles.snapshot().items()}
    finally:
        router.stop()
        engine.stop()
    _reduce(ctx, clients.records, t_open, t_close, state, comp, compiles,
            xla_insight.failure_counts(), dm)
    _check_outputs(ctx, params, clients.records, t_open, t_close)


def _maybe_trace(ctx: Ctx, t_open: float) -> None:
    """In a traced run, profile a short slice in the middle of the window."""
    if not ctx.trace:
        return
    secs = float(ctx.cell["traffic"].get("trace_seconds", 2.0))
    time.sleep(max(0.0, t_open + 0.4 * ctx.seconds - time.perf_counter()))
    sl = TraceSlice(ctx)
    sl.start()
    time.sleep(secs)
    sl.stop()


def _closed_loop(ctx: Ctx, clients: _Clients, engine, vocab: int):
    tr = ctx.cell["traffic"]
    n = int(tr["clients"])
    # more requests per client than any window can use
    per_client = int(tr.get("requests_per_client", 64))
    plans = traffic_gen.closed_loop_plan(tr, vocab, ctx.seed, per_client)
    stop = threading.Event()

    def client(i: int):
        for k, req in enumerate(plans[i]):
            if stop.is_set():
                return
            clients.send(req, f"c{i}-{k}")

    threads = [threading.Thread(target=client, args=(i,), name=f"bench-client-{i}")
               for i in range(n)]
    t_first = time.perf_counter()
    for t in threads:
        t.start()
    # steady state: every client has a request in a decode slot
    while len(engine.active()) < min(n, engine.max_batch):
        if time.perf_counter() - t_first > 60:
            raise RuntimeError("closed loop never filled the batch")
        time.sleep(0.005)
    state0 = _ledger_state()
    t_open = time.perf_counter()
    log(f"window open {t_open - t_first:.2f}s after the first dispatch")
    _maybe_trace(ctx, t_open)
    time.sleep(max(0.0, t_open + ctx.seconds - time.perf_counter()))
    state1 = _ledger_state()
    t_close = time.perf_counter()
    stop.set()
    for t in threads:
        t.join(timeout=300)
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a client is still waiting 300 s after the close")
    log(f"drained {time.perf_counter() - t_close:.2f}s after the close")
    return t_open, t_close, (state0, state1)


def _open_loop(ctx: Ctx, clients: _Clients, vocab: int, rid_prefix: str = "o"):
    tr = ctx.cell["traffic"]
    lead = float(tr["lead_in_s"])
    plan = traffic_gen.open_loop_plan(tr, vocab, ctx.seed, lead + ctx.seconds)
    pool = ThreadPoolExecutor(max_workers=int(tr.get("client_threads", 96)),
                              thread_name_prefix="bench-client")
    futures = []
    state = {}

    def feeder():
        opened = False
        for k, req in enumerate(plan):
            due = t_start + req["due_s"]
            if not opened and req["due_s"] >= lead:
                state[0] = _ledger_state()
                opened = True
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            futures.append(pool.submit(clients.send, req, f"{rid_prefix}{k}", due))
        if not opened:
            state[0] = _ledger_state()

    t_start = time.perf_counter()
    t_open, t_close = t_start + lead, t_start + lead + ctx.seconds
    th = threading.Thread(target=feeder, name="bench-feeder")
    th.start()
    _maybe_trace(ctx, t_open)
    th.join()
    time.sleep(max(0.0, t_close - time.perf_counter()))
    state[1] = _ledger_state()
    for f in futures:
        f.result(timeout=300)
    pool.shutdown(wait=True)
    log(f"drained {time.perf_counter() - t_close:.2f}s after the close")
    return t_open, t_close, (state[0], state[1])


def _reduce(ctx, records, t_open, t_close, state, comp, compiles, insight_failures, dm):
    tr = ctx.cell["traffic"]
    window = t_close - t_open
    closed = tr["kind"] == "serve_closed"
    if closed:  # dispatched before the close, and some part of it inside the window
        mine = [r for r in records if r["t0"] < t_close and r["t1"] > t_open]
    else:       # due inside the window
        mine = [r for r in records if t_open <= r["due"] < t_close]
    ok = [r for r in mine if r["ok"]]
    failed = len(mine) - len(ok)
    res = ctx.results

    # tokens credited to the window by the share of [dispatch, completion] inside it
    def share(r):
        return max(0.0, min(r["t1"], t_close) - max(r["t0"], t_open)) / max(r["t1"] - r["t0"], 1e-9)

    all_ok = [r for r in records if r["ok"] and r["t1"] > t_open and r["t0"] < t_close]
    def decode_share(r):
        # for the cross-check only: the first token comes out of the prefill
        # (engine clock: queue + prefill after dispatch), the rest one a tick
        a = min(r["t0"] + r["engine_ttft"], r["t1"])
        return max(0.0, min(r["t1"], t_close) - max(a, t_open)) / max(r["t1"] - a, 1e-9)

    credited = sum(r["n_out"] * share(r) for r in all_ok)
    credited_decode = sum((r["n_out"] - 1) * decode_share(r) for r in all_ok)
    d = {k: state[1][k] - state[0][k] for k in state[0]}
    res["serve_tokens_per_s"] = credited / window
    res["ledger_decode_tokens"] = d["decode_tokens"]
    res["client_decode_tokens"] = credited_decode
    tok_gap = abs(credited_decode - d["decode_tokens"]) / max(d["decode_tokens"], 1)
    res["token_count_gap"] = tok_gap
    if ok:
        norm = [1e3 * (r["t1"] - r["due"]) / r["n_out"] for r in ok]
        res["norm_latency_p50_ms"] = percentile(norm, 50)
        res["norm_latency_p95_ms"] = percentile(norm, 95)
        res["latency_p50_s"] = percentile([r["t1"] - r["due"] for r in ok], 50)
        res["lateness_p99_ms"] = percentile([1e3 * (r["t0"] - r["due"]) for r in ok], 99)
        res["router_overhead_ms"] = percentile(
            [1e3 * ((r["t1"] - r["t0"]) - r["engine_e2e"]) for r in ok], 50)
        res["engine_ttft_p50_ms"] = percentile([1e3 * r["engine_ttft"] for r in ok], 50)
    log(f"lateness p99 {res.get('lateness_p99_ms', float('nan')):.2f} ms over {len(ok)} requests")
    res.update({
        "window_s": window, "attempted": len(mine), "failed": failed,
        "completed": len(ok), "output_tokens_credited": credited,
        "jax_compiles_in_window": comp["compiles"],
        "compile_events_total": compiles.snapshot(), "insight_failures": insight_failures,
        "max_batch": dm.max_batch, "n_blocks": dm.n_blocks,
        "program_temp_bytes": max([int(getattr(i, "temp_bytes", 0) or 0)
                                   for i in dm.insights.values()] or [0]),
        "errors": sorted({str(r["error"])[:120] for r in mine if not r["ok"]})[:3],
    })
    ctx.counters.update({f"ledger.{k}": v for k, v in d.items()})
    no_compile = comp["compiles"] == 0 and not any(insight_failures.values())
    # the count check needs whole requests on both sides of the edges
    counts_agree = tok_gap <= 0.02 or not closed
    res["correct"] = bool(no_compile and counts_agree and len(ok) > 0)


def _check_outputs(ctx: Ctx, params: dict, records, t_open, t_close) -> None:
    """Teacher-forced float32 reference over a seeded sample of the
    window's requests: every greedy token must be within LOGIT_TOL of the
    reference's best logit at its position."""
    import jax.numpy as jnp

    c = ctx.cell["config"]
    ok = sorted((r for r in records if r["ok"] and t_open <= r["t0"] < t_close),
                key=lambda r: r["rid"])
    if not ok:
        ctx.results["correct"] = False
        return
    rng = traffic_gen.rng_for(ctx.seed, "check")
    picks = [ok[i] for i in rng.choice(len(ok), size=min(N_CHECKED, len(ok)), replace=False)]
    T = int(c["n_positions"])
    P = max(r["n_out"] for r in picks)
    worst, exact, total = 0.0, 0, 0
    t = time.perf_counter()
    for r in picks:
        n_p, n_o = len(r["prompt"]), r["n_out"]
        seq = np.zeros((1, T), np.int32)
        full = (r["prompt"] + r["tokens"])[:T]
        seq[0, :len(full)] = full
        pos = np.minimum(n_p - 1 + np.arange(P), T - 1)[None].astype(np.int32)
        logits = np.asarray(reference.logits_at(
            lambda name: params[name], jnp.asarray(seq), jnp.asarray(pos),
            n_layer=c["n_layer"], n_head=c["n_head"], eps=c["layer_norm_epsilon"]))[0]
        for j in range(n_o):
            row = logits[j]
            gap = float(row.max() - row[r["tokens"][j]])
            worst = max(worst, gap)
            exact += int(gap == 0.0)
            total += 1
    log(f"reference check: {len(picks)} requests, {total} tokens, max gap {worst:.4f}, "
        f"exact argmax {exact}/{total}, {time.perf_counter() - t:.1f}s")
    ctx.results.update({"max_logit_gap": worst, "logit_tol": LOGIT_TOL,
                        "exact_argmax_share": exact / max(total, 1),
                        "checked_requests": len(picks), "checked_tokens": total})
    if worst > LOGIT_TOL:
        ctx.results["correct"] = False
