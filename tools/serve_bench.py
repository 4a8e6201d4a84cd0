"""Serving bench: synthetic heavy traffic -> the SERVE_r*.json surface.

The serving counterpart of bench.py/mesh_bench.py: drive the
continuous-batching engine (paddle_tpu/serving) with Poisson arrivals
and mixed prompt/output lengths, and record the numbers the serving
plane is gated on:

  tokens_per_sec      decode tokens / engine wall (the headline rate)
  ttft_s              mean time-to-first-token; p50/p99 alongside
  p50_latency_s,
  p99_latency_s       whole-request latency percentiles
  batch_occupancy     wall-weighted active slots / max_batch
  kv_block_utilization
  goodput             the serving ledger bucket breakdown — buckets sum
                      to wall by construction, and the bench ASSERTS it
  reconciliations     span-vs-wall (per-request spans vs engine
                      slot-seconds) and measured-vs-roofline (AOT cost
                      analysis + calibration), both with verdicts

`tools/perf_gate.py --pattern 'SERVE_r*.json'` gates the trajectory:
tokens_per_sec higher-is-better, p99_latency_s/ttft_s lower-is-better —
and, for chaos rounds, availability higher-is-better with
error_rate/recovery_seconds lower-is-better.

The multi-replica legs (--chaos, --multi, --autoscale) are CPU
control-flow checks, not benchmark cells: every replica is a child
process pinned to CPU and never uses the chip, so nothing they record is
a device metric (chip_smoke.py phase 3 is the on-chip serving path).

**Chaos mode (--chaos)** is the serving counterpart of
tools/chaos_bench.py: the bench spawns >=2 REAL replica processes (each
a `--replica` worker: DecodeModel warm-loaded from a shared params .npz,
engine + /generate endpoint over paddle_tpu/status.py, serving journal
per replica), drives Poisson load through the serving router
(paddle_tpu/serving/router.py: least-loaded dispatch, retry with
backoff+jitter, optional hedging), and arms the seed-deterministic
``replica_kill@tick=<K>:rank=<R>`` chaos site so one replica dies
mid-traffic with its in-flight requests and KV state. The supervisor
warm-restarts the victim (params reload + journal resume), the router's
health prober re-admits it, and the round records what the fault plane
is gated on:

  availability        fraction of requests completing within their SLO
  error_rate          fraction of requests that failed outright
  detection_seconds   kill -> router marks the replica dead (typed)
  recovery_seconds    kill -> the respawned replica healthy + serving
  redispatch bit-match   every re-dispatched request replayed post-run
                      must produce bit-identical greedy tokens
  p99 dip             client-side p99 inside the failover window vs
                      steady state

Usage:
  python tools/serve_bench.py --out SERVE_new.json         # full bench
  python tools/serve_bench.py --requests 24 --rate 40 --seed 7
  python tools/serve_bench.py --recipe tp                  # sharded decode
  python tools/serve_bench.py --self-test                  # CI smoke
  python tools/serve_bench.py --chaos --out SERVE_new.json # chaos round
  python tools/serve_bench.py --multi --out SERVE_new.json # steady
      # >=2-replica observability round: cross-process tracing on, one
      # forced retry + one forced hedge, per-request attribution and
      # traffic telemetry merged from the router + replica journals
  python tools/serve_bench.py --chaos --self-test          # in-process
      # CI smoke: availability/error-rate math, the chaos record's
      # verdict logic, router retry over an armed admit_error site, and
      # perf_gate catching an injected availability drop
  python tools/serve_bench.py --autoscale --out SERVE_new.json
      # autoscale round: the capacity planner live over real replica
      # processes under a quiet -> burst -> quiet trace — one
      # warm-restart scale-up, one drain-first scale-down, judged on
      # per-class SLO attainment and scale_regret vs the post-hoc
      # oracle schedule
  python tools/serve_bench.py --autoscale --self-test      # in-process
      # CI smoke: forecast/oracle/regret math pinned, the Autoscaler
      # over drainable stubs (drain ALWAYS precedes take-down), and
      # perf_gate catching injected attainment/regret regressions

Methodology notes: arrivals are a seeded Poisson process (exponential
inter-arrival gaps at --rate req/s), prompt lengths draw uniformly from
--prompt-lens and output budgets from --output-lens — the mixed-length
traffic continuous batching exists for. The engine runs its real
scheduler thread; the bench thread only submits and waits, so
queue_wait/batch_gap are measured, not simulated. In chaos mode the
replicas are separate PROCESSES and the router talks real HTTP — the
failure surface is the one production has.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

SCHEMA = "paddle_tpu.serve_bench/1"

# typed client-side failure classes: anything else in an attempt record
# means an untyped (and therefore unexplained) failure — the chaos
# verdict refuses it
TYPED_FAILURES = ("UnavailableError", "ExecutionTimeoutError")


def run_bench(n_layer: int = 2, d_model: int = 64, n_head: int = 4,
              vocab: int = 512, max_seq_len: int = 128,
              max_batch: int = 8, kv_blocks: int = 96, block_size: int = 16,
              prefill_buckets: str = "16,32,64",
              requests: int = 32, rate: float = 30.0,
              prompt_lens: str = "4,8,12,24", output_lens: str = "4,8,16",
              slo_s: float = 30.0, recipe: Optional[str] = None,
              seed: int = 0, threaded: bool = True,
              verbose: bool = True) -> Dict[str, Any]:
    """One bench round. Returns the parsed result dict (the `parsed`
    payload of a SERVE_r*.json)."""
    import numpy as np

    from paddle_tpu import serving
    from paddle_tpu.serving import ledger
    from paddle_tpu.serving.model import calibrate

    t_setup = time.perf_counter()
    cfg = serving.GPTConfig(vocab_size=vocab, n_layer=n_layer,
                            n_head=n_head, d_model=d_model,
                            max_seq_len=max_seq_len)
    resolved = None
    if recipe:
        import jax

        from paddle_tpu.parallel.recipes import resolve_recipe

        resolved = resolve_recipe(recipe, min(jax.device_count(), 2)
                                  if recipe == "tp" else jax.device_count())
    model = serving.DecodeModel(
        cfg, max_batch=max_batch, n_blocks=kv_blocks,
        block_size=block_size,
        prefill_buckets=[int(x) for x in prefill_buckets.split(",")],
        recipe=resolved, seed=seed)
    ledger.reset()
    engine = serving.ServingEngine(model, default_slo_s=slo_s)
    # compile ahead of traffic: first-request latency must measure the
    # serving plane, not XLA (the compile seconds still land in the
    # xla_insight program records)
    model.warm()
    calib = calibrate()
    setup_s = time.perf_counter() - t_setup

    r = np.random.RandomState(seed)
    plens = [int(x) for x in prompt_lens.split(",")]
    olens = [int(x) for x in output_lens.split(",")]
    schedule = []
    t = 0.0
    for i in range(requests):
        t += float(r.exponential(1.0 / rate))
        schedule.append((t, int(r.choice(plens)), int(r.choice(olens))))

    if threaded:
        engine.start()
    handles = []
    bench_t0 = time.perf_counter()
    for arrive, plen, olen in schedule:
        now = time.perf_counter() - bench_t0
        if arrive > now:
            time.sleep(arrive - now)
        prompt = r.randint(1, vocab, size=plen).tolist()
        handles.append(engine.submit(prompt, max_new_tokens=olen))
    if not threaded:
        engine.run_until_idle()
    results = [h.result(timeout=300) for h in handles]
    wall = time.perf_counter() - bench_t0
    if threaded:
        engine.stop(flush=False)

    doc = ledger.totals()
    slo = ledger.slo_summary(doc)
    bucket_sum = sum(doc["buckets"].values())
    # the ledger's contract: closed buckets sum to the engine wall
    assert abs(bucket_sum - doc["wall_seconds"]) < 1e-6 * max(
        1.0, bucket_sum), (bucket_sum, doc["wall_seconds"])

    mean_active = (doc["batch_occupancy"] or 0.0) * max_batch
    roofline = model.decode_roofline(mean_active=max(mean_active, 1e-3),
                                     calibration=calib)
    ledger.set_roofline(roofline)
    doc = ledger.totals()
    span_rec = ledger.reconcile_spans(doc)
    roof_rec = ledger.reconcile_roofline(doc)
    # per-request latency attribution: typed buckets summing to each
    # request's measured e2e by construction, plus the reconciliation
    # the SERVE gate bounds (attribution_residual, lower-is-better)
    attr_summary = ledger.attribution_summary(doc)
    attr_rec = ledger.reconcile_attribution(doc)

    parsed: Dict[str, Any] = {
        "metric": "serve_tokens_per_sec",
        "unit": "decode tokens/s (continuous batching, greedy)",
        "model": {"n_layer": n_layer, "d_model": d_model,
                  "n_head": n_head, "vocab_size": vocab,
                  "max_seq_len": max_seq_len},
        "engine": {"max_batch": max_batch, "kv_blocks": kv_blocks,
                   "block_size": block_size,
                   "prefill_buckets": prefill_buckets,
                   "recipe": (resolved.to_dict() if resolved is not None
                              else None),
                   "sharding_mismatches": len(model.sharding_mismatches)},
        "traffic": {"requests": requests, "rate_per_sec": rate,
                    "prompt_lens": plens, "output_lens": olens,
                    "seed": seed, "threaded": threaded},
        "setup_seconds": round(setup_s, 3),
        "bench_wall_seconds": round(wall, 4),
        "engine_wall_seconds": round(doc["wall_seconds"], 4),
        "tokens_per_sec": round(doc["tokens_per_sec"] or 0.0, 2),
        "decode_tokens": doc["decode_tokens"],
        "prompt_tokens": doc["prompt_tokens"],
        "requests_ok": doc["requests"].get("ok", 0),
        "requests_failed": doc["requests"].get("failed", 0),
        "requests_evicted": doc["requests"].get("evicted", 0),
        "ttft_s": slo["ttft"]["avg"],
        "p50_ttft_s": slo["ttft"]["p50"],
        "p99_ttft_s": slo["ttft"]["p99"],
        "p50_latency_s": slo["latency"]["p50"],
        "p99_latency_s": slo["latency"]["p99"],
        "batch_occupancy": round(doc["batch_occupancy"] or 0.0, 4),
        "kv_block_utilization": round(doc["kv_block_utilization"] or 0.0,
                                      4),
        "goodput": {
            "buckets": {b: round(v, 6)
                        for b, v in doc["buckets"].items()},
            "buckets_sum_seconds": round(bucket_sum, 6),
            "goodput_fraction": doc["goodput_fraction"],
            "top_badput": ledger.top_badput(doc),
        },
        "reconciliations": {
            "span_vs_wall": span_rec,
            "measured_vs_roofline": roof_rec,
        },
        "attribution": {
            "summary": attr_summary,
            "reconciliation": attr_rec,
        },
        # the gated headline: median |sum(buckets) - e2e| / e2e
        "attribution_residual": attr_rec.get("residual_p50"),
        "n_output_tokens": sum(len(t) for t in results),
    }
    if verbose:
        print(ledger.render_summary({**doc,
                                     "top_badput": ledger.top_badput(doc),
                                     "slo": slo}, title="serve_bench"))
        for name, rec in parsed["reconciliations"].items():
            print(f"  reconcile[{name}]: {rec.get('verdict')} "
                  f"(ratio {rec.get('ratio')}, bound "
                  f"x{rec.get('bound_factor')})")
        print(f"  reconcile[attribution]: {attr_rec.get('verdict')} "
              f"(residual p50 {attr_rec.get('residual_p50')}, p99 "
              f"{attr_rec.get('residual_p99')}, bound "
              f"{attr_rec.get('bound')})")
    return parsed


# ---------------------------------------------------------------------------
# chaos mode: replica worker
# ---------------------------------------------------------------------------


def _percentile(values: List[float], q: float) -> Optional[float]:
    if not values:
        return None
    xs = sorted(values)
    idx = min(len(xs) - 1, max(0, int(round(q * (len(xs) - 1)))))
    return xs[idx]


def _free_port() -> int:
    from paddle_tpu.status import free_port

    return free_port()


def _env_truthy(name: str) -> bool:
    return str(os.environ.get(name, "")).strip().lower() \
        in ("1", "true", "yes", "on")


def replica_main(args) -> int:
    """One serving replica process (`--replica`, supervisor-spawned):
    warm boot — params from the shared PADDLE_TPU_SERVE_PARAMS .npz
    (identical across replicas: the bit-match contract's ground), decode
    + smallest prefill bucket compiled, the decode roofline installed on
    the ledger (which also seeds admission shedding's cold-start service
    estimate) — then the engine is registered behind the status server's
    /generate endpoint and the process serves until SIGTERM. The serving
    journal (PADDLE_TPU_SERVE_DIR) resumes across respawns."""
    import numpy as np

    from paddle_tpu import compile_cache
    from paddle_tpu import flags as _flags
    from paddle_tpu import serving
    from paddle_tpu.serving import ledger
    from paddle_tpu.serving.model import calibrate, init_params

    # warm restart's compile half: the persistent cache (the outer
    # JAX_COMPILATION_CACHE_DIR, else the checkout's fixed .jax_cache)
    # turns a respawned replica's program builds into disk hits
    compile_cache.enable()
    t0 = time.perf_counter()
    cfg = serving.GPTConfig(vocab_size=args.vocab, n_layer=args.n_layer,
                            n_head=args.n_head, d_model=args.d_model,
                            max_seq_len=args.max_seq_len)
    params_path = str(_flags.env_flag("PADDLE_TPU_SERVE_PARAMS"))
    if params_path and os.path.exists(params_path):
        with np.load(params_path) as z:
            params = {k: np.asarray(z[k]) for k in z.files}
        source = "npz"
    else:
        params = init_params(cfg, seed=args.seed)
        source = "init"
    model = serving.DecodeModel(
        cfg, params=params, max_batch=args.max_batch,
        n_blocks=args.kv_blocks, block_size=args.block_size,
        prefill_buckets=[int(x) for x in args.prefill_buckets.split(",")])
    engine = serving.ServingEngine(model, default_slo_s=args.slo_s)
    # full warm: every bucket compiled before READY (a respawn pays the
    # XLA persistent-cache hit, not fresh compiles — the warm restart)
    model.warm(full=True)
    # the roofline seeds admission shedding's cold-start estimate; a
    # respawned replica reuses the first boot's calibration instead of
    # re-probing the backend
    roof_path = (params_path + ".roofline.json") if params_path else ""
    roof = None
    if roof_path and os.path.exists(roof_path):
        try:
            with open(roof_path) as f:
                roof = json.load(f)
        except (OSError, ValueError):
            roof = None
    if roof is None:
        roof = model.decode_roofline(mean_active=1.0,
                                     calibration=calibrate())
        if roof_path and roof:
            from paddle_tpu import monitor as _monitor

            _monitor.atomic_write_text(roof_path, json.dumps(roof))
    ledger.set_roofline(roof)
    serving.set_replica_engine(engine)
    engine.start()

    from paddle_tpu import status as _status

    if _status.server_port() is None:
        print("REPLICA_ERROR status port did not bind", flush=True)
        return 2

    def _term(signum, frame):
        try:
            engine.stop(flush=True)
            # os._exit skips atexit: a trace-enabled replica must flush
            # its span buffer here or the merged --serve timeline loses
            # this process's lifecycle legs
            from paddle_tpu import profiler as _profiler

            if _profiler.is_profiler_enabled():
                _profiler.flush_trace()
        finally:
            os._exit(0)

    signal.signal(signal.SIGTERM, _term)

    doc = ledger.totals()
    print("READY " + json.dumps({
        "rank": args.rank,
        "port": _status.server_port(),
        "pid": os.getpid(),
        "params_source": source,
        "boot_seconds": round(time.perf_counter() - t0, 3),
        "resumed_from_journal": bool(doc.get("resumed_from_journal")),
        "attempt": doc.get("attempt"),
        "time_unix": time.time(),
    }), flush=True)
    while True:  # the engine thread serves; SIGTERM is the exit
        time.sleep(0.5)


# ---------------------------------------------------------------------------
# chaos mode: supervisor
# ---------------------------------------------------------------------------


def availability_summary(records: List[Dict[str, Any]]
                         ) -> Dict[str, Any]:
    """The availability/error-rate math over router dispatch records —
    one pure function so the self-test can pin it without processes.

    availability = completed within their own SLO deadline / total;
    error_rate = failed outright / total; typed_failures requires every
    failed attempt to carry a typed error class; no_hang requires no
    attempt to have out-waited its deadline window."""
    total = len(records)
    ok_in_slo = sum(1 for r in records
                    if r.get("ok") and r.get("within_deadline"))
    failed = sum(1 for r in records if not r.get("ok"))
    late = total - failed - ok_in_slo
    failed_attempts = [a for r in records
                       for a in r.get("attempts", ())
                       if not a.get("ok")]
    typed = all(a.get("error_type") in TYPED_FAILURES
                for a in failed_attempts)
    no_hang = all(a.get("reason") != "hang" for a in failed_attempts)
    lat = [float(r["latency_s"]) for r in records
           if r.get("latency_s") is not None]
    return {
        "requests": total,
        "ok_within_slo": ok_in_slo,
        "late": late,
        "failed": failed,
        "availability": (ok_in_slo / total) if total else None,
        "error_rate": (failed / total) if total else None,
        "typed_failures": bool(typed),
        "no_hang": bool(no_hang),
        "failure_reasons": sorted({str(a.get("reason"))
                                   for a in failed_attempts}),
        "client_p50_latency_s": _percentile(lat, 0.50),
        "client_p99_latency_s": _percentile(lat, 0.99),
        "redispatched": sum(1 for r in records
                            if r.get("n_attempts", 1) > 1
                            or r.get("hedged")),
        "failovers": sum(1 for r in records if r.get("failover")),
        "hedged": sum(1 for r in records if r.get("hedged")),
    }


def failover_window_latency(records: List[Dict[str, Any]],
                            t_kill: Optional[float],
                            t_recovered: Optional[float]
                            ) -> Dict[str, Any]:
    """The p99 dip: client latency p99 for requests submitted inside the
    [kill, recovered] window vs the steady-state rest."""
    if t_kill is None:
        return {"available": False}
    hi = t_recovered if t_recovered is not None else float("inf")
    inside = [float(r["latency_s"]) for r in records
              if t_kill <= float(r.get("time_unix") or 0) <= hi]
    outside = [float(r["latency_s"]) for r in records
               if not (t_kill <= float(r.get("time_unix") or 0) <= hi)]
    p99_in = _percentile(inside, 0.99)
    p99_out = _percentile(outside, 0.99)
    return {
        "available": True,
        "n_in_window": len(inside),
        "p99_failover_s": p99_in,
        "p99_steady_s": p99_out,
        "p99_dip_ratio": (round(p99_in / p99_out, 4)
                          if p99_in and p99_out else None),
    }


def build_chaos_record(**kw) -> Dict[str, Any]:
    """Assemble + judge one serving-chaos record (factored out so
    --chaos --self-test exercises the verdict without processes). ``ok``
    requires: the armed kill exit code, typed failure detection with no
    hang, a warm respawn that REJOINED the router's healthy set, at
    least one request actually re-dispatched (a kill nobody felt proves
    nothing), every bit-match comparison equal, availability at or above
    the floor, and a measured recovery time."""
    doc = dict(kw)
    bit = kw.get("redispatch_bit_match") or {}
    floor = float(kw.get("availability_floor", 0.95))
    doc["ok"] = bool(
        kw.get("killed_exit_code") == kw.get("kill_exit_expected")
        and kw.get("typed_failures")
        and kw.get("no_hang")
        and kw.get("respawned")
        and kw.get("rejoined")
        and (kw.get("requests_redispatched") or 0) >= 1
        and bit.get("checked", 0) >= 1
        and bit.get("checked", 0) == bit.get("matched", -1)
        and kw.get("availability") is not None
        and kw.get("availability") >= floor
        and kw.get("recovery_seconds") is not None)
    return doc


REQUIRED_CHAOS_KEYS = (
    "replicas", "victim_rank", "kill_tick", "killed_exit_code",
    "availability", "error_rate", "detection_seconds", "recovery_seconds",
    "typed_failures", "no_hang", "respawned", "rejoined",
    "requests_redispatched", "redispatch_bit_match", "p99_dip", "ok",
)


def _spawn_replica(rank: int, port: int, attempt: int, base_env: dict,
                   log_dir: str, bench_args: dict) -> subprocess.Popen:
    env = dict(base_env)
    env["PADDLE_TRAINER_ID"] = str(rank)
    env["PADDLE_TPU_STATUS_PORT"] = str(port)
    env["PADDLE_RESPAWN_COUNT"] = str(attempt)
    cmd = [sys.executable, os.path.abspath(__file__), "--replica",
           "--rank", str(rank)]
    for flag, val in bench_args.items():
        cmd += [flag, str(val)]
    with open(os.path.join(log_dir,
                           f"replica{rank}.attempt{attempt}.log"),
              "a") as log:
        # the child inherits its own duplicate of the fd; holding the
        # supervisor's copy open would leak one fd per (re)spawn
        return subprocess.Popen(cmd, env=env, stdout=log,
                                stderr=subprocess.STDOUT)


def run_chaos_round(replicas: int = 2, requests: int = 80,
                    rate: float = 25.0,
                    n_layer: int = 2, d_model: int = 64, n_head: int = 4,
                    vocab: int = 512, max_seq_len: int = 128,
                    max_batch: int = 8, kv_blocks: int = 96,
                    block_size: int = 16,
                    prefill_buckets: str = "16,32,64",
                    prompt_lens: str = "4,8,12,24",
                    output_lens: str = "4,8,16",
                    slo_s: float = 30.0,
                    kill_tick: int = 40, victim: int = 1,
                    retries: int = 3, backoff_ms: float = 50.0,
                    hedge_ms: float = 0.0,
                    seed: int = 0,
                    boot_timeout: float = 180.0,
                    recovery_timeout: float = 180.0,
                    workdir: Optional[str] = None,
                    verbose: bool = True) -> Dict[str, Any]:
    """The availability-under-chaos round: >=2 real replica processes,
    Poisson load through the router, one replica killed mid-run by the
    armed ``replica_kill`` site, warm respawn, and the gated record."""
    import shutil
    import tempfile

    import numpy as np

    from paddle_tpu import chaos as _chaos
    from paddle_tpu.serving import ledger as _ledger
    from paddle_tpu.serving.model import GPTConfig, init_params
    from paddle_tpu.serving.router import HttpReplica, Router

    base = workdir or tempfile.mkdtemp(prefix="serve_chaos_")
    own_tmp = workdir is None
    serve_dir = os.path.join(base, "journals")
    log_dir = os.path.join(base, "logs")
    os.makedirs(serve_dir, exist_ok=True)
    os.makedirs(log_dir, exist_ok=True)
    params_path = os.path.join(base, "params.npz")
    cfg = GPTConfig(vocab_size=vocab, n_layer=n_layer, n_head=n_head,
                    d_model=d_model, max_seq_len=max_seq_len)
    np.savez(params_path, **init_params(cfg, seed=seed))

    sites = f"replica_kill@tick={kill_tick}:rank={victim}"
    base_env = dict(os.environ)
    base_env.pop("XLA_FLAGS", None)
    base_env["JAX_PLATFORMS"] = "cpu"
    base_env["PYTHONPATH"] = os.pathsep.join(
        [REPO_ROOT] + base_env.get("PYTHONPATH", "").split(os.pathsep))
    # replicas must not inherit the operator's observability env
    for k in ("PADDLE_TPU_TRACE_DIR", "PADDLE_TPU_GOODPUT_DIR",
              "PADDLE_TPU_MEMWATCH_DIR", "PADDLE_TPU_DYNAMICS_DIR",
              "PADDLE_TPU_CKPT_DIR"):
        base_env.pop(k, None)
    base_env.update({
        "PADDLE_TRAINERS_NUM": str(replicas),
        "PADDLE_TPU_SERVE_DIR": serve_dir,
        "PADDLE_TPU_SERVE_FLUSH_TICKS": "1",
        "PADDLE_TPU_SERVE_PARAMS": params_path,
        "PADDLE_TPU_CHAOS_SITES": sites,
        "PADDLE_TPU_CHAOS_SEED": str(seed),
        "PADDLE_RESTART_COUNT": "0",
    })
    bench_args = {
        "--n-layer": n_layer, "--d-model": d_model, "--n-head": n_head,
        "--vocab": vocab, "--max-seq-len": max_seq_len,
        "--max-batch": max_batch, "--kv-blocks": kv_blocks,
        "--block-size": block_size, "--prefill-buckets": prefill_buckets,
        "--slo-s": slo_s, "--seed": seed,
    }

    ports = [_free_port() for _ in range(replicas)]
    procs: List[subprocess.Popen] = []
    router: Optional[Router] = None
    watch_stop = threading.Event()
    state: Dict[str, Any] = {"t_kill": None, "killed_rc": None,
                             "t_respawn": None, "respawned": False,
                             "unexpected_exits": {}}
    try:
        procs = [_spawn_replica(r, ports[r], 0, base_env, log_dir,
                                bench_args)
                 for r in range(replicas)]
        clients = [HttpReplica(f"replica{r}",
                               f"http://127.0.0.1:{ports[r]}")
                   for r in range(replicas)]

        def _servable(c) -> bool:
            try:
                return (c.healthz(timeout=1.0).get("serving")
                        is not None)
            except Exception:
                return False

        deadline = time.time() + boot_timeout
        while time.time() < deadline:
            if all(_servable(c) for c in clients):
                break
            if any(p.poll() is not None for p in procs):
                raise RuntimeError(
                    "a replica died during boot; see " + log_dir)
            time.sleep(0.2)
        else:
            raise RuntimeError(
                f"replicas not servable within {boot_timeout}s; see "
                + log_dir)

        router = Router(clients, retries=retries, backoff_ms=backoff_ms,
                        hedge_ms=hedge_ms, default_slo_s=slo_s,
                        seed=seed, health_interval_s=0.2)
        router.probe_once()
        router.start_health()

        def _watch():
            while not watch_stop.is_set():
                for r, p in enumerate(procs):
                    rc = p.poll()
                    if rc is None:
                        continue
                    if r == victim and not state["respawned"]:
                        state["t_kill"] = time.time()
                        state["killed_rc"] = rc
                        # warm restart in place: attempt 1 (the armed
                        # replica_kill defaults to attempt=0, so the
                        # respawned incarnation serves instead of
                        # re-dying at the same tick)
                        procs[r] = _spawn_replica(
                            r, ports[r], 1, base_env, log_dir,
                            bench_args)
                        state["t_respawn"] = time.time()
                        state["respawned"] = True
                    elif r != victim or state["respawned"]:
                        state["unexpected_exits"].setdefault(r, rc)
                watch_stop.wait(0.05)

        watcher = threading.Thread(target=_watch, daemon=True)
        watcher.start()

        # -- the Poisson load, dispatched through the router ------------
        from concurrent.futures import ThreadPoolExecutor

        r = np.random.RandomState(seed)
        plens = [int(x) for x in prompt_lens.split(",")]
        olens = [int(x) for x in output_lens.split(",")]
        schedule = []
        t = 0.0
        for i in range(requests):
            t += float(r.exponential(1.0 / rate))
            prompt = r.randint(1, vocab,
                               size=int(r.choice(plens))).tolist()
            schedule.append((t, prompt, int(r.choice(olens))))
        prompts_by_id = {f"cb-{i:04d}": (p, o)
                         for i, (_, p, o) in enumerate(schedule)}
        pool = ThreadPoolExecutor(max_workers=32)
        futures = []
        bench_t0 = time.perf_counter()
        for i, (arrive, prompt, olen) in enumerate(schedule):
            now = time.perf_counter() - bench_t0
            if arrive > now:
                time.sleep(arrive - now)
            futures.append(pool.submit(
                router.dispatch, prompt, olen, slo_s, f"cb-{i:04d}"))
        records = [f.result() for f in futures]
        traffic_wall = time.perf_counter() - bench_t0
        router.wait_hedges()
        pool.shutdown(wait=True)

        # -- wait for the warm restart to rejoin the healthy set --------
        t_recovered = None
        deadline = time.time() + recovery_timeout
        while time.time() < deadline:
            if state["respawned"]:
                for ev in router.health_events:
                    if (ev["replica"] == f"replica{victim}"
                            and ev["to"] == "healthy"
                            and state["t_kill"] is not None
                            and ev["time_unix"] > state["t_kill"]):
                        t_recovered = ev["time_unix"]
                        break
            if t_recovered is not None:
                break
            time.sleep(0.2)
        rejoined = t_recovered is not None
        recovery_seconds = (round(t_recovered - state["t_kill"], 3)
                            if rejoined and state["t_kill"] else None)
        detection_seconds = None
        if state["t_kill"] is not None:
            deaths = [ev["time_unix"] for ev in router.health_events
                      if ev["replica"] == f"replica{victim}"
                      and ev["to"] == "dead"
                      and ev["time_unix"] >= state["t_kill"] - 1.0]
            if deaths:
                # clamped at 0: a dispatch-failure detection can beat
                # the supervisor's own exit-poll clock by a beat
                detection_seconds = round(
                    max(0.0, min(deaths) - state["t_kill"]), 3)

        # -- the bit-match verify pass: every re-dispatched request -----
        # replayed (fresh request_id -> fresh compute on whichever
        # replica) must reproduce the tokens the client was given
        checked = matched = 0
        for rec in records:
            if not rec.get("ok"):
                continue
            if rec.get("n_attempts", 1) <= 1 and not rec.get("hedged"):
                continue
            prompt, olen = prompts_by_id[rec["request_id"]]
            again = router.dispatch(prompt, olen, slo_s,
                                    rec["request_id"] + "-verify")
            if again.get("ok"):
                checked += 1
                if list(again["tokens"]) == list(rec["tokens"]):
                    matched += 1
        snap = router.snapshot()
        bit = {"checked": checked, "matched": matched,
               "hedge_compared": snap["stats"]["bitmatch_checked"],
               "hedge_mismatch": snap["stats"]["bitmatch_mismatch"],
               "ok": bool(checked == matched
                          and snap["stats"]["bitmatch_mismatch"] == 0)}

        avail = availability_summary(records)
        dip = failover_window_latency(records, state["t_kill"],
                                      t_recovered)
        # graceful stop BEFORE the merge: each replica's SIGTERM flush
        # writes its final journal state (the respawned replica's
        # resumed_from_journal provenance included). The watcher is
        # JOINED first — a mid-iteration watcher would classify the
        # teardown SIGTERMs as unexpected replica exits and flip the
        # round verdict
        watch_stop.set()
        watcher.join(timeout=5)
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=15)
            except subprocess.TimeoutExpired:
                p.kill()
        merged = _ledger.load_journals(serve_dir, ranks=range(replicas))
        slo = _ledger.slo_summary(merged) if merged else {}

        chaos = build_chaos_record(
            replicas=replicas,
            victim_rank=victim,
            kill_tick=kill_tick,
            sites=sites,
            seed=seed,
            killed_exit_code=state["killed_rc"],
            kill_exit_expected=_chaos.KILL_EXIT_CODE,
            t_kill_unix=state["t_kill"],
            t_respawn_unix=state["t_respawn"],
            t_recovered_unix=t_recovered,
            respawned=state["respawned"],
            rejoined=rejoined,
            unexpected_exits={str(k): v for k, v in
                              state["unexpected_exits"].items()},
            availability=avail["availability"],
            availability_floor=0.95,
            error_rate=avail["error_rate"],
            detection_seconds=detection_seconds,
            recovery_seconds=recovery_seconds,
            typed_failures=(avail["typed_failures"]
                            and not state["unexpected_exits"]),
            no_hang=avail["no_hang"],
            failure_reasons=avail["failure_reasons"],
            requests_redispatched=avail["redispatched"],
            redispatch_bit_match=bit,
            p99_dip=dip,
            router=snap["stats"],
            replica_states=snap["replicas"],
            health_events=snap["health_events"],
        )

        parsed: Dict[str, Any] = {
            "metric": "serve_availability",
            "unit": "fraction of requests completing within SLO under "
                    "one replica kill (chaos round)",
            "mode": "chaos",
            "model": {"n_layer": n_layer, "d_model": d_model,
                      "n_head": n_head, "vocab_size": vocab,
                      "max_seq_len": max_seq_len},
            "engine": {"max_batch": max_batch, "kv_blocks": kv_blocks,
                       "block_size": block_size,
                       "prefill_buckets": prefill_buckets,
                       "replicas": replicas},
            "traffic": {"requests": requests, "rate_per_sec": rate,
                        "prompt_lens": plens, "output_lens": olens,
                        "seed": seed, "slo_s": slo_s,
                        "retries": retries, "backoff_ms": backoff_ms,
                        "hedge_ms": hedge_ms},
            "bench_wall_seconds": round(traffic_wall, 4),
            # the gated headlines (perf_gate SERVE pattern)
            "availability": avail["availability"],
            "error_rate": avail["error_rate"],
            "detection_seconds": detection_seconds,
            "recovery_seconds": recovery_seconds,
            "requests_ok": avail["ok_within_slo"] + avail["late"],
            "requests_failed": avail["failed"],
            "client_p50_latency_s": avail["client_p50_latency_s"],
            "client_p99_latency_s": avail["client_p99_latency_s"],
            "chaos": chaos,
        }
        if merged:
            # engine-side SLO + goodput across replicas, NAMESPACED
            # under engine_slo: a chaos round's throughput/latency is a
            # load-regime artifact (one replica spends the outage
            # absorbing the other's traffic), so it must not feed the
            # steady rounds' tokens_per_sec/p99 gate medians — the
            # chaos trajectory is gated on availability / error_rate /
            # recovery_seconds instead
            parsed["engine_slo"] = {
                "tokens_per_sec": round(
                    merged.get("tokens_per_sec") or 0.0, 2),
                "decode_tokens": merged.get("decode_tokens"),
                "prompt_tokens": merged.get("prompt_tokens"),
                "ttft_s": slo["ttft"]["avg"],
                "p99_ttft_s": slo["ttft"]["p99"],
                "p50_latency_s": slo["latency"]["p50"],
                "p99_latency_s": slo["latency"]["p99"],
                "batch_occupancy": merged.get("batch_occupancy"),
                "kv_block_utilization": merged.get(
                    "kv_block_utilization"),
            }
            parsed.update({
                "n_replicas_merged": merged.get("n_replicas"),
                "n_journals_resumed": merged.get("n_resumed"),
                "stale_filtered": merged.get("stale_filtered"),
                "goodput": {
                    "buckets": {b: round(v, 6) for b, v in
                                merged.get("buckets", {}).items()},
                    "goodput_fraction": merged.get("goodput_fraction"),
                    "top_badput": merged.get("top_badput"),
                },
            })
        parsed["ok"] = chaos["ok"]
        if verbose:
            print(f"chaos round {'PASS' if chaos['ok'] else 'FAIL'}: "
                  f"availability {avail['availability']:.4f} "
                  f"({avail['ok_within_slo']}/{avail['requests']} in "
                  f"SLO), error_rate {avail['error_rate']:.4f}, "
                  f"detection {detection_seconds}s, recovery "
                  f"{recovery_seconds}s, redispatched "
                  f"{avail['redispatched']} (bit-match "
                  f"{bit['matched']}/{bit['checked']}), retries "
                  f"{snap['stats']['retries']}, hedges "
                  f"{snap['stats']['hedges']}")
            if merged:
                eslo = parsed["engine_slo"]
                print(f"  merged ledger: {eslo['tokens_per_sec']} "
                      f"tokens/s over {merged.get('n_replicas')} "
                      f"replica journal(s), engine p99 "
                      f"{eslo['p99_latency_s']}s")
        return parsed
    finally:
        watch_stop.set()
        if router is not None:
            router.stop()
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        if own_tmp:
            shutil.rmtree(base, ignore_errors=True)


# ---------------------------------------------------------------------------
# multi mode: the steady >=2-replica observability round (--multi)
# ---------------------------------------------------------------------------


def _req_trace_view(merged_trace: Dict[str, Any], rid: str
                    ) -> Dict[str, Any]:
    """How one request renders in the merged --serve timeline: its
    serving spans, the processes they live in, and whether the spans
    chain into ONE connected flow (every span either the root or
    parented on another span of the same request)."""
    spans = [e for e in merged_trace.get("traceEvents", ())
             if e.get("ph") == "X"
             and (e.get("args") or {}).get("request_id") == rid]
    ids = {e["args"].get("span_id") for e in spans} - {None}
    parents = {e["args"].get("parent_span_id") for e in spans} - {None}
    procs = sorted({e["args"].get("proc") for e in spans} - {None})
    return {
        "request_id": rid,
        "n_spans": len(spans),
        "processes": procs,
        "connected": bool(spans) and parents <= ids,
    }


def run_multi_round(replicas: int = 2, requests: int = 48,
                    rate: float = 25.0,
                    n_layer: int = 2, d_model: int = 64, n_head: int = 4,
                    vocab: int = 512, max_seq_len: int = 128,
                    max_batch: int = 8, kv_blocks: int = 96,
                    block_size: int = 16,
                    prefill_buckets: str = "16,32,64",
                    prompt_lens: str = "4,8,12,24",
                    output_lens: str = "4,8,16",
                    slo_s: float = 30.0,
                    retries: int = 3, backoff_ms: float = 40.0,
                    hedge_ms: float = 40.0,
                    seed: int = 0,
                    boot_timeout: float = 180.0,
                    workdir: Optional[str] = None,
                    verbose: bool = True) -> Dict[str, Any]:
    """The serving-observability round: >=2 REAL replica processes with
    tracing on, Poisson load through the router under mixed traffic
    classes, one FORCED retry (first attempt deliberately aimed at a
    dead endpoint) and one FORCED hedge (the router's latency EMA
    seeded pessimistic so the SLO-at-risk test trips at the hedge
    window) — then the round is judged on what this PR's observability
    claims: every closed request's buckets sum to its measured e2e
    (attribution_residual at the median inside the gate bound), the
    router + replica journals merge into one attribution/traffic view,
    and both forced paths render as ONE connected flow in the merged
    ``tools/timeline.py --serve`` trace."""
    import shutil
    import tempfile

    import numpy as np

    from paddle_tpu import profiler as _profiler
    from paddle_tpu.serving import ledger as _ledger
    from paddle_tpu.serving.model import GPTConfig, init_params
    from paddle_tpu.serving.router import HttpReplica, Router

    base = workdir or tempfile.mkdtemp(prefix="serve_multi_")
    own_tmp = workdir is None
    serve_dir = os.path.join(base, "journals")
    log_dir = os.path.join(base, "logs")
    trace_dir = os.path.join(base, "trace")
    for d in (serve_dir, log_dir, trace_dir):
        os.makedirs(d, exist_ok=True)
    params_path = os.path.join(base, "params.npz")
    cfg = GPTConfig(vocab_size=vocab, n_layer=n_layer, n_head=n_head,
                    d_model=d_model, max_seq_len=max_seq_len)
    np.savez(params_path, **init_params(cfg, seed=seed))

    base_env = dict(os.environ)
    base_env.pop("XLA_FLAGS", None)
    base_env["JAX_PLATFORMS"] = "cpu"
    base_env["PYTHONPATH"] = os.pathsep.join(
        [REPO_ROOT] + base_env.get("PYTHONPATH", "").split(os.pathsep))
    # replicas must not inherit the operator's observability env — but
    # THIS round's whole point is the cross-process trace, so the trace
    # knobs are deliberately re-armed at our own trace_dir
    for k in ("PADDLE_TPU_TRACE_DIR", "PADDLE_TPU_GOODPUT_DIR",
              "PADDLE_TPU_MEMWATCH_DIR", "PADDLE_TPU_DYNAMICS_DIR",
              "PADDLE_TPU_CKPT_DIR", "PADDLE_TPU_CHAOS_SITES"):
        base_env.pop(k, None)
    base_env.update({
        "PADDLE_TRAINERS_NUM": str(replicas),
        "PADDLE_TPU_SERVE_DIR": serve_dir,
        "PADDLE_TPU_SERVE_FLUSH_TICKS": "1",
        "PADDLE_TPU_SERVE_PARAMS": params_path,
        "PADDLE_TPU_TRACE": "1",
        "PADDLE_TPU_TRACE_DIR": trace_dir,
    })
    bench_args = {
        "--n-layer": n_layer, "--d-model": d_model, "--n-head": n_head,
        "--vocab": vocab, "--max-seq-len": max_seq_len,
        "--max-batch": max_batch, "--kv-blocks": kv_blocks,
        "--block-size": block_size, "--prefill-buckets": prefill_buckets,
        "--slo-s": slo_s, "--seed": seed,
    }

    ports = [_free_port() for _ in range(replicas)]
    procs: List[subprocess.Popen] = []
    router: Optional[Router] = None
    autoscaler = None
    # the supervisor is the router process: its spans (dispatch roots,
    # attempt children) are the router leg of the merged timeline
    _profiler.clear_events()
    _profiler.enable_tracing()
    try:
        procs = [_spawn_replica(r, ports[r], 0, base_env, log_dir,
                                bench_args)
                 for r in range(replicas)]
        clients = [HttpReplica(f"replica{r}",
                               f"http://127.0.0.1:{ports[r]}")
                   for r in range(replicas)]

        def _servable(c) -> bool:
            try:
                return (c.healthz(timeout=1.0).get("serving")
                        is not None)
            except Exception:
                return False

        deadline = time.time() + boot_timeout
        while time.time() < deadline:
            if all(_servable(c) for c in clients):
                break
            if any(p.poll() is not None for p in procs):
                raise RuntimeError(
                    "a replica died during boot; see " + log_dir)
            time.sleep(0.2)
        else:
            raise RuntimeError(
                f"replicas not servable within {boot_timeout}s; see "
                + log_dir)

        # a dead endpoint in the pool: nothing listens on its port, and
        # its name sorts FIRST in the least-loaded tie-break, so the
        # pre-probe dispatch below deterministically attempts it, takes
        # the typed connect failure, and retries onto a live replica —
        # the forced-retry flow the merged timeline must connect
        ghost = HttpReplica("replica-00down",
                            f"http://127.0.0.1:{_free_port()}")
        router = Router([ghost] + clients, retries=retries,
                        backoff_ms=backoff_ms, hedge_ms=hedge_ms,
                        default_slo_s=slo_s, seed=seed,
                        health_interval_s=0.2)
        r = np.random.RandomState(seed)
        plens = [int(x) for x in prompt_lens.split(",")]
        olens = [int(x) for x in output_lens.split(",")]

        retry_rec = router.dispatch(
            r.randint(1, vocab, size=max(plens)).tolist(),
            max_new_tokens=max(olens), deadline_s=slo_s,
            request_id="cb-retry", traffic_class="retry-probe")

        # now let the prober own health (the ghost stays dead)
        router.probe_once()
        router.start_health()

        # PADDLE_TPU_SERVE_AUTOSCALE: the supervisor IS the router
        # process, so the capacity loop attaches here when the operator
        # opts in — default off, the steady-wave round's replica set
        # stays as launched (the dedicated --autoscale round always
        # runs the loop)
        if _env_truthy("PADDLE_TPU_SERVE_AUTOSCALE"):
            from paddle_tpu.serving import capacity as _capacity
            try:
                # the file IS the decode-roofline legs doc replica0
                # cached next to the shared params (replica_main)
                with open(params_path + ".roofline.json") as f:
                    _roof = json.load(f) or {}
            except Exception:
                _roof = {}
            auto_procs: Dict[str, subprocess.Popen] = {}

            def _auto_spawn(index: int):
                port = _free_port()
                p = _spawn_replica(index, port, 0, base_env, log_dir,
                                   bench_args)
                procs.append(p)
                c = HttpReplica(f"replica{index}",
                                f"http://127.0.0.1:{port}")
                auto_procs[c.name] = p
                boot_deadline = time.time() + boot_timeout
                while time.time() < boot_deadline:
                    if _servable(c):
                        return c
                    if p.poll() is not None:
                        break
                    time.sleep(0.2)
                raise RuntimeError(f"replica{index} failed to boot")

            def _auto_stop(name: str) -> None:
                p = auto_procs.pop(name, None)
                if p is not None and p.poll() is None:
                    p.terminate()

            # the managed set includes the dead ghost, so the floor is
            # the as-launched count — the loop may add one replica
            # under a burst but never drains the steady-wave set
            _n_managed = len(router.replica_names())
            autoscaler = _capacity.Autoscaler(
                router, _roof, spawn_replica=_auto_spawn,
                stop_replica=_auto_stop,
                device_budget=_n_managed + 1,
                tp=1, max_batch=max_batch,
                min_replicas=_n_managed, max_replicas=_n_managed + 1)
            # one synchronous tick before the wave: a round shorter
            # than the loop interval still journals the plan it ran
            # under (the loop swallows bad ticks the same way)
            try:
                autoscaler.step()
            except Exception as e:
                print(f"[bench] autoscale first tick failed: {e!r}",
                      file=sys.stderr)
            autoscaler.start()

        # -- the steady Poisson wave, mixed traffic classes -------------
        from concurrent.futures import ThreadPoolExecutor

        olen_split = sorted(olens)[len(olens) // 2]
        schedule = []
        t = 0.0
        for i in range(requests):
            t += float(r.exponential(1.0 / rate))
            prompt = r.randint(1, vocab,
                               size=int(r.choice(plens))).tolist()
            schedule.append((t, prompt, int(r.choice(olens))))
        pool = ThreadPoolExecutor(max_workers=32)
        futures = []
        bench_t0 = time.perf_counter()
        for i, (arrive, prompt, olen) in enumerate(schedule):
            now = time.perf_counter() - bench_t0
            if arrive > now:
                time.sleep(arrive - now)
            klass = "interactive" if olen <= olen_split else "bulk"
            futures.append(pool.submit(
                router.dispatch, prompt, olen, slo_s, f"cb-{i:04d}",
                klass))
        records = [f.result() for f in futures]
        traffic_wall = time.perf_counter() - bench_t0
        pool.shutdown(wait=True)

        # -- the forced hedge -------------------------------------------
        # seed the completed-latency EMA pessimistic: the SLO-at-risk
        # test ("remaining budget < expected service") then trips at the
        # hedge window, so the next dispatch hedges onto the second
        # replica — the overlapping-attempts flow, plus a bit-match
        # comparison when the loser is harvested
        with router._lock:
            router._latency_ema["hedge-probe"] = float(slo_s)
        hedge_rec = router.dispatch(
            r.randint(1, vocab, size=max(plens)).tolist(),
            max_new_tokens=max(olens), deadline_s=slo_s,
            request_id="cb-hedge", traffic_class="hedge-probe")
        router.wait_hedges()
        records_all = [retry_rec] + records + [hedge_rec]
        snap = router.snapshot()

        # -- teardown -> journals + traces on disk ----------------------
        router.stop()
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=15)
            except subprocess.TimeoutExpired:
                p.kill()
        router.flush_ledger(serve_dir)
        _profiler.flush_trace(os.path.join(trace_dir,
                                           "trace.router.json"))
        _profiler.clear_events()

        # -- merge + judge ----------------------------------------------
        merged = _ledger.load_journals(serve_dir, ranks=range(replicas))
        slo = _ledger.slo_summary(merged) if merged else {}
        attr_summary = _ledger.attribution_summary(merged)
        attr_rec = _ledger.reconcile_attribution(merged)

        client_residuals = sorted(
            rec["attribution_residual"] for rec in records_all
            if rec.get("attribution_residual") is not None)
        lat = [rec["latency_s"] for rec in records_all
               if rec.get("latency_s") is not None]

        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        try:
            import timeline as _timeline
        finally:
            sys.path.pop(0)
        by_proc = _timeline.load_serve_traces(trace_dir)
        merged_trace = _timeline.merge_serve_traces(by_proc)
        _timeline.validate_chrome_trace(merged_trace)
        retry_view = _req_trace_view(merged_trace, "cb-retry")
        hedge_view = _req_trace_view(merged_trace, "cb-hedge")
        phase_summary = _timeline.serve_phase_summary(by_proc)

        n_ok = sum(1 for rec in records_all if rec.get("ok"))
        ok = bool(
            n_ok == len(records_all)
            and retry_rec.get("ok") and retry_rec["n_attempts"] >= 2
            and retry_rec.get("failover")
            and hedge_rec.get("ok") and hedge_rec.get("hedged")
            and attr_rec.get("within_bound")
            # the forced paths must each read as one connected
            # cross-process flow in the merged timeline
            and retry_view["connected"]
            and len(retry_view["processes"]) >= 2
            and hedge_view["connected"]
            and len(hedge_view["processes"]) >= 3
            and merged_trace["metadata"]["wire_flows"] >= 1
            and snap["stats"]["bitmatch_mismatch"] == 0)

        parsed: Dict[str, Any] = {
            "metric": "serve_attribution_residual",
            "unit": "median |sum(buckets) - e2e| / e2e over closed "
                    "requests (multi-replica steady round)",
            "mode": "multi",
            "model": {"n_layer": n_layer, "d_model": d_model,
                      "n_head": n_head, "vocab_size": vocab,
                      "max_seq_len": max_seq_len},
            "engine": {"max_batch": max_batch, "kv_blocks": kv_blocks,
                       "block_size": block_size,
                       "prefill_buckets": prefill_buckets,
                       "replicas": replicas},
            "traffic": {"requests": requests, "rate_per_sec": rate,
                        "prompt_lens": plens, "output_lens": olens,
                        "seed": seed, "slo_s": slo_s,
                        "retries": retries, "backoff_ms": backoff_ms,
                        "hedge_ms": hedge_ms},
            "bench_wall_seconds": round(traffic_wall, 4),
            # the gated headline (perf_gate SERVE pattern,
            # lower-is-better): ledger-side residual across every
            # closed request, router + engine classes merged
            "attribution_residual": attr_rec.get("residual_p50"),
            "attribution": {
                "summary": attr_summary,
                "reconciliation": attr_rec,
                "client_residual_p50": _percentile(client_residuals,
                                                   0.50),
                "client_residual_p99": _percentile(client_residuals,
                                                   0.99),
            },
            # the router's arrival-process telemetry (rate EMAs,
            # interarrival CV, depth series) as merged from its journal
            "traffic_telemetry": (merged or {}).get("traffic"),
            "requests_ok": n_ok,
            "requests_failed": len(records_all) - n_ok,
            "client_p50_latency_s": _percentile(lat, 0.50),
            "client_p99_latency_s": _percentile(lat, 0.99),
            "forced_retry": {
                "record": {k: retry_rec.get(k) for k in
                           ("request_id", "ok", "n_attempts", "failover",
                            "replicas_tried", "attribution",
                            "attribution_residual", "latency_s")},
                "timeline": retry_view,
            },
            "forced_hedge": {
                "record": {k: hedge_rec.get(k) for k in
                           ("request_id", "ok", "hedged", "n_attempts",
                            "replicas_tried", "attribution",
                            "attribution_residual", "latency_s")},
                "timeline": hedge_view,
            },
            "trace": {
                "dir": trace_dir if not own_tmp else None,
                "processes": merged_trace["metadata"]["processes"],
                "wire_flows": merged_trace["metadata"]["wire_flows"],
                "serve_flows": merged_trace["metadata"]["serve_flows"],
                "serve_requests": merged_trace["metadata"][
                    "serve_requests"],
                "phases": {ph: {"calls": row["calls"],
                                "slowest_proc": row["slowest_proc"]}
                           for ph, row in phase_summary["phases"].items()},
            },
            "router": snap["stats"],
        }
        if merged:
            # engine-side SLO NAMESPACED under engine_slo, same rule as
            # the chaos round: a routed multi-replica regime must not
            # feed the single-engine steady gate medians
            parsed["engine_slo"] = {
                "tokens_per_sec": round(
                    merged.get("tokens_per_sec") or 0.0, 2),
                "decode_tokens": merged.get("decode_tokens"),
                "prompt_tokens": merged.get("prompt_tokens"),
                "ttft_s": slo["ttft"]["avg"],
                "p99_ttft_s": slo["ttft"]["p99"],
                "p50_latency_s": slo["latency"]["p50"],
                "p99_latency_s": slo["latency"]["p99"],
                "batch_occupancy": merged.get("batch_occupancy"),
                "kv_block_utilization": merged.get(
                    "kv_block_utilization"),
            }
            parsed["n_replicas_merged"] = merged.get("n_replicas")
            # the opt-in autoscaler's decision trail (plan + typed
            # journal) folds in off the router's merged ledger doc
            if merged.get("autoscale"):
                parsed["autoscale"] = merged["autoscale"]
        parsed["ok"] = ok
        if verbose:
            print(f"multi round {'PASS' if ok else 'FAIL'}: "
                  f"{n_ok}/{len(records_all)} ok, attribution residual "
                  f"p50 {attr_rec.get('residual_p50')} (bound "
                  f"{attr_rec.get('bound')}, "
                  f"{attr_rec.get('verdict')}), retry "
                  f"{retry_rec['n_attempts']} attempts "
                  f"(connected={retry_view['connected']}), hedge "
                  f"hedged={hedge_rec.get('hedged')} "
                  f"(connected={hedge_view['connected']}, procs "
                  f"{hedge_view['processes']}), "
                  f"{merged_trace['metadata']['wire_flows']} wire "
                  f"flow(s) across {len(by_proc)} process trace(s)")
            print(_timeline.render_serve_summary(phase_summary))
        return parsed
    finally:
        if autoscaler is not None:
            autoscaler.stop()
        if router is not None:
            router.stop()
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        if own_tmp:
            shutil.rmtree(base, ignore_errors=True)


# ---------------------------------------------------------------------------
# autoscale mode (--autoscale): the capacity planner judged live
# ---------------------------------------------------------------------------


def run_autoscale_round(n_layer: int = 2, d_model: int = 64,
                        n_head: int = 4, vocab: int = 512,
                        max_seq_len: int = 128,
                        max_batch: int = 4, kv_blocks: int = 96,
                        block_size: int = 16,
                        prefill_buckets: str = "16,32,64",
                        prompt_lens: str = "4,8,12",
                        slo_classes_spec: str =
                        "interactive:slo=3,weight=3,hedge=1;"
                        "batch:slo=30,weight=1,hedge=0",
                        retries: int = 3, backoff_ms: float = 40.0,
                        hedge_ms: float = 40.0,
                        seed: int = 0,
                        boot_timeout: float = 180.0,
                        quiet_s: float = 5.0, burst_s: float = 6.0,
                        cool_s: float = 12.0,
                        window_s: float = 2.0,
                        interval_s: float = 0.7,
                        cooldown_s: float = 2.5,
                        workdir: Optional[str] = None,
                        verbose: bool = True) -> Dict[str, Any]:
    """The autoscale round: ONE real replica process boots, the
    capacity planner (paddle_tpu/serving/capacity.py) watches the
    router's traffic telemetry, and a quiet -> burst -> quiet diurnal
    trace must force it through both live actions — a warm-restart
    scale-up when the burst's CV-widened forecast outruns one
    replica's calibrated capacity, and a drain-first scale-down once
    the forecast decays. The round is judged on what this PR's
    observability claims: per-class SLO attainment against the class
    table (the realized side of every decision's prediction),
    utilization, and ``scale_regret`` against the post-hoc oracle
    schedule built from the SAME arrival trace. Rates self-scale to
    the host: a saturation warm-up probe measures one replica's real
    request-level tokens/s, calibrates the roofline prediction with
    it, and sizes the burst at ~1.5x that capacity so the planner's
    verdict flips by construction — but through the real forecast,
    not a scripted trigger."""
    import math
    import shutil
    import tempfile

    import numpy as np

    from paddle_tpu import profiler as _profiler
    from paddle_tpu.serving import capacity as _capacity
    from paddle_tpu.serving import ledger as _ledger
    from paddle_tpu.serving.model import GPTConfig, init_params
    from paddle_tpu.serving.router import HttpReplica, Router

    base = workdir or tempfile.mkdtemp(prefix="serve_autoscale_")
    own_tmp = workdir is None
    serve_dir = os.path.join(base, "journals")
    log_dir = os.path.join(base, "logs")
    trace_dir = os.path.join(base, "trace")
    for d in (serve_dir, log_dir, trace_dir):
        os.makedirs(d, exist_ok=True)
    params_path = os.path.join(base, "params.npz")
    cfg = GPTConfig(vocab_size=vocab, n_layer=n_layer, n_head=n_head,
                    d_model=d_model, max_seq_len=max_seq_len)
    np.savez(params_path, **init_params(cfg, seed=seed))

    min_replicas, max_replicas = 1, 2
    base_env = dict(os.environ)
    base_env.pop("XLA_FLAGS", None)
    base_env["JAX_PLATFORMS"] = "cpu"
    base_env["PYTHONPATH"] = os.pathsep.join(
        [REPO_ROOT] + base_env.get("PYTHONPATH", "").split(os.pathsep))
    for k in ("PADDLE_TPU_TRACE_DIR", "PADDLE_TPU_GOODPUT_DIR",
              "PADDLE_TPU_MEMWATCH_DIR", "PADDLE_TPU_DYNAMICS_DIR",
              "PADDLE_TPU_CKPT_DIR", "PADDLE_TPU_CHAOS_SITES"):
        base_env.pop(k, None)
    base_env.update({
        "PADDLE_TRAINERS_NUM": str(max_replicas),
        "PADDLE_TPU_SERVE_DIR": serve_dir,
        "PADDLE_TPU_SERVE_FLUSH_TICKS": "1",
        "PADDLE_TPU_SERVE_PARAMS": params_path,
        "PADDLE_TPU_TRACE": "1",
        "PADDLE_TPU_TRACE_DIR": trace_dir,
    })
    bench_args = {
        "--n-layer": n_layer, "--d-model": d_model, "--n-head": n_head,
        "--vocab": vocab, "--max-seq-len": max_seq_len,
        "--max-batch": max_batch, "--kv-blocks": kv_blocks,
        "--block-size": block_size, "--prefill-buckets": prefill_buckets,
        "--slo-s": 30.0, "--seed": seed,
    }
    slo_classes = _capacity.parse_slo_classes(slo_classes_spec)

    procs: List[subprocess.Popen] = []
    proc_by_name: Dict[str, subprocess.Popen] = {}
    router: Optional[Router] = None
    autoscaler = None
    _profiler.clear_events()
    _profiler.enable_tracing()
    try:
        # -- boot the anchor replica (replica0) -------------------------
        port0 = _free_port()
        p0 = _spawn_replica(0, port0, 0, base_env, log_dir, bench_args)
        procs.append(p0)
        client0 = HttpReplica("replica0", f"http://127.0.0.1:{port0}")
        proc_by_name["replica0"] = p0

        def _servable(c) -> bool:
            try:
                return (c.healthz(timeout=1.0).get("serving")
                        is not None)
            except Exception:
                return False

        deadline = time.time() + boot_timeout
        while time.time() < deadline:
            if _servable(client0):
                break
            if p0.poll() is not None:
                raise RuntimeError(
                    "replica0 died during boot; see " + log_dir)
            time.sleep(0.2)
        else:
            raise RuntimeError(
                f"replica0 not servable within {boot_timeout}s; see "
                + log_dir)

        # replica0 wrote its decode roofline next to the shared params
        # before READY — the same AOT legs the planner scores with
        roof_path = params_path + ".roofline.json"
        with open(roof_path) as f:
            roofline = json.load(f)

        router = Router([client0], retries=retries,
                        backoff_ms=backoff_ms, hedge_ms=hedge_ms,
                        default_slo_s=30.0, seed=seed,
                        health_interval_s=0.2)
        router.probe_once()
        router.start_health()

        # -- saturation warm-up: the measured side of calibration -------
        # direct client submits (no router -> no telemetry pollution):
        # saturate replica0's batch and measure real request-level
        # tokens/s, the number the roofline prediction is corrected by
        from concurrent.futures import ThreadPoolExecutor

        r = np.random.RandomState(seed)
        plens = [int(x) for x in prompt_lens.split(",")]
        olen_probe = 8
        n_probe = 4 * max_batch

        def _probe(i):
            prompt = r.randint(1, vocab,
                               size=int(r.choice(plens))).tolist()
            return client0.submit(prompt, olen_probe, 30.0,
                                  f"warm-{i:03d}", timeout=30.0)

        probe_pool = ThreadPoolExecutor(max_workers=2 * max_batch)
        t0 = time.perf_counter()
        probe_ok = sum(1 for f in [probe_pool.submit(_probe, i)
                                   for i in range(n_probe)]
                       if f.result().get("tokens"))
        warm_wall = time.perf_counter() - t0
        probe_pool.shutdown(wait=True)
        cap_measured = probe_ok * olen_probe / max(warm_wall, 1e-6)

        raw = _capacity.score_config(
            {"spec": f"r1/tp1/mb{max_batch}", "replicas": 1, "tp": 1,
             "max_batch": max_batch, "devices": 1}, roofline)
        cap_predicted = raw["predicted"]["tokens_per_sec_per_replica"]
        calibration = {"tokens_per_sec": {
            "correction_factor": round(
                cap_measured / max(cap_predicted, 1e-9), 6),
            "n_pairs": 1, "source": "warmup_probe",
        }}

        # -- size the trace to the measured capacity --------------------
        # burst demand targets ~1.5x one replica's calibrated capacity
        # (through the CV-widened upper bound, upper ~= 2x rate for
        # Poisson): r1 must reject, r2 must be the plan — by the
        # planner's own arithmetic, whatever this host's speed
        olen_i = int(min(32, max(4, round(1.5 * cap_measured / 36.0))))
        olen_b = min(48, 2 * olen_i)
        rate_burst = min(40.0, max(6.0, 1.5 * cap_measured
                                   / (2.0 * olen_i)))
        rate_quiet = min(4.0, max(1.0, 0.15 * cap_measured
                                  / (2.0 * olen_i)))
        rate_batch = 0.5
        burst_s_eff = min(burst_s, max(3.5, 150.0 / rate_burst))
        tokens_per_request = float(olen_i)

        # -- the autoscaler over the live router ------------------------
        def _spawn(index: int):
            port = _free_port()
            p = _spawn_replica(index, port, 0, base_env, log_dir,
                               bench_args)
            procs.append(p)
            c = HttpReplica(f"replica{index}",
                            f"http://127.0.0.1:{port}")
            dl = time.time() + boot_timeout
            while time.time() < dl:
                if _servable(c):
                    proc_by_name[c.name] = p
                    return c
                if p.poll() is not None:
                    raise RuntimeError(
                        f"replica{index} died during warm boot; see "
                        + log_dir)
                time.sleep(0.1)
            raise RuntimeError(
                f"replica{index} not servable within {boot_timeout}s")

        def _stop(name: str) -> None:
            p = proc_by_name.get(name)
            if p is not None and p.poll() is None:
                p.terminate()
                try:
                    p.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    p.kill()

        autoscaler = _capacity.Autoscaler(
            router, roofline, spawn_replica=_spawn, stop_replica=_stop,
            device_budget=max_replicas, tp=1, max_batch=max_batch,
            slo_classes=slo_classes, min_replicas=min_replicas,
            max_replicas=max_replicas, interval_s=interval_s,
            cooldown_s=cooldown_s, headroom=0.15,
            tokens_per_request=tokens_per_request,
            calibration=calibration,
            tp_degrees=(1,), max_batches=(max_batch,))
        autoscaler.start()

        # -- the diurnal trace: quiet -> burst -> quiet -----------------
        phases = [("quiet", quiet_s, rate_quiet),
                  ("burst", burst_s_eff, rate_burst),
                  ("cool", cool_s, rate_quiet)]
        schedule = []
        t_cursor = 0.0
        phase_edges = []
        for phase, dur, rate_i in phases:
            t_end = t_cursor + dur
            phase_edges.append({"phase": phase,
                                "t0_s": round(t_cursor, 3),
                                "t1_s": round(t_end, 3),
                                "rate_per_s": round(rate_i, 3)})
            t = t_cursor
            while True:
                t += float(r.exponential(1.0 / rate_i))
                if t >= t_end:
                    break
                prompt = r.randint(1, vocab,
                                   size=int(r.choice(plens))).tolist()
                schedule.append((t, prompt, olen_i, "interactive"))
            # the batch tenant: a steady trickle in every phase
            tb = t_cursor + 0.25
            while tb < t_end:
                prompt = r.randint(1, vocab,
                                   size=int(r.choice(plens))).tolist()
                schedule.append((tb, prompt, olen_b, "batch"))
                tb += 1.0 / rate_batch
            t_cursor = t_end
        schedule.sort(key=lambda e: e[0])

        pool = ThreadPoolExecutor(max_workers=64)
        futures = []
        arrivals: List[tuple] = []
        bench_t0 = time.perf_counter()
        bench_t0_unix = _profiler.span_clock_unix()
        for i, (arrive, prompt, olen, klass) in enumerate(schedule):
            now = time.perf_counter() - bench_t0
            if arrive > now:
                time.sleep(arrive - now)
            arrivals.append((time.perf_counter() - bench_t0,
                             float(olen)))
            futures.append(pool.submit(
                router.dispatch, prompt, olen, None, f"cb-{i:04d}",
                klass))
        records = [f.result() for f in futures]
        traffic_wall = time.perf_counter() - bench_t0
        pool.shutdown(wait=True)

        # safety tail: if the forecast has not decayed enough for the
        # drain-first scale-down inside the trace, keep a light trickle
        # flowing (the EMAs decay on arrivals) and give the loop a
        # bounded grace window
        k = 0
        t_tail0 = time.perf_counter()
        while (not any(d["action"] == "scale_down"
                       for d in autoscaler.decisions)
               and autoscaler.n_replicas() > min_replicas
               and time.perf_counter() - t_tail0 < 25.0):
            prompt = r.randint(1, vocab,
                               size=int(r.choice(plens))).tolist()
            arrivals.append((time.perf_counter() - bench_t0,
                             float(olen_i)))
            records.append(router.dispatch(
                prompt, olen_i, None, f"cb-x{k:03d}", "interactive"))
            k += 1
            time.sleep(0.7)
        autoscaler.stop()
        attainment = autoscaler.finalize(records)
        snap = router.snapshot()

        # -- the judged numbers: oracle schedule + scale regret ---------
        horizon = max(traffic_wall,
                      max((t for t, _ in arrivals), default=0.0),
                      max((d["time_unix"] - bench_t0_unix
                           for d in autoscaler.decisions), default=0.0)
                      + 1e-3)
        oracle = _capacity.oracle_schedule(
            arrivals, capacity_tokens_per_sec=cap_measured,
            window_s=window_s, max_replicas=max_replicas,
            min_replicas=min_replicas, horizon_s=horizon)
        events = [(0.0, 1)]
        for d in autoscaler.decisions:
            if d["action"] in ("scale_up", "scale_down"):
                events.append((max(0.0, d["time_unix"] - bench_t0_unix),
                               int(d["to_replicas"])))
        actual = _capacity.schedule_windows(events, horizon, window_s,
                                            initial_replicas=1)
        regret = _capacity.scale_regret(actual, oracle)

        # -- teardown -> journals + traces on disk ----------------------
        router.stop()
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=15)
            except subprocess.TimeoutExpired:
                p.kill()
        router.flush_ledger(serve_dir)
        _profiler.flush_trace(os.path.join(trace_dir,
                                           "trace.router.json"))
        _profiler.clear_events()

        merged = _ledger.load_journals(serve_dir,
                                       ranks=range(max_replicas))
        slo = _ledger.slo_summary(merged) if merged else {}

        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        try:
            import timeline as _timeline
        finally:
            sys.path.pop(0)
        by_proc = _timeline.load_serve_traces(trace_dir)
        merged_trace = _timeline.merge_serve_traces(by_proc)
        _timeline.validate_chrome_trace(merged_trace)
        scale_events = merged_trace["metadata"].get("scale_events", 0)

        decisions = autoscaler.decisions
        n_up = sum(1 for d in decisions if d["action"] == "scale_up")
        n_down = sum(1 for d in decisions
                     if d["action"] == "scale_down")
        drained_downs = sum(1 for d in decisions
                            if d["action"] == "scale_down"
                            and d.get("drained"))
        lat = [rec["latency_s"] for rec in records
               if rec.get("latency_s") is not None]
        n_ok = sum(1 for rec in records if rec.get("ok"))

        by_class = attainment["by_class"]
        ok = bool(
            n_up >= 1 and n_down >= 1 and drained_downs >= 1
            and attainment["overall"] is not None
            and all(klass in by_class for klass in slo_classes)
            and math.isfinite(regret["scale_regret"])
            and (merged or {}).get("autoscale")
            and ((merged or {}).get("autoscale") or {}).get("decisions")
            and scale_events >= 2)

        parsed: Dict[str, Any] = {
            "metric": "serve_slo_attainment",
            "unit": "fraction of requests inside their class SLO "
                    "(autoscale round; scale_regret vs the post-hoc "
                    "oracle alongside)",
            "mode": "autoscale",
            "model": {"n_layer": n_layer, "d_model": d_model,
                      "n_head": n_head, "vocab_size": vocab,
                      "max_seq_len": max_seq_len},
            "engine": {"max_batch": max_batch, "kv_blocks": kv_blocks,
                       "block_size": block_size,
                       "prefill_buckets": prefill_buckets,
                       "replicas": max_replicas},
            "slo_classes": slo_classes,
            "traffic": {
                "phases": phase_edges,
                "requests": len(records),
                "prompt_lens": plens,
                "olen_interactive": olen_i, "olen_batch": olen_b,
                "rate_batch_per_s": rate_batch,
                "tail_trickle_requests": k,
                "seed": seed,
                "retries": retries, "backoff_ms": backoff_ms,
                "hedge_ms": hedge_ms,
            },
            "bench_wall_seconds": round(traffic_wall, 4),
            # the two gated headlines (perf_gate SERVE pattern):
            # slo_attainment higher-is-better, scale_regret
            # lower-is-better vs the oracle built from the same trace
            "slo_attainment": attainment["overall"],
            "slo_attainment_by_class": by_class,
            "scale_regret": regret["scale_regret"],
            "utilization": {
                "actual_replica_seconds":
                    regret["actual_replica_seconds"],
                "oracle_replica_seconds":
                    regret["oracle_replica_seconds"],
                "mean_replicas": round(
                    regret["actual_replica_seconds"]
                    / max(horizon, 1e-9), 4),
                "over_provisioned_windows":
                    regret["over_provisioned_windows"],
                "under_provisioned_windows":
                    regret["under_provisioned_windows"],
                "batch_occupancy": (merged or {}).get(
                    "batch_occupancy"),
            },
            "oracle": {
                "window_s": window_s,
                "capacity_tokens_per_sec_per_replica":
                    round(cap_measured, 2),
                "windows": [w["replicas"] for w in oracle["windows"]],
                "final_backlog_tokens": oracle["final_backlog_tokens"],
            },
            "actual_schedule": actual,
            # the AOT legs the planner scored with: serve_plan can
            # re-decide straight off this committed round
            "roofline": roofline,
            "autoscale": {
                "plan": autoscaler.current_plan,
                "decisions": decisions,
                "n_scale_up": n_up, "n_scale_down": n_down,
                "n_drained_scale_down": drained_downs,
                "boot_seconds": [d.get("boot_seconds")
                                 for d in decisions
                                 if d["action"] == "scale_up"],
                # the pair future rounds calibrate against: the raw
                # roofline prediction vs the saturation-measured
                # request-level rate at this exact config
                "calibration_pair": {
                    "config": f"r1/tp1/mb{max_batch}",
                    "predicted_tokens_per_sec_per_replica":
                        cap_predicted,
                    "measured_tokens_per_sec_per_replica":
                        round(cap_measured, 2),
                },
                "calibration_used": calibration,
            },
            "traffic_telemetry": (merged or {}).get("traffic"),
            "requests_ok": n_ok,
            "requests_failed": len(records) - n_ok,
            "client_p50_latency_s": _percentile(sorted(lat), 0.50),
            "client_p99_latency_s": _percentile(sorted(lat), 0.99),
            "router": snap["stats"],
            "trace": {
                "dir": trace_dir if not own_tmp else None,
                "processes": merged_trace["metadata"]["processes"],
                "scale_events": scale_events,
            },
        }
        if merged:
            parsed["engine_slo"] = {
                "tokens_per_sec": round(
                    merged.get("tokens_per_sec") or 0.0, 2),
                "decode_tokens": merged.get("decode_tokens"),
                "ttft_s": slo["ttft"]["avg"],
                "p99_ttft_s": slo["ttft"]["p99"],
                "p50_latency_s": slo["latency"]["p50"],
                "p99_latency_s": slo["latency"]["p99"],
                "batch_occupancy": merged.get("batch_occupancy"),
            }
            parsed["n_replicas_merged"] = merged.get("n_replicas")
        parsed["ok"] = ok
        if verbose:
            att_str = ", ".join(
                f"{klass}={c.get('attainment')}"
                for klass, c in sorted(by_class.items()))
            print(f"autoscale round {'PASS' if ok else 'FAIL'}: "
                  f"{n_ok}/{len(records)} ok, attainment "
                  f"{attainment['overall']} ({att_str}), "
                  f"scale_regret {regret['scale_regret']} "
                  f"(actual {actual} vs oracle "
                  f"{[w['replicas'] for w in oracle['windows']]}), "
                  f"{n_up} scale-up(s) / {n_down} scale-down(s) "
                  f"({drained_downs} drained), capacity "
                  f"{cap_measured:.1f} tok/s/replica (predicted "
                  f"{cap_predicted:.1f}), {scale_events} scale "
                  f"instant(s) in the merged trace")
        return parsed
    finally:
        if autoscaler is not None:
            autoscaler.stop()
        if router is not None:
            router.stop()
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        if own_tmp:
            shutil.rmtree(base, ignore_errors=True)


def autoscale_self_test(verbose: bool = True) -> Dict[str, Any]:
    """In-process autoscale-plumbing smoke (tier-1): the forecast
    blend/widening math pinned to hand-computed values, the oracle
    schedule + scale-regret arithmetic on a known trace, per-class SLO
    attainment, and the REAL Autoscaler over scripted drainable stubs
    proving the action contract — scale-up journals a typed record
    with its forecast snapshot, scale-down ALWAYS drains first, and
    both land as instant events in the flushed trace."""
    import math
    import tempfile

    from paddle_tpu import profiler as _profiler
    from paddle_tpu.serving import capacity as _capacity
    from paddle_tpu.serving.router import Router

    # 1) forecast: 1/h-weighted horizon blend + CV-widened upper bound
    traffic = {
        "horizons_s": [1.0, 10.0, 60.0],
        "classes": {"interactive": {
            "n": 20, "rate_ema": {"1s": 12.0, "10s": 6.0, "60s": 2.0},
            "interarrival": {"cv": 1.5},
        }},
        "series": [{"queued": 3, "inflight": 2}],
        "depth_summary": {"queued_mean": 1.5, "queued_max": 3},
    }
    fc = _capacity.forecast_demand(traffic, cv_widen=1.0)
    blend = (12.0 / 1 + 6.0 / 10 + 2.0 / 60) / (1 + 0.1 + 1 / 60)
    cls = fc["classes"]["interactive"]
    assert abs(cls["rate_blend_per_s"] - blend) < 1e-3, fc
    assert abs(cls["rate_upper_per_s"] - blend * 2.5) < 1e-3, fc
    assert cls["cv_measured"] and fc["backlog"]["queued_last"] == 3, fc

    # 2) oracle + actual schedule + regret on a hand trace: a 2-window
    # burst the capacity cap saturates (backlog carries, clamped at 2)
    arrivals = [(0.5, 10.0), (1.5, 10.0), (2.5, 40.0), (3.5, 40.0),
                (4.5, 10.0)]
    oracle = _capacity.oracle_schedule(
        arrivals, capacity_tokens_per_sec=10.0, window_s=1.0,
        max_replicas=2, min_replicas=1)
    assert [w["replicas"] for w in oracle["windows"]] == \
        [1, 1, 2, 2, 2], oracle
    assert oracle["replica_seconds"] == 8.0, oracle
    actual = _capacity.schedule_windows(
        [(0.0, 1), (3.0, 2), (4.6, 1)], 5.0, 1.0, initial_replicas=1)
    assert actual == [1, 1, 1, 2, 2], actual
    reg = _capacity.scale_regret(actual, oracle)
    assert abs(reg["scale_regret"] - 1.0 / 8.0) < 1e-9, reg
    assert reg["under_provisioned_windows"] == 1, reg

    # 3) per-class attainment recomputed against the class table (a
    # record with a laundered deadline still counts as a miss)
    classes = _capacity.parse_slo_classes(
        "interactive:slo=1,weight=3,hedge=1;batch:slo=30,weight=1")
    att = _capacity.slo_attainment([
        {"traffic_class": "interactive", "ok": True, "latency_s": 0.5,
         "time_unix": 1.0},
        {"traffic_class": "interactive", "ok": True, "latency_s": 2.0,
         "time_unix": 2.0, "deadline_s": 30.0},  # laundered: still late
        {"traffic_class": "batch", "ok": True, "latency_s": 8.0,
         "time_unix": 3.0},
        {"traffic_class": "batch", "ok": False, "latency_s": 0.1,
         "time_unix": 4.0},
    ], classes)
    assert att["by_class"]["interactive"]["attainment"] == 0.5, att
    assert att["by_class"]["batch"]["attainment"] == 0.5, att
    assert att["overall"] == 0.5 and att["requests"] == 4, att

    # 4) the REAL Autoscaler over drainable stubs: forecast flip ->
    # scale-up, decay -> drain-first scale-down, typed journal records
    class _DrainableStub(_StubReplica):
        def __init__(self, name):
            super().__init__(name, [])
            self.draining = False

        def drain(self, timeout=1.0):
            self.draining = True
            return {"draining": True}

        def healthz(self, timeout=1.0):
            return {"status": "ok",
                    "serving": {"draining": self.draining,
                                "drained": self.draining, "queued": 0}}

    class _TelemetryStub:
        def __init__(self):
            self.traffic = {}

        def snapshot(self):
            return self.traffic

        def note_arrival(self, klass, now=None):
            pass

        def note_depth(self, *a, **k):
            pass

    stub0 = _DrainableStub("replica0")
    router = Router([stub0], retries=1, backoff_ms=1.0, hedge_ms=0.0,
                    default_slo_s=5.0, seed=0)
    telem = _TelemetryStub()
    router.telemetry = telem
    spawned, stopped = [], []

    def _spawn(index):
        c = _DrainableStub(f"replica{index}")
        spawned.append(c)
        return c

    def _stop(name):
        stopped.append(name)

    roofline = {"legs": {"compute_s": 2e-4, "memory_s": 1e-3,
                         "dispatch_s": 1e-5}, "mean_active": 4.0}
    _profiler.clear_events()
    _profiler.enable_tracing()
    try:
        auto = _capacity.Autoscaler(
            router, roofline, spawn_replica=_spawn, stop_replica=_stop,
            device_budget=2, tp=1, max_batch=4,
            slo_classes=_capacity.parse_slo_classes(
                "interactive:slo=3,weight=3,hedge=1;"
                "batch:slo=30,weight=1,hedge=0"),
            min_replicas=1, max_replicas=2, interval_s=0.1,
            cooldown_s=0.0, headroom=0.15, tokens_per_request=8.0,
            tp_degrees=(1,), max_batches=(4,))
        # the class table re-tuned the router
        assert router.slo_classes and "interactive" in \
            router.slo_classes, router.slo_classes

        # per-replica capacity 4/1e-3 = 4000 tok/s; 500 req/s upper
        # 1000 -> demand 8000 tok/s: r1 AND r2 infeasible -> hold at max
        telem.traffic = {
            "horizons_s": [1.0],
            "classes": {"interactive": {
                "n": 100, "rate_ema": {"1s": 500.0},
                "interarrival": {"cv": 1.0}}},
        }
        rec_up = auto.step()
        assert rec_up and rec_up["action"] == "scale_up", rec_up
        assert rec_up["boot_seconds"] is not None, rec_up
        assert rec_up["inputs"]["forecast"][
            "total_rate_upper_per_s"] == 1000.0, rec_up
        assert auto.n_replicas() == 2 and spawned, rec_up
        assert "replica1" in router.replica_names(), \
            router.replica_names()

        # decay: 10 req/s -> 160 tok/s demand, r1 comfortably feasible
        telem.traffic = {
            "horizons_s": [1.0],
            "classes": {"interactive": {
                "n": 120, "rate_ema": {"1s": 10.0},
                "interarrival": {"cv": 1.0}}},
        }
        rec_down = auto.step()
        assert rec_down and rec_down["action"] == "scale_down", rec_down
        actions = [d["action"] for d in auto.decisions]
        i_down = actions.index("scale_down")
        # the ordering contract: drain_start journaled IMMEDIATELY
        # before the take-down, and the drain actually completed
        assert actions[i_down - 1] == "drain_start", actions
        assert rec_down["drained"] is True, rec_down
        assert spawned[0].draining, "scale-down did not drain the stub"
        assert stopped == ["replica1"], stopped
        assert auto.n_replicas() == 1, auto.managed
        assert router.replica_names() == ["replica0"], \
            router.replica_names()
        # the plan carries a spec again and predictions ride the record
        assert auto.current_plan["spec"] == "r1/tp1/mb4", \
            auto.current_plan
        assert rec_down["predicted_slo_attainment"], rec_down

        # realized attainment back-fills per decision window
        t_up = auto.decisions[0]["time_unix"]
        t_down = auto.decisions[-1]["time_unix"]
        mid = (t_up + t_down) / 2.0
        recs = [
            {"traffic_class": "interactive", "ok": True,
             "latency_s": 0.5, "time_unix": mid},
            {"traffic_class": "interactive", "ok": True,
             "latency_s": 10.0, "time_unix": mid},
            {"traffic_class": "interactive", "ok": True,
             "latency_s": 0.4, "time_unix": t_down + 1.0},
        ]
        overall = auto.finalize(recs)
        assert auto.decisions[0]["realized_slo_attainment"][
            "interactive"] == 0.5, auto.decisions[0]
        assert auto.decisions[-1]["realized_slo_attainment"][
            "interactive"] == 1.0, auto.decisions[-1]
        assert abs(overall["overall"] - 2.0 / 3.0) < 1e-3, overall
        # the decisions rode into the router's journal doc
        doc = router.ledger_doc()
        assert doc.get("autoscale") and \
            doc["autoscale"].get("decisions"), doc.get("autoscale")

        # the scale instants are in the flushed trace, typed
        with tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, "trace.json")
            _profiler.flush_trace(path)
            with open(path) as f:
                events = json.load(f)["traceEvents"]
        scale = [e for e in events if e.get("cat") == "serve_scale"]
        assert len(scale) >= 3, len(scale)
        assert all(e["ph"] == "i" and "dur" not in e for e in scale), \
            scale[:2]
        names = {e["args"]["action"] for e in scale}
        assert {"scale_up", "drain_start", "scale_down"} <= names, names
    finally:
        _profiler.clear_events()
        router.stop()

    # 5) perf_gate catches a regressing autoscale trajectory through
    # the SERVE pattern: a -10pp attainment drop and a +10pp regret
    # rise must each fail the gate (history synthesized where rounds
    # predate the autoscale metrics)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import perf_gate
    finally:
        sys.path.pop(0)
    history = perf_gate.load_history(REPO_ROOT, pattern="SERVE_r*.json")
    if len(history) < 2:
        history = perf_gate._synthetic_serve_history()
    history = perf_gate._augment_autoscale_history(history)
    current = json.loads(json.dumps(history[-1]))
    tols = perf_gate._self_test_tolerances(current, history)
    rows_ok, ok = perf_gate.gate(current, history, tolerances=tols)
    assert ok, rows_ok
    missing_bursts = json.loads(json.dumps(current))
    perf_gate.parsed_result(missing_bursts)["slo_attainment"] -= 0.10
    rows_att, ok_att = perf_gate.gate(missing_bursts, history,
                                      tolerances=tols)
    assert not ok_att, "-10pp slo_attainment slipped through the gate"
    assert {r["check"]: r["verdict"] for r in rows_att}[
        "slo_attainment"] == "REGRESSION", rows_att
    thrashing = json.loads(json.dumps(current))
    p = perf_gate.parsed_result(thrashing)
    p["scale_regret"] = (p.get("scale_regret") or 0.0) + 0.10
    rows_reg, ok_reg = perf_gate.gate(thrashing, history,
                                      tolerances=tols)
    assert not ok_reg, "+10pp scale_regret slipped through the gate"
    assert {r["check"]: r["verdict"] for r in rows_reg}[
        "scale_regret"] == "REGRESSION", rows_reg

    if verbose:
        print(f"autoscale self-test OK ({len(history)} SERVE round(s) "
              f"in the gate smoke)")
    return {"forecast": fc, "oracle": oracle, "regret": reg,
            "attainment": att,
            "gate_attainment_rows": rows_att,
            "gate_regret_rows": rows_reg}


# ---------------------------------------------------------------------------
# chaos mode: in-process CI smoke (--chaos --self-test)
# ---------------------------------------------------------------------------


class _StubReplica:
    """Scripted replica client for the in-process self-test: each submit
    pops the next canned behavior ('ok' returns deterministic tokens,
    'fail' raises typed Unavailable)."""

    def __init__(self, name: str, script: List[str]):
        self.name = name
        self.script = list(script)
        self.submits = 0

    def submit(self, prompt, max_new_tokens, deadline_s, request_id,
               timeout, trace=None):
        from paddle_tpu.framework import errors as _errors

        self.submits += 1
        step = self.script.pop(0) if self.script else "ok"
        if step == "fail":
            e = _errors.errors.Unavailable(
                f"{self.name} scripted failure")
            e.reason = "connect"
            raise e
        tokens = [(int(t) * 7 + i) % 97
                  for i, t in enumerate(list(prompt)[:max_new_tokens])]
        return {"tokens": tokens, "cached": False}

    def healthz(self, timeout=1.0):
        return {"status": "ok", "serving": {"draining": False,
                                            "queued": 0}}

    def drain(self, timeout=1.0):
        return {"draining": True}


def chaos_self_test(verbose: bool = True) -> Dict[str, Any]:
    """In-process chaos-plumbing smoke (tier-1): availability/error-rate
    math, the chaos record's verdict logic, the REAL router retrying a
    typed failure onto a second replica (bit-identical stub tokens),
    and perf_gate catching an injected availability drop + error-rate
    rise over the SERVE pattern."""
    from paddle_tpu.serving.router import Router

    # 1) availability / error-rate math over synthetic records
    recs = [
        {"ok": True, "within_deadline": True, "latency_s": 0.5,
         "time_unix": 100.0, "n_attempts": 1, "attempts": [{"ok": True}]},
        {"ok": True, "within_deadline": True, "latency_s": 0.9,
         "time_unix": 101.0, "n_attempts": 2, "failover": True,
         "attempts": [{"ok": False, "error_type": "UnavailableError",
                       "reason": "connect"}, {"ok": True}]},
        {"ok": True, "within_deadline": False, "latency_s": 31.0,
         "time_unix": 102.0, "n_attempts": 1, "attempts": [{"ok": True}]},
        {"ok": False, "within_deadline": False, "latency_s": 2.0,
         "time_unix": 103.0, "n_attempts": 3,
         "attempts": [{"ok": False, "error_type": "UnavailableError",
                       "reason": "timeout"}] * 3},
    ]
    avail = availability_summary(recs)
    assert avail["requests"] == 4 and avail["ok_within_slo"] == 2, avail
    assert avail["availability"] == 0.5, avail
    assert avail["error_rate"] == 0.25, avail
    assert avail["late"] == 1 and avail["failed"] == 1, avail
    assert avail["typed_failures"] and avail["no_hang"], avail
    assert avail["redispatched"] == 2 and avail["failovers"] == 1, avail
    untyped = [dict(recs[3],
                    attempts=[{"ok": False, "error_type": "OSError"}])]
    assert not availability_summary(untyped)["typed_failures"]
    hung = [dict(recs[3],
                 attempts=[{"ok": False,
                            "error_type": "ExecutionTimeoutError",
                            "reason": "hang"}])]
    assert not availability_summary(hung)["no_hang"]
    dip = failover_window_latency(recs, 100.5, 102.5)
    assert dip["n_in_window"] == 2 and dip["p99_failover_s"] == 31.0, dip

    # 2) the chaos record's verdict logic
    good = dict(
        replicas=2, victim_rank=1, kill_tick=40, killed_exit_code=43,
        kill_exit_expected=43, availability=0.975, error_rate=0.0,
        detection_seconds=0.4, recovery_seconds=12.5,
        typed_failures=True, no_hang=True, respawned=True, rejoined=True,
        requests_redispatched=3,
        redispatch_bit_match={"checked": 3, "matched": 3, "ok": True},
        p99_dip={"available": True})
    rec = build_chaos_record(**good)
    assert rec["ok"], rec
    for key in REQUIRED_CHAOS_KEYS:
        assert key in rec, f"chaos record missing {key}"
    assert not build_chaos_record(**{**good, "killed_exit_code": 1})["ok"]
    assert not build_chaos_record(**{**good, "typed_failures": False})["ok"]
    assert not build_chaos_record(**{**good, "rejoined": False})["ok"]
    assert not build_chaos_record(**{**good, "availability": 0.90})["ok"]
    assert not build_chaos_record(
        **{**good, "requests_redispatched": 0,
           "redispatch_bit_match": {"checked": 0, "matched": 0}})["ok"]
    assert not build_chaos_record(
        **{**good,
           "redispatch_bit_match": {"checked": 3, "matched": 2}})["ok"]
    assert not build_chaos_record(**{**good, "recovery_seconds": None})["ok"]

    # 3) the REAL router over scripted replicas: a typed first-attempt
    # failure fails over (with backoff) and the record says so
    a = _StubReplica("a", ["fail"])
    b = _StubReplica("b", [])
    router = Router([a, b], retries=2, backoff_ms=1.0, hedge_ms=0,
                    default_slo_s=10.0, seed=3)
    out = router.dispatch([5, 6, 7], max_new_tokens=3, request_id="st-1")
    assert out["ok"] and out["n_attempts"] == 2, out
    assert out["failover"] is True, out
    assert out["attempts"][0]["error_type"] == "UnavailableError", out
    # the stub token function is replica-independent, like greedy decode
    # over identical params: a replay must bit-match
    again = router.dispatch([5, 6, 7], max_new_tokens=3,
                            request_id="st-1-verify")
    assert again["tokens"] == out["tokens"], (again, out)
    assert router.snapshot()["stats"]["retries"] >= 1
    router.stop()

    # 4) perf_gate catches the injected availability drop + error-rate
    # rise through the SERVE pattern (history synthesized where rounds
    # predate the chaos metrics)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import perf_gate
    finally:
        sys.path.pop(0)
    history = perf_gate.load_history(REPO_ROOT, pattern="SERVE_r*.json")
    if len(history) < 2:
        history = perf_gate._synthetic_serve_history()
    history = perf_gate._augment_serve_chaos_history(history)
    current = json.loads(json.dumps(history[-1]))
    tols = perf_gate._self_test_tolerances(current, history)
    rows_ok, ok = perf_gate.gate(current, history, tolerances=tols)
    assert ok, rows_ok
    dropped = json.loads(json.dumps(current))
    perf_gate.parsed_result(dropped)["availability"] *= 0.9
    rows_bad, ok_bad = perf_gate.gate(dropped, history, tolerances=tols)
    assert not ok_bad, "-10% availability slipped through the gate"
    assert {r["check"]: r["verdict"] for r in rows_bad}[
        "availability"] == "REGRESSION", rows_bad
    flaky = json.loads(json.dumps(current))
    p = perf_gate.parsed_result(flaky)
    p["error_rate"] = (p.get("error_rate") or 0.0) + 0.05
    rows_err, ok_err = perf_gate.gate(flaky, history, tolerances=tols)
    assert not ok_err, "+5pp error_rate slipped through the gate"
    assert {r["check"]: r["verdict"] for r in rows_err}[
        "error_rate"] == "REGRESSION", rows_err

    if verbose:
        print(f"serve_bench chaos self-test OK ({len(history)} SERVE "
              f"round(s) in the gate smoke)")
    return {"availability": avail, "record": rec,
            "router_record": out,
            "gate_availability_rows": rows_bad,
            "gate_error_rate_rows": rows_err}


# ---------------------------------------------------------------------------
# CI smoke (--self-test)
# ---------------------------------------------------------------------------


def self_test(verbose: bool = True) -> Dict[str, Any]:
    """A tiny threaded round that must produce a structurally complete
    SERVE record: every gated metric present, buckets summing to wall,
    every request accounted for, and both reconciliation verdicts
    rendered (the span one must PASS — it audits the bench's own
    plumbing; the roofline one may be outside_bound on a noisy host but
    must carry its bound factors)."""
    parsed = run_bench(n_layer=1, d_model=32, n_head=2, vocab=128,
                       max_seq_len=64, max_batch=4, kv_blocks=32,
                       block_size=8, prefill_buckets="16,32",
                       requests=10, rate=200.0, prompt_lens="4,9",
                       output_lens="3,6", seed=3, verbose=verbose)
    for key in ("tokens_per_sec", "ttft_s", "p50_latency_s",
                "p99_latency_s", "batch_occupancy",
                "kv_block_utilization"):
        assert parsed.get(key) is not None and parsed[key] >= 0, (
            key, parsed.get(key))
    assert parsed["tokens_per_sec"] > 0, parsed
    assert parsed["requests_ok"] == 10, parsed
    assert parsed["requests_failed"] == 0, parsed
    g = parsed["goodput"]
    assert abs(g["buckets_sum_seconds"]
               - parsed["engine_wall_seconds"]) < 1e-3, g
    assert g["top_badput"] is not None, g
    span = parsed["reconciliations"]["span_vs_wall"]
    assert span["verdict"] == "within_bound", span
    roof = parsed["reconciliations"]["measured_vs_roofline"]
    assert roof["verdict"] in ("within_bound", "outside_bound"), roof
    assert roof["bound_factors"], roof
    assert roof["bound_by"] in roof["bound_factors"], roof
    # per-request attribution: the engine-side buckets sum to each e2e
    # by construction, so a healthy round's residual must sit inside
    # the gate's acceptance bound
    attr = parsed["attribution"]
    assert attr["reconciliation"]["verdict"] == "within_bound", attr
    assert attr["summary"]["classes"]["engine"]["n"] == 10, attr
    assert parsed["attribution_residual"] is not None, parsed
    assert parsed["attribution_residual"] <= 0.05, parsed
    if verbose:
        print("self-test OK")
    return parsed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n-layer", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--n-head", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--max-seq-len", type=int, default=128)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--kv-blocks", type=int, default=96)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--prefill-buckets", default="16,32,64")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=30.0,
                    help="Poisson arrival rate (requests/s)")
    ap.add_argument("--prompt-lens", default="4,8,12,24")
    ap.add_argument("--output-lens", default="4,8,16")
    ap.add_argument("--slo-s", type=float, default=30.0)
    ap.add_argument("--recipe", default=None,
                    help="decode sharding recipe (parallel/recipes.py), "
                    "e.g. tp")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sync", action="store_true",
                    help="drive the engine synchronously (no scheduler "
                    "thread; deterministic, but queue_wait is not "
                    "measured)")
    ap.add_argument("--out", help="write the SERVE json here")
    ap.add_argument("--self-test", action="store_true",
                    help="CI smoke: tiny round, structural assertions "
                    "(with --chaos: the in-process chaos-plumbing smoke)")
    ap.add_argument("--chaos", action="store_true",
                    help="availability-under-chaos round: >=2 real "
                    "replica processes, Poisson load through the "
                    "router, one replica killed mid-run + warm restart")
    ap.add_argument("--multi", action="store_true",
                    help="steady >=2-replica observability round: "
                    "cross-process tracing, forced retry + forced "
                    "hedge, merged per-request attribution + traffic "
                    "telemetry")
    ap.add_argument("--autoscale", action="store_true",
                    help="autoscale round: the capacity planner live "
                    "over real replica processes under a quiet -> "
                    "burst -> quiet trace; one warm-restart scale-up + "
                    "one drained scale-down, judged on per-class SLO "
                    "attainment and scale_regret vs the post-hoc "
                    "oracle (with --self-test: the in-process "
                    "planner-plumbing smoke)")
    ap.add_argument("--slo-classes", default=None,
                    help="SLO class table for the autoscale round, "
                    "e.g. 'interactive:slo=2,weight=3,hedge=1;"
                    "batch:slo=30,weight=1,hedge=0'")
    ap.add_argument("--replica", action="store_true",
                    help="internal: run one serving replica "
                    "(supervisor-spawned)")
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=2,
                    help="replica processes in the chaos round")
    ap.add_argument("--kill-tick", type=int, default=40,
                    help="decode tick at which the armed victim dies")
    ap.add_argument("--victim", type=int, default=1,
                    help="replica rank the replica_kill site is armed "
                    "for")
    ap.add_argument("--retries", type=int, default=3,
                    help="router re-dispatch budget in the chaos round")
    ap.add_argument("--backoff-ms", type=float, default=50.0)
    ap.add_argument("--hedge-ms", type=float, default=0.0,
                    help="router hedge window (0 = no hedging)")
    ap.add_argument("--recovery-timeout", type=float, default=180.0)
    ap.add_argument("--workdir", default=None,
                    help="keep the chaos round's journals/logs here "
                    "(default: a deleted temp dir)")
    args = ap.parse_args(argv)

    if args.replica:
        return replica_main(args)
    if args.chaos and args.self_test:
        chaos_self_test()
        return 0
    if args.autoscale and args.self_test:
        autoscale_self_test()
        return 0
    if args.self_test:
        self_test()
        return 0
    if args.autoscale:
        kwargs = dict(
            n_layer=args.n_layer, d_model=args.d_model,
            n_head=args.n_head, vocab=args.vocab,
            max_seq_len=args.max_seq_len,
            max_batch=min(args.max_batch, 4),
            kv_blocks=args.kv_blocks, block_size=args.block_size,
            prefill_buckets=args.prefill_buckets,
            prompt_lens=args.prompt_lens, retries=args.retries,
            backoff_ms=args.backoff_ms,
            hedge_ms=args.hedge_ms if args.hedge_ms > 0 else 40.0,
            seed=args.seed, workdir=args.workdir)
        if args.slo_classes:
            kwargs["slo_classes_spec"] = args.slo_classes
        parsed = run_autoscale_round(**kwargs)
        doc = {"schema": SCHEMA, "rc": 0 if parsed.get("ok") else 1,
               "time_unix": time.time(), "parsed": parsed}
        out = json.dumps(doc, indent=1)
        if args.out:
            with open(args.out, "w") as f:
                f.write(out + "\n")
            print(f"wrote {args.out}")
        else:
            print(out)
        return 0 if parsed.get("ok") else 1
    if args.multi:
        parsed = run_multi_round(
            replicas=args.replicas, requests=args.requests,
            rate=args.rate, n_layer=args.n_layer, d_model=args.d_model,
            n_head=args.n_head, vocab=args.vocab,
            max_seq_len=args.max_seq_len, max_batch=args.max_batch,
            kv_blocks=args.kv_blocks, block_size=args.block_size,
            prefill_buckets=args.prefill_buckets,
            prompt_lens=args.prompt_lens, output_lens=args.output_lens,
            slo_s=args.slo_s, retries=args.retries,
            backoff_ms=args.backoff_ms,
            hedge_ms=args.hedge_ms if args.hedge_ms > 0 else 40.0,
            seed=args.seed, workdir=args.workdir)
        doc = {"schema": SCHEMA, "rc": 0 if parsed.get("ok") else 1,
               "time_unix": time.time(), "parsed": parsed}
        out = json.dumps(doc, indent=1)
        if args.out:
            with open(args.out, "w") as f:
                f.write(out + "\n")
            print(f"wrote {args.out}")
        else:
            print(out)
        return 0 if parsed.get("ok") else 1
    if args.chaos:
        parsed = run_chaos_round(
            replicas=args.replicas, requests=args.requests,
            rate=args.rate, n_layer=args.n_layer, d_model=args.d_model,
            n_head=args.n_head, vocab=args.vocab,
            max_seq_len=args.max_seq_len, max_batch=args.max_batch,
            kv_blocks=args.kv_blocks, block_size=args.block_size,
            prefill_buckets=args.prefill_buckets,
            prompt_lens=args.prompt_lens, output_lens=args.output_lens,
            slo_s=args.slo_s, kill_tick=args.kill_tick,
            victim=args.victim, retries=args.retries,
            backoff_ms=args.backoff_ms, hedge_ms=args.hedge_ms,
            seed=args.seed, recovery_timeout=args.recovery_timeout,
            workdir=args.workdir)
        doc = {"schema": SCHEMA, "rc": 0 if parsed.get("ok") else 1,
               "time_unix": time.time(), "parsed": parsed}
        out = json.dumps(doc, indent=1)
        if args.out:
            with open(args.out, "w") as f:
                f.write(out + "\n")
            print(f"wrote {args.out}")
        else:
            print(out)
        return 0 if parsed.get("ok") else 1

    parsed = run_bench(
        n_layer=args.n_layer, d_model=args.d_model, n_head=args.n_head,
        vocab=args.vocab, max_seq_len=args.max_seq_len,
        max_batch=args.max_batch, kv_blocks=args.kv_blocks,
        block_size=args.block_size, prefill_buckets=args.prefill_buckets,
        requests=args.requests, rate=args.rate,
        prompt_lens=args.prompt_lens, output_lens=args.output_lens,
        slo_s=args.slo_s, recipe=args.recipe, seed=args.seed,
        threaded=not args.sync)
    doc = {"schema": SCHEMA, "rc": 0, "time_unix": time.time(),
           "parsed": parsed}
    out = json.dumps(doc, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")
        print(f"wrote {args.out}")
    else:
        print(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
