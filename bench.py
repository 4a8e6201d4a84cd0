"""Headline benchmark: GPT pretraining step throughput + MFU on one chip.

The reference publishes no in-repo numbers (BASELINE.md); the north star is
ERNIE/BERT-class pretraining at >= A100-NCCL MFU. Two configs run, each a
full training step (forward, backward, Adam) as one XLA program:

- gpt2s @ seq 512 (the round-1/2 headline, XLA-fused attention path)
- gpt2s @ seq 2048 (long sequence: the pallas flash-attention kernel's
  regime — the bench asserts via ops.attention.FLASH_DISPATCH_COUNT that
  the flash path was actually dispatched at trace time)

Every number it prints is a device rate, so it needs a TPU whose
``device_kind`` is in paddle_tpu.device.DEVICE_PEAKS and fails on any
other platform (chip_smoke.py is the quick on-chip proof; tests run on
CPU). Not measured on current code: the last recorded rounds predate
PR 1 and were taken on another installation.

Prints ONE JSON line: the headline {"metric", "value", "unit",
"vs_baseline"} plus a "long_seq" sub-object with the seq-2048 numbers.
"""
import json
import os
import time

import numpy as np


def bench_config(batch, seq, iters, n_layer=12, n_head=12, d_model=768):
    import jax

    from paddle_tpu import goodput as _goodput
    from paddle_tpu import memwatch as _memwatch
    from paddle_tpu.device import device_peaks
    from paddle_tpu.framework import Executor, Scope, program_guard
    from paddle_tpu.framework import shard_insight as _shard
    from paddle_tpu.models.gpt import GPTConfig, build_train_program
    from paddle_tpu.optimizer import Adam

    # per-config HBM window: everything from build through the timed
    # loops contributes to this config's measured peak watermark
    _memwatch.reset_window()
    # per-config comms window: the measured collective byte counters at
    # config start, so predicted-vs-measured reconciles over exactly the
    # steps this config ran
    coll_before = _shard.measured_collective_bytes()

    cfg = GPTConfig(
        vocab_size=32768,
        n_layer=n_layer,
        n_head=n_head,
        d_model=d_model,
        max_seq_len=seq,
        dtype="bfloat16",
    )
    main_prog, startup, io = build_train_program(cfg, batch=batch, seq=seq)
    with program_guard(main_prog, startup):
        Adam(learning_rate=1e-4).minimize(io["loss"])

    scope = Scope()
    exe = Executor()
    exe.run(startup, scope=scope)

    n_params = sum(int(np.prod(p.shape)) for p in main_prog.all_parameters())

    r = np.random.RandomState(0)
    # device-resident feeds: the measured loop is the training step, not
    # the h2d transfer (the DataLoader path overlaps transfers with compute)
    tokens = jax.device_put(r.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64))
    labels = jax.device_put(r.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64))
    feed = {"tokens": tokens, "labels": labels}

    # compile + warmup
    for _ in range(3):
        loss = exe.run(main_prog, feed=feed, fetch_list=[io["loss"]], scope=scope)[0]
    assert np.isfinite(float(loss)), loss

    # three timed windows; the headline uses the MEDIAN window, and all
    # windows are reported alongside so the spread is auditable
    dts = []
    gp_before = _goodput.totals()["buckets"]
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = exe.run(main_prog, feed=feed, fetch_list=[io["loss"]], scope=scope, return_numpy=False)
        # fetching the final value to the host closes the window
        assert np.isfinite(float(np.asarray(out[0])))
        dts.append(time.perf_counter() - t0)
    med_dt = sorted(dts)[len(dts) // 2]

    # step-time attribution over the measured windows (goodput ledger
    # delta): device-compute seconds vs. everything else, so each
    # BENCH_r*.json round carries where its seconds went, not just totals
    gp_after = _goodput.totals()["buckets"]
    wall = sum(dts)
    gp_buckets = {b: round(gp_after[b] - gp_before.get(b, 0.0), 6)
                  for b in gp_after}
    gp_buckets["host_other"] = round(
        gp_buckets["host_other"]
        + max(0.0, wall - sum(gp_buckets.values())), 6)
    productive = sum(gp_buckets[b] for b in _goodput.PRODUCTIVE_BUCKETS)
    goodput_breakdown = {
        "wall_seconds": round(wall, 6),
        "steps": 3 * iters,
        "buckets": gp_buckets,
        "goodput_fraction": round(productive / wall, 4) if wall > 0 else None,
        # the lower-is-better comms headline perf_gate tracks: host
        # seconds blocked on collectives over the measured wall (~0 on
        # one chip — the DP comms layer is inert at nranks==1, and this
        # row is the gate that keeps it that way)
        "collective_fraction": (round(gp_buckets["collective"] / wall, 6)
                                if wall > 0 else None),
    }

    tok_s = batch * seq * iters / med_dt
    window_tok_s = [batch * seq * iters / d for d in dts]
    # standard 6ND transformer train FLOPs + attention term 12*L*T*D per token
    flops_per_token = 6 * n_params + 12 * n_layer * seq * d_model
    achieved = tok_s * flops_per_token

    # compiler-side accounting (xla_insight capture on the compile path):
    # the train step is the most expensive program in the executor cache.
    # Unlike the 6ND analytic model above, these are the FLOPs XLA says
    # the compiled program executes — utilization from them is auditable
    # against the dumped HLO (tools/xla_report.py)
    xla_cost = None
    insights = exe.compiled_insights()
    if insights:
        flops_per_step = max((c.get("flops") or 0) for c in insights)
        if flops_per_step > 0:
            steps_per_sec = iters / med_dt
            xla_cost = {
                "flops_per_step": round(flops_per_step),
                "steps_per_sec": round(steps_per_sec, 3),
                "achieved_flops_per_sec": round(
                    flops_per_step * steps_per_sec),
                "peak_bytes": max(
                    (c.get("peak_bytes") or 0) for c in insights),
            }

    # peak bf16 FLOP/s of the chip this ran on, from the one table; an
    # unknown device_kind raises there instead of being priced by guess
    peak = device_peaks()["bf16_flops_per_sec"]
    if xla_cost is not None:
        xla_cost["xla_mfu"] = round(
            xla_cost["achieved_flops_per_sec"] / peak, 4)

    # device-memory accounting for this config: the measured per-step
    # watermark (executor samples every run; the window covers compile +
    # warmup + timed loops) reconciled against the static
    # program_peak_bytes estimate of the compiled train step. The
    # reconciliation carries its own agreement bound, so BENCH rounds
    # record not just the peak but whether the estimate can be trusted.
    _memwatch.sample()
    estimates = [c.get("peak_bytes") for c in insights]
    measured = float(_memwatch.window_peak())
    static_peak = max((e for e in estimates if e), default=0)
    memory = {
        # the gated metric: measured watermark when sampling works on
        # this backend, else the static estimate; None (-> perf_gate
        # SKIP) when BOTH are unavailable — a 0 would read as a perfect
        # lower-is-better score and poison the rolling median
        "peak_hbm_bytes": (int(measured) if measured > 0
                           else int(static_peak) if static_peak else None),
        "measured_peak_bytes": int(measured) if measured > 0 else None,
        "static_peak_bytes": int(static_peak) if static_peak else None,
        # the donation-adjusted static peak (args+outs+temps minus the
        # bytes aliased in place over donated params): what the step
        # actually holds live — the spread vs static_peak_bytes is the
        # donated state, and the donation tests gate that it stays >0
        "donated_peak_bytes": (max(
            (c.get("donated_peak_bytes") or 0 for c in insights),
            default=0) or None),
        "source": (_memwatch.totals().get("source")
                   if measured > 0 else "estimate"),
        "reconciliation": _memwatch.reconcile(
            estimates=estimates,
            measured_peak=measured if measured > 0 else None),
    }
    # median steady-state step latency, from the same window the
    # throughput headline uses (no re-derivation from batch*seq later)
    step_seconds = med_dt / iters

    # loss trajectory for tools/curve_gate.py: a short UNTIMED tail of
    # steps fetching the loss each iteration (the timed windows above
    # fetch only at their boundaries, so the headline stays free of
    # per-step host syncs). Training continues from the timed state on
    # the same seeded batch, so the curve is deterministic enough for
    # the band comparison; rounds embed it in BENCH_r*.json and the
    # curve gate judges fresh rounds against that history.
    traj_iters = 24
    base_step = 3 + 3 * iters  # warmup + timed windows already run
    traj_steps, traj_loss = [], []
    for i in range(traj_iters):
        loss = exe.run(main_prog, feed=feed, fetch_list=[io["loss"]],
                       scope=scope)[0]
        traj_steps.append(base_step + i)
        traj_loss.append(round(float(np.asarray(loss)), 6))
    trajectory = {"steps": traj_steps, "loss": traj_loss}

    # comms plane: what the compiled plan says each step ships
    # (shard_insight's HLO summary on the train-step program — 0 on one
    # chip, and the reconciliation below is the gate that keeps the
    # single-chip step free of surprise collectives) vs what the
    # collective byte counters measured over this config's steps
    total_steps = base_step + traj_iters
    predicted_per_step = max(
        ((c.get("collectives") or {}).get("payload_bytes_total", 0)
         for c in insights), default=0)
    coll_after = _shard.measured_collective_bytes()
    measured_logical = (coll_after["logical_bytes"]
                        - coll_before["logical_bytes"])
    comms_plane = {
        "predicted_collective_bytes": int(predicted_per_step),
        "predicted_total_bytes": int(predicted_per_step * total_steps),
        "measured_wire_bytes": int(coll_after["wire_bytes"]
                                   - coll_before["wire_bytes"]),
        "measured_logical_bytes": int(measured_logical),
        "steps": total_steps,
        "reconciliation": _shard.reconcile(
            predicted_per_step * total_steps,
            measured_bytes=measured_logical),
    }

    return (achieved / peak, tok_s, n_params, window_tok_s, xla_cost,
            goodput_breakdown, memory, step_seconds, trajectory,
            comms_plane)


def main():
    import paddle_tpu as paddle
    from paddle_tpu import compile_cache
    from paddle_tpu.device import require_tpu

    require_tpu("bench.py")  # every number below is a device rate
    compile_cache.enable()

    paddle.enable_static()
    from paddle_tpu.ops import attention

    baseline_mfu = 0.40  # A100+NCCL-class MFU on this workload (north star)

    # opt-in tracing rider: with PADDLE_TPU_TRACE_DIR set, each
    # benchmarked config runs under the tracer and drops its own chrome
    # trace next to the metrics snapshot (table printing suppressed —
    # stdout must stay the single JSON result line)
    from paddle_tpu import flags as _flags

    trace_dir = _flags.env_flag("PADDLE_TPU_TRACE_DIR") or None

    def traced(tag, **kw):
        if not trace_dir:
            return bench_config(**kw)
        from paddle_tpu import profiler

        profiler.start_profiler()
        try:
            return bench_config(**kw)
        finally:
            profiler.stop_profiler(
                profile_path=os.path.join(trace_dir, f"bench_trace.{tag}.json"),
                print_table=False)
            # the env-registered atexit flush must not re-export these
            # events as a stale trace.rank0.json next to the per-run files
            profiler.clear_events()

    (mfu, tok_s, n_params, windows, xla_cost, gp, mem, step_s,
     traj, comms) = traced("gpt2s_seq512", batch=8, seq=512, iters=80)

    flash_before = attention.FLASH_DISPATCH_COUNT
    (mfu_long, tok_s_long, _, windows_long, xla_cost_long, gp_long,
     mem_long, _step_s_long, traj_long, comms_long) = traced(
        "gpt2s_seq2048", batch=8, seq=2048, iters=40)
    flash_hit = attention.FLASH_DISPATCH_COUNT > flash_before
    assert flash_hit, "long-seq config did not dispatch the flash kernel"

    # opt-in observability rider: PADDLE_TPU_METRICS_PATH=<file> writes
    # the JSON metrics snapshot (executor compile/run series, per-op
    # context) next to the bench result, so BENCH_r*.json rounds carry
    # the telemetry that explains their numbers (tools/obs_report.py
    # renders it)
    metrics_path = _flags.env_flag("PADDLE_TPU_METRICS_PATH") or None
    if metrics_path:
        from paddle_tpu import monitor

        monitor.stat_set("bench_tokens_per_sec", tok_s)
        monitor.stat_set("bench_long_seq_tokens_per_sec", tok_s_long)
        monitor.write_snapshot(metrics_path)

    result = {
        "metric": "gpt2s_pretrain_mfu",
        "value": round(mfu, 4),
        "unit": "MFU (model-flops util, bf16, 1 chip)",
        "vs_baseline": round(mfu / baseline_mfu, 3),
        "tokens_per_sec": round(tok_s),
        # median steady-state step latency (seconds/step): the second
        # lower-is-better metric the perf gate tracks
        "step_seconds": round(step_s, 6),
        "window_tokens_per_sec": [round(w) for w in windows],
        "params": n_params,
        "goodput": gp,
        # top-level copy of the goodput comms headline so perf_gate's
        # collective_fraction check reads it like mfu/peak_hbm_bytes
        "collective_fraction": gp.get("collective_fraction"),
        # per-config peak HBM (measured watermark, or the static
        # estimate when the backend reports no allocator stats) — the
        # lower-is-better metric tools/perf_gate.py gates alongside MFU
        "peak_hbm_bytes": mem["peak_hbm_bytes"],
        "memory": mem,
        # the convergence counterpart of the perf metrics: a downsampled
        # loss trajectory + final loss per config, so BENCH_r*.json
        # history carries the reference curves tools/curve_gate.py
        # gates fresh rounds (and real training journals) against
        "loss_trajectory": traj,
        "final_loss": traj["loss"][-1],
        # comms plane: HLO-predicted collective bytes per step vs the
        # measured byte counters, with the reconciliation verdict — the
        # predicted-vs-measured pair MULTICHIP rounds record per mode
        "comms_plane": comms,
        "predicted_collective_bytes": comms["predicted_collective_bytes"],
        "long_seq": {
            "seq": 2048,
            "value": round(mfu_long, 4),
            "vs_baseline": round(mfu_long / baseline_mfu, 3),
            "tokens_per_sec": round(tok_s_long),
            "window_tokens_per_sec": [round(w) for w in windows_long],
            "flash_path_hit": flash_hit,
            "goodput": gp_long,
            "peak_hbm_bytes": mem_long["peak_hbm_bytes"],
            "memory": mem_long,
            "loss_trajectory": traj_long,
            "final_loss": traj_long["loss"][-1],
            "comms_plane": comms_long,
            "predicted_collective_bytes":
                comms_long["predicted_collective_bytes"],
        },
    }
    # XLA cost-analysis utilization (when the insight capture ran): the
    # compiled program's own FLOPs next to the analytic-model headline,
    # so BENCH_*.json rounds carry utilization, not just latency
    if xla_cost is not None:
        result["flops_per_step"] = xla_cost["flops_per_step"]
        result["achieved_flops_per_sec"] = xla_cost["achieved_flops_per_sec"]
        result["steps_per_sec"] = xla_cost["steps_per_sec"]
        result["xla_cost"] = xla_cost
    if xla_cost_long is not None:
        result["long_seq"]["flops_per_step"] = xla_cost_long["flops_per_step"]
        result["long_seq"]["achieved_flops_per_sec"] = (
            xla_cost_long["achieved_flops_per_sec"])
        result["long_seq"]["xla_cost"] = xla_cost_long
    print(json.dumps(result))


if __name__ == "__main__":
    main()
