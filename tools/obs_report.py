"""Structured run report: metrics snapshot + profiler host spans, merged.

The reference ships its observability as three disconnected artifacts —
the profiler's sorted op table, monitor.h stat gauges, and per-tool
printouts. This merges the paddle_tpu counterparts into ONE JSON (or
text) report per run: executor compile/cache/run latency, DataLoader
queue health, PS RPC msgs/s + MB/s, per-collective traffic, fit-loop
throughput, and the per-op host-span table from the profiler trace.

Usage:
  python tools/obs_report.py --metrics run_metrics.json \
      [--trace profile.json] [--out report.json] [--format text]
  python tools/obs_report.py --self-test    # CI smoke: tiny static run

The metrics file is a `paddle_tpu.monitor.write_snapshot()` JSON; the
trace is the chrome://tracing JSON `profiler.stop_profiler` writes (or
is omitted, in which case live in-process spans are used when present).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPORT_SCHEMA = "paddle_tpu.obs_report/1"

# keys every report must carry (the CI smoke asserts on these)
REQUIRED_KEYS = ("schema", "executor", "dataloader", "ps", "collectives",
                 "throughput", "op_table", "timeline", "compile", "goodput",
                 "dynamics",
                 "memory", "comms", "comms_plane", "serving", "recovery",
                 "plan", "request_attribution", "autoscale", "interconnect")


def _import_timeline():
    """Sibling tools/timeline.py (multi-rank merge + straggler summary)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import timeline
        return timeline
    finally:
        sys.path.pop(0)


# ---------------------------------------------------------------------------
# metric readers
# ---------------------------------------------------------------------------


def _families(snapshot: Dict[str, Any]) -> Dict[str, Any]:
    return snapshot.get("metrics", {})


def _series(snapshot, name) -> List[dict]:
    return _families(snapshot).get(name, {}).get("series", [])


def _scalar(snapshot, name, labels: Optional[Dict[str, str]] = None,
            default: float = 0.0) -> float:
    for s in _series(snapshot, name):
        if labels is None or s.get("labels") == labels:
            return float(s.get("value", default))
    return default


def _by_label(snapshot, name, label: str) -> Dict[str, dict]:
    """label value -> series entry, for single-label families."""
    return {s["labels"].get(label, ""): s for s in _series(snapshot, name)}


def _quantile_from_buckets(bounds: List[float], counts: List[int],
                           q: float) -> Optional[float]:
    """Approximate quantile by linear interpolation inside the winning
    bucket (the Prometheus histogram_quantile estimator)."""
    total = sum(counts)
    if total == 0:
        return None
    rank = q * total
    cum = 0
    lo = 0.0
    for bound, c in zip(bounds, counts):
        if cum + c >= rank:
            frac = (rank - cum) / c if c else 0.0
            return lo + (bound - lo) * frac
        cum += c
        lo = bound
    return bounds[-1]  # landed in +Inf: clamp to the top bound


def hist_summary(entry: Optional[dict]) -> Dict[str, Any]:
    """count/sum/avg/p50/p99 for one histogram series entry."""
    if not entry or not entry.get("count"):
        return {"count": 0, "sum": 0.0, "avg": None, "p50": None, "p99": None}
    bounds, counts = entry["buckets"], entry["counts"]
    return {
        "count": entry["count"],
        "sum": round(entry["sum"], 6),
        "avg": round(entry["sum"] / entry["count"], 6),
        "p50": _quantile_from_buckets(bounds, counts, 0.50),
        "p99": _quantile_from_buckets(bounds, counts, 0.99),
    }


def _hist_entry(snapshot, name,
                labels: Optional[Dict[str, str]] = None) -> Optional[dict]:
    for s in _series(snapshot, name):
        if labels is None or s.get("labels") == labels:
            return s
    return None


# ---------------------------------------------------------------------------
# report assembly
# ---------------------------------------------------------------------------


def _flash_tiles(snap) -> Dict[str, Any]:
    """Per flash kernel (fwd, dq, dkv): the score tiles of the calls traced
    so far by what the causal schedule does with them, and the share of the
    score square that is computed (1.0: nothing skipped; a non-causal call,
    or tiles as wide as the sequence)."""
    out: Dict[str, Any] = {}
    for s in _series(snap, "flash_tiles_total"):
        labels = s.get("labels") or {}
        out.setdefault(labels.get("kernel", ""), {})[labels.get("cls", "")] = float(s.get("value", 0))
    for tiles in out.values():
        total = sum(tiles.values())
        tiles["computed_share"] = (round((total - tiles.get("skipped", 0.0)) / total, 4)
                                   if total else None)
    return out


def _executor_section(snap) -> Dict[str, Any]:
    hits = _scalar(snap, "executor_cache_lookups_total", {"result": "hit"})
    misses = _scalar(snap, "executor_cache_lookups_total", {"result": "miss"})
    lookups = hits + misses
    return {
        "compile_total": _scalar(snap, "executor_compile_total"),
        "cache_hits": hits,
        "cache_misses": misses,
        "cache_hit_rate": round(hits / lookups, 4) if lookups else None,
        "cache_size": _scalar(snap, "executor_cache_size"),
        "run_total": _scalar(snap, "executor_run_total"),
        # generic grad ops by how they were lowered when their block was
        # traced: with the pullback their forward op made, or by tracing
        # the forward rule a second time (a Mosaic kernel then runs twice)
        "grad_paired": _scalar(snap, "executor_grad_paired_total"),
        "grad_retraced": _scalar(snap, "executor_grad_retraced_total"),
        "flash_tiles": _flash_tiles(snap),
        # BTHD flash forwards traced so far by how the kernel walks its heads
        "flash_fwd_calls": {(s.get("labels") or {}).get("body", ""): float(s.get("value", 0))
                            for s in _series(snap, "flash_fwd_calls_total")},
        "compile_seconds": hist_summary(
            _hist_entry(snap, "executor_compile_seconds")),
        "run_seconds": hist_summary(_hist_entry(snap, "executor_run_seconds")),
    }


def _dataloader_section(snap) -> Dict[str, Any]:
    return {
        "queue_depth": _scalar(snap, "dataloader_queue_depth"),
        "batches_total": _scalar(snap, "dataloader_batches_total"),
        "wait_seconds": hist_summary(
            _hist_entry(snap, "dataloader_wait_seconds")),
        "dataset_records_loaded": _scalar(snap, "dataset_records_loaded"),
        "dataset_batches_total": _scalar(snap, "dataset_batches_total"),
    }


def _ps_section(snap) -> Dict[str, Any]:
    out: Dict[str, Any] = {"client": {}, "server": {}}
    for side, req, lat, tx, rx in (
        ("client", "ps_client_requests_total", "ps_client_request_seconds",
         "ps_client_bytes_sent_total", "ps_client_bytes_recv_total"),
        ("server", "ps_server_requests_total", "ps_server_request_seconds",
         "ps_server_bytes_in_total", "ps_server_bytes_out_total"),
    ):
        reqs = _by_label(snap, req, "method")
        lats = _by_label(snap, lat, "method")
        txs = _by_label(snap, tx, "method")
        rxs = _by_label(snap, rx, "method")
        for method in sorted(reqs):
            n = float(reqs[method].get("value", 0))
            latency = hist_summary(lats.get(method))
            busy_s = latency["sum"] or 0.0
            row = {
                "requests": n,
                "latency_seconds": latency,
                "bytes_out" if side == "client" else "bytes_in":
                    float(txs.get(method, {}).get("value", 0)),
                "bytes_in" if side == "client" else "bytes_out":
                    float(rxs.get(method, {}).get("value", 0)),
            }
            # absolute rates over the measured (in-flight) window: the
            # first real msgs/s and MB/s numbers for the PS path
            if busy_s > 0:
                total_bytes = (float(txs.get(method, {}).get("value", 0))
                               + float(rxs.get(method, {}).get("value", 0)))
                row["msgs_per_sec"] = round(n / busy_s, 2)
                row["mb_per_sec"] = round(total_bytes / busy_s / 1e6, 3)
            out[side][method] = row
    return out


def _collectives_section(snap) -> Dict[str, Any]:
    calls = _by_label(snap, "collective_calls_total", "op")
    byts = _by_label(snap, "collective_bytes_total", "op")
    return {
        op: {
            "calls": float(calls[op].get("value", 0)),
            "bytes": float(byts.get(op, {}).get("value", 0)),
        }
        for op in sorted(calls)
    }


def _comms_section(snap, goodput_ledger: Optional[Dict[str, Any]]
                   ) -> Dict[str, Any]:
    """DP-comms accounting: per-op collective calls, WIRE bytes actually
    shipped vs their fp32-logical equivalent (the quantized-allreduce
    compression ratio), and the goodput collective seconds/fraction —
    the three numbers that say whether the bucketed/quantized gradient
    sync is earning its bucket."""
    calls = _by_label(snap, "collective_calls_total", "op")
    wire = _by_label(snap, "collective_bytes_total", "op")
    logical = _by_label(snap, "collective_logical_bytes_total", "op")
    ops = {
        op: {
            "calls": float(calls[op].get("value", 0)),
            "wire_bytes": float(wire.get(op, {}).get("value", 0)),
            "logical_bytes": float(logical.get(op, {}).get(
                "value", wire.get(op, {}).get("value", 0))),
        }
        for op in sorted(calls)
    }
    wire_total = sum(r["wire_bytes"] for r in ops.values())
    logical_total = sum(r["logical_bytes"] for r in ops.values())
    out: Dict[str, Any] = {
        "available": bool(ops),
        "ops": ops,
        "calls_total": sum(r["calls"] for r in ops.values()),
        "wire_bytes_total": wire_total,
        "logical_bytes_total": logical_total,
        # >1 means quantization shrank the wire vs the logical fp32 view
        "compression_ratio": (round(logical_total / wire_total, 4)
                              if wire_total > 0 else None),
    }
    if goodput_ledger:
        denom = goodput_ledger.get("wall_seconds") or sum(
            goodput_ledger.get("buckets", {}).values()) or 0.0
        coll_s = float(goodput_ledger.get("buckets", {}).get(
            "collective", 0.0))
        out["collective_seconds"] = round(coll_s, 6)
        out["collective_fraction"] = (round(coll_s / denom, 6)
                                      if denom > 0 else None)
    return out


def _comms_plane_section(snap, dump_records: Optional[Dict[str, dict]]
                         ) -> Dict[str, Any]:
    """Predicted-vs-measured comms plane: the HLO collective summaries
    (per-program predicted payload bytes, from the --xla-dump cost
    records or the live program_collective_bytes gauges) against the
    measured collective byte counters, with the shard_insight
    reconciliation verdict.

    The two sides cover DIFFERENT transport layers: the prediction sees
    in-program (GSPMD/XLA) collectives, the counters see the eager API
    path (DP buckets, PS exchanges). The verdict is therefore read with
    the mismatch taxonomy: ``measured_only`` means eager traffic the
    compiled plan cannot see (normal for dygraph DP), ``predicted_only``
    means compiled collectives no counter measures (the GSPMD tripwire),
    and a both-sided ratio uses executor run counts as the step
    estimate."""
    from paddle_tpu.framework import shard_insight as _shard

    per_program: Dict[str, dict] = {}
    gauge_bytes = _by_label(snap, "program_collective_bytes", "program")
    for h, entry in gauge_bytes.items():
        per_program[h] = {
            "payload_bytes": float(entry.get("value", 0)), "by_kind": {}}
    counts = _series(snap, "program_collective_count")
    for s in counts:
        h = s.get("labels", {}).get("program", "")
        kind = s.get("labels", {}).get("kind", "")
        per_program.setdefault(h, {"payload_bytes": 0, "by_kind": {}})[
            "by_kind"][kind] = float(s.get("value", 0))
    for h, rec in (dump_records or {}).items():
        summ = rec.get("collectives")
        if not summ:
            continue
        row = per_program.setdefault(h, {"payload_bytes": 0, "by_kind": {}})
        row["payload_bytes"] = summ.get("payload_bytes_total", 0)
        row["by_kind"] = {
            k: v.get("count", 0) for k, v in summ.get("by_kind", {}).items()}
        row["comms_to_compute_bytes_per_flop"] = summ.get(
            "comms_to_compute_bytes_per_flop")
    # a reset registry keeps old label sets as zero-valued series: only
    # programs whose plan actually moves bytes (or counts instructions)
    # belong in the table
    per_program = {
        h: r for h, r in per_program.items()
        if r["payload_bytes"] or any(r["by_kind"].values())
    }

    measured = _shard.measured_collective_bytes(snap)
    predicted_per_exec = sum(r["payload_bytes"]
                             for r in per_program.values())
    # predicted total: per-program execution counts (the labeled
    # executor_program_run_total counter) x that program's per-execution
    # bytes — two programs running different step counts must not share
    # one multiplier. Snapshots predating the counter fall back to the
    # coarse total-runs estimate (every program charged every run),
    # stated via steps_estimate
    prog_runs = _by_label(snap, "executor_program_run_total", "program")
    for h, r in per_program.items():
        r["runs"] = float(prog_runs.get(h, {}).get("value", 0.0))
    runs = max(1.0, _scalar(snap, "executor_run_total"))
    if any(r["runs"] for r in per_program.values()):
        predicted_total = sum(r["payload_bytes"] * r["runs"]
                              for r in per_program.values())
    else:
        predicted_total = predicted_per_exec * runs
    reconciliation = _shard.reconcile(
        predicted_total if predicted_per_exec else 0,
        measured_bytes=measured["logical_bytes"])
    return {
        "available": bool(per_program) or measured["logical_bytes"] > 0,
        "predicted": {
            "n_programs_with_collectives": len(per_program),
            "payload_bytes_per_execution": predicted_per_exec,
            "payload_bytes_total": int(predicted_total),
            "per_program": dict(sorted(per_program.items())),
        },
        "measured": measured,
        "steps_estimate": runs,
        "reconciliation": reconciliation,
        "verdict": reconciliation.get("verdict"),
    }


def _compile_section(snap, dump_records: Optional[Dict[str, dict]] = None
                     ) -> Dict[str, Any]:
    """Per-compiled-program XLA cost accounting: the program_flops /
    program_peak_bytes gauge series (xla_insight capture), enriched with
    the full cost records when a PADDLE_TPU_XLA_DUMP_DIR is given."""
    flops_by = _by_label(snap, "program_flops", "program")
    peak_by = _by_label(snap, "program_peak_bytes", "program")
    bytes_by = _by_label(snap, "program_bytes_accessed", "program")
    programs: Dict[str, dict] = {}
    for h in sorted(set(flops_by) | set(peak_by) | set(bytes_by)):
        programs[h] = {
            "flops": float(flops_by.get(h, {}).get("value", 0)),
            "peak_bytes": float(peak_by.get(h, {}).get("value", 0)),
            "bytes_accessed": float(bytes_by.get(h, {}).get("value", 0)),
        }
    for h, rec in (dump_records or {}).items():
        row = programs.setdefault(h, {})
        for key in ("flops", "bytes_accessed", "peak_bytes", "label",
                    "fetch_names", "n_jaxpr_eqns", "build_s", "cache"):
            if rec.get(key) is not None:
                row[key] = rec[key]
    stages = _by_label(snap, "program_build_seconds_total", "stage")
    counts = _by_label(snap, "program_build_total", "stage")
    return {
        # every jit of the process by stage (xla_insight.build_log), the
        # named programs' own stages in each row's build_s
        "build_seconds": {st: float(v.get("value", 0))
                          for st, v in sorted(stages.items())},
        "builds": {st: float(v.get("value", 0))
                   for st, v in sorted(counts.items())},
        "n_programs": len(programs),
        "total_flops": sum(p.get("flops") or 0 for p in programs.values()),
        "max_peak_bytes": max(
            (p.get("peak_bytes") or 0 for p in programs.values()), default=0),
        "programs": programs,
    }


def _goodput_section(ledger: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """Step-time attribution from the goodput ledger journal(s): bucket
    table + the badput top offender ('why is my step slow' in one row).
    `ledger` is a merged/per-rank journal doc (paddle_tpu.goodput); when
    absent the section stays present but empty so report consumers can
    rely on the key."""
    from paddle_tpu import goodput as _goodput

    if not ledger:
        return {"available": False}
    denom = ledger.get("wall_seconds") or sum(
        ledger.get("buckets", {}).values()) or 0.0
    buckets = {
        b: {
            "seconds": round(float(ledger.get("buckets", {}).get(b, 0.0)), 6),
            "fraction": (round(ledger.get("buckets", {}).get(b, 0.0) / denom,
                               4) if denom > 0 else None),
        }
        for b in _goodput.BUCKETS
    }
    return {
        "available": True,
        "ranks": ledger.get("ranks", [ledger.get("rank", 0)]),
        "steps": ledger.get("steps", 0),
        "wall_seconds": ledger.get("wall_seconds", 0.0),
        "samples": ledger.get("samples", 0.0),
        "productive_seconds": ledger.get("productive_seconds", 0.0),
        "goodput_fraction": ledger.get("goodput_fraction"),
        "buckets": buckets,
        "top_badput": (ledger.get("top_badput")
                       or _goodput.top_badput(ledger)),
    }


def _memory_section(snap, ledger: Optional[Dict[str, Any]],
                    compile_section: Dict[str, Any]) -> Dict[str, Any]:
    """Device-memory accounting: the memwatch ledger journal(s) (per-rank
    peaks, leak-detector state) + the live hbm_* gauges from the metrics
    snapshot, reconciled against the compile section's static
    program_peak_bytes estimates (estimate-vs-actual utilization)."""
    from paddle_tpu import memwatch as _memwatch

    gauges = {
        "bytes_in_use": _scalar(snap, "hbm_bytes_in_use"),
        "peak_bytes": _scalar(snap, "hbm_peak_bytes"),
        "step_delta_bytes": _scalar(snap, "hbm_step_delta_bytes"),
        "leak_suspects": _scalar(snap, "hbm_leak_suspects_total"),
    }
    if not ledger:
        out: Dict[str, Any] = {"available": gauges["peak_bytes"] > 0,
                               "gauges": gauges}
        if out["available"]:
            out["reconciliation"] = _memwatch.reconcile(
                estimates=[p.get("peak_bytes")
                           for p in compile_section["programs"].values()],
                measured_peak=gauges["peak_bytes"])
        return out
    measured = float(ledger.get("lifetime_peak_bytes") or 0)
    return {
        "available": True,
        "ranks": ledger.get("ranks", [ledger.get("rank", 0)]),
        "steps": ledger.get("steps", 0),
        "lifetime_peak_bytes": measured,
        "bytes_in_use": ledger.get("bytes_in_use"),
        "bytes_limit": ledger.get("bytes_limit"),
        "source": ledger.get("source"),
        "leak_events": ledger.get("leak_events", 0),
        "per_rank": ledger.get("per_rank"),
        "gauges": gauges,
        "reconciliation": _memwatch.reconcile(
            estimates=[p.get("peak_bytes")
                       for p in compile_section["programs"].values()],
            measured_peak=measured),
    }


def _dynamics_section(snap, ledger: Optional[Dict[str, Any]]
                      ) -> Dict[str, Any]:
    """Training-dynamics accounting: the dynamics journal(s) (per-rank
    final losses, anomaly episodes, the cross-rank desync probe) + the
    live loss/grad gauges from the metrics snapshot."""
    anomalies = _by_label(snap, "dynamics_anomalies_total", "kind")
    gauges = {
        "loss": _scalar(snap, "fit_loss"),
        "loss_ema": _scalar(snap, "dynamics_loss_ema"),
        "grad_norm": _scalar(snap, "fit_grad_norm"),
        "grad_norm_ema": _scalar(snap, "dynamics_grad_norm_ema"),
        "update_ratio": _scalar(snap, "dynamics_update_ratio"),
        "anomalies": {k: v.get("value", 0) for k, v in anomalies.items()},
    }
    if not ledger:
        return {"available": gauges["loss_ema"] > 0 or gauges["loss"] > 0,
                "gauges": gauges}
    out: Dict[str, Any] = {
        "available": True,
        "ranks": ledger.get("ranks", [ledger.get("rank", 0)]),
        "steps": ledger.get("steps", 0),
        "anomaly_counts": ledger.get("anomaly_counts", {}),
        "anomalies_total": ledger.get(
            "anomalies_total",
            sum((ledger.get("anomaly_counts") or {}).values())),
        "per_rank": ledger.get("per_rank"),
        "desync": ledger.get("desync"),
        "gauges": gauges,
    }
    # a single-rank journal carries the trajectory itself: surface the
    # convergence headline (final-window loss) the curve gate judges
    series = ledger.get("series")
    if series:
        losses = [s["loss"] for s in series if s.get("loss") is not None]
        if losses:
            tail = losses[-5:]
            out["final_loss"] = losses[-1]
            out["final_window_loss"] = sum(tail) / len(tail)
            out["n_recorded_steps"] = len(losses)
    return out


def _serving_section(snap, ledger: Optional[Dict[str, Any]]
                     ) -> Dict[str, Any]:
    """Serving-plane accounting: the serving ledger journal(s)
    (--serve): the SLO table (tokens/s, TTFT/latency p50/p99), batch
    occupancy, KV utilization, the serving goodput buckets with the top
    badput offender, and the reconciliation verdicts — plus the live
    serve_* gauges from the metrics snapshot."""
    from paddle_tpu.serving import ledger as _serving

    requests = _by_label(snap, "serve_requests_total", "outcome")
    gauges = {
        "batch_occupancy": _scalar(snap, "serve_batch_occupancy"),
        "kv_block_utilization": _scalar(snap,
                                        "serve_kv_block_utilization"),
        "queue_depth": _scalar(snap, "serve_queue_depth"),
        "tokens_per_sec_ema": _scalar(snap, "serve_tokens_per_sec"),
        "ttft_seconds": hist_summary(_hist_entry(snap,
                                                 "serve_ttft_seconds")),
        "latency_seconds": hist_summary(
            _hist_entry(snap, "serve_request_latency_seconds")),
        "requests": {k: v.get("value", 0) for k, v in requests.items()},
    }
    failover = _serving_failover(snap)
    if not ledger:
        return {"available": bool(sum(gauges["requests"].values())),
                "failover": failover,
                "gauges": gauges}
    denom = ledger.get("wall_seconds") or sum(
        ledger.get("buckets", {}).values()) or 0.0
    buckets = {
        b: {
            "seconds": round(float(ledger.get("buckets", {}).get(b, 0.0)),
                             6),
            "fraction": (round(ledger.get("buckets", {}).get(b, 0.0)
                               / denom, 4) if denom > 0 else None),
        }
        for b in _serving.BUCKETS
    }
    span_rec = (ledger.get("span_reconciliation")
                or _serving.reconcile_spans(ledger))
    roof_rec = (ledger.get("roofline_reconciliation")
                or _serving.reconcile_roofline(ledger))
    return {
        "available": True,
        "ranks": ledger.get("ranks", [ledger.get("rank", 0)]),
        "ticks": ledger.get("ticks", 0),
        "wall_seconds": ledger.get("wall_seconds", 0.0),
        "goodput_fraction": ledger.get("goodput_fraction"),
        "slo": ledger.get("slo") or _serving.slo_summary(ledger),
        "buckets": buckets,
        "top_badput": (ledger.get("top_badput")
                       or _serving.top_badput(ledger)),
        "reconciliations": {
            "span_vs_wall": span_rec,
            "measured_vs_roofline": roof_rec,
        },
        "verdicts": {"span_vs_wall": span_rec.get("verdict"),
                     "measured_vs_roofline": roof_rec.get("verdict")},
        "failover": failover,
        "gauges": gauges,
    }


def _traffic_summary(snap: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """Router arrival-process telemetry -> the autoscaler-facing
    summary: per-class request-rate EMAs at each horizon, interarrival
    CV with a burstiness reading (~1 is Poisson, >>1 bursty — a bursty
    class needs headroom a mean rate alone would not justify), and the
    queue-depth / in-flight load picture."""
    if not snap or not snap.get("classes"):
        return {"available": False}
    classes = {}
    for klass, row in snap["classes"].items():
        inter = row.get("interarrival") or {}
        cv = inter.get("cv")
        classes[klass] = {
            "n": row.get("n"),
            "rate_ema": row.get("rate_ema"),
            "interarrival_mean_s": inter.get("mean_s"),
            "interarrival_cv": cv,
            "burstiness": (None if cv is None
                           else "bursty" if cv > 1.5
                           else "steady" if cv < 0.5
                           else "poisson-like"),
        }
    return {
        "available": True,
        "horizons_s": snap.get("horizons_s"),
        "classes": classes,
        "depth": snap.get("depth_summary"),
    }


def _request_attribution_section(ledger: Optional[Dict[str, Any]]
                                 ) -> Dict[str, Any]:
    """Per-request latency attribution (--serve journals carrying the
    `attribution` aggregate): the per-traffic-class bucket table
    (count/avg/p50/p99 per typed bucket — router_queue, backoff_wait,
    transport, admission_queue, batch_wait, prefill_compute,
    decode_compute, postprocess), the top-latency offender per class
    with its dominant bucket, the router's arrival-rate / burstiness
    telemetry, and the residual verdict (do the buckets reconstruct
    the measured e2e walls?) — the "my p99 spiked, where did the time
    go" section."""
    from paddle_tpu.serving import ledger as _serving

    attr = (ledger or {}).get("attribution") or {}
    traffic = _traffic_summary((ledger or {}).get("traffic"))
    if not attr.get("n_requests"):
        return {"available": False, "traffic": traffic}
    table = _serving.attribution_summary(ledger)
    recon = (ledger.get("attribution_reconciliation")
             or _serving.reconcile_attribution(ledger))
    offenders = {}
    for klass, cls in table["classes"].items():
        slow = cls.get("slowest")
        if not slow:
            continue
        buckets = slow.get("buckets") or {}
        top = max(buckets, key=buckets.get) if buckets else None
        offenders[klass] = {
            "request_id": slow.get("request_id"),
            "outcome": slow.get("outcome"),
            "e2e_s": slow.get("e2e_s"),
            "top_bucket": top,
            "top_bucket_s": buckets.get(top) if top else None,
        }
    return {
        "available": True,
        "n_requests": table["n_requests"],
        "classes": table["classes"],
        "offenders": offenders,
        "traffic": traffic,
        "reconciliation": recon,
        "verdict": recon.get("verdict"),
    }


def _serving_failover(snap) -> Dict[str, Any]:
    """The serving fault-plane verdict: router retry/hedge/failover
    counters, the redispatch bit-match tally, and the engine-side
    reap/shed counts — with one headline verdict: ``bit_mismatch``
    (a re-dispatched request produced different tokens — a correctness
    alarm), ``failover_active`` (the fault path did real work this run)
    or ``clean``."""
    bitmatch = {k: v.get("value", 0) for k, v in _by_label(
        snap, "serve_router_bitmatch_total", "verdict").items()}
    out = {
        "retries": _scalar(snap, "serve_router_retries_total"),
        "hedges": _scalar(snap, "serve_router_hedges_total"),
        "hedge_wins": _scalar(snap, "serve_router_hedge_wins_total"),
        "failovers": _scalar(snap, "serve_router_failover_total"),
        "reaped": _scalar(snap, "serve_reaped_total"),
        "shed": _scalar(snap, "serve_shed_total"),
        "bitmatch": bitmatch,
        "chaos_injected": {
            k: v.get("value", 0)
            for k, v in _by_label(snap, "chaos_injected_total",
                                  "site").items()
            if k in ("replica_kill", "decode_stall", "admit_error")},
    }
    if bitmatch.get("mismatch"):
        out["verdict"] = "bit_mismatch"
    elif any(out[k] for k in ("retries", "hedges", "failovers",
                              "reaped")) \
            or any(out["chaos_injected"].values()):
        out["verdict"] = "failover_active"
    else:
        out["verdict"] = "clean"
    return out


def _recovery_section(snap, chaos_record: Optional[Dict[str, Any]] = None
                      ) -> Dict[str, Any]:
    """Fault-plane accounting (--chaos: a tools/chaos_bench.py record or
    a MULTICHIP round carrying a ``chaos`` section): detection latency,
    MTTR, steps lost, the drift-audit verdict and the curve cert — plus
    the live chaos/checkpoint/collective-failure counters from the
    metrics snapshot."""
    injected = _by_label(snap, "chaos_injected_total", "site")
    unavail = _by_label(snap, "collective_unavailable_total", "reason")
    counters = {
        "chaos_injected": {k: v.get("value", 0)
                           for k, v in injected.items()},
        "collective_unavailable": {k: v.get("value", 0)
                                   for k, v in unavail.items()},
        "checkpoints_saved": _scalar(snap, "train_checkpoint_saved_total"),
        "checkpoint_resumes": _scalar(snap,
                                      "train_checkpoint_resumed_total"),
        "serve_shed": _scalar(snap, "serve_shed_total"),
        "serve_reaped": _scalar(snap, "serve_reaped_total"),
    }
    if not chaos_record:
        return {"available": bool(sum(counters["chaos_injected"].values())
                                  or counters["checkpoints_saved"]),
                "counters": counters}
    doc = chaos_record.get("chaos") if isinstance(
        chaos_record.get("chaos"), dict) else chaos_record
    audit = doc.get("drift_audit") or {}
    failed = [c.get("check") for r in (audit.get("per_rank") or {}).values()
              for c in (r.get("checks") or []) if not c.get("ok")]
    return {
        "available": True,
        "ok": doc.get("ok"),
        "detection_latency_s": doc.get("detection_seconds"),
        "recovery_seconds": doc.get("recovery_seconds"),
        "steps_lost": doc.get("steps_lost"),
        "resumed_from": doc.get("resumed_from"),
        "kill_step": doc.get("kill_step"),
        "typed_unavailable": doc.get("typed_unavailable"),
        "resume_bit_identical": doc.get("resume_bit_identical"),
        "ef_residual_buckets": doc.get("ef_residual_buckets"),
        "drift_audit": {"ok": audit.get("ok"),
                        "failed_checks": sorted(set(failed))},
        "curve_ok": (doc.get("curve_gate") or {}).get("ok"),
        "counters": counters,
    }


def _plan_section(plan_record: Optional[Dict[str, Any]] = None
                  ) -> Dict[str, Any]:
    """Decision-plane accounting (--plan: a tools/auto_plan.py report,
    a mesh_bench --validate record, or a MULTICHIP round carrying a
    ``plan`` section): the planner's pick, the gated planner_regret,
    the per-metric predictor-error table, the calibration correction
    factors, and the rejected-candidate tally with reasons."""
    if not plan_record:
        return {"available": False}
    doc = plan_record.get("plan") if isinstance(
        plan_record.get("plan"), dict) else plan_record
    if not doc or not doc.get("available", True) or "error" in doc:
        # a round whose plan leg raised records {'error': ...}: that is
        # an unavailable section carrying the failure, not a plan
        return {"available": False,
                "skip_reason": ((doc or {}).get("skip_reason")
                                or (doc or {}).get("error"))}
    pick = doc.get("pick") or {}
    val = doc.get("validation") or {}
    tally = doc.get("rejected_tally") or {}
    calibration = {
        metric: {k: c.get(k) for k in ("n_pairs", "correction_factor",
                                       "raw_error", "residual_error")}
        for metric, c in (doc.get("calibration") or {}).items()
        if isinstance(c, dict)
    }
    pred = pick.get("predicted") or {}
    return {
        "available": True,
        "schema": doc.get("schema"),
        "pick": {
            "spec": pick.get("spec"), "name": pick.get("name"),
            "axes": pick.get("axes"),
            "predicted_step_seconds": pred.get("step_seconds"),
            "predicted_step_seconds_corrected":
                pred.get("step_seconds_corrected"),
            "predicted_peak_bytes": pred.get("peak_bytes"),
            "bound_by": pred.get("bound_by"),
        },
        "n_candidates": doc.get("n_candidates"),
        "n_feasible": doc.get("n_feasible"),
        "rejected": {"total": sum(tally.values()), "by_reason": tally},
        "planner_regret": (doc.get("planner_regret")
                           if doc.get("planner_regret") is not None
                           else val.get("planner_regret")),
        "validated": bool(val),
        "measured_best": val.get("measured_best"),
        "measured_step_seconds": val.get("measured_step_seconds"),
        "predictor_error": doc.get("predictor_error"),
        "calibration": calibration,
        "verdict": doc.get("planner_verdict") or doc.get("verdict"),
    }


def _autoscale_section(autoscale_record: Optional[Dict[str, Any]] = None,
                       serving_ledger: Optional[Dict[str, Any]] = None
                       ) -> Dict[str, Any]:
    """Scale-plane accounting (--autoscale: a tools/serve_bench.py
    --autoscale SERVE round, or the autoscale trail the router folds
    into the merged --serve journals): the capacity plan, the typed
    scale-decision trail (scale_up / drain_start / scale_down) with
    predicted-vs-realized SLO attainment per decision, boot seconds,
    the warm-up calibration pair, and the round's gated headlines
    (per-class attainment, scale_regret, utilization)."""
    doc = None
    round_parsed = None
    rec = autoscale_record
    if isinstance(rec, dict):
        if isinstance(rec.get("parsed"), dict):
            # a full SERVE round record ({"schema": ..., "parsed": ...})
            round_parsed = rec["parsed"]
            rec = round_parsed
        if isinstance(rec.get("autoscale"), dict):
            # a round's parsed doc, or a merged serving ledger
            doc = rec["autoscale"]
        elif "decisions" in rec or "plan" in rec:
            # a bare autoscale doc (router.ledger_doc()['autoscale'])
            doc = rec
    if doc is None and isinstance(serving_ledger, dict) \
            and isinstance(serving_ledger.get("autoscale"), dict):
        doc = serving_ledger["autoscale"]
    if not doc:
        return {"available": False}
    if "error" in doc:
        # an autoscale leg that raised records {'error': ...}: honestly
        # unavailable, the failure carried as the skip reason
        return {"available": False,
                "skip_reason": doc.get("skip_reason") or doc.get("error")}
    plan = doc.get("plan") or {}
    decisions = [d for d in (doc.get("decisions") or [])
                 if isinstance(d, dict)]
    by_action: Dict[str, int] = {}
    for d in decisions:
        act = d.get("action") or "unknown"
        by_action[act] = by_action.get(act, 0) + 1
    tally = plan.get("rejected_tally") or {}
    cal = {
        metric: {k: c.get(k) for k in ("n_pairs", "correction_factor",
                                       "source")}
        for metric, c in (doc.get("calibration_used") or {}).items()
        if isinstance(c, dict)
    }
    by_class = {
        klass: {k: row.get(k)
                for k in ("n", "ok_within_slo", "attainment", "slo_s")}
        for klass, row in ((round_parsed or {}).get("slo_attainment_by_class")
                           or {}).items()
        if isinstance(row, dict)
    }
    return {
        "available": True,
        "plan": {
            "spec": plan.get("spec"),
            "target_replicas": plan.get("target_replicas"),
            "verdict": plan.get("verdict"),
            "demand_tokens_per_sec": plan.get("demand_tokens_per_sec"),
            "rejected": {"total": sum(tally.values()), "by_reason": tally},
        },
        "decisions": {
            "total": len(decisions),
            "by_action": by_action,
            "n_scale_up": doc.get("n_scale_up",
                                  by_action.get("scale_up", 0)),
            "n_scale_down": doc.get("n_scale_down",
                                    by_action.get("scale_down", 0)),
            "n_drained_scale_down": doc.get(
                "n_drained_scale_down",
                sum(1 for d in decisions
                    if d.get("action") == "scale_down"
                    and d.get("drained"))),
        },
        "boot_seconds": doc.get("boot_seconds"),
        # every decision that carries a forecast: the planner's predicted
        # attainment next to what the window actually delivered
        "predicted_vs_realized": [
            {"action": d.get("action"), "time_unix": d.get("time_unix"),
             "from_replicas": d.get("from_replicas"),
             "to_replicas": d.get("to_replicas"),
             "reason": d.get("reason"),
             "predicted_slo_attainment": d.get("predicted_slo_attainment"),
             "realized_slo_attainment": d.get("realized_slo_attainment")}
            for d in decisions
            if d.get("predicted_slo_attainment") is not None
            or d.get("realized_slo_attainment") is not None
        ],
        "calibration_pair": doc.get("calibration_pair"),
        "calibration": cal,
        "slo_attainment": (round_parsed or {}).get("slo_attainment"),
        "slo_attainment_by_class": by_class,
        "scale_regret": (round_parsed or {}).get("scale_regret"),
        "utilization": (round_parsed or {}).get("utilization"),
    }


def _interconnect_section(ledger: Optional[Dict[str, Any]]
                          ) -> Dict[str, Any]:
    """Interconnect accounting (--comms: a PADDLE_TPU_COMMSWATCH_DIR of
    per-rank commswatch.rank<k>.json journals, merged, or one journal
    file): the per-(kind, axis, size-bucket) measured bus-bandwidth
    table with its stated normalization, the per-axis collective-wall
    attribution, the per-link-class bandwidth summary, the
    barrier-skew verdict naming the suspect rank, and the
    predicted-bytes / measured-bandwidth vs measured-wall
    reconciliation with its explicit bound — the "my
    collective_fraction jumped, which link or rank is it" section."""
    from paddle_tpu import commswatch as _commswatch

    if not ledger:
        return {"available": False}
    sk = ledger.get("skew") or {}
    rec = ledger.get("reconciliation") or _commswatch.reconcile(doc=ledger)
    episodes = int(ledger.get("straggler_episodes")
                   or sk.get("straggler_episodes") or 0)
    skew = {
        "probes": sk.get("probes", 0),
        "skew_p50_s": sk.get("skew_p50_s"),
        "skew_p99_s": sk.get("skew_p99_s"),
        "suspect_rank": sk.get("suspect_rank"),
        "suspect_counts": sk.get("suspect_counts") or {},
        "straggler_episodes": episodes,
        "verdict": ("straggler" if episodes
                    else "healthy" if sk.get("probes") else "unprobed"),
    }
    return {
        "available": True,
        "ranks": ledger.get("ranks", [ledger.get("rank", 0)]),
        "steps": ledger.get("steps", 0),
        "collective_seconds": ledger.get("collective_seconds"),
        "bandwidth": ledger.get("bandwidth") or [],
        "by_axis": ledger.get("by_axis") or {},
        "link_classes": ledger.get("link_classes") or {},
        "skew": skew,
        "reconciliation": rec,
        "reconciliation_verdict": (
            ("within_bound" if rec.get("within_bound")
             else "outside_bound") if rec.get("available") else None),
    }


def _throughput_section(snap) -> Dict[str, Any]:
    out = {
        "fit_samples_per_sec": _scalar(snap, "fit_samples_per_sec"),
        "fit_steps_total": _scalar(snap, "fit_steps_total"),
        "fit_step_seconds": hist_summary(
            _hist_entry(snap, "fit_step_seconds")),
    }
    # bench.py publishes tokens/sec through the legacy stat gauges
    stats = snap.get("stats", {})
    for key in ("bench_tokens_per_sec", "tokens_per_sec"):
        if key in stats:
            out["tokens_per_sec"] = stats[key]
    return out


def _op_table(trace_events: Optional[List[dict]], top: int = 40) -> List[dict]:
    if not trace_events:
        return []
    from paddle_tpu import profiler

    rows = profiler.summarize_events(trace_events)
    return [
        {"name": name, "calls": calls, "total_us": round(tot, 1),
         "min_us": round(mn, 1), "max_us": round(mx, 1),
         "avg_us": round(avg, 1)}
        for name, calls, tot, mn, mx, avg in rows[:top]
    ]


def build_report(metrics_snapshot: Dict[str, Any],
                 trace_events: Optional[List[dict]] = None,
                 timeline_summary: Optional[Dict[str, Any]] = None,
                 xla_dump_records: Optional[Dict[str, dict]] = None,
                 goodput_ledger: Optional[Dict[str, Any]] = None,
                 memwatch_ledger: Optional[Dict[str, Any]] = None,
                 dynamics_ledger: Optional[Dict[str, Any]] = None,
                 serving_ledger: Optional[Dict[str, Any]] = None,
                 chaos_record: Optional[Dict[str, Any]] = None,
                 plan_record: Optional[Dict[str, Any]] = None,
                 autoscale_record: Optional[Dict[str, Any]] = None,
                 comms_ledger: Optional[Dict[str, Any]] = None,
                 ) -> Dict[str, Any]:
    compile_section = _compile_section(metrics_snapshot, xla_dump_records)
    return {
        "schema": REPORT_SCHEMA,
        "generated_from": {
            "metrics_schema": metrics_snapshot.get("schema"),
            "metrics_time_unix": metrics_snapshot.get("time_unix"),
            "n_trace_events": len(trace_events or []),
        },
        "executor": _executor_section(metrics_snapshot),
        # compiler-side accounting (per-program FLOPs / peak bytes from
        # the xla_insight gauges, enriched by --xla-dump artifacts)
        "compile": compile_section,
        "dataloader": _dataloader_section(metrics_snapshot),
        "ps": _ps_section(metrics_snapshot),
        "collectives": _collectives_section(metrics_snapshot),
        # DP comms: wire-vs-logical bytes (quantization ratio) + the
        # goodput collective seconds/fraction in one place
        "comms": _comms_section(metrics_snapshot, goodput_ledger),
        # comms plane: HLO-predicted collective traffic per program vs
        # the measured byte counters, with the reconciliation verdict
        "comms_plane": _comms_plane_section(metrics_snapshot,
                                            xla_dump_records),
        "throughput": _throughput_section(metrics_snapshot),
        # step-time attribution (goodput ledger journals: --goodput)
        "goodput": _goodput_section(goodput_ledger),
        # device-memory accounting (memwatch journals: --memwatch),
        # reconciled against the compile section's static estimates
        "memory": _memory_section(metrics_snapshot, memwatch_ledger,
                                  compile_section),
        # training-dynamics accounting (dynamics journals: --dynamics):
        # loss trajectory headline, anomaly episodes, desync probe
        "dynamics": _dynamics_section(metrics_snapshot, dynamics_ledger),
        # serving-plane accounting (serving journals: --serve): SLO
        # table, occupancy, serving goodput buckets, reconciliation
        # verdicts
        "serving": _serving_section(metrics_snapshot, serving_ledger),
        # per-request latency attribution + traffic telemetry (the
        # same --serve journals): bucket table per traffic class,
        # top-latency offenders, arrival-rate/burstiness summary,
        # residual verdict
        "request_attribution": _request_attribution_section(serving_ledger),
        # fault-plane accounting (chaos_bench records: --chaos):
        # detection latency / MTTR / steps lost + drift-audit verdict
        "recovery": _recovery_section(metrics_snapshot, chaos_record),
        # decision-plane accounting (auto_plan / mesh_bench --validate
        # records: --plan): planner pick, regret, predictor error,
        # rejected-candidate tally
        "plan": _plan_section(plan_record),
        # scale-plane accounting (serve_bench --autoscale rounds:
        # --autoscale, or the autoscale trail in the --serve journals):
        # capacity plan, scale-decision trail, predicted-vs-realized
        # attainment, calibration pair
        "autoscale": _autoscale_section(autoscale_record, serving_ledger),
        # interconnect accounting (commswatch journals: --comms):
        # measured per-(kind, axis, bucket) bus bandwidth, per-axis
        # attribution, link-class table, skew verdict with the named
        # suspect, predicted-vs-measured reconciliation
        "interconnect": _interconnect_section(comms_ledger),
        "stats": metrics_snapshot.get("stats", {}),
        "op_table": _op_table(trace_events),
        # multi-rank straggler view (tools/timeline.py) when --trace was
        # a PADDLE_TPU_TRACE_DIR of per-rank files; None for single traces
        "timeline": timeline_summary,
    }


def load_goodput_arg(path: str) -> Optional[Dict[str, Any]]:
    """--goodput accepts a PADDLE_TPU_GOODPUT_DIR of per-rank
    goodput.rank<k>.json journals (merged across ranks) or one journal
    file."""
    from paddle_tpu import goodput as _goodput

    if os.path.isdir(path):
        return _goodput.load_journals(path)
    return _goodput.load_journal(path)


def load_memwatch_arg(path: str) -> Optional[Dict[str, Any]]:
    """--memwatch accepts a PADDLE_TPU_MEMWATCH_DIR of per-rank
    memwatch.rank<k>.json journals (merged across ranks) or one
    journal file."""
    from paddle_tpu import memwatch as _memwatch

    if os.path.isdir(path):
        return _memwatch.load_journals(path)
    return _memwatch.load_journal(path)


def load_dynamics_arg(path: str) -> Optional[Dict[str, Any]]:
    """--dynamics accepts a PADDLE_TPU_DYNAMICS_DIR of per-rank
    dynamics.rank<k>.jsonl journals (merged across ranks, desync probe
    included) or one journal file."""
    from paddle_tpu import dynamics as _dynamics

    if os.path.isdir(path):
        return _dynamics.load_journals(path)
    return _dynamics.load_journal(path)


def load_serve_arg(path: str) -> Optional[Dict[str, Any]]:
    """--serve accepts a PADDLE_TPU_SERVE_DIR of per-replica
    serving.rank<k>.json journals (merged across replicas) or one
    journal file."""
    from paddle_tpu.serving import ledger as _serving

    if os.path.isdir(path):
        return _serving.load_journals(path)
    return _serving.load_journal(path)


def load_comms_arg(path: str) -> Optional[Dict[str, Any]]:
    """--comms accepts a PADDLE_TPU_COMMSWATCH_DIR of per-rank
    commswatch.rank<k>.json journals (merged across ranks; the
    reconciliation is computed per rank — predicted bytes and the
    collective wall are per-rank quantities — and the first available
    verdict rides the merged doc) or one journal file."""
    import glob as _glob

    from paddle_tpu import commswatch as _commswatch

    if not os.path.isdir(path):
        doc = _commswatch.load_journal(path)
        doc.setdefault("reconciliation", _commswatch.reconcile(doc=doc))
        return doc
    docs = []
    for p in sorted(_glob.glob(
            os.path.join(path, "commswatch.rank*.json"))):
        try:
            docs.append(_commswatch.load_journal(p))
        except (OSError, ValueError):
            continue
    if not docs:
        return None
    merged = _commswatch.merge_ledgers(docs)
    merged["reconciliation"] = {"available": False,
                                "reason": "no attributed steps in any "
                                          "rank journal"}
    for d in docs:
        rec = d.get("reconciliation") or _commswatch.reconcile(doc=d)
        if rec.get("available"):
            merged["reconciliation"] = rec
            break
    return merged


def load_xla_dump(dump_dir: str) -> Dict[str, dict]:
    """--xla-dump: PADDLE_TPU_XLA_DUMP_DIR -> {hash: cost record}."""
    from paddle_tpu.framework import xla_insight

    return xla_insight.load_dump_dir(dump_dir)


def load_trace_arg(trace: str):
    """--trace accepts a chrome-trace FILE or a PADDLE_TPU_TRACE_DIR of
    per-rank trace.rank<k>.json files. Returns (flat events for the op
    table, straggler summary or None)."""
    if os.path.isdir(trace):
        tl = _import_timeline()
        by_rank = tl.load_rank_traces(trace)
        events = [
            {"name": e["name"], "ts": e["ts"], "dur": e["dur"],
             "tid": e["tid"]}
            for evs in by_rank.values() for e in evs
        ]
        return events, (tl.straggler_summary(by_rank) if by_rank else None)
    return load_trace(trace), None


def load_trace(path: str) -> List[dict]:
    """chrome://tracing JSON -> profiler event dicts (full span names)."""
    with open(path) as f:
        doc = json.load(f)
    events = []
    for e in doc.get("traceEvents", []):
        if e.get("ph") != "X":
            continue
        name = e.get("args", {}).get("full_name") or e.get("name", "")
        events.append({"name": name, "ts": e.get("ts", 0.0),
                       "dur": e.get("dur", 0.0), "tid": e.get("tid", 0)})
    return events


def render_text(report: Dict[str, Any]) -> str:
    ex = report["executor"]
    lines = [
        "== paddle_tpu run report ==",
        f"executor: compiles={ex['compile_total']:.0f} "
        f"cache={ex['cache_hits']:.0f}h/{ex['cache_misses']:.0f}m "
        f"runs={ex['run_total']:.0f} "
        f"run_avg={ex['run_seconds']['avg']}s p99={ex['run_seconds']['p99']}",
    ]
    comp = report.get("compile") or {}
    if comp.get("n_programs"):
        lines.append(
            f"compile: {comp['n_programs']} program(s) "
            f"total_flops={comp['total_flops']:.3g} "
            f"max_peak={comp['max_peak_bytes'] / 1e6:.2f}MB")
        for h, p in list(comp["programs"].items())[:10]:
            line = (f"  program {h}: flops={p.get('flops') or 0:.3g} "
                    f"peak={(p.get('peak_bytes') or 0) / 1e6:.2f}MB")
            if p.get("build_s"):
                line += " build " + " ".join(
                    f"{st}={sec:.2f}s" for st, sec in p["build_s"].items()
                ) + f" cache={p.get('cache')}"
            lines.append(line)
    if comp.get("build_seconds"):
        lines.append("build (every jit): " + " ".join(
            f"{st}={sec:.2f}s/{comp['builds'].get(st, 0):.0f}"
            for st, sec in comp["build_seconds"].items()))
    dl = report["dataloader"]
    lines.append(
        f"dataloader: batches={dl['batches_total']:.0f} "
        f"depth={dl['queue_depth']:.0f} wait_avg={dl['wait_seconds']['avg']}s")
    for side in ("client", "server"):
        for method, row in report["ps"][side].items():
            rate = (f" {row['msgs_per_sec']}msg/s {row['mb_per_sec']}MB/s"
                    if "msgs_per_sec" in row else "")
            lines.append(
                f"ps.{side}.{method}: n={row['requests']:.0f}"
                f" lat_avg={row['latency_seconds']['avg']}s{rate}")
    for op, row in report["collectives"].items():
        lines.append(f"collective.{op}: calls={row['calls']:.0f} "
                     f"bytes={row['bytes']:.0f}")
    comms = report.get("comms") or {}
    if comms.get("available"):
        ratio = comms.get("compression_ratio")
        line = (f"comms: calls={comms['calls_total']:.0f} "
                f"wire={comms['wire_bytes_total']:.0f}B "
                f"logical={comms['logical_bytes_total']:.0f}B")
        if ratio is not None:
            line += f" compression={ratio:.2f}x"
        if comms.get("collective_seconds") is not None:
            line += (f" collective={comms['collective_seconds']:.3f}s"
                     f" ({(comms.get('collective_fraction') or 0) * 100:.1f}%"
                     f" of wall)")
        lines.append(line)
    plane = report.get("comms_plane") or {}
    if plane.get("available"):
        pred = plane["predicted"]
        meas = plane["measured"]
        rec = plane.get("reconciliation") or {}
        lines.append(
            f"comms plane: predicted "
            f"{pred['payload_bytes_per_execution']:.0f}B/exec over "
            f"{pred['n_programs_with_collectives']} program(s), measured "
            f"wire={meas['wire_bytes']:.0f}B "
            f"logical={meas['logical_bytes']:.0f}B — "
            f"{(rec.get('verdict') or 'n/a').upper()}"
            + (f" (ratio {rec['ratio']:.2f}, bound "
               f"x{rec['bound_factor']:g})"
               if rec.get("ratio") is not None else ""))
        for h, row in list(pred["per_program"].items())[:8]:
            kinds = ",".join(f"{k}x{int(v)}"
                             for k, v in sorted(row["by_kind"].items()))
            lines.append(f"  program {h}: {row['payload_bytes']:.0f}B/exec "
                         f"{kinds}")
    ic = report.get("interconnect") or {}
    if ic.get("available"):
        from paddle_tpu import commswatch as _commswatch

        lines.extend(_commswatch.render_summary(
            {k: ic.get(k) for k in ("link_classes", "by_axis", "skew",
                                    "reconciliation")}).splitlines())
    gp = report.get("goodput") or {}
    if gp.get("available"):
        # one renderer for the bucket table (launch teardown shares it)
        from paddle_tpu import goodput as _goodput

        doc = {
            "buckets": {b: r["seconds"] for b, r in gp["buckets"].items()},
            "wall_seconds": gp.get("wall_seconds", 0.0),
            "steps": gp.get("steps", 0),
            "goodput_fraction": gp.get("goodput_fraction"),
            "top_badput": gp.get("top_badput"),
        }
        lines.extend(_goodput.render_summary(doc).splitlines())
    mem = report.get("memory") or {}
    if mem.get("available"):
        from paddle_tpu import memwatch as _memwatch

        mem_doc = {
            "lifetime_peak_bytes": (mem.get("lifetime_peak_bytes")
                                    or mem.get("gauges", {}).get("peak_bytes")),
            "steps": mem.get("steps", 0),
            "bytes_in_use": mem.get("bytes_in_use"),
            "bytes_limit": mem.get("bytes_limit"),
            "leak_events": mem.get("leak_events", 0),
            "per_rank": mem.get("per_rank"),
            "reconciliation": mem.get("reconciliation"),
        }
        lines.extend(_memwatch.render_summary(mem_doc).splitlines())
    dyn = report.get("dynamics") or {}
    if dyn.get("available") and (dyn.get("steps") or dyn.get("per_rank")):
        from paddle_tpu import dynamics as _dynamics

        lines.extend(_dynamics.render_summary(dyn).splitlines())
        if dyn.get("final_window_loss") is not None:
            lines.append(f"  final_window_loss="
                         f"{dyn['final_window_loss']:.5f} over "
                         f"{dyn.get('n_recorded_steps', 0)} recorded "
                         f"step(s)")
    srv = report.get("serving") or {}
    if srv.get("available") and srv.get("ticks"):
        from paddle_tpu.serving import ledger as _serving

        srv_doc = {
            "buckets": {b: r["seconds"]
                        for b, r in srv.get("buckets", {}).items()},
            "wall_seconds": srv.get("wall_seconds", 0.0),
            "ticks": srv.get("ticks", 0),
            "goodput_fraction": srv.get("goodput_fraction"),
            "top_badput": srv.get("top_badput"),
            "slo": srv.get("slo"),
            "requests": (srv.get("slo") or {}).get("requests", {}),
        }
        lines.extend(_serving.render_summary(srv_doc).splitlines())
        for name, verdict in (srv.get("verdicts") or {}).items():
            if verdict:
                lines.append(f"  reconcile[{name}]: {verdict}")
    fo = srv.get("failover") or {}
    if srv.get("available") and fo:
        bm = fo.get("bitmatch") or {}
        lines.append(
            f"  failover: {fo.get('verdict')} "
            f"(retries={fo.get('retries') or 0:.0f} "
            f"hedges={fo.get('hedges') or 0:.0f} "
            f"failovers={fo.get('failovers') or 0:.0f} "
            f"reaped={fo.get('reaped') or 0:.0f} "
            f"shed={fo.get('shed') or 0:.0f} "
            f"bitmatch={bm.get('match', 0):.0f}/"
            f"{bm.get('match', 0) + bm.get('mismatch', 0):.0f})")
    ra = report.get("request_attribution") or {}
    if ra.get("available"):
        rec = ra.get("reconciliation") or {}
        lines.append(
            f"attribution: {ra['n_requests']} request(s), residual "
            f"p50={rec.get('residual_p50')} p99={rec.get('residual_p99')} "
            f"[{ra.get('verdict')}]")
        for klass, cls in ra["classes"].items():
            e2e = cls.get("e2e") or {}
            lines.append(f"  class {klass}: n={cls['n']} "
                         f"e2e p50={e2e.get('p50')}s p99={e2e.get('p99')}s")
            for b, row in (cls.get("buckets") or {}).items():
                lines.append(f"    {b:<16} n={row['count']} "
                             f"avg={row['avg']}s p99={row['p99']}s")
            off = (ra.get("offenders") or {}).get(klass)
            if off:
                lines.append(
                    f"    slowest: {off.get('request_id')} "
                    f"e2e={off.get('e2e_s')}s, dominated by "
                    f"{off.get('top_bucket')}={off.get('top_bucket_s')}s")
    tr = (ra or {}).get("traffic") or {}
    if tr.get("available"):
        for klass, row in tr["classes"].items():
            rates = row.get("rate_ema") or {}
            rate_txt = " ".join(f"{h}={v:.3f}/s"
                                for h, v in sorted(rates.items())
                                if v is not None)
            lines.append(f"  traffic[{klass}]: n={row.get('n')} {rate_txt} "
                         f"cv={row.get('interarrival_cv')} "
                         f"({row.get('burstiness')})")
    rcv = report.get("recovery") or {}
    if rcv.get("available") and rcv.get("recovery_seconds") is not None:
        audit = rcv.get("drift_audit") or {}
        lines.append(
            f"recovery: detection={rcv.get('detection_latency_s')}s "
            f"mttr={rcv.get('recovery_seconds')}s "
            f"steps_lost={rcv.get('steps_lost')} "
            f"bit_identical={rcv.get('resume_bit_identical')} "
            f"drift_audit={'PASS' if audit.get('ok') else 'FAIL'} "
            f"curve={'PASS' if rcv.get('curve_ok') else 'FAIL'}")
        if audit.get("failed_checks"):
            lines.append("  failed drift checks: "
                         + ", ".join(audit["failed_checks"]))
    pln = report.get("plan") or {}
    if pln.get("available"):
        pick = pln.get("pick") or {}
        rej = pln.get("rejected") or {}
        regret = pln.get("planner_regret")
        line = (f"plan: pick {pick.get('spec')} {pick.get('axes')} "
                f"({pln.get('n_feasible')}/{pln.get('n_candidates')} "
                f"feasible, rejected "
                + " ".join(f"{k}={v}" for k, v in
                           (rej.get("by_reason") or {}).items()) + ")")
        if regret is not None:
            line += (f" regret={regret:.4f}"
                     f" vs measured best {pln.get('measured_best')}")
        lines.append(line)
        for metric, c in (pln.get("calibration") or {}).items():
            if c.get("n_pairs"):
                lines.append(
                    f"  calibration[{metric}]: "
                    f"x{c['correction_factor']:g} over {c['n_pairs']} "
                    f"pair(s), residual {(c['residual_error'] or 0) * 100:.1f}%")
    auto = report.get("autoscale") or {}
    if auto.get("available"):
        apl = auto.get("plan") or {}
        dec = auto.get("decisions") or {}
        line = (f"autoscale: plan {apl.get('spec')} -> "
                f"{apl.get('target_replicas')} replica(s) "
                f"[{apl.get('verdict')}], "
                f"{dec.get('n_scale_up', 0)} up / "
                f"{dec.get('n_scale_down', 0)} down "
                f"({dec.get('n_drained_scale_down', 0)} drained)")
        if auto.get("slo_attainment") is not None:
            line += f" attainment={auto['slo_attainment']}"
            cls_txt = " ".join(
                f"{k}={v.get('attainment')}"
                for k, v in (auto.get("slo_attainment_by_class")
                             or {}).items())
            if cls_txt:
                line += f" ({cls_txt})"
        if auto.get("scale_regret") is not None:
            line += f" regret={auto['scale_regret']:.4f}"
        lines.append(line)
        for row in (auto.get("predicted_vs_realized") or [])[:8]:
            lines.append(
                f"  {row.get('action')}: {row.get('from_replicas')}->"
                f"{row.get('to_replicas')} ({row.get('reason')}) "
                f"predicted={row.get('predicted_slo_attainment')} "
                f"realized={row.get('realized_slo_attainment')}")
        pair = auto.get("calibration_pair") or {}
        if pair.get("config"):
            lines.append(
                f"  calibration[{pair['config']}]: predicted "
                f"{pair.get('predicted_tokens_per_sec_per_replica')} "
                f"tok/s, measured "
                f"{pair.get('measured_tokens_per_sec_per_replica')} "
                f"tok/s per replica")
    tp = report["throughput"]
    if tp.get("fit_steps_total"):
        lines.append(f"fit: steps={tp['fit_steps_total']:.0f} "
                     f"samples/s={tp['fit_samples_per_sec']:.1f}")
    if tp.get("tokens_per_sec"):
        lines.append(f"tokens/s: {tp['tokens_per_sec']}")
    if report["op_table"]:
        lines.append(f"{'op span':<40}{'calls':>7}{'total(us)':>12}{'avg':>9}")
        for row in report["op_table"][:20]:
            lines.append(f"{row['name']:<40}{row['calls']:>7}"
                         f"{row['total_us']:>12}{row['avg_us']:>9}")
    tl = report.get("timeline")
    if tl:
        lines.append(
            f"timeline: {len(tl['ranks'])} ranks, {tl['n_steps']} steps, "
            f"critical path {tl['total_critical_path_us'] / 1000.0:.2f}ms")
        for step, row in list(tl["steps"].items())[:10]:
            lines.append(
                f"  step {step}: critical={row['critical_path_us']:.0f}us "
                f"slowest=rank{row['slowest_rank']} skew={row['skew_us']:.0f}us")
        for op, row in tl["collectives"].items():
            lines.append(
                f"  straggler.{op}: slowest=rank{row['slowest_rank']} "
                f"({row['slowest_rank_counts']}) max={row['max_dur_us']:.0f}us")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# CI smoke (--self-test)
# ---------------------------------------------------------------------------


def self_test(tmpdir: Optional[str] = None, verbose: bool = True) -> Dict[str, Any]:
    """Tiny static-graph training run with metrics + profiler enabled;
    builds the merged report and asserts the required keys carry real
    series. Returns the report (CI: exit 0 == pass)."""
    import tempfile

    import paddle_tpu as paddle

    tmpdir = tmpdir or tempfile.mkdtemp(prefix="obs_report_selftest_")
    was_dygraph = paddle.in_dygraph_mode()
    paddle.enable_static()
    try:
        return _self_test_body(tmpdir, verbose)
    finally:
        if was_dygraph:
            paddle.disable_static()


def _self_test_body(tmpdir: str, verbose: bool) -> Dict[str, Any]:
    from paddle_tpu import monitor

    monitor.enable(True)
    monitor.reset_metrics()

    # compiler artifacts ride along: dump into the self-test tmpdir so
    # the --xla-dump path is exercised by the same tiny run
    xla_dump = os.path.join(tmpdir, "xla")
    prev_dump = os.environ.get("PADDLE_TPU_XLA_DUMP_DIR")
    os.environ["PADDLE_TPU_XLA_DUMP_DIR"] = xla_dump
    try:
        return _self_test_run(tmpdir, xla_dump, verbose)
    finally:
        if prev_dump is None:
            os.environ.pop("PADDLE_TPU_XLA_DUMP_DIR", None)
        else:
            os.environ["PADDLE_TPU_XLA_DUMP_DIR"] = prev_dump


def _self_test_run(tmpdir: str, xla_dump: str, verbose: bool) -> Dict[str, Any]:
    import time as _time

    import numpy as np

    from paddle_tpu import dynamics, goodput, memwatch, monitor, profiler, static
    from paddle_tpu.framework import Executor, Program, Scope, program_guard
    from paddle_tpu.io import DataLoader, TensorDataset
    from paddle_tpu.optimizer import SGD

    main, startup = Program(), Program()
    scope = Scope()
    with program_guard(main, startup):
        x = static.data("x", shape=[-1, 8], dtype="float32")
        y = static.data("y", shape=[-1, 1], dtype="float32")
        pred = static.nn.fc(x, size=1)
        loss = static.nn.reduce_mean(
            static.nn.square(static.nn.elementwise_sub(pred, y)))
        SGD(learning_rate=0.05).minimize(loss)

    exe = Executor()
    exe.run(startup, scope=scope)

    r = np.random.RandomState(0)
    ds = TensorDataset([r.rand(64, 8).astype("float32"),
                        r.rand(64, 1).astype("float32")])
    loader = DataLoader(ds, batch_size=16, shuffle=False)

    goodput.reset()  # a prior in-process run must not leak into the
    memwatch.reset()  # ledgers this self-test asserts on
    dynamics.reset()
    # DP comms coverage: a quantized bucket round-trip per step through
    # the real bucketer over a loopback 2-rank transport — records
    # collective calls + wire/logical bytes and a goodput collective
    # window INSIDE the step (so the flushed ledger's collective bucket
    # is non-zero and the comms section below carries real series)
    from paddle_tpu.distributed import comms as _comms

    class _P:
        def __init__(self, name, shape):
            self.name, self.shape, self.dtype = name, shape, "float32"
            self.trainable = True

    bucketer = _comms.GradBucketer(
        [_P("obs_selftest_w", (64, 64))], bucket_mb=1.0, overlap=False,
        quantize="int8", transport=_comms.LoopbackTransport(2))

    profiler.start_profiler()
    try:
        for xb, yb in loader:
            it0 = _time.perf_counter()
            out = exe.run(main, feed={"x": xb, "y": yb},
                          fetch_list=[loss], scope=scope)
            bucketer.grad_ready(
                "obs_selftest_w", np.asarray(r.randn(64, 64), "float32"))
            reduced = bucketer.sync()
            assert "obs_selftest_w" in reduced
            # stage the step's loss for the dynamics series (the fit
            # loop does this for real training) and close a ledger step
            # per batch — dynamics/memwatch close at the same boundary
            dynamics.feed(loss=float(np.asarray(out[0])))
            goodput.end_step(_time.perf_counter() - it0)
    finally:
        trace_path = os.path.join(tmpdir, "trace.json")
        profiler.stop_profiler(profile_path=trace_path)

    # goodput journal: flush per-rank, reload through the --goodput path
    gp_path = goodput.flush(os.path.join(tmpdir, "goodput.rank0.json"))
    gp_ledger = load_goodput_arg(os.path.dirname(gp_path))

    # memwatch journal: same flush/reload round trip (--memwatch path);
    # on CPU the ledger rides the deterministic synthetic fallback
    mw_path = memwatch.flush(os.path.join(tmpdir, "memwatch.rank0.json"))
    mw_ledger = load_memwatch_arg(mw_path)

    # dynamics journal: flush the recorded loss series, reload through
    # the --dynamics path (single journal AND the merged-dir route)
    dyn_path = dynamics.flush(os.path.join(tmpdir, "dynamics.rank0.jsonl"))
    dyn_ledger = load_dynamics_arg(dyn_path)

    # serving coverage: a tiny REAL engine round (continuous batching,
    # paged KV, per-request SLO records) journals through the --serve
    # dir path — the serving section below carries live series
    from paddle_tpu import serving
    from paddle_tpu.serving import ledger as serving_ledger

    serving_ledger.reset()
    scfg = serving.GPTConfig(vocab_size=64, n_layer=1, n_head=2,
                             d_model=16, max_seq_len=32)
    smodel = serving.DecodeModel(scfg, max_batch=2, n_blocks=8,
                                 block_size=8, prefill_buckets=[8],
                                 seed=0)
    sengine = serving.ServingEngine(smodel)
    shandles = [sengine.submit([1 + i, 2, 3], max_new_tokens=3)
                for i in range(2)]
    sengine.run_until_idle()
    stoks = [h.result(timeout=30) for h in shandles]
    assert all(len(t) == 3 for t in stoks), stoks
    serving_ledger.set_roofline(smodel.decode_roofline(mean_active=1.0))

    # failover coverage: one REAL router dispatch whose first replica
    # is unreachable (connect-refused HTTP) fails over — typed — onto
    # the live engine; the retry/failover counters feed the serving
    # section's failover verdict below, and the dispatch's latency
    # decomposition + arrival telemetry feed the request_attribution
    # section
    from paddle_tpu.serving.router import HttpReplica as _HttpReplica
    from paddle_tpu.serving.router import LocalReplica as _LocalReplica
    from paddle_tpu.serving.router import Router as _Router

    _router = _Router([_HttpReplica("a-dead", "http://127.0.0.1:9"),
                       _LocalReplica("live", sengine)],
                      retries=2, backoff_ms=1.0, hedge_ms=0,
                      default_slo_s=30.0)
    # force the dead replica first: the live one carries queue history
    _router._reps["live"].last_queued = 1
    fo_rec = _router.dispatch([1, 2, 3], max_new_tokens=2,
                              request_id="obs-fo")
    assert fo_rec["ok"] and fo_rec["failover"], fo_rec
    assert fo_rec["attempts"][0]["reason"] == "connect", fo_rec
    assert fo_rec["attribution"], fo_rec
    assert fo_rec["attribution_residual"] <= 0.05, fo_rec

    # journal AFTER the router drive so the engine-side attribution of
    # the dispatched request rides the replica journal, and the router's
    # own journal (role=router: its latency decomposition + the traffic
    # telemetry) merges in through the same --serve dir route
    serving_ledger.flush(os.path.join(tmpdir, "serving.rank0.json"))
    _router.flush_ledger(tmpdir)
    _router.stop()
    srv_ledger = load_serve_arg(tmpdir)  # the merged-dir route

    metrics_path = monitor.write_snapshot(
        os.path.join(tmpdir, "metrics.json"))
    prom_path = monitor.write_snapshot(
        os.path.join(tmpdir, "metrics.prom"), fmt="prom")

    with open(metrics_path) as f:
        snap = json.load(f)

    # timeline coverage: synthetic 2-rank traces through the same
    # --trace <dir> path the CLI takes (tools/timeline.py merge)
    tl = _import_timeline()
    rank_dir = os.path.join(tmpdir, "ranks")
    tl.write_synthetic_traces(rank_dir, ranks=2)
    _, timeline_summary = load_trace_arg(rank_dir)
    assert timeline_summary and timeline_summary["n_steps"] >= 1
    assert timeline_summary["collectives"]["all_reduce"]["slowest_rank"] == 1

    # comms-plane coverage: the tiny 1-chip run compiles no collectives,
    # so a synthetic sharded program's artifacts ride the same dump dir —
    # the predicted table, the measured counters (fed by the loopback
    # bucketer above) and the reconciliation verdict are all real paths
    from paddle_tpu.framework import shard_insight, xla_insight

    synth = xla_insight.ProgramInsight(key_hash="synthcomms00",
                                       label="comms-synth", flops=2e6)
    synth.collectives = shard_insight.comms_summary(
        "ENTRY %m (p: f32[64,64]) -> f32[64,64] {\n"
        "  %p = f32[64,64]{1,0} parameter(0)\n"
        "  ROOT %ar = f32[64,64]{1,0} all-reduce(f32[64,64]{1,0} %p), "
        "channel_id=1, replica_groups={{0,1},{2,3}}, to_apply=%add\n}\n",
        flops=2e6)
    xla_insight.dump_artifacts(synth, xla_dump)

    # recovery coverage: a chaos_bench-shaped record through the --chaos
    # path (the REQUIRED recovery section must carry detection latency,
    # MTTR, steps lost and the drift-audit verdict)
    chaos_rec = {
        "nranks": 2, "kill_step": 7, "ckpt_steps": 4,
        "killed_exit_code": 43, "kill_exit_expected": 43,
        "detection_seconds": 3.1, "recovery_seconds": 11.2,
        "steps_lost": 3, "resumed_from": 4,
        "typed_unavailable": True, "no_hang": True,
        "resume_bit_identical": True, "ef_residual_buckets": 2,
        "drift_audit": {"ok": True, "per_rank": {
            "0": {"ok": True, "checks": [
                {"check": "goodput_buckets_sum_to_wall", "ok": True,
                 "note": "..."}]}}},
        "curve_gate": {"ok": True}, "ok": True,
    }

    # decision-plane coverage: a mesh_bench --validate-shaped record
    # through the --plan path (the REQUIRED plan section must carry the
    # pick, the gated regret, the predictor-error table and the
    # rejected-candidate tally)
    plan_rec = {
        "schema": "paddle_tpu.plan_validate/1", "available": True,
        "n_candidates": 10, "n_feasible": 8, "top_k": 3,
        "pick": {"spec": "dp", "name": "dp", "axes": {"dp": 8},
                 "predicted": {"step_seconds": 3.1e-4,
                               "step_seconds_corrected": 1.93,
                               "peak_bytes": 1.7e8,
                               "bound_by": "collective"}},
        "rejected_tally": {"oom": 2, "comms-bound": 3,
                           "worse-roofline": 2},
        "calibration": {"step_seconds": {
            "n_pairs": 6, "correction_factor": 5200.0,
            "raw_error": 0.32, "residual_error": 0.16}},
        "planner_verdict": "ok",
        "validation": {"measured_step_seconds": {"dp": 1.9, "fsdp": 2.0},
                       "measured_best": "dp", "planner_regret": 0.0},
        "planner_regret": 0.0,
        "predictor_error": {"median": {"step_seconds": 0.98}},
    }

    # scale-plane coverage: a serve_bench --autoscale-shaped SERVE round
    # through the --autoscale path (the REQUIRED autoscale section must
    # carry the plan, the decision trail with predicted-vs-realized
    # attainment, the calibration pair and the gated headlines)
    auto_rec = {
        "schema": "paddle_tpu.serve_bench/1",
        "parsed": {
            "mode": "autoscale",
            "slo_attainment": 0.93,
            "slo_attainment_by_class": {
                "interactive": {"n": 40, "ok_within_slo": 36,
                                "attainment": 0.9, "slo_s": 3.0},
                "batch": {"n": 10, "ok_within_slo": 10,
                          "attainment": 1.0, "slo_s": 30.0}},
            "scale_regret": 0.125,
            "utilization": {"actual_replica_seconds": 30.0,
                            "oracle_replica_seconds": 24.0,
                            "mean_replicas": 1.25,
                            "over_provisioned_windows": 3,
                            "under_provisioned_windows": 0,
                            "batch_occupancy": 0.5},
            "autoscale": {
                "plan": {"spec": "r1/tp1/mb4", "target_replicas": 1,
                         "verdict": "ok",
                         "demand_tokens_per_sec": 144.6,
                         "rejected_tally": {"under-capacity": 1}},
                "decisions": [
                    {"action": "plan_change", "from_replicas": 1,
                     "to_replicas": 2, "reason": "plan r2/tp1/mb4",
                     "time_unix": 1.0,
                     "predicted_slo_attainment": 0.95,
                     "realized_slo_attainment": 0.9},
                    {"action": "scale_up", "replica": "replica1",
                     "from_replicas": 1, "to_replicas": 2,
                     "reason": "demand over capacity", "time_unix": 1.1,
                     "predicted_slo_attainment": 0.95,
                     "realized_slo_attainment": 0.92},
                    {"action": "drain_start", "replica": "replica1",
                     "from_replicas": 2, "to_replicas": 1,
                     "reason": "over-provisioned", "time_unix": 9.0},
                    {"action": "scale_down", "replica": "replica1",
                     "from_replicas": 2, "to_replicas": 1,
                     "reason": "over-provisioned", "drained": True,
                     "time_unix": 9.4,
                     "predicted_slo_attainment": 1.0,
                     "realized_slo_attainment": 1.0},
                ],
                "n_scale_up": 1, "n_scale_down": 1,
                "n_drained_scale_down": 1,
                "boot_seconds": [2.1],
                "calibration_pair": {
                    "config": "r1/tp1/mb4",
                    "predicted_tokens_per_sec_per_replica": 12000.0,
                    "measured_tokens_per_sec_per_replica": 870.0},
                "calibration_used": {"tokens_per_sec": {
                    "correction_factor": 0.0725, "n_pairs": 1,
                    "source": "warmup_probe"}},
            },
        },
    }

    # interconnect coverage: two synthetic per-rank commswatch journals
    # through the --comms dir path — sweep bandwidth rows on both link
    # classes, attributed steps (so the reconciliation is computable),
    # and a probe trail whose episode names rank 1 as the straggler
    from paddle_tpu import commswatch as _cw

    comms_dir = os.path.join(tmpdir, "comms")
    os.makedirs(comms_dir, exist_ok=True)
    for rank in (0, 1):
        led = _cw.CommsLedger()
        led.record_bandwidth("all_reduce", "dp", 1 << 20, 2, 0.004,
                             link_class="ici", source="sweep")
        led.record_bandwidth("all_gather", "tp", 1 << 18, 2, 0.002,
                             link_class="ici", source="sweep")
        led.record_bandwidth("all_reduce", "process", 1 << 18, 2, 0.01,
                             link_class="dcn", source="eager")
        led.configure_attribution({"dp": 2 * (1 << 20)})
        for s in range(4):
            led.end_step(collective_seconds=0.02, step=s)
        for _i in range(3):
            led.record_skew(
                {"skew_s": 0.04, "suspect_rank": 1,
                 "arrivals_rel": {"0": 0.0, "1": 0.04}},
                floor_s=0.01, episode_probes=2)
        comms_doc = led.totals()
        comms_doc["rank"] = rank
        with open(os.path.join(comms_dir,
                               f"commswatch.rank{rank}.json"), "w") as f:
            json.dump(comms_doc, f)
    comms_ledger = load_comms_arg(comms_dir)

    dump_records = load_xla_dump(xla_dump) if os.path.isdir(xla_dump) else None
    report = build_report(snap, load_trace(trace_path), timeline_summary,
                          dump_records, gp_ledger, mw_ledger, dyn_ledger,
                          srv_ledger, chaos_rec, plan_rec, auto_rec,
                          comms_ledger)

    for key in REQUIRED_KEYS:
        assert key in report, f"report missing {key!r}"
    pln = report["plan"]
    assert pln["available"], pln
    assert pln["pick"]["spec"] == "dp", pln
    assert pln["planner_regret"] == 0.0, pln
    assert pln["validated"] and pln["measured_best"] == "dp", pln
    assert pln["rejected"]["total"] == 7, pln
    assert pln["rejected"]["by_reason"]["oom"] == 2, pln
    assert pln["calibration"]["step_seconds"]["n_pairs"] == 6, pln
    assert pln["predictor_error"]["median"]["step_seconds"] == 0.98, pln
    # a MULTICHIP round wrapping the same record resolves identically,
    # and absence stays honest
    wrapped = _plan_section({"n_devices": 8, "plan": plan_rec})
    assert wrapped["planner_regret"] == 0.0, wrapped
    assert _plan_section(None) == {"available": False}
    # a round whose plan leg errored is honestly unavailable, with the
    # error surfaced as the skip reason — never a pick-less "plan"
    errored = _plan_section({"plan": {"error": "RuntimeError: boom"}})
    assert not errored["available"], errored
    assert "boom" in errored["skip_reason"], errored
    assert "plan: pick dp" in render_text(report), render_text(report)
    auto = report["autoscale"]
    assert auto["available"], auto
    assert auto["plan"]["spec"] == "r1/tp1/mb4", auto
    assert auto["plan"]["rejected"]["by_reason"]["under-capacity"] == 1, auto
    assert auto["decisions"]["total"] == 4, auto
    assert auto["decisions"]["by_action"]["drain_start"] == 1, auto
    assert auto["decisions"]["n_scale_up"] == 1, auto
    assert auto["decisions"]["n_drained_scale_down"] == 1, auto
    # the drain_start row carries no forecast, so only the three
    # forecast-bearing decisions land in the predicted-vs-realized table
    assert len(auto["predicted_vs_realized"]) == 3, auto
    assert auto["predicted_vs_realized"][0]["predicted_slo_attainment"] \
        == 0.95, auto
    assert auto["predicted_vs_realized"][0]["realized_slo_attainment"] \
        == 0.9, auto
    assert auto["calibration"]["tokens_per_sec"]["correction_factor"] \
        == 0.0725, auto
    assert auto["calibration_pair"]["config"] == "r1/tp1/mb4", auto
    assert auto["slo_attainment"] == 0.93, auto
    assert auto["slo_attainment_by_class"]["interactive"]["attainment"] \
        == 0.9, auto
    assert auto["scale_regret"] == 0.125, auto
    assert auto["utilization"]["mean_replicas"] == 1.25, auto
    # the merged --serve journals carrying the router's autoscale trail
    # resolve through the fallback path to the same plan
    via_ledger = _autoscale_section(
        None, {"autoscale": auto_rec["parsed"]["autoscale"]})
    assert via_ledger["available"], via_ledger
    assert via_ledger["plan"]["spec"] == "r1/tp1/mb4", via_ledger
    assert via_ledger["decisions"]["n_drained_scale_down"] == 1, via_ledger
    # absence stays honest, and an errored autoscale leg surfaces its
    # failure as the skip reason — never a decision-less "autoscale"
    assert _autoscale_section(None, None) == {"available": False}
    errored = _autoscale_section({"autoscale": {"error": "boom"}})
    assert not errored["available"] and "boom" in errored["skip_reason"]
    assert "autoscale: plan r1/tp1/mb4" in render_text(report), \
        render_text(report)
    rcv = report["recovery"]
    assert rcv["available"], rcv
    assert rcv["ok"] is True, rcv
    assert rcv["detection_latency_s"] == 3.1, rcv
    assert rcv["recovery_seconds"] == 11.2, rcv
    assert rcv["steps_lost"] == 3, rcv
    assert rcv["resume_bit_identical"] is True, rcv
    assert rcv["drift_audit"]["ok"] is True, rcv
    assert rcv["drift_audit"]["failed_checks"] == [], rcv
    assert rcv["curve_ok"] is True, rcv
    assert "chaos_injected" in rcv["counters"], rcv
    # the wrapped form (a MULTICHIP round carrying a chaos section)
    # resolves to the same view
    wrapped = _recovery_section(snap, {"n_devices": 8, "chaos": chaos_rec})
    assert wrapped["recovery_seconds"] == 11.2, wrapped
    # and without a record the section stays honest about absence
    bare = _recovery_section(snap)
    assert "available" in bare and "counters" in bare, bare
    srv = report["serving"]
    assert srv["available"], srv
    assert srv["ticks"] >= 1, srv
    # 2 direct submissions + the router-dispatched failover request
    assert srv["slo"]["requests"].get("ok", 0) == 3, srv
    assert srv["slo"]["tokens_per_sec"] and srv["slo"]["tokens_per_sec"] > 0
    assert srv["slo"]["ttft"]["p99"] is not None, srv
    assert srv["slo"]["latency"]["p50"] is not None, srv
    assert srv["slo"]["batch_occupancy"] is not None, srv
    # buckets sum to wall (the ledger contract survives the journal
    # round trip and the merge)
    srv_sum = sum(r["seconds"] for r in srv["buckets"].values())
    assert abs(srv_sum - srv["wall_seconds"]) < 1e-3, srv
    assert srv["top_badput"] is not None, srv
    assert srv["verdicts"]["span_vs_wall"] == "within_bound", srv
    assert srv["verdicts"]["measured_vs_roofline"] in (
        "within_bound", "outside_bound"), srv
    assert srv["gauges"]["requests"].get("ok", 0) >= 2, srv
    # the failover verdict: the router drive above retried a dead
    # replica onto the live engine, so the fault path shows as active
    fo = srv["failover"]
    assert fo["verdict"] == "failover_active", fo
    assert (fo["retries"] or 0) >= 1, fo
    assert (fo["failovers"] or 0) >= 1, fo
    assert not (fo["bitmatch"] or {}).get("mismatch"), fo
    # the request_attribution section: engine-side records (the direct
    # submissions + the dispatched request, class "engine") merged with
    # the router's full-stack record (class "default") through the same
    # --serve dir; buckets reconstruct the measured walls, the slowest
    # request names its dominant bucket, and the router's traffic
    # telemetry rides along
    ra = report["request_attribution"]
    assert ra["available"], ra
    assert ra["n_requests"] >= 4, ra
    assert "engine" in ra["classes"] and "default" in ra["classes"], ra
    eng_cls = ra["classes"]["engine"]
    assert eng_cls["n"] >= 3, eng_cls
    assert eng_cls["buckets"]["prefill_compute"]["count"] >= 3, eng_cls
    assert eng_cls["e2e"]["p50"] is not None, eng_cls
    dflt = ra["classes"]["default"]
    assert dflt["buckets"]["transport"]["count"] >= 1, dflt
    assert dflt["buckets"]["backoff_wait"]["count"] >= 1, dflt
    ra_rec = ra["reconciliation"]
    assert ra_rec["verdict"] == "within_bound", ra_rec
    assert ra_rec["residual_p50"] is not None, ra_rec
    assert ra_rec["residual_p50"] <= 0.05, ra_rec
    assert ra["offenders"] and all(
        o["top_bucket"] for o in ra["offenders"].values()), ra["offenders"]
    tr = ra["traffic"]
    assert tr["available"], tr
    assert tr["classes"]["default"]["n"] == 1, tr
    assert tr["depth"]["samples"] >= 1, tr
    assert "attribution: " in render_text(report), render_text(report)
    dyn = report["dynamics"]
    assert dyn["available"], dyn
    # one dynamics step closed per goodput.end_step (shared boundary)
    assert dyn["steps"] >= 4, dyn
    assert dyn["n_recorded_steps"] >= 4, dyn
    assert dyn["final_window_loss"] is not None, dyn
    assert dyn["anomalies_total"] == 0, dyn
    mem = report["memory"]
    assert mem["available"], mem
    # one memory step closed per goodput.end_step (the shared boundary)
    assert mem["steps"] >= 4, mem
    assert mem["lifetime_peak_bytes"] > 0, mem
    assert mem["source"] in ("device", "synthetic"), mem
    rec = mem["reconciliation"]
    assert rec["measured_peak_bytes"] and rec["static_peak_bytes"], rec
    assert rec.get("utilization") is not None, rec
    plane = report["comms_plane"]
    assert plane["available"], plane
    pred = plane["predicted"]
    assert pred["n_programs_with_collectives"] == 1, plane
    row = pred["per_program"]["synthcomms00"]
    assert row["payload_bytes"] == 64 * 64 * 4, row
    assert row["by_kind"].get("all-reduce") == 1, row
    # the loopback bucketer really moved bytes, so the measured side is
    # live and the verdict is a both-sided ratio, not a vacuous pass
    assert plane["measured"]["wire_bytes"] > 0, plane
    rec = plane["reconciliation"]
    assert rec["verdict"] in ("within_bound", "outside_bound",
                              "predicted_only", "measured_only"), rec
    assert rec["bound_factor"] >= 1.0, rec
    # the interconnect section: merged per-rank journals, the bandwidth
    # table with its stated normalization, both link classes, the
    # straggler verdict naming rank 1, and an in-bound reconciliation
    ic = report["interconnect"]
    assert ic["available"], ic
    assert ic["ranks"] == ["0", "1"], ic
    assert {r["kind"] for r in ic["bandwidth"]} >= {
        "all_reduce", "all_gather"}, ic["bandwidth"]
    ar = next(r for r in ic["bandwidth"]
              if r["kind"] == "all_reduce" and r["axis"] == "dp")
    assert ar["bus_factor"] == 1.0, ar  # 2(n-1)/n with n=2
    assert "busBW" in ar["normalization"], ar
    assert ar["samples"] == 2, ar  # one per rank journal, merged
    assert "ici" in ic["link_classes"] and "dcn" in ic["link_classes"], ic
    ic_sk = ic["skew"]
    assert ic_sk["verdict"] == "straggler", ic_sk
    assert ic_sk["suspect_rank"] == 1, ic_sk
    assert ic_sk["straggler_episodes"] >= 2, ic_sk  # one per rank
    ic_rec = ic["reconciliation"]
    assert ic_rec["available"] and ic_rec["within_bound"], ic_rec
    assert ic["reconciliation_verdict"] == "within_bound", ic
    assert ic["by_axis"]["dp"]["link_class"] == "ici", ic["by_axis"]
    assert "== interconnect: " in render_text(report), render_text(report)
    # absence stays honest
    assert _interconnect_section(None) == {"available": False}
    comms = report["comms"]
    assert comms["available"], comms
    assert "all_reduce_bucket_int8" in comms["ops"], comms
    q = comms["ops"]["all_reduce_bucket_int8"]
    assert q["calls"] >= 4, comms
    assert 0 < q["wire_bytes"] < q["logical_bytes"], comms
    # blockwise int8 + scales must compress the fp32 payload >= 3x
    assert comms["compression_ratio"] and comms["compression_ratio"] >= 3, comms
    assert comms["collective_seconds"] > 0, comms
    assert comms["collective_fraction"] is not None, comms
    gp = report["goodput"]
    assert gp["available"] and gp["steps"] >= 4, gp
    assert gp["wall_seconds"] > 0, gp
    # the tiny run compiled once and ran steps: both buckets must be real
    assert gp["buckets"]["compile"]["seconds"] > 0, gp
    assert gp["buckets"]["device_compute"]["seconds"] > 0, gp
    assert gp["top_badput"] is not None, gp
    assert 0.0 < (gp["goodput_fraction"] or 0.0) <= 1.0, gp
    ex = report["executor"]
    assert ex["compile_total"] >= 1, ex
    assert ex["run_total"] >= 4, ex
    assert ex["cache_hits"] >= 1, ex
    comp = report["compile"]
    assert comp["n_programs"] >= 1, comp
    assert comp["total_flops"] > 0, comp
    assert comp["max_peak_bytes"] > 0, comp
    # the dump-dir enrichment really merged (label comes only from disk)
    assert any("label" in p for p in comp["programs"].values()), comp
    dl = report["dataloader"]
    assert dl["batches_total"] >= 4, dl
    assert dl["wait_seconds"]["count"] >= 4, dl
    prom = open(prom_path).read()
    assert "executor_compile_total" in prom
    assert "dataloader_wait_seconds_bucket" in prom

    report_path = os.path.join(tmpdir, "report.json")
    with open(report_path, "w") as f:
        json.dump(report, f, indent=1)
    if verbose:
        print(render_text(report))
        print(f"self-test OK: {report_path}")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--metrics", help="monitor.write_snapshot() JSON file")
    ap.add_argument("--trace", help="chrome-trace JSON from the profiler, "
                    "or a PADDLE_TPU_TRACE_DIR of per-rank "
                    "trace.rank<k>.json files (adds the straggler summary)")
    ap.add_argument("--xla-dump", help="PADDLE_TPU_XLA_DUMP_DIR of "
                    "program.<hash>.* compile artifacts (enriches the "
                    "compile section; tools/xla_report.py renders them "
                    "standalone)")
    ap.add_argument("--goodput", help="goodput ledger journal: a "
                    "PADDLE_TPU_GOODPUT_DIR of goodput.rank<k>.json "
                    "files (merged across ranks) or one journal file "
                    "(adds the step-time attribution section)")
    ap.add_argument("--memwatch", help="memory ledger journal: a "
                    "PADDLE_TPU_MEMWATCH_DIR of memwatch.rank<k>.json "
                    "files (merged across ranks) or one journal file "
                    "(fills the memory section: per-rank peaks, leak "
                    "events, estimate-vs-actual reconciliation)")
    ap.add_argument("--dynamics", help="training-dynamics journal: a "
                    "PADDLE_TPU_DYNAMICS_DIR of dynamics.rank<k>.jsonl "
                    "files (merged across ranks, cross-rank desync "
                    "probe included) or one journal file (fills the "
                    "dynamics section: loss trajectory headline, "
                    "anomaly episodes)")
    ap.add_argument("--serve", help="serving ledger journal: a "
                    "PADDLE_TPU_SERVE_DIR of serving.rank<k>.json "
                    "files (merged across replicas) or one journal "
                    "file (fills the serving section: SLO table, "
                    "occupancy, goodput buckets, reconciliation "
                    "verdicts)")
    ap.add_argument("--chaos", help="a tools/chaos_bench.py record JSON "
                    "or a MULTICHIP_r*.json carrying a 'chaos' section "
                    "(fills the recovery section: detection latency, "
                    "MTTR, steps lost, drift-audit verdict)")
    ap.add_argument("--plan", help="a tools/auto_plan.py report, a "
                    "mesh_bench --validate record, or a "
                    "MULTICHIP_r*.json carrying a 'plan' section (fills "
                    "the plan section: planner pick, planner_regret, "
                    "predictor error, rejected-candidate tally)")
    ap.add_argument("--autoscale", help="a tools/serve_bench.py "
                    "--autoscale SERVE round JSON, or any record "
                    "carrying an 'autoscale' section (fills the "
                    "autoscale section: capacity plan, scale-decision "
                    "trail, predicted-vs-realized SLO attainment, "
                    "scale_regret, calibration pair; when omitted, the "
                    "autoscale trail in the merged --serve journals is "
                    "used)")
    ap.add_argument("--comms", help="interconnect ledger journal: a "
                    "PADDLE_TPU_COMMSWATCH_DIR of "
                    "commswatch.rank<k>.json files (merged across "
                    "ranks) or one journal file (fills the "
                    "interconnect section: measured per-axis bus "
                    "bandwidth, barrier-skew verdict with the named "
                    "suspect rank, predicted-vs-measured "
                    "reconciliation)")
    ap.add_argument("--out", help="write the report JSON here (else stdout)")
    ap.add_argument("--format", choices=("json", "text"), default="json")
    ap.add_argument("--self-test", action="store_true",
                    help="run the CI smoke: tiny training run -> report")
    args = ap.parse_args(argv)

    if args.self_test:
        self_test()
        return 0

    if not args.metrics:
        ap.error("--metrics is required (or use --self-test)")
    with open(args.metrics) as f:
        snap = json.load(f)
    events, timeline_summary = (load_trace_arg(args.trace)
                                if args.trace else (None, None))
    dump_records = load_xla_dump(args.xla_dump) if args.xla_dump else None
    gp_ledger = load_goodput_arg(args.goodput) if args.goodput else None
    mw_ledger = load_memwatch_arg(args.memwatch) if args.memwatch else None
    dyn_ledger = load_dynamics_arg(args.dynamics) if args.dynamics else None
    srv_ledger = load_serve_arg(args.serve) if args.serve else None
    chaos_rec = None
    if args.chaos:
        with open(args.chaos) as f:
            chaos_rec = json.load(f)
    plan_rec = None
    if args.plan:
        with open(args.plan) as f:
            plan_rec = json.load(f)
    auto_rec = None
    if args.autoscale:
        with open(args.autoscale) as f:
            auto_rec = json.load(f)
    comms_ledger = load_comms_arg(args.comms) if args.comms else None
    report = build_report(snap, events, timeline_summary, dump_records,
                          gp_ledger, mw_ledger, dyn_ledger, srv_ledger,
                          chaos_rec, plan_rec, auto_rec, comms_ledger)
    rendered = (render_text(report) if args.format == "text"
                else json.dumps(report, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            f.write(rendered + "\n")
    else:
        print(rendered)
    return 0


if __name__ == "__main__":
    sys.exit(main())
