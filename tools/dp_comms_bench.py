"""Multi-process DP comms benchmark: per-param vs bucketed vs int8.

CPU control-flow check, not a benchmark cell: every worker is a child
process pinned to forced-host CPU devices and never uses the chip, so
nothing it records is a device metric (chip_smoke.py is the on-chip path).

The MULTICHIP harness's comms leg (__graft_entry__._record_multichip_round)
and a standalone tool. Spawns ``nranks`` real worker processes (one CPU
device each, rendezvoused over jax.distributed) per mode and trains the
same deterministic model on sharded data three ways:

  baseline   the legacy recipe: one blocking all-reduce per parameter
             after backward (PADDLE_TPU_DP_BUCKET_MB=0)
  bucketed   ~bucket-sized fused all-reduces dispatched as the backward
             produces each bucket's last grad (overlap on), exact fp32
  int8       bucketed + blockwise-int8 wire payloads with error feedback

Each worker runs the REAL stack — DataParallel, the tracer grad-ready
hooks, distributed/comms.py, the goodput ledger and collective byte
counters — and reports its loss trajectory, goodput bucket breakdown and
wire byte totals. The supervisor merges ranks per mode and judges the
modes against each other:

- collective_fraction (host seconds blocked on collectives / wall) must
  SHRINK from baseline to bucketed — the goodput-bucket acceptance the
  ROADMAP sets;
- int8 wire bytes must undercut exact wire bytes >= 3x;
- the int8 loss curve must pass tools/curve_gate.py's band/final checks
  against the exact curves (equal loss curves, EQuARX's bar).

Usage:
  python tools/dp_comms_bench.py --nranks 8 --steps 10      # supervisor
  python tools/dp_comms_bench.py --self-test                # 2-rank smoke
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

MODES = ("baseline", "bucketed", "int8")

# worker model/workload: MANY parameter tensors (deep, narrow MLP), so
# the per-parameter baseline pays one full collective round-trip per
# tensor per step — the per-call dispatch cost bucketing exists to
# amortize — while staying small enough that a mode finishes in ~15s
# with 8 ranks on the CPU simulator
HIDDEN = 128
DEPTH = 8
IN_DIM = 64
DEFAULT_STEPS = 10
BUCKET_MB = 0.2

_MODE_ENV: Dict[str, Dict[str, str]] = {
    "baseline": {"PADDLE_TPU_DP_BUCKET_MB": "0"},
    "bucketed": {"PADDLE_TPU_DP_BUCKET_MB": str(BUCKET_MB),
                 "PADDLE_TPU_DP_OVERLAP": "1",
                 "PADDLE_TPU_DP_QUANTIZE": ""},
    "int8": {"PADDLE_TPU_DP_BUCKET_MB": str(BUCKET_MB),
             "PADDLE_TPU_DP_OVERLAP": "1",
             "PADDLE_TPU_DP_QUANTIZE": "int8"},
}


def _free_port() -> int:
    from paddle_tpu.status import free_port

    return free_port()


# ---------------------------------------------------------------------------
# worker (one rank)
# ---------------------------------------------------------------------------


def worker_main(mode: str, rank: int, nranks: int, steps: int) -> None:
    """One rank's training run; prints ``OK <json>`` with its losses,
    goodput buckets and collective byte totals. Env (PADDLE_TRAINER_*,
    PADDLE_TPU_DP_*) is prepared by the supervisor."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import goodput, monitor
    from paddle_tpu import nn
    from paddle_tpu.distributed.parallel import DataParallel
    from paddle_tpu.optimizer import SGD
    from paddle_tpu.parallel.env import init_parallel_env

    init_parallel_env()

    rng = np.random.RandomState(7)
    layers: list = [nn.Linear(IN_DIM, HIDDEN), nn.ReLU()]
    for _ in range(DEPTH - 2):
        layers += [nn.Linear(HIDDEN, HIDDEN), nn.ReLU()]
    layers += [nn.Linear(HIDDEN, 1)]
    model = nn.Sequential(*layers)
    # deterministic identical init on every rank (the DP contract)
    for p in model.parameters():
        scale = 1.0 / np.sqrt(max(p.shape[0], 1))
        p.set_value(rng.uniform(-scale, scale, p.shape).astype(np.float32))

    data_rng = np.random.RandomState(11)
    total = 16 * nranks
    x = data_rng.randn(total, IN_DIM).astype(np.float32)
    w_true = (data_rng.randn(IN_DIM, 1) / np.sqrt(IN_DIM)).astype(np.float32)
    y = (x @ w_true + 0.05 * data_rng.randn(total, 1)).astype(np.float32)
    sl = slice(rank * 16, (rank + 1) * 16)
    xs, ys = paddle.to_tensor(x[sl]), paddle.to_tensor(y[sl])

    model = DataParallel(model)
    opt = SGD(learning_rate=0.02, parameters=model.parameters())

    # the comms PLAN: what this rank's gradient sync should ship per
    # step, computed from the deterministic bucket layout (the eager
    # path's counterpart of the HLO collective summary). Baseline mode
    # has no bucketer — its plan is one fp32 all-reduce per parameter.
    if model._comms is not None:
        plan = model._comms.predicted_step_bytes()
        predicted_wire_step = plan["wire_bytes"]
        predicted_logical_step = plan["logical_bytes"]
    else:
        predicted_wire_step = predicted_logical_step = sum(
            4 * int(np.prod(p.shape)) for p in model.parameters()
            if getattr(p, "trainable", True))

    def train_step():
        t0 = time.perf_counter()
        pred = model(xs)
        diff = pred - ys
        loss = (diff * diff).mean()
        loss_v = float(loss.numpy())
        model.scale_loss(loss).backward()
        model.apply_collective_grads()
        opt.step()
        opt.clear_grad()
        goodput.end_step(time.perf_counter() - t0, samples=16)
        return loss_v

    # warmup OUTSIDE the measured window: first-use compiles (the
    # quantizer's jitted encode/decode per bucket shape, tiny eager-op
    # programs) land here for every mode alike, so the measured
    # collective fraction is steady-state, not compile skew. The loss
    # trajectory still starts at step 0 — warmup steps train too.
    losses: List[float] = []
    for _ in range(2):
        losses.append(train_step())
    goodput.reset()
    monitor.reset_metrics()
    t_start = time.perf_counter()
    for _ in range(steps):
        losses.append(train_step())
    wall = time.perf_counter() - t_start

    totals = goodput.totals(include_open=False)
    snap = monitor.snapshot()

    def _sum_series(name: str) -> float:
        fam = snap.get("metrics", {}).get(name, {})
        return sum(float(s.get("value", 0.0)) for s in fam.get("series", []))

    report = {
        "rank": rank,
        "measured_steps": steps,
        "losses": [round(v, 6) for v in losses],
        "wall_seconds": round(wall, 6),
        "buckets": {k: round(v, 6) for k, v in totals["buckets"].items()},
        "collective_seconds": round(totals["buckets"]["collective"], 6),
        "collective_calls": _sum_series("collective_calls_total"),
        "wire_bytes": _sum_series("collective_bytes_total"),
        "logical_bytes": _sum_series("collective_logical_bytes_total"),
        # the plan side of the reconciliation, over the same measured
        # window the byte counters cover (post-warmup steps only)
        "predicted_wire_bytes": predicted_wire_step * steps,
        "predicted_logical_bytes": predicted_logical_step * steps,
    }
    print("OK " + json.dumps(report), flush=True)


# ---------------------------------------------------------------------------
# supervisor
# ---------------------------------------------------------------------------


def _run_mode(mode: str, nranks: int, steps: int,
              timeout: float) -> Dict[str, Any]:
    """Spawn one worker process per rank for ``mode``; returns the merged
    per-mode record (sum of rank walls/collective seconds, mean-across-
    ranks loss curve — the global-batch loss trajectory)."""
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # one CPU device per process
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO_ROOT] + env.get("PYTHONPATH", "").split(os.pathsep))
    env["PADDLE_TRAINERS_NUM"] = str(nranks)
    env["PADDLE_TRAINER_ENDPOINTS"] = coord
    # a worker must not inherit the operator's observability journals
    for k in ("PADDLE_TPU_GOODPUT_DIR", "PADDLE_TPU_TRACE_DIR",
              "PADDLE_TPU_STATUS_PORT", "PADDLE_TPU_MEMWATCH_DIR",
              "PADDLE_TPU_DYNAMICS_DIR", "PADDLE_TPU_COMMSWATCH_DIR"):
        env.pop(k, None)
    env.update(_MODE_ENV[mode])

    procs = []
    for r in range(nranks):
        renv = dict(env)
        renv["PADDLE_TRAINER_ID"] = str(r)
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker",
             "--mode", mode, "--rank", str(r), "--nranks", str(nranks),
             "--steps", str(steps)],
            env=renv, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    reports: Dict[int, dict] = {}
    errors: List[str] = []
    for r, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            out = (p.communicate()[0] or "") + "\n<timeout>"
        if p.returncode != 0:
            errors.append(f"rank {r} rc={p.returncode}: {out[-800:]}")
            continue
        for line in out.splitlines():
            if line.startswith("OK "):
                reports[r] = json.loads(line[3:])
    if len(reports) != nranks:
        raise RuntimeError(
            f"dp_comms mode {mode}: {len(reports)}/{nranks} ranks "
            f"reported; errors: {' | '.join(errors)[:2000]}")

    ranks = [reports[r] for r in sorted(reports)]
    steps_n = len(ranks[0]["losses"])
    merged_loss = [
        round(sum(rk["losses"][i] for rk in ranks) / nranks, 6)
        for i in range(steps_n)
    ]
    wall = sum(rk["wall_seconds"] for rk in ranks)
    coll = sum(rk["collective_seconds"] for rk in ranks)
    buckets = {
        b: round(sum(rk["buckets"].get(b, 0.0) for rk in ranks), 6)
        for b in ranks[0]["buckets"]
    }
    wire_bytes = sum(rk["wire_bytes"] for rk in ranks)
    logical_bytes = sum(rk["logical_bytes"] for rk in ranks)
    predicted_wire = sum(rk.get("predicted_wire_bytes", 0) for rk in ranks)
    predicted_logical = sum(rk.get("predicted_logical_bytes", 0)
                            for rk in ranks)
    # predicted-vs-measured reconciliation over the measured window: the
    # bucket-layout plan against the wire-honest counters, per mode —
    # the tripwire that catches the gradient sync shipping bytes its
    # plan never declared (or quietly dropping buckets)
    from paddle_tpu.framework import shard_insight as _shard

    reconciliation = {
        "wire": _shard.reconcile(predicted_wire, measured_bytes=wire_bytes,
                                 measured_kind="wire"),
        "logical": _shard.reconcile(predicted_logical,
                                    measured_bytes=logical_bytes),
    }
    return {
        "nranks": nranks,
        # byte/second totals cover the MEASURED steps (post-warmup);
        # the loss trajectory includes the warmup steps too (training
        # starts at step 0 either way)
        "steps": ranks[0].get("measured_steps", steps_n),
        "trajectory_steps": steps_n,
        "wall_seconds": round(wall, 6),
        "buckets": buckets,
        "collective_seconds": round(coll, 6),
        "collective_fraction": round(coll / wall, 6) if wall > 0 else None,
        "collective_calls": sum(rk["collective_calls"] for rk in ranks),
        "wire_bytes": wire_bytes,
        "logical_bytes": logical_bytes,
        "predicted_wire_bytes": predicted_wire,
        "predicted_logical_bytes": predicted_logical,
        "reconciliation": reconciliation,
        "loss_trajectory": {
            "steps": list(range(steps_n)),
            "loss": merged_loss,
        },
        "final_loss": merged_loss[-1],
        "per_rank_final_loss": [rk["losses"][-1] for rk in ranks],
    }


def _curve_verdict(candidate_traj: dict,
                   reference_trajs: List[dict]) -> Dict[str, Any]:
    """Judge the quantized mode's merged loss curve against the exact
    modes' curves with tools/curve_gate.py's own band/final machinery —
    the in-round 'equal loss curves' certification."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import curve_gate
    finally:
        sys.path.pop(0)
    history = [{"loss_trajectory": t} for t in reference_trajs]
    rows, ok = curve_gate.gate(
        {"loss_trajectory": candidate_traj}, history)
    return {
        "ok": bool(ok),
        "rows": [{k: r.get(k) for k in
                  ("config", "check", "n_refs", "candidate", "bound",
                   "verdict", "note") if r.get(k) is not None}
                 for r in rows if r.get("config") == "loss"],
    }


def run_comparison(nranks: int = 8, steps: int = DEFAULT_STEPS,
                   timeout: float = 240.0,
                   modes: tuple = MODES) -> Dict[str, Any]:
    """The full three-mode comparison; returns the ``dp_comms`` record
    the MULTICHIP round embeds."""
    results = {}
    for mode in modes:
        t0 = time.perf_counter()
        results[mode] = _run_mode(mode, nranks, steps, timeout)
        results[mode]["mode_wall_seconds"] = round(
            time.perf_counter() - t0, 3)
    doc: Dict[str, Any] = {"nranks": nranks, "steps": steps,
                           "modes": results}
    base, buck, q = (results.get("baseline"), results.get("bucketed"),
                     results.get("int8"))
    if base and buck:
        doc["collective_fraction_baseline"] = base["collective_fraction"]
        doc["collective_fraction_bucketed"] = buck["collective_fraction"]
        doc["collective_fraction_shrink"] = round(
            (base["collective_fraction"] or 0.0)
            - (buck["collective_fraction"] or 0.0), 6)
    if base and q and q["wire_bytes"]:
        # per-step wire cost of the quantized mode vs the exact baseline
        # (both sides measured by the wire-honest byte counters)
        doc["wire_bytes_ratio"] = round(
            (base["wire_bytes"] / base["steps"])
            / (q["wire_bytes"] / q["steps"]), 4)
    if q and base and buck:
        doc["curve_gate"] = _curve_verdict(
            q["loss_trajectory"],
            [base["loss_trajectory"], buck["loss_trajectory"]])
    # the round-level predicted-vs-measured headline: every mode's plan
    # must reconcile with its measured bytes (wire AND logical) — the
    # acceptance bar the MULTICHIP record carries
    doc["reconciliation_ok"] = all(
        mode["reconciliation"][k]["ok"]
        for mode in doc["modes"].values() for k in ("wire", "logical"))
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--worker", action="store_true",
                    help="internal: run one rank (supervisor-spawned)")
    ap.add_argument("--mode", default="bucketed", choices=MODES)
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--nranks", type=int, default=8)
    ap.add_argument("--steps", type=int, default=DEFAULT_STEPS)
    ap.add_argument("--timeout", type=float, default=240.0)
    ap.add_argument("--out", help="write the comparison JSON here")
    ap.add_argument("--self-test", action="store_true",
                    help="2-rank, 4-step smoke of all three modes")
    args = ap.parse_args(argv)

    if args.worker:
        worker_main(args.mode, args.rank, args.nranks, args.steps)
        return 0
    if args.self_test:
        import math

        doc = run_comparison(nranks=2, steps=4, timeout=args.timeout)
        for mode, rec in doc["modes"].items():
            assert all(math.isfinite(v)
                       for v in rec["loss_trajectory"]["loss"]), (
                mode, rec["loss_trajectory"])
        for mode, rec in doc["modes"].items():
            for kind in ("wire", "logical"):
                r = rec["reconciliation"][kind]
                assert r["ok"], (mode, kind, r)
                # the bucket-layout plan is exact bookkeeping of the
                # same payloads the counters record: agreement should be
                # near-perfect, not merely inside the bound
                if r["ratio"] is not None:
                    assert 0.95 <= r["ratio"] <= 1.05, (mode, kind, r)
        assert doc["reconciliation_ok"], doc
        cg = doc["curve_gate"]
        assert cg["ok"], cg
        # the band check must have REAL references (a divergence-filtered
        # empty reference set passes vacuously — that is not a cert)
        band = [r for r in cg["rows"] if r.get("check") == "band"]
        assert band and band[0].get("verdict") == "PASS", cg
        assert doc["wire_bytes_ratio"] >= 3.0, doc["wire_bytes_ratio"]
        print(json.dumps(doc, indent=1))
        print("dp_comms_bench self-test OK")
        return 0
    doc = run_comparison(nranks=args.nranks, steps=args.steps,
                         timeout=args.timeout)
    rendered = json.dumps(doc, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(rendered + "\n")
    print(rendered)
    return 0


if __name__ == "__main__":
    sys.exit(main())
