"""Kill-one-rank chaos benchmark: certify detection, recovery and drift.

CPU control-flow check, not a benchmark cell: every worker is a child
process pinned to forced-host CPU devices and never uses the chip, so
nothing it records is a device metric (chip_smoke.py is the on-chip path).

The MULTICHIP harness's fault leg (__graft_entry__._record_multichip_round)
and a standalone tool. Runs the same deterministic DataParallel training
job twice over real worker processes (rendezvoused over jax.distributed,
one CPU device each):

  baseline   uninterrupted — the reference loss trajectory
  chaos      attempt 0 arms ``kill_rank@step=<K>:rank=<R>``
             (paddle_tpu/chaos.py, seed-deterministic): rank R dies at
             the open of global step K with journals/checkpoints holding
             only what the cadence flushed — the honest SIGKILL shape.
             Survivors must surface typed ``errors.Unavailable`` (the
             bounded coordination-KV deadline, never a hang) within the
             configured detection window; the supervisor then sweeps the
             collective epoch (PADDLE_TPU_COLL_EPOCH) and respawns the
             set, which auto-resumes from the newest full-state
             checkpoint (params + optimizer incl. __dp_comms__
             error-feedback residuals + step + data cursor).

Measured and judged, in the measure->reconcile->gate idiom:

- detection_seconds  kill -> last survivor raising typed Unavailable
- recovery_seconds   kill -> every respawned rank training again (MTTR)
- steps_lost         kill step - checkpoint step actually resumed from
- resume_bit_identical   every rank's restored state digest equals the
  checkpoint's recorded digest (EF residuals included)
- drift_audit        paddle_tpu/recovery.py over before/after journal
  snapshots: buckets sum to wall, lifetime totals monotone, dynamics
  trajectory a clean prefix + continuation
- curve_gate         the killed-and-recovered run's merged loss curve
  against the uninterrupted baseline (equal curves, the quality bar)

Usage:
  python tools/chaos_bench.py --nranks 8 --steps 24      # full round
  python tools/chaos_bench.py --self-test                # in-process CI
      # smoke: record/audit/gate plumbing over synthetic inputs,
      # including perf_gate catching an injected +50% MTTR regression
      # (recovery history synthesized where rounds predate the chaos
      # section)
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

# worker model: deep narrow MLP (many parameter tensors -> several
# buckets at the tiny cap), small enough that one attempt finishes in
# seconds on the CPU simulator
HIDDEN = 64
DEPTH = 6
IN_DIM = 32
BATCH = 16
BUCKET_MB = 0.05

DEFAULT_STEPS = 24
DEFAULT_KILL_STEP = 15
DEFAULT_CKPT_STEPS = 6
DEFAULT_KILL_RANK = 1
DEFAULT_COLL_TIMEOUT_MS = 4000

# a survivor that DETECTED the dead peer (typed Unavailable) exits with
# this code after flushing its journals — distinct from the chaos kill
# code (43) and from an undetected crash, so the supervisor can tell
# "failed loudly as designed" from "fell over"
DETECT_EXIT_CODE = 23


def _free_port() -> int:
    from paddle_tpu.status import free_port

    return free_port()


# ---------------------------------------------------------------------------
# worker (one rank)
# ---------------------------------------------------------------------------


def worker_main(rank: int, nranks: int, steps: int) -> None:
    """One rank's training run through the REAL elastic stack: hapi
    Model.fit over DataParallel (int8-quantized bucketed grad sync),
    auto-checkpoint + auto-resume, goodput/dynamics journals flushed
    every step. Prints ``OK <json>`` on clean completion."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu as paddle  # noqa: F401
    from paddle_tpu import checkpoint as _checkpoint
    from paddle_tpu import goodput, nn
    from paddle_tpu.distributed.parallel import DataParallel
    from paddle_tpu.hapi.model import Callback, Model
    from paddle_tpu.optimizer import Adam
    from paddle_tpu.parallel.env import init_parallel_env

    init_parallel_env()

    rng = np.random.RandomState(7)
    layers: list = [nn.Linear(IN_DIM, HIDDEN), nn.ReLU()]
    for _ in range(DEPTH - 2):
        layers += [nn.Linear(HIDDEN, HIDDEN), nn.ReLU()]
    layers += [nn.Linear(HIDDEN, 1)]
    net = nn.Sequential(*layers)
    # deterministic identical init on every rank (the DP contract)
    for p in net.parameters():
        scale = 1.0 / np.sqrt(max(p.shape[0], 1))
        p.set_value(rng.uniform(-scale, scale, p.shape).astype(np.float32))

    data_rng = np.random.RandomState(11)
    total = BATCH * steps
    x = data_rng.randn(nranks, total, IN_DIM).astype(np.float32)
    w_true = (data_rng.randn(IN_DIM, 1) / np.sqrt(IN_DIM)).astype(np.float32)
    xs = x[rank]
    ys = (xs @ w_true + 0.05 * data_rng.randn(total, 1)).astype(np.float32)
    ds = [(xs[i], ys[i]) for i in range(total)]

    dp = DataParallel(net)
    model = Model(dp)
    model.prepare(Adam(learning_rate=0.01, parameters=dp.parameters()),
                  loss=lambda pred, y: ((pred - y) ** 2).mean())

    # explicit resume probe BEFORE fit: restore the newest checkpoint and
    # assert bit-identity against its recorded digest (fit re-applies the
    # same doc — idempotent). This is the resume-equality oracle the
    # supervisor's resume_bit_identical headline aggregates.
    ck = _checkpoint.from_env()
    resumed_from = None
    bit_identical = None
    ef_buckets = 0
    if ck is not None:
        doc = ck.load_latest()
        if doc is not None:
            resumed_from = int(doc["step"])
            ck.restore(model.network, model._optimizer, doc)
            bit_identical = bool(
                ck.current_digest(model.network, model._optimizer)
                == doc.get("digest"))
            ef = (doc.get("optimizer") or {}).get("__dp_comms__") or {}
            ef_buckets = sum(len(v.get("residuals") or {})
                             for v in ef.values())

    stamps: Dict[str, float] = {}

    class _Stamps(Callback):
        def on_train_batch_end(self, step, logs=None):
            stamps.setdefault("t_first_step_unix", time.time())

    from paddle_tpu import dynamics as _dynamics
    from paddle_tpu.framework import errors as _errors

    try:
        model.fit(ds, batch_size=BATCH, epochs=1, shuffle=False,
                  verbose=0, callbacks=[_Stamps()])
    except _errors.errors.Unavailable as e:
        # detected a dead peer: the launcher's contract is fail-fast —
        # flush the journals, report the typed verdict, and exit hard
        # (jax.distributed's atexit shutdown barrier would otherwise
        # block this process on the dead rank for its full heartbeat
        # window, turning a 3s detection into a minute of exit badput)
        goodput.flush()
        _dynamics.flush()
        print("DETECTED " + json.dumps({
            "rank": rank,
            "time_unix": time.time(),
            "missing_rank": getattr(e, "missing_rank", None),
            "tag": getattr(e, "tag", None),
            "reason": getattr(e, "reason", None),
            "error": f"{type(e).__name__}: {str(e)[:300]}",
        }), flush=True)
        if jax.process_index() == 0:
            # this process HOSTS the coordination service (and the
            # failure epoch every survivor polls): linger one detection
            # deadline so peers finish their own typed detection against
            # a live KV store instead of watching it die under them
            from paddle_tpu import flags as _pflags

            time.sleep(
                _pflags.env_flag("PADDLE_TPU_COLL_TIMEOUT_MS") / 1e3
                + 1.0)
        os._exit(DETECT_EXIT_CODE)
    goodput.flush()

    totals = goodput.totals(include_open=False)
    report = {
        "rank": rank,
        "steps_completed": int(model._global_step),
        "resumed_from": resumed_from,
        "resume_bit_identical": bit_identical,
        "ef_residual_buckets": ef_buckets,
        "t_first_step_unix": stamps.get("t_first_step_unix"),
        "t_end_unix": time.time(),
        "goodput_steps": totals["steps"],
        "goodput_fraction": totals["goodput_fraction"],
        "final_digest": (ck.current_digest(model.network, model._optimizer)
                         if ck is not None else None),
    }
    print("OK " + json.dumps(report), flush=True)


# ---------------------------------------------------------------------------
# supervisor
# ---------------------------------------------------------------------------


def _attempt_env(nranks: int, journal_dir: str, ckpt_dir: str,
                 attempt: int, steps: int, ckpt_steps: int,
                 coll_timeout_ms: int,
                 chaos_sites: str = "", seed: int = 0) -> Dict[str, str]:
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # one CPU device per process
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO_ROOT] + env.get("PYTHONPATH", "").split(os.pathsep))
    env["PADDLE_TRAINERS_NUM"] = str(nranks)
    env["PADDLE_TRAINER_ENDPOINTS"] = coord
    # a worker must not inherit the operator's observability env
    for k in ("PADDLE_TPU_TRACE_DIR", "PADDLE_TPU_STATUS_PORT",
              "PADDLE_TPU_MEMWATCH_DIR", "PADDLE_TPU_SERVE_DIR",
              "PADDLE_TPU_CHAOS_SITES"):
        env.pop(k, None)
    env.update({
        # journals current to the last CLOSED step: a kill loses nothing
        # but the open step, which is exactly the honest contract
        "PADDLE_TPU_GOODPUT_DIR": journal_dir,
        "PADDLE_TPU_GOODPUT_FLUSH_STEPS": "1",
        "PADDLE_TPU_DYNAMICS_DIR": journal_dir,
        "PADDLE_TPU_DYNAMICS_FLUSH_STEPS": "1",
        # full-state recovery
        "PADDLE_TPU_CKPT_DIR": ckpt_dir,
        "PADDLE_TPU_CKPT_STEPS": str(ckpt_steps),
        "PADDLE_TPU_CKPT_KEEP": "2",
        # int8 bucketed DP sync, so the EF residuals ride the checkpoint
        "PADDLE_TPU_DP_BUCKET_MB": str(BUCKET_MB),
        "PADDLE_TPU_DP_OVERLAP": "1",
        "PADDLE_TPU_DP_QUANTIZE": "int8",
        # coordinated failure detection: bounded KV deadlines + the
        # launcher-swept collective epoch (attempt N+1 cannot pair with
        # attempt N's stale keys)
        "PADDLE_TPU_COLL_TIMEOUT_MS": str(coll_timeout_ms),
        "PADDLE_TPU_COLL_EPOCH": str(attempt),
        "PADDLE_RESTART_COUNT": str(attempt),
        "PADDLE_TPU_CHAOS_SEED": str(seed),
    })
    if chaos_sites:
        env["PADDLE_TPU_CHAOS_SITES"] = chaos_sites
    return env


def _spawn(env: Dict[str, str], nranks: int, steps: int
           ) -> List[subprocess.Popen]:
    procs = []
    for r in range(nranks):
        renv = dict(env)
        renv["PADDLE_TRAINER_ID"] = str(r)
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker",
             "--rank", str(r), "--nranks", str(nranks),
             "--steps", str(steps)],
            env=renv, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    return procs


def _watch(procs: List[subprocess.Popen], timeout: float) -> Dict[str, Any]:
    """Poll the attempt to completion, recording each rank's exit time
    (the supervisor-side clock the detection/recovery latencies use).
    A rank still alive at the deadline is killed and marked hung."""
    t0 = time.time()
    exit_time: Dict[int, float] = {}
    hung: List[int] = []
    while len(exit_time) < len(procs):
        alive = False
        for r, p in enumerate(procs):
            if r in exit_time:
                continue
            if p.poll() is None:
                alive = True
            else:
                exit_time[r] = time.time()
        if alive and time.time() - t0 > timeout:
            for r, p in enumerate(procs):
                if r not in exit_time:
                    p.kill()
                    hung.append(r)
                    exit_time[r] = time.time()
            break
        if alive:
            time.sleep(0.05)
    out: Dict[int, str] = {}
    for r, p in enumerate(procs):
        try:
            out[r] = p.communicate(timeout=10)[0] or ""
        except subprocess.TimeoutExpired:
            p.kill()
            out[r] = (p.communicate()[0] or "") + "\n<kill-timeout>"
    reports = {}
    detected = {}
    for r, text in out.items():
        for line in text.splitlines():
            if line.startswith("OK "):
                reports[r] = json.loads(line[3:])
            elif line.startswith("DETECTED "):
                detected[r] = json.loads(line[len("DETECTED "):])
    return {
        "rc": {r: p.returncode for r, p in enumerate(procs)},
        "exit_time": exit_time,
        "output": out,
        "reports": reports,
        "detected": detected,
        "hung": hung,
    }


# ---------------------------------------------------------------------------
# trajectory assembly over dynamics journals
# ---------------------------------------------------------------------------


def cover_series(series: List[dict]) -> List[dict]:
    """Latest record per step: the EFFECTIVE trajectory of a journal
    whose resume honestly re-ran the killed steps (prefix holds the
    first run's records, the continuation the re-run's — the re-run is
    what actually trained the surviving state)."""
    by: Dict[int, dict] = {}
    for s in series:
        if s.get("step") is not None:
            by[int(s["step"])] = s
    return [by[k] for k in sorted(by)]


def merged_trajectory(docs: List[dict]) -> Dict[str, list]:
    """Mean-across-ranks loss trajectory over each rank's cover — the
    global-batch curve curve_gate judges."""
    covers = [cover_series(d.get("series") or []) for d in docs]
    step_sets = [set(int(s["step"]) for s in c) for c in covers if c]
    if not step_sets:
        return {"steps": [], "loss": []}
    common = sorted(set.intersection(*step_sets))
    loss_by = [{int(s["step"]): float(s["loss"]) for s in c
                if s.get("loss") is not None} for c in covers]
    steps, losses = [], []
    for st in common:
        vals = [lb[st] for lb in loss_by if st in lb]
        if len(vals) == len(covers):
            steps.append(st)
            losses.append(round(sum(vals) / len(vals), 6))
    return {"steps": steps, "loss": losses}


def _load_journals(journal_dir: str, nranks: int) -> Dict[str, dict]:
    from paddle_tpu import dynamics as _dynamics
    from paddle_tpu import goodput as _goodput

    gp, dyn = {}, {}
    for r in range(nranks):
        gpath = os.path.join(journal_dir, f"goodput.rank{r}.json")
        dpath = os.path.join(journal_dir, f"dynamics.rank{r}.jsonl")
        if os.path.exists(gpath):
            try:
                gp[r] = _goodput.load_journal(gpath)
            except (OSError, ValueError):
                pass
        if os.path.exists(dpath):
            try:
                dyn[r] = _dynamics.load_journal(dpath)
            except (OSError, ValueError):
                pass
    return {"goodput": gp, "dynamics": dyn}


def _curve_verdict(candidate_traj: dict, reference_traj: dict
                   ) -> Dict[str, Any]:
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import curve_gate
    finally:
        sys.path.pop(0)
    rows, ok = curve_gate.gate(
        {"loss_trajectory": candidate_traj},
        [{"loss_trajectory": reference_traj}])
    # a SKIP-only verdict (empty trajectory on either side) is NOT a
    # cert: the chaos record's curve PASS must mean a comparison ran
    compared = any(r.get("config") == "loss"
                   and r.get("verdict") == "PASS" for r in rows)
    return {
        "ok": bool(ok) and compared,
        "rows": [{k: r.get(k) for k in
                  ("config", "check", "n_refs", "candidate", "bound",
                   "verdict", "note") if r.get(k) is not None}
                 for r in rows if r.get("config") == "loss"],
    }


# ---------------------------------------------------------------------------
# the round
# ---------------------------------------------------------------------------


def run_chaos_round(nranks: int = 8, steps: int = DEFAULT_STEPS,
                    kill_step: int = DEFAULT_KILL_STEP,
                    ckpt_steps: int = DEFAULT_CKPT_STEPS,
                    kill_rank: int = DEFAULT_KILL_RANK,
                    coll_timeout_ms: int = DEFAULT_COLL_TIMEOUT_MS,
                    seed: int = 0,
                    timeout: float = 240.0,
                    workdir: Optional[str] = None) -> Dict[str, Any]:
    """The full kill-one-rank round; returns the ``chaos`` record the
    MULTICHIP round embeds (recovery_seconds / steps_lost are the
    perf_gate-checked headlines)."""
    import shutil
    import tempfile

    from paddle_tpu import chaos as _chaos
    from paddle_tpu import recovery as _recovery

    base = workdir or tempfile.mkdtemp(prefix="chaos_bench_")
    own_tmp = workdir is None
    paths = {}
    for leg in ("baseline", "chaos"):
        paths[leg] = {
            "journals": os.path.join(base, leg, "journals"),
            "ckpt": os.path.join(base, leg, "ckpt"),
        }
        for p in paths[leg].values():
            os.makedirs(p, exist_ok=True)

    try:
        # -- baseline leg: the uninterrupted reference curve ------------
        env = _attempt_env(nranks, paths["baseline"]["journals"],
                           paths["baseline"]["ckpt"], attempt=0,
                           steps=steps, ckpt_steps=ckpt_steps,
                           coll_timeout_ms=coll_timeout_ms, seed=seed)
        res = _watch(_spawn(env, nranks, steps), timeout)
        if any(rc != 0 for rc in res["rc"].values()):
            raise RuntimeError(
                "chaos_bench baseline leg failed: rc="
                f"{res['rc']} output="
                + " | ".join(o[-400:] for o in res["output"].values()))
        baseline_docs = _load_journals(paths["baseline"]["journals"],
                                       nranks)
        baseline_traj = merged_trajectory(
            list(baseline_docs["dynamics"].values()))

        # -- chaos leg, attempt 0: the kill -----------------------------
        sites = f"kill_rank@step={kill_step}:rank={kill_rank}"
        env0 = _attempt_env(nranks, paths["chaos"]["journals"],
                            paths["chaos"]["ckpt"], attempt=0,
                            steps=steps, ckpt_steps=ckpt_steps,
                            coll_timeout_ms=coll_timeout_ms,
                            chaos_sites=sites, seed=seed)
        res0 = _watch(_spawn(env0, nranks, steps), timeout)
        killed_rc = res0["rc"].get(kill_rank)
        t_kill = res0["exit_time"].get(kill_rank)
        survivors = [r for r in range(nranks) if r != kill_rank]
        detected = res0["detected"]
        detect_times = [detected[r]["time_unix"] for r in survivors
                        if r in detected]
        detection_seconds = (max(detect_times) - t_kill
                             if t_kill and len(detect_times)
                             == len(survivors) else None)
        # typed detection: every survivor surfaced errors.Unavailable
        # (a bounded deadline or the published failure epoch), exited
        # with the detect code, and none had to be killed by the
        # supervisor
        typed = all(
            r in detected
            and detected[r].get("reason") in ("timeout", "failure_epoch",
                                              "barrier_timeout",
                                              "coordination_lost")
            and res0["rc"].get(r) == DETECT_EXIT_CODE
            for r in survivors)
        no_hang = not res0["hung"]
        detect_reasons = sorted({d.get("reason")
                                 for d in detected.values()})
        # diagnostics for survivors that exited WITHOUT the typed
        # detect path: their rc and output tail make a failed round
        # self-explaining instead of a bare typed_unavailable=false
        survivor_rc = {str(r): res0["rc"].get(r) for r in survivors}
        undetected_tails = {
            str(r): res0["output"].get(r, "")[-600:]
            for r in survivors
            if r not in detected or res0["rc"].get(r) != DETECT_EXIT_CODE}
        before = _load_journals(paths["chaos"]["journals"], nranks)

        # -- chaos leg, attempt 1: epoch swept, full-state resume -------
        env1 = _attempt_env(nranks, paths["chaos"]["journals"],
                            paths["chaos"]["ckpt"], attempt=1,
                            steps=steps, ckpt_steps=ckpt_steps,
                            coll_timeout_ms=coll_timeout_ms, seed=seed)
        t_respawn = time.time()
        res1 = _watch(_spawn(env1, nranks, steps), timeout)
        if any(rc != 0 for rc in res1["rc"].values()):
            raise RuntimeError(
                "chaos_bench recovery attempt failed: rc="
                f"{res1['rc']} output="
                + " | ".join(o[-400:] for o in res1["output"].values()))
        after = _load_journals(paths["chaos"]["journals"], nranks)
        reports = res1["reports"]

        first_steps = [rep.get("t_first_step_unix")
                       for rep in reports.values()]
        recovery_seconds = (max(first_steps) - t_kill
                            if t_kill and all(first_steps) else None)
        resumed_from = sorted({rep.get("resumed_from")
                               for rep in reports.values()})
        steps_lost = (kill_step - resumed_from[0]
                      if len(resumed_from) == 1
                      and resumed_from[0] is not None else None)

        audits = {}
        for r in range(nranks):
            audits[r] = _recovery.drift_audit(
                goodput_before=before["goodput"].get(r),
                goodput_after=after["goodput"].get(r),
                dynamics_before=before["dynamics"].get(r),
                dynamics_after=after["dynamics"].get(r))
        drift_ok = all(a["ok"] for a in audits.values())

        chaos_traj = merged_trajectory(list(after["dynamics"].values()))
        curve = _curve_verdict(chaos_traj, baseline_traj)

        doc = build_record(
            nranks=nranks, steps=steps, kill_step=kill_step,
            ckpt_steps=ckpt_steps, kill_rank=kill_rank,
            coll_timeout_ms=coll_timeout_ms,
            killed_exit_code=killed_rc,
            kill_exit_expected=_chaos.KILL_EXIT_CODE,
            detection_seconds=detection_seconds,
            recovery_seconds=recovery_seconds,
            respawn_to_recovered_seconds=(
                max(first_steps) - t_respawn
                if all(first_steps) else None),
            steps_lost=steps_lost,
            resumed_from=(resumed_from[0] if len(resumed_from) == 1
                          else resumed_from),
            typed_unavailable=typed,
            detect_reasons=detect_reasons,
            survivor_rc=survivor_rc,
            undetected_tails=undetected_tails,
            no_hang=no_hang,
            resume_bit_identical=all(
                rep.get("resume_bit_identical") is True
                for rep in reports.values()),
            ef_residual_buckets=min(
                (rep.get("ef_residual_buckets") or 0
                 for rep in reports.values()), default=0),
            drift_audit={"ok": drift_ok,
                         "per_rank": {str(r): a for r, a in
                                      audits.items()}},
            curve_gate=curve,
            baseline_trajectory=baseline_traj,
            chaos_trajectory=chaos_traj,
        )
        return doc
    finally:
        if own_tmp:
            shutil.rmtree(base, ignore_errors=True)


def build_record(**kw) -> Dict[str, Any]:
    """Assemble + judge one chaos record (factored out so --self-test
    exercises the verdict logic without the multi-process run). ``ok``
    requires: the armed exit code, typed detection with no hang, a
    bit-identical resume with EF residuals present, a passing drift
    audit and a passing curve cert."""
    doc = dict(kw)
    doc["ok"] = bool(
        kw.get("killed_exit_code") == kw.get("kill_exit_expected")
        and kw.get("typed_unavailable")
        and kw.get("no_hang")
        and kw.get("resume_bit_identical")
        and (kw.get("ef_residual_buckets") or 0) > 0
        and (kw.get("steps_lost") is not None
             and 0 <= kw["steps_lost"] <= kw.get("ckpt_steps", 1 << 30))
        and (kw.get("drift_audit") or {}).get("ok")
        and (kw.get("curve_gate") or {}).get("ok"))
    return doc


REQUIRED_KEYS = (
    "nranks", "kill_step", "killed_exit_code", "detection_seconds",
    "recovery_seconds", "steps_lost", "typed_unavailable", "no_hang",
    "resume_bit_identical", "ef_residual_buckets", "drift_audit",
    "curve_gate", "ok",
)


# ---------------------------------------------------------------------------
# CI smoke (--self-test): in-process, no subprocesses
# ---------------------------------------------------------------------------


def _synth_series(steps, start=0, loss0=1.0):
    return [{"step": s, "loss": round(loss0 * (0.95 ** s), 6)}
            for s in range(start, steps)]


def self_test(verbose: bool = True) -> Dict[str, Any]:
    from paddle_tpu import recovery as _recovery

    # 1) trajectory assembly: the cover keeps the LAST record per step
    series = _synth_series(8) + _synth_series(8, start=4)
    cov = cover_series(series)
    assert [s["step"] for s in cov] == list(range(8)), cov
    traj = merged_trajectory([{"series": series}, {"series": series}])
    assert traj["steps"] == list(range(8)) and len(traj["loss"]) == 8

    # 2) drift audit wiring: a clean prefix+continuation passes; a
    # gapped resume and a rewritten history both fail
    gp_before = {"steps": 7, "wall_seconds": 7.0, "samples": 112.0,
                 "buckets": {"device_compute": 5.0, "collective": 1.0,
                             "input_wait": 0.5, "compile": 0.3,
                             "host_other": 0.2},
                 "goodput_fraction": 5.0 / 7.0}
    gp_after = {"steps": 13, "wall_seconds": 13.0, "samples": 208.0,
                "buckets": {"device_compute": 9.0, "collective": 2.0,
                            "input_wait": 1.0, "compile": 0.6,
                            "host_other": 0.4},
                "goodput_fraction": 9.0 / 13.0}
    dyn_before = {"series": _synth_series(7)}
    dyn_after = {"series": _synth_series(7) + _synth_series(12, start=4)}
    audit = _recovery.drift_audit(gp_before, gp_after, dyn_before,
                                  dyn_after)
    assert audit["ok"], audit
    gapped = {"series": _synth_series(7) + _synth_series(12, start=9)}
    assert not _recovery.drift_audit(
        gp_before, gp_after, dyn_before, gapped)["ok"]
    rewritten = {"series": _synth_series(12, loss0=2.0)}
    assert not _recovery.drift_audit(
        gp_before, gp_after, dyn_before, rewritten)["ok"]
    shrunk = dict(gp_after, steps=3)
    assert not _recovery.drift_audit(
        gp_before, shrunk, dyn_before, dyn_after)["ok"]

    # 3) the record's verdict logic
    good = dict(
        nranks=2, steps=12, kill_step=7, ckpt_steps=4, kill_rank=1,
        coll_timeout_ms=3000, killed_exit_code=43, kill_exit_expected=43,
        detection_seconds=3.2, recovery_seconds=9.5, steps_lost=3,
        resumed_from=4, typed_unavailable=True, no_hang=True,
        resume_bit_identical=True, ef_residual_buckets=4,
        drift_audit={"ok": True}, curve_gate={"ok": True},
        baseline_trajectory={"steps": [], "loss": []},
        chaos_trajectory={"steps": [], "loss": []})
    rec = build_record(**good)
    assert rec["ok"], rec
    for key in REQUIRED_KEYS:
        assert key in rec, f"record missing {key}"
    assert not build_record(**{**good, "typed_unavailable": False})["ok"]
    assert not build_record(**{**good, "resume_bit_identical": False})["ok"]
    assert not build_record(**{**good, "ef_residual_buckets": 0})["ok"]
    assert not build_record(
        **{**good, "drift_audit": {"ok": False}})["ok"]
    assert not build_record(**{**good, "steps_lost": None})["ok"]

    # 4) perf_gate's recovery checks over the MULTICHIP pattern: an
    # injected +50% MTTR regression must be caught (history synthesized
    # where rounds predate the chaos section — the committed MULTICHIP
    # rounds before this one carry no recovery metrics)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import perf_gate
    finally:
        sys.path.pop(0)
    history = perf_gate.load_history(REPO_ROOT,
                                     pattern="MULTICHIP_r*.json")
    history = perf_gate._augment_recovery_history(history or [])
    current = json.loads(json.dumps(history[-1]))
    tols = perf_gate._self_test_tolerances(current, history)
    rows_ok, ok = perf_gate.gate(current, history, tolerances=tols)
    assert ok, rows_ok
    slow = json.loads(json.dumps(current))
    perf_gate.parsed_result(slow)["recovery_seconds"] *= 1.5
    rows_bad, ok_bad = perf_gate.gate(slow, history, tolerances=tols)
    assert not ok_bad, "+50% MTTR regression slipped through"
    assert {r["check"]: r["verdict"] for r in rows_bad}[
        "recovery_seconds"] == "REGRESSION", rows_bad

    if verbose:
        print(f"chaos_bench self-test OK (synth audit checks pass, "
              f"{len(history)} MULTICHIP round(s) in the gate smoke)")
    return {"record": rec, "audit": audit,
            "gate_regression_rows": rows_bad}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--worker", action="store_true",
                    help="internal: run one rank (supervisor-spawned)")
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--nranks", type=int, default=8)
    ap.add_argument("--steps", type=int, default=DEFAULT_STEPS)
    ap.add_argument("--kill-step", type=int, default=DEFAULT_KILL_STEP)
    ap.add_argument("--ckpt-steps", type=int, default=DEFAULT_CKPT_STEPS)
    ap.add_argument("--kill-rank", type=int, default=DEFAULT_KILL_RANK)
    ap.add_argument("--coll-timeout-ms", type=int,
                    default=DEFAULT_COLL_TIMEOUT_MS)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--timeout", type=float, default=240.0)
    ap.add_argument("--out", help="write the chaos record JSON here")
    ap.add_argument("--self-test", action="store_true",
                    help="in-process CI smoke (no subprocesses)")
    args = ap.parse_args(argv)

    if args.worker:
        worker_main(args.rank, args.nranks, args.steps)
        return 0
    if args.self_test:
        self_test()
        return 0
    doc = run_chaos_round(
        nranks=args.nranks, steps=args.steps, kill_step=args.kill_step,
        ckpt_steps=args.ckpt_steps, kill_rank=args.kill_rank,
        coll_timeout_ms=args.coll_timeout_ms, seed=args.seed,
        timeout=args.timeout)
    text = json.dumps(doc, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text if not args.out else f"chaos round recorded: {args.out}")
    print(f"chaos round {'PASS' if doc.get('ok') else 'FAIL'}: "
          f"detection {doc.get('detection_seconds')}s, MTTR "
          f"{doc.get('recovery_seconds')}s, steps lost "
          f"{doc.get('steps_lost')}")
    return 0 if doc.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
