"""Cluster launcher: `python -m paddle_tpu.distributed.launch train.py`.

Counterpart of /root/reference/python/paddle/distributed/launch.py:214 and
fleet/launch_utils.py:409-440 — builds the cluster map and spawns one
worker process per *host* (not per chip: on TPU all local chips belong to
one process; SURVEY.md §7.2.6) with the same PADDLE_* env protocol:
PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM / PADDLE_CURRENT_ENDPOINT /
PADDLE_TRAINER_ENDPOINTS. Workers rendezvous via jax.distributed
(paddle_tpu.parallel.env.init_parallel_env) instead of NCCL-id broadcast.
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time
from typing import List


def _parse_args(argv=None):
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument(
        "--ips", type=str, default="127.0.0.1",
        help="comma-separated host ips of the job (reference --cluster_node_ips)",
    )
    p.add_argument(
        "--nproc_per_node", type=int, default=1,
        help="worker processes per host; >1 only for CPU-simulation runs "
        "(one process per TPU host owns all its chips)",
    )
    p.add_argument("--started_port", type=int, default=6170)
    p.add_argument("--log_dir", type=str, default=None)
    p.add_argument(
        "--trace_dir", type=str,
        default=os.environ.get("PADDLE_TPU_TRACE_DIR"),
        help="enable distributed tracing: every rank records spans and "
        "writes trace.rank<k>.json here (merge with tools/timeline.py); "
        "flight-recorder dumps from dead/hung ranks land here too",
    )
    p.add_argument(
        "--status_port", type=int,
        default=int(os.environ.get("PADDLE_TPU_STATUS_PORT", "0") or 0),
        help="serve a live status endpoint per rank: rank k binds "
        "status_port+k and answers /status, /metrics and /healthz "
        "(paddle_tpu.status); 0 disables",
    )
    p.add_argument(
        "--goodput_dir", type=str,
        default=os.environ.get("PADDLE_TPU_GOODPUT_DIR"),
        help="persist each rank's goodput ledger journal "
        "(goodput.rank<k>.json) here; the launcher prints the merged "
        "job-level goodput summary at teardown (defaults to --trace_dir "
        "when that is set)",
    )
    p.add_argument(
        "--dp_bucket_mb", type=float, default=None,
        help="gradient-sync bucket size (MB) exported to every rank as "
        "PADDLE_TPU_DP_BUCKET_MB; 0 restores the per-parameter "
        "all-reduce loop (unset: the ranks' env/default decides)",
    )
    p.add_argument(
        "--dp_quantize", type=str, default=None, choices=("none", "int8"),
        help="gradient all-reduce wire encoding exported as "
        "PADDLE_TPU_DP_QUANTIZE: int8 = blockwise-quantized with error "
        "feedback (~4x fewer wire bytes), none = exact fp32",
    )
    p.add_argument(
        "--dp_overlap", type=str, default=None, choices=("0", "1"),
        help="PADDLE_TPU_DP_OVERLAP for the ranks: 1 dispatches grad "
        "buckets during the backward (default), 0 defers them to the "
        "sync point (debugging aid)",
    )
    p.add_argument(
        "--serve", action="store_true",
        help="serving-replica mode: each spawned worker is a serving "
        "replica — PADDLE_TPU_SERVE_DIR is exported so every replica "
        "journals its serving ledger (serving.rank<k>.json; defaults "
        "to --serve_dir, then --goodput_dir/--trace_dir), and the "
        "supervisor prints the merged SLO summary (tokens/s, TTFT/p99, "
        "occupancy, serving goodput buckets) at teardown; with "
        "--elastic_retries > 0 a dead replica respawns IN PLACE (warm "
        "restart) regardless of --elastic_mode — replicas have no "
        "collective membership to restart together",
    )
    p.add_argument(
        "--serve_dir", type=str,
        default=os.environ.get("PADDLE_TPU_SERVE_DIR"),
        help="directory for the per-replica serving journals "
        "(PADDLE_TPU_SERVE_DIR exported to children under --serve)",
    )
    p.add_argument(
        "--ckpt_dir", type=str,
        default=os.environ.get("PADDLE_TPU_CKPT_DIR"),
        help="export PADDLE_TPU_CKPT_DIR to every rank: the hapi fit "
        "loop writes periodic atomic full-state training checkpoints "
        "(params + optimizer incl. EF residuals + step + data cursor) "
        "there and a respawned rank auto-resumes from the newest one — "
        "the recovery half of --elastic_retries",
    )
    p.add_argument(
        "--elastic_retries", type=int, default=0,
        help="restart the whole local worker set up to N times after a "
        "failure (job-level elasticity; workers resume from their "
        "auto-checkpoints — incubate.checkpoint.auto_checkpoint)",
    )
    p.add_argument(
        "--elastic_mode", type=str, default="restart_all",
        choices=("restart_all", "respawn_worker"),
        help="restart_all: any failure tears down and relaunches every "
        "local worker (collective mode needs consistent membership); "
        "respawn_worker: only the failed rank restarts in place (PS "
        "mode, where trainers are independent) — single-worker rejoin",
    )
    p.add_argument(
        "--heartbeat_endpoints", type=str, default="",
        help="comma-separated pserver endpoints to poll for trainer "
        "liveness; a LOCAL rank the servers consider dead while its "
        "process still runs (hung trainer) is killed and respawned",
    )
    p.add_argument(
        "--heartbeat_timeout", type=float, default=30.0,
        help="seconds without a beat before a trainer counts as dead",
    )
    p.add_argument("--host_rank", type=int, default=int(os.environ.get("POD_INDEX", "0")))
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def get_cluster_endpoints(ips: List[str], nproc: int, port: int) -> List[str]:
    eps = []
    for ip in ips:
        for i in range(nproc):
            eps.append(f"{ip}:{port + i}")
    return eps


def _shed_rank_observability() -> None:
    """The launcher imports paddle_tpu itself, so with the
    rank-observability env exported (PADDLE_TPU_STATUS_PORT, the
    PADDLE_TPU_*_DIR journal directories) the import wiring gave THIS
    process a rank identity it must not keep: release the status port
    (or rank 0's bind at base+0 fails) and drop journal persistence (or
    the launcher's exit flush clobbers rank 0's journals)."""
    try:
        from .. import journal, status

        status.stop_status_server()
        journal.disable_persistence()
    except Exception:
        pass  # observability shedding must never block the launch


def launch(args) -> int:
    """Spawn + supervise the local workers; with --elastic_retries, a
    failed worker set is torn down and restarted (the reference
    launch_utils.py:409-440 watch loop is fail-fast only; restart is the
    elastic extension, with auto-checkpoint providing resume)."""
    _shed_rank_observability()
    attempts = 0
    while True:
        rc = _launch_once(args, attempts)
        if rc == 0 or attempts >= args.elastic_retries:
            return rc
        attempts += 1
        time.sleep(1.0)


def _clear_heartbeat(endpoints: List[str], trainer_id: int) -> None:
    """Reset the pservers' stale timestamp for a killed+respawned rank so
    the fresh worker is not re-flagged before its first beat."""
    from .ps.rpc import PSClient

    for ep in endpoints:
        try:
            client = PSClient(ep, timeout=5.0, recv_timeout=5.0)
            client.call("heartbeat_clear", trainer_id=trainer_id)
            client.close()
        except Exception:
            continue


def _collect_flight_dumps(trace_dir: str, seen: set) -> List[str]:
    """Surface flight-recorder dumps (monitor.dump_flight_record files)
    that appeared since the last sweep — the launcher's 'what was the
    dead rank doing' report, printed as it reaps workers."""
    import glob
    import json as _json

    found = []
    for path in sorted(glob.glob(os.path.join(trace_dir, "flight.*.json"))):
        if path in seen:
            continue
        seen.add(path)
        line = f"[launch] flight-recorder dump: {path}"
        try:
            with open(path) as f:
                doc = _json.load(f)
            line = (f"[launch] flight-recorder dump from rank "
                    f"{doc.get('rank')} ({doc.get('reason') or 'unknown'}, "
                    f"{len(doc.get('events', []))} events, "
                    f"{len(doc.get('stacks', {}))} threads): {path}")
        except (OSError, ValueError):
            pass  # half-written dump: still name the file
        print(line, file=sys.stderr)
        found.append(path)
    return found


def _request_flight_dump(proc, wait: float = 1.0) -> None:
    """Ask a live-but-suspect worker to dump its flight record (SIGUSR1,
    handled by monitor.install_dump_handlers) before it is killed."""
    if not hasattr(signal, "SIGUSR1"):
        return
    try:
        proc.send_signal(signal.SIGUSR1)
    except OSError:
        return
    time.sleep(wait)  # give the handler a beat to write the file


def _print_goodput_summary(goodput_dir: str, nranks: int) -> None:
    """Merge this job's rank journals and print the job-level ledger —
    the launcher's 'where did the training seconds go' report, the last
    thing an operator sees after a run. Filtered to ranks < nranks so a
    stale journal from an earlier, larger run sharing the directory
    cannot skew the summary."""
    try:
        from .. import goodput as _goodput

        merged = _goodput.load_journals(goodput_dir, ranks=range(nranks))
        if merged and (merged["steps"] or sum(merged["buckets"].values())):
            print("[launch] " + _goodput.render_summary(
                merged,
                title=f"goodput ({len(merged['ranks'])} rank(s))"
            ).replace("\n", "\n[launch] "), file=sys.stderr)
    except Exception as e:  # a summary failure must not mask the job rc
        print(f"[launch] goodput summary unavailable: {e}", file=sys.stderr)


def _print_memory_summary(memwatch_dir: str, nranks: int) -> None:
    """The memory half of the teardown report: merged per-rank peaks +
    leak counts from the memwatch journals. Called on its own dir
    resolution (PADDLE_TPU_MEMWATCH_DIR, falling back to the goodput
    directory) so an operator who exported only the memwatch dir still
    gets the table."""
    try:
        from .. import memwatch as _memwatch

        merged = _memwatch.load_journals(memwatch_dir, ranks=range(nranks))
        if merged and merged.get("lifetime_peak_bytes"):
            print("[launch] " + _memwatch.render_summary(
                merged,
                title=f"memory ({len(merged['ranks'])} rank(s))"
            ).replace("\n", "\n[launch] "), file=sys.stderr)
    except Exception as e:
        print(f"[launch] memory summary unavailable: {e}", file=sys.stderr)


def _print_dynamics_summary(dynamics_dir: str, nranks: int) -> None:
    """The training-quality third of the teardown report: merged
    per-rank final losses + anomaly episode counts from the dynamics
    journals, including the cross-rank loss-desync probe — under data
    parallelism a rank whose curve drifts from the others signals broken
    gradient synchronization, and this is the one place every rank's
    trajectory is in hand to check it."""
    try:
        from .. import dynamics as _dynamics

        merged = _dynamics.load_journals(dynamics_dir, ranks=range(nranks))
        if merged and merged.get("steps"):
            print("[launch] " + _dynamics.render_summary(
                merged,
                title=f"dynamics ({len(merged['ranks'])} rank(s))"
            ).replace("\n", "\n[launch] "), file=sys.stderr)
    except Exception as e:
        print(f"[launch] dynamics summary unavailable: {e}", file=sys.stderr)


def _print_serving_summary(serve_dir: str, nranks: int) -> None:
    """The serving quarter of the teardown report: merged per-replica
    SLO table (tokens/s across replicas, exact-merged TTFT/latency
    histograms for job-level p50/p99, occupancy) + the serving goodput
    buckets and span reconciliation — the last thing an operator sees
    after a --serve run."""
    try:
        from ..serving import ledger as _serving_ledger

        merged = _serving_ledger.load_journals(serve_dir,
                                               ranks=range(nranks))
        if merged and (merged.get("ticks")
                       or any((merged.get("requests") or {}).values())):
            print("[launch] " + _serving_ledger.render_summary(
                merged,
                title=f"serving ({len(merged['ranks'])} replica(s))"
            ).replace("\n", "\n[launch] "), file=sys.stderr)
            rec = merged.get("span_reconciliation") or {}
            if rec.get("verdict"):
                print(f"[launch] serving span reconciliation: "
                      f"{rec['verdict']} (ratio "
                      f"{rec.get('ratio')}, bound "
                      f"x{rec.get('bound_factor')})", file=sys.stderr)
            # the autoscaler's trail, when a capacity loop ran over this
            # job: current plan + the typed scale decisions
            auto = merged.get("autoscale") or {}
            plan = auto.get("plan") or {}
            decisions = [d for d in (auto.get("decisions") or [])
                         if isinstance(d, dict)]
            if plan or decisions:
                ups = sum(1 for d in decisions
                          if d.get("action") == "scale_up")
                downs = sum(1 for d in decisions
                            if d.get("action") == "scale_down")
                drained = sum(1 for d in decisions
                              if d.get("action") == "scale_down"
                              and d.get("drained"))
                print(f"[launch] autoscale: plan {plan.get('spec')} -> "
                      f"{plan.get('target_replicas')} replica(s) "
                      f"[{plan.get('verdict')}], {ups} scale-up(s) / "
                      f"{downs} scale-down(s) ({drained} drained)",
                      file=sys.stderr)
                for d in decisions[-4:]:
                    pred = d.get("predicted_slo_attainment")
                    real = d.get("realized_slo_attainment")
                    print(f"[launch]   {d.get('action')}: "
                          f"{d.get('from_replicas')}->"
                          f"{d.get('to_replicas')} ({d.get('reason')})"
                          + (f" predicted={pred} realized={real}"
                             if pred is not None or real is not None
                             else ""), file=sys.stderr)
    except Exception as e:
        print(f"[launch] serving summary unavailable: {e}", file=sys.stderr)


def _stale_ranks(endpoints: List[str], timeout: float) -> List[int]:
    """Union of trainer ids any pserver's heartbeat monitor considers
    dead (server.py do_heartbeat_status — the supervisor-side consumer
    of heart_beat_monitor.h)."""
    import numpy as np

    from .ps.rpc import PSClient

    dead = set()
    for ep in endpoints:
        try:
            # bounded connect AND recv deadlines: the supervisor's
            # liveness must not depend on a hung pserver
            client = PSClient(ep, timeout=5.0, recv_timeout=5.0)
            rep = client.call("heartbeat_status", timeout=timeout)
            dead.update(int(t) for t in np.asarray(rep["dead"]).ravel())
            client.close()
        except Exception:
            continue  # an unreachable server cannot vote
    return sorted(dead)


def _launch_once(args, restart_count: int) -> int:
    ips = args.ips.split(",")
    endpoints = get_cluster_endpoints(ips, args.nproc_per_node, args.started_port)
    nranks = len(endpoints)
    local_base = args.host_rank * args.nproc_per_node

    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)
    trace_dir = args.trace_dir
    if trace_dir:
        trace_dir = os.path.abspath(trace_dir)
        os.makedirs(trace_dir, exist_ok=True)
    goodput_dir = args.goodput_dir or trace_dir
    if goodput_dir:
        goodput_dir = os.path.abspath(goodput_dir)
        os.makedirs(goodput_dir, exist_ok=True)
    serve_dir = None
    if args.serve:
        serve_dir = args.serve_dir or goodput_dir or trace_dir
        if serve_dir:
            serve_dir = os.path.abspath(serve_dir)
            os.makedirs(serve_dir, exist_ok=True)
    seen_dumps: set = set()

    respawns = [0] * args.nproc_per_node
    hb_eps = [e for e in args.heartbeat_endpoints.split(",") if e]

    def spawn(local_rank: int, attempt: int) -> subprocess.Popen:
        rank = local_base + local_rank
        env = dict(os.environ)
        env.update(
            {
                "PADDLE_TRAINER_ID": str(rank),
                "PADDLE_TRAINERS_NUM": str(nranks),
                "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
                "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
                "FLAGS_selected_tpus": str(local_rank),
                # job-level whole-set restarts and per-rank respawns are
                # DISTINCT attempt identities (auto-checkpoint dirs/logs)
                "PADDLE_RESTART_COUNT": str(restart_count),
                "PADDLE_RESPAWN_COUNT": str(attempt),
                # the launcher-swept collective epoch: every KV key the
                # eager collectives publish is scoped by it, so attempt
                # N+1 can never pair against attempt N's stale payloads
                # still sitting in a surviving coordination service
                "PADDLE_TPU_COLL_EPOCH": str(restart_count),
            }
        )
        if args.ckpt_dir:
            # full-state recovery plumbing: every rank checkpoints its
            # training state here and auto-resumes from it on respawn
            ckpt_dir = os.path.abspath(args.ckpt_dir)
            os.makedirs(ckpt_dir, exist_ok=True)
            env["PADDLE_TPU_CKPT_DIR"] = ckpt_dir
        else:
            # an unset flag sheds the inherited env (the PR-4 idiom): a
            # supervisor's stale dir must not resurrect on the children
            env.pop("PADDLE_TPU_CKPT_DIR", None)
        # DP comms recipe plumbing: one launcher flag configures every
        # rank's gradient-sync behavior (distributed/comms.py reads the
        # env live; the teardown goodput summary's `collective` row is
        # where the effect shows up)
        if args.dp_bucket_mb is not None:
            env["PADDLE_TPU_DP_BUCKET_MB"] = str(args.dp_bucket_mb)
        if args.dp_quantize is not None:
            env["PADDLE_TPU_DP_QUANTIZE"] = (
                "" if args.dp_quantize == "none" else args.dp_quantize)
        if args.dp_overlap is not None:
            env["PADDLE_TPU_DP_OVERLAP"] = args.dp_overlap
        if trace_dir:
            # distributed-tracing env plumbing: each rank traces itself
            # (profiler.py auto-enables) and writes trace.rank<k>.json +
            # flight dumps into the shared dir
            env["PADDLE_TPU_TRACE_DIR"] = trace_dir
            if "PADDLE_TPU_TRACE" not in env:
                env["PADDLE_TPU_TRACE"] = "1"
        if goodput_dir:
            # each rank journals its goodput ledger; the launcher merges
            # and prints the job-level summary at teardown. The memory
            # ledger (memwatch.rank<k>.json) shares the directory unless
            # the operator pointed PADDLE_TPU_MEMWATCH_DIR elsewhere
            env["PADDLE_TPU_GOODPUT_DIR"] = goodput_dir
            env.setdefault("PADDLE_TPU_MEMWATCH_DIR", goodput_dir)
            # the training-dynamics journal (dynamics.rank<k>.jsonl)
            # shares the directory too: the teardown merge runs the
            # cross-rank loss-desync probe over it
            env.setdefault("PADDLE_TPU_DYNAMICS_DIR", goodput_dir)
        else:
            # an explicitly-disabled flag must also shed the inherited
            # env, or the children re-enable what the operator turned off
            env.pop("PADDLE_TPU_GOODPUT_DIR", None)
        if serve_dir:
            # serving-replica plumbing: each replica journals its SLO
            # ledger (serving.rank<k>.json) into the shared dir; the
            # supervisor merges and prints the job SLO summary at
            # teardown. Per-replica /status ports ride --status_port.
            env["PADDLE_TPU_SERVE_DIR"] = serve_dir
        elif not args.serve:
            # not a serving job: shed any inherited serving env so
            # training children don't journal a phantom serving plane
            env.pop("PADDLE_TPU_SERVE_DIR", None)
        if args.status_port:
            # live per-rank introspection: rank k serves base+k
            # (paddle_tpu.status auto-binds at import). The printed link
            # honors the bind interface: loopback unless the operator
            # opted into external scraping via PADDLE_TPU_STATUS_HOST
            port = args.status_port + rank
            env["PADDLE_TPU_STATUS_PORT"] = str(port)
            bind = env.get("PADDLE_TPU_STATUS_HOST", "127.0.0.1")
            ip = (endpoints[rank].rsplit(":", 1)[0]
                  if bind not in ("127.0.0.1", "localhost") else bind)
            print(f"[launch] rank {rank} status: http://{ip}:{port}/status "
                  f"(also /metrics, /healthz)", file=sys.stderr)
        else:
            # --status_port 0 with the env exported: a per-rank port was
            # NOT assigned, so all ranks would fight over the inherited
            # one — disable instead
            env.pop("PADDLE_TPU_STATUS_PORT", None)
        cmd = [sys.executable, "-u", args.training_script] + args.training_script_args
        log = (
            open(os.path.join(args.log_dir, f"workerlog.{rank}"), "a")
            if args.log_dir
            else None
        )
        return subprocess.Popen(cmd, env=env, stdout=log, stderr=log)

    procs: List[subprocess.Popen] = [
        spawn(lr, restart_count) for lr in range(args.nproc_per_node)
    ]
    spawn_time = [time.monotonic()] * args.nproc_per_node

    # supervise (reference launch_utils.py TrainerProc watch loop).
    # restart_all: fail fast, the caller relaunches the set.
    # respawn_worker: the failed rank alone restarts in place (PS-mode
    # single-worker rejoin, the r4 verdict gap); hung workers flagged by
    # the pserver heartbeat are killed and respawned the same way.
    rc = 0
    last_hb = time.monotonic()
    try:
        alive = True
        while alive:
            alive = False
            for lr, p in enumerate(procs):
                code = p.poll()
                if code is None:
                    alive = True
                elif code != 0:
                    if trace_dir:  # a crashed rank may have dumped on TERM
                        _collect_flight_dumps(trace_dir, seen_dumps)
                    # serving replicas are independent by construction
                    # (no collective membership): a dead replica warm-
                    # restarts IN PLACE (params reload + serving-journal
                    # resume + router re-admission via /healthz) while
                    # the survivors keep serving — restart_all would
                    # tear down healthy replicas mid-traffic for no
                    # membership reason
                    if ((args.elastic_mode == "respawn_worker"
                         or (args.serve and args.elastic_retries > 0))
                            and respawns[lr] < args.elastic_retries):
                        respawns[lr] += 1
                        procs[lr] = spawn(lr, respawns[lr])
                        spawn_time[lr] = time.monotonic()
                        alive = True
                        continue
                    rc = code
                    for q in procs:
                        if q.poll() is None:
                            q.send_signal(signal.SIGTERM)
                    alive = False
                    break
            if (alive and hb_eps
                    and time.monotonic() - last_hb >= args.heartbeat_timeout / 3):
                last_hb = time.monotonic()
                for dead_rank in _stale_ranks(hb_eps, args.heartbeat_timeout):
                    lr = dead_rank - local_base
                    if not (0 <= lr < len(procs)) or procs[lr].poll() is not None:
                        continue
                    # a freshly respawned worker needs time for imports +
                    # first compile before its first beat clears the
                    # server's stale timestamp — grace-period it
                    if time.monotonic() - spawn_time[lr] < args.heartbeat_timeout:
                        continue
                    if args.elastic_mode != "respawn_worker":
                        # collective mode: membership must stay consistent
                        # — treat the hung rank as a whole-set failure
                        rc = 1
                        for q in procs:
                            if q.poll() is None:
                                q.send_signal(signal.SIGTERM)
                        alive = False
                        break
                    if respawns[lr] >= args.elastic_retries:
                        continue
                    if trace_dir:
                        # the rank is hung, not dead: ask for a flight
                        # dump (stacks + last spans) before killing it
                        _request_flight_dump(procs[lr])
                    procs[lr].terminate()
                    try:
                        procs[lr].wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        # SIGTERM blocked (truly hung): escalate
                        procs[lr].kill()
                        try:
                            procs[lr].wait(timeout=10)
                        except subprocess.TimeoutExpired:
                            continue  # unkillable; leave it to the OS
                    respawns[lr] += 1
                    _clear_heartbeat(hb_eps, dead_rank)
                    if trace_dir:
                        _collect_flight_dumps(trace_dir, seen_dumps)
                    procs[lr] = spawn(lr, respawns[lr])
                    spawn_time[lr] = time.monotonic()
            time.sleep(1)
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        if trace_dir:
            # SIGTERM handlers (monitor.install_dump_handlers) may still
            # be writing: one grace beat, then surface everything new
            time.sleep(0.5)
            _collect_flight_dumps(trace_dir, seen_dumps)
        mw_dir = os.environ.get("PADDLE_TPU_MEMWATCH_DIR") or goodput_dir
        dyn_dir = os.environ.get("PADDLE_TPU_DYNAMICS_DIR") or goodput_dir
        if goodput_dir or mw_dir or dyn_dir:
            # atexit journal flushes may trail the SIGTERM by a beat
            if not trace_dir:
                time.sleep(0.5)
        if goodput_dir:
            _print_goodput_summary(goodput_dir, nranks)
        if mw_dir:
            _print_memory_summary(mw_dir, nranks)
        if dyn_dir:
            _print_dynamics_summary(dyn_dir, nranks)
        if serve_dir:
            _print_serving_summary(serve_dir, nranks)
    return rc


def main(argv=None):
    sys.exit(launch(_parse_args(argv)))


if __name__ == "__main__":
    main()
