"""Interconnect microbenchmark: the MULTICHIP comms leg.

CPU control-flow check, not a benchmark cell: every worker is a child
process pinned to forced-host CPU devices and never uses the chip, so
nothing it records is a device metric (chip_smoke.py is the on-chip path).

Three legs, all feeding paddle_tpu/commswatch.py (the interconnect
ledger) and merged into one round record:

  sweep    a compiled-mesh bandwidth sweep: all-reduce / all-gather /
           reduce-scatter / all-to-all / permute over message sizes,
           per mesh axis of a {dp, tp} mesh, each timed and folded into
           the per-(kind, axis, size-bucket) table with the standard
           bus-bandwidth normalization stated per row (busBW = algBW x
           2(n-1)/n for all-reduce, x (n-1)/n for gather/scatter/a2a —
           the NCCL-tests convention). The in-process compiled mesh is
           the harness's ICI link class.
  skew     the straggler-localization probe as a dedicated leg: N real
           worker processes rendezvous (the dp_comms_bench spawn
           pattern), stamp per-rank arrivals on the shared unix clock
           via commswatch.barrier_probe, and the merged verdict names
           the last-arriving rank. Run twice — clean (headline:
           collective_skew_p99) and with an INJECTED delay on a chosen
           rank, proving localization names exactly that rank and the
           flight-recorder episode fires (memwatch-leak semantics).
  steady   steady-state attribution end to end: N worker processes run
           an eager all-reduce training-shaped loop (the cross-process
           KV path — the harness's DCN-proxy link class), goodput
           closes steps, commswatch pro-rates the measured collective
           wall through the configured predicted-bytes attribution,
           and reconcile() checks predicted-bytes / measured-bandwidth
           against the measured wall within the explicit bound.

The round's headline metrics (gated by tools/perf_gate.py over
MULTICHIP_r*.json):
  allreduce_bus_bw     median measured all-reduce bus bytes/s (sweep)
  collective_skew_p99  clean-leg p99 barrier skew seconds

Usage:
  python tools/comms_bench.py --nranks 8          # the full round
  python tools/comms_bench.py --self-test         # 2-rank/2-dev smoke

On this CPU container the absolute numbers are simulator artifacts —
the record states platform and link-class semantics so nothing
masquerades as TPU hardware — but the whole pipeline (sweep math,
journal schema, merge, verdicts, gate wiring) is the real one.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

SCHEMA = "paddle_tpu.comms_bench/1"

SWEEP_KINDS = ("all_reduce", "all_gather", "reduce_scatter",
               "all_to_all", "permute")
# per-rank message sizes: one latency-regime point, one
# bandwidth-regime point (power-of-two so every divisibility
# constraint below holds for axis sizes 2/4/8)
DEFAULT_SIZES = (1 << 16, 1 << 20)
DEFAULT_MESH = "dp=4,tp=2"
DEFAULT_STEPS = 6
DEFAULT_CALLS = 4
STEADY_NBYTES = 1 << 18  # 256KiB eager all-reduce payload


def _free_port() -> int:
    from paddle_tpu.status import free_port

    return free_port()


# ---------------------------------------------------------------------------
# sweep worker (one process, forced-host mesh)
# ---------------------------------------------------------------------------


def _parse_mesh(spec: str) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for part in spec.split(","):
        name, n = part.split("=")
        out[name.strip()] = int(n)
    return out


def sweep_live_mesh(axes: Dict[str, int],
                    sizes: Tuple[int, ...] = DEFAULT_SIZES,
                    iters: int = 3,
                    kinds: Tuple[str, ...] = SWEEP_KINDS) -> List[dict]:
    """Time every (kind, axis, size) collective on a mesh built from
    THIS process's jax devices, recording each measurement into the
    commswatch ledger (link class "ici" — the in-process compiled
    mesh). Importable by mesh_bench so its training legs carry the same
    per-axis bandwidth rows. Returns the list of per-point errors
    (empty on a clean sweep)."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu import commswatch

    n_devices = 1
    for n in axes.values():
        n_devices *= n
    devs = np.array(jax.devices()[:n_devices]).reshape(
        tuple(axes.values()))
    mesh = Mesh(devs, tuple(axes.keys()))

    def _fn(kind: str, axis: str, n_ax: int):
        if kind == "all_reduce":
            return lambda x: jax.lax.psum(x, axis), P(), P()
        if kind == "all_gather":
            return (lambda x: jax.lax.all_gather(x, axis),
                    P(), P())
        if kind == "reduce_scatter":
            return (lambda x: jax.lax.psum_scatter(
                x, axis, scatter_dimension=0, tiled=True), P(), P(axis))
        if kind == "all_to_all":
            return (lambda x: jax.lax.all_to_all(
                x, axis, split_axis=0, concat_axis=0, tiled=True),
                P(), P())
        if kind == "permute":
            perm = [(i, (i + 1) % n_ax) for i in range(n_ax)]
            return (lambda x: jax.lax.ppermute(x, axis, perm=perm),
                    P(), P())
        raise ValueError(kind)

    errors: List[dict] = []
    for axis, n_ax in axes.items():
        if n_ax <= 1:
            continue
        for kind in kinds:
            for size in sizes:
                n_elems = max(n_ax, int(size) // 4)
                n_elems -= n_elems % n_ax  # a2a/scatter divisibility
                x = jnp.zeros((n_elems,), jnp.float32)
                try:
                    fn, in_spec, out_spec = _fn(kind, axis, n_ax)
                    timed = jax.jit(shard_map(
                        fn, mesh=mesh, in_specs=in_spec,
                        out_specs=out_spec, check_vma=False))
                    jax.block_until_ready(timed(x))  # compile + warmup
                    best = None
                    for _ in range(iters):
                        t0 = time.perf_counter()
                        jax.block_until_ready(timed(x))
                        dt = time.perf_counter() - t0
                        best = dt if best is None else min(best, dt)
                    commswatch.record_bandwidth(
                        kind, axis, n_elems * 4, n_ax, best,
                        link_class="ici", source="sweep")
                except Exception as e:  # record, never abort the sweep
                    errors.append({"kind": kind, "axis": axis,
                                   "size": size,
                                   "error": f"{type(e).__name__}: "
                                            f"{str(e)[:300]}"})
    return errors


def sweep_worker_main(mesh_spec: str, sizes: Tuple[int, ...],
                      iters: int) -> None:
    """Run the sweep on a fresh ledger and print the bandwidth table.
    The supervisor forced ``xla_force_host_platform_device_count``
    before jax imported."""
    import jax

    from paddle_tpu import commswatch

    axes = _parse_mesh(mesh_spec)
    n_devices = 1
    for n in axes.values():
        n_devices *= n
    commswatch.reset()
    errors = sweep_live_mesh(axes, sizes, iters)
    doc = commswatch.totals()
    report = {
        "platform": jax.devices()[0].platform,
        "mesh": dict(axes),
        "n_devices": n_devices,
        "sizes": list(sizes),
        "iters": iters,
        "bandwidth": doc["bandwidth"],
        "link_classes": doc["link_classes"],
        "errors": errors,
    }
    print("OK " + json.dumps(report), flush=True)


def run_sweep(mesh_spec: str = DEFAULT_MESH,
              sizes: Tuple[int, ...] = DEFAULT_SIZES, iters: int = 3,
              timeout: float = 600.0) -> Dict[str, Any]:
    """Spawn the sweep worker with the forced-host device count (the
    mesh_bench leg pattern) and return its bandwidth table."""
    axes = _parse_mesh(mesh_spec)
    n_devices = 1
    for n in axes.values():
        n_devices *= n
    env = dict(os.environ)
    flags = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO_ROOT] + env.get("PYTHONPATH", "").split(os.pathsep))
    _pop_observability(env)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--worker", "sweep",
         "--mesh", mesh_spec, "--sizes",
         ",".join(str(s) for s in sizes), "--iters", str(iters)],
        env=env, capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(
            f"comms_bench sweep rc={proc.returncode}\n"
            f"{(proc.stderr or proc.stdout)[-2000:]}")
    for line in (proc.stdout or "").splitlines():
        if line.startswith("OK "):
            return json.loads(line[3:])
    raise RuntimeError("comms_bench sweep: no report line\n"
                       f"{(proc.stdout or '')[-2000:]}")


# ---------------------------------------------------------------------------
# multi-process legs (skew probe, steady attribution)
# ---------------------------------------------------------------------------


def _pop_observability(env: Dict[str, str]) -> None:
    # a leg must not inherit the operator's observability journals
    for k in ("PADDLE_TPU_GOODPUT_DIR", "PADDLE_TPU_TRACE_DIR",
              "PADDLE_TPU_STATUS_PORT", "PADDLE_TPU_MEMWATCH_DIR",
              "PADDLE_TPU_DYNAMICS_DIR", "PADDLE_TPU_COMMSWATCH_DIR"):
        env.pop(k, None)


def _spawn_ranks(worker: str, nranks: int, timeout: float,
                 extra_args: List[str],
                 extra_env: Optional[Dict[str, str]] = None
                 ) -> List[dict]:
    """dp_comms_bench's spawn pattern: one process per rank,
    rendezvoused over the coordination service; every rank must print
    ``OK <json>``."""
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # one CPU device per process
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO_ROOT] + env.get("PYTHONPATH", "").split(os.pathsep))
    env["PADDLE_TRAINERS_NUM"] = str(nranks)
    env["PADDLE_TRAINER_ENDPOINTS"] = coord
    _pop_observability(env)
    env.update(extra_env or {})

    procs = []
    for r in range(nranks):
        renv = dict(env)
        renv["PADDLE_TRAINER_ID"] = str(r)
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker",
             worker, "--rank", str(r), "--nranks", str(nranks)]
            + extra_args,
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
            f"comms_bench {worker}: {len(reports)}/{nranks} ranks "
            f"reported; errors: {' | '.join(errors)[:2000]}")
    return [reports[r] for r in sorted(reports)]


def skew_worker_main(rank: int, nranks: int, probes: int,
                     delay_rank: int, delay_ms: float) -> None:
    """One rank of the straggler-probe leg: ``probes`` barrier probes on
    the shared unix clock, with ``delay_ms`` injected before every probe
    on ``delay_rank`` (the localization proof)."""
    from paddle_tpu import commswatch
    from paddle_tpu.parallel.env import init_parallel_env

    init_parallel_env()
    commswatch.reset()
    delay_s = (delay_ms / 1e3) if rank == delay_rank else 0.0
    for i in range(probes):
        commswatch.barrier_probe(tag=f"bench{i}", delay_s=delay_s)
    doc = commswatch.totals()
    doc.pop("step_series", None)
    doc.pop("skew_series", None)
    print("OK " + json.dumps(doc), flush=True)


def run_skew(nranks: int = 4, probes: int = 4, delay_rank: int = -1,
             delay_ms: float = 0.0, floor_ms: Optional[float] = None,
             episode_probes: Optional[int] = None,
             timeout: float = 300.0) -> Dict[str, Any]:
    """The probe leg, merged across ranks. With an injected delay the
    merged verdict must name ``delay_rank``; the record carries both
    the expectation and whether localization met it."""
    from paddle_tpu import commswatch

    extra_env: Dict[str, str] = {}
    if floor_ms is not None:
        extra_env["PADDLE_TPU_COMMSWATCH_SKEW_FLOOR_MS"] = str(floor_ms)
    if episode_probes is not None:
        extra_env["PADDLE_TPU_COMMSWATCH_SKEW_PROBES"] = str(
            episode_probes)
    docs = _spawn_ranks(
        "skew", nranks, timeout,
        ["--probes", str(probes), "--delay-rank", str(delay_rank),
         "--delay-ms", str(delay_ms)],
        extra_env)
    merged = commswatch.merge_ledgers(docs)
    sk = merged["skew"]
    out: Dict[str, Any] = {
        "nranks": nranks,
        "probes_per_rank": probes,
        "skew": sk,
        "skew_p99_s": sk.get("skew_p99_s"),
        "per_rank": merged["per_rank"],
    }
    if delay_rank >= 0:
        out["injected"] = {"rank": delay_rank, "delay_ms": delay_ms}
        out["localized"] = (sk.get("suspect_rank") == delay_rank)
        out["episodes"] = sk.get("straggler_episodes", 0)
    return out


def steady_worker_main(rank: int, nranks: int, steps: int,
                       calls: int) -> None:
    """One rank of the attribution leg: a training-shaped loop of eager
    all-reduces (the cross-process KV path — the dcn-proxy link class)
    with goodput closing steps, the analytic per-step byte plan
    configured as the attribution weights, and reconcile() run at the
    end."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import commswatch, goodput
    from paddle_tpu.distributed import collective
    from paddle_tpu.parallel.env import init_parallel_env

    init_parallel_env()
    commswatch.reset()
    goodput.reset()

    tensor = paddle.to_tensor(
        np.ones(STEADY_NBYTES // 4, np.float32))
    # the analytic plan for this loop: `calls` all-reduces of the known
    # payload per step — the predicted-bytes side of the reconciliation
    commswatch.configure_attribution(
        {"process": calls * STEADY_NBYTES},
        link_classes={"process": "dcn"})

    # warmup outside the measured window (KV-path first-contact setup)
    collective.all_reduce(tensor)
    goodput.reset()
    commswatch.reset()
    commswatch.configure_attribution(
        {"process": calls * STEADY_NBYTES},
        link_classes={"process": "dcn"})
    for s in range(steps):
        t0 = time.perf_counter()
        for _ in range(calls):
            collective.all_reduce(tensor)
        goodput.end_step(time.perf_counter() - t0, step=s)

    doc = commswatch.totals()
    rec = commswatch.reconcile(doc=doc)
    doc.pop("step_series", None)
    doc.pop("skew_series", None)
    doc["reconciliation"] = rec
    print("OK " + json.dumps(doc), flush=True)


def run_steady(nranks: int = 4, steps: int = DEFAULT_STEPS,
               calls: int = DEFAULT_CALLS,
               timeout: float = 300.0) -> Dict[str, Any]:
    """The steady-state attribution leg, merged across ranks."""
    from paddle_tpu import commswatch

    docs = _spawn_ranks("steady", nranks, timeout,
                        ["--steps", str(steps), "--calls", str(calls)])
    merged = commswatch.merge_ledgers(docs)
    recs = [d.get("reconciliation") or {} for d in docs]
    ok = all(r.get("available") and r.get("within_bound") for r in recs)
    return {
        "nranks": nranks,
        "steps": steps,
        "calls_per_step": calls,
        "payload_bytes_per_call": STEADY_NBYTES,
        "by_axis": merged["by_axis"],
        "link_classes": merged["link_classes"],
        "reconciliation": recs[0],
        "reconciliation_per_rank": recs,
        "reconciliation_ok": bool(ok),
    }


# ---------------------------------------------------------------------------
# the round
# ---------------------------------------------------------------------------


def run_round(nranks: int = 8, mesh_spec: str = DEFAULT_MESH,
              sizes: Tuple[int, ...] = DEFAULT_SIZES,
              steps: int = DEFAULT_STEPS,
              timeout: float = 600.0) -> Dict[str, Any]:
    """The full comms round the MULTICHIP recorder embeds: sweep +
    clean skew + injected-straggler skew + steady attribution, with the
    two gated headline metrics hoisted."""
    probe_ranks = min(4, nranks)
    sweep = run_sweep(mesh_spec, sizes, timeout=timeout)
    skew_clean = run_skew(nranks=probe_ranks, probes=4, timeout=timeout)
    # the localization proof: rank 1 arrives 150ms late, the floor is
    # dropped below the injection so the episode machinery must fire
    skew_injected = run_skew(
        nranks=probe_ranks, probes=3, delay_rank=1, delay_ms=150.0,
        floor_ms=30.0, episode_probes=2, timeout=timeout)
    steady = run_steady(nranks=probe_ranks, steps=steps,
                        timeout=timeout)

    # per-class table over BOTH feeds: the sweep's compiled-mesh rows
    # (ici) and the steady leg's eager cross-process rows (dcn)
    link_classes = dict(steady.get("link_classes") or {})
    link_classes.update(sweep.get("link_classes") or {})

    ar_rows = [r for r in sweep.get("bandwidth", [])
               if r["kind"] == "all_reduce"
               and r.get("bus_bytes_per_sec", 0) > 0]
    allreduce_bus_bw = (round(statistics.median(
        [r["bus_bytes_per_sec"] for r in ar_rows]), 3)
        if ar_rows else None)

    doc: Dict[str, Any] = {
        "schema": SCHEMA,
        "platform": sweep.get("platform"),
        "link_class_semantics": {
            "ici": "in-process compiled mesh (forced-host devices)",
            "dcn": "cross-process eager KV exchange (the slow-link "
                   "proxy this harness has)",
        },
        "sweep": sweep,
        "skew": skew_clean,
        "straggler_injection": skew_injected,
        "steady": steady,
        "link_classes": link_classes,
        # the gated headlines
        "allreduce_bus_bw": allreduce_bus_bw,
        "collective_skew_p99": skew_clean.get("skew_p99_s"),
        "straggler_localized": skew_injected.get("localized"),
        "reconciliation_ok": steady.get("reconciliation_ok"),
        "reconciliation": steady.get("reconciliation"),
    }
    return doc


# ---------------------------------------------------------------------------
# CI smoke (--self-test)
# ---------------------------------------------------------------------------


def self_test(verbose: bool = True) -> Dict[str, Any]:
    """2-rank / 2-device smoke of every leg with machine-checked
    verdicts: every sweep kind lands a row with the right normalization
    factor, the injected straggler is NAMED with an episode, and the
    steady reconciliation is available and within bound."""
    doc = run_round(nranks=2, mesh_spec="dp=2",
                    sizes=(1 << 16,), steps=3, timeout=300.0)

    sweep = doc["sweep"]
    assert not sweep["errors"], sweep["errors"]
    rows = {(r["kind"], r["axis"]): r for r in sweep["bandwidth"]}
    from paddle_tpu import commswatch

    for kind in SWEEP_KINDS:
        row = rows[(kind, "dp")]
        want = commswatch.bus_bandwidth_factor(kind, 2)
        assert abs(row["bus_factor"] - want) < 1e-9, (kind, row)
        assert row["bus_bytes_per_sec"] > 0, (kind, row)
        assert "busBW" in row["normalization"], row
    assert doc["allreduce_bus_bw"] and doc["allreduce_bus_bw"] > 0, doc

    assert doc["collective_skew_p99"] is not None, doc["skew"]
    inj = doc["straggler_injection"]
    assert inj["localized"], inj
    assert inj["skew"]["suspect_rank"] == 1, inj
    assert inj["episodes"] >= 1, inj

    steady = doc["steady"]
    assert steady["reconciliation_ok"], steady["reconciliation_per_rank"]
    rec = steady["reconciliation"]
    assert rec["available"] and rec["within_bound"], rec
    assert "dcn" in steady["link_classes"], steady["link_classes"]
    assert "ici" in doc["link_classes"], doc["link_classes"]

    if verbose:
        print(json.dumps({k: doc[k] for k in (
            "allreduce_bus_bw", "collective_skew_p99",
            "straggler_localized", "reconciliation_ok",
            "link_classes")}, indent=1))
        print("comms_bench self-test OK")
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--worker", choices=("sweep", "skew", "steady"),
                    help="internal: run one leg (supervisor-spawned)")
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--nranks", type=int, default=8)
    ap.add_argument("--mesh", default=DEFAULT_MESH)
    ap.add_argument("--sizes",
                    default=",".join(str(s) for s in DEFAULT_SIZES))
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--probes", type=int, default=4)
    ap.add_argument("--delay-rank", type=int, default=-1)
    ap.add_argument("--delay-ms", type=float, default=0.0)
    ap.add_argument("--steps", type=int, default=DEFAULT_STEPS)
    ap.add_argument("--calls", type=int, default=DEFAULT_CALLS)
    ap.add_argument("--timeout", type=float, default=600.0)
    ap.add_argument("--out", help="write the round JSON here")
    ap.add_argument("--self-test", action="store_true",
                    help="2-rank smoke of every leg")
    args = ap.parse_args(argv)

    sizes = tuple(int(s) for s in args.sizes.split(",") if s)
    if args.worker == "sweep":
        sweep_worker_main(args.mesh, sizes, args.iters)
        return 0
    if args.worker == "skew":
        skew_worker_main(args.rank, args.nranks, args.probes,
                         args.delay_rank, args.delay_ms)
        return 0
    if args.worker == "steady":
        steady_worker_main(args.rank, args.nranks, args.steps,
                           args.calls)
        return 0
    if args.self_test:
        self_test()
        return 0
    doc = run_round(nranks=args.nranks, mesh_spec=args.mesh,
                    sizes=sizes, steps=args.steps, timeout=args.timeout)
    rendered = json.dumps(doc, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(rendered + "\n")
    print(rendered)
    return 0


if __name__ == "__main__":
    sys.exit(main())
