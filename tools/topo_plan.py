"""Pod-scale plan report: will this model + mesh recipe fit, and what
will it cost — computed ahead of time, with no TPU attached.

Wraps paddle_tpu.framework.topology: a topology spec (``v4:2x2x1``,
``v5e:4x4``, ``cpu:8``) is described (or degraded to a multi-device CPU
mesh with an explicit reason, when this host cannot describe TPU
topologies), a ``data``/``fsdp``/``tp`` recipe is laid over the devices,
and the FULL GPT training step (forward + backward + Adam) is AOT
trace->lower->compiled against abstract sharded inputs — nothing is
materialized, so a dev box can plan a pod. The report carries:

- per-device cost (FLOPs, bytes accessed) and predicted peak HBM
  (donation-adjusted), with a fit verdict against the chip's stated
  HBM limit (``--hbm-gb`` overrides);
- the comms plan: every collective GSPMD emitted, bytes per kind,
  attributed to mesh axes via replica-group sizes;
- a roofline-style step-time estimate (compute vs HBM vs ICI) naming
  what bounds the step.

Usage:
  python tools/topo_plan.py --topology v5e:4x4 --recipe data=4,tp=4 \
      [--preset gpt2s] [--batch 32] [--seq 1024] [--hbm-gb 16] \
      [--num-slices 1] [--format text|json] [--out plan.json]
  python tools/topo_plan.py --topology cpu:8 --recipe data=2,fsdp=2,tp=2
  python tools/topo_plan.py --self-test     # tier-1: CPU-mesh plan smoke

When a CPU topology wants more devices than the process has, the tool
re-execs itself with ``--xla_force_host_platform_device_count`` set
(the same bootstrap the test suite and the multichip dry-run use).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import Any, Dict, List, Optional, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

PLAN_SCHEMA = "paddle_tpu.topo_plan/1"


def _presets() -> Dict[str, dict]:
    """Model presets come from THE planner table (paddle_tpu/planner.py
    MODEL_PRESETS) — topo_plan is the planner's single-candidate
    degenerate case and must not grow a second preset copy."""
    from paddle_tpu import planner

    return planner.MODEL_PRESETS


def parse_recipe(text: str) -> Dict[str, int]:
    """``data=2,fsdp=2,tp=2`` -> ordered {axis: size}. Delegates to THE
    shared layout-spec parser (parallel/recipes.parse_layout_spec) —
    this entry point additionally requires the explicit axis=size form
    (named presets take the other branch in main())."""
    from paddle_tpu.parallel.recipes import parse_layout_spec

    out = parse_layout_spec(text)
    if not isinstance(out, dict):
        raise ValueError(
            f"bad recipe entry {text!r} (want axis=size[,axis=size...])")
    return out


def build_plan(topology: str, recipe,
               preset: str = "tiny", batch: int = 8, seq: int = 128,
               hbm_gb: Optional[float] = None, num_slices: int = 1,
               probe_timeout: Optional[float] = None,
               cfg_overrides: Optional[dict] = None) -> Dict[str, Any]:
    """Assemble the single-candidate plan report (the CLI is a thin
    wrapper). This IS the auto-planner's scoring path run for one
    layout: paddle_tpu/planner.py owns the program build, the AOT
    compile/mine pipeline and the memory_fit/roofline/comms verdict
    math — tools/auto_plan.py runs the same :func:`planner.score_candidate`
    for every enumerated layout, so the two reports cannot drift."""
    from paddle_tpu import planner
    from paddle_tpu.framework import topology as topo

    res = planner.resolve_devices(topology, num_slices=num_slices,
                                  probe_timeout=probe_timeout)
    spec, devices = res["spec"], res["devices"]
    if devices is None:
        out = {"schema": PLAN_SCHEMA, "available": False,
               "topology": {**spec.to_dict(), "source": None},
               "skip_reason": res["skip_reason"]}
        if res["detail"]:
            out["detail"] = res["detail"]
        return out

    # the ONE shared recipe source (parallel/recipes.py): a named preset
    # resolves through the same table the runtime executor lays out, and
    # an explicit dict is normalized onto the same ResolvedRecipe — the
    # scoring below uses the resolved recipe's OWN rules/batch placement,
    # so a plan cannot drift from the runtime
    mesh = topo.build_mesh(devices, recipe)
    from paddle_tpu.parallel.recipes import ResolvedRecipe

    resolved = ResolvedRecipe(
        name=recipe if isinstance(recipe, str) else "custom",
        axes={str(a): int(n) for a, n in mesh.shape.items()})
    chip = dict(spec.chip_spec())
    if hbm_gb:
        chip["hbm_gb"] = float(hbm_gb)

    artifacts = planner.build_train_artifacts(preset, batch, seq,
                                              cfg_overrides)
    scored = planner.score_candidate(artifacts, resolved, devices, chip)

    hbm_limit = chip["hbm_gb"] * (1 << 30)
    fit = topo.memory_fit(scored["program"]["fit_bytes_per_device"],
                          hbm_limit, state_bytes=artifacts["state_bytes"])

    comms = dict(scored["comms"])
    report: Dict[str, Any] = {
        "schema": PLAN_SCHEMA,
        "available": True,
        "topology": {**spec.to_dict(), "source": res["source"],
                     "skip_reason": res["skip_reason"]},
        "recipe": resolved.to_dict(),
        "mesh_axes": scored["axes"],
        "model": {
            "preset": artifacts["preset"], "config": artifacts["cfg_kwargs"],
            "batch": batch, "seq": seq,
            "n_params": artifacts["n_params"],
            "state_bytes_total": artifacts["state_bytes"],
            "n_state_vars": artifacts["n_state_vars"],
        },
        "program": scored["program"],
        "comms": comms,
        "memory_fit": fit,
        "roofline": scored["roofline"],
        "verdict": fit["verdict"],
    }
    if scored.get("largest_param"):
        report["model"]["largest_param"] = scored["largest_param"]
    return report


def render_text(report: Dict[str, Any]) -> str:
    topo_d = report.get("topology", {})
    if not report.get("available"):
        return (f"topo_plan: UNAVAILABLE for {topo_d.get('raw')} — "
                f"{report.get('skip_reason')} {report.get('detail', '')}")
    lines = [
        f"== topo plan: {topo_d['raw']} ({topo_d['source']}"
        + (f", degraded: {topo_d['skip_reason']}" if topo_d.get("skip_reason")
           else "") + ") ==",
        f"mesh {report['mesh_axes']}  model {report['model']['preset']} "
        f"batch={report['model']['batch']} seq={report['model']['seq']} "
        f"params={report['model']['n_params']:,}",
    ]
    prog = report["program"]
    lines.append(
        f"per-device: flops={prog['flops_per_device'] or 0:.3g} "
        f"bytes={prog['bytes_accessed_per_device'] or 0:.3g} "
        f"peak={(prog['peak_bytes_per_device'] or 0) / 1e6:.1f}MB "
        f"(fit-adjusted {(prog['fit_bytes_per_device'] or 0) / 1e6:.1f}MB)")
    fit = report["memory_fit"]
    lines.append(
        f"memory fit: {fit['verdict'].upper()} — "
        f"{(fit.get('per_device_bytes') or 0) / 1e9:.3f}GB of "
        f"{fit['hbm_limit_bytes'] / 1e9:.1f}GB"
        + (f" ({fit['utilization'] * 100:.1f}%)"
           if fit.get("utilization") is not None else ""))
    comms = report["comms"]
    lines.append(f"comms plan: {comms['n_collectives']} collective(s), "
                 f"{comms['payload_bytes_total'] / 1e6:.3f}MB payload "
                 f"per step per device")
    for kind, row in comms["by_kind"].items():
        lines.append(f"  {kind:<20} x{row['count']:<4} "
                     f"{row['payload_bytes'] / 1e6:.3f}MB")
    for axis, row in comms["by_axis"].items():
        lines.append(f"  axis {axis:<15} x{row['count']:<4} "
                     f"{row['payload_bytes'] / 1e6:.3f}MB  {row['kinds']}")
    roof = report["roofline"]
    if roof["step_seconds_estimate"]:
        lines.append(
            f"roofline: step ~{roof['step_seconds_estimate'] * 1e3:.2f}ms "
            f"(compute {((roof['compute_seconds'] or 0)) * 1e3:.2f}ms, "
            f"memory {((roof['memory_seconds'] or 0)) * 1e3:.2f}ms, "
            f"collective {((roof['collective_seconds'] or 0)) * 1e3:.2f}ms)"
            f" — {roof['bound_by']}-bound")
    lines.append(f"verdict: {report['verdict'].upper()}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# CI smoke (--self-test)
# ---------------------------------------------------------------------------


def self_test(verbose: bool = True) -> Dict[str, Any]:
    """Tier-1 smoke. (1) A TPU topology describe is PROBED (subprocess,
    hard timeout): hosts with a TPU runtime go on to plan against the
    described devices; everywhere else the SKIP reason is asserted and
    printed — graceful degrade is part of the contract. (2) The full
    plan pipeline runs against a {data:2, fsdp:2, tp:2} CPU mesh: the
    report must carry real per-device cost, a non-empty comms plan with
    per-axis attribution, fit/oom verdicts that flip with the stated
    HBM limit, and a roofline estimate."""
    import jax

    from paddle_tpu.framework import topology as topo

    # -- TPU describe: probe, never hang ------------------------------
    from paddle_tpu import flags as _flags

    # v5e: one device per chip, so the described count equals the chip
    # count (a v4 describe yields two cores per chip on this libtpu)
    spec = topo.parse_topology("v5e:2x2")
    # the registry owns this knob (default + coercion); the self-test
    # only caps it so tier-1 never waits longer than the smoke budget
    ok, reason = topo.probe_tpu_topology(spec, timeout=min(
        12.0, float(_flags.env_flag("PADDLE_TPU_TOPOLOGY_TIMEOUT"))))
    if verbose:
        print(f"tpu topology describe: "
              f"{'OK' if ok else 'SKIP — ' + reason}")
    if ok:
        devices, source = topo.describe(spec)
        assert devices and len(devices) == spec.n_devices, (source, devices)

    # -- CPU-mesh plan (needs 8 devices; the CLI re-exec provides them
    # when the test runner's conftest has not already) -----------------
    n_cpu = len([d for d in jax.devices() if d.platform == "cpu"])
    assert n_cpu >= 8, (
        f"self-test needs 8 CPU devices, found {n_cpu} — run through the "
        f"CLI (it re-execs with --xla_force_host_platform_device_count)")
    report = build_plan("cpu:8", {"data": 2, "fsdp": 2, "tp": 2},
                        preset="tiny", batch=8, seq=32)
    assert report["available"], report
    assert report["schema"] == PLAN_SCHEMA
    prog = report["program"]
    assert prog["flops_per_device"] and prog["flops_per_device"] > 0, prog
    assert prog["peak_bytes_per_device"] and prog["fit_bytes_per_device"], (
        prog)
    comms = report["comms"]
    assert comms["n_collectives"] >= 1, (
        "a dp+fsdp+tp-sharded train step must emit collectives", comms)
    assert comms["payload_bytes_total"] > 0, comms
    assert comms["by_axis"], comms
    assert "all-reduce" in comms["by_kind"] or "reduce-scatter" in \
        comms["by_kind"], comms
    assert report["memory_fit"]["verdict"] in ("fit", "tight"), (
        report["memory_fit"])
    roof = report["roofline"]
    assert roof["step_seconds_estimate"] and roof["bound_by"], roof

    # the fit verdict must flip when the stated HBM cannot hold the
    # program (hbm_gb small enough that even the tiny model OOMs)
    tight = build_plan("cpu:8", {"data": 2, "fsdp": 2, "tp": 2},
                       preset="tiny", batch=8, seq=32, hbm_gb=1e-4)
    assert tight["memory_fit"]["verdict"] == "oom", tight["memory_fit"]

    # named presets come from the ONE shared recipe table: the plan's
    # mesh must equal what the runtime executor would lay out, and the
    # recipe's analytic comms plan must reconcile with the AOT HLO
    from paddle_tpu.parallel.recipes import resolve_recipe

    named = build_plan("cpu:8", "fsdp", preset="tiny", batch=8, seq=32)
    assert named["available"], named
    assert named["mesh_axes"] == resolve_recipe("fsdp", 8).axes, named
    assert named["recipe"]["name"] == "fsdp", named["recipe"]
    pr = named["comms"]["plan_reconciliation"]
    assert pr["ok"], pr
    assert named["comms"]["recipe_plan"]["payload_bytes_total"] > 0, named

    # a TPU plan on a host that cannot describe TPUs degrades to the
    # CPU mesh but keeps the reason in the report
    if not ok:
        degraded = build_plan("v4:2x2x1", {"data": 2, "tp": 2},
                              preset="tiny", batch=4, seq=32,
                              probe_timeout=3.0)
        assert degraded["available"], degraded
        assert degraded["topology"]["source"] == "cpu-fallback", degraded
        assert degraded["topology"]["skip_reason"], degraded

    if verbose:
        print(render_text(report))
        print("topo_plan self-test OK")
    return report


def _reexec_with_devices(n: int, argv: List[str]) -> int:
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    flags = " ".join(
        f for f in flags.split()
        if "xla_force_host_platform_device_count" not in f)
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["_TOPO_PLAN_REEXEC"] = "1"
    return subprocess.call(
        [sys.executable, os.path.abspath(__file__)] + argv, env=env)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--topology", default="cpu",
                    help="'v4:2x2x1', 'v5e:4x4', 'cpu:8', 'cpu' (all "
                    "local devices)")
    ap.add_argument("--num-slices", type=int, default=1,
                    help="multi-slice pods: slices of --topology shape")
    ap.add_argument("--recipe", default=None,
                    help="mesh recipe: a named preset from the shared "
                    "table ('dp', 'fsdp', 'tp', 'dp_fsdp', 'dp_tp', "
                    "'fsdp_tp', 'dp_fsdp_tp') or explicit "
                    "'data=4,fsdp=2,tp=2' (default: pure data parallel "
                    "over every device)")
    ap.add_argument("--preset", default="tiny", choices=sorted(_presets()),
                    help="model preset (config overridable below)")
    ap.add_argument("--batch", type=int, default=8,
                    help="GLOBAL batch size")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--n-layer", type=int, default=None)
    ap.add_argument("--d-model", type=int, default=None)
    ap.add_argument("--vocab", type=int, default=None)
    ap.add_argument("--hbm-gb", type=float, default=None,
                    help="per-device HBM limit the fit verdict is judged "
                    "against (default: the chip's table value)")
    ap.add_argument("--out", help="write the plan JSON here")
    ap.add_argument("--format", choices=("json", "text"), default="text")
    ap.add_argument("--self-test", action="store_true",
                    help="CI smoke: probe TPU describe, plan a CPU mesh")
    args = ap.parse_args(argv)

    # resolve the device count the run needs BEFORE jax initializes, so
    # a cpu:N topology bigger than this process can see re-execs itself
    # with the forced host device count (once)
    from paddle_tpu.framework import topology as topo

    want = 8 if args.self_test else None
    if want is None:
        try:
            spec = topo.parse_topology(args.topology,
                                       num_slices=args.num_slices)
            want = spec.n_devices or None
        except ValueError as e:
            print(f"topo_plan: {e}", file=sys.stderr)
            return 2
    if want and not os.environ.get("_TOPO_PLAN_REEXEC"):
        import jax

        if len(jax.devices()) < want and jax.devices()[0].platform == "cpu":
            return _reexec_with_devices(want, argv)

    if args.self_test:
        self_test()
        return 0

    overrides = {}
    if args.n_layer:
        overrides["n_layer"] = args.n_layer
    if args.d_model:
        overrides["d_model"] = args.d_model
    if args.vocab:
        overrides["vocab_size"] = args.vocab
    if args.recipe:
        # axis=size syntax -> explicit dict; otherwise a named preset
        # from the shared recipe table (dp / fsdp / tp / hybrids)
        recipe = (parse_recipe(args.recipe) if "=" in args.recipe
                  else args.recipe.strip().lower())
    else:
        import jax

        recipe = {"data": want or len(jax.devices())}
    try:
        report = build_plan(
            args.topology, recipe, preset=args.preset, batch=args.batch,
            seq=args.seq, hbm_gb=args.hbm_gb, num_slices=args.num_slices,
            cfg_overrides=overrides)
    except ValueError as e:
        print(f"topo_plan: {e}", file=sys.stderr)
        return 2
    rendered = (render_text(report) if args.format == "text"
                else json.dumps(report, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
            f.write("\n")
    print(rendered)
    return 0 if report.get("available") else 3


if __name__ == "__main__":
    sys.exit(main())
