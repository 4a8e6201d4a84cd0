"""Runtime flag registry — the FLAGS_* config tier + PADDLE_TPU_* env vars.

Counterpart of /root/reference/paddle/fluid/platform/flags.cc:33-521
(DEFINE_* global flags read by the runtime) and the Python surface
`paddle.set_flags` / `paddle.get_flags` (framework.py). Flags initialize
from the environment (FLAGS_name=value, same convention the reference's
gflags env bridge uses) and can be flipped at runtime; consumers read at
compile/run time, so flipping a flag takes effect on the next executor
compile or run.

A second registry covers the framework's PADDLE_TPU_* observability env
vars (metrics, tracing, watchdog, compiler insight, numerics sentinel).
They used to be ~10 scattered ``os.environ.get`` calls with the default
and the documentation drifting independently; every one is now declared
here once (name, typed default, help) and consumed through
:func:`env_flag`. README's env-var table is generated from
:func:`render_env_table` and checked in CI via :func:`check_env_docs`.
Unlike FLAGS_*, env flags are read live from ``os.environ`` — tests
flip them with monkeypatch.setenv and the next compile/run sees the new
value.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Iterable, Union

_DEFS: Dict[str, dict] = {}
_VALUES: Dict[str, Any] = {}


def _coerce(value, proto):
    if isinstance(proto, bool):
        if isinstance(value, str):
            return value.lower() in ("1", "true", "yes", "on")
        return bool(value)
    if isinstance(proto, int) and not isinstance(proto, bool):
        return int(value)
    if isinstance(proto, float):
        return float(value)
    return str(value)


def define_flag(name: str, default: Any, help_str: str = "") -> None:
    """Register a flag (reference DEFINE_bool/int32/... in flags.cc)."""
    if not name.startswith("FLAGS_"):
        name = "FLAGS_" + name
    _DEFS[name] = {"default": default, "help": help_str}
    env = os.environ.get(name)
    _VALUES[name] = _coerce(env, default) if env is not None else default


def get_flags(flags: Union[str, Iterable[str]]):
    """paddle.get_flags: str -> value; list -> {name: value}."""
    if isinstance(flags, str):
        name = flags if flags.startswith("FLAGS_") else "FLAGS_" + flags
        if name not in _DEFS:
            raise KeyError(f"unknown flag {name!r}")
        return _VALUES[name]
    return {f: get_flags(f) for f in flags}


def set_flags(flags: Dict[str, Any]) -> None:
    """paddle.set_flags({name: value})."""
    for name, value in flags.items():
        if not name.startswith("FLAGS_"):
            name = "FLAGS_" + name
        if name not in _DEFS:
            raise KeyError(f"unknown flag {name!r}")
        _VALUES[name] = _coerce(value, _DEFS[name]["default"])


def all_flags() -> Dict[str, Any]:
    return dict(_VALUES)


# ---------------------------------------------------------------------------
# PADDLE_TPU_* observability env-var registry
# ---------------------------------------------------------------------------

_ENV_DEFS: Dict[str, dict] = {}


def define_env_flag(name: str, default: Any, help_str: str = "") -> None:
    """Declare a PADDLE_TPU_* env var (typed default + one-line help)."""
    _ENV_DEFS[name] = {"default": default, "help": help_str}


def _coerce_env(name: str, raw: str, proto: Any) -> Any:
    if isinstance(proto, bool):
        # the historical monitor.py convention: set-but-disabling values
        # are "0/false/off/no"; anything else set counts as enabled
        return raw.strip().lower() not in ("0", "false", "off", "no", "")
    # malformed numerics must fail LOUDLY: silently falling back to the
    # default would e.g. leave the watchdog the operator armed with
    # PADDLE_TPU_WATCHDOG_SECS=120s switched off
    if isinstance(proto, int) and not isinstance(proto, bool):
        try:
            return int(raw)
        except ValueError as e:
            raise ValueError(
                f"{name}={raw!r} is not a valid integer") from e
    if isinstance(proto, float):
        try:
            return float(raw)
        except ValueError as e:
            raise ValueError(
                f"{name}={raw!r} is not a valid number") from e
    return raw


def env_flag(name: str) -> Any:
    """Current value of a declared env var: live os.environ read, coerced
    to the declared default's type; the default when unset."""
    if name not in _ENV_DEFS:
        raise KeyError(f"undeclared env flag {name!r}")
    raw = os.environ.get(name)
    if raw is None:
        return _ENV_DEFS[name]["default"]
    return _coerce_env(name, raw, _ENV_DEFS[name]["default"])


def env_flag_defs() -> Dict[str, dict]:
    """{name: {default, help, value}} for every declared env var."""
    return {
        name: {**dict(d), "value": env_flag(name)}
        for name, d in sorted(_ENV_DEFS.items())
    }


def render_env_table() -> str:
    """The README observability env-var table, generated (markdown)."""
    lines = [
        "| variable | default | effect |",
        "| --- | --- | --- |",
    ]
    for name, d in sorted(_ENV_DEFS.items()):
        default = d["default"]
        if isinstance(default, bool):
            shown = "1" if default else "0"
        elif default == "":
            shown = "unset"
        else:
            shown = str(default)
        lines.append(f"| `{name}` | `{shown}` | {d['help']} |")
    return "\n".join(lines)


def check_env_docs(text: str) -> list:
    """Names of declared env vars a document fails to mention (CI asserts
    this is empty for README.md). Whole-name match: a mention of
    PADDLE_TPU_TRACE_DIR must not satisfy the check for PADDLE_TPU_TRACE."""
    import re as _re

    return [
        name for name in sorted(_ENV_DEFS)
        if not _re.search(_re.escape(name) + r"(?![A-Za-z0-9_])", text)
    ]


# -- the observability env-var set ------------------------------------------
define_env_flag(
    "PADDLE_TPU_METRICS", True,
    "typed metrics registry on/off; 0 reduces every inc/observe to one "
    "bool check")
define_env_flag(
    "PADDLE_TPU_METRICS_PATH", "",
    "bench.py writes the JSON metrics snapshot to this file")
define_env_flag(
    "PADDLE_TPU_OP_CALLSTACK", True,
    "record the Python build-site callstack on every Operator (op "
    "provenance on errors); 0 skips the capture")
define_env_flag(
    "PADDLE_TPU_TRACE", False,
    "enable host-span tracing at import (executor, fit loop, DataLoader, "
    "collectives, PS RPC)")
define_env_flag(
    "PADDLE_TPU_TRACE_DIR", "",
    "flush each rank's trace to <dir>/trace.rank<k>.json at exit and "
    "enable the flight recorder")
define_env_flag(
    "PADDLE_TPU_TRACE_SAMPLE", 0.0,
    "always-on tracing that records ~every 1/rate-th step (0 < rate <= 1)")
define_env_flag(
    "PADDLE_TPU_TRACE_MAX_EVENTS", 1000000,
    "host-span ring capacity; beyond it the oldest spans drop")
define_env_flag(
    "PADDLE_TPU_WATCHDOG_SECS", 0.0,
    "start the hang watchdog: no step progress for N seconds triggers a "
    "flight-recorder dump")
define_env_flag(
    "PADDLE_TPU_FLIGHT_CAPACITY", 512,
    "flight-recorder ring size (recent span/progress events kept for "
    "hang dumps)")
define_env_flag(
    "PADDLE_TPU_XLA_INSIGHT", True,
    "capture per-compiled-program XLA cost/memory analysis and export "
    "program_flops / program_peak_bytes metrics; 0 restores plain jit "
    "dispatch")
define_env_flag(
    "PADDLE_TPU_XLA_DUMP_DIR", "",
    "dump per-program compile artifacts (program.<hash>.{jaxpr,hlo,"
    "cost.json}) into this directory for tools/xla_report.py")
define_env_flag(
    "PADDLE_TPU_STATUS_PORT", 0,
    "serve /status, /metrics and /healthz on this HTTP port (stdlib "
    "server, one per rank; launch.py assigns base-port+rank); 0 disables")
define_env_flag(
    "PADDLE_TPU_STATUS_HOST", "127.0.0.1",
    "interface the status server binds; loopback by default (the "
    "endpoints are unauthenticated) — set 0.0.0.0 to let external "
    "scrapers reach /metrics")
define_env_flag(
    "PADDLE_TPU_GOODPUT_DIR", "",
    "persist the per-rank goodput ledger journal "
    "(goodput.rank<k>.json, atomic writes) into this directory; a "
    "restarted rank resumes its cumulative totals from it")
define_env_flag(
    "PADDLE_TPU_GOODPUT_FLUSH_STEPS", 50,
    "flush the goodput journal every N closed steps (plus once at exit)")
define_env_flag(
    "PADDLE_TPU_MEMWATCH", True,
    "live device-memory accounting (hbm_* gauges, per-step watermarks, "
    "leak detector, OOM post-mortem enrichment); 0 disables sampling")
define_env_flag(
    "PADDLE_TPU_MEMWATCH_DIR", "",
    "persist the per-rank memory ledger journal (memwatch.rank<k>.json, "
    "atomic writes) into this directory; a restarted rank resumes its "
    "lifetime peak from it")
define_env_flag(
    "PADDLE_TPU_MEMWATCH_LEAK_STEPS", 30,
    "steady-state leak detector: this many consecutive closed steps of "
    "monotonic bytes_in_use growth raise a leak-suspect event")
define_env_flag(
    "PADDLE_TPU_MEMWATCH_LEAK_MIN_MB", 8.0,
    "minimum total growth (MB) across the leak window before a "
    "leak-suspect event fires (filters allocator jitter)")
define_env_flag(
    "PADDLE_TPU_DYNAMICS", True,
    "training-dynamics telemetry (per-step loss/grad-norm series, "
    "anomaly detectors, fused grad reductions in the fit loop); 0 "
    "disables recording")
define_env_flag(
    "PADDLE_TPU_DYNAMICS_DIR", "",
    "persist the per-rank training-dynamics journal "
    "(dynamics.rank<k>.jsonl: header line + one JSON line per closed "
    "step, atomic writes) into this directory; a restarted rank resumes "
    "its trajectory from it")
define_env_flag(
    "PADDLE_TPU_DYNAMICS_FLUSH_STEPS", 50,
    "flush the dynamics journal every N closed steps (plus once at exit)")
define_env_flag(
    "PADDLE_TPU_DYNAMICS_SAMPLE", 25,
    "record the per-layer-prefix grad/weight/update norm breakdown "
    "every N fit steps (one fused jitted reduction per sample); 0 "
    "disables the breakdown")
define_env_flag(
    "PADDLE_TPU_DYNAMICS_SPIKE_Z", 6.0,
    "loss-spike detector: a step whose loss sits more than this many "
    "EMA standard deviations above the loss EMA starts a loss_spike "
    "episode")
define_env_flag(
    "PADDLE_TPU_DYNAMICS_DIVERGE_STEPS", 25,
    "sustained-divergence detector: the loss EMA staying >1% above its "
    "best value for this many consecutive steps starts a divergence "
    "episode")
define_env_flag(
    "PADDLE_TPU_DYNAMICS_PLATEAU_STEPS", 200,
    "plateau detector: this many consecutive steps without a loss-EMA "
    "improvement starts a plateau episode (informational)")
define_env_flag(
    "PADDLE_TPU_COMMSWATCH", True,
    "interconnect observability ledger (per-(kind, axis, size-bucket) "
    "measured bus bandwidth, per-axis collective-wall attribution, "
    "barrier-skew straggler probes, link-class term table); 0 disables "
    "recording")
define_env_flag(
    "PADDLE_TPU_COMMSWATCH_DIR", "",
    "persist the per-rank interconnect ledger journal "
    "(commswatch.rank<k>.json, atomic writes) into this directory; a "
    "restarted rank resumes its step/episode base from it")
define_env_flag(
    "PADDLE_TPU_COMMSWATCH_PROBE_EVERY", 0,
    "barrier-skew straggler probe cadence: every N closed training "
    "steps each rank stamps its arrival on the shared unix clock and "
    "the last arrival is named the suspect; 0 (default) disables the "
    "sampled probe (comms_bench runs a dedicated probe leg regardless)")
define_env_flag(
    "PADDLE_TPU_COMMSWATCH_SKEW_FLOOR_MS", 50.0,
    "straggler-episode skew floor in ms: probes whose max-min rank "
    "arrival skew stays below this never open an episode")
define_env_flag(
    "PADDLE_TPU_COMMSWATCH_SKEW_PROBES", 3,
    "consecutive probes above the skew floor before a straggler "
    "episode is flagged (flight-recorded once per run of bad probes; "
    "any healthy probe re-arms)")
define_env_flag(
    "PADDLE_TPU_COMMSWATCH_BOUND", 4.0,
    "predicted-vs-measured reconciliation bound factor: predicted "
    "collective bytes over measured link-class bus bandwidth must "
    "agree with the measured collective wall per step within this "
    "factor in either direction")
define_env_flag(
    "PADDLE_TPU_DP_BUCKET_MB", 25.0,
    "data-parallel gradient-sync bucket size in MB: grads coalesce into "
    "fixed-size fp32 buckets (reverse build order) and each bucket ships "
    "as ONE all-reduce; 0 restores the per-parameter collective loop")
define_env_flag(
    "PADDLE_TPU_DP_OVERLAP", True,
    "dispatch each gradient bucket on the comms thread as soon as its "
    "last grad is produced, overlapping the collective with the "
    "remaining backward; 0 defers every bucket to the sync point")
define_env_flag(
    "PADDLE_TPU_DP_QUANTIZE", "",
    "gradient all-reduce payload encoding: 'int8' = blockwise int8 with "
    "per-block fp32 scales and an error-feedback residual (wire bytes "
    "cut ~4x, residuals persist with optimizer state); unset = exact "
    "fp32 sum")
define_env_flag(
    "PADDLE_TPU_DP_QUANT_BLOCK", 256,
    "block size of the quantized all-reduce: one fp32 scale is shipped "
    "per this many int8 gradient elements")
define_env_flag(
    "PADDLE_TPU_SHARD_INSIGHT", True,
    "parse every captured program's post-optimization HLO for collective "
    "instructions (comms-plane summary: counts/bytes per kind, "
    "program_collective_bytes gauges, cost.json 'collectives' section); "
    "0 skips the extraction")
define_env_flag(
    "PADDLE_TPU_SHARD_INSIGHT_BOUND", 2.0,
    "predicted-vs-measured collective byte reconciliation bound: the HLO "
    "or bucket-layout prediction and the measured collective byte "
    "counters must agree within this factor in either direction")
define_env_flag(
    "PADDLE_TPU_SHARD_VERIFY", False,
    "verify intended-vs-actual parameter shardings at executor compile "
    "time for mesh programs carrying sharding rules "
    "(sharding_mismatch_total counter + flight-recorder event on drift)")
define_env_flag(
    "PADDLE_TPU_SHARDING_RECIPE", "",
    "default GSPMD sharding recipe for fleet.distributed_optimizer when "
    "strategy.sharding_recipe is unset: 'dp', 'fsdp', 'tp' or a hybrid "
    "preset (parallel/recipes.py) pjit-lowers the whole training step "
    "over one named-axis mesh; unset keeps the explicit-collectives "
    "path")
define_env_flag(
    "PADDLE_TPU_TOPOLOGY_TIMEOUT", 15.0,
    "seconds the described-TPU-topology probe subprocess may take before "
    "tools/topo_plan.py falls back to a multi-device CPU mesh (the "
    "describe call hangs on hosts without a TPU runtime)")
define_env_flag(
    "PADDLE_TPU_PLAN_HEADROOM", 0.10,
    "memory-fit headroom fraction reserved off the stated HBM limit "
    "(allocator fragmentation, infeed buffers): a program inside the "
    "limit but eating the headroom verdicts 'tight', and the "
    "auto-planner rejects such candidates as oom")
define_env_flag(
    "PADDLE_TPU_PLAN_TOPK", 3,
    "auto-planner survivors: the top-K feasible layouts by predicted "
    "step time kept in the ranked plan report; mesh_bench --validate "
    "measures the pick plus these runners-up for planner_regret")
define_env_flag(
    "PADDLE_TPU_AUTO_PLAN", True,
    "run the auto-planner validation leg in the 8-way MULTICHIP round "
    "(tools/mesh_bench.py run_validation: plan, measure pick + "
    "runners-up, record the gated planner_regret); 0 skips the leg")
define_env_flag(
    "PADDLE_TPU_SERVE_RECIPE", "",
    "sharding recipe for the serving decode/prefill programs ('tp' or a "
    "hybrid from parallel/recipes.py): parameters and the KV pages "
    "shard off the SAME recipe table training uses — serving has no "
    "second sharding layer; unset = single-device programs")
define_env_flag(
    "PADDLE_TPU_SERVE_SLO_S", 30.0,
    "default per-request latency SLO in seconds: the admission queue "
    "orders by absolute deadline (arrival + SLO), and eviction under "
    "KV pressure victimizes the latest deadline first")
define_env_flag(
    "PADDLE_TPU_SERVE_DIR", "",
    "persist the per-rank serving ledger journal "
    "(serving.rank<k>.json, atomic writes) into this directory; a "
    "restarted replica resumes its cumulative SLO totals from it")
define_env_flag(
    "PADDLE_TPU_SERVE_FLUSH_TICKS", 50,
    "flush the serving journal every N closed engine ticks (plus once "
    "at exit)")
define_env_flag(
    "PADDLE_TPU_SERVE_SPAN_BOUND", 1.5,
    "request-span reconciliation bound: summed per-request decode span "
    "seconds and the engine's slot-seconds (decode bucket x batch "
    "occupancy) must agree within this factor in either direction")
define_env_flag(
    "PADDLE_TPU_SERVE_ROOFLINE_BOUND", 8.0,
    "decode roofline reconciliation bound: measured decode tokens/s "
    "must sit within this factor below the AOT cost-analysis roofline "
    "prediction (and no more than ~25% above it)")
define_env_flag(
    "PADDLE_TPU_CHAOS_SITES", "",
    "arm deterministic fault injection (paddle_tpu/chaos.py): "
    "comma-separated site@key=val:key=val entries over the named sites "
    "kill_rank / collective_delay / collective_abort / rpc_error / "
    "io_stall plus the serving sites replica_kill / decode_stall / "
    "admit_error (e.g. 'kill_rank@step=5:rank=1', "
    "'replica_kill@tick=60:rank=1'); unset = fully inert")
define_env_flag(
    "PADDLE_TPU_CHAOS_SEED", 0,
    "seed of the chaos injector's deterministic per-site decision "
    "stream: the same spec + seed reproduces the same faults at the "
    "same checks")
define_env_flag(
    "PADDLE_TPU_COLL_TIMEOUT_MS", 300000,
    "deadline (ms) each coordination-KV collective wait may block for "
    "one peer's payload before raising typed errors.Unavailable naming "
    "the missing rank and collective tag — a dead peer surfaces as a "
    "detectable failure, never a silent hang")
define_env_flag(
    "PADDLE_TPU_COLL_EPOCH", "",
    "collective-exchange epoch baked into every coordination-KV key: a "
    "restarted attempt with a new epoch can never pair against a dead "
    "attempt's stale payloads (launch.py exports the restart count; "
    "unset falls back to PADDLE_RESTART_COUNT)")
define_env_flag(
    "PADDLE_TPU_CKPT_DIR", "",
    "enable periodic atomic training checkpoints in the hapi fit loop: "
    "params + optimizer state (incl. __dp_comms__ error-feedback "
    "residuals) + step counter + data/RNG cursor persist to "
    "<dir>/trainckpt.rank<k>.step<N>.pdz and a respawned rank "
    "auto-resumes from the newest one")
define_env_flag(
    "PADDLE_TPU_CKPT_STEPS", 25,
    "training-checkpoint cadence: write one every N closed fit steps")
define_env_flag(
    "PADDLE_TPU_CKPT_KEEP", 2,
    "training-checkpoint retention window: newer writes sweep all but "
    "the latest N checkpoints of this rank")
define_env_flag(
    "PADDLE_TPU_SERVE_REAP_GRACE_S", 5.0,
    "serving-engine reaper: an in-flight request still holding its slot "
    "this many seconds past its absolute SLO deadline is failed and its "
    "slot + KV blocks reclaimed (serve_reaped_total); 0 disables")
define_env_flag(
    "PADDLE_TPU_SERVE_SHED", True,
    "admission-time load shedding: a request whose SLO deadline is "
    "already unmeetable at the current queue depth is rejected with "
    "typed errors.Unavailable (serve_shed_total) instead of occupying "
    "a slot it cannot use; 0 admits everything")
define_env_flag(
    "PADDLE_TPU_SERVE_RETRIES", 2,
    "serving router (serving/router.py): re-dispatch a failed request "
    "up to this many times on another replica, with exponential backoff "
    "+ deterministic jitter between attempts; every attempt carries the "
    "same request_id (idempotent re-dispatch, bit-identical greedy "
    "tokens); 0 fails on the first error")
define_env_flag(
    "PADDLE_TPU_SERVE_BACKOFF_MS", 50.0,
    "base of the router's retry backoff: re-dispatch k waits "
    "base*2^k ms (capped at 2000ms), jittered into [1/2, 1) of the raw "
    "delay by a per-(request_id, attempt) hash")
define_env_flag(
    "PADDLE_TPU_SERVE_HEDGE_MS", 0.0,
    "deadline-aware hedging: a dispatch still outstanding after this "
    "many ms whose SLO is at risk (remaining budget below the router's "
    "latency EMA) is duplicated onto a second replica — first success "
    "wins, both results are bit-match audited; 0 disables hedging")
define_env_flag(
    "PADDLE_TPU_SERVE_DRAIN_S", 10.0,
    "connection-draining budget: Router.drain_replica stops routing to "
    "a replica, asks its engine to finish all admitted work "
    "(new submissions rejected with typed Unavailable) and waits up to "
    "this many seconds for it to report drained")
define_env_flag(
    "PADDLE_TPU_SERVE_PARAMS", "",
    "warm-restart parameter source for serving replicas: an .npz of "
    "named GPT parameters (models/gpt.py naming) every replica loads at "
    "boot — identical params across replicas is what makes router "
    "re-dispatch bit-identical, and reloading beats re-initializing on "
    "respawn; unset = seeded random init")
define_env_flag(
    "PADDLE_TPU_SERVE_TRACE", True,
    "cross-process request tracing on the serving plane: the router "
    "opens a root span per dispatch, pre-mints one span id per attempt "
    "and ships trace_id:span_id as __trace__ on every /generate POST "
    "and LocalReplica call; replicas parent their request-lifecycle "
    "spans under the inbound context (one connected flow per request "
    "in timeline.py --serve). Only active while profiler tracing is on "
    "(PADDLE_TPU_TRACE); 0 strips the propagation")
define_env_flag(
    "PADDLE_TPU_SERVE_ATTR_BOUND", 0.05,
    "per-request latency-attribution residual bound: "
    "|sum(buckets) - e2e| / e2e at the median must stay below this for "
    "the attribution reconciliation verdict to read within_bound "
    "(serving ledger + SERVE_r*.json attribution_residual)")
define_env_flag(
    "PADDLE_TPU_SERVE_TELEMETRY_HORIZONS", "1,10,60",
    "traffic-telemetry EMA horizons in seconds (comma-separated): the "
    "router tracks request-rate EMAs at each horizon per traffic class "
    "— the arrival-rate forecast inputs the serving planner reads")
define_env_flag(
    "PADDLE_TPU_SERVE_TELEMETRY_SERIES", 512,
    "max retained samples in the router's queue-depth / in-flight "
    "time series (ring buffer; oldest samples drop first)")
define_env_flag(
    "PADDLE_TPU_SERVE_SLO_CLASSES",
    "interactive:slo=2,weight=3,hedge=1;batch:slo=30,weight=1,hedge=0",
    "multi-tenant SLO classes for the serving plane "
    "(serving/capacity.py): 'name:slo=<s>,weight=<w>,hedge=<0|1>' "
    "entries joined by ';' — slo is the class's default dispatch "
    "deadline and the attainment target the autoscale round grades, "
    "weight its admission share under the router's cap, hedge whether "
    "its SLO-at-risk requests may duplicate onto a second replica")
define_env_flag(
    "PADDLE_TPU_SERVE_AUTOSCALE", False,
    "traffic-aware autoscale in the serving supervisor (launch "
    "serve_bench --autoscale unconditionally runs it): each interval "
    "the capacity planner re-forecasts per-class demand from the "
    "router's telemetry and moves one replica toward the cheapest "
    "configuration predicted to meet every SLO class; 0 keeps the "
    "replica set as launched")
define_env_flag(
    "PADDLE_TPU_SERVE_AUTOSCALE_INTERVAL_S", 2.0,
    "seconds between autoscaler ticks (forecast -> decide -> at most "
    "one scale action)")
define_env_flag(
    "PADDLE_TPU_SERVE_AUTOSCALE_COOLDOWN_S", 3.0,
    "minimum seconds between consecutive scale ACTIONS (plan changes "
    "still journal during cooldown): long enough for a warm-booted "
    "replica's capacity to show up in the measured rates before the "
    "next decision, so the loop cannot flap")
define_env_flag(
    "PADDLE_TPU_SERVE_AUTOSCALE_MAX_REPLICAS", 4,
    "autoscaler replica ceiling — the warm-restart spawn path is "
    "bounded by this even when the planner's pick asks for more "
    "(the device budget is the other bound)")
define_env_flag(
    "PADDLE_TPU_SERVE_AUTOSCALE_HEADROOM", 0.15,
    "capacity headroom the serving planner reserves: a configuration "
    "is feasible only when the CV-widened demand fits inside "
    "(1 - headroom) of its calibrated tokens/s — the burst absorber "
    "between forecast and reality")
define_env_flag(
    "PADDLE_TPU_SERVE_AUTOSCALE_CV_WIDEN", 1.0,
    "demand-forecast burst widening: the planning upper bound is the "
    "blended rate EMA times (1 + cv_widen * interarrival_cv), so a "
    "bursty class (CV >> 1) plans more slack than a metronome one; "
    "0 plans the mean rate")
define_env_flag(
    "PADDLE_TPU_SERVE_ADMIT_CAP", 0,
    "router-wide weighted-admission cap: once total in-flight "
    "dispatches reach this, each SLO class keeps admitting only inside "
    "its weight-proportional share (typed Unavailable bounce beyond "
    "it) so one tenant's burst cannot starve another's p99; 0 disables")
define_env_flag(
    "PADDLE_TPU_ASYNC_LOSS", True,
    "pipelined fit-loop loss readback: the per-step host float() of the "
    "loss is deferred one step so the next step's dispatch overlaps the "
    "device finishing the current one (detectors and step logs run one "
    "step behind; the epoch tail is flushed exactly); 0 restores the "
    "blocking per-step readback")
define_env_flag(
    "PADDLE_TPU_MEMWATCH_SAMPLE_RUNS", 10,
    "executor HBM sampling cadence: query allocator stats every N "
    "steady-state Executor.run calls (compiles and explicitly-fed "
    "samples are always recorded); 1 restores the per-run query, whose "
    "host cost lands in the goodput host_other bucket")
define_env_flag(
    "PADDLE_TPU_CHECK_NUMERICS", False,
    "numerics sentinel: probe every float op output inside the compiled "
    "block and raise a typed InvalidArgument naming the first op that "
    "produced nan/inf (op provenance attached); also arms loss/grad "
    "health checks in the hapi fit loop")


# -- core flag set (the subset of flags.cc the TPU runtime honors) ----------
define_flag(
    "FLAGS_check_nan_inf", False,
    "executor debug mode: after every op, assert all float outputs are "
    "finite and report the first offending op (reference operator.cc:1056)",
)
define_flag(
    "FLAGS_benchmark", False,
    "print per-run wall times from the executor",
)
define_flag(
    "FLAGS_paddle_num_threads", 1,
    "accepted for parity; XLA manages its own thread pools",
)
define_flag(
    "FLAGS_use_pinned_memory", True,
    "accepted for parity; host staging is managed by jax.device_put",
)
define_flag(
    "FLAGS_init_allocated_mem", False,
    "accepted for parity; XLA buffers are always defined-initialized",
)
