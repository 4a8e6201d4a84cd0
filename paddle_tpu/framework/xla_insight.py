"""Compiler-side observability: XLA cost/memory accounting + artifact capture.

PR 1 (metrics) and PR 2 (tracing) made the host side of the runtime
observable; this module opens the third black box — the compiler. The
executor lowers a whole ProgramDesc block into ONE jit-compiled XLA
callable, so the natural unit of compiler accounting is the compiled
cache entry. On every cache miss the executor routes compilation through
:func:`capture`, which uses the jax AOT stages API
(``jit_fn.trace -> .lower -> .compile``) so the *same single XLA
compile* that produces the executable also yields:

- the jaxpr text (what the lowering rules traced),
- the post-optimization HLO text (what XLA actually fused and scheduled),
- ``cost_analysis()`` FLOPs / bytes-accessed per execution,
- ``memory_analysis()`` argument / output / temp byte sizes, summed into
  a peak-HBM estimate.

The derived numbers are exported through the PR 1 metrics registry
(``program_flops`` / ``program_peak_bytes`` / ``program_bytes_accessed``
gauges, labeled by a short hash of the executor cache key) and — when
``PADDLE_TPU_XLA_DUMP_DIR`` is set — dumped per program as
``program.<hash>.{jaxpr,hlo,cost.json}`` for ``tools/xla_report.py`` to
render (per-program cost table, top-k fused computations, achieved-FLOPs
utilization against a bench JSON).

Env knobs (declared in paddle_tpu/flags.py):
  PADDLE_TPU_XLA_INSIGHT=0    disable capture (plain jit dispatch)
  PADDLE_TPU_XLA_DUMP_DIR=d   dump per-program artifacts into d

MLPerf-scale TPU practice treats achieved-FLOPs utilization and
per-program memory as first-class signals; this is the layer that makes
a cached paddle-tpu program answer for both.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import threading
import time
from dataclasses import asdict, dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax.monitoring as _jax_monitoring

from .. import flags as _flags
from .. import monitor as _monitor
from .. import profiler as _profiler
from . import shard_insight as _shard

__all__ = [
    "ProgramInsight", "enabled", "dump_dir", "key_hash", "capture",
    "aot_call", "memory_analysis_bytes", "dump_artifacts",
    "load_dump_dir", "recent", "clear_recent", "program_footprint",
    "value_bytes", "new_footprint_row", "footprint_report",
    "failure_counts", "build_log", "program_of", "COST_SCHEMA",
    "FOOTPRINT_SCHEMA",
]

COST_SCHEMA = "paddle_tpu.xla_cost/1"

# per-program compiler gauges, labeled by the cache-key hash: one series
# per compiled cache entry, so a snapshot names every resident program's
# cost next to the executor cache counters PR 1 added
_M_FLOPS = _monitor.gauge(
    "program_flops",
    "XLA cost-analysis FLOPs for one execution of a compiled program",
    labelnames=("program",))
_M_PEAK = _monitor.gauge(
    "program_peak_bytes",
    "XLA memory-analysis peak device bytes (arguments + outputs + temps) "
    "of a compiled program", labelnames=("program",))
_M_BYTES = _monitor.gauge(
    "program_bytes_accessed",
    "XLA cost-analysis bytes accessed for one execution of a compiled "
    "program", labelnames=("program",))
_M_CAPTURE = _monitor.counter(
    "xla_insight_captures_total",
    "compile-time insight captures by outcome", labelnames=("result",))
_M_AOT_FALLBACK = _monitor.counter(
    "xla_insight_aot_fallback_total",
    "AOT executables abandoned for plain jit after a call-time "
    "signature mismatch (each one is a second compile of that program)")

_M_BUILD_S = _monitor.counter(
    "program_build_seconds_total",
    "seconds this process spent building jitted programs, by stage: trace "
    "(Python -> jaxpr), lower (jaxpr -> StableHLO, Mosaic kernels "
    "included), compile (XLA, or the load from the persistent cache). "
    "Every jit of the process, named or not; time nested inside another "
    "build is counted once, where it was spent", labelnames=("stage",))
_M_BUILD_N = _monitor.counter(
    "program_build_total",
    "build stages this process went through (see "
    "program_build_seconds_total)", labelnames=("stage",))

_log = logging.getLogger(__name__)


def enabled() -> bool:
    return bool(_flags.env_flag("PADDLE_TPU_XLA_INSIGHT"))


def dump_dir() -> Optional[str]:
    return _flags.env_flag("PADDLE_TPU_XLA_DUMP_DIR") or None


def key_hash(key: Any) -> str:
    """Short content hash — the label that ties a metric series, a dump
    artifact, and a cache entry to one program. Callers must feed it
    process-stable material (op-type sequence, feed spec, fetch names —
    NOT id()s), so the same program hashes the same across runs and a
    reused dump dir overwrites rather than accumulates."""
    return hashlib.sha1(repr(key).encode()).hexdigest()[:12]


@dataclass
class ProgramInsight:
    """Everything the compiler disclosed about one cache entry."""

    key_hash: str
    label: str = ""
    program: str = ""  # the module's name in a device trace: jit_<fn>
    fetch_names: Tuple[str, ...] = ()
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    argument_bytes: Optional[int] = None
    output_bytes: Optional[int] = None
    temp_bytes: Optional[int] = None
    alias_bytes: Optional[int] = None
    generated_code_bytes: Optional[int] = None
    peak_bytes: Optional[int] = None
    donated_peak_bytes: Optional[int] = None
    n_jaxpr_eqns: Optional[int] = None
    time_unix: float = 0.0
    cost_raw: Dict[str, float] = field(default_factory=dict)
    artifacts: Dict[str, str] = field(default_factory=dict)  # kind -> path
    # comms-plane summary parsed from the post-optimization HLO
    # (shard_insight.comms_summary): collective counts/bytes per kind
    collectives: Optional[dict] = None
    # the build/* spans' seconds ({"trace", "lower", "compile", "analyze"})
    # and what the persistent cache did for the compile: hit | miss | off
    build_s: Dict[str, float] = field(default_factory=dict)
    cache: str = ""

    def to_dict(self) -> dict:
        d = asdict(self)
        d["schema"] = COST_SCHEMA
        d["fetch_names"] = list(self.fetch_names)
        return d


_RECENT: List[ProgramInsight] = []
_RECENT_MAX = 128
_RECENT_LOCK = threading.Lock()


def recent() -> List[ProgramInsight]:
    """Insights captured by this process, oldest first (bounded ring)."""
    with _RECENT_LOCK:
        return list(_RECENT)


def clear_recent() -> None:
    with _RECENT_LOCK:
        del _RECENT[:]


# ---------------------------------------------------------------------------
# build log (every jit of the process, through jax.monitoring)
# ---------------------------------------------------------------------------

BUILD_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_BUILD_LOG_MAX = 2048
_SMALL_TRACE_S = 1e-3
_MERGE_BELOW_S = 0.05
_OPEN_MAX = 1 << 16
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
# per stage, the two counters' children: one dict lookup an event
_M_BUILD = {st: (_M_BUILD_S.labels(stage=st), _M_BUILD_N.labels(stage=st))
            for st in BUILD_STAGES.values()}


def program_of(fun_name: str) -> str:
    """A build record's name without its jit wrapper: ``train_step`` for
    ``train_step``, ``jit(train_step)`` and ``jit_train_step``."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name[4:] if fun_name.startswith("jit_") else fun_name


class _BuildLog:
    """What JAX reports of every jit's three stages, as it reports it.

    JAX calls the listeners on the thread that builds, when a stage ENDS,
    with the stage's duration and a name: the trace event carries the
    Python function's (``train_step``), the other two the module's
    (``jit(train_step)``; ``jit_train_step`` in a device trace), so a
    record also has ``program``, the name without its jit wrapper, to
    join them by. A record is ``{fun_name, program, stage, t_end,
    seconds, self_s, count, thread}`` (and ``cache`` on a compile):
    ``t_end`` on ``time.perf_counter``'s clock, ``self_s`` the seconds
    less the records of the same thread that lie inside this one (a
    jitted layer traced inside its program, a constant compiled while a
    program is traced), so ``self_s`` summed over any records is wall
    time counted once.

    JAX reports a trace for every ``jnp`` function a program's trace
    calls and for every op whose shapes the program builder infers
    (``registry``'s ``eval_shape``): 2,500 for a two-layer GPT. So a
    trace under a millisecond loses its name (``<small>``), and a record
    under ``_MERGE_BELOW_S`` is added to the last one of its thread,
    stage and name if that ended less than a second before (``count`` > 1,
    ``t_end`` the newest). Nothing here runs unless something is built.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._tls = threading.local()
        self.records: List[dict] = []
        stages = tuple(BUILD_STAGES.values())
        self.totals = {st: {"seconds": 0.0, "count": 0} for st in stages}
        self.dropped = {st: {"seconds": 0.0, "count": 0} for st in stages}
        self.cache = {"requests": 0, "hits": 0, "misses": 0,
                      "retrieval_s": 0.0}

    def _self_seconds(self, t_end: float, seconds: float) -> float:
        """``seconds`` less what this thread built inside [t_end -
        seconds, t_end]. A record is claimed by the first one that closes
        over it, so a grandchild is taken off its parent only."""
        mine = self._tls.__dict__.setdefault("open", [])
        start = t_end - seconds
        inside = 0.0
        # 2e-5: JAX times a stage on time.time() and calls the listener a
        # few microseconds after its end, later for one record than for
        # the next (at worst a neighbour that short is taken for a child)
        while mine and mine[-1][0] >= start - 2e-5:
            inside += mine.pop()[1]
        mine.append((start, seconds))
        del mine[:-_OPEN_MAX]
        return max(0.0, seconds - inside)

    def on_duration(self, event: str, seconds: float, **kw) -> None:
        stage = BUILD_STAGES.get(event)
        if stage is None:
            if event == "/jax/compilation_cache/cache_retrieval_time_sec":
                with self._lock:
                    self.cache["retrieval_s"] += float(seconds)
            return
        t_end, seconds = time.perf_counter(), float(seconds)
        self_s = self._self_seconds(t_end, seconds)
        seconds_total, stages_total = _M_BUILD[stage]
        seconds_total.inc(self_s)
        stages_total.inc()
        name = str(kw.get("fun_name", ""))
        if stage == "trace" and seconds < _SMALL_TRACE_S:
            name = "<small>"
        cache = None
        if stage == "compile":
            cache = getattr(self._tls, "pending", None) or "off"
            self._tls.pending = None
            self._tls.compiled = cache
        merge = self._tls.__dict__.setdefault("merge", {})
        with self._lock:
            tot = self.totals[stage]
            tot["seconds"] += self_s
            tot["count"] += 1
            last = merge.get((stage, name))
            if (last is not None and seconds < _MERGE_BELOW_S
                    and t_end - last["t_end"] < 1.0
                    and last.get("cache") == cache):
                last["t_end"] = t_end
                last["seconds"] += seconds
                last["self_s"] += self_s
                last["count"] += 1
                return
            rec = {"fun_name": name, "program": program_of(name),
                   "stage": stage, "t_end": t_end, "seconds": seconds,
                   "self_s": self_s, "count": 1,
                   "thread": threading.get_ident()}
            if cache is not None:
                rec["cache"] = cache
            if seconds < _MERGE_BELOW_S:
                if len(merge) >= 256:
                    merge.clear()
                merge[(stage, name)] = rec
            self.records.append(rec)
            for old in self.records[:-_BUILD_LOG_MAX]:
                gone = self.dropped[old["stage"]]
                gone["seconds"] += old["self_s"]
                gone["count"] += old["count"]
            del self.records[:-_BUILD_LOG_MAX]

    def on_event(self, event: str, **_kw) -> None:
        what = _CACHE_EVENTS.get(event)
        if what is None:
            return
        with self._lock:
            self.cache[what] += 1
        # a request that uses the cache is a miss until the hit is seen
        # (JAX reports a miss only where it then WRITES the entry)
        if what == "hits":
            self._tls.pending = "hit"
        elif what == "requests":
            self._tls.pending = "miss"

    def take_compiled(self) -> Optional[str]:
        """The ``cache`` (hit | miss | off) of the newest compile this
        thread made since the last call, None where it made none."""
        out = getattr(self._tls, "compiled", None)
        self._tls.compiled = None
        return out

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "records": [dict(r) for r in self.records],
                "dropped": {st: dict(t) for st, t in self.dropped.items()},
                "totals": {st: dict(t) for st, t in self.totals.items()},
                "cache": dict(self.cache),
            }


_BUILD_LOG = _BuildLog()
_jax_monitoring.register_event_duration_secs_listener(_BUILD_LOG.on_duration)
_jax_monitoring.register_event_listener(_BUILD_LOG.on_event)


def build_log() -> dict:
    """Every jit this process built, named by the program or not:
    ``records`` (the newest 2,048, oldest first; what fell off the end
    is summed by stage in ``dropped``), per-stage ``totals`` of ``self_s``
    and of stages gone through, which never drop (also
    ``program_build_seconds_total{stage}`` and
    ``program_build_total{stage}`` on ``/metrics``), and the persistent
    cache's ``requests``, ``hits``, ``misses`` and ``retrieval_s``. The
    same ``program`` with two ``compile`` records was compiled twice."""
    return _BUILD_LOG.snapshot()


# ---------------------------------------------------------------------------
# capture (the executor cache-miss hook)
# ---------------------------------------------------------------------------


def capture(jit_fn, example_args: Sequence[Any], *, key_hash: str,
            label: str = "", fetch_names: Sequence[str] = (),
            dump_to: Optional[str] = None):
    """AOT-compile ``jit_fn`` at ``example_args`` and mine the stages.

    Returns ``(insight, executable)``. ``executable`` is the XLA-compiled
    callable for exactly these avals — the caller installs it (via
    :func:`aot_call`) as the cache entry's function, so the capture costs
    no second XLA compile. On any failure returns ``(None, None)`` and
    the caller keeps plain jit dispatch, which compiles the program a
    second time (and raises there if the failure was the program's own);
    the failure is logged and counted (:func:`failure_counts`) so a
    measured run can assert it never happened.
    """
    if not enabled() or not hasattr(jit_fn, "trace"):
        return None, None
    # one span a stage, named as the module is in a device trace; their
    # seconds are the insight's build_s
    who = {"program": "jit_" + getattr(jit_fn, "__name__", "program"),
           "key": key_hash}
    try:
        with _profiler.span("build/trace", cat="build", **who) as sp_trace:
            traced = jit_fn.trace(*example_args)
            jaxpr = traced.jaxpr
        with _profiler.span("build/lower", cat="build", **who) as sp_lower:
            lowered = traced.lower()
        with _profiler.span("build/compile", cat="build", **who) as sp_comp:
            _BUILD_LOG.take_compiled()
            executable = lowered.compile()
            cache = _BUILD_LOG.take_compiled() or "off"
            sp_comp.set(cache=cache)
    except Exception as e:
        _M_CAPTURE.labels(result="error").inc()
        _log.warning("xla_insight capture of %s (%s) failed, the caller "
                     "recompiles through plain jit: %s: %s",
                     label or "program", key_hash, type(e).__name__, e)
        return None, None

    insight = ProgramInsight(
        key_hash=key_hash, label=label, program=who["program"],
        fetch_names=tuple(fetch_names), time_unix=time.time(), cache=cache,
        build_s={"trace": sp_trace.seconds, "lower": sp_lower.seconds,
                 "compile": sp_comp.seconds})
    with _profiler.span("build/analyze", cat="build", **who) as sp_analyze:
        _analyze(insight, jaxpr, lowered, executable, dump_to)
    insight.build_s["analyze"] = sp_analyze.seconds
    _M_CAPTURE.labels(result="ok").inc()
    with _RECENT_LOCK:
        _RECENT.append(insight)
        del _RECENT[:-_RECENT_MAX]
    return insight, executable


def _analyze(insight: ProgramInsight, jaxpr, lowered, executable,
             dump_to: Optional[str]) -> None:
    """What `capture` does beside jit's own path once the executable
    exists: cost and memory analysis into the insight and the gauges, the
    HLO text where someone reads it, the comms plan, the dump."""
    started = time.perf_counter()
    key_hash = insight.key_hash
    try:
        insight.n_jaxpr_eqns = len(jaxpr.jaxpr.eqns)
    except Exception:
        pass

    cost: Any = None
    try:
        cost = executable.cost_analysis()
    except Exception:
        pass
    if isinstance(cost, dict):
        insight.cost_raw = {
            str(k): float(v) for k, v in cost.items()
            if isinstance(v, (int, float))
        }
        insight.flops = insight.cost_raw.get("flops")
        insight.bytes_accessed = insight.cost_raw.get("bytes accessed")

    mem = memory_analysis_bytes(executable)
    if mem:
        for name in ("argument_bytes", "output_bytes", "temp_bytes",
                     "alias_bytes", "generated_code_bytes", "peak_bytes",
                     "donated_peak_bytes"):
            if mem.get(name) is not None:
                setattr(insight, name, mem[name])

    if insight.flops is not None:
        _M_FLOPS.labels(program=key_hash).set(insight.flops)
    if insight.bytes_accessed is not None:
        _M_BYTES.labels(program=key_hash).set(insight.bytes_accessed)
    if insight.peak_bytes is not None:
        _M_PEAK.labels(program=key_hash).set(insight.peak_bytes)
    _monitor.flight_record("compile", f"program.{key_hash}",
                           flops=insight.flops,
                           peak_bytes=insight.peak_bytes)

    # the HLO text is rendered when there is a consumer: a dump dir, or
    # the comms-plane extractor (shard_insight) mining it for collective
    # instructions — and the extractor only has something to find when
    # more than one device exists (a single-device program cannot emit
    # cross-device collectives); pretty-printing a full train step's HLO
    # is pure overhead on the compile path otherwise
    out_dir = dump_to or dump_dir()
    hlo_text = None
    if out_dir or (_shard.enabled() and _device_count() > 1):
        try:
            hlo_text = executable.as_text()  # post-optimization HLO
        except Exception:
            try:
                hlo_text = lowered.as_text()  # pre-optimization StableHLO
            except Exception:
                pass
    if hlo_text is not None:
        # comms plan: every collective GSPMD/XLA emitted, as counts and
        # predicted payload bytes per kind (the predicted side of
        # shard_insight.reconcile); rides the cost.json dump below
        insight.collectives = _shard.attach(insight, hlo_text)
    if out_dir:
        # the dump's cost.json holds the analysis up to its own writing
        insight.build_s["analyze"] = time.perf_counter() - started
        try:
            dump_artifacts(insight, out_dir, jaxpr_text=str(jaxpr),
                           hlo_text=hlo_text)
        except OSError:
            pass


def _device_count() -> int:
    import jax

    return jax.device_count()


def memory_analysis_bytes(executable) -> Dict[str, Optional[int]]:
    """Normalized ``memory_analysis()`` byte sizes of an AOT executable:
    {argument_bytes, output_bytes, temp_bytes, alias_bytes,
    generated_code_bytes, peak_bytes}. THE one place the PJRT attribute
    names and the peak convention live — donation aliases outputs onto
    arguments, so args+outs+temps is the upper bound of what the program
    holds live at once. Empty dict when the backend has no analysis."""
    try:
        mem = executable.memory_analysis()
    except Exception:
        mem = None
    if mem is None:
        return {}
    out: Dict[str, Optional[int]] = {}
    for attr, name in (
        ("argument_size_in_bytes", "argument_bytes"),
        ("output_size_in_bytes", "output_bytes"),
        ("temp_size_in_bytes", "temp_bytes"),
        ("alias_size_in_bytes", "alias_bytes"),
        ("generated_code_size_in_bytes", "generated_code_bytes"),
    ):
        try:
            out[name] = int(getattr(mem, attr))
        except (AttributeError, TypeError, ValueError):
            out[name] = None
    out["peak_bytes"] = sum(
        v for v in (out.get("argument_bytes"), out.get("output_bytes"),
                    out.get("temp_bytes")) if v is not None) or None
    # the donation-adjusted peak: aliased bytes are outputs written in
    # place over donated arguments — counting them on both sides (as the
    # conservative peak_bytes sum does) overstates what the program
    # holds live by exactly the donated state. This is the number the
    # planner's memory_fit reasons with and the donation tests assert
    # shrinks when params are donated and returned in place.
    if out["peak_bytes"] and out.get("alias_bytes"):
        out["donated_peak_bytes"] = max(
            0, out["peak_bytes"] - out["alias_bytes"])
    else:
        out["donated_peak_bytes"] = out["peak_bytes"]
    return out


def aot_call(executable, fallback):
    """Wrap an AOT executable with a permanent fallback to plain jit.

    Signature-mismatch errors (an aval the cache key failed to pin) are
    raised by the executable BEFORE execution, so no donated buffer has
    been consumed when the fallback takes over. The fallback compiles
    the program again, so it is logged and counted
    (:func:`failure_counts`).
    """
    use_aot = [True]

    def call(*args):
        if use_aot[0]:
            try:
                return executable(*args)
            except (TypeError, ValueError) as e:
                use_aot[0] = False
                _M_AOT_FALLBACK.inc()
                _log.warning("AOT executable rejected its arguments, "
                             "recompiling through plain jit: %s: %s",
                             type(e).__name__, e)
        return fallback(*args)

    return call


def failure_counts() -> Dict[str, int]:
    """How often this process lost an AOT executable: failed captures
    and call-time fallbacks. Both mean a program compiled twice, so
    chip_smoke.py and benchmarks assert both are zero. Counted through
    the metrics registry (zero forever under PADDLE_TPU_METRICS=0)."""
    return {
        "capture_errors": int(_M_CAPTURE.labels(result="error").value),
        "aot_fallbacks": int(_M_AOT_FALLBACK.value),
    }


# ---------------------------------------------------------------------------
# artifact dump / load (the xla_report.py contract)
# ---------------------------------------------------------------------------


def dump_artifacts(insight: ProgramInsight, out_dir: str,
                   jaxpr_text: Optional[str] = None,
                   hlo_text: Optional[str] = None) -> Dict[str, str]:
    """Write ``program.<hash>.{jaxpr,hlo,cost.json}`` into ``out_dir``.
    The cost.json is written LAST so a reader that sees it can rely on
    the sibling text artifacts being complete."""
    os.makedirs(out_dir, exist_ok=True)
    base = os.path.join(out_dir, f"program.{insight.key_hash}")
    if jaxpr_text:
        with open(base + ".jaxpr", "w") as f:
            f.write(jaxpr_text)
        insight.artifacts["jaxpr"] = base + ".jaxpr"
    if hlo_text:
        with open(base + ".hlo", "w") as f:
            f.write(hlo_text)
        insight.artifacts["hlo"] = base + ".hlo"
    with open(base + ".cost.json", "w") as f:
        json.dump(insight.to_dict(), f, indent=1)
    insight.artifacts["cost"] = base + ".cost.json"
    return dict(insight.artifacts)


def load_dump_dir(dump_dir: str) -> Dict[str, dict]:
    """``PADDLE_TPU_XLA_DUMP_DIR`` -> {key_hash: cost record}. Records
    are the ``ProgramInsight.to_dict()`` JSONs; sibling .hlo/.jaxpr paths
    are filled into ``artifacts`` when present on disk."""
    import glob

    out: Dict[str, dict] = {}
    for path in sorted(glob.glob(os.path.join(dump_dir,
                                              "program.*.cost.json"))):
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        h = rec.get("key_hash") or os.path.basename(path).split(".")[1]
        base = path[: -len(".cost.json")]
        arts = dict(rec.get("artifacts") or {})
        for kind, suffix in (("jaxpr", ".jaxpr"), ("hlo", ".hlo")):
            if os.path.exists(base + suffix):
                arts[kind] = base + suffix
        rec["artifacts"] = arts
        out[h] = rec
    return out


# ---------------------------------------------------------------------------
# model footprint (static-graph side; hapi Model.footprint mirrors this)
# ---------------------------------------------------------------------------


FOOTPRINT_SCHEMA = "paddle_tpu.footprint/1"


def value_bytes(value: Any) -> int:
    """Device bytes of one array-like (params, accumulators)."""
    try:
        return int(np.dtype(value.dtype).itemsize) * int(np.prod(value.shape))
    except (TypeError, ValueError):
        return 0


def new_footprint_row() -> dict:
    return {
        "param_bytes": 0, "opt_state_bytes": 0, "other_bytes": 0,
        "n_params": 0, "n_elements": 0,
    }


def footprint_report(layers: Dict[str, dict], total_param_bytes: int,
                     total_opt_state_bytes: int,
                     total_other_bytes: int = 0) -> dict:
    """Assemble the shared footprint result and publish the totals to the
    stat gauges (the run-report hook). Both producers — the static
    :func:`program_footprint` and the dygraph ``Model.footprint`` — build
    their rows with :func:`new_footprint_row` and finish here, so the
    schema and the gauges cannot drift between them."""
    out = {
        "schema": FOOTPRINT_SCHEMA,
        "total_param_bytes": total_param_bytes,
        "total_opt_state_bytes": total_opt_state_bytes,
        "total_other_bytes": total_other_bytes,
        "total_bytes": (total_param_bytes + total_opt_state_bytes
                        + total_other_bytes),
        "layers": dict(sorted(layers.items())),
    }
    _monitor.stat_set("model_param_bytes", total_param_bytes)
    _monitor.stat_set("model_opt_state_bytes", total_opt_state_bytes)
    return out


def program_footprint(program, scope, depth: int = 1) -> dict:
    """Byte accounting of a program's scope-resident state, aggregated by
    layer prefix (the segment of the variable name before the first '.',
    e.g. ``fc_0`` owns ``fc_0.w_0`` and its ``fc_0.w_0_moment_0``
    optimizer accumulators). Parameters are told apart from optimizer
    state via ``program.all_parameters()``; everything else persistable
    lands in ``other_bytes``. Totals ride into the run report through the
    legacy stat gauges (``model_param_bytes`` / ``model_opt_state_bytes``)."""
    param_names = {p.name for p in program.all_parameters()}
    layers: Dict[str, dict] = {}

    def row(name: str) -> dict:
        prefix = ".".join(name.split(".")[:depth]) or name
        return layers.setdefault(prefix, new_footprint_row())

    def is_accumulator(name: str) -> bool:
        # accumulators are named <param.name>_<acc>[_N]: test the prefix
        # at each '_' boundary against the param-name set instead of
        # scanning every param name per var (O(underscores) set lookups,
        # not O(params) startswith calls)
        i = name.find("_")
        while i != -1:
            if name[:i] in param_names:
                return True
            i = name.find("_", i + 1)
        return False

    total_p = total_o = total_x = 0
    for var in program.global_block().vars.values():
        if not getattr(var, "persistable", False):
            continue
        value = scope.get(var.name) if scope.has(var.name) else None
        if value is None:
            continue
        b = value_bytes(value)
        r = row(var.name)
        if var.name in param_names:
            r["param_bytes"] += b
            r["n_params"] += 1
            r["n_elements"] += int(np.prod(value.shape))
            total_p += b
        elif is_accumulator(var.name):
            r["opt_state_bytes"] += b
            total_o += b
        else:
            r["other_bytes"] += b
            total_x += b
    return footprint_report(layers, total_p, total_o, total_x)
