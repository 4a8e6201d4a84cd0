"""What every runner shares: the run's context, benchmark-side spans, the
count of compilations, the profiler slice and percentiles."""
from __future__ import annotations

import contextlib
import glob
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg: str) -> None:
    import sys

    print(f"[bench +{time.perf_counter() - T0:7.2f}s] {msg}", file=sys.stderr, flush=True)


T0 = time.perf_counter()  # reset by run.py at its first line


@dataclass
class Ctx:
    """One run of one cell: what the runner is given and what it leaves
    for the per-layer readers."""
    cell: Dict[str, Any]          # name, config (dict), traffic (dict), chips
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    devices: list                 # the jax devices this cell may use
    peaks: Optional[dict]         # None in a rehearsal
    t0: float                     # process start on perf_counter's clock
    results: Dict[str, Any] = field(default_factory=dict)   # end-to-end values and facts
    counters: Dict[str, float] = field(default_factory=dict)  # deltas over the window
    spans: Dict[str, List[float]] = field(default_factory=dict)  # name -> seconds
    norm_trace: Optional[dict] = None   # trace_reduce's normalised slice
    trace_facts: Dict[str, Any] = field(default_factory=dict)
    _lock: threading.Lock = field(default_factory=threading.Lock)

    @contextlib.contextmanager
    def span(self, name: str):
        """A benchmark-side span: seconds on the host clock, and, while
        the profiler runs, a TraceAnnotation on the profiler's clock."""
        import jax

        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench/{name}"):
            yield
        dt = time.perf_counter() - t
        with self._lock:
            self.spans.setdefault(name, []).append(dt)


class CompileCounter:
    """Counts XLA backend compilations and persistent-cache hits through
    jax.monitoring: the benchmark's own check that nothing compiles
    inside the measured window, whatever the program's counters say."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0

        def on_duration(event, _secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def percentile(values, q: float) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), q))


def device_facts(devices, program_temp_bytes: int = 0) -> dict:
    """The device as JAX reports it and the peak bytes on the fullest chip
    (None where the backend reports no allocator stats: CPU rehearsals).

    The TPU allocator's ``peak_bytes_in_use`` counts buffers (weights,
    state, feeds, outputs) and NOT the scratch a running program holds
    (measured in PR 23: 1.93 GB reported while the GPT-2 small step's own
    memory analysis has 7.3 GB of temporaries). The peak is therefore the
    allocator's peak plus the temporaries of the largest program the cell
    ran, from the compiler's memory analysis of that executable."""
    peak = None
    for d in devices:
        stats = d.memory_stats() or {}
        p = stats.get("peak_bytes_in_use")
        if p is not None:
            peak = max(peak or 0, int(p))
    if peak is not None:
        peak += int(program_temp_bytes)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class TraceSlice:
    """jax.profiler around a short slice; the .xplane.pb lands under the
    checkout's git-ignored ``.bench_trace/`` and is normalised at once."""

    def __init__(self, ctx: Ctx):
        self.ctx = ctx
        self.dir = os.path.join(ROOT, ".bench_trace", ctx.cell["name"])
        self.t_start = self.t_stop = None

    def start(self) -> None:
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        self.t_start = time.perf_counter()
        jax.profiler.start_trace(self.dir)

    def stop(self) -> None:
        import jax

        from . import trace_reduce

        jax.profiler.stop_trace()
        self.t_stop = time.perf_counter()
        paths = glob.glob(os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not paths:
            log("trace: the profiler wrote no .xplane.pb")
            return
        t = time.perf_counter()
        self.ctx.norm_trace = trace_reduce.from_xplane(paths[0])
        self.ctx.trace_facts = {
            "host_slice_s": self.t_stop - self.t_start,
            "xplane_bytes": os.path.getsize(paths[0]),
            "reduce_s": time.perf_counter() - t,
            "device_planes": sorted(self.ctx.norm_trace["devices"]),
            "device_events": sum(len(v) for v in self.ctx.norm_trace["devices"].values()),
            "host_events": len(self.ctx.norm_trace["host"]),
        }
        log(f"trace: {self.ctx.trace_facts}")
