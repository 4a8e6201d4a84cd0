"""Interconnect observability: measured bandwidth, stragglers, link classes.

goodput.py made *time* observable, memwatch.py *memory*, dynamics.py the
*training signal* — this layer does the same for the *interconnect*, the
axis the pod-scale ROADMAP items depend on. Until now the planner priced
collectives off an analytic plan plus one scalar correction; nothing
measured achieved bus bandwidth per mesh axis, localized which rank
arrives late to a collective, or separated fast-link from slow-link
terms. The design deliberately mirrors the goodput/memwatch/dynamics
ledger triplet:

- **measured bandwidth**: :func:`record_bandwidth` folds one timed
  collective into a per-(kind, axis, size-bucket) table with the
  standard bus-bandwidth normalization stated in every row
  (:func:`bus_bandwidth_factor` — the NCCL-tests convention: all-reduce
  busBW = algBW x 2(n-1)/n, all-gather/reduce-scatter x (n-1)/n).
  ``tools/comms_bench.py`` sweeps kinds x sizes x mesh axes through it;
  the eager cross-process path (``distributed/collective.py``) feeds it
  live from every ``_collective_window`` via :func:`record_collective`.
- **steady-state attribution**: :func:`configure_attribution` takes the
  recipe's ``predicted_collectives`` bytes pro-rated per mesh axis
  (``topology.axis_bytes_breakdown`` — see
  ``ResolvedRecipe.payload_by_axis``), and :func:`end_step` (riding
  ``goodput.end_step``, so every step driver participates for free)
  splits the step's measured ``collective`` goodput bucket across axes
  by byte share. :func:`reconcile` then checks the three-way contract —
  predicted bytes / measured bandwidth vs the measured collective wall —
  within an explicit bound factor.
- **straggler localization**: :func:`barrier_probe` gathers per-rank
  arrival timestamps on the shared unix-anchored clock (the same
  ``time.time()`` anchor the profiler spans and timeline tracks use),
  names the last-arriving rank as the suspect with the full arrival
  vector as evidence, and raises flight-recorder episodes in the
  memwatch-leak style (N consecutive probes above the skew floor flag
  ONCE; any healthy probe re-arms). :func:`maybe_probe` runs it at a
  sampled step cadence during training (``PADDLE_TPU_COMMSWATCH_PROBE_EVERY``).
- **link classes**: every bandwidth row carries a link class —
  ``ici`` (intra-host: the compiled in-process mesh path) or ``dcn``
  (cross-host proxy: the eager coordination-service path) — and
  :func:`link_class_table` reduces the table to the per-class measured
  term the planner's roofline consumes in place of the single flat
  ICI-bytes correction (``planner.calibrate`` /
  ``topology.roofline(payload_by_link_class=...)``).

Journal (paddle_tpu/journal.py has the contract):
``PADDLE_TPU_COMMSWATCH_DIR/commswatch.rank<k>.json``, cross-rank
:func:`merge_ledgers`.

Env knobs (declared in paddle_tpu/flags.py):
  PADDLE_TPU_COMMSWATCH                 ledger on/off (default on)
  PADDLE_TPU_COMMSWATCH_DIR             journal directory (persistence)
  PADDLE_TPU_COMMSWATCH_PROBE_EVERY     barrier-skew probe cadence in
                                        steps (0 = off)
  PADDLE_TPU_COMMSWATCH_SKEW_FLOOR_MS   skew episode floor (50ms)
  PADDLE_TPU_COMMSWATCH_SKEW_PROBES     consecutive probes above the
                                        floor before an episode (3)
  PADDLE_TPU_COMMSWATCH_BOUND           reconciliation bound factor (4)
"""
from __future__ import annotations

import collections
import math
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from . import flags as _flags
from . import journal as _journal
from . import monitor as _monitor

__all__ = [
    "CommsLedger", "enabled", "ledger", "reset",
    "bus_bandwidth_factor", "size_bucket", "LINK_CLASSES",
    "record_bandwidth", "record_collective",
    "configure_attribution", "end_step",
    "barrier_probe", "maybe_probe",
    "totals", "status", "summary", "link_class_table", "reconcile",
    "configure", "disable_persistence", "flush", "journal_path",
    "load_journal", "load_journals", "merge_ledgers",
    "render_summary", "SCHEMA",
]

SCHEMA = "paddle_tpu.commswatch/1"

# recent closed steps / probes kept for /status + the timeline tracks
_SERIES_CAP = 256
# skew samples kept for the p50/p99 summary (quantiles over the recent
# window, not the whole run — a straggler episode must move the tail)
_SKEW_CAP = 512

LINK_CLASSES = ("ici", "dcn")

_M_SKEW = _monitor.gauge(
    "collective_skew_seconds",
    "barrier-probe arrival skew (max - min rank arrival) at the last "
    "probe")
_M_STRAGGLER = _monitor.counter(
    "collective_straggler_episodes_total",
    "straggler episodes (N consecutive probes above the skew floor)")
_M_AXIS_BPS = _monitor.gauge(
    "collective_axis_bytes_per_sec",
    "attributed collective bytes/s per mesh axis at the last closed "
    "step (predicted bytes over the attributed share of the measured "
    "collective wall)", ("axis",))


def enabled() -> bool:
    return _monitor.enabled() and bool(
        _flags.env_flag("PADDLE_TPU_COMMSWATCH"))


def _skew_floor_s() -> float:
    return float(_flags.env_flag("PADDLE_TPU_COMMSWATCH_SKEW_FLOOR_MS")) / 1e3


def _skew_probes() -> int:
    return max(1, int(_flags.env_flag("PADDLE_TPU_COMMSWATCH_SKEW_PROBES")))


def _bound_factor() -> float:
    return max(1.0, float(_flags.env_flag("PADDLE_TPU_COMMSWATCH_BOUND")))


# ---------------------------------------------------------------------------
# the bus-bandwidth normalization (the NCCL-tests convention)
# ---------------------------------------------------------------------------

# busBW = algBW x factor(kind, n). The factor restates an algorithm's
# achieved rate as the per-link utilization a ring of n participants
# implies: an all-reduce moves 2(n-1)/n of the payload over every link
# (reduce-scatter + all-gather phases), a one-phase gather/scatter
# (n-1)/n, an all-to-all (n-1)/n (each rank keeps 1/n of its payload
# local), and point-to-point kinds (permute, broadcast over a tree,
# barrier) are reported unnormalized (factor 1).
_BUS_FACTORS = {
    "all_reduce": lambda n: 2.0 * (n - 1) / n,
    "all_gather": lambda n: (n - 1) / n,
    "reduce_scatter": lambda n: (n - 1) / n,
    "all_to_all": lambda n: (n - 1) / n,
}


def bus_bandwidth_factor(kind: str, group_size: int) -> float:
    """busBW/algBW for one collective kind over ``group_size``
    participants — 2(n-1)/n for all-reduce, (n-1)/n for
    all-gather/reduce-scatter/all-to-all, 1.0 for everything else
    (permute, broadcast, barrier, the eager API ops). ``group_size``
    <= 1 is factor 0 for the reduction kinds (no link ever carries a
    byte) and 1.0 otherwise."""
    n = max(1, int(group_size))
    fn = _BUS_FACTORS.get(str(kind))
    if fn is None:
        return 1.0
    return fn(n) if n > 1 else 0.0


def _normalization_note(kind: str, group_size: int) -> str:
    """The formula stated in every bandwidth record — the record must be
    self-describing (satellite: the math is tested directly)."""
    kind = str(kind)
    if kind == "all_reduce":
        return f"busBW = algBW * 2(n-1)/n, n={max(1, int(group_size))}"
    if kind in ("all_gather", "reduce_scatter", "all_to_all"):
        return f"busBW = algBW * (n-1)/n, n={max(1, int(group_size))}"
    return "busBW = algBW (unnormalized point-to-point kind)"


def size_bucket(nbytes: float) -> str:
    """Power-of-4 message-size bucket label (<=256B, <=1KiB, <=4KiB,
    ...): coarse enough that a sweep lands repeats in one row, fine
    enough that the latency-vs-bandwidth regimes stay separable."""
    n = max(1.0, float(nbytes))
    exp = max(4, math.ceil(math.log2(n) / 2.0) * 2)  # even powers of 2
    bound = 1 << exp
    for div, unit in ((1 << 30, "GiB"), (1 << 20, "MiB"), (1 << 10, "KiB")):
        if bound >= div:
            return f"<={bound // div}{unit}"
    return f"<={bound}B"


# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------


class CommsLedger:
    """Per-process interconnect ledger: the (kind, axis, size-bucket)
    bandwidth table, per-axis steady-state attribution, and the
    barrier-skew probe series with straggler-episode state. Thread-safe;
    ``base`` holds the journal a restarted rank resumed from."""

    def __init__(self):
        self._lock = threading.RLock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.steps = 0
            self.current_step: Optional[int] = None
            self.collective_seconds = 0.0
            # (kind, axis, bucket) -> bandwidth row; string-keyed so the
            # journal round-trips through JSON untouched
            self.bandwidth: Dict[str, dict] = {}
            # steady-state attribution: predicted bytes per step per axis
            self.attribution: Dict[str, float] = {}
            self.axis_link: Dict[str, str] = {}
            self.by_axis: Dict[str, dict] = {}
            # eager per-op feed (open step + lifetime)
            self.open_ops: Dict[str, dict] = {}
            self.op_totals: Dict[str, dict] = {}
            self.step_series: collections.deque = collections.deque(
                maxlen=_SERIES_CAP)
            # skew probe state
            self.probes = 0
            self.skew_series: collections.deque = collections.deque(
                maxlen=_SERIES_CAP)
            self.skew_values: collections.deque = collections.deque(
                maxlen=_SKEW_CAP)
            self.last_skew: Optional[dict] = None
            self.suspect_counts: Dict[str, int] = {}
            self.skew_run = 0
            self.skew_run_suspects: Dict[str, int] = {}
            self._skew_flagged = False
            self.straggler_episodes = 0
            self.base: Optional[dict] = None
            self.started_unix = time.time()

    # -- measured bandwidth --------------------------------------------
    def record_bandwidth(self, kind: str, axis: str, payload_bytes: float,
                         group_size: int, seconds: float, *,
                         link_class: str = "ici",
                         source: str = "bench") -> Optional[dict]:
        """Fold one timed collective into the bandwidth table. Returns
        the updated row (algBW = payload/seconds; busBW = algBW x the
        stated normalization factor)."""
        if seconds <= 0 or payload_bytes <= 0:
            return None
        factor = bus_bandwidth_factor(kind, group_size)
        alg = float(payload_bytes) / float(seconds)
        bus = alg * factor
        key = f"{kind}/{axis}/{size_bucket(payload_bytes)}"
        with self._lock:
            row = self.bandwidth.setdefault(key, {
                "kind": str(kind), "axis": str(axis),
                "size_bucket": size_bucket(payload_bytes),
                "link_class": str(link_class), "source": str(source),
                "group_size": int(group_size),
                "samples": 0, "payload_bytes": 0.0, "seconds": 0.0,
                "alg_bytes_per_sec": 0.0, "bus_bytes_per_sec": 0.0,
                "bus_bytes_per_sec_best": 0.0,
                "bus_factor": round(factor, 6),
                "normalization": _normalization_note(kind, group_size),
            })
            row["samples"] += 1
            row["payload_bytes"] += float(payload_bytes)
            row["seconds"] += float(seconds)
            row["alg_bytes_per_sec"] = round(alg, 3)
            row["bus_bytes_per_sec"] = round(bus, 3)
            row["bus_bytes_per_sec_best"] = round(
                max(row["bus_bytes_per_sec_best"], bus), 3)
            return row

    def record_collective(self, op: str, nbytes: Optional[float],
                          seconds: float, *, group_size: int = 1) -> None:
        """The eager-path feed (every ``_collective_window``): per-op
        wall + bytes of the OPEN step, summed into lifetime totals, and
        — when the call moved bytes across >1 process — a ``dcn``-class
        bandwidth row (the cross-host proxy term: eager collectives ride
        the coordination service between processes, the closest thing
        the harness has to a slow inter-host link)."""
        with self._lock:
            for table in (self.open_ops, self.op_totals):
                row = table.setdefault(str(op), {
                    "calls": 0, "payload_bytes": 0.0, "seconds": 0.0})
                row["calls"] += 1
                row["payload_bytes"] += float(nbytes or 0.0)
                row["seconds"] += float(seconds)
        if nbytes and group_size > 1:
            self.record_bandwidth(op, "process", nbytes, group_size,
                                  seconds, link_class="dcn",
                                  source="eager")

    # -- steady-state attribution --------------------------------------
    def configure_attribution(self, by_axis: Dict[str, Any],
                              link_classes: Optional[Dict[str, str]] = None
                              ) -> None:
        """Set the per-step predicted collective bytes per mesh axis
        (``topology.axis_bytes_breakdown`` rows or plain axis->bytes),
        the pro-rating weights :meth:`end_step` splits the measured
        collective wall with. ``link_classes`` maps each axis to
        ici/dcn for the reconciliation's bandwidth lookup (default:
        ``process`` is dcn, every mesh axis ici)."""
        flat: Dict[str, float] = {}
        for axis, v in (by_axis or {}).items():
            b = v.get("payload_bytes") if isinstance(v, dict) else v
            if b and float(b) > 0:
                flat[str(axis)] = float(b)
        with self._lock:
            self.attribution = flat
            self.axis_link = {
                str(a): str(c) for a, c in (link_classes or {}).items()}

    def _axis_class(self, axis: str) -> str:
        return self.axis_link.get(
            axis, "dcn" if axis == "process" else "ici")

    def end_step(self, collective_seconds: float,
                 step: Optional[int] = None) -> Optional[dict]:
        """Close the in-flight step: pro-rate the step's measured
        collective wall across the attributed axes by predicted-byte
        share (all of it to the ``process`` axis when only the eager
        feed saw traffic), fold into the per-axis lifetime table, and
        freeze the step record."""
        coll = max(0.0, float(collective_seconds or 0.0))
        with self._lock:
            open_ops = self.open_ops
            self.open_ops = {}
            if coll <= 0 and not open_ops:
                return None
            self.steps += 1
            self.current_step = (int(step) if step is not None
                                 else (self.current_step or 0) + 1)
            self.collective_seconds += coll
            weights = dict(self.attribution)
            if not weights:
                moved = sum(r["payload_bytes"] for r in open_ops.values())
                weights = {"process": moved or 1.0}
            total_w = sum(weights.values()) or 1.0
            by_axis_step: Dict[str, dict] = {}
            for axis, w in weights.items():
                share = coll * (w / total_w)
                life = self.by_axis.setdefault(axis, {
                    "seconds": 0.0, "payload_bytes": 0.0, "steps": 0,
                    "link_class": self._axis_class(axis)})
                life["seconds"] += share
                life["payload_bytes"] += (
                    w if self.attribution else
                    sum(r["payload_bytes"] for r in open_ops.values()))
                life["steps"] += 1
                bps = (w / share) if share > 0 else None
                by_axis_step[axis] = {
                    "seconds": round(share, 6),
                    "payload_bytes": round(w, 3),
                    "bytes_per_sec": round(bps, 3) if bps else None,
                    "link_class": life["link_class"],
                }
            closed = {
                "step": self.current_step,
                "t": time.time(),
                "collective_seconds": round(coll, 6),
                "by_axis": by_axis_step,
                "ops": {op: {k: round(v, 6) for k, v in r.items()}
                        for op, r in open_ops.items()},
            }
            self.step_series.append(closed)
            return closed

    # -- straggler probes ----------------------------------------------
    def record_skew(self, probe: Dict[str, Any],
                    floor_s: Optional[float] = None,
                    episode_probes: Optional[int] = None) -> Dict[str, Any]:
        """Fold one barrier-probe result into the skew series and
        advance the episode window (memwatch-leak semantics: N
        consecutive probes above the floor flag ONCE — counter +
        flight-record + one stderr warning naming the suspect; any
        healthy probe re-arms)."""
        floor = _skew_floor_s() if floor_s is None else float(floor_s)
        need = episode_probes or _skew_probes()
        skew = float(probe.get("skew_s") or 0.0)
        suspect = probe.get("suspect_rank")
        with self._lock:
            self.probes += 1
            self.last_skew = dict(probe)
            self.skew_series.append(dict(probe))
            self.skew_values.append(skew)
            if suspect is not None:
                key = str(suspect)
                self.suspect_counts[key] = (
                    self.suspect_counts.get(key, 0) + 1)
            episode = None
            if skew > floor:
                self.skew_run += 1
                if suspect is not None:
                    key = str(suspect)
                    self.skew_run_suspects[key] = (
                        self.skew_run_suspects.get(key, 0) + 1)
                if not self._skew_flagged and self.skew_run >= need:
                    self._skew_flagged = True
                    self.straggler_episodes += 1
                    named = max(self.skew_run_suspects,
                                key=self.skew_run_suspects.get,
                                default=None)
                    episode = {
                        "probes": self.skew_run,
                        "skew_s": round(skew, 6),
                        "floor_s": floor,
                        "suspect_rank": (int(named) if named is not None
                                         else None),
                        "evidence": probe.get("arrivals_rel"),
                    }
            else:
                self.skew_run = 0
                self.skew_run_suspects = {}
                self._skew_flagged = False
        out = dict(probe)
        out["episode"] = episode
        return out

    def _skew_summary(self) -> Dict[str, Any]:
        vals = sorted(self.skew_values)

        def q(p: float) -> Optional[float]:
            if not vals:
                return None
            i = min(len(vals) - 1, int(p * (len(vals) - 1) + 0.5))
            return round(vals[i], 6)

        named = max(self.suspect_counts, key=self.suspect_counts.get,
                    default=None)
        return {
            "probes": self.probes,
            "skew_last_s": (round(self.last_skew["skew_s"], 6)
                            if self.last_skew else None),
            "skew_p50_s": q(0.50),
            "skew_p99_s": q(0.99),
            "floor_s": _skew_floor_s(),
            "straggler_episodes": self.straggler_episodes,
            "suspect_rank": int(named) if named is not None else None,
            "suspect_counts": dict(sorted(self.suspect_counts.items())),
            "last_probe": dict(self.last_skew) if self.last_skew else None,
        }

    # -- views ----------------------------------------------------------
    def link_class_table(self) -> Dict[str, dict]:
        """The per-link-class measured term table: median (and best) bus
        bandwidth over every bandwidth row of each class — what the
        planner's roofline consumes in place of the flat ICI term."""
        import statistics

        with self._lock:
            rows = list(self.bandwidth.values())
        out: Dict[str, dict] = {}
        for cls in LINK_CLASSES:
            mine = [r for r in rows if r["link_class"] == cls
                    and r["bus_bytes_per_sec"] > 0]
            if not mine:
                continue
            bws = [r["bus_bytes_per_sec"] for r in mine]
            out[cls] = {
                "rows": len(mine),
                "samples": sum(r["samples"] for r in mine),
                "bus_bytes_per_sec_median": round(statistics.median(bws), 3),
                "bus_bytes_per_sec_best": round(
                    max(r["bus_bytes_per_sec_best"] for r in mine), 3),
                "kinds": sorted({r["kind"] for r in mine}),
            }
        return out

    def totals(self) -> Dict[str, Any]:
        with self._lock:
            doc: Dict[str, Any] = {
                "schema": SCHEMA,
                "rank": _monitor.trainer_rank(),
                "pid": os.getpid(),
                "time_unix": time.time(),
                "collective_seconds": round(self.collective_seconds, 6),
                "attribution": {a: round(b, 3)
                                for a, b in self.attribution.items()},
                "by_axis": {
                    a: {"seconds": round(r["seconds"], 6),
                        "payload_bytes": round(r["payload_bytes"], 3),
                        "steps": r["steps"],
                        "link_class": r["link_class"],
                        "bytes_per_sec": (
                            round(r["payload_bytes"] / r["seconds"], 3)
                            if r["seconds"] > 0 else None)}
                    for a, r in sorted(self.by_axis.items())
                },
                "ops": {op: {"calls": r["calls"],
                             "payload_bytes": round(r["payload_bytes"], 3),
                             "seconds": round(r["seconds"], 6)}
                        for op, r in sorted(self.op_totals.items())},
                "bandwidth": [dict(r) for _, r in
                              sorted(self.bandwidth.items())],
                "skew": self._skew_summary(),
                "skew_series": [dict(s) for s in self.skew_series],
                "step_series": [dict(s) for s in self.step_series],
            }
            steps = self.steps
            episodes = self.straggler_episodes
        if self.base:
            steps += int(self.base.get("steps", 0))
            episodes += int(self.base.get("straggler_episodes", 0))
            doc["resumed_from_journal"] = True
        doc["steps"] = steps
        doc["straggler_episodes"] = episodes
        doc["link_classes"] = self.link_class_table()
        return doc


_LEDGER = CommsLedger()
_PROBE_SEQ = 0


def ledger() -> CommsLedger:
    return _LEDGER


def reset() -> None:
    """Drop everything recorded (journal base included); tests."""
    _LEDGER.reset()
    _JOURNAL.reset()


def record_bandwidth(kind: str, axis: str, payload_bytes: float,
                     group_size: int, seconds: float, *,
                     link_class: str = "ici",
                     source: str = "bench") -> Optional[dict]:
    if not enabled():
        return None
    return _LEDGER.record_bandwidth(kind, axis, payload_bytes, group_size,
                                    seconds, link_class=link_class,
                                    source=source)


def record_collective(op: str, nbytes: Optional[float],
                      seconds: float) -> None:
    """The ``_collective_window`` hook (distributed/collective.py): one
    eager collective's wall + wire bytes. Never raises — the interconnect
    ledger must not take down a collective."""
    if not enabled():
        return
    try:
        import jax

        group = jax.process_count()
    except Exception:
        group = 1
    try:
        _LEDGER.record_collective(op, nbytes, seconds, group_size=group)
    except Exception:
        pass


def configure_attribution(by_axis: Dict[str, Any],
                          link_classes: Optional[Dict[str, str]] = None
                          ) -> None:
    _LEDGER.configure_attribution(by_axis, link_classes)


def end_step(collective_seconds: float = 0.0,
             step: Optional[int] = None) -> Optional[dict]:
    """Close the comms step (called by goodput.end_step with the closed
    step's ``collective`` bucket seconds, so every step driver — hapi
    fit, bench, custom loops — participates for free) and run the
    sampled barrier-skew probe when the cadence hits."""
    if not enabled():
        return None
    closed = _LEDGER.end_step(collective_seconds, step=step)
    if closed is not None:
        for axis, row in closed["by_axis"].items():
            if row["bytes_per_sec"]:
                _M_AXIS_BPS.labels(axis=axis).set(row["bytes_per_sec"])
    maybe_probe(step)
    if closed is not None:
        _JOURNAL.flush_if_due()
    return closed


# ---------------------------------------------------------------------------
# the barrier-skew probe
# ---------------------------------------------------------------------------


def barrier_probe(tag: Optional[str] = None,
                  delay_s: float = 0.0) -> Optional[dict]:
    """One straggler probe: every rank stamps its arrival on the shared
    unix clock (``time.time()`` — the anchor the profiler spans and the
    timeline tracks already use), allgathers the stamps through the
    identity-paired KV exchange, and the LAST arrival names the suspect.
    Collective by construction: every rank of the job must call it at
    the same point (the sampled step cadence, or a comms_bench leg).
    ``delay_s`` injects a straggler on THIS rank (bench/self-test
    evidence that localization names the right rank). Single-process
    runs record a trivial zero-skew probe. Returns the probe record
    (with any flagged episode under ``"episode"``), or None when
    disabled."""
    global _PROBE_SEQ
    if not enabled():
        return None
    if delay_s > 0:
        time.sleep(delay_s)
    _PROBE_SEQ += 1
    try:
        import jax

        n = jax.process_count()
        rank = jax.process_index()
    except Exception:
        n, rank = 1, 0
    arrival = time.time()
    if n <= 1:
        probe = {
            "t": arrival, "tag": tag, "n_ranks": 1, "rank": 0,
            "skew_s": 0.0, "suspect_rank": None,
            "arrivals_rel": {"0": 0.0},
        }
    else:
        import numpy as np

        from .distributed import collective as _coll

        # identity-paired exchange: the probe tag + a process-local
        # sequence that stays aligned because every rank probes at the
        # same step cadence. NOT routed through the public barrier() —
        # the probe must not fold its own wall into the goodput
        # collective bucket it is diagnosing.
        key = f"commswatch/probe/{_PROBE_SEQ}/{tag or 'step'}"
        stacked = _coll._process_allgather(
            np.asarray([arrival], np.float64), tag=key)
        arrivals = [float(stacked[r][0]) for r in range(n)]
        first = min(arrivals)
        last_rank = max(range(n), key=lambda r: arrivals[r])
        probe = {
            "t": arrival, "tag": tag, "n_ranks": n, "rank": rank,
            "skew_s": round(max(arrivals) - first, 6),
            "suspect_rank": int(last_rank),
            "arrivals_rel": {str(r): round(arrivals[r] - first, 6)
                             for r in range(n)},
        }
    out = _LEDGER.record_skew(probe)
    _M_SKEW.set(probe["skew_s"])
    episode = out.get("episode")
    if episode:
        _M_STRAGGLER.inc()
        _monitor.flight_record(
            "commswatch", "straggler_suspect",
            suspect_rank=episode["suspect_rank"],
            skew_s=episode["skew_s"], probes=episode["probes"],
            floor_s=episode["floor_s"], tag=tag)
        print(f"[paddle_tpu.commswatch] straggler suspect: rank "
              f"{episode['suspect_rank']} arrived "
              f"{episode['skew_s'] * 1e3:.1f}ms late over "
              f"{episode['probes']} consecutive probes "
              f"(floor {episode['floor_s'] * 1e3:.0f}ms)",
              file=sys.stderr)
    return out


def maybe_probe(step: Optional[int] = None) -> Optional[dict]:
    """The sampled training-time probe: fires every
    PADDLE_TPU_COMMSWATCH_PROBE_EVERY closed steps (0 = off — the
    default, so single-process runs and benches pay nothing). The
    cadence is step-keyed, so every rank of an SPMD job probes at the
    same boundary."""
    every = int(_flags.env_flag("PADDLE_TPU_COMMSWATCH_PROBE_EVERY"))
    if every <= 0 or step is None or int(step) % every != 0:
        return None
    try:
        import jax

        if jax.process_count() <= 1:
            return None
    except Exception:
        return None
    try:
        return barrier_probe(tag=f"step{int(step)}")
    except Exception:
        return None  # a failed probe must never take down the step


# ---------------------------------------------------------------------------
# views
# ---------------------------------------------------------------------------


def totals() -> Dict[str, Any]:
    return _LEDGER.totals()


def link_class_table() -> Dict[str, dict]:
    return _LEDGER.link_class_table()


def summary() -> Dict[str, Any]:
    doc = totals()
    doc.pop("step_series", None)
    doc.pop("skew_series", None)
    return doc


def status() -> Dict[str, Any]:
    """The /status ``comms`` section: totals + bounded recent tails."""
    doc = totals()
    doc["step_tail"] = doc.pop("step_series", [])[-20:]
    doc["skew_tail"] = doc.pop("skew_series", [])[-20:]
    doc["reconciliation"] = reconcile(doc=doc)
    return doc


def reconcile(doc: Optional[Dict[str, Any]] = None,
              bound_factor: Optional[float] = None) -> Dict[str, Any]:
    """The tentpole's three-way check: predicted collective bytes per
    step (the attribution weights) over the MEASURED per-class bus
    bandwidth must agree with the MEASURED collective wall per step
    within ``bound_factor`` in either direction. The bound is loose by
    design — the bandwidth table is a microbenchmark and the wall
    includes host dispatch — but an order-of-magnitude disagreement
    means the plan, the sweep, or the attribution is lying."""
    bound = bound_factor or _bound_factor()
    doc = doc or totals()
    steps = int(doc.get("steps") or 0)
    attribution = doc.get("attribution") or {}
    classes = doc.get("link_classes") or {}
    coll = float(doc.get("collective_seconds") or 0.0)
    if steps <= 0 or not attribution or coll <= 0:
        return {"available": False, "reason": "no attributed steps"}
    by_axis = doc.get("by_axis") or {}
    predicted_s = 0.0
    terms: Dict[str, dict] = {}
    for axis, nbytes in attribution.items():
        cls = (by_axis.get(axis) or {}).get(
            "link_class", "dcn" if axis == "process" else "ici")
        bw = (classes.get(cls) or {}).get("bus_bytes_per_sec_median")
        if not bw:
            return {"available": False,
                    "reason": f"no measured {cls} bandwidth for "
                              f"axis {axis!r}"}
        t = float(nbytes) / float(bw)
        predicted_s += t
        terms[axis] = {"payload_bytes": nbytes, "link_class": cls,
                       "bus_bytes_per_sec": bw,
                       "predicted_seconds": round(t, 6)}
    measured_per_step = coll / steps
    if predicted_s <= 0:
        return {"available": False, "reason": "zero predicted seconds"}
    ratio = measured_per_step / predicted_s
    return {
        "available": True,
        "predicted_seconds_per_step": round(predicted_s, 6),
        "measured_seconds_per_step": round(measured_per_step, 6),
        "ratio": round(ratio, 4),
        "bound_factor": bound,
        "within_bound": (1.0 / bound) <= ratio <= bound,
        "terms": terms,
    }


# ---------------------------------------------------------------------------
# journal persistence (journal.py has the contract)
# ---------------------------------------------------------------------------


def _unused() -> bool:
    return (_LEDGER.steps == 0 and _LEDGER.probes == 0
            and not _LEDGER.bandwidth)


def configure(dir: Optional[str] = None,
              flush_steps: Optional[int] = None,
              resume: bool = True) -> None:
    """Set up journal persistence; with ``resume``, an existing journal
    seeds the step/episode base."""
    _JOURNAL.configure(dir, every=flush_steps, resume=resume)


def merge_ledgers(docs: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Cross-rank view: bandwidth rows merged by (kind, axis, bucket)
    — samples/bytes/seconds summed, best busBW the max; skew probes
    summed with the suspect tally merged (the straggler verdict must
    survive the merge — each rank's probes name the SAME suspect, so
    the mode is the job-level verdict); per-rank summaries kept."""
    import statistics

    per_rank: Dict[str, dict] = {}
    bw: Dict[str, dict] = {}
    suspect_counts: Dict[str, int] = {}
    probes = 0
    episodes = 0
    steps = 0
    coll = 0.0
    skew_vals: List[float] = []
    by_axis: Dict[str, dict] = {}
    for d in docs:
        r = str(d.get("rank", len(per_rank)))
        sk = d.get("skew") or {}
        per_rank[r] = {
            "steps": int(d.get("steps", 0)),
            "collective_seconds": float(d.get("collective_seconds", 0.0)),
            "probes": int(sk.get("probes", 0)),
            "straggler_episodes": int(d.get("straggler_episodes", 0)),
            "skew_p99_s": sk.get("skew_p99_s"),
        }
        steps = max(steps, per_rank[r]["steps"])
        coll += per_rank[r]["collective_seconds"]
        probes += per_rank[r]["probes"]
        episodes += per_rank[r]["straggler_episodes"]
        if sk.get("skew_p99_s") is not None:
            skew_vals.append(float(sk["skew_p99_s"]))
        for rank_s, n in (sk.get("suspect_counts") or {}).items():
            suspect_counts[rank_s] = suspect_counts.get(rank_s, 0) + int(n)
        for row in d.get("bandwidth") or []:
            key = f"{row['kind']}/{row['axis']}/{row['size_bucket']}"
            if key not in bw:  # first doc seeds the row; later docs fold in
                bw[key] = dict(row)
            else:
                dst = bw[key]
                dst["samples"] += row.get("samples", 0)
                dst["payload_bytes"] += row.get("payload_bytes", 0.0)
                dst["seconds"] += row.get("seconds", 0.0)
                dst["bus_bytes_per_sec_best"] = max(
                    dst["bus_bytes_per_sec_best"],
                    row.get("bus_bytes_per_sec_best", 0.0))
                dst["bus_bytes_per_sec"] = round(
                    (dst["payload_bytes"] / dst["seconds"]
                     * dst.get("bus_factor", 1.0))
                    if dst["seconds"] > 0 else 0.0, 3)
        for axis, row in (d.get("by_axis") or {}).items():
            dst = by_axis.setdefault(axis, {
                "seconds": 0.0, "payload_bytes": 0.0,
                "link_class": row.get("link_class", "ici")})
            dst["seconds"] += float(row.get("seconds", 0.0))
            dst["payload_bytes"] += float(row.get("payload_bytes", 0.0))
    for axis, row in by_axis.items():
        row["bytes_per_sec"] = (round(row["payload_bytes"] / row["seconds"], 3)
                                if row["seconds"] > 0 else None)
        row["seconds"] = round(row["seconds"], 6)
        row["payload_bytes"] = round(row["payload_bytes"], 3)
    named = max(suspect_counts, key=suspect_counts.get, default=None)
    classes: Dict[str, dict] = {}
    for cls in LINK_CLASSES:
        mine = [r for r in bw.values() if r.get("link_class") == cls
                and r.get("bus_bytes_per_sec", 0) > 0]
        if mine:
            classes[cls] = {
                "rows": len(mine),
                "samples": sum(r["samples"] for r in mine),
                "bus_bytes_per_sec_median": round(statistics.median(
                    [r["bus_bytes_per_sec"] for r in mine]), 3),
                "bus_bytes_per_sec_best": round(
                    max(r["bus_bytes_per_sec_best"] for r in mine), 3),
                "kinds": sorted({r["kind"] for r in mine}),
            }
    return {
        "schema": SCHEMA,
        "ranks": sorted(per_rank, key=int),
        "steps": steps,
        "collective_seconds": round(coll, 6),
        "by_axis": dict(sorted(by_axis.items())),
        "bandwidth": [bw[k] for k in sorted(bw)],
        "link_classes": classes,
        "skew": {
            "probes": probes,
            "skew_p99_s": (round(max(skew_vals), 6) if skew_vals
                           else None),
            "straggler_episodes": episodes,
            "suspect_rank": int(named) if named is not None else None,
            "suspect_counts": dict(sorted(suspect_counts.items())),
        },
        "straggler_episodes": episodes,
        "per_rank": dict(sorted(per_rank.items(), key=lambda kv:
                                int(kv[0]))),
    }


def _fmt_bps(v: Optional[float]) -> str:
    if not v:
        return "-"
    for bound, div, unit in ((1e9, 1e9, "GB/s"), (1e6, 1e6, "MB/s"),
                             (1e3, 1e3, "KB/s")):
        if v >= bound:
            return f"{v / div:.2f}{unit}"
    return f"{v:.0f}B/s"


def render_summary(doc: Dict[str, Any], title: str = "interconnect") -> str:
    """Human-readable one-glance comms table (obs_report text mode):
    the per-class bandwidth headline, the per-axis attribution rows,
    and the skew verdict naming the suspect."""
    classes = doc.get("link_classes") or {}
    head = ", ".join(
        f"{cls} {_fmt_bps(row.get('bus_bytes_per_sec_median'))} "
        f"({row.get('samples', 0)} sample(s))"
        for cls, row in sorted(classes.items())) or "no bandwidth rows"
    lines = [f"== {title}: {head} =="]
    for axis, row in (doc.get("by_axis") or {}).items():
        lines.append(
            f"  axis {axis} [{row.get('link_class', '?')}]: "
            f"{_fmt_bps(row.get('bytes_per_sec'))} attributed over "
            f"{row.get('seconds', 0.0):.3f}s")
    sk = doc.get("skew") or {}
    if sk.get("probes"):
        verdict = ("straggler rank "
                   f"{sk['suspect_rank']}" if sk.get("straggler_episodes")
                   and sk.get("suspect_rank") is not None else "healthy")
        p99 = sk.get("skew_p99_s")
        lines.append(
            f"  skew: {sk['probes']} probe(s), "
            f"p99={p99 * 1e3:.1f}ms — {verdict}"
            if p99 is not None else
            f"  skew: {sk['probes']} probe(s) — {verdict}")
    rec = doc.get("reconciliation")
    if rec and rec.get("available"):
        lines.append(
            f"  predicted-vs-measured: "
            f"{rec['predicted_seconds_per_step'] * 1e3:.2f}ms/step plan "
            f"vs {rec['measured_seconds_per_step'] * 1e3:.2f}ms/step "
            f"wall, ratio {rec['ratio']:g} "
            f"(bound x{rec['bound_factor']:g}: "
            f"{'OK' if rec['within_bound'] else 'OUTSIDE'})")
    return "\n".join(lines)


# under launch.py (or a user export of PADDLE_TPU_COMMSWATCH_DIR) every
# rank persists its interconnect ledger with no code change
_JOURNAL = _journal.Journal(
    globals(), _LEDGER, "commswatch", SCHEMA, "PADDLE_TPU_COMMSWATCH_DIR",
    snapshot=totals, unused=_unused, merge=merge_ledgers)
journal_path = _JOURNAL.path
disable_persistence = _JOURNAL.disable_persistence
flush = _JOURNAL.flush
load_journal = _JOURNAL.load
load_journals = _JOURNAL.load_merged
