"""Reduction from a profiler trace to numbers: device busy union, op time
by name pattern, collective time and its exposed part, idle gaps by what
the host was doing.

Works on a NORMALISED trace, a plain dict that ``from_xplane`` makes from
the ``.xplane.pb`` JAX's profiler writes and that ``benchmark/selftest.py``
checks on the recorded one under ``benchmark/testdata/``:

    {"devices": {"/device:TPU:0": [[name, start_ns, dur_ns, detail], ...]},
     "modules": {"/device:TPU:0": [[program, start_ns, dur_ns, full_name], ...]},
     "host":    [[name, start_ns, dur_ns, thread], ...]}

``devices`` holds, per chip, the events of the line that lists the
operations the core executed ("XLA Ops"); ``name`` is the HLO
instruction's name and ``detail`` its text (result shape, operation,
operand shapes, call target), so that a Mosaic kernel, which carries no
name of its own yet, can still be found by what it reads and writes. All
times are on the profiler's one clock, so host spans and device gaps can
be compared.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
DETAIL_CHARS = 1200  # of the HLO text kept per event: operands and the call target
# host frames that only wait: never the answer to "what was the host doing"
_WAITING = re.compile(r"\b(wait|sleep|acquire|join|result|_wait_for_tstate_lock|"
                      r"block_until_ready|select|poll|get)\b")
COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute|collective-broadcast)")


def from_xplane(path: str) -> dict:
    """Normalise one ``.xplane.pb`` (needs only JAX)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    modules: Dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get(OP_LINE)
            if line is None:
                continue
            if MODULE_LINE in lines:
                modules[plane.name] = sorted(
                    [re.sub(r"\(\d+\)$", "", e.name), float(e.start_ns), float(e.duration_ns), e.name]
                    for e in lines[MODULE_LINE].events)
            evs = []
            for e in line.events:
                short, text = split_hlo(e.name)
                evs.append([short, float(e.start_ns), float(e.duration_ns), text])
            evs.sort(key=lambda r: (r[1], -r[2]))
            devices[plane.name] = evs
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    host.append([e.name, float(e.start_ns), float(e.duration_ns), line.name])
    host.sort(key=lambda r: r[1])
    return {"devices": devices, "modules": modules, "host": host}


def split_hlo(text: str) -> Tuple[str, str]:
    """On a TPU the op line names an event by the whole HLO instruction,
    ``%copy.444 = bf16[48,2,432,16,25,64]{...} copy(... %pages.1)``. The
    short name (``copy.444``) is what collectives and stems are read from;
    the text stays as the detail, so that a pattern can also name a
    kernel by its operand shapes and ``custom_call_target``."""
    head, sep, _ = text.partition(" = ")
    if not sep:
        return text, ""
    return head.lstrip("%"), text[:DETAIL_CHARS]


_SHAPE = re.compile(r"(?:pred|[a-z]+\d+)\[[\d,]*\]")


def clip(trace: dict, t0: float, t1: float) -> dict:
    """The part of a trace inside [t0, t1] (events cut at the edges)."""
    def cut(evs):
        out = []
        for r in evs:
            s, e = max(r[1], t0), min(r[1] + r[2], t1)
            if e > s:
                out.append([r[0], s, e - s, r[3]])
        return out

    return {"devices": {k: cut(v) for k, v in trace["devices"].items()},
            "modules": {k: cut(v) for k, v in trace.get("modules", {}).items()},
            "host": cut(trace["host"])}


def merged(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def span(events) -> Tuple[float, float]:
    """[first start, last end] of a list of events."""
    return (min(r[1] for r in events), max(r[1] + r[2] for r in events))


def busy(events) -> dict:
    """Union of the intervals in which an operation ran, over the slice
    from the first operation's start to the last one's end (ns -> s)."""
    if not events:
        return {"busy_s": 0.0, "window_s": 0.0}
    t0, t1 = span(events)
    return {"busy_s": _total(merged((r[1], r[1] + r[2]) for r in events)) / 1e9,
            "window_s": (t1 - t0) / 1e9}


def device_busy(trace: dict) -> dict:
    """busy_s and window_s averaged over the chips, and the idle share."""
    per = [busy(evs) for evs in trace["devices"].values() if evs]
    if not per:
        return {}
    b = sum(p["busy_s"] for p in per) / len(per)
    w = sum(p["window_s"] for p in per) / len(per)
    return {"busy_s": b, "window_s": w, "idle_pct": 100.0 * (1.0 - b / w) if w > 0 else None}


def self_times(events) -> List[Tuple[str, str, float]]:
    """(name, detail, self seconds) per event: its duration minus the
    part its nested children cover (a ``while`` or a ``fusion`` that the
    line shows around its body is not counted twice)."""
    out = []
    stack: list = []  # [end, index]
    child = [0.0] * len(events)
    for i, r in enumerate(events):
        s, e = r[1], r[1] + r[2]
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            child[stack[-1][1]] += min(e, stack[-1][0]) - s
        stack.append((e, i))
    for i, r in enumerate(events):
        out.append((r[0], r[3], max(0.0, r[2] - child[i]) / 1e9))
    return out


def op_label(name: str, detail: str) -> str:
    """A label to group operations by: the instruction's stem and the
    shape it produces (layouts dropped); a Mosaic kernel (``tpu_custom_call``)
    is marked ``pallas:`` and carries its first operand's shape too, since
    the kernels have no names of their own yet."""
    stem = re.sub(r"[.\d]+$", "", name)
    _, _, rest = detail.partition(" = ")
    result, _, operands = rest.partition("(" if not rest.startswith("(") else ") ")
    shapes = _SHAPE.findall(result)
    out = shapes[0] if shapes else ""
    if "tpu_custom_call" in detail:
        first = _SHAPE.search(operands)
        return f"pallas:{stem} {out} <- {first.group(0) if first else ''}".strip()
    return f"{stem} {out}".strip()


def top_ops(trace: dict, top: int = 10) -> List[List]:
    """Device operations that took most (self) time, averaged over chips."""
    acc: Dict[str, float] = {}
    n = max(1, len(trace["devices"]))
    for evs in trace["devices"].values():
        for name, detail, s in self_times(evs):
            k = op_label(name, detail)
            acc[k] = acc.get(k, 0.0) + s / n
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:top]]


def op_time(trace: dict, pattern: str) -> Optional[dict]:
    """Device seconds (mean over chips) and event count of the operations
    whose name or detail matches ``pattern``; None when nothing matches."""
    rx = re.compile(pattern)
    secs, count = 0.0, 0
    n = max(1, len(trace["devices"]))
    for evs in trace["devices"].values():
        hit = [r for r in evs if rx.search(r[0]) or rx.search(r[3])]
        count += len(hit)
        secs += _total(merged((r[1], r[1] + r[2]) for r in hit)) / 1e9
    if not count:
        return None
    return {"seconds": secs / n, "events": count / n}


def collectives(trace: dict) -> Optional[dict]:
    """Share of the slice in collective operations and the exposed part.

    On the op line a core runs one operation at a time, so a synchronous
    collective, and the wait inside an asynchronous one's ``-done``, are
    time in which no compute operation ran: EXPOSED. An asynchronous
    collective is in flight from its ``-start`` to its matching ``-done``
    (paired first-in first-out per kind); ``total`` is the union of the
    synchronous events and those in-flight intervals, so total - exposed
    is communication hidden behind compute."""
    tot = exp = win = 0.0
    seen = False
    for evs in trace["devices"].values():
        if not evs:
            continue
        t0, t1 = span(evs)
        win += (t1 - t0) / 1e9
        sync, flight = [], []
        pending: Dict[str, list] = {}
        for name, s, d, _ in evs:
            m = COLLECTIVE.match(name)
            if not m:
                continue
            seen = True
            kind = m.group(1)
            rest = name[len(kind):]
            if rest.startswith("-start"):
                pending.setdefault(kind, []).append(s)
                sync.append((s, s + d))
            elif rest.startswith("-done"):
                q = pending.get(kind)
                flight.append(((q.pop(0) if q else s), s + d))
                sync.append((s, s + d))
            else:
                sync.append((s, s + d))
        exp += _total(merged(sync)) / 1e9
        tot += _total(merged(sync + flight)) / 1e9
    if not seen or win <= 0:
        return None
    return {"collective_pct": 100.0 * tot / win, "exposed_pct": 100.0 * exp / win}


def idle_gaps(trace: dict, top: int = 10) -> List[List]:
    """The longest device idle gaps of the first chip, each named by what
    the host was doing at its midpoint: the innermost benchmark span
    (``bench/...``) and the innermost host frame that is not a wait.
    Gaps with the same name are added up; the list is by total seconds."""
    if not trace["devices"]:
        return []
    evs = next(iter(trace["devices"].values()))
    if not evs:
        return []
    m = merged((r[1], r[1] + r[2]) for r in evs)
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(m, m[1:])), reverse=True)[:200]
    import numpy as np

    host = [r for r in (trace.get("host") or []) if r[0].startswith("bench/")
            or not _WAITING.search(r[0])]
    starts = np.asarray([r[1] for r in host], np.float64)
    ends = starts + np.asarray([r[2] for r in host], np.float64)
    acc: Dict[str, float] = {}
    for dur, g0, g1 in gaps:
        mid = 0.5 * (g0 + g1)
        bench, frame = None, None
        for i in np.nonzero((starts <= mid) & (ends >= mid))[0]:
            name, _, d, _ = host[i]
            if name.startswith("bench/"):
                if bench is None or d < bench[1]:
                    bench = (name, d)
            elif frame is None or d < frame[1]:
                frame = (name, d)
        label = " > ".join(x[0] for x in (bench, frame) if x) or "(no host span)"
        acc[label] = acc.get(label, 0.0) + dur / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:top]]
