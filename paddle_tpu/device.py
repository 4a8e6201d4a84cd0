"""paddle.device namespace: device enumeration + init surface.

Counterpart of /root/reference/paddle/fluid/platform/init.cc (InitDevices
enumerates GPUs and warms contexts, :146) and the 2.0 paddle.device
module. On TPU, enumeration/init delegate to the PJRT client behind jax:
`init_devices()` forces client creation (the reference's warm-up), the
getters expose chip kind/count/topology, and set_device/get_device keep
the reference's "tpu:0" string surface (framework/core.py).

Since the memory-observability round this module is also the ONE place
device memory is read: :func:`memory_stats` normalizes the per-backend
PJRT allocator stats (TPU and GPU disagree on key names; CPU reports
nothing at all) into a fixed schema, with a deterministic synthetic
fallback — live-array byte accounting — so paddle_tpu.memwatch works
under ``JAX_PLATFORMS=cpu`` (tier-1 tests). On a TPU the allocator must
answer: the synthetic numbers there would be an error nobody sees.

:data:`DEVICE_PEAKS` is the one table of hardware peaks, keyed by
``device_kind``; a kind it does not list is an error, never a default."""
from __future__ import annotations

from typing import List

from .framework.core import get_device, set_device  # noqa: F401

_initialized = False

# Published per-chip peaks, keyed by jax's ``device_kind`` string. The one
# table every utilization or roofline number divides by.
# Source: Google Cloud documentation, "TPU v5e" system architecture page.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_sec": 197e12,
        "hbm_bytes_per_sec": 819e9,
        "hbm_bytes": 16e9,
    },
}


def device_peaks(device=None) -> dict:
    """Peaks of one device (default: the first) from :data:`DEVICE_PEAKS`;
    raises for a ``device_kind`` the table does not list — pricing an
    unknown chip at some default turns a wrong number into a result."""
    import jax

    d = device if device is not None else jax.devices()[0]
    try:
        return DEVICE_PEAKS[d.device_kind]
    except KeyError:
        raise RuntimeError(
            f"no hardware peaks for device_kind {d.device_kind!r} "
            f"(platform {d.platform!r}); known kinds: "
            f"{sorted(DEVICE_PEAKS)}. Add the chip to "
            f"paddle_tpu.device.DEVICE_PEAKS with its source.") from None


def require_tpu(who: str):
    """The first device, after insisting it is a TPU the peaks table
    knows: what an entry point that reports device facts or rates
    (chip_smoke.py, bench.py) calls before any work, so that a CPU run
    fails with a message instead of producing numbers."""
    import jax

    d = jax.devices()[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"{who}: no TPU. JAX found platform {d.platform!r} "
            f"({d.device_kind!r} x{len(jax.devices())}); this only runs "
            f"on a machine with a chip (tests run on CPU).")
    device_peaks(d)
    return d


def init_devices() -> int:
    """Eagerly create the runtime client and warm the compile path
    (reference InitDevices, init.cc:146; default init stays lazy).
    Returns the device count."""
    global _initialized
    import jax
    import jax.numpy as jnp

    n = len(jax.devices())
    if not _initialized:
        # one tiny dispatch warms the PJRT client + compiler channel
        jnp.zeros((1,)).block_until_ready()
        _initialized = True
    return n


def device_count(device_type: str = "") -> int:
    import jax

    if not device_type:
        return len(jax.devices())
    return len([d for d in jax.devices() if device_type in d.platform.lower()
                or device_type in d.device_kind.lower()])


def get_all_device_type() -> List[str]:
    import jax

    return sorted({d.platform for d in jax.devices()})


def get_available_device() -> List[str]:
    """Reference paddle.device.get_available_device: 'tpu:i' strings."""
    import jax

    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_device_properties(device=None) -> dict:
    """Chip properties (the reference returns cudaDeviceProp; TPU exposes
    kind/topology through PJRT)."""
    import jax

    devices = jax.devices()
    idx = 0
    if isinstance(device, int):
        idx = device
    elif isinstance(device, str) and ":" in device:
        idx = int(device.rsplit(":", 1)[1])
    d = devices[idx]
    return {
        "device_kind": d.device_kind,
        "platform": d.platform,
        "id": d.id,
        "process_index": d.process_index,
        "coords": tuple(getattr(d, "coords", ()) or ()),
        "core_on_chip": getattr(d, "core_on_chip", 0),
        "memory_stats": (d.memory_stats()
                         if hasattr(d, "memory_stats") else None),
    }


# ---------------------------------------------------------------------------
# normalized device-memory stats (the paddle_tpu.memwatch source)
# ---------------------------------------------------------------------------

# per-backend PJRT key spellings -> the normalized name. First alias
# present wins; TPU reports bytes_in_use/peak_bytes_in_use, GPU mostly
# matches, other plugins drift (bytes_used, pool_bytes, ...).
_MEM_KEY_ALIASES = (
    ("bytes_in_use", ("bytes_in_use", "bytes_used", "allocated_bytes")),
    ("peak_bytes_in_use", ("peak_bytes_in_use", "peak_bytes",
                           "max_bytes_in_use", "peak_allocated_bytes")),
    ("bytes_limit", ("bytes_limit", "bytes_reservable_limit", "pool_bytes",
                     "memory_limit")),
    ("largest_alloc_size", ("largest_alloc_size", "largest_allocation")),
    ("num_allocs", ("num_allocs", "num_allocations")),
)

# synthetic allocator state: per-device running peak of live-array bytes
# (a real allocator remembers its high-water mark; the fallback must too)
_synth_peak: dict = {}


def _resolve_device(device=None):
    import jax

    devices = jax.local_devices()
    if device is None:
        return devices[0]
    if isinstance(device, int):
        return devices[device]
    if isinstance(device, str):
        idx = int(device.rsplit(":", 1)[1]) if ":" in device else 0
        return devices[idx]
    return device  # already a jax Device


def _synthetic_stats(d) -> dict:
    """Deterministic fallback: bytes_in_use = sum of live jax arrays
    resident on `d` (sharded arrays count one shard's worth per device).
    Tracks its own running peak so watermark semantics match a real
    allocator. This is what makes memwatch testable on JAX_PLATFORMS=cpu."""
    import jax

    in_use = 0
    for a in jax.live_arrays():
        try:
            devs = a.devices()
            if d in devs:
                in_use += int(a.nbytes) // max(1, len(devs))
        except Exception:
            continue  # a deleted/donated buffer mid-iteration
    key = (d.platform, d.id)
    peak = max(_synth_peak.get(key, 0), in_use)
    _synth_peak[key] = peak
    return {
        "bytes_in_use": in_use,
        "peak_bytes_in_use": peak,
        "bytes_limit": None,
        "largest_alloc_size": None,
        "num_allocs": None,
        "source": "synthetic",
    }


def memory_stats(device=None) -> dict:
    """Normalized allocator stats for one device:

      {bytes_in_use, peak_bytes_in_use, bytes_limit, largest_alloc_size,
       num_allocs, source, platform, device_id}

    ``source`` is "device" when the PJRT allocator answered (TPU/GPU) and
    "synthetic" when the live-array fallback did (CPU only: a TPU whose
    allocator does not answer raises). Unmapped backend keys ride along
    under ``raw`` so nothing the allocator said is lost."""
    d = _resolve_device(device)
    raw = d.memory_stats()  # None on backends without allocator stats
    if not raw and d.platform == "tpu":
        raise RuntimeError(
            f"{d} reported no allocator stats; refusing the synthetic "
            f"live-array fallback on a TPU")
    if raw:
        out = {}
        for norm, aliases in _MEM_KEY_ALIASES:
            out[norm] = next(
                (int(raw[a]) for a in aliases if raw.get(a) is not None),
                None)
        # an allocator that answered but never reported a peak still gets
        # watermark semantics: carry the running max ourselves
        if out["peak_bytes_in_use"] is None and out["bytes_in_use"] is not None:
            key = (d.platform, d.id)
            out["peak_bytes_in_use"] = max(
                _synth_peak.get(key, 0), out["bytes_in_use"])
            _synth_peak[key] = out["peak_bytes_in_use"]
        out["source"] = "device"
        out["raw"] = {k: v for k, v in raw.items()
                      if isinstance(v, (int, float))}
    else:
        out = _synthetic_stats(d)
    out["platform"] = d.platform
    out["device_id"] = d.id
    return out


def reset_peak_memory_stats(device=None) -> None:
    """Re-anchor the tracked peak at the current bytes_in_use. Only the
    synthetic/carried peak can be reset — a real PJRT allocator's
    peak_bytes_in_use is monotone for the process lifetime."""
    d = _resolve_device(device)
    stats = memory_stats(d)
    _synth_peak[(d.platform, d.id)] = int(stats.get("bytes_in_use") or 0)


def synchronize(device=None) -> None:
    """Block until all dispatched work drains (reference
    device_synchronize; XLA equivalent: fence via a tiny transfer)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    np.asarray(jnp.zeros(()))  # a host transfer orders after queued work


def is_compiled_with_tpu() -> bool:
    import jax

    return any(d.platform == "tpu" for d in jax.devices())
