"""Device-memory observability (paddle_tpu/memwatch.py + device.py).

The contract under test: normalized memory_stats() works on every
backend (synthetic live-array fallback on CPU keeps tier-1 real), the
per-step ledger freezes watermarks and deltas at goodput step
boundaries, the leak detector fires once per monotonic-growth episode,
the journal survives a restart, and a RESOURCE_EXHAUSTED dispatch
failure surfaces as the typed error with op provenance plus a
post-mortem JSON next to the XLA artifacts.
"""
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import device, goodput, memwatch, monitor
from paddle_tpu.framework import errors as errs


@pytest.fixture(autouse=True)
def _fresh():
    monitor.enable(True)
    memwatch.reset()
    goodput.reset()
    prev_dir = memwatch._JOURNAL_DIR
    was_dygraph = paddle.in_dygraph_mode()
    yield
    if was_dygraph and not paddle.in_dygraph_mode():
        paddle.disable_static()  # _tiny_train_setup flips to static
    memwatch._JOURNAL_DIR = prev_dir
    memwatch.reset()
    goodput.reset()


# ---------------------------------------------------------------------------
# device.memory_stats normalization + synthetic fallback
# ---------------------------------------------------------------------------


def test_memory_stats_normalized_schema():
    stats = device.memory_stats()
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                "source", "platform", "device_id"):
        assert key in stats, key
    assert stats["source"] in ("device", "synthetic")
    assert stats["bytes_in_use"] >= 0
    assert stats["peak_bytes_in_use"] >= stats["bytes_in_use"] or \
        stats["peak_bytes_in_use"] == stats["bytes_in_use"]


def test_synthetic_fallback_tracks_live_arrays():
    """On CPU the fallback must SEE allocations: a 4MB array raises
    bytes_in_use by at least its size, and the peak is sticky after
    the array dies."""
    import gc

    import jax.numpy as jnp

    # an earlier test file's arrays held in reference cycles must die
    # now, not between the two readings (seen once under xdist, PR 35)
    gc.collect()
    before = device.memory_stats()
    big = jnp.zeros((1024, 1024), jnp.float32)  # 4MiB
    big.block_until_ready()
    after = device.memory_stats()
    assert after["bytes_in_use"] >= before["bytes_in_use"] + 4 * 2**20
    peak_with_big = after["peak_bytes_in_use"]
    del big
    later = device.memory_stats()
    assert later["peak_bytes_in_use"] >= peak_with_big  # peak is sticky


def test_reset_peak_reanchors_synthetic_peak():
    import jax.numpy as jnp

    big = jnp.zeros((512, 1024), jnp.float32)
    big.block_until_ready()
    device.memory_stats()
    del big
    device.reset_peak_memory_stats()
    stats = device.memory_stats()
    assert stats["peak_bytes_in_use"] == pytest.approx(
        stats["bytes_in_use"], abs=1 * 2**20)


# ---------------------------------------------------------------------------
# ledger: watermarks, deltas, step series
# ---------------------------------------------------------------------------


def _feed(in_use, peak=None):
    memwatch.sample(stats={"bytes_in_use": in_use,
                           "peak_bytes_in_use": peak or in_use,
                           "bytes_limit": 16_000_000_000,
                           "source": "synthetic"})


def test_step_watermark_delta_and_lifetime_peak():
    _feed(100)
    _feed(300)  # intra-step spike
    _feed(200)
    closed = memwatch.end_step(step=7)
    assert closed["watermark_bytes"] == 300
    assert closed["bytes_in_use"] == 200
    assert closed["delta_bytes"] == 0  # first step has no predecessor
    assert closed["step"] == 7

    _feed(260)
    closed = memwatch.end_step(step=8)
    assert closed["watermark_bytes"] == 260
    assert closed["delta_bytes"] == 60  # vs the previous step's close

    t = memwatch.totals()
    assert t["steps"] == 2
    assert t["lifetime_peak_bytes"] == 300
    assert t["bytes_limit"] == 16_000_000_000
    assert len(t["step_series"]) == 2
    assert t["peak_fraction_of_limit"] == pytest.approx(300 / 16e9)


def test_ledger_end_step_without_samples_is_none():
    led = memwatch.MemLedger()
    assert led.end_step() is None
    assert led.steps == 0


def test_goodput_end_step_closes_memory_step():
    """The shared step boundary: closing a goodput step closes the
    memory step (no second hook for drivers to forget)."""
    _feed(1000)
    goodput.add("device_compute", 0.01)
    goodput.end_step(0.02, step=3)
    t = memwatch.totals()
    assert t["steps"] == 1
    assert t["last_step"]["step"] == 3


def test_status_doc_has_bounded_tail():
    for i in range(30):
        _feed(100 + i)
        memwatch.end_step(step=i)
    doc = memwatch.status()
    assert doc["steps"] == 30
    assert len(doc["step_tail"]) == 20
    assert "step_series" not in doc


# ---------------------------------------------------------------------------
# leak detector
# ---------------------------------------------------------------------------


def test_leak_detector_fires_once_per_episode(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_MEMWATCH_LEAK_STEPS", "4")
    monkeypatch.setenv("PADDLE_TPU_MEMWATCH_LEAK_MIN_MB", "0.000001")
    base = 1_000_000
    leak = None
    for i in range(1, 9):  # 8 consecutive growing steps
        _feed(base + i * 1000)
        closed = memwatch.end_step(step=i)
        if closed.get("leak"):
            assert leak is None, "leak flagged twice in one episode"
            leak = closed
    assert leak is not None
    # first close has delta 0 (no predecessor), growth run starts at
    # step 2, so the 4-step window completes on step 5
    assert leak["step"] == 5
    assert leak["leak"]["steps"] == 4
    assert memwatch.totals()["leak_events"] == 1

    # plateau resets the episode...
    for i in range(9, 12):
        _feed(base + 8000)
        memwatch.end_step(step=i)
    # ...and a new monotonic run fires again
    for i in range(12, 17):
        _feed(base + 8000 + (i - 11) * 1000)
        memwatch.end_step(step=i)
    assert memwatch.totals()["leak_events"] == 2


def test_leak_detector_respects_min_growth(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_MEMWATCH_LEAK_STEPS", "3")
    monkeypatch.setenv("PADDLE_TPU_MEMWATCH_LEAK_MIN_MB", "1.0")
    for i in range(1, 10):
        _feed(1_000_000 + i * 10)  # grows, but only by ~90 bytes total
        closed = memwatch.end_step(step=i)
        assert not closed.get("leak"), closed
    assert memwatch.totals()["leak_events"] == 0


# ---------------------------------------------------------------------------
# journal persistence + resume
# ---------------------------------------------------------------------------


def test_journal_flush_and_resume(tmp_path):
    _feed(500)
    memwatch.end_step(step=1)
    _feed(900)
    memwatch.end_step(step=2)
    path = memwatch.flush(str(tmp_path / "memwatch.rank0.json"))
    doc = json.load(open(path))
    assert doc["schema"] == memwatch.SCHEMA
    assert doc["steps"] == 2 and doc["lifetime_peak_bytes"] == 900

    # a restarted rank resumes lifetime peak + step count from the journal
    memwatch.reset()
    memwatch.configure(dir=str(tmp_path))
    _feed(300)
    memwatch.end_step(step=3)
    t = memwatch.totals()
    assert t["steps"] == 3  # 2 journaled + 1 fresh
    assert t["lifetime_peak_bytes"] == 900  # the old peak survives
    assert t.get("resumed_from_journal")


def test_journal_resume_skipped_when_not_pristine(tmp_path):
    _feed(500)
    memwatch.end_step(step=1)
    memwatch.flush(str(tmp_path / "memwatch.rank0.json"))
    # the in-process ledger already has steps: resuming would double-count
    memwatch.configure(dir=str(tmp_path))
    assert memwatch.totals()["steps"] == 1


def test_load_journals_merges_ranks(tmp_path):
    for rank, peak in ((0, 700), (1, 1100)):
        doc = {"schema": memwatch.SCHEMA, "rank": rank, "steps": 5,
               "lifetime_peak_bytes": peak, "bytes_in_use": peak - 100,
               "leak_events": rank, "source": "device",
               "bytes_limit": 16_000_000_000}
        (tmp_path / f"memwatch.rank{rank}.json").write_text(json.dumps(doc))
    merged = memwatch.load_journals(str(tmp_path))
    assert merged["ranks"] == ["0", "1"]
    # job peak is the MAX (HBM is per-chip), leaks sum
    assert merged["lifetime_peak_bytes"] == 1100
    assert merged["leak_events"] == 1
    assert merged["per_rank"]["0"]["lifetime_peak_bytes"] == 700
    # headline fields survive the merge (the %-of-limit view): tightest
    # limit, fullest chip, source union
    assert merged["bytes_limit"] == 16_000_000_000
    assert merged["bytes_in_use"] == 1000
    assert merged["source"] == "device"


# ---------------------------------------------------------------------------
# reconciliation
# ---------------------------------------------------------------------------


def test_reconcile_bound_math():
    rec = memwatch.reconcile(estimates=[1000, 4000], measured_peak=6000)
    assert rec["available"] and rec["static_peak_bytes"] == 4000
    assert rec["utilization"] == pytest.approx(1.5)
    assert rec["within_bound"]
    # an order-of-magnitude disagreement fails the stated bound
    rec = memwatch.reconcile(estimates=[1000], measured_peak=50_000)
    assert not rec["within_bound"]
    rec = memwatch.reconcile(estimates=[], measured_peak=5000)
    assert not rec["available"]


# ---------------------------------------------------------------------------
# executor integration: sampling + OOM post-mortem
# ---------------------------------------------------------------------------


def _tiny_train_setup():
    from paddle_tpu import static
    from paddle_tpu.framework import Executor, Program, Scope, program_guard
    from paddle_tpu.optimizer import SGD

    paddle.enable_static()
    main, startup = Program(), Program()
    scope = Scope()
    with program_guard(main, startup):
        x = static.data("x", shape=[-1, 8], dtype="float32")
        y = static.data("y", shape=[-1, 1], dtype="float32")
        pred = static.nn.fc(x, size=1)
        loss = static.nn.reduce_mean(
            static.nn.square(static.nn.elementwise_sub(pred, y)))
        SGD(learning_rate=0.05).minimize(loss)
    exe = Executor()
    exe.run(startup, scope=scope)
    feed = {"x": np.random.RandomState(0).rand(16, 8).astype("float32"),
            "y": np.random.RandomState(1).rand(16, 1).astype("float32")}
    return exe, main, scope, feed, loss


def test_executor_run_samples_memory():
    exe, main, scope, feed, loss = _tiny_train_setup()
    for i in range(3):
        t0 = time.perf_counter()
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        goodput.end_step(time.perf_counter() - t0, step=i)
    t = memwatch.totals()
    assert t["samples"] >= 3
    assert t["steps"] == 3
    assert t["lifetime_peak_bytes"] > 0
    # the gauges carry the live view
    assert monitor.default_registry().get("hbm_bytes_in_use").value >= 0
    assert monitor.default_registry().get("hbm_peak_bytes").value > 0


def test_oom_postmortem_typed_error_with_provenance(tmp_path, monkeypatch):
    """Acceptance: a simulated RESOURCE_EXHAUSTED yields the typed error
    with op provenance plus a post-mortem JSON next to the artifacts."""
    monkeypatch.setenv("PADDLE_TPU_XLA_DUMP_DIR", str(tmp_path))
    exe, main, scope, feed, loss = _tiny_train_setup()
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)  # compile

    def boom(*args):
        raise RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
            "68719476736 bytes.")

    for entry in exe._cache.values():
        entry.fn = boom
    with pytest.raises(errs.ResourceExhaustedError) as ei:
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    e = ei.value
    # typed + catchable as the base EnforceError contract
    assert isinstance(e, errs.EnforceError)
    assert e.op_provenance is not None
    assert e.op_provenance.op_type  # the blamed op is named
    assert "out of memory" in str(e).lower()

    report = e.memory_report
    assert report["schema"] == memwatch.POSTMORTEM_SCHEMA
    assert report["blame"]["op_type"] == e.op_provenance.op_type
    assert report["blame"]["output_bytes_estimate"] > 0
    # model/optimizer footprint by layer prefix made it in
    assert report["footprint"]["total_param_bytes"] > 0
    assert any(r["param_bytes"] > 0
               for r in report["footprint"]["layers"].values())
    # top compiled programs by estimated peak
    assert report["top_programs"] and all(
        p["peak_bytes"] > 0 for p in report["top_programs"])
    assert report["hints"]
    assert "RESOURCE_EXHAUSTED" in report["error"]

    # the JSON dump landed next to the XLA artifacts
    assert e.postmortem_path and os.path.dirname(
        e.postmortem_path) == str(tmp_path)
    on_disk = json.load(open(e.postmortem_path))
    assert on_disk["schema"] == memwatch.POSTMORTEM_SCHEMA
    assert on_disk["blame"]["op_type"] == report["blame"]["op_type"]


def test_non_oom_dispatch_errors_pass_through():
    exe, main, scope, feed, loss = _tiny_train_setup()
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)

    def boom(*args):
        raise RuntimeError("something unrelated went wrong")

    for entry in exe._cache.values():
        entry.fn = boom
    with pytest.raises(RuntimeError, match="unrelated"):
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)


def test_is_oom_error_classification():
    assert memwatch.is_oom_error(RuntimeError("RESOURCE_EXHAUSTED: ..."))
    assert memwatch.is_oom_error(RuntimeError("Out of memory allocating"))
    assert memwatch.is_oom_error(errs.errors.ResourceExhausted("hbm"))
    assert not memwatch.is_oom_error(ValueError("shape mismatch"))


def test_disabled_memwatch_is_inert(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_MEMWATCH", "0")
    assert memwatch.sample() is None
    _feed_attempted = memwatch.end_step()
    assert _feed_attempted is None
    assert memwatch.totals()["samples"] == 0
