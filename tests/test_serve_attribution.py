"""Per-request latency attribution: buckets sum to measured e2e.

The PR's accounting contract, pinned at every layer:
- engine-side: admission_queue/prefill_compute/decode_compute/
  postprocess measured from lifecycle timestamps, batch_wait the
  remainder — so the buckets reconstruct the engine e2e BY CONSTRUCTION;
- router-side: backoff_wait measured, transport the UNION of attempt
  wall intervals minus the winner's engine e2e (overlapping hedge
  attempts must not double-count), router_queue the remainder;
- ledger-side: typed bucket names enforced, residuals aggregated per
  traffic class, and reconcile_attribution bounding the median.

Retried and hedged dispatches are the hard cases — a retry adds a
failed attempt plus a backoff sleep, a hedge OVERLAPS two attempts —
and both must still sum to the router-measured e2e.
"""
import time

import pytest

import paddle_tpu as paddle  # noqa: F401
from paddle_tpu import serving
from paddle_tpu.framework import errors as _errs
from paddle_tpu.serving import ledger as serving_ledger
from paddle_tpu.serving import router as rt


@pytest.fixture(scope="module")
def tiny_model():
    cfg = serving.GPTConfig(vocab_size=128, n_layer=2, n_head=2,
                            d_model=32, max_seq_len=64)
    return serving.DecodeModel(cfg, max_batch=4, n_blocks=16,
                               block_size=8, prefill_buckets=[16, 32],
                               seed=1)


@pytest.fixture(autouse=True)
def _fresh():
    serving_ledger.reset()
    yield
    serving_ledger.reset()


class FailingReplica:
    """Typed-Unavailable-on-first-N-submits replica client: the wire
    shape of a dead peer, for deterministic forced retries."""

    def __init__(self, name, failures=1):
        self.name = name
        self.failures = failures

    def submit(self, prompt, max_new_tokens, deadline_s, request_id,
               timeout, trace=None):
        if self.failures > 0:
            self.failures -= 1
            e = _errs.errors.Unavailable(f"{self.name} down")
            e.reason = "connect"
            raise e
        raise AssertionError("healthy path not scripted")

    def healthz(self, timeout=1.0):
        return {"status": "ok", "serving": {"draining": False,
                                            "queued": 0}}

    def drain(self, timeout=1.0):
        return {"draining": True}


@pytest.mark.parametrize("staggered", [False, True],
                         ids=["together", "staggered"])
def test_engine_buckets_sum_to_e2e(tiny_model, staggered):
    """Every retired request's engine-side buckets reconstruct its
    measured submit->done wall, and only typed bucket names appear.
    With one decode tick in flight ahead of the host a tick's window
    runs from the read before it to its own read: a request's windows
    (its prefill, then its ticks) stay disjoint, also where a later
    admission's prefill went out behind the tick in flight and was read
    after it (`staggered`): its window opens at that read's end and its
    own read is the first token's stamp; the ledger's two legs and its
    buckets still close."""
    eng = serving.ServingEngine(tiny_model)
    hs = [eng.submit([3 + i, 5, 7], max_new_tokens=4 + 4 * staggered)
          for i in range(3)]
    if staggered:
        for _ in range(3):
            eng.step()
        hs.append(eng.submit([9, 5, 7], max_new_tokens=5))
        eng.step()  # tick | prefill | tick: the first two are read
        late, tick = hs[-1]._req, eng._inflight
        assert hs[0]._req.tick_windows[-1][1] == late.t_prefill0 == late.t_admit
        assert late.t_prefill1 == late.t_first_token == tick.t0
        assert len(late.out_tokens) == 1 and late.unread == 1
    eng.run_until_idle()
    for h in hs:
        h.result(timeout=10)
        attr = h.attribution
        assert attr, attr
        assert set(attr) <= set(serving_ledger.ATTRIBUTION_BUCKETS), attr
        got = sum(attr.values())
        assert got == pytest.approx(h.engine_e2e_s, rel=1e-3, abs=1e-6)
        req = h._req
        spans = [(req.t_prefill0, req.t_prefill1)] + [
            (t0, t1) for t0, t1, _ in req.tick_windows]
        assert all(a1 <= b0 < b1
                   for (_, a1), (b0, b1) in zip(spans, spans[1:])), spans
        # queue + prefill is the wall up to the first token's read
        assert attr["admission_queue"] + attr["prefill_compute"] == \
            pytest.approx((req.t_first_token - req.t_submit) / 1e9, abs=1e-9)
    doc = serving_ledger.totals()
    assert (doc["prefills"], doc["prefills_ahead"]) == (
        3 + staggered, int(staggered))
    assert doc["pipeline_drains"] == {
        "evict": 0, "error": 0, "stop": 0, "empty": 1}
    assert serving_ledger.reconcile_spans(doc)["verdict"] == "within_bound"
    assert doc["request_span_seconds"] == pytest.approx(
        doc["decode_slot_seconds"], rel=1e-9)
    assert abs(sum(doc["buckets"].values()) - doc["wall_seconds"]) < 1e-6
    rec = serving_ledger.reconcile_attribution(doc)
    assert rec["available"] and rec["n_requests"] == len(hs), rec
    assert rec["verdict"] == "within_bound", rec
    assert rec["residual_p50"] <= 1e-3, rec


def test_retry_attribution_sums_with_backoff(tiny_model):
    """A forced retry: failed attempt + measured backoff sleep + winning
    attempt still sum to the router-measured e2e, with the backoff
    landing in its OWN bucket (not smeared into transport)."""
    eng = serving.ServingEngine(tiny_model)
    eng.start()
    router = rt.Router([FailingReplica("a-dead"),
                        rt.LocalReplica("b", eng)],
                       retries=2, backoff_ms=25.0, hedge_ms=0,
                       default_slo_s=10.0, seed=5)
    try:
        rec = router.dispatch([9, 2, 4], max_new_tokens=4,
                              request_id="attr-retry",
                              traffic_class="probe")
    finally:
        router.stop()
        eng.stop(flush=False)
    assert rec["ok"] and rec["n_attempts"] == 2 and rec["failover"], rec
    attr = rec["attribution"]
    assert set(attr) <= set(serving_ledger.ATTRIBUTION_BUCKETS), attr
    # the crc32-jittered backoff sleep was actually slept and measured
    assert attr["backoff_wait"] > 0.0, attr
    assert sum(attr.values()) == pytest.approx(rec["latency_s"],
                                               rel=0.02, abs=2e-3)
    assert rec["attribution_residual"] <= 0.05, rec
    # the record landed in the router's OWN ledger under its class
    doc = router.ledger_doc()
    assert doc["role"] == "router"
    assert doc["attribution"]["classes"]["probe"]["n"] == 1
    assert doc["attribution_reconciliation"]["within_bound"], doc


def test_hedge_union_prevents_double_count():
    """Overlapping hedge attempts: transport is the interval UNION
    minus the winner's engine e2e — summing the two attempt walls
    would double-count the overlap and blow the residual."""
    router = rt.Router([FailingReplica("unused", failures=0)],
                       retries=0, backoff_ms=0, hedge_ms=0,
                       default_slo_s=10.0, seed=0)
    try:
        # primary [0.0, 1.0] and hedge [0.4, 1.2]: union 1.2s, naive
        # sum 1.8s; winner spent 0.5s inside the engine
        attempts = [
            {"_t0_mono": 10.0, "_t1_mono": 11.0, "ok": False},
            {"_t0_mono": 10.4, "_t1_mono": 11.2, "ok": True},
        ]
        winner = {"ok": True,
                  "attribution": {"prefill_compute": 0.2,
                                  "decode_compute": 0.3}}
        buckets, residual = router._assemble_attribution(
            attempts, winner, e2e_s=1.3, backoff_wait_s=0.0)
    finally:
        router.stop()
    assert buckets["transport"] == pytest.approx(1.2 - 0.5)
    assert buckets["router_queue"] == pytest.approx(1.3 - 1.2)
    assert sum(buckets.values()) == pytest.approx(1.3)
    assert residual == pytest.approx(0.0, abs=1e-9)


class SlowLocalReplica(rt.LocalReplica):
    """LocalReplica with a fixed pre-submit delay — long enough that
    the hedge window deterministically expires while the primary is
    still in flight (a timing-free forced hedge)."""

    def __init__(self, name, engine, delay_s):
        super().__init__(name, engine)
        self.delay_s = delay_s

    def submit(self, *a, **kw):
        time.sleep(self.delay_s)
        return super().submit(*a, **kw)


def test_hedged_dispatch_attribution_end_to_end(tiny_model):
    """A real hedged dispatch (latency EMA seeded pessimistic so the
    SLO-at-risk test trips at the hedge window, replicas slow enough
    that the window always expires first): buckets still sum to the
    measured e2e with no double-count from the overlap."""
    eng_a = serving.ServingEngine(tiny_model)
    eng_b = serving.ServingEngine(tiny_model)
    eng_a.start()
    eng_b.start()
    router = rt.Router([SlowLocalReplica("a", eng_a, 0.08),
                        SlowLocalReplica("b", eng_b, 0.08)],
                       retries=1, backoff_ms=5.0, hedge_ms=10.0,
                       default_slo_s=10.0, seed=7)
    try:
        with router._lock:
            # every budget of this class reads as at-risk
            router._latency_ema["probe"] = 100.0
        rec = router.dispatch([8, 1, 6], max_new_tokens=6,
                              request_id="attr-hedge",
                              traffic_class="probe")
        router.wait_hedges()
    finally:
        router.stop()
        eng_a.stop(flush=False)
        eng_b.stop(flush=False)
    assert rec["ok"], rec
    assert rec["hedged"], rec
    attr = rec["attribution"]
    assert sum(attr.values()) == pytest.approx(rec["latency_s"],
                                               rel=0.02, abs=2e-3)
    assert rec["attribution_residual"] <= 0.05, rec
    # overlap bound: transport can never exceed the request wall
    assert attr["transport"] <= rec["latency_s"] + 1e-6, attr


def test_ledger_rejects_untyped_bucket_and_bounds_residual():
    led = serving_ledger.ServingLedger()
    with pytest.raises(Exception):
        led.record_attribution({"made_up_bucket": 0.1}, 0.1)
    # a dropped bucket (20% of the e2e missing) must breach the bound
    led.record_attribution({"decode_compute": 0.8}, 1.0,
                           klass="default", request_id="r1",
                           time_unix=time.time())
    rec = serving_ledger.reconcile_attribution(
        led.totals(include_open=False), bound=0.05)
    assert rec["available"] and rec["residual_p50"] > 0.05, rec
    assert rec["verdict"] == "outside_bound", rec
    assert not rec["within_bound"], rec
