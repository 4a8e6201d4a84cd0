"""Serving plane: the continuous-batching engine + the legacy surfaces.

Three layers under test:
- the serving engine (paddle_tpu/serving): paged KV block alloc/free/
  reuse under eviction, SLO-ordered admission, continuous-batching
  correctness (batched decode bit-matches sequential decode),
  recipes-driven TP decode sharding with compile-time verify_scope,
  per-request lifecycle spans -> timeline flow arrows, the serving
  ledger's reconciliation bound math, the /status serving section, and
  disabled-mode inertness;
- the legacy C inference ABI (inference/capi/ counterpart, exercised by
  a real compiled-and-linked C program);
- post-training quantization (contrib/slim).
"""
import json
import os
import subprocess
import sys
import textwrap
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle


def _save_lenet_like(tmp_path, scope_holder):
    """Small conv+fc classifier saved as an inference model."""
    from paddle_tpu import static
    from paddle_tpu.framework import Executor, Program, Scope, program_guard

    main, startup = Program(), Program()
    main.random_seed = startup.random_seed = 3
    with program_guard(main, startup):
        img = static.data("img", shape=[2, 1, 8, 8], dtype="float32")
        c = static.nn.conv2d(img, num_filters=4, filter_size=3, act="relu",
                             name="c1")
        p = static.nn.pool2d(c, pool_size=2, pool_stride=2)
        flat = static.nn.reshape(p, [2, 4 * 3 * 3])
        logits = static.nn.fc(flat, size=10, name="fc_out")
    scope = Scope()
    exe = Executor()
    exe.run(startup, scope=scope)
    model_dir = str(tmp_path / "lenet")
    static.io.save_inference_model(
        model_dir, ["img"], [logits], executor=exe, main_program=main,
        scope=scope,
    )
    scope_holder.append((exe, scope, main, logits))
    return model_dir


C_PROGRAM = r"""
#include <stdio.h>
#include <stdlib.h>
#include <stdint.h>

typedef struct PD_Predictor PD_Predictor;
extern PD_Predictor* PD_NewPredictor(const char* model_dir);
extern void PD_DeletePredictor(PD_Predictor*);
extern int PD_GetInputNum(PD_Predictor*);
extern int PD_PredictorRunFloat(PD_Predictor*, const float**, const int64_t* const*,
                                const int*, int, float**, int64_t**, int*);

int main(int argc, char** argv) {
  PD_Predictor* p = PD_NewPredictor(argv[1]);
  if (!p) return 2;
  if (PD_GetInputNum(p) != 1) return 3;
  float in[2 * 1 * 8 * 8];
  for (int i = 0; i < 128; ++i) in[i] = (float)(i % 7) * 0.1f - 0.3f;
  int64_t shape[4] = {2, 1, 8, 8};
  const float* ins[1] = {in};
  const int64_t* shapes[1] = {shape};
  int ndims[1] = {4};
  float* out = NULL;
  int64_t* out_shape = NULL;
  int out_ndim = 0;
  int rc = PD_PredictorRunFloat(p, ins, shapes, ndims, 1, &out, &out_shape, &out_ndim);
  if (rc != 0) return 4;
  printf("SHAPE");
  long numel = 1;
  for (int d = 0; d < out_ndim; ++d) { printf(" %lld", (long long)out_shape[d]); numel *= out_shape[d]; }
  printf("\n");
  printf("DATA");
  for (long i = 0; i < numel; ++i) printf(" %.6f", out[i]);
  printf("\n");
  free(out); free(out_shape);
  PD_DeletePredictor(p);
  return 0;
}
"""


def test_c_api_runs_saved_model(tmp_path):
    """A real C program (compiled + linked against libpaddle_tpu_capi.so)
    loads the saved model and its logits match the Python predictor."""
    paddle.enable_static()
    try:
        holder = []
        model_dir = _save_lenet_like(tmp_path, holder)

        # python-side reference output on the same input the C program uses
        from paddle_tpu.inference import Config, create_predictor

        x = ((np.arange(128) % 7) * 0.1 - 0.3).astype(np.float32).reshape(2, 1, 8, 8)
        pred = create_predictor(Config(model_dir))
        expect = np.asarray(pred.run([x])[0])

        # compile the C program
        src = tmp_path / "capi_main.c"
        src.write_text(C_PROGRAM)
        exe_path = tmp_path / "capi_main"
        lib = os.path.abspath("paddle_tpu/lib")
        subprocess.run(
            ["cc", str(src), "-o", str(exe_path),
             f"-L{lib}", "-lpaddle_tpu_capi", f"-Wl,-rpath,{lib}"],
            check=True,
        )
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.abspath(".") + os.pathsep + env.get("PYTHONPATH", "")
        env["PADDLE_CAPI_PLATFORM"] = "cpu"
        out = subprocess.run(
            [str(exe_path), model_dir], env=env, capture_output=True,
            text=True, timeout=240,
        )
        assert out.returncode == 0, out.stdout + out.stderr
        lines = {l.split()[0]: l.split()[1:] for l in out.stdout.splitlines()
                 if l.startswith(("SHAPE", "DATA"))}
        shape = [int(v) for v in lines["SHAPE"]]
        data = np.asarray([float(v) for v in lines["DATA"]]).reshape(shape)
        assert shape == list(expect.shape)
        np.testing.assert_allclose(data, expect, rtol=1e-4, atol=1e-5)
    finally:
        paddle.disable_static()


def test_ptq_weight_int8_accuracy_delta(tmp_path):
    """quant_post_static: int8 weights + calibration scales; the quantized
    model's predictions stay close (argmax agreement + small relative
    error) and the artifacts (int8 blobs, scales json) exist."""
    from paddle_tpu.contrib.slim import quant_post_static
    from paddle_tpu.framework import Executor
    from paddle_tpu.inference import Config, create_predictor

    paddle.enable_static()
    try:
        holder = []
        model_dir = _save_lenet_like(tmp_path, holder)
        r = np.random.RandomState(0)

        def samples():
            while True:
                yield {"img": r.randn(2, 1, 8, 8).astype(np.float32)}

        qdir = str(tmp_path / "lenet_int8")
        quant_post_static(Executor(), model_dir, qdir,
                          sample_generator=samples, batch_nums=3)

        assert os.path.exists(os.path.join(qdir, "int8_weights.npz"))
        scales = json.load(open(os.path.join(qdir, "quant_scales.json")))
        assert scales["weights"] and scales["activations"]
        with np.load(os.path.join(qdir, "int8_weights.npz")) as z:
            assert all(z[k].dtype == np.int8 for k in z.files)

        fp32 = create_predictor(Config(model_dir))
        int8 = create_predictor(Config(qdir))
        agree = 0
        rel_errs = []
        for _ in range(8):
            x = r.randn(2, 1, 8, 8).astype(np.float32)
            a = np.asarray(fp32.run([x])[0])
            b = np.asarray(int8.run([x])[0])
            agree += int((a.argmax(-1) == b.argmax(-1)).all())
            rel_errs.append(np.abs(a - b).max() / max(np.abs(a).max(), 1e-6))
        assert agree >= 7  # argmax preserved on >= 7/8 batches
        assert np.median(rel_errs) < 0.05
    finally:
        paddle.disable_static()


# ---------------------------------------------------------------------------
# the continuous-batching serving engine (paddle_tpu/serving)
# ---------------------------------------------------------------------------

from paddle_tpu import serving  # noqa: E402
from paddle_tpu.serving import ledger as serving_ledger  # noqa: E402
from paddle_tpu.serving.kv_cache import (  # noqa: E402
    BlockAllocator, blocks_for_tokens)


@pytest.fixture(scope="module")
def tiny_model():
    """One compiled model for the whole module (prefill@16/32 + decode
    compile once)."""
    cfg = serving.GPTConfig(vocab_size=128, n_layer=2, n_head=2,
                            d_model=32, max_seq_len=64)
    return serving.DecodeModel(cfg, max_batch=4, n_blocks=16, block_size=8,
                               prefill_buckets=[16, 32], seed=1)


@pytest.fixture(autouse=True)
def _fresh_ledger():
    serving_ledger.reset()
    yield
    serving_ledger.reset()


def _engine(model, **kw):
    return serving.ServingEngine(model, **kw)


def test_kv_block_alloc_free_reuse():
    """Allocator contract: all-or-nothing grants, LIFO reuse, scratch
    block 0 reserved, double-free loud."""
    alloc = BlockAllocator(8, block_size=4)  # 7 usable + scratch
    assert alloc.capacity == 7
    a = alloc.alloc(3, "a")
    assert a is not None and 0 not in a
    assert alloc.used() == 3 and alloc.available() == 4
    assert alloc.alloc(5, "b") is None  # all-or-nothing: 4 < 5
    assert alloc.used() == 3  # the failed ask granted nothing
    b = alloc.alloc(4, "b")
    assert b is not None and not set(a) & set(b)
    assert alloc.utilization() == 1.0
    alloc.free(b)
    # LIFO reuse: the freed blocks come straight back (cache-friendly
    # and observable — the eviction test leans on this)
    c = alloc.alloc(2, "c")
    assert set(c) <= set(b)
    with pytest.raises(paddle.errors.InvalidArgument):
        alloc.free(c + c[:1])  # double free
    with pytest.raises(paddle.errors.InvalidArgument):
        alloc.free([0])  # scratch is never allocatable
    # a rejected free is ATOMIC: nothing moved, so the valid blocks are
    # still owned and a clean retry succeeds
    assert alloc.used() == 3 + 2
    alloc.free(c)
    assert alloc.used() == 3
    assert blocks_for_tokens(0, 8) == 0
    assert blocks_for_tokens(8, 8) == 1
    assert blocks_for_tokens(9, 8) == 2


def test_admission_queue_slo_ordering(tiny_model):
    """The queue admits by absolute deadline, not arrival: a max_batch=1
    engine must complete a late-arriving tight-SLO request first."""
    q = serving.AdmissionQueue()
    r_loose = serving.ServeRequest(request_id="loose", deadline_s=100.0,
                                   t_submit=0)
    r_tight = serving.ServeRequest(request_id="tight", deadline_s=1.0,
                                   t_submit=0)
    q.push(r_loose)
    q.push(r_tight)
    assert q.pop().request_id == "tight"
    assert q.pop().request_id == "loose"

    eng = _engine(tiny_model, max_batch=1)
    done_order = []
    h1 = eng.submit([3, 4, 5], max_new_tokens=2, deadline_s=100.0)
    h2 = eng.submit([6, 7], max_new_tokens=2, deadline_s=1.0)
    eng.run_until_idle()
    for h, name in ((h1, "loose"), (h2, "tight")):
        assert h.done
    # the tight request retired first despite arriving second
    assert h2._req.t_done < h1._req.t_done


@pytest.fixture(scope="module")
def roomy_model():
    """tiny_model's widths with blocks for four long answers at once."""
    cfg = serving.GPTConfig(vocab_size=128, n_layer=2, n_head=2,
                            d_model=32, max_seq_len=64)
    return serving.DecodeModel(cfg, max_batch=4, n_blocks=40, block_size=8,
                               prefill_buckets=[16, 32], seed=1)


@pytest.mark.parametrize("model_name,lens,budgets", [
    ("tiny_model", (5, 11, 7, 14), (6, 6, 6, 6)),
    # long answers of mixed lengths: nearly every tick goes out ahead
    ("roomy_model", (3, 9, 5, 12), (33, 40, 36, 48)),
])
def test_continuous_batching_bit_match(request, model_name, lens, budgets):
    """The acceptance property: batched continuous decode produces
    BIT-IDENTICAL tokens to sequential decode for the same prompts (and
    both match the full-context greedy reference), with each tick
    enqueued on the last one's unread tokens."""
    model = request.getfixturevalue(model_name)
    r = np.random.RandomState(0)
    prompts = [list(r.randint(1, 128, size=n)) for n in lens]

    eng = _engine(model)
    handles = [eng.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, budgets)]
    eng.run_until_idle()
    batched = [h.result(timeout=5) for h in handles]
    # the ledger's decode-token count includes every request's FINAL
    # tick (retirement must not eat it): n tokens = 1 prefill + n-1 ticks
    doc = serving_ledger.totals()
    assert doc["decode_tokens"] == sum(budgets) - len(budgets)
    # one program a tick of the longest answer, all but the first ahead;
    # all four were admitted in one step, so no prefill found a tick in
    # flight, and only the very last tick was read with nothing behind it
    assert doc["decode_ticks"] == max(budgets) - 1
    assert doc["ticks_ahead"] == doc["decode_ticks"] - 1
    assert doc["ticks_ahead"] / doc["decode_ticks"] > (
        0.8 if max(budgets) >= 32 else 0.0)
    assert (doc["prefills"], doc["prefills_ahead"]) == (4, 0)
    assert doc["pipeline_drains"] == {
        "evict": 0, "error": 0, "stop": 0, "empty": 1}
    assert serving_ledger.reconcile_spans(doc)["ok"]

    eng_seq = _engine(model)
    sequential = []
    for p, n in zip(prompts, budgets):
        h = eng_seq.submit(p, max_new_tokens=n)
        eng_seq.run_until_idle()
        sequential.append(h.result(timeout=5))

    assert batched == sequential  # bitwise: same ints, same order

    # full-context greedy reference (non-paged forward)
    for p, got in zip(prompts, batched):
        assert _greedy_teacher_forced(model, p, got) == got


def _greedy_teacher_forced(model, prompt, answer):
    """The greedy token at every position of `answer`, from ONE non-paged
    forward over prompt + answer: equal to `answer` exactly when each of
    its tokens is the argmax given everything before it."""
    logits = model.full_logits(np.asarray(list(prompt) + list(answer)))[0]
    return [int(row.argmax())
            for row in logits[len(prompt) - 1:len(prompt) - 1 + len(answer)]]


def _greedy_reference(model, prompt, n):
    """n greedy tokens from the non-paged full-context forward."""
    toks = list(prompt)
    for _ in range(n):
        toks.append(int(model.full_logits(np.asarray(toks))[0, -1].argmax()))
    return toks[len(prompt):]


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_program_consumes_the_pool_it_is_given(tiny_model, program):
    """The pool is donated: the array that went in is deleted, the one
    that came out is the pool (same shape and placement)."""
    pages = tiny_model.init_pages()
    assert pages.shape == tiny_model.pool_shape() == (2 * 16, 8, 2 * 2 * 16)
    if program == "prefill":
        out, _, _, _ = tiny_model.prefill_enqueue(
            pages, None, np.asarray([3, 4, 5]), 3, [1])
    else:
        tables = np.zeros((4, tiny_model.max_blocks_per_req), np.int32)
        out, _, _, _ = tiny_model.decode_enqueue(
            pages, None, tables, np.zeros(4, np.int32), np.zeros(4, np.int32))
    assert pages.is_deleted() and not out.is_deleted()
    assert out.shape == pages.shape and out.sharding == pages.sharding


def test_engine_holds_only_the_newest_pool(tiny_model):
    eng = _engine(tiny_model)
    seen = [eng.pages]
    h = eng.submit([3, 4, 5], max_new_tokens=4)
    while eng.step():
        if eng.pages is not seen[-1]:  # the last step only reads
            seen.append(eng.pages)
    assert h.result(timeout=5) == _greedy_reference(tiny_model, [3, 4, 5], 4)
    assert len(seen) >= 3 and seen[-1] is eng.pages
    assert all(p.is_deleted() for p in seen[:-1])
    assert not eng.pages.is_deleted()


def _fail_once_after_dispatch(real):
    """`real`, except that its first call raises AFTER the program ran:
    the donated pool is already consumed, as when a device error
    surfaces at the read-back."""
    calls = []

    def flaky(*args):
        out = real(*args)
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("injected: failed after dispatch")
        return out

    return flaky


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_engine_survives_a_program_failing_after_dispatch(
        tiny_model, monkeypatch, program):
    """A program that dies holding the donated pool leaves the engine
    serving: a fresh pool, the implicated requests failed with a reason,
    the innocent ones re-prefilled as after an eviction, the allocator
    consistent, and the next answer bit-equal to the reference."""
    tiny_model.warm()
    eng = _engine(tiny_model)
    a = eng.submit([3, 4, 5, 6], max_new_tokens=5)
    eng.step()  # a: prefilled and one tick decoded
    lost = eng.pages
    if program == "decode":
        monkeypatch.setattr(tiny_model, "_decode_fn",
                            _fail_once_after_dispatch(tiny_model._decode_fn))
        failed, reason = a, "decode program failed: RuntimeError: injected"
    else:
        monkeypatch.setitem(tiny_model._prefill_fns, 16,
                            _fail_once_after_dispatch(
                                tiny_model._prefill_fns[16]))
        failed = eng.submit([9, 8, 7], max_new_tokens=3)
        reason = "RuntimeError: injected"
    eng.run_until_idle()
    assert lost.is_deleted() and not eng.pages.is_deleted()
    assert failed.done and reason in failed._req.error
    with pytest.raises(Exception, match="injected"):
        failed.result(timeout=1)
    if program == "prefill":
        # the running request lost its context with the pool: evicted,
        # re-prefilled, and still the reference's tokens
        assert a._req.evictions == 1
        assert a.result(timeout=5) == _greedy_reference(
            tiny_model, [3, 4, 5, 6], 5)
    assert eng.allocator.used() == 0 and not eng.active()
    assert eng.queue.depth() == 0
    nxt = eng.submit([11, 12, 13], max_new_tokens=4)
    eng.run_until_idle()
    assert nxt.result(timeout=5) == _greedy_reference(
        tiny_model, [11, 12, 13], 4)


def _spy_on_enqueue(model, monkeypatch):
    """Record (tables, lens, tokens) of every decode tick enqueued."""
    calls, real = [], model.decode_enqueue

    def spy(pages, state, tables, lens, toks, prev=None):
        calls.append((tables.copy(), lens.copy(), toks.copy()))
        return real(pages, state, tables, lens, toks, prev)

    monkeypatch.setattr(model, "decode_enqueue", spy)
    return calls


def _edge_last_token_in_flight(model, monkeypatch):
    """A request whose last token is in flight is not dispatched again,
    and no tick writes K/V past a request's blocks."""
    calls = _spy_on_enqueue(model, monkeypatch)
    eng = _engine(model)
    prompt = [3, 4, 5, 6, 7, 8]
    h = eng.submit(prompt, max_new_tokens=3)
    eng.step()  # prefill out (token 1), tick 1 out on it (token 2), 1 read
    eng.step()  # tick 2 out on tick 1's unread token (token 3), tick 1 read
    assert len(calls) == 2 and h._req.unread == 1 and not h.done
    assert eng.step() and h.done  # nothing left to dispatch: tick 2 read
    assert len(calls) == 2 and not eng.step()
    assert h.result(timeout=5) == _greedy_reference(model, prompt, 3)
    for tables, lens, toks in calls:
        # the one live row writes inside a block it holds, never past
        # the position of the last token it may compute from
        assert lens[0] <= len(prompt) + 3 - 2
        assert tables[0, lens[0] // 8] != 0 and not lens[1:].any()
    # neither tick had its slot's token from the host: tick 1 went out on
    # the prefill's, tick 2 on tick 1's, both still on the device
    assert [c[2][0] for c in calls] == [-1, -1]


def _edge_block_boundary(model, monkeypatch):
    """The context crosses into a new block on the lookahead tick: the
    block is grown from what was dispatched, before anything is read."""
    calls = _spy_on_enqueue(model, monkeypatch)
    eng = _engine(model)
    prompt = [9, 2, 4, 6, 1, 3, 5]  # 7 tokens: tick 1 fills block one
    h = eng.submit(prompt, max_new_tokens=5)
    eng.run_until_idle()
    assert h.result(timeout=5) == _greedy_reference(model, prompt, 5)
    (t1, l1, k1), (t2, l2, k2) = calls[:2]
    assert (l1[0], k1[0], t1[0, 1]) == (7, -1, 0)
    assert (l2[0], k2[0]) == (8, -1) and t2[0, 1] != 0
    assert serving_ledger.totals()["ticks_ahead"] == 3


def _edge_evict_in_flight(model, monkeypatch):
    """An eviction with a tick in flight reads it first: the victim's
    generated tokens are folded into its prompt exactly once."""
    eng = serving.ServingEngine(model, n_blocks=4)
    eng.allocator = BlockAllocator(4, block_size=8)
    loose_p = list(np.random.RandomState(1).randint(1, 128, size=20))
    loose = eng.submit(loose_p, max_new_tokens=4, deadline_s=100.0)
    eng.step()  # prefill + tick 1 in flight; all 3 blocks held
    assert eng._inflight is not None and loose._req.unread == 1
    tight = eng.submit([9, 8, 7], max_new_tokens=2, deadline_s=0.5)
    eng.step()
    assert loose._req.evictions == 1
    assert len(loose._req.generated_prefix) == 2  # prefill's + tick 1's
    eng.run_until_idle()
    assert tight.result(timeout=5) == _greedy_reference(model, [9, 8, 7], 2)
    assert loose.result(timeout=5) == _greedy_reference(model, loose_p, 4)
    doc = serving_ledger.totals()
    assert doc["pipeline_drains"]["evict"] == 1
    # every prefill (two admissions, one resume) found the device empty:
    # the eviction had read the tick in flight before tight's went out
    assert (doc["prefills"], doc["prefills_ahead"]) == (3, 0)
    assert eng.allocator.used() == 0


def _edge_error_behind(model, monkeypatch):
    """A decode program that fails on the device, found at its read with
    the next tick already behind it: its requests go once (the tick behind
    it, which took its pool, is no result either), the pool is rebuilt,
    and the engine serves the next request."""
    model.warm()
    reads, real = [], model.decode_read

    def flaky(nxt):
        reads.append(1)
        if len(reads) == 2:
            raise RuntimeError("injected: failed on the device")
        return real(nxt)

    monkeypatch.setattr(model, "decode_read", flaky)
    eng = _engine(model)
    a = eng.submit([3, 4, 5, 6], max_new_tokens=6)
    b = eng.submit([7, 8], max_new_tokens=3)  # its last token is tick 2's
    for _ in range(3):
        eng.step()  # tick 3 out (a alone), then tick 2's read raises
    lost = eng.pages
    assert eng._inflight is None and not lost.is_deleted()
    for h in (a, b):
        assert h.done and "decode program failed: RuntimeError: injected" \
            in h._req.error
    doc = serving_ledger.totals()
    assert doc["requests"]["failed"] == 2
    assert doc["pipeline_drains"]["error"] == 1
    assert eng.allocator.used() == 0 and not eng.active()
    nxt = eng.submit([11, 12, 13], max_new_tokens=4)
    eng.run_until_idle()
    assert nxt.result(timeout=5) == _greedy_reference(model, [11, 12, 13], 4)


def _edge_stop_in_flight(model, monkeypatch):
    """stop() leaves nothing the device was given unread."""
    eng = _engine(model)
    h = eng.submit([3, 4, 5], max_new_tokens=4)
    eng.step()
    assert eng._inflight is not None and len(h._req.out_tokens) == 1
    eng.stop(flush=False)
    assert eng._inflight is None and len(h._req.out_tokens) == 2
    doc = serving_ledger.totals()
    assert doc["pipeline_drains"]["stop"] == 1 and doc["decode_tokens"] == 1
    assert abs(sum(doc["buckets"].values()) - doc["wall_seconds"]) < 1e-6
    eng.run_until_idle()  # and the engine can go on from there
    assert h.result(timeout=5) == _greedy_reference(model, [3, 4, 5], 4)


def _edge_drain_in_flight(model, monkeypatch):
    """drain() with a tick in flight: admitted work completes, and the
    replica is not `drained` until the last tick has been read."""
    eng = _engine(model)
    h = eng.submit([3, 4, 5], max_new_tokens=3)
    eng.step()
    eng.drain()
    assert eng._inflight is not None and not eng.drained()
    with pytest.raises(paddle.errors.Unavailable):
        eng.submit([1, 2], max_new_tokens=2)
    eng.run_until_idle()
    assert eng.drained() and eng._inflight is None
    assert h.result(timeout=5) == _greedy_reference(model, [3, 4, 5], 3)


def _edge_budget(n):
    def case(model, monkeypatch):
        """An answer of one token is the prefill's; of two, one tick
        that nothing is ever enqueued behind."""
        calls = _spy_on_enqueue(model, monkeypatch)
        eng = _engine(model)
        h = eng.submit([5, 6, 7, 8], max_new_tokens=n)
        eng.run_until_idle()
        assert h.result(timeout=5) == _greedy_reference(model, [5, 6, 7, 8], n)
        doc = serving_ledger.totals()
        assert len(calls) == doc["decode_ticks"] == n - 1
        assert doc["ticks_ahead"] == 0
        assert doc["pipeline_drains"]["empty"] == n - 1
        assert eng._inflight is None and eng.allocator.used() == 0
    return case


def _edge_late_admission(model, monkeypatch):
    """An admission that finds a tick in flight reads nothing first: its
    prefill goes out behind that tick, the next tick behind the prefill
    with the new slot's token still on the device."""
    calls = _spy_on_enqueue(model, monkeypatch)
    eng = _engine(model)
    prompts = [[3, 4, 5], [6, 7], [8, 9, 10]]
    hs = [eng.submit(prompts[0], max_new_tokens=12)]
    for _ in range(3):
        eng.step()
    for i, (p, n) in enumerate(zip(prompts[1:], (6, 5)), start=1):
        hs.append(eng.submit(p, max_new_tokens=n))  # finds a tick
        before = eng._inflight
        eng.step()
        doc = serving_ledger.totals()
        assert (doc["prefills"], doc["prefills_ahead"]) == (1 + i, i)
        assert not any(doc["pipeline_drains"].values())
        # the tick that was in flight is read, the one built on the
        # prefill is out, and both slots' tokens came from the device
        assert eng._inflight is not before and len(eng._unread) == 1
        assert list(calls[-1][2][:i + 1]) == [-1] * (i + 1)
        assert len(hs[-1]._req.out_tokens) == 1 and hs[-1]._req.unread == 1
    assert doc["ticks_ahead"] == doc["decode_ticks"] - 1
    eng.run_until_idle()
    for p, n, h in zip(prompts, (12, 6, 5), hs):
        assert h.result(timeout=5) == _greedy_reference(model, p, n)


def _edge_late_budget(n):
    def case(model, monkeypatch):
        """An answer of one token admitted behind a tick in flight is the
        prefill's, and no tick carries its slot; of two, one tick takes
        the first from the device and nothing is enqueued behind it."""
        calls = _spy_on_enqueue(model, monkeypatch)
        eng = _engine(model)
        a = eng.submit([3, 4, 5], max_new_tokens=8)
        for _ in range(2):
            eng.step()
        b = eng.submit([5, 6, 7, 8], max_new_tokens=n)
        eng.step()
        assert b._req.slot in (1, -1) and b.done == (n == 1)
        assert len(b._req.out_tokens) == 1
        eng.run_until_idle()
        assert b.result(timeout=5) == _greedy_reference(model, [5, 6, 7, 8], n)
        assert a.result(timeout=5) == _greedy_reference(model, [3, 4, 5], 8)
        # slot 1 is live in n - 1 ticks, each time on the device's token
        assert [c[2][1] for c in calls if c[1][1]] == [-1] * (n - 1)
        doc = serving_ledger.totals()
        assert (doc["prefills"], doc["prefills_ahead"]) == (2, 1)
        assert doc["pipeline_drains"] == {
            "evict": 0, "error": 0, "stop": 0, "empty": 1}
        assert doc["ticks_ahead"] == doc["decode_ticks"] - 1 == 6
        assert not eng._unread and eng.allocator.used() == 0
    return case


def _edge_two_admissions(model, monkeypatch):
    """Two admissions in one step behind a tick in flight: the second
    prefill takes the first one's merged tokens, the tick after them
    both, and the reads come back in the order the device ran them."""
    calls = _spy_on_enqueue(model, monkeypatch)
    eng = _engine(model)
    prompts = [[3, 4, 5], [8, 9, 10], [11, 12]]
    hs = [eng.submit(prompts[0], max_new_tokens=9)]
    for _ in range(2):
        eng.step()
    hs += [eng.submit(p, max_new_tokens=5) for p in prompts[1:]]
    eng.step()
    doc = serving_ledger.totals()
    assert (doc["prefills"], doc["prefills_ahead"]) == (3, 2)
    assert not any(doc["pipeline_drains"].values())
    assert list(calls[-1][2][:3]) == [-1, -1, -1]
    first, b, c = (h._req for h in hs)
    assert [len(r.out_tokens) for r in (b, c)] == [1, 1]
    # tick | prefill b | prefill c | tick: disjoint windows, in that order
    assert first.tick_windows[-1][1] <= b.t_prefill0 < b.t_prefill1 \
        <= c.t_prefill0 < c.t_prefill1 <= eng._inflight.t0
    assert (b.t_first_token, c.t_first_token) == (b.t_prefill1, c.t_prefill1)
    eng.run_until_idle()
    for p, n, h in zip(prompts, (9, 5, 5), hs):
        assert h.result(timeout=5) == _greedy_reference(model, p, n)


def _edge_prefill_error_behind(model, monkeypatch):
    """A prefill that fails on the device, found at its read with a tick
    enqueued behind it: its request fails, the tick that took its pool is
    no result, the running request is re-prefilled once, the pool is
    rebuilt once, and the engine serves on."""
    model.warm()
    real = model.prefill_read

    def flaky(nxt, slot=0):
        if slot == 1:
            raise RuntimeError("injected: failed on the device")
        return real(nxt, slot)

    monkeypatch.setattr(model, "prefill_read", flaky)
    rebuilt = []
    monkeypatch.setattr(
        model, "init_pages",
        lambda real=model.init_pages: rebuilt.append(1) or real())
    eng = _engine(model)
    a = eng.submit([3, 4, 5, 6], max_new_tokens=6)
    for _ in range(2):
        eng.step()
    del rebuilt[:]
    b = eng.submit([7, 8], max_new_tokens=3)
    eng.step()  # tick | prefill b | tick: the first read is sound
    assert b.done and "prefill failed: RuntimeError: injected" in b._req.error
    assert not eng._unread and rebuilt == [1] and not eng.pages.is_deleted()
    assert a._req.evictions == 1 and a._req.unread == 0
    assert len(a._req.generated_prefix) == 3  # the prefill's + two ticks'
    doc = serving_ledger.totals()
    assert doc["pipeline_drains"] == {
        "evict": 0, "error": 1, "stop": 0, "empty": 0}
    eng.run_until_idle()
    assert a.result(timeout=5) == _greedy_reference(model, [3, 4, 5, 6], 6)
    doc = serving_ledger.totals()
    assert doc["requests"]["failed"] == 1 and rebuilt == [1]
    assert eng.allocator.used() == 0 and not eng.active()
    monkeypatch.setattr(model, "prefill_read", real)
    nxt = eng.submit([11, 12, 13], max_new_tokens=4)
    eng.run_until_idle()
    assert nxt.result(timeout=5) == _greedy_reference(model, [11, 12, 13], 4)


def _edge_stop_prefill_unread(model, monkeypatch):
    """stop() with a prefill's token unread behind the tick in flight:
    both are read, in order, and the engine can go on from there."""
    eng = _engine(model)
    a = eng.submit([3, 4, 5], max_new_tokens=5)
    for _ in range(2):
        eng.step()
    b = eng.submit([6, 7, 8, 9], max_new_tokens=3)
    with eng._step_lock:  # an admission, cut short before its tick
        (req,) = eng._admit()
        eng._run_prefill(req)
    assert len(eng._unread) == 2 and req.unread == 1 and not req.out_tokens
    eng.stop(flush=False)
    assert not eng._unread and req.unread == 0 and len(req.out_tokens) == 1
    assert a._req.tick_windows[-1][1] <= req.t_prefill0 < req.t_prefill1
    doc = serving_ledger.totals()
    assert doc["pipeline_drains"]["stop"] == 1
    assert abs(sum(doc["buckets"].values()) - doc["wall_seconds"]) < 1e-6
    eng.run_until_idle()
    assert a.result(timeout=5) == _greedy_reference(model, [3, 4, 5], 5)
    assert b.result(timeout=5) == _greedy_reference(model, [6, 7, 8, 9], 3)


@pytest.mark.parametrize("case", [
    _edge_last_token_in_flight, _edge_block_boundary, _edge_evict_in_flight,
    _edge_error_behind, _edge_stop_in_flight, _edge_drain_in_flight,
    _edge_budget(1), _edge_budget(2), _edge_late_admission,
    _edge_late_budget(1), _edge_late_budget(2), _edge_two_admissions,
    _edge_prefill_error_behind, _edge_stop_prefill_unread,
], ids=["last_token_in_flight", "block_boundary", "evict_in_flight",
        "error_behind", "stop_in_flight", "drain_in_flight",
        "max_new_tokens_1", "max_new_tokens_2", "late_admission",
        "late_max_new_tokens_1", "late_max_new_tokens_2",
        "two_admissions", "prefill_error_behind", "stop_prefill_unread"])
def test_one_tick_in_flight_edges(tiny_model, monkeypatch, case):
    """The corners of the one-tick lookahead (serving/engine.py): what
    the host has not read yet is never needed unread, never computed
    twice, and never lost."""
    case(tiny_model, monkeypatch)


# -- the decode tick's row lookup: a slice a slot where the table rests
# vocabulary-on-lanes (DecodeModel.embed_path; PERF.md, PR 47) --------------


def _lookup_model(position="learned", dtype="float32", gather=False):
    """d_model 48 (no multiple of 128) under a lane-aligned vocabulary:
    the decode tick takes its rows by slices. ``gather``: the same model
    made to keep the one gather, as a table that rests row-major would."""
    cfg = serving.GPTConfig(vocab_size=256, n_layer=2, n_head=2, d_model=48,
                            max_seq_len=64, position=position, dtype=dtype)
    dm = serving.DecodeModel(cfg, max_batch=8, n_blocks=40, block_size=8,
                             prefill_buckets=[16, 32], seed=5)
    assert dm.embed_path()[0] == "slices"
    if gather:
        dm.embed_path = lambda: ("gather", "the test's")
    return dm


@pytest.mark.parametrize("position,dtype", [
    ("learned", "float32"), ("learned", "bfloat16"),
    ("rope", "float32"), ("rope", "bfloat16")])
def test_row_slices_are_the_gathers_rows_bit_for_bit(position, dtype):
    """Repeated tokens, token 0 and the last vocabulary row; positions
    likewise; a ``rope`` model has no ``wpe`` to look up."""
    import jax

    dm = _lookup_model(position, dtype)
    p = dm.params
    assert ("gpt.wpe" in p) == (position == "learned")
    tokens = np.asarray([0, 255, 7, 7, 255, 0, 3, 200], np.int32)
    pos = np.asarray([0, 63, 5, 5, 63, 1, 0, 31], np.int32)
    want = np.asarray(p["gpt.wte"])[tokens]
    if position == "learned":
        want = want + np.asarray(p["gpt.wpe"])[pos]
    for sliced in (True, False):
        got = jax.jit(lambda p, t, q: dm._embed(p, t, q, sliced))(
            p, tokens, pos)
        assert got.dtype == want.dtype and got.shape == (8, 48)
        assert np.array_equal(np.asarray(got), want), sliced


def test_embed_path_follows_from_the_tables_shape():
    """The same rule on every backend, no knob: lanes that are no whole
    tiles under a vocabulary that is; a mesh program keeps the gather."""
    from paddle_tpu.serving.model import rests_lanes_first

    assert rests_lanes_first((50304, 1600)) and rests_lanes_first((1024, 1600))
    assert not rests_lanes_first((65536, 2048)) and not rests_lanes_first((50304, 2048))
    assert not rests_lanes_first((512, 256))  # neither tiling is smaller
    path, why = _lookup_model().embed_path()
    assert path == "slices" and "vocabulary-on-lanes" in why and "48" in why
    wide = serving.DecodeModel(
        serving.GPTConfig(vocab_size=256, n_layer=1, n_head=2, d_model=128,
                          max_seq_len=64),
        max_batch=2, n_blocks=8, block_size=16, prefill_buckets=[16])
    path, why = wide.embed_path()
    assert path == "gather" and "row-major" in why
    path, why = serving.DecodeModel(
        serving.GPTConfig(vocab_size=256, n_layer=1, n_head=2, d_model=48,
                          max_seq_len=64),
        max_batch=2, n_blocks=8, block_size=16, prefill_buckets=[16],
        recipe="tp").embed_path()
    assert path == "gather" and "mesh" in why


@pytest.mark.parametrize("position", ["learned", "rope"])
def test_decode_tick_returns_the_same_tokens_on_either_path(position):
    """One tick, slot by slot: a ``-1`` slot takes the tick before's own
    output (``prev``) in front of the lookup, and the tokens that come
    back are those of the one gather."""
    import jax.numpy as jnp

    B = 8
    tables = np.zeros((B, 8), np.int32)
    lens = np.asarray([3, 0, 5, 1, 0, 7, 2, 4], np.int32)
    unread = np.asarray([-1, 5, -1, 0, 255, -1, 9, 9], np.int32)
    prev = np.asarray([255, 1, 0, 1, 1, 17, 1, 1], np.int32)
    read = np.where(unread < 0, prev, unread)
    out = {}
    for gather in (False, True):
        dm = _lookup_model(position, gather=gather)
        for name, toks, pv in (("unread", unread, jnp.asarray(prev)),
                               ("read", read, None)):
            _, _, nxt, _ = dm.decode_enqueue(dm.init_pages(), None, tables,
                                             lens, toks, pv)
            out[gather, name] = dm.decode_read(nxt)[0]
    first = out[False, "unread"]
    assert first.shape == (B,) and len(set(first.tolist())) > 1
    for key, toks in out.items():
        assert np.array_equal(toks, first), key


def test_engine_answers_are_the_same_on_either_path():
    """Whole answers through the engine (prefill, then ticks enqueued on
    unread tokens): slices and gather serve the same tokens, the greedy
    ones of the non-paged forward."""
    r = np.random.RandomState(2)
    prompts = [list(r.randint(0, 256, size=n)) for n in (5, 11, 7, 14)]
    answers = []
    for gather in (False, True):
        dm = _lookup_model(gather=gather)
        eng = _engine(dm)
        handles = [eng.submit(p, max_new_tokens=9) for p in prompts]
        eng.run_until_idle()
        answers.append([h.result(timeout=5) for h in handles])
    assert answers[0] == answers[1]
    for p, got in zip(prompts, answers[0]):
        assert _greedy_teacher_forced(dm, p, got) == got


def test_kv_eviction_under_pressure(tiny_model):
    """Under KV exhaustion a tight-SLO arrival preempts the loosest
    running request: the victim's blocks free and are REUSED by the
    incoming request; the victim resumes (recompute) and still delivers
    its full token budget."""
    # capacity 3 usable blocks (bs 8): the loose request's 20-token
    # prompt takes all 3
    eng = serving.ServingEngine(tiny_model, n_blocks=4)
    # engine-level n_blocks smaller than the model envelope is legal:
    # the model's gather covers max_seq_len, the allocator just holds
    # fewer blocks
    eng.allocator = BlockAllocator(4, block_size=8)
    r = np.random.RandomState(1)
    loose = eng.submit(list(r.randint(1, 128, size=20)), max_new_tokens=3,
                       deadline_s=100.0)
    eng.step()  # admit + prefill the loose request (holds 3 blocks)
    loose_blocks = list(loose._req.blocks)
    assert len(loose_blocks) == 3 and eng.allocator.available() == 0
    tight = eng.submit([9, 8, 7], max_new_tokens=2, deadline_s=0.5)
    eng.run_until_idle()
    assert tight.result(timeout=5) and loose.result(timeout=5)
    assert loose._req.evictions >= 1
    # the evicted request's freed blocks were reused by the tight one
    assert set(tight._req.blocks) == set()  # freed after retirement
    assert len(loose.result(timeout=5)) == 3  # full budget despite evict
    doc = serving_ledger.totals()
    assert doc["requests"].get("evicted", 0) >= 1
    assert doc["requests"].get("ok", 0) == 2


def test_decode_tp_sharding_from_recipes(tiny_model):
    """The decode program's TP sharding comes from parallel/recipes.py
    (no serving-local rules) and compile-time verify_scope passes; the
    sharded engine produces the same tokens as the single-device one."""
    from paddle_tpu.parallel.recipes import GPT_TP_RULES, resolve_recipe

    cfg = serving.GPTConfig(vocab_size=128, n_layer=2, n_head=2,
                            d_model=32, max_seq_len=64)
    recipe = resolve_recipe("tp", 2)
    m = serving.DecodeModel(cfg, max_batch=4, n_blocks=16, block_size=8,
                            prefill_buckets=[16, 32], recipe=recipe,
                            seed=1)
    # the rules ARE the shared table's (tp rules + state variants): every
    # tp rule the model compiled with appears in GPT_TP_RULES
    assert [rule for rule in GPT_TP_RULES if rule in m.rules] == list(
        GPT_TP_RULES)
    # compile-time placement verification (PADDLE_TPU_SHARD_VERIFY=1 is
    # on suite-wide): zero intended-vs-actual mismatches
    assert m.sharding_mismatches == []
    # the qkv weight is really column-sharded over tp on the mesh
    spec = tuple(m.params["gpt.h0.attn.q.w"].sharding.spec)
    assert spec == (None, "tp"), spec
    eng = _engine(m)
    h = eng.submit([5, 9, 3, 44, 17], max_new_tokens=5)
    eng.run_until_idle()
    tp_tokens = h.result(timeout=5)

    eng1 = _engine(tiny_model)
    h1 = eng1.submit([5, 9, 3, 44, 17], max_new_tokens=5)
    eng1.run_until_idle()
    assert tp_tokens == h1.result(timeout=5)


@pytest.mark.parametrize("n_head,spec", [(2, (None, None, "tp")),
                                         (3, (None, None, None))])
def test_pool_shards_whole_heads_or_not_at_all(n_head, spec):
    """The pool's row shards over tp only where each device then holds
    whole heads (K and V together): 3 heads over tp=2 stay replicated
    although the 96-lane row itself would divide."""
    from paddle_tpu.parallel.recipes import resolve_recipe

    cfg = serving.GPTConfig(vocab_size=64, n_layer=1, n_head=n_head,
                            d_model=16 * n_head, max_seq_len=32)
    m = serving.DecodeModel(cfg, max_batch=2, n_blocks=4, block_size=8,
                            prefill_buckets=[16],
                            recipe=resolve_recipe("tp", 2), seed=1)
    pages = m.init_pages()
    assert pages.shape == (4, 8, n_head * 2 * 16)
    assert tuple(pages.sharding.spec) == spec
    share = pages.addressable_shards[0].data.nbytes / pages.nbytes
    assert share == (0.5 if spec[2] else 1.0)


def test_never_fitting_request_fails_fast(tiny_model):
    """A trajectory the cache can never hold fails at admission instead
    of requeueing forever (the engine must stay live)."""
    eng = serving.ServingEngine(tiny_model)
    eng.allocator = BlockAllocator(3, block_size=8)  # 2 usable blocks
    # prompt 20 needs 3 blocks just for prefill: impossible, ever
    h = eng.submit(list(range(1, 21)), max_new_tokens=2, deadline_s=5.0)
    eng.run_until_idle()
    assert h.done
    with pytest.raises(paddle.errors.InvalidArgument,
                       match="KV blocks"):
        h.result(timeout=1)
    assert eng.queue.depth() == 0 and not eng.active()
    assert serving_ledger.totals()["requests"].get("failed", 0) == 1


def test_span_reconciliation_bound_math():
    """The request-span and roofline reconciliation verdicts at their
    boundaries (the memwatch/shard_insight taxonomy idiom)."""
    rec = serving_ledger.reconcile_spans(
        {"request_span_seconds": 1.0, "decode_slot_seconds": 1.2},
        bound_factor=1.5)
    assert rec["verdict"] == "within_bound" and rec["ok"]
    rec = serving_ledger.reconcile_spans(
        {"request_span_seconds": 2.0, "decode_slot_seconds": 1.0},
        bound_factor=1.5)
    assert rec["verdict"] == "outside_bound" and not rec["ok"]
    rec = serving_ledger.reconcile_spans(
        {"request_span_seconds": 1.0, "decode_slot_seconds": 0.0})
    assert rec["verdict"] == "spans_only" and not rec["ok"]
    rec = serving_ledger.reconcile_spans(
        {"request_span_seconds": 0.0, "decode_slot_seconds": 1.0})
    assert rec["verdict"] == "engine_only" and not rec["ok"]
    rec = serving_ledger.reconcile_spans(
        {"request_span_seconds": 0.0, "decode_slot_seconds": 0.0})
    assert rec["available"] is False and rec["verdict"] is None

    base = {"decode_tokens": 100, "buckets": {"decode_compute": 1.0},
            "tokens_per_sec": 50.0}
    roof = {"predicted_tokens_per_sec": 200.0,
            "legs": {"compute_s": 1e-3, "memory_s": 2e-3,
                     "dispatch_s": 1e-5},
            "bound_by": "memory_s"}
    rec = serving_ledger.reconcile_roofline(dict(base), roofline=roof,
                                            bound_factor=8.0)
    # measured side is the decode-plane rate (100 tok / 1.0s), ratio 0.5
    assert rec["measured_tokens_per_sec"] == pytest.approx(100.0)
    assert rec["ratio"] == pytest.approx(0.5)
    assert rec["verdict"] == "within_bound"
    assert rec["bound_by"] == "memory_s"
    assert rec["bound_factors"]["memory_s"] == pytest.approx(2e-3)
    rec = serving_ledger.reconcile_roofline(dict(base), roofline=roof,
                                            bound_factor=1.5)
    assert rec["verdict"] == "outside_bound"  # 0.5 < 1/1.5
    rec = serving_ledger.reconcile_roofline(
        {"decode_tokens": 1000, "buckets": {"decode_compute": 1.0}},
        roofline=roof, bound_factor=8.0)
    assert rec["verdict"] == "outside_bound"  # 5x ABOVE the ceiling
    rec = serving_ledger.reconcile_roofline(dict(base), roofline=None)
    assert rec["verdict"] == "measured_only" and not rec["ok"]
    rec = serving_ledger.reconcile_roofline(
        {"decode_tokens": 0, "buckets": {}}, roofline=roof)
    assert rec["verdict"] == "predicted_only" and not rec["ok"]


def test_serving_ledger_journal_resume_and_merge(tiny_model, tmp_path):
    """The journal round trip: flush -> resume seeds the cumulative
    base; two replica journals merge with exact histogram addition."""
    eng = _engine(tiny_model)
    h = eng.submit([1, 2, 3], max_new_tokens=3)
    eng.run_until_idle()
    h.result(timeout=5)
    doc0 = serving_ledger.totals()
    path = serving_ledger.flush(str(tmp_path / "serving.rank0.json"))
    loaded = serving_ledger.load_journal(path)
    assert loaded["requests"]["ok"] == 1
    assert loaded["span_reconciliation"]["verdict"] == "within_bound"

    # resume: a pristine ledger seeds from the journal
    serving_ledger.reset()
    serving_ledger.configure(dir=str(tmp_path))
    resumed = serving_ledger.totals()
    assert resumed.get("resumed_from_journal")
    assert resumed["requests"]["ok"] == 1
    assert resumed["ticks"] == doc0["ticks"]
    # 3 tokens: two ticks, the second ahead, the last one read alone
    for doc in (doc0, loaded, resumed):
        assert (doc["decode_ticks"], doc["ticks_ahead"]) == (2, 1)
        assert (doc["prefills"], doc["prefills_ahead"]) == (1, 0)
        assert doc["pipeline_drains"] == {
            "evict": 0, "error": 0, "stop": 0, "empty": 1}
    serving_ledger.disable_persistence()

    # merge two replicas: counts add, histograms add exactly
    rank1 = dict(loaded)
    rank1["rank"] = 1
    with open(tmp_path / "serving.rank1.json", "w") as f:
        json.dump(rank1, f)
    merged = serving_ledger.load_journals(str(tmp_path))
    assert merged["ranks"] == [0, 1]
    assert merged["requests"]["ok"] == 2
    assert merged["ttft_hist"]["count"] == 2
    assert merged["slo"]["latency"]["count"] == 2
    assert merged["wall_seconds"] == pytest.approx(
        2 * loaded["wall_seconds"])
    assert merged["span_reconciliation"]["verdict"] == "within_bound"
    assert (merged["decode_ticks"], merged["ticks_ahead"]) == (4, 2)
    assert merged["pipeline_drains"]["empty"] == 2
    assert serving_ledger.render_summary(merged).startswith("== serving")
    serving_ledger.reset()
    cleared = serving_ledger.totals()
    assert cleared["ticks_ahead"] == 0
    assert not any(cleared["pipeline_drains"].values())


def test_lifecycle_spans_merge_into_timeline(tiny_model, tmp_path):
    """The engine's per-request lifecycle spans flush through the
    profiler and merge into timeline flow arrows threading the shared
    batch ticks."""
    sys.path.insert(0, os.path.abspath("tools"))
    try:
        import timeline as tl
    finally:
        sys.path.pop(0)
    from paddle_tpu import profiler

    profiler.clear_events()
    profiler.enable_tracing()
    try:
        eng = _engine(tiny_model)
        hs = [eng.submit([7 + i, 3, 9], max_new_tokens=4)
              for i in range(2)]
        eng.run_until_idle()
        [h.result(timeout=5) for h in hs]
        events = [e for e in profiler.get_events()
                  if e.get("cat") == "serve"]
    finally:
        profiler.stop_profiler(print_table=False)
    names = {e["name"] for e in events}
    for expect in ("serve/admit", "serve/queue", "serve/prefill",
                   "serve/decode_tick", "serve/done"):
        assert expect in names, names
    rids = {e["meta"]["request_id"] for e in events if e.get("meta")}
    assert len(rids) == 2
    # every request's chain is parent-linked end to end
    for rid in rids:
        chain = [e for e in events
                 if (e.get("meta") or {}).get("request_id") == rid]
        assert sum(1 for e in chain if e["parent_span_id"] is None) == 1

    trace_path = str(tmp_path / "trace.rank0.json")
    profiler.flush_trace(trace_path)
    profiler.clear_events()
    by_rank = tl.load_rank_traces(str(tmp_path))
    merged = tl.merge_traces(by_rank)
    tl.validate_chrome_trace(merged)
    assert merged["metadata"]["serve_requests"] == 2
    # admit/queue/prefill/3 decode ticks/done per request (the first
    # of the 4 tokens comes from prefill): 7 spans -> 6 links each
    assert merged["metadata"]["serve_flows"] == 2 * 6, merged["metadata"]


def test_status_serving_section(tiny_model):
    """/status grows a serving section once an engine ran: the SLO
    table, occupancy, buckets and the span reconciliation — live over
    HTTP from the stdlib status server."""
    from paddle_tpu import status as status_mod

    eng = _engine(tiny_model)
    h = eng.submit([2, 4, 6, 8], max_new_tokens=3)
    eng.run_until_idle()
    h.result(timeout=5)

    srv = status_mod.start_status_server(port=0)
    try:
        port = status_mod.server_port()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/status", timeout=10) as resp:
            doc = json.loads(resp.read().decode())
    finally:
        status_mod.stop_status_server()
    s = doc["serving"]
    assert s["available"] is True
    assert s["ticks"] >= 1
    assert s["slo"]["requests"]["ok"] >= 1
    assert s["slo"]["ttft"]["p50"] is not None
    assert s["slo"]["latency"]["p99"] is not None
    assert s["slo"]["batch_occupancy"] is not None
    assert abs(sum(s["buckets"].values()) - s["wall_seconds"]) < 1e-6
    assert s["top_badput"] is not None
    assert s["reconciliation"]["verdict"] == "within_bound"
    # how often a tick went out before the last one was read, and why not
    assert s["pipeline"] == {
        "decode_ticks": 2, "ticks_ahead": 1, "ahead_share": 0.5,
        "prefills": 1, "prefills_ahead": 0,
        "drains": {"evict": 0, "error": 0, "stop": 0, "empty": 1}}


def test_disabled_mode_inert(tmp_path):
    """No engine -> no serving plane: the status section reports
    unavailable, nothing journals, and the ledger records nothing when
    the metrics layer is off."""
    assert serving_ledger.status() == {"available": False}
    # flush without persistence configured is a no-op
    assert serving_ledger.flush() is None
    # with the metrics layer off, module-level recording is inert
    from paddle_tpu import monitor

    monitor.enable(False)
    try:
        serving_ledger.add("decode_compute", 1.0)
        serving_ledger.end_tick(1.0)
        serving_ledger.record_request(outcome="ok", latency_s=1.0)
    finally:
        monitor.enable(True)
    assert serving_ledger.status() == {"available": False}
    assert list(tmp_path.iterdir()) == []


def test_predictor_routes_through_serving_engine(tmp_path):
    """The legacy single-request Predictor is a batch-of-one client of
    the serving engine: its runs land on the serving lifecycle (request
    counter, prefill_compute bucket) with its API unchanged."""
    from paddle_tpu.inference import Config, create_predictor

    paddle.enable_static()
    try:
        holder = []
        model_dir = _save_lenet_like(tmp_path, holder)
        pred = create_predictor(Config(model_dir))
        before = serving_ledger.totals()
        x = np.random.RandomState(0).randn(2, 1, 8, 8).astype(np.float32)
        out1 = pred.run([x])[0]
        out2 = pred.run([x])[0]
        np.testing.assert_array_equal(out1, out2)
        after = serving_ledger.totals()
        assert (after["requests"].get("ok", 0)
                - before["requests"].get("ok", 0)) == 2
        assert after["buckets"]["prefill_compute"] > \
            before["buckets"]["prefill_compute"]
        assert after["ticks"] - before["ticks"] == 2
    finally:
        paddle.disable_static()


# -- robustness rider: reaper + admission shedding --------------------------


def _counter_total(name):
    from paddle_tpu import monitor

    fam = monitor.snapshot().get("metrics", {}).get(name, {})
    return sum(float(s.get("value", 0.0)) for s in fam.get("series", []))


def test_failed_thunk_leaks_nothing(tiny_model):
    """A client whose execute thunk raises must not leak its slot (the
    engine keeps serving, the original exception surfaces)."""
    eng = serving.ServingEngine(tiny_model)

    def boom():
        raise ValueError("poisoned thunk")

    h = eng.execute(boom, deadline_s=5.0)
    eng.run_until_idle()
    with pytest.raises(ValueError, match="poisoned thunk"):
        h.result(timeout=1)
    assert not eng.active() and not eng._exec_ready  # nothing held
    assert eng.allocator.used() == 0
    # the engine still serves real work afterwards
    toks = eng.generate([1, 2, 3], max_new_tokens=2)
    assert len(toks) == 2


def test_reaper_reclaims_stale_slot_and_blocks(tiny_model, monkeypatch):
    """An in-flight request whose driving client died keeps holding its
    slot + KV blocks past its SLO deadline: the reaper fails it typed
    and reclaims everything."""
    monkeypatch.setenv("PADDLE_TPU_SERVE_REAP_GRACE_S", "0.05")
    eng = serving.ServingEngine(tiny_model)
    before = _counter_total("serve_reaped_total")
    # admit a generate request, then simulate the orphaned client: its
    # deadline is already far in the past
    h = eng.submit([1, 2, 3, 4], max_new_tokens=8, deadline_s=30.0)
    req = h._req
    with eng._step_lock:
        eng._step_locked()  # admit + prefill: slot + blocks held
    assert req.slot >= 0 and req.blocks
    used_before = eng.allocator.used()
    assert used_before > 0
    req.t_submit -= int(120e9)  # 2 minutes overdue
    with eng._step_lock:
        eng._step_locked()
    assert h.done
    with pytest.raises(paddle.errors.Unavailable, match="reaped"):
        h.result(timeout=1)
    assert req.slot == -1 and not req.blocks
    assert eng.allocator.used() == 0  # KV blocks reclaimed
    assert not eng.active()
    assert _counter_total("serve_reaped_total") == before + 1
    assert serving_ledger.totals()["requests"].get("reaped", 0) == 1
    # reclaimed capacity really is reusable
    assert len(eng.generate([5, 6, 7], max_new_tokens=2)) == 2


def test_reaper_covers_orphaned_executes(tiny_model, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_SERVE_REAP_GRACE_S", "0.05")
    eng = serving.ServingEngine(tiny_model)
    h = eng.execute(lambda: 1, deadline_s=30.0)
    with eng._step_lock:
        eng._step_locked()  # admitted into the claim queue
    assert eng._exec_ready
    h._req.t_submit -= int(120e9)
    with eng._step_lock:
        eng._step_locked()
    assert not eng._exec_ready
    with pytest.raises(paddle.errors.Unavailable, match="reaped"):
        h.result(timeout=1)


def test_reaper_disabled_at_zero_grace(tiny_model, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_SERVE_REAP_GRACE_S", "0")
    eng = serving.ServingEngine(tiny_model)
    h = eng.submit([1, 2, 3], max_new_tokens=4, deadline_s=30.0)
    with eng._step_lock:
        eng._step_locked()
    h._req.t_submit -= int(120e9)
    with eng._step_lock:
        eng._step_locked()
    assert h._req.status != "failed"  # nobody reaped it


def test_admission_sheds_unmeetable_deadline(tiny_model, monkeypatch):
    """A request whose deadline passed while it queued is rejected with
    typed Unavailable + serve_shed_total instead of occupying a slot."""
    monkeypatch.setenv("PADDLE_TPU_SERVE_SHED", "1")
    eng = serving.ServingEngine(tiny_model)
    before = _counter_total("serve_shed_total")
    h = eng.submit([1, 2, 3], max_new_tokens=2, deadline_s=30.0)
    h._req.t_submit -= int(120e9)  # deadline long gone at admission
    eng.run_until_idle()
    assert h.done
    with pytest.raises(paddle.errors.Unavailable, match="shed"):
        h.result(timeout=1)
    assert _counter_total("serve_shed_total") == before + 1
    assert serving_ledger.totals()["requests"].get("shed", 0) == 1
    assert not eng.active() and eng.allocator.used() == 0


def test_admission_shed_uses_service_estimate(tiny_model, monkeypatch):
    """With a learned service EMA, a request whose remaining budget is
    smaller than the minimum service estimate sheds BEFORE wasting a
    slot; a meetable one admits."""
    monkeypatch.setenv("PADDLE_TPU_SERVE_SHED", "1")
    eng = serving.ServingEngine(tiny_model)
    eng._service_ema = 5.0  # "requests take ~5s here"
    tight = eng.submit([1, 2, 3], max_new_tokens=2, deadline_s=0.5)
    eng.run_until_idle()
    with pytest.raises(paddle.errors.Unavailable, match="shed"):
        tight.result(timeout=1)
    loose = eng.submit([1, 2, 3], max_new_tokens=2, deadline_s=60.0)
    eng.run_until_idle()
    assert len(loose.result(timeout=5)) == 2


def test_shedding_disabled_admits_everything(tiny_model, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_SERVE_SHED", "0")
    eng = serving.ServingEngine(tiny_model)
    eng._service_ema = 50.0
    h = eng.submit([1, 2, 3], max_new_tokens=2, deadline_s=0.001)
    eng.run_until_idle()
    assert len(h.result(timeout=5)) == 2  # admitted and served anyway


def test_retirement_teaches_the_service_ema(tiny_model):
    eng = serving.ServingEngine(tiny_model)
    assert eng._service_ema == 0.0
    eng.generate([1, 2, 3], max_new_tokens=2)
    assert eng._service_ema > 0.0


# -- PR 13: cold-start shed seeding + died/respawned replica merge ----------


def test_cold_start_shed_seeded_from_roofline(tiny_model, monkeypatch):
    """Satellite fix: with an empty retirement EMA (cold start / warm
    restart) the shedder's service estimate comes from the installed
    decode roofline — per-tick floor x token budget — instead of
    admitting everything on a zero estimate."""
    monkeypatch.setenv("PADDLE_TPU_SERVE_SHED", "1")
    eng = serving.ServingEngine(tiny_model)
    assert eng._service_ema == 0.0
    # no roofline installed: estimate 0, the tight request is admitted
    h0 = eng.submit([1, 2, 3], max_new_tokens=4, deadline_s=0.001)
    eng.run_until_idle()
    assert len(h0.result(timeout=10)) == 4
    # a warm restart re-installs the roofline before traffic; 10s/tick
    # makes a 4-token budget need ~40s — unmeetable in 0.5s
    serving_ledger.reset()
    eng2 = serving.ServingEngine(tiny_model)
    serving_ledger.set_roofline({"tick_seconds_floor": 10.0,
                                 "predicted_tokens_per_sec": 0.1})
    assert eng2._service_estimate(
        serving.ServeRequest(request_id="x", max_new_tokens=4)) == 40.0
    tight = eng2.submit([1, 2, 3], max_new_tokens=4, deadline_s=0.5)
    eng2.run_until_idle()
    with pytest.raises(paddle.errors.Unavailable, match="shed"):
        tight.result(timeout=1)
    # the retirement EMA, once taught, overrides the roofline seed
    loose = eng2.submit([1, 2, 3], max_new_tokens=2, deadline_s=120.0)
    eng2.run_until_idle()
    loose.result(timeout=10)
    assert 0.0 < eng2._service_ema < 10.0
    h2 = eng2.submit([1, 2, 3], max_new_tokens=4, deadline_s=5.0)
    eng2.run_until_idle()
    assert len(h2.result(timeout=10)) == 4  # admitted on the real EMA


def test_merge_tolerates_died_and_respawned_replicas(tmp_path):
    """Satellite fix: the cross-replica merge must not assume a fixed
    replica count — a replica dead mid-run (short wall) must not
    shrink the tokens/s divisor, a respawned replica's resumed journal
    merges cumulatively, and a stale journal from an earlier run
    sharing the directory is filtered by time (the ranks= fix's
    time-based twin for callers that cannot know the rank set)."""
    import json as _json

    now = 1_700_000_000.0

    def _journal(rank, started, flushed, wall, tokens, ok,
                 resumed=False):
        led = serving_ledger.ServingLedger()
        led.started_unix = started
        doc = led.totals(include_open=False)
        doc.update({"rank": rank, "started_unix": started,
                    "time_unix": flushed, "wall_seconds": wall,
                    "decode_tokens": tokens, "ticks": 10,
                    "requests": {"ok": ok, "failed": 0, "evicted": 0}})
        if resumed:
            doc["resumed_from_journal"] = True
        path = tmp_path / f"serving.rank{rank}.json"
        path.write_text(_json.dumps(doc))
        return doc

    # rank0: full-duration survivor; rank1: respawned replica whose
    # resumed journal spans both incarnations; rank7: a journal from an
    # earlier 8-replica run whose last flush predates this run's start
    _journal(0, started=now, flushed=now + 20.0, wall=10.0,
             tokens=1000, ok=20)
    _journal(1, started=now, flushed=now + 20.0, wall=4.0,
             tokens=300, ok=6, resumed=True)
    _journal(7, started=now - 500.0, flushed=now - 400.0, wall=50.0,
             tokens=9999, ok=99)

    merged = serving_ledger.load_journals(str(tmp_path))
    assert merged["stale_filtered"] == 1
    assert merged["ranks"] == [0, 1]
    assert merged["n_replicas"] == 2 and merged["n_resumed"] == 1
    assert merged["decode_tokens"] == 1300
    assert merged["requests"]["ok"] == 26
    # tokens/s over the LONGEST wall (10s), not the mean (7s): the
    # died-then-respawned replica's short wall must not inflate the rate
    assert abs(merged["tokens_per_sec"] - 1300 / 10.0) < 1e-9
    # the ranks= route (launch.py teardown) filters the same stale file
    merged2 = serving_ledger.load_journals(str(tmp_path), ranks=range(2))
    assert merged2["ranks"] == [0, 1]
    # opting out of the time filter keeps every journal (forensics)
    merged3 = serving_ledger.load_journals(str(tmp_path),
                                           drop_stale=False)
    assert 7 in merged3["ranks"]
