"""The program's spans in the profiler's own trace, and the counters that
read the same intervals.

One ``jax.profiler`` session (CPU) around executor steps of a tiny GPT
program and a few ticks of a tiny serving engine; the ``.xplane.pb`` is
read back with ``ProfileData``, as the benchmark's ``TraceSlice`` does.
``paddle_tpu.profiler`` tracing stays OFF throughout: the spans must reach
the trace without it.
"""
import glob
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor, profiler, serving
from paddle_tpu.serving import ledger

# span -> parent (None: a root), from the table in PERF.md
EXECUTOR_SPANS = {
    "executor/run": None, "executor/prepare": "executor/run",
    "executor/dispatch": "executor/run", "executor/commit": "executor/run",
    "executor/release": "executor/run",
    "executor/fetch": "executor/run"}
ENGINE_SPANS = {
    "engine/step": None, "engine/idle": None, "engine/admit": "engine/step",
    "engine/prefill": "engine/step", "engine/decode_tick": "engine/step",
    "engine/retire": "engine/step", "engine/ledger": "engine/step",
    "tick/grow_blocks": "engine/decode_tick",
    "tick/build_inputs": "engine/decode_tick",
    "tick/put_inputs": ("engine/decode_tick", "engine/prefill"),
    "tick/enqueue": ("engine/decode_tick", "engine/prefill"),
    # the read half of a tick or a prefill: in the tick enqueued behind it,
    # or where what is in flight had to be read first (an eviction)
    "tick/device_sync": ("engine/decode_tick", "engine/admit"),
    "tick/bookkeeping": ("engine/decode_tick", "engine/admit")}
N_STEPS = 3  # the first compiles: two steady-state runs
# what a run that builds its program holds under executor/prepare, in order
BUILD_SPANS = ("executor/build", "build/trace", "build/lower",
               "build/compile", "build/analyze")


def _hist(name):
    h = monitor.default_registry().get(name).labels()
    return h.count, h.sum


def _train_steps():
    from paddle_tpu.framework import Executor, Scope, program_guard
    from paddle_tpu.models.gpt import GPTConfig, build_train_program
    from paddle_tpu.optimizer import Adam

    paddle.enable_static()
    try:
        cfg = GPTConfig(vocab_size=64, n_layer=2, n_head=2, d_model=32,
                        max_seq_len=16)
        main, startup, io = build_train_program(cfg, batch=4, seq=16)
        with program_guard(main, startup):
            Adam(learning_rate=1e-3).minimize(io["loss"])
        scope, exe = Scope(), Executor()
        exe.run(startup, scope=scope)
        r = np.random.RandomState(0)
        feed = {"tokens": r.randint(0, 64, (4, 16)).astype("int64"),
                "labels": r.randint(0, 64, (4, 16)).astype("int64")}
        for _ in range(N_STEPS):
            exe.run(main, feed=feed, fetch_list=[io["loss"]], scope=scope)
        return [c.module_name for c in exe._cache.values()]
    finally:
        paddle.disable_static()


def _serve(model):
    eng = serving.ServingEngine(model, default_slo_s=30.0)
    hs = [eng.submit([5 + i, 3, 9, 1], max_new_tokens=5, request_id=f"r{i}")
          for i in range(3)]
    eng.run_until_idle()
    answers = [h.result(timeout=10) for h in hs]
    # the scheduler thread with nothing to run: engine/idle
    eng.start()
    try:
        time.sleep(0.12)
    finally:
        eng.stop(flush=False)
    assert not eng.running_thread()
    return answers


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Everything once: the session, the work, the parsed host events."""
    import jax
    from jax.profiler import ProfileData

    assert not profiler.tracing_active()
    profiler.clear_events()
    cfg = serving.GPTConfig(vocab_size=128, n_layer=2, n_head=2, d_model=32,
                            max_seq_len=64)
    hist0 = {n: _hist(n) for n in ("executor_run_seconds",
                                   "executor_dispatch_seconds",
                                   "executor_host_seconds")}
    d = str(tmp_path_factory.mktemp("xplane"))
    jax.profiler.start_trace(d)
    try:
        # a replica's boot is inside the session: serve/load, serve/warm
        model = serving.DecodeModel(cfg, max_batch=4, n_blocks=16,
                                    block_size=8, prefill_buckets=[16, 32],
                                    seed=1)
        model.warm(full=True)
        ledger.reset()
        modules = _train_steps()
        answers = _serve(model)
    finally:
        jax.profiler.stop_trace()
    totals = ledger.totals()
    ledger.reset()
    hist1 = {n: _hist(n) for n in hist0}
    path = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))[0]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name.partition("#")[0]
                if name.split("/")[0] in ("executor", "engine", "tick",
                                          "build", "serve"):
                    events.append({"name": name, "t0": e.start_ns,
                                   "t1": e.start_ns + e.duration_ns,
                                   "thread": line.name,
                                   "attrs": dict(e.stats)})
    return {"events": events, "totals": totals, "answers": answers,
            "modules": modules, "model": model,
            "hist": {n: (hist1[n][0] - hist0[n][0], hist1[n][1] - hist0[n][1])
                     for n in hist0},
            "buffer": profiler.get_events()}


def _named(traced, name):
    return [e for e in traced["events"] if e["name"] == name]


@pytest.mark.parametrize("name", sorted({**EXECUTOR_SPANS, **ENGINE_SPANS}))
def test_span_is_in_the_profilers_trace_inside_its_parent(traced, name):
    mine = _named(traced, name)
    assert mine, f"no {name} among {sorted({e['name'] for e in traced['events']})}"
    parent = {**EXECUTOR_SPANS, **ENGINE_SPANS}[name]
    if parent is None:
        return
    parents = [p for pn in ([parent] if isinstance(parent, str) else parent)
               for p in _named(traced, pn)]
    for e in mine:
        assert any(p["thread"] == e["thread"] and p["t0"] <= e["t0"]
                   and e["t1"] <= p["t1"] for p in parents), (name, e)


def test_span_counts_follow_the_work(traced):
    # startup + N_STEPS runs, each with all four phases
    for name in EXECUTOR_SPANS:
        assert len(_named(traced, name)) == 1 + N_STEPS, name
    # 3 requests of 5 tokens: 3 prefills, 4 decode ticks (continuous
    # batch), each but the first enqueued before the one before it was
    # read, and a fifth span that only reads the last
    assert len(_named(traced, "engine/prefill")) == 3
    ticks = _named(traced, "engine/decode_tick")
    totals = traced["totals"]
    assert len(ticks) - 1 == totals["decode_ticks"] == 4
    assert totals["ticks_ahead"] == 3
    assert (totals["prefills"], totals["prefills_ahead"]) == (3, 0)
    assert totals["pipeline_drains"] == {
        "evict": 0, "error": 0, "stop": 0, "empty": 1}
    # the first tick reads the three prefills enqueued before it (a sync
    # each, no bookkeeping), every later span the tick before it
    for name, n in (("tick/device_sync", 3 + 4), ("tick/bookkeeping", 4)):
        assert len([e for e in _named(traced, name) if any(
            t["t0"] <= e["t0"] and e["t1"] <= t["t1"] for t in ticks)]) == n
    assert not [e for e in _named(traced, "tick/device_sync") if any(
        p["t0"] <= e["t0"] and e["t1"] <= p["t1"]
        for p in _named(traced, "engine/prefill"))]
    # the budget of the issue: at most 12 span entries a decode tick
    # (the last step that ticked: every prefill is behind it)
    step = [s for s in _named(traced, "engine/step")
            if any(s["t0"] <= t["t0"] and t["t1"] <= s["t1"] for t in ticks)][-1]
    inside = [e["name"] for e in traced["events"] if e["thread"] == step["thread"]
              and step["t0"] <= e["t0"] and e["t1"] <= step["t1"]]
    assert "engine/prefill" not in inside and len(inside) <= 12, sorted(inside)


def test_span_attributes(traced):
    assert [e["attrs"]["step"] for e in _named(traced, "executor/run")] == \
        list(range(1 + N_STEPS))
    assert {e["attrs"]["program"] for e in _named(traced, "executor/dispatch")} \
        == {"jit_startup", "jit_train_step"}
    assert set(traced["modules"]) == {"jit_startup", "jit_train_step"}
    pre = _named(traced, "engine/prefill")
    assert {e["attrs"]["request_id"] for e in pre} == {"r0", "r1", "r2"}
    assert {e["attrs"]["bucket"] for e in pre} == {16}
    assert {e["attrs"]["prompt_len"] for e in pre} == {4}
    ticks = sorted(_named(traced, "engine/decode_tick"), key=lambda e: e["t0"])
    numbers = [e["attrs"]["tick"] for e in ticks]
    assert numbers == list(range(numbers[0], numbers[0] + 5))
    assert [e["attrs"]["slots"] for e in ticks] == [3, 3, 3, 3, 0]
    admits = _named(traced, "engine/admit")
    assert sum(e["attrs"]["admitted"] for e in admits) == 3
    assert all("queued" in e["attrs"] for e in admits)
    assert all(e["attrs"]["queued"] == 0 for e in _named(traced, "engine/idle"))


def _inside(traced, outer):
    return sorted((e for e in traced["events"] if e is not outer
                   and e["thread"] == outer["thread"]
                   and outer["t0"] <= e["t0"] and e["t1"] <= outer["t1"]),
                  key=lambda e: e["t0"])


@pytest.mark.parametrize("step", range(1 + N_STEPS))
def test_a_run_that_builds_says_so_and_a_hit_holds_no_build_span(traced, step):
    run = next(e for e in _named(traced, "executor/run")
               if e["attrs"]["step"] == step)
    inside = _inside(traced, run)
    built = [e for e in inside if e["name"] in BUILD_SPANS]
    if step >= 2:  # a hit in the executor's cache
        assert built == [] and "compiled" not in run["attrs"]
        return
    # startup (step 0) and the first train step: one of each, in order,
    # all under executor/prepare, capture's after executor/build closed
    assert str(run["attrs"]["compiled"]) in ("True", "1")
    assert tuple(e["name"] for e in built) == BUILD_SPANS
    prepare = next(e for e in inside if e["name"] == "executor/prepare")
    assert all(prepare["t0"] <= e["t0"] and e["t1"] <= prepare["t1"]
               for e in built)
    assert all(a["t1"] <= b["t0"] for a, b in zip(built, built[1:]))
    program = ("jit_startup", "jit_train_step")[step]
    assert {e["attrs"]["program"] for e in built} == {program}
    assert len({e["attrs"]["key"] for e in built}) == 1
    assert built[3]["attrs"]["cache"] in ("hit", "miss", "off")


def test_serving_boot_spans_and_status(traced):
    import json
    import urllib.request

    from paddle_tpu import status

    model = traced["model"]
    loads = {e["attrs"]["what"]: e for e in _named(traced, "serve/load")}
    assert set(loads) == {"params", "kv_pool"}  # no conv layer: no state pool
    assert loads["params"]["attrs"]["param_bytes"] == sum(
        int(a.nbytes) for a in model.params.values())
    assert {e["attrs"]["pool_bytes"] for e in loads.values()} == {
        model.pool_bytes()} == {int(np.prod(model.pool_shape())) * 4}
    warm, = _named(traced, "serve/warm")
    assert warm["attrs"]["programs"] == 3
    built = [e for e in _inside(traced, warm) if e["name"] in BUILD_SPANS]
    assert [e["name"] for e in built] == list(BUILD_SPANS[1:]) * 3
    assert [e["attrs"]["program"] for e in built[::4]] == [
        "jit_decode_tick", "jit_prefill_16", "jit_prefill_32"]
    assert all(model.insights[k].build_s["compile"] > 0 and model.insights[k].cache
               for k in ("decode", "prefill@16", "prefill@32"))
    boot = status.boot()
    assert boot["serve_warm_seconds"] >= (warm["t1"] - warm["t0"]) / 1e9 * 0.9
    # set once when the package was imported (a test that resets the
    # registry since has zeroed it: the key is what /status promises)
    assert boot["serve_load_seconds"] > 0 and boot["import_seconds"] >= 0
    assert boot["builds"]["compile"] >= 5 and boot["build_seconds"]["trace"] > 0
    srv = status.start_status_server(port=0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.server_port}/status", timeout=10) as r:
            doc = json.load(r)
    finally:
        status.stop_status_server()
    assert doc["boot"]["serve_warm_seconds"] == boot["serve_warm_seconds"]
    assert set(doc["boot"]["compile_cache"]) == {
        "requests", "hits", "misses", "retrieval_s"}


def test_nothing_recorded_with_tracing_off(traced):
    """A profiler session does not switch the program's own buffer on."""
    assert traced["buffer"] == []
    assert all(a["ok"] if isinstance(a, dict) else len(a) == 5
               for a in traced["answers"])


def test_executor_counters_are_the_spans_intervals(traced):
    runs, run_s = traced["hist"]["executor_run_seconds"]
    disp, disp_s = traced["hist"]["executor_dispatch_seconds"]
    host, host_s = traced["hist"]["executor_host_seconds"]
    assert runs == disp == host == N_STEPS - 1  # the compiling runs are not binned
    assert disp_s > 0 and host_s > 0
    assert abs(disp_s + host_s - run_s) <= 1e-9 * max(1.0, run_s)
    # counters (perf_counter) and spans (profiler clock) see the same thing
    steady = sorted(_named(traced, "executor/dispatch"), key=lambda e: e["t0"])[2:]
    span_s = sum(e["t1"] - e["t0"] for e in steady) / 1e9
    assert abs(span_s - disp_s) <= 0.1 * disp_s + 2e-4


def test_ledger_tick_counters_are_the_spans_intervals(traced):
    t = traced["totals"]
    assert t["decode_ticks"] == 4 and t["ticks"] >= t["decode_ticks"]
    assert 0 < t["tick_sync_s"] < t["tick_wall_s"]
    tick_s = sum(e["t1"] - e["t0"] for e in _named(traced, "engine/decode_tick")) / 1e9
    ticks = _named(traced, "engine/decode_tick")
    sync_s = sum(e["t1"] - e["t0"] for e in _named(traced, "tick/device_sync")
                 if any(p["t0"] <= e["t0"] and e["t1"] <= p["t1"] for p in ticks)) / 1e9
    assert abs(tick_s - t["tick_wall_s"]) <= 0.1 * tick_s + 2e-4
    assert abs(sync_s - t["tick_sync_s"]) <= 0.1 * sync_s + 2e-4


def test_ledger_keeps_inter_token_gaps(traced):
    t = traced["totals"]
    # 3 requests x 5 tokens: 4 gaps each, all positive, and gone after reset
    assert t["itl_gaps_seen"] == len(t["itl_gaps_s"]) == 12
    assert all(g > 0 for g in t["itl_gaps_s"])
    assert ledger.totals()["itl_gaps_s"] == [] and ledger.totals()["decode_ticks"] == 0


def test_gap_sample_is_bounded_and_left_out_of_the_journal(tmp_path):
    led = ledger.ServingLedger()
    led.note_token_gaps([0.001] * (ledger._ITL_SAMPLE + 10))
    doc = led.totals()
    assert len(doc["itl_gaps_s"]) == ledger._ITL_SAMPLE
    assert doc["itl_gaps_seen"] == ledger._ITL_SAMPLE + 10
    ledger.reset()
    ledger.note_token_gaps([0.5])
    ledger.note_decode_tick(0.25, 0.125)
    import json

    with open(ledger.flush(str(tmp_path / "serving.rank0.json"))) as f:
        journal = json.load(f)
    ledger.reset()
    assert "itl_gaps_s" not in journal and "itl_gaps_seen" not in journal
    assert (journal["decode_ticks"], journal["tick_wall_s"], journal["tick_sync_s"]) == (1, 0.25, 0.125)
    merged = ledger.merge_ledgers([journal, journal])
    assert (merged["decode_ticks"], merged["tick_wall_s"]) == (2, 0.5)


def test_span_with_tracing_on_keeps_attributes_and_seconds():
    profiler.start_profiler("All")
    try:
        with profiler.span("engine/admit", cat="engine", queued=2) as sp:
            sp.set(admitted=1)
        ev = profiler.get_events()[-1]
    finally:
        profiler.stop_profiler(print_table=False)
        profiler.clear_events()
    assert ev["name"] == "engine/admit" and ev["attrs"] == {"queued": 2, "admitted": 1}
    assert sp.seconds >= 0 and abs(ev["dur"] - sp.seconds * 1e6) < 1e-6
    chrome = profiler._chrome_trace([ev])["traceEvents"][-1]
    assert chrome["args"]["queued"] == 2 and chrome["args"]["admitted"] == 1


# -- a model whose layers are of three kinds (tests/test_lfm2_serving.py) ----

LFM2_SCOPES = {
    "layer_conv_swiglu": ("conv/in_proj", "conv/mix", "conv/state_write", "conv/out_proj", "mlp"),
    "layer_attn_moe": ("attn/qk_norm", "attn/rope", "attn/kv_write", "moe/route", "moe/experts"),
    "layer_conv_moe": ("conv/in_proj", "conv/mix", "conv/state_write", "conv/out_proj", "moe/route",
                       "moe/experts"),
}


@pytest.fixture(scope="module")
def lfm2_programs():
    """op names of a tiny three-kind model's decode tick and prefill, as
    compiled here (the scopes are the tracer's: the same on every backend)."""
    import re
    import sys

    from test_lfm2_serving import tiny_cfg, tiny_model

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
    import serve_compile_report as report

    dm = tiny_model(tiny_cfg("kernel"))
    return dm, {name: set(re.findall(r'op_name="([^"]*)"', jit_fn.lower(*args).compile().as_text()))
                for name, (jit_fn, args) in report.serving_programs(dm).items()}


@pytest.mark.parametrize("program,attends", [("decode_tick", "attn/paged"), ("prefill_32", "attn/scores")])
@pytest.mark.parametrize("layer", sorted(LFM2_SCOPES))
def test_layers_of_three_kinds_carry_their_names_and_scopes(lfm2_programs, program, attends, layer):
    """One inner jit a KIND of layer, named by it, so that a trace tells
    conv + dense, attention + experts and conv + experts apart; under
    each, the scopes of what that kind computes and no other kind's."""
    ops = lfm2_programs[1][program]
    own = LFM2_SCOPES[layer] + ((attends,) if "attn" in layer else ())
    for scope in own:
        assert any(f"jit({program})/jit({layer})/{scope}/" in o for o in ops), (scope, sorted(ops)[:10])
    others = {s for v in LFM2_SCOPES.values() for s in v} | {"attn/paged", "attn/scores"}
    for scope in others - set(own):
        assert not any(f"jit({layer})/{scope}/" in o for o in ops), scope
    assert not any("/jit(layer)/" in o for o in ops)  # that name is a one-kind model's


def test_state_pool_counters_follow_the_work(lfm2_programs):
    """``state_writes``: one a prefill (admission or resume);
    ``state_pool_bytes`` and ``attn_layers``: gauges of the model served;
    the pages counted are the attention layers' alone. In totals(), on
    /status, merged (sums; gauges as the largest), cleared by reset()."""
    from paddle_tpu.serving import ledger

    dm = lfm2_programs[0]
    ledger.reset()
    eng = serving.ServingEngine(dm)
    handles = [eng.submit(list(range(1, n + 1)), max_new_tokens=4) for n in (14, 3)]
    eng.run_until_idle()
    assert all(len(h.result(timeout=5)) == 4 for h in handles)
    doc = ledger.totals()
    nbytes = 3 * 2 * 4 * 1024 * 4  # conv layers x gated inputs x slots x hidden x float32
    assert (doc["state_writes"], doc["state_pool_bytes"], doc["attn_layers"]) == (2, nbytes, 1)
    assert doc["attn_pages_read"] == (1 + 1 + 2) + 3  # a layer's pages, as tests/test_paged_attention.py
    st = ledger.status()
    assert st["state_pool"] == {"bytes": nbytes, "slot_writes": 2} and st["attention"]["layers"] == 1
    merged = ledger.merge_ledgers([doc, doc])
    assert (merged["state_writes"], merged["state_pool_bytes"], merged["attn_layers"]) == (4, nbytes, 1)
    # the routing counters ride behind the tokens, over the three expert layers
    assert doc["moe_assignments"] == doc["decode_tokens"] * 3 * 2
    ledger.reset()
    doc = ledger.totals()
    assert doc["state_writes"] == doc["state_pool_bytes"] == doc["attn_layers"] == 0
    assert "state_pool" not in ledger.status()
