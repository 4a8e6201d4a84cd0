"""The per-layer readers PR 24 added to the benchmark, each on a small
hand-built normalised trace and ledger document
(``benchmark/testdata/program_spans.json``) against values worked out by
hand, and the manifest with their entries in it."""
import json
import os
import re

import pytest

from benchmark import common, flops, manifest
from benchmark.readers import (idle_in_spans, kernel_events_per_step, kernel_roofline,
                               ledger_itl_ms, ledger_tick_host_ms, module_device_ms,
                               monitor_hist_mean_ms, setup_build_s)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW = ("executor_host_ms", "executor_dispatch_ms", "fwd_passes_per_step", "flash_kernels_roofline",
       "lmhead_ce_kernels_roofline", "decode_program_ms", "tick_host_ms", "idle_in_spans_pct",
       "prefill_program_ms", "engine_itl_p99_ms", "chat_idle_in_spans_pct")


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(ROOT, "benchmark", "testdata", "program_spans.json")) as f:
        return json.load(f)


def _ctx(trace=None, config=None, traffic=None, steps=None, peaks=None):
    ctx = common.Ctx(cell={"name": "hand-built", "chips": 1, "config": config or {}, "traffic": traffic or {}},
                     seed=0, seconds=1.0, trace=True, rehearse=False, devices=[], peaks=peaks, t0=0.0)
    ctx.norm_trace = trace
    if steps:
        ctx.trace_facts["steps"] = steps
    return ctx


def _args(metric):
    return manifest.layer_metric(metric).get("args", {})


def test_manifest_has_no_problems_and_the_new_metrics():
    man = manifest.load()
    assert manifest.problems(man) == []
    by_name = {m["name"]: m for m in man["per_layer"]}
    names = [m["name"] for m in man["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == list(NEW)  # present, contiguous, in order
    for name in NEW:
        spec = manifest.layer_metric(name)
        assert spec["workloads"] == by_name[name]["workloads"]
        assert os.path.exists(os.path.join(ROOT, "benchmark", "readers", spec["reader"] + ".py"))
    assert set(by_name["executor_host_ms"]["workloads"]) == {"gpt2s-train-1k", "gpt2xl-train-fsdp4"}
    assert by_name["idle_in_spans_pct"]["source"] == "program_span"


def test_idle_in_spans_cuts_gaps_at_span_edges(doc):
    ctx = _ctx(doc["traces"]["ticks"])
    got = idle_in_spans.read(ctx, _args("idle_in_spans_pct"))
    want = doc["expected"]
    assert got == pytest.approx(want["idle_in_spans_pct"], rel=1e-9)
    table = ctx.results["idle_by_span"]
    assert table["idle_s"] == pytest.approx(want["idle_s"], rel=1e-9)
    assert table["parents"] == want["idle_parents"]
    assert table["by_span_s"] == pytest.approx(want["idle_by_span_s"], rel=1e-9)
    assert sum(table["by_span_s"].values()) == pytest.approx(table["idle_s"], rel=1e-9)
    # and the spans themselves: what the ledger's tick_wall_s / tick_sync_s are checked against
    means = ctx.results["program_span_ms"]
    assert means["engine/decode_tick"] == {"count": 2, "mean_ms": pytest.approx(0.092)}
    assert means["tick/device_sync"] == {"count": 2, "mean_ms": pytest.approx(0.0795)}
    assert _args("chat_idle_in_spans_pct") == _args("idle_in_spans_pct")


def test_idle_in_spans_reads_names_with_attributes_and_finds_nothing_without_spans(doc):
    tr = json.loads(json.dumps(doc["traces"]["ticks"]))
    for r in tr["host"]:
        if r[0] == "engine/decode_tick":
            r[0] = "engine/decode_tick#tick=7,slots=12#"
    assert idle_in_spans.read(_ctx(tr), {}) == pytest.approx(doc["expected"]["idle_in_spans_pct"])
    tr["host"] = [r for r in tr["host"] if not re.match("(engine|tick)/", r[0])]
    assert idle_in_spans.read(_ctx(tr), {}) is None  # the parent commit's trace
    assert idle_in_spans.read(_ctx(None), {}) is None


@pytest.mark.parametrize("metric,trace,key", [("decode_program_ms", "ticks", "decode_program_ms"),
                                              ("decode_program_ms", "prefills", None),
                                              ("prefill_program_ms", "prefills", "prefill_program_ms")])
def test_module_device_ms_finds_programs_by_name(doc, metric, trace, key):
    ctx = _ctx(doc["traces"][trace])
    got = module_device_ms.read(ctx, _args(metric))
    if key is None:  # one decode program among the prefills
        assert got == pytest.approx(0.08)
        assert list(ctx.results["module_device_ms"]) == ["jit_decode_tick"]
        return
    assert got == pytest.approx(doc["expected"][key])
    if metric == "prefill_program_ms":  # per bucket in the report; jit_prefill (no bucket) is not one
        per = {k: v["median_ms"] for k, v in ctx.results["module_device_ms"].items()}
        assert per == pytest.approx(doc["expected"]["prefill_by_bucket_ms"])


def test_module_device_ms_finds_nothing_in_a_trace_of_jit_fn(doc):
    tr = json.loads(json.dumps(doc["traces"]["prefills"]))
    for evs in tr["modules"].values():
        for r in evs:
            r[0] = "jit_fn"
    assert module_device_ms.read(_ctx(tr), _args("prefill_program_ms")) is None
    assert module_device_ms.read(_ctx(tr), _args("decode_program_ms")) is None


def test_kernel_events_per_step_counts_named_kernels(doc):
    ctx = _ctx(doc["traces"]["train"], config={"n_layer": doc["train_n_layer"]}, steps=doc["train_steps"])
    got = kernel_events_per_step.read(ctx, _args("fwd_passes_per_step"))
    assert got == doc["expected"]["fwd_passes_per_step"]
    assert ctx.results["kernel_events_per_step"] == doc["expected"]["kernel_events_per_step"]
    # the parent's names (fn, jvp__, transpose_jvp___): nothing to read, no error
    tr = json.loads(json.dumps(doc["traces"]["train"]))
    for r in tr["devices"]["/device:TPU:0"]:
        r[0] = re.sub(r"^(jvp_)?(flash|lmhead)\w*", "fn", r[0])
    assert kernel_events_per_step.read(_ctx(tr, config={"n_layer": 2}, steps=2), _args("fwd_passes_per_step")) is None
    assert kernel_events_per_step.read(_ctx(tr, config={"n_layer": 2}), _args("fwd_passes_per_step")) is None


@pytest.mark.parametrize("metric,family,transposed", [
    ("flash_kernels_roofline", "flash_family", "transpose_jvp_flash_dq__.4"),
    ("lmhead_ce_kernels_roofline", "lmhead_ce_family", "transpose_jvp_lmhead_ce_dw__.4")])
def test_name_patterns_select_the_family_for_the_existing_roofline_reader(doc, metric, family, transposed):
    cell = manifest.cell(manifest.load(), "gpt2s-train-1k")
    peaks = flops.load_peaks("TPU v5 lite")
    ctx = _ctx(doc["traces"]["train"], config=cell["config"], traffic=cell["traffic"],
               steps=doc["train_steps"], peaks=peaks)
    args = _args(metric)
    got = kernel_roofline.read(ctx, args)
    facts = ctx.results["rooflines"][args["work"]]
    want = doc["expected"][family]
    assert facts["events_per_step"] == want["events_per_step"]
    assert facts["device_ms_per_step"] == pytest.approx(want["device_ms_per_step"], rel=1e-9)
    assert got == pytest.approx(100.0 * facts["least_ms_per_step"] / facts["device_ms_per_step"])
    # anchored on the instruction's own name: never an operand, never the text
    rx = re.compile(args["pattern"])
    assert not rx.search("fusion.7") and not rx.search("%flash_fwd.1 = bf16[4] custom-call()")
    assert not rx.search("fused_adam.3") and rx.search(transposed)


def test_ledger_readers_on_the_ledger_document(doc, monkeypatch):
    from paddle_tpu.serving import ledger

    monkeypatch.setattr(ledger, "totals", lambda: dict(doc["ledger"]))
    ctx = _ctx()
    assert ledger_tick_host_ms.read(ctx, {}) == pytest.approx(doc["expected"]["tick_host_ms"])
    assert ctx.results["tick_ms"]["device_sync"] == pytest.approx(doc["expected"]["tick_sync_ms"])
    assert ctx.results["tick_ms"]["wall"] == pytest.approx(
        doc["expected"]["tick_host_ms"] + doc["expected"]["tick_sync_ms"])
    assert ledger_itl_ms.read(ctx, _args("engine_itl_p99_ms")) == pytest.approx(doc["expected"]["engine_itl_p99_ms"])
    assert ledger_itl_ms.read(ctx, {"q": 50}) == pytest.approx(doc["expected"]["engine_itl_p50_ms"])
    itl = ctx.results["engine_itl_ms"]
    assert (itl["gaps"], itl["seen"], itl["truncated"]) == (200, 200, False)
    monkeypatch.setattr(ledger, "totals", lambda: dict(doc["ledger"], itl_gaps_seen=9000))  # the sample dropped some
    ledger_itl_ms.read(ctx, {"q": 99})
    assert (ctx.results["engine_itl_ms"]["seen"], ctx.results["engine_itl_ms"]["truncated"]) == (9000, True)


def test_ledger_readers_find_nothing_in_the_parents_ledger(monkeypatch):
    from paddle_tpu.serving import ledger

    monkeypatch.setattr(ledger, "totals", lambda: {"ticks": 5, "decode_tokens": 60})  # PR 23's keys
    assert ledger_tick_host_ms.read(_ctx(), {}) is None
    assert ledger_itl_ms.read(_ctx(), {"q": 99}) is None
    monkeypatch.undo()
    ledger.reset()  # the real ledger before any tick
    assert ledger_tick_host_ms.read(_ctx(), {}) is None
    assert ledger_itl_ms.read(_ctx(), {"q": 99}) is None


def test_monitor_hist_mean_ms(monkeypatch):
    from paddle_tpu import monitor

    reg = monitor.MetricsRegistry()
    h = reg.histogram("executor_host_seconds", "test")
    reg.histogram("executor_dispatch_seconds", "test")  # registered, nothing observed
    for v in (0.004, 0.006):
        h.observe(v)
    monkeypatch.setattr(monitor, "default_registry", lambda: reg)
    ctx = _ctx()
    assert monitor_hist_mean_ms.read(ctx, _args("executor_host_ms")) == pytest.approx(5.0)
    assert ctx.results["monitor_hists"]["executor_host_seconds"]["count"] == 2
    assert ctx.results["monitor_hists"]["executor_host_seconds"]["le"] == {"0.005": 1, "0.01": 1}
    assert "program_span_ms" not in ctx.results  # no trace, no span means
    assert monitor_hist_mean_ms.read(ctx, _args("executor_dispatch_ms")) is None
    assert monitor_hist_mean_ms.read(ctx, {"family": "no_such_family_in_the_parent"}) is None


def test_the_executor_registers_the_families_the_metrics_name():
    import paddle_tpu.framework.executor  # noqa: F401  (registers at import)
    from paddle_tpu import monitor

    for metric in ("executor_host_ms", "executor_dispatch_ms"):
        assert monitor.default_registry().get(_args(metric)["family"]) is not None


# -- setup_s split by the program's build log (PR 36) ------------------------

SETUP = ("setup_trace_s", "setup_lower_s", "setup_compile_s", "setup_rest_s")


def _rec(fun_name, stage, t_end, self_s, **more):
    from paddle_tpu.framework import xla_insight

    return {"fun_name": fun_name, "program": xla_insight.program_of(fun_name), "stage": stage,
            "t_end": t_end, "seconds": self_s, "self_s": self_s, "count": 1, **more}


def _hand_made_log():
    # a process that starts at t = 100 on perf_counter's clock and opens its
    # window at 130; what is built after that is the reference check's
    records = [_rec("train_step", "trace", 110.0, 4.0), _rec("jit(train_step)", "lower", 112.0, 2.0),
               _rec("jit(train_step)", "compile", 115.0, 3.0, cache="hit"),
               _rec("jit(copy)", "compile", 116.0, 0.5, cache="miss", count=5),
               _rec("jit_train_step", "compile", 117.0, 1.0, cache="miss"),  # a fallback to plain jit
               _rec("mean_nll", "trace", 131.0, 7.0), _rec("jit(mean_nll)", "compile", 140.0, 9.0, cache="miss")]
    return {"records": records, "cache": {"requests": 8, "hits": 1, "misses": 7, "retrieval_s": 0.2},
            "dropped": {"trace": {"seconds": 0.25, "count": 3}, "lower": {"seconds": 0.0, "count": 0},
                        "compile": {"seconds": 0.0, "count": 0}}}


def test_manifest_takes_the_setup_metrics_in_every_cell_without_a_list():
    man = manifest.load()
    assert manifest.problems(man) == []
    names = [m["name"] for m in man["per_layer"]]
    at = names.index(SETUP[0])  # one run of four; the cells added since (PR 50) stand behind it
    assert names[at:at + 4] == list(SETUP)
    for m in man["per_layer"][at:at + 4]:
        spec = manifest.layer_metric(m["name"])
        assert "workloads" not in m and "workloads" not in spec
        assert (m["moves"], m["unit"], m["source"]) == ("setup_s", "s", "program_counter")
        assert spec["reader"] == "setup_build_s" and spec["args"]["stage"] == m["name"][6:-2]
    for w in man["workloads"]:
        own = [m["name"] for m in manifest.cell(man, w["name"])["per_layer"]]
        assert [n for n in own if n in SETUP] == list(SETUP)
    # the only per-layer metrics that move the one metric every cell reports
    assert {m["name"] for m in man["per_layer"] if m["moves"] == "setup_s"} == set(SETUP)


@pytest.mark.parametrize("metric,want", [("setup_trace_s", 4.25), ("setup_lower_s", 2.0),
                                         ("setup_compile_s", 4.5), ("setup_rest_s", 19.25)])
def test_setup_build_reader_cuts_at_the_windows_opening(monkeypatch, metric, want):
    from paddle_tpu.framework import xla_insight

    monkeypatch.setattr(xla_insight, "build_log", _hand_made_log)
    monkeypatch.setattr(xla_insight, "recent", lambda: [
        xla_insight.ProgramInsight(key_hash="k", program="jit_train_step", cache="hit",
                                   build_s={"analyze": 0.75})])
    ctx = _ctx()
    ctx.t0 = 100.0
    ctx.results["setup_s"] = 30.0
    assert setup_build_s.read(ctx, _args(metric)) == pytest.approx(want)
    tl = ctx.results["setup_timeline"]
    # the three and the rest are setup_s; what came after the opening is out
    assert sum(tl["stage_s"].values()) + tl["rest_s"] == pytest.approx(30.0) and not tl["clamped"]
    assert (tl["records_before_opening"], tl["records_after"]) == (5, 2) and "mean_nll" not in tl["by_name"]
    # names joined across the jit wrapper, whichever way JAX spells it
    row = tl["by_name"]["train_step"]
    assert (row["trace"], row["lower"]) == ({"s": 4.0, "n": 1}, {"s": 2.0, "n": 1})
    assert row["compile"] == {"s": 4.0, "n": 2, "cache": {"hit": 1, "miss": 1}}
    # a named program with two compile records is said; a helper compiled once a shape is not
    assert tl["compiled_twice"] == ["train_step"] and tl["by_name"]["copy"]["compile"]["n"] == 5
    assert tl["cache_before_opening"] == {"hit": 1, "miss": 6, "off": 0, "hit_s": 3.0, "miss_s": 1.5, "off_s": 0.0}
    assert tl["programs"] == {"jit_train_step": {"cache": "hit", "analyze_s": 0.75}}
    assert tl["rest_unowned_s"] == pytest.approx(19.25 - 0.75 - (tl["import_s"] or 0.0)
                                                 - (tl["serve_boot_s"]["load"] or 0.0))


def test_setup_build_reader_clamps_the_rest_and_says_so():
    log = _hand_made_log()
    tl = setup_build_s.split(log, t_cut=130.0, setup_s=8.0)  # two threads built at once
    assert tl["rest_s"] == 0.0 and tl["clamped"] and tl["rest_unowned_s"] == 0.0
    assert sum(tl["stage_s"].values()) == pytest.approx(10.75)


def test_setup_build_reader_finds_nothing_in_the_parent(monkeypatch):
    from paddle_tpu.framework import xla_insight

    ctx = _ctx()
    assert setup_build_s.read(ctx, {"stage": "trace"}) is None  # no setup_s yet
    ctx.results["setup_s"] = 30.0
    monkeypatch.delattr(xla_insight, "build_log")  # a program from before PR 36
    for metric in SETUP:
        assert setup_build_s.read(ctx, _args(metric)) is None
    assert "setup_timeline" not in ctx.results
