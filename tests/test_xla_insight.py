"""Compiler-side observability (framework/xla_insight.py + tools/xla_report.py).

Coverage the compiler-observability round added: XLA cost/memory capture
on the executor's compile path (CPU cost analysis works under
JAX_PLATFORMS=cpu), the PADDLE_TPU_XLA_DUMP_DIR artifact round trip,
the xla_report CI smoke, the model footprint accounting, and the
declared-env-var registry that generates/checks README's table.
"""
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import flags, monitor
from paddle_tpu.framework import Executor, Program, Scope, program_guard
from paddle_tpu.framework import xla_insight

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOLS = os.path.join(_REPO, "tools")


def _import_xla_report():
    sys.path.insert(0, _TOOLS)
    try:
        import xla_report
        return xla_report
    finally:
        sys.path.pop(0)


@pytest.fixture(autouse=True)
def _fresh():
    monitor.enable(True)
    monitor.reset_metrics()
    yield
    monitor.enable(True)


def _build_train_program():
    from paddle_tpu import static
    from paddle_tpu.optimizer import SGD

    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = static.data("x", shape=[-1, 8], dtype="float32")
        y = static.data("y", shape=[-1, 1], dtype="float32")
        pred = static.nn.fc(x, size=1)
        loss = static.nn.reduce_mean(
            static.nn.square(static.nn.elementwise_sub(pred, y)))
        SGD(learning_rate=0.05).minimize(loss)
    return main, startup, loss


def _run_steps(main, startup, loss, scope, steps=3):
    exe = Executor()
    exe.run(startup, scope=scope)
    r = np.random.RandomState(0)
    for _ in range(steps):
        out = exe.run(
            main,
            feed={"x": r.rand(16, 8).astype("float32"),
                  "y": r.rand(16, 1).astype("float32")},
            fetch_list=[loss], scope=scope)
    return exe, out


# ---------------------------------------------------------------------------
# cost/memory capture + metrics export
# ---------------------------------------------------------------------------


def test_cost_memory_capture_and_metrics(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_XLA_DUMP_DIR", str(tmp_path))
    paddle.enable_static()
    try:
        main, startup, loss = _build_train_program()
        scope = Scope()
        exe, _ = _run_steps(main, startup, loss, scope)
    finally:
        paddle.disable_static()

    # the startup program and the train step each compiled once
    insights = exe.compiled_insights()
    assert len(insights) >= 2, insights
    rec = max(insights, key=lambda r: r.get("flops") or 0)
    assert rec["schema"] == xla_insight.COST_SCHEMA
    assert rec["flops"] > 0
    assert rec["bytes_accessed"] > 0
    assert rec["peak_bytes"] > 0
    assert rec["n_jaxpr_eqns"] > 0
    assert "loss" in "".join(rec["fetch_names"]) or rec["fetch_names"]

    # cost gauges landed in the PR 1 metrics snapshot, labeled by hash
    snap = monitor.snapshot()
    for name in ("program_flops", "program_peak_bytes",
                 "program_bytes_accessed"):
        series = snap["metrics"][name]["series"]
        assert series, name
        assert all(s["labels"]["program"] for s in series)
        assert any(s["value"] > 0 for s in series), (name, series)

    # artifact round trip: dumped files parse back to the same record
    records = xla_insight.load_dump_dir(str(tmp_path))
    assert rec["key_hash"] in records
    loaded = records[rec["key_hash"]]
    assert loaded["flops"] == rec["flops"]
    assert loaded["peak_bytes"] == rec["peak_bytes"]
    base = tmp_path / f"program.{rec['key_hash']}"
    jaxpr_text = (base.parent / (base.name + ".jaxpr")).read_text()
    assert "lambda" in jaxpr_text  # a real jaxpr, not an empty stub
    hlo_text = (base.parent / (base.name + ".hlo")).read_text()
    assert "HloModule" in hlo_text or "ENTRY" in hlo_text
    assert loaded["artifacts"]["hlo"].endswith(".hlo")


def test_capture_disabled_by_env(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_XLA_INSIGHT", "0")
    paddle.enable_static()
    try:
        main, startup, loss = _build_train_program()
        scope = Scope()
        exe, out = _run_steps(main, startup, loss, scope)
    finally:
        paddle.disable_static()
    assert np.isfinite(out[0])  # plain jit dispatch still trains
    assert exe.compiled_insights() == []


def test_cached_entry_not_recaptured():
    paddle.enable_static()
    try:
        main, startup, loss = _build_train_program()
        scope = Scope()
        exe, _ = _run_steps(main, startup, loss, scope, steps=4)
    finally:
        paddle.disable_static()
    snap = monitor.snapshot()
    captures = snap["metrics"]["xla_insight_captures_total"]["series"]
    ok = sum(s["value"] for s in captures if s["labels"]["result"] == "ok")
    # one capture per compiled entry (startup + train), not per run
    assert ok == len(exe.compiled_insights())


# ---------------------------------------------------------------------------
# cache-size gauge consolidation (satellite fix)
# ---------------------------------------------------------------------------


def test_cache_size_views_agree():
    paddle.enable_static()
    try:
        main, startup, loss = _build_train_program()
        scope = Scope()
        _run_steps(main, startup, loss, scope)
    finally:
        paddle.disable_static()
    gauge = monitor.default_registry().get("executor_cache_size")
    assert gauge is not None
    assert gauge.value == monitor.stat_get("executor_cache_size")
    assert gauge.value >= 1


# ---------------------------------------------------------------------------
# footprint accounting
# ---------------------------------------------------------------------------


def test_program_footprint_static():
    from paddle_tpu import static
    from paddle_tpu.optimizer import Adam

    paddle.enable_static()
    try:
        main, startup = Program(), Program()
        with program_guard(main, startup):
            x = static.data("x", shape=[-1, 8], dtype="float32")
            y = static.data("y", shape=[-1, 1], dtype="float32")
            pred = static.nn.fc(x, size=1)
            loss = static.nn.reduce_mean(
                static.nn.square(static.nn.elementwise_sub(pred, y)))
            Adam(learning_rate=0.01).minimize(loss)
        scope = Scope()
        _run_steps(main, startup, loss, scope)
    finally:
        paddle.disable_static()

    fp = xla_insight.program_footprint(main, scope)
    assert fp["total_param_bytes"] > 0
    # Adam moments live in scope after a step and fold into the owning layer
    assert fp["total_opt_state_bytes"] > 0
    fc = [row for prefix, row in fp["layers"].items()
          if row["param_bytes"] > 0]
    assert fc and any(row["opt_state_bytes"] > 0 for row in fc), fp["layers"]
    assert fp["total_bytes"] == (fp["total_param_bytes"]
                                 + fp["total_opt_state_bytes"]
                                 + fp["total_other_bytes"])
    # totals rode into the stat gauges (the run-report hook)
    assert monitor.stat_get("model_param_bytes") == fp["total_param_bytes"]


def test_model_footprint_dygraph():
    from paddle_tpu import nn
    from paddle_tpu.hapi import Model
    from paddle_tpu.io import TensorDataset
    from paddle_tpu.optimizer import Adam

    net = nn.Sequential(nn.Linear(8, 4), nn.ReLU(), nn.Linear(4, 1))
    model = Model(net)
    model.prepare(optimizer=Adam(learning_rate=0.01,
                                 parameters=net.parameters()),
                  loss=nn.MSELoss())
    r = np.random.RandomState(0)
    ds = TensorDataset([r.rand(16, 8).astype("float32"),
                        r.rand(16, 1).astype("float32")])
    model.fit(ds, batch_size=8, epochs=1, verbose=0)

    fp = model.footprint()
    assert fp["total_param_bytes"] == 4 * ((8 * 4 + 4) + (4 * 1 + 1))
    assert fp["total_opt_state_bytes"] > 0  # Adam moments exist post-fit
    assert any(row["opt_state_bytes"] > 0 for row in fp["layers"].values())
    summary = model.summary()
    assert summary["param_bytes"] == fp["total_param_bytes"]
    assert summary["opt_state_bytes"] == fp["total_opt_state_bytes"]


# ---------------------------------------------------------------------------
# xla_report tool + env-var registry
# ---------------------------------------------------------------------------


def test_xla_report_self_test(tmp_path):
    xla_report = _import_xla_report()
    report = xla_report.self_test(tmpdir=str(tmp_path), verbose=False)
    assert report["n_programs"] == 1
    assert report["utilization"]["utilization"] == pytest.approx(0.1)


def test_xla_report_on_executor_dump(tmp_path, monkeypatch):
    """The report CLI path over a real executor dump directory."""
    monkeypatch.setenv("PADDLE_TPU_XLA_DUMP_DIR", str(tmp_path))
    paddle.enable_static()
    try:
        main, startup, loss = _build_train_program()
        _run_steps(main, startup, loss, Scope())
    finally:
        paddle.disable_static()
    xla_report = _import_xla_report()
    report = xla_report.build_report(str(tmp_path))
    assert report["n_programs"] >= 2
    assert report["total_flops"] > 0
    text = xla_report.render_text(report)
    assert "compiled program(s)" in text


def test_xla_report_custom_call_flops_labeling():
    """The raw-speed rider: pallas custom calls (invisible to XLA's
    cost_analysis) are parsed out of the HLO with analytic FLOPs, so
    achieved-MFU attribution does not report the fused lm-head (or
    flash attention) as vanished compute."""
    xla_report = _import_xla_report()
    hlo = """
HloModule jit_fn
ENTRY %main {
  %cc.1 = f32[3,16384]{1,0} custom-call(bf16[16384,768]{1,0} %x, bf16[32768,768]{1,0} %w, s32[1,16384]{1,0} %l), custom_call_target="tpu_custom_call", metadata={op_name="jit(fn)/lmhead_ce/_stats_kernel"}
  %cc.2 = bf16[8,2048,768]{2,1,0} custom-call(bf16[8,2048,768]{2,1,0} %q, bf16[8,2048,768]{2,1,0} %k, bf16[8,2048,768]{2,1,0} %v), custom_call_target="tpu_custom_call"
}
"""
    calls = xla_report.parse_hlo_custom_calls(hlo)
    assert len(calls) == 2
    lm = next(c for c in calls if c["kernel_family"] == "lmhead_ce")
    assert lm["flops_estimate"] == 2 * 16384 * 768 * 32768
    assert lm["target"] == "tpu_custom_call"
    assert "lmhead" in (lm["op_name"] or "")
    att = next(c for c in calls if c["kernel_family"] == "attention")
    assert att["flops_estimate"] == 4 * 8 * 2048 * 2048 * 768
    # the utilization table labels the adjustment
    programs = {"h": {"flops": 1e9, "custom_call_flops": 2e9,
                      "custom_calls": calls}}
    util = xla_report._utilization(
        {"flops_per_step": 1e9, "steps_per_sec": 2.0}, 1e12, programs)
    assert util["custom_call_flops_per_step"] == 2e9
    assert util["flops_per_step_with_custom_calls"] == 3e9
    assert util["achieved_flops_per_sec_with_custom_calls"] == 6e9
    assert util["utilization_with_custom_calls"] == pytest.approx(0.006)


def test_donated_peak_bytes_convention():
    """memory_analysis_bytes: donated_peak_bytes = peak - alias (the
    donation-adjusted live set), degrading to peak when the backend
    reports no aliasing."""
    from paddle_tpu.framework import xla_insight

    class _Mem:
        argument_size_in_bytes = 100
        output_size_in_bytes = 120
        temp_size_in_bytes = 30
        alias_size_in_bytes = 80
        generated_code_size_in_bytes = 1

    class _Exe:
        def memory_analysis(self):
            return _Mem()

    out = xla_insight.memory_analysis_bytes(_Exe())
    assert out["peak_bytes"] == 250
    assert out["donated_peak_bytes"] == 170

    class _MemNoAlias(_Mem):
        alias_size_in_bytes = None

    class _Exe2:
        def memory_analysis(self):
            return _MemNoAlias()

    out2 = xla_insight.memory_analysis_bytes(_Exe2())
    assert out2["donated_peak_bytes"] == out2["peak_bytes"] == 250


def test_env_flag_registry_and_readme():
    defs = flags.env_flag_defs()
    # every scattered observability env var is declared exactly here
    for name in ("PADDLE_TPU_METRICS", "PADDLE_TPU_METRICS_PATH",
                 "PADDLE_TPU_OP_CALLSTACK", "PADDLE_TPU_TRACE",
                 "PADDLE_TPU_TRACE_DIR", "PADDLE_TPU_TRACE_SAMPLE",
                 "PADDLE_TPU_TRACE_MAX_EVENTS", "PADDLE_TPU_WATCHDOG_SECS",
                 "PADDLE_TPU_FLIGHT_CAPACITY", "PADDLE_TPU_XLA_INSIGHT",
                 "PADDLE_TPU_XLA_DUMP_DIR", "PADDLE_TPU_CHECK_NUMERICS"):
        assert name in defs, name
        assert defs[name]["help"], name
    readme = open(os.path.join(_REPO, "README.md")).read()
    assert flags.check_env_docs(readme) == []
    # README's table is the generated one, verbatim (no doc drift)
    assert flags.render_env_table() in readme


def test_env_flag_coercion(monkeypatch):
    assert flags.env_flag("PADDLE_TPU_XLA_INSIGHT") is True
    monkeypatch.setenv("PADDLE_TPU_XLA_INSIGHT", "0")
    assert flags.env_flag("PADDLE_TPU_XLA_INSIGHT") is False
    monkeypatch.setenv("PADDLE_TPU_TRACE_SAMPLE", "0.25")
    assert flags.env_flag("PADDLE_TPU_TRACE_SAMPLE") == 0.25
    monkeypatch.setenv("PADDLE_TPU_FLIGHT_CAPACITY", "64")
    assert flags.env_flag("PADDLE_TPU_FLIGHT_CAPACITY") == 64
    with pytest.raises(KeyError):
        flags.env_flag("PADDLE_TPU_NO_SUCH_FLAG")


def test_obs_report_compile_section(tmp_path):
    """obs_report folds the compiler section in (satellite): covered via
    its self-test elsewhere; here the section builder is checked directly
    on a snapshot carrying program gauges."""
    sys.path.insert(0, _TOOLS)
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    monitor.gauge("program_flops", labelnames=("program",)).labels(
        program="abc123").set(1000.0)
    monitor.gauge("program_peak_bytes", labelnames=("program",)).labels(
        program="abc123").set(2048.0)
    section = obs_report._compile_section(
        monitor.snapshot(),
        {"abc123": {"label": "loss", "flops": 1000.0, "n_jaxpr_eqns": 7}})
    # series from earlier tests survive reset_metrics (zeroed in place),
    # so assert on the row this test planted rather than the count
    assert section["n_programs"] >= 1
    assert section["total_flops"] >= 1000.0
    row = section["programs"]["abc123"]
    assert row["flops"] == 1000.0 and row["peak_bytes"] == 2048.0
    assert row["label"] == "loss" and row["n_jaxpr_eqns"] == 7
    assert "compile" in obs_report.REQUIRED_KEYS


# ---------------------------------------------------------------------------
# the build log (every jit of the process) and a program's own stages
# ---------------------------------------------------------------------------

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"


def _static_steps(steps=3):
    paddle.enable_static()
    try:
        main, startup, loss = _build_train_program()
        return _run_steps(main, startup, loss, Scope(), steps=steps)[0]
    finally:
        paddle.disable_static()


def _slow_to_trace(name):
    """A jit under a name of its own whose trace takes well over the
    millisecond under which a trace record loses its name."""
    import jax
    import jax.numpy as jnp

    def fn(x):
        for i in range(150):
            x = jnp.sin(x) * (1.0 + i)
        return x

    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def _records(name, since=0):
    return [r for r in xla_insight.build_log()["records"][since:]
            if r["program"] == name]


@pytest.mark.parametrize("stage", ["trace", "lower", "compile"])
def test_fresh_jit_leaves_one_record_a_stage_and_a_second_call_none(stage):
    import time

    import jax.numpy as jnp

    name = f"build_log_probe_{stage}"
    f = _slow_to_trace(name)
    t0 = time.perf_counter()
    f(jnp.ones((4,))).block_until_ready()
    t1 = time.perf_counter()
    mine = _records(name)
    assert [r["stage"] for r in mine] == ["trace", "lower", "compile"]
    # JAX names the trace by the function and the others by the module
    assert mine[0]["fun_name"] == name and mine[1]["fun_name"] == f"jit({name})"
    assert xla_insight.program_of(f"jit_{name}") == name
    rec = next(r for r in mine if r["stage"] == stage)
    assert rec["count"] == 1 and 0 < rec["self_s"] <= rec["seconds"] < t1 - t0
    ends = [r["t_end"] for r in mine]
    assert t0 < ends[0] < ends[1] < ends[2] < t1  # perf_counter's clock
    assert mine[2]["cache"] in ("hit", "miss", "off")
    totals = xla_insight.build_log()["totals"]
    f(jnp.ones((4,))).block_until_ready()
    assert len(_records(name)) == 3
    assert xla_insight.build_log()["totals"] == totals


def test_build_log_is_bounded_and_its_totals_are_not():
    log = xla_insight._BuildLog()
    n = xla_insight._BUILD_LOG_MAX + 500
    for i in range(n):
        log.on_duration(_COMPILE, 0.01, fun_name=f"jit(p{i})")
    doc = log.snapshot()
    assert len(doc["records"]) == xla_insight._BUILD_LOG_MAX
    assert doc["records"][-1]["program"] == f"p{n - 1}"
    assert doc["totals"]["compile"]["count"] == n
    assert doc["dropped"]["compile"]["count"] == 500
    kept = sum(r["self_s"] for r in doc["records"])
    assert kept + doc["dropped"]["compile"]["seconds"] == pytest.approx(
        doc["totals"]["compile"]["seconds"])
    log.on_duration("/jax/some/other/event", 1.0)
    assert log.snapshot()["totals"] == doc["totals"]


def test_build_log_counts_nested_time_once_and_merges_short_runs():
    import time

    log = xla_insight._BuildLog()
    # what JAX reports while a program is traced: every jnp function it
    # calls (sub-millisecond), a jitted layer, then the program itself
    t0 = time.perf_counter()
    for _ in range(40):
        time.sleep(2e-4)
        log.on_duration(_TRACE, 2e-5, fun_name="add")
    time.sleep(0.02)
    log.on_duration(_TRACE, 0.015, fun_name="layer")
    time.sleep(0.02)
    outer = time.perf_counter() - t0
    log.on_duration(_TRACE, outer, fun_name="decode_tick")
    log.on_duration(_LOWER, 0.0, fun_name="jit(decode_tick)")
    recs = {r["fun_name"]: r for r in log.snapshot()["records"]}
    assert set(recs) == {"<small>", "layer", "decode_tick", "jit(decode_tick)"}
    assert recs["<small>"]["count"] == 40
    assert recs["<small>"]["self_s"] == pytest.approx(40 * 2e-5)
    assert recs["layer"]["self_s"] == pytest.approx(0.015)
    # the program's own share: its seconds less what lies inside it
    assert recs["decode_tick"]["self_s"] == pytest.approx(
        outer - 0.015 - 40 * 2e-5, abs=1e-9)
    totals = log.snapshot()["totals"]
    assert totals["trace"]["count"] == 42
    assert totals["trace"]["seconds"] == pytest.approx(outer, abs=1e-9)


def test_build_log_reads_the_cache_events_of_its_thread():
    log = xla_insight._BuildLog()
    log.on_duration(_COMPILE, 0.2, fun_name="jit(a)")  # no request: cache off
    log.on_event("/jax/compilation_cache/compile_requests_use_cache")
    log.on_duration(_COMPILE, 0.2, fun_name="jit(b)")  # asked, not found
    log.on_event("/jax/compilation_cache/compile_requests_use_cache")
    log.on_event("/jax/compilation_cache/cache_hits")
    log.on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.03)
    log.on_duration(_COMPILE, 0.2, fun_name="jit(c)")
    assert log.take_compiled() == "hit" and log.take_compiled() is None
    doc = log.snapshot()
    assert [r["cache"] for r in doc["records"]] == ["off", "miss", "hit"]
    assert doc["cache"] == {"requests": 2, "hits": 1, "misses": 0,
                            "retrieval_s": 0.03}


def test_program_insight_has_four_build_stages_and_flows_where_insights_flow(
        tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_XLA_DUMP_DIR", str(tmp_path))
    docs = _static_steps().compiled_insights()
    assert len(docs) == 2
    for doc in docs:
        assert sorted(doc["build_s"]) == ["analyze", "compile", "lower", "trace"]
        assert all(v > 0 for v in doc["build_s"].values())
        assert doc["cache"] in ("hit", "miss", "off")
        assert doc["program"] in ("jit_startup", "jit_train_step")
        with open(tmp_path / f"program.{doc['key_hash']}.cost.json") as f:
            dumped = json.load(f)
        assert dumped["cache"] == doc["cache"]
        assert sorted(dumped["build_s"]) == sorted(doc["build_s"])
    # the registry's totals are the log's
    totals = xla_insight.build_log()["totals"]
    fam = monitor.default_registry().get("program_build_total")
    assert fam.labels(stage="compile").value >= 2
    assert totals["compile"]["count"] >= fam.labels(stage="compile").value
    sys.path.insert(0, _TOOLS)
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    report = obs_report.build_report(
        monitor.snapshot(),
        xla_dump_records=xla_insight.load_dump_dir(str(tmp_path)))
    section = report["compile"]
    assert section["builds"]["compile"] >= 2
    assert set(section["build_seconds"]) == {"trace", "lower", "compile"}
    row = section["programs"][docs[0]["key_hash"]]
    assert row["cache"] == docs[0]["cache"] and "analyze" in row["build_s"]
    # the text prints the first ten programs: this worker may have built more
    section["programs"] = {docs[0]["key_hash"]: row}
    text = obs_report.render_text(report)
    assert "build (every jit): " in text and f"cache={row['cache']}" in text


def test_second_build_of_a_program_reads_cache_hit(tmp_path):
    """A process that finds its program in the persistent cache says so:
    the insight of the second build of the same program."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    paddle.enable_static()
    try:
        programs = _build_train_program()
        seen = []
        for _ in range(2):  # the same programs, nothing kept in memory
            jax.clear_caches()
            exe, _ = _run_steps(*programs, Scope(), steps=1)
            seen.append({d["program"]: d["cache"]
                         for d in exe.compiled_insights()})
    finally:
        paddle.disable_static()
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert seen[0] == {"jit_startup": "miss", "jit_train_step": "miss"}
    assert seen[1] == {"jit_startup": "hit", "jit_train_step": "hit"}
