"""Pipeline-parallelism tests: sectioning + F-then-B execution parity.

Reference semantics under test (section_worker.cc:107-174 +
optimizer.py:3666 PipelineOptimizer): a program whose forward is split
across stages by device_guard must train to the same losses as the dense
single-device program — microbatch gradient accumulation averaged over
num_microbatches is mathematically the full-batch gradient, and the
optimizer runs once per step on each stage. Runs on the 8-virtual-device
CPU mesh (conftest.py), so sections really execute on distinct devices.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.optimizer import Adam, SGD


def _train_gpt(pp_stages, num_microbatches, steps=3, opt_cls=SGD, batch=4):
    from paddle_tpu.distributed.fleet.meta_optimizers import PipelineOptimizer
    from paddle_tpu.framework import Executor, Scope, program_guard
    from paddle_tpu.models.gpt import GPTConfig, build_train_program

    cfg = GPTConfig(
        vocab_size=64, n_layer=4, n_head=2, d_model=32, max_seq_len=16,
        pp_stages=pp_stages,
    )
    main, startup, io = build_train_program(cfg, batch=batch, seq=16)
    with program_guard(main, startup):
        opt = opt_cls(learning_rate=0.1)
        if pp_stages > 1:
            PipelineOptimizer(opt, num_microbatches=num_microbatches).minimize(io["loss"])
        else:
            opt.minimize(io["loss"])
    scope = Scope()
    exe = Executor()
    exe.run(startup, scope=scope)
    r = np.random.RandomState(0)
    feed = {
        "tokens": r.randint(0, 64, (batch, 16)).astype("int64"),
        "labels": r.randint(0, 64, (batch, 16)).astype("int64"),
    }
    losses = [
        float(exe.run(main, feed=feed, fetch_list=[io["loss"]], scope=scope)[0])
        for _ in range(steps)
    ]
    return losses, main, scope


def test_pipeline_loss_parity_vs_dense():
    """2-stage GPT with 2 microbatches == dense program, step for step."""
    paddle.enable_static()
    try:
        dense, _, _ = _train_gpt(1, 1)
        piped, main, _ = _train_gpt(2, 2)
        np.testing.assert_allclose(dense, piped, rtol=2e-4, atol=1e-5)
        assert getattr(main, "_pipeline_meta", None) is not None
    finally:
        paddle.disable_static()


def test_pipeline_four_stages_four_microbatches():
    paddle.enable_static()
    try:
        dense, _, _ = _train_gpt(1, 1, batch=8)
        piped, _, _ = _train_gpt(4, 4, batch=8)
        np.testing.assert_allclose(dense, piped, rtol=2e-4, atol=1e-5)
    finally:
        paddle.disable_static()


def test_pipeline_with_adam_trains():
    """Adam state (moments) lives per-stage; loss must decrease."""
    paddle.enable_static()
    try:
        losses, _, _ = _train_gpt(2, 2, steps=5, opt_cls=Adam)
        assert losses[-1] < losses[0], losses
    finally:
        paddle.disable_static()


def test_split_program_sections_and_interfaces():
    """The splitter must produce per-stage forward/backward/optimize
    sections with stage-monotone forward order and every param owned by
    exactly one stage (reference PipelineOptimizer device-index
    bookkeeping, optimizer.py:3666)."""
    paddle.enable_static()
    try:
        _, main, _ = _train_gpt(2, 2, steps=1)
        meta = main._pipeline_meta
        assert meta.num_stages == 2
        fwd = [s for s in meta.sections if s.phase == "forward"]
        bwd = [s for s in meta.sections if s.phase == "backward"]
        opt = [s for s in meta.sections if s.phase == "optimize"]
        assert [s.stage for s in fwd] == [0, 1]
        assert [s.stage for s in bwd] == [1, 0]
        assert opt, "no optimizer sections"
        assert set(meta.param_stage.values()) == {0, 1}
        # stage-1 forward must read at least one boundary activation
        # produced by stage 0
        s0_outs = set(fwd[0].out_vars)
        assert any(v in s0_outs for v in fwd[1].in_vars)
    finally:
        paddle.disable_static()


def test_pipeline_sections_on_distinct_devices():
    """Each stage's parameters must be committed to that stage's device
    of the pp axis (explicit placement, not GSPMD)."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs >=2 devices")
    paddle.enable_static()
    try:
        _, main, scope = _train_gpt(2, 2, steps=1)
        meta = main._pipeline_meta
        devs = {}
        for pname, stage in meta.param_stage.items():
            arr = scope.get(pname)
            if arr is not None and hasattr(arr, "devices"):
                devs.setdefault(stage, set()).update(arr.devices())
        assert devs[0] and devs[1] and devs[0] != devs[1], devs
    finally:
        paddle.disable_static()


def test_1f1b_schedule_structure_and_memory_bound():
    """1F1B (the default): after a warmup of S-1 forwards each forward is
    followed by the oldest pending backward, so at most S microbatches of
    activations are live — vs all M under the reference's F-then-B
    (section_worker.cc:107). Asserts the executed interleave and the live
    bound recorded by the executor."""
    from paddle_tpu.distributed.fleet.meta_optimizers import PipelineOptimizer
    from paddle_tpu.framework import Executor, Scope, program_guard
    from paddle_tpu.models.gpt import GPTConfig, build_train_program

    paddle.enable_static()
    try:
        cfg = GPTConfig(vocab_size=64, n_layer=4, n_head=2, d_model=32,
                        max_seq_len=16, pp_stages=4)
        main, startup, io = build_train_program(cfg, batch=8, seq=16)
        with program_guard(main, startup):
            PipelineOptimizer(SGD(learning_rate=0.1),
                              num_microbatches=8).minimize(io["loss"])
        scope = Scope()
        exe = Executor()
        exe.run(startup, scope=scope)
        r = np.random.RandomState(0)
        feed = {
            "tokens": r.randint(0, 64, (8, 16)).astype("int64"),
            "labels": r.randint(0, 64, (8, 16)).astype("int64"),
        }
        exe.run(main, feed=feed, fetch_list=[io["loss"]], scope=scope)
        log = exe._pp_dispatch_log
        S, M = 4, 8
        # first backward is issued right after the S-th forward, NOT after
        # all M forwards
        first_b = log.index(("B", 0))
        assert log[:first_b] == [("F", m) for m in range(S)]
        # interleave in steady state: F4 B0 F5 B1 ...
        assert log[first_b:first_b + 4] == [("B", 0), ("F", 4), ("B", 1), ("F", 5)]
        # activation-live bound is S, not M
        assert exe._pp_live_peak == S
        # every microbatch ran exactly one F and one B
        assert sorted(m for p, m in log if p == "F") == list(range(M))
        assert sorted(m for p, m in log if p == "B") == list(range(M))
    finally:
        paddle.disable_static()


def test_fthenb_schedule_still_available_and_matches():
    """Legacy schedule flag keeps reference behavior (all M live)."""
    from paddle_tpu.distributed.fleet.meta_optimizers import PipelineOptimizer
    from paddle_tpu.framework import Executor, Scope, program_guard
    from paddle_tpu.models.gpt import GPTConfig, build_train_program

    paddle.enable_static()
    try:
        losses = {}
        for schedule in ("1F1B", "FThenB"):
            cfg = GPTConfig(vocab_size=64, n_layer=4, n_head=2, d_model=32,
                            max_seq_len=16, pp_stages=2)
            main, startup, io = build_train_program(cfg, batch=4, seq=16)
            with program_guard(main, startup):
                PipelineOptimizer(SGD(learning_rate=0.1), num_microbatches=4,
                                  schedule=schedule).minimize(io["loss"])
            scope = Scope()
            exe = Executor()
            exe.run(startup, scope=scope)
            r = np.random.RandomState(0)
            feed = {
                "tokens": r.randint(0, 64, (4, 16)).astype("int64"),
                "labels": r.randint(0, 64, (4, 16)).astype("int64"),
            }
            losses[schedule] = [
                float(exe.run(main, feed=feed, fetch_list=[io["loss"]],
                              scope=scope)[0])
                for _ in range(3)
            ]
            if schedule == "FThenB":
                assert exe._pp_live_peak == 4  # all M live
        np.testing.assert_allclose(losses["1F1B"], losses["FThenB"],
                                   rtol=1e-6, atol=1e-7)
    finally:
        paddle.disable_static()


def test_pipeline_dispatches_every_stage_program_and_matches_dense():
    """The 4-stage x 8-microbatch step dispatches every
    `pp_<phase>_stage<k>` program it compiled (each forward and backward
    once a microbatch, each optimizer section once) and its losses track
    the serial single-device run's. How much the stages overlap is a
    speed, and waits for a pipeline cell on the chip."""
    import collections

    from paddle_tpu.distributed.fleet.meta_optimizers import PipelineOptimizer
    from paddle_tpu.framework import Executor, Scope, program_guard
    from paddle_tpu.models.gpt import GPTConfig, build_train_program

    S, M = 4, 8
    paddle.enable_static()
    try:
        def run(pp, mb, calls=None):
            cfg = GPTConfig(vocab_size=256, n_layer=4, n_head=4,
                            d_model=256, max_seq_len=64, pp_stages=pp)
            main, startup, io = build_train_program(cfg, batch=16, seq=64)
            with program_guard(main, startup):
                if pp > 1:
                    PipelineOptimizer(SGD(learning_rate=0.1),
                                      num_microbatches=mb).minimize(io["loss"])
                else:
                    SGD(learning_rate=0.1).minimize(io["loss"])
            scope = Scope()
            exe = Executor()
            exe.run(startup, scope=scope)
            r = np.random.RandomState(0)
            feed = {
                "tokens": r.randint(0, 256, (16, 64)).astype("int64"),
                "labels": r.randint(0, 256, (16, 64)).astype("int64"),
            }
            losses = [exe.run(main, feed=feed, fetch_list=[io["loss"]],
                              scope=scope)[0]]
            if calls is not None:
                # count the second step's dispatches by program name
                (comp,) = [v for k, v in exe._cache.items() if k[0] == "pp"]
                for info in comp["sections"]:
                    def counted(inputs, key, fn=info["fn"]):
                        calls[fn.__name__] += 1
                        return fn(inputs, key)

                    info["fn"] = counted
            losses.append(exe.run(main, feed=feed, fetch_list=[io["loss"]],
                                  scope=scope)[0])
            return [float(np.asarray(v).reshape(-1)[0]) for v in losses]

        calls = collections.Counter()
        dense = run(1, 1)
        piped = run(S, M, calls)
        expect = {f"pp_{phase}_stage{k}": n for k in range(S)
                  for phase, n in (("forward", M), ("backward", M),
                                   ("optimize", 1))}
        assert dict(calls) == expect
        assert piped[1] < piped[0]
        np.testing.assert_allclose(dense, piped, rtol=2e-4, atol=1e-5)
    finally:
        paddle.disable_static()


def test_pipeline_composes_with_recompute_and_amp():
    """PipelineOptimizer over RecomputeOptimizer over AMP-decorated SGD:
    the stacked meta-optimizers (reference strategy_compiler.py chain) must
    produce a trainable program whose losses track the plain pipeline."""
    from paddle_tpu import static
    from paddle_tpu.distributed.fleet.meta_optimizers import (
        PipelineOptimizer,
        RecomputeOptimizer,
    )
    from paddle_tpu.framework import Executor, Scope, program_guard
    from paddle_tpu.models.gpt import GPTConfig, build_train_program

    paddle.enable_static()
    try:
        def run(stack):
            cfg = GPTConfig(vocab_size=64, n_layer=4, n_head=2, d_model=32,
                            max_seq_len=16, pp_stages=2)
            main, startup, io = build_train_program(cfg, batch=4, seq=16)
            with program_guard(main, startup):
                inner = SGD(learning_rate=0.1)
                if stack == "amp+rc+pp":
                    inner = static.amp.decorate(
                        inner, use_dynamic_loss_scaling=False,
                        init_loss_scaling=1.0)
                    inner = RecomputeOptimizer(
                        inner, configs={"checkpoints": io["checkpoints"]})
                PipelineOptimizer(inner, num_microbatches=2).minimize(io["loss"])
            scope = Scope()
            exe = Executor()
            exe.run(startup, scope=scope)
            r = np.random.RandomState(0)
            feed = {
                "tokens": r.randint(0, 64, (4, 16)).astype("int64"),
                "labels": r.randint(0, 64, (4, 16)).astype("int64"),
            }
            return [
                float(exe.run(main, feed=feed, fetch_list=[io["loss"]],
                              scope=scope)[0])
                for _ in range(4)
            ]

        plain = run("pp")
        stacked = run("amp+rc+pp")
        assert all(np.isfinite(stacked))
        assert stacked[-1] < stacked[0]  # trains
        # bf16 compute tracks fp32 loosely (~2-3 decimal digits)
        np.testing.assert_allclose(plain, stacked, rtol=0.05, atol=0.02)
    finally:
        paddle.disable_static()
