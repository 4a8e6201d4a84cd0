"""Executor: lowers a program block to XLA and runs it.

Counterpart of the reference serial Executor
(/root/reference/paddle/fluid/framework/executor.cc:180,376,428,474): where
the reference interprets a block op-by-op (choose kernel -> transfer ->
InferShape -> launch, operator.cc:944-1068), this executor *compiles* the
whole block once: every op's lowering rule is traced in program order into a
single pure function (feeds, params, rng) -> (fetches, new params), which is
jit-compiled by XLA and cached — the per-op hot loop disappears into one
fused device program. Parameter mutation (Scope writes) becomes buffer
donation: params go in donated and come back as the updated arrays.

The (program, feed-spec, fetch-spec) -> compiled-callable cache mirrors the
reference Python executor's program cache (executor.py:1258).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags as _flags
from .. import goodput as _goodput
from .. import memwatch as _memwatch
from .. import monitor as _monitor
from .. import profiler as _profiler
from . import core, registry
from . import errors as _errs
from . import shard_insight as _shard_insight
from . import xla_insight as _insight
from .program import Block, Program, Variable, default_main_program
from .registry import LoweringContext
from .scope import Scope, global_scope

# ops handled by the executor itself, not by lowering rules
_STRUCTURAL_OPS = frozenset({"feed", "fetch"})

# telemetry families (module-level handles: one dict lookup at import,
# zero lookups on the hot path; everything is a no-op when metrics are
# disabled via PADDLE_TPU_METRICS=0)
_M_CACHE = _monitor.counter(
    "executor_cache_lookups_total",
    "compiled-program cache lookups by outcome", labelnames=("result",))
_M_CACHE_HIT = _M_CACHE.labels(result="hit")
_M_CACHE_MISS = _M_CACHE.labels(result="miss")
_M_COMPILE = _monitor.counter(
    "executor_compile_total", "program block compiles (cache misses)")
_M_COMPILE_T = _monitor.histogram(
    "executor_compile_seconds",
    "the WHOLE first run of a freshly built block (build, trace, lower, "
    "compile or cache load, and the run itself); the stages alone are "
    "program_build_seconds_total{stage}",
    buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0))
_M_RUN = _monitor.counter("executor_run_total", "Executor.run calls")
_M_PROG_RUN = _monitor.counter(
    "executor_program_run_total",
    "executions of each compiled program, labeled by cache-key hash — "
    "the per-program step count comms-plane reconciliation multiplies "
    "its per-execution HLO byte prediction by", labelnames=("program",))
_M_RUN_T = _monitor.histogram(
    "executor_run_seconds", "steady-state Executor.run wall time")
_M_DISPATCH_T = _monitor.histogram(
    "executor_dispatch_seconds",
    "steady-state runs: wall time inside the one call of the compiled "
    "program (enqueue, plus back-pressure once the in-flight queue is "
    "full: near the step time means the device sets the pace)")
_M_HOST_T = _monitor.histogram(
    "executor_host_seconds",
    "steady-state runs: Executor.run wall time outside the compiled "
    "call (feed placement, cache lookup, scope reads and writes, fetch)")
_M_CACHE_SIZE = _monitor.gauge(
    "executor_cache_size", "compiled programs resident in the run cache")
_M_NONFINITE = _monitor.counter(
    "executor_nonfinite_total",
    "numerics-sentinel / FLAGS_check_nan_inf probe failures")
_M_GRAD_PAIRED = _monitor.counter(
    "executor_grad_paired_total",
    "generic grad ops that applied the pullback their forward op made "
    "where it was traced (counted when a block is traced, not per step)")
_M_GRAD_RETRACED = _monitor.counter(
    "executor_grad_retraced_total",
    "generic grad ops that traced their forward rule a second time: the "
    "forward op is not in the trace, or an input was rewritten since "
    "(counted when a block is traced; the op types are in the flight "
    "recorder's grad_retraced events)")


def _slot_args(pvs, prefix: str = "") -> Dict[str, Tuple[str, ...]]:
    """{slot: variable names} of a desc's filled slots that start with
    `prefix`, the prefix dropped."""
    return {pv.parameter[len(prefix):]: tuple(pv.arguments) for pv in pvs
            if pv.arguments and pv.parameter.startswith(prefix)}


class _GradPairing:
    """One trace of one block: which forward ops are differentiated where
    they are traced, and the pullbacks they leave for their grad ops.

    A generic `<op>_grad` finds its forward by the forward's output
    variable names, which it carries as its `__out__<slot>` inputs
    (backward.py), and pairs with it if it also names the same input
    variables (a recompute segment's grad ops name the CLONED forward
    ops, so the clone is differentiated and the original stays plain).
    Whether the pullback is used is settled when the grad op is traced:
    only if it reads the very values the forward consumed."""

    def __init__(self, ops):
        # keyed by id(op): a pipeline section's positions are not the block's
        self.cot_slots: Dict[int, set] = {}  # forward op -> slots to differentiate
        self.forward_of: Dict[int, int] = {}  # grad op -> forward op
        self.waiting: Dict[int, int] = {}  # forward op -> grad ops still to come
        self.pullbacks: Dict[int, registry.Pullback] = {}
        self.paired = 0
        self.retraced: List[str] = []
        if not any(op.type.endswith("_grad") for op in ops):
            return  # a forward-only block (inference, startup, most sub-blocks)
        writer: Dict[tuple, Any] = {}  # (op type, its outputs) -> the last such op

        def key(type, outs):
            return (type, tuple(sorted(outs.items())))

        for op in ops:
            if op.type in _STRUCTURAL_OPS:
                continue
            fwd_def = registry.generic_grad_forward(op.type)
            if fwd_def is not None:
                slots = _slot_args(op.desc.inputs)
                fwd = writer.get(key(fwd_def.type, _slot_args(op.desc.inputs, "__out__")))
                if (fwd is not None and _slot_args(fwd.desc.inputs)
                        == registry.forward_inputs(slots)):
                    self.forward_of[id(op)] = id(fwd)
                    self.waiting[id(fwd)] = self.waiting.get(id(fwd), 0) + 1
                    self.cot_slots.setdefault(id(fwd), set()).update(
                        registry.cotangent_slots(slots))
            writer[key(op.type, _slot_args(op.desc.outputs))] = op

    def lower(self, opdef, ctx, op, ins, attrs):
        """`run_lowering` of `op`, with the pairing."""
        if isinstance(opdef.lower, registry.GenericGrad):
            fwd = self.forward_of.get(id(op))
            pullback = self.pullbacks.get(fwd)
            if fwd is not None:
                self.waiting[fwd] -= 1
                if not self.waiting[fwd]:  # last consumer: keep nothing alive
                    self.pullbacks.pop(fwd, None)
            if pullback is not None and pullback.read_by(ins):
                self.paired += 1
                return pullback(ins)
            self.retraced.append(op.type)
        elif id(op) in self.cot_slots:
            outs, self.pullbacks[id(op)] = registry.lower_differentiated(
                opdef, ctx, ins, attrs, self.cot_slots[id(op)])
            return outs
        return registry.run_lowering(opdef, ctx, ins, attrs)

    def report(self) -> None:
        _M_GRAD_PAIRED.inc(self.paired)
        if self.retraced:
            _M_GRAD_RETRACED.inc(len(self.retraced))
            _monitor.flight_record(
                "grad_retraced", ",".join(sorted(set(self.retraced))),
                ops=len(self.retraced), paired=self.paired)


def lower_block(
    ctx: LoweringContext,
    block,
    env: Dict[str, Any],
    gc_plan: Optional[Dict[int, List[str]]] = None,
    after_op=None,
) -> Dict[str, Any]:
    """Trace every op of `block` (anything with `.ops`: a pipeline section
    too) in program order, threading values through `env` (name -> jax
    value). Shared with control-flow op lowerings, which call it
    recursively on sub-blocks. A forward op whose generic grad op is in
    the same block is differentiated here, where it is traced, and the
    grad op applies that pullback (`_GradPairing`), so a forward rule is
    traced once. `gc_plan` (from the native core, framework/native.py —
    reference executor.cc:474-480 per-op GC) names the temporaries that
    die after each op; dropping them keeps the trace env from pinning dead
    intermediates. `after_op(i, op, env)` runs after each lowered op."""
    pairing = _GradPairing(block.ops)
    numbered = isinstance(block, Block)  # a section's positions are not the block's
    for i, op in enumerate(block.ops):
        if op.type not in _STRUCTURAL_OPS:
            # every HLO instruction carries its Paddle op in op_name
            # (metadata only: the compiled program is the same); per-op
            # host spans only when profiling: real per-op wall time in
            # interpreted (eager/host-op) mode, per-op trace time under
            # jit (the trace runs once, at compile)
            op_span = (_profiler.RecordEvent(f"op/{op.type}")
                       if _profiler.tracing_active()
                       else contextlib.nullcontext())
            with jax.named_scope(op.type), op_span:
                lower_op(ctx, op, env, op_idx=i if numbered else None,
                         pairing=pairing)
            if ctx.var_constraints and ctx.mesh is not None:
                _apply_var_constraints(ctx, op, env)
            if after_op is not None:
                after_op(i, op, env)
        if gc_plan:
            for name in gc_plan.get(i, ()):
                env.pop(name, None)
    pairing.report()
    return env


def _program_role(block, feed_names, fetch_names, updated_names) -> str:
    """What a block is for, read off its structure: the name its
    compiled program carries (``jit_startup``, ``jit_train_step``,
    ``jit_forward``)."""
    if any(op.type.endswith("_grad") for op in block.ops):
        return "train_step"
    if updated_names and not feed_names and not fetch_names:
        return "startup"
    return "forward"


def _compile_constraints(program):
    """program._var_sharding_constraints [(regex str, axes)] -> compiled,
    shared by the single-program and pipeline compile paths."""
    import re

    return [
        (re.compile(pat), axes)
        for pat, axes in getattr(program, "_var_sharding_constraints", [])
    ]


def _apply_var_constraints(ctx: LoweringContext, op, env: Dict[str, Any]) -> None:
    """Pin matching op outputs to a mesh layout (ZeRO-2 grad sharding:
    GSPMD otherwise chooses the layout by propagation alone)."""
    from jax.sharding import NamedSharding, PartitionSpec

    for name in op.output_arg_names():
        val = env.get(name)
        if val is None or not hasattr(val, "ndim"):
            continue
        for pat, axes in ctx.var_constraints:
            if pat.fullmatch(name):
                spec = []
                divisible = True
                for dim, ax in zip(
                    val.shape, tuple(axes) + (None,) * (val.ndim - len(axes))
                ):
                    size = (np.prod([ctx.mesh.shape[a] for a in ax])
                            if isinstance(ax, tuple)
                            else (ctx.mesh.shape[ax] if ax else 1))
                    if ax and dim % int(size) != 0:
                        divisible = False
                    spec.append(ax)
                # an indivisible dim means the rule cannot apply — leave
                # the layout to GSPMD propagation rather than pinning the
                # value fully replicated with an all-None constraint
                if divisible:
                    env[name] = jax.lax.with_sharding_constraint(
                        val, NamedSharding(ctx.mesh, PartitionSpec(*spec))
                    )
                break


def lower_op(ctx: LoweringContext, op, env: Dict[str, Any],
             op_idx: Optional[int] = None,
             pairing: Optional[_GradPairing] = None) -> None:
    try:
        opdef = registry.get_op_def(op.type)
    except NotImplementedError as e:
        # errors.Unimplemented: already typed, gains op provenance
        raise _errs.attach_op_provenance(e, op, op_idx=op_idx)
    ins: Dict[str, List[Any]] = {}
    for pv in op.desc.inputs:
        vals = []
        for name in pv.arguments:
            if name not in env:
                raise _errs.attach_op_provenance(
                    _errs.errors.PreconditionNotMet(
                        f"op {op.type!r} reads uninitialized variable {name!r}"
                    ), op, op_idx=op_idx)
            vals.append(env[name])
        if vals:
            ins[pv.parameter] = vals
    attrs = op.all_attrs()
    try:
        if pairing is None:
            outs = registry.run_lowering(opdef, ctx, ins, attrs)
        else:
            outs = pairing.lower(opdef, ctx, op, ins, attrs)
    except _errs.EnforceError as e:
        # an inner op (control-flow sub-block) may already have claimed
        # the provenance slot; set_op_provenance attaches only once
        raise _errs.attach_op_provenance(e, op, op_idx=op_idx)
    except Exception as e:
        raise _errs.attach_op_provenance(e, op, op_idx=op_idx) from e
    for pv in op.desc.outputs:
        vals = outs.get(pv.parameter, [])
        for name, val in zip(pv.arguments, vals):
            env[name] = val


class _CompiledBlock:
    def __init__(self, fn, feed_names, mutable_names, const_names, fetch_names, updated_names):
        self.fn = fn
        self.feed_names = feed_names
        self.mutable_names = mutable_names  # donated: read and written back
        self.const_names = const_names  # read-only scope inputs (not donated)
        self.fetch_names = fetch_names
        self.updated_names = updated_names
        # compiler-observability slots (xla_insight.py): filled on the
        # first run of a fresh entry, when example arguments exist
        self.module_name = None  # the program's name in a profile
        self.key_hash = None
        self.jittable = False
        self.insight = None  # ProgramInsight once captured
        self.insight_done = False  # one attempt per entry, even on failure
        self.check_numerics = False


class Executor:
    """`Executor(place)` with the reference `run(program, feed, fetch_list)`
    contract (executor.py:915)."""

    def __init__(self, place: Optional[core.Place] = None):
        self.place = place or core.default_place()
        self._cache: Dict[Tuple, _CompiledBlock] = {}
        self._step = 0
        self._seed = None
        self._seed_step = None  # device-resident [seed, step] uint32
        self._last_run_compiled = False  # telemetry: last run was a compile
        self._dispatch_s: Optional[float] = None  # last run's compiled call
        self._runs_since_sample = 0  # memwatch allocator-query cadence

    # -- public API ----------------------------------------------------
    def run(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        use_prune: bool = False,  # accepted for API parity
    ):
        # step-scoped tracing: declare the step (drives trace sampling),
        # open the per-step span every other span of this run nests under
        _profiler.set_step(self._step)
        with _profiler.span("executor/run", cat="step",
                            step=self._step) as sp:
            out = self._run_impl(
                program, feed, fetch_list, scope, return_numpy, use_prune
            )
            if self._last_run_compiled:
                # "which step recompiled", by step number, in any trace
                sp.set(compiled=True)
        dt = sp.seconds
        _monitor.note_progress()  # hang-watchdog heartbeat
        _M_RUN.inc()
        if self._last_run_compiled:
            # first invocation of a fresh block: trace + XLA compile +
            # run — binned separately so steady-state latency stays clean
            _M_COMPILE_T.observe(dt)
            _goodput.add("compile", dt)
        else:
            _M_RUN_T.observe(dt)
            if self._dispatch_s is not None:
                # the same two intervals the executor/dispatch span and
                # the rest of executor/run show in a trace
                _M_DISPATCH_T.observe(self._dispatch_s)
                _M_HOST_T.observe(dt - self._dispatch_s)
            # steady-state run wall time is the device-compute window of
            # the step (a driver closing the step via goodput.end_step
            # accounts anything outside it as other buckets/host_other)
            _goodput.add("device_compute", dt)
        return out

    def _run_impl(
        self,
        program,
        feed,
        fetch_list,
        scope,
        return_numpy,
        use_prune,
    ):
        from .compiler import CompiledProgram

        self._last_run_compiled = False
        self._dispatch_s = None
        with _profiler.span("executor/prepare", cat="executor"):
            compiled_prog = None
            if isinstance(program, CompiledProgram):
                # reference executor.py:855 _run_parallel path: unwrap, shard
                compiled_prog = program
                program = compiled_prog._program
            program = program or default_main_program()
            feed = feed or {}
            fetch_list = list(fetch_list or [])
            scope = scope or global_scope()
            if compiled_prog is not None and compiled_prog._mesh is not None:
                compiled_prog._prepare_scope(scope)
                feed = compiled_prog._shard_feed(
                    {k: np.asarray(v) if not isinstance(v, jax.Array) else v
                     for k, v in feed.items()}
                )

            fetch_names = [v.name if isinstance(v, Variable) else str(v) for v in fetch_list]
            pp_meta = getattr(program, "_pipeline_meta", None)
            if pp_meta is None:
                compiled, call_args = self._prepare(
                    program, feed, fetch_names, scope)
        if pp_meta is not None:  # per-section dispatch
            return self._run_pipeline(
                program, pp_meta, feed, fetch_names, scope, return_numpy
            )
        with _profiler.span("executor/dispatch", cat="executor",
                            program=compiled.module_name) as sp:
            try:
                fetches, new_params, self._seed_step, probes = compiled.fn(
                    *call_args)
            except Exception as e:
                # XLA RESOURCE_EXHAUSTED -> typed error + post-mortem:
                # blamed op provenance, footprint by layer, top programs
                # by peak, last live stats, remediation hints, JSON dump
                # next to the XLA artifacts (paddle_tpu/memwatch.py). A
                # failed dispatch may already have consumed donated
                # buffers — there is no retry path, only a better autopsy.
                if _memwatch.is_oom_error(e):
                    raise _memwatch.oom_error(
                        e, program=program, scope=scope,
                        insights=self.compiled_insights()) from e
                raise
        self._dispatch_s = sp.seconds
        with _profiler.span("executor/commit", cat="executor"):
            self._commit(program, compiled, probes, new_params, scope)
        with _profiler.span("executor/release", cat="executor"):
            # the call consumed the donated state (every parameter and
            # optimizer moment) and `_commit` replaced it in the scope, so
            # this frame holds the last reference to each old array
            # object: they are freed here, under a name, and not when the
            # frame dies, inside executor/run and outside every child span
            del call_args, new_params, probes
        if not return_numpy:
            return list(fetches)
        with _profiler.span("executor/fetch", cat="executor"):
            try:
                return [np.asarray(f) for f in fetches]
            except Exception as e:
                # async dispatch: an OOM raised by the device often
                # surfaces at the host transfer, not the dispatch call —
                # same post-mortem treatment
                if _memwatch.is_oom_error(e):
                    raise _memwatch.oom_error(
                        e, program=program, scope=scope,
                        insights=self.compiled_insights()) from e
                raise

    def _prepare(self, program, feed, fetch_names, scope):
        """Everything between the caller's arguments and the compiled
        call: feed conversion and placement, the cache lookup (or
        compile), the scope reads. Returns (compiled, call_args)."""
        feed_vals = {k: self._to_device_array(program, k, v) for k, v in feed.items()}

        extra = getattr(program, "_extra_feeds", None)
        if extra:
            for n, fn in extra.items():
                if n not in feed_vals:
                    feed_vals[n] = jnp.asarray(fn())

        # mesh programs carrying a sharding recipe: feeds land on the
        # mesh per the recipe's batch spec (dp/fsdp axes; clean_spec
        # degrades scalars and indivisible dims to replicated), so the
        # compiled program's explicit in_shardings always match placement
        recipe = getattr(program, "_sharding_recipe", None)
        mesh = getattr(program, "_mesh", None)
        if recipe is not None and mesh is not None:
            feed_vals = {
                k: jax.device_put(v, recipe.feed_sharding(mesh, v))
                for k, v in feed_vals.items()
            }

        compiled = self._get_compiled(program, feed_vals, fetch_names, scope)

        mut = {n: scope.get(n) for n in compiled.mutable_names}
        const = {n: scope.get(n) for n in compiled.const_names}
        seed = program.random_seed if program.random_seed is not None else 0
        # seed/step live on device and fold inside the compiled program;
        # the step counter is incremented by the program itself and the
        # buffer donated back — a host-side fold_in or per-step numpy
        # transfer would cost several synchronous dispatches per step
        if self._seed_step is None or self._seed != seed:
            self._seed = seed
            self._seed_step = jnp.asarray([seed, self._step], jnp.uint32)
        seed_step = self._seed_step

        # compiler insight: on the run that compiles a fresh entry, route
        # through the AOT stages (trace -> lower -> compile) so the one
        # XLA compile also yields jaxpr/HLO text + cost/memory analysis;
        # the compiled executable becomes the cache entry's fn
        if (self._last_run_compiled and compiled.jittable
                and not compiled.insight_done and _insight.enabled()):
            compiled.insight_done = True
            insight, executable = _insight.capture(
                compiled.fn, (feed_vals, mut, const, seed_step),
                key_hash=compiled.key_hash,
                label=",".join(fetch_names) or "program",
                fetch_names=fetch_names)
            if insight is not None:
                compiled.insight = insight
            if executable is not None:
                compiled.fn = _insight.aot_call(executable, compiled.fn)

        if compiled.key_hash:
            _M_PROG_RUN.labels(program=compiled.key_hash).inc()
        return compiled, (feed_vals, mut, const, seed_step)

    def _commit(self, program, compiled, probes, new_params, scope) -> None:
        """After the compiled call returned (the device may still be
        running it): the memory sample, the nan probes, and the updated
        parameters written back to the scope."""
        # device-memory watermark: allocator queries are host work on
        # the dispatch path (goodput host_other), so steady-state runs
        # sample on a cadence — compiles always sample, and drivers that
        # close ledger steps still get per-step watermarks from
        # memwatch.end_step's auto-sample at the step boundary
        self._runs_since_sample += 1
        if self._last_run_compiled or self._runs_since_sample >= max(
                1, int(_flags.env_flag("PADDLE_TPU_MEMWATCH_SAMPLE_RUNS"))):
            self._runs_since_sample = 0
            _memwatch.sample()
        self._step += 1
        if getattr(compiled, "nan_probes", None):
            for (op_idx, op_type, var), ok in zip(compiled.nan_probes, probes):
                if not bool(ok):
                    _M_NONFINITE.inc()
                    if compiled.check_numerics:
                        # numerics sentinel: a typed error carrying the
                        # producing op's provenance (type, block/op idx,
                        # build callstack — the PR 1 error contract)
                        op = program.global_block().ops[op_idx]
                        raise _errs.attach_op_provenance(
                            _errs.errors.InvalidArgument(
                                f"check_numerics: op #{op_idx} "
                                f"{op_type!r} produced non-finite values "
                                f"in output {var!r}"
                            ), op, op_idx=op_idx)
                    raise FloatingPointError(
                        f"FLAGS_check_nan_inf: op #{op_idx} {op_type!r} "
                        f"produced nan/inf in output {var!r}"
                    )
        for n in compiled.updated_names:
            scope.set(n, new_params[n])

    # -- dataset-driven training (reference Trainer/DeviceWorker) ------
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread: int = 0, debug: bool = False,
                           fetch_list=None, fetch_info=None,
                           print_period: int = 100):
        """The HogwildWorker loop (hogwild_worker.cc:197 `while
        reader->Next(): for op: op->Run`) over a Dataset's batches: each
        batch feeds the same jitted step; fetch_list values print every
        print_period batches like the reference's fetch_config. Returns
        the list of fetched rows (empty when fetch_list is None)."""
        program = program or default_main_program()
        scope = scope or global_scope()
        if dataset is None:
            raise ValueError("train_from_dataset needs a dataset")
        names = [v.name if isinstance(v, Variable) else str(v)
                 for v in (fetch_list or [])]
        # the fleet opt-info on the program selects the trainer/worker
        # family (reference trainer_factory.py; DownpourWorker drives PS
        # sparse pull/push per batch, HogwildWorker is the plain loop)
        from .trainer import TrainerFactory

        trainer = TrainerFactory.create_trainer(
            getattr(program, "_fleet_opt", None))
        return trainer.train(
            self, program, dataset, scope, fetch_names=names, debug=debug,
            print_period=print_period, fetch_info=fetch_info)

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           **kw):
        return self.train_from_dataset(program, dataset, scope, **kw)

    # -- helpers -------------------------------------------------------
    def _to_device_array(self, program: Program, name: str, value: Any):
        if isinstance(value, (jax.Array,)):
            return value
        arr = np.asarray(value)
        return jnp.asarray(arr)

    def _get_compiled(
        self,
        program: Program,
        feed_vals: Dict[str, Any],
        fetch_names: List[str],
        scope: Scope,
    ) -> _CompiledBlock:
        feed_spec = tuple(
            (k, tuple(v.shape), str(jnp.result_type(v))) for k, v in sorted(feed_vals.items())
        )
        # the nan-check flags change the compiled function, so they are
        # part of the cache key (flipping either after a first run
        # recompiles); the numerics sentinel (typed-error mode) and the
        # legacy FLAGS_check_nan_inf share the same probe machinery
        check_numerics = bool(_flags.env_flag("PADDLE_TPU_CHECK_NUMERICS"))
        check_nan = bool(_flags.get_flags("FLAGS_check_nan_inf")) or check_numerics
        key = (
            id(program), program._version, feed_spec, tuple(fetch_names),
            id(scope), check_nan, check_numerics,
        )
        cached = self._cache.get(key)
        if cached is not None:
            if all(scope.has(n) for n in cached.mutable_names + cached.const_names):
                _M_CACHE_HIT.inc()
                return cached
        _M_CACHE_MISS.inc()
        self._last_run_compiled = True
        with _profiler.span("executor/build", cat="build") as sp:
            compiled = self._build(program, feed_vals, fetch_names, scope,
                                   feed_spec, check_nan, check_numerics)
            sp.set(program=compiled.module_name, key=compiled.key_hash)
        self._cache[key] = compiled
        self._note_cache_size()
        return compiled

    def _build(self, program, feed_vals, fetch_names, scope, feed_spec,
               check_nan, check_numerics) -> _CompiledBlock:
        """The miss path up to the jit wrapper: block analysis, the
        recipe's placement of the scope, the function the block lowers
        through. Nothing is traced or compiled here: `_prepare` hands the
        wrapper to `xla_insight.capture` once the arguments exist."""
        block = program.global_block()
        mesh = getattr(program, "_mesh", None)
        feed_names = sorted(feed_vals)
        param_names, updated_names = self._analyze_block(block, feed_names, scope)
        updated_set = set(updated_names)
        # only vars that are both read and written may be donated; read-only
        # inputs (learning rate, frozen params) must survive the call
        mutable_names = [n for n in param_names if n in updated_set]
        const_names = [n for n in param_names if n not in updated_set]
        recipe = getattr(program, "_sharding_recipe", None)
        if mesh is not None and recipe is not None:
            # recipe programs shard their own scope (params + optimizer
            # state onto the mesh per the merged rules) once per
            # (program, scope) pair — the declarative counterpart of
            # CompiledProgram._prepare_scope, so exe.run(main) needs no
            # wrapper object
            prepared = getattr(scope, "_recipe_prepared_for", None)
            if prepared is None:
                prepared = set()
                scope._recipe_prepared_for = prepared
            # versioned key: re-applying a different recipe bumps the
            # program version, so the scope reshards instead of keeping
            # the previous placement
            prep_key = (id(program), program._version)
            if prep_key not in prepared:
                from ..parallel.mesh import shard_scope

                shard_scope(scope, mesh,
                            getattr(program, "_sharding_rules", []))
                prepared.add(prep_key)
        if mesh is not None and _shard_insight.verify_enabled():
            # sharding verification at the one boundary where placement
            # is settled and cheap to check (compile time, not per step):
            # drifted parameters count on sharding_mismatch_total and
            # land in the flight recorder with intended-vs-actual specs
            rules = getattr(program, "_sharding_rules", None)
            if rules:
                _shard_insight.verify_scope(
                    scope, mesh, rules,
                    names=[p.name for p in program.all_parameters()])

        # native desc-layer analyses (C++ when built): structural checks at
        # compile time + per-op death points for trace-env hygiene
        from . import native

        prog_bytes = program.serialize_to_string() if native.available() else None
        native.validate_program(program, data=prog_bytes)
        plan = native.gc_plan(
            program, list(fetch_names) + updated_names, data=prog_bytes
        )

        nan_probes: List[Tuple[int, str, str]] = []  # (op idx, type, var)

        var_constraints = _compile_constraints(program)

        def fn(feeds, mut, const, seed_step):
            rng_key = jax.random.fold_in(
                jax.random.key(seed_step[0]), seed_step[1]
            )
            env = dict(const)
            env.update(mut)
            env.update(feeds)
            ctx = LoweringContext(rng_key=rng_key, mesh=mesh,
                                  var_constraints=var_constraints)
            ctx.program = program
            probes = []

            def probe(i, op, env):
                # FLAGS_check_nan_inf debug mode (reference
                # operator.cc:1056 per-op CheckNanInf scan): probe every
                # float output; the host run raises on the first bad op
                for name in op.output_arg_names():
                    val = env.get(name)
                    if val is not None and jnp.issubdtype(
                        jnp.result_type(val), jnp.inexact
                    ):
                        probes.append(jnp.all(jnp.isfinite(val)))
                        if len(nan_probes) < len(probes):
                            nan_probes.append((i, op.type, name))

            lower_block(ctx, block, env, gc_plan=plan,
                        after_op=probe if check_nan else None)
            fetches = [env[n] for n in fetch_names]
            new_params = {n: env[n] for n in updated_names}
            next_seed_step = seed_step + jnp.asarray([0, 1], jnp.uint32)
            return fetches, new_params, next_seed_step, probes

        # blocks containing host ops (dynamic output shapes: unique,
        # where_index, ...) cannot be traced as one XLA program; run them
        # eagerly — op-by-op like the reference serial executor
        # (executor.cc:474), values still device-resident between ops.
        # ALL of the program's blocks are scanned: a host op inside a
        # while/cond sub-block (beam search in a decode loop) forces the
        # eager path just the same.
        def _any_host(blk):
            for op in blk.ops:
                if op.type in _STRUCTURAL_OPS:
                    continue
                try:
                    if registry.get_op_def(op.type).host:
                        return True
                except NotImplementedError:
                    pass
            return False

        has_host = any(_any_host(b) for b in program.blocks)

        _M_COMPILE.inc()
        # GSPMD-native mesh programs: the recipe states the in/out
        # shardings declaratively (batch over dp/fsdp, params/optimizer
        # state per the merged rules, fetches/seed replicated) instead of
        # leaving placement to propagation alone. Parameters keep the
        # SAME sharding on both sides, so donation aliases shard-for-
        # shard and fsdp state never rematerializes unsharded.
        jit_kwargs: Dict[str, Any] = {}
        if mesh is not None and recipe is None and not has_host:
            # the explicit-collectives / hand-sharded mesh path (PR 8's
            # c_* programs, dryrun-style main._mesh programs): no recipe
            # states placement declaratively, but the scope already
            # holds each parameter's ACTUAL sharding — pin it on the
            # output side so donation aliases shard-for-shard exactly
            # like recipe programs. Left to GSPMD propagation, an output
            # layout that drifts from the input's silently rematerializes
            # the donated buffer (peak + a reshard each step).
            jit_kwargs = self._scope_sharding_kwargs(
                mesh, updated_names, scope)
        elif mesh is not None and recipe is not None and not has_host:
            mut_ex = {n: scope.get(n) for n in mutable_names}
            const_ex = {n: scope.get(n) for n in const_names}

            # new_params covers EVERY updated persistable, including
            # write-only ones with no scope value yet — their shapes
            # come from the block's var metadata
            class _ShapeOnly:
                def __init__(self, shape):
                    self.shape = tuple(int(s) for s in (shape or ()))

            upd_ex: Dict[str, Any] = {}
            for n in updated_names:
                if n in mut_ex:
                    upd_ex[n] = mut_ex[n]
                else:
                    var = block._find_var_recursive(n)
                    upd_ex[n] = _ShapeOnly(
                        getattr(var, "shape", ()) if var is not None else ())
            in_sh, out_sh = recipe.jit_shardings(
                mesh, feed_vals, mut_ex, const_ex,
                rules=getattr(program, "_sharding_rules", None) or None,
                updated=upd_ex)
            jit_kwargs = {"in_shardings": in_sh, "out_shardings": out_sh}
        # the module's name in a profile and in the HLO: jit_<role>. No
        # counter, id or hash: the name is part of the persistent compile
        # cache's key and must be the same in every process
        fn.__name__ = fn.__qualname__ = _program_role(
            block, feed_names, fetch_names, updated_names)
        jit_fn = fn if has_host else jax.jit(fn, donate_argnums=(1, 3),
                                             **jit_kwargs)
        compiled = _CompiledBlock(
            jit_fn, feed_names, mutable_names, const_names, fetch_names, updated_names
        )
        compiled.module_name = (fn.__name__ if has_host
                                else f"jit_{fn.__name__}")
        compiled.nan_probes = nan_probes if check_nan else None
        compiled.check_numerics = check_numerics
        # the insight/dump label hashes program STRUCTURE, not the cache
        # key: the cache key's id(program)/id(scope) change every process,
        # and a stable hash is what lets a reused PADDLE_TPU_XLA_DUMP_DIR
        # overwrite a program's artifacts instead of duplicating them
        compiled.key_hash = _insight.key_hash((
            tuple(op.type for b in program.blocks for op in b.ops),
            feed_spec, tuple(fetch_names), check_nan, check_numerics,
        ))
        compiled.jittable = not has_host
        return compiled

    @staticmethod
    def _scope_sharding_kwargs(mesh, updated_names, scope) -> Dict[str, Any]:
        """out_shardings pinning each updated param to the sharding its
        scope value ALREADY has on this mesh (None = compiler's choice
        for everything else). Best-effort: values not placed on the
        mesh (single-device lr vars, counters) stay unpinned, and any
        failure degrades to propagation — never a broken compile."""
        from jax.sharding import NamedSharding

        try:
            mesh_devs = set(mesh.devices.flat)
            out_params: Dict[str, Any] = {}
            pinned = 0
            for n in updated_names:
                sh = None
                val = scope.get(n) if scope.has(n) else None
                cur = getattr(val, "sharding", None)
                if (isinstance(cur, NamedSharding)
                        and set(cur.mesh.devices.flat) == mesh_devs):
                    sh = cur
                    pinned += 1
                out_params[n] = sh
            if not pinned:
                return {}
            return {"out_shardings": (None, out_params, None, None)}
        except Exception:  # noqa: BLE001 - pinning is an optimization
            return {}

    def _note_cache_size(self) -> None:
        """Single authority for the cache-size level: the typed gauge and
        the legacy stat gauge are two exporter views of ONE value and
        must not be updated separately (they previously were, via
        different APIs, and could diverge)."""
        n = len(self._cache)
        _M_CACHE_SIZE.set(n)
        _monitor.stat_set("executor_cache_size", n)

    def compiled_insights(self) -> List[dict]:
        """Cost/memory records (ProgramInsight.to_dict) for every
        insight-captured entry resident in this executor's cache."""
        out = []
        for entry in self._cache.values():
            ins = getattr(entry, "insight", None)
            if ins is not None:
                out.append(ins.to_dict())
        return out

    # -- pipeline parallelism ------------------------------------------
    def _get_pipeline_compiled(self, program, meta, scope: Scope, fetch_names):
        """Compile each pipeline section (parallel/pipeline.py Section) to
        its own jitted XLA program. TPU translation of the reference
        SectionWorker setup (framework/pipeline_trainer.cc:122 per-section
        scopes): the section's read-set/write-set become the jit function's
        explicit inputs/outputs, and each program is pinned to its stage's
        device of the pp axis by committing its inputs there."""
        key = ("pp", id(program), program._version, tuple(fetch_names), id(scope))
        cached = self._cache.get(key)
        if cached is not None:
            _M_CACHE_HIT.inc()
            return cached
        _M_CACHE_MISS.inc()
        # first pipeline run traces + XLA-compiles every section: bin it
        # as compile latency, not steady-state run latency
        self._last_run_compiled = True
        _M_COMPILE.inc()

        from ..parallel.pipeline import _section_reads

        block = program.global_block()

        def is_persistable(name):
            var = block._find_var_recursive(name)
            return var is not None and var.persistable

        devices = jax.devices()
        S = meta.num_stages
        stage_dev = [devices[s % len(devices)] for s in range(S)]

        sections = []
        for sec in meta.sections:
            reads = sorted(_section_reads(sec))
            outs: List[str] = []
            for n in sec.out_vars:
                if n not in outs:
                    outs.append(n)
            for op in sec.ops:
                for n in op.output_arg_names():
                    if n not in outs and (is_persistable(n) or n in fetch_names):
                        outs.append(n)
            out_names = list(outs)

            mesh = getattr(program, "_mesh", None)

            sec_constraints = _compile_constraints(program)

            def make_fn(sec=sec, out_names=out_names, mesh=mesh):
                def fn(inputs, rng_key):
                    ctx = LoweringContext(rng_key=rng_key, mesh=mesh,
                                          var_constraints=sec_constraints)
                    ctx.program = program
                    env = lower_block(ctx, sec, dict(inputs))
                    return {n: env[n] for n in out_names}

                fn.__name__ = fn.__qualname__ = (
                    f"pp_{sec.phase}_stage{sec.stage}")
                return jax.jit(fn)

            sections.append(
                {
                    "sec": sec,
                    "fn": make_fn(),
                    "reads": reads,
                    "outs": out_names,
                    "persist": [n for n in out_names if is_persistable(n)],
                    "device": stage_dev[sec.stage],
                }
            )

        # each grad's home stage = the backward section that produces it;
        # per-stage jitted reducers average microbatch grads in ONE compiled
        # program per stage instead of a per-grad host loop of device_puts
        # (round-3 review finding)
        grad_stage: Dict[str, int] = {}
        for info in sections:
            if info["sec"].phase != "backward":
                continue
            produced = {
                n for op in info["sec"].ops for n in op.output_arg_names()
            }
            for g in meta.grad_names:
                if g in produced:
                    grad_stage[g] = info["sec"].stage

        def make_reducer():
            def pp_grad_mean(parts):
                return {
                    g: sum(vs) / float(len(vs)) for g, vs in parts.items()
                }

            return jax.jit(pp_grad_mean)

        reducers = {s: make_reducer() for s in set(grad_stage.values())}

        compiled = {
            "sections": sections,
            "stage_dev": stage_dev,
            "grad_stage": grad_stage,
            "reducers": reducers,
            "scope_cache": {},  # name -> device-committed array
            "scope_src": {},  # name -> the scope object it was placed from
        }
        self._cache[key] = compiled
        self._note_cache_size()  # pipeline entries count too
        return compiled

    def _run_pipeline(
        self, program, meta, feed, fetch_names, scope: Scope, return_numpy: bool
    ):
        """F-then-B microbatch schedule over per-stage jitted sections
        (reference section_worker.cc:107-174: num_microbatches forwards,
        then backwards, then the optimizer once). Gradients accumulate
        across microbatches on each grad's home stage and the optimizer
        sections consume the average — identical update semantics to the
        reference's per-microbatch grad accumulation + scale."""
        M = meta.num_microbatches
        comp = self._get_pipeline_compiled(program, meta, scope, fetch_names)

        feed_vals = {k: self._to_device_array(program, k, v) for k, v in feed.items()}
        extra = getattr(program, "_extra_feeds", None)
        if extra:
            for n, fn in extra.items():
                if n not in feed_vals:
                    feed_vals[n] = jnp.asarray(fn())
        for name in meta.batch_feeds:
            if name in feed_vals and feed_vals[name].shape[0] % M != 0:
                raise ValueError(
                    f"pipeline feed {name!r} batch {feed_vals[name].shape[0]} "
                    f"not divisible by num_microbatches={M}"
                )

        def scope_val(name, device):
            # cache key includes the device: a param read by two stages
            # (e.g. tied embeddings) is replicated, one copy per stage;
            # staleness tracking is per (name, device) too, so an external
            # scope.set refreshes every stage's copy, not just the first
            cache, src = comp["scope_cache"], comp["scope_src"]
            cur = scope.get(name) if scope.has(name) else None
            if cur is None:
                return None
            k = (name, device)
            if k not in cache or src.get(k) is not cur:
                cache[k] = jax.device_put(cur, device)
                src[k] = cur
            return cache[k]

        def run_section(info, env, rng_key):
            dev = info["device"]
            inputs = {}
            for n in info["reads"]:
                if n in env:
                    inputs[n] = jax.device_put(env[n], dev)
                else:
                    v = scope_val(n, dev)
                    if v is None:
                        raise RuntimeError(
                            f"pipeline stage {info['sec'].stage} "
                            f"({info['sec'].phase}) reads {n!r} which is "
                            f"neither fed, produced upstream, nor in scope"
                        )
                    inputs[n] = v
            env.update(info["fn"](inputs, rng_key))

        seed = program.random_seed if program.random_seed is not None else 0
        base_key = jax.random.fold_in(jax.random.key(seed), self._step)
        self._step += 1

        fwd = [s for s in comp["sections"] if s["sec"].phase == "forward"]
        bwd = [s for s in comp["sections"] if s["sec"].phase == "backward"]
        opt = [s for s in comp["sections"] if s["sec"].phase == "optimize"]

        S = meta.num_stages
        schedule = getattr(meta, "schedule", "1F1B")

        def new_env(m):
            env = {}
            for name, val in feed_vals.items():
                if name in meta.batch_feeds:
                    mb = val.shape[0] // M
                    env[name] = val[m * mb:(m + 1) * mb]
                else:
                    env[name] = val
            return env

        # microbatch interleave order. 1F1B (the reference's F-then-B is
        # the memory-hungry floor, section_worker.cc:107): after a warmup
        # of S-1 forwards, each forward is followed by the oldest pending
        # backward, so at most S microbatches of activations are live at
        # once (vs all M under F-then-B). Device queues drain
        # asynchronously, so consecutive entries targeting different
        # stages overlap on hardware.
        if schedule == "FThenB":
            order = [("F", m) for m in range(M)] + [("B", m) for m in range(M)]
        else:
            order = []
            for m in range(M):
                order.append(("F", m))
                if m >= S - 1:
                    order.append(("B", m - (S - 1)))
            for m in range(max(M - S + 1, 0), M):
                order.append(("B", m))

        # keep-set after a microbatch's backward: its grads + fetches (the
        # rest of the activations die, bounding live memory)
        keep_after_bwd = set(meta.grad_names) | set(fetch_names)

        envs: List[Optional[Dict[str, Any]]] = [None] * M
        keys = [jax.random.fold_in(base_key, m) for m in range(M)]
        live_peak = 0
        dispatch_log = []
        live = set()
        for phase, m in order:
            dispatch_log.append((phase, m))
            if phase == "F":
                envs[m] = new_env(m)
                live.add(m)
                live_peak = max(live_peak, len(live))
                for info in fwd:
                    run_section(info, envs[m], keys[m])
            else:
                # same per-microbatch key so RNG-consuming grad lowerings
                # replay the forward masks
                for info in bwd:
                    run_section(info, envs[m], keys[m])
                if m != M - 1:  # last env also feeds persistable write-back
                    envs[m] = {
                        k: v for k, v in envs[m].items() if k in keep_after_bwd
                    }
                live.discard(m)
        # test/diagnostic hooks: the executed interleave + activation bound
        self._pp_dispatch_log = dispatch_log
        self._pp_live_peak = live_peak

        # average raw grads across microbatches: one jitted reducer per
        # home stage (all parts already live on that stage's device)
        grad_avg: Dict[str, Any] = {}
        by_stage: Dict[int, Dict[str, List[Any]]] = {}
        for g in meta.grad_names:
            parts = [env[g] for env in envs if env is not None and g in env]
            if not parts:
                continue
            s = comp["grad_stage"].get(g)
            if s is None:
                grad_avg[g] = sum(parts) / float(len(parts))
            else:
                by_stage.setdefault(s, {})[g] = parts
        for s, parts in by_stage.items():
            grad_avg.update(comp["reducers"][s](parts))

        # one optimizer pass on the averaged grads (+ non-batch feeds: lr)
        opt_env = {
            n: v for n, v in feed_vals.items() if n not in meta.batch_feeds
        }
        opt_env.update(grad_avg)
        opt_key = jax.random.fold_in(base_key, M)
        for info in opt:
            run_section(info, opt_env, opt_key)

        # write back persistables: optimizer outputs + any forward/backward
        # persistable (e.g. BN running stats — last microbatch's value)
        for info in comp["sections"]:
            src_env = opt_env if info["sec"].phase == "optimize" else envs[-1]
            for n in info["persist"]:
                if n in src_env:
                    val = src_env[n]
                    scope.set(n, val)
                    # invalidate stale per-device copies, reseed the home one
                    for k in [k for k in comp["scope_cache"] if k[0] == n]:
                        del comp["scope_cache"][k]
                        comp["scope_src"].pop(k, None)
                    home = (n, list(val.devices())[0])
                    comp["scope_cache"][home] = val
                    comp["scope_src"][home] = val

        # fetches: per-microbatch values average (scalars) / concat (batched);
        # otherwise optimizer-phase or scope values
        results = []
        for n in fetch_names:
            if any(n in env for env in envs):
                vals = [env[n] for env in envs if n in env]
                if vals[0].ndim == 0 or vals[0].shape == (1,):
                    out = sum(jnp.mean(v) for v in vals) / len(vals)
                else:
                    out = jnp.concatenate(
                        [jax.device_put(v, list(vals[0].devices())[0]) for v in vals], axis=0
                    )
            elif n in opt_env:
                out = opt_env[n]
            elif scope.has(n):
                out = scope.get(n)
            else:
                raise RuntimeError(f"fetch {n!r} not produced by the pipeline")
            results.append(out)
        if return_numpy:
            return [np.asarray(r) for r in results]
        return results

    @staticmethod
    def _analyze_block(block, feed_names: Sequence[str], scope: Scope):
        """Find scope-resident vars the block reads before writing (inputs)
        and persistable vars it writes (stored back). Mirrors the variable
        scoping rules of reference executor.cc:103 (persistables live in the
        root scope; temporaries are per-run)."""
        written = set(feed_names)
        param_names: List[str] = []
        updated: List[str] = []
        seen_params = set()
        for op in block.ops:
            if op.type in _STRUCTURAL_OPS:
                continue
            for name in op.input_arg_names():
                if name in written or name in seen_params:
                    continue
                if scope.has(name):
                    seen_params.add(name)
                    param_names.append(name)
                else:
                    var = block._find_var_recursive(name)
                    pers = var.persistable if var is not None else False
                    raise _errs.attach_op_provenance(
                        _errs.errors.PreconditionNotMet(
                            f"op {op.type!r} reads variable {name!r} which is "
                            f"neither fed, produced earlier in the block, nor "
                            f"present in the scope (persistable={pers}). Run "
                            f"the startup program first."
                        ), op)
            for name in op.output_arg_names():
                written.add(name)
                var = block._find_var_recursive(name)
                if var is not None and var.persistable and name not in updated:
                    updated.append(name)
        return param_names, updated
