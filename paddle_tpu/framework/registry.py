"""Op registry + lowering rules.

TPU-native counterpart of the reference operator registry
(/root/reference/paddle/fluid/framework/op_registry.h:68,223,265 and
operator.h:130): where the reference registers a C++ `OperatorWithKernel`
subclass plus per-device kernels per op, here an op registers a single
*lowering rule* — a pure JAX function from input arrays to output arrays.
The executor stitches lowering rules for a whole block into one function and
jit-compiles it, so "kernel choice" (operator.cc:1068) becomes XLA's job.

Three reference subsystems collapse into this design:
  * InferShape (shape_inference.h) -> `jax.eval_shape` over the lowering rule;
  * grad-op makers (grad_op_desc_maker.h) -> a generic `<op>_grad` that
    applies the `jax.vjp` pullback of the forward rule: the one its
    forward op made where the executor traced it, or, where that forward
    is not in the trace, one made afresh from the forward inputs;
  * AMP autocast lists -> dtype promotion inside rules (bf16-first).
Custom overrides remain possible per op for all three.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import core

# Sentinel used to stand in for a dynamic (-1) dim during builder-time shape
# inference; any inferred dim >= _DYN is mapped back to -1.
_DYN = 1 << 22


class LoweringContext:
    """Per-trace state handed to lowering rules: the PRNG key for this step,
    the active device mesh (None single-chip), and train/eval mode."""

    def __init__(self, rng_key=None, mesh=None, training: bool = True,
                 var_constraints=None):
        if rng_key is None:
            rng_key = jax.random.key(0)
        self.rng_key = rng_key
        self.mesh = mesh
        self.training = training
        # [(compiled regex, PartitionSpec axes)] applied to matching op
        # OUTPUT vars via with_sharding_constraint during lowering — how
        # ZeRO-2 pins gradient layouts without materialized grad buffers
        self.var_constraints = var_constraints or []

    def rng(self, rng_id: int):
        """Stable per-op key: forward and its grad replay identical randomness
        by folding the same op id into the step key."""
        return jax.random.fold_in(self.rng_key, int(rng_id))


InsDict = Dict[str, List[Any]]
LowerFn = Callable[[LoweringContext, InsDict, Dict[str, Any]], Dict[str, Any]]


@dataclass
class OpDef:
    type: str
    lower: LowerFn
    # custom builder-time inference: fn(op) -> None, sets output var shapes
    infer: Optional[Callable] = None
    # input slots that never receive gradient (e.g. integer indices)
    no_grad_inputs: frozenset = field(default_factory=frozenset)
    # custom desc-level grad maker: fn(op, grad_out_names) -> list of
    # (type, inputs, outputs, attrs) tuples. None -> generic maker.
    grad_maker: Optional[Callable] = None
    # ops with no gradient at all (metrics, optimizers, IO)
    stop_gradient: bool = False
    # does the rule consume ctx.rng? (needs a stable _rng_id attr)
    uses_rng: bool = False
    # skip eval_shape inference entirely (collectives outside mesh, IO ops)
    skip_infer: bool = False
    # runs on host with concrete values (dynamic output shapes: unique,
    # where_index, ...): the executor drops to eager segment execution for
    # blocks containing such ops instead of jitting the whole block
    host: bool = False
    # outputs carry gradient even when no input does — ops that SOURCE
    # trainable state from outside the program (distributed_lookup_table
    # reads pserver-resident embedding rows; its only in-program input is
    # the integer Ids, which the grad_needed forward propagation would
    # never mark)
    grad_source: bool = False


_REGISTRY: Dict[str, OpDef] = {}


def register_op(
    type: str,
    *,
    infer: Optional[Callable] = None,
    no_grad_inputs: Sequence[str] = (),
    grad_maker: Optional[Callable] = None,
    stop_gradient: bool = False,
    uses_rng: bool = False,
    skip_infer: bool = False,
    grad_source: bool = False,
    host: bool = False,
):
    """Decorator: register `fn(ctx, ins, attrs) -> {slot: array|list}` as the
    lowering rule for op `type`."""

    def deco(fn: LowerFn):
        _REGISTRY[type] = OpDef(
            type=type,
            lower=fn,
            infer=infer,
            no_grad_inputs=frozenset(no_grad_inputs),
            grad_maker=grad_maker,
            stop_gradient=stop_gradient,
            uses_rng=uses_rng,
            skip_infer=skip_infer,
            grad_source=grad_source,
            host=host,
        )
        return fn

    return deco


def get_op_def(type: str) -> OpDef:
    _ensure_ops_loaded()
    if type in _REGISTRY:
        return _REGISTRY[type]
    if type.endswith("_grad"):
        fwd = _REGISTRY.get(type[: -len("_grad")])
        if fwd is not None:
            gdef = _make_generic_grad_def(fwd)
            _REGISTRY[type] = gdef
            return gdef
    # UnimplementedError is ALSO a NotImplementedError, so the existing
    # `except NotImplementedError` probes (host-op scan, grad walker)
    # keep working while callers get a typed, code-carrying error
    from . import errors as _errs

    raise _errs.errors.Unimplemented(
        f"no lowering registered for op {type!r}")


def has_op(type: str) -> bool:
    _ensure_ops_loaded()
    if type in _REGISTRY:
        return True
    return type.endswith("_grad") and type[: -len("_grad")] in _REGISTRY


def registered_ops() -> List[str]:
    _ensure_ops_loaded()
    return sorted(_REGISTRY)


_ops_loaded = False


def _ensure_ops_loaded():
    global _ops_loaded
    if not _ops_loaded:
        _ops_loaded = True
        from .. import ops as _ops  # noqa: F401  (registers everything)


# ---------------------------------------------------------------------------
# normalization helpers
# ---------------------------------------------------------------------------


def normalize_outs(out) -> Dict[str, List[Any]]:
    """lower() may return {slot: array} or {slot: [arrays]}; normalize."""
    norm = {}
    for k, v in out.items():
        if v is None:
            norm[k] = []
        elif isinstance(v, (list, tuple)):
            norm[k] = list(v)
        else:
            norm[k] = [v]
    return norm


def run_lowering(opdef: OpDef, ctx: LoweringContext, ins: InsDict, attrs) -> Dict[str, List[Any]]:
    return normalize_outs(opdef.lower(ctx, ins, attrs))


# ---------------------------------------------------------------------------
# builder-time shape/dtype inference (replaces reference InferShape)
# ---------------------------------------------------------------------------


def _canon_dtype(dt):
    return jax.dtypes.canonicalize_dtype(core.convert_dtype(dt))


def _var_struct(var):
    shape = tuple(_DYN if d == -1 else int(d) for d in var.shape)
    return jax.ShapeDtypeStruct(shape, _canon_dtype(var.dtype))


def _apply_struct(var, struct):
    dims = tuple(-1 if d >= _DYN else int(d) for d in struct.shape)
    var.shape = dims
    var.dtype = struct.dtype


def assign_rng_id(op) -> None:
    """Give RNG-consuming ops a stable per-program fold-in id (set once at
    op creation so forward and grad replays share randomness)."""
    try:
        opdef = get_op_def(op.type)
    except NotImplementedError:
        return
    if opdef.uses_rng and not op.has_attr("_rng_id"):
        prog = op.block.program
        op._set_attr("_rng_id", prog._rng_op_count)
        prog._rng_op_count += 1


def infer_op(op) -> None:
    """Infer output shapes/dtypes for a freshly built Operator by abstract
    evaluation of its lowering rule (TPU-first replacement for per-op C++
    InferShape, reference operator.cc:1002)."""
    if op.type in ("feed", "fetch"):
        return
    # unknown op types raise here (at graph-build time), not silently at
    # lowering time with a missing-shape error downstream
    try:
        opdef = get_op_def(op.type)
    except NotImplementedError as e:  # errors.Unimplemented: add build site
        from . import errors as _errs

        raise _errs.attach_op_provenance(e, op)
    if opdef.skip_infer:
        return
    if opdef.infer is not None:
        opdef.infer(op)
        return

    ins = {
        slot: [_var_struct(v) for v in vs]
        for slot, vs in op._input_vars.items()
        if vs
    }
    attrs = op.all_attrs()
    ctx = LoweringContext(training=True)

    def f(ins_):
        return run_lowering(opdef, ctx, ins_, attrs)

    try:
        outs = jax.eval_shape(f, ins)
    except Exception as e:  # surface with op context, like PADDLE_ENFORCE
        from . import errors as _errs

        shown = {k: v for k, v in attrs.items() if k != "op_callstack"}
        err = _errs.errors.InvalidArgument(
            f"shape inference failed for op {op.type!r} "
            f"(inputs={{{', '.join(f'{k}: {[tuple(v.shape) for v in vs]}' for k, vs in op._input_vars.items())}}}, "
            f"attrs={shown}): {e}"
        )
        err.__cause__ = e
        raise _errs.attach_op_provenance(err, op)

    for slot, out_vars in op._output_vars.items():
        structs = outs.get(slot, [])
        for var, st in zip(out_vars, structs):
            _apply_struct(var, st)


# ---------------------------------------------------------------------------
# generic gradient (replaces reference grad-op makers + grad kernels)
# ---------------------------------------------------------------------------

GRAD_SUFFIX = "@GRAD"


def grad_var_name(name: str) -> str:
    return name + GRAD_SUFFIX


def _is_diff_dtype(x) -> bool:
    return jnp.issubdtype(jnp.result_type(x), jnp.inexact)


class Pullback:
    """What differentiating one forward op where it was traced leaves for
    its `<op>_grad`: the values the forward consumed, its cotangent-
    carrying outputs, and the `jax.vjp` pullback over the differentiable
    inputs. Calling it on a grad op's `ins` builds the cotangents (zeros
    for a missing `@GRAD`) and returns `{<in_slot>@GRAD: arrays}`."""

    def __init__(self, fwd_ins: InsDict, outs: Dict[str, List[Any]], vjp):
        self.fwd_ins = fwd_ins
        self.outs = outs
        self.vjp = vjp

    def read_by(self, ins: InsDict) -> bool:
        """Does a grad op's `ins` hold, for every forward input, the very
        value the forward consumed? (A variable rewritten in between is
        another object, and its grad op must differentiate afresh.)"""
        theirs = forward_inputs(ins)
        return theirs.keys() == self.fwd_ins.keys() and all(
            len(theirs[slot]) == len(mine)
            and all(a is b for a, b in zip(theirs[slot], mine))
            for slot, mine in self.fwd_ins.items())

    def __call__(self, ins: InsDict) -> Dict[str, Any]:
        cot = {}
        for slot, arrs in self.outs.items():
            gs = list(ins.get(slot + GRAD_SUFFIX, []))
            gs += [None] * (len(arrs) - len(gs))
            cot[slot] = [jnp.zeros_like(a) if g is None else g
                         for a, g in zip(arrs, gs)]
        (gins,) = self.vjp(cot)
        return {slot + GRAD_SUFFIX: arrs for slot, arrs in gins.items()}


def forward_inputs(ins: InsDict) -> InsDict:
    """The forward op's own inputs among a generic grad op's: not the
    `<slot>@GRAD` cotangents, and not the forward outputs, which grad-op
    builders tag `__out__<slot>` to tell them from same-named inputs."""
    return {k: v for k, v in ins.items()
            if not k.endswith(GRAD_SUFFIX) and not k.startswith("__out__")}


def cotangent_slots(slots) -> set:
    """Forward output slots that the grad-op slots `slots` bring a
    cotangent for."""
    return {k[: -len(GRAD_SUFFIX)] for k in slots if k.endswith(GRAD_SUFFIX)}


def lower_differentiated(fwd: OpDef, ctx: LoweringContext, ins: InsDict,
                         attrs, cot_slots) -> tuple:
    """Trace `fwd`'s rule ONCE, under `jax.vjp` over its differentiable
    inputs: returns (every output slot, normalized; the `Pullback`).
    Only float outputs in `cot_slots` are differentiated; the rest ride
    along as aux (integer outputs included), so the caller can still
    write every output slot."""
    diff, fixed = {}, {}
    for slot, arrs in ins.items():
        if slot in fwd.no_grad_inputs or not all(_is_diff_dtype(a) for a in arrs):
            fixed[slot] = arrs
        else:
            diff[slot] = arrs

    def f(diff_):
        outs = run_lowering(fwd, ctx, {**fixed, **diff_}, attrs)
        carrying = {
            k: v for k, v in outs.items()
            if k in cot_slots and all(_is_diff_dtype(a) for a in v)
        }
        return carrying, outs

    carrying, vjp, outs = jax.vjp(f, diff, has_aux=True)
    return outs, Pullback(ins, carrying, vjp)


class GenericGrad:
    """Lowering rule of every `<op>_grad` without a rule of its own: the
    pullback of the forward rule. The executor hands a grad op the
    pullback its forward op made where it was traced
    (executor._GradPairing); called as a rule, as here, it differentiates
    the forward rule afresh over the forward inputs the grad op carries,
    which traces that rule a second time (XLA merges the copy where it
    lowered to XLA operations, and runs a Mosaic kernel twice).

    Grad-op contract (mirrors reference GradOpDescMaker conventions):
      inputs : forward input slots, forward output slots (`__out__<slot>`),
               and `<out_slot>@GRAD` cotangent slots;
      outputs: `<in_slot>@GRAD` for differentiable forward inputs.
    """

    def __init__(self, fwd: OpDef):
        self.fwd = fwd

    def __call__(self, ctx: LoweringContext, ins: InsDict, attrs) -> Dict[str, Any]:
        _, pullback = lower_differentiated(
            self.fwd, ctx, forward_inputs(ins), attrs, cotangent_slots(ins))
        return pullback(ins)


def generic_grad_forward(type: str) -> Optional[OpDef]:
    """The forward OpDef if op `type` lowers by the generic grad rule."""
    if not type.endswith("_grad"):
        return None
    try:
        rule = get_op_def(type).lower
    except NotImplementedError:
        return None
    return rule.fwd if isinstance(rule, GenericGrad) else None


def _make_generic_grad_def(fwd: OpDef) -> OpDef:
    """Build `<op>_grad`: the `GenericGrad` rule, and an inference that
    gives each `<in_slot>@GRAD` the shape and dtype of its input."""

    def ginfer(op) -> None:
        # d(input) has the shape/dtype of the input itself
        for slot, out_vars in op._output_vars.items():
            if not slot.endswith(GRAD_SUFFIX):
                continue
            src = op._input_vars.get(slot[: -len(GRAD_SUFFIX)], [])
            for var, s in zip(out_vars, src):
                if s is not None:
                    var.shape = s.shape
                    var.dtype = s.dtype

    return OpDef(
        type=fwd.type + "_grad",
        lower=GenericGrad(fwd),
        infer=ginfer,
        stop_gradient=True,
        uses_rng=fwd.uses_rng,
    )
