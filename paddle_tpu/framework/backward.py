"""Desc-level autodiff: append_backward / gradients.

Counterpart of the reference appender
(/root/reference/python/paddle/fluid/backward.py:1215 append_backward,
:1665 calc_gradient): walks the block's ops in reverse, emits one `<op>_grad`
op per differentiated forward op, seeds the loss gradient with a
fill_constant(1.0), and sums duplicated gradients. Unlike the reference —
where every op type ships a hand-written grad-op maker and grad kernels —
grad ops here default to a generic rule that applies the `jax.vjp` pullback
of the forward lowering (framework/registry.py), so autodiff coverage tracks
op coverage automatically. A generic grad op carries its forward op's inputs
and, as `__out__<slot>`, its outputs: by those output names the executor
finds the forward op in the block it traces, differentiates it where it is
traced, and hands the grad op that pullback (executor._GradPairing), so a
forward rule is traced once. Where the forward is not in the trace the grad
op makes the pullback afresh from the inputs it carries.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax.numpy as jnp
import numpy as np

from . import registry, unique_name
from .program import Block, Parameter, Variable
from .registry import GRAD_SUFFIX, grad_var_name


def _is_float_var(var: Variable) -> bool:
    try:
        return jnp.issubdtype(jnp.result_type(var.dtype), jnp.inexact)
    except Exception:
        return False


def _create_grad_var(block: Block, ref_var: Variable, name: str) -> Variable:
    return block.create_var(
        name=name,
        shape=ref_var.shape,
        dtype=ref_var.dtype,
        persistable=False,
        stop_gradient=True,
    )


def _compute_grad_needed(block: Block, start: Set[str], no_grad: Set[str]) -> Set[str]:
    """Forward-propagate "this var needs a gradient" from trainable leaves
    (and from grad_source ops, whose trainable state lives outside the
    program — e.g. pserver embedding tables)."""
    needed = set(start) - no_grad
    for op in block.ops:
        try:
            opdef = registry.get_op_def(op.type)
        except NotImplementedError:
            continue
        if opdef.stop_gradient:
            continue
        if opdef.grad_source or any(n in needed for n in op.input_arg_names()):
            for n in op.output_arg_names():
                var = block._find_var_recursive(n)
                if var is not None and not var.stop_gradient and n not in no_grad:
                    needed.add(n)
    return needed


def _diff_input_slots(op, opdef) -> List[str]:
    """Slots eligible for gradients: float-typed and not opted out."""
    slots = []
    for slot, vs in op._input_vars.items():
        if slot in opdef.no_grad_inputs or not vs:
            continue
        if all(_is_float_var(v) for v in vs):
            slots.append(slot)
    return slots


class _GradAccumulator:
    """Collects partial gradients per forward var; emits `sum` ops on
    finalization (reference backward.py `_addup_repetitive_outputs_`)."""

    def __init__(self, block: Block):
        self.block = block
        self.partials: Dict[str, List[Variable]] = {}
        self.final: Dict[str, Variable] = {}

    def add_partial(self, fwd_name: str, grad_var: Variable) -> None:
        self.partials.setdefault(fwd_name, []).append(grad_var)
        self.final.pop(fwd_name, None)

    def has(self, fwd_name: str) -> bool:
        return fwd_name in self.partials or fwd_name in self.final

    def set_final(self, fwd_name: str, grad_var: Variable) -> None:
        self.final[fwd_name] = grad_var
        self.partials.pop(fwd_name, None)

    def finalize(self, fwd_name: str) -> Optional[Variable]:
        if fwd_name in self.final:
            return self.final[fwd_name]
        parts = self.partials.get(fwd_name)
        if not parts:
            return None
        if len(parts) == 1:
            out = parts[0]
        else:
            out = _create_grad_var(
                self.block, parts[0], grad_var_name(fwd_name)
            )
            if out.name in (p.name for p in parts):
                out = self.block.create_var(
                    name=unique_name.generate(grad_var_name(fwd_name) + "@SUM"),
                    shape=parts[0].shape,
                    dtype=parts[0].dtype,
                    stop_gradient=True,
                )
            self.block.append_op("sum", inputs={"X": parts}, outputs={"Out": out})
        self.final[fwd_name] = out
        self.partials.pop(fwd_name, None)
        return out


def _resolve_params_and_no_grad(
    loss: Variable,
    parameter_list: Optional[Sequence],
    no_grad_set: Optional[Set[str]],
) -> Tuple[List[Variable], Set[str]]:
    """Shared preamble of the backward builders: the effective no-grad set
    (explicit + stop_gradient non-parameters) and the trainable params."""
    block = loss.block
    program = block.program
    no_grad = set(no_grad_set or ())
    for var in program.list_vars():
        if var.stop_gradient and not isinstance(var, Parameter):
            no_grad.add(var.name)
    if parameter_list is not None:
        params = [
            p if isinstance(p, Variable) else block.var(str(p))
            for p in parameter_list
        ]
    else:
        params = [p for p in program.all_parameters() if getattr(p, "trainable", True)]
    params = [p for p in params if not p.stop_gradient and p.name not in no_grad]
    return params, no_grad


def _seed_target_grad(block: Block, t: Variable) -> Variable:
    """fill_constant(1.0) seed for a target's gradient."""
    seed = block.create_var(
        name=unique_name.generate(grad_var_name(t.name)),
        shape=t.shape,
        dtype=t.dtype,
        stop_gradient=True,
    )
    block.append_op(
        "fill_constant",
        outputs={"Out": seed},
        attrs={
            "shape": list(t.shape),
            "value": 1.0,
            "dtype": np.dtype(t.dtype).name,
        },
    )
    return seed


def append_backward(
    loss: Variable,
    parameter_list: Optional[Sequence] = None,
    no_grad_set: Optional[Set[str]] = None,
    callbacks=None,
) -> List[Tuple[Parameter, Variable]]:
    """Append grad ops for `loss` to its block; return [(param, grad)].
    Reference contract: backward.py:1215."""
    params, no_grad = _resolve_params_and_no_grad(loss, parameter_list, no_grad_set)
    grads = calc_gradient(targets=[loss], inputs=params, no_grad_set=no_grad)
    return [(p, g) for p, g in zip(params, grads) if g is not None]


def append_backward_with_checkpoints(
    loss: Variable,
    checkpoints: Sequence,
    parameter_list: Optional[Sequence] = None,
    no_grad_set: Optional[Set[str]] = None,
) -> List[Tuple[Parameter, Variable]]:
    """append_backward with activation recomputation between checkpoints.

    Reference algorithm: optimizer.py:4518 RecomputeOptimizer +
    backward.py `_append_backward_ops_with_checkpoints_` — only the
    checkpoint activations are kept; each segment's forward ops are
    re-emitted (cloned with renamed outputs) right before that segment's
    grad ops, which read the recomputed clones.

    TPU adaptation: a desc-level clone alone would be undone by XLA common
    subexpression elimination. Every boundary value entering a cloned
    segment passes through a `recompute_barrier` op whose second input is
    the incoming cotangent of the segment — this both breaks CSE (the
    clone chain hangs off different values) and hands XLA's scheduler a
    data dependency that orders recomputation after the downstream
    backward, which is what actually frees the memory.
    """
    block = loss.block
    params, no_grad = _resolve_params_and_no_grad(loss, parameter_list, no_grad_set)

    fwd_ops = list(block.ops)
    produced_at: Dict[str, int] = {}
    for i, op in enumerate(fwd_ops):
        for n in op.output_arg_names():
            produced_at[n] = i
    ck_names = [
        c.name if isinstance(c, Variable) else str(c) for c in checkpoints
    ]
    ck_names = [c for c in ck_names if c in produced_at]
    ck_names.sort(key=lambda c: produced_at[c])
    if not ck_names:
        return append_backward(loss, parameter_list, no_grad_set)
    saved = set(ck_names)

    leaf_names = {p.name for p in params}
    grad_needed = _compute_grad_needed(block, leaf_names, no_grad)
    influencing = {loss.name}
    for op in reversed(fwd_ops):
        if any(n in influencing for n in op.output_arg_names()):
            influencing.update(op.input_arg_names())

    acc = _GradAccumulator(block)
    acc.set_final(loss.name, _seed_target_grad(block, loss))

    # tail region (after the last checkpoint): normal backward, activations kept
    last = produced_at[ck_names[-1]]
    _backward_over_ops(
        block, fwd_ops[last + 1:], acc, grad_needed, no_grad, influencing
    )

    # segment i covers fwd_ops[bounds[i]:bounds[i+1]); ck_names[i] is
    # produced by the last op of segment i
    bounds = [0] + [produced_at[c] + 1 for c in ck_names]
    for i in reversed(range(len(bounds) - 1)):
        seg_ops = fwd_ops[bounds[i]:bounds[i + 1]]
        dep = acc.finalize(ck_names[i])  # cotangent entering this segment
        var_subst = _clone_segment(block, seg_ops, saved, dep)
        _backward_over_ops(
            block, seg_ops, acc, grad_needed, no_grad, influencing,
            var_subst=var_subst,
        )

    grads = [acc.finalize(p.name) for p in params]
    return [(p, g) for p, g in zip(params, grads) if g is not None]


def _clone_segment(
    block: Block,
    seg_ops,
    saved: Set[str],
    dep: Optional[Variable],
) -> Dict[str, Variable]:
    """Re-emit `seg_ops` with renamed outputs; boundary inputs are read
    through `recompute_barrier`. Returns original-name -> the Variable the
    segment's grad ops read in its place: a clone's output, a boundary
    input's barriered value, a saved checkpoint's throwaway duplicate. So
    a grad op names exactly what its CLONE read and wrote, which is how
    the executor pairs the two (the clone is differentiated where it is
    traced; the original forward stays plain). Downstream forward ops keep
    reading the saved originals. Ops whose every output is saved need no
    clone. RNG-consuming clones keep the original op's attrs (same
    `_rng_id`), so dropout masks replay bit-identically."""
    subst: Dict[str, Variable] = {}
    barriered: Dict[str, Variable] = {}
    dups: Dict[str, Variable] = {}
    internal = set()
    for op in seg_ops:
        internal.update(op.output_arg_names())

    def boundary(v: Variable) -> Variable:
        # Every boundary input is barriered — including parameters: if a
        # clone's entire operand set were identical to the original op's
        # (e.g. a segment-entry op reading only params/feeds), XLA CSE
        # would merge it and the whole recomputed chain would collapse
        # back onto the saved activations. Parameters skip the Dep
        # ordering operand though: they are persistent leaves that cannot
        # be freed, so only the CSE break matters for them.
        if v.name in barriered:
            return barriered[v.name]
        out = block.create_var(
            name=unique_name.generate(v.name + "@RECOMPUTE.in"),
            shape=v.shape,
            dtype=v.dtype,
            stop_gradient=True,
        )
        ins = {"X": [v]}
        if dep is not None and not (isinstance(v, Parameter) or v.persistable):
            ins["Dep"] = [dep]
        block.append_op("recompute_barrier", inputs=ins, outputs={"Out": [out]})
        barriered[v.name] = out
        return out

    for op in seg_ops:
        outs = op.output_arg_names()
        if all(n in saved for n in outs):
            continue
        new_inputs: Dict[str, List[Variable]] = {}
        for slot, vs in op._input_vars.items():
            vals = []
            for v in vs:
                if v.name in subst:
                    vals.append(subst[v.name])
                elif v.name in internal and v.name not in saved:
                    vals.append(v)  # produced later in segment? keep (defensive)
                else:
                    vals.append(boundary(v))
            new_inputs[slot] = vals
        new_outputs: Dict[str, List[Variable]] = {}
        for slot, vs in op._output_vars.items():
            vals = []
            for v in vs:
                if v.name in saved:
                    # saved checkpoints keep their original buffer; route
                    # the clone's duplicate to a throwaway
                    nv = block.create_var(
                        name=unique_name.generate(v.name + "@RECOMPUTE.dup"),
                        shape=v.shape, dtype=v.dtype, stop_gradient=True,
                    )
                    dups[v.name] = nv
                else:
                    nv = block.create_var(
                        name=unique_name.generate(v.name + "@RECOMPUTE"),
                        shape=v.shape, dtype=v.dtype, stop_gradient=True,
                    )
                    subst[v.name] = nv
                vals.append(nv)
            new_outputs[slot] = vals
        block.append_op(op.type, inputs=new_inputs, outputs=new_outputs, attrs=op.all_attrs())
    return {**barriered, **dups, **subst}


def gradients(targets, inputs, target_gradients=None, no_grad_set=None):
    """Reference fluid.gradients (backward.py:1795)."""
    targets = targets if isinstance(targets, (list, tuple)) else [targets]
    inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    return calc_gradient(targets, inputs, target_gradients, set(no_grad_set or ()))


def calc_gradient(
    targets: Sequence[Variable],
    inputs: Sequence[Variable],
    target_gradients: Optional[Sequence[Variable]] = None,
    no_grad_set: Optional[Set[str]] = None,
) -> List[Optional[Variable]]:
    block = targets[0].block
    no_grad = set(no_grad_set or ())

    leaf_names = {v.name for v in inputs}
    grad_needed = _compute_grad_needed(block, leaf_names, no_grad)
    target_names = {t.name for t in targets}

    # vars that actually influence the targets (reverse reachability)
    influencing = set(target_names)
    fwd_ops = list(block.ops)
    for op in reversed(fwd_ops):
        if any(n in influencing for n in op.output_arg_names()):
            influencing.update(op.input_arg_names())

    acc = _GradAccumulator(block)

    # seed target gradients
    for i, t in enumerate(targets):
        if target_gradients is not None and i < len(target_gradients) and target_gradients[i] is not None:
            acc.set_final(t.name, target_gradients[i])
        else:
            acc.set_final(t.name, _seed_target_grad(block, t))

    _backward_over_ops(block, fwd_ops, acc, grad_needed, no_grad, influencing)

    results: List[Optional[Variable]] = []
    for v in inputs:
        g = acc.finalize(v.name)
        results.append(g)
    return results


def _backward_over_ops(
    block: Block,
    fwd_ops,
    acc: _GradAccumulator,
    grad_needed: Set[str],
    no_grad: Set[str],
    influencing: Set[str],
    var_subst: Optional[Dict[str, Variable]] = None,
) -> None:
    """Reverse-walk `fwd_ops` emitting grad ops into `block`. `var_subst`
    maps forward var names to replacement Variables read by the grad ops —
    the recompute path points saved activations at their recomputed clones
    while gradient accumulation keys stay on the original names."""
    sub = var_subst or {}

    def s(v: Variable) -> Variable:
        return sub.get(v.name, v)

    for op in reversed(list(fwd_ops)):
        try:
            opdef = registry.get_op_def(op.type)
        except NotImplementedError:
            continue
        if opdef.stop_gradient:
            continue
        out_names = op.output_arg_names()
        if not any(acc.has(n) for n in out_names):
            continue
        in_names = op.input_arg_names()
        # grad_source ops (pserver-backed lookups) have no in-program
        # trainable input, but their maker must still run to push the
        # out-gradient to the external state
        if not opdef.grad_source and not any(n in grad_needed for n in in_names):
            continue
        if not any(n in influencing for n in out_names):
            continue

        if opdef.grad_maker is not None:
            # keyword so existing 5-arg makers keep working; makers used
            # inside recomputed segments must honor var_subst or their
            # saved activations stay live past the checkpoint boundary
            try:
                opdef.grad_maker(
                    op, acc, block, grad_needed, no_grad, var_subst=sub
                )
            except TypeError:
                if sub:
                    raise NotImplementedError(
                        f"grad_maker for {op.type!r} does not accept "
                        f"var_subst and cannot be used inside a recompute "
                        f"segment"
                    )
                opdef.grad_maker(op, acc, block, grad_needed, no_grad)
            continue

        # wire the generic grad op
        g_inputs: Dict[str, List[Variable]] = {}
        for slot, vs in op._input_vars.items():
            if vs:
                g_inputs[slot] = [s(v) for v in vs]
        for slot, vs in op._output_vars.items():
            if vs:
                g_inputs["__out__" + slot] = [s(v) for v in vs]
        any_out_grad = False
        for slot, vs in op._output_vars.items():
            if not all(_is_float_var(v) for v in vs):
                continue  # integer outputs (indices etc.) carry no cotangent
            gvars = []
            for v in vs:
                g = acc.finalize(v.name)
                if g is None:
                    g = _create_grad_var(
                        block, v, unique_name.generate(grad_var_name(v.name) + "@ZERO")
                    )
                    block.append_op(
                        "fill_zeros_like", inputs={"X": s(v)}, outputs={"Out": g}
                    )
                else:
                    any_out_grad = True
                gvars.append(g)
            if gvars:
                g_inputs[slot + GRAD_SUFFIX] = gvars
        if not any_out_grad:
            continue

        g_outputs: Dict[str, List[Variable]] = {}
        record: List[Tuple[str, Variable]] = []
        for slot in _diff_input_slots(op, opdef):
            gvars = []
            for v in op._input_vars[slot]:
                gv = _create_grad_var(
                    block,
                    v,
                    unique_name.generate(grad_var_name(v.name) + "@RENAME"),
                )
                gvars.append(gv)
                if v.name in grad_needed and v.name not in no_grad:
                    record.append((v.name, gv))
            g_outputs[slot + GRAD_SUFFIX] = gvars
        if not g_outputs:
            continue

        block.append_op(
            op.type + "_grad",
            inputs=g_inputs,
            outputs=g_outputs,
            attrs=op.all_attrs(),
        )
        for fwd_name, gv in record:
            acc.add_partial(fwd_name, gv)
