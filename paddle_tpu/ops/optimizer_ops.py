"""Optimizer op lowerings: device-side parameter update rules.

Counterpart of the reference optimizer kernels
(/root/reference/paddle/fluid/operators/optimizers/: sgd_op.cc,
momentum_op.cc, adam_op.cc, lamb_op.cc, lars_momentum_op.cc, ...). In-place
Scope mutation (ParamOut aliasing Param) becomes donated-buffer threading:
the update is pure, and the executor stores the returned arrays back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..framework.registry import register_op


def _lr(ins):
    lr = ins["LearningRate"][0]
    return lr.reshape(()) if hasattr(lr, "reshape") else lr


def register_optimizer(name, fused=None):
    """register_op for update rules, with fp32 master arithmetic: inputs are
    upcast to fp32 for the update math and each `<Slot>Out` is cast back to
    the stored dtype of its `<Slot>` input. bf16's ~3 significant decimal
    digits cannot represent adam's m2 / beta_pow accumulators (the reference
    has the same split: fp32 master weights in its AMP decorator,
    /root/reference/python/paddle/fluid/contrib/mixed_precision/decorator.py).

    `fused(ctx, ins, attrs)` (optional) runs first on the RAW (un-upcast)
    inputs — a pallas single-pass kernel path; returning None selects the
    jnp rule."""

    def deco(fn):
        @functools.wraps(fn)  # __wrapped__ = the plain jnp rule
        def wrapped(ctx, ins, attrs):
            if fused is not None:
                res = fused(ctx, ins, attrs)
                if res is not None:
                    return res
            f32_ins = {
                slot: [
                    a.astype(jnp.float32)
                    if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating)
                    else a
                    for a in arrs
                ]
                for slot, arrs in ins.items()
            }
            outs = fn(ctx, f32_ins, attrs)
            res = {}
            irregular = {
                "SquaredAccumOut": "SquaredAccumulator",
                "LinearAccumOut": "LinearAccumulator",
            }
            for slot, val in outs.items():
                src = irregular.get(slot) or (slot[:-3] if slot.endswith("Out") else slot)
                ref = ins.get(src)
                if ref is not None and hasattr(val, "astype"):
                    val = val.astype(ref[0].dtype)
                res[slot] = val
            return res

        return register_op(name, stop_gradient=True)(wrapped)

    return deco


@register_optimizer("sgd")
def _sgd(ctx, ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    return {"ParamOut": p - _lr(ins) * g}


@register_optimizer("momentum")
def _momentum(ctx, ins, attrs):
    p, g, v = ins["Param"][0], ins["Grad"][0], ins["Velocity"][0]
    mu = attrs.get("mu", 0.9)
    lr = _lr(ins)
    rd = attrs.get("regularization_coeff", 0.0)
    if attrs.get("regularization_method", "") == "l2_decay" and rd:
        g = g + rd * p
    v_out = mu * v + g
    if attrs.get("use_nesterov", False):
        p_out = p - (g + mu * v_out) * lr
    else:
        p_out = p - lr * v_out
    return {"ParamOut": p_out, "VelocityOut": v_out}


def _adam_fused_maybe(ctx, ins, attrs, weight_decay):
    """Single-pass pallas adam for tile-aligned 2-D params on TPU (the hot
    buffers: embeddings and weight matrices). Returns None to select the
    jnp rule: off TPU, for odd shapes, and in mesh programs — GSPMD cannot
    partition a Mosaic call, and this lowering does not know the
    parameter's PartitionSpec to open a shard_map region around it, while
    XLA partitions the elementwise jnp rule for free."""
    import os

    if os.environ.get("PADDLE_TPU_DISABLE_FUSED_ADAM"):
        return None
    from .pallas import fused_adam as fa
    from .pallas.backend import on_tpu

    mesh = getattr(ctx, "mesh", None)
    if not on_tpu() or (mesh is not None and mesh.size > 1):
        return None

    p, g = ins["Param"][0], ins["Grad"][0]
    m1, m2 = ins["Moment1"][0], ins["Moment2"][0]
    if not fa.supported(p, g, m1, m2):
        return None
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    b1p, b2p = ins["Beta1Pow"][0], ins["Beta2Pow"][0]
    p_out, m1_out, m2_out = fa.fused_adam(
        p, g, m1, m2, _lr(ins), b1p, b2p,
        beta1=b1, beta2=b2, eps=eps, weight_decay=weight_decay,
    )
    return {
        "ParamOut": p_out,
        "Moment1Out": m1_out.astype(m1.dtype),
        "Moment2Out": m2_out.astype(m2.dtype),
        "Beta1PowOut": b1p * b1,
        "Beta2PowOut": b2p * b2,
    }


@register_optimizer(
    "adam", fused=lambda ctx, ins, attrs: _adam_fused_maybe(ctx, ins, attrs, 0.0))
def _adam(ctx, ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    m1, m2 = ins["Moment1"][0], ins["Moment2"][0]
    b1p, b2p = ins["Beta1Pow"][0], ins["Beta2Pow"][0]
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    lr = _lr(ins)
    m1_out = b1 * m1 + (1 - b1) * g
    m2_out = b2 * m2 + (1 - b2) * jnp.square(g)
    denom = jnp.sqrt(m2_out) / jnp.sqrt(1 - b2p.reshape(())) + eps
    p_out = p - lr * (m1_out / denom) / (1 - b1p.reshape(()))
    return {
        "ParamOut": p_out,
        "Moment1Out": m1_out,
        "Moment2Out": m2_out,
        "Beta1PowOut": b1p * b1,
        "Beta2PowOut": b2p * b2,
    }


def _adamw_fused(ctx, ins, attrs):
    coeff = attrs.get("coeff", 0.01) if attrs.get("with_decay", True) else 0.0
    return _adam_fused_maybe(ctx, ins, attrs, coeff)


@register_optimizer("adamw", fused=_adamw_fused)
def _adamw(ctx, ins, attrs):
    p = ins["Param"][0]
    coeff = attrs.get("coeff", 0.01)
    lr = _lr(ins)
    with_decay = attrs.get("with_decay", True)
    out = _adam(ctx, ins, attrs)
    if with_decay:
        out["ParamOut"] = out["ParamOut"] - lr * coeff * p
    return out


@register_optimizer("adamax")
def _adamax(ctx, ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    m, inf = ins["Moment"][0], ins["InfNorm"][0]
    b1p = ins["Beta1Pow"][0]
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    lr = _lr(ins)
    m_out = b1 * m + (1 - b1) * g
    inf_out = jnp.maximum(b2 * inf, jnp.abs(g) + eps)
    p_out = p - (lr / (1 - b1p.reshape(()))) * (m_out / inf_out)
    return {"ParamOut": p_out, "MomentOut": m_out, "InfNormOut": inf_out}


@register_optimizer("adagrad")
def _adagrad(ctx, ins, attrs):
    p, g, mom = ins["Param"][0], ins["Grad"][0], ins["Moment"][0]
    eps = attrs.get("epsilon", 1e-6)
    mom_out = mom + jnp.square(g)
    p_out = p - _lr(ins) * g / (jnp.sqrt(mom_out) + eps)
    return {"ParamOut": p_out, "MomentOut": mom_out}


@register_optimizer("rmsprop")
def _rmsprop(ctx, ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    ms, mom = ins["MeanSquare"][0], ins["Moment"][0]
    rho = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    momentum = attrs.get("momentum", 0.0)
    lr = _lr(ins)
    if attrs.get("centered", False):
        mg = ins["MeanGrad"][0]
        ms_out = rho * ms + (1 - rho) * jnp.square(g)
        mg_out = rho * mg + (1 - rho) * g
        mom_out = momentum * mom + lr * g / jnp.sqrt(ms_out - jnp.square(mg_out) + eps)
        return {
            "ParamOut": p - mom_out,
            "MeanSquareOut": ms_out,
            "MeanGradOut": mg_out,
            "MomentOut": mom_out,
        }
    ms_out = rho * ms + (1 - rho) * jnp.square(g)
    mom_out = momentum * mom + lr * g / jnp.sqrt(ms_out + eps)
    return {
        "ParamOut": p - mom_out,
        "MeanSquareOut": ms_out,
        "MomentOut": mom_out,
    }


@register_optimizer("adadelta")
def _adadelta(ctx, ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    avg_sq, avg_up = ins["AvgSquaredGrad"][0], ins["AvgSquaredUpdate"][0]
    rho = attrs.get("rho", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    sq_out = rho * avg_sq + (1 - rho) * jnp.square(g)
    update = -jnp.sqrt((avg_up + eps) / (sq_out + eps)) * g
    up_out = rho * avg_up + (1 - rho) * jnp.square(update)
    return {
        "ParamOut": p + update,
        "AvgSquaredGradOut": sq_out,
        "AvgSquaredUpdateOut": up_out,
    }


@register_optimizer("lamb")
def _lamb(ctx, ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    m1, m2 = ins["Moment1"][0], ins["Moment2"][0]
    b1p, b2p = ins["Beta1Pow"][0], ins["Beta2Pow"][0]
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-6)
    wd = attrs.get("weight_decay", 0.01)
    lr = _lr(ins)
    m1_out = b1 * m1 + (1 - b1) * g
    m2_out = b2 * m2 + (1 - b2) * jnp.square(g)
    m1_hat = m1_out / (1 - b1p.reshape(()))
    m2_hat = m2_out / (1 - b2p.reshape(()))
    r = m1_hat / (jnp.sqrt(m2_hat) + eps) + wd * p
    w_norm = jnp.linalg.norm(p)
    r_norm = jnp.linalg.norm(r)
    trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
    p_out = p - lr * trust * r
    return {
        "ParamOut": p_out,
        "Moment1Out": m1_out,
        "Moment2Out": m2_out,
        "Beta1PowOut": b1p * b1,
        "Beta2PowOut": b2p * b2,
    }


@register_optimizer("lars_momentum")
def _lars_momentum(ctx, ins, attrs):
    p, g, v = ins["Param"][0], ins["Grad"][0], ins["Velocity"][0]
    mu = attrs.get("mu", 0.9)
    coeff = attrs.get("lars_coeff", 0.001)
    wd = attrs.get("lars_weight_decay", 0.0005)
    eps = attrs.get("epsilon", 0.0)
    lr = _lr(ins)
    p_norm = jnp.linalg.norm(p)
    g_norm = jnp.linalg.norm(g)
    local_lr = jnp.where(
        (p_norm > 0) & (g_norm > 0),
        lr * coeff * p_norm / (g_norm + wd * p_norm + eps),
        lr,
    )
    v_out = mu * v + local_lr * (g + wd * p)
    return {"ParamOut": p - v_out, "VelocityOut": v_out}


@register_optimizer("ftrl")
def _ftrl(ctx, ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    sq, lin = ins["SquaredAccumulator"][0], ins["LinearAccumulator"][0]
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    power = attrs.get("lr_power", -0.5)
    lr = _lr(ins)
    new_sq = sq + jnp.square(g)
    if power == -0.5:
        sigma = (jnp.sqrt(new_sq) - jnp.sqrt(sq)) / lr
    else:
        sigma = (jnp.power(new_sq, -power) - jnp.power(sq, -power)) / lr
    lin_out = lin + g - sigma * p
    if power == -0.5:
        denom = jnp.sqrt(new_sq) / lr + 2 * l2
    else:
        denom = jnp.power(new_sq, -power) / lr + 2 * l2
    pre = jnp.clip(lin_out, -l1, l1) - lin_out
    p_out = pre / denom
    return {"ParamOut": p_out, "SquaredAccumOut": new_sq, "LinearAccumOut": lin_out}


@register_op("dpsgd", stop_gradient=True, uses_rng=True)
def _dpsgd(ctx, ins, attrs):
    import jax.random as jrandom

    p, g = ins["Param"][0], ins["Grad"][0]
    clip = attrs.get("clip", 10.0)
    batch_size = attrs.get("batch_size", 16.0)
    sigma = attrs.get("sigma", 1.0)
    g_norm = jnp.linalg.norm(g)
    g = g / jnp.maximum(1.0, g_norm / clip)
    noise = sigma * clip * jrandom.normal(ctx.rng(attrs.get("_rng_id", 0)), g.shape)
    return {"ParamOut": (p - _lr(ins) * (g + noise) / batch_size).astype(p.dtype)}


# -- AMP support ops (reference operators/amp/) -----------------------------


@register_op("check_finite_and_unscale", stop_gradient=True)
def _check_finite_and_unscale(ctx, ins, attrs):
    scale = ins["Scale"][0].reshape(())
    xs = ins["X"]
    found_inf = jnp.zeros((), jnp.bool_)
    outs = []
    for v in xs:
        finite = jnp.all(jnp.isfinite(v))
        found_inf = found_inf | ~finite
        outs.append(v / scale)
    return {"Out": outs, "FoundInfinite": found_inf.reshape((1,))}


@register_op("update_loss_scaling", stop_gradient=True)
def _update_loss_scaling(ctx, ins, attrs):
    found_inf = ins["FoundInfinite"][0].reshape(())
    prev_scale = ins["PrevLossScaling"][0].reshape(())
    good = ins["InGoodSteps"][0].reshape(())
    bad = ins["InBadSteps"][0].reshape(())
    incr_every = attrs.get("incr_every_n_steps", 1000)
    decr_every = attrs.get("decr_every_n_nan_or_inf", 2)
    incr_ratio = attrs.get("incr_ratio", 2.0)
    decr_ratio = attrs.get("decr_ratio", 0.5)
    good_new = jnp.where(found_inf, 0, good + 1)
    bad_new = jnp.where(found_inf, bad + 1, 0)
    scale_up = good_new >= incr_every
    scale_down = bad_new >= decr_every
    new_scale = jnp.where(
        scale_down,
        jnp.maximum(prev_scale * decr_ratio, 1.0),
        jnp.where(scale_up, prev_scale * incr_ratio, prev_scale),
    )
    good_new = jnp.where(scale_up, 0, good_new)
    bad_new = jnp.where(scale_down, 0, bad_new)
    outs = list(ins.get("X", []))
    zero_if_inf = [jnp.where(found_inf, jnp.zeros_like(v), v) for v in outs]
    return {
        "Out": zero_if_inf,
        "LossScaling": new_scale.reshape((1,)),
        "OutGoodSteps": good_new.astype(jnp.int32).reshape((1,)),
        "OutBadSteps": bad_new.astype(jnp.int32).reshape((1,)),
    }


@register_optimizer("decayed_adagrad")
def _decayed_adagrad(ctx, ins, attrs):
    p, g, m = ins["Param"][0], ins["Grad"][0], ins["Moment"][0]
    decay = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    m_new = decay * m + (1 - decay) * g * g
    return {"ParamOut": p - _lr(ins) * g / (jnp.sqrt(m_new) + eps),
            "MomentOut": m_new}


@register_optimizer("proximal_gd")
def _proximal_gd(ctx, ins, attrs):
    """FOBOS step (optimizers/proximal_gd_op.h): l1 shrinkage + l2 decay
    of the plain SGD iterate."""
    p, g = ins["Param"][0], ins["Grad"][0]
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    lr = _lr(ins)
    prox = p - lr * g
    out = jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0) / (1.0 + lr * l2)
    return {"ParamOut": out}


@register_optimizer("proximal_adagrad")
def _proximal_adagrad(ctx, ins, attrs):
    p, g, m = ins["Param"][0], ins["Grad"][0], ins["Moment"][0]
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    m_new = m + g * g
    lr_eff = _lr(ins) / jnp.sqrt(m_new + 1e-10)
    prox = p - lr_eff * g
    out = jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr_eff * l1, 0.0) / (1.0 + lr_eff * l2)
    return {"ParamOut": out, "MomentOut": m_new}


@register_op("dgc_clip_by_norm", stop_gradient=True)
def _dgc_clip_by_norm(ctx, ins, attrs):
    """clip_by_norm gated on the DGC rampup step (optimizers/
    dgc_momentum_op.h pattern): before rampup_begin_step, pass through."""
    v = ins["X"][0]
    step = ins["current_step"][0].reshape(())
    begin = attrs.get("rampup_begin_step", 0.0)
    max_norm = attrs.get("max_norm", 1.0)
    norm = jnp.sqrt(jnp.sum(v.astype(jnp.float32) ** 2))
    clipped = v * jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-10)).astype(v.dtype)
    return {"Out": jnp.where(step < begin, v, clipped)}


@register_op("dgc_momentum", stop_gradient=True)
def _dgc_momentum(ctx, ins, attrs):
    """MOMENTUM before rampup_begin_step, plain SGD after
    (dgc_momentum_op.h:64-70): once compression starts, momentum lives in
    the dgc op's U accumulator, so applying it again here would double
    it and diverge."""
    p, g, vel = ins["Param"][0], ins["Grad"][0], ins["Velocity"][0]
    lr = ins["LearningRate"][0].reshape(())
    step = ins["current_step"][0].reshape(())
    mu = attrs.get("mu", 0.9)
    begin = attrs.get("rampup_begin_step", 0.0)
    nesterov = attrs.get("use_nesterov", False)
    vel_new = mu * vel + g
    p_mom = p - lr * (g + mu * vel_new if nesterov else vel_new)
    p_sgd = p - lr * g
    use_momentum = step < begin
    return {
        "ParamOut": jnp.where(use_momentum, p_mom, p_sgd),
        "VelocityOut": jnp.where(use_momentum, vel_new, vel),
    }


@register_op("dgc", stop_gradient=True)
def _dgc(ctx, ins, attrs):
    """Deep gradient compression (dgc_op.h): momentum-correct locally (U),
    accumulate (V), keep the top-s fraction of |V| (threshold from top_k),
    emit the sparse gradient, keep the residual as error feedback."""
    u, v, g = ins["U"][0], ins["V"][0], ins["Grad"][0]
    step = ins["current_step"][0].reshape(())
    m = attrs.get("m", 0.9)
    ratio = attrs.get("ratio", 0.001)
    begin = attrs.get("rampup_begin_step", 0.0)
    use_momentum = attrs.get("use_local_momentum", True)
    k = max(1, int(ratio * g.size))

    u_new = m * u + g if use_momentum else u + g
    v_new = v + u_new
    flat = jnp.abs(v_new.reshape(-1))
    thr = jax.lax.top_k(flat, k)[0][-1]
    mask = jnp.abs(v_new) >= thr
    encoded = jnp.where(mask, v_new, 0.0)
    v_out = jnp.where(mask, 0.0, v_new)
    u_out = jnp.where(mask, 0.0, u_new)
    # before rampup: no compression, plain grad passes through
    active = step >= begin
    return {
        "U_out": jnp.where(active, u_out, u),
        "V_out": jnp.where(active, v_out, v),
        "EncodeGrad": jnp.where(active, encoded, g),
        "Grad_out": jnp.where(active, encoded, g),
        "GatherBuff": jnp.zeros_like(g),
        "k": jnp.asarray(float(k)),
    }


@register_op("average_accumulates", stop_gradient=True)
def _average_accumulates(ctx, ins, attrs):
    """ModelAverage accumulator shuffle (average_accumulates_op.h):
    sum_1 accumulates params; on window overflow sums shift down."""
    p = ins["param"][0]
    s1, s2, s3 = ins["in_sum_1"][0], ins["in_sum_2"][0], ins["in_sum_3"][0]
    n_acc = ins["in_num_accumulates"][0].reshape(())
    o_acc = ins["in_old_num_accumulates"][0].reshape(())
    n_upd = ins["in_num_updates"][0].reshape(())
    avg_window = attrs.get("average_window", 0.0)
    max_avg = attrs.get("max_average_window", 10000)
    min_avg = attrs.get("min_average_window", 10000)

    n_acc = n_acc + 1
    n_upd = n_upd + 1
    s1 = s1 + p
    window = jnp.maximum(
        jnp.minimum(jnp.asarray(max_avg, n_upd.dtype),
                    (n_upd.astype(jnp.float32) * avg_window).astype(n_upd.dtype)),
        jnp.asarray(min_avg, n_upd.dtype),
    )
    overflow = n_acc >= window
    s3_n = jnp.where(overflow, s1 + s2, s3 * 0 + s3)
    s1_n = jnp.where(overflow, jnp.zeros_like(s1), s1)
    s2_n = jnp.where(overflow, jnp.zeros_like(s2), s2)
    o_acc_n = jnp.where(overflow, n_acc, o_acc)
    n_acc_n = jnp.where(overflow, jnp.zeros_like(n_acc), n_acc)
    return {
        "out_sum_1": s1_n, "out_sum_2": s2_n, "out_sum_3": s3_n,
        "out_num_accumulates": n_acc_n.reshape(1),
        "out_old_num_accumulates": o_acc_n.reshape(1),
        "out_num_updates": n_upd.reshape(1),
    }
