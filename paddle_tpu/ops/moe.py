"""A sparse expert layer (router + SwiGLU experts), drop-free.

    p        = softmax_float32(x @ router_w)                 [N, E]
    top-k of p, its weights used as they are (not rescaled to sum to 1)
    y[n]     = sum_{e in topk(n)} p[n, e] * down_e(silu(gate_e x[n]) * up_e x[n])

(the default router; ``route`` also scores by a sigmoid, selects under a
per-expert bias that the weights do not carry, limits the selection to
the best few of the groups the experts lie in, and rescales the top-k
weights to sum to 1, as the block's description asks)

Pure ``jax.numpy`` on stacked expert weights (``gate``, ``up``:
``[E, D, F]``; ``down``: ``[E, F, D]``), for the serving programs: no
graph op is registered, training a model with experts is not built yet.

How it is computed: EVERY expert on EVERY token, and a token's routing
weights (zero off its top-k) pick what counts. Nothing is sorted,
gathered or capped, so no assignment can be dropped at any skew, and a
token's result depends on its own row alone: the sum over experts runs
in expert order whoever else is in the batch. That is what a decode tick
wants (a few dozen tokens hit nearly every expert, the layer is bound by
reading each expert's weights once and the extra operations hide under
that stream); a long prefill pays ``E / k`` times the operations it
needs, and a grouped matmul over tokens sorted by expert is the known
remedy (PERF.md, open questions).

A share of an expert-parallel layer: ``experts`` told ``share = (first,
held)`` is given the stack of the ``held`` experts from ``first`` on and
routing weights as wide as the ROUTER (all the layer's experts). It takes
the held columns of the weights and computes the held stack only: the
part of the layer's result that its own experts give, for the tokens
routed to them. What the absent experts would add is left out (over all
the shares the parts sum to the whole layer: tests/test_axk1_serving.py),
and nothing stands in for the exchange that would bring it. A shared
expert is a plain SwiGLU that the caller adds. ``routing_counts`` told the
same share counts over the held experts, and behind its three counts the
assignments over ALL experts, so that held / routed can be read.
"""
from __future__ import annotations

__all__ = ["route", "experts", "routing_counts"]


def _scores(x, router_w, score: str):
    """Every expert's score of tokens ``x`` [N, D], float32 [N, E]."""
    import jax
    import jax.numpy as jnp

    logits = jnp.dot(x, router_w, preferred_element_type=jnp.float32)
    logits = logits.astype(jnp.float32)
    if score == "sigmoid":
        return jax.nn.sigmoid(logits)
    if score == "softmax":
        return jax.nn.softmax(logits, axis=-1)
    raise ValueError(f"unknown router score {score!r}")


def route(x, router_w, k: int, score: str = "softmax", bias=None,
          norm_topk: bool = False, scale: float = 1.0,
          norm_eps: float = 1e-6, groups: int = 1, keep_groups: int = 1):
    """Routing of tokens ``x`` [N, D]: (dense weights [N, E] float32,
    zero off each token's top-k; the top-k expert ids [N, k]).

    ``score``: "softmax" over the experts, or "sigmoid" of each logit.
    ``bias`` [E] is added to the scores that SELECT the top-k only: the
    weights are the chosen experts' unbiased scores. ``groups`` > 1: the
    experts lie in that many equal groups in order, a group's score is the
    sum of its two largest selecting scores, and only experts of the
    ``keep_groups`` best groups can be chosen. ``norm_topk`` divides the
    weights by their sum (+ ``norm_eps``, as the published forwards have
    it), ``scale`` multiplies them."""
    import jax
    import jax.numpy as jnp

    probs = _scores(x, router_w, score)
    if bias is None and groups == 1:
        w, idx = jax.lax.top_k(probs, k)
    else:
        select = probs if bias is None else probs + bias.astype(jnp.float32)
        if groups > 1:
            n, e = select.shape
            by_group = select.reshape(n, groups, e // groups)
            group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
            _, kept = jax.lax.top_k(group_score, keep_groups)  # [N, keep]
            open_ = jnp.any(kept[:, :, None] == jnp.arange(groups), axis=1)
            select = jnp.where(open_[:, :, None], by_group,
                               -jnp.inf).reshape(n, e)
        _, idx = jax.lax.top_k(select, k)
        w = jnp.take_along_axis(probs, idx, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + norm_eps)
    if scale != 1.0:
        w = w * scale
    rows = jnp.arange(x.shape[0])[:, None]
    dense = jnp.zeros(probs.shape, jnp.float32).at[rows, idx].set(w)
    return dense, idx


def experts(x, dense_w, gate, up, down, share=None):
    """``sum_e dense_w[n, e] * down_e(silu(gate_e x[n]) * up_e x[n])`` for
    x [N, D]: the experts as one batched matmul over the stack, the
    weighted sum over experts in float32. ``share = (first, held)``: the
    stack is experts ``first .. first + held - 1`` of a wider router's,
    and the sum runs over those (the module's docstring)."""
    import jax
    import jax.numpy as jnp

    if share is not None:
        first, held = share
        dense_w = dense_w[:, first:first + held]
    g = jnp.einsum("nd,edf->enf", x, gate)
    u = jnp.einsum("nd,edf->enf", x, up)
    y = jnp.einsum("enf,efd->end", jax.nn.silu(g) * u, down)
    out = jnp.einsum("ne,end->nd", dense_w, y.astype(jnp.float32))
    return out.astype(x.dtype)


def routing_counts(idx, live, n_experts: int, share=None):
    """int32 [3] of one layer's routing ``idx`` [N, k] over the tokens
    marked ``live`` [N]: assignments, distinct experts hit, the largest
    expert's load. ``share = (first, held)``: the three count over the
    held experts, and a fourth is the live tokens' assignments over all
    ``n_experts`` that the router chose among."""
    import jax.numpy as jnp

    first, held = share or (0, n_experts)
    hit = ((idx[:, :, None] == jnp.arange(first, first + held))
           & live[:, None, None])
    load = jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)  # [E]
    counts = [jnp.sum(load), jnp.sum(load > 0, dtype=jnp.int32),
              jnp.max(load)]
    if share is not None:
        counts.append(jnp.sum(live, dtype=jnp.int32) * idx.shape[1])
    return jnp.stack(counts)
