"""A sparse expert layer (router + SwiGLU experts), drop-free.

    p        = softmax_float32(x @ router_w)                 [N, E]
    top-k of p, its weights used as they are (not rescaled to sum to 1)
    y[n]     = sum_{e in topk(n)} p[n, e] * down_e(silu(gate_e x[n]) * up_e x[n])

(the default router; ``route`` also scores by a sigmoid, selects under a
per-expert bias that the weights do not carry, and rescales the top-k
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
"""
from __future__ import annotations

__all__ = ["route", "experts", "routing_counts"]


def route(x, router_w, k: int, score: str = "softmax", bias=None,
          norm_topk: bool = False, scale: float = 1.0):
    """Routing of tokens ``x`` [N, D]: (dense weights [N, E] float32,
    zero off each token's top-k; the top-k expert ids [N, k]).

    ``score``: "softmax" over the experts, or "sigmoid" of each logit.
    ``bias`` [E] is added to the scores that SELECT the top-k only: the
    weights are the chosen experts' unbiased scores. ``norm_topk`` divides
    them by their sum (+ 1e-6, as the published forward has it), ``scale``
    multiplies them."""
    import jax
    import jax.numpy as jnp

    logits = jnp.dot(x, router_w, preferred_element_type=jnp.float32)
    logits = logits.astype(jnp.float32)
    if score == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    elif score == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown router score {score!r}")
    if bias is None:
        w, idx = jax.lax.top_k(probs, k)
    else:
        _, idx = jax.lax.top_k(probs + bias.astype(jnp.float32), k)
        w = jnp.take_along_axis(probs, idx, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    if scale != 1.0:
        w = w * scale
    rows = jnp.arange(x.shape[0])[:, None]
    dense = jnp.zeros(probs.shape, jnp.float32).at[rows, idx].set(w)
    return dense, idx


def experts(x, dense_w, gate, up, down):
    """``sum_e dense_w[n, e] * down_e(silu(gate_e x[n]) * up_e x[n])`` for
    x [N, D]: the experts as one batched matmul over the stack, the
    weighted sum over experts in float32."""
    import jax
    import jax.numpy as jnp

    g = jnp.einsum("nd,edf->enf", x, gate)
    u = jnp.einsum("nd,edf->enf", x, up)
    y = jnp.einsum("enf,efd->end", jax.nn.silu(g) * u, down)
    out = jnp.einsum("ne,end->nd", dense_w, y.astype(jnp.float32))
    return out.astype(x.dtype)


def routing_counts(idx, live, n_experts: int):
    """int32 [3] of one layer's routing ``idx`` [N, k] over the tokens
    marked ``live`` [N]: assignments, distinct experts hit, the largest
    expert's load."""
    import jax.numpy as jnp

    hit = (idx[:, :, None] == jnp.arange(n_experts)) & live[:, None, None]
    load = jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)  # [E]
    return jnp.stack([jnp.sum(load), jnp.sum(load > 0, dtype=jnp.int32),
                      jnp.max(load)])
