"""LFM2-MoE (``model_type`` "lfm2_moe") for the benchmark: layers of three
kinds under one RMSNorm residual stream. A layer's operator is a gated
short convolution (three causal depthwise taps over ``B * X``, gated by
``C``) or grouped-query attention (32 query heads over 8 K|V heads, a norm
per head over q and k, RoPE); its feed-forward is a dense SwiGLU (the two
leading layers) or 64 SwiGLU experts of which a token takes 4, scored by a
sigmoid, selected under a per-expert bias and weighted by their normalised
unbiased scores. The head is the embedding.

Names only are shared with the program (``gpt.h<i>.conv.in_proj.w`` ...).
The table of shapes below is written out here and not taken from
``serving.model.param_table``: tests/test_lfm2_serving.py holds the two to
each other.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

from ..reference import lfm2_moe as reference

# A greedy token is accepted when the float32 reference, teacher-forced on
# the same prefix, scores it within LOGIT_TOL of its own best token. The
# program computes in bfloat16, so rounding alone moves a logit, and a
# near-tie between a token's 4th and 5th expert can fall the other way and
# swap one expert of one layer, a quarter of that layer's output since the
# four weights sum to 1 (``routing_agreement_share`` of each run: 0.93-0.94;
# OLMoE's raw softmax weights make the same swap move a thirtieth). The
# limit lies between two readings (my chip runs, PR 33; PERF.md section 6):
# the largest gap the sound program's served tokens showed, 0.323 over
# eight runs and 37,439 checked tokens (per run 0.158-0.323), and the
# weakest of the eight faults of benchmark/tools/lfm2_fault_readings.py,
# the decode position off by one, 0.569 (the others 0.90-7.0); the same
# reference with its matmul operands rounded to float8 (e4m3), the nearest
# precision below the configuration's bfloat16, reads 1.735 and so comes
# out NOT correct, as it has to.
LOGIT_TOL = 0.45
N_CHECKED = 4

_OPS = {"conv": "conv", "full_attention": "attn"}


def _kinds(c: dict):
    """Per layer (operator, feed-forward) in the program's words."""
    if len(c["layer_types"]) != c["n_layer"]:
        raise SystemExit(f"lfm2_moe: layer_types names {len(c['layer_types'])} layers, "
                         f"n_layer is {c['n_layer']}")
    return [(_OPS[t], "swiglu" if i < c["num_dense_layers"] else "moe")
            for i, t in enumerate(c["layer_types"])]


def gpt_config(c: dict, engine: dict) -> dict:
    """``serving.GPTConfig`` keywords for configuration ``c`` served with
    the traffic file's ``engine`` settings. ``max_seq_len`` is the
    engine's window (the operator's max_model_len); RoPE has no table, so
    the model's own 128,000 positions constrain nothing shorter."""
    rope = c["rope_parameters"]
    if rope["rope_type"] != "default":
        raise SystemExit(f"lfm2_moe: rope_type {rope['rope_type']!r} is not built")
    kinds = _kinds(c)
    return dict(
        vocab_size=c["vocab_size"], n_layer=c["n_layer"], n_head=c["n_head"], d_model=c["n_embd"],
        n_kv_head=c["num_key_value_heads"], d_ff=c["moe_intermediate_size"],
        d_ff_dense=c["intermediate_size"], max_seq_len=int(engine.get("window", c["n_positions"])),
        dtype=engine.get("dtype", "bfloat16"), tie_embeddings=True, norm="rmsnorm",
        norm_eps=float(c["norm_eps"]), position="rope", rope_theta=float(rope["rope_theta"]),
        qk_norm="head", bias=False, mlp="moe", n_experts=c["num_experts"],
        experts_per_token=c["num_experts_per_tok"], layer_ops=tuple(k[0] for k in kinds),
        layer_mlps=tuple(k[1] for k in kinds), conv_kernel=c["conv_L_cache"],
        conv_bias=bool(c["conv_bias"]), router_score="sigmoid",
        router_bias=bool(c["use_expert_bias"]), norm_topk=bool(c["norm_topk_prob"]),
        routed_scale=float(c["routed_scaling_factor"]))


# Norm gains are drawn from --seed as mean x (1 + GAIN_SPREAD x N(0, 1)),
# mean 1 but for the q and k norms of the FIRST attention layer,
# QK_GAIN_FIRST: one layer whose scores spread by a few units, so that its
# softmax picks positions as a trained model's does and a wrong position,
# head mapping or dropped norm moves served tokens (PR 26 found both
# numbers for OLMoE; benchmark/arch/olmoe.py has the sweep's reasons). The
# selection bias is N(0, BIAS_STD), as wide as a seed-made router's
# sigmoid scores spread (~0.2), so that it decides nearly every selection
# and a program that drops it fails the check. The taps are N(0, TAPS_STD)
# so that all three weigh in and the state matters.
#
# The two leading dense layers' down projections are DENSE_DOWN_GAIN times
# the repo's residual scale, and the attention layers' output projections
# ATTN_OUT_GAIN times. Why (PERF.md section 6, PR 33, has the readings):
# with every layer's output as large as the stream it is added to, a
# bfloat16 near-tie between a token's 4th and 5th expert (one in five
# top-4 sets of a tick differs from float32's) swaps a quarter of a
# layer's output, since the normalised weights sum to 1, and the next
# layers amplify it: the SOUND program then read 0.83 on the chip, beside
# faults that read 0.3-0.5. A trained model's stream is large against any
# one layer's output. The dense layers, which come first and do not route,
# give the seed-made model such a stream (sound 0.05-0.15, the dropped
# bias 0.85 on the CPU at 64 experts), and the doubled attention output
# keeps the two attention layers' share of it large enough that a decode
# position off by one reads 0.5 and not 0.3.
GAIN_SPREAD = 0.3
QK_GAIN_FIRST = 2.0
BIAS_STD = 0.3
TAPS_STD = 0.5
DENSE_DOWN_GAIN = 2.0
ATTN_OUT_GAIN = 2.0


def _layer_table(c: dict, kind) -> Dict[str, Tuple[tuple, float, float]]:
    d, hd = c["n_embd"], c["n_embd"] // c["n_head"]
    d_kv = c["num_key_value_heads"] * hd
    res = 0.02 / math.sqrt(2 * c["n_layer"])
    gain = (1.0, GAIN_SPREAD)
    t = {"ln1.scale": ((d,), *gain), "ln2.scale": ((d,), *gain)}
    if kind[0] == "conv":
        t.update({"conv.in_proj.w": ((d, 3 * d), 0.0, 0.02),
                  "conv.taps.w": ((c["conv_L_cache"], d), 0.0, TAPS_STD),
                  "conv.out_proj.w": ((d, d), 0.0, res)})
    else:
        t.update({"attn.q.w": ((d, d), 0.0, 0.02), "attn.k.w": ((d, d_kv), 0.0, 0.02),
                  "attn.v.w": ((d, d_kv), 0.0, 0.02), "attn.proj.w": ((d, d), 0.0, ATTN_OUT_GAIN * res),
                  "attn.q_norm.scale": ((hd,), *gain), "attn.k_norm.scale": ((hd,), *gain)})
    if kind[1] == "moe":
        e, f = c["num_experts"], c["moe_intermediate_size"]
        t.update({"moe.router.w": ((d, e), 0.0, 0.02), "moe.router.bias": ((e,), 0.0, BIAS_STD),
                  "moe.gate.w": ((e, d, f), 0.0, 0.02), "moe.up.w": ((e, d, f), 0.0, 0.02),
                  "moe.down.w": ((e, f, d), 0.0, res)})
    else:
        f = c["intermediate_size"]
        t.update({"mlp.gate.w": ((d, f), 0.0, 0.02), "mlp.up.w": ((d, f), 0.0, 0.02),
                  "mlp.down.w": ((f, d), 0.0, DENSE_DOWN_GAIN * res)})
    return t


def _top_table(c: dict) -> Dict[str, Tuple[tuple, float, float]]:
    d, v = c["n_embd"], c["vocab_size"]
    return {"gpt.wte": ((v, d), 0.0, 0.02), "gpt.lnf.scale": ((d,), 1.0, GAIN_SPREAD)}


def param_table(c: dict) -> Dict[str, Tuple[tuple, float, float]]:
    """name -> (shape, mean, std) of a normal draw. Weights N(0, 0.02),
    residual projections (conv and attention out, dense and expert down)
    scaled by 1/sqrt(2L): the repo's initialisation; gains, bias, taps and
    the two projections scaled further as above. Listed under ``assumed``
    in the configuration file."""
    t = dict(_top_table(c))
    for i, kind in enumerate(_kinds(c)):
        t.update({f"gpt.h{i}.{k}": v for k, v in _layer_table(c, kind).items()})
    return t


def make_params(c: dict, seed: int, dtype: str) -> dict:
    """The weights on the device, one jitted call a layer (one compiled
    program for each kind of layer), so that set-up never holds a second
    copy of the model: a float32 draw lives only until it is cast."""
    import jax
    import jax.numpy as jnp

    def builder(table):
        names = sorted(table)

        @jax.jit
        def build(key, qk_gain):
            out = {}
            for j, name in enumerate(names):
                shape, mean, std = table[name]
                draw = mean + std * jax.random.normal(jax.random.fold_in(key, j), shape, jnp.float32)
                if name.endswith("_norm.scale"):
                    draw = qk_gain * draw
                out[name] = draw.astype(dtype)
            return out
        return build

    key = jax.random.key(int(seed))
    params = builder(_top_table(c))(jax.random.fold_in(key, 0), 1.0)
    kinds = _kinds(c)
    build = {kind: builder(_layer_table(c, kind)) for kind in set(kinds)}
    first_attn = next(i for i, k in enumerate(kinds) if k[0] == "attn")
    for i, kind in enumerate(kinds):
        layer = build[kind](jax.random.fold_in(key, i + 1),
                            QK_GAIN_FIRST if i == first_attn else 1.0)
        params.update({f"gpt.h{i}.{k}": v for k, v in layer.items()})
    return params


def reference_logits(get, tokens, positions, c: dict, matmul_dtype=None):
    """(logits [1, P, V] at ``positions``, routing [1, T, L_moe, k]) of the
    float32 reference on ``tokens`` [1, T]."""
    return reference.logits_at(
        get, tokens, positions, layer_types=tuple(c["layer_types"]),
        num_dense_layers=c["num_dense_layers"], n_head=c["n_head"],
        n_kv_head=c["num_key_value_heads"], top_k=c["num_experts_per_tok"],
        eps=float(c["norm_eps"]), theta=float(c["rope_parameters"]["rope_theta"]),
        scale=float(c["routed_scaling_factor"]), matmul_dtype=matmul_dtype)


# -- bytes and operations the algorithm NEEDS (as benchmark/flops.py) --------


def _count(c: dict, op: str = None, mlp: str = None) -> int:
    return sum(1 for k in _kinds(c) if op in (None, k[0]) and mlp in (None, k[1]))


def expert_bytes(c: dict, itemsize: int = 2) -> int:
    """One expert of one layer: gate, up and down."""
    return 3 * c["n_embd"] * c["moe_intermediate_size"] * itemsize


def n_params(c: dict) -> int:
    return sum(math.prod(shape) for shape, _, _ in param_table(c).values())


def kv_token_bytes(c: dict, itemsize: int = 2) -> int:
    """K and V of one context position in every layer that attends."""
    hd = c["n_embd"] // c["n_head"]
    return _count(c, op="attn") * c["num_key_value_heads"] * 2 * hd * itemsize


def state_bytes(c: dict, slots: int, itemsize: int = 2) -> int:
    """The conv layers' state of ``slots`` decode slots."""
    return _count(c, op="conv") * (c["conv_L_cache"] - 1) * slots * c["n_embd"] * itemsize


def decode_tick_bytes(c: dict, slots: int, live_kv_tokens: float, experts_hit: float,
                      itemsize: int = 2) -> dict:
    """Bytes one decode tick MUST stream, by part. ``experts_hit`` is the
    tick's count of (layer, expert) pairs with at least one token: only
    those experts' weights are needed. Every other weight is read once
    (the embedding whole: it is the head), K and V of every live context
    position in the layers that attend, and every slot's conv state read
    and written."""
    experts = _count(c, mlp="moe") * c["num_experts"] * expert_bytes(c, 1)
    return {"experts": experts_hit * expert_bytes(c, itemsize),
            "other_weights": (n_params(c) - experts) * itemsize,
            "kv": kv_token_bytes(c, itemsize) * live_kv_tokens,
            "state": 2 * state_bytes(c, slots, itemsize)}


def paged_attention_bytes(c: dict, live_kv_tokens: float, itemsize: int = 2) -> float:
    """Bytes the decode attention of one tick MUST read: K and V of every
    live context position of every layer that attends, once (q and the
    output are a few rows a slot)."""
    return kv_token_bytes(c, itemsize) * live_kv_tokens


def expert_shapes(c: dict) -> list:
    """The stacked expert weights' shapes as they read in HLO text."""
    e, d, f = c["num_experts"], c["n_embd"], c["moe_intermediate_size"]
    return [f"[{e},{d},{f}]", f"[{e},{f},{d}]"]


def conv_shapes(c: dict) -> dict:
    """Shapes, as they read in HLO text, whose readers are the conv
    layers' operations, each with the share of such an operation's time
    that is the conv layers': the input projection and the taps are theirs
    alone; ``[D, D]`` is the conv layers' output projection and the
    attention layers' q and output projections alike (the same matmul on
    the same rows), so the conv layers' share of those is their share of
    the ``[D, D]`` weights."""
    d = c["n_embd"]
    n_conv, n_attn = _count(c, op="conv"), _count(c, op="attn")
    return {f"[{d},{3 * d}]": 1.0, f"[{c['conv_L_cache']},{d}]": 1.0,
            f"[{d},{d}]": n_conv / (n_conv + 2 * n_attn)}
