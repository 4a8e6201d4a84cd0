"""OLMoE (``model_type`` "olmoe") for the benchmark: RMSNorm, RoPE, a norm
over q and k, 64 SwiGLU experts of which a token takes 8, an untied head.

Names only are shared with the program (``gpt.h<i>.moe.gate.w`` ...). The
table of shapes below is written out here and not taken from
``serving.model.param_table``: tests/test_olmoe_serving.py holds the two to
each other.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

from ..reference import olmoe as reference

# A greedy token is accepted when the float32 reference, teacher-forced
# on the same prefix, scores it within LOGIT_TOL of its own best token.
# Reason: the program computes in bfloat16. Rounding alone moves a logit
# as it does for GPT-2 (seed-made N(0, 0.02) weights: the best two of
# 50k logits are often closer than bf16 resolves after 12 layers), and
# here a near-tie between a token's 8th and 9th expert can also fall the
# other way in bf16, which swaps one expert of one layer for that token
# and moves its logits by more than rounding does (the routing agreement
# of each run is reported as ``routing_agreement_share``). The two
# readings the limit is set from are in PERF.md section 6 (PR 26): the
# largest gap the served tokens showed over the builder's seeds, and the
# gap of the same reference with its matmul operands rounded to float8
# (e4m3), the nearest precision below the configuration's bfloat16, which
# has to come out as NOT correct (a run of the REFERENCE, not of the served
# program: benchmark/tools/tolerance_readings.py). What the limit has to
# catch in the program itself is benchmark/tools/fault_readings.py's.
LOGIT_TOL = 0.15
N_CHECKED = 4


def gpt_config(c: dict, engine: dict) -> dict:
    """``serving.GPTConfig`` keywords for configuration ``c`` served with
    the traffic file's ``engine`` settings. ``max_seq_len`` is the
    engine's window (the operator's max_model_len); RoPE has no table, so
    the model's own 4,096 positions constrain nothing shorter."""
    if c["norm_topk_prob"] or c["rope_scaling"] or c["clip_qkv"]:
        raise SystemExit("olmoe: norm_topk_prob, rope_scaling and clip_qkv are not built; "
                         "the published configuration sets none of them")
    return dict(
        vocab_size=c["vocab_size"], n_layer=c["n_layer"], n_head=c["n_head"], d_model=c["n_embd"],
        d_ff=c["intermediate_size"], max_seq_len=int(engine.get("window", c["n_positions"])),
        dtype=engine.get("dtype", "bfloat16"), tie_embeddings=bool(c["tie_word_embeddings"]),
        norm="rmsnorm", norm_eps=float(c["rms_norm_eps"]), position="rope",
        rope_theta=float(c["rope_theta"]), qk_norm=True, bias=bool(c["attention_bias"]), mlp="moe",
        n_experts=c["num_experts"], experts_per_token=c["num_experts_per_tok"])


# Norm gains are drawn from --seed too, as mean x (1 + GAIN_SPREAD x N(0, 1)),
# mean 1 but for the q and k norms of the FIRST layer, QK_GAIN_FIRST. With
# every gain 1 attention comes out close to a mean over the context, so a
# decode position off by one or a dropped q/k norm moves no served token
# and ``correct`` cannot see it (REVIEW of PR 26; on the chip the cell's
# check read 0.045 for the first against 0.025 sound). Scores of std
# ~QK_GAIN_FIRST**2 make one layer's softmax pick positions, as a trained
# model's does, and what it picks dominates the residual stream from there
# on. One layer and not all: a random-weight stack whose every layer is
# that sharp amplifies bfloat16 rounding layer by layer (all twelve at 1.7:
# the SOUND program reads 0.42; at 2: 1.4-2.0), and the last layer alone
# shows no fault. The sweep is in PERF.md section 6 (PR 26).
GAIN_SPREAD = 0.3
QK_GAIN_FIRST = 2.0


def _layer_table(c: dict) -> Dict[str, Tuple[tuple, float, float]]:
    d, f, e = c["n_embd"], c["intermediate_size"], c["num_experts"]
    res = 0.02 / math.sqrt(2 * c["n_layer"])
    gain = (1.0, GAIN_SPREAD)
    return {"ln1.scale": ((d,), *gain), "ln2.scale": ((d,), *gain),
            "attn.q.w": ((d, d), 0.0, 0.02), "attn.k.w": ((d, d), 0.0, 0.02),
            "attn.v.w": ((d, d), 0.0, 0.02), "attn.proj.w": ((d, d), 0.0, res),
            "attn.q_norm.scale": ((d,), *gain), "attn.k_norm.scale": ((d,), *gain),
            "moe.router.w": ((d, e), 0.0, 0.02), "moe.gate.w": ((e, d, f), 0.0, 0.02),
            "moe.up.w": ((e, d, f), 0.0, 0.02), "moe.down.w": ((e, f, d), 0.0, res)}


def _top_table(c: dict) -> Dict[str, Tuple[tuple, float, float]]:
    d, v = c["n_embd"], c["vocab_size"]
    return {"gpt.wte": ((v, d), 0.0, 0.02), "gpt.lm_head.w": ((d, v), 0.0, 0.02),
            "gpt.lnf.scale": ((d,), 1.0, GAIN_SPREAD)}


def param_table(c: dict) -> Dict[str, Tuple[tuple, float, float]]:
    """name -> (shape, mean, std) of a normal draw. Weights N(0, 0.02),
    residual projections (attention out, expert down) scaled by 1/sqrt(2L):
    the repo's initialisation; the gains as above (``make_params`` scales
    the first layer's q and k gains). Listed under ``assumed`` in the
    configuration file."""
    t = dict(_top_table(c))
    for i in range(c["n_layer"]):
        t.update({f"gpt.h{i}.{k}": v for k, v in _layer_table(c).items()})
    return t


def make_params(c: dict, seed: int, dtype: str) -> dict:
    """The weights on the device, one jitted call a layer (one compiled
    program for all layers), so that set-up never holds a second copy of
    the model: a float32 draw lives only until it is cast."""
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
    build_layer = builder(_layer_table(c))
    for i in range(c["n_layer"]):
        layer = build_layer(jax.random.fold_in(key, i + 1), QK_GAIN_FIRST if i == 0 else 1.0)
        params.update({f"gpt.h{i}.{k}": v for k, v in layer.items()})
    return params


def reference_logits(get, tokens, positions, c: dict, matmul_dtype=None):
    """(logits [B, P, V] at ``positions``, routing [B, T, L, k]) of the
    float32 reference on ``tokens`` [B, T]."""
    return reference.logits_at(get, tokens, positions, n_layer=c["n_layer"], n_head=c["n_head"],
                               top_k=c["num_experts_per_tok"], eps=float(c["rms_norm_eps"]),
                               theta=float(c["rope_theta"]), matmul_dtype=matmul_dtype)


# -- bytes and operations the algorithm NEEDS (as benchmark/flops.py) --------


def expert_bytes(c: dict, itemsize: int = 2) -> int:
    """One expert of one layer: gate, up and down."""
    return 3 * c["n_embd"] * c["intermediate_size"] * itemsize


def n_params(c: dict) -> int:
    d, L = c["n_embd"], c["n_layer"]
    per_layer = 4 * d * d + 4 * d + d * c["num_experts"] + c["num_experts"] * expert_bytes(c, 1)
    return 2 * c["vocab_size"] * d + d + L * per_layer


def decode_tick_bytes(c: dict, slots: int, live_kv_tokens: float, experts_hit: float,
                      itemsize: int = 2) -> dict:
    """Bytes one decode tick MUST stream, by part. ``experts_hit`` is the
    tick's count of (layer, expert) pairs with at least one token: only
    those experts' weights are needed. Every other weight is read once (of
    the embedding only the ``slots`` rows looked up), and K and V of every
    live context position in every layer."""
    d, L = c["n_embd"], c["n_layer"]
    dense = L * (4 * d * d + 4 * d + d * c["num_experts"]) + d * c["vocab_size"] + d + slots * d
    return {"experts": experts_hit * expert_bytes(c, itemsize), "other_weights": dense * itemsize,
            "kv": 2.0 * L * d * itemsize * live_kv_tokens}


def expert_shapes(c: dict) -> list:
    """The stacked expert weights' shapes as they read in HLO text."""
    e, d, f = c["num_experts"], c["n_embd"], c["intermediate_size"]
    return [f"[{e},{d},{f}]", f"[{e},{f},{d}]"]
