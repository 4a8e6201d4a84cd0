"""GPT/Llama-style decoder LM as a static ProgramDesc builder — the
flagship model (BASELINE.json configs 3 and 5).

No reference twin exists (the goodcoder-cnn/Paddle snapshot predates LLMs;
its transformer coverage is inference-only fused multihead_matmul,
/root/reference/paddle/fluid/operators/fused/multihead_matmul_op.cu). This
is the TPU-first equivalent of an ERNIE/BERT/GPT pretraining graph: the
whole step lowers to one XLA program, attention runs through the
`fused_attention_tpu` op (pallas flash path for long sequences), and
parameter names are structured (`gpt.h<i>.<sub>.<w|b>`) so mesh sharding
rules (paddle_tpu.parallel) can map them to tensor-parallel PartitionSpecs.

Tensor-parallel layout follows the Megatron pattern expressed as shardings
instead of explicit collectives: qkv/ffn-in weights are column-sharded,
proj/ffn-out row-sharded; GSPMD inserts the all-reduces on ICI.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..framework import LayerHelper, ParamAttr, Program, program_guard
from ..framework import initializer as init
from ..static import nn as snn


@dataclass(frozen=True)
class YarnRope:
    """YaRN's description of rotary frequencies stretched past the length
    a model was trained at (a configuration's ``rope_scaling`` of type
    "yarn"): frequency ``i`` of ``d / 2`` is divided by ``factor`` where
    it turns less than ``beta_slow`` times over ``original_max_position``
    positions, kept where it turns more than ``beta_fast`` times, and
    blended linearly between. ``mscale`` / ``mscale_all_dim`` scale cos
    and sin by their ratio (:meth:`attention_factor`), and the softmax
    scale of an attention that says so by ``mscale_all_dim``'s square
    (:meth:`softmax_gain`)."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @staticmethod
    def _mscale(factor: float, mscale: float) -> float:
        return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0

    def attention_factor(self) -> float:
        return (self._mscale(self.factor, self.mscale)
                / self._mscale(self.factor, self.mscale_all_dim))

    def softmax_gain(self) -> float:
        return self._mscale(self.factor, self.mscale_all_dim) ** 2

    def ramp_bounds(self, dim: int, base: float) -> Tuple[int, int]:
        """(low, high): the frequency indices between which the blend
        runs, for ``dim`` rotated lanes of base ``base``."""
        def index(turns):
            return (dim * math.log(self.original_max_position
                                   / (turns * 2 * math.pi))
                    / (2 * math.log(base)))
        return (max(math.floor(index(self.beta_fast)), 0),
                min(math.ceil(index(self.beta_slow)), dim - 1))

    def inv_freq(self, dim: int, base: float) -> np.ndarray:
        """The ``dim / 2`` frequencies, float64; they hold at EVERY
        position, not only past ``original_max_position``."""
        f = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
        low, high = self.ramp_bounds(dim, base)
        ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                       / max(high - low, 1e-3), 0.0, 1.0)
        return (f / self.factor) * ramp + f * (1.0 - ramp)


@dataclass
class GPTConfig:
    vocab_size: int = 32000
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    d_ff: Optional[int] = None  # default 4*d_model
    max_seq_len: int = 1024
    dropout: float = 0.0
    dtype: str = "float32"
    tie_embeddings: bool = True
    # mesh axis for ring-attention context parallelism ("" = off): the
    # sequence dim is sharded over this axis and attention runs the
    # ppermute ring schedule (paddle_tpu/parallel/ring_attention.py)
    sequence_parallel_axis: str = ""
    # pipeline-parallel stage count (>1 tags layers with device_guard
    # 'tpu:<stage>' for PipelineOptimizer sectioning)
    pp_stages: int = 1
    # attention tensor layout override: "" = auto (BTHD single-chip,
    # BHTD under sequence parallelism)
    attention_layout: str = ""
    # the loss without the logits (fused_lm_head_ce: the [B, T, V] logits
    # are never materialized, forward or backward), wherever the head is
    # tied and the graph unpipelined. False: materialized logits +
    # softmax_with_cross_entropy, for a caller that needs the logits.
    fused_lm_head: bool = True
    # -- the block's description (defaults: GPT-2). The serving plane's
    # one layer body (serving/model.py) reads these; the training graph
    # below builds the GPT-2 block only and refuses anything else.
    # norm_eps and rope_theta mirror the published keys a configuration
    # file carries (layer_norm_epsilon / rms_norm_eps, rope_theta): GPT-2
    # and OLMoE publish the same two values, the next ones do not.
    norm: str = "layernorm"       # or "rmsnorm": no mean, no bias
    norm_eps: float = 1e-5
    position: str = "learned"     # or "rope": rotate-half on q and k
    rope_theta: float = 10000.0
    # the norm over q and k: True over all heads' lanes at once, "head"
    # over each head's own head_dim lanes (one gain of head_dim)
    qk_norm: object = False
    bias: bool = True             # biases on the projections
    # the feed-forward: "gelu" (fc_in, erf GELU, fc_out), "swiglu" (gate,
    # up, down) or "moe": router + SwiGLU experts of d_ff
    mlp: str = "gelu"
    n_experts: int = 0
    experts_per_token: int = 0            # their softmax weights as they are
    # K|V heads, each shared by n_head / n_kv_head query heads in order
    # (grouped-query attention); None: one per query head
    n_kv_head: Optional[int] = None
    # layers of more than one kind, one entry a layer (None: all alike).
    # layer_ops: "attn", "latent" (latent attention, described further
    # down), or "conv": the gated short convolution, x W_in
    # split into B | C | X, causal depthwise taps over B * X, C * that
    # through W_out, whose last conv_kernel - 1 gated inputs are a decode
    # slot's state. layer_mlps: each layer's feed-forward, as `mlp`; a
    # "swiglu" layer of a model with experts is d_ff_dense wide
    layer_ops: Optional[Tuple[str, ...]] = None
    layer_mlps: Optional[Tuple[str, ...]] = None
    d_ff_dense: Optional[int] = None
    conv_kernel: int = 3
    conv_bias: bool = False
    # the router: scores "softmax" over the experts, or "sigmoid" of each
    # logit; router_bias adds a per-expert bias to the scores that SELECT
    # the top-k and never to the weights; norm_topk rescales the k weights
    # to sum to 1; routed_scale multiplies them
    router_score: str = "softmax"
    router_bias: bool = False
    norm_topk: bool = False
    routed_scale: float = 1.0
    # what norm_topk adds to the sum it divides by
    norm_topk_eps: float = 1e-6
    # group-limited selection: the experts lie in router_groups equal
    # groups in order, a group scores the sum of its two largest scores,
    # and only experts of the router_keep_groups best groups can be chosen
    router_groups: int = 1
    router_keep_groups: int = 1
    # a shared expert beside the routed ones: one SwiGLU this wide on
    # every token, added unweighted (0: none)
    d_ff_shared: int = 0
    # (first, held): the routed experts this model HOLDS of the router's
    # n_experts, one chip's share of an expert-parallel layer. It routes
    # over all n_experts and computes its own experts' part of the result;
    # None: all of them
    experts_held: Optional[Tuple[int, int]] = None
    # latent attention (a layer whose operator is "latent"): q through a
    # normed latent of q_lora_rank, K and V through ONE normed latent of
    # kv_lora_rank a position beside qk_rope_dim rotated lanes that every
    # head shares; a head scores qk_nope_dim + qk_rope_dim lanes and
    # weighs v_head_dim. Pairs (2i, 2i + 1) of the rotated lanes turn
    # together. rope_yarn: the rotated lanes' frequencies, None: plain
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    rope_yarn: Optional[YarnRope] = None

    def __post_init__(self):
        for name in ("layer_ops", "layer_mlps"):
            kinds = getattr(self, name)
            if kinds is not None:
                if len(kinds) != self.n_layer:
                    raise ValueError(f"{name} names {len(kinds)} layers, "
                                     f"n_layer is {self.n_layer}")
                setattr(self, name, tuple(kinds))
        if self.experts_held is not None:
            first, held = self.experts_held = tuple(self.experts_held)
            if not 0 <= first < first + held <= self.n_experts:
                raise ValueError(f"experts_held {self.experts_held} is no "
                                 f"share of {self.n_experts} experts")
        if self.router_groups > 1 and (
                self.n_experts % self.router_groups
                or not 0 < self.router_keep_groups <= self.router_groups):
            raise ValueError(
                f"{self.n_experts} experts do not lie in "
                f"{self.router_groups} equal groups of which "
                f"{self.router_keep_groups} are kept")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @property
    def kv_heads(self) -> int:
        return self.n_kv_head or self.n_head

    @property
    def ffn_dim(self) -> int:
        return self.d_ff or 4 * self.d_model

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(first, held) of the routed experts this model holds."""
        return self.experts_held or (0, self.n_experts)

    @property
    def latent_row(self) -> int:
        """Lanes of a position's row under latent attention as they are
        used: the K|V latent, then the rotated key lanes."""
        return self.kv_lora_rank + self.qk_rope_dim

    def layer_kind(self, i: int) -> Tuple[str, str]:
        """Layer ``i``'s (operator, feed-forward)."""
        return (self.layer_ops[i] if self.layer_ops else "attn",
                self.layer_mlps[i] if self.layer_mlps else self.mlp)

    def layers_of(self, op: str) -> List[int]:
        """The layers whose operator is ``op``, in order: an attention
        layer's place here is its share of the KV pool, a conv layer's its
        share of the state pool."""
        return [i for i in range(self.n_layer) if self.layer_kind(i)[0] == op]

    def mlp_width(self, mlp: str) -> int:
        """Width of a feed-forward of kind ``mlp`` in this model."""
        if mlp == "swiglu" and self.d_ff_dense:
            return self.d_ff_dense
        return self.ffn_dim

    def block(self) -> tuple:
        """The block's description as one hashable value."""
        return (self.norm, self.norm_eps, self.position, self.rope_theta,
                self.qk_norm, self.bias, self.mlp, self.n_experts,
                self.experts_per_token, self.n_kv_head, self.layer_ops,
                self.layer_mlps, self.d_ff_dense, self.conv_kernel,
                self.conv_bias, self.router_score, self.router_bias,
                self.norm_topk, self.routed_scale, self.norm_topk_eps,
                self.router_groups, self.router_keep_groups,
                self.d_ff_shared, self.experts_held, self.q_lora_rank,
                self.kv_lora_rank, self.qk_nope_dim, self.qk_rope_dim,
                self.v_head_dim, self.rope_yarn, self.tie_embeddings)


def _param(helper: LayerHelper, name: str, shape, dtype, std: float = 0.02, zeros=False):
    ini = init.ConstantInitializer(0.0) if zeros else init.NormalInitializer(0.0, std)
    return helper.create_parameter(
        ParamAttr(name=name, initializer=ini), shape=shape, dtype=dtype
    )


def _linear(helper, x, name: str, d_in: int, d_out: int, dtype: str, std=0.02, bias=True):
    w = _param(helper, f"{name}.w", [d_in, d_out], dtype, std=std)
    out = snn.matmul(x, w)
    if bias:
        b = _param(helper, f"{name}.b", [d_out], dtype, zeros=True)
        out = snn.elementwise_add(out, b)
    return out


def _attention(helper, x, cfg: GPTConfig, lname: str, batch, seq):
    d, h, hd = cfg.d_model, cfg.n_head, cfg.head_dim
    # Layout: heads stay where the qkv matmul leaves them (BTHD) — no
    # transpose ops in the graph at ANY length (profiled ~10% of the step
    # at T=512 and worse at flash lengths). The pallas flash kernel tiles
    # BTHD natively; only ring attention (sp) still wants BHTD.
    layout = cfg.attention_layout or ("BHTD" if cfg.sequence_parallel_axis else "BTHD")
    qkv = []
    for part in ("q", "k", "v"):
        p = _linear(helper, x, f"{lname}.attn.{part}", d, d, cfg.dtype)
        p = snn.reshape(p, [batch, seq, h, hd])
        if layout == "BHTD":
            p = snn.transpose(p, [0, 2, 1, 3])
        qkv.append(p)
    q, k, v = qkv

    block = helper.main_program.current_block()
    out = helper.create_variable_for_type_inference(dtype=cfg.dtype)
    block.append_op(
        type="fused_attention_tpu",
        inputs={"Q": [q], "K": [k], "V": [v]},
        outputs={"Out": [out]},
        attrs={
            "is_causal": True,
            "dropout_p": cfg.dropout,
            "is_test": False,
            "layout": layout,
            "sequence_parallel_axis": cfg.sequence_parallel_axis,
        },
    )
    if layout == "BHTD":
        out = snn.transpose(out, [0, 2, 1, 3])
    out = snn.reshape(out, [batch, seq, d])
    # residual-scaled init on the output projection (GPT-2 trick)
    return _linear(
        helper, out, f"{lname}.attn.proj", d, d, cfg.dtype,
        std=0.02 / math.sqrt(2 * cfg.n_layer),
    )


def _mlp(helper, x, cfg: GPTConfig, lname: str):
    d, dff = cfg.d_model, cfg.ffn_dim
    hgelu = snn.gelu(_linear(helper, x, f"{lname}.mlp.fc_in", d, dff, cfg.dtype))
    return _linear(
        helper, hgelu, f"{lname}.mlp.fc_out", dff, d, cfg.dtype,
        std=0.02 / math.sqrt(2 * cfg.n_layer),
    )


def _layer_norm(x, name: str):
    return snn.layer_norm(
        x,
        begin_norm_axis=len(x.shape) - 1,
        param_attr=ParamAttr(name=f"{name}.scale", initializer=init.ConstantInitializer(1.0)),
        bias_attr=ParamAttr(name=f"{name}.bias", initializer=init.ConstantInitializer(0.0)),
    )


def build_forward(cfg: GPTConfig, tokens, batch: int, seq: int,
                  checkpoints_out: Optional[list] = None,
                  lm_head: bool = True):
    """Append the decoder forward to the current program; returns logits
    [B, T, V] — or, with lm_head=False, the (final hidden state, wte)
    pair the fused lm-head CE consumes. If `checkpoints_out` is given,
    the per-layer residual outputs are appended to it — the natural
    recompute boundaries (RecomputeOptimizer /
    append_backward_with_checkpoints)."""
    from ..framework import device_guard

    if cfg.block()[:-1] != GPTConfig().block()[:-1]:
        raise NotImplementedError(
            f"the training graph builds the GPT-2 block only; this "
            f"configuration describes {cfg.block()[:-1]} (served by "
            f"serving/model.py, not yet trained)")
    helper = LayerHelper("gpt")
    d = cfg.d_model
    pp = max(1, cfg.pp_stages)

    def stage_guard(s: int):
        return device_guard(f"tpu:{s}") if pp > 1 else device_guard(None)

    with stage_guard(0):
        wte = _param(helper, "gpt.wte", [cfg.vocab_size, d], cfg.dtype)
        wpe = _param(helper, "gpt.wpe", [cfg.max_seq_len, d], cfg.dtype)

        block = helper.main_program.current_block()
        tok_emb = helper.create_variable_for_type_inference(dtype=cfg.dtype)
        block.append_op(
            type="lookup_table_v2",
            inputs={"W": [wte], "Ids": [tokens]},
            outputs={"Out": [tok_emb]},
            attrs={},
        )
        pos = snn.slice(wpe, axes=[0], starts=[0], ends=[seq])
        x = snn.elementwise_add(tok_emb, pos)  # broadcast [T,D] over batch

    for i in range(cfg.n_layer):
        with stage_guard(i * pp // cfg.n_layer):
            ln = f"gpt.h{i}"
            a = _attention(helper, _layer_norm(x, f"{ln}.ln1"), cfg, ln, batch, seq)
            x = snn.elementwise_add(x, a)
            m = _mlp(helper, _layer_norm(x, f"{ln}.ln2"), cfg, ln)
            x = snn.elementwise_add(x, m)
            if checkpoints_out is not None:
                checkpoints_out.append(x)

    with stage_guard(pp - 1):
        x = _layer_norm(x, "gpt.lnf")
        if not lm_head:
            return x, wte
        if cfg.tie_embeddings:
            logits = snn.matmul(x, wte, transpose_y=True)
        else:
            logits = _linear(helper, x, "gpt.lm_head", d, cfg.vocab_size, cfg.dtype, bias=False)
    return logits


def resolve_lm_head_impl(cfg: GPTConfig) -> str:
    """The training loss path for this config: "pallas" (the fused
    flash-style kernels: PR 40 measured them ahead of the chunked loop
    and of the materialized logits on gpt2s-train-1k, in time and in
    memory) where the config wants the loss without the logits, the head
    is tied and the graph unpipelined; "off" (materialized logits)
    otherwise. The op still picks its chunked loop itself where a mesh
    program leaves the kernels no region
    (ops/fused_ops.py::_pallas_shard_plan)."""
    if not isinstance(cfg.fused_lm_head, bool):
        raise ValueError(f"fused_lm_head must be True or False, got {cfg.fused_lm_head!r}")
    eligible = cfg.tie_embeddings and max(1, cfg.pp_stages) == 1
    return "pallas" if cfg.fused_lm_head and eligible else "off"


def build_train_program(
    cfg: GPTConfig, batch: int, seq: int
) -> Tuple[Program, Program, Dict[str, object]]:
    """Full LM training graph: tokens/labels feeds -> mean NLL loss.
    Returns (main, startup, io) where io holds tokens/labels/loss/
    checkpoints plus "logits" — which is None when the fused lm-head CE
    is active (io["lm_head_impl"] says which; the fused path never
    materializes logits, that being its point). Callers needing logits
    must pass fused_lm_head=False."""
    main, startup = Program(), Program()
    ckpts: list = []
    impl = resolve_lm_head_impl(cfg)
    with program_guard(main, startup):
        tokens = snn.data("tokens", shape=[batch, seq], dtype="int64")
        labels = snn.data("labels", shape=[batch, seq], dtype="int64")
        if impl == "pallas":
            hidden, wte = build_forward(
                cfg, tokens, batch, seq, checkpoints_out=ckpts, lm_head=False)
            block = main.current_block()
            loss = block.create_var(name="lm_ce_loss")
            block.append_op(
                type="fused_lm_head_ce",
                inputs={"X": [hidden], "W": [wte], "Label": [labels]},
                outputs={"Loss": [loss]},
                attrs={"chunk_size": 4096, "impl": impl},
            )
            logits = None
        else:
            logits = build_forward(cfg, tokens, batch, seq,
                                   checkpoints_out=ckpts)
            labels3 = snn.reshape(labels, [batch, seq, 1])
            loss = snn.softmax_with_cross_entropy(logits, labels3, axis=-1)
        avg_loss = snn.mean(loss)
    return main, startup, {
        "tokens": tokens,
        "labels": labels,
        "logits": logits,
        "loss": avg_loss,
        "checkpoints": ckpts,
        "lm_head_impl": impl,
    }


# -- sharding rules ----------------------------------------------------------

def tp_sharding_rules(cfg: GPTConfig) -> List[Tuple[str, Tuple]]:
    """(param-name regex, PartitionSpec axes) for Megatron-style TP over a
    {'dp','tp'} mesh. Column-parallel: qkv + ffn-in (shard output dim on
    'tp'); row-parallel: attn proj + ffn-out (shard input dim on 'tp');
    embeddings sharded on vocab/ffn axis. The table itself lives in
    parallel/recipes.py (GPT_TP_RULES) — the ONE shared source the
    runtime recipes and the AOT planner both read."""
    from ..parallel.recipes import GPT_TP_RULES

    return list(GPT_TP_RULES)
