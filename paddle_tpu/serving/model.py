"""Serving-side decoder LM: prefill/decode split over a paged KV cache.

The serving twin of ``models/gpt.py``: the SAME parameter names
(``gpt.h<i>.attn.q.w`` ...), expressed as two pure-JAX programs instead
of one training ProgramDesc. WHICH block the one layer body computes is
the configuration's description of it (``GPTConfig.norm``, ``position``,
``qk_norm``, ``bias``, ``mlp``, ``tie_embeddings``): GPT-2's by default
(LayerNorm, learned positions, erf GELU, biases, tied head), OLMoE's
with RMSNorm, rotate-half RoPE, a norm over q and k, bias-free
projections, a router + expert layer (``ops/moe.py``) and an untied
head; and blocks whose layers are of more than one kind
(``GPTConfig.layer_ops`` / ``layer_mlps``): attention over fewer K|V
heads than query heads, gated short convolutions that keep a few gated
inputs per decode slot instead of K and V, dense SwiGLU layers before
the expert ones; latent attention (``"latent"`` layers: q through a
normed low-rank latent, K and V through ONE normed latent a position
beside a few rotated lanes every head shares, YaRN frequencies), whose
pool row is that latent and which attends in two forms, expanded per head
over a prompt and with the up-projections absorbed into q and into the
output on a decode tick; a shared expert beside the routed ones, routing
limited to the best groups of experts, and an expert layer that holds one
chip's share of the router's experts. Pool, donation, programs, names and
insight are one path —

- **prefill**: the whole (bucket-padded) prompt in one causal pass,
  writing every position's K/V into the request's cache blocks and
  returning the first generated token;
- **decode**: one token per active batch slot per tick, scattering the
  new token's K/V into the tail slot and attending over each request's
  own context through its block table: on one device the pallas kernel
  ``ops/pallas/paged_attention`` reads the pages a context occupies
  where they lie; a mesh program (GSPMD cannot partition a Mosaic call)
  and a pool whose row the runtime would pad gather the whole window
  instead (``DecodeModel.attention_path``). Where the embedding table
  rests with its vocabulary on the lanes the tick takes each slot's row
  by a slice of its own, not by a gather that would first copy the whole
  table (``DecodeModel.embed_path``). A tick is two calls,
  ``decode_enqueue`` and ``decode_read``, and takes a slot's last token
  from the host or, unread, from the output of the tick before it
  (``prev``): the engine enqueues tick N+1 before it reads tick N.

Both are AOT-lowered through ``framework/xla_insight.capture`` — the
same single compile that produces the executable also yields the
cost/memory/comms plan, so serving programs are first-class observable
artifacts exactly like training programs (``program_flops`` gauges,
``PADDLE_TPU_XLA_DUMP_DIR`` dumps, and the decode roofline the SERVE
bench reconciles measured tokens/s against).

The KV pool passes through both as ONE donated array in the layout the
chip keeps at rest (``DecodeModel.pool_shape``: rows of whole 128-lane
tiles, per head its K then its V or, under latent attention, the one
latent row; the attention layer folded into the block index; serving/
kv_cache.py says why): each program consumes the pool it is given and
returns the same buffer updated in place, so a caller holds only the
newest handle. A model with conv layers has a second donated array
beside it, the state pool (``DecodeModel.state_shape``): per conv layer
and decode slot the last ``conv_kernel - 1`` gated inputs. Prefill
writes a request's final state into its slot, whole (a prompt shorter
than the state leaves zeros in front), so a slot never shows its last
tenant's; every decode tick reads each slot's, shifts it by the new
input and writes it back. A model without conv layers has none (None).

Sharding comes STRAIGHT off ``parallel/recipes.py``: a resolved recipe
supplies the mesh and the parameter rules (``GPT_TP_RULES`` — qkv/ffn-in
column-parallel, proj/ffn-out row-parallel, vocab-sharded embeddings),
and the KV pool shards its rows' heads over the recipe's tp axis — the
placement the column-sharded qkv weights already imply, not a
serving-local rule. ``shard_insight.verify_scope`` checks the
intended-vs-actual placement at compile time, the same tripwire the
executor arms for training programs.

Numerical contract the engine's tests lean on: every per-row computation
in decode depends only on that row's inputs and that request's own cache
blocks (padded table entries point at the reserved scratch block 0 and
are masked with a finite -1e30 before the softmax), so the same request
produces BIT-IDENTICAL tokens whether it decodes alone or batched with
others — the continuous-batching correctness property.
"""
from __future__ import annotations

import contextlib
import math
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import flags as _flags
from .. import monitor as _monitor
from .. import profiler as _profiler
from ..models.gpt import GPTConfig
from .kv_cache import BLOCK_SIZE, blocks_for_tokens

__all__ = ["GPTConfig", "DecodeModel", "init_params", "calibrate"]

_M_BOOT = _monitor.gauge(
    "serve_boot_seconds",
    "seconds this process spent bringing serving models up: load (the "
    "serve/load spans: weights placed, KV and state pools allocated) and "
    "warm (the serve/warm spans: DecodeModel.warm building programs ahead "
    "of traffic; their stages are program_build_seconds_total)",
    labelnames=("phase",))

_NEG = -1e30  # finite mask value: garbage behind it stays non-NaN
_SUBLANES = 8  # rows of one (8, 128) tile, the unit the TPU lays arrays out in
_LANES = 128  # its columns
_FFN_ROWS = 512  # positions full_logits puts through a feed-forward at once


def rests_lanes_first(shape: Tuple[int, int]) -> bool:
    """Whether the TPU runtime stores a ``[rows, cols]`` array with its
    ROWS on the lanes (``{0,1}``): it picks the most compact tiled layout
    for the shape, each dimension padded to whole (8, 128) tiles, and
    row-major where neither is smaller. ``[50304, 1600]`` (1600 = 12.5 x
    128, 50304 = 393 x 128) rests so; ``[65536, 2048]`` and anything whose
    columns are whole lane tiles rest row-major. A matmul contracts such a
    table as it lies; a row gather first copies ALL of it to row-major
    (tests/test_tpu_aot_compile.py holds the rule to the compiler)."""
    def tiled(sublanes, lanes):
        return (-(-sublanes // _SUBLANES) * _SUBLANES
                * -(-lanes // _LANES) * _LANES)

    rows, cols = shape
    return tiled(cols, rows) < tiled(rows, cols)


def param_table(cfg: GPTConfig) -> Dict[str, Tuple[tuple, float]]:
    """``{name: (shape, std)}`` of every parameter the serving programs
    read, as the block ``cfg`` describes it, under the models/gpt.py
    naming scheme (the names the recipes.py tp rules match). ``std`` 0
    stands for zeros (biases) and -1 for ones (norm gains). Expert
    weights are stacked per layer: ``moe.gate.w`` and ``moe.up.w``
    ``[E, D, F]``, ``moe.down.w`` ``[E, F, D]``; an untied head is
    ``gpt.lm_head.w`` ``[D, V]`` (the training graph's name and shape).
    A layer has the weights of its own kind (``cfg.layer_kind``): ``attn.*``
    (k and v ``kv_heads * head_dim`` wide; a latent layer's seven:
    ``attn.q_down``, ``q_norm``, ``q_up``, ``kv_down``, ``kv_norm``,
    ``kv_up`` ``[H, kv_lora_rank, qk_nope_dim + v_head_dim]``, head by head
    its K up-projection then its V one, so that the absorbed form's two
    per-head matmuls read it as it lies, and ``proj``) or ``conv.*``;
    ``mlp.fc_*``, ``mlp.gate|up|down`` or ``moe.*`` (the stacks hold
    ``cfg.held_experts``, the router is ``n_experts`` wide; a shared expert
    is ``moe.shared.gate|up|down``)."""
    d, v = cfg.d_model, cfg.vocab_size
    res_std = 0.02 / math.sqrt(2 * cfg.n_layer)
    t: Dict[str, Tuple[tuple, float]] = {"gpt.wte": ((v, d), 0.02)}

    def norm(name, shape=(d,)):
        t[f"{name}.scale"] = (shape, -1.0)
        if cfg.norm == "layernorm":
            t[f"{name}.bias"] = (shape, 0.0)

    def linear(name, d_in, d_out, std=0.02):
        t[f"{name}.w"] = ((d_in, d_out), std)
        if cfg.bias:
            t[f"{name}.b"] = ((d_out,), 0.0)

    if cfg.position == "learned":
        t["gpt.wpe"] = ((cfg.max_seq_len, d), 0.02)
    norm("gpt.lnf")
    if not cfg.tie_embeddings:
        t["gpt.lm_head.w"] = ((d, v), 0.02)
    d_kv = cfg.kv_heads * cfg.head_dim
    for i in range(cfg.n_layer):
        ln = f"gpt.h{i}"
        op, mlp = cfg.layer_kind(i)
        dff = cfg.mlp_width(mlp)
        if op == "conv":
            # B | C | X in one projection; the taps [kernel, D], tap j
            # on the input conv_kernel - 1 - j positions back
            t[f"{ln}.conv.in_proj.w"] = ((d, 3 * d), 0.02)
            t[f"{ln}.conv.taps.w"] = ((cfg.conv_kernel, d), 0.5)
            t[f"{ln}.conv.out_proj.w"] = ((d, d), res_std)
            if cfg.conv_bias:
                for part, width in (("in_proj", 3 * d), ("taps", d),
                                    ("out_proj", d)):
                    t[f"{ln}.conv.{part}.b"] = ((width,), 0.0)
        elif op == "latent":
            h = cfg.n_head
            linear(f"{ln}.attn.q_down", d, cfg.q_lora_rank)
            norm(f"{ln}.attn.q_norm", (cfg.q_lora_rank,))
            linear(f"{ln}.attn.q_up", cfg.q_lora_rank,
                   h * (cfg.qk_nope_dim + cfg.qk_rope_dim))
            linear(f"{ln}.attn.kv_down", d, cfg.latent_row)
            norm(f"{ln}.attn.kv_norm", (cfg.kv_lora_rank,))
            t[f"{ln}.attn.kv_up.w"] = ((h, cfg.kv_lora_rank,
                                        cfg.qk_nope_dim + cfg.v_head_dim),
                                       0.02)
            linear(f"{ln}.attn.proj", h * cfg.v_head_dim, d, res_std)
        else:
            linear(f"{ln}.attn.q", d, d)
            linear(f"{ln}.attn.k", d, d_kv)
            linear(f"{ln}.attn.v", d, d_kv)
            linear(f"{ln}.attn.proj", d, d, res_std)
            if cfg.qk_norm == "head":
                norm(f"{ln}.attn.q_norm", (cfg.head_dim,))
                norm(f"{ln}.attn.k_norm", (cfg.head_dim,))
            elif cfg.qk_norm:
                norm(f"{ln}.attn.q_norm")
                norm(f"{ln}.attn.k_norm", (d_kv,))
        if mlp == "moe":
            e = cfg.held_experts[1]
            t[f"{ln}.moe.router.w"] = ((d, cfg.n_experts), 0.02)
            if cfg.router_bias:
                t[f"{ln}.moe.router.bias"] = ((cfg.n_experts,), 0.05)
            t[f"{ln}.moe.gate.w"] = ((e, d, dff), 0.02)
            t[f"{ln}.moe.up.w"] = ((e, d, dff), 0.02)
            t[f"{ln}.moe.down.w"] = ((e, dff, d), res_std)
            if cfg.d_ff_shared:
                linear(f"{ln}.moe.shared.gate", d, cfg.d_ff_shared)
                linear(f"{ln}.moe.shared.up", d, cfg.d_ff_shared)
                linear(f"{ln}.moe.shared.down", cfg.d_ff_shared, d, res_std)
        elif mlp == "swiglu":
            linear(f"{ln}.mlp.gate", d, dff)
            linear(f"{ln}.mlp.up", d, dff)
            linear(f"{ln}.mlp.down", dff, d, res_std)
        else:
            linear(f"{ln}.mlp.fc_in", d, dff)
            linear(f"{ln}.mlp.fc_out", dff, d, res_std)
        norm(f"{ln}.ln1")
        norm(f"{ln}.ln2")
    return t


def init_params(cfg: GPTConfig, seed: int = 0) -> Dict[str, np.ndarray]:
    """Random parameters of :func:`param_table` (N(0, std), residual
    projections scaled by 1/sqrt(2L), gains 1, biases 0). Serving benches
    and tests use this; real deployments load a checkpoint with the same
    names."""
    r = np.random.RandomState(seed)
    p: Dict[str, np.ndarray] = {}
    for name, (shape, std) in param_table(cfg).items():
        if std > 0:
            p[name] = (r.randn(*shape) * std).astype(cfg.dtype)
        else:
            p[name] = np.full(shape, 1.0 if std < 0 else 0.0, cfg.dtype)
    return p


def _rms(x, gain, eps: float):
    """RMSNorm over the last axis, computed in float32 (as the published
    implementations do) and returned in x's dtype."""
    import jax
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                            + eps)
    return xf.astype(x.dtype) * gain


def _rope(x, rot):
    """Rotate-half RoPE of heads ``x`` [..., H, hd] by ``rot`` = (cos,
    sin), each [..., 1, hd/2] float32: the two halves of a head are the
    real and imaginary parts."""
    import jax.numpy as jnp

    cos, sin = rot
    half = x.shape[-1] // 2
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _kv_rows(k, v):
    """K and V ``[..., H, hd]`` of some tokens as rows of the KV pool
    ``[..., H * 2 * hd]``: per head, its K then its V."""
    import jax.numpy as jnp

    kv = jnp.stack([k, v], axis=-2)  # [..., H, 2, hd]
    return kv.reshape(kv.shape[:-3] + (-1,))


def _rope_pairs(x, rot):
    """RoPE of ``x`` [..., r] by ``rot`` = (cos, sin), each broadcastable
    to [..., r/2] float32, where lanes ``(2i, 2i + 1)`` are the real and
    imaginary parts of pair ``i`` (the interleaved convention of the
    latent-attention family; :func:`_rope` pairs lane ``i`` with ``i +
    r/2``)."""
    import jax.numpy as jnp

    cos, sin = rot
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def _latent_rows(c_kv, k_rope, lanes: int):
    """The normed K|V latent ``[..., c]`` and the rotated key lanes
    ``[..., r]`` of some tokens as rows of the latent pool ``[..., lanes]``:
    the latent, the rotated lanes, zeros up to whole tiles."""
    import jax.numpy as jnp

    pad = lanes - c_kv.shape[-1] - k_rope.shape[-1]
    return jnp.concatenate(
        [c_kv, k_rope, jnp.zeros(c_kv.shape[:-1] + (pad,), c_kv.dtype)],
        axis=-1)


def _row_slices(table, idx):
    """``table[idx]`` for ``idx`` ``[B]`` in bounds, as B slices of one
    row each: a slice reads its row out of the table as it lies, whatever
    its layout. A loop on purpose: under ``vmap`` the slices are one
    gather again, and the copy of the table is back."""
    import jax
    import jax.numpy as jnp

    return jnp.concatenate([
        jax.lax.dynamic_slice(table, (idx[b], 0), (1, table.shape[1]),
                              allow_negative_indices=False)
        for b in range(idx.shape[0])])


_LAYER = "gpt.h"  # a layer's parameters inside the traced layer body


def _layer_params(p, i: int):
    """Layer ``i``'s parameters under names that carry no layer number
    (``gpt.h.attn.q.w``): every layer then gives the layer body the same
    argument tree, so the body is traced and lowered once per program,
    not once per layer (set-up time; the compiled program is the same)."""
    pre = f"{_LAYER}{i}."
    return {f"{_LAYER}.{n[len(pre):]}": a for n, a in p.items()
            if n.startswith(pre)}


class _DictScope:
    """Adapt a params dict to the scope protocol verify_scope reads."""

    def __init__(self, params: Dict[str, Any]):
        self._p = params

    def all_var_names(self):
        return list(self._p)

    def has(self, name):
        return name in self._p

    def get(self, name):
        return self._p.get(name)


def calibrate(n: int = 384, copy_mb: int = 16) -> Dict[str, float]:
    """Measure this backend's achievable matmul FLOPs/s, memory
    bandwidth and jit dispatch floor — the denominators of the decode
    roofline. Best-of-3 timings of warm jitted probes; deliberately
    coarse (a roofline is a bound, not a benchmark)."""
    import jax
    import jax.numpy as jnp

    def best(fn, *args):
        fn(*args)  # warm (compile)
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter() - t0)
        return min(ts)

    a = jnp.asarray(np.random.RandomState(0).randn(n, n), jnp.float32)
    mm = jax.jit(lambda x, y: x @ y)
    t_mm = best(mm, a, a)

    m = (copy_mb << 20) // 4
    x = jnp.ones((m,), jnp.float32)
    cp = jax.jit(lambda v: v * 1.0000001)
    t_cp = best(cp, x)

    s = jnp.float32(1.0)
    disp = jax.jit(lambda v: v + 1.0)
    t_disp = best(disp, s)

    return {
        "flops_per_sec": (2.0 * n ** 3) / max(t_mm, 1e-9),
        "bytes_per_sec": (2.0 * m * 4) / max(t_cp, 1e-9),
        "dispatch_s": t_disp,
    }


class DecodeModel:
    """The engine's compute plane: compiled prefill/decode callables +
    their xla_insight cost records, over a fixed (max_batch, kv layout,
    recipe) envelope. The geometry is a deployment's setting: decode
    slots that share one tick (``max_batch``), paged KV-cache blocks
    (``n_blocks``; block 0 is the reserved scratch block) of
    ``block_size`` tokens, and the padded prompt lengths the prefill
    compiles for (``prefill_buckets``: a prompt runs at the smallest
    that holds it, bounding compile count)."""

    def __init__(self, cfg: GPTConfig,
                 params: Optional[Dict[str, np.ndarray]] = None,
                 recipe: Optional[Any] = None,
                 max_batch: int = 8,
                 n_blocks: int = 64,
                 block_size: int = BLOCK_SIZE,
                 prefill_buckets: Sequence[int] = (32, 128, 512),
                 seed: int = 0):
        self.cfg = cfg
        # the kinds of layer this model has, in order of first appearance
        # (one traced body each), and which layers own a share of a pool
        self.kinds = list(dict.fromkeys(
            cfg.layer_kind(i) for i in range(cfg.n_layer)))
        self.latent = bool(cfg.layers_of("latent"))
        if self.latent and cfg.layers_of("attn"):
            raise ValueError(
                "layers of latent attention and of per-head attention in "
                "one model: the KV pool has one row width")
        self.attn_layers = cfg.layers_of("latent" if self.latent else "attn")
        self.conv_layers = cfg.layers_of("conv")
        self.routes = any(mlp == "moe" for _, mlp in self.kinds)
        self.max_batch = int(max_batch)
        self.n_blocks = int(n_blocks)
        self.block_size = int(block_size)
        self.prefill_buckets = sorted(
            min(int(b), cfg.max_seq_len) for b in prefill_buckets)
        # every request's window: the whole (block-padded) context. The
        # table's width, and what the gathered formulation reads
        self.max_blocks_per_req = blocks_for_tokens(cfg.max_seq_len,
                                                    self.block_size)
        self.gather_len = self.max_blocks_per_req * self.block_size

        # -- recipe-driven placement (the ONE sharding source) ----------
        self.recipe = self._resolve_recipe(recipe)
        self.mesh = None
        self.rules: List[Tuple[str, Tuple]] = []
        self.sharding_mismatches: List[dict] = []
        host_params = params if params is not None else init_params(cfg, seed)
        with self._load_span("params") as sp:
            self._place_params(host_params)
            sp.set(param_bytes=sum(int(a.nbytes)
                                   for a in self.params.values()))

        self.insights: Dict[str, Any] = {}
        self._decode_fn = None
        self._prefill_fns: Dict[int, Any] = {}
        self._score_fns: Dict[int, Any] = {}
        # what a decode tick takes as `prev` when no tick ran before it:
        # the shape of its own second output (behind the tokens of a model
        # with experts ride the three ops/moe.py::routing_counts)
        # (four of a model that holds a share of its experts)
        self._no_prev = np.zeros(
            (self.max_batch + (0 if not self.routes else
                               4 if cfg.experts_held else 3),), np.int32)

    # -- placement ------------------------------------------------------

    @contextlib.contextmanager
    def _load_span(self, what: str):
        """A ``serve/load`` span (``what``: params | kv_pool | state_pool;
        ``pool_bytes``: what both pools hold once allocated), its seconds
        added to ``serve_boot_seconds{phase="load"}``."""
        with _profiler.span("serve/load", cat="build", what=what,
                            pool_bytes=self.pool_bytes()) as sp:
            yield sp
        _M_BOOT.labels(phase="load").inc(sp.seconds)

    def _place_params(self, host_params) -> None:
        """``self.params``: the weights on the device, or on the recipe's
        mesh as its rules lay them out."""
        import jax
        import jax.numpy as jnp

        if self.recipe is not None and self.recipe.n_devices > 1:
            if self.routes:
                raise NotImplementedError(
                    f"recipe {self.recipe.name!r} places the model on "
                    f"{self.recipe.n_devices} devices, and a model with "
                    f"experts is served on one: parallel/recipes.py has no "
                    f"placement for the moe.* weights (the `ep` axis) yet")
            if self.conv_layers:
                raise NotImplementedError(
                    f"recipe {self.recipe.name!r} places the model on "
                    f"{self.recipe.n_devices} devices, and a model with "
                    f"conv layers is served on one: parallel/recipes.py has "
                    f"no placement for the conv.* weights or the state pool "
                    f"yet")
            if self.latent:
                raise NotImplementedError(
                    f"recipe {self.recipe.name!r} places the model on "
                    f"{self.recipe.n_devices} devices, and a model with "
                    f"latent attention is served on one: its pool row has no "
                    f"heads to divide, and parallel/recipes.py has no "
                    f"placement for the latent projections yet")

            # a recipe smaller than the host's device pool runs on the
            # leading devices (the CPU-sim tests resolve tp=2 on the
            # 8-device conftest mesh)
            self.mesh = self.recipe.mesh(
                jax.devices()[:self.recipe.n_devices])
            self.rules = self.recipe.sharding_rules()
            self.params = {
                name: jax.device_put(
                    np.asarray(arr),
                    self.recipe.param_sharding(self.mesh, name, arr,
                                               self.rules))
                for name, arr in host_params.items()
            }
            self._verify_placement()
        else:
            self.params = {name: jnp.asarray(arr)
                           for name, arr in host_params.items()}

    @staticmethod
    def _resolve_recipe(recipe):
        from ..parallel.recipes import ResolvedRecipe, resolve_recipe

        if recipe is None:
            name = str(_flags.env_flag("PADDLE_TPU_SERVE_RECIPE")).strip()
            if not name:
                return None
            import jax

            return resolve_recipe(name, jax.device_count())
        if isinstance(recipe, ResolvedRecipe):
            return recipe
        import jax

        return resolve_recipe(recipe, jax.device_count())

    def _verify_placement(self) -> None:
        """Compile-time intended-vs-actual sharding check — the same
        verify_scope tripwire the executor arms for training programs
        (counts on sharding_mismatch_total, lands in the flight
        recorder)."""
        from ..framework import shard_insight

        if not shard_insight.verify_enabled():
            return
        self.sharding_mismatches = shard_insight.verify_scope(
            _DictScope(self.params), self.mesh, self.rules)

    def pool_shape(self) -> Tuple[int, int, int]:
        """The KV pool ``[n_attn_layers * n_blocks, block_size, row]``: the
        ``a``-th attention layer's block ``b`` is row-block ``a * n_blocks
        + b`` (a layer that does not attend owns nothing here). What a
        token's row holds is the block's: K|V head by K|V head, that
        head's K then its V (``kv_heads * 2 * head_dim`` lanes, 128 a head
        at GPT-2's 64); or, under latent attention, ONE row for all heads:
        the normed K|V latent, the rotated key lanes, zeros up to whole
        128-lane tiles (512 + 64 + 64 = 640 lanes where per-head K and V
        would take 64 x 320).

        Why this shape: the TPU runtime stores an array in the most
        compact tiled layout FOR ITS SHAPE, and a program whose gather
        and scatter need another one opens and closes with a copy of the
        whole array. With a row that is a whole number of 128-lane tiles
        over a 16-token block, the layout at rest is row-major, which is
        what gather and scatter by block id work on: the pool is donated,
        updated in place and never copied (tests/test_tpu_aot_compile.py
        holds the compiled programs to that; tools/serve_compile_report.py
        prints it for any widths). Where a model's row is not such a
        multiple the runtime pads it, and nothing here needs to know."""
        cfg = self.cfg
        row = (-(-cfg.latent_row // _LANES) * _LANES if self.latent
               else cfg.kv_heads * 2 * cfg.head_dim)
        return (len(self.attn_layers) * self.n_blocks, self.block_size, row)

    def state_shape(self) -> Optional[Tuple[int, int, int, int]]:
        """The state pool ``[n_conv_layers, conv_kernel - 1, max_batch,
        d_model]``: per conv layer and decode slot the gated inputs of the
        last ``conv_kernel - 1`` positions, oldest first. The slots lie on
        the sublanes (whole tiles at 16 slots or more), so a tick reads
        and writes a layer's states as one dense ``[kernel - 1, B, D]``
        slab and a prefill one ``[kernel - 1, 1, D]`` column of it. None
        for a model without conv layers."""
        if not self.conv_layers:
            return None
        return (len(self.conv_layers), self.cfg.conv_kernel - 1,
                self.max_batch, self.cfg.d_model)

    def pool_bytes(self) -> int:
        """Bytes of the KV pool and the state pool together."""
        import jax.numpy as jnp

        elems = math.prod(self.pool_shape()) + math.prod(
            self.state_shape() or (0,))
        return elems * jnp.dtype(self.cfg.dtype).itemsize

    def attention_path(self) -> Tuple[str, str]:
        """How the decode program attends, and why: ``("kernel", "")`` is
        ``ops/pallas/paged_attention`` over the pool as it lies;
        ``("gather", reason)`` the XLA formulation over the gathered
        window. Decided by what the model can see of itself, the same on
        every backend."""
        from ..ops.pallas import paged_attention as pa

        if self.mesh is not None:
            return "gather", ("a mesh program: GSPMD cannot partition a "
                              "Mosaic call")
        if self.latent:
            why = pa.unsupported(
                0, self.block_size, self.cfg.dtype,
                latent=(self.pool_shape()[2], self.cfg.kv_lora_rank))
        else:
            why = pa.unsupported(self.cfg.head_dim, self.block_size,
                                 self.cfg.dtype, self.cfg.n_head,
                                 self.cfg.kv_heads)
        return ("gather", why) if why else ("kernel", "")

    def attention_step(self):
        """How the paged kernel walks a slot's pages at this pool's row
        (``ops/pallas/paged_attention.step_schedule``, the rule the kernel
        itself sizes its step by); None where the decode program gathers
        the window instead and no kernel steps."""
        import jax.numpy as jnp

        from ..ops.pallas import paged_attention as pa

        if self.attention_path()[0] != "kernel":
            return None
        return pa.step_schedule(
            self.pool_shape()[2] * jnp.dtype(self.cfg.dtype).itemsize)

    def _pages_sharding(self):
        """KV pool placement, None off a mesh: the row shards over the
        recipe's tp axis, which gives each device whole heads (K and V
        together) — the layout the column-sharded qkv weights already
        imply. The degrade rule judges the HEAD count, so a row that
        divides where the heads do not stays replicated."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec

        from ..parallel.mesh import clean_spec

        rows, bs, _ = self.pool_shape()
        spec = PartitionSpec(None, None, self.recipe.layout.tp_axis)
        return NamedSharding(self.mesh, clean_spec(
            spec, (rows, bs, self.cfg.kv_heads), self.mesh))

    def init_pages(self):
        """A zeroed KV pool (:meth:`pool_shape`; block 0 of every layer
        is scratch). The decode and prefill programs consume the array
        they are given and return its successor: hold only the newest."""
        import jax.numpy as jnp

        with self._load_span("kv_pool"):
            return jnp.zeros(self.pool_shape(), self.cfg.dtype,
                             device=self._pages_sharding())

    def init_state(self):
        """A zeroed state pool (:meth:`state_shape`), or None where the
        model keeps none. Donated and returned like the KV pool."""
        import jax.numpy as jnp

        shape = self.state_shape()
        if shape is None:
            return None
        with self._load_span("state_pool"):
            return jnp.zeros(shape, self.cfg.dtype)

    # -- shared forward pieces -----------------------------------------

    def _linear(self, p, x, name):
        y = x @ p[f"{name}.w"]
        return y + p[f"{name}.b"] if self.cfg.bias else y

    def _ln_p(self, p, x, name):
        """The block's norm: LayerNorm, or RMSNorm (no mean, no bias)."""
        import jax.numpy as jnp

        if self.cfg.norm == "rmsnorm":
            return _rms(x, p[f"{name}.scale"], self.cfg.norm_eps)
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return ((x - mu) / jnp.sqrt(var + self.cfg.norm_eps)
                * p[f"{name}.scale"] + p[f"{name}.bias"])

    def _swiglu(self, p, x, name):
        """``(silu(x W_gate) * (x W_up)) W_down`` of the weights ``name.*``."""
        import jax

        h = (jax.nn.silu(self._linear(p, x, f"{name}.gate"))
             * self._linear(p, x, f"{name}.up"))
        return self._linear(p, h, f"{name}.down")

    def _mlp(self, p, x, ln, mlp: str = "gelu"):
        import jax

        if mlp == "swiglu":
            return self._swiglu(p, x, f"{ln}.mlp")
        h = jax.nn.gelu(self._linear(p, x, f"{ln}.mlp.fc_in"),
                        approximate=False)
        return self._linear(p, h, f"{ln}.mlp.fc_out")

    def embed_path(self) -> Tuple[str, str]:
        """How the decode program looks up its ``max_batch`` token rows,
        and why: ``("slices", reason)`` is one ``dynamic_slice`` a slot,
        for a table that rests with the vocabulary on the lanes
        (:func:`rests_lanes_first`), which a gather would first copy
        whole, every tick; ``("gather", reason)`` is one gather over a
        table that rests row-major. Decided by what the model can see of
        itself, the same on every backend. Prefill and ``score`` always
        gather: a bucket's worth of row slices of such a table reads as
        much as the copy does."""
        v, d = self.cfg.vocab_size, self.cfg.d_model
        if self.mesh is not None:
            return "gather", "a mesh program: the recipe places the table"
        if rests_lanes_first((v, d)):
            return "slices", (f"the [{v}, {d}] table rests vocabulary-on-"
                              f"lanes: {d} is no multiple of {_LANES}")
        return "gather", f"the [{v}, {d}] table rests row-major"

    def _embed(self, p, tokens, pos, slices: bool = False):
        """Token rows, plus the learned position rows where the block has
        them (``pos`` broadcasts against ``tokens``). ``slices``: a row at
        a time (``tokens`` and ``pos`` ``[B]``), the same bits."""
        rows = _row_slices if slices else (lambda table, idx: table[idx])
        x = rows(p["gpt.wte"], tokens)
        if self.cfg.position == "learned":
            x = x + rows(p["gpt.wpe"], pos)
        return x

    def _rot(self, pos):
        """What :func:`_rope` turns heads at positions ``pos`` [...] by:
        (cos, sin) [..., 1, hd/2], or None where positions are learned.
        Under latent attention only ``qk_rope_dim`` lanes turn
        (:func:`_rope_pairs`), by the block's YaRN description where it has
        one: its frequencies at every position, cos and sin scaled by its
        attention factor."""
        import jax.numpy as jnp

        if self.cfg.position != "rope":
            return None
        if self.latent:
            cfg = self.cfg
            yarn, r = cfg.rope_yarn, cfg.qk_rope_dim
            inv = (yarn.inv_freq(r, cfg.rope_theta) if yarn is not None else
                   cfg.rope_theta ** (-np.arange(0, r, 2, dtype=np.float64)
                                      / r))
            ang = (pos.astype(jnp.float32)[..., None, None]
                   * jnp.asarray(inv, jnp.float32))
            gain = yarn.attention_factor() if yarn is not None else 1.0
            cos, sin = jnp.cos(ang), jnp.sin(ang)
            return (cos, sin) if gain == 1.0 else (cos * gain, sin * gain)
        half = self.cfg.head_dim // 2
        inv = self.cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32)
                                      / half)
        ang = pos.astype(jnp.float32)[..., None, None] * inv
        return jnp.cos(ang), jnp.sin(ang)

    def _qkv(self, lp, h, rot, lead: tuple):
        """q ``[*lead, H, hd]``, k and v ``[*lead, H_kv, hd]`` of normed
        hidden ``h`` ``[*lead, D]``: the projections, the split into
        heads, the block's norm over q and k where it has one (over all
        of a token's lanes before the split, or over each head's own
        after it), RoPE by ``rot``. The K that leaves here is the K the
        pool keeps."""
        import jax

        cfg, ln = self.cfg, _LAYER
        q = self._linear(lp, h, f"{ln}.attn.q")
        k = self._linear(lp, h, f"{ln}.attn.k")
        v = self._linear(lp, h, f"{ln}.attn.v")

        def qk_norm(q, k):
            with jax.named_scope("attn/qk_norm"):
                return (_rms(q, lp[f"{ln}.attn.q_norm.scale"], cfg.norm_eps),
                        _rms(k, lp[f"{ln}.attn.k_norm.scale"], cfg.norm_eps))

        if cfg.qk_norm and cfg.qk_norm != "head":
            q, k = qk_norm(q, k)
        q = q.reshape(*lead, cfg.n_head, cfg.head_dim)
        k, v = (a.reshape(*lead, cfg.kv_heads, cfg.head_dim) for a in (k, v))
        if cfg.qk_norm == "head":
            q, k = qk_norm(q, k)
        if rot is not None:
            with jax.named_scope("attn/rope"):
                q, k = _rope(q, rot), _rope(k, rot)
        return q, k, v

    def _latent_qkv(self, lp, h, rot, lead: tuple):
        """Latent attention's projections of normed hidden ``h`` ``[*lead,
        D]``: (q_nope ``[*lead, H, qk_nope_dim]``, q_rope ``[*lead, H,
        qk_rope_dim]`` rotated, the K|V latent ``[*lead, kv_lora_rank]``
        AFTER its norm, the rotated key lanes ``[*lead, qk_rope_dim]``, one
        set a position for every head). The last two are what the pool
        keeps and what decode scores."""
        import jax

        cfg, ln = self.cfg, _LAYER
        with jax.named_scope("attn/q_lora"):
            c_q = _rms(self._linear(lp, h, f"{ln}.attn.q_down"),
                       lp[f"{ln}.attn.q_norm.scale"], cfg.norm_eps)
            q = self._linear(lp, c_q, f"{ln}.attn.q_up").reshape(
                *lead, cfg.n_head, cfg.qk_nope_dim + cfg.qk_rope_dim)
            q_nope, q_rope = q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
        with jax.named_scope("attn/kv_latent"):
            ckr = self._linear(lp, h, f"{ln}.attn.kv_down")
            c_kv = _rms(ckr[..., :cfg.kv_lora_rank],
                        lp[f"{ln}.attn.kv_norm.scale"], cfg.norm_eps)
            k_rope = ckr[..., cfg.kv_lora_rank:]
        with jax.named_scope("attn/rope"):
            q_rope = _rope_pairs(q_rope, rot)
            # the key's lanes are one "head": rot broadcasts over that axis
            k_rope = _rope_pairs(k_rope[..., None, :], rot)[..., 0, :]
        return q_nope, q_rope, c_kv, k_rope

    def _kv_up(self, lp):
        """The K|V up-projection by head: (W_uk ``[H, c, qk_nope_dim]``,
        W_uv ``[H, c, v_head_dim]``), no bias."""
        w = lp[f"{_LAYER}.attn.kv_up.w"]
        return w[..., :self.cfg.qk_nope_dim], w[..., self.cfg.qk_nope_dim:]

    def _latent_scale(self) -> float:
        """Latent attention's softmax scale: over the scored head width,
        times YaRN's gain where the block has one."""
        cfg = self.cfg
        gain = cfg.rope_yarn.softmax_gain() if cfg.rope_yarn else 1.0
        return gain / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)

    def _ffn(self, lp, x, mlp: str):
        """A layer's second half on the residual stream ``x``: norm, the
        feed-forward of kind ``mlp``, residual add. Returns (x, each row's
        top-k expert ids ``[rows, k]``, or None where the layer has no
        experts)."""
        import jax

        cfg, ln = self.cfg, _LAYER
        if mlp != "moe":
            with jax.named_scope("mlp"):
                return x + self._mlp(
                    lp, self._ln_p(lp, x, f"{ln}.ln2"), ln, mlp), None
        from ..ops import moe

        # what the router's description departs from ops/moe.py's defaults
        # by (softmax scores, weights as they are), and no more
        how: Dict[str, Any] = {}
        if cfg.router_score != "softmax":
            how["score"] = cfg.router_score
        if cfg.router_bias:
            how["bias"] = lp[f"{ln}.moe.router.bias"]
        if cfg.norm_topk:
            how["norm_topk"] = True
        if cfg.routed_scale != 1.0:
            how["scale"] = cfg.routed_scale
        if cfg.norm_topk_eps != 1e-6:
            how["norm_eps"] = cfg.norm_topk_eps
        if cfg.router_groups > 1:
            how.update(groups=cfg.router_groups,
                       keep_groups=cfg.router_keep_groups)
        h = self._ln_p(lp, x, f"{ln}.ln2").reshape(-1, cfg.d_model)
        with jax.named_scope("moe/route"):
            dense, idx = moe.route(h, lp[f"{ln}.moe.router.w"],
                                   cfg.experts_per_token, **how)
        with jax.named_scope("moe/experts"):
            y = moe.experts(h, dense, lp[f"{ln}.moe.gate.w"],
                            lp[f"{ln}.moe.up.w"], lp[f"{ln}.moe.down.w"],
                            share=cfg.experts_held)
        if cfg.d_ff_shared:
            with jax.named_scope("moe/shared"):
                y = y + self._swiglu(lp, h, f"{ln}.moe.shared")
        return x + y.reshape(x.shape), idx

    def _conv_in(self, lp, h):
        """A conv layer's input side: ``h W_in`` split into B, C, X."""
        import jax
        import jax.numpy as jnp

        with jax.named_scope("conv/in_proj"):
            bcx = h @ lp[f"{_LAYER}.conv.in_proj.w"]
            if self.cfg.conv_bias:
                bcx = bcx + lp[f"{_LAYER}.conv.in_proj.b"]
            return jnp.split(bcx, 3, axis=-1)

    def _conv_out(self, lp, c, taps_sum):
        """The output side: ``(C * conv) W_out``; ``taps_sum`` float32."""
        import jax

        with jax.named_scope("conv/mix"):
            if self.cfg.conv_bias:
                taps_sum = taps_sum + lp[f"{_LAYER}.conv.taps.b"]
            y = c * taps_sum.astype(c.dtype)
        with jax.named_scope("conv/out_proj"):
            y = y @ lp[f"{_LAYER}.conv.out_proj.w"]
            return (y + lp[f"{_LAYER}.conv.out_proj.b"]
                    if self.cfg.conv_bias else y)

    def _conv_prompt(self, lp, i, h, L: int, state_dest):
        """A conv layer over the whole prompt ``h`` [1, L, D], causal and
        depthwise: position t sums tap j over the gated input ``kernel - 1
        - j`` positions back, zeros before position 0. ``state_dest =
        (state, length, slot)`` is prefill's: the gated inputs of the last
        ``kernel - 1`` positions before ``length`` go to conv layer ``i``'s
        state of ``slot``. Returns (the operator's output, state_dest)."""
        import jax
        import jax.numpy as jnp

        K = self.cfg.conv_kernel
        b, c, x = self._conv_in(lp, h)
        with jax.named_scope("conv/mix"):
            # the gated input z of position t at row t + K - 1, zeros in front
            zp = jnp.pad(b * x, ((0, 0), (K - 1, 0), (0, 0)))
            taps = lp[f"{_LAYER}.conv.taps.w"].astype(jnp.float32)
            mixed = sum(taps[j] * zp[:, j:j + L].astype(jnp.float32)
                        for j in range(K))
        if state_dest is not None:
            state, length, slot = state_dest
            with jax.named_scope("conv/state_write"):
                # positions length - K + 1 .. length - 1 lie K - 1 rows
                # further down: rows length .. length + K - 2
                last = jax.lax.dynamic_slice_in_dim(zp[0], length, K - 1)
                state = jax.lax.dynamic_update_slice(
                    state, last[None, :, None, :].astype(state.dtype),
                    (i, 0, slot, 0))
            state_dest = (state, length, slot)
        return self._conv_out(lp, c, mixed), state_dest

    def _conv_step(self, lp, i, h, state):
        """A conv layer on one new token a slot, ``h`` [B, D]: the taps
        over conv layer ``i``'s state of every slot and the new gated
        input, then the state shifted by it. Returns (output, state)."""
        import jax
        import jax.numpy as jnp

        K = self.cfg.conv_kernel
        b, c, x = self._conv_in(lp, h)
        with jax.named_scope("conv/mix"):
            z = b * x
            taps = lp[f"{_LAYER}.conv.taps.w"].astype(jnp.float32)
            past = jax.lax.dynamic_index_in_dim(state, i, keepdims=False)
            mixed = taps[K - 1] * z.astype(jnp.float32) + sum(
                taps[j] * past[j].astype(jnp.float32) for j in range(K - 1))
        with jax.named_scope("conv/state_write"):
            shifted = jnp.concatenate(
                [past[1:], z[None].astype(state.dtype)], axis=0)
            state = jax.lax.dynamic_update_slice(
                state, shifted[None], (i, 0, 0, 0))
        return self._conv_out(lp, c, mixed), state

    def _grouped(self, kv):
        """K or V ``[..., H_kv, hd]`` as every query head sees it, ``[...,
        H, hd]``: query head ``i`` reads K|V head ``i // group``."""
        import jax.numpy as jnp

        group = self.cfg.n_head // self.cfg.kv_heads
        return kv if group == 1 else jnp.repeat(kv, group, axis=-2)

    def _layer_fn(self, body, kind):
        """``body`` as the traced layer of ``kind``: an inner jit, traced
        once for all layers of its kind (``_layer_params`` gives them one
        argument tree). A model of one kind of layer has the one body
        ``layer``; of several, each is named by its kind
        (``layer_conv_moe``), so that a trace tells them apart."""
        import jax

        name = "layer" if len(self.kinds) == 1 else "layer_" + "_".join(kind)
        body.__name__ = body.__qualname__ = name
        return jax.jit(body)

    def _place(self, i: int):
        """Layer ``i``'s kind, and its place among the layers that share
        its pool: the attention layers' KV pool or the conv layers' state
        pool."""
        kind = self.cfg.layer_kind(i)
        own = self.conv_layers if kind[0] == "conv" else self.attn_layers
        return kind, own.index(i)

    def _logits(self, p, x):
        """Vocabulary logits of final-normed hidden ``x`` [..., D]."""
        if self.cfg.tie_embeddings:
            return x @ p["gpt.wte"].T
        return x @ p["gpt.lm_head.w"]

    # -- prefill --------------------------------------------------------

    def bucket_for(self, prompt_len: int) -> Optional[int]:
        for b in self.prefill_buckets:
            if prompt_len <= b:
                return b
        return None

    def _prompt_op(self, op: str, L: int, lp, i, x, causal, kv_dest,
                   state_dest, rot):
        """A layer's first half over the whole prompt ``x`` [1, L, D]:
        norm, the operator ``op`` (the ``i``-th of the layers that share
        its pool), residual add. Returns (x, kv_dest, state_dest)."""
        import jax
        import jax.numpy as jnp

        cfg, NB, ln = self.cfg, self.n_blocks, _LAYER
        h = self._ln_p(lp, x, f"{ln}.ln1")
        if op == "conv":
            y, state_dest = self._conv_prompt(lp, i, h, L, state_dest)
            x = x + y
        elif op == "latent":
            # the expanded form: K and V of every head from the latent
            q_nope, q_rope, c_kv, k_rope = self._latent_qkv(lp, h, rot,
                                                            (1, L))
            if kv_dest is not None:
                pages, blk, slot = kv_dest
                with jax.named_scope("attn/kv_write"):
                    pages = pages.at[i * NB + blk, slot].set(_latent_rows(
                        c_kv[0], k_rope[0], pages.shape[-1]))
                kv_dest = (pages, blk, slot)
            with jax.named_scope("attn/kv_expand"):
                w_uk, w_uv = self._kv_up(lp)
                k_nope = jnp.einsum("blc,hcn->blhn", c_kv, w_uk)
                v = jnp.einsum("blc,hcv->blhv", c_kv, w_uv)
            with jax.named_scope("attn/scores"):
                q = jnp.concatenate([q_nope, q_rope], axis=-1)
                k = jnp.concatenate([k_nope, jnp.broadcast_to(
                    k_rope[:, :, None, :], q_rope.shape)], axis=-1)
                s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * self._latent_scale()
                s = jnp.where(causal[None, None], s, _NEG)
                a = jax.nn.softmax(s, axis=-1)
                o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(1, L, -1)
            x = x + self._linear(lp, o, f"{ln}.attn.proj")
        else:
            q, k, v = self._qkv(lp, h, rot, (1, L))
            if kv_dest is not None:
                pages, blk, slot = kv_dest
                with jax.named_scope("attn/kv_write"):
                    pages = pages.at[i * NB + blk, slot].set(
                        _kv_rows(k[0], v[0]))
                kv_dest = (pages, blk, slot)
            k, v = self._grouped(k), self._grouped(v)
            with jax.named_scope("attn/scores"):
                s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (
                    1.0 / math.sqrt(cfg.head_dim))
                s = jnp.where(causal[None, None], s, _NEG)
                a = jax.nn.softmax(s, axis=-1)
                o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(1, L, -1)
            x = x + self._linear(lp, o, f"{ln}.attn.proj")
        return x, kv_dest, state_dest

    def _prompt_trunk(self, p, tokens, L: int, kv_dest=None,
                      state_dest=None):
        """The full-prompt causal transformer forward shared by prefill
        and scoring: [1, L] tokens -> final-LN hidden states [1, L, D].
        ``kv_dest = (pages, blk, slot)`` is prefill's: the pool, and per
        position the block and slot its K/V go to; every attention layer
        scatters there. ``state_dest = (state, length, slot)`` likewise:
        every conv layer writes the prompt's final state into the decode
        slot. Scoring keeps nothing (None). Returns (hidden, pages,
        state)."""
        import jax
        import jax.numpy as jnp

        def traced(kind):
            op, mlp = kind

            def layer(lp, i, x, causal, kv_dest, state_dest, rot):
                x, kv_dest, state_dest = self._prompt_op(
                    op, L, lp, i, x, causal, kv_dest, state_dest, rot)
                return self._ffn(lp, x, mlp)[0], kv_dest, state_dest
            return self._layer_fn(layer, kind)

        layers = {kind: traced(kind) for kind in self.kinds}
        pos = jnp.arange(L)
        with jax.named_scope("embed"):
            x = self._embed(p, tokens, pos)  # [1, L, D]
        causal = pos[:, None] >= pos[None, :]
        rot = self._rot(pos[None])
        for i in range(self.cfg.n_layer):
            kind, own = self._place(i)
            x, kv_dest, state_dest = layers[kind](
                _layer_params(p, i), own, x, causal, kv_dest, state_dest, rot)
        return (self._ln_p(p, x, "gpt.lnf"),
                None if kv_dest is None else kv_dest[0],
                None if state_dest is None else state_dest[0])

    def _build_prefill(self, L: int):
        """The bucket-L prefill program: causal pass over [1, L], K/V
        scattered into the request's blocks, the conv layers' final state
        into decode slot ``slot_id`` (``state`` None for a model that
        keeps none), and the argmax token at length-1 written into
        ``prev[slot_id]``: ``prev`` is the tick in flight's second output
        (or ``_no_prev``), so what comes back is what the next decode tick
        takes as ITS ``prev``, the new slot's first token in it, without
        the host (routing counts behind ``[:B]`` pass through)."""
        import jax
        import jax.numpy as jnp

        BS = self.block_size

        def prefill(p, pages, state, tokens, length, block_ids, slot_id,
                    prev):
            pos = jnp.arange(L)
            blk = jnp.where(pos < length, block_ids[pos // BS], 0)
            slot = jnp.where(pos < length, pos % BS, 0)
            x, pages, state = self._prompt_trunk(
                p, tokens, L, (pages, blk, slot),
                None if state is None else (state, length, slot_id))
            with jax.named_scope("lm_head"):
                last = jnp.take(x, length - 1, axis=1)  # [1, D]
                logits = self._logits(p, last)  # [1, V]
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                nxt = jax.lax.dynamic_update_slice(prev, nxt, (slot_id,))
            return pages, nxt, state  # None: no third result

        return self._compile(prefill, "prefill", L)

    # -- prompt scoring -------------------------------------------------

    def _build_score(self, L: int):
        """The bucket-L scoring program: per-token NLL of the prompt
        under the model — the SAME fused lm-head+CE pallas kernel the
        training loss path runs (ops/pallas/fused_lmhead_ce), so the
        serving twin's prefill scoring never materializes the
        [tokens, vocab] logits either. No KV pages: scoring reads the
        whole prompt once and keeps nothing, and the transformer forward
        is THE shared ``_prompt_trunk`` prefill runs — score cannot
        drift from the model that decodes."""
        import jax.numpy as jnp

        from ..ops.pallas.fused_lmhead_ce import lmhead_ce

        def score(p, tokens, length):
            x = self._prompt_trunk(p, tokens, L)[0]
            # positions 0..L-2 predict tokens 1..L-1; padded tail masked
            head = (p["gpt.wte"] if self.cfg.tie_embeddings
                    else p["gpt.lm_head.w"].T)
            nll = lmhead_ce(x[0, :L - 1], head, tokens[0, 1:])
            valid = jnp.arange(L - 1) < (length - 1)
            nll = jnp.where(valid, nll, 0.0)
            return nll, jnp.sum(nll)

        return self._compile(score, "score", L)

    def score(self, tokens, length: Optional[int] = None):
        """Per-token NLL of a prompt (the scoring API): returns
        (nll[np, length-1], total_nll). Runs at the smallest prefill
        bucket that holds the prompt, like prefill itself."""
        from ..framework import errors as _errors

        import jax.numpy as jnp

        toks = np.asarray(tokens, np.int32).reshape(-1)
        n = int(length) if length is not None else int(toks.size)
        L = self.bucket_for(n)
        if L is None:
            raise _errors.errors.InvalidArgument(
                f"prompt of {n} tokens exceeds the largest prefill "
                f"bucket {self.prefill_buckets[-1]}")
        if L not in self._score_fns:
            self._score_fns[L] = self._build_score(L)
        padded = np.zeros((1, L), np.int32)
        padded[0, :n] = toks[:n]
        nll, total = self._score_fns[L](
            self.params, jnp.asarray(padded), jnp.int32(n))
        return np.asarray(nll)[:max(0, n - 1)], float(total)

    # -- decode ---------------------------------------------------------

    def _build_decode(self):
        """The continuous-batching decode program: one token per slot,
        attending over the slot's own context through its block table
        (:meth:`attention_path`: the paged kernel, or the gathered
        window); a conv layer reads, shifts and writes its slots' states.
        Inactive slots carry all-zero tables (reads masked, writes land in
        the scratch block; a conv layer's write lands in the idle slot's
        own state, which the next prefill into it replaces whole) so the
        program is shape-stable at max_batch."""
        import jax
        import jax.numpy as jnp

        from ..ops import moe
        from ..ops.pallas.paged_attention import (paged_attention,
                                                  paged_latent_attention)

        cfg, BS, NB = self.cfg, self.block_size, self.n_blocks
        B, H, hd = self.max_batch, cfg.kv_heads, cfg.head_dim
        G = cfg.n_head // H  # query heads over one K|V head
        S = self.gather_len
        T = math.gcd(S, _SUBLANES)
        scale = 1.0 / math.sqrt(hd)
        barange = jnp.arange(B)

        def attend_paged(q, pages, tables, pos, valid):
            # the new token's K and V are in the pool: the kernel reads
            # the pages up to `pos` where they lie, and no other
            with jax.named_scope("attn/paged"):
                return paged_attention(q, pages, tables, pos, scale)

        def attend_gathered(q, pages, tables, pos, valid):
            with jax.named_scope("attn/kv_gather"):
                # [B, MAXB, BS, H*2*hd] seen as [B, S/T, H, T, 2*hd]: T
                # tokens of one head are one (T, 128) tile of the gathered
                # rows as they lie in HBM, so this view moves nothing. (A
                # [B, S, H, 2*hd] view wants H on the sublanes and relays
                # every layer's context out: a third of the tick.)
                ctx = pages[tables].reshape(
                    B, S // T, T, H, 2 * hd).transpose(0, 1, 3, 2, 4)
                kk, vv = ctx[..., :hd], ctx[..., hd:]
            with jax.named_scope("attn/scores"):
                q = q.reshape(B, H, G, hd)
                s = jnp.einsum("bhgd,bjhtd->bhgjt", q, kk).reshape(
                    B, H * G, S) * scale
                s = jnp.where(valid[:, None, :], s, _NEG)
                a = jax.nn.softmax(s, axis=-1).reshape(B, H, G, S // T, T)
                return jnp.einsum("bhgjt,bjhtd->bhgd", a, vv).reshape(B, -1)

        def attend_latent_paged(q, pages, tables, pos, valid):
            with jax.named_scope("attn/paged"):
                return paged_latent_attention(
                    q, pages, tables, pos, self._latent_scale(),
                    cfg.kv_lora_rank)

        def attend_latent_gathered(q, pages, tables, pos, valid):
            with jax.named_scope("attn/kv_gather"):
                ctx = pages[tables].reshape(B, S, -1)[..., :q.shape[-1]]
            with jax.named_scope("attn/scores"):
                s = jnp.einsum("bhc,bsc->bhs", q, ctx) * self._latent_scale()
                s = jnp.where(valid[:, None, :], s, _NEG)
                a = jax.nn.softmax(s, axis=-1)
                return jnp.einsum("bhs,bsc->bhc", a,
                                  ctx[..., :cfg.kv_lora_rank])

        kernel = self.attention_path()[0] == "kernel"
        attend = attend_paged if kernel else attend_gathered
        if self.latent:
            attend = attend_latent_paged if kernel else attend_latent_gathered
        sliced = self.embed_path()[0] == "slices"

        def traced(kind):
            op, mlp = kind

            def layer(lp, i, x, pages, state, block_tables, blk, slot, pos,
                      valid, rot, live):
                ln = _LAYER
                h = self._ln_p(lp, x, f"{ln}.ln1")
                if op == "conv":
                    y, state = self._conv_step(lp, i, h, state)
                    x = x + y
                elif op == "latent":
                    # the absorbed form: scores against the rows as they
                    # lie, the up-projections moved into q and the output
                    q_nope, q_rope, c_kv, k_rope = self._latent_qkv(
                        lp, h, rot, (B,))
                    with jax.named_scope("attn/kv_write"):
                        pages = pages.at[i * NB + blk, slot].set(
                            _latent_rows(c_kv, k_rope, pages.shape[-1]))
                    w_uk, w_uv = self._kv_up(lp)
                    with jax.named_scope("attn/absorb_q"):
                        q = jnp.concatenate(
                            [jnp.einsum("bhn,hcn->bhc", q_nope, w_uk),
                             q_rope], axis=-1)
                    o = attend(q, pages, i * NB + block_tables, pos, valid)
                    with jax.named_scope("attn/absorb_o"):
                        o = jnp.einsum("bhc,hcv->bhv", o, w_uv).reshape(B, -1)
                    x = x + self._linear(lp, o, f"{ln}.attn.proj")
                else:
                    q, k, v = self._qkv(lp, h, rot, (B,))
                    # the layer is part of the block index: no slice of
                    # the pool is ever materialised
                    with jax.named_scope("attn/kv_write"):
                        pages = pages.at[i * NB + blk, slot].set(
                            _kv_rows(k, v))
                    x = x + self._linear(
                        lp, attend(q, pages, i * NB + block_tables, pos,
                                   valid), f"{ln}.attn.proj")
                x, idx = self._ffn(lp, x, mlp)
                return x, pages, state, (
                    None if idx is None else
                    moe.routing_counts(idx, live, cfg.n_experts,
                                       cfg.experts_held))
            return self._layer_fn(layer, kind)

        layers = {kind: traced(kind) for kind in self.kinds}

        def decode_tick(p, pages, state, block_tables, context_lens, tokens,
                        prev):
            # a slot whose last token the host has not read sends -1: the
            # token is the previous tick's own output, still on the device
            tokens = jnp.where(tokens < 0, prev[:B], tokens)
            pos = context_lens  # [B]: the new token's position
            with jax.named_scope("embed"):
                x = self._embed(p, tokens, pos, sliced)  # [B, D]
            blk = block_tables[barange, pos // BS]  # [B]
            slot = pos % BS
            # the gathered window's mask; the kernel masks by `pos`
            valid = (None if kernel else
                     jnp.arange(S)[None, :] <= pos[:, None])  # [B, S]
            rot = self._rot(pos)
            # a slot in use has a prompt behind it; an empty one is at 0
            live = pos > 0 if self.routes else None
            routing = []
            for i in range(cfg.n_layer):
                kind, own = self._place(i)
                x, pages, state, counts = layers[kind](
                    _layer_params(p, i), own, x, pages, state, block_tables,
                    blk, slot, pos, valid, rot, live)
                if counts is not None:
                    routing.append(counts)
            with jax.named_scope("lm_head"):
                x = self._ln_p(p, x, "gpt.lnf")
                logits = self._logits(p, x)  # [B, V]
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if live is not None:
                # the routing counts ride behind the tokens: one read-back
                nxt = jnp.concatenate([nxt, sum(routing)])
            return pages, nxt, state  # None: no third result

        return self._compile(decode_tick, "decode")

    # -- compile + AOT insight -----------------------------------------

    def _compile(self, fn, kind: str, bucket: Optional[int] = None):
        """jit + xla_insight AOT capture: the serving program's
        cost/memory/comms plan becomes a first-class artifact (the same
        capture path the executor uses for training programs)."""
        from ..framework import xla_insight

        jit_fn, args = self._program(fn, kind, bucket)
        key = xla_insight.key_hash((
            "serve", kind, bucket, self.max_batch, self.n_blocks,
            self.block_size, self.cfg.n_layer, self.cfg.n_head,
            self.cfg.d_model, self.cfg.vocab_size, self.cfg.max_seq_len,
            self.cfg.ffn_dim, self.cfg.block(),
            tuple(sorted(self.recipe.axes.items()))
            if self.recipe is not None else None,
        ))
        label = f"serve/{kind}" + (f"@{bucket}" if bucket else "")
        insight, executable = xla_insight.capture(
            jit_fn, args, key_hash=key, label=label,
            fetch_names=(("nll", "total_nll") if kind == "score"
                         else ("pages", "next_tokens")
                         + (("state",) if self.conv_layers else ())))
        name = kind if bucket is None else f"{kind}@{bucket}"
        if insight is not None:
            self.insights[name] = insight
        if executable is not None:
            return xla_insight.aot_call(executable, jit_fn)
        return jit_fn

    def _program(self, fn, kind: str, bucket: Optional[int] = None):
        """``fn`` under its name and jit wrapper, and the arguments it
        compiles at: the real parameters and abstract stand-ins for the
        rest (compile == serve shapes and shardings; no second pool is
        allocated to describe the first)."""
        import jax
        import jax.numpy as jnp

        # the module's name in a profile and in the HLO: jit_decode_tick,
        # jit_prefill_<bucket>, jit_score_<bucket>. The same in every
        # process: it is part of the persistent compile cache's key
        fn.__name__ = fn.__qualname__ = self.program_name(kind, bucket)

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        pages = jax.ShapeDtypeStruct(self.pool_shape(), self.cfg.dtype,
                                     sharding=self._pages_sharding())
        # None where the model keeps no state: no argument of the program
        state = None
        if self.state_shape() is not None:
            state = jax.ShapeDtypeStruct(self.state_shape(), self.cfg.dtype)
        prev = i32(*self._no_prev.shape)
        if kind == "decode":
            B = self.max_batch
            args = (self.params, pages, state,
                    i32(B, self.max_blocks_per_req), i32(B), i32(B), prev)
        elif kind == "score":
            args = (self.params, i32(1, bucket), i32())
        else:
            args = (self.params, pages, state, i32(1, bucket), i32(),
                    i32(self.max_blocks_per_req), i32(), prev)
        return self._jit_for(fn, kind), args

    @staticmethod
    def program_name(kind: str, bucket: Optional[int] = None) -> str:
        """What a serving program is called wherever it shows: the trace's
        module line (``jit_`` + this), the HLO, a span's attribute."""
        base = "decode_tick" if kind == "decode" else kind
        return base if bucket is None else f"{base}_{bucket}"

    def _jit_for(self, fn, kind: str):
        """The jit wrapper of a serving program. Prefill and decode
        DONATE the pools (arguments 1 and 2, the KV pool and the state
        pool): each returned pool is the same buffer updated in place, and
        the array passed in is deleted."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        donate = () if kind == "score" else (1, 2)
        if self.mesh is None:
            return jax.jit(fn, donate_argnums=donate)
        repl = NamedSharding(self.mesh, PartitionSpec())
        param_sh = {
            name: self.recipe.param_sharding(self.mesh, name, arr,
                                             self.rules)
            for name, arr in self.params.items()
        }
        if kind == "score":
            # (params, tokens, length) -> (nll, total): no pages
            return jax.jit(fn, in_shardings=(param_sh, repl, repl),
                           out_shardings=(repl, repl))
        pages_sh = self._pages_sharding()
        # no state pool on a mesh (__init__ refuses the model), then
        # (tables, lens, tokens, prev) or (tokens, length, block_ids, slot,
        # prev)
        n_host = 4 if kind == "decode" else 5
        in_sh = (param_sh, pages_sh, None) + (repl,) * n_host
        return jax.jit(fn, in_shardings=in_sh,
                       out_shardings=(pages_sh, repl, None),
                       donate_argnums=donate)

    # -- public API (host-array in, host-scalar-friendly out) ----------

    def prefill_enqueue(self, pages, state, tokens: np.ndarray, length: int,
                        block_ids: Sequence[int], slot: int = 0, prev=None):
        """The first half of a prefill: pad the prompt to the smallest
        bucket that holds it, put the inputs and enqueue the program;
        nothing is waited for. The program leaves the prompt's K/V in
        ``block_ids``, where the model keeps a state pool its conv layers'
        final state in decode slot ``slot`` (``state`` is None otherwise),
        and its first token in ``prev[slot]``: ``prev`` is the newest
        token vector on the device (the tick in flight's, or an earlier
        prefill's behind it; None: nothing is in flight). Returns (pages,
        state, next, t0): the pools' successors and ``prev`` with the
        token in it, all still on the device (:meth:`prefill_read` is the
        sync; ``next`` is the next decode tick's ``prev``), and the
        ``perf_counter_ns`` stamp of ``tick/put_inputs``. Raises
        InvalidArgument when no bucket fits (the engine fails the request,
        not the batch)."""
        import jax.numpy as jnp

        from ..framework import errors as _errors

        L = self.bucket_for(int(length))
        if L is None:
            raise _errors.errors.InvalidArgument(
                f"prompt of {length} tokens exceeds the largest prefill "
                f"bucket {self.prefill_buckets[-1]}")
        if L not in self._prefill_fns:
            self._prefill_fns[L] = self._build_prefill(L)
        padded = np.zeros((1, L), np.int32)
        padded[0, :int(length)] = np.asarray(tokens, np.int32)[:int(length)]
        ids = np.zeros((self.max_blocks_per_req,), np.int32)
        blocks = list(block_ids)[:self.max_blocks_per_req]
        ids[:len(blocks)] = blocks
        with _profiler.span("tick/put_inputs", cat="engine") as put:
            args = (jnp.asarray(padded), jnp.int32(int(length)),
                    jnp.asarray(ids), jnp.int32(int(slot)),
                    jnp.asarray(self._no_prev) if prev is None else prev)
        with _profiler.span("tick/enqueue", cat="engine"):
            pages, nxt, state = self._prefill_fns[L](
                self.params, pages, state, *args)
        return pages, state, nxt, put.t0_ns

    @staticmethod
    def prefill_read(nxt, slot: int = 0) -> int:
        """The second half: wait for a prefill's first token."""
        return int(np.asarray(nxt)[slot])

    def decode_enqueue(self, pages, state, block_tables: np.ndarray,
                       context_lens: np.ndarray, tokens: np.ndarray,
                       prev=None):
        """The first half of one decode tick at max_batch: put the inputs
        and enqueue the program; nothing is waited for. ``tokens`` holds
        each slot's last token, or -1 where that token is ``prev``'s: the
        second output of the tick before, as it left the device, unread.
        Returns (pages, state, next, t0): the pools' successors and the
        tick's tokens, all still on the device (:meth:`decode_read` is the
        sync), and the ``perf_counter_ns`` stamp of ``tick/put_inputs``."""
        import jax.numpy as jnp

        if self._decode_fn is None:
            self._decode_fn = self._build_decode()
        with _profiler.span("tick/put_inputs", cat="engine") as put:
            args = (jnp.asarray(np.asarray(block_tables, np.int32)),
                    jnp.asarray(np.asarray(context_lens, np.int32)),
                    jnp.asarray(np.asarray(tokens, np.int32)),
                    jnp.asarray(self._no_prev) if prev is None else prev)
        with _profiler.span("tick/enqueue", cat="engine"):
            pages, nxt, state = self._decode_fn(self.params, pages, state,
                                                *args)
        return pages, state, nxt, put.t0_ns

    def decode_read(self, nxt):
        """The second half: wait for a tick's tokens. Returns (next[B] np,
        routing): for a model with experts the tick's routing counts
        (assignments of live slots, distinct experts hit, the largest
        expert's load, each summed over the expert layers, and over the
        experts HELD where the model holds a share, with the assignments
        over all the router's experts as a fourth) come back behind the
        tokens, in the one read; None for any other."""
        nxt = np.asarray(nxt)
        if not self.routes:
            return nxt, None
        return tuple(np.split(nxt, [self.max_batch]))

    def warm(self, full: bool = False) -> None:
        """Compile the decode program (and the smallest prefill bucket)
        ahead of traffic so first-request latency is serving, not XLA.
        ``full`` warms EVERY prefill bucket — the serving-replica boot
        path, where a mid-traffic bucket compile would masquerade as a
        multi-second p99 tail (and a warm RESTART should pay the XLA
        persistent-cache hit, not a fresh compile)."""
        buckets = (self.prefill_buckets if full
                   else self.prefill_buckets[:1])
        with _profiler.span("serve/warm", cat="build") as sp:
            todo = [L for L in buckets if L not in self._prefill_fns]
            sp.set(programs=len(todo) + (self._decode_fn is None))
            if self._decode_fn is None:
                self._decode_fn = self._build_decode()
            for L in todo:
                self._prefill_fns[L] = self._build_prefill(L)
        _M_BOOT.labels(phase="warm").inc(sp.seconds)

    # -- reference path (tests) ----------------------------------------

    def full_logits(self, tokens: np.ndarray, with_routing: bool = False):
        """Non-paged reference forward over [1, T] — the ground truth
        the engine's batched output is checked against. ``with_routing``
        adds every position's top-k expert ids in every expert layer,
        ``[T, n_expert_layers, k]`` (a model with experts)."""
        import jax.numpy as jnp

        t = np.asarray(tokens, np.int32).reshape(1, -1)
        T = t.shape[1]
        p = self.params
        pos = jnp.arange(T)
        x = self._embed(p, jnp.asarray(t), pos)
        causal = pos[:, None] >= pos[None, :]
        rot = self._rot(pos[None])
        routing = []
        for i in range(self.cfg.n_layer):
            (op, mlp), own = self._place(i)
            lp = _layer_params(p, i)
            x = self._prompt_op(op, T, lp, own, x, causal, None, None, rot)[0]
            # the feed-forward is row by row: a few hundred positions at a
            # time, so that a long sequence never holds every expert's
            # activations for all of it at once
            parts = [self._ffn(lp, x[:, a:a + _FFN_ROWS], mlp)
                     for a in range(0, T, _FFN_ROWS)]
            x = jnp.concatenate([y for y, _ in parts], axis=1)
            if parts[0][1] is not None:
                routing.append(jnp.concatenate([idx for _, idx in parts]))
        logits = np.asarray(self._logits(p, self._ln_p(p, x, "gpt.lnf")))
        if with_routing:
            return logits, np.stack([np.asarray(r) for r in routing], axis=1)
        return logits

    # -- roofline -------------------------------------------------------

    def decode_roofline(self, mean_active: float,
                        calibration: Optional[Dict[str, float]] = None
                        ) -> Optional[Dict[str, Any]]:
        """The decode program's tokens/s ceiling from its AOT cost
        analysis: per-tick lower bounds for the compute, memory and
        dispatch legs (explicit bound factors), the binding one named,
        and the implied rate at the observed occupancy."""
        ins = self.insights.get("decode")
        if ins is None or not ins.flops:
            return None
        calib = calibration or calibrate()
        legs = {
            "compute_s": float(ins.flops) / max(calib["flops_per_sec"], 1.0),
            "memory_s": (float(ins.bytes_accessed or 0)
                         / max(calib["bytes_per_sec"], 1.0)),
            "dispatch_s": float(calib["dispatch_s"]),
        }
        bound_by = max(legs, key=legs.get)
        floor = max(legs.values())
        active = max(float(mean_active), 1e-6)
        return {
            "legs": {k: round(v, 9) for k, v in legs.items()},
            "bound_by": bound_by,
            "tick_seconds_floor": round(floor, 9),
            "mean_active": round(active, 4),
            "predicted_tokens_per_sec": active / floor,
            "flops": float(ins.flops),
            "bytes_accessed": float(ins.bytes_accessed or 0),
            "calibration": {k: round(float(v), 3) if k.endswith("per_sec")
                            else float(v) for k, v in calib.items()},
            "program": ins.key_hash,
        }
