"""Compile-only report of the serving programs for a described TPU.

XLA:TPU ships in libtpu and compiles for a chip that is described, not
attached, so the questions that decide a serving change's memory traffic
are answered here in seconds and at no chip time: which layout the KV
pool has at the program's edge, whether the output aliases it, which
whole-array ``copy`` (and unfused ``reshape`` / ``transpose``: a relayout)
instructions the compiler put into the program, which Mosaic kernels it
holds, how the decode tick attends (the ``paged_attention`` kernel, its
latent mode ``paged_latent_attention`` over a pool whose row is one latent a
position, or the gathered window and why: a model whose K|V row is not whole
128-lane tiles shows here, before a chip run; ``row`` says what a position's
row holds and how many lanes of it are used, ``step`` how the kernel walks a
slot's pages at that row: the positions and buffers its VMEM scratch is sized
by), how it looks up its token rows (``embed``:
a slice a slot or one gather, the embedding table's layouts in the program
and the copies as large as the table) and ``memory_analysis()``. Sizes and
instruction names only: nothing runs, so no time comes out of this tool.

The programs are the ones ``DecodeModel`` serves with: its own functions
under its own jit wrapper (donation, shardings) at its own pool
description, with every argument re-placed on the described device.

Usage (JAX_PLATFORMS=cpu; 2 layers of GPT-2 XL's widths in ~15 s, the
full 48 in a few minutes):
  python tools/serve_compile_report.py                  # the serving cells' widths
  python tools/serve_compile_report.py --n-layer 48 --vocab 50304
  python tools/serve_compile_report.py --n-head 12 --d-model 768 \\
      --max-batch 8 --n-blocks 256 --buckets 128,512    # chip_smoke's GPT-2 small
  python tools/serve_compile_report.py --hlo-dir /root/scratch/hlo   # keep the HLO text
  python tools/serve_compile_report.py --cell olmoe-serve-batch      # a benchmark cell whose
      # configuration has a module under benchmark/arch/: its block, widths and engine
      # settings, all its layers (--n-layer 2 for a quick look); no weight is allocated
"""
from __future__ import annotations

import argparse
import collections
import json
import math
import os
import re
import sys
from typing import Any, Dict, List, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# `%name = dtype[dims]{layout} opcode(` of one HLO instruction
_INSTR = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>[\w.\-]+) = (?P<dtype>\w+)\[(?P<dims>[\d,]*)\]"
    r"(?:\{(?P<layout>[^}]*)\})? (?P<op>[\w\-]+)\((?P<rest>.*)$")


def described_device(topology: str = "v5e:2x2"):
    """Device 0 of a TPU topology that is described, not attached."""
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name=topology).devices[0]


def abstract_model(cfg, **engine):
    """A DecodeModel of ``cfg`` whose parameters are shapes only
    (``serving.model.param_table``): nothing is drawn or allocated, so a
    model that fills a chip is described on any host."""
    import jax

    from paddle_tpu.serving import DecodeModel
    from paddle_tpu.serving.model import param_table

    dm = DecodeModel(cfg, params={}, **engine)
    dm.params = {name: jax.ShapeDtypeStruct(shape, cfg.dtype)
                 for name, (shape, _std) in param_table(cfg).items()}
    return dm


def cell_model(cell: str, n_layer=None):
    """The DecodeModel (abstract) a benchmark cell serves with."""
    from benchmark import arch, manifest
    from paddle_tpu import serving

    c = manifest.cell(manifest.load(), cell)
    conf, e = dict(c["config"]), c["traffic"]["engine"]
    if n_layer:
        conf["n_layer"] = n_layer
        if "layer_types" in conf:  # a model of several kinds of layer: its first ones
            conf["layer_types"] = conf["layer_types"][:n_layer]
    cfg = serving.GPTConfig(**arch.of(conf).gpt_config(conf, e))
    return abstract_model(cfg, **arch.engine_args(e))


def serving_programs(dm) -> Dict[str, Tuple[Any, tuple]]:
    """``{program name: (jit wrapper, example arguments)}`` of a
    DecodeModel's decode tick and prefill buckets, as the model itself
    would compile them (``DecodeModel._program``); nothing is compiled."""
    built: Dict[str, Tuple[Any, tuple]] = {}
    dm._compile = lambda fn, kind, bucket=None: built.setdefault(
        dm.program_name(kind, bucket), dm._program(fn, kind, bucket))
    dm._build_decode()
    for b in dm.prefill_buckets:
        dm._build_prefill(b)
    del dm._compile  # the class's own again
    return built


def compile_on(jit_fn, args, device):
    """Compile ``jit_fn`` at ``args`` with every leaf placed on ``device``."""
    return lower_on(jit_fn, args, device).compile()


def lower_on(jit_fn, args, device):
    """Lower ``jit_fn`` at ``args`` with every leaf placed on ``device``.
    A pallas kernel in it is lowered through Mosaic, as on the chip: the
    package decides that by ``on_tpu()``, which sees this host's CPU."""
    import importlib

    import jax
    from jax.sharding import SingleDeviceSharding

    sh = SingleDeviceSharding(device)
    placed = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), args)
    # by name: the package's attribute `flash_attention` is a function
    kernel = importlib.import_module("paddle_tpu.ops.pallas.paged_attention")
    on_tpu, kernel.on_tpu = kernel.on_tpu, lambda: True
    try:
        return jit_fn.lower(*placed)
    finally:
        kernel.on_tpu = on_tpu


def entry_instructions(hlo_text: str) -> List[Dict[str, Any]]:
    """The instructions of the ENTRY computation, in order."""
    out, inside = [], False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            inside = True
            continue
        if inside and line.startswith("}"):
            break
        m = _INSTR.match(line) if inside else None
        if m:
            d = m.groupdict()
            d["dims"] = tuple(int(x) for x in d["dims"].split(",") if x)
            d["elements"] = math.prod(d["dims"])
            out.append(d)
    return out


def attention_facts(dm, mosaic_kernels: Dict[str, int]) -> Dict[str, Any]:
    """How ``dm``'s decode tick attends (``DecodeModel.attention_path``)
    beside what a compiled program holds of it: the kernel's calls (one a
    layer in the decode tick, none in a prefill) and its VMEM scratch."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    path, why = dm.attention_path()
    cfg, lanes = dm.cfg, dm.pool_shape()[2]
    # the kernel's step at this row, which the scratch is sized by
    step = dm.attention_step()
    step = step._asdict() if step else None
    if dm.latent:  # one row a position for every head
        calls = mosaic_kernels.get("paged_latent_attention", 0)
        return {"decode_path": path, "attention_layers": len(dm.attn_layers),
                "kernel": "paged_latent_attention",
                "row": {"lanes": lanes, "holds": "latent | rotated key lanes | zeros",
                        "latent": cfg.kv_lora_rank, "rotated": cfg.qk_rope_dim,
                        "zeros": lanes - cfg.latent_row, "heads_sharing_it": cfg.n_head},
                "why": why or "one device, a latent row and its V prefix of whole 128-lane tiles, "
                              "pages of whole tiles",
                "paged_attention_calls": calls, "step": step,
                "vmem_scratch_bytes": pa.vmem_scratch_bytes(
                    cfg.n_head, 0, dm.block_size, cfg.dtype,
                    latent=(lanes, cfg.kv_lora_rank)) if calls else 0}
    calls = mosaic_kernels.get("paged_attention", 0)
    return {"decode_path": path, "attention_layers": len(dm.attn_layers),
            "kernel": "paged_attention",
            "row": {"lanes": lanes, "holds": "per K|V head, its K then its V",
                    "kv_heads": cfg.kv_heads, "head_dim": cfg.head_dim},
            "query_heads_a_kv_head": cfg.n_head // cfg.kv_heads,
            "why": why or "one device, heads of whole 128-lane tiles, pages of whole tiles",
            "paged_attention_calls": calls, "step": step,
            "vmem_scratch_bytes": pa.vmem_scratch_bytes(
                cfg.n_head, cfg.head_dim, dm.block_size, cfg.dtype,
                cfg.kv_heads) if calls else 0}


def embed_facts(dm, hlo_text: str, top_level_copies: List[Dict[str, Any]]) -> Dict[str, Any]:
    """How ``dm``'s decode tick looks up its token rows
    (``DecodeModel.embed_path``) beside what a compiled program holds of
    the table: every layout it is named in, fused computations included
    (one, the entry's, when nothing relays it), and how many of
    ``describe``'s ``top_level_copies`` move ``vocab_size x d_model``
    elements or more."""
    path, why = dm.embed_path()
    v, d = dm.cfg.vocab_size, dm.cfg.d_model
    layouts = {re.sub(r"S\(\d\)$", "", lay) for lay in
               re.findall(rf"\w+\[{v},{d}\]\{{([^}}]*)\}}", hlo_text)}
    return {"decode_path": path, "why": why, "table": [v, d], "table_layouts": sorted(layouts),
            "table_sized_copies": sum(c["count"] for c in top_level_copies if c["elements"] >= v * d)}


def describe(compiled, pool_shape: Tuple[int, ...]) -> Dict[str, Any]:
    """What the compiled program does with a pool of ``pool_shape``."""
    text = compiled.as_text()
    instrs = entry_instructions(text)
    pool = tuple(int(d) for d in pool_shape)
    param = next((i for i in instrs if i["op"] == "parameter" and i["dims"] == pool), None)
    param_no = int(re.match(r"(\d+)", param["rest"]).group(1)) if param else None
    # the module line's `input_output_alias={ {0}: (772, {}, may-alias) }`
    aliased = sorted(int(n) for n in re.findall(r"\((\d+), \{\}, \w+-alias\)",
                                                text.split("\n", 1)[0]))
    # a reshape or transpose that is free is a `bitcast` by now: one still
    # standing at the top level moves its whole operand, like a copy
    copies = collections.Counter(
        (i["op"], f"{i['dtype']}[{','.join(map(str, i['dims']))}]", i["elements"])
        for i in instrs if i["op"] in ("copy", "reshape", "transpose"))
    mem = compiled.memory_analysis()
    kernels = collections.Counter(
        re.sub(r"\.\d+$", "", n) for n in re.findall(
            r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text))
    return {
        "module": re.match(r"HloModule (\S+?),", text).group(1),
        "pool": {"shape": list(pool), "parameter": param_no,
                 "layout": param["layout"] if param else None,
                 "aliased_to_output": param_no in aliased,
                 # every top-level result of the pool's shape (the scatters
                 # and what carries them) and the layout it is in
                 "layouts_in_program": sorted({i["layout"] or "" for i in instrs
                                               if i["dims"] == pool and i["op"] != "parameter"})},
        "aliased_parameters": aliased,
        "mosaic_kernels": dict(sorted(kernels.items())),
        "top_level_copies": [{"op": op, "shape": shape, "count": n, "elements": elements}
                             for (op, shape, elements), n in sorted(copies.items())],
        "memory": {k: int(getattr(mem, k)) for k in
                   ("argument_size_in_bytes", "output_size_in_bytes", "alias_size_in_bytes",
                    "temp_size_in_bytes", "generated_code_size_in_bytes")},
        "instructions": len(instrs),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default=None,
                    help="a cell of BENCHMARK.json (its configuration needs a module "
                         "under benchmark/arch/); the width arguments are then unused")
    ap.add_argument("--n-layer", type=int, default=None, help="default 2; a cell's own depth")
    ap.add_argument("--n-head", type=int, default=25)
    ap.add_argument("--d-model", type=int, default=1600)
    ap.add_argument("--vocab", type=int, default=1024)
    ap.add_argument("--max-seq-len", type=int, default=1024)
    ap.add_argument("--max-batch", type=int, default=12)
    ap.add_argument("--n-blocks", type=int, default=432)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--buckets", default="256")
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--hlo-dir", default=None, help="write each program's HLO text here")
    a = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from paddle_tpu import serving

    if a.cell:
        dm = cell_model(a.cell, a.n_layer)
    else:
        cfg = serving.GPTConfig(vocab_size=a.vocab, n_layer=a.n_layer or 2, n_head=a.n_head,
                                d_model=a.d_model, max_seq_len=a.max_seq_len, dtype="bfloat16")
        dm = abstract_model(cfg, max_batch=a.max_batch, n_blocks=a.n_blocks,
                            block_size=a.block_size,
                            prefill_buckets=[int(b) for b in a.buckets.split(",")])
    device = described_device(a.topology)
    for name, (jit_fn, args) in serving_programs(dm).items():
        compiled = compile_on(jit_fn, args, device)
        text = compiled.as_text()
        if a.hlo_dir:
            os.makedirs(a.hlo_dir, exist_ok=True)
            with open(os.path.join(a.hlo_dir, f"{name}.hlo"), "w") as f:
                f.write(text)
        facts = describe(compiled, dm.pool_shape())
        facts["attention"] = attention_facts(dm, facts["mosaic_kernels"])
        facts["embed"] = embed_facts(dm, text, facts["top_level_copies"])
        if dm.state_shape() is not None:  # the conv layers' second donated pool
            facts["state_pool"] = describe(compiled, dm.state_shape())["pool"]
        print(json.dumps({name: facts}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
