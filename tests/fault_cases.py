"""The faults of benchmark/tools/fault_readings.py (OLMoE) and
lfm2_fault_readings.py (LFM2), each served on the CPU at its cell's published
widths and read by the cell's own check: the cut cells and the ONE test body.

A case serves a 2,048-wide model for a minute or more, and the test runner
hands a worker whole files, so the cases live in several files
(test_lfm2_faults_*.py by the mechanism the fault is made in,
test_olmoe_faults.py); each imports its ``cut_cell`` fixture and the body
from here."""
import types

import numpy as np
import pytest

from benchmark import arch as arch_modules
from benchmark.tools import fault_readings, lfm2_fault_readings
from paddle_tpu import serving

TOL = 1e-4  # float32 against float32: rounding and summation order only

# the sound program (None) beside the lightest group
LFM2_GROUPS = {
    "state": ("state_not_carried", "state_shifted_wrong_way", "state_of_last_tenant"),
    "routing": (None, "no_selection_bias", "top_k_not_normalised"),
    "attention": ("kv_head_modulo", "no_qk_norm", "position_off_by_one"),
}
assert sorted(f for g in LFM2_GROUPS.values() for f in g if f) == sorted(lfm2_fault_readings.FAULTS)


def _cut(workload, tool, cuts, before_lens, **serve):
    """The cell's configuration with its OWN seed-made weights (the
    architecture module's ``make_params``: nothing reweighted here), ``cuts``
    applied, float32, with two requests of 24 and 40 tokens and, where the
    tool takes them, the tenants that pass through the slots before."""
    from benchmark import manifest

    c = dict(manifest.cell(manifest.load(), workload)["config"], **cuts)
    arch = arch_modules.of(c)
    cfg = serving.GPTConfig(**arch.gpt_config(c, {"dtype": "float32", "window": 128}))
    params = arch.make_params(c, 2**31 + 11, "float32")
    rng = np.random.RandomState(4)
    requests = [rng.randint(0, 2048, n).tolist() for n in (24, 40)]
    before = [rng.randint(0, 2048, n).tolist() for n in before_lens]
    return types.SimpleNamespace(tool=tool, arch=arch, c=c, cfg=cfg, params=params, requests=requests,
                                 extra={"tenants_before": before} if before else {}, **serve)


@pytest.fixture(scope="module")
def lfm2_cut_cell():
    """lfm2-serve-reason at its published widths (hidden 2048, 32 query
    heads and 8 K|V heads of 64, dense 11,776, experts of 1,536), cut to
    what a CPU test carries: a layer of each kind (conv + dense, attention
    + experts, conv + experts twice), 16 experts of which a token takes the
    published 4, 2,048 vocabulary rows. Two slots, each with a tenant
    before the checked one."""
    return _cut("lfm2-serve-reason", lfm2_fault_readings,
                dict(n_layer=4, layer_types=["conv", "full_attention", "conv", "conv"], num_dense_layers=1,
                     num_experts=16, vocab_size=2048), before_lens=(5, 33),
                engine=dict(max_batch=2, n_blocks=64, block_size=16, prefill_buckets=[64]), max_new=40, window=128)


@pytest.fixture(scope="module")
def olmoe_cut_cell():
    """olmoe-serve-batch at its published hidden width (2048, 16 heads of
    128, experts of 1024), cut to what a CPU test carries: 2 layers, 8
    experts of which a token takes 2, 2,048 vocabulary rows."""
    return _cut("olmoe-serve-batch", fault_readings,
                dict(n_layer=2, num_experts=8, num_experts_per_tok=2, vocab_size=2048), before_lens=(),
                engine=dict(max_batch=4, n_blocks=64, block_size=16, prefill_buckets=[64]), max_new=12, window=64)


def a_fault_the_tolerance_must_catch_fails_it(fault, cut):
    """Each plausible mistake, made on purpose in the program (the cut's
    ``tool``), moves a served token's reference logit gap past the tolerance
    the benchmark's runner applies to bfloat16 (LOGIT_TOL), read by the
    runner's own check on the cell's own initialisation; the sound program
    (None) stays at float32 rounding. (The limit is set for the chip's 64
    experts and thousands of tokens: LFM2's weakest two faults here, the
    dropped bias and the position, read 0.50 and 0.54 against 0.45.)"""
    r = cut.tool.reading(fault, cut.arch, cut.c, cut.cfg, cut.params, cut.engine, cut.requests, cut.max_new,
                         window=cut.window, **cut.extra)
    assert r["checked_tokens"] == len(cut.requests) * cut.max_new
    if fault is None:
        assert r["max_logit_gap"] <= 10 * TOL and not r["caught"]
    else:
        assert r["max_logit_gap"] > cut.arch.LOGIT_TOL and r["caught"], r
