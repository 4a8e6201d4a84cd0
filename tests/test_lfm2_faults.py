"""The eight faults of benchmark/tools/lfm2_fault_readings.py, each served on
the CPU at the cell's published widths and read by the cell's own check
(a file of its own: nine servings of a 2,048-wide model take minutes, and
the test runner hands a worker whole files)."""
import numpy as np
import pytest

from benchmark import arch as arch_modules
from benchmark.tools import lfm2_fault_readings as fault_readings
from paddle_tpu import serving

TOL = 1e-4  # float32 against float32: rounding and summation order only


@pytest.fixture(scope="module")
def cut_cell():
    """The cell's configuration at its published widths (hidden 2048, 32
    query heads and 8 K|V heads of 64, dense 11,776, experts of 1,536)
    with the cell's OWN seed-made weights
    (``benchmark/arch/lfm2_moe.py::make_params``: nothing reweighted here),
    cut to what a CPU test carries: a layer of each kind (conv + dense,
    attention + experts, conv + experts twice), 16 experts of which a token
    takes the published 4, 2,048 vocabulary rows, float32."""
    from benchmark import manifest

    c = dict(manifest.cell(manifest.load(), "lfm2-serve-reason")["config"], n_layer=4,
             layer_types=["conv", "full_attention", "conv", "conv"], num_dense_layers=1, num_experts=16,
             vocab_size=2048)
    mod = arch_modules.of(c)
    cfg = serving.GPTConfig(**mod.gpt_config(c, {"dtype": "float32", "window": 128}))
    params = mod.make_params(c, 2**31 + 11, "float32")
    rng = np.random.RandomState(4)
    requests = [rng.randint(0, 2048, n).tolist() for n in (24, 40)]
    before = [rng.randint(0, 2048, n).tolist() for n in (5, 33)]
    return mod, c, cfg, params, requests, before


@pytest.mark.parametrize("fault", [None, *fault_readings.FAULTS])
def test_a_fault_the_tolerance_must_catch_fails_it(fault, cut_cell):
    """Each of eight plausible mistakes, made on purpose in the program
    (benchmark/tools/lfm2_fault_readings.py), moves a served token's
    reference logit gap past the tolerance the benchmark's runner applies
    to bfloat16 (LOGIT_TOL), read by the runner's own check on the cell's
    own initialisation; the sound program stays at float32 rounding. Two
    slots, each with a tenant before the checked one. (The limit is set for
    the chip's 64 experts and thousands of tokens: the weakest two faults
    here, the dropped bias and the position, read 0.50 and 0.54 against
    0.45.)"""
    mod, c, cfg, params, requests, before = cut_cell
    engine = dict(max_batch=2, n_blocks=64, block_size=16, prefill_buckets=[64])  # window 128
    r = fault_readings.reading(fault, mod, c, cfg, params, engine, requests, 40, before, window=128)
    assert r["checked_tokens"] == 80
    if fault is None:
        assert r["max_logit_gap"] <= 10 * TOL and not r["caught"]
    else:
        assert r["max_logit_gap"] > mod.LOGIT_TOL and r["caught"], r
