"""The nine faults of benchmark/tools/axk1_fault_readings.py and the float8
reference, each served on the CPU through Router -> ServingEngine ->
DecodeModel at a cut size and read by the cell's own check
(``serve_arch.reference_gaps``). The cell's tolerance (``LOGIT_TOL``) is set
from chip readings at the published widths (PERF.md section 6, PR 50); here,
float32 at tiny widths with the seed-made matrices four times as large (so
that logits spread by units and the softmax picks positions, as the cell's
do), every fault has to move a served token's reference gap far beyond
float32 rounding, and the sound program has to stay inside it."""
import numpy as np
import pytest

from benchmark.tools import axk1_fault_readings as tool
from paddle_tpu import serving
from test_axk1_serving import MOD, TOL, V, conf_of


@pytest.fixture(scope="module")
def cut():
    # half of the 16 experts held: a near-tie the bfloat16 scores decide the other way then moves the
    # held part every other time (with 2 held, as the serving tests have it, it moved no argmax in 80 tokens)
    conf = conf_of("kernel", n_layer=4, n_routed_experts=8, first_expert_held=0)
    cfg = serving.GPTConfig(**MOD.gpt_config(conf, {"dtype": "float32", "window": 128}))
    params = {k: (v * 4 if v.ndim >= 2 and "router" not in k else v)
              for k, v in MOD.make_params(conf, 2**31 + 11, "float32").items()}
    rng = np.random.RandomState(4)
    return dict(c=conf, cfg=cfg, params=params, requests=[rng.randint(0, V, n).tolist() for n in (24, 40)],
                before=[rng.randint(0, V, n).tolist() for n in (5, 33)],
                engine=dict(max_batch=2, n_blocks=64, block_size=16, prefill_buckets=[64]))


@pytest.mark.parametrize("fault", (None,) + tool.FAULTS)
def test_a_fault_moves_served_tokens_and_the_sound_program_does_not(fault, cut):
    out = tool.reading(fault, MOD, cut["c"], cut["cfg"], cut["params"], cut["engine"], cut["requests"], 40,
                       cut["before"], window=128, float8=fault is None)
    r = out[0]
    assert r["checked_tokens"] == 80 and r["fault"] == (fault or "none")
    if fault is None:
        assert r["max_logit_gap"] <= 10 * TOL and r["exact_argmax_share"] == 1.0
        assert r["routing_agreement_share"] == 1.0
        # the same tokens under the reference with float8 matmul operands: off by far more than rounding
        assert out[1]["fault"] == "reference_in_float8" and out[1]["max_logit_gap"] > 500 * TOL
    else:
        assert len(out) == 1 and r["max_logit_gap"] > 500 * TOL and r["exact_argmax_share"] < 1.0, r
    if fault == "groups_not_limited":
        assert r["routing_agreement_share"] < 0.5
    if fault in ("experts_offset_one_share", "shared_expert_dropped"):
        assert 0.5 < r["routing_agreement_share"] < 1.0  # the stream moved, the router did not


def test_an_unknown_fault_is_refused_by_name(cut):
    with pytest.raises(ValueError, match="no fault 'typo': one of"):
        with tool.made("typo", cut["cfg"], cut["params"]):
            pass
