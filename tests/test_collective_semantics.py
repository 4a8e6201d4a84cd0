"""Collective op semantics on the 8-device virtual mesh.

Reference semantics under test (/root/reference/paddle/fluid/operators/
collective/c_reduce_op.h, c_allreduce_op.h:124): `c_allreduce_*` leaves the
reduced value on every rank; `c_reduce_*` leaves it on `root_id` only, with
other ranks keeping their input (the NCCL kernels run in-place). The
product reduction must be a true product — correct for zeros and negative
elements.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from paddle_tpu.framework.registry import LoweringContext, get_op_def  # noqa: E402
from paddle_tpu.parallel import make_mesh  # noqa: E402


def _run_collective(op_type, per_rank_vals, attrs):
    """Run one registered collective lowering under shard_map on an 8-way
    'dp' mesh; returns the (n, ...) stacked per-rank outputs."""
    from jax import shard_map

    n = len(per_rank_vals)
    mesh = make_mesh({"dp": n}, jax.devices()[:n])
    opdef = get_op_def(op_type)
    ctx = LoweringContext(mesh=mesh)
    ctx.ring_axes = {0: "dp"}

    def body(v):
        out = opdef.lower(ctx, {"X": [v[0]]}, attrs)
        return out["Out"][None] if not isinstance(out, dict) else jnp.asarray(out["Out"])[None]

    stacked = jnp.stack([jnp.asarray(v) for v in per_rank_vals])
    f = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    with mesh:
        return np.asarray(f(stacked))


VALS = [np.array([float(i) - 3.0, 0.5 * i], np.float32) for i in range(8)]


def test_c_allreduce_prod_true_product():
    # includes zero and negative elements — exp/log tricks would NaN here
    out = _run_collective("c_allreduce_prod", VALS, {"ring_id": 0})
    expect = np.prod(np.stack(VALS), axis=0)
    for r in range(8):
        np.testing.assert_allclose(out[r], expect, rtol=1e-5)


@pytest.mark.parametrize("kind,npop", [
    ("sum", np.sum), ("max", np.max), ("min", np.min), ("prod", np.prod),
])
def test_c_reduce_root_only(kind, npop):
    root = 3
    out = _run_collective(f"c_reduce_{kind}", VALS, {"ring_id": 0, "root_id": root})
    expect = npop(np.stack(VALS), axis=0)
    np.testing.assert_allclose(out[root], expect, rtol=1e-5)
    for r in range(8):
        if r != root:
            np.testing.assert_allclose(out[r], VALS[r], rtol=1e-6)


def test_c_allreduce_sum_all_ranks():
    out = _run_collective("c_allreduce_sum", VALS, {"ring_id": 0})
    expect = np.sum(np.stack(VALS), axis=0)
    for r in range(8):
        np.testing.assert_allclose(out[r], expect, rtol=1e-5)
