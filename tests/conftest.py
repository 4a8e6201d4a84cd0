"""Test config: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's strategy of exercising multi-device paths without a
real cluster (/root/reference/python/paddle/fluid/tests/unittests/
test_dist_base.py): where the reference spawns subprocesses with real NCCL,
we give XLA 8 host devices so mesh/collective code paths compile and run
in-process.  XLA_FLAGS must be set BEFORE jax initializes; the
jax.config platform pin below keeps the suite on CPU even when the
caller forgot JAX_PLATFORMS=cpu.
"""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# sharding verification armed for the whole suite: every mesh program
# carrying sharding rules has its intended-vs-actual PartitionSpecs
# checked at compile time (paddle_tpu/framework/shard_insight.py), and
# the mesh-program suites assert the mismatch counter stayed flat via
# the sharding_drift_guard fixture below — placement drift fails
# tier-1, not just a gauge
os.environ.setdefault("PADDLE_TPU_SHARD_VERIFY", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


# build the native libraries on fresh checkouts (a few seconds, once):
# the core, feed and ps tables, and the C inference ABI that
# test_c_api_runs_saved_model links
import subprocess  # noqa: E402

_repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if not all(os.path.exists(os.path.join(_repo, "paddle_tpu", "lib", f"libpaddle_tpu_{_n}.so"))
           for _n in ("core", "capi")):
    subprocess.run(["make", "-C", os.path.join(_repo, "csrc"), "all", "capi"],
                   check=False, capture_output=True)


def pytest_configure(config):
    # tier-1 runs -m 'not slow'; anything marked slow is the long-haul
    # tail (subprocess re-exec compiles, big-mesh plans)
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 run (-m 'not slow')")


import pytest  # noqa: E402


@pytest.fixture
def sharding_drift_guard():
    """Fail the test if executor-side sharding verification counted any
    intended-vs-actual placement drift while it ran. Mesh-program
    suites (test_recipes, test_recipe_checkpoint, ...) opt in; suites
    that construct mismatches on purpose (test_shard_insight) do not."""
    from paddle_tpu import monitor

    def _mismatches():
        fam = monitor.snapshot().get("metrics", {}).get(
            "sharding_mismatch_total", {})
        return sum(float(s.get("value", 0.0))
                   for s in fam.get("series", []))

    before = _mismatches()
    yield
    after = _mismatches()
    assert after == before, (
        f"sharding drift under PADDLE_TPU_SHARD_VERIFY=1: "
        f"sharding_mismatch_total grew {before} -> {after}")


@pytest.fixture(autouse=True, scope="module")
def _dygraph_between_modules():
    """Each test file starts in dygraph mode whatever ran before it on
    its xdist worker: test_recipes.py and test_recipe_checkpoint.py end
    in static mode, and test_distributed.py's dygraph tests then fail
    with "'Variable' object has no attribute 'backward'"."""
    yield
    import paddle_tpu

    paddle_tpu.disable_static()


def free_ports(n):
    """Reserve n distinct OS-assigned free ports (bind :0, SO_REUSEADDR).

    Replaces pid-derived/hardcoded test ports, which collide across
    concurrent runs and TIME_WAIT reuse (the reference wraps the same
    flakiness in dist_test.sh port-retry logic; asking the kernel is
    cleaner).
    """
    import socket

    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports
