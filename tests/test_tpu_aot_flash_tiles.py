"""The flash dispatcher's table entries at GPT-2 small's widths, compiled for
the described chip (tests/tpu_aot.py holds the fixtures and the body): the
cell's own call, BHTD, the two-kernel entry and the calls that fall back to
it. The widest fused calls are in test_tpu_aot_flash_vmem.py."""
import pytest

from tpu_aot import the_dispatchers_tiles_compile_at_gpt2s_widths, tpu_arg, tpu_device, tpu_topology  # noqa: F401


@pytest.mark.parametrize("layout", ["BTHD", "BHTD", "BTHD_two_kernels", "BTHD_1600_wide", "BTHD_heads_of_128"])
def test_the_dispatchers_tiles_compile_at_gpt2s_widths(tpu_arg, layout):  # noqa: F811
    the_dispatchers_tiles_compile_at_gpt2s_widths(tpu_arg, layout)
