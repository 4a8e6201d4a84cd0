"""The fused flash backward at 16, 20 and 32 heads of 64, compiled for the
described chip (tests/tpu_aot.py holds the fixtures and the body): the only
guard of the kernel's own VMEM count (24, 29 and 44 MiB). A file of their
own: the three compiles take as long as all the table's other entries."""
import pytest

from tpu_aot import the_dispatchers_tiles_compile_at_gpt2s_widths, tpu_arg, tpu_device, tpu_topology  # noqa: F401


@pytest.mark.parametrize("layout", ["BTHD_1024_wide", "BTHD_1280_wide", "BTHD_2048_wide"])
def test_the_dispatchers_tiles_compile_at_gpt2s_widths(tpu_arg, layout):  # noqa: F811
    the_dispatchers_tiles_compile_at_gpt2s_widths(tpu_arg, layout)
