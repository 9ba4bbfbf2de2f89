"""The control fails the check: the reference put in the program's place
and computed in bfloat16, the precision below the configuration's
float32, reads above the limits; so do the planted faults. At a tiny
size on the CPU; ``perfbench/control.py`` reads the same at a cell's own
size on the chip."""
import pytest

from perfbench import control, harness
from perfbench.tests.conftest import make_root

VARIANTS = ["bf16", "half_batch", "frozen", "no_exchange"]


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    cell = harness.Cell("tiny", root=make_root(str(tmp_path_factory.mktemp(
        "checkout"))))
    return cell, control.variant_readings(cell, 2147483659, VARIANTS)


@pytest.mark.parametrize("variant", VARIANTS)
def test_variant_fails_a_limit(readings, variant):
    cell, got = readings
    checks = got[variant]
    assert not harness.passes(checks), checks
