"""The control, at a size a test run holds: the plain reference computed
in bfloat16 and put in the program's place fails the tiny split LM's
limits, while the program passes them; the planted half-batch fault fails
them too. (On the chip the same readings, at each cell's own size, set
the cells' limits: ``bench/control.py``.)"""
import tempfile

import pytest

from bench import check, control
from benchtools import TINY_LIMITS, make_root


@pytest.fixture(scope="module")
def readings():
    root = make_root(tempfile.mkdtemp(), ["tiny_lm"])
    return {r["reading"]: r for r in control.readings(
        "tiny_lm", 2 ** 31 + 9, program=True, allow_cpu=True, root=root)}


def test_program_passes(readings):
    assert check.verdict(readings["program"], TINY_LIMITS)[0]


@pytest.mark.parametrize("name", ["control_bf16", "fault_half_batch"])
def test_control_and_fault_fail(readings, name):
    ok, rows = check.verdict(readings[name], TINY_LIMITS)
    assert not ok, rows
