"""The control of the correctness check -- the plain reference in
bfloat16 in the program's place -- fails the check the cells run."""

import pytest

from bench import control
from bench.tests import tiny


@pytest.mark.parametrize("workload", ["vht-dense1000.train",
                                      "vamr-waveform40.train",
                                      "vht-dense1000.serve"])
def test_control_fails_the_check(workload):
    cell = tiny.cell(workload)
    got = control.readings(cell, 2**31 + 3, 8)
    limits = cell.cfg["limits"]
    assert set(got) == set(limits) or set(got) <= set(limits)
    assert any(got[k] > limits[k] for k in got), got
