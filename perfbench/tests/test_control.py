"""The control (the plain reference computed in bfloat16, the precision
below the configuration's float32) must come out not correct, while
the program's own readings stay within the limits.  At the rehearsal
size; the chip readings at each cell's own size are in PERF.md."""

import pytest

from perfbench import harness
from perfbench.tests.helpers import rehearse


@pytest.mark.parametrize("workload", ["palfa.search", "gbncc.dedisp"])
def test_control_fails_and_program_passes(workload):
    limits = harness.load_cell(workload)["traffic"]["check"]["limits"]
    rc, lines = rehearse(workload, 3000000031, "--readings", "2")
    assert rc == 0 and len(lines) == 2
    for r in lines:
        assert r["failed"] == 0
        assert all(r["program"][k] <= limits[k] for k in limits)
        assert any(r["control"][k] > limits[k] for k in limits)
