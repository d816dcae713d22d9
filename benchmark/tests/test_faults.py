"""Each cell's check passes a sound window and fails each planted fault
(CPU, small deployment; ``control.py`` takes the same readings on the
chip at the cells' own sizes)."""
import pytest

from benchmark.harness import Cell, load_json, ROOT
from benchmark.tests.control import readings
from benchmark.tests.faults import BY_TRAFFIC

CELLS = [w["name"] for w in load_json(f"{ROOT}/BENCHMARK.json")["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_window_passes_and_every_fault_fails(cell):
    faults = BY_TRAFFIC[Cell(cell).entry["traffic"]]
    got = readings(cell, 2**31 + 7, 1.5, faults, shrink=True)
    sound = got[0]
    assert sound[0] is None and sound[1], sound[2]
    assert sound[3] > 0
    for fault, correct, checks, _n in got[1:]:
        assert not correct, (fault, checks)
