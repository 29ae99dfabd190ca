"""Cell fleet_sf.fit at CPU-test size: a sound run is correct; the control in
the program's place, and each fault the cell can have planted under the
timed path, make ``correct`` false."""
import pytest

from bench.tests import cells, faults
from bench.tests.tiny import run_tiny, tiny_root

CELL = "fleet_sf.fit"


def test_sound_run_is_correct(tmp_path):
    line = run_tiny(tiny_root(tmp_path), CELL, seconds=0.3)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0


def test_control_fails(tmp_path):
    cells.assert_control_fails(tmp_path, CELL)


@pytest.mark.parametrize("fault", [
    faults.answer_altered,
    faults.half_batch,
], ids=lambda f: f.__name__)
def test_fault_fails(tmp_path, fault):
    cells.assert_fault_fails(tmp_path, CELL, fault)
