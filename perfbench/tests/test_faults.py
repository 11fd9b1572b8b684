"""The check sees each fault a one-chip cell can have: an answer altered
where it is produced, half of a batch left out, and a job step that
returns without its work. (Cells on one chip have no exchange between
chips to leave out.)"""

import pytest

FAULTS = ("answer_altered", "half_batch", "state_unchanged")


@pytest.mark.parametrize("cell", ["scan.desktop", "validate.verify"])
@pytest.mark.parametrize("fault", FAULTS)
def test_fault_makes_the_run_incorrect(run_tiny, cell, fault):
    line = run_tiny(cell, fault)
    assert line["correct"] is False, line["checks"]
