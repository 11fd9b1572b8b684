"""Each cell's control fails its check, and the sound path passes it:
the reference in the program's place with one stated guarantee broken
(small files' CAS IDs over the zero-padded staged row; "full-file"
checksums over the CAS sample only). Runs the harness without the look for a chip, on the
CPU, at a tiny size (conftest.py)."""

import pytest


@pytest.mark.parametrize("cell,control,correct", [
    ("scan.desktop", None, True),
    ("scan.desktop", "cas_padded_row", False),
    ("validate.verify", None, True),
    ("validate.verify", "checksum_sampled", False),
])
def test_control_fails_the_check(run_tiny, cell, control, correct):
    line = run_tiny(cell, control)
    assert line["correct"] is correct, line["checks"]
    assert list(line)[-1] == "checks"
