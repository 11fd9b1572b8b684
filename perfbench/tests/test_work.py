"""Work counts from file sizes, and the peaks table."""

import pytest

from perfbench import work


def test_cas_bytes_count_the_message_not_the_row():
    # empty: nothing; small: prefix + file; large: prefix + sample
    assert work.cas_message_bytes([0, 1000, 102400, 102401, 9 << 20]) == (
        8 + 1000 + 8 + 102400 + 2 * (8 + 57344))


def test_checksum_bytes_are_the_files():
    assert work.checksum_message_bytes([0, 5, 1 << 20]) == 5 + (1 << 20)


def test_roofline_share():
    # 819 MB in 10 ms on a chip that moves 819 GB/s: 10% of the roofline
    assert work.roofline_percent(819_000_000, 0.01, "TPU v5 lite") == \
        pytest.approx(10.0)
    assert work.roofline_percent(0, 1.0, "TPU v5 lite") is None
    assert work.roofline_percent(10, 0.0, "TPU v5 lite") is None


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peak("TPU v99", "hbm_bytes_per_s")
