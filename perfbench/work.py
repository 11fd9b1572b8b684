"""Work counts and chip peaks: what the algorithm must move, from file sizes.

Counts are of the algorithm's messages, never of padded rows, so they
read the same work whatever implements it:

- a CAS message is the 8-byte size prefix plus the whole file up to
  100 KiB, else the 57,344-byte sample (Spacedrive core/src/object/cas.rs);
  empty files get no CAS ID and hash nothing;
- a checksum message is the whole file
  (core/src/object/validation/hash.rs).

Each message byte has to be read from HBM at least once, so bytes over
the chip's published HBM bandwidth is the least time the work can take.
"""

from __future__ import annotations

import json
import os
from typing import Iterable

SIZE_PREFIX = 8
SMALL_MAX = 100 * 1024
SAMPLED_PAYLOAD = 2 * 8 * 1024 + 4 * 10 * 1024  # 57,344

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def cas_message_bytes(sizes: Iterable[int]) -> int:
    return sum(SIZE_PREFIX + (s if s <= SMALL_MAX else SAMPLED_PAYLOAD)
               for s in sizes if s > 0)


def checksum_message_bytes(sizes: Iterable[int]) -> int:
    return sum(sizes)


def peak(device_kind: str, key: str) -> float:
    """A published peak of one chip; an unknown device is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return float(table[device_kind][key])


def roofline_percent(message_bytes: int, seconds: float, device_kind: str):
    """Share of the HBM roofline, in %, or None when nothing ran."""
    if seconds <= 0 or message_bytes <= 0:
        return None
    least = message_bytes / peak(device_kind, "hbm_bytes_per_s")
    return 100.0 * least / seconds
