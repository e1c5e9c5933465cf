"""The kernel byte count against shapes worked out by hand, and the peaks
table."""

import numpy as np
import pytest

from bench.lib.kernel_bytes import chunk_bytes
from bench.lib.peaks import peaks


def test_chunk_bytes_by_hand():
    # 3 events, 20 items, 3 rows into two distinct blocks of widths 50, 30
    got = chunk_bytes(20, 3, 3, np.asarray([50, 30]), np.asarray([50, 50, 30]))
    items = 20 * (4 + 4)
    events = 3 * (4 + 4)
    table = (50 + 30) * 4
    out = (50 + 50 + 30) * (4 + 1)
    assert got == items + events + table + out == 1154


def test_peaks_are_keyed_by_device_kind():
    p = peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        peaks("cpu")
