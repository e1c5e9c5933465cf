"""Bytes the mapping of one chunk needs, from shapes alone.

Whatever implements the kernel has to read each payload item (attribute
id and value, 4 B each), each mapped event's column and item count (4 B
each), the block-table row of every distinct block the chunk touches (one
4 B source index per output attribute), and write each output row (a
float32 value and a one-byte mask flag per output attribute).  Padding,
layout and repeated reads are the implementation's, and are not counted.
"""

from __future__ import annotations

import numpy as np


def chunk_bytes(n_items: int, n_events: int, n_rows: int, block_widths: np.ndarray,
                row_widths: np.ndarray) -> int:
    """``block_widths``: output width of each distinct block touched;
    ``row_widths``: output width of each output row (``n_rows`` of them)."""
    return int(
        8 * n_items
        + 8 * n_events
        + 4 * int(np.sum(block_widths))
        + 5 * int(np.sum(row_widths))
    )
