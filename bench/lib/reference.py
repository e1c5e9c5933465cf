"""The plain reference, and the comparison that decides ``correct``.

The reference maps a delivered stream with numpy alone, from the
deployment's name-level ground truth (:class:`bench.lib.deployment.Tables`'
``cdm_pos``).  It shares no code and no table with the program:

* at-least-once input, exactly-once output: the first delivery of each
  key maps, later ones (redeliveries inside the dedup window) map nowhere;
* an event of schema ``o`` maps into its entity ``r`` (version 1, the one
  live CDM version): one row whose value at CDM position ``q`` is the
  event's value of the attribute that feeds ``q``, where that value is
  present;
* an event none of whose present attributes is mapped yields no row.

Rows are compared by (key, entity): the mask exactly, the float32 value
bits wherever the mask is set.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import numpy as np

from .traffic import Batch


@dataclasses.dataclass
class Rows:
    """Canonical rows, one per (key, entity), sorted by that pair."""

    key: np.ndarray  # int64 (n,)
    entity: np.ndarray  # int64 (n,)
    mask: np.ndarray  # bool (n, width)
    bits: np.ndarray  # int32 (n, width): float32 bits, 0 where unmasked

    @property
    def n(self) -> int:
        return int(self.key.size)


def _sorted(key, entity, mask, bits) -> Rows:
    order = np.lexsort((entity, key))
    return Rows(key[order], entity[order], mask[order], bits[order])


def expected_rows(b: Batch, tables, lo: int, hi: int, width: int,
                  key_shift: int = 0, value_dtype=np.float32) -> Rows:
    """Reference rows of delivered events [lo, hi) of ``b`` (one pass).

    ``value_dtype`` other than float32 computes the values in that type
    first: the control that a lower precision must fail."""
    start, _, _, pos_flat = tables.flat()
    entity_of = np.asarray(tables.entity, np.int64)
    keys = b.key[:hi]
    first = np.zeros(hi, bool)
    first[np.unique(keys, return_index=True)[1]] = True
    ev = np.arange(lo, hi)[first[lo:hi]]
    a, z = b.offsets[ev], b.offsets[ev + 1]
    per = z - a
    row = np.repeat(np.arange(ev.size), per)
    item = np.arange(int(per.sum())) + np.repeat(a - np.cumsum(np.r_[0, per[:-1]]), per)
    cdm = pos_flat[start[b.col[ev]][row] + b.pos[item]]
    keep = cdm >= 0
    row, item, cdm = row[keep], item[keep], cdm[keep]
    has = np.zeros(ev.size, bool)
    has[row] = True
    out_of = np.cumsum(has) - 1
    n = int(has.sum())
    mask = np.zeros((n, width), bool)
    vals = np.zeros((n, width), np.float32)
    v = b.val[item]
    if value_dtype is not np.float32:
        v = v.astype(value_dtype).astype(np.float32)
    mask[out_of[row], cdm] = True
    vals[out_of[row], cdm] = v
    evs = ev[has]
    return _sorted(
        keys[evs] + key_shift,
        entity_of[b.col[evs]],
        mask,
        np.where(mask, vals.view(np.int32), 0),
    )


def empty_rows(width: int) -> Rows:
    return Rows(np.zeros(0, np.int64), np.zeros(0, np.int64),
                np.zeros((0, width), bool), np.zeros((0, width), np.int32))


def concat_rows(parts) -> Rows:
    parts = list(parts)
    return _sorted(
        np.concatenate([p.key for p in parts]),
        np.concatenate([p.entity for p in parts]),
        np.concatenate([p.mask for p in parts]),
        np.concatenate([p.bits for p in parts]),
    )


def table_rows(tables: Dict[Tuple[int, int], Dict[str, np.ndarray]], width: int) -> Rows:
    """The program's rows, from ``TableSink.to_arrays()``."""
    keys, ents, masks, bits = [], [], [], []
    for (r, _w), t in tables.items():
        n, w = t["mask"].shape
        m = np.zeros((n, width), bool)
        m[:, :w] = t["mask"].astype(bool)
        v = np.zeros((n, width), np.int32)
        v[:, :w] = np.asarray(t["values"], np.float32).view(np.int32)
        keys.append(t["keys"].astype(np.int64))
        ents.append(np.full(n, r, np.int64))
        masks.append(m)
        bits.append(np.where(m, v, 0))
    if not keys:
        return empty_rows(width)
    return _sorted(np.concatenate(keys), np.concatenate(ents),
                   np.concatenate(masks), np.concatenate(bits))


def compare(want: Rows, got: Rows) -> Dict[str, int]:
    """Rows the program lacks, rows it has beyond the reference (repeats
    included), and rows present on both sides that differ."""
    n_ent = int(max(want.entity.max(initial=0), got.entity.max(initial=0))) + 1
    wid = want.key * n_ent + want.entity
    gid = got.key * n_ent + got.entity
    uniq, first, counts = np.unique(gid, return_index=True, return_counts=True)
    extra_repeats = int((counts - 1).sum())
    hit = np.isin(wid, uniq)
    missing = int((~hit).sum())
    extra = int((~np.isin(uniq, wid)).sum()) + extra_repeats
    at = first[np.searchsorted(uniq, wid[hit])]
    w_idx = np.nonzero(hit)[0]
    differ = (want.mask[w_idx] != got.mask[at]).any(axis=1) | (
        want.bits[w_idx] != got.bits[at]
    ).any(axis=1)
    return {"rows_missing": missing, "rows_extra": extra, "rows_wrong": int(differ.sum())}
