"""Traffic: the vectorised event generator and the arrival schedule.

One general generator reads the deployment's stream semantics and the
cell's traffic file (arrivals, rate, skew) and builds every event of a run
before its window opens, as flat columnar arrays drawn from ``--seed``.  No
Python runs per event.  Items are kept as (column, attribute position,
value): the program sees the registry's attribute ids, the reference sees
positions.

A deployment file states only what its source supports.  The shares a
source may state and the generator honours are optional, and each defaults
to what a Debezium-style full row image of the current schema gives:
``p_null`` (share of attribute values null; default 0, every column
present), ``p_duplicate`` (share of events redelivered right after
themselves; default 0, a connector's normal operation delivers each change
once) and ``latest_version_share`` (default 1, every producer at its
schema's newest version).

Work is the same on every seed: the number of events is fixed by the
traffic file, and which schemas are hot is a property of the deployment
(its registry seed), so a seed changes the order of the work and not its
amount.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List

import numpy as np


def load_traffic(root: Path, cell: str) -> dict:
    return json.loads((root / "traffic" / f"{cell}.json").read_text())


def schema_weights(n_schemas: int, zipf_s: float, rank_seed: int) -> np.ndarray:
    """Zipf(s) popularity over the schemas, in a rank order drawn from the
    deployment's seed: ``w[o]`` is schema ``o``'s share of the traffic."""
    ranks = np.random.default_rng([rank_seed, 1]).permutation(n_schemas)
    p = 1.0 / np.arange(1, n_schemas + 1, dtype=np.float64) ** zipf_s
    w = np.empty(n_schemas)
    w[ranks] = p / p.sum()
    return w


@dataclasses.dataclass
class Batch:
    """Delivered events in stream order, with their payload items (CSR)."""

    key: np.ndarray  # int64 (n,)
    col: np.ndarray  # int32 (n,): global column (schema, version) id
    state: np.ndarray  # int64 (n,): registry state the producer stamped
    offsets: np.ndarray  # int64 (n+1,)
    pos: np.ndarray  # int32 (items,): attribute position in its column
    uid: np.ndarray  # int32 (items,): the registry's attribute id
    val: np.ndarray  # float32 (items,)

    @property
    def n(self) -> int:
        return int(self.key.size)


def generate(
    rng: np.random.Generator,
    stream: dict,
    weights: np.ndarray,
    version_cols: Dict[int, List[int]],
    flat,
    n: int,
    key0: int,
    state: int,
) -> Batch:
    """``n`` delivered events at one registry state.

    ``version_cols[o]`` lists schema ``o``'s column ids, version 1 first;
    ``flat`` is :meth:`Tables.flat`.  A duplicate (at-least-once
    redelivery) follows its original directly and carries its key.
    """
    start, count, uid_flat, _ = flat
    n_schemas = weights.size
    o = rng.choice(n_schemas, size=n, p=weights)
    latest = np.asarray([len(version_cols[s]) for s in range(n_schemas)], np.int64)[o]
    older = 1 + np.floor(rng.random(n) * np.maximum(latest - 1, 1)).astype(np.int64)
    at_latest = (rng.random(n) < stream.get("latest_version_share", 1.0)) | (latest == 1)
    v = np.where(at_latest, latest, older)
    width = max(len(c) for c in version_cols.values())
    col_of = np.full((n_schemas, width + 1), -1, np.int64)
    for s, cols in version_cols.items():
        col_of[s, 1 : len(cols) + 1] = cols
    col = col_of[o, v]
    dup = rng.random(n) < stream.get("p_duplicate", 0.0)
    # items of the n base events: every attribute, kept when not null
    cnt = count[col]
    total = int(cnt.sum())
    ev_of = np.repeat(np.arange(n), cnt)
    first = np.zeros(n, np.int64)
    np.cumsum(cnt[:-1], out=first[1:])
    pos = np.arange(total, dtype=np.int64) - np.repeat(first, cnt)
    present = rng.random(total) >= stream.get("p_null", 0.0)
    if "max_items" in stream:  # warm-up only: cap the items of each event
        rank = np.cumsum(present) - np.repeat(np.cumsum(present)[first] - present[first], cnt)
        present &= rank <= stream["max_items"]
    lo, hi = stream["value_range"]
    val = rng.integers(lo, hi, size=total).astype(np.float32)
    ev_of, pos, val = ev_of[present], pos[present], val[present]
    items_per = np.bincount(ev_of, minlength=n)
    base_off = np.zeros(n + 1, np.int64)
    np.cumsum(items_per, out=base_off[1:])
    # delivery order: each base event, then its duplicate, cut to n
    idx = np.repeat(np.arange(n), 1 + dup)[:n]
    per = items_per[idx]
    off = np.zeros(n + 1, np.int64)
    np.cumsum(per, out=off[1:])
    item_idx = np.arange(int(off[-1]), dtype=np.int64) - np.repeat(off[:-1] - base_off[idx], per)
    pos_d = pos[item_idx].astype(np.int32)
    col_d = col[idx]
    col_items = np.repeat(col_d, per)
    return Batch(
        key=key0 + idx.astype(np.int64),
        col=col_d.astype(np.int32),
        state=np.full(n, state, np.int64),
        offsets=off,
        pos=pos_d,
        uid=uid_flat[start[col_items] + pos_d],
        val=val[item_idx],
    )


def concat(batches: List[Batch]) -> Batch:
    offs = [b.offsets for b in batches]
    shift = np.cumsum([0] + [int(o[-1]) for o in offs[:-1]])
    return Batch(
        key=np.concatenate([b.key for b in batches]),
        col=np.concatenate([b.col for b in batches]),
        state=np.concatenate([b.state for b in batches]),
        offsets=np.concatenate(
            [offs[0][:1]] + [o[1:] + s for o, s in zip(offs, shift)]
        ),
        pos=np.concatenate([b.pos for b in batches]),
        uid=np.concatenate([b.uid for b in batches]),
        val=np.concatenate([b.val for b in batches]),
    )


def arrivals(rng: np.random.Generator, arrival: dict, seconds: float) -> np.ndarray:
    """Sorted due offsets (s) of ``rate * seconds`` events.

    ``kind`` "poisson": a Poisson stream at ``rate_events_per_s``.
    ``kind`` "poisson_burst": its rate is ``burst_factor`` times the base
    rate for ``burst_length_s`` of every ``burst_every_s`` (from
    ``burst_offset_s``), scaled so that the mean over each period is
    ``rate_events_per_s``.  The count is fixed; the times are i.i.d. under
    the intensity, which is a Poisson process conditioned on its count."""
    rate = float(arrival["rate_events_per_s"])
    n = int(round(rate * seconds))
    if arrival["kind"] == "poisson":
        return np.sort(rng.random(n) * seconds)
    if arrival["kind"] != "poisson_burst":
        raise ValueError(f"unknown arrival kind {arrival['kind']!r}")
    period, length = arrival["burst_every_s"], arrival["burst_length_s"]
    factor, phase = arrival["burst_factor"], arrival["burst_offset_s"]
    # piecewise-constant intensity on [0, seconds): split into segments
    edges = [0.0]
    t = 0.0
    while t < seconds:
        for e in (t + phase, t + phase + length, t + period):
            if e < seconds:
                edges.append(e)
        t += period
    edges = np.asarray(sorted(set(edges + [seconds])))
    mids = (edges[:-1] + edges[1:]) / 2
    in_burst = ((mids % period) >= phase) & ((mids % period) < phase + length)
    lam = np.where(in_burst, factor, 1.0)
    mass = lam * np.diff(edges)
    seg = rng.choice(mass.size, size=n, p=mass / mass.sum())
    t = edges[seg] + rng.random(n) * np.diff(edges)[seg]
    return np.sort(t)
