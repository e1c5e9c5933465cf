"""A deployment kind for the tests alone: a few fixed, named schemas whose
attribute-to-CDM mapping the spec states, each drawn at a fixed share of
the stream, with float32 values.  It is no deployment of the benchmark:
it shows that a kind brought as its own module runs through
``run_cell.run`` with no edit to the harness or to ``bench/lib``, and its
reference shares no code with EOS's (``bench/lib/reference.py``).
See ``bench/kinds/__init__.py`` for what each function does."""

from __future__ import annotations

import copy
import dataclasses
from typing import Dict, List, Tuple

import numpy as np

from repro.etl import CDCEvent, ColumnarChunk

from bench.lib.kernel_bytes import chunk_bytes

_SPEC = {
    "name": "fixed",
    "kind": "fixed_schemas",
    "entities": {
        "order": ["id", "amount", "quantity", "discount", "tax"],
        "customer": ["id", "score", "age", "visits"],
    },
    "schemas": {
        "shop.orders": {"entity": "order", "share": 0.6,
                        "map": {"order_id": "id", "total": "amount", "qty": "quantity",
                                "coupon": None, "vat": "tax"}},
        "pos.sales": {"entity": "order", "share": 0.25,
                      "map": {"sale_no": "id", "gross": "amount", "rebate": "discount",
                              "till": None}},
        "crm.customers": {"entity": "customer", "share": 0.15,
                          "map": {"cust_id": "id", "rating": "score", "age_years": "age",
                                  "sessions": "visits", "segment": None}},
    },
    "stream": {"value_range": [1, 100000]},
    "engine": {"engine": "fused", "device_densify": True, "async_consume": True,
               "dedup_window": 4096},
}


def spec() -> dict:
    return copy.deepcopy(_SPEC)


@dataclasses.dataclass
class Fixed:
    coordinator: object
    uids: List[np.ndarray]  # per schema: the registry's attribute ids, in spec order
    target: List[np.ndarray]  # per schema: each attribute's CDM position, -1 if unmapped
    entity: np.ndarray  # per schema: its entity
    width: List[int]  # per entity: its CDM attributes

    @property
    def state(self) -> int:
        return self.coordinator.registry.state


@dataclasses.dataclass
class Events:
    key: np.ndarray  # int64 (n,)
    schema: np.ndarray  # int64 (n,)
    state: np.ndarray  # int64 (n,)
    offsets: np.ndarray  # int64 (n+1,)
    pos: np.ndarray  # int64 (items,): the attribute's index in its schema
    uid: np.ndarray  # int32 (items,)
    val: np.ndarray  # float32 (items,)

    @property
    def n(self) -> int:
        return int(self.key.size)


def build(spec: dict) -> Fixed:
    from repro.core.dmm import MappingMatrix, transform_to_dpm
    from repro.core.registry import Registry
    from repro.core.state import StateCoordinator

    reg = Registry()
    names = list(spec["entities"])
    for r, (entity, attrs) in enumerate(spec["entities"].items()):
        reg.add_schema(reg.range, r, [f"{entity}.{a}" for a in attrs])
    for o, (schema, s) in enumerate(spec["schemas"].items()):
        reg.add_schema(reg.domain, o, [f"{schema}.{a}" for a in s["map"]])
    matrix = MappingMatrix(reg)
    uids, target, entity = [], [], []
    for o, s in enumerate(spec["schemas"].values()):
        r = names.index(s["entity"])
        cdm = spec["entities"][s["entity"]]
        t = np.asarray([cdm.index(c) if c else -1 for c in s["map"].values()], np.int64)
        u = np.asarray(reg.domain.get(o, 1).uids, np.int32)
        for p, q in enumerate(t):
            if q >= 0:
                matrix.set(reg.range.get(r, 1).uids[q], int(u[p]), 1)
        uids.append(u)
        target.append(t)
        entity.append(r)
    matrix.validate_one_to_one()
    return Fixed(StateCoordinator(reg, transform_to_dpm(matrix)), uids, target,
                 np.asarray(entity, np.int64), [len(a) for a in spec["entities"].values()])


def weights(spec: dict, traffic: dict) -> np.ndarray:
    w = np.asarray([s["share"] for s in spec["schemas"].values()], np.float64)
    return w / w.sum()


def generate(d: Fixed, w, rng, stream: dict, n: int, key0: int) -> Events:
    """Full rows of schemas drawn at their shares; ``p_null`` drops items,
    ``max_items`` keeps each event's first attributes."""
    o = rng.choice(w.size, size=n, p=w)
    count = np.asarray([u.size for u in d.uids], np.int64)
    per = count[o]
    ev = np.repeat(np.arange(n), per)
    pos = np.arange(int(per.sum())) - np.repeat(np.cumsum(per) - per, per)
    lo, hi = stream["value_range"]
    val = rng.integers(lo, hi, size=pos.size).astype(np.float32)
    keep = rng.random(pos.size) >= stream.get("p_null", 0.0)
    keep &= pos < stream.get("max_items", pos.size + 1)
    ev, pos, val = ev[keep], pos[keep], val[keep]
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(ev, minlength=n), out=offsets[1:])
    start = np.cumsum(count) - count
    return Events(key=key0 + np.arange(n, dtype=np.int64), schema=o.astype(np.int64),
                  state=np.full(n, d.state, np.int64), offsets=offsets, pos=pos,
                  uid=np.concatenate(d.uids)[start[o[ev]] + pos], val=val)


def chunk(b: Events, lo: int, hi: int, key_shift: int, state, events) -> ColumnarChunk:
    a, z = b.offsets[lo], b.offsets[hi]
    return ColumnarChunk(
        events=events, uids=b.uid[a:z], vals=b.val[a:z],
        event_offsets=b.offsets[lo : hi + 1] - a, keys=b.key[lo:hi] + key_shift,
        bad=np.zeros(hi - lo, bool),
        states=b.state[lo:hi] if state is None else np.full(hi - lo, state, np.int64),
        schema_ids=b.schema[lo:hi], versions=np.ones(hi - lo, np.int64),
    )


def event(b: Events, i: int, key_shift: int, state, ts: int) -> CDCEvent:
    lo, hi = b.offsets[i], b.offsets[i + 1]
    return CDCEvent(key=int(b.key[i]) + key_shift, op="c",
                    state=int(b.state[i]) if state is None else state,
                    schema_id=int(b.schema[i]), version=1, before=None,
                    after={int(u): float(x) for u, x in zip(b.uid[lo:hi], b.val[lo:hi])},
                    ts=ts)


@dataclasses.dataclass
class Rows:
    """Each (key, entity)'s row as ((CDM position, float32 bits), ...), and
    how many rows repeated a (key, entity) already held."""

    rows: Dict[Tuple[int, int], tuple]
    repeats: int = 0

    @property
    def n(self) -> int:
        return len(self.rows) + self.repeats


def _bits(x) -> int:
    return int(np.float32(x).view(np.int32))


def expected(d: Fixed, b: Events, passes, control: str) -> Rows:
    """Each key's first delivery maps into its schema's entity: one row of
    its mapped, present values; an event with none yields no row.  The
    control rounds each value to bfloat16."""
    if control == "bf16":
        import ml_dtypes

        value = lambda x: _bits(np.float32(ml_dtypes.bfloat16(x)))  # noqa: E731
    elif control == "none":
        value = _bits
    else:
        raise ValueError(f"unknown control {control!r}")
    out: Dict[Tuple[int, int], tuple] = {}
    seen = set()
    for take, shift in passes:
        for e in range(take):
            key = int(b.key[e]) + shift
            if key in seen:
                continue
            seen.add(key)
            o = int(b.schema[e])
            row = sorted((int(d.target[o][b.pos[k]]), value(b.val[k]))
                         for k in range(b.offsets[e], b.offsets[e + 1])
                         if d.target[o][b.pos[k]] >= 0)
            if row:
                out[(key, int(d.entity[o]))] = tuple(row)
    return Rows(out)


def program_rows(d: Fixed, arrays) -> Rows:
    got = Rows({})
    for (r, _w), t in arrays.items():
        for key, vals, mask in zip(t["keys"], t["values"], t["mask"]):
            k = (int(key), int(r))
            if k in got.rows:
                got.repeats += 1
                continue
            got.rows[k] = tuple((int(q), _bits(vals[q])) for q in np.flatnonzero(mask))
    return got


def compare(want: Rows, got: Rows) -> Dict[str, int]:
    return {
        "rows_missing": sum(k not in got.rows for k in want.rows),
        "rows_extra": sum(k not in want.rows for k in got.rows) + got.repeats,
        "rows_wrong": sum(got.rows[k] != v for k, v in want.rows.items() if k in got.rows),
    }


def kernel_bytes(d: Fixed, b: Events, polls) -> int:
    mapped = np.asarray([bool((t >= 0).any()) for t in d.target])[b.schema]
    width = np.asarray(d.width)[d.entity]
    items = np.diff(b.offsets)
    total = 0
    for lo, hi, _ in polls:
        i, j = lo % b.n, (hi - 1) % b.n + 1
        s = mapped[i:j]
        o = b.schema[i:j][s]
        total += chunk_bytes(int(items[i:j][s].sum()), int(s.sum()), int(s.sum()),
                             width[np.unique(o)], width[o])
    return total
