"""Pluggable mapping engines: the device side of the METL app.

A :class:`MappingEngine` owns the compiled representation of the state-``i``
DPM and maps *triaged* event chunks (``(schema, version) -> [CDCEvent]``
groups, produced by :meth:`repro.etl.metl.METLApp.triage`) to canonical rows
through four explicit stages:

    compile(snapshot, registry)   acquire the device plan for one state
                                  from the engine's PlanManager (the single
                                  plan construction site, repro.etl.plan)
    densify(groups)               host side: payload tensors + routing
    dispatch(dense)               device side: launch, return an UNBLOCKED
                                  handle (jax async dispatch: the output
                                  arrays are futures)
    emit(handle)                  the only sync point: read back, slice each
                                  surviving row to its block's true width

Densification is **pure numpy over columnar chunks**: triage produces a
:class:`TriagedChunk` -- a :class:`~repro.etl.events.ColumnarChunk` (flat
``uids`` / ``vals`` item columns + CSR ``event_offsets``) plus per-(schema,
version) event-index arrays -- and both engines lay the chunk out
(:func:`_chunk_layout`) against the plan's precomputed global uid ->
(slot, owning column) dense tables (``FusedDMM.uid_slot`` / ``uid_col``).
No per-item python runs on the hot thread.  Legacy dict ``Groups``
(``(o, v) -> [CDCEvent]``) are still accepted everywhere and are lifted
through :func:`repro.etl.events.columnarize` on entry; the pre-columnar
dict walk survives as :func:`densify_chunk_dicts`, the bit-exactness
oracle.

The stage boundary is the seam the streaming pipeline
(:mod:`repro.etl.pipeline`) exploits for double-buffered async consume:
densify is pure host work (numpy), dispatch never blocks, so chunk N+1's
densification can overlap chunk N's device execution.  Each
:class:`DenseChunk` captures the plan it was densified against, so a state
bump between stages can never mix plans.

Engines register by name (:func:`register_engine`) and are built through
:func:`make_engine`, which turns ``engine="sharded"`` on a 1-shard (or
absent) mesh into the replicated fused engine.

Built-in engines:

  ``fused``    :class:`FusedEngine` -- the whole chunk is mapped across ALL
      its blocks in ONE device dispatch over the state's
      :class:`repro.core.dmm_jax.FusedDMM` block table;

  ``sharded``  :class:`ShardedEngine` -- the fused path with the block table
      partitioned over the mesh ``data`` axis
      (:class:`repro.core.dmm_jax.ShardedFusedDMM`); per-shard routing is
      split host-side in densify (overlappable), one shard_map launch per
      chunk, emitted rows all-gathered in emit -- bit-exact with ``fused``.

Each engine has one chunk path, densify -> dispatch -> emit, with the
densification in one of two places:

  * host scatter (the default): densify scatters the chunk into a dense
    ``(B, n_in_pad)`` payload (:class:`DenseChunk`) and dispatch runs
    :func:`repro.kernels.ops.dmm_apply_fused` (or ``dmm_apply_sharded``);
  * device densify (``device_densify=True``): densify packs the raw
    columnar (uid, value) items and the routing into ONE flat int32
    buffer (:class:`ColumnarDense`), and the single dispatch resolves
    uids, densifies and maps in one program
    (:func:`repro.kernels.ops.dmm_apply_columnar`, the ``densify_map``
    kernel).  Chunks below ``min_device_events`` selected events take the
    host scatter, which also stays the bit-exactness oracle.

Either way emit reads the outputs back and builds the rows in one place,
:func:`_emit_rows`.  Measurements of these paths on the chip are in
``PERF.md``.

``info()`` is the public observability surface (engine name, shard count,
block count, device-resident table bytes, cumulative dispatches/transfers,
``device_densify``) -- callers must use it instead of reaching into private
engine state.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Dict, List, Optional, Tuple, Type

import numpy as np
import jax.numpy as jnp

from ..core.dmm_jax import CompiledDMM, bucket_rows
from ..core.registry import Registry
from ..core.state import SystemState
from ..kernels.ops import (
    dmm_apply_columnar,
    dmm_apply_columnar_sharded,
    dmm_apply_fused,
    dmm_apply_sharded,
)
from . import tracing
from .events import CDCEvent, ColumnarChunk, columnarize
from .plan import PlanEpoch, PlanManager

__all__ = [
    "CanonicalRow",
    "Groups",
    "TriagedChunk",
    "as_triaged",
    "densify_chunk_dicts",
    "DenseChunk",
    "ColumnarDense",
    "DispatchHandle",
    "MappingEngine",
    "FusedEngine",
    "ShardedEngine",
    "ENGINES",
    "register_engine",
    "make_engine",
]


CanonicalRow = Tuple[Tuple[int, int], np.ndarray, np.ndarray, int]
# ((business entity r, version w), values (n_out,), mask (n_out,), event key)

Groups = Dict[Tuple[int, int], List[CDCEvent]]
# legacy triaged-chunk form: (schema o, version v) -> mappable events, in
# arrival order; accepted by every densify and lifted via as_triaged()

IMPLS = ("auto", "ref", "fused")
# the kernel implementations an engine's ``impl`` selects (repro.kernels.ops)


@dataclasses.dataclass
class TriagedChunk:
    """One triaged chunk in columnar form: the surviving events of a
    :class:`~repro.etl.events.ColumnarChunk`, bucketed by (schema, version).

    ``by_column`` maps each (o, v) to the indices (into ``chunk.events`` /
    ``chunk.event_offsets``) of its mappable events, in arrival order and
    first-appearance column order -- exactly the legacy ``Groups`` layout,
    minus the per-event dicts.  Densification gathers each column's payload
    items straight from the chunk's flat (uid, value) arrays.
    """

    chunk: ColumnarChunk
    by_column: Dict[Tuple[int, int], np.ndarray]  # (o, v) -> event indices

    def __bool__(self) -> bool:
        return bool(self.by_column)

    def to_groups(self) -> Groups:
        """The legacy dict-of-event-lists view (oracle tests, A/B bench)."""
        evs = self.chunk.events
        return {
            ov: [evs[int(i)] for i in idx] for ov, idx in self.by_column.items()
        }


def as_triaged(groups) -> Optional[TriagedChunk]:  # metl: allow[hot-path-python-loop] legacy Groups lift at the consume boundary: one pass per chunk, only for dict-input callers (production consume passes TriagedChunk straight through)
    """Coerce any accepted densify input to a non-empty :class:`TriagedChunk`.

    ``TriagedChunk`` passes through; a legacy ``Groups`` dict is columnarised
    once (events with non-numeric payload values are excluded -- on the
    normal path triage already dead-lettered them).  Returns None when there
    is nothing to map.
    """
    if groups is None:
        return None
    if isinstance(groups, TriagedChunk):
        return groups if groups.by_column else None
    if not groups:
        return None
    events = [ev for evs in groups.values() for ev in evs]
    chunk = columnarize(events)
    by_column: Dict[Tuple[int, int], np.ndarray] = {}
    base = 0
    for ov, evs in groups.items():
        idx = [base + k for k in range(len(evs)) if not chunk.bad[base + k]]
        if idx:
            by_column[ov] = np.asarray(idx, dtype=np.int64)
        base += len(evs)
    if not by_column:
        return None
    return TriagedChunk(chunk=chunk, by_column=by_column)


def _excl_cumsum(counts: np.ndarray) -> np.ndarray:
    """Exclusive prefix sum: element i is sum(counts[:i])."""
    out = np.zeros(counts.size, dtype=np.int64)
    np.cumsum(counts[:-1], out=out[1:])
    return out


def _segmented_arange(starts: np.ndarray, counts: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorised ``concatenate([arange(s, s + c) for s, c in ...])``.

    Returns ``(values, seg_of)``: the concatenated ranges plus, per output
    element, the index of the segment it came from.  One arange + two
    repeats -- no per-segment python.
    """
    total = int(counts.sum())
    if total == 0:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    shift = starts - _excl_cumsum(counts)
    values = np.arange(total, dtype=np.int64) + np.repeat(shift, counts)
    seg_of = np.repeat(np.arange(counts.size, dtype=np.int64), counts)
    return values, seg_of


def _event_items(chunk: ColumnarChunk, idx: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorised CSR gather: the payload items of the selected events.

    Returns ``(ev_rows, item_idx)``: the flat positions (into ``chunk.uids``
    / ``chunk.vals``) of every item owned by the events in ``idx``, plus the
    event-local row (0..len(idx)-1) each item scatters into.
    """
    offs = chunk.event_offsets
    starts = offs[idx]
    counts = offs[idx + 1] - starts
    item_idx, ev_rows = _segmented_arange(starts, counts)
    return ev_rows, item_idx


def _uid_slots(lut: np.ndarray, uids: np.ndarray) -> np.ndarray:
    """Bounds-checked dense-table lookup: uid -> payload slot, -1 = foreign
    uid (the vectorised twin of the legacy ``uid_pos.get(uid)``).

    Out-of-range uids (negative, or beyond the table -- e.g. an event
    racing ahead of a schema evolution) are clamped to -1, never
    index-errors; :func:`_count_unknown_uids` accounts them under
    ``stats["unknown_uid"]`` identically across engines."""
    if lut.size == 0:
        return np.full(uids.shape, -1, dtype=np.int32)
    valid = (uids >= 0) & (uids < lut.size)
    slots = lut[np.where(valid, uids, 0)]
    return np.where(valid, slots, np.int32(-1))


def _count_unknown_uids(
    uid_col: np.ndarray,
    chunk: ColumnarChunk,
    by_column: Dict[Tuple[int, int], np.ndarray],
    stats: collections.Counter,
) -> None:
    """Count payload items whose uid NO column of the current plan knows.

    Covers uids beyond the plan's dense-table range (an event racing ahead
    of a schema evolution) and in-range holes (e.g. a deleted version's
    attributes).  Counted over ALL triaged events against the plan-GLOBAL
    uid -> owning-column table, so every engine -- fused or sharded, with
    or without device densify -- reports the identical
    ``stats["unknown_uid"]``.  The items themselves are clamped out of the
    scatter (host) / compare-accumulate (device); they never crash."""
    if not by_column:
        return
    idx = np.concatenate(list(by_column.values()))
    _, item_idx = _event_items(chunk, idx)
    if item_idx.size:
        n = int((_uid_slots(uid_col, chunk.uids[item_idx]) < 0).sum())
        if n:
            stats["unknown_uid"] += n


@dataclasses.dataclass
class DenseChunk:
    """One densified chunk: payload tensors plus (row, block) routing.

    ``plan`` pins the engine plan the chunk was densified against so
    dispatch/emit stay consistent even if the engine recompiles (state bump)
    while the chunk is in flight -- :attr:`epoch` names the pinned state
    ``i``.  This pin is what keeps the pipeline's double-buffered async
    consume bit-exact across a mid-stream schema evolution: a control event
    may recompile the engine while chunk N is on device, but chunk N emits
    against its own epoch's plan.
    """

    plan: Any
    vals: np.ndarray  # (bucket(n_events), n_in_pad) f32
    mask: np.ndarray  # (bucket(n_events), n_in_pad) i8
    row_ids: np.ndarray  # (S,) i32: event row per output row
    blk_ids: np.ndarray  # (S,) i32: global block per output row
    out_keys: np.ndarray  # (S,) i64: event key per output row (emission order)
    # sharded extras (per-shard routing split, filled by ShardedEngine)
    shard_sel: Optional[List[np.ndarray]] = None
    rows_sh: Optional[np.ndarray] = None  # (n_shards, S_loc) i32
    blks_sh: Optional[np.ndarray] = None  # (n_shards, S_loc) i32

    @property
    def epoch(self) -> Optional[int]:
        """The state ``i`` this chunk was densified against (its plan's)."""
        return getattr(self.plan, "state", None)


@dataclasses.dataclass
class ColumnarDense:
    """A chunk densified ON DEVICE: the raw columnar operands packed into
    one flat int32 buffer, so the whole chunk crosses the host->device
    boundary in a single transfer and densification happens inside the one
    fused dispatch (:func:`repro.kernels.ops.dmm_apply_columnar`).

    ``packed`` layout (section sizes are the bucketed statics below):

        [ uids(NI) | val_bits(NI) | starts(B) | counts(B) | ev_col(B)
          | rows | blks ]

    where ``rows``/``blks`` are the (S,) routing (replicated) or the
    flattened (n_shards, S_loc) per-shard pair (sharded).  ``row_ids`` /
    ``blk_ids`` / ``out_keys`` keep the HOST copy of the global routing for
    emit, which is unchanged from the host-densified path.  Same epoch pin
    as :class:`DenseChunk`.
    """

    plan: Any
    packed: np.ndarray  # flat int32 operand buffer (one transfer per chunk)
    n_items: int  # NI: bucketed item-column length
    n_events: int  # B: bucketed selected-event count
    n_rows: int  # S: bucketed routing length (per shard when sharded)
    k: int  # bucketed max items per selected event
    row_ids: np.ndarray  # host routing for emit, global order
    blk_ids: np.ndarray
    out_keys: np.ndarray
    shard_sel: Optional[List[np.ndarray]] = None
    n_shards: int = 1

    @property
    def epoch(self) -> Optional[int]:
        return getattr(self.plan, "state", None)


@dataclasses.dataclass
class DispatchHandle:
    """An in-flight device dispatch.

    ``outputs`` are unblocked jax arrays (futures under async dispatch)
    whose copy to the host dispatch has already started
    (:func:`_start_readback`).  The handle is consumed exactly once by
    :meth:`MappingEngine.emit`, the only stage that synchronises with the
    device.
    """

    outputs: Any
    dense: Any


@dataclasses.dataclass
class _ChunkLayout:
    """Selection + routing of one triaged chunk against one plan -- the
    engine-agnostic prefix shared by the host-scatter and device-densify
    paths.  ``sel`` is the dense-row order (every mappable column's events,
    column by column); ``row_ids``/``blk_ids``/``out_keys`` are the legacy
    emission-order routing."""

    chunk: ColumnarChunk
    sel: np.ndarray  # (B,) i64: chunk event index per dense row
    ev_counts: np.ndarray  # (n_cols,) i64: dense rows per column
    col_ids: np.ndarray  # (n_cols,) i32: plan col_id per column
    row_ids: np.ndarray  # (S,) i32
    blk_ids: np.ndarray  # (S,) i32
    out_keys: np.ndarray  # (S,) i64


@tracing.traced("densify.layout")
def _chunk_layout(
    plan: Any,
    tri: TriagedChunk,
    stats: Optional[collections.Counter] = None,
    uid_col: Optional[np.ndarray] = None,
) -> Optional[_ChunkLayout]:
    """Build the dense-row selection and (row, block) routing for a chunk.

    Fully vectorised: per-column work is two dict lookups (the (o, v) ->
    FusedColumn resolution); the routing itself comes from the plan's
    contiguous per-column block ranges (``col_block_start``/``count``) via
    segmented aranges in legacy emission order (per column, per block, per
    event).  Also accounts ``stats["unknown_uid"]`` when ``stats`` is given
    (over ALL triaged events, mappable or not -- see
    :func:`_count_unknown_uids`) against ``uid_col``, the plan's own
    table unless given.  Returns None for an unmappable chunk (zero
    dispatches) -- exactly the legacy behaviour: columns with no mapping
    paths contribute no output rows.
    """
    chunk = tri.chunk
    if stats is not None:
        _count_unknown_uids(
            plan.uid_col if uid_col is None else uid_col,
            chunk,
            tri.by_column,
            stats,
        )
    cols = [
        (col, idx)
        for (o, v), idx in tri.by_column.items()
        if (col := plan.column(o, v)) is not None and col.block_ids.size
    ]
    if not cols:
        return None

    # dense-row order: every column's events, column by column
    sel = np.concatenate([idx for _, idx in cols])
    ev_counts = np.asarray([idx.size for _, idx in cols], dtype=np.int64)
    col_ids = np.asarray([col.col_id for col, _ in cols], dtype=np.int32)

    # routing in legacy emission order: block t of a column owning n events
    # yields the segment arange(base, base + n); each column's blocks are
    # the contiguous plan range [start, start + count)
    bstart = plan.col_block_start[col_ids].astype(np.int64)
    bcount = plan.col_block_count[col_ids].astype(np.int64)
    seg_starts = np.repeat(_excl_cumsum(ev_counts), bcount)
    seg_counts = np.repeat(ev_counts, bcount)
    row_ids, seg_of = _segmented_arange(seg_starts, seg_counts)
    blk_seq, _ = _segmented_arange(bstart, bcount)

    return _ChunkLayout(
        chunk=chunk,
        sel=sel,
        ev_counts=ev_counts,
        col_ids=col_ids,
        row_ids=row_ids.astype(np.int32),
        blk_ids=blk_seq[seg_of].astype(np.int32),
        out_keys=chunk.keys[sel][row_ids],
    )


def _densify_host(plan: Any, layout: _ChunkLayout) -> DenseChunk:
    """Host-side densification of a laid-out chunk: one CSR gather
    (:func:`_event_items`), one resolve through the plan's global uid
    tables (the owner comparison reproduces the legacy per-column
    ``uid_pos.get`` semantics for stray uids), one numpy scatter.
    Bit-exact with the dict walk (:func:`densify_chunk_dicts`), and the
    bit-exactness oracle for the device-densify path."""
    chunk, sel = layout.chunk, layout.sel
    vals = np.zeros((bucket_rows(sel.size), plan.n_in_pad), np.float32)
    mask = np.zeros_like(vals, dtype=np.int8)
    ev_rows, item_idx = _event_items(chunk, sel)
    if item_idx.size:
        uids = chunk.uids[item_idx]
        slots = _uid_slots(plan.uid_slot, uids)
        owner = _uid_slots(plan.uid_col, uids)
        # column id per dense row -> per item; an item scatters only when
        # its uid belongs to THIS event's column (legacy .get semantics)
        keep = owner == np.repeat(layout.col_ids, layout.ev_counts)[ev_rows]
        if keep.any():
            r, c = ev_rows[keep], slots[keep]
            vals[r, c] = chunk.vals[item_idx[keep]]
            mask[r, c] = 1
    return DenseChunk(
        plan=plan,
        vals=vals,
        mask=mask,
        row_ids=layout.row_ids,
        blk_ids=layout.blk_ids,
        out_keys=layout.out_keys,
    )


def _to_device(*arrays: np.ndarray) -> Tuple[Any, ...]:  # metl: allow[transfer-accounting] the engines' ONE accounted conversion site: every caller increments stats["transfers"] alongside
    """The engines' single host->device conversion site.

    Every per-chunk host->device crossing outside the packed columnar
    buffer (which transfers implicitly inside its jit call) goes through
    here, next to the callers' ``stats["transfers"]`` accounting -- the
    roofline and the bench gate price chunks by that accounting, so a
    conversion anywhere else on the hot path is an unaccounted transfer
    (the ``transfer-accounting`` analyzer rule flags exactly that)."""
    return tuple(jnp.asarray(a) for a in arrays)


def _start_readback(outputs: Tuple[Any, ...], stats: collections.Counter) -> None:
    """Start the device->host copy of a dispatch's outputs, without waiting.

    ``copy_to_host_async`` only enqueues the transfer behind the launch;
    ``emit``'s ``np.asarray`` later picks up the copy already under way.
    In the double-buffered pipeline the read-back thus runs behind the next
    chunk's poll, triage and densify instead of on ``emit``'s critical
    path; in the sync path emit follows at once and nothing changes.
    Counts one ``stats["readbacks_early"]`` per output set."""
    for out in outputs:
        out.copy_to_host_async()
    stats["readbacks_early"] += 1


@tracing.traced("densify.pack")
def _pack_columnar(
    layout: _ChunkLayout, rows_flat: np.ndarray, blks_flat: np.ndarray
) -> Tuple[np.ndarray, Tuple[int, int, int, int]]:
    """Pack one chunk's device-densify operands into ONE flat int32 buffer
    (the :class:`ColumnarDense` layout).  Sections are bucketed to powers
    of two so the jit cache sees a handful of static shapes; float values
    travel as int32 bitcasts (one dtype -> one transfer).  Returns
    ``(packed, n_items, n_events, k)`` with the bucketed statics."""
    chunk, sel = layout.chunk, layout.sel
    offs = chunk.event_offsets
    starts = offs[sel].astype(np.int32)
    counts = (offs[sel + 1] - offs[sel]).astype(np.int32)
    k = bucket_rows(int(counts.max(initial=1)))
    b = sel.size
    b_pad = bucket_rows(b)
    ni = chunk.n_items
    ni_pad = bucket_rows(ni)
    ev_col = np.repeat(layout.col_ids, layout.ev_counts)
    p = np.empty(2 * ni_pad + 3 * b_pad + rows_flat.size + blks_flat.size, np.int32)
    # uids beyond int32 would silently wrap on the cast and could alias a
    # real uid on device; they are unknown by definition (the dense table is
    # int32-indexed), so clamp them to the -1 sentinel like the host path
    uids = chunk.uids
    p[:ni] = np.where((uids >= 0) & (uids < np.int64(2**31)), uids, -1)
    p[ni:ni_pad] = -1  # padded items: unknown uid, never scatters
    p[ni_pad : ni_pad + ni] = chunk.vals.view(np.int32)
    p[ni_pad + ni : 2 * ni_pad] = 0
    o = 2 * ni_pad
    for arr, fill in ((starts, 0), (counts, 0), (ev_col, -1)):
        p[o : o + b] = arr
        p[o + b : o + b_pad] = fill  # padded events: 0 items, no column
        o += b_pad
    p[o : o + rows_flat.size] = rows_flat
    o += rows_flat.size
    p[o : o + blks_flat.size] = blks_flat
    return p, ni_pad, b_pad, k


def densify_chunk_dicts(plan: Any, groups: Groups) -> Optional[DenseChunk]:  # metl: allow[hot-path-python-loop] the pre-columnar oracle: deliberately per-event, kept as the correctness twin for densify_chunk
    """The pre-columnar densification: one python pass over every payload
    dict item per consume, resolved through the ``uid_pos`` dict.

    Kept (not routed in production) as the bit-exactness oracle for the
    property tests and the dict-walk side of the benchmark's densify A/B;
    accepts only the legacy ``Groups`` form.
    """
    cols = [
        (col, evs)
        for (o, v), evs in groups.items()
        if (col := plan.column(o, v)) is not None and col.block_ids.size
    ]
    if not cols:
        return None

    n_events = sum(len(evs) for _, evs in cols)
    vals = np.zeros((bucket_rows(n_events), plan.n_in_pad), np.float32)
    mask = np.zeros_like(vals, dtype=np.int8)
    row_parts: List[np.ndarray] = []
    blk_parts: List[np.ndarray] = []
    out_keys: List[int] = []
    base = 0
    for col, evs in cols:
        lookup = col.uid_pos
        r_idx: List[int] = []
        c_idx: List[int] = []
        v_buf: List[float] = []
        for b, ev in enumerate(evs):
            for uid, val in ev.payload().items():
                if val is None:
                    continue
                pos = lookup.get(uid)
                if pos is not None:
                    r_idx.append(base + b)
                    c_idx.append(pos)
                    v_buf.append(val)
        if r_idx:
            vals[r_idx, c_idx] = v_buf
            mask[r_idx, c_idx] = 1
        ev_rows = np.arange(base, base + len(evs), dtype=np.int32)
        for t in col.block_ids:
            row_parts.append(ev_rows)
            blk_parts.append(np.full(len(evs), t, np.int32))
            out_keys.extend(ev.key for ev in evs)
        base += len(evs)

    return DenseChunk(
        plan=plan,
        vals=vals,
        mask=mask,
        row_ids=np.concatenate(row_parts),
        blk_ids=np.concatenate(blk_parts),
        out_keys=np.asarray(out_keys, dtype=np.int64),
    )


@tracing.traced("emit.rows")
def _emit_rows(plan, ov, om, blk_ids, out_keys, stats) -> List[CanonicalRow]:
    """Row emission shared by the fused and sharded engines: one
    ``any``/``nonzero`` over the gathered output mask, then slice each
    surviving row to its block's true width."""
    rows: List[CanonicalRow] = []
    emit = np.nonzero(om.any(axis=1))[0]  # only non-empty outgoing messages
    stats["mapped"] += int(emit.size)
    stats["empty"] += int(blk_ids.size - emit.size)
    routes, n_out = plan.routes, plan.n_out
    # .tolist() once: the loop body then touches only python ints (numpy
    # scalar boxing per element is the emit hot-path tax otherwise)
    widths = n_out[blk_ids[emit]].tolist()
    for i, t, no, key in zip(
        emit.tolist(), blk_ids[emit].tolist(), widths, out_keys[emit].tolist()
    ):
        rows.append((routes[t], ov[i, :no], om[i, :no], key))
    return rows


class MappingEngine:
    """Protocol base for pluggable mapping engines.

    Subclasses declare their ``plan_kind`` and implement the three chunk
    stages (``densify`` / ``dispatch`` / ``emit``) plus ``info``; the plan
    itself is never built here -- ``compile`` ACQUIRES it from the engine's
    :class:`~repro.etl.plan.PlanManager` (the single construction site; the
    ``plan-publish-single-site`` analyzer rule holds the line), which owns
    epochs and incremental recompaction.  An engine without an explicitly
    bound manager gets a private default on first compile.  ``stats`` is
    the shared counter the owning :class:`~repro.etl.metl.METLApp`
    injects, so engine-side accounting (``dispatches`` / ``mapped`` /
    ``empty``) lands in the app's ``stats``.
    """

    name: str = "base"
    plan_kind: str = "fused"  # the PlanManager kind this engine consumes

    def __init__(
        self,
        *,
        impl: str = "auto",
        stats: Optional[collections.Counter] = None,
        manager: Optional[PlanManager] = None,
    ) -> None:
        if impl not in IMPLS:
            raise ValueError(
                f"unknown impl {impl!r}: the engines run {IMPLS} -- 'fused' "
                "is the segmented_gather / densify_map kernels, 'ref' their "
                "pure-jnp twins, 'auto' the kernels on a TPU and the twins "
                "elsewhere"
            )
        self.impl = impl
        self.stats = stats if stats is not None else collections.Counter()
        self.compiled: Optional[CompiledDMM] = None
        self.plan: Any = None
        self.manager = manager
        # observability binding (set by METLApp): the coordinator whose
        # replication surface info() reports when the manager carries none
        self.coordinator: Optional[Any] = None
        self.lease: Optional[PlanEpoch] = None

    # -- plan lifecycle -----------------------------------------------------
    @property
    def ready(self) -> bool:
        return self.plan is not None

    def compile(self, snapshot: SystemState, registry: Registry) -> Any:
        """Acquire (and retain) the device plan for one state snapshot from
        the plan manager -- cached when current, spliced incrementally when
        the DPM diff allows, fully rebuilt otherwise."""
        if self.manager is None:
            self.manager = PlanManager(
                kind=self.plan_kind, mesh=getattr(self, "mesh", None)
            )
        if self.manager.kind != self.plan_kind:
            raise ValueError(
                f"engine {self.name!r} consumes plan kind "
                f"{self.plan_kind!r}, manager builds {self.manager.kind!r}"
            )
        lease = self.manager.acquire(snapshot, registry)
        self.lease = lease
        self.compiled = lease.compiled
        self.plan = lease.plan
        return self.plan

    def evict(self) -> None:
        """Drop every state-derived cache (the Caffeine analogue).  The
        manager keeps ITS lease -- it is state-keyed, so a re-acquire at an
        unchanged state is a cache hit, and a state bump rebuilds."""
        self.compiled = None
        self.plan = None
        self.lease = None

    def _manager_info(self) -> Dict[str, Any]:
        """The manager-derived keys every engine's ``info()`` carries."""
        if self.manager is None:
            m = {"plan_epoch": 0, "rebuilds": 0, "table_shape_changes": 0}
        else:
            mi = self.manager.info()
            keys = ("plan_epoch", "rebuilds", "table_shape_changes")
            m = {k: mi[k] for k in keys}
        # replication surface: prefer the manager's own coordinator, fall
        # back to the app-level observability binding; a bare engine with
        # neither reports "unbound" (explicitly NOT a leader claim)
        coord = getattr(self.manager, "coordinator", None) or self.coordinator
        if coord is not None:
            m.update(coord.replication_info())
        else:
            m.update(role="unbound", term=0, log_offset=0, lag_records=0)
        return m

    # -- chunk stages --------------------------------------------------------
    def densify(self, groups: Groups) -> Any:
        """Host-side densification; returns an engine-specific dense chunk
        or None when the chunk touches no mapping path."""
        raise NotImplementedError

    def dispatch(self, dense: Any) -> DispatchHandle:
        """Launch the device work for one dense chunk WITHOUT blocking on
        it; increments ``stats['dispatches']`` once per launch."""
        raise NotImplementedError

    def emit(self, handle: DispatchHandle) -> List[CanonicalRow]:
        """Synchronise on a dispatch handle and emit canonical rows."""
        raise NotImplementedError

    # -- conveniences --------------------------------------------------------
    def consume_groups(self, groups: Groups) -> List[CanonicalRow]:
        """Synchronous densify -> dispatch -> emit of one triaged chunk."""
        dense = self.densify(groups)
        if dense is None:
            return []
        return self.emit(self.dispatch(dense))

    def info(self) -> Dict[str, Any]:
        """Public observability surface; the supported way for launchers,
        benchmarks and the cluster runtime to read engine state (no private
        reach-ins; CI grep-gates them).

        Documented keys (every engine):

          ``engine``      registered engine name (``fused``/``sharded``/...)
          ``impl``        kernel implementation variant
          ``n_shards``    mesh shards the plan is partitioned over (1 when
                          replicated)
          ``dispatches``  cumulative device dispatches through this engine
          ``readbacks_early``  cumulative dispatches whose output read-back
                          ``dispatch`` started (equals ``dispatches``)
          ``transfers``   cumulative host->device transfers
          ``device_densify``  whether densification runs on device
          ``plan_epoch``  the plan manager's monotone build counter (0
                          before the first acquire; several epochs can
                          serve one state ``i``)
          ``rebuilds``    cumulative plan builds through the manager
                          (incremental splices + full rebuilds)
          ``table_shape_changes``  installed plans whose device tables
                          changed shape from the previous plan's (each
                          recompiles every chunk shape; 0 while schema
                          evolutions fit the tables' capacity)
          ``role``        control-plane role of the bound coordinator:
                          ``"leader"`` (any unreplicated or leader-bound
                          coordinator), ``"follower"`` (a replica fed by
                          :func:`repro.etl.control.replay_control_log`),
                          or ``"unbound"`` when the engine has no plan
                          manager at all
          ``term``        replication fencing term (0 when unreplicated)
          ``log_offset``  next control-log sequence number the bound
                          coordinator would append/accept (``log_base``
                          + applied records)
          ``lag_records`` received-but-unapplied control records a
                          follower replica is behind by (0 on leaders)

        and, once a plan is compiled (absent while evicted):

          ``state``                 the plan's system state ``i`` (its epoch)
          ``n_blocks``              compacted blocks in the plan
          ``blocks_per_shard``      blocks resident per shard
          ``table_bytes``           device-resident block-table bytes, total
          ``table_bytes_per_shard`` per-shard slice bytes (~ total/N sharded)
          ``bytes_resident``        device-resident block-table bytes the
                                    lease holds (the whole table:
                                    equals ``table_bytes``)
          ``width``                 padded block-table row width
          ``uid_capacity``          uid-table length, a capacity bucket
          ``block_capacity``        block-table rows, a capacity bucket
                                    (per shard times shards)

        ``Cluster.info()`` (:mod:`repro.etl.cluster`) aggregates these per
        instance."""
        raise NotImplementedError


# -- engine registry ---------------------------------------------------------

ENGINES: Dict[str, Type[MappingEngine]] = {}


def register_engine(name: str) -> Any:
    """Class decorator: register a :class:`MappingEngine` under ``name`` so
    ``METLApp(..., engine=name)`` resolves it through :func:`make_engine`."""

    def deco(cls: Type[MappingEngine]) -> Type[MappingEngine]:
        cls.name = name
        ENGINES[name] = cls
        return cls

    return deco


def make_engine(
    engine: Any = "fused",
    *,
    impl: str = "auto",
    mesh: Any = None,
    device_densify: bool = False,
    stats: Optional[collections.Counter] = None,
    manager: Optional[PlanManager] = None,
) -> MappingEngine:
    """Resolve an engine name (or pass through an instance) to a ready
    :class:`MappingEngine`.

    ``engine="sharded"`` needs >1 shard on the mesh ``data`` axis;
    otherwise it degenerates to the replicated fused engine.  ``impl`` is
    one of :data:`IMPLS`; any other value raises (the engine constructor
    checks it) instead of silently running a different path.

    ``device_densify=True`` moves chunk densification on-device
    (:class:`ColumnarDense` / :func:`repro.kernels.ops.dmm_apply_columnar`);
    only the fused and sharded engines realise it, and asking any other
    registered engine for it raises.

    ``manager`` binds an explicit :class:`~repro.etl.plan.PlanManager`
    (e.g. coordinator-published epochs); its ``kind`` must match the
    engine the routing rules resolve to.  Without one the engine builds a
    private default on first compile.
    """
    if isinstance(engine, MappingEngine):
        # an instance carries its own impl/mesh; silently overriding (or
        # dropping) conflicting kwargs would run a different path than asked
        if impl != "auto" and impl != engine.impl:
            raise ValueError(
                f"impl={impl!r} conflicts with engine instance impl={engine.impl!r}; "
                "configure the instance instead"
            )
        if mesh is not None and getattr(engine, "mesh", None) is not mesh:
            raise ValueError(
                "mesh= conflicts with the engine instance; construct the "
                "engine with its mesh instead"
            )
        if device_densify and not getattr(engine, "device_densify", False):
            raise ValueError(
                "device_densify=True conflicts with the engine instance; "
                "construct the engine with device_densify=True instead"
            )
        if stats is not None:
            engine.stats = stats
        if manager is not None:
            if engine.manager is not None and engine.manager is not manager:
                raise ValueError(
                    "manager= conflicts with the engine instance's manager; "
                    "construct the engine with its manager instead"
                )
            engine.manager = manager
        return engine
    if engine not in ENGINES:
        raise ValueError(
            f"unknown engine {engine!r} (registered: {sorted(ENGINES)})"
        )
    if engine == "sharded":
        n_shards = int(mesh.shape["data"]) if mesh is not None else 1
        if n_shards <= 1:
            return ENGINES["fused"](
                impl=impl, device_densify=device_densify, stats=stats,
                manager=manager,
            )
        return ENGINES["sharded"](
            mesh=mesh, impl=impl, device_densify=device_densify, stats=stats,
            manager=manager,
        )
    if device_densify and engine != "fused":
        raise ValueError(
            f"engine={engine!r} has no device-densify path (fused/sharded only)"
        )
    kwargs = {"device_densify": device_densify} if engine == "fused" else {}
    return ENGINES[engine](impl=impl, stats=stats, manager=manager, **kwargs)


# -- the fused engine ---------------------------------------------------------


@register_engine("fused")
class FusedEngine(MappingEngine):
    """One fused dispatch for the whole chunk (all columns, all blocks).

    ``device_densify=True`` skips the host scatter entirely: densify packs
    the chunk's raw columnar items + routing into ONE flat int32 buffer
    (:func:`_pack_columnar`), and dispatch resolves, densifies and maps them
    inside the one fused launch (:func:`repro.kernels.ops.
    dmm_apply_columnar`) against the plan's device-resident uid tables --
    one host->device transfer and one dispatch per chunk.  Chunks below
    ``min_device_events`` selected events fall back to the host scatter
    (kernel padding would dominate); the host path also remains the
    bit-exactness oracle.
    """

    def __init__(
        self,
        *,
        impl: str = "auto",
        device_densify: bool = False,
        min_device_events: int = 32,
        stats: Optional[collections.Counter] = None,
        manager: Optional[PlanManager] = None,
    ) -> None:
        super().__init__(impl=impl, stats=stats, manager=manager)
        self.device_densify = device_densify
        self.min_device_events = min_device_events

    @tracing.traced("densify")
    def densify(self, groups: Groups) -> Any:
        tri = as_triaged(groups)
        if tri is None:
            return None
        layout = _chunk_layout(self.plan, tri, self.stats)
        if layout is None:
            return None
        if not self.device_densify or layout.sel.size < self.min_device_events:
            return _densify_host(self.plan, layout)
        s = layout.row_ids.size
        s_pad = bucket_rows(s)
        rows = np.zeros(s_pad, np.int32)
        blks = np.zeros(s_pad, np.int32)
        rows[:s] = layout.row_ids
        blks[:s] = layout.blk_ids
        packed, ni, b, k = _pack_columnar(layout, rows, blks)
        return ColumnarDense(
            plan=self.plan,
            packed=packed,
            n_items=ni,
            n_events=b,
            n_rows=s_pad,
            k=k,
            row_ids=layout.row_ids,
            blk_ids=layout.blk_ids,
            out_keys=layout.out_keys,
        )

    @tracing.traced("dispatch")
    def dispatch(self, dense) -> DispatchHandle:
        fused = dense.plan
        if isinstance(dense, ColumnarDense):
            outputs = dmm_apply_columnar(
                dense.packed,
                fused.uid_slot_dev,
                fused.uid_col_dev,
                fused.src2d,
                n_items=dense.n_items,
                n_events=dense.n_events,
                n_rows=dense.n_rows,
                k=dense.k,
                impl=self.impl,
            )
            self.stats["transfers"] += 1  # the packed buffer is the chunk
        else:
            s = dense.row_ids.size
            s_pad = bucket_rows(s)
            jv, jm, jr, jb = _to_device(
                dense.vals,
                dense.mask,
                np.pad(dense.row_ids, (0, s_pad - s)),
                np.pad(dense.blk_ids, (0, s_pad - s)),
            )
            outputs = dmm_apply_fused(jv, jm, jr, jb, fused.src2d, impl=self.impl)
            self.stats["transfers"] += 4  # vals, mask, rows, blks
        _start_readback(outputs, self.stats)
        self.stats["dispatches"] += 1
        return DispatchHandle(outputs=outputs, dense=dense)

    @tracing.traced("emit")
    def emit(self, handle: DispatchHandle) -> List[CanonicalRow]:
        dense = handle.dense
        s = dense.row_ids.size
        # the rest of the read-back dispatch started (_start_readback)
        with tracing.span("emit.sync"):
            ov = np.asarray(handle.outputs[0])[:s]  # metl: allow[host-sync-in-hot-path] the engine sync point
            om = np.asarray(handle.outputs[1])[:s]  # metl: allow[host-sync-in-hot-path] the engine sync point
        return _emit_rows(
            dense.plan, ov, om, dense.blk_ids, dense.out_keys, self.stats
        )

    def info(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "engine": self.name,
            "impl": self.impl,
            "n_shards": 1,
            "device_densify": self.device_densify,
            "dispatches": int(self.stats["dispatches"]),
            "readbacks_early": int(self.stats["readbacks_early"]),
            "transfers": int(self.stats["transfers"]),
            **self._manager_info(),
        }
        if self.plan is not None:
            p = self.plan
            table_bytes = int(p.src2d.nbytes)
            d.update(
                state=p.state,
                n_blocks=p.n_blocks,
                blocks_per_shard=p.n_blocks,
                width=p.width,
                uid_capacity=int(p.uid_slot.size),
                block_capacity=int(p.src2d.shape[0]),
                table_bytes=table_bytes,
                table_bytes_per_shard=table_bytes,
                bytes_resident=table_bytes,
            )
        return d


# -- the sharded engine -------------------------------------------------------


@register_engine("sharded")
class ShardedEngine(MappingEngine):
    """The fused path with the block table sharded over the mesh ``data``
    axis: per-shard routing split in densify (host work, overlappable), one
    shard_map launch per chunk (one kernel execution per shard), then an
    all-gather of the emitted dense rows in emit and the shared emission
    pass in global (replicated-engine) order -- bit-exact with ``fused``."""

    plan_kind = "sharded"

    def __init__(
        self, *, mesh: Any, impl: str = "auto", device_densify: bool = False,
        min_device_events: int = 32, stats: Optional[collections.Counter] = None,
        manager: Optional[PlanManager] = None,
    ) -> None:
        super().__init__(impl=impl, stats=stats, manager=manager)
        if mesh is None:
            raise ValueError("engine='sharded' needs a mesh (make_etl_mesh)")
        self.mesh = mesh
        self.n_shards = int(mesh.shape["data"])
        self.device_densify = device_densify
        self.min_device_events = min_device_events

    def _shard_split(self, row_ids, blk_ids):
        """Split the global (row, block) routing by owning shard; the
        contiguous block partition makes ownership a divide, and each
        shard's selection preserves global order for the scatter-back."""
        sh = self.plan
        per = sh.blocks_per_shard
        owner = blk_ids // per
        sel = [np.nonzero(owner == s)[0] for s in range(sh.n_shards)]
        s_pad = bucket_rows(max(len(idx) for idx in sel))
        rows_sh = np.zeros((sh.n_shards, s_pad), np.int32)
        blks_sh = np.zeros((sh.n_shards, s_pad), np.int32)
        for s, idx in enumerate(sel):
            rows_sh[s, : len(idx)] = row_ids[idx]
            blks_sh[s, : len(idx)] = blk_ids[idx] - s * per
        return sel, rows_sh, blks_sh

    @tracing.traced("densify")
    def densify(self, groups: Groups) -> Any:
        tri = as_triaged(groups)
        if tri is None:
            return None
        layout = _chunk_layout(self.plan, tri, self.stats)
        if layout is None:
            return None
        sel, rows_sh, blks_sh = self._shard_split(layout.row_ids, layout.blk_ids)
        if not self.device_densify or layout.sel.size < self.min_device_events:
            dense = _densify_host(self.plan, layout)
            dense.shard_sel, dense.rows_sh, dense.blks_sh = sel, rows_sh, blks_sh
            return dense
        # per-shard routing rides flattened in the packed buffer; the kernel
        # side reshapes to (n_shards, S_loc) and shard_map fans it out
        packed, ni, b, k = _pack_columnar(layout, rows_sh.ravel(), blks_sh.ravel())
        return ColumnarDense(
            plan=self.plan,
            packed=packed,
            n_items=ni,
            n_events=b,
            n_rows=rows_sh.shape[1],
            k=k,
            row_ids=layout.row_ids,
            blk_ids=layout.blk_ids,
            out_keys=layout.out_keys,
            shard_sel=sel,
            n_shards=self.n_shards,
        )

    @tracing.traced("dispatch")
    def dispatch(self, dense) -> DispatchHandle:
        sh = dense.plan
        if isinstance(dense, ColumnarDense):
            outputs = dmm_apply_columnar_sharded(
                dense.packed,
                sh.uid_slot_dev,
                sh.uid_col_dev,
                sh.src3d,
                mesh=sh.mesh,
                n_items=dense.n_items,
                n_events=dense.n_events,
                n_rows=dense.n_rows,
                k=dense.k,
                n_shards=dense.n_shards,
                impl=self.impl,
            )
            self.stats["transfers"] += 1
        else:
            jv, jm, jr, jb = _to_device(
                dense.vals, dense.mask, dense.rows_sh, dense.blks_sh
            )
            outputs = dmm_apply_sharded(
                jv, jm, jr, jb, sh.src3d, mesh=sh.mesh, impl=self.impl
            )
            self.stats["transfers"] += 4
        _start_readback(outputs, self.stats)
        self.stats["dispatches"] += 1
        return DispatchHandle(outputs=outputs, dense=dense)

    @tracing.traced("emit")
    def emit(self, handle: DispatchHandle) -> List[CanonicalRow]:
        dense = handle.dense
        sh = dense.plan
        # all-gather: pull every shard's emitted dense rows to the host
        # (the copy dispatch started) and scatter them back to the
        # global output order
        with tracing.span("emit.sync"):
            ov = np.asarray(handle.outputs[0])  # metl: allow[host-sync-in-hot-path] the engine sync point (all-gather)
            om = np.asarray(handle.outputs[1])  # metl: allow[host-sync-in-hot-path] the engine sync point (all-gather)
        gv = np.zeros((dense.row_ids.size, sh.width), ov.dtype)
        gm = np.zeros((dense.row_ids.size, sh.width), om.dtype)
        for s, idx in enumerate(dense.shard_sel):
            gv[idx] = ov[s, : len(idx)]
            gm[idx] = om[s, : len(idx)]
        return _emit_rows(
            sh, gv, gm, dense.blk_ids, dense.out_keys, self.stats
        )

    def info(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "engine": self.name,
            "impl": self.impl,
            "n_shards": self.n_shards,
            "device_densify": self.device_densify,
            "dispatches": int(self.stats["dispatches"]),
            "readbacks_early": int(self.stats["readbacks_early"]),
            "transfers": int(self.stats["transfers"]),
            **self._manager_info(),
        }
        if self.plan is not None:
            p = self.plan
            table_bytes = int(p.src3d.nbytes)
            d.update(
                state=p.state,
                n_blocks=p.n_blocks,
                blocks_per_shard=p.blocks_per_shard,
                width=p.width,
                uid_capacity=int(p.uid_slot.size),
                block_capacity=p.n_shards * p.n_blocks_pad_loc,
                table_bytes=table_bytes,
                table_bytes_per_shard=p.table_bytes_per_shard,
                bytes_resident=table_bytes,
            )
        return d
