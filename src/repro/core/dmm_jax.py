"""Tensorised, device-resident form of the compacted mapping (Algorithm 6).

The paper's final mapping function is a *set lookup*: for each dense set
element ``(q, p)`` with value 1, move payload slot ``p`` to output slot ``q``.
On a TPU that is a **gather along the attribute axis**, batched over messages.

Shapes are static (XLA requirement), so the paper's variable-width JSON
messages become fixed-width payload tensors plus a validity mask:

    values : (batch, n_in)  payload slots in schema-version attribute order
    mask   : (batch, n_in)  bool; the paper's  nad_p in {0, 1}

and a compacted block becomes an index vector

    src    : (n_out_pad,)   int32; src[q] = p  or  -1 ("null" / filtered)

``n_out_pad`` is rounded up to the TPU lane width (128) so the gather tiles
cleanly; the pad slots carry src = -1 and are masked out, exactly the paper's
"there may also be empty container places in the new ships".

Two apply paths are provided:

  * :func:`apply_compacted`   -- the DMM path (gather; optimal)
  * :func:`apply_onehot`      -- the baseline path (one-hot matmul; this is
      the "use the matrix directly" formulation the DMM replaces -- kept for
      A/B benchmarking and as the oracle for the Pallas kernel)

The Pallas kernel realisation of :func:`apply_compacted` is
:mod:`repro.kernels.masked_gather`.

On top of the per-block form sits the **fused engine**: :class:`FusedDMM`
(built by :func:`compile_fused`) flattens *every* compacted block of the
state-``i`` DPM into device-resident tables so a whole heterogeneous event
chunk maps in ONE device dispatch (:func:`repro.kernels.ops.dmm_apply_fused`
over :mod:`repro.kernels.segmented_gather`):

    src2d      (n_blocks_pad, W) int32   all block index vectors, stacked in
               column order and right-padded with -1 to W = max(n_out_pad);
               n_blocks_pad = bucket_rows(n_blocks), rows past n_blocks -1
    routes     block t emits to business entity routes[t] = (r, w)
    n_out      true (unpadded) output width per block
    columns    (o, v) -> FusedColumn: the column super-set iDCPM_v^o as
               global block ids plus the uid -> payload-slot lookup used for
               vectorised densification
    uid_slot / uid_col  (bucket_rows(max_uid + 1),) int32 uid tables, -1
               past max_uid

Batch-shape bucketing (:func:`bucket_rows`, powers of two) keeps the set of
operand shapes small so steady-state consume chunks never retrace.  The
plan's tables are sized by the same rule: a schema evolution adds a few
fresh uids and blocks, which almost always stay inside the current
capacity, so the new state's tables have the old state's shapes and every
compiled chunk program is reused -- a new state costs a table upload, not
a compile.  The padding is -1, which every lookup already reads as "no
such uid / block".

For CDMs too wide for one device, :class:`ShardedFusedDMM` (built by
:func:`compile_fused_sharded`) partitions the flattened block table over the
entity/output axis: global block ``t`` lives on shard ``t //
blocks_per_shard``, and the stacked per-shard tables form

    src3d      (n_shards, n_blocks_pad_loc, W) int32, leading axis placed
               over the mesh ``data`` axis (NamedSharding), so each device
               holds only its (1, n_blocks_pad_loc, W) slice;
               n_blocks_pad_loc = bucket_rows(blocks_per_shard)

executed per shard under ``shard_map``
(:func:`repro.kernels.ops.dmm_apply_sharded`) -- still one dispatch per
chunk per shard.  The contiguous-by-block partition preserves the
replicated engine's emission order, so sharded consume is bit-exact with
the fused path.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
import numpy as np

from .dmm import DPM, BlockKey
from .registry import Registry

__all__ = [
    "LANE",
    "SUBLANE",
    "pad_to_lane",
    "bucket_rows",
    "uid_lookup_table",
    "CompactedBlockMap",
    "compile_block",
    "compile_dpm",
    "compile_fused",
    "apply_compacted",
    "apply_onehot",
    "CompiledDMM",
    "FusedColumn",
    "FusedDMM",
    "ShardedFusedDMM",
    "compile_fused_sharded",
    "global_uid_tables",
    "recompile_columns",
    "splice_fused",
]

LANE = 128  # TPU vector lane width; last-dim tiles must be multiples of this
SUBLANE = 8  # second-minor tile width; sublane axes pad to multiples of this


def uid_lookup_table(uids) -> np.ndarray:
    """Dense uid -> position table for vectorised densification.

    ``lut[uid] = k`` for the k-th uid in ``uids``, -1 elsewhere.  Registry
    uids are small sequential ints, so the dense table stays tiny; lookups
    become one bounds-checked numpy gather instead of a per-item dict.get.
    """
    uids = np.asarray(list(uids), dtype=np.int64)
    if uids.size == 0:
        return np.empty(0, dtype=np.int32)
    lut = np.full(int(uids.max()) + 1, -1, dtype=np.int32)
    lut[uids] = np.arange(uids.size, dtype=np.int32)
    return lut


def pad_to_lane(n: int, lane: int = LANE) -> int:
    return max(lane, -(-n // lane) * lane)


def bucket_rows(n: int, floor: int = SUBLANE) -> int:
    """Round a batch/row count up to the next power of two (>= ``floor``).

    The fused engine pads every per-chunk operand to a bucketed shape so a
    steady stream of slightly-varying chunk sizes hits a handful of jit-cache
    entries instead of retracing per chunk.  The plan's uid tables and block
    table take the same rule (their capacity), so they keep their shapes
    across registry states until a table outgrows its bucket.
    """
    if n <= floor:
        return floor
    return 1 << (n - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class CompactedBlockMap:
    """One compacted mapping block, ready for device execution."""

    key: BlockKey
    n_in: int  # true width of the incoming message (attrs of iD_v^o)
    n_out: int  # true width of the outgoing message (attrs of iR_w^r)
    src: jax.Array  # int32 (n_out_pad,): input slot per output slot, -1 = null

    @property
    def n_out_pad(self) -> int:
        return int(self.src.shape[0])

    def tree_flatten(self):  # pragma: no cover - convenience
        return (self.src,), (self.key, self.n_in, self.n_out)


def compile_block(
    key: BlockKey, elements: Sequence, registry: Registry, lane: int = LANE
) -> CompactedBlockMap:
    """Lower one dense set ``{(q_uid, p_uid)}`` to an index vector."""
    o, v, r, w = key
    in_uids = registry.domain.get(o, v).uids
    out_uids = registry.range.get(r, w).uids
    in_pos = {u: k for k, u in enumerate(in_uids)}
    out_pos = {u: k for k, u in enumerate(out_uids)}
    n_in, n_out = len(in_uids), len(out_uids)
    src = np.full((pad_to_lane(n_out, lane),), -1, dtype=np.int32)
    for q_uid, p_uid in elements:
        src[out_pos[q_uid]] = in_pos[p_uid]
    return CompactedBlockMap(key=key, n_in=n_in, n_out=n_out, src=jnp.asarray(src))


def apply_compacted(
    block: CompactedBlockMap,
    values: jax.Array,
    mask: jax.Array,
    *,
    fill: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """The DMM mapping: batched masked gather.

    values: (..., n_in) payload, mask: (..., n_in) bool.
    Returns (out_values (..., n_out_pad), out_mask (..., n_out_pad)).
    """
    src = block.src
    valid = src >= 0
    safe = jnp.where(valid, src, 0)
    out_v = jnp.take(values, safe, axis=-1)
    out_m = jnp.take(mask, safe, axis=-1) & valid
    out_v = jnp.where(out_m, out_v, jnp.asarray(fill, dtype=out_v.dtype))
    return out_v, out_m


def onehot_matrix(block: CompactedBlockMap) -> jax.Array:
    """The block as an explicit (n_out_pad, n_in) 0/1 matrix -- the baseline
    representation the paper compacts away."""
    src = block.src
    cols = jnp.arange(block.n_in, dtype=jnp.int32)
    return (src[:, None] == cols[None, :]).astype(jnp.float32)


def apply_onehot(
    block: CompactedBlockMap,
    values: jax.Array,
    mask: jax.Array,
    *,
    fill: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """Baseline: out = M @ in  (MXU matmul against a sparse 0/1 matrix).

    Mathematically identical to :func:`apply_compacted`; structurally it is
    the paper's Algorithm-1 world where the matrix itself is the operator.
    Kept as the A/B baseline and the allclose oracle.
    """
    m = onehot_matrix(block)  # (n_out_pad, n_in)
    out_v = jnp.einsum("qp,...p->...q", m, values.astype(jnp.float32))
    out_m = jnp.einsum("qp,...p->...q", m, mask.astype(jnp.float32)) > 0.5
    out_v = jnp.where(out_m, out_v, fill).astype(values.dtype)
    return out_v, out_m


@dataclasses.dataclass
class CompiledDMM:
    """All compacted blocks of a state-i DPM, grouped by incoming (o, v).

    This is the device-side analogue of the paper's cached hashmap of
    column super-sets ``iDCPM_v^o`` ("accessible in O(1)", SS6.2): blocks are
    keyed by the incoming message's (schema, version), so the per-message
    work is exactly the blocks that can produce non-empty output.
    """

    state: int
    by_column: Dict[Tuple[int, int], List[CompactedBlockMap]]

    def column(self, o: int, v: int) -> List[CompactedBlockMap]:
        return self.by_column.get((o, v), [])

    @property
    def n_blocks(self) -> int:
        return sum(len(b) for b in self.by_column.values())

    def map_batch(
        self, o: int, v: int, values: jax.Array, mask: jax.Array
    ) -> List[Tuple[BlockKey, jax.Array, jax.Array]]:
        """Map a batch of dense messages of one (o, v) through every block in
        its column super-set.  Each block is an independent mapping path
        (paper SS5.5) -- XLA executes the gathers in parallel."""
        outs = []
        for block in self.column(o, v):
            ov, om = apply_compacted(block, values, mask)
            outs.append((block.key, ov, om))
        return outs


def compile_dpm(dpm: DPM, registry: Registry, lane: int = LANE) -> CompiledDMM:
    """Lower a whole iDPM super-set to device index vectors (the "read into
    an efficient hashmap" step of the paper's hybrid implementation)."""
    by_column: Dict[Tuple[int, int], List[CompactedBlockMap]] = {}
    for key, elements in sorted(dpm.items()):
        o, v, r, w = key
        by_column.setdefault((o, v), []).append(
            compile_block(key, elements, registry, lane)
        )
    return CompiledDMM(state=registry.state, by_column=by_column)


# ---------------------------------------------------------------------------
# The fused engine: one device dispatch per event chunk, across all blocks
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FusedColumn:
    """Host-side routing for one incoming (schema o, version v) column.

    ``uid_pos`` is the precomputed attribute-uid -> payload-slot lookup the
    legacy dict-walk densification resolved payload items against; the
    vectorised densification instead uses the PLAN-global ``uid_slot`` /
    ``uid_col`` dense tables (uids are globally unique), with ``col_id``
    naming this column in those tables.  ``block_ids`` are the global
    block-table rows of the column super-set iDCPM_v^o, in compile (column)
    order.  ``uids_arr`` carries the column's uids in slot order as one
    int64 array so an incremental recompile (:func:`splice_fused`) can
    rebuild the plan-global uid tables with two scatters instead of
    re-walking every column's ``uid_pos`` dict.
    """

    o: int
    v: int
    n_in: int
    uid_pos: Dict[int, int]
    block_ids: np.ndarray  # int32 (k,): rows of FusedDMM.src2d
    col_id: int = -1  # position of this column in the plan's column order
    uids_arr: Optional[np.ndarray] = None  # int64 (n_in,): uids in slot order


@dataclasses.dataclass
class FusedDMM:
    """Every compacted block of a state-``i`` DPM, flattened for one-launch
    execution (see module docstring for the table layout)."""

    state: int
    n_in_pad: int  # uniform dense-payload width (lane multiple)
    width: int  # W: uniform output width = max n_out_pad (lane multiple)
    n_blocks: int  # true block count (src2d rows beyond this are -1 pad)
    src2d: jax.Array  # int32 (bucket_rows(n_blocks), W), device-resident
    routes: List[Tuple[int, int]]  # block t -> business entity (r, w)
    n_out: np.ndarray  # int32 (n_blocks,): true output width per block
    columns: Dict[Tuple[int, int], FusedColumn]
    uid_slot: np.ndarray  # int32 (bucket_rows(max_uid+1),): uid -> payload slot, -1 = none
    uid_col: np.ndarray  # int32 (bucket_rows(max_uid+1),): uid -> owning col_id, -1 = none
    # column col_id owns the contiguous global block range
    # [col_block_start[c], col_block_start[c] + col_block_count[c]) -- block
    # ids are assigned in column order, so per-column routing vectorises to
    # two repeats instead of a per-column python loop
    col_block_start: np.ndarray = None  # int32 (n_cols,)
    col_block_count: np.ndarray = None  # int32 (n_cols,)
    # device-resident copies of the uid tables (uploaded once per state, same
    # shapes across states while the capacity holds) for the device-densify
    # path (repro.kernels.ops.dmm_apply_columnar)
    uid_slot_dev: Optional[jax.Array] = None
    uid_col_dev: Optional[jax.Array] = None

    def column(self, o: int, v: int) -> Optional[FusedColumn]:
        return self.columns.get((o, v))


def _uid_tables_from(cols) -> Tuple[np.ndarray, np.ndarray]:
    """Dense global (uid -> payload slot, uid -> owning col_id) tables from
    ``(uid_pos dict, col_id)`` pairs; -1 marks uids no column knows.  The
    tables hold ``bucket_rows(max_uid + 1)`` entries, so the fresh uids of
    a schema evolution usually fit and the shapes stay put."""
    cols = list(cols)
    max_uid = max((int(u) for pos, _ in cols for u in pos), default=-1)
    uid_slot = np.full(bucket_rows(max_uid + 1), -1, dtype=np.int32)
    uid_col = np.full(uid_slot.size, -1, dtype=np.int32)
    for pos, cid in cols:
        for u, k in pos.items():
            uid_slot[u] = k
            uid_col[u] = cid
    return uid_slot, uid_col


def global_uid_tables(
    compiled: CompiledDMM, registry: Registry
) -> Tuple[np.ndarray, np.ndarray]:
    """The fused plan's global uid tables, derivable from any engine's plan.

    Column ids follow ``compiled.by_column`` insertion order -- the same
    order :func:`_fused_tables` assigns -- so the ``blocks`` engine can
    account ``stats["unknown_uid"]`` identically to the fused/sharded
    engines without materialising a fused plan."""
    return _uid_tables_from(
        ({u: k for k, u in enumerate(registry.domain.get(o, v).uids)}, cid)
        for cid, (o, v) in enumerate(compiled.by_column)
    )


def _fused_tables(
    compiled: CompiledDMM, registry: Registry, lane: int = LANE
) -> Tuple:
    """Host-side flattening shared by the replicated and sharded compiles.

    Returns ``(table, routes, n_out, columns, n_in_pad, width, n_blocks)``
    where ``table`` is the full numpy (n_blocks_pad, W) block table; the
    callers decide device placement (replicated vs sharded over a mesh).
    """
    routes: List[Tuple[int, int]] = []
    n_out: List[int] = []
    src_rows: List[np.ndarray] = []
    columns: Dict[Tuple[int, int], FusedColumn] = {}
    width = lane
    n_in_max = 1
    for (o, v), blocks in compiled.by_column.items():
        for blk in blocks:
            width = max(width, blk.n_out_pad)
    for (o, v), blocks in compiled.by_column.items():
        sv = registry.domain.get(o, v)
        uid_pos = {u: k for k, u in enumerate(sv.uids)}
        n_in_max = max(n_in_max, len(sv.uids))
        ids = []
        for blk in blocks:
            t = len(routes)
            ids.append(t)
            routes.append((blk.key[2], blk.key[3]))
            n_out.append(blk.n_out)
            row = np.full((width,), -1, dtype=np.int32)
            row[: blk.n_out_pad] = np.asarray(blk.src)
            src_rows.append(row)
        columns[(o, v)] = FusedColumn(
            o=o,
            v=v,
            n_in=len(sv.uids),
            uid_pos=uid_pos,
            block_ids=np.asarray(ids, dtype=np.int32),
            col_id=len(columns),
            uids_arr=np.asarray(sv.uids, dtype=np.int64),
        )
    # plan-global uid tables for the fully-vectorised densification: every
    # attribute uid is globally unique (one registry counter), so one dense
    # table resolves any payload uid to (its payload slot, its owning
    # column) in a single gather; the owner check reproduces the legacy
    # per-column lookup semantics for stray/foreign uids
    uid_slot, uid_col = _uid_tables_from(
        (col.uid_pos, col.col_id) for col in columns.values()
    )
    n_blocks = len(routes)
    table = np.full((bucket_rows(n_blocks), width), -1, dtype=np.int32)
    if src_rows:
        table[:n_blocks] = np.stack(src_rows)
    n_out_arr = np.asarray(n_out, dtype=np.int32)
    # block ids are assigned sequentially per column, so each column's
    # blocks are the contiguous range [start, start + count)
    col_block_start = np.asarray(
        [int(c.block_ids[0]) if c.block_ids.size else 0 for c in columns.values()],
        dtype=np.int32,
    )
    col_block_count = np.asarray(
        [c.block_ids.size for c in columns.values()], dtype=np.int32
    )
    return (
        table,
        routes,
        n_out_arr,
        columns,
        pad_to_lane(n_in_max, lane),
        width,
        n_blocks,
        uid_slot,
        uid_col,
        col_block_start,
        col_block_count,
    )


def _assemble_replicated(parts: Tuple, state: int) -> FusedDMM:
    """Place a host-side table bundle (``_fused_tables`` layout) on the
    default device as a replicated :class:`FusedDMM`."""
    (table, routes, n_out, columns, n_in_pad, width, n_blocks, uid_slot,
     uid_col, cb_start, cb_count) = parts
    return FusedDMM(
        state=state,
        n_in_pad=n_in_pad,
        width=width,
        n_blocks=n_blocks,
        src2d=jnp.asarray(table),
        routes=routes,
        n_out=n_out,
        columns=columns,
        uid_slot=uid_slot,
        uid_col=uid_col,
        col_block_start=cb_start,
        col_block_count=cb_count,
        uid_slot_dev=jnp.asarray(uid_slot),
        uid_col_dev=jnp.asarray(uid_col),
    )


def compile_fused(
    compiled: CompiledDMM, registry: Registry, lane: int = LANE
) -> FusedDMM:
    """Flatten a :class:`CompiledDMM` into the fused block table.

    Compiled once per state (alongside the per-block form) and cached until
    the next state bump evicts it -- the fused analogue of the paper's
    Caffeine-cached hashmap of column super-sets.  This full rebuild is the
    bit-exactness ORACLE for the incremental path
    (:func:`recompile_columns` / :func:`splice_fused`).
    """
    return _assemble_replicated(
        _fused_tables(compiled, registry, lane), compiled.state
    )


@dataclasses.dataclass
class ShardedFusedDMM:
    """The fused block table partitioned over the entity/output axis.

    Global block ``t`` (the replicated table's row ``t``, in compile/column
    order) lives on shard ``t // blocks_per_shard`` at local row ``t %
    blocks_per_shard``; the contiguous partition keeps emission order
    identical to the replicated engine.  ``src3d`` stacks the per-shard
    tables with a leading shard axis that is placed over the mesh ``data``
    axis when a mesh is given -- each device then holds only its own
    (1, n_blocks_pad_loc, W) slice, so per-shard table bytes are ~ total /
    n_shards.  ``routes`` / ``n_out`` / ``columns`` are host-side emission
    and densification metadata (global order; the per-shard views are
    :meth:`shard_routes` / :meth:`shard_n_out`).
    """

    state: int
    n_shards: int
    blocks_per_shard: int
    n_in_pad: int
    width: int
    n_blocks: int  # true global block count
    src3d: jax.Array  # int32 (n_shards, n_blocks_pad_loc, W)
    mesh: Optional[object]  # jax Mesh the table is placed on (None = host)
    routes: List[Tuple[int, int]]  # global block t -> business entity (r, w)
    n_out: np.ndarray  # int32 (n_blocks,) true output width per block
    columns: Dict[Tuple[int, int], FusedColumn]
    uid_slot: np.ndarray  # int32 (bucket_rows(max_uid+1),): uid -> payload slot, -1 = none
    uid_col: np.ndarray  # int32 (bucket_rows(max_uid+1),): uid -> owning col_id, -1 = none
    col_block_start: np.ndarray = None  # int32 (n_cols,): see FusedDMM
    col_block_count: np.ndarray = None  # int32 (n_cols,)
    uid_slot_dev: Optional[jax.Array] = None  # device copies (once per state)
    uid_col_dev: Optional[jax.Array] = None

    def column(self, o: int, v: int) -> Optional[FusedColumn]:
        return self.columns.get((o, v))

    @property
    def n_blocks_pad_loc(self) -> int:
        return int(self.src3d.shape[1])

    @property
    def table_bytes_per_shard(self) -> int:
        """Device-resident block-table bytes held by ONE shard."""
        return self.n_blocks_pad_loc * self.width * 4

    def shard_slice(self, s: int) -> Tuple[int, int]:
        """Global block id range [lo, hi) owned by shard ``s``."""
        lo = s * self.blocks_per_shard
        return lo, min(lo + self.blocks_per_shard, self.n_blocks)

    def shard_routes(self, s: int) -> List[Tuple[int, int]]:
        lo, hi = self.shard_slice(s)
        return self.routes[lo:hi]

    def shard_n_out(self, s: int) -> np.ndarray:
        lo, hi = self.shard_slice(s)
        return self.n_out[lo:hi]


def compile_fused_sharded(
    compiled: CompiledDMM,
    registry: Registry,
    *,
    mesh: Optional[Mesh] = None,
    n_shards: Optional[int] = None,
    axis: str = "data",
    lane: int = LANE,
) -> ShardedFusedDMM:
    """Partition the fused block table over ``n_shards`` (the mesh ``data``
    axis size when a mesh is given) and place each shard's slice on its own
    device.

    With ``mesh=None`` the stacked table stays on the default device
    (host-only partitioning -- used by unit tests and the 1-shard fallback
    path); with a mesh it is ``device_put`` under
    :func:`repro.sharding.specs.dmm_table_sharding`.
    """
    if n_shards is None:
        if mesh is None:
            raise ValueError("need a mesh or an explicit n_shards")
        n_shards = mesh.shape[axis]
    return _assemble_sharded(
        _fused_tables(compiled, registry, lane),
        compiled.state,
        mesh=mesh,
        n_shards=n_shards,
        axis=axis,
    )


def _assemble_sharded(
    parts: Tuple,
    state: int,
    *,
    mesh: Optional[Mesh],
    n_shards: int,
    axis: str = "data",
) -> ShardedFusedDMM:
    """Partition a host-side table bundle over ``n_shards`` and place each
    slice (``device_put`` under a mesh, default device otherwise)."""
    (table, routes, n_out, columns, n_in_pad, width, n_blocks, uid_slot,
     uid_col, cb_start, cb_count) = parts
    per = -(-max(n_blocks, 1) // n_shards)
    per_pad = bucket_rows(per)
    src3d_np = np.full((n_shards, per_pad, width), -1, dtype=np.int32)
    for s in range(n_shards):
        lo, hi = s * per, min((s + 1) * per, n_blocks)
        if hi > lo:
            src3d_np[s, : hi - lo] = table[lo:hi]
    if mesh is not None:
        from ..sharding.specs import dmm_table_sharding

        src3d = jax.device_put(src3d_np, dmm_table_sharding(mesh, axis))
    else:
        src3d = jnp.asarray(src3d_np)
    return ShardedFusedDMM(
        state=state,
        n_shards=n_shards,
        blocks_per_shard=per,
        n_in_pad=n_in_pad,
        width=width,
        n_blocks=n_blocks,
        src3d=src3d,
        mesh=mesh,
        routes=routes,
        n_out=n_out,
        columns=columns,
        uid_slot=uid_slot,
        uid_col=uid_col,
        col_block_start=cb_start,
        col_block_count=cb_count,
        uid_slot_dev=jnp.asarray(uid_slot),
        uid_col_dev=jnp.asarray(uid_col),
    )


# ---------------------------------------------------------------------------
# Incremental recompaction: rebuild only the touched columns (PlanManager)
# ---------------------------------------------------------------------------


def recompile_columns(
    compiled: CompiledDMM,
    dpm: DPM,
    registry: Registry,
    touched,
    *,
    lane: int = LANE,
) -> CompiledDMM:
    """Incrementally re-lower a DPM after a localised change.

    ``touched`` is the set of incoming ``(schema o, version v)`` columns
    whose mapping paths (or attribute lists) changed since ``compiled`` was
    built -- typically the DPM diff a :class:`repro.etl.plan.PlanManager`
    computes across a ``SchemaEvolved`` / ``MatrixEdit``.  Blocks of
    untouched columns are REUSED by block key (safe because registry
    versions are immutable once cut: ``evolve`` re-issues kept attributes
    with fresh uids in a NEW version, it never rewrites an existing one);
    only touched columns pay the per-block :func:`compile_block` python
    loop.  The caller must include in ``touched`` every column whose
    elements changed -- an under-report reuses a stale block.

    Bit-exact with a from-scratch :func:`compile_dpm` of the same DPM.
    """
    touched = frozenset(touched)
    old_by_key = {
        blk.key: blk
        for blocks in compiled.by_column.values()
        for blk in blocks
    }
    by_column: Dict[Tuple[int, int], List[CompactedBlockMap]] = {}
    for key, elements in sorted(dpm.items()):
        o, v, r, w = key
        blk = old_by_key.get(key) if (o, v) not in touched else None
        if blk is None:
            blk = compile_block(key, elements, registry, lane)
        by_column.setdefault((o, v), []).append(blk)
    return CompiledDMM(state=registry.state, by_column=by_column)


def _vectorised_uid_tables(columns) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorised twin of :func:`_uid_tables_from` over
    :class:`FusedColumn` rows carrying ``uids_arr``: two scatters instead of
    a per-uid dict walk.  Bit-identical because registry uids are globally
    unique (one counter; kept attributes are re-issued with NEW uids), so no
    uid is claimed by two columns and scatter order cannot matter."""
    cols = [c for c in columns if c.uids_arr is not None and c.uids_arr.size]
    if not cols:
        empty = np.full(bucket_rows(0), -1, dtype=np.int32)
        return empty, empty.copy()
    all_uids = np.concatenate([c.uids_arr for c in cols])
    sizes = np.asarray([c.uids_arr.size for c in cols], dtype=np.int64)
    col_ids = np.asarray([c.col_id for c in cols], dtype=np.int32)
    uid_slot = np.full(bucket_rows(int(all_uids.max()) + 1), -1, dtype=np.int32)
    uid_col = np.full(uid_slot.size, -1, dtype=np.int32)
    starts = np.repeat(np.cumsum(sizes) - sizes, sizes)
    uid_slot[all_uids] = (
        np.arange(all_uids.size, dtype=np.int64) - starts
    ).astype(np.int32)
    uid_col[all_uids] = np.repeat(col_ids, sizes)
    return uid_slot, uid_col


def _host_table(plan) -> np.ndarray:
    """The plan's block table as one host (n_rows >= n_blocks, W) array in
    GLOBAL block order -- the splice's bulk-copy source.  For a sharded plan
    the per-shard slices are re-flattened (row ``t`` lives on shard
    ``t // per`` at local row ``t - s*per``); this is a control-plane
    (rebuild-time) readback, never on the per-chunk path."""
    if isinstance(plan, ShardedFusedDMM):
        flat = np.asarray(plan.src3d).reshape(-1, plan.width)
        per, per_pad = plan.blocks_per_shard, plan.n_blocks_pad_loc
        t = np.arange(plan.n_blocks, dtype=np.int64)
        s = t // per
        return flat[s * per_pad + (t - s * per)]
    return np.asarray(plan.src2d)


def _spliced_tables(old, compiled: CompiledDMM, registry: Registry, touched, lane: int) -> Tuple:
    """Build a ``_fused_tables``-layout bundle for ``compiled`` by splicing:
    untouched columns reuse the old plan's table rows (one fancy-index bulk
    copy) and ``FusedColumn`` metadata; only touched/new columns re-run the
    per-block row fill and the per-uid dict build."""
    width = lane
    for blocks in compiled.by_column.values():
        for blk in blocks:
            width = max(width, blk.n_out_pad)
    old_np = _host_table(old)
    routes: List[Tuple[int, int]] = []
    n_out: List[int] = []
    columns: Dict[Tuple[int, int], FusedColumn] = {}
    n_in_max = 1
    reuse_new: List[int] = []  # new global row of each reused column's start
    reuse_old: List[np.ndarray] = []  # the old block_ids being copied
    fresh: List[Tuple[int, np.ndarray]] = []  # rebuilt (row, src) pairs
    for (o, v), blocks in compiled.by_column.items():
        old_col = None if (o, v) in touched else old.columns.get((o, v))
        if old_col is not None and old_col.block_ids.size != len(blocks):
            old_col = None  # block layout changed: rebuild this column
        start = len(routes)
        for blk in blocks:
            t = len(routes)
            routes.append((blk.key[2], blk.key[3]))
            n_out.append(blk.n_out)
            if old_col is None:
                row = np.full((width,), -1, dtype=np.int32)
                row[: blk.n_out_pad] = np.asarray(blk.src)
                fresh.append((t, row))
        if old_col is not None:
            uid_pos, n_in = old_col.uid_pos, old_col.n_in
            uids_arr = old_col.uids_arr
            if uids_arr is None:  # plan predates uids_arr: derive once
                uids_arr = np.fromiter(
                    uid_pos, dtype=np.int64, count=len(uid_pos)
                )
            reuse_new.append(start)
            reuse_old.append(old_col.block_ids)
        else:
            sv = registry.domain.get(o, v)
            uid_pos = {u: k for k, u in enumerate(sv.uids)}
            uids_arr = np.asarray(sv.uids, dtype=np.int64)
            n_in = len(sv.uids)
        n_in_max = max(n_in_max, n_in)
        columns[(o, v)] = FusedColumn(
            o=o,
            v=v,
            n_in=n_in,
            uid_pos=uid_pos,
            block_ids=np.arange(start, len(routes), dtype=np.int32),
            col_id=len(columns),
            uids_arr=uids_arr,
        )
    n_blocks = len(routes)
    table = np.full((bucket_rows(n_blocks), width), -1, dtype=np.int32)
    if reuse_new:
        new_ids = np.concatenate([
            np.arange(s, s + ids.size, dtype=np.int64)
            for s, ids in zip(reuse_new, reuse_old)
        ])
        old_ids = np.concatenate([ids.astype(np.int64) for ids in reuse_old])
        # width can shrink when the widest column was rebuilt narrower: the
        # truncated tail of every reused row is -1 pad by construction
        # (width still covers each reused block's n_out_pad)
        w = min(old_np.shape[1], width)
        table[new_ids, :w] = old_np[old_ids, :w]
    for t, row in fresh:
        table[t] = row
    uid_slot, uid_col = _vectorised_uid_tables(columns.values())
    col_block_start = np.asarray(
        [int(c.block_ids[0]) if c.block_ids.size else 0 for c in columns.values()],
        dtype=np.int32,
    )
    col_block_count = np.asarray(
        [c.block_ids.size for c in columns.values()], dtype=np.int32
    )
    return (
        table,
        routes,
        np.asarray(n_out, dtype=np.int32),
        columns,
        pad_to_lane(n_in_max, lane),
        width,
        n_blocks,
        uid_slot,
        uid_col,
        col_block_start,
        col_block_count,
    )


def splice_fused(
    plan,
    compiled: CompiledDMM,
    registry: Registry,
    touched,
    *,
    lane: int = LANE,
):
    """Incrementally rebuild a fused plan: splice ``compiled``'s touched
    columns into ``plan``'s block table instead of re-flattening every
    column (the expensive per-uid / per-block python of
    :func:`_fused_tables`).

    ``plan`` is the previous epoch's :class:`FusedDMM` or
    :class:`ShardedFusedDMM` (the result keeps the same flavour, mesh and
    shard count); ``touched`` is the changed-column set (see
    :func:`recompile_columns`).  Columns absent from ``compiled`` (deleted
    versions, or columns a residency policy keeps compacted-out) simply
    drop out of the new table; columns absent from the OLD plan are rebuilt
    from scratch.  The whole old table is bulk-copied with one fancy-index
    gather, so splice cost scales with the touched columns plus O(columns),
    not with total attributes.

    Bit-exact with a from-scratch :func:`compile_fused` /
    :func:`compile_fused_sharded` of the same ``compiled`` -- the full
    rebuild stays the oracle (asserted in tests and the compaction soak).
    """
    touched = frozenset(touched)
    parts = _spliced_tables(plan, compiled, registry, touched, lane)
    if isinstance(plan, ShardedFusedDMM):
        return _assemble_sharded(
            parts, compiled.state, mesh=plan.mesh, n_shards=plan.n_shards
        )
    return _assemble_replicated(parts, compiled.state)
