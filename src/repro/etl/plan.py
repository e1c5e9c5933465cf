"""Epoched plan lifecycle: ONE owner for every device-plan build.

Before this layer each :class:`~repro.etl.engines.MappingEngine` recompiled
its own plan ad hoc inside ``compile()``: every mid-stream ``SchemaEvolved``
paid a full ``compile_dpm`` -> ``compile_fused`` rebuild on the hot path,
synchronously, once per engine instance.  The :class:`PlanManager` turns
plan build/publish into an explicit, epoch-versioned protocol -- the
"highly efficient compacting" claim made *online*:

  * **single construction site** -- only the manager (and the compile
    functions it delegates to in :mod:`repro.core.dmm_jax`) may construct a
    fused plan.  Engines ask the manager (:meth:`PlanManager.acquire`) and
    consume the returned :class:`PlanEpoch` lease; the
    ``plan-publish-single-site`` analyzer rule enforces the boundary.
  * **incremental recompaction** -- across a ``SchemaEvolved`` /
    ``MatrixEdit`` the manager diffs the DPM, re-lowers ONLY the touched
    ``(schema, version)`` columns (:func:`repro.core.dmm_jax.
    recompile_columns`) and splices them into the previous epoch's fused
    table (:func:`repro.core.dmm_jax.splice_fused`).  The full rebuild
    stays available -- and stays the bit-exactness oracle -- via
    ``incremental=False``.
  * **epoch cutover without a stall** -- a build produces epoch N+1 while
    epoch N keeps serving: in-flight :class:`~repro.etl.engines.DenseChunk`
    s carry their plan pin (the PR-5 mechanism) and drain on the OLD table;
    new chunks densify against the new lease.  With ``background=True`` the
    next epoch is prepared on a worker thread as soon as the coordinator's
    eviction fan-out announces the state change, so the consuming thread
    usually finds the table already built.  A manager bound to a
    coordinator with ``publish=True`` records each cutover as a
    :class:`~repro.etl.control.PlanPublished` control event -- replayable,
    no state bump, legal inside a Freeze window.
  * **hot/cold residency tiering** -- per-``(o, v)`` hit counters (fed by
    ``METLApp.triage`` through :meth:`record_hits`) drive a
    :class:`TieringPolicy`: rarely-hit version columns stay compacted-out
    of the device table as host-side :class:`ColdColumn` leases, and a miss
    falls back to the per-block :func:`repro.core.dmm_jax.apply_compacted`
    path.  ``bytes_resident`` (surfaced through ``engine.info()`` and
    ``Cluster.info()``) prices exactly what the device holds.

The epoch counter is the manager's monotone build count, NOT the registry
state ``i``: one state can be served by several epochs (e.g. a residency
repartition), and a background build for a state that is superseded before
it lands is simply discarded.

Thread-safety: ``acquire`` and the background worker synchronise on one
manager lock; registry reads during a background build race a concurrent
schema mutation only in the window between bump and eviction, so a build
whose state no longer matches the coordinator's is thrown away and rebuilt
synchronously -- the worker is an optimisation, never a correctness
dependency (and any background build error falls back to the synchronous
path).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Dict, FrozenSet, List, Optional, Set, Tuple

import numpy as np

from ..core.dmm import DPM
from ..core.dmm_jax import (
    CompactedBlockMap,
    CompiledDMM,
    FusedDMM,
    ShardedFusedDMM,
    compile_dpm,
    compile_fused,
    compile_fused_sharded,
    recompile_columns,
    splice_fused,
    uid_lookup_table,
)
from ..core.registry import Registry
from ..core.state import StateCoordinator, SystemState
from .control import PlanPublished

__all__ = ["TieringPolicy", "ColdColumn", "PlanEpoch", "PlanManager"]


@dataclasses.dataclass
class TieringPolicy:
    """Residency policy: which incoming columns deserve device-table rows.

    A column is COLD (kept out of the fused table, served host-side on a
    miss) when its cumulative triage hits are below ``min_hits`` -- except
    that with ``pin_latest=True`` (the default) the latest live version of
    every schema stays resident regardless, so the first chunk after an
    evolution never takes the miss path.  Residency is re-evaluated at
    build time (state change or an explicit :meth:`PlanManager.
    repartition`), never mid-epoch: a serving table is immutable.
    """

    min_hits: int = 1
    pin_latest: bool = True

    def cold_columns(
        self,
        compiled: CompiledDMM,
        registry: Registry,
        hits: Dict[Tuple[int, int], int],
    ) -> Set[Tuple[int, int]]:
        cold: Set[Tuple[int, int]] = set()
        for o, v in compiled.by_column:
            if hits.get((o, v), 0) >= self.min_hits:
                continue
            if (
                self.pin_latest
                and registry.domain.has(o, v)
                and v == registry.domain.latest_version(o)
            ):
                continue
            cold.add((o, v))
        return cold


@dataclasses.dataclass
class ColdColumn:
    """One compacted-out column: enough host-side state to serve a tier
    miss (per-column scatter + per-block ``apply_compacted``) without the
    fused table knowing the column exists."""

    o: int
    v: int
    n_in: int
    lut: np.ndarray  # uid -> payload slot (dense, -1 = foreign)
    blocks: List[CompactedBlockMap]


@dataclasses.dataclass
class PlanEpoch:
    """One published plan epoch -- the immutable lease an engine serves.

    ``plan`` is the device plan for the engine kind (:class:`FusedDMM`,
    :class:`ShardedFusedDMM`, or the :class:`CompiledDMM` itself for the
    per-block engine) covering the RESIDENT columns; ``compiled`` is the
    full per-block lowering of the state's DPM (every column, hot or cold);
    ``cold`` holds the compacted-out columns.  In-flight chunks pin
    ``plan`` (their ``.epoch`` property reads its ``state``), so an epoch
    keeps serving its drains after the manager moves on.
    """

    epoch: int
    state: int
    compiled: CompiledDMM
    plan: Any
    cold: Dict[Tuple[int, int], ColdColumn]
    bytes_resident: int
    incremental: bool
    touched_columns: int
    rebuild_s: float


def _resident_compiled(
    compiled: CompiledDMM, cold: Set[Tuple[int, int]]
) -> CompiledDMM:
    """The hot-column view the fused table is built from."""
    if not cold:
        return compiled
    return CompiledDMM(
        state=compiled.state,
        by_column={
            ov: blocks
            for ov, blocks in compiled.by_column.items()
            if ov not in cold
        },
    )


def _table_shapes(plan: Any) -> Optional[Tuple]:
    """``(uid table shape, block table shape)`` of a fused or sharded plan:
    the shapes the chunk programs are compiled for.  None for the per-block
    engine's plan, which has no such tables."""
    if isinstance(plan, FusedDMM):
        return plan.uid_slot.shape, plan.src2d.shape
    if isinstance(plan, ShardedFusedDMM):
        return plan.uid_slot.shape, plan.src3d.shape
    return None


def _bytes_resident(kind: str, plan: Any) -> int:
    """Device-resident block-table bytes of one plan."""
    if kind == "sharded":
        return int(plan.src3d.nbytes)
    if kind == "fused":
        return int(plan.src2d.nbytes)
    # per-block engine: every compacted block lives on device (all-hot)
    return int(
        sum(b.src.nbytes for col in plan.by_column.values() for b in col)
    )


class PlanManager:
    """Epoch-versioned owner of the plan build/publish lifecycle (see the
    module docstring).  One manager serves one engine kind; engines without
    an explicitly bound manager get a private default from
    ``MappingEngine.compile``, and :class:`~repro.etl.metl.METLApp` wires an
    app-provided manager to its coordinator.
    """

    def __init__(
        self,
        *,
        kind: str = "fused",
        mesh: Any = None,
        n_shards: Optional[int] = None,
        coordinator: Optional[StateCoordinator] = None,
        incremental: bool = True,
        background: bool = False,
        publish: bool = False,
        tiering: Optional[TieringPolicy] = None,
    ) -> None:
        if kind not in ("fused", "sharded", "blocks"):
            raise ValueError(f"unknown plan kind {kind!r}")
        if kind == "sharded" and mesh is None and not n_shards:
            raise ValueError("kind='sharded' needs a mesh or n_shards")
        self.kind = kind
        self.mesh = mesh
        self.n_shards = n_shards
        self.coordinator = coordinator
        self.incremental = incremental
        self.publish = publish and coordinator is not None
        self.tiering = tiering
        self._lock = threading.Lock()
        self._lease: Optional[PlanEpoch] = None
        self._dpm: Optional[DPM] = None  # the DPM the lease was built from
        self._hits: Dict[Tuple[int, int], int] = {}
        self._epoch = 0
        self.rebuilds = 0
        self.incremental_rebuilds = 0
        self.last_rebuild_s = 0.0
        self.total_rebuild_s = 0.0
        # device-table shapes of the last installed lease, and how often an
        # install changed them (each change recompiles every chunk shape)
        self._table_shapes: Optional[Tuple] = None
        self.table_shape_changes = 0
        self._pool: Optional[ThreadPoolExecutor] = None
        self._prepared: Optional[Future] = None
        if background:
            if coordinator is None:
                raise ValueError("background=True needs a coordinator")
            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="plan-recompactor"
            )
            # the eviction fan-out IS the epoch-change announcement: start
            # preparing epoch N+1 the moment the state bump lands, while
            # epoch N keeps serving (weak: the coordinator must not keep a
            # dropped manager's recompactor alive)
            coordinator.on_evict(self._on_coordinator_evict, weak=True)

    # -- plan acquisition (the engines' single entry point) -----------------
    def acquire(self, snapshot: SystemState, registry: Registry) -> PlanEpoch:
        """The lease for ``snapshot``'s state: cached when current, adopted
        from the background recompactor when it prepared this state, built
        (incrementally when possible) otherwise."""
        with self._lock:
            if self._lease is not None and self._lease.state == snapshot.i:
                return self._lease
            lease = self._take_prepared(snapshot.i)
            if lease is None:
                lease = self._build(snapshot, registry)
            self._install(lease, snapshot.dpm)
            return self._lease

    def repartition(
        self, snapshot: SystemState, registry: Registry
    ) -> PlanEpoch:
        """Force a same-state rebuild so the residency policy sees the hit
        counters accumulated since the serving epoch was cut (a new epoch
        for the SAME state ``i``)."""
        with self._lock:
            lease = self._build(snapshot, registry)
            self._install(lease, snapshot.dpm)
            return self._lease

    def invalidate(self) -> None:
        """Drop the cached lease (the next acquire rebuilds)."""
        with self._lock:
            self._lease = None
            self._dpm = None

    def close(self) -> None:
        """Stop the background recompactor (no-op without one)."""
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    # -- tier hit accounting -------------------------------------------------
    def record_hits(self, by_column) -> None:
        """Fold one triaged chunk's per-``(o, v)`` event counts into the
        residency counters.  Accepts the triage ``by_column`` mapping
        (values sized) or any ``(key, count)`` iterable."""
        items = (
            by_column.items() if hasattr(by_column, "items") else by_column
        )
        with self._lock:
            for ov, val in items:
                n = int(val.size if hasattr(val, "size") else val)
                if n:
                    self._hits[ov] = self._hits.get(ov, 0) + n

    # -- observability -------------------------------------------------------
    def info(self) -> Dict[str, Any]:
        """Manager-side keys merged into ``engine.info()``: ``plan_epoch``,
        ``rebuilds``, ``table_shape_changes``, ``bytes_resident``, plus
        rebuild-cost and tiering detail.

        ``table_shape_changes`` counts installed leases whose device tables
        (uid tables, block table) changed shape from the previous lease's:
        each one recompiles every chunk shape."""
        with self._lock:
            lease = self._lease
            d: Dict[str, Any] = {
                "plan_epoch": lease.epoch if lease is not None else 0,
                "rebuilds": self.rebuilds,
                "incremental_rebuilds": self.incremental_rebuilds,
                "last_rebuild_s": self.last_rebuild_s,
                "total_rebuild_s": self.total_rebuild_s,
                "table_shape_changes": self.table_shape_changes,
            }
            if lease is not None:
                d["bytes_resident"] = lease.bytes_resident
                d["cold_columns"] = len(lease.cold)
            return d

    # -- build internals -----------------------------------------------------
    def _on_coordinator_evict(self, i: int) -> None:
        # called on the control thread, outside the coordinator lock, after
        # the state bump: kick off epoch N+1's build while N keeps serving
        if self._pool is None or self.coordinator is None:
            return
        snap = self.coordinator.snapshot()
        registry = self.coordinator.registry
        with self._lock:
            self._prepared = self._pool.submit(self._build, snap, registry)

    def _take_prepared(self, state: int) -> Optional[PlanEpoch]:
        # lock held.  Adopt the background build iff it is for THIS state;
        # a stale or failed build is discarded (sync rebuild covers it).
        fut, self._prepared = self._prepared, None
        if fut is None:
            return None
        try:
            lease = fut.result()
        except Exception:
            return None
        return lease if lease.state == state else None

    def _install(self, lease: PlanEpoch, dpm: DPM) -> None:
        # lock held
        self._epoch += 1
        lease = dataclasses.replace(lease, epoch=self._epoch)
        self._lease = lease
        self._dpm = dict(dpm)
        self.rebuilds += 1
        if lease.incremental:
            self.incremental_rebuilds += 1
        self.last_rebuild_s = lease.rebuild_s
        self.total_rebuild_s += lease.rebuild_s
        shapes = _table_shapes(lease.plan)
        if self._table_shapes is not None and shapes != self._table_shapes:
            self.table_shape_changes += 1
        self._table_shapes = shapes
        if self.publish and self.coordinator.is_control_writer:
            # the coordinator's single-writer apply logs the publication;
            # "plan" events bump nothing, so no eviction re-entrancy.  On a
            # follower replica the gate holds the record back: epochs stay
            # local, the replicated log carries only the LEADER's writes --
            # a follower-injected record would diverge the replica log
            # (promotion flips the role and publishing resumes)
            self.coordinator.apply(
                PlanPublished(
                    epoch=lease.epoch,
                    state=lease.state,
                    kind=self.kind,
                    incremental=lease.incremental,
                    touched_columns=lease.touched_columns,
                    n_blocks=lease.compiled.n_blocks,
                    bytes_resident=lease.bytes_resident,
                    rebuild_s=lease.rebuild_s,
                )
            )

    def _touched(self, old_dpm: DPM, new_dpm: DPM) -> Set[Tuple[int, int]]:
        """Incoming columns whose mapping paths changed between two DPMs.
        Snapshot dicts share element containers with the authoritative DPM,
        so unchanged entries hit the identity fast path."""
        touched: Set[Tuple[int, int]] = set()
        for key in old_dpm.keys() ^ new_dpm.keys():
            touched.add((key[0], key[1]))
        for key in old_dpm.keys() & new_dpm.keys():
            a, b = old_dpm[key], new_dpm[key]
            if a is not b and a != b:
                touched.add((key[0], key[1]))
        return touched

    def _build(self, snapshot: SystemState, registry: Registry) -> PlanEpoch:
        """One epoch build: incremental when a previous lease allows it,
        full otherwise.  Pure function of (snapshot, registry, hit
        counters) apart from timing -- callable from the worker thread."""
        t0 = time.perf_counter()
        old = self._lease
        old_dpm = self._dpm
        touched: Optional[FrozenSet[Tuple[int, int]]] = None
        if self.incremental and old is not None and old_dpm is not None:
            touched = frozenset(self._touched(old_dpm, snapshot.dpm))
        if touched is not None:
            compiled = recompile_columns(
                old.compiled, snapshot.dpm, registry, touched
            )
        else:
            compiled = compile_dpm(snapshot.dpm, registry)

        cold_set: Set[Tuple[int, int]] = set()
        if self.tiering is not None and self.kind != "blocks":
            hits = dict(self._hits)
            cold_set = self.tiering.cold_columns(compiled, registry, hits)
        resident = _resident_compiled(compiled, cold_set)

        if self.kind == "blocks":
            plan: Any = compiled
        elif (
            touched is not None
            and old.plan is not None
            and isinstance(old.plan, (FusedDMM, ShardedFusedDMM))
        ):
            plan = splice_fused(old.plan, resident, registry, touched)
        elif self.kind == "sharded":
            plan = compile_fused_sharded(
                resident, registry, mesh=self.mesh, n_shards=self.n_shards
            )
        else:
            plan = compile_fused(resident, registry)

        cold = {
            ov: ColdColumn(
                o=ov[0],
                v=ov[1],
                n_in=len(registry.domain.get(*ov).uids),
                lut=uid_lookup_table(registry.domain.get(*ov).uids),
                blocks=compiled.by_column[ov],
            )
            for ov in sorted(cold_set)
        }
        return PlanEpoch(
            epoch=0,  # assigned at install time (monotone under the lock)
            state=snapshot.i,
            compiled=compiled,
            plan=plan,
            cold=cold,
            bytes_resident=_bytes_resident(self.kind, plan),
            incremental=touched is not None,
            touched_columns=len(touched) if touched is not None else len(
                compiled.by_column
            ),
            rebuild_s=time.perf_counter() - t0,
        )
