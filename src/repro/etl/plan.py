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
    new chunks densify against the new lease.  A manager bound to a
    coordinator with ``publish=True`` records each cutover as a
    :class:`~repro.etl.control.PlanPublished` control event -- replayable,
    no state bump, legal inside a Freeze window.

Every lease holds the whole plan on the device: ``bytes_resident``
(surfaced through ``engine.info()`` and ``Cluster.info()``) is the block
table's bytes.

The epoch counter is the manager's monotone build count, NOT the registry
state ``i``: after :meth:`PlanManager.invalidate` the same state is served
by a new epoch.

Thread-safety: a build runs synchronously on the thread that calls
:meth:`PlanManager.acquire` -- the thread that consumes chunks; the
pipeline does any lazy refresh there before it hands a chunk to its
optional densify worker (``Pipeline(densify_thread=True)``), which only
reads the engine's current lease.  One manager lock serialises
``acquire``, ``invalidate`` and ``info``, so a manager shared by several
engines, or read through ``info()`` from another thread, never sees a
half-installed lease.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, FrozenSet, Optional, Set, Tuple

from ..core.dmm import DPM
from ..core.dmm_jax import (
    CompiledDMM,
    ShardedFusedDMM,
    compile_dpm,
    compile_fused,
    compile_fused_sharded,
    recompile_columns,
    splice_fused,
)
from ..core.registry import Registry
from ..core.state import StateCoordinator, SystemState
from .control import PlanPublished

__all__ = ["PlanEpoch", "PlanManager"]


@dataclasses.dataclass
class PlanEpoch:
    """One published plan epoch -- the immutable lease an engine serves.

    ``plan`` is the device plan for the engine kind (:class:`FusedDMM` or
    :class:`ShardedFusedDMM`); ``compiled`` is the per-block lowering of
    the state's DPM it was flattened from.  In-flight chunks pin ``plan``
    (their ``.epoch`` property reads its ``state``), so an epoch keeps
    serving its drains after the manager moves on.
    """

    epoch: int
    state: int
    compiled: CompiledDMM
    plan: Any
    bytes_resident: int
    incremental: bool
    touched_columns: int
    rebuild_s: float


def _table_shapes(plan: Any) -> Tuple:
    """``(uid table shape, block table shape)`` of a fused or sharded plan:
    the shapes the chunk programs are compiled for."""
    if isinstance(plan, ShardedFusedDMM):
        return plan.uid_slot.shape, plan.src3d.shape
    return plan.uid_slot.shape, plan.src2d.shape


def _bytes_resident(plan: Any) -> int:
    """Device-resident block-table bytes of one plan."""
    if isinstance(plan, ShardedFusedDMM):
        return int(plan.src3d.nbytes)
    return int(plan.src2d.nbytes)


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
        publish: bool = False,
    ) -> None:
        if kind not in ("fused", "sharded"):
            raise ValueError(
                f"unknown plan kind {kind!r} (kinds: 'fused', 'sharded')"
            )
        if kind == "sharded" and mesh is None and not n_shards:
            raise ValueError("kind='sharded' needs a mesh or n_shards")
        self.kind = kind
        self.mesh = mesh
        self.n_shards = n_shards
        self.coordinator = coordinator
        self.incremental = incremental
        self.publish = publish and coordinator is not None
        self._lock = threading.Lock()
        self._lease: Optional[PlanEpoch] = None
        self._dpm: Optional[DPM] = None  # the DPM the lease was built from
        self._epoch = 0
        self.rebuilds = 0
        self.incremental_rebuilds = 0
        self.last_rebuild_s = 0.0
        self.total_rebuild_s = 0.0
        # device-table shapes of the last installed lease, and how often an
        # install changed them (each change recompiles every chunk shape)
        self._table_shapes: Optional[Tuple] = None
        self.table_shape_changes = 0

    # -- plan acquisition (the engines' single entry point) -----------------
    def acquire(self, snapshot: SystemState, registry: Registry) -> PlanEpoch:
        """The lease for ``snapshot``'s state: cached when current, built
        (incrementally when possible) otherwise."""
        with self._lock:
            if self._lease is not None and self._lease.state == snapshot.i:
                return self._lease
            self._install(self._build(snapshot, registry), snapshot.dpm)
            return self._lease

    def invalidate(self) -> None:
        """Drop the cached lease (the next acquire rebuilds)."""
        with self._lock:
            self._lease = None
            self._dpm = None

    # -- observability -------------------------------------------------------
    def info(self) -> Dict[str, Any]:
        """Manager-side keys merged into ``engine.info()``: ``plan_epoch``,
        ``rebuilds``, ``table_shape_changes``, ``bytes_resident``, plus
        rebuild-cost detail.

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
            return d

    # -- build internals -----------------------------------------------------
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
        full otherwise.  Pure function of (snapshot, registry, previous
        lease) apart from timing; lock held."""
        t0 = time.perf_counter()
        old = self._lease
        old_dpm = self._dpm
        touched: Optional[FrozenSet[Tuple[int, int]]] = None
        if self.incremental and old is not None and old_dpm is not None:
            touched = frozenset(self._touched(old_dpm, snapshot.dpm))
        plan: Any
        if touched is not None:
            compiled = recompile_columns(
                old.compiled, snapshot.dpm, registry, touched
            )
            plan = splice_fused(old.plan, compiled, registry, touched)
        else:
            compiled = compile_dpm(snapshot.dpm, registry)
            if self.kind == "sharded":
                plan = compile_fused_sharded(
                    compiled, registry, mesh=self.mesh, n_shards=self.n_shards
                )
            else:
                plan = compile_fused(compiled, registry)
        return PlanEpoch(
            epoch=0,  # assigned at install time (monotone under the lock)
            state=snapshot.i,
            compiled=compiled,
            plan=plan,
            bytes_resident=_bytes_resident(plan),
            incremental=touched is not None,
            touched_columns=len(touched) if touched is not None else len(
                compiled.by_column
            ),
            rebuild_s=time.perf_counter() - t0,
        )
