"""The METL app: consume CDC events, map them to the CDM, emit canonical rows.

This is the paper's microservice re-housed as a library component, split in
two since the engine/pipeline redesign:

  * **METLApp (this module)** is the *stream-side* facade.  It owns every
    per-event responsibility -- state sync (paper SS3.4: stale events raise
    in strict mode, or park/dead-letter on the semi-automated error path),
    at-least-once dedup over a sliding key window, parked-event replay after
    a refresh, and dead-letter offset reset -- and exposes them as
    :meth:`METLApp.triage`, which buckets the surviving events into
    ``(schema, version) -> [event]`` groups.

  * **The mapping itself lives behind the MappingEngine protocol**
    (:mod:`repro.etl.engines`): ``compile / densify / dispatch / emit``
    plus ``info()``.  ``METLApp(engine="fused"|"sharded"|"blocks")`` resolves
    a registered engine through :func:`repro.etl.engines.make_engine`
    (strings keep working; engine *instances* plug in custom
    implementations), and :meth:`METLApp.consume` is now just
    ``triage -> engine.consume_groups`` -- densify, one dispatch, emit.

The explicit stage split is what the streaming Pipeline
(:mod:`repro.etl.pipeline`) builds on: ``Source -> METLApp -> [Sink, ...]``
with chunked pull, sink fan-out (DW + ML platform, paper SS5.5) and
double-buffered async consume that overlaps chunk N+1's host-side
densification with chunk N's device dispatch.

State lifecycle: a coordinator state bump -- typically a typed control
event applied through :meth:`repro.core.state.StateCoordinator.apply`
(:mod:`repro.etl.control`), in-band or out-of-band -- evicts the engine
plan (the Caffeine analogue); the next consume re-snapshots and recompiles.  Parked
events (from the app's future) replay through :meth:`refresh`; replays are
counted only under ``stats["replayed"]``, never a second time under
``stats["events"]``.  Dead-lettered events (from the past) are cleared by
:meth:`reset_offset`, which returns the stream position to rewind to and
forgets their dedup keys so the re-delivered events map.

Per-chunk operands are bucketed to powers of two
(:func:`repro.core.dmm_jax.bucket_rows`) before dispatch, so the jit cache is
effectively keyed on (state, bucketed batch shape) and steady-state consume
traffic never retraces.  ``stats["dispatches"]`` counts device dispatches;
``engine.info()`` is the supported observability surface (table bytes,
shards, dispatch count) -- external code must not reach into private
attributes (CI grep-gates ``app._`` outside this package).
"""

from __future__ import annotations

import collections
from typing import Any, Dict, Iterable, List, Optional, Union

import numpy as np

from ..core.dmm import Message, map_message_dense
from ..core.dmm_jax import CompiledDMM, FusedDMM, ShardedFusedDMM
from ..core.registry import StaleStateError
from ..core.state import StateCoordinator, SystemState
from . import tracing
from .engines import CanonicalRow, Groups, MappingEngine, TriagedChunk, make_engine
from .events import CDCEvent, ColumnarChunk, columnarize

__all__ = ["METLApp", "CanonicalRow"]


class METLApp:
    """One horizontally-scaled METL instance (triage facade + engine)."""

    def __init__(
        self,
        coordinator: StateCoordinator,
        *,
        strict_state: bool = False,
        dedup_window: int = 4096,
        impl: str = "auto",
        engine: Union[str, MappingEngine] = "fused",
        mesh: Any = None,
        device_densify: bool = False,
        plan_manager: Any = None,
    ) -> None:
        self.coordinator = coordinator
        self.strict_state = strict_state
        self.impl = impl
        self.mesh = mesh
        self.stats = collections.Counter()
        # engine resolution: strings go through the registry factory (which
        # also applies the legacy impl="onehot" -> blocks and 1-shard
        # sharded -> fused routing); instances are adopted as-is and share
        # the app's stats counter.  plan_manager binds an explicit
        # repro.etl.plan.PlanManager (incremental recompaction is on by
        # default either way; an explicit manager adds residency tiering,
        # background recompaction and PlanPublished control events)
        self.engine = make_engine(
            engine, impl=impl, mesh=mesh, device_densify=device_densify,
            stats=self.stats, manager=plan_manager,
        )
        # observability binding only: engine.info() reads the replication
        # surface (role/term/log_offset/lag_records) off this coordinator
        # when its plan manager carries none of its own
        self.engine.coordinator = coordinator
        self._seen: collections.OrderedDict = collections.OrderedDict()
        self._dedup_window = dedup_window
        self._snapshot: Optional[SystemState] = None
        # error management (paper §3.4): events from the future (app behind)
        # are parked and replayed after a refresh; events from the past are
        # dead-lettered with enough info to reset the Kafka offset
        self._parked: List[CDCEvent] = []
        self.dead_letter: List[CDCEvent] = []
        # rows produced by a replay inside a *lazy* refresh (triggered from
        # triage/state rather than called by the user); delivered by the
        # next consume() / take_replayed() so they are never lost
        self._replay_rows: List[CanonicalRow] = []
        # consume_scalar's per-(o, v) view of the snapshot's DPM
        self._scalar_cols: Optional[tuple] = None
        # weak registration: the coordinator must not keep this app alive
        # (or keep evicting its corpse) after the owner drops it -- the
        # bench/test pattern constructs many apps against one coordinator
        coordinator.on_evict(self._on_coordinator_evict, weak=True)
        self.refresh()

    # -- state management -----------------------------------------------------
    def refresh(self) -> "List[CanonicalRow]":
        """Re-snapshot the coordinator state and replay parked events.

        Returns canonical rows produced by the replay (empty when nothing
        was parked).  Replayed events are counted under ``stats["replayed"]``
        only -- they were already counted under ``stats["events"]`` when they
        first arrived."""
        self._snapshot = self.coordinator.snapshot()
        self.engine.compile(self._snapshot, self.coordinator.registry)
        self.stats["refreshes"] += 1
        rows: List[CanonicalRow] = []
        if self._parked:
            replay, self._parked = self._parked, []
            # allow re-consumption: parked events were dedup-registered
            for ev in replay:
                self._seen.pop(ev.key, None)
            rows = self.engine.consume_groups(self.triage(replay, replay=True))
            self.stats["replayed"] += len(replay)
        return rows

    def reset_offset(self) -> Optional[int]:
        """Smallest dead-lettered stream position -- where to rewind the
        Kafka offset for a re-pull ('options to set back Kafka-offsets and
        start new initial loads', paper §3.4).  Clears the dead letter."""
        if not self.dead_letter:
            return None
        pos = min(ev.ts for ev in self.dead_letter)
        for ev in self.dead_letter:  # will be re-delivered; forget dedup keys
            self._seen.pop(ev.key, None)
        self.dead_letter.clear()
        return pos

    def _on_coordinator_evict(self, i: int) -> None:
        self.evict()

    def evict(self) -> None:
        """Cache eviction on state change (the Caffeine analogue)."""
        self.engine.evict()
        self._snapshot = None
        self.stats["evictions"] += 1

    def reset_dedup(self) -> None:
        """Forget every dedup key.  For harnesses that re-consume the same
        chunk (benchmarks time repeated consume of one slice; without this
        every iteration after the first measures the dedup-drop path)."""
        self._seen.clear()

    def ensure_ready(self) -> None:
        """Lazy refresh (after eviction / before first use).  Rows replayed
        by the refresh are buffered, not dropped: the next consume() (or an
        explicit take_replayed()) delivers them."""
        if self._snapshot is None or not self.engine.ready:
            self._replay_rows.extend(self.refresh())

    def take_replayed(self) -> List[CanonicalRow]:
        """Drain rows produced by parked-event replay inside a lazy refresh.
        consume() calls this itself; callers driving the staged triage /
        densify / dispatch / emit path (the Pipeline) must drain it after
        emit so replayed rows reach the sinks."""
        rows, self._replay_rows = self._replay_rows, []
        return rows

    @property
    def state(self) -> int:
        self.ensure_ready()
        return self._snapshot.i

    @property
    def engine_name(self) -> str:
        return self.engine.name

    # -- dedup (at-least-once) -------------------------------------------------
    def _is_duplicate(self, key: int) -> bool:
        if key in self._seen:
            self.stats["duplicates"] += 1
            return True
        self._seen[key] = True
        while len(self._seen) > self._dedup_window:
            self._seen.popitem(last=False)
        return False

    # -- triage + mapping --------------------------------------------------------
    @tracing.traced("triage")
    def triage(
        self,
        events: Union[Iterable[CDCEvent], ColumnarChunk],
        *,
        replay: bool = False,
    ) -> TriagedChunk:
        """Per-event dedup / state check / parking; returns the mappable
        events bucketed by (schema, version) for the engine, in columnar
        form (:class:`~repro.etl.engines.TriagedChunk`).

        Accepts a :class:`~repro.etl.events.ColumnarChunk` (the streaming
        sources' native form -- payloads already flattened once at the
        source boundary) or any legacy event iterable, which is columnarised
        here so ``consume(list_of_events)`` keeps working.  Events flagged
        ``bad`` (non-numeric payload values that can neither scatter into
        the float32 value column nor be silently truncated) are routed to
        the dead-letter path and counted under ``stats["bad_payload"]`` --
        identically for every engine, since all of them consume this triage.

        With ``replay=True`` (parked events re-entering after a refresh) the
        events are NOT re-counted under ``stats["events"]`` -- the caller
        accounts for them under ``stats["replayed"]``."""
        if not replay:
            self.ensure_ready()
        chunk = events if isinstance(events, ColumnarChunk) else columnarize(events)
        by_column: Dict = collections.defaultdict(list)
        # hot loop runs on python scalars pulled from the chunk's metadata
        # columns once (.tolist()); the CDCEvent objects are touched only on
        # the park / dead-letter error paths.  Same per-event order and
        # semantics as the legacy object walk (incl. mid-chunk strict-state
        # raise and dedup-window eviction), just without per-event attribute
        # access.
        states, schema_ids, versions = chunk.meta_columns()
        keys = chunk.keys.tolist()
        bad = chunk.bad.tolist()
        states = states.tolist()
        schema_ids = schema_ids.tolist()
        versions = versions.tolist()
        app_state = self._snapshot.i
        seen = self._seen
        window = self._dedup_window
        stats = self.stats
        # bulk-count arrivals unless a mid-chunk strict-state raise could
        # leave the count legitimately partial (legacy per-event semantics)
        if not replay and not self.strict_state:
            stats["events"] += len(keys)
        for e, key in enumerate(keys):
            if not replay and self.strict_state:
                stats["events"] += 1
            if key in seen:
                stats["duplicates"] += 1
                continue
            seen[key] = True
            while len(seen) > window:
                seen.popitem(last=False)
            if bad[e]:
                # un-scatterable payload (str/bool/Decimal/...): semi-
                # automated error path, same as an outdated event -- dead-
                # letter for offset reset after the producer is fixed
                self.dead_letter.append(chunk.events[e])
                stats["bad_payload"] += 1
                stats["dead_lettered"] += 1
                continue
            if states[e] != app_state:
                stats["stale"] += 1
                if self.strict_state:
                    raise StaleStateError(
                        f"event state {states[e]} != app state {app_state}"
                    )
                if states[e] > app_state:
                    # the *app* is behind: park, replayed after refresh
                    self._parked.append(chunk.events[e])
                    stats["parked"] += 1
                else:
                    # the event is outdated: dead-letter for offset reset
                    self.dead_letter.append(chunk.events[e])
                    stats["dead_lettered"] += 1
                continue
            by_column[(schema_ids[e], versions[e])].append(e)
        tri = TriagedChunk(
            chunk=chunk,
            by_column={
                ov: np.asarray(idx, dtype=np.int64) for ov, idx in by_column.items()
            },
        )
        # residency tiering: triage is where every mappable event passes, so
        # the per-(o, v) hit counters feeding the plan manager's hot/cold
        # policy are folded in here (no-op without a tiering policy)
        mgr = self.engine.manager
        if mgr is not None and mgr.tiering is not None and tri.by_column:
            mgr.record_hits(tri.by_column)
        return tri

    def consume(
        self, events: Union[Iterable[CDCEvent], ColumnarChunk]
    ) -> List[CanonicalRow]:
        """Map a chunk of events (legacy list or columnar) to canonical rows.

        Triage (dedup / state check / parking) is per event; the mapping is
        chunk-batched through the engine's densify -> dispatch -> emit
        stages, with densification running as pure numpy over the chunk's
        columnar (uid, value) arrays.  The fused engine issues a constant number of device
        dispatches per chunk (one, when any mappable event is present); the
        legacy per-block engine issues one per (column, block) pair.

        If the triage tripped a lazy refresh that replayed parked events,
        their rows are delivered first (they are the older events).
        """
        rows = self.engine.consume_groups(self.triage(events))
        replayed = self.take_replayed()
        return replayed + rows if replayed else rows

    # -- test-suite back-compat shims (read-only views into the engine) --------
    # External code must use ``self.engine`` / ``engine.info()`` instead; the
    # CI grep gate rejects ``app._`` outside repro.etl.
    @property
    def _compiled(self) -> Optional[CompiledDMM]:
        return self.engine.compiled

    @property
    def _fused(self) -> Optional[FusedDMM]:
        plan = self.engine.plan
        return plan if isinstance(plan, FusedDMM) else None

    @property
    def _sharded(self) -> Optional[ShardedFusedDMM]:
        plan = self.engine.plan
        return plan if isinstance(plan, ShardedFusedDMM) else None

    # -- scalar oracle path (pure Algorithm 6; used in tests) -------------------
    def consume_scalar(self, events: Iterable[CDCEvent]) -> List[Message]:
        # lazy refresh buffers (not drops) any replayed-parked-event rows
        self.ensure_ready()
        snap = self._snapshot
        if self._scalar_cols is None or self._scalar_cols[0] is not snap:
            # Algorithm 6 scans the DPM for the message's (o, v) column;
            # handing it only that column's blocks (in DPM order) keeps the
            # output identical and the oracle affordable at paper scale
            cols: Dict = collections.defaultdict(dict)
            for key, elements in snap.dpm.items():
                cols[key[:2]][key] = elements
            self._scalar_cols = (snap, dict(cols))
        by_col = self._scalar_cols[1]
        out: List[Message] = []
        for ev in events:
            msg = ev.message().densify()
            if msg.state != snap.i:
                continue
            col = by_col.get((msg.schema_id, msg.version), {})
            out.extend(map_message_dense(col, self.coordinator.registry, msg))
        return out
