"""The epoched plan lifecycle: PlanManager, incremental recompaction, epochs.

Covers the acceptance surface of the plan-lifecycle tentpole:
  * incremental lowering is bit-exact with the full-rebuild oracle at both
    layers: ``recompile_columns`` == ``compile_dpm`` and ``splice_fused`` ==
    ``compile_fused`` across a scripted churn sequence (evolutions plus a
    MatrixEdit that deletes columns from the table);
  * a :class:`PlanManager` with ``incremental=True`` produces bit-identical
    canonical rows (and stats) to ``incremental=False`` through the full
    in-band pipeline -- host and device densify, sync and async consume,
    and the sharded engine on a forced 1x4 topology;
  * the manager builds one resident plan: ``bytes_resident`` is the device
    block table's bytes before and after an evolution, and the removed
    kinds and options are refused with an error naming what exists;
  * ``publish=True`` logs :class:`PlanPublished` cutovers in the control
    log, ``replay_control_log`` reproduces registry/state/DPM bit-exactly
    across them, and an in-flight epoch-pinned chunk drains on the OLD
    table with rows equal to the sync oracle;
  * the plan's device tables are sized to capacity buckets: splice and
    full rebuild agree padding included, the shapes hold across ten
    evolutions, and ``table_shape_changes`` counts a bucket crossing;
  * satellite: the documented ``engine.info()`` / ``Cluster.info()`` key
    lists match what the engines actually return.
"""

import functools
import re

import numpy as np
import pytest

from _subproc import run_sub as _run_sub
from repro.core.dmm_jax import (
    compile_dpm,
    compile_fused,
    recompile_columns,
    splice_fused,
)
from repro.core.state import StateCoordinator
from repro.core.synthetic import ScenarioConfig, build_scenario
from repro.etl import (
    CollectSink,
    Cluster,
    EventChunkSource,
    EventSource,
    MatrixEdit,
    METLApp,
    Pipeline,
    PlanManager,
    PlanPublished,
    SchemaEvolved,
    make_engine,
    replay_control_log,
)

run_sub = functools.partial(_run_sub, devices=4)

STAT_KEYS = ("events", "duplicates", "mapped", "empty", "stale")


def _world(seed=71):
    sc = build_scenario(ScenarioConfig(seed=seed))
    return sc, StateCoordinator(sc.registry, sc.dpm)


def _evolve_event(reg, which=0, tag="evo"):
    o = reg.domain.schema_ids()[which]
    v = reg.domain.latest_version(o)
    keep = tuple(a.name for a in reg.domain.get(o, v).attributes)[1:]
    return SchemaEvolved(tree="domain", schema_id=o, keep=keep, add=(tag,)), o, v


def _assert_rows_equal(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x[0] == y[0] and x[3] == y[3]
        np.testing.assert_array_equal(x[1], y[1])
        np.testing.assert_array_equal(x[2], y[2])


def _sorted_rows(rows):
    # (event key, route) is a unique row identity: an event maps through at
    # most one (o, v) column and block keys are unique within it
    return sorted(rows, key=lambda r: (r[3], r[0]))


def _touched_diff(old_dpm, new_dpm):
    touched = {(k[0], k[1]) for k in set(old_dpm) ^ set(new_dpm)}
    for k in set(old_dpm) & set(new_dpm):
        if old_dpm[k] != new_dpm[k]:
            touched.add((k[0], k[1]))
    return touched


def _assert_compiled_equal(a, b):
    assert a.state == b.state
    assert list(a.by_column) == list(b.by_column)
    for ov in a.by_column:
        ba, bb = a.by_column[ov], b.by_column[ov]
        assert [x.key for x in ba] == [x.key for x in bb]
        for x, y in zip(ba, bb):
            assert (x.n_in, x.n_out) == (y.n_in, y.n_out)
            np.testing.assert_array_equal(np.asarray(x.src), np.asarray(y.src))


def _assert_plans_equal(a, b):
    assert type(a) is type(b)
    assert a.state == b.state
    assert (a.n_blocks, a.width, a.n_in_pad) == (b.n_blocks, b.width, b.n_in_pad)
    assert a.routes == b.routes
    np.testing.assert_array_equal(np.asarray(a.n_out), np.asarray(b.n_out))
    if hasattr(a, "src3d"):
        assert a.n_shards == b.n_shards
        np.testing.assert_array_equal(np.asarray(a.src3d), np.asarray(b.src3d))
    else:
        np.testing.assert_array_equal(np.asarray(a.src2d), np.asarray(b.src2d))
    np.testing.assert_array_equal(a.uid_slot, b.uid_slot)
    np.testing.assert_array_equal(a.uid_col, b.uid_col)
    np.testing.assert_array_equal(a.col_block_start, b.col_block_start)
    np.testing.assert_array_equal(a.col_block_count, b.col_block_count)
    assert list(a.columns) == list(b.columns)
    for ov in a.columns:
        ca, cb = a.columns[ov], b.columns[ov]
        assert (ca.o, ca.v, ca.n_in, ca.col_id) == (cb.o, cb.v, cb.n_in, cb.col_id)
        assert ca.uid_pos == cb.uid_pos
        np.testing.assert_array_equal(ca.block_ids, cb.block_ids)


# ---------------------------------------------------------------------------
# incremental lowering vs the full-rebuild oracle (pure dmm_jax layer)
# ---------------------------------------------------------------------------


class TestIncrementalLowering:
    def test_recompile_columns_matches_compile_dpm_across_churn(self):
        sc, coord = _world(seed=91)
        compiled = compile_dpm(coord.snapshot().dpm, coord.registry)
        for step in range(3):
            old_dpm = dict(coord.snapshot().dpm)
            ev, _, _ = _evolve_event(coord.registry, step, f"c{step}")
            coord.apply(ev)
            new_dpm = coord.snapshot().dpm
            touched = _touched_diff(old_dpm, new_dpm)
            assert touched  # an evolution must touch at least the new column
            compiled = recompile_columns(
                compiled, new_dpm, coord.registry, touched
            )
            _assert_compiled_equal(
                compiled, compile_dpm(new_dpm, coord.registry)
            )

    def test_splice_fused_matches_compile_fused_across_churn(self):
        """The tentpole oracle at the table layer: splicing only the touched
        columns into the previous epoch's table is bit-identical to the full
        re-flatten -- including across a MatrixEdit that REVERTS the DPM, so
        previously-added columns must drop out of the spliced table."""
        sc, coord = _world(seed=92)
        dpm0 = dict(coord.snapshot().dpm)
        compiled = compile_dpm(dpm0, coord.registry)
        plan = compile_fused(compiled, coord.registry)
        script = [
            _evolve_event(coord.registry, 0, "s0")[0],
            _evolve_event(coord.registry, 1, "s1")[0],
            MatrixEdit(dpm=dpm0),  # deletes the two evolved columns
        ]
        for ev in script:
            old_dpm = dict(coord.snapshot().dpm)
            coord.apply(ev)
            new_dpm = coord.snapshot().dpm
            touched = _touched_diff(old_dpm, new_dpm)
            compiled = recompile_columns(
                compiled, new_dpm, coord.registry, touched
            )
            plan = splice_fused(plan, compiled, coord.registry, touched)
            _assert_plans_equal(plan, compile_fused(compiled, coord.registry))


# ---------------------------------------------------------------------------
# the PlanManager: caching, epochs, incremental == full through the manager
# ---------------------------------------------------------------------------


class TestPlanManager:
    def test_acquire_caches_by_state_and_bumps_epochs(self):
        sc, coord = _world(seed=93)
        mgr = PlanManager(kind="fused")
        snap = coord.snapshot()
        l1 = mgr.acquire(snap, coord.registry)
        assert l1.epoch == 1 and not l1.incremental
        assert mgr.acquire(snap, coord.registry) is l1  # cache hit, no build
        ev, _, _ = _evolve_event(coord.registry)
        coord.apply(ev)
        l2 = mgr.acquire(coord.snapshot(), coord.registry)
        assert l2.epoch == 2 and l2.incremental
        assert 1 <= l2.touched_columns < len(l2.compiled.by_column)
        info = mgr.info()
        assert info["plan_epoch"] == 2 and info["rebuilds"] == 2
        assert info["incremental_rebuilds"] == 1
        assert info["bytes_resident"] == l2.bytes_resident > 0

    def test_manager_kind_is_validated(self):
        with pytest.raises(ValueError):
            PlanManager(kind="warp")
        with pytest.raises(ValueError):
            PlanManager(kind="sharded")  # needs a mesh or n_shards
        sc, coord = _world()
        with pytest.raises(ValueError, match="consumes plan kind"):
            # the fused engine cannot consume a sharded manager
            METLApp(coord, plan_manager=PlanManager(kind="sharded", n_shards=2))

    def test_manager_incremental_plan_equals_full_oracle_plan(self):
        """The manager's own DPM diff + splice, checked against a
        from-scratch lowering after every churn step."""
        sc, coord = _world(seed=94)
        mgr = PlanManager(kind="fused")
        mgr.acquire(coord.snapshot(), coord.registry)
        for step in range(3):
            ev, _, _ = _evolve_event(coord.registry, step, f"m{step}")
            coord.apply(ev)
            lease = mgr.acquire(coord.snapshot(), coord.registry)
            assert lease.incremental
            snap = coord.snapshot()
            oracle = compile_fused(
                compile_dpm(snap.dpm, coord.registry), coord.registry
            )
            _assert_plans_equal(lease.plan, oracle)


@pytest.mark.parametrize("kind", ["fused", "sharded"])
def test_padded_tables_splice_equals_full_rebuild(kind):
    """Ten evolutions through an incremental and a full-rebuild manager:
    the spliced tables equal the rebuilt ones, padding included, and every
    table is its capacity bucket long with -1 past what is needed, so the
    shapes hold across the ten states."""
    from repro.core.dmm_jax import bucket_rows

    sc = build_scenario(ScenarioConfig(n_schemas=12, versions_per_schema=3, seed=75))
    coord = StateCoordinator(sc.registry, sc.dpm)
    shards = 4 if kind == "sharded" else None
    inc = PlanManager(kind=kind, n_shards=shards)
    full = PlanManager(kind=kind, n_shards=shards, incremental=False)
    shapes = set()
    for step in range(11):
        if step:
            ev, _, _ = _evolve_event(coord.registry, step, f"p{step}")
            coord.apply(ev)
        a = inc.acquire(coord.snapshot(), coord.registry)
        b = full.acquire(coord.snapshot(), coord.registry)
        assert a.incremental == bool(step) and not b.incremental
        _assert_plans_equal(a.plan, b.plan)
        p = a.plan
        known = int(np.flatnonzero(p.uid_col >= 0).max()) + 1
        assert p.uid_slot.size == p.uid_col.size == bucket_rows(known)
        assert np.all(p.uid_slot[known:] == -1) and np.all(p.uid_col[known:] == -1)
        if kind == "sharded":
            table = p.src3d
            assert table.shape[1] == bucket_rows(p.blocks_per_shard)
            rows = np.asarray(table)[:, p.blocks_per_shard:]
        else:
            table = p.src2d
            assert table.shape[0] == bucket_rows(p.n_blocks)
            rows = np.asarray(table)[p.n_blocks:]
        assert np.all(rows == -1)
        shapes.add((p.uid_slot.shape, table.shape))
    assert len(shapes) == 1
    assert inc.info()["table_shape_changes"] == full.info()["table_shape_changes"] == 0


def test_table_shape_changes_counts_a_capacity_crossing():
    """An evolution that takes the block table past its capacity bucket
    changes the table's shape, and the manager counts it."""
    sc, coord = _world(seed=71)
    mgr = PlanManager(kind="fused")
    lease = mgr.acquire(coord.snapshot(), coord.registry)
    n = lease.plan.n_blocks
    assert mgr.info()["table_shape_changes"] == 0
    assert lease.plan.src2d.shape[0] == n  # a full bucket
    ev, _, _ = _evolve_event(coord.registry, 0, "grow")
    coord.apply(ev)
    lease = mgr.acquire(coord.snapshot(), coord.registry)
    assert lease.plan.n_blocks > n
    assert mgr.info()["table_shape_changes"] == 1
    assert lease.plan.src2d.shape[0] == 2 * n
    # a same-state rebuild keeps the shapes: nothing more to count
    mgr.invalidate()
    mgr.acquire(coord.snapshot(), coord.registry)
    assert mgr.info()["table_shape_changes"] == 1


# ---------------------------------------------------------------------------
# one resident plan: what the manager guarantees, and what it refuses
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["fused", "sharded"])
def test_bytes_resident_is_the_device_table(kind):
    """Every lease holds the whole plan on the device: ``bytes_resident``
    is the block table's bytes, on the lease, in the manager's info and in
    the engine's, before and after an evolution."""
    sc, coord = _world(seed=103)
    mgr = PlanManager(kind=kind, n_shards=4 if kind == "sharded" else None)
    for step in range(2):
        if step:
            ev, _, _ = _evolve_event(coord.registry, 0, "res")
            coord.apply(ev)
        lease = mgr.acquire(coord.snapshot(), coord.registry)
        assert lease.incremental == bool(step)
        table = lease.plan.src3d if kind == "sharded" else lease.plan.src2d
        assert lease.bytes_resident == int(table.nbytes) > 0
        assert mgr.info()["bytes_resident"] == lease.bytes_resident
    if kind == "fused":
        app = METLApp(coord, plan_manager=mgr)
        info = app.engine.info()
        assert info["bytes_resident"] == info["table_bytes"] == lease.bytes_resident


@pytest.mark.parametrize(
    "build,names",
    [
        (lambda: PlanManager(kind="blocks"), ("fused", "sharded")),
        (lambda: make_engine("blocks"), ("fused", "sharded")),
        (lambda: make_engine("fused", impl="onehot"),
         ("fused", "ref", "segmented_gather", "densify_map")),
    ],
    ids=["plan_kind_blocks", "engine_blocks", "impl_onehot"],
)
def test_removed_paths_are_refused_by_name(build, names):
    """The per-block plan kind and engine and the one-hot impl are gone:
    asking for one raises, and the error names what exists instead of
    falling back to another path."""
    with pytest.raises(ValueError) as err:
        build()
    for name in names:
        assert name in str(err.value), (name, str(err.value))


def test_publish_manager_device_densify_replays_bit_exact():
    """A ``publish=True`` manager on the device-densify path across two
    evolutions and a revert: the rows equal the host-densify run's, each
    published epoch prices its whole table, and a coordinator rebuilt from
    the control log maps a fresh chunk bit-exactly like the original."""
    rows_dev, app, mgr = _run_churn(
        "fused", "fused", False, incremental=True, seed=88, publish=True,
        device_densify=True,
    )
    rows_host, _, _ = _run_churn(
        "fused", "fused", False, incremental=True, seed=88, publish=True
    )
    assert len(rows_dev) > 0
    _assert_rows_equal(rows_host, rows_dev)
    assert app.stats["transfers"] == app.stats["dispatches"]  # device path
    coord = app.coordinator
    pubs = [r.event for r in coord.control_log if isinstance(r.event, PlanPublished)]
    assert [p.epoch for p in pubs] == [1, 2, 3, 4]
    assert pubs[-1].bytes_resident == app.engine.info()["table_bytes"]

    seed = build_scenario(ScenarioConfig(seed=88))
    replayed = replay_control_log(coord.control_log, seed.registry, seed.dpm)
    assert replayed.snapshot().dpm == coord.snapshot().dpm
    rows = [
        METLApp(c, device_densify=True).consume(
            EventSource(c.registry, seed=7).slice_columnar(0, 96)
        )
        for c in (coord, replayed)
    ]
    assert len(rows[0]) > 0
    _assert_rows_equal(rows[0], rows[1])


# ---------------------------------------------------------------------------
# end-to-end: incremental vs full rebuild through the in-band pipeline
# ---------------------------------------------------------------------------


def _run_churn(
    engine,
    kind,
    async_consume,
    *,
    incremental,
    device_densify=False,
    seed=91,
    publish=False,
    n_chunks=7,
    size=64,
):
    """One in-band churn run: two evolutions plus a MatrixEdit reverting to
    the seed DPM, interleaved with data chunks."""
    sc = build_scenario(ScenarioConfig(seed=seed))
    coord = StateCoordinator(sc.registry, sc.dpm)
    mgr = PlanManager(
        kind=kind, coordinator=coord, incremental=incremental, publish=publish
    )
    app = METLApp(
        coord, engine=engine, plan_manager=mgr, device_densify=device_densify
    )
    dpm0 = dict(coord.snapshot().dpm)
    ev1, _, _ = _evolve_event(coord.registry, 0, "c1")
    ev2, _, _ = _evolve_event(coord.registry, 1, "c2")
    sink = CollectSink()
    st = Pipeline(
        EventChunkSource(
            EventSource(sc.registry, seed=5), chunk_size=size,
            max_chunks=n_chunks,
            control={1: ev1, 3: ev2, 5: MatrixEdit(dpm=dpm0)},
        ),
        app, [sink], async_consume=async_consume,
    ).run()
    assert st.chunks == n_chunks and st.control == 3
    return sink.rows, app, mgr


@pytest.mark.parametrize("device_densify", [False, True], ids=["fused", "device"])
@pytest.mark.parametrize("async_consume", [False, True])
def test_incremental_rows_match_full_rebuild_oracle(device_densify, async_consume):
    """The acceptance oracle: a manager splicing only the touched columns
    yields bit-identical rows (zero dropped, zero duplicated) to a manager
    doing the full rebuild at every churn step."""
    rows_full, app_full, mgr_full = _run_churn(
        "fused", "fused", async_consume, incremental=False,
        device_densify=device_densify,
    )
    rows_inc, app_inc, mgr_inc = _run_churn(
        "fused", "fused", async_consume, incremental=True,
        device_densify=device_densify,
    )
    assert len(rows_full) > 0
    _assert_rows_equal(rows_full, rows_inc)
    for k in STAT_KEYS:
        assert app_full.stats[k] == app_inc.stats[k], k
    # 1 initial full build + 3 churn builds on both sides; only the
    # incremental manager spliced
    assert mgr_inc.info()["rebuilds"] == mgr_full.info()["rebuilds"] == 4
    assert mgr_inc.info()["incremental_rebuilds"] == 3
    assert mgr_full.info()["incremental_rebuilds"] == 0
    assert mgr_inc.info()["plan_epoch"] == 4


def test_incremental_rows_match_oracle_device_densify():
    """The same oracle with on-device densification (the Pallas densify
    path feeds from the spliced table's device arrays)."""
    rows_full, _, _ = _run_churn(
        "fused", "fused", False, incremental=False, device_densify=True
    )
    rows_inc, _, _ = _run_churn(
        "fused", "fused", False, incremental=True, device_densify=True
    )
    assert len(rows_full) > 0
    _assert_rows_equal(rows_full, rows_inc)


@pytest.mark.slow
def test_incremental_rows_match_oracle_sharded():
    """Sharded splice parity on a forced 1x4 topology: rows AND the device
    src3d table are bit-identical to the full rebuild."""
    out = run_sub("""
        import numpy as np
        from repro.core.state import StateCoordinator
        from repro.core.synthetic import ScenarioConfig, build_scenario
        from repro.etl import (CollectSink, EventChunkSource, EventSource,
                               METLApp, Pipeline, PlanManager, SchemaEvolved)
        from repro.launch.mesh import make_etl_mesh

        def evolve_event(reg, which, tag):
            o = reg.domain.schema_ids()[which]
            v = reg.domain.latest_version(o)
            keep = tuple(a.name for a in reg.domain.get(o, v).attributes)[1:]
            return SchemaEvolved(tree="domain", schema_id=o, keep=keep,
                                 add=(tag,))

        mesh = make_etl_mesh(4)

        def run(incremental):
            sc = build_scenario(ScenarioConfig(seed=84))
            coord = StateCoordinator(sc.registry, sc.dpm)
            mgr = PlanManager(kind="sharded", mesh=mesh, coordinator=coord,
                              incremental=incremental)
            app = METLApp(coord, engine="sharded", mesh=mesh,
                          plan_manager=mgr)
            ev1 = evolve_event(coord.registry, 0, "s1")
            ev2 = evolve_event(coord.registry, 1, "s2")
            sink = CollectSink()
            Pipeline(EventChunkSource(EventSource(sc.registry, seed=5),
                                      chunk_size=64, max_chunks=4,
                                      control={1: ev1, 3: ev2}),
                     app, [sink]).run()
            return sink.rows, app, mgr

        rows_full, app_f, mgr_f = run(False)
        rows_inc, app_i, mgr_i = run(True)
        assert len(rows_full) == len(rows_inc) > 0
        for a, b in zip(rows_full, rows_inc):
            assert a[0] == b[0] and a[3] == b[3]
            np.testing.assert_array_equal(a[1], b[1])
            np.testing.assert_array_equal(a[2], b[2])
        assert mgr_i.info()["incremental_rebuilds"] == 2
        assert mgr_f.info()["incremental_rebuilds"] == 0
        assert mgr_i.info()["plan_epoch"] == 3
        np.testing.assert_array_equal(np.asarray(app_i.engine.plan.src3d),
                                      np.asarray(app_f.engine.plan.src3d))
        print("sharded incremental parity OK")
    """)
    assert "sharded incremental parity OK" in out


# ---------------------------------------------------------------------------
# PlanPublished: the control-log record and replay across the boundary
# ---------------------------------------------------------------------------


class TestPublish:
    def test_publish_logs_cutovers_and_replays_bit_exact(self):
        """Satellite: replay_control_log across PlanPublished/recompaction
        records reproduces registry, state counter and DPM bit-exactly, and
        a fresh instance built from the replayed coordinator emits the same
        rows."""
        rows, app, mgr = _run_churn(
            "fused", "fused", False, incremental=True, seed=89, publish=True
        )
        coord = app.coordinator
        log = coord.control_log
        pubs = [r for r in log if isinstance(r.event, PlanPublished)]
        assert [r.event.epoch for r in pubs] == [1, 2, 3, 4]
        assert [r.event.incremental for r in pubs] == [False, True, True, True]
        assert all(r.event.kind == "fused" for r in pubs)
        assert pubs[-1].event.state == coord.registry.state
        assert pubs[-1].event.bytes_resident == app.engine.info()["bytes_resident"]
        # interleaving: each churn event precedes the epoch it triggered
        kinds = [type(r.event).__name__ for r in log]
        assert kinds == [
            "PlanPublished", "SchemaEvolved", "PlanPublished",
            "SchemaEvolved", "PlanPublished", "MatrixEdit", "PlanPublished",
        ]

        seed = build_scenario(ScenarioConfig(seed=89))
        replayed = replay_control_log(log, seed.registry, seed.dpm)
        assert replayed.registry.state == coord.registry.state
        assert replayed.snapshot().dpm == coord.snapshot().dpm
        assert replayed.registry.col_axis() == coord.registry.col_axis()
        # plan events replay as no-ops: same log length, no state drift
        assert len(replayed.control_log) == len(log)

        # a joining instance at the replayed state maps identically (fresh
        # apps on both sides: the original app's dedup window has already
        # seen the pipeline's key range)
        src_a = EventSource(coord.registry, seed=6)
        src_b = EventSource(replayed.registry, seed=6)
        rows_a = METLApp(coord).consume(src_a.slice_columnar(0, 64))
        rows_b = METLApp(replayed).consume(src_b.slice_columnar(0, 64))
        assert len(rows_a) > 0
        _assert_rows_equal(rows_a, rows_b)

    def test_unpublished_manager_keeps_control_log_clean(self):
        rows, app, _ = _run_churn(
            "fused", "fused", False, incremental=True, seed=89, publish=False
        )
        kinds = [type(r.event).__name__ for r in app.coordinator.control_log]
        assert kinds == ["SchemaEvolved", "SchemaEvolved", "MatrixEdit"]

    def test_inflight_chunk_drains_on_old_epoch_across_publish(self):
        """Satellite: a chunk densified under epoch N keeps its plan pin
        across the epoch N+1 publish and drains on the OLD table, with rows
        equal to the sync oracle that consumed it before the evolution."""
        seed = 96
        sc2 = build_scenario(ScenarioConfig(seed=seed))
        coord2 = StateCoordinator(sc2.registry, sc2.dpm)
        rows_oracle = METLApp(coord2).consume(
            EventSource(sc2.registry, seed=5, p_duplicate=0.0)
            .slice_columnar(0, 64)
        )

        sc = build_scenario(ScenarioConfig(seed=seed))
        coord = StateCoordinator(sc.registry, sc.dpm)
        mgr = PlanManager(kind="fused", coordinator=coord, publish=True)
        app = METLApp(coord, plan_manager=mgr)
        src = EventSource(sc.registry, seed=5, p_duplicate=0.0)
        dense = app.engine.densify(app.triage(src.slice_columnar(0, 64)))
        old_plan = dense.plan
        old_epoch = dense.epoch
        ev, _, _ = _evolve_event(coord.registry)
        coord.apply(ev)
        app.refresh()  # publish epoch 2 while the chunk is still in flight
        assert app.engine.lease.epoch == 2
        assert [
            r.event.epoch for r in coord.control_log
            if isinstance(r.event, PlanPublished)
        ] == [1, 2]
        assert dense.plan is old_plan and dense.epoch == old_epoch
        rows = app.engine.emit(app.engine.dispatch(dense))
        assert len(rows) > 0
        _assert_rows_equal(rows_oracle, rows)


# ---------------------------------------------------------------------------
# satellite: documented info() key lists match reality
# ---------------------------------------------------------------------------

REPLICATION_KEYS = {"role", "term", "log_offset", "lag_records"}
FUSED_ALWAYS = {
    "engine", "impl", "n_shards", "device_densify", "dispatches",
    "readbacks_early", "transfers", "plan_epoch", "rebuilds",
    "table_shape_changes",
} | REPLICATION_KEYS
PLAN_KEYS = {"state", "n_blocks", "blocks_per_shard", "table_bytes",
             "table_bytes_per_shard", "bytes_resident"}
FUSED_PLAN_KEYS = PLAN_KEYS | {"width", "uid_capacity", "block_capacity"}
CLUSTER_KEYS = {
    "instances", "engine", "state", "states", "control_log", "dispatches",
    "events", "mapped", "dead_letter", "plan_epoch", "rebuilds",
    "bytes_resident", "per_instance",
} | REPLICATION_KEYS


def _documented(doc):
    return set(re.findall(r"``([a-z_]+)``", doc))


def test_engine_info_keys_match_documented_lists():
    from repro.etl.engines import MappingEngine

    doc = _documented(MappingEngine.info.__doc__)
    assert (FUSED_ALWAYS | FUSED_PLAN_KEYS) <= doc

    sc, coord = _world(seed=101)
    src = EventSource(sc.registry, seed=5)
    for device_densify in (False, True):
        # pre-compile surface (METLApp compiles eagerly, so ask a bare one)
        bare = make_engine("fused", device_densify=device_densify)
        assert set(bare.info()) == FUSED_ALWAYS, device_densify
        app = METLApp(coord, device_densify=device_densify)
        eng = app.engine
        app.consume(src.slice_columnar(0, 32))
        info = eng.info()
        assert set(info) == FUSED_ALWAYS | FUSED_PLAN_KEYS, device_densify
        assert info["plan_epoch"] == 1 and info["rebuilds"] == 1
        # unreplicated coordinator: the single writer IS the leader
        assert info["role"] == "leader" and info["term"] == 0
        assert info["log_offset"] == len(coord.control_log)
        assert info["lag_records"] == 0
        # one resident plan: the lease prices the full table
        assert info["bytes_resident"] == info["table_bytes"] > 0
        eng.evict()
        # plan-gated keys (bytes_resident included) drop while evicted; the
        # manager-side counters survive
        evicted = eng.info()
        assert set(evicted) == FUSED_ALWAYS, device_densify
        assert evicted["plan_epoch"] == 1


def test_cluster_info_keys_match_documented_list():
    import repro.etl.cluster as cluster_mod

    assert CLUSTER_KEYS <= _documented(cluster_mod.__doc__)
    sc, coord = _world(seed=102)
    cl = Cluster.over_stream(
        coord, EventSource(sc.registry, seed=5), instances=2, chunk_size=32,
        max_chunks=4, sinks=[CollectSink()],
    )
    cl.run()
    info = cl.info()
    assert set(info) == CLUSTER_KEYS
    assert info["plan_epoch"] == 1  # max over instances, no churn here
    assert info["rebuilds"] == len(cl.apps)
    assert info["bytes_resident"] == sum(
        i["bytes_resident"] for i in info["per_instance"]
    ) > 0
    # replication surface: an unreplicated cluster is its own leader
    assert info["role"] == "leader" and info["term"] == 0
    assert info["log_offset"] == len(coord.control_log)
    assert info["lag_records"] == 0
    cl.close()
