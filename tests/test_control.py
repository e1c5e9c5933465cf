"""The control-plane API: typed events, epoch-pinned plans, in-band streams.

Covers the acceptance surface of the control-plane tentpole:
  * declarative apply(event) == the closure-based apply_update oracle
    (registry state, DPM, report) for every event type;
  * the epoch-ordered control log and replay determinism: replaying
    coordinator.control_log over a seed registry reproduces registry.state
    and the DPM bit-exactly (closure records are flagged non-replayable);
  * a mid-stream SchemaEvolved applied through the IN-BAND control path
    yields bit-identical canonical rows to the same scenario run with
    out-of-band apply_update + manual refresh (host and device densify,
    sync and async consume; the sharded engine in a forced-topology
    subprocess);
  * freeze/thaw during a running pipeline: data flows inside the window, a
    schema change arriving inside it is deferred and re-admitted by the
    Thaw (paper SS3.4), and direct coordinator application is rejected;
  * in-flight DenseChunks stay pinned to their epoch;
  * satellite regressions: weakref evict hooks (no hook-list leak),
    public Registry.bump_state, the cached equivalence index surviving
    version adds/deletes.
"""

import functools
import gc

import numpy as np
import pytest

from _subproc import run_sub as _run_sub
from repro.core.state import ControlRecord, StateCoordinator
from repro.core.synthetic import ScenarioConfig, build_scenario
from repro.etl import (
    CollectSink,
    ControlReplayError,
    EventChunkSource,
    EventSource,
    Freeze,
    ListSource,
    MatrixEdit,
    METLApp,
    Pipeline,
    SchemaAdded,
    SchemaEvolved,
    ScriptedControlSource,
    Thaw,
    VersionDeleted,
    replay_control_log,
)

run_sub = functools.partial(_run_sub, devices=4)


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


# ---------------------------------------------------------------------------
# declarative apply() vs the closure oracle
# ---------------------------------------------------------------------------


class TestApply:
    def test_schema_evolved_matches_closure_update(self):
        sc_a, coord_a = _world()
        sc_b, coord_b = _world()
        ev, o, v = _evolve_event(coord_a.registry)
        snap_a = coord_a.apply(ev)

        def mutate(r):
            r.evolve(r.domain, o, keep=list(ev.keep), add=list(ev.add))
            return ("added_domain", o, v + 1)

        snap_b = coord_b.apply_update(mutate)
        assert snap_a.i == snap_b.i
        assert snap_a.dpm == snap_b.dpm
        assert coord_a.last_report.new_blocks == coord_b.last_report.new_blocks
        assert coord_a.registry.col_axis() == coord_b.registry.col_axis()

    def test_schema_added_and_version_deleted(self):
        sc, coord = _world()
        reg = coord.registry
        s0 = reg.state
        sid = max(reg.domain.schema_ids()) + 1
        coord.apply(SchemaAdded(tree="domain", schema_id=sid, names=("x1", "x2")))
        assert reg.domain.has(sid, 1) and reg.state == s0 + 1
        coord.apply(VersionDeleted(tree="domain", schema_id=sid, version=1))
        assert not reg.domain.has(sid, 1) and reg.state == s0 + 2

    def test_matrix_edit_bumps_and_evicts(self):
        sc, coord = _world()
        app = METLApp(coord)
        s0 = coord.registry.state
        coord.apply(MatrixEdit(dpm=dict(sc.dpm)))
        assert coord.registry.state == s0 + 1
        assert app.stats["evictions"] == 1  # broadcast reached the instance

    def test_matrix_edit_snapshots_the_dpm(self):
        """REGRESSION: the logged event must not alias the caller's dict --
        a post-apply mutation would silently corrupt log replay."""
        sc, coord = _world()
        d = dict(sc.dpm)
        coord.apply(MatrixEdit(dpm=d))
        d.clear()  # caller reuses its dict
        seed = build_scenario(ScenarioConfig(seed=71))
        replayed = replay_control_log(coord.control_log, seed.registry, seed.dpm)
        assert replayed.snapshot().dpm == coord.snapshot().dpm == dict(sc.dpm)

    def test_apply_rejects_non_events(self):
        _, coord = _world()
        with pytest.raises(TypeError):
            coord.apply(object())

    def test_events_are_appended_epoch_ordered(self):
        sc, coord = _world()
        ev1, _, _ = _evolve_event(coord.registry, 0, "a")
        ev2, _, _ = _evolve_event(coord.registry, 1, "b")
        coord.apply(ev1)
        coord.apply(ev2)
        log = coord.control_log
        assert [r.seq for r in log] == [0, 1]
        assert [r.event for r in log] == [ev1, ev2]
        assert log[0].state < log[1].state == coord.registry.state


# ---------------------------------------------------------------------------
# the control log: replay determinism
# ---------------------------------------------------------------------------


class TestControlLogReplay:
    def test_replay_reproduces_state_and_dpm_bit_exact(self):
        sc, coord = _world(seed=77)
        reg = coord.registry
        ev1, _, _ = _evolve_event(reg, 0, "r1")
        coord.apply(ev1)
        sid = max(reg.domain.schema_ids()) + 1
        coord.apply(SchemaAdded(tree="domain", schema_id=sid, names=("n1", "n2")))
        coord.apply(Freeze())
        coord.apply(Thaw())
        ev2, o2, _ = _evolve_event(reg, 2, "r2")
        coord.apply(ev2)
        coord.apply(VersionDeleted(tree="domain", schema_id=o2, version=1))
        coord.apply(MatrixEdit(dpm=coord.snapshot().dpm))

        seed = build_scenario(ScenarioConfig(seed=77))
        replayed = replay_control_log(coord.control_log, seed.registry, seed.dpm)
        assert replayed.registry.state == reg.state
        assert replayed.snapshot().dpm == coord.snapshot().dpm
        assert replayed.registry.col_axis() == reg.col_axis()
        assert replayed.registry.row_axis() == reg.row_axis()
        # the replayed single writer logged the same sequence
        assert [r.state for r in replayed.control_log] == [
            r.state for r in coord.control_log
        ]

    def test_closure_updates_are_not_replayable(self):
        sc, coord = _world()
        _, o, v = _evolve_event(coord.registry)

        def mutate(r):
            keep = [a.name for a in r.domain.get(o, v).attributes]
            r.evolve(r.domain, o, keep=keep)
            return ("added_domain", o, v + 1)

        coord.apply_update(mutate)
        assert coord.control_log[-1].event.trigger == ("added_domain", o, v + 1)
        seed = build_scenario(ScenarioConfig(seed=71))
        with pytest.raises(ControlReplayError):
            replay_control_log(coord.control_log, seed.registry, seed.dpm)

    def test_replay_detects_wrong_seed(self):
        sc, coord = _world(seed=77)
        ev, _, _ = _evolve_event(coord.registry)
        coord.apply(ev)
        wrong = build_scenario(ScenarioConfig(seed=78, n_schemas=4))
        with pytest.raises((ControlReplayError, KeyError)):
            replay_control_log(coord.control_log, wrong.registry, wrong.dpm)


# ---------------------------------------------------------------------------
# the in-band oracle (acceptance): in-band == out-of-band, bit-exact
# ---------------------------------------------------------------------------


def _run_out_of_band(seed, engine, n_chunks, size, boundary, device_densify=False):
    """The oracle: same chunk grid, manual apply_update + refresh."""
    sc = build_scenario(ScenarioConfig(seed=seed))
    coord = StateCoordinator(sc.registry, sc.dpm)
    app = METLApp(coord, engine=engine, device_densify=device_densify)
    src = EventSource(sc.registry, seed=5)
    ev, o, v = _evolve_event(coord.registry, 0, "mid")
    rows = []
    for k in range(n_chunks):
        if k == boundary:
            def mutate(r):
                r.evolve(r.domain, o, keep=list(ev.keep), add=list(ev.add))
                return ("added_domain", o, v + 1)

            coord.apply_update(mutate)
            app.refresh()
        rows.extend(app.consume(src.slice_columnar(k * size, size)))
    return rows, app


def _run_in_band(
    seed, engine, n_chunks, size, boundary, async_consume, device_densify=False
):
    sc = build_scenario(ScenarioConfig(seed=seed))
    coord = StateCoordinator(sc.registry, sc.dpm)
    app = METLApp(coord, engine=engine, device_densify=device_densify)
    ev, _, _ = _evolve_event(coord.registry, 0, "mid")
    sink = CollectSink()
    pipe = Pipeline(
        EventChunkSource(EventSource(sc.registry, seed=5), chunk_size=size,
                         max_chunks=n_chunks, control={boundary: ev}),
        app, [sink], async_consume=async_consume,
    )
    st = pipe.run()
    assert st.control == 1 and st.chunks == n_chunks
    return sink.rows, app


STAT_KEYS = ("events", "duplicates", "mapped", "empty", "dispatches", "stale")


@pytest.mark.parametrize("device_densify", [False, True], ids=["fused", "device"])
@pytest.mark.parametrize("async_consume", [False, True])
def test_inband_evolution_matches_out_of_band_oracle(device_densify, async_consume):
    """The acceptance oracle: a mid-stream SchemaEvolved through the in-band
    control path is bit-identical to out-of-band apply_update + refresh."""
    rows_oob, app_oob = _run_out_of_band(
        81, "fused", 6, 64, 3, device_densify=device_densify
    )
    rows_ib, app_ib = _run_in_band(
        81, "fused", 6, 64, 3, async_consume, device_densify=device_densify
    )
    assert len(rows_oob) > 0
    _assert_rows_equal(rows_oob, rows_ib)
    for k in STAT_KEYS:
        assert app_oob.stats[k] == app_ib.stats[k], k
    assert app_ib.stats["dispatches"] == 6  # still 1/chunk across the epoch


@pytest.mark.slow
def test_inband_evolution_matches_oracle_sharded():
    """The same oracle for engine="sharded" on a forced 1x4 topology."""
    out = run_sub("""
        import numpy as np
        from repro.core.state import StateCoordinator
        from repro.core.synthetic import ScenarioConfig, build_scenario
        from repro.etl import (CollectSink, EventChunkSource, EventSource,
                               METLApp, Pipeline, SchemaEvolved)
        from repro.launch.mesh import make_etl_mesh

        def evolve_event(reg):
            o = reg.domain.schema_ids()[0]
            v = reg.domain.latest_version(o)
            keep = tuple(a.name for a in reg.domain.get(o, v).attributes)[1:]
            return SchemaEvolved(tree="domain", schema_id=o, keep=keep,
                                 add=("mid",)), o, v

        # oracle: out-of-band on the sharded engine
        sc = build_scenario(ScenarioConfig(seed=83))
        coord = StateCoordinator(sc.registry, sc.dpm)
        app = METLApp(coord, engine="sharded", mesh=make_etl_mesh(4))
        src = EventSource(sc.registry, seed=5)
        ev, o, v = evolve_event(coord.registry)
        rows_oob = []
        for k in range(4):
            if k == 2:
                def mutate(r):
                    r.evolve(r.domain, o, keep=list(ev.keep), add=list(ev.add))
                    return ("added_domain", o, v + 1)
                coord.apply_update(mutate)
                app.refresh()
            rows_oob.extend(app.consume(src.slice_columnar(k * 64, 64)))

        # in-band, sync and async
        for async_consume in (False, True):
            sc2 = build_scenario(ScenarioConfig(seed=83))
            coord2 = StateCoordinator(sc2.registry, sc2.dpm)
            app2 = METLApp(coord2, engine="sharded", mesh=make_etl_mesh(4))
            ev2, _, _ = evolve_event(coord2.registry)
            sink = CollectSink()
            Pipeline(EventChunkSource(EventSource(sc2.registry, seed=5),
                                      chunk_size=64, max_chunks=4,
                                      control={2: ev2}),
                     app2, [sink], async_consume=async_consume).run()
            assert len(sink.rows) == len(rows_oob) > 0
            for a, b in zip(rows_oob, sink.rows):
                assert a[0] == b[0] and a[3] == b[3]
                np.testing.assert_array_equal(a[1], b[1])
                np.testing.assert_array_equal(a[2], b[2])
            assert app2.stats["dispatches"] == 4  # 1 shard_map launch/chunk
        print("sharded in-band parity OK")
    """)
    assert "sharded in-band parity OK" in out


def test_scripted_control_source_wraps_any_source():
    """ScriptedControlSource injects the same mid-stream evolution over a
    plain ListSource, with identical results."""
    rows_oob, _ = _run_out_of_band(85, "fused", 4, 64, 2)

    sc = build_scenario(ScenarioConfig(seed=85))
    coord = StateCoordinator(sc.registry, sc.dpm)
    app = METLApp(coord)
    ev, _, _ = _evolve_event(coord.registry, 0, "mid")
    src = EventSource(sc.registry, seed=5)
    # chunks 0,1 pre-materialised at the old state; 2,3 must be generated
    # after the evolution, so use a live EventChunkSource underneath
    inner = EventChunkSource(src, chunk_size=64, max_chunks=4)
    sink = CollectSink()
    st = Pipeline(ScriptedControlSource(inner, {2: ev}), app, [sink]).run()
    assert st.control == 1
    _assert_rows_equal(rows_oob, sink.rows)


def test_control_in_list_source_stream():
    """A ControlEvent placed literally between chunks of a ListSource is
    applied at that boundary (and events/chunk accounting ignores it)."""
    sc = build_scenario(ScenarioConfig(seed=86))
    coord = StateCoordinator(sc.registry, sc.dpm)
    app = METLApp(coord)
    src = EventSource(sc.registry, seed=5)
    chunk = src.slice_columnar(0, 50)
    ev, _, _ = _evolve_event(coord.registry)
    s0 = coord.registry.state
    sink = CollectSink()
    st = Pipeline(ListSource([chunk, ev]), app, [sink]).run()
    assert st.chunks == 1 and st.control == 1 and st.events == 50
    assert coord.registry.state == s0 + 1


@pytest.mark.parametrize("async_consume", [False, True])
def test_inband_control_replays_parked_events_into_sinks(async_consume):
    """Events from the app's future are parked; an in-band control event
    brings the state up at the chunk boundary; the next chunk's lazy
    refresh replays them THROUGH the pipeline into the sinks (the PR-3
    parked-replay seam, now driven by the control plane)."""
    sc = build_scenario(ScenarioConfig(seed=88))
    coord = StateCoordinator(sc.registry, sc.dpm)
    app = METLApp(coord)
    src = EventSource(sc.registry, seed=5, p_duplicate=0.0)
    future = src.slice(0, 6)
    later = src.slice(50, 40)
    for e in future + later:
        e.state += 1  # both chunks speak the post-evolution state
    ev, _, _ = _evolve_event(coord.registry)
    sink = CollectSink()
    st = Pipeline(ListSource([future, ev, later]), app, [sink],
                  async_consume=async_consume).run()
    assert st.chunks == 2 and st.control == 1
    assert app.stats["parked"] == 6 and app.stats["replayed"] == 6
    # the replayed rows reached the sinks, ahead of the later chunk's rows
    want = METLApp(coord).consume_scalar(future)
    replay_keys = {e.key for e in future}
    got = [r for r in sink.rows if r[3] in replay_keys]
    assert len(got) == len(want) > 0
    assert st.rows == len(sink.rows)


def test_control_does_not_count_against_max_chunks():
    sc = build_scenario(ScenarioConfig(seed=86))
    coord = StateCoordinator(sc.registry, sc.dpm)
    app = METLApp(coord)
    ev, _, _ = _evolve_event(coord.registry)
    source = EventChunkSource(EventSource(sc.registry, seed=5), chunk_size=32,
                              max_chunks=4, control={1: ev})
    pipe = Pipeline(source, app, [CollectSink()])
    st1 = pipe.run(max_chunks=2)
    assert st1.chunks == 2 and st1.control == 1
    st2 = pipe.run()
    assert st2.chunks == 2 and st2.control == 0  # applied exactly once


# ---------------------------------------------------------------------------
# freeze / thaw (paper SS3.4 initial-load windows)
# ---------------------------------------------------------------------------


class TestFreezeThaw:
    def test_direct_apply_rejected_while_frozen(self):
        sc, coord = _world()
        ev, _, _ = _evolve_event(coord.registry)
        coord.apply(Freeze())
        with pytest.raises(RuntimeError):
            coord.apply(ev)
        coord.apply(Thaw())
        s0 = coord.registry.state
        coord.apply(ev)
        assert coord.registry.state == s0 + 1

    def test_deferred_schema_change_readmitted_by_thaw(self):
        sc, coord = _world()
        ev, _, _ = _evolve_event(coord.registry)
        s0 = coord.registry.state
        coord.apply(Freeze())
        snap = coord.apply(ev, defer_frozen=True)
        assert snap.i == s0  # nothing applied yet
        assert coord.deferred_control == (ev,)
        assert coord.registry.state == s0
        coord.apply(Thaw())
        assert coord.deferred_control == ()
        assert coord.registry.state == s0 + 1
        # the log records events in APPLICATION order: Freeze, Thaw, evolved
        kinds = [type(r.event).__name__ for r in coord.control_log]
        assert kinds == ["Freeze", "Thaw", "SchemaEvolved"]

    def test_freeze_thaw_during_running_pipeline(self):
        """A Freeze opens the window mid-stream; data chunks keep flowing; a
        schema change inside the window is deferred exactly as SS3.4
        prescribes; the Thaw re-admits it -- and the whole run matches the
        oracle that applies the evolution at the thaw boundary."""
        # oracle: evolution lands at chunk 3 (where the Thaw re-admits it)
        rows_oracle, _ = _run_out_of_band(87, "fused", 5, 64, 3)

        sc = build_scenario(ScenarioConfig(seed=87))
        coord = StateCoordinator(sc.registry, sc.dpm)
        app = METLApp(coord)
        ev, _, _ = _evolve_event(coord.registry, 0, "mid")
        s0 = coord.registry.state
        sink = CollectSink()

        class Probe(CollectSink):
            """Records the registry state as each chunk's rows fan out."""

            def __init__(self, coord):
                super().__init__()
                self.coord = coord
                self.states = []

            def write(self, rows):
                super().write(rows)
                self.states.append(self.coord.registry.state)

        probe = Probe(coord)
        st = Pipeline(
            EventChunkSource(EventSource(sc.registry, seed=5), chunk_size=64,
                             max_chunks=5,
                             control={1: Freeze(), 2: ev, 3: Thaw()}),
            app, [sink, probe],
        ).run()
        assert st.chunks == 5 and st.control == 3
        # data flowed inside the window at the frozen state; the evolution
        # only landed at the thaw
        assert probe.states == [s0, s0, s0, s0 + 1, s0 + 1]
        assert coord.registry.state == s0 + 1
        _assert_rows_equal(rows_oracle, sink.rows)
        kinds = [type(r.event).__name__ for r in coord.control_log]
        assert kinds == ["Freeze", "Thaw", "SchemaEvolved"]


# ---------------------------------------------------------------------------
# epoch pinning
# ---------------------------------------------------------------------------


def test_dense_chunk_exposes_pinned_epoch():
    sc, coord = _world()
    app = METLApp(coord)
    src = EventSource(sc.registry, seed=5, p_duplicate=0.0)
    dense = app.engine.densify(app.triage(src.slice_columnar(0, 40)))
    epoch = dense.epoch
    assert epoch == coord.registry.state
    ev, _, _ = _evolve_event(coord.registry)
    coord.apply(ev)  # evicts + bumps
    assert dense.epoch == epoch == coord.registry.state - 1  # metl: allow[epoch-pin-escape] this test IS the pin: asserting the in-flight chunk's epoch survives the mutation
    # the in-flight chunk still maps, against its own epoch's plan
    rows = app.engine.emit(app.engine.dispatch(dense))
    assert len(rows) > 0


# ---------------------------------------------------------------------------
# satellites: hook leak, bump_state, equivalence-index cache
# ---------------------------------------------------------------------------


def test_evict_hook_list_does_not_leak_dead_apps():
    """REGRESSION: every METLApp registered a strong closure on the
    coordinator with no deregistration, so repeatedly constructing apps
    (the bench/test pattern) grew the hook list and evicted dead apps
    forever.  Weak registration prunes collected apps at the next evict."""
    sc, coord = _world()
    for _ in range(12):
        METLApp(coord)
    gc.collect()
    ev, _, _ = _evolve_event(coord.registry, 0, "h1")
    coord.apply(ev)  # eviction fan-out prunes the corpses
    assert coord.n_evict_hooks == 0
    app = METLApp(coord)
    ev2, _, _ = _evolve_event(coord.registry, 1, "h2")
    coord.apply(ev2)
    assert coord.n_evict_hooks == 1  # the live app stays registered
    assert app.stats["evictions"] == 1
    # non-weak hooks (plain callables) are kept as before
    fired = []
    coord.on_evict(lambda i: fired.append(i))
    ev3, _, _ = _evolve_event(coord.registry, 2, "h3")
    coord.apply(ev3)
    assert fired == [coord.registry.state]
    assert coord.n_evict_hooks == 2


def test_registry_bump_state_public():
    sc, coord = _world()
    s0 = coord.registry.state
    assert coord.registry.bump_state() == s0 + 1
    assert coord.registry.state == s0 + 1


def test_equivalence_index_invalidated_on_version_changes():
    """The cached uid->equiv index must follow version adds AND deletes."""
    sc, coord = _world()
    reg = coord.registry
    o = reg.domain.schema_ids()[0]
    v = reg.domain.latest_version(o)
    first = reg.domain.get(o, v).attributes[0]
    root = reg.domain.equivalence_root(first.uid)  # build + cache the index
    sv = reg.evolve(reg.domain, o, keep=[first.name])
    kept = sv.attributes[0]
    # the new version's kept attribute chains to the same root
    assert kept.equiv == first.uid
    assert reg.domain.equivalence_root(kept.uid) == root
    reg.delete_version(reg.domain, o, v + 1)
    # the deleted attribute no longer appears in the rebuilt index
    assert kept.uid not in reg.domain._equiv_index()
    assert reg.domain.equivalence_root(first.uid) == root
