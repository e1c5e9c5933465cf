"""Vectorised densification: columnar (uid, value) event batches end-to-end.

Covers the acceptance surface of the columnar tentpole plus the hot-path
bugfix sweep that rides along:

  * ColumnarChunk structure: CSR offsets, None dropping, bad-value flags;
  * property test (hypothesis): columnar densify == dict-walk densify on
    random payloads including empty / all-None / foreign-uid / unmappable
    events, for the fused plan -- bit-exact DenseChunk fields;
  * consume parity: columnar chunks vs legacy event lists produce identical
    rows AND stats with host and with device densify (the sharded engine
    shares _chunk_layout and is parity-tested in test_sharded_engine.py);
  * non-numeric payload values (str / bool / Decimal) are routed to the
    dead-letter path with a counted stat -- identically on both densify
    paths --
    instead of crashing or silently truncating inside the float32 scatter;
  * Source.reset_offset: a finished ListSource / EventChunkSource cursor is
    resettable and re-slices deterministically (the dead-letter replay
    contract);
  * Pipeline.run(max_chunks=) / backpressure regressions: a full sink never
    makes the sync loop pull-and-drop a chunk, and a still-backpressured
    resume does not burn the pull budget.
"""

import decimal

import numpy as np
import pytest

from repro.core.state import StateCoordinator
from repro.core.synthetic import ScenarioConfig, build_scenario, scenario_event_chunks
from repro.etl import (
    CDCEvent,
    CollectSink,
    ColumnarChunk,
    EventChunkSource,
    EventSource,
    FusedEngine,
    ListSource,
    METLApp,
    Pipeline,
    columnarize,
    densify_chunk_dicts,
)

STAT_KEYS = ("events", "duplicates", "mapped", "empty", "dispatches", "stale",
             "dead_lettered", "bad_payload")


@pytest.fixture(scope="module")
def world():
    sc = build_scenario(ScenarioConfig(seed=61))
    coord = StateCoordinator(sc.registry, sc.dpm)
    return sc, coord


def _app(coord, device_densify):
    """A fused app densifying on the host, or on the device for every
    chunk (no small-chunk fallback, so a two-event chunk takes it too)."""
    if not device_densify:
        return METLApp(coord)
    return METLApp(
        coord, engine=FusedEngine(device_densify=True, min_device_events=0)
    )


def _mk_event(key, o, v, payload, state):
    return CDCEvent(key=key, op="c", state=state, schema_id=o, version=v,
                    before=None, after=payload, ts=key)


def _assert_rows_equal(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x[0] == y[0] and x[3] == y[3]
        np.testing.assert_array_equal(x[1], y[1])
        np.testing.assert_array_equal(x[2], y[2])


# ---------------------------------------------------------------------------
# ColumnarChunk structure
# ---------------------------------------------------------------------------


def test_columnarize_csr_structure(world):
    sc, coord = world
    o = sc.registry.domain.schema_ids()[0]
    v = sc.registry.domain.versions(o)[-1]
    uids = sc.registry.domain.get(o, v).uids
    s = sc.registry.state
    events = [
        _mk_event(0, o, v, {uids[0]: 1.5, uids[1]: None, uids[2]: 3.0}, s),
        _mk_event(1, o, v, {}, s),  # empty payload
        _mk_event(2, o, v, {uids[0]: None}, s),  # all-None
        _mk_event(3, o, v, {uids[1]: 7.0}, s),
    ]
    chunk = columnarize(events)
    assert len(chunk) == 4 and chunk.n_items == 3
    np.testing.assert_array_equal(chunk.event_offsets, [0, 2, 2, 2, 3])
    np.testing.assert_array_equal(chunk.uids, [uids[0], uids[2], uids[1]])
    np.testing.assert_array_equal(chunk.vals, np.asarray([1.5, 3.0, 7.0], np.float32))
    np.testing.assert_array_equal(chunk.keys, [0, 1, 2, 3])
    assert not chunk.bad.any()
    assert list(chunk) == events  # iterates the per-event metadata


def test_slice_columnar_matches_slice(world):
    sc, _ = world
    src = EventSource(sc.registry, seed=3, p_duplicate=0.1)
    chunk = src.slice_columnar(100, 50)
    events = src.slice(100, 50)
    assert isinstance(chunk, ColumnarChunk)
    assert [e.key for e in chunk] == [e.key for e in events]
    ref = columnarize(events)
    np.testing.assert_array_equal(chunk.uids, ref.uids)
    np.testing.assert_array_equal(chunk.vals, ref.vals)
    np.testing.assert_array_equal(chunk.event_offsets, ref.event_offsets)


# ---------------------------------------------------------------------------
# columnar densify == dict-walk densify (property test)
# ---------------------------------------------------------------------------


def _dense_equal(a, b):
    if a is None or b is None:
        assert a is None and b is None
        return
    for f in ("vals", "mask", "row_ids", "blk_ids", "out_keys"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f), err_msg=f)


def test_densify_oracle_deterministic_stream(world):
    """The real synthetic stream, both engines, chunk by chunk."""
    sc, coord = world
    src = EventSource(sc.registry, seed=5, p_duplicate=0.1)
    app = METLApp(coord, engine="fused")
    for k in range(4):
        tri = app.triage(src.slice_columnar(k * 200, 200))
        _dense_equal(
            app.engine.densify(tri),
            densify_chunk_dicts(app.engine.plan, tri.to_groups()),
        )


def test_densify_oracle_hypothesis(world):
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    sc, coord = world
    app = METLApp(coord, engine="fused")
    app.ensure_ready()
    plan = app.engine.plan
    reg = sc.registry
    blocks = reg.domain.blocks()
    state = reg.state

    def events_strategy():
        val = st.one_of(
            st.none(),
            st.integers(-10**6, 10**6),
            st.floats(allow_nan=False, allow_infinity=False, width=32),
        )

        @st.composite
        def one_event(draw, key):
            sv = blocks[draw(st.integers(0, len(blocks) - 1))]
            uids = list(sv.uids)
            # random subset of real attributes, possibly none, possibly a
            # foreign uid (another column's / unknown) mixed in
            payload = {}
            for u in uids:
                if draw(st.booleans()):
                    payload[u] = draw(val)
            if draw(st.booleans()):
                payload[draw(st.sampled_from([10**7, 0]))] = draw(val)
            return _mk_event(key, sv.schema_id, sv.version, payload, state)

        return st.lists(st.integers(0, 3), min_size=0, max_size=12).flatmap(
            lambda ks: st.tuples(*(one_event(key=i) for i in range(len(ks))))
        )

    @given(events_strategy())
    @settings(max_examples=30, deadline=None)
    def check(events):
        groups = {}
        for ev in events:
            groups.setdefault((ev.schema_id, ev.version), []).append(ev)
        _dense_equal(
            app.engine.densify(groups),  # legacy dict form -> columnar lift
            densify_chunk_dicts(plan, groups),
        )

    check()


@pytest.mark.parametrize("device_densify", [False, True], ids=["fused", "device"])
def test_consume_parity_columnar_vs_legacy(world, device_densify):
    """Same events, columnar chunk vs legacy list: identical rows and stats
    on both densify paths (the stats-parity acceptance assertion)."""
    sc, coord = world
    src = EventSource(sc.registry, seed=7, p_duplicate=0.1)
    a = _app(coord, device_densify)
    b = _app(coord, device_densify)
    for k in range(3):
        rows_legacy = a.consume(src.slice(k * 150, 150))
        rows_col = b.consume(src.slice_columnar(k * 150, 150))
        _assert_rows_equal(rows_legacy, rows_col)
    for k in STAT_KEYS:
        assert a.stats[k] == b.stats[k], k


def test_fused_blocks_stats_parity_on_columnar(world):
    """Across densify paths: the engine-side stats (mapped/empty) agree on
    the same columnar stream, host scatter against device densify."""
    sc, coord = world
    src = EventSource(sc.registry, seed=8, p_duplicate=0.05)
    apps = {"host": _app(coord, False), "device": _app(coord, True)}
    rows = {}
    for e, app in apps.items():
        rows[e] = [r for k in range(3) for r in app.consume(src.slice_columnar(k * 100, 100))]
    _assert_rows_equal(rows["host"], rows["device"])
    for k in ("events", "duplicates", "mapped", "empty", "stale"):
        assert apps["host"].stats[k] == apps["device"].stats[k], k
    assert apps["device"].stats["transfers"] == apps["device"].stats["dispatches"] == 3


def test_empty_and_unmappable_chunks(world):
    sc, coord = world
    app = METLApp(coord, engine="fused")
    assert app.consume([]) == []
    assert app.consume(columnarize([])) == []
    # all-None payloads: densifies (rows exist) but every row is empty;
    # pick a column that actually has mapping paths in the plan
    app.ensure_ready()
    (o, v) = next(iter(app.engine.plan.columns))
    uids = sc.registry.domain.get(o, v).uids
    evs = [_mk_event(10_000 + i, o, v, {uids[0]: None}, sc.registry.state)
           for i in range(4)]
    before = app.stats["dispatches"]
    rows = app.consume(columnarize(evs))
    assert rows == []
    assert app.stats["empty"] >= 4 and app.stats["dispatches"] == before + 1


# ---------------------------------------------------------------------------
# non-numeric payloads: dead-letter, counted, engine-identical
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("device_densify", [False, True], ids=["fused", "device"])
@pytest.mark.parametrize(
    "badval", ["3.5", True, decimal.Decimal("1.25"), object()]
)
def test_bad_payload_routed_to_dead_letter(world, device_densify, badval):
    sc, coord = world
    app = _app(coord, device_densify)
    o = sc.registry.domain.schema_ids()[0]
    v = sc.registry.domain.versions(o)[-1]
    uids = sc.registry.domain.get(o, v).uids
    s = sc.registry.state
    good = _mk_event(1, o, v, {uids[0]: 2.0}, s)
    bad = _mk_event(2, o, v, {uids[0]: 1.0, uids[1]: badval}, s)
    rows = app.consume([good, bad])
    # the good event still maps; the bad one is dead-lettered and counted
    assert [r[3] for r in rows] == [1]
    assert app.stats["bad_payload"] == 1
    assert app.stats["dead_lettered"] == 1
    assert app.dead_letter == [bad]
    # the offset-reset contract covers bad-payload events too
    assert app.reset_offset() == bad.ts
    assert app.dead_letter == []


def test_bad_payload_stats_identical_across_engines(world):
    sc, coord = world
    o = sc.registry.domain.schema_ids()[0]
    v = sc.registry.domain.versions(o)[-1]
    uids = sc.registry.domain.get(o, v).uids
    s = sc.registry.state
    evs = [
        _mk_event(1, o, v, {uids[0]: 5.0}, s),
        _mk_event(2, o, v, {uids[0]: "oops"}, s),
        _mk_event(3, o, v, {uids[1]: True}, s),
        _mk_event(4, o, v, {uids[1]: 6.0}, s),
    ]
    stats = {}
    for e, device_densify in (("host", False), ("device", True)):
        app = _app(coord, device_densify)
        app.consume(columnarize(evs))
        stats[e] = {k: app.stats[k] for k in ("bad_payload", "dead_lettered", "mapped")}
        assert app.stats["bad_payload"] == 2
    assert stats["host"] == stats["device"]


# ---------------------------------------------------------------------------
# Source.reset_offset: the dead-letter replay contract
# ---------------------------------------------------------------------------


def test_list_source_finished_cursor_resets(world):
    sc, _ = world
    src = EventSource(sc.registry, seed=9)
    chunks = [src.slice_columnar(k * 40, 40) for k in range(3)]
    source = ListSource(chunks)
    first = list(source.chunks())
    assert len(first) == 3 and list(source.chunks()) == []  # exhausted
    source.reset_offset(45)  # position inside chunk 1
    replayed = list(source.chunks())
    assert replayed == chunks[1:]  # same chunk objects, deterministic
    # past-the-end position: stays exhausted rather than re-delivering
    source.reset_offset(10_000)
    assert list(source.chunks()) == []
    # legacy event-list chunks honour the same contract
    legacy = ListSource([src.slice(0, 40), src.slice(40, 40)])
    list(legacy.chunks())
    legacy.reset_offset(0)
    assert len(list(legacy.chunks())) == 2


def test_event_chunk_source_reset_offset_realigns_grid(world):
    sc, _ = world
    src = EventSource(sc.registry, seed=10)
    source = EventChunkSource(src, chunk_size=32, max_chunks=3)
    first = list(source.chunks())
    assert len(first) == 3 and list(source.chunks()) == []  # lifetime bound
    source.reset_offset(40)  # inside the second slice -> grid-aligns to 32
    again = list(source.chunks())
    assert [e.key for e in again[0]] == [e.key for e in first[1]]
    assert len(again) == 2  # budget re-aimed, not burned by the replay


def test_dead_letter_replay_through_source_reset():
    """End to end: outdated events dead-letter, METLApp.reset_offset names
    the rewind position, source.reset_offset re-slices deterministically at
    the CURRENT state, and the re-delivered events map."""
    # own scenario: this test bumps the registry state
    sc = build_scenario(ScenarioConfig(seed=62))
    coord = StateCoordinator(sc.registry, sc.dpm)
    src = EventSource(sc.registry, seed=11, p_duplicate=0.0)
    app = METLApp(coord, engine="fused")
    stale = src.slice(64, 32)  # generated at the current state...
    coord.registry.bump_state()  # ...which the registry then leaves behind
    app.refresh()
    assert app.consume(stale) == []
    assert app.stats["dead_lettered"] == 32
    pos = app.reset_offset()
    assert pos == 64
    source = EventChunkSource(src, chunk_size=32, columnar=True)
    source.reset_offset(pos)
    sink = CollectSink()
    st = Pipeline(source, app, [sink]).run(max_chunks=1)
    assert st.chunks == 1 and st.events == 32
    assert len(sink.rows) > 0  # re-sliced at the new state: they map now
    assert app.stats["duplicates"] == 0  # dedup keys were forgotten


# ---------------------------------------------------------------------------
# run(max_chunks=) / backpressure regressions
# ---------------------------------------------------------------------------


def _pipe_world(world, n_chunks=4, size=80):
    sc, coord = world
    src = EventSource(sc.registry, seed=12, p_duplicate=0.0)
    chunks = [src.slice_columnar(k * size, size) for k in range(n_chunks)]
    ref = METLApp(coord, engine="fused")
    rows_ref = [r for c in chunks for r in ref.consume(c)]
    return coord, chunks, rows_ref


def test_sync_backpressure_never_drops_a_chunk(world):
    """REGRESSION: the sync loop used to pull the next chunk and THEN check
    full(), silently skipping that chunk's events on resume."""
    coord, chunks, rows_ref = _pipe_world(world)
    app = METLApp(coord, engine="fused")
    bounded = CollectSink(limit=1)  # trips after the first chunk's rows
    collect = CollectSink()
    pipe = Pipeline(ListSource(chunks), app, [bounded, collect])
    st1 = pipe.run()
    assert st1.chunks == 1  # stopped by backpressure
    bounded.limit = None
    st2 = pipe.run()
    assert st1.chunks + st2.chunks == len(chunks)  # nothing skipped
    _assert_rows_equal(rows_ref, collect.rows)


def test_stalled_run_keeps_budget_and_source_intact(world):
    """REGRESSION: a backpressured resume (pending retained because full())
    must neither burn the max_chunks budget nor advance the source."""
    coord, chunks, rows_ref = _pipe_world(world)
    app = METLApp(coord, engine="fused")
    bounded = CollectSink(limit=1)
    collect = CollectSink()
    pipe = Pipeline(ListSource(chunks), app, [bounded, collect], async_consume=True)
    st1 = pipe.run()
    assert st1.chunks == 1 and pipe._pending is not None
    # still backpressured: the resume is a no-op -- pending retained, zero
    # chunks mapped, zero chunks pulled from the source
    st_stall = pipe.run(max_chunks=2)
    assert st_stall.chunks == 0 and pipe._pending is not None
    bounded.limit = None
    st2 = pipe.run(max_chunks=2)  # budget: pending + exactly one fresh pull
    assert st2.chunks == 2
    st3 = pipe.run()  # drain the rest
    assert st1.chunks + st2.chunks + st3.chunks == len(chunks)
    _assert_rows_equal(rows_ref, collect.rows)


def test_scenario_event_chunks_helper(world):
    sc, coord = world
    chunks = scenario_event_chunks(sc, seed=13, chunk_size=50, n_chunks=3)
    assert len(chunks) == 3 and all(isinstance(c, ColumnarChunk) for c in chunks)
    legacy = scenario_event_chunks(sc, seed=13, chunk_size=50, n_chunks=3,
                                   columnar=False)
    assert [e.key for c in chunks for e in c] == [e.key for c in legacy for e in c]
    app = METLApp(coord, engine="fused")
    assert sum(len(app.consume(c)) for c in chunks) > 0
