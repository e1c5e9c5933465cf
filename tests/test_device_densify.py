"""On-device densification (PR-6 tentpole): the raw columnar (uid, value)
items cross host->device in ONE packed int32 transfer and are resolved +
densified + mapped inside the single fused dispatch
(repro.kernels.densify_map / ops.dmm_apply_columnar).

Covers the acceptance surface:
  * device consume == host consume, bit-exact rows AND stats, over the real
    synthetic stream (duplicates + stale events) at several chunk sizes;
  * property test (hypothesis): random payloads -- empty / all-None /
    foreign-uid / out-of-range-uid / bad (non-numeric) values -- across
    engine pairs and chunk sizes, device rows+stats == host oracle;
  * parked-event replay (events from the app's future) and an epoch
    transition (live schema evolution mid-stream) stay bit-exact;
  * out-of-range uid regression: never an index error, clamped out of the
    scatter, counted under stats["unknown_uid"] IDENTICALLY across the
    blocks / fused / fused+device engines, for a uid in the uid tables'
    capacity padding as for one past their end;
  * one program across registry states: ten schema evolutions keep the
    plan's table shapes, so the columnar program never recompiles;
  * accounting: the device path makes exactly 1 host->device transfer and
    1 dispatch per chunk (host path: 4 transfers); small chunks fall back
    to the host scatter below min_device_events;
  * the Pallas kernel (interpret mode on CPU) against the pure-jnp
    reference on the raw kernel contract.

The sharded device path needs a multi-device topology, so its parity case
runs in a subprocess via the shared forced-topology harness
(tests/_subproc.py), like test_sharded_engine.py.
"""

import numpy as np
import pytest

from _subproc import run_sub
from repro.core.state import StateCoordinator
from repro.core.synthetic import ScenarioConfig, build_scenario
from repro.etl import (
    CDCEvent,
    CollectSink,
    EventChunkSource,
    EventSource,
    FusedEngine,
    METLApp,
    Pipeline,
    columnarize,
)

STAT_KEYS = ("events", "duplicates", "mapped", "empty", "dispatches", "stale",
             "dead_lettered", "bad_payload", "unknown_uid", "parked",
             "replayed")


@pytest.fixture(scope="module")
def world():
    sc = build_scenario(ScenarioConfig(seed=71))
    coord = StateCoordinator(sc.registry, sc.dpm)
    return sc, coord


def _device_app(coord, min_device_events=0):
    """A fused app forced onto the device-densify path (no small-chunk
    fallback unless asked)."""
    return METLApp(
        coord,
        engine=FusedEngine(device_densify=True,
                           min_device_events=min_device_events),
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


def _assert_stats_equal(a, b, keys=STAT_KEYS):
    for k in keys:
        assert a.stats[k] == b.stats[k], k


# ---------------------------------------------------------------------------
# stream parity: device == host, rows and stats, several chunk sizes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk_size", [3, 40, 200])
def test_device_consume_parity_stream(world, chunk_size):
    sc, coord = world
    src = EventSource(sc.registry, seed=5, p_duplicate=0.1, p_stale=0.05)
    host = METLApp(coord, engine="fused")
    dev = _device_app(coord)
    for k in range(4):
        chunk = src.slice_columnar(k * chunk_size, chunk_size)
        _assert_rows_equal(host.consume(chunk), dev.consume(chunk))
    _assert_stats_equal(host, dev)
    assert host.stats["mapped"] > 0  # the parity is not vacuous


def test_device_path_is_actually_taken(world):
    """The forced device app really routes through ColumnarDense -- exactly
    one host->device transfer per chunk vs the host path's four."""
    sc, coord = world
    src = EventSource(sc.registry, seed=6, p_duplicate=0.0)
    host = METLApp(coord, engine="fused")
    dev = _device_app(coord)
    chunk = src.slice_columnar(0, 64)
    for app, transfers in ((host, 4), (dev, 1)):
        t0, d0 = app.stats["transfers"], app.stats["dispatches"]
        app.consume(chunk)
        assert app.stats["transfers"] - t0 == transfers
        assert app.stats["dispatches"] - d0 == 1


def test_small_chunk_falls_back_to_host_scatter(world):
    """Below min_device_events the device app uses the host scatter (the
    pack + kernel overhead is not worth 3 events) -- and stays bit-exact."""
    sc, coord = world
    src = EventSource(sc.registry, seed=7, p_duplicate=0.0)
    host = METLApp(coord, engine="fused")
    dev = _device_app(coord, min_device_events=32)
    chunk = src.slice_columnar(0, 5)
    t0 = dev.stats["transfers"]
    _assert_rows_equal(host.consume(chunk), dev.consume(chunk))
    assert dev.stats["transfers"] - t0 == 4  # host-path accounting


# ---------------------------------------------------------------------------
# property test: adversarial payloads across engines x chunk sizes
# ---------------------------------------------------------------------------


def test_device_densify_hypothesis(world):
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    sc, coord = world
    reg = sc.registry
    blocks = reg.domain.blocks()
    state = reg.state

    def events_strategy():
        val = st.one_of(
            st.none(),
            st.integers(-10**6, 10**6),
            st.floats(allow_nan=False, allow_infinity=False, width=32),
            st.just("bad"),  # non-numeric -> dead-letter path
        )

        @st.composite
        def one_event(draw, key):
            sv = blocks[draw(st.integers(0, len(blocks) - 1))]
            payload = {}
            for u in sv.uids:
                if draw(st.booleans()):
                    payload[u] = draw(val)
            if draw(st.booleans()):
                # foreign / hole / out-of-range uid mixed in
                payload[draw(st.sampled_from([0, 10**7, 2**40]))] = draw(
                    st.floats(allow_nan=False, allow_infinity=False, width=32)
                )
            return _mk_event(key, sv.schema_id, sv.version, payload, state)

        return st.lists(st.integers(0, 3), min_size=0, max_size=24).flatmap(
            lambda ks: st.tuples(*(one_event(key=i) for i in range(len(ks))))
        )

    @given(events_strategy())
    @settings(max_examples=25, deadline=None)
    def check(events):
        chunk = columnarize(list(events))
        host = METLApp(coord, engine="fused")
        dev = _device_app(coord)
        blk = METLApp(coord, engine="blocks")
        rows_h = host.consume(chunk)
        _assert_rows_equal(rows_h, dev.consume(chunk))
        _assert_stats_equal(host, dev)
        # the blocks engine agrees on the shared accounting too
        blk.consume(chunk)
        for k in ("events", "mapped", "empty", "bad_payload", "unknown_uid"):
            assert host.stats[k] == blk.stats[k], k

    check()


def test_device_densify_adversarial_deterministic(world):
    """The hypothesis mix, seeded (runs even without hypothesis installed):
    random payload subsets with None / bad / foreign-uid / out-of-range-uid
    values over varying chunk sizes, device == host rows AND stats."""
    sc, coord = world
    reg = sc.registry
    blocks = reg.domain.blocks()
    state = reg.state
    rng = np.random.default_rng(42)
    bad_uids = [0, 10**7, 2**40, -3]
    for trial in range(30):
        n = int(rng.integers(0, 25))
        events = []
        for i in range(n):
            sv = blocks[rng.integers(0, len(blocks))]
            payload = {}
            for u in sv.uids:
                r = rng.random()
                if r < 0.4:
                    continue
                elif r < 0.55:
                    payload[u] = None
                elif r < 0.62:
                    payload[u] = "bad"
                else:
                    payload[u] = float(rng.normal())
            if rng.random() < 0.3:
                payload[bad_uids[rng.integers(0, len(bad_uids))]] = 1.0
            events.append(_mk_event(i, sv.schema_id, sv.version, payload, state))
        chunk = columnarize(events)
        host = METLApp(coord, engine="fused")
        dev = _device_app(coord)
        _assert_rows_equal(host.consume(chunk), dev.consume(chunk))
        _assert_stats_equal(host, dev)


# ---------------------------------------------------------------------------
# replay + epoch transition
# ---------------------------------------------------------------------------


def test_device_parked_replay_parity():
    """Events from the app's future park, then replay through the device
    path after the state bump -- bit-exact with a fresh host app."""
    sc = build_scenario(ScenarioConfig(seed=72))
    coord = StateCoordinator(sc.registry, sc.dpm)
    src = EventSource(sc.registry, seed=8, p_duplicate=0.0)
    dev = _device_app(coord)
    events = src.slice(0, 40)
    for e in events[:7]:
        e.state += 1  # from the future
    dev.consume(events)
    assert dev.stats["parked"] == 7
    coord.registry.bump_state()
    replayed = dev.refresh()
    assert dev.stats["replayed"] == 7
    fresh = METLApp(coord, engine="fused")
    _assert_rows_equal(replayed, fresh.consume(events[:7]))


def test_device_epoch_transition_parity():
    """A live in-band schema evolution mid-stream: the device-densify
    pipeline emits exactly the host-densify pipeline's rows."""
    from repro.etl.control import SchemaEvolved

    def _run(device_densify):
        sc = build_scenario(ScenarioConfig(seed=73))
        coord = StateCoordinator(sc.registry, sc.dpm)
        reg = sc.registry
        o = reg.domain.schema_ids()[0]
        v = reg.domain.latest_version(o)
        keep = tuple(a.name for a in reg.domain.get(o, v).attributes)[1:]
        ev = SchemaEvolved(tree="domain", schema_id=o, keep=keep, add=("dd",))
        app = METLApp(coord, engine="fused", device_densify=device_densify)
        sink = CollectSink()
        Pipeline(
            EventChunkSource(EventSource(reg, seed=9), chunk_size=64,
                             max_chunks=6, control={3: ev}),
            app, [sink], async_consume=True,
        ).run()
        return sink.rows, app

    rows_h, app_h = _run(False)
    rows_d, app_d = _run(True)
    assert len(rows_h) > 0
    _assert_rows_equal(rows_h, rows_d)
    _assert_stats_equal(app_h, app_d,
                        keys=("events", "mapped", "empty", "dispatches"))


# ---------------------------------------------------------------------------
# out-of-range uid regression (satellite 1)
# ---------------------------------------------------------------------------


def test_out_of_range_uid_never_crashes_and_is_counted(world):
    sc, coord = world
    reg = sc.registry
    o = reg.domain.schema_ids()[0]
    v = reg.domain.versions(o)[-1]
    uids = reg.domain.get(o, v).uids
    s = reg.state
    evs = [
        _mk_event(1, o, v, {uids[0]: 2.0, 2**40: 1.0}, s),  # beyond the table
        _mk_event(2, o, v, {uids[1]: 3.0, -5: 1.0}, s),     # negative
        _mk_event(3, o, v, {10**7: 4.0}, s),                # only unknowns
        _mk_event(4, o, v, {uids[0]: 5.0}, s),              # clean
    ]
    stats = {}
    rows = {}
    for name, app in (
        ("blocks", METLApp(coord, engine="blocks")),
        ("fused", METLApp(coord, engine="fused")),
        ("device", _device_app(coord)),
    ):
        rows[name] = app.consume(columnarize(evs))
        stats[name] = {k: app.stats[k]
                       for k in ("unknown_uid", "mapped", "empty", "events")}
        assert app.stats["unknown_uid"] == 3, name
    assert stats["blocks"] == stats["fused"] == stats["device"]
    _assert_rows_equal(rows["fused"], rows["device"])
    _assert_rows_equal(rows["fused"], rows["blocks"])


@pytest.mark.parametrize("where", ["in_padding", "past_capacity"])
def test_uid_outside_the_plan_is_dropped_and_counted(world, where):
    """The uid tables run past the largest known uid to their capacity
    bucket.  A uid in that padding is dropped and counted under
    ``stats["unknown_uid"]`` exactly like a uid past the table's end, on
    every engine: blocks, host scatter, device densify and the device
    app's small-chunk host fallback."""
    sc, coord = world
    reg = sc.registry
    o = reg.domain.schema_ids()[0]
    v = reg.domain.latest_version(o)
    uids = reg.domain.get(o, v).uids
    s = reg.state
    probe = METLApp(coord, engine="fused")
    probe.ensure_ready()
    plan = probe.engine.plan
    known = int(np.flatnonzero(plan.uid_col >= 0).max()) + 1
    cap = plan.uid_slot.size
    assert known < cap and np.all(plan.uid_slot[known:] == -1)
    assert np.all(plan.uid_col[known:] == -1)
    stray = known if where == "in_padding" else cap
    clean = [_mk_event(1, o, v, {uids[0]: 2.0, uids[1]: 3.0}, s),
             _mk_event(2, o, v, {uids[2]: 4.0}, s)]
    dirty = [_mk_event(1, o, v, {uids[0]: 2.0, stray: 9.0, uids[1]: 3.0}, s),
             _mk_event(2, o, v, {stray: 1.0, uids[2]: 4.0}, s)]
    for name, make in (
        ("blocks", lambda: METLApp(coord, engine="blocks")),
        ("fused", lambda: METLApp(coord, engine="fused")),
        ("device", lambda: _device_app(coord)),
        ("fallback", lambda: _device_app(coord, min_device_events=32)),
    ):
        want, got = make(), make()
        rows_want = want.consume(columnarize(clean))
        t0 = got.stats["transfers"]
        rows_got = got.consume(columnarize(dirty))
        assert rows_want, name
        _assert_rows_equal(rows_want, rows_got)
        if name in ("device", "fallback"):  # the path the name promises
            assert got.stats["transfers"] - t0 == (1 if name == "device" else 4)
        assert (want.stats["unknown_uid"], got.stats["unknown_uid"]) == (0, 2), name


def test_one_program_across_registry_states():
    """Ten in-band schema evolutions, each adding fresh uids and a block,
    leave the plan's device tables at one shape: the same-shaped chunk
    after each reuses the columnar program compiled before the first, and
    its rows equal the host scatter's at every state."""
    from repro.core.dmm_jax import bucket_rows
    from repro.etl.control import SchemaEvolved
    from repro.kernels import ops

    sc = build_scenario(ScenarioConfig(n_schemas=12, versions_per_schema=3, seed=74))
    coord = StateCoordinator(sc.registry, sc.dpm)
    reg = sc.registry
    dev = _device_app(coord)
    host = METLApp(coord, engine="fused")
    o = reg.domain.schema_ids()[0]  # never evolved: its events keep one shape
    v = reg.domain.latest_version(o)
    uids = reg.domain.get(o, v).uids
    program = ops._columnar_program("ref", 0.0, False)  # what dev runs on CPU

    def consume(step):
        evs = [
            _mk_event(1000 * step + i, o, v,
                      {u: float(1000 * step + 10 * i + j) for j, u in enumerate(uids)},
                      reg.state)
            for i in range(40)
        ]
        d0 = dev.stats["dispatches"]
        rows = dev.consume(columnarize(evs))
        assert dev.stats["dispatches"] - d0 == 1
        assert dev.stats["transfers"] == dev.stats["dispatches"]  # device path
        assert rows
        _assert_rows_equal(host.consume(columnarize(evs)), rows)

    def tables():
        p = dev.engine.plan
        return p.uid_slot_dev.shape, p.uid_col_dev.shape, p.src2d.shape

    def needed():
        p = dev.engine.plan
        return int(np.flatnonzero(p.uid_col >= 0).max()) + 1, p.n_blocks

    consume(0)
    shapes, cache, first = tables(), program._cache_size(), needed()
    assert shapes[0] == (bucket_rows(first[0]),)
    assert shapes[2][0] == bucket_rows(first[1])
    for step in range(1, 11):
        schema = reg.domain.schema_ids()[step]
        latest = reg.domain.latest_version(schema)
        keep = tuple(a.name for a in reg.domain.get(schema, latest).attributes)[1:]
        coord.apply(SchemaEvolved(tree="domain", schema_id=schema, keep=keep,
                                  add=(f"fresh{step}",)))
        consume(step)
        assert dev.engine.plan.state == reg.state
        assert tables() == shapes, step
        assert program._cache_size() == cache, step
    last = needed()
    # exact-length tables would have changed shape at every evolution
    assert last[0] > first[0] and last[1] == first[1] + 10
    info = dev.engine.info()
    assert info["table_shape_changes"] == 0 and info["rebuilds"] == 11
    assert (info["uid_capacity"], info["block_capacity"]) == (shapes[0][0], shapes[2][0])


# ---------------------------------------------------------------------------
# the raw kernel contract: Pallas interpret vs pure-jnp reference
# ---------------------------------------------------------------------------


def test_densify_map_kernel_matches_ref():
    from repro.kernels.densify_map import densify_map
    from repro.kernels.ref import densify_map_ref

    rng = np.random.default_rng(0)
    # W lane-aligned, n_blocks sublane-aligned (the ops caller pads both);
    # everything else is odd on purpose
    b, k, n_rows, n_blocks, w = 24, 7, 50, 8, 128
    slot2d = rng.integers(-1, 30, size=(b, k)).astype(np.int32)
    x2d = rng.normal(size=(b, k)).astype(np.float32)
    rows = rng.integers(0, b, size=n_rows).astype(np.int32)
    blks = rng.integers(0, n_blocks, size=n_rows).astype(np.int32)
    src2d = rng.integers(-1, 30, size=(n_blocks, w)).astype(np.int32)
    v_k, m_k = densify_map(slot2d, x2d, rows, blks, src2d, fill=0.5,
                           interpret=True)
    v_r, m_r = densify_map_ref(slot2d, x2d, rows, blks, src2d, fill=0.5)
    np.testing.assert_array_equal(np.asarray(v_k), np.asarray(v_r))
    np.testing.assert_array_equal(np.asarray(m_k), np.asarray(m_r))
    # duplicate slots: last writer (ascending item index) wins, like the
    # host scatter's fancy-index assignment
    slot2d[0, :] = 3
    x2d[0, :] = np.arange(k, dtype=np.float32)
    src2d[0, 0] = 3
    v_k, _ = densify_map(slot2d, x2d, np.zeros(8, np.int32),
                         np.zeros(8, np.int32), src2d, interpret=True)
    assert float(np.asarray(v_k)[0, 0]) == float(k - 1)


# ---------------------------------------------------------------------------
# sharded device densify (multi-device subprocess)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sharded_device_densify_parity_subprocess():
    out = run_sub("""
        import numpy as np
        from repro.core.state import StateCoordinator
        from repro.core.synthetic import ScenarioConfig, build_scenario
        from repro.etl import EventSource, METLApp
        from repro.launch.mesh import make_etl_mesh
        from repro.kernels import ops

        N = 4
        sc = build_scenario(ScenarioConfig(n_schemas=8, versions_per_schema=3, seed=74))
        coord = StateCoordinator(sc.registry, sc.dpm)
        mesh = make_etl_mesh(N)
        host = METLApp(coord, engine="sharded", mesh=mesh)
        dev = METLApp(coord, engine="sharded", mesh=mesh, device_densify=True)
        rep = METLApp(coord, engine="fused")
        src = EventSource(sc.registry, seed=9, p_duplicate=0.1)
        for k in range(3):
            chunk = src.slice_columnar(k * 120, 120)
            rows_r = rep.consume(chunk)
            rows_h = host.consume(chunk)
            b_ops, b_t = ops.dispatch_count, dev.stats["transfers"]
            rows_d = dev.consume(chunk)
            assert ops.dispatch_count - b_ops == 1  # one shard_map launch
            assert dev.stats["transfers"] - b_t == 1  # one packed buffer
            assert rows_r and len(rows_r) == len(rows_h) == len(rows_d)
            for a, b in zip(rows_h, rows_d):
                assert a[0] == b[0] and a[3] == b[3]
                np.testing.assert_array_equal(a[1], b[1])
                np.testing.assert_array_equal(a[2], b[2])
            for a, b in zip(rows_r, rows_d):
                assert a[0] == b[0] and a[3] == b[3]
                np.testing.assert_array_equal(a[1], b[1])
                np.testing.assert_array_equal(a[2], b[2])
        for k in ("events", "mapped", "empty", "unknown_uid", "dispatches"):
            assert host.stats[k] == dev.stats[k], k
        print("sharded device densify parity OK")
    """, devices=4)
    assert "sharded device densify parity OK" in out
