"""The streaming Pipeline API: Source -> METLApp -> [Sink, ...].

Covers the acceptance surface of the pipeline tentpole:
  * sync pipeline == direct chunked consume (rows, order, stats);
  * double-buffered async consume is bit-exact with sync (rows AND stats,
    dispatches/chunk unchanged at 1) for the fused and legacy engines;
  * fan-out: every sink sees every row; TableSink materialises per-entity
    tables; TokenizerSink produces in-vocab prompts;
  * backpressure: a full() sink stops the pull, and the async lookahead
    chunk is carried across run() calls so no event is ever lost;
  * BatcherSink turns run() into "pull until the trainer has a batch".
"""

import numpy as np
import pytest

from repro.core.state import StateCoordinator
from repro.core.synthetic import ScenarioConfig, build_scenario
from repro.etl import (
    BatcherSink,
    CanonicalBatcher,
    CollectSink,
    EventChunkSource,
    EventSource,
    ListSource,
    METLApp,
    Pipeline,
    TableSink,
    TokenizerSink,
)


@pytest.fixture
def world():
    sc = build_scenario(ScenarioConfig(seed=51))
    coord = StateCoordinator(sc.registry, sc.dpm)
    src = EventSource(sc.registry, seed=2, p_duplicate=0.1)
    return sc, coord, src


def _chunks(src, n, size=100):
    return [src.slice(k * size, size) for k in range(n)]


def _assert_rows_equal(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x[0] == y[0] and x[3] == y[3]
        np.testing.assert_array_equal(x[1], y[1])
        np.testing.assert_array_equal(x[2], y[2])


STAT_KEYS = ("events", "duplicates", "mapped", "empty", "dispatches", "stale")


def test_sync_pipeline_matches_direct_consume(world):
    sc, coord, src = world
    chunks = _chunks(src, 4)

    direct = METLApp(coord, engine="fused")
    rows_direct = [r for c in chunks for r in direct.consume(c)]

    app = METLApp(coord, engine="fused")
    sink = CollectSink()
    st = Pipeline(ListSource(chunks), app, [sink]).run()
    _assert_rows_equal(rows_direct, sink.rows)
    assert st.chunks == 4 and st.events == 400 and st.rows == len(sink.rows)
    for k in STAT_KEYS:
        assert direct.stats[k] == app.stats[k], k


@pytest.mark.parametrize("device_densify", [False, True], ids=["fused", "device"])
@pytest.mark.parametrize("densify_thread", [False, True])
def test_async_bit_exact_with_sync(world, device_densify, densify_thread):
    """The double buffer changes wall-clock, never results: same rows, same
    order, same stats, still one dispatch per chunk, with host and with
    device densify."""
    sc, coord, src = world
    chunks = _chunks(src, 5)

    app_s = METLApp(coord, device_densify=device_densify)
    sink_s = CollectSink()
    Pipeline(ListSource(chunks), app_s, [sink_s]).run()

    app_a = METLApp(coord, device_densify=device_densify)
    sink_a = CollectSink()
    pipe = Pipeline(
        ListSource(chunks), app_a, [sink_a],
        async_consume=True, densify_thread=densify_thread,
    )
    st = pipe.run()
    pipe.close()

    assert sink_s.rows and st.chunks == 5
    _assert_rows_equal(sink_s.rows, sink_a.rows)
    for k in STAT_KEYS:
        assert app_s.stats[k] == app_a.stats[k], k
    assert app_a.stats["dispatches"] == 5  # 1 per chunk, unchanged


def test_fanout_two_sinks(world):
    sc, coord, src = world
    chunks = _chunks(src, 3)
    app = METLApp(coord, engine="fused")
    dw = TableSink()
    ml = TokenizerSink(vocab=512, max_len=12)
    collect = CollectSink()
    Pipeline(ListSource(chunks), app, [dw, ml, collect], async_consume=True).run()

    n_rows = len(collect.rows)
    assert n_rows > 0
    # every sink saw every row
    assert sum(len(v) for v in dw.tables.values()) == n_rows
    assert len(ml.prompts) == n_rows
    for p in ml.prompts:
        assert 1 <= len(p) <= 12
        assert all(1 <= t < 512 for t in p)
    tables = dw.to_arrays()
    for (r, w), t in tables.items():
        n_out = len(coord.registry.range.get(r, w).uids)
        assert t["values"].shape == (len(dw.tables[(r, w)]), n_out)
        assert t["keys"].dtype == np.int64


def test_backpressure_full_sink_stops_pull(world):
    sc, coord, src = world
    app = METLApp(coord, engine="fused")
    sink = TokenizerSink(vocab=512, limit=30)
    source = EventChunkSource(src, chunk_size=100, max_chunks=10)
    st = Pipeline(source, app, [sink], async_consume=True).run()
    assert sink.full() and len(sink.prompts) == 30
    assert st.chunks < 10  # the bounded sink gated the stream


def test_async_lookahead_survives_stop_no_event_loss(world):
    """A pipeline stopped by a full sink has one triaged lookahead chunk in
    flight; resuming must map it (not drop it), so total output matches an
    uninterrupted reference run."""
    sc, coord, src = world
    chunks = _chunks(src, 6)

    ref_app = METLApp(coord, engine="fused")
    rows_ref = [r for c in chunks for r in ref_app.consume(c)]

    app = METLApp(coord, engine="fused")
    bounded = TokenizerSink(vocab=512, limit=25)  # trips mid-stream
    collect = CollectSink()
    pipe = Pipeline(ListSource(chunks), app, [bounded, collect], async_consume=True)
    st1 = pipe.run()
    assert bounded.full() and st1.chunks < 6
    assert pipe._pending is not None  # one lookahead chunk parked

    bounded.limit = None  # drain the backpressure and resume
    st2 = pipe.run()
    pipe.close()
    assert st1.chunks + st2.chunks == 6
    _assert_rows_equal(rows_ref, collect.rows)
    for k in STAT_KEYS:
        assert ref_app.stats[k] == app.stats[k], k


def test_pending_also_flushed_by_sync_resume(world):
    sc, coord, src = world
    chunks = _chunks(src, 4)
    ref_app = METLApp(coord, engine="fused")
    rows_ref = [r for c in chunks for r in ref_app.consume(c)]

    app = METLApp(coord, engine="fused")
    bounded = CollectSink(limit=1)
    collect = CollectSink()
    pipe = Pipeline(ListSource(chunks), app, [bounded, collect], async_consume=True)
    pipe.run()
    assert pipe._pending is not None
    bounded.limit = None
    pipe.async_consume = False  # resume on the sync path
    pipe.run()
    _assert_rows_equal(rows_ref, collect.rows)


def test_pending_not_flushed_into_still_full_sink(world):
    """Resuming on the sync path while the bounded sink is STILL full must
    keep the pending chunk parked (flushing would drop its rows in the full
    sink), matching the async path's behaviour."""
    sc, coord, src = world
    chunks = _chunks(src, 4)
    app = METLApp(coord, engine="fused")
    bounded = CollectSink(limit=1)
    pipe = Pipeline(ListSource(chunks), app, [bounded], async_consume=True)
    pipe.run()
    assert pipe._pending is not None
    pipe.async_consume = False
    st = pipe.run()  # sink still full: nothing processed, pending kept
    assert st.chunks == 0 and pipe._pending is not None


def test_max_chunks_budget_includes_pending(world):
    sc, coord, src = world
    chunks = _chunks(src, 5)
    app = METLApp(coord, engine="fused")
    bounded = CollectSink(limit=1)
    pipe = Pipeline(ListSource(chunks), app, [bounded], async_consume=True)
    st1 = pipe.run()  # stops immediately: chunk 1 fanned out, chunk 2 pending
    assert st1.chunks == 1 and pipe._pending is not None
    bounded.limit = None
    st2 = pipe.run(max_chunks=2)  # budget covers pending + ONE fresh pull
    assert st2.chunks == 2
    assert st1.chunks + st2.chunks + len(list(pipe.source.chunks())) == 5


def test_consume_scalar_lazy_refresh_buffers_replay(world):
    """consume_scalar's lazy refresh must buffer replayed rows like every
    other lazy-refresh path, not drop them."""
    sc, coord, src = world
    app = METLApp(coord, engine="fused")
    evs = EventSource(sc.registry, seed=13, p_duplicate=0.0).slice(0, 5)
    for e in evs:
        e.state += 1
    app.consume(evs)
    assert app.stats["parked"] == 5
    o = coord.registry.domain.schema_ids()[0]
    v = coord.registry.domain.latest_version(o)

    def mutate(reg):
        keep = [a.name for a in reg.domain.get(o, v).attributes]
        reg.evolve(reg.domain, o, keep=keep)
        return ("added_domain", o, v + 1)

    coord.apply_update(mutate)  # evicts; app not yet refreshed
    want = METLApp(coord).consume_scalar(evs)
    app.consume_scalar([])  # triggers the lazy refresh + replay
    got = app.take_replayed()
    assert app.stats["replayed"] == 5
    assert len(got) == len(want)


def test_event_chunk_source_cursor_persists(world):
    sc, coord, src = world
    source = EventChunkSource(src, chunk_size=64, max_chunks=4)
    first = list(source.chunks())
    assert len(first) == 4
    assert [e.key for e in first[1]][0] != [e.key for e in first[0]][0]
    # exhausted: lifetime bound reached
    assert list(source.chunks()) == []


def test_batcher_sink_pulls_until_batch_ready(world):
    sc, coord, src = world
    app = METLApp(coord, engine="fused")
    batcher = CanonicalBatcher(vocab=512, seq_len=16, batch_size=2)
    pipe = Pipeline(
        EventChunkSource(src, chunk_size=100),
        app,
        [BatcherSink(batcher)],
        async_consume=True,
    )
    for _ in range(3):
        while not batcher.ready():
            pipe.run()
        batch = batcher.next_batch()
        assert batch["tokens"].shape == (2, 16)
        assert (batch["labels"][:, :-1] == batch["tokens"][:, 1:]).all()
    pipe.close()


def test_state_bump_mid_stream_replays_into_sinks(world):
    """A coordinator state bump between chunks evicts the plan; the next
    chunk's triage refreshes lazily and replays any parked events -- those
    rows must reach the sinks, not vanish (the staged path drains
    take_replayed())."""
    sc, coord, _ = world
    src = EventSource(sc.registry, seed=12, p_duplicate=0.0)
    parked_chunk = src.slice(0, 6)
    for e in parked_chunk:
        e.state += 1  # all from the app's future

    app = METLApp(coord, engine="fused")
    sink = CollectSink()
    pipe = Pipeline(ListSource([parked_chunk]), app, [sink], async_consume=True)
    pipe.run()
    assert sink.rows == [] and app.stats["parked"] == 6
    # a real coordinator update: bumps state and evicts the app's plan;
    # the replay happens inside the next run's lazy refresh
    o = coord.registry.domain.schema_ids()[0]
    v = coord.registry.domain.latest_version(o)

    def mutate(reg):
        keep = [a.name for a in reg.domain.get(o, v).attributes]
        reg.evolve(reg.domain, o, keep=keep)
        return ("added_domain", o, v + 1)

    coord.apply_update(mutate)
    want = METLApp(coord).consume_scalar(parked_chunk)
    later_chunk = src.slice(50, 40)  # generated at the NEW state
    pipe2 = Pipeline(ListSource([later_chunk]), app, [sink], async_consume=True)
    st = pipe2.run()
    pipe2.close()
    assert app.stats["replayed"] == 6
    replay_keys = {e.key for e in parked_chunk}
    got_replay = [r for r in sink.rows if r[3] in replay_keys]
    assert len(got_replay) == len(want) > 0
    assert st.rows == len(sink.rows)  # replayed rows are accounted too


def test_empty_source(world):
    sc, coord, src = world
    app = METLApp(coord, engine="fused")
    st = Pipeline(ListSource([]), app, [CollectSink()], async_consume=True).run()
    assert st.chunks == 0 and st.rows == 0
