"""Fused mapping engine: one device dispatch per chunk, bit-exact with the
pure Algorithm-6 oracle (``METLApp.consume_scalar``).

Covers the acceptance surface of the fused refactor:
  * fused consume == consume_scalar, and host densify == device densify,
    exactly;
  * multi-block columns (one schema feeding several business entities);
  * empty / null-block columns (events with no mapping paths);
  * padded lane widths (CDM wider than one 128-lane tile);
  * parked-event replay after a state bump flows through the rebuilt engine;
  * dispatch count is constant per chunk (not O(#blocks));
  * dispatch starts the outputs' read-back, and the double buffer stays
    bit-exact with it (the case is shared with the sharded engine's tests);
  * the Pallas segmented-gather kernel matches the jnp oracle.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.dmm import MappingMatrix, transform_to_dpm
from repro.core.dmm_jax import LANE, bucket_rows, compile_dpm, compile_fused
from repro.core.registry import Registry
from repro.core.state import StateCoordinator
from repro.core.synthetic import ScenarioConfig, build_scenario
from repro.etl import CollectSink, EventSource, ListSource, METLApp, Pipeline
from repro.kernels import ops


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _rows_as_payload_multiset(app, rows):
    """Canonical rows -> sorted multiset of ((r, w), sorted payload items)."""
    reg = app.coordinator.registry
    out = []
    for (r, w), vals, mask, _key in rows:
        uids = reg.range.get(r, w).uids
        payload = tuple(
            sorted((uid, float(vals[i])) for i, uid in enumerate(uids) if mask[i])
        )
        out.append(((r, w), payload))
    return sorted(out)


def _scalar_as_payload_multiset(msgs):
    return sorted(
        ((m.schema_id, m.version), tuple(sorted(m.payload.items())))
        for m in msgs
    )


def _unique(events):
    seen, out = set(), []
    for e in events:
        if e.key not in seen:
            seen.add(e.key)
            out.append(e)
    return out


def _multi_entity_world(cdm_attrs: int = 3):
    """A hand-built registry where ONE extraction schema feeds TWO business
    entities (multi-block column) and a second schema feeds none (null
    column) -- shapes the synthetic generator never produces."""
    reg = Registry()
    e0 = reg.add_schema(reg.range, 0, [f"e0.c{k}" for k in range(cdm_attrs)])
    e1 = reg.add_schema(reg.range, 1, [f"e1.c{k}" for k in range(cdm_attrs)])
    s0 = reg.add_schema(reg.domain, 0, ["s0.a0", "s0.a1", "s0.a2", "s0.a3"])
    reg.add_schema(reg.domain, 1, ["s1.a0", "s1.a1"])  # maps to nothing
    matrix = MappingMatrix(reg)
    # schema 0 -> entity 0 (two attrs) and entity 1 (two attrs): 2 blocks
    matrix.set(e0.uids[0], s0.uids[0], 1)
    matrix.set(e0.uids[1], s0.uids[1], 1)
    matrix.set(e1.uids[0], s0.uids[2], 1)
    matrix.set(e1.uids[1], s0.uids[3], 1)
    matrix.validate_one_to_one()
    dpm = transform_to_dpm(matrix)
    coord = StateCoordinator(reg, dpm)
    return reg, dpm, coord


# ---------------------------------------------------------------------------
# oracle bit-exactness
# ---------------------------------------------------------------------------


def test_fused_matches_scalar_oracle_synthetic():
    sc = build_scenario(ScenarioConfig(seed=41))
    coord = StateCoordinator(sc.registry, sc.dpm)
    app = METLApp(coord, engine="fused")
    src = EventSource(sc.registry, seed=4, p_duplicate=0.0)
    events = _unique(src.slice(0, 128))
    rows = app.consume(events)
    msgs = app.consume_scalar(events)
    assert _rows_as_payload_multiset(app, rows) == _scalar_as_payload_multiset(msgs)


def test_fused_matches_legacy_engine_exactly():
    """Same chunk through host and device densify: identical rows,
    identical order, identical stats and one dispatch each -- only the
    transfers differ (four operands against one packed buffer)."""
    sc = build_scenario(ScenarioConfig(seed=42))
    coord = StateCoordinator(sc.registry, sc.dpm)
    fused = METLApp(coord, engine="fused")
    device = METLApp(coord, engine="fused", device_densify=True)
    src = EventSource(sc.registry, seed=5)
    events = src.slice(0, 200)
    rf = fused.consume(events)
    rd = device.consume(events)
    assert len(rf) == len(rd) > 0
    for a, b in zip(rf, rd):
        assert a[0] == b[0] and a[3] == b[3]
        np.testing.assert_array_equal(a[1], b[1])
        np.testing.assert_array_equal(a[2], b[2])
    for k in ("events", "duplicates", "mapped", "empty", "dispatches"):
        assert fused.stats[k] == device.stats[k], k
    assert fused.stats["dispatches"] == 1
    assert (fused.stats["transfers"], device.stats["transfers"]) == (4, 1)


def test_multi_block_column_and_null_column():
    reg, dpm, coord = _multi_entity_world()
    app = METLApp(coord, engine="fused")
    src = EventSource(reg, seed=0, p_duplicate=0.0, p_null=0.3)
    events = _unique([e for e in src.slice(0, 60)])
    assert {e.schema_id for e in events} == {0, 1}, "need both columns in chunk"
    rows = app.consume(events)
    msgs = app.consume_scalar(events)
    assert _rows_as_payload_multiset(app, rows) == _scalar_as_payload_multiset(msgs)
    # schema-0 events with both halves non-null produce rows for BOTH entities
    targets = {r[0] for r in rows}
    assert (0, 1) in targets and (1, 1) in targets
    # schema-1 events (null column) never produce rows
    mapped_keys = {r[3] for r in rows}
    assert not mapped_keys & {e.key for e in events if e.schema_id == 1}
    # still exactly one device dispatch for the mixed chunk
    assert app.stats["dispatches"] == 1


def test_padded_lane_widths():
    """CDM wider than one lane tile (n_out > 128) exercises the multi-tile
    output grid; narrow CDM exercises the pad slots."""
    reg, dpm, coord = _multi_entity_world(cdm_attrs=LANE + 5)
    fused = compile_fused(compile_dpm(dpm, reg), reg)
    assert fused.width == 2 * LANE  # 133 attrs -> two lane tiles
    app = METLApp(coord, engine="fused")
    src = EventSource(reg, seed=1, p_duplicate=0.0)
    events = _unique(src.slice(0, 40))
    rows = app.consume(events)
    msgs = app.consume_scalar(events)
    assert _rows_as_payload_multiset(app, rows) == _scalar_as_payload_multiset(msgs)
    for (r, w), vals, mask, _ in rows:
        assert vals.shape == (LANE + 5,)  # true width, pad sliced off


def test_parked_replay_after_state_bump_uses_rebuilt_engine():
    sc = build_scenario(ScenarioConfig(seed=43))
    coord = StateCoordinator(sc.registry, sc.dpm)
    app = METLApp(coord, engine="fused")
    src = EventSource(sc.registry, seed=6, p_duplicate=0.0)
    events = src.slice(0, 12)
    for e in events[:5]:
        e.state += 1  # from the app's future
    app.consume(events)
    assert app.stats["parked"] == 5
    assert app._fused is not None  # metl: allow[private-reach-in] asserting the cached-plan lifecycle itself (no public probe for the internal cache)
    old_state = app._fused.state  # metl: allow[private-reach-in] asserting the cached-plan lifecycle itself (no public probe for the internal cache)
    coord.registry.bump_state()
    replayed = app.refresh()  # rebuilds FusedDMM, replays parked events
    assert app.stats["replayed"] == 5
    assert app._fused.state == old_state + 1  # metl: allow[private-reach-in] asserting the cached-plan lifecycle itself (no public probe for the internal cache)
    # replayed rows must match the scalar oracle on the same events
    fresh = METLApp(coord, engine="fused")
    for e in events[:5]:
        e_state_ok = e.state == coord.registry.state
        assert e_state_ok
    msgs = fresh.consume_scalar(events[:5])
    assert _rows_as_payload_multiset(app, replayed) == _scalar_as_payload_multiset(msgs)


# ---------------------------------------------------------------------------
# dispatch accounting
# ---------------------------------------------------------------------------


def test_constant_dispatches_per_chunk():
    """The fused engine's contract: dispatches per chunk do not grow with the
    number of blocks/columns the chunk touches."""
    sc = build_scenario(
        ScenarioConfig(n_schemas=12, versions_per_schema=3, seed=44)
    )
    coord = StateCoordinator(sc.registry, sc.dpm)
    app = METLApp(coord, engine="fused")
    src = EventSource(sc.registry, seed=7, p_duplicate=0.0)
    for chunk_no in range(3):
        before = app.stats["dispatches"]
        rows = app.consume(src.slice(chunk_no * 100, 100))
        assert rows, "chunk should map something"
        assert app.stats["dispatches"] - before == 1
    # and the module-level counter agrees (no hidden per-block calls)
    before_ops = ops.dispatch_count
    app._seen.clear()  # metl: allow[private-reach-in] deliberate dedup reset so the re-consumed chunk is not swallowed; reset_dedup() would also reset stats under test
    app.consume(src.slice(0, 100))
    assert ops.dispatch_count - before_ops == 1


def _assert_rows_bit_identical(a, b):
    assert a and len(a) == len(b)
    for x, y in zip(a, b):
        assert x[0] == y[0] and x[3] == y[3]  # route, event key
        np.testing.assert_array_equal(x[1].view(np.int32), y[1].view(np.int32))
        np.testing.assert_array_equal(x[2], y[2])


def early_readback_case(engine="fused", mesh=None, device_densify=True):
    """dispatch starts the copy of both outputs to the host before it
    returns and counts it in ``readbacks_early``; sync and double-buffered
    pipelines still write rows bit-identical to the host-densify oracle.
    Also run by tests/test_sharded_engine.py, on a forced 4-device mesh."""
    sc = build_scenario(ScenarioConfig(seed=47))
    coord = StateCoordinator(sc.registry, sc.dpm)
    src = EventSource(sc.registry, seed=5, p_duplicate=0.1)
    chunks = [src.slice(k * 100, 100) for k in range(4)]
    oracle = METLApp(coord, engine="fused")  # host densify, one chunk at a time
    want = [r for c in chunks for r in oracle.consume(c)]

    array_cls = type(jnp.zeros(1))
    real = array_cls.copy_to_host_async
    started = []

    def spy(self):
        started.append(self)
        return real(self)

    array_cls.copy_to_host_async = spy
    try:
        app = METLApp(coord, engine=engine, mesh=mesh, device_densify=device_densify)
        handle = app.engine.dispatch(app.engine.densify(app.triage(chunks[0])))
        assert len(started) == 2  # both outputs, before emit
        assert all(a is b for a, b in zip(started, handle.outputs))
        info = app.engine.info()
        assert info["engine"] == engine
        assert info["readbacks_early"] == info["dispatches"] == 1
        app.engine.emit(handle)
        for async_consume in (False, True):
            app = METLApp(coord, engine=engine, mesh=mesh, device_densify=device_densify)
            sink = CollectSink()
            before = len(started)
            pipe = Pipeline(ListSource(chunks), app, [sink], async_consume=async_consume)
            pipe.run()
            pipe.close()
            info = app.engine.info()
            assert info["dispatches"] == len(chunks)
            assert info["readbacks_early"] == info["dispatches"]
            assert len(started) - before == 2 * info["dispatches"]
            _assert_rows_bit_identical(want, sink.rows)
    finally:
        array_cls.copy_to_host_async = real


@pytest.mark.parametrize("device_densify", [False, True])
def test_dispatch_starts_readback(device_densify):
    early_readback_case("fused", device_densify=device_densify)


def test_empty_chunk_dispatches_nothing():
    sc = build_scenario(ScenarioConfig(seed=45))
    coord = StateCoordinator(sc.registry, sc.dpm)
    app = METLApp(coord, engine="fused")
    before = app.stats["dispatches"]
    assert app.consume([]) == []
    assert app.stats["dispatches"] == before


# ---------------------------------------------------------------------------
# kernel-level checks
# ---------------------------------------------------------------------------


def test_segmented_kernel_matches_ref():
    rng = np.random.default_rng(3)
    B, n_in, n_blocks, W, S = 21, 45, 11, 2 * LANE, 70
    n_blocks_pad = 16
    src2d = np.full((n_blocks_pad, W), -1, np.int32)
    for t in range(n_blocks):
        k = int(rng.integers(1, 40))
        src2d[t, rng.choice(W, k, replace=False)] = rng.integers(0, n_in, k)
    args = (
        jnp.asarray(rng.normal(size=(B, n_in)).astype(np.float32)),
        jnp.asarray((rng.random((B, n_in)) < 0.6).astype(np.int8)),
        jnp.asarray(rng.integers(0, B, S).astype(np.int32)),
        jnp.asarray(rng.integers(0, n_blocks, S).astype(np.int32)),
        jnp.asarray(src2d),
    )
    rv, rm = ops.dmm_apply_fused(*args, impl="ref")
    kv, km = ops.dmm_apply_fused(*args, impl="fused")  # Pallas, interpret on CPU
    np.testing.assert_array_equal(np.asarray(rm), np.asarray(km))
    np.testing.assert_array_equal(np.asarray(rv), np.asarray(kv))


def test_bucket_rows_policy():
    assert bucket_rows(0) == 8
    assert bucket_rows(1) == 8
    assert bucket_rows(8) == 8
    assert bucket_rows(9) == 16
    assert bucket_rows(300) == 512
    # bucketing means a steady stream of ragged chunk sizes reuses traces
    assert len({bucket_rows(n) for n in range(200, 256)}) == 1


def test_unknown_engine_rejected():
    sc = build_scenario(ScenarioConfig(seed=46))
    coord = StateCoordinator(sc.registry, sc.dpm)
    with pytest.raises(ValueError):
        METLApp(coord, engine="warp")
