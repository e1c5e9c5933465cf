"""The MappingEngine protocol: registry factory, staged consume
(densify -> dispatch -> emit), info() observability, custom engines.

Covers the acceptance surface of the API redesign:
  * the fused engine, with host and with device densify, passes the
    scalar-oracle bit-exactness check through the protocol (``engine=``
    string kwargs still accepted);
  * the staged path (triage -> densify -> dispatch -> emit) produces
    exactly what the one-shot ``consume`` produces;
  * the factory's routing rule (sharded on a 1-shard mesh -> fused), and
    its refusal of an unknown ``impl`` such as ``"onehot"``;
  * dispatch returns an unblocked handle; emit is the only sync point;
  * ``info()`` exposes what launchers/benchmarks used to reach into
    private attributes for;
  * engines are pluggable: registering a name and passing an instance both
    work, and instances share the app's stats counter.
"""

import numpy as np
import pytest

import jax

from repro.core.state import StateCoordinator
from repro.core.synthetic import ScenarioConfig, build_scenario
from repro.etl import (
    EventSource,
    FusedEngine,
    METLApp,
    MappingEngine,
    make_engine,
    register_engine,
)
from repro.etl.engines import ENGINES, DenseChunk, DispatchHandle


def _world(seed=41, **kw):
    sc = build_scenario(ScenarioConfig(seed=seed, **kw))
    coord = StateCoordinator(sc.registry, sc.dpm)
    return sc, coord


def _rows_as_payload_multiset(app, rows):
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
        ((m.schema_id, m.version), tuple(sorted(m.payload.items()))) for m in msgs
    )


def _unique(events):
    seen, out = set(), []
    for e in events:
        if e.key not in seen:
            seen.add(e.key)
            out.append(e)
    return out


# ---------------------------------------------------------------------------
# factory / registry
# ---------------------------------------------------------------------------


def test_factory_resolves_builtin_names():
    assert isinstance(make_engine("fused"), FusedEngine)
    assert {"fused", "sharded"} <= set(ENGINES) and "blocks" not in ENGINES
    with pytest.raises(ValueError):
        make_engine("warp")


def test_factory_legacy_routing_rules():
    # impl="onehot" has no realisation: refused, never rerouted
    for engine in ("fused", "sharded"):
        with pytest.raises(ValueError, match="onehot"):
            make_engine(engine, impl="onehot")
    # sharded without a multi-shard mesh degenerates to replicated fused
    assert isinstance(make_engine("sharded", mesh=None), FusedEngine)


def test_instance_with_conflicting_kwargs_rejected():
    # silently dropping impl=/mesh= for an instance would run a different
    # path than requested
    with pytest.raises(ValueError):
        make_engine(FusedEngine(), impl="ref")
    eng = FusedEngine(impl="ref")
    assert make_engine(eng, impl="ref") is eng  # matching impl is fine


def test_app_accepts_engine_instance_and_shares_stats():
    sc, coord = _world()
    eng = FusedEngine()
    app = METLApp(coord, engine=eng)
    assert app.engine is eng
    assert eng.stats is app.stats  # engine accounting lands in app.stats
    src = EventSource(sc.registry, seed=4, p_duplicate=0.0)
    rows = app.consume(src.slice(0, 40))
    assert rows and app.stats["dispatches"] == 1


def test_custom_engine_registration():
    @register_engine("test-tee")
    class TeeEngine(FusedEngine):
        pass

    try:
        sc, coord = _world()
        app = METLApp(coord, engine="test-tee")
        assert app.engine_name == "test-tee"
        src = EventSource(sc.registry, seed=4, p_duplicate=0.0)
        rows = app.consume(_unique(src.slice(0, 40)))
        msgs = app.consume_scalar(_unique(src.slice(0, 40)))
        assert _rows_as_payload_multiset(app, rows) == _scalar_as_payload_multiset(msgs)
    finally:
        ENGINES.pop("test-tee")


# ---------------------------------------------------------------------------
# staged protocol == one-shot consume == scalar oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("device_densify", [False, True], ids=["fused", "device"])
def test_staged_protocol_matches_consume(device_densify):
    """triage -> densify -> dispatch -> emit, called stage by stage, must
    reproduce consume() exactly (rows, order, stats)."""
    sc, coord = _world(seed=42)
    ref = METLApp(coord, device_densify=device_densify)
    staged = METLApp(coord, device_densify=device_densify)
    src = EventSource(sc.registry, seed=5)
    events = src.slice(0, 150)

    rows_ref = ref.consume(events)

    groups = staged.triage(events)
    dense = staged.engine.densify(groups)
    assert dense is not None
    handle = staged.engine.dispatch(dense)
    rows_staged = staged.engine.emit(handle)

    assert len(rows_ref) == len(rows_staged) > 0
    for a, b in zip(rows_ref, rows_staged):
        assert a[0] == b[0] and a[3] == b[3]
        np.testing.assert_array_equal(a[1], b[1])
        np.testing.assert_array_equal(a[2], b[2])
    for k in ("events", "duplicates", "mapped", "empty", "dispatches"):
        assert ref.stats[k] == staged.stats[k], k


@pytest.mark.parametrize("device_densify", [False, True], ids=["fused", "device"])
def test_engine_bit_exact_with_scalar_oracle(device_densify):
    sc, coord = _world(seed=43)
    app = METLApp(coord, device_densify=device_densify)
    src = EventSource(sc.registry, seed=6, p_duplicate=0.0)
    events = _unique(src.slice(0, 120))
    rows = app.consume(events)
    msgs = app.consume_scalar(events)
    assert _rows_as_payload_multiset(app, rows) == _scalar_as_payload_multiset(msgs)


def test_dense_chunk_pins_its_plan():
    """A state bump between densify and dispatch must not mix plans: the
    in-flight chunk maps against the plan it was densified with."""
    sc, coord = _world(seed=44)
    app = METLApp(coord, engine="fused")
    src = EventSource(sc.registry, seed=7, p_duplicate=0.0)
    events = _unique(src.slice(0, 60))
    rows_ref = METLApp(coord, engine="fused").consume(list(events))

    groups = app.triage(list(events))
    dense = app.engine.densify(groups)
    old_plan = dense.plan
    coord.registry.bump_state()
    app.refresh()  # recompiles the engine plan
    assert app.engine.plan is not old_plan
    assert dense.plan is old_plan  # the chunk still carries its own plan
    rows = app.engine.emit(app.engine.dispatch(dense))
    assert len(rows) == len(rows_ref)
    for a, b in zip(rows_ref, rows):
        assert a[0] == b[0] and a[3] == b[3]
        np.testing.assert_array_equal(a[1], b[1])


def test_dispatch_handle_is_unblocked_jax_output():
    """The dispatch stage returns device arrays (async-dispatch futures),
    not host numpy -- emit owns the sync."""
    sc, coord = _world(seed=45)
    app = METLApp(coord, engine="fused")
    src = EventSource(sc.registry, seed=8, p_duplicate=0.0)
    dense = app.engine.densify(app.triage(src.slice(0, 30)))
    handle = app.engine.dispatch(dense)
    assert isinstance(handle, DispatchHandle)
    ov, om = handle.outputs
    assert isinstance(ov, jax.Array) and isinstance(om, jax.Array)
    rows = app.engine.emit(handle)
    assert all(isinstance(r[1], np.ndarray) for r in rows)


def test_unmappable_chunk_densifies_to_none():
    sc, coord = _world(seed=46)
    app = METLApp(coord, engine="fused")
    assert app.engine.densify({}) is None
    before = app.stats["dispatches"]
    assert app.consume([]) == []
    assert app.stats["dispatches"] == before


# ---------------------------------------------------------------------------
# info(): the public observability surface
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("device_densify", [False, True], ids=["fused", "device"])
def test_info_exposes_plan_and_accounting(device_densify):
    sc, coord = _world(seed=47)
    app = METLApp(coord, device_densify=device_densify)
    info = app.engine.info()
    assert info["engine"] == "fused"
    assert info["device_densify"] is device_densify
    assert info["n_shards"] == 1
    assert info["n_blocks"] > 0
    assert info["table_bytes"] > 0
    assert info["table_bytes_per_shard"] == info["table_bytes"]
    assert info["dispatches"] == 0
    src = EventSource(sc.registry, seed=9)
    app.consume(src.slice(0, 50))
    assert app.engine.info()["dispatches"] == app.stats["dispatches"] > 0


def test_info_survives_eviction():
    sc, coord = _world(seed=48)
    app = METLApp(coord, engine="fused")
    app.evict()
    info = app.engine.info()  # plan-less info still answers
    assert info["engine"] == "fused" and "n_blocks" not in info
    app.consume(EventSource(sc.registry, seed=1).slice(0, 10))  # auto-refresh
    assert "n_blocks" in app.engine.info()
