"""Sharded fused mapping engine (engine="sharded"): the block table lives
sliced over the mesh ``data`` axis, one segmented-gather dispatch per chunk
per shard, emitted rows all-gathered before emission.

Covers the acceptance surface of the sharding tentpole:
  * sharded consume == replicated fused consume, bit-exact, same row order;
  * 1 dispatch per chunk per shard (module counter + app stats);
  * the device table really is distributed: each device holds only its
    (1, n_blocks_pad_loc, W) slice, ~ total/N bytes;
  * host-side partitioning reconstructs the replicated table exactly;
  * 1-device mesh (or no mesh) falls back to the replicated fused path;
  * dispatch starts the all-gather's read-back (the fused engine's case).

The multi-device cases run in a *subprocess* via the shared forced-topology
harness (tests/_subproc.py): jax pins the device count at first init and
the rest of the suite must see exactly one device.
"""

import functools
import os

import numpy as np
import pytest

from _subproc import run_sub as _run_sub

run_sub = functools.partial(_run_sub, devices=4)
TESTS = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.slow
def test_sharded_consume_bit_exact_and_one_dispatch_per_shard():
    """Replicated-vs-sharded parity on a 1x4 CPU mesh: identical rows in
    identical order, 1 dispatch/chunk/shard, per-shard table ~ total/N."""
    out = run_sub("""
        import numpy as np
        from repro.core.state import StateCoordinator
        from repro.core.synthetic import ScenarioConfig, build_scenario
        from repro.etl import EventSource, METLApp
        from repro.launch.mesh import make_etl_mesh
        from repro.kernels import ops

        N = 4
        sc = build_scenario(ScenarioConfig(n_schemas=8, versions_per_schema=3, seed=21))
        coord = StateCoordinator(sc.registry, sc.dpm)
        mesh = make_etl_mesh(N)
        rep = METLApp(coord, engine="fused")
        shd = METLApp(coord, engine="sharded", mesh=mesh)
        src = EventSource(sc.registry, seed=9)
        for chunk in range(3):
            events = src.slice(chunk * 120, 120)
            rows_r = rep.consume(events)
            b_ops, b_app = ops.dispatch_count, shd.stats["dispatches"]
            rows_s = shd.consume(events)
            # ONE shard_map launch per chunk == one kernel execution per
            # shard per chunk (the per-shard fused-engine contract)
            assert ops.dispatch_count - b_ops == 1
            assert shd.stats["dispatches"] - b_app == 1
            assert rows_r and len(rows_r) == len(rows_s)
            for a, b in zip(rows_r, rows_s):
                assert a[0] == b[0] and a[3] == b[3]  # route, event key
                np.testing.assert_array_equal(a[1], b[1])  # values
                np.testing.assert_array_equal(a[2], b[2])  # mask
        for k in ("events", "duplicates", "mapped", "empty"):
            assert rep.stats[k] == shd.stats[k], k

        # the table is physically distributed: N device shards, each holding
        # a (1, rows_loc, W) slice -> per-shard bytes ~ total/N
        t = shd._sharded
        assert t.src3d.shape[0] == N
        shards = t.src3d.addressable_shards
        assert len({s.device.id for s in shards}) == N
        for s in shards:
            assert s.data.shape == (1, t.n_blocks_pad_loc, t.width)
        total = t.n_blocks * t.width * 4
        assert t.table_bytes_per_shard <= -(-total // N) + 8 * t.width * 4
        print("sharded parity OK")
    """)
    assert "sharded parity OK" in out


@pytest.mark.slow
def test_sharded_replay_and_state_bump():
    """A state bump rebuilds the sharded table and parked-event replay flows
    through it, staying bit-exact with a fresh replicated app."""
    out = run_sub("""
        import numpy as np
        from repro.core.state import StateCoordinator
        from repro.core.synthetic import ScenarioConfig, build_scenario
        from repro.etl import EventSource, METLApp
        from repro.launch.mesh import make_etl_mesh

        sc = build_scenario(ScenarioConfig(seed=43))
        coord = StateCoordinator(sc.registry, sc.dpm)
        app = METLApp(coord, engine="sharded", mesh=make_etl_mesh(4))
        src = EventSource(sc.registry, seed=6, p_duplicate=0.0)
        events = src.slice(0, 12)
        for e in events[:5]:
            e.state += 1  # from the app's future
        app.consume(events)
        assert app.stats["parked"] == 5
        old_state = app._sharded.state
        coord.registry.bump_state()
        replayed = app.refresh()
        assert app.stats["replayed"] == 5
        assert app._sharded.state == old_state + 1
        fresh = METLApp(coord, engine="fused")
        ref = fresh.consume(events[:5])
        assert len(replayed) == len(ref)
        for a, b in zip(replayed, ref):
            assert a[0] == b[0] and a[3] == b[3]
            np.testing.assert_array_equal(a[1], b[1])
            np.testing.assert_array_equal(a[2], b[2])
        print("sharded replay OK")
    """)
    assert "sharded replay OK" in out


@pytest.mark.parametrize("device_densify", [False, True])
def test_sharded_dispatch_starts_readback(device_densify):
    """The fused engine's early read-back case, on a 1x4 CPU mesh."""
    out = run_sub(f"""
        import sys
        sys.path.insert(0, {TESTS!r})
        from test_fused_engine import early_readback_case
        from repro.launch.mesh import make_etl_mesh

        early_readback_case("sharded", make_etl_mesh(4), device_densify={device_densify})
        print("sharded early readback OK")
    """)
    assert "sharded early readback OK" in out


def test_sharded_table_partitioning_host():
    """compile_fused_sharded (host-only, no mesh): every global block row
    lands at (t // per, t % per) and per-shard routes/widths tile the global
    lists."""
    from repro.core.dmm_jax import compile_dpm, compile_fused, compile_fused_sharded
    from repro.core.synthetic import ScenarioConfig, build_scenario

    sc = build_scenario(ScenarioConfig(seed=41))
    compiled = compile_dpm(sc.dpm, sc.registry)
    fused = compile_fused(compiled, sc.registry)
    for n in (1, 3, 4, 64):
        sh = compile_fused_sharded(compiled, sc.registry, n_shards=n)
        t2, t3 = np.asarray(fused.src2d), np.asarray(sh.src3d)
        assert t3.shape[0] == n and t3.shape[2] == fused.width
        for t in range(fused.n_blocks):
            s, loc = divmod(t, sh.blocks_per_shard)
            np.testing.assert_array_equal(t3[s, loc], t2[t])
        # pad rows stay null so stray routing can never fabricate output
        for s in range(n):
            lo, hi = sh.shard_slice(s)
            assert (t3[s, hi - lo:] == -1).all()
        assert sum(len(sh.shard_routes(s)) for s in range(n)) == fused.n_blocks
        assert np.concatenate([sh.shard_n_out(s) for s in range(n)]).tolist() \
            == fused.n_out.tolist()


def test_sharded_engine_falls_back_on_single_device():
    """engine="sharded" without a multi-device mesh degenerates to the
    replicated fused path (this process has exactly one device)."""
    from repro.core.state import StateCoordinator
    from repro.core.synthetic import ScenarioConfig, build_scenario
    from repro.etl import EventSource, METLApp
    from repro.launch.mesh import make_etl_mesh

    sc = build_scenario(ScenarioConfig(seed=41))
    coord = StateCoordinator(sc.registry, sc.dpm)
    rep = METLApp(coord, engine="fused")
    shd = METLApp(coord, engine="sharded", mesh=make_etl_mesh())
    src = EventSource(sc.registry, seed=4)
    events = src.slice(0, 100)
    rows_r = rep.consume(events)
    rows_s = shd.consume(events)
    assert shd._sharded is None and shd._fused is not None  # metl: allow[private-reach-in] asserting which internal plan cache the single-device fallback populated
    assert len(rows_r) == len(rows_s) > 0
    for a, b in zip(rows_r, rows_s):
        assert a[0] == b[0] and a[3] == b[3]
        np.testing.assert_array_equal(a[1], b[1])
        np.testing.assert_array_equal(a[2], b[2])
