"""Paper §7 (evaluation): per-event mapping latency and throughput.

The paper measures 39 ms mean (10-20 ms warm) per CDC event on the JVM
microservice.  Hardware differs; the comparable numbers are (a) the absolute
per-event cost of the compacted-set formulation -- the paper's Algorithm 6
vs Algorithm 1 story -- (b) one compacted block applied to a batch through
the XLA gather and the Pallas masked-gather kernel, (c) `METLApp` consume
through the fused one-dispatch-per-chunk engine (events/s and
device-dispatch count), (d) the
**replicated-vs-sharded A/B**: the fused engine against `engine="sharded"`
(block table partitioned over the mesh ``data`` axis) per shard count, with
per-shard table bytes ~ total/N.  The sharded rows run in a subprocess with
a forced N-device CPU topology (jax pins the device count at first init).
And (e) the **sync-vs-async pipeline A/B**: the streaming Pipeline over the
same chunk stream with and without double-buffered consume (chunk N+1's
host densification overlapped with chunk N's device dispatch), plus the
re-measured ``densify_thread=True`` variant now that densify is pure
GIL-releasing numpy.  And (f) the **densify A/B**: the legacy per-item
dict walk vs the columnar numpy scatter over the same triaged chunk.
And (g) the **epoch-transition A/B**: events/s across a LIVE schema
evolution -- the same stream mapped with the evolution applied out-of-band
(manual ``apply_update`` + refresh) vs in-band (a ``SchemaEvolved`` control
event riding the stream), plus a 4-instance ``Cluster`` over sliced
sources running the identical transition.

This benchmark is also a CI gate: it exits non-zero if the fused engine's
dispatches-per-chunk regress above 1 (direct consume, async pipeline, or
any cluster instance across the epoch transition), if columnar densify is
slower than the dict walk at the default chunk size, if the two densify
paths diverge bit-wise, or if the epoch transition drops/duplicates rows
(in-band vs out-of-band oracle, cluster vs single instance).

Standalone smoke entry point (used by scripts/ci.sh):

    PYTHONPATH=src python benchmarks/bench_mapping.py --smoke
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.dmm import Message, map_message_dense, map_message_sparse
from repro.core.dmm_jax import compile_dpm
from repro.core.state import StateCoordinator
from repro.core.synthetic import ScenarioConfig, build_scenario
from repro.etl import EventSource, METLApp
from repro.kernels import ops

from common import bench

# CI gate (scripts/ci.sh runs --smoke): run() appends a message here whenever
# a dispatch-count contract breaks (fused consume or async pipeline above 1
# dispatch/chunk); __main__ then exits non-zero so the build fails.
GATE_FAILURES: list = []

# Per-engine per-chunk facts for the ETL roofline + the BENCH_*.json
# trajectory artifact (benchmarks/run.py --artifact); populated by run().
# Entry keys: engine, chunk_events, dispatches, host_bytes, device_bytes,
# events_per_s -- see repro.launch.roofline.analyze_etl.
ENGINE_METRICS: list = []

# name -> events/s for the perf-trajectory diff (scripts/perf_diff.py);
# populated by run().
PERF_METRICS: dict = {}


def _dense_host_bytes(dense) -> int:
    """Host->device operand bytes one dispatch of this dense chunk ships."""
    from repro.core.dmm_jax import bucket_rows
    from repro.etl.engines import ColumnarDense

    if isinstance(dense, ColumnarDense):
        return int(dense.packed.nbytes)  # the single packed transfer
    return int(
        dense.vals.nbytes + dense.mask.nbytes + 2 * bucket_rows(dense.row_ids.size) * 4
    )


def _engine_metric(app: METLApp, chunk, *, label: str, dispatches: int, us: float):
    """One roofline row: measured per-chunk facts for this engine config."""
    app.reset_dedup()
    tri = app.triage(chunk)
    dense = app.engine.densify(tri)
    host_bytes = 0 if dense is None else _dense_host_bytes(dense)
    info = app.engine.info()
    width = info.get("width", 0)
    out_rows = 0 if dense is None else int(dense.row_ids.size)
    out_bytes = out_rows * int(width) * 5  # f32 values + i8 mask
    n_events = len(chunk)
    entry = {
        "engine": label,
        "chunk_events": n_events,
        "dispatches": int(dispatches),
        "host_bytes": int(host_bytes),
        # bytes the device work touches: operands in, table, outputs out
        "device_bytes": int(host_bytes + info.get("table_bytes", 0) + out_bytes),
        "events_per_s": n_events / (us / 1e6),
    }
    ENGINE_METRICS.append(entry)
    return entry


def _consume_bench(app: METLApp, events, *, warmup: int = 1, iters: int = 5):
    """Time repeated consume of one chunk, resetting dedup between calls
    (otherwise every iteration after the first measures the dedup-drop path).
    Returns (us_per_call, device dispatches per chunk)."""
    def call():
        app.reset_dedup()
        return app.consume(events)

    us = bench(call, warmup=warmup, iters=iters)
    before = app.stats["dispatches"]
    call()
    dispatches = app.stats["dispatches"] - before
    return us, dispatches


def _bench_shapes(smoke: bool):
    if smoke:
        cfg = ScenarioConfig(n_schemas=4, versions_per_schema=2, attrs_per_version=6,
                             n_entities=2, cdm_attrs=8, seed=11)
        return cfg, 64, 64, 2
    cfg = ScenarioConfig(n_schemas=40, versions_per_schema=10, attrs_per_version=10,
                         n_entities=10, cdm_attrs=25, seed=11)
    return cfg, 1024, 512, 5


def sharded_worker(shards: int, smoke: bool) -> list:
    """Replicated-vs-sharded consume A/B; runs in the forced N-device
    subprocess so both sides see the same topology/process."""
    from repro.launch.mesh import make_etl_mesh

    cfg, _, n_events, iters = _bench_shapes(smoke)
    sc = build_scenario(cfg)
    coord = StateCoordinator(sc.registry, sc.dpm)
    events = EventSource(sc.registry, seed=1).slice(0, n_events)
    rows = []

    app_rep = METLApp(coord, engine="fused")
    us_rep, _ = _consume_bench(app_rep, events, iters=iters)
    total_bytes = app_rep.engine.info()["table_bytes"]

    mesh = make_etl_mesh(shards)
    app_sh = METLApp(coord, engine="sharded", mesh=mesh)
    us_sh, disp = _consume_bench(app_sh, events, iters=iters)
    info = app_sh.engine.info()
    rows.append((
        f"mapping/metl_consume_sharded_{shards}sh_{n_events}ev",
        us_sh,
        f"{n_events / (us_sh / 1e6):.0f} events/s, {disp} dispatch/chunk "
        f"(x{shards} shards), {us_rep / us_sh:.2f}x vs replicated-in-proc "
        f"({us_rep:.0f} us)",
    ))
    rows.append((
        f"mapping/sharded_table_bytes_{shards}sh",
        float(info["table_bytes_per_shard"]),
        f"{info['table_bytes_per_shard']} B/shard vs {total_bytes} B replicated "
        f"(total/{shards} = {total_bytes / shards:.0f}; "
        f"{info['blocks_per_shard']}/{info['n_blocks']} blocks per shard)",
    ))

    # sharded device densify: same mesh, packed columnar transfer; must be
    # bit-exact with the replicated host-densify rows
    app_shd = METLApp(coord, engine="sharded", mesh=mesh, device_densify=True)
    us_shd, disp_d = _consume_bench(app_shd, events, iters=iters)
    app_rep.reset_dedup()
    app_shd.reset_dedup()
    rows_rep, rows_shd = app_rep.consume(events), app_shd.consume(events)
    sh_exact = len(rows_rep) == len(rows_shd) and all(
        a[0] == b[0] and a[3] == b[3]
        and np.array_equal(a[1], b[1]) and np.array_equal(a[2], b[2])
        for a, b in zip(rows_rep, rows_shd)
    )
    rows.append((
        f"mapping/metl_consume_sharded_devdensify_{shards}sh_{n_events}ev",
        us_shd,
        f"{n_events / (us_shd / 1e6):.0f} events/s, {disp_d} dispatch/chunk, "
        f"{us_sh / us_shd:.2f}x vs sharded host densify, bit_exact={sh_exact}",
    ))
    if not sh_exact:
        GATE_FAILURES.append(
            f"sharded device densify ({shards} shards) diverged from the "
            f"replicated host oracle"
        )
    chunk_m = EventSource(sc.registry, seed=1).slice_columnar(0, n_events)
    _engine_metric(app_sh, chunk_m, label=f"sharded-{shards}sh-host",
                   dispatches=disp, us=us_sh)
    _engine_metric(app_shd, chunk_m, label=f"sharded-{shards}sh-device",
                   dispatches=disp_d, us=us_shd)
    return rows


def _sharded_ab(shards: int, smoke: bool) -> list:
    """The replicated-vs-sharded A/B over ``shards`` devices.

    On the CPU backend it runs in a child under a forced {shards}-device
    topology, whose CSV rows are re-parsed here.  An accelerator belongs to
    the one process that holds it, so elsewhere the A/B runs in this process
    when it has the devices, and otherwise reports a row that says it did
    not run (no timing, no pass)."""
    if jax.default_backend() != "cpu":
        if jax.device_count() >= shards:
            return sharded_worker(shards, smoke)
        return [(
            f"mapping/metl_consume_sharded_{shards}sh",
            float("nan"),
            f"not run: {jax.device_count()} devices",
        )]
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={shards}"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    args = [sys.executable, os.path.abspath(__file__), "--sharded-worker", str(shards)]
    if smoke:
        args.append("--smoke")
    r = subprocess.run(args, capture_output=True, text=True, timeout=560, env=env)
    if r.returncode != 0:
        raise RuntimeError(f"sharded worker failed:\n{r.stdout}\n{r.stderr[-2000:]}")
    rows = []
    for line in r.stdout.strip().splitlines():
        # the worker streams three record kinds: roofline metric entries and
        # gate failures (re-raised into this process) ride as prefixed JSON
        # sidecars of the plain CSV rows
        if line.startswith("metric:"):
            ENGINE_METRICS.append(json.loads(line[len("metric:"):]))
            continue
        if line.startswith("gate:"):
            GATE_FAILURES.append(json.loads(line[len("gate:"):]))
            continue
        name, us, derived = line.split(",", 2)
        rows.append((name, float(us), derived))
    return rows


def run(smoke: bool = False) -> list:
    rows = []
    cfg, B, n_events, iters = _bench_shapes(smoke)
    sc = build_scenario(cfg)
    reg = sc.registry
    compiled = compile_dpm(sc.dpm, reg)

    # -- python reference paths (per single event) ---------------------------
    o = reg.domain.schema_ids()[0]
    v = reg.domain.versions(o)[-1]
    sv = reg.domain.get(o, v)
    rng = np.random.default_rng(0)
    payload = {a.uid: float(rng.integers(1, 100)) for a in sv.attributes}
    msg = Message(state=reg.state, schema_id=o, version=v, payload=payload)
    us = bench(lambda: map_message_sparse(sc.matrix, msg), iters=20)
    rows.append(("mapping/alg1_sparse_python_per_event", us, "baseline Algorithm 1"))
    us = bench(lambda: map_message_dense(sc.dpm, reg, msg), iters=20)
    rows.append(("mapping/alg6_dense_python_per_event", us, "DMM Algorithm 6"))

    # -- batched tensor path (the production device path) --------------------
    n_in = len(sv.attributes)
    vals = jnp.asarray(rng.normal(size=(B, n_in)).astype(np.float32))
    mask = jnp.asarray((rng.random((B, n_in)) < 0.75).astype(np.int8))
    blk = compiled.column(o, v)[0]
    for impl, label in [("ref", "xla_gather"), ("gather", "pallas_gather")]:
        f = jax.jit(lambda v_, m_: ops.dmm_apply(v_, m_, blk.src, impl=impl))
        us = bench(f, vals, mask)
        rows.append((f"mapping/batched_{label}", us, f"{us/B:.3f} us/event, B={B}"))

    # -- end-to-end METL app: fused consume ----------------------------------
    coord = StateCoordinator(reg, sc.dpm)
    src = EventSource(reg, seed=1)
    events = src.slice(0, n_events)

    app_fused = METLApp(coord, engine="fused")
    us_fused, disp_fused = _consume_bench(app_fused, events, iters=iters)
    rows.append((
        f"mapping/metl_consume_fused_{n_events}ev",
        us_fused,
        f"{n_events / (us_fused / 1e6):.0f} events/s, {disp_fused} dispatch/chunk",
    ))
    PERF_METRICS["consume_fused"] = n_events / (us_fused / 1e6)
    if disp_fused > 1:
        GATE_FAILURES.append(
            f"fused engine regressed to {disp_fused} dispatches/chunk (want <= 1)"
        )

    # -- densify A/B: legacy dict walk vs columnar numpy scatter --------------
    # The tentpole gate: with the chunk columnarised once at the source
    # boundary, the hot-thread densification must beat the per-item python
    # dict walk at the bench's default chunk size -- and stay bit-exact.
    from repro.etl import densify_chunk_dicts

    app_den = METLApp(coord, engine="fused")
    app_den.reset_dedup()
    tri = app_den.triage(src.slice_columnar(30_000, n_events))
    legacy_groups = tri.to_groups()
    plan = app_den.engine.plan
    den_iters = max(iters, 11)
    us_dict = bench(lambda: densify_chunk_dicts(plan, legacy_groups),
                    warmup=2, iters=den_iters)
    us_col = bench(lambda: app_den.engine.densify(tri), warmup=2, iters=den_iters)
    d_col, d_dict = app_den.engine.densify(tri), densify_chunk_dicts(plan, legacy_groups)
    if d_col is None or d_dict is None:
        # both paths must agree that the chunk is unmappable
        bit_exact = d_col is None and d_dict is None
    else:
        bit_exact = (
            np.array_equal(d_col.vals, d_dict.vals)
            and np.array_equal(d_col.mask, d_dict.mask)
            and np.array_equal(d_col.row_ids, d_dict.row_ids)
            and np.array_equal(d_col.blk_ids, d_dict.blk_ids)
            and np.array_equal(d_col.out_keys, d_dict.out_keys)
        )
    rows.append((
        f"mapping/densify_dictwalk_{n_events}ev",
        us_dict,
        f"{n_events / (us_dict / 1e6):.0f} events/s (per-item python)",
    ))
    rows.append((
        f"mapping/densify_columnar_{n_events}ev",
        us_col,
        f"{n_events / (us_col / 1e6):.0f} events/s, "
        f"{us_dict / us_col:.1f}x vs dict walk, "
        f"{tri.chunk.n_items} items, bit_exact={bit_exact}",
    ))
    if not bit_exact:
        GATE_FAILURES.append("columnar densify diverged from the dict-walk oracle")
    if us_col > us_dict:
        GATE_FAILURES.append(
            f"columnar densify slower than the dict walk at {n_events} events "
            f"({us_col:.0f} us vs {us_dict:.0f} us)"
        )

    # -- device-densify A/B: host scatter vs on-device densification ----------
    # The PR-6 tentpole: the same chunk consumed through (a) the host numpy
    # scatter + dense-payload transfer and (b) the packed columnar transfer
    # + on-device densify_map (one buffer, one dispatch).  Gates: bit-exact
    # rows, <= 1 dispatch AND exactly 1 host->device transfer per chunk, and
    # (full mode) the device path must not be slower end-to-end.  On CPU
    # there is no PCIe boundary, so the measured ratio understates the
    # accelerator win -- the roofline model (repro.launch.roofline --etl)
    # prices the transfer term where the 2x comes from; here the packed
    # buffer is ~12x smaller than the dense payload it replaces.
    chunk_dd = src.slice_columnar(70_000, n_events)
    app_hd = METLApp(coord, engine="fused")
    app_dd = METLApp(coord, engine="fused", device_densify=True)
    us_hd, disp_hd = _consume_bench(app_hd, chunk_dd, iters=den_iters)
    us_dd, disp_dd = _consume_bench(app_dd, chunk_dd, iters=den_iters)
    app_hd.reset_dedup()
    app_dd.reset_dedup()
    rows_h = app_hd.consume(chunk_dd)
    t_before, d_before = app_dd.stats["transfers"], app_dd.stats["dispatches"]
    rows_d = app_dd.consume(chunk_dd)
    transfers_dd = app_dd.stats["transfers"] - t_before
    dd_exact = len(rows_h) == len(rows_d) and all(
        a[0] == b[0] and a[3] == b[3]
        and np.array_equal(a[1], b[1]) and np.array_equal(a[2], b[2])
        for a, b in zip(rows_h, rows_d)
    )
    m_host = _engine_metric(app_hd, chunk_dd, label="fused-host-densify",
                            dispatches=disp_hd, us=us_hd)
    m_dev = _engine_metric(app_dd, chunk_dd, label="fused-device-densify",
                           dispatches=disp_dd, us=us_dd)
    rows.append((
        f"mapping/metl_consume_devdensify_{n_events}ev",
        us_dd,
        f"{n_events / (us_dd / 1e6):.0f} events/s, {disp_dd} dispatch + "
        f"{transfers_dd} transfer/chunk ({m_dev['host_bytes']} B packed vs "
        f"{m_host['host_bytes']} B dense, "
        f"{m_host['host_bytes'] / max(1, m_dev['host_bytes']):.1f}x less PCIe), "
        f"{us_hd / us_dd:.2f}x vs host densify ({us_hd:.0f} us), "
        f"bit_exact={dd_exact}",
    ))
    PERF_METRICS["consume_fused_host"] = n_events / (us_hd / 1e6)
    PERF_METRICS["consume_fused_device"] = n_events / (us_dd / 1e6)
    if not dd_exact:
        GATE_FAILURES.append("device densify diverged from the host oracle")
    if disp_dd > 1:
        GATE_FAILURES.append(
            f"device densify issued {disp_dd} dispatches/chunk (want <= 1)"
        )
    if transfers_dd != 1:
        GATE_FAILURES.append(
            f"device densify made {transfers_dd} host->device transfers/chunk "
            f"(want exactly 1 packed buffer)"
        )
    if not smoke and us_dd > us_hd:
        GATE_FAILURES.append(
            f"device densify slower than host densify end-to-end at "
            f"{n_events} events ({us_dd:.0f} us vs {us_hd:.0f} us)"
        )

    # -- streaming pipeline: sync vs double-buffered async consume ------------
    # Same chunks, same app config; the A/B isolates the overlap of chunk
    # N+1's host-side densification with chunk N's device dispatch.  Chunks
    # are columnar (the sources' default form since the densify tentpole).
    from repro.etl import CollectSink, ListSource, Pipeline

    n_chunks = 8 if smoke else 6
    chunks = [src.slice_columnar(50_000 + k * n_events, n_events) for k in range(n_chunks)]
    total_ev = n_chunks * n_events
    app_pipe = METLApp(coord, engine="fused")
    app_pipe_dd = METLApp(coord, engine="fused", device_densify=True)

    def pipe_run(async_consume, densify_thread=False, app=None):
        app = app or app_pipe
        app.reset_dedup()
        sink = CollectSink()
        pipe = Pipeline(ListSource(chunks), app, [sink],
                        async_consume=async_consume, densify_thread=densify_thread)
        pipe.run()
        if densify_thread:
            pipe.close()
        return sink.rows

    # the pipeline pass is cheap (~tens of ms) but the A/B margin is ~10-30%,
    # so use enough samples for a stable median regardless of the smoke iters
    pipe_iters = max(iters, 11)
    us_psync = bench(lambda: pipe_run(False), warmup=2, iters=pipe_iters)
    us_pasync = bench(lambda: pipe_run(True), warmup=2, iters=pipe_iters)
    # the PR-3 caveat, re-measured on the columnar path: densify is now
    # GIL-releasing numpy, so the opt-in worker thread should no longer
    # convoy with the dispatch thread (was 0.6-0.8x on the dict walk)
    us_pthread = bench(lambda: pipe_run(True, densify_thread=True),
                       warmup=2, iters=pipe_iters)
    before = app_pipe.stats["dispatches"]
    pipe_run(True)
    disp_async = (app_pipe.stats["dispatches"] - before) / n_chunks
    rows.append((
        f"mapping/pipeline_sync_{n_chunks}x{n_events}ev",
        us_psync,
        f"{total_ev / (us_psync / 1e6):.0f} events/s",
    ))
    rows.append((
        f"mapping/pipeline_async_{n_chunks}x{n_events}ev",
        us_pasync,
        f"{total_ev / (us_pasync / 1e6):.0f} events/s, "
        f"{us_psync / us_pasync:.2f}x vs sync, "
        f"{disp_async:.0f} dispatch/chunk",
    ))
    rows.append((
        f"mapping/pipeline_async_densify_thread_{n_chunks}x{n_events}ev",
        us_pthread,
        f"{total_ev / (us_pthread / 1e6):.0f} events/s, "
        f"{us_psync / us_pthread:.2f}x vs sync (dict walk measured 0.6-0.8x)",
    ))
    PERF_METRICS["pipeline_sync"] = total_ev / (us_psync / 1e6)
    PERF_METRICS["pipeline_async"] = total_ev / (us_pasync / 1e6)
    # the tentpole end state: double-buffered async consume with NO host
    # per-chunk scatter -- the overlapped host work is triage + routing +
    # the int32 pack; rows must stay identical to the host-densify pipeline
    us_pdd = bench(lambda: pipe_run(True, app=app_pipe_dd),
                   warmup=2, iters=pipe_iters)
    rows_pipe_h = pipe_run(True)
    rows_pipe_d = pipe_run(True, app=app_pipe_dd)
    pipe_exact = len(rows_pipe_h) == len(rows_pipe_d) and all(
        a[0] == b[0] and a[3] == b[3]
        and np.array_equal(a[1], b[1]) and np.array_equal(a[2], b[2])
        for a, b in zip(rows_pipe_h, rows_pipe_d)
    )
    rows.append((
        f"mapping/pipeline_async_devdensify_{n_chunks}x{n_events}ev",
        us_pdd,
        f"{total_ev / (us_pdd / 1e6):.0f} events/s, "
        f"{us_pasync / us_pdd:.2f}x vs async host-densify, "
        f"{us_psync / us_pdd:.2f}x vs sync, bit_exact={pipe_exact}",
    ))
    PERF_METRICS["pipeline_async_device"] = total_ev / (us_pdd / 1e6)
    if not pipe_exact:
        GATE_FAILURES.append(
            "async device-densify pipeline diverged from the host-densify pipeline"
        )
    if disp_async > 1:
        # an unmappable chunk legitimately issues 0 dispatches; only a
        # ratio above 1/chunk is a fused-engine regression
        GATE_FAILURES.append(
            f"async pipeline consume issued {disp_async} dispatches/chunk (want <= 1)"
        )

    # -- epoch transition A/B: events/s across a LIVE schema evolution --------
    # An in-band SchemaEvolved lands mid-stream (control plane): evict, lazy
    # recompile at the new state, jit retrace -- all inside the timed run.
    # Gates: the in-band run must emit EXACTLY the out-of-band oracle's rows
    # (zero dropped/duplicated rows across the transition), and fused
    # dispatches/chunk must stay at 1 per instance, including on a
    # 4-instance Cluster over sliced sources.
    from repro.etl import Cluster, CollectSink, EventChunkSource, Pipeline
    from repro.etl.control import SchemaEvolved

    n_epoch_chunks = 8
    mid = n_epoch_chunks // 2

    def _epoch_world():
        sc_e = build_scenario(cfg)
        coord_e = StateCoordinator(sc_e.registry, sc_e.dpm)
        reg_e = sc_e.registry
        o_e = reg_e.domain.schema_ids()[0]
        v_e = reg_e.domain.latest_version(o_e)
        keep = tuple(a.name for a in reg_e.domain.get(o_e, v_e).attributes)[1:]
        ev = SchemaEvolved(tree="domain", schema_id=o_e, keep=keep, add=("bench_evo",))
        return sc_e, coord_e, (o_e, v_e, keep), ev

    def _keys(rows_):
        return [r[3] for r in rows_]

    # out-of-band oracle: same grid, manual apply_update + refresh at mid
    sc_o, coord_o, (o_o, v_o, keep_o), _ = _epoch_world()
    app_o = METLApp(coord_o, engine="fused")
    src_o = EventSource(sc_o.registry, seed=3)
    t0 = time.perf_counter()
    rows_oob = []
    for k in range(n_epoch_chunks):
        if k == mid:
            def _mutate(r):
                r.evolve(r.domain, o_o, keep=list(keep_o), add=["bench_evo"])
                return ("added_domain", o_o, v_o + 1)
            coord_o.apply_update(_mutate)
            app_o.refresh()
        rows_oob.extend(app_o.consume(src_o.slice_columnar(k * n_events, n_events)))
    us_oob = (time.perf_counter() - t0) * 1e6
    total_epoch_ev = n_epoch_chunks * n_events

    # in-band: the same evolution as a control event ON the stream
    sc_i, coord_i, _, ev_i = _epoch_world()
    app_i = METLApp(coord_i, engine="fused")
    sink_i = CollectSink()
    pipe_i = Pipeline(
        EventChunkSource(EventSource(sc_i.registry, seed=3), chunk_size=n_events,
                         max_chunks=n_epoch_chunks, control={mid: ev_i}),
        app_i, [sink_i],
    )
    t0 = time.perf_counter()
    pipe_i.run()
    us_inband = (time.perf_counter() - t0) * 1e6
    rows.append((
        f"mapping/epoch_transition_oob_{n_epoch_chunks}x{n_events}ev",
        us_oob,
        f"{total_epoch_ev / (us_oob / 1e6):.0f} events/s across an out-of-band evolution",
    ))
    rows.append((
        f"mapping/epoch_transition_inband_{n_epoch_chunks}x{n_events}ev",
        us_inband,
        f"{total_epoch_ev / (us_inband / 1e6):.0f} events/s across an in-band "
        f"evolution, {us_oob / us_inband:.2f}x vs out-of-band, "
        f"{app_i.stats['dispatches']} dispatches/{n_epoch_chunks} chunks",
    ))
    if _keys(sink_i.rows) != _keys(rows_oob):
        GATE_FAILURES.append(
            f"epoch transition dropped/duplicated rows: in-band emitted "
            f"{len(sink_i.rows)} rows vs oracle {len(rows_oob)}"
        )
    if app_i.stats["dispatches"] > n_epoch_chunks:
        GATE_FAILURES.append(
            f"in-band epoch transition issued {app_i.stats['dispatches']} "
            f"dispatches over {n_epoch_chunks} chunks (want <= 1/chunk)"
        )

    # 4-instance cluster over sliced sources, same stream + evolution
    sc_c, coord_c, _, ev_c = _epoch_world()
    sink_c = CollectSink()
    cluster = Cluster.over_stream(
        coord_c, EventSource(sc_c.registry, seed=3), instances=4,
        chunk_size=n_events, max_chunks=n_epoch_chunks, control={mid: ev_c},
        sinks=[sink_c],
    )
    t0 = time.perf_counter()
    cluster.run()
    us_cluster = (time.perf_counter() - t0) * 1e6
    cinfo = cluster.info()
    rows.append((
        f"mapping/epoch_transition_cluster4_{n_epoch_chunks}x{n_events}ev",
        us_cluster,
        f"{total_epoch_ev / (us_cluster / 1e6):.0f} events/s across the same "
        f"evolution on 4 instances, {cinfo['dispatches']} total dispatches, "
        f"per-instance states {cinfo['states']}",
    ))
    if _keys(sink_c.rows) != _keys(rows_oob):
        GATE_FAILURES.append(
            f"4-instance cluster diverged across the epoch transition: "
            f"{len(sink_c.rows)} rows vs single-instance {len(rows_oob)}"
        )
    for k, app_k in enumerate(cluster.apps):
        # instance k owns chunks k, k+4, ... below n_epoch_chunks
        own = len(range(k, n_epoch_chunks, 4))
        if app_k.stats["dispatches"] > own:
            GATE_FAILURES.append(
                f"cluster instance {k} issued {app_k.stats['dispatches']} "
                f"dispatches over {own} chunks (want <= 1/chunk/instance)"
            )

    # -- replicated vs sharded A/B (subprocess per shard count) ---------------
    for shards in ((2,) if smoke else (2, 4, 8)):
        rows.extend(_sharded_ab(shards, smoke))
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny shapes, CI-sized")
    ap.add_argument("--sharded-worker", type=int, default=0,
                    help="internal: emit sharded A/B rows on a forced "
                         "N-device topology (set via XLA_FLAGS by the parent)")
    args = ap.parse_args()
    if args.sharded_worker:
        for name, us, derived in sharded_worker(args.sharded_worker, args.smoke):
            print(f"{name},{us:.1f},{derived}", flush=True)
        for entry in ENGINE_METRICS:
            print(f"metric:{json.dumps(entry)}", flush=True)
        for msg in GATE_FAILURES:
            print(f"gate:{json.dumps(msg)}", flush=True)
        sys.exit(0)
    print("name,us_per_call,derived")
    for name, us, derived in run(smoke=args.smoke):
        print(f"{name},{us:.1f},{derived}", flush=True)
    if GATE_FAILURES:
        for msg in GATE_FAILURES:
            print(f"GATE FAILURE: {msg}", file=sys.stderr)
        sys.exit(1)
