"""Serving launcher: batched greedy decoding with the continuous-batching
server.

    PYTHONPATH=src python -m repro.launch.serve --arch olmo_1b --smoke \
        --requests 8 --max-new 16

With ``--etl`` the prompts are not random: a CDC stream flows through the
streaming METL pipeline (``EventChunkSource -> METLApp -> TokenizerSink``,
:mod:`repro.etl.pipeline`) with the *fused* mapping engine (one device
dispatch per event chunk, :mod:`repro.etl.engines`), and the bounded
tokenizer sink backpressures the pull once serving has enough prompts --
the paper's pipeline (CDC -> DMM -> CDM) fronting the model server.  The
source yields **columnar chunks** (payload (uid, value) arrays built once
at the source boundary), so the hot consume thread densifies in pure numpy
instead of walking payload dicts.  Add ``--async-consume`` for the
double-buffered consume: chunk N+1's host-side densification overlaps
chunk N's in-flight device dispatch (single-threaded on the host, riding
jax async dispatch -- see repro.etl.pipeline).

    PYTHONPATH=src python -m repro.launch.serve --arch olmo_1b --smoke --etl

``--shards N`` (with ``--etl``) switches the app to ``engine="sharded"``:
the fused DMM block table partitions over the ``data`` axis of a 1xN mesh
(each device holds only its slice; emitted rows are all-gathered before
emission).  On CPU the fake N-device topology is forced via XLA_FLAGS
*before* jax initialises, which is why the flag must be handled here in the
entrypoint.

    PYTHONPATH=src python -m repro.launch.serve --arch olmo_1b --smoke \
        --etl --shards 4

``--device-densify`` (with ``--etl``) moves chunk densification on-device:
the raw columnar (uid, value) items cross the host->device boundary in ONE
packed int32 transfer per chunk and are resolved + densified + mapped inside
the single fused dispatch (:mod:`repro.kernels.densify_map`) -- no host
scatter, no dense payload tensor on the PCIe link.  Composes with
``--shards`` and ``--instances``::

    PYTHONPATH=src python -m repro.launch.serve --arch olmo_1b --smoke \
        --etl --device-densify --async-consume

``--instances N`` (with ``--etl``) fans the stream over a multi-instance
:class:`~repro.etl.cluster.Cluster`: N pipelines over deterministic
round-robin slices of one chunk grid, one coordinator as the single state
writer, and the bounded tokenizer sink as the merge fan-in (paper SS5.5
horizontal scaling).  Composes with ``--shards`` (every instance runs the
sharded engine on the same mesh) and ``--async-consume``.

    PYTHONPATH=src python -m repro.launch.serve --arch olmo_1b --smoke \
        --etl --instances 4

``--replicated`` (with ``--etl --instances N``) runs the same fan-out as a
**distributed control plane** (:mod:`repro.etl.replication`): an in-process
leader owns the single-writer coordinator and streams term-fenced control
records over the socket transport to N-1 follower *processes*, each of
which rebuilds state via ``replay_control_log`` from the leader's snapshot
and maps its own deterministic slice of the chunk grid.  Follower rows come
back as wire-encoded chunk files and merge with the leader's rows in global
chunk order before tokenization -- the multi-process analogue of the
Cluster fan-in.

    PYTHONPATH=src python -m repro.launch.serve --arch olmo_1b --smoke \
        --etl --instances 3 --replicated
"""

from __future__ import annotations

import argparse
import os


def _etl_prompts(
    n_requests: int,
    vocab: int,
    max_len: int = 16,
    shards: int = 0,
    async_consume: bool = False,
    instances: int = 0,
    device_densify: bool = False,
):
    """Stream CDC events through the METL pipeline into token prompts.

    The pull topology is ``EventChunkSource -> METLApp -> TokenizerSink``:
    the bounded sink (``limit=n_requests``) backpressures the stream, so the
    pipeline pulls exactly as many chunks as serving needs.  With
    ``instances > 1`` the stream is sliced deterministically over a
    multi-instance :class:`~repro.etl.cluster.Cluster` (one coordinator as
    the single state writer, the bounded sink as the merge fan-in)."""
    from repro.core.state import StateCoordinator
    from repro.core.synthetic import ScenarioConfig, build_scenario
    from repro.etl import (
        Cluster,
        EventChunkSource,
        EventSource,
        METLApp,
        Pipeline,
        TokenizerSink,
    )

    sc = build_scenario(ScenarioConfig(n_schemas=6, versions_per_schema=3, seed=7))
    coord = StateCoordinator(sc.registry, sc.dpm)
    engine, mesh = "fused", None
    if shards > 1:
        from repro.launch.mesh import make_etl_mesh

        engine, mesh = "sharded", make_etl_mesh(shards)
    sink = TokenizerSink(vocab, max_len=max_len, limit=n_requests)
    stream = EventSource(sc.registry, seed=7)
    if instances > 1:
        # columnar chunks, sliced round-robin over the instances; lockstep
        # rounds keep every instance at the same state i (paper SS5.5)
        cluster = Cluster.over_stream(
            coord, stream, instances=instances, chunk_size=256,
            sinks=[sink], engine=engine, mesh=mesh,
            device_densify=device_densify,
            async_consume=async_consume,
        )
        # pull until the bounded sink gates the stream; a whole window of
        # rounds with zero canonical rows means the stream is unmappable --
        # bail out instead of spinning on an unbounded source forever
        total = 0
        while not sink.full():
            st = cluster.run(max_rounds=16 * instances)
            total += st.rows
            if st.rows == 0:
                raise RuntimeError(
                    f"ETL cluster produced no canonical rows in {st.events} "
                    f"events this window"
                )
        info = cluster.info()
        print(
            f"etl: cluster of {info['instances']} instances "
            f"(engine={info['engine']}, state i={info['state']}, "
            f"per-instance states {info['states']}): {info['events']} events "
            f"-> {total} canonical rows in {info['dispatches']} dispatches"
            f"{', async double-buffered' if async_consume else ''}"
        )
        return sink.prompts
    app = METLApp(coord, engine=engine, mesh=mesh, device_densify=device_densify)
    if device_densify:
        print(
            "etl: device densify on -- raw columnar items cross host->device "
            "in one packed transfer, densified inside the fused dispatch"
        )
    if shards > 1:
        info = app.engine.info()
        print(
            f"etl: sharded engine over {info['n_shards']} shards, "
            f"{info['table_bytes_per_shard']} table bytes/shard "
            f"({info['n_blocks']} blocks, {info['blocks_per_shard']}/shard)"
        )
    # columnar=True (the default): payloads flatten to (uid, value) arrays
    # once at the source boundary; consume densifies in pure numpy
    source = EventChunkSource(stream, chunk_size=256, columnar=True)
    pipe = Pipeline(source, app, [sink], async_consume=async_consume)
    # pull until serving has enough prompts; a whole 16-chunk window with
    # zero canonical rows means the stream is unmappable -- bail out
    total_rows = 0
    while not sink.full():
        st = pipe.run(max_chunks=16)
        total_rows += st.rows
        if st.rows == 0:
            raise RuntimeError(
                f"ETL stream produced no canonical rows in {st.events} "
                f"events (total {app.stats['events']})"
            )
    print(
        f"etl: {app.stats['events']} events -> {total_rows} canonical rows "
        f"in {app.stats['dispatches']} device dispatches "
        f"({app.stats['events'] / max(1, app.stats['dispatches']):.0f} events/dispatch"
        f"{', async double-buffered' if async_consume else ''})"
    )
    return sink.prompts


def _src_path() -> str:
    """PYTHONPATH for follower subprocesses: the tree this repro package was
    imported from, plus whatever the parent already had."""
    import repro

    # repro is a namespace package (no __init__.py): __file__ is None, the
    # package dir lives in __path__
    src = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    have = os.environ.get("PYTHONPATH", "")
    return src + (os.pathsep + have if have else "")


def _etl_replicated(n_requests: int, vocab: int, max_len: int = 16,
                    instances: int = 2) -> list:
    """Leader/follower multi-process METL: the ``--replicated`` path.

    One in-process :class:`~repro.etl.replication.LeaderNode` (slot 0 of the
    chunk grid) + ``instances - 1`` follower subprocesses (``python -m
    repro.etl.replication --role follower``) over the socket transport.  A
    small churn schedule exercises live schema evolution across the
    replicated control plane; rows merge in global chunk order."""
    import json
    import subprocess
    import sys
    import tempfile

    from repro.core.state import StateCoordinator
    from repro.core.synthetic import ScenarioConfig, build_scenario, churn_schedule
    from repro.etl import EventSource, TokenizerSink
    from repro.etl.replication import DataPlane, LeaderNode
    from repro.etl.transport import SocketServer, row_from_wire

    instances = max(2, instances)
    max_chunks, chunk_size = 4 * instances, 256
    sc = build_scenario(ScenarioConfig(n_schemas=6, versions_per_schema=3, seed=7))
    coord = StateCoordinator(sc.registry, sc.dpm)
    leader = LeaderNode(coord, term=1)
    churn = churn_schedule(sc.registry, steps=2, first_chunk=2,
                           every=instances, seed=8)
    leader.set_schedule({k: [v] for k, v in churn.items()})

    srv = SocketServer(port=0)
    tmp = tempfile.mkdtemp(prefix="serve-repl-")
    procs, outs = [], []
    for slot in range(1, instances):
        out = os.path.join(tmp, f"follower{slot}.jsonl")
        outs.append(out)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "repro.etl.replication",
             "--role", "follower", "--host", "127.0.0.1",
             "--port", str(srv.port), "--slot", str(slot),
             "--instances", str(instances),
             "--max-chunks", str(max_chunks),
             "--chunk-size", str(chunk_size),
             "--stream-seed", "7", "--out", out],
            env={**os.environ, "PYTHONPATH": _src_path()},
        ))
    for _ in procs:
        leader.attach(srv.accept(timeout=60.0), timeout=60.0)

    by_chunk = {}
    plane = DataPlane(coord, EventSource(sc.registry, seed=7), slot=0,
                      instances=instances, max_chunks=max_chunks,
                      chunk_size=chunk_size)
    leader.run(plane, on_chunk=lambda h, rows: by_chunk.__setitem__(h, rows))
    leader.finish(end=max_chunks - 1, wait_done=True, timeout=120.0)
    for p in procs:
        if p.wait(timeout=120) != 0:
            raise RuntimeError(f"replicated follower exited {p.returncode}")
    for out in outs:
        with open(out) as f:
            for line in f:
                d = json.loads(line)
                by_chunk[d["chunk"]] = [row_from_wire(r) for r in d["rows"]]
    leader.close()
    srv.close()

    sink = TokenizerSink(vocab, max_len=max_len, limit=n_requests)
    for h in sorted(by_chunk):
        sink.write(by_chunk[h])
        if sink.full():
            break
    if not sink.full():
        raise RuntimeError(
            f"replicated ETL produced only {len(sink.prompts)} prompts of "
            f"{n_requests} over {max_chunks} chunks"
        )
    info = leader.coordinator.replication_info()
    print(
        f"etl: replicated control plane, 1 leader + {instances - 1} followers "
        f"(term {info['term']}, log_offset {info['log_offset']}, "
        f"state i={coord.registry.state}): "
        f"{sum(len(v) for v in by_chunk.values())} canonical rows over "
        f"{len(by_chunk)} chunks"
    )
    return sink.prompts


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo_1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--etl", action="store_true",
                    help="feed prompts from the fused METL mapping path")
    ap.add_argument("--shards", type=int, default=0,
                    help="with --etl: shard the DMM block table over a 1xN "
                         "mesh data axis (engine='sharded'); 0/1 = replicated")
    ap.add_argument("--instances", type=int, default=0,
                    help="with --etl: fan the stream over N horizontally-"
                         "scaled METL instances (a Cluster with one "
                         "coordinator as the single state writer); 0/1 = "
                         "one pipeline")
    ap.add_argument("--replicated", action="store_true",
                    help="with --etl --instances N: run the fan-out as a "
                         "distributed control plane -- an in-process leader "
                         "streams fenced control records to N-1 follower "
                         "processes over the socket transport "
                         "(repro.etl.replication)")
    ap.add_argument("--async-consume", action="store_true",
                    help="with --etl: double-buffered pipeline consume "
                         "(chunk N+1 densifies while chunk N is on device)")
    ap.add_argument("--device-densify", action="store_true",
                    help="with --etl: densify on-device (one packed "
                         "host->device transfer + one fused dispatch per "
                         "chunk; no host scatter)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args(argv)

    if args.etl and args.shards > 1:
        # must land before the first jax import: device topology is pinned
        # at backend init (no-op on real multi-device backends)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.shards}"
        ).strip()

    import numpy as np
    import jax

    import repro.configs as configs
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import model as M
    from repro.serve.decode import ServeConfig, Server

    if args.etl and args.replicated and jax.default_backend() == "tpu":
        raise SystemExit(
            "--replicated starts follower processes that import JAX, and a "
            "TPU belongs to one process: they cannot share the chip this "
            "process holds.  Replicas as devices of one process are ROADMAP "
            "R5; run --replicated on the CPU backend."
        )
    use_compile_cache()
    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    sc = ServeConfig(batch=args.batch, cache_len=args.cache_len, max_new=args.max_new)
    server = Server(params, cfg, sc)
    if args.etl and args.replicated:
        if args.shards > 1 or args.device_densify or args.async_consume:
            raise SystemExit(
                "--replicated composes with --instances only (follower "
                "processes run the plain fused engine)"
            )
        prompts = _etl_replicated(
            args.requests, cfg.vocab, instances=max(2, args.instances)
        )
    elif args.etl:
        prompts = _etl_prompts(
            args.requests, cfg.vocab, shards=args.shards,
            async_consume=args.async_consume, instances=args.instances,
            device_densify=args.device_densify,
        )
    else:
        rng = np.random.default_rng(0)
        prompts = [
            rng.integers(2, cfg.vocab, size=rng.integers(2, 8)).tolist()
            for _ in range(args.requests)
        ]
    rids = [server.submit(p) for p in prompts]
    server.run(n_steps=args.requests * (args.max_new + 8))
    for rid in rids:
        toks = server.done.get(rid)
        print(f"request {rid}: {len(toks or [])} tokens -> {toks[:12] if toks else 'PENDING'}")


if __name__ == "__main__":
    main()
