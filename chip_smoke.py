#!/usr/bin/env python3
"""On-chip smoke test of METL's mapping path at EOS paper scale.

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --chips 4    # the sharded engine on four chips

Drives source -> triage -> densify -> fused dispatch -> emit -> sink through
the library entry points (``EventChunkSource`` -> ``METLApp`` ->
``Pipeline``) on a registry the size of the paper's EOS deployment (§3.5:
>10,000 extraction attributes, >1,000 CDM attributes, >=10 versions per
schema, ~10 attributes per version): 125 schemas x 10 versions x 10
attributes into 20 business entities x 50 attributes.

One chip runs four phases of 32 chunks each, with one in-band
``SchemaEvolved`` landing mid-stream (a plan rebuild and its upload happen
on the chip):

  a  fused engine, host densify, 512-event chunks, sync
  b  fused engine, device densify, 512-event chunks, async double buffer
  c  fused engine, device densify, 64- and 4096-event chunks

plus a check of each mapping kernel against its ``kernels/ref.py`` twin.
Every emitted row is compared with ``METLApp.consume_scalar`` (the
per-event Algorithm-6 oracle) on the same events at the same state: keys
and float32 value bits must be identical.  Each phase must show one
dispatch per chunk, one host->device transfer per device-densified chunk,
and a dispatch program that holds a compiled kernel (``tpu_custom_call``),
and the evolution must leave the plan's table shapes as they were
(``table_shape_changes`` 0), so no chunk program recompiles for it.

``--chips 4`` runs only the sharded engine (``make_etl_mesh(4)``, host and
device densify), compared row for row with the fused engine on the same
stream and with the oracle, and checks that the block table splits over
the four devices.

The script needs a TPU: on any other platform it exits nonzero before any
phase.  Any failed check raises.  The last line of stdout is one JSON
object naming the device; nothing after it.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import functools
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402

from repro.core.dmm_jax import bucket_rows  # noqa: E402
from repro.core.state import StateCoordinator  # noqa: E402
from repro.core.synthetic import ScenarioConfig, build_scenario, churn_schedule  # noqa: E402
from repro.etl import EventSource, METLApp  # noqa: E402
from repro.etl.control import ControlEvent  # noqa: E402
from repro.etl.engines import ColumnarDense  # noqa: E402
from repro.etl.pipeline import EventChunkSource, Pipeline, RowSink, Source  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.densify_map import densify_map  # noqa: E402
from repro.kernels.masked_gather import masked_gather  # noqa: E402
from repro.kernels.onehot_map import onehot_map  # noqa: E402
from repro.kernels.segmented_gather import segmented_gather  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

EOS = ScenarioConfig(
    n_schemas=125, versions_per_schema=10, attrs_per_version=10,
    n_entities=20, cdm_attrs=50, seed=7,
)


@dataclasses.dataclass(frozen=True)
class PhaseSpec:
    name: str
    engine: str  # "fused" | "sharded"
    device_densify: bool
    chunk_size: int
    async_consume: bool
    n_chunks: int = 32
    churn_at: int = 16  # chunk index the in-band SchemaEvolved precedes


ONE_CHIP = [
    PhaseSpec("a fused host 512 sync", "fused", False, 512, False),
    PhaseSpec("b fused device 512 async", "fused", True, 512, True),
    PhaseSpec("c fused device 64 sync", "fused", True, 64, False),
    PhaseSpec("c fused device 4096 sync", "fused", True, 4096, False),
]

FOUR_CHIPS = [
    PhaseSpec("sharded host 512 sync", "sharded", False, 512, False),
    PhaseSpec("sharded device 512 async", "sharded", True, 512, True),
]

Row = Tuple[int, int, int, Tuple[Tuple[int, int], ...]]  # key, r, w, (uid, f32 bits)


class _Recorder(Source):
    """Passes a source's stream through and keeps every item it yields, so
    the oracle can replay the same chunks and control events."""

    def __init__(self, inner: Source) -> None:
        self.inner = inner
        self.items: List[Any] = []

    def poll(self):
        for item in self.inner.poll():
            self.items.append(item)
            yield item


class _ChunkSink(RowSink):
    """Keeps the rows of each chunk apart (the pipeline writes once per chunk)."""

    def __init__(self) -> None:
        self.chunks: List[List[Any]] = []

    def write(self, rows) -> None:
        self.chunks.append(list(rows))


@dataclasses.dataclass
class PhaseResult:
    spec: PhaseSpec
    events: int
    chunks: int
    rows: List[List[Row]]  # canonical rows per chunk, in emission order
    items: List[Any]  # the recorded stream (chunks + control events)
    info: Dict[str, Any]
    first_s: float
    wall_s: float
    program: str  # lowered text of the phase's dispatch program
    table_devices: int


def require(ok: bool, what: str) -> None:
    """One smoke check; raises under ``python -O`` too, unlike ``assert``."""
    if not ok:
        raise AssertionError(what)


def _bits(x: Any) -> int:
    return int(np.float32(x).view(np.int32))


def canon_rows(registry: Any, rows: Sequence[Any]) -> List[Row]:
    out: List[Row] = []
    for (r, w), vals, mask, key in rows:
        uids = registry.range.get(r, w).uids
        keep = np.nonzero(mask)[0].tolist()
        bits = np.asarray(vals, np.float32).view(np.int32).tolist()
        out.append((int(key), r, w, tuple(sorted((uids[i], bits[i]) for i in keep))))
    return out


def _world(cfg: ScenarioConfig) -> Tuple[Any, StateCoordinator]:
    sc = build_scenario(cfg)
    return sc, StateCoordinator(sc.registry, sc.dpm)


def _dispatch_program(app: METLApp, chunk: Any) -> str:
    """Lowered text of the program the engine dispatches for ``chunk``."""
    eng = app.engine
    dense = eng.densify(app.triage(chunk))
    plan = dense.plan
    mesh = getattr(plan, "mesh", None)
    if isinstance(dense, ColumnarDense):
        statics = dict(n_items=dense.n_items, n_events=dense.n_events,
                       n_rows=dense.n_rows, k=dense.k, impl=eng.impl)
        if mesh is None:
            fn = functools.partial(ops.dmm_apply_columnar, **statics)
            args = (dense.packed, plan.uid_slot_dev, plan.uid_col_dev, plan.src2d)
        else:
            fn = functools.partial(ops.dmm_apply_columnar_sharded, mesh=mesh,
                                   n_shards=dense.n_shards, **statics)
            args = (dense.packed, plan.uid_slot_dev, plan.uid_col_dev, plan.src3d)
    elif mesh is None:
        s = dense.row_ids.size
        pad = (0, bucket_rows(s) - s)
        fn = functools.partial(ops.dmm_apply_fused, impl=eng.impl)
        args = (dense.vals, dense.mask, np.pad(dense.row_ids, pad),
                np.pad(dense.blk_ids, pad), plan.src2d)
    else:
        fn = functools.partial(ops.dmm_apply_sharded, mesh=mesh, impl=eng.impl)
        args = (dense.vals, dense.mask, dense.rows_sh, dense.blks_sh, plan.src3d)
    return jax.jit(fn).lower(*args).as_text()


def run_phase(
    cfg: ScenarioConfig,
    spec: PhaseSpec,
    *,
    impl: str = "auto",
    mesh: Any = None,
    seed: int = 0,
) -> PhaseResult:
    """Stream ``spec.n_chunks`` chunks through ``Pipeline`` with one in-band
    schema evolution, and account dispatches, transfers and times."""
    sc, coord = _world(cfg)
    sched = churn_schedule(sc.registry, steps=1, first_chunk=spec.churn_at, seed=cfg.seed)
    events = EventSource(sc.registry, seed=seed)
    src = _Recorder(EventChunkSource(
        events, chunk_size=spec.chunk_size, max_chunks=spec.n_chunks, control=sched,
    ))
    app = METLApp(coord, engine=spec.engine, mesh=mesh, impl=impl,
                  device_densify=spec.device_densify)
    sink = _ChunkSink()
    pipe = Pipeline(src, app, [sink], async_consume=spec.async_consume)
    t0 = time.perf_counter()
    st = pipe.run(max_chunks=1)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    st2 = pipe.run()
    wall = time.perf_counter() - t0
    pipe.close()
    info = app.engine.info()
    rows = [canon_rows(sc.registry, chunk_rows) for chunk_rows in sink.chunks]
    probe = events.slice_columnar(spec.n_chunks * spec.chunk_size, spec.chunk_size)
    program = _dispatch_program(app, probe)
    table = getattr(app.engine.plan, "src3d", None)
    if table is None:
        table = app.engine.plan.src2d
    return PhaseResult(
        spec=spec,
        events=st.events + st2.events,
        chunks=st.chunks + st2.chunks,
        rows=rows,
        items=src.items,
        info=info,
        first_s=first,
        wall_s=wall,
        program=program,
        table_devices=len({s.device for s in table.addressable_shards}),
    )


def oracle_rows(cfg: ScenarioConfig, items: Sequence[Any]) -> List[List[Row]]:
    """Replay the recorded stream through ``METLApp.consume_scalar`` in a
    fresh world: control events apply at the same chunk boundaries, each
    event maps at the state its chunk was mapped at, duplicates (same key)
    map once."""
    sc, coord = _world(cfg)
    app = METLApp(coord)
    seen: set = set()
    out: List[List[Row]] = []
    for item in items:
        if isinstance(item, ControlEvent):
            coord.apply(item, defer_frozen=True)
            continue
        rows: List[Row] = []
        for ev in item.events:
            if ev.key in seen:
                continue
            seen.add(ev.key)
            for m in app.consume_scalar([ev]):
                payload = tuple(sorted((uid, _bits(v)) for uid, v in m.payload.items()))
                rows.append((ev.key, m.schema_id, m.version, payload))
        out.append(rows)
    return out


def check_phase(res: PhaseResult, want: List[List[Row]]) -> None:
    """Rows identical to the oracle (as multisets per chunk), one dispatch
    per chunk, one transfer per device-densified chunk."""
    spec = res.spec
    require(res.chunks == spec.n_chunks, f"{spec.name}: {res.chunks} chunks mapped")
    require(len(res.rows) == len(want) == res.chunks,
            f"{spec.name}: {len(res.rows)} chunks written, oracle {len(want)}")
    for i, (got, exp) in enumerate(zip(res.rows, want)):
        if sorted(got) != sorted(exp):
            raise AssertionError(
                f"{spec.name}: chunk {i} rows differ from consume_scalar "
                f"({len(got)} vs {len(exp)} rows)"
            )
    require(res.info["dispatches"] == res.chunks,
            f"{spec.name}: {res.info['dispatches']} dispatches for {res.chunks} chunks")
    require(res.info["readbacks_early"] == res.info["dispatches"],
            f"{spec.name}: {res.info['readbacks_early']} read-backs started at dispatch "
            f"for {res.info['dispatches']} dispatches")
    per_chunk = 1 if spec.device_densify else 4
    require(res.info["transfers"] == per_chunk * res.chunks,
            f"{spec.name}: {res.info['transfers']} transfers for {res.chunks} chunks")
    require(res.info["rebuilds"] >= 2, f"{spec.name}: no plan rebuild mid-stream")
    require(res.info["table_shape_changes"] == 0,
            f"{spec.name}: the evolution changed the plan's table shapes "
            f"{res.info['table_shape_changes']} times (every chunk shape recompiles)")


def check_kernels(interpret: bool = False, seed: int = 0) -> List[str]:
    """Each mapping kernel against its ``ref.py`` twin, bit for bit, at the
    EOS plan's shapes (512 events, a 2048 x 128 block table: 1,249 blocks
    in their capacity bucket)."""
    rng = np.random.default_rng(seed)
    b, n_in, w, n_blocks, s, k = 512, 128, 128, bucket_rows(1249), 512, 16
    vals = rng.integers(1, 1_000_000, size=(b, n_in)).astype(np.float32)
    mask = (rng.random((b, n_in)) < 0.7).astype(np.int8)
    src2d = np.full((n_blocks, w), -1, np.int32)
    for t in range(n_blocks):
        m = int(rng.integers(1, 51))
        src2d[t, rng.choice(w, size=m, replace=False)] = rng.choice(n_in, size=m, replace=False)
    rows = rng.integers(b, size=s).astype(np.int32)
    blks = rng.integers(n_blocks, size=s).astype(np.int32)
    slot2d = np.where(rng.random((b, k)) < 0.8, rng.integers(n_in, size=(b, k)), -1).astype(np.int32)
    x2d = rng.integers(1, 1_000_000, size=(b, k)).astype(np.float32)
    cases = {
        "segmented_gather": (
            segmented_gather(vals, mask, rows, blks, src2d, fill=0.5, interpret=interpret),
            ref.segmented_gather_ref(vals, mask, rows, blks, src2d, fill=0.5),
        ),
        "densify_map": (
            densify_map(slot2d, x2d, rows, blks, src2d, fill=0.5, interpret=interpret),
            ref.densify_map_ref(slot2d, x2d, rows, blks, src2d, fill=0.5),
        ),
        "masked_gather": (
            masked_gather(vals, mask, src2d[3], interpret=interpret),
            ref.masked_gather_ref(vals, mask, src2d[3]),
        ),
        "onehot_map": (
            onehot_map(vals, mask, src2d[3], interpret=interpret),
            ref.onehot_map_ref(vals, mask, src2d[3]),
        ),
    }
    for name, ((kv, km), (rv, rm)) in cases.items():
        kv, rv = np.asarray(kv), np.asarray(rv)
        if not (np.array_equal(kv.view(np.int32), rv.view(np.int32))
                and np.array_equal(np.asarray(km), np.asarray(rm))):
            raise AssertionError(f"kernel {name} differs from its ref.py twin")
    return list(cases)


def _paper_scale(cfg: ScenarioConfig) -> Tuple[int, int]:
    sc = build_scenario(cfg)
    n_cdm, n_ext = sc.shape
    require(n_ext >= 10_000, f"{n_ext} extraction attributes < 10,000")
    require(n_cdm >= 1_000, f"{n_cdm} CDM attributes < 1,000")
    return n_ext, n_cdm


def _report(res: PhaseResult, *extra: str) -> None:
    n_rows = sum(len(r) for r in res.rows)
    print(
        f"phase {res.spec.name}: events={res.events} chunks={res.chunks} "
        f"rows={n_rows} dispatches={res.info['dispatches']} "
        f"readbacks_early={res.info['readbacks_early']} "
        f"table_shape_changes={res.info['table_shape_changes']} "
        f"uid_capacity={res.info['uid_capacity']} "
        f"block_capacity={res.info['block_capacity']} "
        f"transfers={res.info['transfers']} rebuilds={res.info['rebuilds']} "
        f"first_call_s={res.first_s:.3f} wall_s={res.wall_s:.3f} "
        f"oracle=identical kernel=tpu_custom_call {' '.join(extra)}".rstrip(),
        flush=True,
    )


def one_chip(cfg: ScenarioConfig) -> None:
    t0 = time.perf_counter()
    names = check_kernels()
    print(f"kernels vs ref twins: bit-exact ({', '.join(names)}) "
          f"in {time.perf_counter() - t0:.3f} s", flush=True)
    for spec in ONE_CHIP:
        res = run_phase(cfg, spec)
        check_phase(res, oracle_rows(cfg, res.items))
        require("tpu_custom_call" in res.program, f"{spec.name}: kernel not compiled")
        _report(res)


def four_chips(cfg: ScenarioConfig) -> None:
    from repro.launch.mesh import make_etl_mesh

    require(jax.device_count() == 4, f"--chips 4 needs 4 devices, found {jax.device_count()}")
    mesh = make_etl_mesh(4)
    for spec in FOUR_CHIPS:
        res = run_phase(cfg, spec, mesh=mesh)
        check_phase(res, oracle_rows(cfg, res.items))
        require("tpu_custom_call" in res.program, f"{spec.name}: kernel not compiled")
        twin = run_phase(cfg, dataclasses.replace(spec, engine="fused"))
        if twin.rows != res.rows:
            raise AssertionError(f"{spec.name}: rows differ from the fused engine")
        info = res.info
        require(info["n_shards"] == 4 and res.table_devices == 4,
                f"{spec.name}: table on {res.table_devices} devices, {info['n_shards']} shards")
        require(info["table_bytes_per_shard"] * 4 == info["table_bytes"],
                f"{spec.name}: {info['table_bytes_per_shard']} B per shard of {info['table_bytes']}")
        _report(
            res,
            f"fused_twin=identical shards={info['n_shards']} "
            f"table_bytes={info['table_bytes']} "
            f"table_bytes_per_shard={info['table_bytes_per_shard']} "
            f"fused_table_bytes={twin.info['table_bytes']}",
        )


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    print(f"device: platform={dev.platform} kind={dev.device_kind} count={device['count']}",
          flush=True)
    cache_dir = use_compile_cache()
    cache = collections.Counter()
    jax.monitoring.register_event_listener(lambda event, **_: cache.update([event]))
    n_ext, n_cdm = _paper_scale(EOS)
    print(f"scenario: EOS paper scale, {n_ext} extraction x {n_cdm} CDM attributes",
          flush=True)
    if args.chips == 4:
        four_chips(EOS)
    else:
        one_chip(EOS)
    hits = cache["/jax/compilation_cache/cache_hits"]
    misses = cache["/jax/compilation_cache/cache_misses"]
    print(f"compile cache: dir={cache_dir} hits={hits} misses={misses}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
