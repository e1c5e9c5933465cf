"""Paper Figure 5 / §5.2-5.3 compaction ratios + the plan-lifecycle soak.

Two halves:

1. **Compaction ratios** — the >99% / >99.9% claims at paper scale (>10k
   extraction attributes, ~1k CDM attributes, 10 versions per schema) and
   the Figure-5 worked example (30 -> 7 balanced, 30 -> 5+1 aggressive).

2. **Production-scale soak** — an A/B run of the epoched plan lifecycle
   (``repro.etl.plan.PlanManager``) at ``soak_config()`` scale (80 schemas
   x 6 versions ~= 480 live version columns; a 16x3 miniature under
   ``--smoke``) under continuous schema churn:

   * arm A: incremental recompaction (``recompile_columns`` + splice),
   * arm B: full rebuild on every evolution (the bit-exactness oracle).

   Gates (GATE_FAILURES, fail the harness): A and B emit identical row
   keys in order (zero dropped/duplicated rows across every cutover), and
   — full size only — A's amortised churn rebuild time and p99 chunk
   latency beat/track B's.  Throughputs, the amortised rebuild
   rate and the compaction ratio land in PERF_METRICS so
   ``scripts/perf_diff.py`` tracks them across trajectory artifacts.

All plans here are acquired through the PlanManager — benchmarks never
construct or publish a fused plan directly (the ``plan-publish-single-site``
analyzer rule holds this door shut).
"""

from __future__ import annotations

import time

import numpy as np

from repro.core.dmm import (
    compaction_ratio,
    dpm_size,
    dusb_size,
    transform_to_dpm,
    transform_to_dusb,
)
from repro.core.state import StateCoordinator
from repro.core.synthetic import (
    ScenarioConfig,
    build_scenario,
    churn_schedule,
    soak_config,
)
from repro.etl import EventSource, METLApp, PlanManager

# harness contract (benchmarks/run.py): gates fail the run, perf metrics
# feed scripts/perf_diff.py across BENCH_*.json artifacts
GATE_FAILURES: list = []
PERF_METRICS: dict = {}


# -- §5.2/§5.3 compaction ratios ----------------------------------------------
def _ratio_rows(smoke: bool) -> list:
    rows = []
    cfg = (
        ScenarioConfig(
            n_schemas=30, versions_per_schema=5, attrs_per_version=8,
            n_entities=10, cdm_attrs=20, seed=42,
        )
        if smoke
        else ScenarioConfig(
            n_schemas=100, versions_per_schema=10, attrs_per_version=10,
            n_entities=40, cdm_attrs=25, seed=42,
        )
    )
    sc = build_scenario(cfg)
    m, n = sc.shape
    t0 = time.perf_counter()
    dpm = transform_to_dpm(sc.matrix)
    t_dpm = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    dusb = transform_to_dusb(sc.matrix)
    t_dusb = (time.perf_counter() - t0) * 1e6
    r_dpm = compaction_ratio(sc.matrix, dpm_size(dpm))
    r_dusb = compaction_ratio(sc.matrix, dusb_size(dusb))
    rows.append(("compaction/matrix_elements", 0.0, f"{m}x{n}={m*n}"))
    rows.append(("compaction/dpm_transform", t_dpm, f"ratio={r_dpm:.5f} stored={dpm_size(dpm)}"))
    rows.append(("compaction/dusb_transform", t_dusb, f"ratio={r_dusb:.5f} stored={dusb_size(dusb)}"))
    PERF_METRICS["compaction_ratio_dpm"] = r_dpm
    if not smoke and (r_dpm <= 0.99 or r_dusb <= 0.99):
        GATE_FAILURES.append(
            f"paper compaction claim >99% violated at paper scale "
            f"(dpm {r_dpm:.5f}, dusb {r_dusb:.5f})"
        )

    # Figure-5 worked example numbers
    from tests_fixtures_fig5 import fig5  # local helper below

    reg, mtx = fig5()
    d = transform_to_dpm(mtx)
    u = transform_to_dusb(mtx)
    stored_u = sum(len(b) for s in u.values() for _, b in s)
    nulls_u = sum(1 for s in u.values() for _, b in s if not b)
    rows.append(("compaction/fig5_dpm", 0.0, f"30->{dpm_size(d)} (paper: 7)"))
    rows.append(("compaction/fig5_dusb", 0.0, f"30->{stored_u}+{nulls_u} (paper: 5+1)"))
    return rows


# -- the plan-lifecycle soak --------------------------------------------------
def _soak_shapes(smoke: bool):
    """(config, n_chunks, chunk_size, churn_steps, every)."""
    if smoke:
        return soak_config(smoke=True), 12, 64, 6, 2
    return soak_config(), 36, 256, 16, 2


def _soak_arm(
    cfg: ScenarioConfig,
    *,
    n_chunks: int,
    size: int,
    churn: int,
    every: int,
    incremental: bool = True,
) -> dict:
    """One soak arm: fresh world, PlanManager-served fused engine, timed
    per-chunk consume with schema churn applied at chunk boundaries."""
    sc = build_scenario(cfg)
    coord = StateCoordinator(sc.registry, sc.dpm)
    mgr = PlanManager(kind="fused", coordinator=coord, incremental=incremental)
    app = METLApp(coord, plan_manager=mgr)  # builds + serves epoch 1
    t_first = mgr.info()["total_rebuild_s"]
    # identical schedule content across arms: same fresh registry, same seed
    sched = churn_schedule(
        coord.registry, steps=churn, first_chunk=1, every=every, seed=13
    )
    src = EventSource(sc.registry, seed=5)
    lat, keys = [], []
    t0 = time.perf_counter()
    for k in range(n_chunks):
        ev = sched.get(k)
        if ev is not None:
            coord.apply(ev)
        t1 = time.perf_counter()
        rows = app.consume(src.slice_columnar(k * size, size))
        lat.append(time.perf_counter() - t1)
        keys.extend(r[3] for r in rows)
    total_s = time.perf_counter() - t0
    minfo = mgr.info()
    return {
        "keys": keys,
        "events_per_s": (n_chunks * size) / total_s,
        "mean_ms": float(np.mean(lat) * 1e3),
        "p99_ms": float(np.percentile(lat, 99) * 1e3),
        "first_build_ms": t_first * 1e3,
        "churn_rebuild_ms": (minfo["total_rebuild_s"] - t_first) * 1e3,
        "minfo": minfo,
    }


def _soak_rows(smoke: bool) -> list:
    cfg, n_chunks, size, churn, every = _soak_shapes(smoke)
    inc = _soak_arm(
        cfg, n_chunks=n_chunks, size=size, churn=churn, every=every,
        incremental=True,
    )
    full = _soak_arm(
        cfg, n_chunks=n_chunks, size=size, churn=churn, every=every,
        incremental=False,
    )

    # -- correctness gates (always) ------------------------------------------
    if not inc["keys"]:
        GATE_FAILURES.append("soak emitted zero rows")
    if inc["keys"] != full["keys"]:
        GATE_FAILURES.append(
            f"incremental soak dropped/duplicated/reordered rows vs the "
            f"full-rebuild oracle ({len(inc['keys'])} vs {len(full['keys'])} keys)"
        )
    if inc["minfo"]["incremental_rebuilds"] != churn:
        GATE_FAILURES.append(
            f"expected {churn} incremental rebuilds, saw "
            f"{inc['minfo']['incremental_rebuilds']} "
            f"(epoch {inc['minfo']['plan_epoch']})"
        )
    if full["minfo"]["incremental_rebuilds"] != 0:
        GATE_FAILURES.append(
            "full-rebuild oracle arm took the incremental path "
            f"({full['minfo']['incremental_rebuilds']} times)"
        )
    # -- latency/amortisation gates (full size only: smoke is jitter-bound) --
    if not smoke:
        if inc["churn_rebuild_ms"] >= full["churn_rebuild_ms"]:
            GATE_FAILURES.append(
                f"amortised incremental rebuild ({inc['churn_rebuild_ms']:.0f} ms "
                f"over {churn} cutovers) not cheaper than full rebuilds "
                f"({full['churn_rebuild_ms']:.0f} ms)"
            )
        if inc["p99_ms"] > 1.5 * full["p99_ms"] + 10.0:
            GATE_FAILURES.append(
                f"incremental soak p99 chunk latency {inc['p99_ms']:.1f} ms "
                f"regressed vs full-rebuild baseline {full['p99_ms']:.1f} ms"
            )

    PERF_METRICS["soak_consume_incremental"] = inc["events_per_s"]
    PERF_METRICS["soak_consume_full_rebuild"] = full["events_per_s"]
    PERF_METRICS["soak_rebuilds_per_s"] = (inc["minfo"]["rebuilds"] - 1) / max(
        inc["churn_rebuild_ms"] / 1e3, 1e-9
    )

    shape = f"{n_chunks}x{size}ev_{churn}churn"
    rows = []
    rows.append((
        f"compaction/soak_incremental_{shape}",
        inc["mean_ms"] * 1e3,
        f"{inc['events_per_s']:.0f} events/s, p99 {inc['p99_ms']:.2f} ms/chunk, "
        f"{inc['minfo']['rebuilds']} builds ({inc['minfo']['incremental_rebuilds']} "
        f"incremental), churn rebuilds {inc['churn_rebuild_ms']:.1f} ms",
    ))
    rows.append((
        f"compaction/soak_full_rebuild_{shape}",
        full["mean_ms"] * 1e3,
        f"{full['events_per_s']:.0f} events/s, p99 {full['p99_ms']:.2f} ms/chunk, "
        f"churn rebuilds {full['churn_rebuild_ms']:.1f} ms",
    ))
    return rows


def run(smoke: bool = False) -> list:
    return _ratio_rows(smoke) + _soak_rows(smoke)


# -- minimal local copy of the Figure-5 fixture (keeps benchmarks standalone)
import sys
import types

_fix = types.ModuleType("tests_fixtures_fig5")


def _fig5():
    from repro.core.registry import Registry
    from repro.core.dmm import MappingMatrix

    reg = Registry()
    reg.add_schema(reg.domain, 1, ["a1", "a2", "a3"])
    reg.evolve(reg.domain, 1, keep=["a1", "a3"])
    reg.add_schema(reg.domain, 2, ["a6"])
    reg.add_schema(reg.range, 1, ["c3", "c4"], version=2)
    reg.add_schema(reg.range, 2, ["c5"])
    reg.add_schema(reg.range, 3, ["c6", "c7"])
    m = MappingMatrix(reg)
    c3, c4 = reg.range.get(1, 2).uids
    (c5,) = reg.range.get(2, 1).uids
    c6, c7 = reg.range.get(3, 1).uids
    a1, a2, a3 = reg.domain.get(1, 1).uids
    a4, a5 = reg.domain.get(1, 2).uids
    (a6,) = reg.domain.get(2, 1).uids
    for q, p in [(c3, a1), (c4, a3), (c3, a4), (c4, a5), (c5, a6), (c6, a2), (c7, a1)]:
        m.set(q, p, 1)
    return reg, m


_fix.fig5 = _fig5
sys.modules["tests_fixtures_fig5"] = _fix
