"""repro.etl.tracing: the span recorder inside the chunk path.

Off it records nothing and hands out one shared no-op; on, an async
``Pipeline`` run records each stage once per chunk under the right parent,
with the double buffer's chunk ids (emit(N) carries N while the lookahead's
triage/densify carry N+1); thread CPU time never exceeds the wall and a
sleep shows as time off the CPU; and the mapping program reaches the trace
under its stable module name."""

import glob
import os
import time

import numpy as np
import pytest

from repro.core.state import StateCoordinator
from repro.core.synthetic import ScenarioConfig, build_scenario
from repro.etl import CollectSink, EventSource, ListSource, METLApp, Pipeline, TableSink
from repro.etl import tracing


@pytest.fixture
def recorder():
    tracing.reset()
    tracing.enable()
    yield tracing
    tracing.disable()
    tracing.reset()


@pytest.fixture
def world():
    sc = build_scenario(ScenarioConfig(seed=51))
    coord = StateCoordinator(sc.registry, sc.dpm)
    src = EventSource(sc.registry, seed=2, p_duplicate=0.1)
    return coord, src


def _chunks(src, n, size=100):
    return [src.slice_columnar(k * size, size) for k in range(n)]


def test_off_records_nothing_and_hands_out_the_shared_noop(world):
    tracing.reset()
    assert tracing.span("triage") is tracing.span("emit")
    coord, src = world
    Pipeline(ListSource(_chunks(src, 3)), METLApp(coord, engine="fused"), [CollectSink()],
             async_consume=True).run()
    assert tracing.records().size == 0


def _by_chunk(rec, name):
    sel = rec[rec["name"] == name]
    return sorted(sel["chunk"].tolist())


@pytest.mark.parametrize("device_densify", [False, True])
def test_async_pipeline_records_each_stage_per_chunk(recorder, world, device_densify):
    coord, src = world
    n = 4
    app = METLApp(coord, engine="fused", device_densify=device_densify)
    Pipeline(ListSource(_chunks(src, n)), app, [CollectSink(), TableSink()],
             async_consume=True).run()
    rec = tracing.records()
    names = rec["name"]
    for stage in ("triage", "densify", "densify.layout", "dispatch", "emit", "emit.sync",
                  "emit.rows", "sink.CollectSink", "sink.TableSink"):
        assert _by_chunk(rec, stage) == list(range(n)), stage
    assert _by_chunk(rec, "densify.pack") == (list(range(n)) if device_densify else [])
    # one lookahead per chunk in flight; the last one polls the exhausted source
    assert _by_chunk(rec, "pipeline.lookahead") == list(range(n))
    assert len(_by_chunk(rec, "pipeline.poll")) == n + 1

    def parent(i):
        p = rec["parent"][i]
        return "chunk" if p < 0 else names[p]

    want = {
        "dispatch": {"chunk"}, "emit": {"chunk"}, "sink.CollectSink": {"chunk"},
        "pipeline.lookahead": {"chunk"}, "emit.sync": {"emit"}, "emit.rows": {"emit"},
        "densify.layout": {"densify"}, "densify.pack": {"densify"},
        # chunk 0 is prepared before the loop; every later one in the lookahead
        "triage": {"chunk", "pipeline.lookahead"},
        "densify": {"chunk", "pipeline.lookahead"},
        "pipeline.poll": {"chunk", "pipeline.lookahead"},
    }
    for i, name in enumerate(names):
        if name in want:
            assert parent(i) in want[name], (name, parent(i))
    first = rec[(rec["name"] == "triage") & (rec["chunk"] == 0)]
    assert first["parent"][0] == -1
    # ids and times are consistent: a child lies inside its parent
    kids = rec["parent"] >= 0
    par = rec[rec["parent"][kids]]
    assert (rec["start_ns"][kids] >= par["start_ns"]).all()
    assert (rec["end_ns"][kids] <= par["end_ns"]).all()


def test_lookahead_prepares_the_next_chunk_while_emit_carries_this_one(recorder, world):
    coord, src = world
    Pipeline(ListSource(_chunks(src, 3)), METLApp(coord, engine="fused"), [CollectSink()],
             async_consume=True).run()
    rec = tracing.records()
    names = rec["name"]
    for i in np.nonzero(names == "pipeline.lookahead")[0]:
        n = rec["chunk"][i]
        inside = rec[rec["parent"] == i]
        staged = inside[np.isin(inside["name"], ["triage", "densify"])]
        if n < 2:  # chunks 1 and 2 are prepared ahead, inside N's lookahead
            assert sorted(staged["name"].tolist()) == ["densify", "triage"]
            assert (staged["chunk"] == n + 1).all()
        else:
            assert staged.size == 0
        # emit(N) follows its lookahead and carries N
        after = rec[(names == "emit") & (rec["start_ns"] >= rec["end_ns"][i])]
        assert after["chunk"][0] == n


def test_sync_pipeline_stamps_each_chunk(recorder, world):
    coord, src = world
    Pipeline(ListSource(_chunks(src, 3)), METLApp(coord, engine="fused"),
             [CollectSink()]).run()
    rec = tracing.records()
    for stage in ("triage", "densify", "dispatch", "emit", "sink.CollectSink"):
        assert _by_chunk(rec, stage) == [0, 1, 2]
    assert "pipeline.lookahead" not in rec["name"]
    # outside a pipeline, spans carry no chunk
    tracing.reset()
    METLApp(coord, engine="fused").consume(src.slice_columnar(0, 50))
    assert (tracing.records()["chunk"] == -1).all()


def test_thread_cpu_within_wall_and_sleep_is_off_cpu(recorder):
    with tracing.span("sleep"):
        time.sleep(0.05)
    with tracing.span("spin"):  # 20 ms of this thread's CPU, however long the wall
        c = time.thread_time_ns() + 20_000_000
        while time.thread_time_ns() < c:
            pass
    rec = tracing.records()
    wall = rec["end_ns"] - rec["start_ns"]
    cpu = rec["cpu_end_ns"] - rec["cpu_start_ns"]
    assert (cpu <= wall).all() and (cpu >= 0).all()
    sleep, spin = rec[0], rec[1]
    assert sleep["name"] == "sleep" and wall[0] - cpu[0] >= 45e6
    assert spin["name"] == "spin" and cpu[1] >= 20e6
    assert (rec["parent"] == -1).all()


def test_records_round_trip_through_dump(recorder, tmp_path):
    with tracing.span("outer"):
        with tracing.span("inner"):
            pass
    path = str(tmp_path / "spans.npy")
    tracing.dump(path)
    back = np.load(path)
    assert back.dtype == tracing.RECORD
    assert back["name"].tolist() == ["outer", "inner"]
    assert back["parent"].tolist() == [-1, 0]


def test_mapping_program_reaches_the_trace_by_its_stable_name(tmp_path):
    """A CPU trace of ``dmm_apply_columnar``: its operations carry the
    module ``jit_metl_map_chunk``, and its spans sit on the trace as
    ``metl:*`` annotations while the profiler records them too."""
    import jax
    from jax.profiler import ProfileData

    sc = build_scenario(ScenarioConfig(seed=51))
    coord = StateCoordinator(sc.registry, sc.dpm)
    src = EventSource(sc.registry, seed=3)
    app = METLApp(coord, engine="fused", device_densify=True)
    chunk = src.slice_columnar(0, 200)
    app.consume(chunk)  # compile outside the trace
    app.reset_dedup()
    tracing.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        rows = app.consume(chunk)
    finally:
        jax.profiler.stop_trace()
    assert rows and tracing.span("after") is tracing.span("the trace")
    assert {"triage", "densify", "densify.pack", "dispatch", "emit"} <= set(
        tracing.records()["name"].tolist())
    tracing.reset()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)[0]
    modules, annotations = set(), set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                st = dict(e.stats)
                if "hlo_module" in st:
                    modules.add(st["hlo_module"])
                if e.name.startswith("metl:"):
                    annotations.add(e.name)
    assert "jit_metl_map_chunk" in modules
    assert {"metl:triage", "metl:densify", "metl:dispatch", "metl:emit"} <= annotations
