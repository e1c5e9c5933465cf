"""Schema churn in the window (``bench/lib/churn.py``): the schedules, the
producers' registry copy against the program's, whole churn runs on the
CPU at a small size (a backlog and open-loop arrivals), the faults they
must catch, a backlog's later passes, and the cells without churn
generating what they did before churn existed."""

import copy
import hashlib

import numpy as np
import pytest

from bench.lib import churn as chn
from bench.lib import deployment as dep
from bench.lib import traffic as trf
from bench.lib.source import OpenLoopSource
from bench.tests import test_bench_run
from bench.tests.conftest import run_small

CELL = "eos_paper.churn"


def _numbers(res):
    return {k: v["value"] for k, v in res["check"].items()}


@pytest.fixture(params=["backlog", "open"])
def churn_traffic(request, small_traffic):
    """The churn cell's traffic, shrunk: four evolutions in a 4,000-event
    backlog, polled 250 at a time; or the live cell's open-loop arrivals
    with three evolutions in a 0.6 s window."""
    if request.param == "backlog":
        t = small_traffic(CELL)
        t["max_poll_records"] = 250
        t["churn"] = {"every_events": 1000, "first_event": 500}
    else:
        t = small_traffic("eos_paper.live")
        t["churn"] = {"every_s": 0.2, "first_s": 0.1}
    return t


def test_schedule_of_the_cell():
    """Ten evolutions in the cell's backlog, each on a poll's edge, so that
    every poll stays full."""
    t = trf.load_traffic(dep.Path(__file__).resolve().parents[1], CELL)
    n = t["arrival"]["backlog_events"]
    sched = chn.schedule(t["churn"], np.zeros(n), 20.0)
    assert [f for _, f in sched] == [100_000 + 200_000 * k for k in range(10)]
    assert all(at == 0.0 and f % t["max_poll_records"] == 0 for at, f in sched)
    assert n % t["max_poll_records"] == 0
    assert chn.times({"every_s": 2.0, "first_s": 1.0}, 20.0) == [1.0 + 2.0 * k for k in range(10)]
    due = np.arange(0.0, 5.0, 0.25)
    assert chn.schedule({"every_s": 2.0, "first_s": 1.0}, due, 5.0) == [(1.0, 4), (3.0, 12)]


def test_producers_stamp_what_the_program_registers(small_spec):
    """After the window's evolutions, applied to the program as in-band
    events, every new version's uids on the producers' side are the
    program's, and every event carries the state it was produced at."""
    d = dep.build(small_spec)
    program = d.coordinator
    n0, state0 = len(d.tables.cols), d.state
    w = trf.schema_weights(len(d.history.versions), 0.0, 7)
    due = np.sort(np.random.default_rng(1).random(3000) * 2.0)
    batch, evos = chn.generate(np.random.default_rng(2**33 + 5), small_spec["stream"], w, d,
                               {"every_s": 0.4, "first_s": 0.2}, due, 2.0)
    assert len(evos) == 5 and batch.n == due.size
    edges = [0] + [e.first for e in evos] + [batch.n]
    for k in range(len(edges) - 1):
        assert (batch.state[edges[k]:edges[k + 1]] == state0 + k).all()
    for e in evos:
        program.apply(chn.event(e))
    assert program.registry.state == state0 + 5
    for c in range(n0, len(d.tables.cols)):
        o, v = d.tables.cols[c]
        assert np.array_equal(d.tables.uid[c], program.registry.domain.get(o, v).uids)
    new = batch.col >= n0
    assert new.any() and (np.asarray(d.tables.cols)[batch.col[new], 1] > 1).all()
    # every evolution keeps a name that maps: each one the mapping has to follow
    assert all(any(d.history.slot[e.schema][n] >= 0 for n in e.keep) for e in evos)


def test_draw_skips_versions_that_keep_no_mapped_name(small_spec):
    """Over many draws some cut versions keep no mapped name; ``draw``
    returns none of them."""
    d = dep.build(small_spec)
    reg, h = small_spec["registry"], d.history
    rng = np.random.default_rng(2**32 + 3)
    cuts = [chn.cut(rng, h, o, reg) for o in range(len(h.versions)) for _ in range(200)]
    assert any(not any(h.slot[o][n] >= 0 for n in keep)
               for o, (keep, _) in zip(np.repeat(range(len(h.versions)), 200), cuts))
    for _ in range(200):
        o, keep, add = chn.draw(rng, h, reg)
        assert any(h.slot[o][n] >= 0 for n in keep)


def test_churn_run_is_correct(small_spec, churn_traffic):
    res = run_small(CELL, small_spec, churn_traffic)
    assert res["correct"], _numbers(res)
    assert set(_numbers(res).values()) == {0}
    assert res["attempted"] > 1000 and res["failed"] == 0
    backlog = churn_traffic["arrival"]["kind"] == "backlog"
    assert set(res["metrics"]) == ({"setup_s", "events_per_s.churn"} if backlog else {"setup_s"})


def test_churn_run_reads_its_layers(small_spec, churn_traffic, tmp_path):
    """The compile readers in a traced run (the CPU's trace has no device
    plane, so ``device_idle_share.churn`` is read on the chip alone)."""
    res = run_small(CELL, small_spec, churn_traffic, trace=True, trace_dir=str(tmp_path))
    assert res["correct"], _numbers(res)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["compiles_per_evolution.churn"] >= 0
    assert m["compile_ms_per_evolution.churn"] >= 0
    assert "events_per_s.churn" not in m


def test_backlog_passes_after_the_first_are_correct(small_spec, small_traffic):
    """A window that outruns the backlog replays it at the newest state:
    every row of every pass is right, none dead-lettered as outdated."""
    t = small_traffic(CELL)
    t["arrival"]["backlog_events"] = 2000
    t["max_poll_records"] = 250
    t["churn"] = {"every_events": 1000, "first_event": 500}
    res = run_small(CELL, small_spec, t, seconds=2.0)
    assert res["correct"], _numbers(res)
    assert res["attempted"] > 2 * 2000


def test_later_passes_carry_the_newest_state():
    b = trf.Batch(key=np.arange(4), col=np.zeros(4, np.int32),
                  state=np.asarray([5, 5, 6, 7]), offsets=np.arange(5),
                  pos=np.zeros(4, np.int32), uid=np.arange(4, dtype=np.int32),
                  val=np.ones(4, np.float32))
    src = OpenLoopSource(b, [(0, 1)], np.zeros(4), max_poll=2, cycle=True, key_span=4)
    assert src.chunk(0, 2).states.tolist() == [5, 5]
    assert src.chunk(2, 4).states.tolist() == [6, 7]
    later = src.chunk(4, 6)
    assert later.states.tolist() == [7, 7] and later.keys.tolist() == [4, 5]
    assert src.event(5, 4).state == 7 and src.event(1, 0).state == 5


def test_bf16_control_fails_under_churn(small_spec, churn_traffic):
    res = run_small(CELL, small_spec, churn_traffic, control="bf16")
    assert not res["correct"]
    assert _numbers(res)["rows_wrong"] > 0


def _drop_one(src, monkeypatch):
    """The source never yields the last scheduled evolution (no later one
    names a version the program then lacks)."""
    del src.control[-1]


def _other_keep(src, monkeypatch):
    """The program is told of a version that keeps one name fewer than the
    producers' registry copy kept: the first evolution whose schema does
    not evolve again and whose version keeps two names or more."""
    from repro.etl import SchemaEvolved

    later = [ev.schema_id for _, _, ev in src.control]
    for k, (f, at, ev) in enumerate(src.control):
        if ev.schema_id not in later[k + 1:] and len(ev.keep) >= 2:
            src.control[k] = (f, at, SchemaEvolved(tree=ev.tree, schema_id=ev.schema_id,
                                                   keep=ev.keep[1:], add=ev.add))
            return
    raise AssertionError("no evolution to alter")


def _drop_half(src, monkeypatch):
    test_bench_run._drop_half(monkeypatch)


def _alter_one_value(src, monkeypatch):
    test_bench_run._alter_one_value(monkeypatch)


def _plant_at_open(monkeypatch, fault):
    """Plant ``fault(source, monkeypatch)`` when the window opens: set-up
    (the warm-up) runs the sound program."""
    start = OpenLoopSource.start

    def open_window(self, t0, stop_at):
        assert len(self.control) >= 2
        fault(self, monkeypatch)
        start(self, t0, stop_at)

    monkeypatch.setattr(OpenLoopSource, "start", open_window)


@pytest.mark.parametrize("fault, number", [
    (_drop_one, "rows_missing"),
    (_other_keep, "rows_wrong"),
    (_drop_half, "rows_missing"),
    (_alter_one_value, "rows_wrong"),
])
def test_planted_churn_fault_is_not_correct(fault, number, small_spec, churn_traffic,
                                            monkeypatch):
    _plant_at_open(monkeypatch, fault)
    res = run_small(CELL, small_spec, churn_traffic)
    assert not res["correct"]
    assert _numbers(res)[number] > 0, _numbers(res)


def test_late_evolution_is_parked_and_replayed(small_spec, churn_traffic, monkeypatch):
    """Each evolution yielded one poll late: events at the new version reach
    the program before it knows the version.  It parks them (their state is
    ahead of its own) and maps them once the evolution has applied, so
    every row is right; only their latency grows."""
    from repro.etl.control import ControlEvent

    from bench import run_cell

    late_by = []

    def one_poll_late(src, monkeypatch):
        poll = src.poll

        def late():
            held = []
            for item in poll():
                if isinstance(item, ControlEvent):
                    held.append(item)
                    continue
                yield item
                while held:
                    late_by.append(item.keys.size)
                    yield held.pop(0)

        src.poll = late

    apps = []
    make = run_cell._app
    monkeypatch.setattr(run_cell, "_app", lambda d, e: apps.append(make(d, e)) or apps[-1])
    _plant_at_open(monkeypatch, one_poll_late)
    res = run_small(CELL, small_spec, churn_traffic)
    assert res["correct"], _numbers(res)
    assert len(late_by) >= 2 and min(late_by) > 0
    assert apps[0].stats["parked"] >= len(late_by)
    assert apps[0].stats["replayed"] == apps[0].stats["parked"]


# sha256 of every array the set-up and the window generate (warm-up chunks,
# then the window's batch and due times) at the test seed, as the harness
# made them before churn existed
PINNED = {
    "eos_paper.replay": "2124ef01156eedab70661141282a1883c34fda7ce716c2d33edebd014b5e6c9e",
    "eos_paper.live": "8b33a1484204bc386a057e21f75ce1554ef96aceba82241d2e22ff412c0654ba",
}


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_cells_without_churn_generate_what_they_did(cell, small_spec, small_traffic,
                                                    monkeypatch):
    seen = []
    init = OpenLoopSource.__init__

    def spy(self, batch, cols, due, **kw):
        seen.append((batch, due))
        init(self, batch, cols, due, **kw)

    monkeypatch.setattr(OpenLoopSource, "__init__", spy)
    traffic = small_traffic(cell)
    assert "churn" not in traffic
    res = run_small(cell, copy.deepcopy(small_spec), traffic)
    assert res["correct"]
    h = hashlib.sha256()
    for b, due in seen:
        for f in ("key", "col", "state", "offsets", "pos", "uid", "val"):
            h.update(np.ascontiguousarray(getattr(b, f)).tobytes())
        h.update(np.ascontiguousarray(due).tobytes())
    assert h.hexdigest() == PINNED[cell]


# sha256 of the churn cell's arrays at the test seed (warm-up chunks, the
# window's batch and due times, then each in-band evolution), under either
# kind of arrival, as the harness made them before each deployment named
# its kind
PINNED_CHURN = {
    "backlog": "93bbf9296f97a812fb82151c5ce0ec49ec6024d425711f8bf050fef20953e725",
    "open": "878baae425b8e8aae3043c2ed6d47a09a7ce7a8994c10b5cf6a1eef158d7f510",
}


def test_churn_cell_generates_what_it_did(small_spec, churn_traffic, request, monkeypatch):
    seen = []
    init = OpenLoopSource.__init__

    def spy(self, batch, cols, due, **kw):
        seen.append((batch, due, list(kw.get("control", ()))))
        init(self, batch, cols, due, **kw)

    monkeypatch.setattr(OpenLoopSource, "__init__", spy)
    res = run_small(CELL, copy.deepcopy(small_spec), churn_traffic)
    assert res["correct"]
    h = hashlib.sha256()
    for b, due, control in seen:
        for f in ("key", "col", "state", "offsets", "pos", "uid", "val"):
            h.update(np.ascontiguousarray(getattr(b, f)).tobytes())
        h.update(np.ascontiguousarray(due).tobytes())
        for first, at, ev in control:
            h.update(repr((int(first), float(at), int(ev.schema_id), tuple(ev.keep),
                           tuple(ev.add))).encode())
    assert sum(len(c) for _, _, c in seen) >= 2
    assert h.hexdigest() == PINNED_CHURN[request.node.callspec.params["churn_traffic"]]


COUNT = """
import sys
sys.path[:0] = [{src!r}, {repo!r}]
from bench import run_cell
run_cell.use_compile_cache()
c = run_cell.Counters()
if {off}:
    run_cell.cache_off()
import jax, jax.numpy as jnp
jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(37.0)).block_until_ready()
print(c.compiles, c.loads, c.compiles - c.loads)
"""


def _count(tmp_path, *offs):
    """(compiles, loads, built) of one process per entry of ``offs``, all on
    one persistent cache; ``True`` turns the cache off before the jit."""
    import os
    import subprocess
    import sys

    from bench.tests.conftest import REPO

    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    counts = []
    for off in offs:
        code = COUNT.format(src=str(REPO / "src"), repo=str(REPO), off=off)
        p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                           env=env, timeout=120)
        assert p.returncode == 0, p.stderr[-2000:]
        counts.append([int(x) for x in p.stdout.split()])
    return counts


def test_counters_tell_cache_loads_from_compiles(tmp_path):
    """The backend-compile event fires for a persistent-cache load too; the
    cache-hit event tells the two apart.  Two processes, one cache: the
    first compiles, the second loads."""
    (c1, l1, b1), (c2, l2, b2) = _count(tmp_path, False, False)
    assert l1 == 0 and b1 == c1 >= 1
    assert l2 >= 1 and b2 == c2 - l2
    assert c2 == c1


def test_cache_off_loads_nothing(tmp_path):
    """After ``cache_off`` (the churn window's open) a program that an
    earlier process left in the persistent cache is compiled, not loaded."""
    (c1, l1, b1), (c2, l2, b2), (c3, l3, b3) = _count(tmp_path, False, True, False)
    assert l1 == 0 and b1 >= 1
    assert l2 == 0 and b2 == c2 == c1
    assert l3 >= 1  # the cache still holds it: the second process only passed it by
