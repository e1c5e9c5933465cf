"""A whole run on the CPU at a small size: the plain reference agrees with
the program (``METLApp`` through ``Pipeline`` into ``TableSink``), also
across an in-band schema evolution, and ``correct`` comes out false for the
bf16 control and for each fault the cells can have, planted in the timed
path."""

import gc

import numpy as np
import pytest

from bench.lib import deployment as dep
from bench.lib import reference as ref
from bench.lib import traffic as trf
from bench.tests.conftest import run_small


def _numbers(res):
    return {k: v["value"] for k, v in res["check"].items()}


@pytest.mark.parametrize("cell", ["eos_paper.live", "eos_paper.replay"])
def test_reference_agrees_with_the_program(cell, small_spec, small_traffic):
    res = run_small(cell, small_spec, small_traffic(cell))
    assert res["correct"], _numbers(res)
    assert res["attempted"] > 1000 and res["failed"] == 0
    assert set(res["metrics"]) >= {"setup_s"}
    assert list(res)[-1] == "check"
    assert not gc.get_freeze_count()  # the window's freezes are undone


def test_reference_agrees_across_a_schema_evolution(small_spec):
    """Events at the registry state before an in-band ``SchemaEvolved`` and
    at the state after it, older versions among them, map as the
    reference says."""
    from repro.etl import ListSource, Pipeline, SchemaEvolved, TableSink

    from bench import run_cell
    from bench.lib.source import OpenLoopSource

    d = dep.build(small_spec)
    app = run_cell._app(d, small_spec["engine"])
    w = trf.schema_weights(len(d.history.versions), 0.0, 7)
    rng = np.random.default_rng(2**31 + 11)
    stream = {**small_spec["stream"], "latest_version_share": 0.5}
    table = TableSink()

    def feed(batch, *control):
        chunk = OpenLoopSource(batch, d.tables.cols, np.zeros(batch.n),
                               max_poll=batch.n).chunk(0, batch.n)
        Pipeline(ListSource([chunk, *control]), app, [table],
                 async_consume=small_spec["engine"]["async_consume"]).run()

    before = trf.generate(rng, stream, w, run_cell._version_cols(d.tables), d.tables.flat(),
                          1500, 0, d.state)
    o = 3
    keep, add = d.history.versions[o][-1][1:], [f"s{o}.new0", f"s{o}.new1"]
    feed(before, SchemaEvolved(tree="domain", schema_id=o, keep=tuple(keep), add=tuple(add)))
    d.tables.add(d.history, d.coordinator.registry, o, d.history.evolve(o, keep, add))
    after = trf.generate(rng, stream, w, run_cell._version_cols(d.tables), d.tables.flat(),
                         1500, 10**6, d.state)
    assert (after.col == len(d.tables.cols) - 1).any()  # the new version carries traffic
    feed(after)
    width = max(d.tables.n_out)
    want = ref.concat_rows([ref.expected_rows(b, d.tables, 0, b.n, width)
                            for b in (before, after)])
    got = ref.table_rows(table.to_arrays(), width)
    assert want.n > 1000
    assert ref.compare(want, got) == {"rows_missing": 0, "rows_extra": 0, "rows_wrong": 0}


def test_bf16_control_fails(small_spec, small_traffic):
    res = run_small("eos_paper.replay", small_spec, small_traffic("eos_paper.replay"),
                    control="bf16")
    assert not res["correct"]
    assert _numbers(res)["rows_wrong"] > 0


def _drop_half(monkeypatch):
    import repro.etl.pipeline as pl

    write = pl.TableSink.write
    monkeypatch.setattr(pl.TableSink, "write", lambda self, rows: write(self, rows[::2]))


def _alter_one_value(monkeypatch):
    import repro.etl.engines as en

    emit = en._emit_rows

    def bad(plan, ov, om, blk_ids, out_keys, stats):
        ov = ov.copy()
        ov[0] += 1.0
        return emit(plan, ov, om, blk_ids, out_keys, stats)

    monkeypatch.setattr(en, "_emit_rows", bad)


def _stale_output(monkeypatch):
    """The output buffer read one chunk late: each chunk's rows take the
    values of the chunk before it."""
    import repro.etl.engines as en

    emit = en._emit_rows
    last = {}

    def stale(plan, ov, om, blk_ids, out_keys, stats):
        prev, last["ov"] = last.get("ov"), ov
        if prev is not None:
            k = min(len(prev), len(ov))
            ov = ov.copy()
            ov[:k] = prev[:k]
        return emit(plan, ov, om, blk_ids, out_keys, stats)

    monkeypatch.setattr(en, "_emit_rows", stale)


@pytest.mark.parametrize("fault, cell, number", [
    (_drop_half, "eos_paper.replay", "rows_missing"),
    (_drop_half, "eos_paper.live", "rows_missing"),
    (_alter_one_value, "eos_paper.replay", "rows_wrong"),
    (_alter_one_value, "eos_paper.live", "rows_wrong"),
    (_stale_output, "eos_paper.replay", "rows_wrong"),
])
def test_planted_fault_is_not_correct(fault, cell, number, small_spec, small_traffic,
                                      monkeypatch):
    from bench.lib.source import OpenLoopSource

    # planted when the window opens: set-up (the warm-up) runs the sound
    # program
    start = OpenLoopSource.start

    def open_window(self, t0, stop_at):
        fault(monkeypatch)
        start(self, t0, stop_at)

    monkeypatch.setattr(OpenLoopSource, "start", open_window)
    res = run_small(cell, small_spec, small_traffic(cell))
    assert not res["correct"]
    assert _numbers(res)[number] > 0, _numbers(res)
