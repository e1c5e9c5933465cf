"""The readers of the program's own spans, on hand-built span records, and
the uid-resolve reduction on a small recorded chip trace."""

import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

from bench import run_cell
from bench.lib import program_spans as ps
from bench.lib import scopes
from bench.lib.trace import Events
from repro.etl import tracing

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000


def _records(rows):
    """``(name, chunk, parent, start_ms, wall_ms, cpu_ms)`` per span."""
    rec = np.zeros(len(rows), tracing.RECORD)
    for i, (name, chunk, parent, start, wall, cpu) in enumerate(rows):
        rec[i] = (name, chunk, parent, start * MS, (start + wall) * MS, 7 * MS, (7 + cpu) * MS)
    return rec


# two chunks of a double-buffered window; times in ms from its open at 1 s
REC = _records([
    ("triage", 0, -1, 1000, 2, 2),
    ("densify", 0, -1, 1002, 1, 1),
    ("densify.pack", 0, 1, 1002.2, 0.5, 0.5),
    ("dispatch", 0, -1, 1003, 0.2, 0.2),
    ("pipeline.lookahead", 0, -1, 1003.2, 3, 2.8),
    ("pipeline.poll", 1, 4, 1003.2, 0.2, 0.1),
    ("triage", 1, 4, 1003.4, 1.8, 1.6),
    ("densify", 1, 4, 1005.2, 1, 1),
    ("emit", 0, -1, 1006.2, 4, 3),
    ("emit.sync", 0, 8, 1006.2, 1.5, 0.5),
    ("emit.rows", 0, 8, 1007.7, 2.5, 2.4),
    ("sink.TableSink", 0, -1, 1010.2, 60, 4),
    ("pipeline.lookahead", 1, -1, 1070.2, 1, 1),
])


def _ctx(events=2000, seconds=0.5):
    return types.SimpleNamespace(t0=1.0, tw=1.0 + seconds, seconds=seconds,
                                 events_in_window=events, reduction=None)


@pytest.fixture
def program(monkeypatch):
    """The readers see ``REC`` as the window's program spans."""
    monkeypatch.setattr(ps, "window", lambda ctx: REC)


@pytest.mark.parametrize("metric, want", [
    ("emit_sync_us_per_event.replay", 1.5 * 1e3 / 2000),
    ("emit_rows_us_per_event.replay", 2.5 * 1e3 / 2000),
    ("pack_us_per_event.replay", 0.5 * 1e3 / 2000),
    ("lookahead_us_per_chunk.live", (3 + 1) / 2 * 1e3),
])
def test_reader_on_hand_built_records(program, metric, want):
    assert run_cell.load_metric(metric)(_ctx()) == pytest.approx(want)


def test_readers_give_nothing_without_the_recorder(monkeypatch):
    """A program older than the recorder: every reader returns None."""
    import repro.etl

    monkeypatch.delattr(repro.etl, "tracing")
    monkeypatch.setitem(sys.modules, "repro.etl.tracing", None)
    ps._window.cache_clear()
    ctx = _ctx()
    ctx.t0, ctx.tw = 123.0, 124.0
    assert ps.window(ctx) is None
    for metric in ("emit_sync_us_per_event.replay", "emit_rows_us_per_event.replay",
                   "pack_us_per_event.replay", "lookahead_us_per_chunk.live",
                   "uid_resolve_us_per_chunk.replay"):
        assert run_cell.load_metric(metric)(ctx) is None
    ps._window.cache_clear()


def test_window_keeps_spans_that_started_inside(monkeypatch):
    """The window's spans, and its stall report printed once however many
    readers ask."""
    monkeypatch.setattr(tracing, "records", lambda: REC)
    reported = []
    monkeypatch.setattr(ps, "report_stalls", lambda rec, t0: reported.append((rec, t0)))
    ps._window.cache_clear()
    ctx = _ctx(seconds=0.01)  # [1.000, 1.010) s
    got = ps.window(ctx)
    assert ps.window(ctx) is got
    ps._window.cache_clear()
    assert got["name"].tolist() == REC["name"][:11].tolist()
    ((rec, t0),) = reported
    assert rec is got and t0 == ctx.t0


def test_stall_report_names_the_long_span_and_the_busy_threads():
    threads = {
        "/host:CPU/tpu_runtime": Events.of([("a", 4_010 * MS, 40 * MS), ("b", 4_050 * MS, 10 * MS)]),
        "/host:CPU/idle": Events.of([("c", 9_000 * MS, 1 * MS)]),
    }
    trace = scopes.ProgramTrace(open_ns=4_000 * MS, ops=[], threads=threads)
    lines = ps.stall_report(REC, 1.0, trace)
    assert lines[0].startswith("stall report: 1 program spans over 50 ms")
    (stall,) = lines[1:]
    # sink.TableSink: 60 ms at +0.0102 s, i.e. [4010.2, 4070.2) ms on the trace
    assert stall.startswith("stall: sink.TableSink chunk 0 at +0.010 s: wall 60.0 ms, "
                            "thread CPU 4.0 ms; ")
    assert stall.endswith("other host threads busy: /host:CPU/tpu_runtime 49.8 ms (longest: a)")
    assert ps.stall_report(None, 1.0, None) == ["stall report: no program spans in the window"]


def test_uid_resolve_on_a_recorded_chip_trace():
    """One chunk's mapping program from an ``eos_paper.replay`` trace on a
    TPU v5e, as ``ProfileData`` gives it (the ops carry no stats), with its
    ``XLA Modules`` event and the ops' event metadata read from the
    ``.xplane.pb``.  The uid-resolve ops are those inside the module's
    event whose metadata names the scope; their device time is summed by
    hand here."""
    d = json.loads((DATA / "uid_resolve_v5e.json").read_text())
    ops = scopes.scoped_ops(d["ops"], d["modules"], d["metadata"])
    ns, n = scopes.scoped_ns(ops, d["lo"], d["hi"], "jit_metl_map_chunk", "uid_resolve")
    (mod,) = [m for m in d["modules"] if m[0].startswith("jit_metl_map_chunk(")]
    by_hand = [dur for name, s, dur, _ in d["ops"]
               if mod[1] <= s < mod[1] + mod[2]
               and any("/uid_resolve/" in v for v in d["metadata"][name].values())]
    assert n == len(by_hand) > 0 and ns == sum(by_hand)
    assert (ns, n) == (d["uid_resolve_ns"], d["uid_resolve_ops"])
    (kernel,) = [dur for name, _, dur, _ in d["ops"] if "densify_map" in name]
    assert kernel < ns < mod[2]
    assert all(op.module == "jit_metl_map_chunk" for op in ops if mod[1] <= op.start < mod[1] + mod[2])
    # outside the window, or under another module, nothing counts
    assert scopes.scoped_ns(ops, d["hi"], d["hi"] + 1, "jit_metl_map_chunk", "uid_resolve") == (0, 0)
    assert scopes.scoped_ns(ops, d["lo"], d["hi"], "jit_fn", "uid_resolve") == (0, 0)


def test_module_and_scope_by_hand():
    """Ops take the module whose ``XLA Modules`` event holds their start
    (or their own ``hlo_module``) and their scope from their metadata."""
    ops = scopes.scoped_ops(
        [("%gather.1 = ...", 105, 20, {}), ("%copy.2 = ...", 130, 5, {}),
         ("%gather.1 = ...", 300, 20, {}), ("add", 400, 1, {"hlo_module": "jit_g"})],
        [("jit_metl_map_chunk(123)", 100, 50), ("jit_f(7)", 290, 40)],
        {"%gather.1 = ...": {"tf_op": "jit(metl_map_chunk)/uid_resolve/jit(_take)/gather"},
         "%copy.2 = ...": {"tf_op": "jit(metl_map_chunk)/copy"}})
    assert [op.module for op in ops] == ["jit_metl_map_chunk", "jit_metl_map_chunk", "jit_f",
                                         "jit_g"]
    assert scopes.scoped_ns(ops, 0, 1000, "jit_metl_map_chunk", "uid_resolve") == (20, 1)
    assert scopes.scoped_ns(ops, 0, 1000, "jit_f", "uid_resolve") == (20, 1)
    assert scopes.scoped_ns(ops, 0, 1000, "jit_metl_map_chunk", "copy") == (0, 0)


def _pb(*fields):
    """Protobuf wire bytes of ``(number, value)`` fields: ints as varints,
    bytes and str as length-delimited, floats as fixed64."""

    def varint(v):
        out = bytearray()
        while True:
            out.append((v & 0x7F) | (0x80 if v > 0x7F else 0))
            v >>= 7
            if not v:
                return bytes(out)

    out = b""
    for num, v in fields:
        if isinstance(v, float):
            out += varint(num << 3 | 1) + np.float64(v).tobytes()
        elif isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def test_op_metadata_from_the_wire_format(tmp_path):
    """``xplane_op_metadata`` on a hand-encoded XSpace: a host plane it
    skips, and the device plane's event metadata with a string stat, a
    stat given by reference and numeric stats it leaves out."""
    stat_meta = [_pb((1, k), (2, _pb((1, k), (2, name))))
                 for k, name in ((3, "tf_op"), (4, "hlo_category"), (5, "flops"), (9, "gather"))]
    fusion = _pb((1, 11), (2, "%fusion.3 = f32[16] fusion(...)"), (4, "fusion.3"),
                 (5, _pb((1, 3), (5, "jit(metl_map_chunk)/uid_resolve/gather"))),
                 (5, _pb((1, 4), (7, 9))), (5, _pb((1, 5), (3, 123))), (5, _pb((1, 5), (2, 1.5))),
                 (6, _pb((1, 1), (1, 2))))
    line = _pb((2, "XLA Ops"), (4, _pb((1, 11), (2, 5), (3, 10))))
    device = _pb((1, 7), (2, "/device:TPU:0"), (3, line), (4, _pb((1, 11), (2, fusion))),
                 (4, _pb((1, 12), (2, _pb((1, 12), (2, "densify_map.1"))))),
                 *[(5, s) for s in stat_meta])
    host = _pb((2, "/host:CPU"), (4, _pb((1, 1), (2, _pb((1, 1), (2, "metl:emit"))))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb((1, host), (1, device)))
    meta = scopes.xplane_op_metadata(str(path))
    want = {"tf_op": "jit(metl_map_chunk)/uid_resolve/gather", "hlo_category": "gather"}
    assert meta == {"%fusion.3 = f32[16] fusion(...)": want, "fusion.3": want, "densify_map.1": {}}
    assert scopes.xplane_op_metadata(str(path), plane="/device:TPU:1") == {}


def test_op_metadata_from_a_recorded_chip_trace():
    """``xplane_op_metadata`` on a real ``.xplane.pb`` of an
    ``eos_paper.replay`` window on a TPU v5e, cut to the device plane's
    metadata (the raw bytes of its id, name and metadata maps): it finds
    every ``tf_op`` scope that ``uid_resolve_v5e.json`` recorded, with the
    uid-resolve ops under ``jit(metl_map_chunk)/uid_resolve/``."""
    meta = scopes.xplane_op_metadata(str(DATA / "device_plane_v5e.xplane.pb"))
    d = json.loads((DATA / "uid_resolve_v5e.json").read_text())
    want = {name: st["tf_op"] for name, st in d["metadata"].items() if "tf_op" in st}
    assert want and {name: meta[name]["tf_op"] for name in want} == want
    resolve = [name for name, tf_op in want.items() if "/uid_resolve/" in tf_op]
    assert len(resolve) == d["uid_resolve_ops"]
    assert all(want[n].startswith("jit(metl_map_chunk)/uid_resolve/") for n in resolve)
