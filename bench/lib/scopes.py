"""What the program names on the profiler trace: its device operations by
jitted module and named scope, and the host threads beside the Python one.

``load`` opens the newest ``.xplane.pb`` under the traced run's directory
(``run_cell.py`` traces into ``<repo>/.bench_trace``) and flattens it into
plain lists; ``scoped_ops``, ``scoped_ns`` and ``busy_in`` work on those
lists alone, so a small recorded trace checks them (``bench/tests/data``).

* ``ops``: the ``XLA Ops`` events of the first device, each with the module
  it ran in and its scope path.  On a TPU v5e the events carry no stat but
  their device offset and duration (``jax.profiler.ProfileData`` does not
  expose the events' metadata): the module is the ``XLA Modules`` event
  that holds the op (``jit_metl_map_chunk(<fingerprint>)``), and the scope
  path is read from the op's event metadata in the ``.xplane.pb`` itself
  (``xplane_op_metadata``), where the HLO op metadata keeps the
  ``jax.named_scope`` names it ran under
  (``jit(metl_map_chunk)/uid_resolve/...``);
* ``open_ns``: where the harness's ``bench:open`` span starts: the window's
  open on the trace's clock;
* ``threads``: every host-plane line other than the one holding
  ``bench:open`` (the Python thread), by line name.

A trace older than the window (left by an earlier run) is not read.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import os
import re
import time
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from .trace import SPAN_PREFIX, Events, union_busy

TRACE_DIR = Path(__file__).resolve().parents[2] / ".bench_trace"
OPEN = SPAN_PREFIX + "open"
DEVICE = "/device:TPU:0"

Row = Tuple[str, int, int]  # (name, start ns, duration ns)


@dataclasses.dataclass
class ScopedOp:
    name: str
    start: int  # ns, trace clock
    dur: int
    module: str  # the jitted module, e.g. jit_metl_map_chunk
    scope: str  # the op's metadata: the named scopes it ran under


@dataclasses.dataclass
class ProgramTrace:
    open_ns: Optional[int]
    ops: List[ScopedOp]
    threads: Dict[str, Events]


def _text(v) -> str:
    return v.decode(errors="replace") if isinstance(v, bytes) else str(v)


def scoped_ops(ops: Sequence[Tuple[str, int, int, Dict[str, str]]], modules: Sequence[Row],
               metadata: Dict[str, Dict[str, str]]) -> List[ScopedOp]:
    """Each op ``(name, start, dur, stats)`` with the module whose
    ``XLA Modules`` event holds its start, and the string stats of its own
    event and of its event metadata as its scope path."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for name, start, dur, stats in ops:
        module = stats.get("hlo_module", "")
        k = bisect.bisect_right(starts, start) - 1
        if not module and k >= 0 and start < mods[k][1] + mods[k][2]:
            module = mods[k][0].split("(", 1)[0]
        texts = {**metadata.get(name, {}), **stats}
        scope = " ".join(_text(v) for key, v in sorted(texts.items()) if key != "hlo_module")
        out.append(ScopedOp(name, start, dur, _text(module), scope))
    return out


# -- the op metadata, from the .xplane.pb's protobuf wire format --------------
# XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 (map<int64,
# XEventMetadata>), stat_metadata = 5 (map<int64, XStatMetadata>);
# XEventMetadata: name = 2, display_name = 4, stats = 5; XStatMetadata:
# name = 2; XStat: metadata_id = 1, str_value = 5, ref_value = 7 (the id of
# a stat metadata whose name is the value).


def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: memoryview, i: int, end: int) -> Iterator[Tuple[int, object]]:
    """(field number, varint value or (start, end) of a length-delimited
    field) of one message; fixed-width fields are skipped."""
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield field, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield field, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unexpected protobuf wire type {wire}")


def _str(buf: memoryview, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode(errors="replace")


def _map_value(buf: memoryview, span) -> Optional[Tuple[int, int]]:
    for f, v in _fields(buf, *span):
        if f == 2:
            return v  # type: ignore[return-value]
    return None


def xplane_op_metadata(path: str, plane: str = DEVICE) -> Dict[str, Dict[str, str]]:
    """The string stats of each event metadata of ``plane``, by the
    metadata's name and by its display name."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    for field, span in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name, metas, stat_names = None, [], {}
        for f, v in _fields(buf, *span):  # type: ignore[misc]
            if f == 2:
                name = _str(buf, v)
                if name != plane:
                    break
            elif f == 4:
                metas.append(_map_value(buf, v))
            elif f == 5:
                sm = _map_value(buf, v)
                if sm is not None:
                    ids = dict(_fields(buf, *sm))
                    if 1 in ids and 2 in ids:
                        stat_names[ids[1]] = _str(buf, ids[2])
        if name != plane:
            continue
        out: Dict[str, Dict[str, str]] = {}
        for m in metas:
            if m is None:
                continue
            names, raw = [], []
            for f, v in _fields(buf, *m):
                if f in (2, 4):
                    names.append(_str(buf, v))
                elif f == 5:
                    raw.append(dict(_fields(buf, *v)))  # type: ignore[misc]
            stats = {}
            for st in raw:
                key = stat_names.get(st.get(1), str(st.get(1)))
                if 5 in st:
                    stats[key] = _str(buf, st[5])
                elif 7 in st:
                    stats[key] = stat_names.get(st[7], "")
            for n in names:
                if n:
                    out[n] = stats
        return out
    return {}


def newest_xplane(log_dir: Path = TRACE_DIR) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    return paths[-1] if paths else None


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime: float) -> ProgramTrace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: List[Tuple[str, int, int, Dict[str, str]]] = []
    modules: List[Row] = []
    lines: Dict[str, List[Row]] = {}
    open_ns, python_line = None, None
    for plane in data.planes:
        if plane.name == DEVICE:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += [(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events]
                elif line.name == "XLA Ops":
                    for e in line.events:
                        stats = {k: _text(v) for k, v in dict(e.stats).items()
                                 if isinstance(v, (str, bytes))}
                        ops.append((e.name, int(e.start_ns), int(e.duration_ns), stats))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                key = f"{plane.name}/{line.name}"
                rows = lines.setdefault(key, [])
                for e in line.events:
                    rows.append((e.name, int(e.start_ns), int(e.duration_ns)))
                    if e.name == OPEN:
                        open_ns, python_line = int(e.start_ns), key
    threads = {k: Events.of(v) for k, v in lines.items() if k != python_line and v}
    metadata = xplane_op_metadata(path) if ops else {}
    return ProgramTrace(open_ns, scoped_ops(ops, modules, metadata), threads)


def load(window_open_wall: float, log_dir: Path = TRACE_DIR) -> Optional[ProgramTrace]:
    """The newest trace under ``log_dir``, or None where there is none
    written after ``window_open_wall`` (``time.time()`` at the window's
    open)."""
    path = newest_xplane(log_dir)
    if path is None:
        return None
    mtime = os.path.getmtime(path)
    if mtime < window_open_wall:
        return None
    return _load(path, mtime)


def window_open_wall(t0: float) -> float:
    """``time.time()`` at ``perf_counter()`` reading ``t0``."""
    return time.time() - (time.perf_counter() - t0)


def scoped_ns(ops: Sequence[ScopedOp], lo: int, hi: int, module: str, scope: str) -> Tuple[int, int]:
    """Device time and count of the operations of ``module`` that ran under
    the named scope ``scope`` and started in [lo, hi)."""
    pat = re.compile(rf"(^|[/ ]){re.escape(scope)}/")
    total = n = 0
    for op in ops:
        if lo <= op.start < hi and op.module == module and pat.search(op.scope):
            total += op.dur
            n += 1
    return total, n


def busy_in(threads: Dict[str, Events], lo: int, hi: int) -> List[Tuple[str, int, str]]:
    """Per host thread, its busy ns inside [lo, hi) (the union of its
    events there) and the name of its longest event there, busiest first;
    threads idle there are left out."""
    out = []
    for name, ev in threads.items():
        s, e = union_busy(ev, lo, hi)
        busy = int((e - s).sum())
        if busy > 0:
            inside = np.minimum(ev.start + ev.dur, hi) - np.maximum(ev.start, lo)
            out.append((name, busy, ev.name[int(np.argmax(inside))]))
    return sorted(out, key=lambda kv: -kv[1])
