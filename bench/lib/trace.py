"""From a profiler trace to the device numbers the benchmark reports.

``load`` reads the ``.xplane.pb`` the JAX profiler writes into flat event
lists: the device's operations and the benchmark's host spans (the
``bench:*`` annotations of :mod:`bench.lib.spans`), both on the trace's
clock in nanoseconds.  ``reduce`` works on those lists alone, so a small
recorded trace checks it (``bench/tests/data``).

* busy: the union of the intervals in which an operation ran on the
  device, clipped to the traced window; idle share is 1 - busy / window;
* kernel time: the summed device durations of the events whose name holds
  the kernel's name;
* the longest idle gaps, each named by the innermost host span open across
  its middle (or ``none`` when the host was in no span: waiting for work).
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

SPAN_PREFIX = "bench:"


@dataclasses.dataclass
class Events:
    """Named intervals: ``start``/``dur`` in ns on the trace's clock."""

    name: List[str]
    start: np.ndarray  # int64
    dur: np.ndarray  # int64

    @classmethod
    def of(cls, rows: Sequence[Tuple[str, int, int]]) -> "Events":
        return cls(
            [r[0] for r in rows],
            np.asarray([r[1] for r in rows], np.int64).reshape(-1),
            np.asarray([r[2] for r in rows], np.int64).reshape(-1),
        )


def _device_plane(name: str) -> Optional[int]:
    """Device index of a device plane (``/device:TPU:0``), else None."""
    head = "/device:TPU:"
    if not name.startswith(head):
        return None
    tail = name[len(head):]
    return int(tail) if tail.isdigit() else None


def load(log_dir: str, ops_line: str = "XLA Ops") -> Tuple[Dict[int, Events], Events]:
    """Device operations per device, and the host spans, of the newest
    trace under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    ops: Dict[int, List[Tuple[str, int, int]]] = {}
    spans: List[Tuple[str, int, int]] = []
    for plane in data.planes:
        dev = _device_plane(plane.name)
        for line in plane.lines:
            if dev is not None and line.name == ops_line:
                rows = ops.setdefault(dev, [])
                for e in line.events:
                    rows.append((e.name, int(e.start_ns), int(e.duration_ns)))
            elif dev is None and plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):], int(e.start_ns),
                                      int(e.duration_ns)))
    return {d: Events.of(r) for d, r in ops.items()}, Events.of(spans)


def union_busy(ev: Events, lo: int, hi: int) -> Tuple[np.ndarray, np.ndarray]:
    """Merged busy intervals of ``ev`` clipped to [lo, hi): (starts, ends)."""
    s = np.clip(ev.start, lo, hi)
    e = np.clip(ev.start + ev.dur, lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if s.size == 0:
        return s, e
    order = np.argsort(s, kind="stable")
    s, e = s[order], np.maximum.accumulate(e[order])
    new = np.r_[True, s[1:] > e[:-1]]
    idx = np.nonzero(new)[0]
    ends = np.r_[e[idx[1:] - 1], e[-1]]
    return s[idx], ends


@dataclasses.dataclass
class Reduction:
    window_ns: int
    busy_ns: int
    kernel_ns: int
    kernel_events: int
    top_ops: List[Tuple[str, float]]  # (name, seconds), longest total first
    idle_gaps: List[Tuple[str, float]]  # (host span open across it, seconds)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns


def op_name(name: str) -> str:
    """An operation's short name: the trace names TPU operations by their
    whole HLO instruction (``%fusion.2 = s32[16384]... fusion(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def reduce(ops: Events, spans: Events, lo: int, hi: int, kernel: str,
           top: int = 10) -> Reduction:
    starts, ends = union_busy(ops, lo, hi)
    busy = int((ends - starts).sum())
    is_kernel = np.asarray([kernel in op_name(n) for n in ops.name], bool)
    inside = (ops.start >= lo) & (ops.start < hi)
    k_sel = is_kernel & inside
    totals: Dict[str, int] = {}
    for n, d, ok in zip(ops.name, ops.dur, inside):
        if ok:
            f = op_name(n)
            totals[f] = totals.get(f, 0) + int(d)
    top_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    gap_s = np.r_[lo, ends]
    gap_e = np.r_[starts, hi]
    g = gap_e - gap_s
    order = np.argsort(-g, kind="stable")[:top]
    gaps = []
    for i in order:
        if g[i] <= 0:
            continue
        mid = (gap_s[i] + gap_e[i]) // 2
        gaps.append((span_at(spans, int(mid)), g[i] / 1e9))
    return Reduction(
        window_ns=int(hi - lo),
        busy_ns=busy,
        kernel_ns=int(ops.dur[k_sel].sum()),
        kernel_events=int(k_sel.sum()),
        top_ops=[(n, d / 1e9) for n, d in top_ops],
        idle_gaps=gaps,
    )


def span_at(spans: Events, t: int) -> str:
    """The innermost (latest-starting) host span open at ``t``."""
    open_ = (spans.start <= t) & (spans.start + spans.dur > t)
    if not open_.any():
        return "none"
    idx = np.nonzero(open_)[0]
    return spans.name[int(idx[np.argmax(spans.start[idx])])]
