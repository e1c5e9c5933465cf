"""The program's own spans (``repro.etl.tracing``) as the benchmark reads
them.

The program records its spans while a JAX profiler trace is being taken,
which a ``--trace 1`` run does from just before the window opens to after
it closes; ``--trace 0`` runs record none.  ``window`` returns the spans
that started in the window [t0, tw), on ``time.perf_counter``'s clock (the
program's spans read ``perf_counter_ns``).  A program without the recorder
(a commit older than it) gives None, and every reader then returns nothing.

The first reader to ask for a window's spans also prints the window's
stall report on stderr (``report_stalls``): every span of the window longer
than ``STALL_MS``, with the thread's CPU time across it and the host threads
(from the device trace) busy inside it.  CPU time near the wall says the
thread computed (or faulted pages in); CPU time far under the wall says it
was off the CPU, and the busy threads say whether a runtime thread worked
meanwhile or the whole process stood still.
"""

from __future__ import annotations

import functools
import sys
from typing import Callable, List, Optional

import numpy as np

from . import scopes

STALL_MS = 50.0


@functools.lru_cache(maxsize=1)
def _window(t0: float, tw: float) -> Optional[np.ndarray]:
    try:
        from repro.etl import tracing
    except ImportError:
        return None
    rec = tracing.records()
    lo, hi = int(t0 * 1e9), int(tw * 1e9)
    rec = rec[(rec["start_ns"] >= lo) & (rec["start_ns"] < hi)]
    rec = rec if rec.size else None
    report_stalls(rec, t0)
    return rec


def window(ctx) -> Optional[np.ndarray]:
    """The program's spans that started in the window, or None."""
    return _window(ctx.t0, ctx.tw)


def _wall(rec: np.ndarray) -> np.ndarray:
    return rec["end_ns"] - rec["start_ns"]


def _cpu(rec: np.ndarray) -> np.ndarray:
    return rec["cpu_end_ns"] - rec["cpu_start_ns"]


def us_per_event(rec: Optional[np.ndarray], name: str, events: int) -> Optional[float]:
    """Microseconds in spans ``name`` per event written in the window."""
    if rec is None or not events:
        return None
    sel = rec[rec["name"] == name]
    if not sel.size:
        return None
    return float(_wall(sel).sum()) / 1e3 / events


def mean_us(rec: Optional[np.ndarray], name: str) -> Optional[float]:
    """Mean duration of spans ``name``, in microseconds."""
    if rec is None:
        return None
    sel = rec[rec["name"] == name]
    if not sel.size:
        return None
    return float(_wall(sel).mean()) / 1e3


def stall_report(rec: Optional[np.ndarray], t0: float,
                 trace: Optional[scopes.ProgramTrace], min_ms: float = STALL_MS,
                 top: int = 6) -> List[str]:
    """One line per span of ``rec`` longer than ``min_ms``.  With the
    device trace, the host threads busy inside it: a span maps onto the
    trace's clock through the window's open (``bench:open`` starts at
    ``perf_counter`` ``t0``)."""
    if rec is None:
        return ["stall report: no program spans in the window"]
    wall = _wall(rec)
    long_ = np.nonzero(wall > min_ms * 1e6)[0]
    out = [f"stall report: {long_.size} program spans over {min_ms:g} ms in the window"]
    t0_ns = int(t0 * 1e9)
    for i in long_:
        r = rec[i]
        line = (f"stall: {r['name']} chunk {r['chunk']} at +{(r['start_ns'] - t0_ns) / 1e9:.3f} s:"
                f" wall {wall[i] / 1e6:.1f} ms, thread CPU"
                f" {(r['cpu_end_ns'] - r['cpu_start_ns']) / 1e6:.1f} ms")
        if trace is not None and trace.open_ns is not None:
            lo = trace.open_ns + int(r["start_ns"]) - t0_ns
            busy = scopes.busy_in(trace.threads, lo, lo + int(wall[i]))
            line += "; other host threads busy: " + (", ".join(
                f"{n} {b / 1e6:.1f} ms (longest: {ev[:60]})" for n, b, ev in busy[:top]) or "none")
        out.append(line)
    return out


def report_stalls(rec: Optional[np.ndarray], t0: float,
                  log: Callable[[str], None] = lambda s: print(s, file=sys.stderr,
                                                               flush=True)) -> None:
    """Print the stall report of the window that opened at ``t0``."""
    trace = scopes.load(scopes.window_open_wall(t0))
    for line in stall_report(rec, t0, trace):
        log(line)
