"""The benchmark's consumer: an open-loop source in front of ``Pipeline``.

It behaves like a Kafka Streams consumer.  Each ``poll()`` step returns
every event due by now, up to ``max_poll`` records (Kafka Streams'
``max.poll.records`` default is 1,000).  When nothing is due it waits for
the next due event.  The schedule is fixed before the window opens and
does not slow when the program does: an event's creation time is its due
time, and its latency runs from there.

Chunks are real ``ColumnarChunk``\\ s sliced from the pre-generated
arrays.  The per-event ``CDCEvent`` objects the program needs only on its
dead-letter and park paths are built when asked for.
"""

from __future__ import annotations

import contextlib
import gc
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.etl import CDCEvent, ColumnarChunk, RowSink, Source

from .traffic import Batch


class LazyEvents(Sequence):
    """The ``events`` of a chunk, built one by one on access."""

    def __init__(self, chunk_src: "OpenLoopSource", lo: int, hi: int, key_shift: int) -> None:
        self.src, self.lo, self.hi, self.key_shift = chunk_src, lo, hi, key_shift

    def __len__(self) -> int:
        return self.hi - self.lo

    def __getitem__(self, k):
        if isinstance(k, slice):
            return [self[i] for i in range(*k.indices(len(self)))]
        if not -len(self) <= k < len(self):
            raise IndexError(k)
        return self.src.event(self.lo + (k % len(self)), self.key_shift)


def wait_until(t: float, clock=time.perf_counter) -> None:
    """Sleep, then spin the last millisecond (sleep overshoots ~0.1 ms)."""
    while True:
        d = t - clock()
        if d <= 0:
            return
        if d > 0.002:
            time.sleep(d - 0.001)


def _span(name: str, on: bool):
    """A profiler annotation of the consumer's own poll (traced runs)."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


class OpenLoopSource(Source):
    """Polls of the pre-generated stream on its due times.

    ``due`` holds each event's due offset (s) from ``t0``, which is set when
    the window opens.  ``cycle`` > 0 replays the stream endlessly as a
    backlog, with keys shifted by ``key_span`` per pass (a topic whose
    events are all in it when the window opens).  The source stops yielding
    at ``stop_at`` (absolute), or when the stream ends.

    ``polls`` records ``(first, end, clock)`` of every data chunk yielded
    (``clock`` is when the poll took every event due by then),
    in order; the pipeline writes each chunk's rows once, in the same
    order, which is how a write is matched to its events.

    ``control`` lists in-band control items as ``(first, at, item)``,
    ``first`` ascending: ``item`` is yielded at its due offset ``at``,
    before event ``first`` (the first event due at or after it), and no
    poll spans it.  ``controls`` records ``(first, clock)`` of each yielded.
    A backlog's passes after the first carry the stream's newest registry
    state, where the first pass's control items left the program: the
    topic's events re-produced at that state (their uids are the same).
    """

    def __init__(
        self,
        batch: Batch,
        cols: List[Tuple[int, int]],
        due: np.ndarray,
        *,
        max_poll: int,
        cycle: bool = False,
        key_span: int = 0,
        clock=time.perf_counter,
        annotate: bool = False,
        control: Sequence[Tuple[int, float, object]] = (),
    ) -> None:
        self.b = batch
        self.newest = int(batch.state.max()) if batch.n else 0
        self.control = list(control)
        self.controls: List[Tuple[int, float]] = []
        self.annotate = annotate
        col_o = np.asarray([o for o, _ in cols], np.int64)
        col_v = np.asarray([v for _, v in cols], np.int64)
        self.schema_id = col_o[batch.col]
        self.version = col_v[batch.col]
        self.due = due
        self.max_poll = max_poll
        self.cycle = cycle
        self.key_span = key_span
        self.clock = clock
        self.t0 = 0.0
        self.stop_at = float("inf")
        self.cursor = 0
        self.polls: List[Tuple[int, int, float]] = []

    def start(self, t0: float, stop_at: float) -> None:
        self.t0, self.stop_at = t0, stop_at

    def event(self, g: int, key_shift: int) -> CDCEvent:
        b = self.b
        i = g % b.n
        lo, hi = b.offsets[i], b.offsets[i + 1]
        payload = {int(u): float(x) for u, x in zip(b.uid[lo:hi], b.val[lo:hi])}
        return CDCEvent(
            key=int(b.key[i]) + key_shift, op="c",
            state=int(b.state[i]) if g < b.n else self.newest,
            schema_id=int(self.schema_id[i]), version=int(self.version[i]),
            before=None, after=payload, ts=g,
        )

    def chunk(self, lo: int, hi: int) -> ColumnarChunk:
        """Events [lo, hi) of the stream, which lie in one pass of it."""
        b = self.b
        shift = (lo // b.n) * self.key_span
        i, j = lo % b.n, (hi - 1) % b.n + 1
        a, z = b.offsets[i], b.offsets[j]
        return ColumnarChunk(
            events=LazyEvents(self, lo, hi, shift),
            uids=b.uid[a:z],
            vals=b.val[a:z],
            event_offsets=b.offsets[i : j + 1] - a,
            keys=b.key[i:j] + shift,
            bad=np.zeros(j - i, bool),
            states=b.state[i:j] if lo < b.n else np.full(j - i, self.newest, b.state.dtype),
            schema_ids=self.schema_id[i:j],
            versions=self.version[i:j],
        )

    def poll(self):
        n = self.b.n
        end = None if self.cycle else n
        due_abs = self.due
        while True:
            lo = self.cursor
            k = len(self.controls)
            ctl = self.control[k] if k < len(self.control) else None
            if end is not None and lo >= end and ctl is None:
                return
            if self.clock() >= self.stop_at:
                return
            if ctl is not None and ctl[0] <= lo:
                wait_until(self.t0 + ctl[1], self.clock)
                self.controls.append((lo, self.clock()))
                yield ctl[2]
                continue
            limit = lo + self.max_poll
            if end is not None:
                limit = min(limit, end)
            else:
                limit = min(limit, (lo // n + 1) * n)
            if ctl is not None:
                limit = min(limit, ctl[0])
            with _span("bench:poll", self.annotate):
                if self.cycle:
                    hi, now = limit, self.clock()
                else:
                    wait_until(self.t0 + due_abs[lo], self.clock)
                    now = self.clock()
                    hi = int(np.searchsorted(due_abs, now - self.t0, side="right"))
                    hi = max(lo + 1, min(hi, limit))
                self.cursor = hi
                self.polls.append((lo, hi, now))
                chunk = self.chunk(lo, hi)
            yield chunk


class WriteClock(RowSink):
    """A sink placed after the table: stamps the clock at each chunk's
    write, which closes the latency of every event of that chunk.  With
    ``freeze`` it then calls ``gc.freeze()``, which leaves every object
    alive at that point (the rows the table before it keeps) out of the
    collector's later passes."""

    def __init__(self, clock=time.perf_counter, freeze: bool = False) -> None:
        self.clock = clock
        self.freeze = freeze
        self.times: List[float] = []

    def write(self, rows) -> None:
        self.times.append(self.clock())
        if self.freeze:
            gc.freeze()


def latencies(due: np.ndarray, polls, write_times: List[float], t0: float,
              n_due: Optional[int] = None) -> np.ndarray:
    """Per event: write clock of its chunk minus its due time (s), for the
    first ``n_due`` events (those due in the window).  Events never
    written get +inf."""
    sizes = np.asarray([hi - lo for lo, hi, _ in polls[: len(write_times)]], np.int64)
    written = np.repeat(np.asarray(write_times, np.float64), sizes)
    n_due = due.size if n_due is None else n_due
    lat = np.full(n_due, np.inf)
    m = min(n_due, written.size)
    lat[:m] = written[:m] - (t0 + due[:m])
    return lat
