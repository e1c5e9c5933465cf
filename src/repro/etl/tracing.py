"""Spans inside METL's chunk path: one in-memory recorder, off by default.

Each stage of the chunk path opens a span (``triage``, ``densify`` and its
``densify.layout`` / ``densify.pack`` steps, ``dispatch``, ``emit`` and its
``emit.sync`` / ``emit.rows`` steps, ``sink.<ClassName>``, and the
pipeline's ``pipeline.poll`` and ``pipeline.lookahead``; see
``docs/tracing.md``).  Spans record while :func:`enable` is in force, or
while a JAX profiler trace is being taken (``jax.profiler.start_trace`` /
``jax.profiler.trace``).  While a profiler trace is being taken each span is
also a ``metl:<name>`` ``jax.profiler.TraceAnnotation``, so it sits on the
device trace's clock.

Off, :func:`span` returns one shared no-op context manager: the cost is
the module flag and the profiler's own is-tracing check, nothing else.

On, each span keeps, in memory:

  ``name``                       the span's name
  ``chunk``                      the sequence number the
                                 :class:`~repro.etl.pipeline.Pipeline` gave
                                 the data chunk the span works on (-1 outside
                                 a pipeline); the double buffer sets it before
                                 each stage, so prepare(N+1) carries N+1 while
                                 emit(N) carries N
  ``parent``                     row of the enclosing span on the same thread
                                 in :func:`records` (-1 at the top level)
  ``start_ns`` / ``end_ns``      ``time.perf_counter_ns()``
  ``cpu_start_ns`` / ``cpu_end_ns``  ``time.thread_time_ns()``

Wall time minus thread CPU time is the span's time off the CPU: waiting (on
a lock, on the device) or descheduled.  The wall interval encloses the CPU
one, so thread CPU time never exceeds wall time.  The recorder keeps the newest :data:`KEEP` spans; :func:`reset` empties it.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import itertools
import threading
import time
from typing import Any, Callable, Deque, Tuple, TypeVar

import jax
import numpy as np

__all__ = [
    "RECORD",
    "KEEP",
    "enable",
    "disable",
    "reset",
    "span",
    "traced",
    "set_chunk",
    "records",
    "dump",
]

RECORD = np.dtype(
    [
        ("name", "U48"),
        ("chunk", np.int64),
        ("parent", np.int64),
        ("start_ns", np.int64),
        ("end_ns", np.int64),
        ("cpu_start_ns", np.int64),
        ("cpu_end_ns", np.int64),
    ]
)

KEEP = 1 << 18  # spans kept; the oldest go first (a 20 s window records ~60k)

ANNOTATION_PREFIX = "metl:"

_on = False
_profiling: Callable[[], bool] = jax.profiler.TraceAnnotation.is_enabled
_NOOP = contextlib.nullcontext()
_ids = itertools.count()
_local = threading.local()
# (id, name, chunk, parent id, start, end, cpu start, cpu end)
_done: Deque[Tuple[Any, ...]] = collections.deque(maxlen=KEEP)


def enable() -> None:
    """Record spans from now on, until :func:`disable`."""
    global _on
    _on = True


def disable() -> None:
    """Stop recording (spans still record while a profiler trace runs)."""
    global _on
    _on = False


def reset() -> None:
    """Forget every recorded span."""
    _done.clear()


def set_chunk(seq: int) -> None:
    """Stamp the calling thread's next spans with data chunk ``seq``."""
    _local.chunk = seq


class _Span:
    __slots__ = ("name", "id", "parent", "chunk", "t0", "c0", "ann")

    def __init__(self, name: str) -> None:
        self.name = name

    def __enter__(self) -> "_Span":
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        self.id = next(_ids)
        self.parent = stack[-1] if stack else -1
        self.chunk = getattr(_local, "chunk", -1)
        stack.append(self.id)
        self.ann = None
        if _profiling():
            self.ann = jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + self.name)
            self.ann.__enter__()
        # the wall interval encloses the CPU one, so CPU time <= wall time
        self.t0 = time.perf_counter_ns()
        self.c0 = time.thread_time_ns()
        return self

    def __exit__(self, *exc: Any) -> None:
        c1 = time.thread_time_ns()
        t1 = time.perf_counter_ns()
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        _local.stack.pop()
        _done.append((self.id, self.name, self.chunk, self.parent, self.t0, t1, self.c0, c1))


def span(name: str) -> Any:
    """Context manager timing one stage; the shared no-op while off."""
    if _on or _profiling():
        return _Span(name)
    return _NOOP


F = TypeVar("F", bound=Callable[..., Any])


def traced(name: str) -> Callable[[F], F]:
    """Decorator: each call of the function is one span ``name``."""

    def deco(fn: F) -> F:
        @functools.wraps(fn)
        def wrapper(*args: Any, **kwargs: Any) -> Any:
            if _on or _profiling():
                with _Span(name):
                    return fn(*args, **kwargs)
            return fn(*args, **kwargs)

        return wrapper  # type: ignore[return-value]

    return deco


def records() -> np.ndarray:
    """The recorded spans as one :data:`RECORD` array, in the order they
    opened.  ``parent`` is a row of this array, or -1 (top level, or a
    parent that is still open or no longer kept)."""
    done = sorted(_done)
    out = np.empty(len(done), RECORD)
    if not done:
        return out
    ids = np.asarray([d[0] for d in done], np.int64)
    parents = np.asarray([d[3] for d in done], np.int64)
    for field, k in (("name", 1), ("chunk", 2), ("start_ns", 4), ("end_ns", 5),
                     ("cpu_start_ns", 6), ("cpu_end_ns", 7)):
        out[field] = [d[k] for d in done]
    row = np.searchsorted(ids, parents)
    row = np.minimum(row, ids.size - 1)
    out["parent"] = np.where((parents >= 0) & (ids[row] == parents), row, -1)
    return out


def dump(path: str) -> None:
    """Write the recorded spans to ``path`` as a ``.npy`` file, which
    ``numpy.load`` reads back as a :data:`RECORD` array."""
    np.save(path, records())
