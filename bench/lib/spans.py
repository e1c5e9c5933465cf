"""Host spans around the program's layers, from the benchmark's side.

``Spans.wrap(obj, attr, name)`` replaces a bound method of one instance
the benchmark built with a wrapper that opens a
``jax.profiler.TraceAnnotation`` named ``bench:<name>`` (so the span sits
on the device trace's clock) and records its ``perf_counter`` interval.
Only traced runs install them; spans inside the program are a later
change.

``GcPauses`` records every pass of Python's collector from
``gc.callbacks``, in every run; in traced runs each pass is also a
``bench:gc`` span.
"""

from __future__ import annotations

import functools
import gc
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax

from .trace import SPAN_PREFIX


class Spans:
    def __init__(self, clock=time.perf_counter) -> None:
        self.clock = clock
        self.intervals: Dict[str, List[Tuple[float, float]]] = {}

    def wrap(self, obj, attr: str, name: str,
             tag: Callable[[], str] = None) -> None:
        """Wrap ``obj.attr``.  ``tag``, when given, is called before each
        call and its result replaces ``name`` (e.g. to tell a real refresh
        from a no-op check)."""
        inner = getattr(obj, attr)
        clock, store = self.clock, self.intervals

        @functools.wraps(inner)
        def wrapper(*args, **kwargs):
            label = tag() if tag is not None else name
            t0 = clock()
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + label):
                try:
                    return inner(*args, **kwargs)
                finally:
                    store.setdefault(label, []).append((t0, clock()))

        setattr(obj, attr, wrapper)

    def total(self, name: str, lo: float = float("-inf"), hi: float = float("inf")) -> float:
        """Seconds spent in spans ``name`` that started in [lo, hi)."""
        return sum(b - a for a, b in self.intervals.get(name, ()) if lo <= a < hi)


class GcPauses:
    """The collector's passes while installed: ``(start, stop, generation)``."""

    def __init__(self, clock=time.perf_counter, annotate: bool = False) -> None:
        self.clock = clock
        self.annotate = annotate
        self.pauses: List[Tuple[float, float, int]] = []
        self._t = 0.0
        self._span: Optional[jax.profiler.TraceAnnotation] = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = self.clock()
            if self.annotate:
                self._span = jax.profiler.TraceAnnotation(SPAN_PREFIX + "gc")
                self._span.__enter__()
            return
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        self.pauses.append((self._t, self.clock(), int(info["generation"])))

    def close(self) -> None:
        gc.callbacks.remove(self._on)

    def summary(self, lo: float, hi: float) -> str:
        """Passes that started in [lo, hi), per generation: count, total
        and longest pause."""
        out = []
        for g in range(3):
            d = [b - a for a, b, gen in self.pauses if gen == g and lo <= a < hi]
            if d:
                out.append(f"gen{g} {len(d)} passes, {sum(d) * 1e3:.1f} ms in all, "
                           f"longest {max(d) * 1e3:.2f} ms")
        return "; ".join(out) or "no passes"
