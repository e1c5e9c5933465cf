"""Shared timing harness for the benchmark suite."""

import time
from typing import Callable, Tuple

import jax


def bench(fn: Callable, *args, warmup: int = 2, iters: int = 10) -> float:
    """Median wall-time per call in microseconds (device-synchronised)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def row(name: str, us: float, derived: str = "") -> str:
    return f"{name},{us:.1f},{derived}"
