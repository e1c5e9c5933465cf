"""The open-loop source: due times, the poll limit, lazy events."""

import numpy as np

from bench.lib import deployment as dep
from bench.lib import traffic as trf
from bench.lib.source import OpenLoopSource, latencies


class FakeClock:
    """A clock that moves 10 us per reading, and when the source sleeps or
    the test advances it."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += 1e-5
        return self.t


def _batch(spec, n):
    d = dep.build(spec)
    w = trf.schema_weights(len(d.history.versions), 1.0, 7)
    cols = {}
    for c, (o, v) in enumerate(d.tables.cols):
        cols.setdefault(o, []).append(c)
    return d, trf.generate(np.random.default_rng(3), spec["stream"], w, cols,
                           d.tables.flat(), n, 0, d.state)


def test_polls_respect_due_times_and_the_limit(small_spec, monkeypatch):
    d, b = _batch(small_spec, 5000)
    clock = FakeClock()
    monkeypatch.setattr("time.sleep", lambda s: setattr(clock, "t", clock.t + s + 0.0005))
    due = np.sort(np.random.default_rng(0).random(b.n) * 2.0)
    due[2000:3500] = due[2000]  # a burst: 1,500 events due at once
    src = OpenLoopSource(b, d.tables.cols, due, max_poll=1000, clock=clock)
    src.start(clock.t, float("inf"))
    for chunk in src.poll():
        lo, hi, at = src.polls[-1]
        assert len(chunk) == hi - lo <= 1000
        # nothing is handed out before it is due, and everything due is
        assert clock.t - src.t0 >= due[hi - 1]
        assert hi == b.n or hi - lo == 1000 or due[hi] > at - src.t0
        assert np.array_equal(chunk.keys, b.key[lo:hi])
        clock.t += 0.0003  # the pipeline's work on the chunk
    assert src.polls[-1][1] == b.n
    sizes = [hi - lo for lo, hi, _ in src.polls]
    assert max(sizes) == 1000


def test_backlog_cycles_with_fresh_keys(small_spec):
    d, b = _batch(small_spec, 300)
    clock = FakeClock()
    src = OpenLoopSource(b, d.tables.cols, np.zeros(b.n), max_poll=100, cycle=True,
                         key_span=b.n, clock=clock)
    src.start(clock.t, float("inf"))
    chunks = []
    for chunk in src.poll():
        chunks.append(chunk)
        if len(chunks) == 7:
            break
    assert [hi - lo for lo, hi, _ in src.polls] == [100] * 7
    assert np.array_equal(chunks[3].keys, b.key[:100] + b.n)  # the second pass
    assert np.array_equal(chunks[6].keys, b.key[:100] + 2 * b.n)
    assert np.array_equal(chunks[4].vals, chunks[1].vals)


def test_lazy_events_and_latency(small_spec):
    d, b = _batch(small_spec, 50)
    src = OpenLoopSource(b, d.tables.cols, np.zeros(b.n), max_poll=10)
    chunk = src.chunk(10, 20)
    ev = chunk.events[3]
    assert ev.key == b.key[13] and (ev.schema_id, ev.version) == d.tables.cols[b.col[13]]
    payload = ev.payload()
    lo, hi = b.offsets[13], b.offsets[14]
    assert sorted(payload) == sorted(b.uid[lo:hi].tolist())
    polls = [(0, 20, 0.0), (20, 50, 0.0)]
    lat = latencies(np.arange(50) * 0.1, polls, [5.0], t0=1.0)
    assert np.allclose(lat[:20], 4.0 - np.arange(20) * 0.1)
    assert np.isinf(lat[20:]).all()
