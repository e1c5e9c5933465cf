"""The trace reduction on a small recorded trace, checked in: busy, idle
and kernel times worked out by hand from its events."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench.lib import trace as tr

DATA = Path(__file__).resolve().parent / "data"


def test_union_and_gaps_by_hand():
    ops = tr.Events.of([("fusion.1", 100, 50), ("densify_map", 120, 60),
                        ("copy.3", 300, 20), ("densify_map", 900, 40)])
    spans = tr.Events.of([("emit", 150, 100), ("triage", 400, 300), ("dispatch", 850, 40)])
    red = tr.reduce(ops, spans, 0, 1000, kernel="densify_map")
    # busy: [100, 180) + [300, 320) + [900, 940) = 80 + 20 + 40
    assert red.busy_ns == 140 and red.window_ns == 1000
    assert red.idle_share == pytest.approx(0.86)
    assert red.kernel_ns == 100 and red.kernel_events == 2
    # gaps: [320, 900) 580 (mid 610: triage), [0, 100) 100 (none),
    # [180, 300) 120 (mid 240: emit), [940, 1000) 60 (none)
    assert [(n, round(s * 1e9)) for n, s in red.idle_gaps] == [
        ("triage", 580), ("emit", 120), ("none", 100), ("none", 60)]
    assert dict(red.top_ops) == {"densify_map": 100e-9, "fusion.1": 50e-9, "copy.3": 20e-9}


def test_clip_to_the_window():
    ops = tr.Events.of([("a", 0, 100), ("b", 50, 100), ("c", 400, 200)])
    s, e = tr.union_busy(ops, 80, 500)
    assert s.tolist() == [80, 400] and e.tolist() == [150, 500]


def test_recorded_chip_trace():
    """A 3 ms slice of an ``eos_paper.replay`` trace on a TPU v5e: one
    chunk's mapping program (29 operations) and the host spans around it.
    Busy time is checked against a painted nanosecond timeline."""
    d = json.loads((DATA / "trace_replay_v5e.json").read_text())
    ops, spans = tr.Events.of(d["ops"]), tr.Events.of(d["spans"])
    lo, hi = d["lo"], d["hi"]
    paint = np.zeros(hi - lo, bool)
    for _, s, dur in d["ops"]:
        paint[max(s, lo) - lo : min(s + dur, hi) - lo] = True
    red = tr.reduce(ops, spans, lo, hi, kernel=d["kernel"])
    assert red.busy_ns == int(paint.sum()) == 502207
    assert red.idle_share == pytest.approx(1 - 502207 / 3e6)
    assert (red.kernel_ns, red.kernel_events) == (30556, 1)
    assert red.top_ops[0][0] in ("fusion", "fusion.1", "fusion.2", "fusion.3")
    assert len(red.top_ops) == 10 and all(n in {"dispatch", "emit", "sink", "poll", "triage",
                                                "check", "none"} for n, _ in red.idle_gaps)
