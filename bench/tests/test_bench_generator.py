"""The vectorised generator: reproducible from a seed, at the shares a
stream states (nulls, duplicates, schema skew, version mix), and at its
defaults where a stream states none."""

import numpy as np
import pytest

from bench.lib import deployment as dep
from bench.lib import traffic as trf


STATED = {"p_null": 0.25, "p_duplicate": 0.05, "latest_version_share": 0.9}


def _gen(spec, seed, n=60000, stream=None, zipf_s=1.0):
    d = dep.build(spec)
    w = trf.schema_weights(len(d.history.versions), zipf_s, spec["registry"]["seed"])
    cols = {}
    for c, (o, v) in enumerate(d.tables.cols):
        cols.setdefault(o, []).append(c)
    b = trf.generate(np.random.default_rng(seed), {**spec["stream"], **(stream or {})}, w,
                     cols, d.tables.flat(), n, 0, d.state)
    return d, w, b


def test_same_seed_same_stream(small_spec):
    _, _, a = _gen(small_spec, 5, 5000)
    _, _, b = _gen(small_spec, 5, 5000)
    _, _, c = _gen(small_spec, 6, 5000)
    for f in ("key", "col", "offsets", "uid", "val"):
        assert np.array_equal(getattr(a, f), getattr(b, f)), f
    assert not np.array_equal(a.val[:100], c.val[:100])


def test_stated_shares(small_spec):
    d, w, b = _gen(small_spec, 2**31 + 3, stream=STATED)
    st = STATED
    n = b.n
    # duplicates follow their original directly, with its key
    dup = np.r_[False, b.key[1:] == b.key[:-1]]
    base = n - dup.sum()
    assert dup.sum() / base == pytest.approx(st["p_duplicate"], abs=0.005)
    # nulls: present items over attributes of the delivered events
    attrs = np.asarray([u.size for u in d.tables.uid])[b.col]
    assert 1 - b.uid.size / attrs.sum() == pytest.approx(st["p_null"], abs=0.01)
    # schema skew: the stated Zipf weights
    o = np.asarray([c[0] for c in d.tables.cols])[b.col[~dup]]
    share = np.bincount(o, minlength=w.size) / base
    assert share == pytest.approx(w, abs=0.01)
    assert sorted(w)[-1] / sorted(w)[0] == pytest.approx(w.size, rel=1e-9)
    # versions: the stated share at each schema's latest version
    v = np.asarray([c[1] for c in d.tables.cols])[b.col[~dup]]
    latest = np.asarray([d.history.latest(s) for s in range(w.size)])[o]
    assert (v == latest).mean() == pytest.approx(st["latest_version_share"], abs=0.01)
    assert ((v >= 1) & (v <= latest)).all()


def test_defaults_are_full_rows_of_the_newest_version_once(small_spec):
    d, w, b = _gen(small_spec, 2**31 + 5, n=20000, zipf_s=0.0)
    assert np.unique(b.key).size == b.n  # no redelivery
    attrs = np.asarray([u.size for u in d.tables.uid])[b.col]
    assert np.array_equal(np.diff(b.offsets), attrs)  # no nulls
    o = np.asarray([c[0] for c in d.tables.cols])[b.col]
    v = np.asarray([c[1] for c in d.tables.cols])[b.col]
    assert (v == np.asarray([d.history.latest(s) for s in range(w.size)])[o]).all()
    assert np.allclose(w, 1 / w.size)  # s = 0: uniform over the schemas
    share = np.bincount(o, minlength=w.size) / b.n
    assert share == pytest.approx(w, abs=0.02)


def test_items_carry_the_registry_ids(small_spec):
    d, _, b = _gen(small_spec, 11, 2000)
    for e in range(0, 2000, 97):
        c = b.col[e]
        lo, hi = b.offsets[e], b.offsets[e + 1]
        assert np.array_equal(b.uid[lo:hi], d.tables.uid[c][b.pos[lo:hi]])


def test_poisson_arrivals_fix_the_count_and_the_rate():
    arr = {"kind": "poisson", "rate_events_per_s": 10000}
    t = trf.arrivals(np.random.default_rng(2**31 + 9), arr, 20.0)
    assert t.size == 200000 and (np.diff(t) >= 0).all() and 0 <= t[0] and t[-1] < 20.0
    assert np.bincount(t.astype(int), minlength=20) == pytest.approx([10000] * 20, rel=0.04)
    gaps = np.diff(t)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.02)  # exponential gaps


def test_burst_arrivals_fix_the_count_and_the_mean_rate():
    arr = {"kind": "poisson_burst", "rate_events_per_s": 10000, "burst_factor": 3.0,
           "burst_every_s": 5.0, "burst_length_s": 1.0, "burst_offset_s": 2.0}
    t = trf.arrivals(np.random.default_rng(1), arr, 20.0)
    assert t.size == 200000 and (np.diff(t) >= 0).all() and t[-1] < 20.0
    per_s = np.bincount(t.astype(int), minlength=20)
    base = 10000 * 5 / 7
    burst = np.asarray([s % 5 == 2 for s in range(20)])
    assert per_s[burst].mean() == pytest.approx(3 * base, rel=0.02)
    assert per_s[~burst].mean() == pytest.approx(base, rel=0.02)
    assert per_s.reshape(4, 5).sum(axis=1) == pytest.approx([50000] * 4, rel=0.02)


def test_history_matches_the_paper_scale_registry():
    """eos_paper's history reproduces the EOS bring-up registry's sizes."""
    spec = dep.load_spec(dep.Path(__file__).resolve().parents[1], "eos_paper")
    h = dep.build_history(spec["registry"])
    n_ext = sum(len(v) for chain in h.versions.values() for v in chain)
    assert n_ext == 10227
    assert sum(len(n) for n in h.cdm_names) == 1000
