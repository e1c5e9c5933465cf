"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.masked_gather import masked_gather
from repro.kernels.moe_combine import moe_combine
from repro.kernels.densify_map import densify_map
from repro.kernels.segmented_gather import segmented_gather


def _mk_case(rng, b, n_in, n_out, density, dtype):
    vals = rng.normal(size=(b, n_in)).astype(dtype)
    mask = (rng.random((b, n_in)) < 0.7).astype(np.int8)
    src = np.full((n_out,), -1, np.int32)
    k = int(density * min(n_in, n_out))
    if k:
        src[rng.choice(n_out, size=k, replace=False)] = rng.choice(
            n_in, size=k, replace=False
        )
    return jnp.asarray(vals), jnp.asarray(mask), jnp.asarray(src)


SHAPES = [
    (1, 1, 128),
    (8, 10, 128),
    (37, 300, 256),
    (130, 1000, 384),
    (256, 128, 128),
]


@pytest.mark.parametrize("b,n_in,n_out", SHAPES)
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("density", [0.0, 0.3, 1.0])
def test_masked_gather_matches_oracle(b, n_in, n_out, dtype, density):
    rng = np.random.default_rng(hash((b, n_in, n_out, density)) % 2**31)
    vals, mask, src = _mk_case(rng, b, n_in, n_out, density, np.float32)
    vals = vals.astype(dtype)
    rv, rm = ref.masked_gather_ref(vals, mask, src)
    gv, gm = masked_gather(vals, mask, src, interpret=True)
    np.testing.assert_allclose(
        np.asarray(rv, np.float32), np.asarray(gv, np.float32), atol=1e-6
    )
    assert np.array_equal(np.asarray(rm), np.asarray(gm))


def _densify_case(rng, case, w):
    """Operands of one ``densify_map`` case: (B, K) resolved items, (S,)
    routing and an (n_blocks, W) block table whose entries are payload
    slots drawn from the items' slot range, so most output lanes hit."""
    b, k, n_rows, n_blocks, n_slots = 24, 8, 40, 16, 20
    if case == "rows_above_events":
        n_rows = 3 * b
    elif case == "rows_below_events":
        n_rows = b // 3
    slot2d = rng.integers(0, n_slots, size=(b, k)).astype(np.int32)
    # distinct slots per event by default: duplicates get their own case
    for e in range(b):
        slot2d[e] = rng.choice(n_slots, size=k, replace=False)
    if case == "zero_items":
        slot2d[::2] = -1  # every other event carries no item at all
    elif case == "duplicate_uids":
        slot2d[:, k // 2:] = slot2d[:, : k - k // 2]  # each slot twice
    elif case == "unknown_uids":
        slot2d[rng.random((b, k)) < 0.5] = -1  # unknown / foreign: dropped
    x2d = rng.normal(size=(b, k)).astype(np.float32)
    rows = rng.integers(0, b, size=n_rows).astype(np.int32)
    blks = rng.integers(0, n_blocks, size=n_rows).astype(np.int32)
    src2d = np.where(
        rng.random((n_blocks, w)) < 0.3,
        rng.integers(0, n_slots, size=(n_blocks, w)),
        -1,
    ).astype(np.int32)
    return slot2d, x2d, rows, blks, src2d


def _densify_scatter_oracle(slot2d, x2d, rows, blks, src2d, fill):
    """The host semantics in plain numpy: scatter each event's items into a
    dense row in item order (a later duplicate overwrites an earlier one),
    then gather every routed (event, block) row through the block table."""
    b, _ = slot2d.shape
    n_slots = int(max(slot2d.max(), src2d.max())) + 1
    dense = np.zeros((b, n_slots), np.float32)
    have = np.zeros((b, n_slots), bool)
    for e in range(b):
        for j in range(slot2d.shape[1]):
            if slot2d[e, j] >= 0:
                dense[e, slot2d[e, j]] = x2d[e, j]
                have[e, slot2d[e, j]] = True
    src = src2d[blks]
    safe = np.where(src >= 0, src, 0)
    hit = (src >= 0) & np.take_along_axis(have[rows], safe, axis=1)
    vals = np.where(hit, np.take_along_axis(dense[rows], safe, axis=1), fill)
    return vals.astype(np.float32), hit.astype(np.int8)


DENSIFY_CASES = [
    "max_items", "zero_items", "duplicate_uids", "unknown_uids",
    "rows_above_events", "rows_below_events",
]


@pytest.mark.parametrize("w", [128, 256])
@pytest.mark.parametrize("case", DENSIFY_CASES)
def test_densify_map_matches_oracle(case, w):
    """The kernel every cell runs, under the interpreter, against its
    ``ref.py`` twin and a plain numpy scatter-then-gather: events with no
    item and with all ``k``, duplicate slots (last writer wins), dropped
    (-1) items, more and fewer output rows than events, two table widths."""
    rng = np.random.default_rng([DENSIFY_CASES.index(case), w])
    slot2d, x2d, rows, blks, src2d = _densify_case(rng, case, w)
    fill = 0.25
    kv, km = densify_map(slot2d, x2d, rows, blks, src2d, fill=fill, interpret=True)
    rv, rm = ref.densify_map_ref(slot2d, x2d, rows, blks, src2d, fill=fill)
    ov, om = _densify_scatter_oracle(slot2d, x2d, rows, blks, src2d, fill)
    assert np.asarray(kv).shape == (rows.size, w)
    np.testing.assert_array_equal(np.asarray(kv), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(km), np.asarray(rm))
    np.testing.assert_array_equal(np.asarray(kv), ov)
    np.testing.assert_array_equal(np.asarray(km), om)
    assert om.any()  # the case maps something


@pytest.mark.parametrize("b,n_in,w", [(8, 64, 128), (37, 300, 256), (64, 128, 128)])
@pytest.mark.parametrize("n_blocks,s", [(8, 16), (16, 130)])
def test_segmented_gather_matches_oracle(b, n_in, w, n_blocks, s):
    rng = np.random.default_rng(hash((b, n_in, w, n_blocks, s)) % 2**31)
    vals = jnp.asarray(rng.normal(size=(b, n_in)).astype(np.float32))
    mask = jnp.asarray((rng.random((b, n_in)) < 0.7).astype(np.int8))
    src2d = np.full((n_blocks, w), -1, np.int32)
    for blk in range(n_blocks):
        k = int(0.5 * min(n_in, w))
        src2d[blk, rng.choice(w, size=k, replace=False)] = rng.choice(
            n_in, size=k, replace=False
        )
    src2d = jnp.asarray(src2d)
    rows = jnp.asarray(rng.integers(b, size=s).astype(np.int32))
    blks = jnp.asarray(rng.integers(n_blocks, size=s).astype(np.int32))
    rv, rm = ref.segmented_gather_ref(vals, mask, rows, blks, src2d, fill=0.25)
    gv, gm = segmented_gather(vals, mask, rows, blks, src2d, fill=0.25, interpret=True)
    np.testing.assert_allclose(np.asarray(rv), np.asarray(gv), atol=1e-6)
    assert np.array_equal(np.asarray(rm), np.asarray(gm))


@pytest.mark.parametrize(
    "t,e,c,d", [(8, 2, 4, 32), (64, 8, 16, 96), (130, 4, 8, 256), (256, 16, 8, 128)]
)
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_moe_combine_matches_oracle(t, e, c, d, dtype):
    rng = np.random.default_rng(hash((t, e, c, d)) % 2**31)
    eo = jnp.asarray(rng.normal(size=(e, c, d)).astype(np.float32)).astype(dtype)
    cw = np.zeros((t, e, c), np.float32)
    for ti in range(t):
        for _ in range(2):
            cw[ti, rng.integers(e), rng.integers(c)] = rng.random()
    cw = jnp.asarray(cw)
    r = ref.moe_combine_ref(eo, cw)
    p = moe_combine(cw, eo, interpret=True)
    atol = 1e-4 if dtype == np.float32 else 0.1
    np.testing.assert_allclose(
        np.asarray(r, np.float32), np.asarray(p, np.float32), atol=atol, rtol=1e-2
    )


def test_block_shape_sweep():
    """Tile-size robustness: same result for every legal blocking."""
    rng = np.random.default_rng(0)
    vals, mask, src = _mk_case(rng, 64, 200, 256, 0.5, np.float32)
    want, want_m = ref.masked_gather_ref(vals, mask, src)
    for bb in (8, 32, 256):
        for bn in (128, 256):
            gv, gm = masked_gather(vals, mask, src, block_b=bb, block_n=bn, interpret=True)
            np.testing.assert_allclose(np.asarray(want), np.asarray(gv), atol=1e-6)
            assert np.array_equal(np.asarray(want_m), np.asarray(gm))
