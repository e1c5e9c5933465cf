"""Pallas TPU kernel for device-resident densification + fused mapping.

:mod:`repro.kernels.segmented_gather` maps a chunk in one launch, but its
operands are HOST-densified: the engine scatters the chunk's (uid, value)
items into a dense ``(B, n_in_pad)`` payload tensor in numpy and ships that
tensor -- ``B * n_in_pad * 5`` bytes of mostly zeros -- across the PCIe
boundary every chunk.  At ETL chunk sizes the dense payload is ~50x larger
than the raw columnar items it encodes, so the transfer (and the host
scatter feeding it) dominates the consume wall clock (ROADMAP open item 2).

This kernel moves densification on-device and FUSES it with the mapping, so
the dense intermediate never exists anywhere -- not in host memory, not in
HBM.  Per chunk the host ships only the resolved columnar items

    slot2d : (B_pad, K) int32   payload slot per item of event row b
                                (-1 = dropped: foreign uid / padding);
                                K = bucketed max items/event
    x2d    : (B_pad, K) f32     the item's value

plus the same scalar-prefetched ``rows``/``blks`` routing as the segmented
gather, against the state's device-resident block table ``src2d``.  Output
tile (s, q) is produced by a compare-accumulate over the K items of event
``rows[s]``:

    out[s, q] = x2d[rows[s], j]   where  slot2d[rows[s], j] == src2d[blks[s], q]

i.e. the scatter (dense build) and the gather (mapping) cancel into one
K-term select.  K is tiny (items per event, sublane-bucketed), so the loop
is statically unrolled -- no scatter, no atomics, no dense (B, n_in_pad)
intermediate in HBM, and the only per-chunk HBM traffic is
O(B*K + S*W) instead of O(B*n_in_pad + S*W).

Duplicate slots within one event resolve last-writer-wins (ascending j),
exactly the numpy fancy-index semantics of the host scatter
(``vals[r, c] = ...``), which keeps this path bit-exact with the host
``_densify_chunk`` + segmented-gather oracle.

Grid: (S_pad / block_s, W / block_n); ``rows``/``blks`` are scalar-prefetch
operands (SMEM), ``src2d`` contributes one lane tile of all blocks per grid
cell, and the item tables ride whole in VMEM, single-buffered (they are
O(chunk) small).  Each cell first copies its block rows and event rows into
VMEM scratch one routing scalar at a time
(:func:`repro.kernels.tiling.gather_rows`) -- Mosaic has no vector row
gather -- then runs the select chain on the gathered tiles.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiling import LANE, SUBLANE, gather_rows, round_up, vmem_limit

__all__ = ["densify_map", "densify_map_shard"]


def _kernel(
    rows_ref: Any,
    blks_ref: Any,
    src2d_ref: Any,
    slot_ref: Any,
    x_ref: Any,
    out_v_ref: Any,
    out_m_ref: Any,
    src_buf: Any,
    es_buf: Any,
    ex_buf: Any,
    *,
    block_s: int,
    k: int,
    fill: float,
) -> None:
    base = pl.program_id(0) * block_s
    gather_rows(blks_ref, base, block_s, [(src_buf, src2d_ref)])
    gather_rows(rows_ref, base, block_s, [(es_buf, slot_ref), (ex_buf, x_ref)])
    src = src_buf[...]  # (block_s, block_n)
    es = es_buf[...]  # (block_s, K_pad)
    ex = ex_buf[...]  # (block_s, K_pad)
    valid = src >= 0
    # compare-accumulate over the K items of each output row's event: item j
    # lands in every output slot whose src equals its payload slot.  -1
    # (dropped item) can never match a valid src entry, so no extra mask.
    acc = jnp.full(src.shape, fill, ex.dtype)
    hit = jnp.zeros(src.shape, jnp.bool_)
    for j in range(k):  # K is tiny and static: unrolled select chain
        m = valid & (src == es[:, j : j + 1])
        acc = jnp.where(m, ex[:, j : j + 1], acc)
        hit = hit | m
    out_v_ref[...] = acc
    out_m_ref[...] = hit.astype(jnp.int8)


@functools.partial(
    jax.jit, static_argnames=("block_s", "block_n", "fill", "interpret")
)
def densify_map(
    slot2d: jax.Array,
    x2d: jax.Array,
    rows: jax.Array,
    blks: jax.Array,
    src2d: jax.Array,
    *,
    block_s: int = 256,
    block_n: int = LANE,
    fill: float = 0.0,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Densify + map every (event, block) pair of a chunk in one launch.

    slot2d/x2d: (B, K) resolved columnar items (slot -1 = dropped), rows/
    blks: (S,) int32 routing, src2d: (n_blocks_pad, W) int32 block table
    with n_blocks_pad % 8 == 0 and W % block_n == 0.  Returns ((S, W)
    values, (S, W) int8 mask); output row ``s`` is the densified event
    ``rows[s]`` mapped through block ``blks[s]``.
    """
    b, k = slot2d.shape
    (s,) = rows.shape
    n_blocks_pad, w = src2d.shape
    if w % block_n:
        raise ValueError(f"W={w} not a multiple of block_n={block_n}")
    if n_blocks_pad % SUBLANE:
        raise ValueError(f"n_blocks_pad={n_blocks_pad} not a multiple of {SUBLANE}")
    if x2d.dtype.itemsize != 4:
        raise ValueError(f"x2d must be a 32-bit dtype, got {x2d.dtype}")

    # pad to tile boundaries (callers bucket shapes, so these usually no-op);
    # the item lane axis pads to the vector width -- the padded lanes carry
    # slot -1 and are never read by the unrolled loop (it runs true-K only)
    bs = round_up(min(block_s, round_up(s, SUBLANE)), SUBLANE)
    s_pad = round_up(s, bs)
    b_pad = round_up(b, SUBLANE)
    k_pad = round_up(k, LANE)
    if s_pad != s:
        rows = jnp.pad(rows, (0, s_pad - s))
        blks = jnp.pad(blks, (0, s_pad - s))
    if b_pad != b or k_pad != k:
        slot2d = jnp.pad(
            slot2d, ((0, b_pad - b), (0, k_pad - k)), constant_values=-1
        )
        x2d = jnp.pad(x2d, ((0, b_pad - b), (0, k_pad - k)))

    grid = (s_pad // bs, w // block_n)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    out_v, out_m = pl.pallas_call(
        functools.partial(_kernel, block_s=bs, k=k, fill=fill),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((n_blocks_pad, block_n), lambda i, j, rows, blks: (0, j)),
                whole,
                whole,
            ],
            out_specs=[
                pl.BlockSpec((bs, block_n), lambda i, j, rows, blks: (i, j)),
                pl.BlockSpec((bs, block_n), lambda i, j, rows, blks: (i, j)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bs, block_n), jnp.int32),
                pltpu.VMEM((bs, k_pad), jnp.int32),
                pltpu.VMEM((bs, k_pad), x2d.dtype),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((s_pad, w), x2d.dtype),
            jax.ShapeDtypeStruct((s_pad, w), jnp.int8),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(
                slot2d.nbytes, x2d.nbytes, 2 * n_blocks_pad * block_n * 4
            )
        ),
        interpret=interpret,
    )(rows, blks, src2d, slot2d, x2d)
    return out_v[:s], out_m[:s]


def densify_map_shard(
    slot2d: jax.Array,
    x2d: jax.Array,
    rows: jax.Array,
    blks: jax.Array,
    src3d: jax.Array,
    *,
    block_s: int = 256,
    block_n: int = LANE,
    fill: float = 0.0,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Per-shard body of the sharded device-densify path -- runs INSIDE
    shard_map.  Same layout contract as
    :func:`repro.kernels.segmented_gather.segmented_gather_shard`: this body
    sees rows/blks (1, S_loc) and src3d (1, n_blocks_pad_loc, W) -- its own
    slice of the block table -- while the resolved item tables stay
    replicated.  The leading shard axis is re-added so the stacked
    (n_shards, S_loc, W) output can be all-gathered by the caller."""
    out_v, out_m = densify_map(
        slot2d,
        x2d,
        rows[0],
        blks[0],
        src3d[0],
        block_s=block_s,
        block_n=block_n,
        fill=fill,
        interpret=interpret,
    )
    return out_v[None], out_m[None]
