"""Pallas TPU kernel for the *fused* DMM mapping: one launch per event chunk.

:mod:`repro.kernels.masked_gather` applies ONE compacted block to a batch --
so a heterogeneous CDC chunk costs one device dispatch per block per
(schema, version) group.  This kernel generalises it to the whole chunk:
every (event, block) mapping path of the chunk becomes one *output row* of a
single gather, so the dispatch count per chunk is 1 regardless of how many
blocks or columns the chunk touches (the fused-engine contract of
``METLApp.consume``).

Device layout (built once per state by :class:`repro.core.dmm_jax.FusedDMM`):

    src2d   : (n_blocks_pad, W) int32 -- every compacted block's index vector,
              one row per block, right-padded with -1 to the uniform output
              width W = max(n_out_pad).  Device-resident across chunks.

Per-chunk operands (host-built, bucketed so jit caches hit):

    values  : (B_pad, n_in_pad)  dense payloads, one row per mappable event,
              in the event's own (o, v) attribute order
    mask    : (B_pad, n_in_pad)  int8 validity (the paper's nad_p)
    rows    : (S_pad,) int32     output row s reads event row rows[s]
    blks    : (S_pad,) int32     ... through block src2d[blks[s]]

``rows``/``blks`` are *scalar-prefetch* operands: they land in SMEM before
the grid body runs, so the per-tile routing is known ahead of the payload
tiles streaming HBM->VMEM.  ``src2d`` stays in VMEM (it can be MBs for big
states -- too large for SMEM) and only the lane tile ``j`` of all blocks is
resident per grid cell.

Grid: (S_pad / block_s, W / block_n).  Each cell gathers a (block_s, block_n)
output tile.  The payload and its mask sit whole in VMEM (chunk batches are
O(1000s) rows of 128 lanes), single-buffered.  Mosaic has no vector row
gather, so the tile's block rows and event rows are copied one routing
scalar at a time into VMEM scratch (:func:`repro.kernels.tiling.gather_rows`),
then a lane-axis gather (:func:`repro.kernels.tiling.lane_gather`) maps them
exactly like the single-block kernel.  Pad slots (src = -1, or padding
rows) come out invalid.  The mask travels as int8 and widens to int32 on
device: dynamic single-row loads need 32-bit rows.

Roofline: same O(S * (N_in + W)) bytes and zero FLOPs as the per-block path,
but amortised into one kernel -- the win is dispatch/launch overhead and the
Python loop around it, which dominates at ETL chunk sizes.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiling import LANE, SUBLANE, gather_rows, lane_gather, round_up, vmem_limit

__all__ = ["segmented_gather", "segmented_gather_shard"]


def _kernel(
    rows_ref: Any,
    blks_ref: Any,
    src2d_ref: Any,
    vals_ref: Any,
    mask_ref: Any,
    out_v_ref: Any,
    out_m_ref: Any,
    src_buf: Any,
    v_buf: Any,
    m_buf: Any,
    *,
    block_s: int,
    fill: float,
) -> None:
    base = pl.program_id(0) * block_s
    gather_rows(blks_ref, base, block_s, [(src_buf, src2d_ref)])
    gather_rows(rows_ref, base, block_s, [(v_buf, vals_ref), (m_buf, mask_ref)])
    src = src_buf[...]  # (block_s, block_n)
    valid = src >= 0
    safe = jnp.where(valid, src, 0)
    g_v = lane_gather(v_buf[...], safe)
    g_m = lane_gather(m_buf[...], safe)
    ok = (g_m != 0) & valid
    out_v_ref[...] = jnp.where(ok, g_v, jnp.asarray(fill, g_v.dtype))
    out_m_ref[...] = ok.astype(jnp.int8)


@functools.partial(
    jax.jit, static_argnames=("block_s", "block_n", "fill", "interpret")
)
def segmented_gather(
    values: jax.Array,
    mask: jax.Array,
    rows: jax.Array,
    blks: jax.Array,
    src2d: jax.Array,
    *,
    block_s: int = 256,
    block_n: int = LANE,
    fill: float = 0.0,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Map every (event, block) pair of a chunk in one kernel launch.

    values: (B, N_in) 32-bit, mask: (B, N_in), rows/blks: (S,) int32,
    src2d: (n_blocks_pad, W) int32 with n_blocks_pad % 8 == 0 and
    W % block_n == 0.  Returns ((S, W) values, (S, W) int8 mask); output row
    ``s`` is event row ``rows[s]`` mapped through block ``blks[s]``.
    """
    b, n_in = values.shape
    (s,) = rows.shape
    n_blocks_pad, w = src2d.shape
    if w % block_n:
        raise ValueError(f"W={w} not a multiple of block_n={block_n}")
    if n_blocks_pad % SUBLANE:
        raise ValueError(f"n_blocks_pad={n_blocks_pad} not a multiple of {SUBLANE}")
    if values.dtype.itemsize != 4:
        raise ValueError(f"values must be a 32-bit dtype, got {values.dtype}")
    mask = mask.astype(jnp.int32)

    # pad the chunk to tile boundaries (callers bucket to powers of two, so
    # these pads are usually no-ops and the jit cache keys recur)
    bs = round_up(min(block_s, round_up(s, SUBLANE)), SUBLANE)
    s_pad = round_up(s, bs)
    b_pad = round_up(b, SUBLANE)
    n_in_pad = round_up(n_in, LANE)
    if s_pad != s:
        rows = jnp.pad(rows, (0, s_pad - s))
        blks = jnp.pad(blks, (0, s_pad - s))
    if b_pad != b or n_in_pad != n_in:
        values = jnp.pad(values, ((0, b_pad - b), (0, n_in_pad - n_in)))
        mask = jnp.pad(mask, ((0, b_pad - b), (0, n_in_pad - n_in)))

    grid = (s_pad // bs, w // block_n)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    out_v, out_m = pl.pallas_call(
        functools.partial(_kernel, block_s=bs, fill=fill),
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
                pltpu.VMEM((bs, n_in_pad), values.dtype),
                pltpu.VMEM((bs, n_in_pad), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((s_pad, w), values.dtype),
            jax.ShapeDtypeStruct((s_pad, w), jnp.int8),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(
                values.nbytes, mask.nbytes, 2 * n_blocks_pad * block_n * 4
            )
        ),
        interpret=interpret,
    )(rows, blks, src2d, values, mask)
    return out_v[:s], out_m[:s]


def segmented_gather_shard(
    values: jax.Array,
    mask: jax.Array,
    rows: jax.Array,
    blks: jax.Array,
    src3d: jax.Array,
    *,
    block_s: int = 256,
    block_n: int = LANE,
    fill: float = 0.0,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Per-shard body of the *sharded* fused engine -- runs INSIDE shard_map.

    The sharded dispatcher (:func:`repro.kernels.ops.dmm_apply_sharded`)
    partitions ``rows``/``blks``/``src3d`` over the mesh ``data`` axis, so
    this body sees a leading shard axis of size 1: rows/blks (1, S_loc),
    src3d (1, n_blocks_pad_loc, W) -- this shard's slice of the block table
    -- while values/mask stay replicated (every shard reads the full chunk
    payload).  One :func:`segmented_gather` launch per shard per chunk; the
    leading axis is re-added so the stacked (n_shards, S_loc, W) output can
    be all-gathered by the caller before row emission.
    """
    out_v, out_m = segmented_gather(
        values,
        mask,
        rows[0],
        blks[0],
        src3d[0],
        block_s=block_s,
        block_n=block_n,
        fill=fill,
        interpret=interpret,
    )
    return out_v[None], out_m[None]
