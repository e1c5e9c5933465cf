"""Pallas TPU kernel for the DMM mapping: batched masked gather.

This is the device realisation of paper Algorithm 6.  The compacted block is
an index vector ``src (N_out,)`` (-1 = filtered/null); applying it to a batch
of dense messages is a gather along the attribute (lane) axis.

TPU adaptation (vs. the paper's JVM hashmap lookups):

  * ``src`` rides as a (1, N_out) VMEM row, tiled by ``block_n`` lanes next
    to the payload tiles (the TPU analogue of the paper's Caffeine-cached
    O(1) column lookup).  SMEM serves only scalar loads, so an index
    *vector* cannot come from a scalar-prefetch operand.
  * The batch axis is tiled to ``block_b`` sublane rows; the output attribute
    axis is tiled to ``block_n`` lanes (multiples of 128).  Each grid cell
    reads the *full* input row (mapping widths are small -- schema versions
    have O(10..1000) attributes, so a row tile fits VMEM comfortably) and
    gathers one output tile with a lane-axis gather
    (:func:`repro.kernels.tiling.lane_gather`).  Payloads narrower than 32
    bits and the int8 mask are widened on device first: the lane gather
    moves 32-bit lanes.
  * The paper's "null object" is the validity mask: ``mask`` rides through
    the same gather and pad slots (src = -1) are forced invalid.

Roofline: the gather moves O(B * (N_in + N_out)) bytes and does no FLOPs --
it is memory-bound by construction, which is exactly the paper's claim that
the DMM turns a matrix operator into data movement proportional to the
*dense* content.  The baseline one-hot matmul
(:func:`repro.core.dmm_jax.apply_onehot`) moves the same bytes but burns
O(B * N_in * N_out) FLOPs.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tiling import LANE, SUBLANE, lane_gather, round_up

__all__ = ["masked_gather"]


def _kernel(
    src_ref: Any,
    vals_ref: Any,
    mask_ref: Any,
    out_v_ref: Any,
    out_m_ref: Any,
    *,
    fill: float,
) -> None:
    idx = src_ref[...]  # (1, block_n) int32
    valid = idx >= 0
    bb = vals_ref.shape[0]
    safe = jnp.broadcast_to(jnp.where(valid, idx, 0), (bb, idx.shape[1]))
    g_v = lane_gather(vals_ref[...], safe)
    g_m = lane_gather(mask_ref[...], safe)
    ok = (g_m != 0) & valid
    out_v_ref[...] = jnp.where(ok, g_v, jnp.asarray(fill, g_v.dtype))
    out_m_ref[...] = ok.astype(jnp.int8)


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_n", "fill", "interpret")
)
def masked_gather(
    values: jax.Array,
    mask: jax.Array,
    src: jax.Array,
    *,
    block_b: int = 256,
    block_n: int = LANE,
    fill: float = 0.0,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Apply a compacted DMM block to a batch of dense messages.

    values: (B, N_in), mask: (B, N_in) int8/bool, src: (N_out,) int32.
    N_out must be a multiple of ``block_n``; B is padded internally to a
    multiple of ``block_b``.  Returns ((B, N_out) values, (B, N_out) int8).
    """
    b, n_in = values.shape
    (n_out,) = src.shape
    if n_out % block_n:
        raise ValueError(f"N_out={n_out} not a multiple of block_n={block_n}")
    dtype = values.dtype
    values = values.astype(jnp.float32) if dtype.itemsize < 4 else values
    mask = mask.astype(jnp.int32)

    # pad batch to the sublane tile and n_in to the lane tile
    bb = round_up(min(block_b, max(SUBLANE, b)), SUBLANE)
    b_pad = round_up(b, bb)
    n_in_pad = round_up(n_in, LANE)
    if b_pad != b or n_in_pad != n_in:
        values = jnp.pad(values, ((0, b_pad - b), (0, n_in_pad - n_in)))
        mask = jnp.pad(mask, ((0, b_pad - b), (0, n_in_pad - n_in)))

    grid = (b_pad // bb, n_out // block_n)
    out_v, out_m = pl.pallas_call(
        functools.partial(_kernel, fill=fill),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((bb, n_in_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, n_in_pad), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((bb, block_n), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b_pad, n_out), values.dtype),
            jax.ShapeDtypeStruct((b_pad, n_out), jnp.int8),
        ],
        interpret=interpret,
    )(src.reshape(1, n_out), values, mask)
    return out_v[:b].astype(dtype), out_m[:b]
