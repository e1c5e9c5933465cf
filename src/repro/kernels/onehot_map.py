"""Baseline Pallas kernel: the mapping applied as an explicit 0/1 matmul.

This is the *pre-DMM* formulation (paper Algorithm 1 / "use the matrix
directly"): materialise the mapping block as a one-hot matrix and push the
payload through the MXU.  It exists so the benchmark harness can report the
paper's A/B -- compacted gather vs. matrix operator -- at the kernel level.

The one-hot matrix is built on the fly inside the kernel from the same
``src`` row tile as the gather kernel (building it in HBM would hand the
gather version a free win on bytes); the MXU contraction is the cost
difference.  It is built transposed, (N_in, block_n), so ``src`` stays a
lane row and the contraction is a plain (bb, N_in) @ (N_in, block_n).  The
f32 contraction runs at ``HIGHEST`` precision: with one nonzero term per
output, that reproduces the payload exactly.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tiling import LANE, SUBLANE, round_up

__all__ = ["onehot_map"]


def _kernel(
    src_ref: Any,
    vals_ref: Any,
    mask_ref: Any,
    out_v_ref: Any,
    out_m_ref: Any,
    *,
    fill: float,
) -> None:
    idx = src_ref[...]  # (1, block_n); src = -1 columns are all-zero
    vals = vals_ref[...].astype(jnp.float32)  # (bb, n_in_pad)
    mask = mask_ref[...].astype(jnp.float32)
    n_in_pad, block_n = vals.shape[1], idx.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (n_in_pad, block_n), 0)
    m = (rows == idx).astype(jnp.float32)  # (n_in_pad, block_n) one-hot
    hi = jax.lax.Precision.HIGHEST
    out_v = jnp.dot(vals, m, precision=hi, preferred_element_type=jnp.float32)
    out_m = jnp.dot(mask, m, precision=hi, preferred_element_type=jnp.float32) > 0.5
    out_v_ref[...] = jnp.where(out_m, out_v, fill).astype(out_v_ref.dtype)
    out_m_ref[...] = out_m.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("block_b", "block_n", "fill", "interpret"))
def onehot_map(
    values: jax.Array,
    mask: jax.Array,
    src: jax.Array,
    *,
    block_b: int = 256,
    block_n: int = LANE,
    fill: float = 0.0,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Same contract as :func:`repro.kernels.masked_gather.masked_gather`."""
    b, n_in = values.shape
    (n_out,) = src.shape
    if n_out % block_n:
        raise ValueError(f"N_out={n_out} not a multiple of block_n={block_n}")
    mask = mask.astype(jnp.int8)
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
    return out_v[:b], out_m[:b]
