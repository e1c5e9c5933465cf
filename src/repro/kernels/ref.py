"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for the per-kernel allclose sweeps in
``tests/test_kernels.py`` and are also what the model code calls on
non-TPU backends.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "masked_gather_ref",
    "segmented_gather_ref",
    "densify_map_ref",
    "moe_combine_ref",
]


def masked_gather_ref(
    values: jax.Array, mask: jax.Array, src: jax.Array, *, fill: float = 0.0
) -> Tuple[jax.Array, jax.Array]:
    """DMM mapping oracle.

    values: (B, N_in) payload, mask: (B, N_in) validity (bool or int8),
    src: (N_out,) int32 with -1 for filtered/null output slots.
    Returns (out_values (B, N_out), out_mask (B, N_out) int8).
    """
    mask = mask.astype(jnp.bool_)
    valid = src >= 0
    safe = jnp.where(valid, src, 0)
    out_v = jnp.take(values, safe, axis=1)
    out_m = jnp.take(mask, safe, axis=1) & valid[None, :]
    out_v = jnp.where(out_m, out_v, jnp.asarray(fill, values.dtype))
    return out_v, out_m.astype(jnp.int8)


def segmented_gather_ref(
    values: jax.Array,
    mask: jax.Array,
    rows: jax.Array,
    blks: jax.Array,
    src2d: jax.Array,
    *,
    fill: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """Fused DMM mapping oracle (whole chunk, all blocks, one pass).

    values: (B, N_in) payload, mask: (B, N_in) validity, rows/blks: (S,)
    int32 routing tables (output row s = event rows[s] through block blks[s]),
    src2d: (n_blocks_pad, W) int32 stacked block index vectors (-1 = null).
    Returns (out_values (S, W), out_mask (S, W) int8).
    """
    mask = mask.astype(jnp.bool_)
    src = jnp.take(src2d, blks, axis=0)  # (S, W)
    valid = src >= 0
    safe = jnp.where(valid, src, 0)
    v_rows = jnp.take(values, rows, axis=0)  # (S, N_in)
    m_rows = jnp.take(mask, rows, axis=0)
    out_v = jnp.take_along_axis(v_rows, safe, axis=1)
    out_m = jnp.take_along_axis(m_rows, safe, axis=1) & valid
    out_v = jnp.where(out_m, out_v, jnp.asarray(fill, values.dtype))
    return out_v, out_m.astype(jnp.int8)


def densify_map_ref(
    slot2d: jax.Array,
    x2d: jax.Array,
    rows: jax.Array,
    blks: jax.Array,
    src2d: jax.Array,
    *,
    fill: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """Device-densify + fused-mapping oracle (scatter-free formulation).

    slot2d: (B, K) int32 payload slot per columnar item of event row b
    (-1 = dropped/foreign/padding), x2d: (B, K) values, rows/blks: (S,)
    int32 routing, src2d: (n_blocks_pad, W) int32 block table.  Equivalent
    to scattering each event's items into a dense (B, n_in) row and
    applying :func:`segmented_gather_ref`, but the scatter and the gather
    cancel into a K-term compare-select, so no dense intermediate is built
    (XLA scatter is the slow path on every backend).  Duplicate slots
    within an event resolve last-writer-wins (ascending item index),
    matching numpy fancy-index assignment in the host densify.
    Returns (out_values (S, W), out_mask (S, W) int8).
    """
    k = slot2d.shape[1]
    src = jnp.take(src2d, blks, axis=0)  # (S, W)
    valid = src >= 0
    es = jnp.take(slot2d, rows, axis=0)  # (S, K)
    ex = jnp.take(x2d, rows, axis=0)  # (S, K)
    acc = jnp.full(src.shape, fill, x2d.dtype)
    hit = jnp.zeros(src.shape, jnp.bool_)
    for j in range(k):  # K = items/event (tiny, static): unrolled selects
        m = valid & (src == es[:, j][:, None])
        acc = jnp.where(m, ex[:, j][:, None], acc)
        hit = hit | m
    return acc, hit.astype(jnp.int8)


def moe_combine_ref(
    expert_out: jax.Array, combine: jax.Array
) -> jax.Array:
    """MoE combine oracle.

    expert_out: (E, C, D) per-expert capacity-bucketed outputs,
    combine: (T, E, C) combine weights (router prob where token t occupies
    slot (e, c), else 0).  Returns (T, D).
    """
    return jnp.einsum("tec,ecd->td", combine.astype(jnp.float32), expert_out.astype(jnp.float32)).astype(expert_out.dtype)


def attention_ref(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True, n_rep: int = 1
) -> jax.Array:
    """Dense attention oracle for the flash kernel.

    q: (N, S, hd); k, v: (N // n_rep, T, hd) -- KV heads shared by n_rep
    query heads (GQA).  Returns (N, S, hd).
    """
    import math

    n, s, hd = q.shape
    kk = jnp.repeat(k, n_rep, axis=0)
    vv = jnp.repeat(v, n_rep, axis=0)
    scores = jnp.einsum("nsh,nth->nst", q.astype(jnp.float32), kk.astype(jnp.float32))
    scores = scores / math.sqrt(hd)
    if causal:
        t = kk.shape[1]
        mask = jnp.arange(s)[:, None] >= jnp.arange(t)[None, :]
        scores = jnp.where(mask[None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("nst,nth->nsh", probs, vv.astype(jnp.float32)).astype(q.dtype)
