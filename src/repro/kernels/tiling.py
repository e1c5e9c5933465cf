"""In-kernel gather building blocks that Mosaic (the TPU Pallas compiler)
lowers, shared by the DMM mapping kernels.

Two forms of gather show up in every mapping kernel, and neither has a
direct vector realisation on the TPU:

  * a **row gather** driven by scalar-prefetched routing: output row ``r``
    of a tile reads row ``idx[base + r]`` of a VMEM table.  SMEM only
    serves scalar loads, and ``jnp.take(ref[...], idx, axis=0)`` with a
    vector of indices does not lower.  :func:`gather_rows` reads the
    routing one scalar at a time and copies each row with a dynamic
    sublane slice (``ref[pl.ds(t, 1), :]``) into a VMEM scratch tile;
  * a **lane gather** ``x[r, idx[r, q]]``: Mosaic lowers
    ``take_along_axis(axis=1)`` for one 128-lane vreg column at a time.
    :func:`lane_gather` tiles wider operands into 128-lane chunks and
    selects each output lane from the chunk its index falls in.

Both are plain traced code: they run unchanged under ``interpret=True``.
"""

from __future__ import annotations

from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["LANE", "SUBLANE", "gather_rows", "lane_gather", "round_up", "vmem_limit"]

LANE = 128
SUBLANE = 8


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def vmem_limit(*nbytes: int) -> int:
    """Scoped-VMEM budget for a kernel holding the given buffers, with
    headroom for Mosaic's own temporaries (never below the 16 MiB default,
    never above what a v5e core has)."""
    need = 2 * sum(nbytes) + (4 << 20)
    return int(min(max(need, 16 << 20), 100 << 20))


def gather_rows(
    idx_ref: Any, base: Any, n: int, copies: Sequence[Tuple[Any, Any]]
) -> None:
    """``dst[r, :] = src[idx_ref[base + r], :]`` for ``r < n``, for every
    ``(dst, src)`` pair in ``copies`` (all pairs share the routing).

    ``idx_ref`` is a scalar-prefetched SMEM ref; each index is clamped to
    its table so a stray routing entry can never address past the VMEM
    buffer."""

    def body(r: Any, carry: Any) -> Any:
        t = idx_ref[base + r]
        for dst, src in copies:
            ti = jnp.clip(t, 0, src.shape[0] - 1)
            dst[pl.ds(r, 1), :] = src[pl.ds(ti, 1), :]
        return carry

    jax.lax.fori_loop(0, n, body, 0)


def lane_gather(x: jax.Array, idx: jax.Array) -> jax.Array:
    """``out[r, q] = x[r, idx[r, q]]`` with ``0 <= idx < x.shape[1]``.

    ``x`` is (R, N) and ``idx`` (R, L), both lane multiples.  Each
    (output chunk, input chunk) pair of 128 lanes is one vreg-wide
    ``take_along_axis``; an output lane keeps the chunk its index names."""
    n, w = x.shape[1], idx.shape[1]
    outs = []
    for oc in range(w // LANE):
        ix = idx[:, oc * LANE : (oc + 1) * LANE]
        local = jnp.bitwise_and(ix, LANE - 1)
        which = jnp.right_shift(ix, LANE.bit_length() - 1)  # ix // LANE
        out = jnp.take_along_axis(x[:, :LANE], local, axis=1)
        for ic in range(1, n // LANE):
            part = jnp.take_along_axis(x[:, ic * LANE : (ic + 1) * LANE], local, axis=1)
            out = jnp.where(which == ic, part, out)
        outs.append(out)
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
