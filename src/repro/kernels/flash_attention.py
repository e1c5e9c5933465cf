"""Pallas TPU flash attention (forward): online softmax over KV tiles.

Beyond-paper optimization for the zoo's compute hot-spot.  The jnp chunked
formulation (`models/attention._sdpa_chunked`) already avoids materialising
(S, T) scores at the XLA level; this kernel is the TPU-native version with
explicit VMEM tiling:

  * grid (N, S/bq, T/bk) -- the KV axis is innermost, so the f32
    accumulator / running max / running denominator scratch persists across
    the sequential KV tiles of one Q tile (TPU grids execute the last axis
    sequentially);
  * Q/K/V tiles live in VMEM; block sizes default to (bq, hd) = (256, 128)
    and bk = 512, keeping the working set ~1.5 MB << 128 MB VMEM while the
    MXU sees (256x128)x(128x512) contractions;
  * GQA: the kernel receives an ``n_rep`` so KV rows are shared by groups
    of query heads through the BlockSpec index map (no KV replication in
    HBM);
  * causal masking by absolute tile offsets; fully-masked tiles still
    iterate but skip the matmul through ``pl.when``.

Validated against :func:`repro.kernels.ref.attention_ref` in interpret mode
(tests/test_kernels_flash.py); the model-side numerics twin is
``_sdpa_chunked`` which is allclose-tested against dense attention.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

NEG_INF = -1e30


def _kernel(
    q_ref: Any, k_ref: Any, v_ref: Any, o_ref: Any,
    acc_ref: Any, m_ref: Any, l_ref: Any,
    *, bq: int, bk: int, nk: int, scale: float, causal: bool,
) -> None:
    kj = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * bq
    k_start = kj * bk
    # tile is fully masked iff the earliest query < the last key it must see
    run = (not causal) or (q_start + bq - 1 >= k_start)

    @pl.when(run)
    def _tile():
        q = q_ref[0].astype(jnp.float32)  # (bq, hd)
        k = k_ref[0].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_start
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + k_start
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "n_rep", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (N, S, hd)  N = B * H query heads
    k: jax.Array,  # (Nk, T, hd) Nk = B * KV heads; N = Nk * n_rep
    v: jax.Array,
    *,
    causal: bool = True,
    n_rep: int = 1,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    n, s, hd = q.shape
    nk_heads, t, _ = k.shape
    assert n == nk_heads * n_rep, (q.shape, k.shape, n_rep)
    bq = min(block_q, s)
    bk = min(block_k, t)
    # pad S/T to the tile sizes (pads are masked: extra keys get NEG_INF via
    # causal; for non-causal we must not pad T)
    s_pad = -(-s // bq) * bq
    t_pad = -(-t // bk) * bk
    if t_pad != t and not causal:
        raise ValueError("non-causal flash requires T % block_k == 0")
    if s_pad != s:
        q = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0)))
    if t_pad != t:
        k = jnp.pad(k, ((0, 0), (0, t_pad - t), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, t_pad - t), (0, 0)))
    nq_t, nk_t = s_pad // bq, t_pad // bk
    scale = 1.0 / math.sqrt(hd)

    out = pl.pallas_call(
        functools.partial(
            _kernel, bq=bq, bk=bk, nk=nk_t, scale=scale, causal=causal
        ),
        grid=(n, nq_t, nk_t),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, i, j, n_rep=n_rep: (h // n_rep, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, i, j, n_rep=n_rep: (h // n_rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, s_pad, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :s]
