"""Pallas TPU kernel: MoE combine as a tiled MXU contraction.

The MoE data plane is the model-side instantiation of the paper's mapping
matrix: the dispatch/combine tensors are huge, block-structured 0/1 (or
router-weighted) operators.  The *combine* step

    out[t, d] = sum_{e,c} combine[t, e, c] * expert_out[e, c, d]

is a (T, E*C) x (E*C, D) matmul whose left operand is extremely sparse
(top-k non-zeros per row) -- the exact shape of problem the DMM attacks.
This kernel is the dense-operator formulation, tiled for VMEM/MXU; the
DMM-style alternative (sort + gather on compacted index sets) lives in
``repro.models.moe`` and the A/B is benchmarked in benchmarks/bench_moe.py.

Grid: (T/bt, D/bd, EC/bk) with an f32 VMEM accumulator; K is innermost so
the output tile stays resident while expert tiles stream through.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["moe_combine"]


def _kernel(c_ref: Any, e_ref: Any, o_ref: Any, acc_ref: Any, *, nk: int) -> None:
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot(
        c_ref[...].astype(jnp.float32),
        e_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_t", "block_d", "block_k", "interpret")
)
def moe_combine(
    combine: jax.Array,  # (T, E, C)
    expert_out: jax.Array,  # (E, C, D)
    *,
    block_t: int = 256,
    block_d: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    t, e, c = combine.shape
    e2, c2, d = expert_out.shape
    assert (e, c) == (e2, c2), (combine.shape, expert_out.shape)
    ec = e * c
    cmb = combine.reshape(t, ec)
    exp = expert_out.reshape(ec, d)

    bt = min(block_t, t)
    bd = min(block_d, d)
    bk = min(block_k, ec)
    # pad every axis to its tile
    tp, dp, kp = (-(-t // bt) * bt, -(-d // bd) * bd, -(-ec // bk) * bk)
    if (tp, kp) != (t, ec):
        cmb = jnp.pad(cmb, ((0, tp - t), (0, kp - ec)))
    if (kp, dp) != (ec, d):
        exp = jnp.pad(exp, ((0, kp - ec), (0, dp - d)))
    nk = kp // bk
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(tp // bt, dp // bd, nk),
        in_specs=[
            pl.BlockSpec((bt, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bd), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bt, bd), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((tp, dp), expert_out.dtype),
        scratch_shapes=[pltpu.VMEM((bt, bd), jnp.float32)],
        interpret=interpret,
    )(cmb, exp)
    return out[:t, :d]
