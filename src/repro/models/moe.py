"""Mixture-of-Experts with DMM-style dispatch.

The MoE dispatch operator *is* the paper's mapping matrix, live in the model:
a huge block-structured 0/1 operator (tokens x expert-capacity slots) that is
absurd to materialise and cheap as compacted index sets.  Three
implementations, selected by ``cfg.moe_impl``:

  dense  -- scatter/gather dispatch per batch row ("group"): slot positions
            from a cumsum over the expert one-hot, token dropping beyond
            capacity.  The portable baseline; shards over (data: batch,
            model: experts) under jit.
  dmm    -- the paper's Algorithm-6 analogue on a flat token axis: compacted
            index vectors (argsort by expert) + masked gathers, single-shard
            semantics; the optimized data layout for one device/model shard.
  ep     -- shard_map expert parallelism: local routing, all_to_all over the
            ``model`` axis to the expert owners, grouped FFN, all_to_all
            back.  The production path at pod scale.

All three are allclose (up to token-drop tie-breaking, which is made
deterministic by stable sorts) and are property-tested against each other.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .layers import trunc_normal

__all__ = ["moe_params", "moe_apply", "router_aux_loss"]


def moe_params(key, cfg: ModelConfig) -> Dict:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    return {
        "router": trunc_normal(ks[0], (D, E), 1.0, jnp.float32),  # router in f32
        "w_in": trunc_normal(ks[1], (E, D, F), 1.0, cfg.pdtype),
        "w_gate": trunc_normal(ks[2], (E, D, F), 1.0, cfg.pdtype),
        "w_out": trunc_normal(ks[3], (E, F, D), 1.0, cfg.pdtype),
    }


def _route(p: Dict, x: jax.Array, cfg: ModelConfig):
    """x: (..., D) -> (gates (..., k), experts (..., k) int32, probs (..., E))."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return gates, experts.astype(jnp.int32), probs


def router_aux_loss(probs: jax.Array, experts: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Switch-style load-balance loss: E * <f_e * p_e>."""
    E = cfg.n_experts
    onehot = jax.nn.one_hot(experts, E, dtype=jnp.float32)  # (..., k, E)
    frac = jnp.mean(jnp.sum(onehot, axis=-2).reshape(-1, E), axis=0) / cfg.top_k
    mean_p = jnp.mean(probs.reshape(-1, E), axis=0)
    return E * jnp.sum(frac * mean_p)


def _expert_ffn(p: Dict, h: jax.Array, cfg: ModelConfig, sh=None) -> jax.Array:
    """h: (E, C, D) -> (E, C, D) through each expert's SwiGLU."""
    cd = cfg.cdtype
    a = jnp.einsum("ecd,edf->ecf", h, p["w_in"].astype(cd))
    g = jnp.einsum("ecd,edf->ecf", h, p["w_gate"].astype(cd))
    a = jax.nn.silu(g.astype(jnp.float32)).astype(cd) * a
    if sh is not None:
        a = sh.act_expert_ff(a)
    return jnp.einsum("ecf,efd->ecd", a, p["w_out"].astype(cd))


# ---------------------------------------------------------------------------
# dense: scatter/gather per batch-row group (jit/GSPMD path)
# ---------------------------------------------------------------------------


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    c = int(tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(4, -(-c // 4) * 4)


def _dispatch_indices(experts: jax.Array, E: int, C: int):
    """experts: (T, k) -> (slot (T, k), keep (T, k)) with per-expert cumsum
    positions; tokens beyond an expert's capacity are dropped (keep=0).
    Deterministic: earlier tokens win (paper's 'there cannot be two data
    containers at the same place')."""
    T, k = experts.shape
    flat = experts.reshape(T * k)
    onehot = jax.nn.one_hot(flat, E, dtype=jnp.int32)  # (T*k, E)
    pos = jnp.cumsum(onehot, axis=0) - 1  # position within expert
    slot = jnp.take_along_axis(pos, flat[:, None], axis=1)[:, 0]
    keep = slot < C
    return slot.reshape(T, k), keep.reshape(T, k)


def _moe_group(p: Dict, x: jax.Array, cfg: ModelConfig, sh=None) -> jax.Array:
    """One group's MoE: x (T, D) -> (T, D).  vmapped over the batch axis."""
    T, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    C = _capacity(T, cfg)
    gates, experts, probs = _route(p, x, cfg)
    slot, keep = _dispatch_indices(experts, E, C)
    # scatter tokens into (E, C, D)
    buf = jnp.zeros((E, C, D), cfg.cdtype)
    tok = jnp.broadcast_to(jnp.arange(T)[:, None], (T, k)).reshape(-1)
    e_flat = experts.reshape(-1)
    s_flat = jnp.where(keep.reshape(-1), slot.reshape(-1), C)  # C = overflow bin
    buf = jnp.pad(buf, ((0, 0), (0, 1), (0, 0)))  # overflow slot
    buf = buf.at[e_flat, s_flat].add(x[tok].astype(cfg.cdtype), mode="drop")
    buf = buf[:, :C]
    out_e = _expert_ffn(p, buf, cfg, sh)  # (E, C, D)
    # gather back, weighted by gates
    got = out_e[e_flat, jnp.minimum(s_flat, C - 1)]  # (T*k, D)
    got = got * (keep.reshape(-1, 1) * gates.reshape(-1, 1)).astype(got.dtype)
    out = jnp.zeros((T, D), cfg.cdtype).at[tok].add(got)
    return out, probs, experts


# ---------------------------------------------------------------------------
# dmm: compacted index-set dispatch (Algorithm-6 analogue, flat token axis)
# ---------------------------------------------------------------------------


def _moe_dmm(p: Dict, x: jax.Array, cfg: ModelConfig, sh=None):
    """Sort-based dispatch: the mapping 'matrix' never exists, only its
    compacted index sets -- token order sorted by expert id, segment
    boundaries from a bincount.  (T, D) -> (T, D)."""
    T, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    C = _capacity(T, cfg)
    gates, experts, probs = _route(p, x, cfg)
    flat_e = experts.reshape(-1)  # (T*k,)
    order = jnp.argsort(flat_e, stable=True)  # compacted index set
    tok = jnp.broadcast_to(jnp.arange(T)[:, None], (T, k)).reshape(-1)[order]
    e_sorted = flat_e[order]
    # position within expert segment
    seg_start = jnp.searchsorted(e_sorted, jnp.arange(E))
    pos_in_e = jnp.arange(T * k) - seg_start[e_sorted]
    keep = pos_in_e < C
    slot = e_sorted * C + jnp.minimum(pos_in_e, C - 1)
    # gather payload through the compacted set (the DMM apply)
    buf = jnp.zeros((E * C, D), cfg.cdtype)
    buf = buf.at[jnp.where(keep, slot, E * C - 1)].add(
        jnp.where(keep[:, None], x[tok].astype(cfg.cdtype), 0)
    )
    out_e = _expert_ffn(p, buf.reshape(E, C, D), cfg, sh).reshape(E * C, D)
    got = out_e[slot] * keep[:, None]
    gate_sorted = gates.reshape(-1)[order]
    out = jnp.zeros((T, D), cfg.cdtype).at[tok].add(got * gate_sorted[:, None].astype(got.dtype))
    return out, probs, experts


# ---------------------------------------------------------------------------
# ep: shard_map all-to-all expert parallelism (production path)
# ---------------------------------------------------------------------------


def _moe_ep_local(p_local: Dict, x: jax.Array, cfg: ModelConfig, axis: str):
    """Runs *inside* shard_map.  x: (T_loc, D) local tokens; p_local holds
    this shard's E_loc experts.  Experts are sharded over ``axis``."""
    T, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    n_shards = jax.lax.axis_size(axis)
    E_loc = E // n_shards
    C = _capacity(T, cfg)  # capacity per (expert, source shard)
    # route locally against the full router (router weights replicated)
    gates, experts, probs = _route({"router": p_local["router"]}, x, cfg)
    slot, keep = _dispatch_indices(experts, E, C)
    buf = jnp.zeros((E, C + 1, D), cfg.cdtype)
    tok = jnp.broadcast_to(jnp.arange(T)[:, None], (T, k)).reshape(-1)
    e_flat = experts.reshape(-1)
    s_flat = jnp.where(keep.reshape(-1), slot.reshape(-1), C)
    buf = buf.at[e_flat, s_flat].add(x[tok].astype(cfg.cdtype), mode="drop")
    buf = buf[:, :C]  # (E, C, D) destined for expert owners
    # all_to_all: split expert axis across shards, concat source shards
    recv = jax.lax.all_to_all(
        buf.reshape(n_shards, E_loc, C, D), axis, split_axis=0, concat_axis=0, tiled=False
    )  # (n_shards, E_loc, C, D): peers' tokens for my experts
    recv = recv.transpose(1, 0, 2, 3).reshape(E_loc, n_shards * C, D)
    ffn_p = {k_: p_local[k_] for k_ in ("w_in", "w_gate", "w_out")}
    out_e = _expert_ffn(ffn_p, recv, cfg)  # (E_loc, n_shards*C, D)
    # send results back
    send = out_e.reshape(E_loc, n_shards, C, D).transpose(1, 0, 2, 3)
    back = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0, tiled=False)
    back = back.reshape(E, C, D)  # my tokens' expert outputs, original layout
    got = back[e_flat, jnp.minimum(s_flat, C - 1)]
    got = got * (keep.reshape(-1, 1) * gates.reshape(-1, 1)).astype(got.dtype)
    out = jnp.zeros((T, D), cfg.cdtype).at[tok].add(got)
    return out, probs, experts


def moe_apply(
    p: Dict,
    x: jax.Array,  # (B, S, D)
    cfg: ModelConfig,
    sh=None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (out (B, S, D), aux_loss scalar)."""
    B, S, D = x.shape
    impl = cfg.moe_impl
    if impl == "ep" and sh is not None and sh.mesh is not None:
        mesh = sh.mesh
        axis = sh.model_axis
        dp_axes = sh.data_axes  # ('data',) or ('pod', 'data')
        def local(p_local, xl):
            xl2 = xl.reshape(-1, D)
            out, probs, experts = _moe_ep_local(p_local, xl2, cfg, axis)
            aux = router_aux_loss(probs, experts, cfg)
            return out.reshape(xl.shape), aux

        p_spec = {
            "router": P(),
            "w_in": P(axis, None, None),
            "w_gate": P(axis, None, None),
            "w_out": P(axis, None, None),
        }
        out, aux = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(p_spec, P(dp_axes, None, None)),
            out_specs=(P(dp_axes, None, None), P()),
            check_vma=False,
        )(p, x)
        return out, jnp.mean(aux)
    if impl == "dmm":
        out, probs, experts = _moe_dmm(p, x.reshape(-1, D), cfg, sh)
        return out.reshape(B, S, D), router_aux_loss(probs, experts, cfg)
    # dense: group per batch row, vmapped
    fn = functools.partial(_moe_group, p, cfg=cfg, sh=sh)
    out, probs, experts = jax.vmap(lambda xb: _moe_group(p, xb, cfg, sh))(x)
    return out, router_aux_loss(probs, experts, cfg)
