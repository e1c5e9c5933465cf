"""Jit'd public wrappers around the Pallas kernels.

Backend dispatch: ``impl="auto"`` runs the Pallas kernel, compiled by
Mosaic, on a TPU, and the pure-jnp reference (``kernels/ref.py``) only on
the CPU.  A forced kernel ``impl`` on the CPU runs the same kernel body
under the Pallas interpreter (``interpret=True``), which is how the tests
hold each kernel to its reference.

Dispatch handles, not results: every ``dmm_apply*`` returns its output
arrays WITHOUT blocking on them -- under jax's async dispatch they are
futures, and nothing in this module forces a host transfer or
``block_until_ready``.  Callers choose their own sync point (the mapping
engines' ``emit`` stage reads the arrays back with ``np.asarray``), which
is what lets the streaming pipeline overlap chunk N+1's host-side
densification with chunk N's device execution (double-buffered consume).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from . import ref as _ref
from .masked_gather import masked_gather as _masked_gather_kernel
from .segmented_gather import (
    segmented_gather as _segmented_gather_kernel,
    segmented_gather_shard as _segmented_gather_shard,
)
from .densify_map import (
    densify_map as _densify_map_kernel,
    densify_map_shard as _densify_map_shard,
)
from .onehot_map import onehot_map as _onehot_map_kernel
from .moe_combine import moe_combine as _moe_combine_kernel
from .flash_attention import flash_attention as _flash_attention_kernel

__all__ = [
    "dmm_apply",
    "dmm_apply_fused",
    "dmm_apply_sharded",
    "dmm_apply_columnar",
    "dmm_apply_columnar_sharded",
    "moe_combine",
    "attention",
    "on_tpu",
]

# Device-dispatch accounting: incremented once per dmm_apply / dmm_apply_fused
# call.  The fused-engine contract (one dispatch per consume chunk, not
# O(#blocks)) is asserted against this counter in tests and reported by
# benchmarks/bench_mapping.py.
dispatch_count = 0


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def dmm_apply(
    values: jax.Array,
    mask: jax.Array,
    src: jax.Array,
    *,
    impl: str = "auto",
    fill: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """Apply a compacted DMM block (index vector ``src``) to a payload batch.

    impl:
      "gather"        Pallas masked-gather kernel (the DMM path)
      "onehot"        Pallas one-hot matmul kernel (the baseline path)
      "ref"           pure-jnp oracle (XLA gather)
      "auto"          Pallas kernel on TPU, oracle elsewhere
    """
    global dispatch_count
    dispatch_count += 1
    if impl == "auto":
        impl = "gather" if on_tpu() else "ref"
    if impl == "ref":
        # eager on purpose: the legacy per-block engine does not bucket its
        # batch shapes, so a jit here would retrace per (group, block) shape
        return _ref.masked_gather_ref(values, mask, src, fill=fill)
    if impl == "gather":
        return _masked_gather_kernel(
            values, mask, src, fill=fill, interpret=not on_tpu()
        )
    if impl == "onehot":
        return _onehot_map_kernel(values, mask, src, fill=fill, interpret=not on_tpu())
    raise ValueError(f"unknown impl {impl!r}")


# jit'd fused oracle: the fused engine buckets its batch shapes
# (repro.core.dmm_jax.bucket_rows), so tracing happens once per shape bucket
# and every steady-state consume chunk is a cache hit.
_segmented_gather_ref_jit = jax.jit(
    _ref.segmented_gather_ref, static_argnames=("fill",)
)


def dmm_apply_fused(
    values: jax.Array,
    mask: jax.Array,
    rows: jax.Array,
    blks: jax.Array,
    src2d: jax.Array,
    *,
    impl: str = "auto",
    fill: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """Apply ALL compacted blocks touched by a chunk in one device dispatch.

    ``src2d`` is the state's stacked block table (device-resident, built once
    per state by :class:`repro.core.dmm_jax.FusedDMM`); ``rows``/``blks``
    route output row ``s`` to (event row ``rows[s]``, block ``blks[s]``).

    impl:
      "fused"  Pallas segmented-gather kernel (scalar-prefetched routing)
      "ref"    pure-jnp oracle (XLA gathers, single fused jit)
      "auto"   Pallas kernel on TPU, oracle elsewhere

    The jit cache is keyed by operand shapes: (bucketed S, bucketed B,
    n_in_pad) per chunk plus the state's (n_blocks_pad, W) table shape, so
    steady-state consume traffic never retraces.

    The returned ``(out_values, out_mask)`` are unblocked dispatch handles
    (async-dispatch futures); the caller's first host read is the sync
    point.
    """
    global dispatch_count
    dispatch_count += 1
    if impl == "auto":
        impl = "fused" if on_tpu() else "ref"
    if impl == "ref":
        return _segmented_gather_ref_jit(values, mask, rows, blks, src2d, fill=fill)
    if impl == "fused":
        return _segmented_gather_kernel(
            values, mask, rows, blks, src2d, fill=fill, interpret=not on_tpu()
        )
    raise ValueError(f"unknown impl {impl!r}")


@functools.lru_cache(maxsize=None)
def _sharded_program(
    mesh: Mesh, axis: str, impl: str, fill: float
) -> Callable[..., Tuple[jax.Array, jax.Array]]:
    """One jitted shard_map program per (mesh, axis, impl, fill).

    The cache keeps the shard_map closure identity stable so the jit cache
    underneath is keyed only on operand shapes -- same retrace discipline as
    the replicated fused path (bucketed shapes -> a handful of entries).
    """
    from jax.sharding import PartitionSpec as P

    if impl == "ref":

        def local(v, m, r, b, t):
            ov, om = _ref.segmented_gather_ref(v, m, r[0], b[0], t[0], fill=fill)
            return ov[None], om[None]

    else:
        local = functools.partial(
            _segmented_gather_shard, fill=fill, interpret=not on_tpu()
        )

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
        check_vma=False,
    )
    return jax.jit(fn)


def dmm_apply_sharded(
    values: jax.Array,
    mask: jax.Array,
    rows: jax.Array,
    blks: jax.Array,
    src3d: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "data",
    impl: str = "auto",
    fill: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """Sharded fused mapping: each mesh-``axis`` shard applies its own slice
    of the block table to the (replicated) chunk payload in ONE launch.

    ``src3d`` is the state's stacked per-shard table
    (:class:`repro.core.dmm_jax.ShardedFusedDMM.src3d`), device-placed with
    its leading shard axis over the mesh ``data`` axis; ``rows``/``blks``
    are (n_shards, S_loc) per-shard routing tables in the same layout.
    Returns the stacked (n_shards, S_loc, W) outputs as unblocked dispatch
    handles; reading them back (``np.asarray``) is both the sync point and
    the all-gather of emitted rows, so the sharded engine's emit stage can
    overlap that all-gather with the next chunk's densification.

    One host dispatch per chunk, one kernel execution per shard per chunk:
    the per-shard dispatch count stays 1 exactly as in the replicated
    engine.

    impl: "fused" (Pallas kernel per shard) | "ref" (jnp oracle per shard) |
    "auto" (fused on TPU, ref elsewhere).
    """
    global dispatch_count
    dispatch_count += 1
    if impl == "auto":
        impl = "fused" if on_tpu() else "ref"
    if impl not in ("ref", "fused"):
        raise ValueError(f"unknown impl {impl!r}")
    return _sharded_program(mesh, axis, impl, float(fill))(
        values, mask, rows, blks, src3d
    )


# ---------------------------------------------------------------------------
# Device-resident densification: one packed transfer, one dispatch per chunk
# ---------------------------------------------------------------------------
#
# The columnar entry points take ONE flat int32 buffer per chunk -- the raw
# (uid, value-bits) item columns, the CSR (start, count) of each selected
# event, its plan column id, and the (rows, blks) routing -- plus the plan's
# device-resident uid tables and block table.  uid resolution, densification
# and the fused mapping all happen inside a single jit, so the per-chunk
# host->device traffic is exactly one buffer and the dispatch count stays 1.
# The packed layout (built by repro.etl.engines._pack_columnar):
#
#     [ uids(NI) | val_bits(NI) | starts(B) | counts(B) | ev_col(B) | routing ]
#
# with routing = rows(S)+blks(S) replicated, or the (n_shards, S_loc)
# pair flattened for the sharded path.  Values travel as int32 bitcasts so
# the whole buffer is one dtype (one transfer, no repacking on device).


def _resolve_items(
    packed: jax.Array,
    uid_slot: jax.Array,
    uid_col: jax.Array,
    *,
    n_items: int,
    n_events: int,
    k: int,
) -> Tuple[jax.Array, jax.Array]:
    """Unpack the item columns and resolve them against the plan tables.

    Returns ``(slot2d, x2d)``: per selected event, its first K payload items
    as (payload slot | -1 dropped, value) -- the operand layout of
    :func:`repro.kernels.densify_map.densify_map`.  An item is dropped when
    its CSR slot is padding, its uid is out of table range or unknown, or
    its uid belongs to a different column than the event's (the host
    ``_densify_chunk`` owner-check semantics).
    """
    ni, b = n_items, n_events
    uids = packed[:ni]
    vals = jax.lax.bitcast_convert_type(packed[ni : 2 * ni], jnp.float32)
    o = 2 * ni
    starts = packed[o : o + b]
    counts = packed[o + b : o + 2 * b]
    ev_col = packed[o + 2 * b : o + 3 * b]
    kk = jnp.arange(k, dtype=jnp.int32)
    item_valid = kk[None, :] < counts[:, None]  # (b, k)
    ix = jnp.where(item_valid, starts[:, None] + kk[None, :], 0)
    iu = jnp.take(uids, ix.reshape(-1), mode="clip").reshape(b, k)
    iv = jnp.take(vals, ix.reshape(-1), mode="clip").reshape(b, k)
    nu = uid_slot.shape[0]
    if nu == 0:
        keep = jnp.zeros_like(item_valid)
        slot = jnp.full((b, k), -1, jnp.int32)
    else:
        uid_ok = (iu >= 0) & (iu < nu)
        su = jnp.where(uid_ok, iu, 0)
        slot = jnp.take(uid_slot, su.reshape(-1), mode="clip").reshape(b, k)
        owner = jnp.take(uid_col, su.reshape(-1), mode="clip").reshape(b, k)
        keep = item_valid & uid_ok & (slot >= 0) & (owner == ev_col[:, None])
    slot2d = jnp.where(keep, slot, jnp.int32(-1))
    x2d = jnp.where(keep, iv, jnp.float32(0))
    return slot2d, x2d


def _route_offset(n_items: int, n_events: int) -> int:
    return 2 * n_items + 3 * n_events


@functools.lru_cache(maxsize=None)
def _columnar_program(
    impl: str, fill: float, donate: bool
) -> Callable[..., Tuple[jax.Array, jax.Array]]:
    """One jitted resolve+densify+map program per (impl, fill, donate).

    ``donate`` hands the packed per-chunk buffer back to jax on the steady-
    state path (it is dead after the launch); donation is disabled on CPU
    where XLA cannot alias it and would warn per call.
    """

    def fn(packed, uid_slot, uid_col, src2d, *, n_items, n_events, n_rows, k):
        slot2d, x2d = _resolve_items(
            packed, uid_slot, uid_col, n_items=n_items, n_events=n_events, k=k
        )
        o = _route_offset(n_items, n_events)
        rows = packed[o : o + n_rows]
        blks = packed[o + n_rows : o + 2 * n_rows]
        if impl == "ref":
            return _ref.densify_map_ref(slot2d, x2d, rows, blks, src2d, fill=fill)
        return _densify_map_kernel(
            slot2d, x2d, rows, blks, src2d, fill=fill, interpret=not on_tpu()
        )

    return jax.jit(
        fn,
        static_argnames=("n_items", "n_events", "n_rows", "k"),
        donate_argnums=(0,) if donate else (),
    )


def dmm_apply_columnar(
    packed: jax.Array,
    uid_slot: jax.Array,
    uid_col: jax.Array,
    src2d: jax.Array,
    *,
    n_items: int,
    n_events: int,
    n_rows: int,
    k: int,
    impl: str = "auto",
    fill: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """Densify + map a whole chunk on-device in ONE dispatch.

    ``packed`` is the chunk's single flat int32 operand buffer (layout
    above; ``n_items``/``n_events``/``n_rows``/``k`` are its bucketed
    section sizes, static per jit-cache entry); ``uid_slot``/``uid_col``/
    ``src2d`` are the plan's device-resident tables, uploaded once per
    state.  Returns ((n_rows, W) values, (n_rows, W) int8 mask) as
    unblocked dispatch handles -- rows past the true routing length are
    garbage the caller slices off, exactly as in
    :func:`dmm_apply_fused`.

    impl: "fused" (Pallas densify_map kernel) | "ref" (scatter-free jnp
    oracle) | "auto" (kernel on TPU, oracle elsewhere).
    """
    global dispatch_count
    dispatch_count += 1
    if impl == "auto":
        impl = "fused" if on_tpu() else "ref"
    if impl not in ("ref", "fused"):
        raise ValueError(f"unknown impl {impl!r}")
    donate = jax.default_backend() != "cpu"
    return _columnar_program(impl, float(fill), donate)(
        packed, uid_slot, uid_col, src2d,
        n_items=n_items, n_events=n_events, n_rows=n_rows, k=k,
    )


@functools.lru_cache(maxsize=None)
def _columnar_sharded_program(
    mesh: Mesh, axis: str, impl: str, fill: float, donate: bool
) -> Callable[..., Tuple[jax.Array, jax.Array]]:
    """Sharded twin of :func:`_columnar_program`: the uid resolve runs
    replicated inside the same jit, then shard_map fans the per-shard
    routing and block-table slice out exactly like
    :func:`_sharded_program`."""
    from jax.sharding import PartitionSpec as P

    if impl == "ref":

        def local(s2, x2, r, b, t):
            ov, om = _ref.densify_map_ref(s2, x2, r[0], b[0], t[0], fill=fill)
            return ov[None], om[None]

    else:
        local = functools.partial(
            _densify_map_shard, fill=fill, interpret=not on_tpu()
        )

    inner = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
        check_vma=False,
    )

    def fn(packed, uid_slot, uid_col, src3d, *, n_items, n_events, n_rows, k, n_shards):
        slot2d, x2d = _resolve_items(
            packed, uid_slot, uid_col, n_items=n_items, n_events=n_events, k=k
        )
        o = _route_offset(n_items, n_events)
        rows = packed[o : o + n_shards * n_rows].reshape(n_shards, n_rows)
        o += n_shards * n_rows
        blks = packed[o : o + n_shards * n_rows].reshape(n_shards, n_rows)
        return inner(slot2d, x2d, rows, blks, src3d)

    return jax.jit(
        fn,
        static_argnames=("n_items", "n_events", "n_rows", "k", "n_shards"),
        donate_argnums=(0,) if donate else (),
    )


def dmm_apply_columnar_sharded(
    packed: jax.Array,
    uid_slot: jax.Array,
    uid_col: jax.Array,
    src3d: jax.Array,
    *,
    mesh: Mesh,
    n_items: int,
    n_events: int,
    n_rows: int,
    k: int,
    n_shards: int,
    axis: str = "data",
    impl: str = "auto",
    fill: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """Sharded device densify: the resolved item tables stay replicated,
    each mesh-``axis`` shard densifies + maps its own routing slice against
    its own block-table slice under shard_map.  ``n_rows`` is the PER-SHARD
    routing length (the packed buffer carries the flattened (n_shards,
    n_rows) rows/blks pair).  One host dispatch per chunk; returns the
    stacked (n_shards, n_rows, W) outputs as unblocked handles."""
    global dispatch_count
    dispatch_count += 1
    if impl == "auto":
        impl = "fused" if on_tpu() else "ref"
    if impl not in ("ref", "fused"):
        raise ValueError(f"unknown impl {impl!r}")
    donate = jax.default_backend() != "cpu"
    return _columnar_sharded_program(mesh, axis, impl, float(fill), donate)(
        packed, uid_slot, uid_col, src3d,
        n_items=n_items, n_events=n_events, n_rows=n_rows, k=k,
        n_shards=n_shards,
    )


def moe_combine(
    expert_out: jax.Array, combine: jax.Array, *, impl: str = "auto"
) -> jax.Array:
    """Combine expert outputs: (E, C, D), (T, E, C) -> (T, D)."""
    if impl == "auto":
        impl = "pallas" if on_tpu() else "ref"
    if impl == "ref":
        return _ref.moe_combine_ref(expert_out, combine)
    if impl == "pallas":
        return _moe_combine_kernel(combine, expert_out, interpret=not on_tpu())
    raise ValueError(f"unknown impl {impl!r}")


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    n_rep: int = 1,
    impl: str = "auto",
) -> jax.Array:
    """Single-kernel attention: q (N, S, hd), k/v (N/n_rep, T, hd)."""
    if impl == "auto":
        impl = "flash" if on_tpu() else "ref"
    if impl == "ref":
        return _ref.attention_ref(q, k, v, causal=causal, n_rep=n_rep)
    if impl == "flash":
        return _flash_attention_kernel(
            q, k, v, causal=causal, n_rep=n_rep, interpret=not on_tpu()
        )
    raise ValueError(f"unknown impl {impl!r}")
