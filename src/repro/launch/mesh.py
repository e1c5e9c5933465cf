"""Production mesh construction.

A function (not a module-level constant) so importing never touches jax
device state -- the dry-run must set XLA_FLAGS *before* the first jax call.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh", "make_etl_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e pod mesh: 16x16 = 256 chips per pod; 2 pods = 512 chips.

    Axes: ``model`` is the high-bandwidth TP/EP axis; ``data`` is DP/FSDP;
    ``pod`` (multi-pod) is the DCN-connected pure-DP axis folded into the
    data-parallel group by the sharding policy.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """Debug mesh over however many local devices exist."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"need {data*model} devices, have {n}")
    return jax.make_mesh(
        (data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )


def make_etl_mesh(shards: int = 0):
    """1 x N mesh for the sharded METL mapping engine (``engine="sharded"``).

    The fused DMM block table shards over the ``data`` axis; ``shards=0``
    uses every local device.  Returns a plain (data, model=1) mesh so the
    same ShardingPolicy axis names apply.
    """
    n = len(jax.devices())
    shards = shards or n
    if shards > n:
        raise ValueError(f"need {shards} devices for {shards} shards, have {n}")
    return make_local_mesh(data=shards, model=1)
