"""Compile the ETL mapping kernels for a described TPU v5e, at EOS paper scale.

Nothing here runs on a chip: the TPU compiler that ships with jax compiles
for a topology that is described and not attached, and refuses what Mosaic
would refuse on the chip (vector loads from SMEM, row gathers it cannot
lower, more scoped VMEM than a core has).  Interpret-mode tests cannot see
any of that.  Shapes are those of the 125-schema x 10-version EOS scenario
(1,249 blocks and 11,228 uids, in tables sized to their capacity buckets:
a 2048 x 128 block table and 16,384 uids) at 64-, 512- and 4096-event
chunks.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and a module that decided at import
whether its tests exist would give pytest-xdist workers different test
sets.  Keep every described-topology compile in this one file.
"""

from __future__ import annotations

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.dmm_jax import bucket_rows
from repro.kernels import ops
from repro.kernels.masked_gather import masked_gather
from repro.kernels.segmented_gather import segmented_gather

N_BLOCKS, WIDTH, N_IN_PAD, K = 1249, 128, 128, 16
N_BLOCKS_PAD, N_UIDS = bucket_rows(N_BLOCKS), bucket_rows(11228)
CHUNKS = [64, 512, 4096]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """Described-topology compiles can be written to the persistent cache
    but never read back without a chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def kernels_compiled(monkeypatch):
    """The ops layer picks ``interpret=not on_tpu()``; this CPU process must
    compile the kernels for the described chip instead."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)


def _compile(fn, *args, **statics) -> str:
    compiled = jax.jit(fn, static_argnames=tuple(statics)).lower(*args, **statics).compile()
    return compiled.as_text()


def _columnar_shapes(events: int):
    b = s = bucket_rows(events)
    ni = bucket_rows(8 * events)
    return ni, b, s, 2 * ni + 3 * b + 2 * s


@pytest.mark.parametrize("events", CHUNKS)
def test_segmented_gather_compiles(topo, one_chip, no_cache, events):
    b = s = bucket_rows(events)
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    text = _compile(
        functools.partial(segmented_gather, interpret=False),
        sds((b, N_IN_PAD), jnp.float32),
        sds((b, N_IN_PAD), jnp.int8),
        sds((s,), jnp.int32),
        sds((s,), jnp.int32),
        sds((N_BLOCKS_PAD, WIDTH), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("events", CHUNKS)
def test_columnar_program_compiles(topo, one_chip, no_cache, kernels_compiled, events):
    """The whole device-densify program: uid resolve + densify_map."""
    ni, b, s, total = _columnar_shapes(events)
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    program = ops._columnar_program.__wrapped__("fused", 0.0, True)
    compiled = program.lower(
        sds((total,), jnp.int32),
        sds((N_UIDS,), jnp.int32),
        sds((N_UIDS,), jnp.int32),
        sds((N_BLOCKS_PAD, WIDTH), jnp.int32),
        n_items=ni, n_events=b, n_rows=s, k=K,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("events", CHUNKS)
def test_masked_gather_compiles(topo, one_chip, no_cache, events):
    """The per-block kernel ``engine="blocks"`` reaches on a TPU."""
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    text = _compile(
        functools.partial(masked_gather, interpret=False),
        sds((events, 10), jnp.float32),
        sds((events, 10), jnp.int8),
        sds((WIDTH,), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("events", [512])
def test_sharded_columnar_program_compiles(topo, no_cache, kernels_compiled, events):
    """The sharded device-densify program over a 4-chip ``data`` mesh: each
    chip holds a quarter of the block table."""
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(4, 1), ("data", "model"))
    ni, b, _, _ = _columnar_shapes(events)
    s_loc = bucket_rows(events // 4)
    total = 2 * ni + 3 * b + 2 * 4 * s_loc
    per_pad = bucket_rows(-(-N_BLOCKS // 4))
    rep = NamedSharding(mesh, P())
    program = ops._columnar_sharded_program.__wrapped__(mesh, "data", "fused", 0.0, True)
    compiled = program.lower(
        jax.ShapeDtypeStruct((total,), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((N_UIDS,), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((N_UIDS,), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((4, per_pad, WIDTH), jnp.int32,
                             sharding=NamedSharding(mesh, P("data"))),
        n_items=ni, n_events=b, n_rows=s_loc, k=K, n_shards=4,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem is None or mem.argument_size_in_bytes < 4 * N_BLOCKS_PAD * WIDTH
