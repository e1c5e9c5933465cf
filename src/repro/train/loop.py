"""Training step construction and the driver loop.

Two step builders:

  * :func:`make_train_step` -- the production path: jit + GSPMD auto
    sharding over the (pod, data, model) mesh, microbatch gradient
    accumulation via ``lax.scan``, remat per ``cfg.remat``.
  * :func:`make_dp_train_step` -- explicit shard_map data parallelism with
    optional int8 all-reduce compression + error feedback (the
    distributed-optimization trick; params replicated, DP only).

The driver :func:`train` wires the ETL batcher, checkpointing, and
straggler-tolerant deterministic data assignment together.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models import model as M
from ..models.config import ModelConfig
from ..sharding.specs import ShardingPolicy, make_policy, param_spec_tree
from .optimizer import (
    AdamWConfig,
    adamw_init,
    adamw_update,
    compress_grads_int8,
)

__all__ = ["TrainConfig", "make_train_step", "make_dp_train_step", "train"]


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    steps: int = 100
    batch: int = 8
    seq: int = 128
    n_micro: int = 1  # gradient-accumulation microbatches
    accum_dtype: str = "float32"  # bfloat16 halves the accumulator at >=100B
    log_every: int = 10
    ckpt_every: int = 0  # 0 = disabled
    ckpt_dir: Optional[str] = None
    seed: int = 0
    opt: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)


def _split_micro(batch: Dict[str, jax.Array], n: int) -> Dict[str, jax.Array]:
    def f(x):
        b = x.shape[0]
        return x.reshape(n, b // n, *x.shape[1:])

    return {k: f(v) for k, v in batch.items()}


def make_train_step(
    cfg: ModelConfig, tc: TrainConfig, sh: Optional[ShardingPolicy] = None
) -> Callable:
    """jit-ready (params, opt_state, batch) -> (params, opt_state, metrics)."""

    def _constrain_like_params(params, tree):
        """Pin gradients/accumulators to the parameter sharding.

        Without this GSPMD is free to materialise *replicated* per-layer
        gradients (full all-reduce + dynamic-slice instead of
        reduce-scatter): on llama3-405b that was 1.09 TB of all-reduce and
        118 GB temp per device (see EXPERIMENTS.md §Perf iteration 1).
        """
        if sh is None or sh.mesh is None:
            return tree
        pspecs = param_spec_tree(params, sh)
        return jax.tree_util.tree_map(
            lambda g, s: jax.lax.with_sharding_constraint(
                g, NamedSharding(sh.mesh, s)
            ),
            tree,
            pspecs,
        )

    def train_step(params, opt_state, batch):
        if tc.n_micro > 1:
            micro = _split_micro(batch, tc.n_micro)

            adt = jnp.dtype(tc.accum_dtype)

            def accum(carry, mb):
                gsum, lsum = carry
                loss, grads = jax.value_and_grad(M.loss_fn)(params, cfg, mb, sh)
                grads = _constrain_like_params(params, grads)
                gsum = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(adt), gsum, grads
                )
                gsum = _constrain_like_params(params, gsum)
                return (gsum, lsum + loss), None

            zeros = _constrain_like_params(
                params,
                jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, adt), params),
            )
            (gsum, lsum), _ = jax.lax.scan(accum, (zeros, 0.0), micro)
            grads = jax.tree_util.tree_map(lambda g: g / tc.n_micro, gsum)
            loss = lsum / tc.n_micro
        else:
            loss, grads = jax.value_and_grad(M.loss_fn)(params, cfg, batch, sh)
            grads = _constrain_like_params(params, grads)
        params, opt_state, om = adamw_update(grads, opt_state, params, tc.opt)
        metrics = {"loss": loss, **om}
        return params, opt_state, metrics

    return train_step


def make_dp_train_step(cfg: ModelConfig, tc: TrainConfig, mesh, data_axes=("data",)):
    """Explicit data-parallel step via shard_map with int8 grad compression.

    Params/opt state replicated; the batch is sharded over ``data_axes``.
    Exercises the compressed DP all-reduce wire format end-to-end.
    """
    def local_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(M.loss_fn)(params, cfg, batch, None)
        if tc.opt.compress_grads:
            grads, ef = compress_grads_int8(grads, opt_state["ef"], data_axes)
            opt_state = dict(opt_state, ef=ef)
        else:
            grads = jax.lax.pmean(grads, data_axes)
        loss = jax.lax.pmean(loss, data_axes)
        params, opt_state, om = adamw_update(grads, opt_state, params, tc.opt)
        return params, opt_state, {"loss": loss, **om}

    batch_spec = P(data_axes)
    rep = P()

    def spec_like(tree, spec):
        return jax.tree_util.tree_map(lambda _: spec, tree)

    def step(params, opt_state, batch):
        return jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(
                spec_like(params, rep),
                spec_like(opt_state, rep),
                jax.tree_util.tree_map(lambda _: batch_spec, batch),
            ),
            out_specs=(
                spec_like(params, rep),
                spec_like(opt_state, rep),
                {"loss": rep, "grad_norm": rep, "lr": rep},
            ),
            check_vma=False,
        )(params, opt_state, batch)

    return jax.jit(step)


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def init_all(cfg: ModelConfig, tc: TrainConfig, mesh=None):
    """Initialise (params, opt_state) -- sharded when a mesh is given."""
    key = jax.random.PRNGKey(tc.seed)
    if mesh is None:
        params = M.init_params(cfg, key)
        return params, adamw_init(params, tc.opt), make_policy(None)
    sp = make_policy(mesh)
    pshapes = jax.eval_shape(lambda k: M.init_params(cfg, k), key)
    pspecs = param_spec_tree(pshapes, sp)
    out_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs)
    with mesh:
        params = jax.jit(
            lambda k: M.init_params(cfg, k), out_shardings=out_sh
        )(key)
        ostate_shapes = jax.eval_shape(lambda p: adamw_init(p, tc.opt), params)
        ospecs = param_spec_tree_like(ostate_shapes, pspecs)
        o_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), ospecs)
        opt_state = jax.jit(lambda p: adamw_init(p, tc.opt), out_shardings=o_sh)(params)
    return params, opt_state, sp


def param_spec_tree_like(opt_shapes: Dict, pspecs) -> Dict:
    """Optimizer-state specs: moments/EF mirror the param specs; scalars
    replicate."""
    out = {}
    for k, v in opt_shapes.items():
        if k in ("m", "v", "ef"):
            out[k] = pspecs
        else:
            out[k] = jax.tree_util.tree_map(lambda _: P(), v)
    return out


def train(
    cfg: ModelConfig,
    tc: TrainConfig,
    *,
    mesh=None,
    batch_fn: Optional[Callable[[int], Dict[str, np.ndarray]]] = None,
    on_step: Optional[Callable[[int, Dict[str, float]], None]] = None,
) -> Dict[str, Any]:
    """Run the loop; returns final params/opt_state/history."""
    from ..etl.batcher import make_token_batch
    from .checkpoint import latest_step, restore, save

    params, opt_state, sp = init_all(cfg, tc, mesh)
    start = 0
    if tc.ckpt_dir:
        step0 = latest_step(tc.ckpt_dir)
        if step0 is not None:
            params, opt_state, meta = restore(tc.ckpt_dir, step0, (params, opt_state))
            start = meta["step"]
    step_fn = make_train_step(cfg, tc, sp if mesh is not None else None)
    if mesh is not None:
        batch_sh = NamedSharding(mesh, sp.batch_spec(2))
        step_fn = jax.jit(step_fn, donate_argnums=(0, 1))
    else:
        step_fn = jax.jit(step_fn, donate_argnums=(0, 1))

    history = []
    t0 = time.time()
    ctx = mesh if mesh is not None else _nullcontext()
    with ctx:
        for step in range(start, tc.steps):
            batch = (
                batch_fn(step)
                if batch_fn is not None
                else make_token_batch(cfg, tc.batch, tc.seq, step=step, seed=tc.seed)
            )
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            if step % tc.log_every == 0 or step == tc.steps - 1:
                m = {k: float(v) for k, v in metrics.items()}
                m["step"] = step
                m["wall"] = time.time() - t0
                history.append(m)
                if on_step:
                    on_step(step, m)
            if tc.ckpt_every and tc.ckpt_dir and (step + 1) % tc.ckpt_every == 0:
                save(tc.ckpt_dir, step + 1, params, opt_state, {"step": step + 1})
    return {"params": params, "opt_state": opt_state, "history": history}


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False
