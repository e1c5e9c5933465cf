"""Dry-run machinery: lower + compile every (arch x shape x mesh) cell.

Importable without touching jax device state; the process entry point that
sets ``XLA_FLAGS`` is :mod:`repro.launch.dryrun`.

Per cell this produces (all from the *compiled* artifact, no execution):

  * per-device memory stats (arguments / temps / output bytes),
  * per-device HLO flops & bytes accessed (``cost_analysis``),
  * per-device collective-op bytes by kind (parsed from the SPMD module),
  * the roofline inputs recorded to JSON for §Roofline.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import repro.configs as configs
from repro.configs import ShapeCell
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.sharding.specs import ShardingPolicy, make_policy, param_spec_tree
from repro.train.loop import TrainConfig, make_train_step, param_spec_tree_like
from repro.train.optimizer import AdamWConfig, adamw_init

__all__ = ["run_cell", "train_settings", "input_specs", "decode_state_specs", "CellResult"]


# ---------------------------------------------------------------------------
# Per-arch training settings (memory budget driven; see EXPERIMENTS.md)
# ---------------------------------------------------------------------------


def train_settings(cfg: ModelConfig, cell: ShapeCell) -> TrainConfig:
    n = cfg.param_count()
    if n > 100e9:  # llama3-405b, dbrx-132b
        n_micro, mdt, adt = 8, "bfloat16", "bfloat16"
    elif n > 10e9:  # phi3, qwen3-moe
        n_micro, mdt, adt = 4, "float32", "float32"
    else:
        n_micro, mdt, adt = 1, "float32", "float32"
    if cfg.dryrun_n_micro:
        n_micro = cfg.dryrun_n_micro
    return TrainConfig(
        batch=cell.global_batch,
        seq=cell.seq_len,
        n_micro=n_micro,
        accum_dtype=adt,
        opt=AdamWConfig(moment_dtype=mdt),
    )


# ---------------------------------------------------------------------------
# Abstract inputs
# ---------------------------------------------------------------------------


def _sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ModelConfig, cell: ShapeCell) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for one global batch (train/prefill)."""
    B, S = cell.global_batch, cell.seq_len
    out = {
        "tokens": _sds((B, S), jnp.int32),
        "labels": _sds((B, S), jnp.int32),
        "loss_weight": _sds((B, S), jnp.float32),
    }
    if cfg.family == "audio":
        out["frames"] = _sds((B, cfg.enc_seq, cfg.d_model), jnp.float32)
    if cfg.family == "vlm":
        out["patches"] = _sds((B, cfg.frontend_tokens, cfg.d_model), jnp.float32)
    return out


def _batch_pspec(sp: ShardingPolicy, b: int, ndim: int) -> P:
    dp = sp.data_axes
    lead = dp if sp.dim(b, dp) else None
    return P(lead, *([None] * (ndim - 1)))


def batch_shardings(sp: ShardingPolicy, tree) -> Any:
    return jax.tree_util.tree_map(
        lambda l: NamedSharding(sp.mesh, _batch_pspec(sp, l.shape[0], len(l.shape))),
        tree,
    )


def decode_state_specs(cfg: ModelConfig, sp: ShardingPolicy, state_shapes) -> Any:
    """PartitionSpecs for the serving cache pytree.

    KV caches (L, B, T, KV, hd): batch over DP; KV heads over model when
    divisible, otherwise the *time* axis is sharded over model (distributed
    KV -- softmax reductions become collectives, memory divides by 256).
    """
    dp = sp.data_axes
    m = sp.model_axis

    def spec(path, leaf):
        keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        name = keys[-1]
        shp = leaf.shape
        if name == "pos":
            return P()
        if name in ("k", "v", "xk", "xv"):  # (L, B, T, KV, hd)
            b = dp if sp.dim(shp[1], dp) else None
            if sp.dim(shp[3], m):
                return P(None, b, None, m, None)
            return P(None, b, sp.dim(shp[2], m), None, None)
        if name == "wkv":  # (L, B, H, hd, hd)
            b = dp if sp.dim(shp[1], dp) else None
            return P(None, b, sp.dim(shp[2], m), None, None)
        if name in ("x_tm", "x_cm"):  # (L, B, 1, D)
            b = dp if sp.dim(shp[1], dp) else None
            return P(None, b, None, None)
        if name == "h":  # mamba (L, B, Di, N)
            b = dp if sp.dim(shp[1], dp) else None
            return P(None, b, sp.dim(shp[2], m), None)
        if name == "conv":  # (L, B, 3, Di)
            b = dp if sp.dim(shp[1], dp) else None
            return P(None, b, None, sp.dim(shp[3], m))
        return P(*([None] * len(shp)))

    flat, treedef = jax.tree_util.tree_flatten_with_path(state_shapes)
    return jax.tree_util.tree_unflatten(treedef, [spec(p, l) for p, l in flat])


# ---------------------------------------------------------------------------
# HLO collective accounting
# ---------------------------------------------------------------------------

_COLL_RE = re.compile(
    r"=\s+(?:\()?([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)[\s(]"
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum per-device result bytes of every collective op, by kind."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        dtype, dims, kind = m.groups()
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[kind] = out.get(kind, 0) + n * _DTYPE_BYTES.get(dtype, 4)
    return out


# ---------------------------------------------------------------------------
# Cell runner
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CellResult:
    arch: str
    shape: str
    mesh: str
    ok: bool
    skipped: str = ""
    error: str = ""
    seconds: float = 0.0
    memory: Optional[Dict[str, float]] = None
    # cost_analysis of the production (scanned) executable: scan bodies are
    # counted ONCE by XLA, so these are lower bounds -- kept for reference
    cost_scanned: Optional[Dict[str, float]] = None
    # affine-in-L extrapolation from unrolled L=1 / L=2 compiles: the real
    # per-step numbers used by §Roofline (exact for homogeneous layer stacks)
    cost: Optional[Dict[str, float]] = None
    collectives: Optional[Dict[str, int]] = None
    model_flops_global: float = 0.0
    n_devices: int = 0

    def to_json(self) -> Dict:
        return dataclasses.asdict(self)


def _model_flops(cfg: ModelConfig, cell: ShapeCell) -> float:
    """MODEL_FLOPS = 6*N_active*D tokens (train: fwd+bwd; decode: 2*N_active
    per token forward-only => 2*N*D)."""
    n = cfg.active_param_count()
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * n * tokens
    if cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * cell.global_batch


def _compile_cell(
    cfg: ModelConfig,
    cell: ShapeCell,
    sp: ShardingPolicy,
    *,
    cost_pass: bool,
    n_micro: Optional[int] = None,
):
    with sp.mesh:
        if cell.kind == "train":
            return _lower_train(cfg, cell, sp, force_n_micro=1 if cost_pass else n_micro)
        if cell.kind == "prefill":
            return _lower_prefill(cfg, cell, sp)
        return _lower_decode(cfg, cell, sp)


def _extract_cost(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis() or {}
    out = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
    }
    for kind, b in collective_bytes(compiled.as_text()).items():
        out[f"coll:{kind}"] = float(b)
    return out


def _reduced(cfg: ModelConfig, layers: int) -> ModelConfig:
    kw = {"n_layers": layers, "scan_unroll": True}
    if cfg.enc_dec:
        kw["enc_layers"] = layers
    return cfg.replace(**kw)


def run_cell(
    arch: str,
    shape_name: str,
    mesh: Mesh,
    *,
    verbose: bool = True,
    cost_extrapolation: bool = True,
    overrides: Optional[Dict[str, Any]] = None,
) -> CellResult:
    cfg = configs.get(arch)
    force_n_micro = None
    if overrides:
        overrides = dict(overrides)
        force_n_micro = overrides.pop("_n_micro", None)
        if overrides:
            cfg = cfg.replace(**overrides)
    cell = configs.SHAPES[shape_name]
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    res = CellResult(
        arch=arch, shape=shape_name, mesh=mesh_name, ok=False,
        n_devices=mesh.devices.size,
    )
    ok, why = configs.runnable(cfg, cell)
    if not ok:
        res.skipped = why
        res.ok = True
        return res
    t0 = time.time()
    sp = make_policy(mesh)
    try:
        # 1) production executable (scanned): proves compile + real memory
        compiled = _compile_cell(cfg, cell, sp, cost_pass=False, n_micro=force_n_micro)
        ma = compiled.memory_analysis()
        res.memory = {
            "argument_bytes": float(getattr(ma, "argument_size_in_bytes", 0)),
            "temp_bytes": float(getattr(ma, "temp_size_in_bytes", 0)),
            "output_bytes": float(getattr(ma, "output_size_in_bytes", 0)),
            "alias_bytes": float(getattr(ma, "alias_size_in_bytes", 0)),
        }
        res.cost_scanned = _extract_cost(compiled)
        del compiled
        # 2) cost pass: XLA counts a scan body once, so extrapolate affine
        #    in L from unrolled L=1 / L=2 compiles (exact: homogeneous stack)
        if cost_extrapolation:
            c1 = _extract_cost(_compile_cell(_reduced(cfg, 1), cell, sp, cost_pass=True))
            c2 = _extract_cost(_compile_cell(_reduced(cfg, 2), cell, sp, cost_pass=True))
            L = cfg.n_layers
            keys = set(c1) | set(c2)

            def extrap(k):
                a, b = c1.get(k, 0.0), c2.get(k, 0.0)
                slope = b - a
                if slope < 0:  # cost is L-independent (e.g. embedding-side
                    return max(a, b)  # collectives); noise made the slope < 0
                return b + (L - 2) * slope

            res.cost = {k: extrap(k) for k in keys}
            res.collectives = {
                k.split(":", 1)[1]: int(v)
                for k, v in res.cost.items()
                if k.startswith("coll:")
            }
        res.model_flops_global = _model_flops(cfg, cell)
        res.ok = True
    except (TypeError, ValueError, RuntimeError, NotImplementedError) as e:
        # expected compile-time failure modes (shape/sharding mismatches, OOM
        # estimates, XlaRuntimeError is a RuntimeError): report per-cell
        res.error = f"{type(e).__name__}: {e}"
    except Exception as e:
        # anything else (KeyError, AttributeError, ...) is a bug in the dryrun
        # harness itself -- surface it with the cell that triggered it instead
        # of burying it in a per-cell error column
        raise RuntimeError(
            f"dryrun harness bug on {arch} {shape_name} mesh={mesh_name}: "
            f"unexpected {type(e).__name__}: {e}"
        ) from e
    res.seconds = time.time() - t0
    if verbose:
        status = "SKIP" if res.skipped else ("OK" if res.ok else "FAIL")
        print(f"[{status:4s}] {arch:22s} {shape_name:12s} mesh={mesh_name:8s} "
              f"{res.seconds:6.1f}s {res.error[:90]}", flush=True)
    return res


def _lower_train(
    cfg: ModelConfig, cell: ShapeCell, sp: ShardingPolicy, force_n_micro: Optional[int] = None
):
    tc = train_settings(cfg, cell)
    if force_n_micro is not None:
        tc = dataclasses.replace(tc, n_micro=force_n_micro)
    # each microbatch must still shard over the DP axes
    dp = 1
    for a in sp.data_axes:
        dp *= sp.axis_size(a)
    cap = max(1, cell.global_batch // dp)
    if tc.n_micro > cap:
        tc = dataclasses.replace(tc, n_micro=cap)
    key = jax.random.PRNGKey(0)
    pshapes = jax.eval_shape(lambda k: M.init_params(cfg, k), key)
    pspecs = param_spec_tree(pshapes, sp)
    oshapes = jax.eval_shape(lambda: adamw_init(pshapes, tc.opt))
    ospecs = param_spec_tree_like(oshapes, pspecs)
    mesh = sp.mesh
    p_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs)
    o_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), ospecs)
    batch = input_specs(cfg, cell)
    b_sh = batch_shardings(sp, batch)
    step = make_train_step(cfg, tc, sp)
    jitted = jax.jit(
        step,
        in_shardings=(p_sh, o_sh, b_sh),
        out_shardings=(p_sh, o_sh, None),
        donate_argnums=(0, 1),
    )
    lowered = jitted.lower(pshapes, oshapes, batch)
    return lowered.compile()


def _lower_prefill(cfg: ModelConfig, cell: ShapeCell, sp: ShardingPolicy):
    """Inference prefill: full-sequence forward producing logits."""
    key = jax.random.PRNGKey(0)
    pshapes = jax.eval_shape(lambda k: M.init_params(cfg, k), key)
    pspecs = param_spec_tree(pshapes, sp)
    mesh = sp.mesh
    p_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs)
    batch = input_specs(cfg, cell)
    batch.pop("labels")
    batch.pop("loss_weight")
    b_sh = batch_shardings(sp, batch)

    def prefill(params, batch):
        logits, _ = M.forward(params, cfg, batch, sp)
        return logits

    jitted = jax.jit(prefill, in_shardings=(p_sh, b_sh))
    return jitted.lower(pshapes, batch).compile()


def _lower_decode(cfg: ModelConfig, cell: ShapeCell, sp: ShardingPolicy):
    """serve_step: one new token against a cache of cell.seq_len history."""
    key = jax.random.PRNGKey(0)
    pshapes = jax.eval_shape(lambda k: M.init_params(cfg, k), key)
    pspecs = param_spec_tree(pshapes, sp)
    mesh = sp.mesh
    p_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs)
    B = cell.global_batch
    sshapes = jax.eval_shape(
        lambda: M.init_decode_state(cfg, B, cell.seq_len)
    )
    sspecs = decode_state_specs(cfg, sp, sshapes)
    s_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), sspecs)
    token = jax.ShapeDtypeStruct((B,), jnp.int32)
    t_sh = NamedSharding(mesh, _batch_pspec(sp, B, 1))

    def serve_step(params, state, token):
        logits, state = M.decode_step(params, cfg, state, token, sp)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, state

    jitted = jax.jit(
        serve_step,
        in_shardings=(p_sh, s_sh, t_sh),
        out_shardings=(t_sh, s_sh),
        donate_argnums=(1,),
    )
    return jitted.lower(pshapes, sshapes, token).compile()
