"""Parameter and activation PartitionSpecs for every architecture family.

Scheme (single-pod (data, model) = (16, 16); multi-pod adds a leading
``pod`` axis folded into the data-parallel group):

  * TP: second (output) dim of projection weights over ``model``;
    vocab over ``model``; MoE experts over ``model`` (EP == TP axis).
  * FSDP/ZeRO-3: first (input) dim of projection weights over ``data`` --
    parameters and optimizer state are fully sharded; XLA all-gathers
    weights layer-by-layer under the scan.
  * Activations: batch over (pod, data); the padded vocab dim of logits
    over ``model``.

Every rule is divisibility-guarded: a dim that does not divide by the axis
size falls back to replication (e.g. whisper's 6 attention heads on a
16-wide model axis).  That keeps all 40 (arch x shape) cells lowerable on
the same mesh; the per-arch consequences are discussed in EXPERIMENTS.md.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "ShardingPolicy",
    "make_policy",
    "param_spec_tree",
    "dmm_table_sharding",
]


def dmm_table_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Placement of the sharded fused-DMM block table (and its per-shard
    routing operands): leading shard axis over the mesh ``data`` axis, table
    rows/lanes replicated within a shard.  Used by
    :func:`repro.core.dmm_jax.compile_fused_sharded` so each device holds
    only its (1, n_blocks_pad_loc, W) slice of ``src3d``."""
    return NamedSharding(mesh, P(axis))


@dataclasses.dataclass
class ShardingPolicy:
    mesh: Optional[Mesh]
    model_axis: str = "model"
    data_axis: str = "data"
    pod_axis: Optional[str] = None  # set on the multi-pod mesh

    # ---- axis helpers --------------------------------------------------------
    @property
    def data_axes(self) -> Tuple[str, ...]:
        """The data-parallel axes (pod folds into DP)."""
        if self.pod_axis:
            return (self.pod_axis, self.data_axis)
        return (self.data_axis,)

    def axis_size(self, name: str) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape[name]

    def _fits(self, dim: int, axis) -> bool:
        if self.mesh is None:
            return False
        if isinstance(axis, tuple):
            size = 1
            for a in axis:
                size *= self.axis_size(a)
        else:
            size = self.axis_size(axis)
        return dim % size == 0 and dim >= size

    def dim(self, dim_size: int, axis):
        """axis name if it divides dim_size, else None (replicate)."""
        return axis if self._fits(dim_size, axis) else None

    # ---- activation constraints ---------------------------------------------
    def _wsc(self, x, spec: P):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def act_btd(self, x):
        """(B, S, D) residual-stream activations: batch over DP."""
        return self._wsc(x, P(self.data_axes, None, None))

    def act_ff(self, x):
        """(..., F) MLP hidden: F over model."""
        spec = [None] * (x.ndim - 1) + [self.dim(x.shape[-1], self.model_axis)]
        spec[0] = self.data_axes
        return self._wsc(x, P(*spec))

    def act_heads(self, x):
        """(B, S, H*hd) attention output: heads over model when divisible."""
        return self._wsc(
            x, P(self.data_axes, None, self.dim(x.shape[-1], self.model_axis))
        )

    def act_expert_ff(self, x):
        """(E, C, F) expert hidden: experts over model."""
        return self._wsc(
            x, P(self.dim(x.shape[0], self.model_axis), None, None)
        )

    def logits(self, x):
        """(B, S, V) logits: vocab over model."""
        return self._wsc(
            x, P(self.data_axes, None, self.dim(x.shape[-1], self.model_axis))
        )

    def batch_spec(self, ndim: int) -> P:
        """Input batch arrays: leading dim over DP."""
        return P(self.data_axes, *([None] * (ndim - 1)))


def make_policy(mesh: Optional[Mesh]) -> ShardingPolicy:
    if mesh is None:
        return ShardingPolicy(mesh=None)
    names = mesh.axis_names
    pod = "pod" if "pod" in names else None
    return ShardingPolicy(mesh=mesh, pod_axis=pod)


# ---------------------------------------------------------------------------
# Parameter spec tree: rules keyed on (path, shape)
# ---------------------------------------------------------------------------

# path-suffix regex -> role
_RULES = [
    # embeddings
    (r"embed/tok$", "vocab_in"),
    (r"embed/head$", "vocab_out"),
    (r"embed/pos$", "replicate"),
    # rwkv time-mix: 40 heads do not divide the 16-wide model axis; TP on
    # these projections made GSPMD re-gather the full residual ~18x/layer
    # (24.8 GB of all-gather per 2 layers -- EXPERIMENTS §Perf rwkv iter 1).
    # FSDP-only: weights shard over data, activations stay replicated on D.
    (r"tm/w[rkvgo]$", "fsdp_first"),
    (r"cm/wr$", "fsdp_first"),  # channel-mix gate multiplies a replicated kv
    # attention / generic 2D projections: in-dim FSDP, out-dim TP
    (r"(wq|wk|wv|w_in|w_gate|in_proj)$", "proj_out_tp"),
    (r"(wo|w_out|out_proj)$", "proj_in_tp"),
    # rwkv
    (r"(wr|wg)$", "proj_out_tp"),
    (r"wA$", "fsdp_first"),
    (r"wB$", "fsdp_last"),
    # moe
    (r"router$", "fsdp_first"),
    # mamba
    (r"conv_w$", "last_tp"),
    (r"x_proj$", "first_tp"),
    (r"dt_proj$", "last_tp"),
    (r"A_log$", "first_tp"),
    # norms / scalars / biases
    (r".*", "replicate"),
]


def _spec_for(path: str, shape: Tuple[int, ...], sp: ShardingPolicy, n_stack: int) -> P:
    """n_stack: number of leading stacked-layer dims to skip (None spec)."""
    core = shape[n_stack:]
    lead = [None] * n_stack
    role = "replicate"
    for pat, r in _RULES:
        if re.search(pat, path):
            role = r
            break
    d, m = sp.data_axes, sp.model_axis  # FSDP folds the pod axis in
    is_expert = bool(re.search(r"(w_in|w_gate|w_out)$", path)) and len(core) == 3

    if is_expert:  # (E, D, F) / (E, F, D): experts over model, in-dim FSDP
        e, a, b = core
        return P(*lead, sp.dim(e, m), sp.dim(a, d), None)
    if role == "vocab_in" and len(core) == 2:  # (V, D)
        return P(*lead, sp.dim(core[0], m), sp.dim(core[1], d))
    if role == "vocab_out" and len(core) == 2:  # (D, V)
        return P(*lead, sp.dim(core[0], d), sp.dim(core[1], m))
    if role == "proj_out_tp" and len(core) == 2:  # (D_in, D_out)
        return P(*lead, sp.dim(core[0], d), sp.dim(core[1], m))
    if role == "proj_in_tp" and len(core) == 2:  # (D_in, D_out) contracting TP
        return P(*lead, sp.dim(core[0], m), sp.dim(core[1], d))
    if role == "fsdp_first" and len(core) >= 1:
        return P(*lead, sp.dim(core[0], d), *([None] * (len(core) - 1)))
    if role == "fsdp_last" and len(core) >= 1:
        return P(*lead, *([None] * (len(core) - 1)), sp.dim(core[-1], d))
    if role == "first_tp" and len(core) >= 1:
        return P(*lead, sp.dim(core[0], m), *([None] * (len(core) - 1)))
    if role == "last_tp" and len(core) >= 1:
        return P(*lead, *([None] * (len(core) - 1)), sp.dim(core[-1], m))
    return P(*lead, *([None] * len(core)))


def param_spec_tree(params_shape: Any, sp: ShardingPolicy) -> Any:
    """Build a PartitionSpec pytree mirroring a params(-shape) pytree.

    Leaves under a ``layers``/``enc_layers`` subtree are stacked (leading L
    dim); everything else is unstacked.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    specs = []
    for path, leaf in flat:
        keys = [getattr(k, "key", getattr(k, "idx", "?")) for k in path]
        spath = "/".join(str(k) for k in keys)
        n_stack = 1 if any(str(k).endswith("layers") for k in keys) else 0
        shape = getattr(leaf, "shape", ())
        specs.append(_spec_for(spath, tuple(shape), sp, n_stack))
    return jax.tree_util.tree_unflatten(treedef, specs)
