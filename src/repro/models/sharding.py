"""Logical-axis sharding system (t5x-style rules).

Every parameter leaf is annotated with a tuple of *logical axis names*
(one per array dim).  A *rule set* maps logical names to mesh axes; the same
model code then runs on any mesh.  Hillclimbing a sharding (EXPERIMENTS.md
§Perf) = editing a rule set, not the model.

Logical axes used by the zoo:
  embed      d_model dim               -> FSDP axis ("data") by default
  vocab      vocabulary                -> "model"
  heads      attention query heads     -> "model" when divisible, else None
  kv_heads   GQA kv heads              -> "model" when divisible, else None
  head_dim   per-head dim              -> None
  mlp        FFN hidden                -> "model"
  experts    MoE expert dim            -> "model" (expert parallelism)
  expert_mlp per-expert FFN hidden     -> None (experts already sharded)
  inner      SSM / RG-LRU channel dim  -> "model" (channel parallelism)
  state      SSM state dim             -> None
  conv       conv kernel width         -> None
  dt         SSM dt-rank               -> None
  layers     stacked-scan layer dim    -> None (never sharded)
  null       never sharded
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class AxisRules:
    """Mapping logical axis name -> mesh axis (str | tuple | None)."""

    def __init__(self, rules: dict, mesh: Mesh):
        self.rules = dict(rules)
        self.mesh = mesh
        self._sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def _mesh_size(self, axis) -> int:
        if axis is None:
            return 1
        if isinstance(axis, tuple):
            return int(np.prod([self._sizes[a] for a in axis]))
        return self._sizes[axis]

    def spec(self, logical_axes: tuple, shape: Optional[tuple] = None) -> P:
        """PartitionSpec for one leaf.  If ``shape`` is given, any mapping
        that does not divide the dim evenly is dropped (framework guard —
        uneven sharding is never silently requested)."""
        out, used = [], set()
        for i, name in enumerate(logical_axes):
            ax = self.rules.get(name)
            if ax is not None:
                key = tuple(ax) if isinstance(ax, tuple) else (ax,)
                if used & set(key):
                    ax = None          # a mesh axis may appear only once
                elif shape is not None and shape[i] % self._mesh_size(ax):
                    ax = None          # not divisible -> replicate this dim
                else:
                    used |= set(key)
            out.append(ax)
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    def sharding(self, logical_axes: tuple, shape=None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical_axes, shape))

    def tree_specs(self, axes_tree, shapes_tree=None):
        """Pytree of PartitionSpec matching a pytree of logical-axes tuples.

        shapes_tree: optional congruent tree of arrays / ShapeDtypeStructs
        (anything with .shape) — enables the divisibility guard.
        """
        is_ax = (lambda x: isinstance(x, tuple) and
                 all(isinstance(e, (str, type(None))) for e in x))
        if shapes_tree is None:
            return jax.tree.map(lambda ax: self.spec(ax), axes_tree,
                                is_leaf=is_ax)
        return jax.tree.map(
            lambda ax, sh: self.spec(ax, getattr(sh, "shape", sh)),
            axes_tree, shapes_tree, is_leaf=is_ax)


# ---------------------------------------------------------------------------
# rule sets.  "data" doubles as the FSDP axis: the d_model ("embed") dim of
# every weight is sharded over it, so param memory scales down with both mesh
# axes (2-D sharding = TP x FSDP, the MaxText default posture).  Multi-pod
# meshes keep params *replicated across pods* (pure DP on the pod axis); the
# gradient all-reduce over "pod" is then the only inter-pod collective, which
# is the right posture for low inter-pod bandwidth.


def default_rules(mesh: Mesh, *, fsdp: bool = True,
                  seq_shard_attn: bool = False) -> AxisRules:
    """TP over "model" + FSDP over "data".

    seq_shard_attn: archs whose head count does not divide the model axis
    (starcoder2 36H, paligemma 8H, whisper 8H, recurrentgemma 10H) shard the
    *sequence* dim of activations over "model" inside attention instead
    (context parallelism); their head dims stay replicated.
    """
    rules = {
        "embed": "data" if fsdp else None,
        "vocab": "model",
        "vocab_embed": None,   # see layers.init_embedding
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "mlp": "model",
        "experts": "model",
        "expert_mlp": None,
        "expert_embed": "data" if fsdp else None,
        "inner": "model",
        "state": None,
        "conv": None,
        "dt": None,
        "layers": None,
        "null": None,
        # activation logical axes
        "batch": ("pod", "data") if "pod" in mesh.axis_names else "data",
        "seq": "model" if seq_shard_attn else None,
        "act_heads": "model",
        "act_mlp": "model",
        "act_vocab": "model",
        "seq_kv": "model",    # partitioned-KV decode (FPP serving)
        # layer-boundary activations sequence-sharded over "model"
        # (Megatron-SP): shrinks the per-layer remat saves by the TP degree
        "act_seq": "model",
    }
    return AxisRules(rules, mesh)


def replicated_rules(mesh: Mesh) -> AxisRules:
    rules = {k: None for k in (
        "embed vocab vocab_embed heads kv_heads head_dim mlp experts "
        "expert_mlp expert_embed inner state conv dt layers null seq "
        "act_heads act_mlp act_vocab seq_kv act_seq").split()}
    rules["batch"] = ("pod", "data") if "pod" in mesh.axis_names else "data"
    return AxisRules(rules, mesh)


def batch_spec(rules: AxisRules, extra_dims: int = 1) -> P:
    """P for a [batch, ...] input."""
    return P(rules.rules["batch"], *([None] * extra_dims))


def constrain(x, rules: AxisRules, logical_axes: tuple):
    """with_sharding_constraint via logical names (no-op outside jit mesh)."""
    try:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(rules.mesh, rules.spec(logical_axes, x.shape)))
    except (ValueError, RuntimeError):
        return x
