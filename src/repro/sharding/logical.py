"""Logical-axis sharding: map per-tensor logical axis names to mesh axes.

This is the framework's portable sharding layer (MaxText-style).  Every
parameter is declared as a :class:`ParamSpec` carrying *logical* axis names
("embed", "heads", "mlp", ...).  A :class:`LogicalRules` table maps logical
names to mesh axis names.  Divisibility is checked **per tensor**: if a
dimension does not divide evenly over the requested mesh axes, the rule
falls back to replication for that dimension instead of failing.  This is
what lets one rule table drive 10 heterogeneous architectures (e.g. gemma's
single KV head simply replicates where llama's 8 shard).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Declaration of one parameter: shape + logical axes + initializer."""

    shape: tuple[int, ...]
    logical: tuple[str | None, ...]
    init: str = "normal"  # normal | zeros | ones | scaled (fan-in scaled)
    dtype: Any = jnp.float32
    # scale used by "normal"; "scaled" uses 1/sqrt(fan_in) with fan_axis.
    scale: float = 0.02
    fan_axis: int = 0

    def __post_init__(self):
        if len(self.shape) != len(self.logical):
            raise ValueError(
                f"shape {self.shape} and logical {self.logical} rank mismatch"
            )


# Default rule table. Values are mesh axis names (str), tuples of mesh axes
# (sharded over their product), or None (replicated).
DEFAULT_RULES: dict[str, Any] = {
    # weight matrices: FSDP along the d_model ("embed") dimension, tensor
    # parallel along heads / mlp / vocab.
    "embed": "data",
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    # experts shard over "model" (EP) when the count divides; the greedy
    # per-tensor fallback otherwise leaves them replicated and the "mlp" /
    # "cap" dims pick the axis up instead (expert-TP)
    "experts": "model",
    "cap": "model",           # MoE capacity dim (dispatch tensors)
    "head_dim": None,
    "conv": None,
    "state": None,
    # activations
    "batch": ("pod", "data"),
    "seq": None,
    # sequence parallelism: the residual stream between layers is sharded
    # along S over the TP axis (Megatron SP) - this is what bounds the
    # scan-saved (L, B, S, d) activation carry at train time
    "sp_seq": "model",
    "cache_seq": "model",     # decode KV caches: sequence-sharded
    "long_seq": ("data", "model"),  # 500k decode, batch=1
    "act_embed": None,
    "act_heads": "model",
    "act_mlp": "model",
    "act_vocab": "model",
}


@dataclasses.dataclass
class LogicalRules:
    """Rule table bound to a mesh; resolves logical axes to PartitionSpecs."""

    mesh: Mesh
    rules: Mapping[str, Any] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_RULES)
    )

    def _mesh_axes_for(self, logical_name: str | None):
        if logical_name is None:
            return None
        axes = self.rules.get(logical_name, None)
        if axes is None:
            return None
        if isinstance(axes, str):
            axes = (axes,)
        # Keep only axes that exist in this mesh (single-pod meshes have no
        # "pod" axis).
        axes = tuple(a for a in axes if a in self.mesh.axis_names)
        return axes or None

    def partition_spec(
        self, shape: Sequence[int], logical: Sequence[str | None]
    ) -> P:
        """Resolve logical axes to a PartitionSpec with divisibility fallback.

        A mesh axis may be used by at most one tensor dimension; first come,
        first served (dims are processed left to right).
        """
        used: set[str] = set()
        out: list[Any] = []
        for dim, name in zip(shape, logical):
            axes = self._mesh_axes_for(name)
            if axes is None:
                out.append(None)
                continue
            axes = tuple(a for a in axes if a not in used)
            # greedily drop trailing axes until the product divides the dim
            while axes and dim % math.prod(self.mesh.shape[a] for a in axes):
                axes = axes[:-1]
            if not axes:
                out.append(None)
                continue
            used.update(axes)
            out.append(axes if len(axes) > 1 else axes[0])
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    def sharding(self, shape, logical) -> NamedSharding:
        return NamedSharding(self.mesh, self.partition_spec(shape, logical))


def logical_to_sharding(rules: LogicalRules, spec: ParamSpec) -> NamedSharding:
    return rules.sharding(spec.shape, spec.logical)


def spec_shardings(tree: Any, rules: LogicalRules) -> Any:
    """Map a ParamSpec tree to a NamedSharding tree."""
    return jax.tree.map(
        lambda s: logical_to_sharding(rules, s),
        tree,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def eval_shape_tree(tree: Any) -> Any:
    """Map a ParamSpec tree to jax.ShapeDtypeStruct leaves (no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
        tree,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def _init_one(spec: ParamSpec, key: jax.Array) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "scaled":
        fan_in = spec.shape[spec.fan_axis]
        std = 1.0 / math.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, spec.shape) * std).astype(spec.dtype)
    if spec.init == "normal":
        return (jax.random.normal(key, spec.shape) * spec.scale).astype(
            spec.dtype
        )
    raise ValueError(f"unknown init {spec.init}")


def materialize(
    tree: Any,
    key: jax.Array,
    rules: LogicalRules | None = None,
) -> Any:
    """Instantiate a ParamSpec tree into arrays (optionally sharded)."""
    leaves, treedef = jax.tree.flatten(
        tree, is_leaf=lambda x: isinstance(x, ParamSpec)
    )
    keys = jax.random.split(key, len(leaves))
    vals = []
    for spec, k in zip(leaves, keys):
        v = _init_one(spec, k)
        if rules is not None:
            v = jax.device_put(v, logical_to_sharding(rules, spec))
        vals.append(v)
    return jax.tree.unflatten(treedef, vals)


# Alternative rule profiles (the hillclimbing levers in EXPERIMENTS.md §Perf)

# Pure data parallelism: for models too small to amortize 16-way TP
# activation collectives, the model axis carries batch instead of weights.
PROFILE_DP: dict[str, Any] = dict(
    DEFAULT_RULES,
    **{
        "vocab": None, "heads": None, "kv_heads": None, "mlp": None,
        "experts": None, "cap": None, "sp_seq": None,
        "act_heads": None, "act_mlp": None, "act_vocab": None,
        "batch": ("pod", "data", "model"),
    },
)

# Serving: weights resident (TP over "model", NO FSDP - a per-token FSDP
# all-gather would move the whole model over ICI every decode step),
# batch over ("pod","data"), KV cache sequence-sharded over "model".
PROFILE_SERVE: dict[str, Any] = dict(
    DEFAULT_RULES,
    **{"embed": None},
)

PROFILES = {"tp": dict(DEFAULT_RULES), "dp": PROFILE_DP, "serve": PROFILE_SERVE}


def mesh_axis_size(mesh: Mesh, axis) -> int:
    """Size of a (possibly folded tuple of) mesh axis(es)."""
    if isinstance(axis, (tuple, list)):
        return math.prod(mesh.shape[a] for a in axis)
    return mesh.shape[axis]


def folded_axis_index(axis):
    """axis_index generalized to folded tuples (row-major), for use inside
    shard_map bodies."""
    import jax

    if isinstance(axis, (tuple, list)):
        idx = jax.lax.axis_index(axis[0])
        for a in axis[1:]:
            idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        return idx
    return jax.lax.axis_index(axis)


def param_count(tree: Any) -> int:
    leaves = jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, ParamSpec))
    total = 0
    for leaf in leaves:
        shape = leaf.shape if isinstance(leaf, ParamSpec) else np.shape(leaf)
        total += int(math.prod(shape))
    return total
