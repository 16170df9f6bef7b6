"""Logical-axis → physical-mesh sharding machinery.

Model code annotates every parameter with *logical* axis names (e.g.
``("embed", "mlp")`` for a (d_model, d_ff) matrix). A ``ShardingRules``
table maps logical names to physical mesh axes. This is how the same model
definition lowers onto the single-pod ``("data", "model")`` mesh, the
multi-pod ``("pod", "data", "model")`` mesh, and the tiny CPU test meshes,
and how the classical-FL (replicated, flat all-reduce) vs SFL (FSDP,
two-step reduce-scatter + cross-pod all-reduce) regimes are expressed as
*data* rather than as different model code.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]


def pad_to_multiple(n: int, m: int) -> int:
    """Smallest multiple of m that is >= n."""
    return ((n + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Mapping from logical parameter/activation axes to mesh axes.

    The defaults express the production sharding:
      * ``batch``   — data-parallel clients over ("pod", "data")
      * ``embed``   — FSDP (ZeRO-3-style) sharding of d_model over "data"
      * ``heads`` / ``mlp`` / ``vocab`` — tensor parallel over "model"
      * ``experts`` — expert parallel over "data"
    Classical-FL benchmark: ``replicated()`` turns FSDP off so gradient
    sync becomes a flat all-reduce (the paper's benchmark topology).
    """

    batch: Axis = ("pod", "data")
    fsdp: Axis = "data"            # weight d_model / stacked dims
    tensor: Axis = "model"         # heads / mlp / vocab columns
    expert: Axis = "model"         # MoE expert dim (EP over the TP axis:
                                   # dispatch stays within batch shards)
    sequence: Axis = None          # sequence parallelism (prefill)
    table: Mapping[str, Axis] = dataclasses.field(default_factory=dict)

    def axis_for(self, logical: Optional[str]) -> Axis:
        if logical is None:
            return None
        if logical in self.table:
            return self.table[logical]
        builtin = {
            "batch": self.batch,
            "embed": self.fsdp,
            "heads": self.tensor,
            "mlp": self.tensor,
            "vocab": self.tensor,
            "vocab_rows": self.fsdp,     # embedding-table rows (FSDP'd)
            "tensor_cols": self.tensor,  # embedding-table columns (TP'd)
            "experts": self.expert,
            "sequence": self.sequence,
            # never-sharded logical axes
            "layers": None,
            "head_dim": None,
            "kv_heads": None,
            "seq": None,
            "stack": None,
            "conv": None,
            "state": None,
            "lora": None,
            "classes": None,
        }
        if logical in builtin:
            return builtin[logical]
        return None

    def replicated(self) -> "ShardingRules":
        """Classical-FL benchmark: no FSDP; params replicated over data."""
        return dataclasses.replace(self, fsdp=None)

    def with_(self, **kw) -> "ShardingRules":
        return dataclasses.replace(self, **kw)


def logical_to_physical(rules: ShardingRules, logical: Sequence[Optional[str]]) -> P:
    """Convert a tuple of logical axis names into a PartitionSpec.

    A mesh axis may appear at most once in a PartitionSpec; later duplicate
    uses degrade to None (replicated on that dim) — this happens e.g. for
    (embed, mlp) weights when fsdp and tensor point at the same axis in
    degenerate test meshes.
    """
    used: set = set()
    spec = []
    for name in logical:
        ax = rules.axis_for(name)
        if ax is None:
            spec.append(None)
            continue
        ax_tuple = (ax,) if isinstance(ax, str) else tuple(ax)
        ax_tuple = tuple(a for a in ax_tuple if a not in used)
        if not ax_tuple:
            spec.append(None)
            continue
        used.update(ax_tuple)
        spec.append(ax_tuple if len(ax_tuple) > 1 else ax_tuple[0])
    return P(*spec)


def spec_tree(rules: ShardingRules, logical_tree) -> Any:
    """Map a pytree of logical-axis tuples to a pytree of PartitionSpecs."""
    return jax.tree.map(
        lambda lg: logical_to_physical(rules, lg),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
    )


def sharding_tree(mesh: Mesh, rules: ShardingRules, logical_tree) -> Any:
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        spec_tree(rules, logical_tree),
        is_leaf=lambda x: isinstance(x, P),
    )


def param_sharding_tree(mesh: Mesh, rules: ShardingRules, params, logical) -> Any:
    """A NamedSharding per parameter leaf from its logical axes; a mesh axis
    that does not divide its dim is dropped (see ``filter_valid_spec``).
    ``params`` may hold arrays or ShapeDtypeStructs."""
    return jax.tree.map(
        lambda x, lg: NamedSharding(
            mesh, filter_valid_spec(mesh, logical_to_physical(rules, lg), x.shape)),
        params, logical,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def shard_or_replicate(mesh: Mesh, x, spec: P):
    """Device-put with a named sharding (used by hosts feeding real runs)."""
    return jax.device_put(x, NamedSharding(mesh, spec))


def constrain(x, rules: ShardingRules, logical: Sequence[Optional[str]]):
    """with_sharding_constraint by logical names.

    A no-op exactly when no mesh is set (``jax.set_mesh``). Under a mesh an
    all-``None`` spec is still applied: it pins the value replicated, which
    is what makes the classical regime's gradient sync a flat all-reduce.
    Anything the constraint raises (an axis the mesh lacks, a bad spec)
    propagates."""
    if jax.sharding.get_abstract_mesh().empty:
        return x
    return jax.lax.with_sharding_constraint(x, logical_to_physical(rules, logical))


def filter_valid_spec(mesh: Mesh, spec: P, shape: Tuple[int, ...]) -> P:
    """Drop mesh axes that do not evenly divide the corresponding dim.

    Keeps GSPMD clean: rather than relying on implicit padding for
    non-divisible shardings we replicate that dimension. Callers that need
    head-padding (e.g. 56 heads on a 16-way tensor axis) pad parameters
    explicitly instead.
    """
    out = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            out.append(None)
            continue
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        extent = 1
        for a in axes:
            extent *= mesh.shape[a]
        out.append(ax if dim % extent == 0 else None)
    return P(*out)
