"""Logical-axis -> mesh-axis partitioning rules (flax-style, dependency-free).

Every parameter builder in repro.models returns a spec pytree whose leaves
are tuples of logical axis names (or None). This module maps those to
jax.sharding.PartitionSpec / NamedSharding for a given mesh.

Default strategy (the paper-agnostic, 1000-node posture — DESIGN.md §6):

  model axis  : tensor-parallel dims — heads / kv_heads / ffn / vocab /
                experts (EP)
  data axis   : FSDP/ZeRO-3 — the "embed" dim of weight matrices is sharded
                over data; GSPMD all-gathers weights per layer inside the
                scan and reduce-scatters their gradients
  pod axis    : pure data parallelism; weights REPLICATED across pods so
                gradient sync over the slow DCN hop is a single all-reduce
                of already-reduce-scattered shards (hierarchical reduction)

A mesh axis is consumed at most once per PartitionSpec (first logical axis
wins; later mentions degrade to replication) so specs like
("embed", "embed") stay valid.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

MeshAxes = tuple[str, ...] | str | None


def abstract_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """A device-free mesh of the given axis sizes and names.

    Rule/spec logic only needs names and sizes, not real devices, so
    tests build meshes through this.
    """
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """logical axis name -> mesh axis (or tuple of mesh axes)."""

    rules: dict[str, MeshAxes]

    def lookup(self, logical: Optional[str]) -> MeshAxes:
        if logical is None:
            return None
        return self.rules.get(logical, None)

    def replace(self, **kv) -> "AxisRules":
        return AxisRules({**self.rules, **kv})


DEFAULT_RULES = AxisRules(
    {
        "batch": ("pod", "data"),
        "vocab": "model",
        "heads": "model",
        "kv_heads": "model",
        "ffn": "model",
        "experts": "model",
        "embed": "data",   # FSDP: weight-matrix d_model dim sharded over data
        "layers": None,    # stacked-layer leading axis: never sharded
    }
)

# TP-only variant (no FSDP) — used by the perf loop and small models where
# weight all-gathers cost more than the memory they save.
TP_ONLY_RULES = DEFAULT_RULES.replace(embed=None)

# Pure data parallelism over the whole mesh: for small models whose head
# counts don't divide the model axis, TP wastes it — attention then shards
# only 16/256 ways (measured 26x useless-flops factor on smollm train_4k).
# Weights replicated (they're small by construction of this regime).
DP_ONLY_RULES = AxisRules(
    {
        "batch": ("pod", "data", "model"),
        "layers": None,
    }
)

# Expert-parallelism over the DATA axis: expert weights live fully sharded
# (experts x data, ffn x model), tokens all-to-all to their experts'
# owners (GShard). Removes the per-layer-per-microbatch FSDP all-gather of
# expert weights that dominates the 128-expert models' train cells
# (weights >> activations: gathering 3.3 GB/layer of experts vs ~0.2 GB of
# tokens — see EXPERIMENTS.md §Perf).
EP_DATA_RULES = DEFAULT_RULES.replace(experts="data", embed=None)

# Sequence parallelism (Korthikanti et al. 2022): the residual stream is
# sequence-sharded over 'model' between blocks, turning each Megatron
# activation all-reduce (2(g-1)/g x bytes) into a reduce-scatter + later
# all-gather pair (half the wire bytes) and shrinking the norm/residual
# working set by the TP width.
SP_RULES = DEFAULT_RULES.replace(seq="model")

# EP over data + pure DP (batch over data AND model) for the dense parts:
# removes Megatron TP activation all-reduces entirely; dense/attention
# weights replicate (grads all-reduce once per microbatch — the measured
# trade, see §Perf iteration log).
EP_DP_RULES = AxisRules(
    {
        "batch": ("pod", "data", "model"),
        "experts": "data",
        "ffn": "model",     # expert ffn dim only (dense FFN uses 'ffn' too —
                            # batch consumes 'model' first on activations)
        "layers": None,
    }
)


def spec_to_pspec(spec: tuple, rules: AxisRules, mesh: Mesh) -> PartitionSpec:
    """Map one leaf spec (tuple of logical names) to a PartitionSpec."""
    used: set[str] = set()
    out = []
    for logical in spec:
        mesh_axes = rules.lookup(logical)
        if mesh_axes is None:
            out.append(None)
            continue
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        # keep only axes present in the mesh and not already consumed
        usable = tuple(
            a for a in mesh_axes if a in mesh.axis_names and a not in used
        )
        used.update(usable)
        if not usable:
            out.append(None)
        elif len(usable) == 1:
            out.append(usable[0])
        else:
            out.append(usable)
    return PartitionSpec(*out)


def _is_spec_leaf(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x)


def param_shardings(spec_tree, rules: AxisRules, mesh: Mesh):
    """Map a spec pytree to a NamedSharding pytree."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, spec_to_pspec(s, rules, mesh)),
        spec_tree,
        is_leaf=_is_spec_leaf,
    )


def param_pspecs(spec_tree, rules: AxisRules, mesh: Mesh):
    return jax.tree.map(
        lambda s: spec_to_pspec(s, rules, mesh),
        spec_tree,
        is_leaf=_is_spec_leaf,
    )


def batch_pspec(mesh: Mesh, rules: AxisRules = DEFAULT_RULES) -> PartitionSpec:
    """PartitionSpec for the leading batch dim of inputs/activations."""
    axes = rules.lookup("batch")
    if isinstance(axes, str):
        axes = (axes,)
    usable = tuple(a for a in axes if a in mesh.axis_names)
    if not usable:
        return PartitionSpec(None)
    return PartitionSpec(usable if len(usable) > 1 else usable[0])


def zero1_opt_sharding(param_sharding: NamedSharding, shape: tuple[int, ...], mesh: Mesh):
    """ZeRO-1: additionally shard optimizer moments over 'data' along the
    largest currently-unsharded dim (falls back to the param sharding)."""
    spec = list(param_sharding.spec) + [None] * (len(shape) - len(param_sharding.spec))
    if "data" in [a for s in spec if s for a in (s if isinstance(s, tuple) else (s,))]:
        return param_sharding
    # find largest unsharded, divisible dim
    data_size = mesh.shape.get("data", 1)
    best, best_dim = -1, -1
    for i, (s, n) in enumerate(zip(spec, shape)):
        if s is None and n % data_size == 0 and n > best:
            best, best_dim = n, i
    if best_dim < 0:
        return param_sharding
    spec[best_dim] = "data"
    return NamedSharding(mesh, PartitionSpec(*spec))
