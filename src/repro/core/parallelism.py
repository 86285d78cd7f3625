"""Adaptive parallelism — FIXAR §V-B mapped onto JAX meshes.

The AAP core runs the *same* PE array under two dataflows:

  * inference  -> intra-layer parallelism (columns of W interleaved across
                  cores; one vector finishes N× faster),
  * training   -> intra-batch parallelism (each core owns whole MVMs for
                  different batch elements).

On a TPU mesh the exact analogue is a *phase-dependent logical-axis rule
set*: the same parameter pytree gets different `NamedSharding`s depending on
whether we are lowering `train_step` or `serve_step`.  Logical tensor axes
(named below) are mapped to mesh axes by `ShardingRules`; models annotate
every parameter and activation with logical axes and never mention mesh axes
directly — swap the rules, swap the parallelism.

Logical axes used across the framework
--------------------------------------
  batch      global batch
  seq        sequence (activations)
  kv_seq     KV-cache / recurrence sequence dimension
  embed      d_model
  q_heads    query heads
  kv_heads   KV heads
  head_dim   per-head dim
  mlp        FFN hidden
  vocab      vocabulary
  experts    MoE expert dimension
  layers     stacked-scan layer dimension (never sharded)
  state      recurrent state channels (rwkv/rg-lru)
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """logical axis name -> mesh axis (or axes, or None=replicated).

    A logical axis may also map to a *fallback chain* (tuple of candidate
    mesh axes tried in order) by listing it in `rules` as a tuple of tuples
    — but the common case is a single mesh axis or an axis pair like
    ("pod", "data").
    """

    rules: dict[str, MeshAxes]
    phase: str  # "train" | "serve" — documentation + assertions only

    def mesh_axes(self, logical: Sequence[Optional[str]],
                  shape: Optional[Sequence[int]] = None,
                  mesh: Optional[Mesh] = None) -> P:
        """Build a PartitionSpec; if `shape`+`mesh` given, drop mesh axes
        that do not evenly divide the corresponding dimension (e.g. 4 query
        heads cannot shard over model=16 — replicate instead)."""
        used: list[str] = []
        out = []
        for i, ax in enumerate(logical):
            m = self.rules.get(ax) if ax is not None else None
            if m is not None:
                flat = (m,) if isinstance(m, str) else tuple(m)
                if any(f in used for f in flat):
                    m = None
                elif shape is not None and mesh is not None:
                    total = 1
                    for f in flat:
                        total *= mesh.shape[f]
                    if shape[i] % total != 0:
                        m = None
                if m is not None:
                    used.extend(flat)
            out.append(m)
        return P(*out)

    def spec(self, *logical: Optional[str]) -> P:
        return self.mesh_axes(logical)

    def named(self, mesh: Mesh, *logical: Optional[str]) -> NamedSharding:
        return NamedSharding(mesh, self.mesh_axes(logical))

    def named_for(self, mesh: Mesh, shape: Sequence[int],
                  *logical: Optional[str]) -> NamedSharding:
        return NamedSharding(mesh, self.mesh_axes(logical, shape, mesh))


# ---------------------------------------------------------------------------
# Phase presets — the FIXAR dataflow switch
# ---------------------------------------------------------------------------

# Batch axes: on the multi-pod mesh the pod axis composes with data for
# hierarchical data parallelism (reduce-scatter intra-pod, all-reduce
# inter-pod comes out of XLA's hierarchical collective lowering).


def _batch_axes(mesh: Mesh) -> MeshAxes:
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


def train_rules(mesh: Mesh, *, shard_seq: bool = False) -> ShardingRules:
    """Intra-batch parallelism (FIXAR training dataflow) + Megatron TP.

    batch over (pod,)data; contracting/feature dims over model.
    """
    return ShardingRules(
        rules={
            "batch": _batch_axes(mesh),
            "seq": "model" if shard_seq else None,  # sequence-parallel option
            "kv_seq": None,
            "embed": None,
            "q_heads": "model",
            "kv_heads": "model",
            # NO head_dim fallback in training: sharding head_dim makes the
            # attention score einsum contract over a sharded axis, inserting
            # a per-layer psum of the (B,S,·) score tensor (measured: gemma3
            # train collective 3.8 s -> 12.9 s, §Perf opt-1 revision).  The
            # fallback lives in serve_rules where the win is KV-cache
            # memory, not score locality.
            "head_dim": None,
            "mlp": "model",
            "vocab": "model",
            "experts": "model",
            "exp_cap": "data",       # expert capacity dim follows tokens
            "expert_ffn": "data",    # ZeRO-style: expert d_ff over data
            "layers": None,
            "state": "model",
            "heads_rwkv": "model",
        },
        phase="train",
    )


def serve_rules(mesh: Mesh, *, shard_kv_seq: bool = False,
                prefer_head_dim: bool = False,
                shard_expert_ffn: bool = True) -> ShardingRules:
    """Intra-layer parallelism (FIXAR inference dataflow).

    Model (feature) dims over `model`; batch over `data` when it exists;
    for single-request long-context decode (`long_500k`) the KV cache /
    recurrence dim is sharded over `data` instead (sequence-parallel decode)
    so 256 chips stay busy on one request — the batch axis would idle.

    `prefer_head_dim`: set when the arch's kv_heads does not divide the
    model axis — the KV cache can only TP-shard on head_dim then, and the
    q projections must FOLLOW that layout or XLA reshards the whole cache
    every layer (measured: dbrx decode 53 GB/step of involuntary cache
    all-gathers, §Perf opt-5).

    `shard_expert_ffn`: ZeRO-shard expert weights over `data`.  Required
    when bf16 params exceed HBM at model-parallel only (dbrx: 16.5 GB/dev);
    turn OFF when they fit (moonshot: 3.5 GB/dev) — resident weights avoid
    the per-layer FSDP gather that dominates small-token decode steps
    (measured §Perf opt-5).
    """
    head_axes = ({"q_heads": None, "kv_heads": None, "head_dim": "model"}
                 if prefer_head_dim else
                 {"q_heads": "model", "kv_heads": "model",
                  "head_dim": "model"})
    return ShardingRules(
        rules={
            "batch": None if shard_kv_seq else _batch_axes(mesh),
            "seq": None,
            "kv_seq": "data" if shard_kv_seq else None,
            "embed": None,
            **head_axes,
            "mlp": "model",
            "vocab": "model",
            "experts": "model",
            "exp_cap": "data" if not shard_kv_seq else None,
            "expert_ffn": "data" if shard_expert_ffn else None,
            "layers": None,
            "state": "model",
            "heads_rwkv": "model",
        },
        phase="serve",
    )


def rules_for(mesh: Mesh, phase: str, **kw) -> ShardingRules:
    if phase == "train":
        return train_rules(mesh, **kw)
    if phase == "serve":
        return serve_rules(mesh, **kw)
    raise ValueError(f"unknown phase {phase!r}")


# ---------------------------------------------------------------------------
# Applying rules to annotated pytrees
# ---------------------------------------------------------------------------


class Logical:
    """A pytree-leaf annotation: array (or ShapeDtypeStruct) + logical axes."""

    __slots__ = ("axes",)

    def __init__(self, *axes: Optional[str]):
        self.axes = axes

    def __repr__(self):
        return f"Logical{self.axes}"


def tree_shardings(spec_tree, mesh: Mesh, rules: ShardingRules, shape_tree=None):
    """Map a pytree of `Logical` annotations to NamedShardings.

    If `shape_tree` (matching pytree of ShapeDtypeStruct/arrays) is given,
    shardings are divisibility-checked per leaf.
    """
    if shape_tree is None:
        return jax.tree.map(
            lambda l: rules.named(mesh, *l.axes),
            spec_tree,
            is_leaf=lambda x: isinstance(x, Logical),
        )
    return jax.tree.map(
        lambda l, s: rules.named_for(mesh, s.shape, *l.axes),
        spec_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, Logical),
    )


def tree_pspecs(spec_tree, rules: ShardingRules):
    return jax.tree.map(
        lambda l: rules.mesh_axes(l.axes),
        spec_tree,
        is_leaf=lambda x: isinstance(x, Logical),
    )


def constrain(x: jax.Array, rules: Optional[ShardingRules],
              *logical: Optional[str]) -> jax.Array:
    """with_sharding_constraint via logical axes (shape-aware; no-op when
    rules is None or outside a mesh context)."""
    if rules is None:
        return x
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty:
            return x
        spec = rules.mesh_axes(logical, x.shape, mesh)
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x


__all__ = ["ShardingRules", "Logical", "train_rules", "serve_rules",
           "rules_for", "tree_shardings", "tree_pspecs", "constrain"]
