"""Sharding rules for every tree the launch/serve layers move onto a mesh.

One place owns the mapping from (param tree | batch dict | KV cache) to
``PartitionSpec``s over the canonical ``("data", "model")`` mesh (with an
optional leading ``"pod"`` axis for multi-pod meshes):

* LM params follow the Megatron layout — attention q/k/v and MLP up/gate are
  column-parallel (output dim over ``model``), attention o and MLP down are
  row-parallel (input dim over ``model``), embeddings shard the vocab dim,
  MoE experts shard the expert dim.  ``fsdp=True`` additionally shards one
  remaining dim over the data axes (ZeRO-3 style).
* Batches shard their leading (batch) dim over the data axes.
* KV caches mirror the split-K flash-decode layout in
  ``repro.models.layers``: sequence over ``model`` (plus the data axes for
  batch-1 long-context), batch over the data axes.

Every emitted spec passes through :func:`validate_spec`, which degrades any
axis that does not evenly divide the corresponding dim to replicated — the
same tree of rules therefore works for the 1×1 CPU smoke mesh, the 16×16
production pod, and the 2×16×16 multi-pod mesh.

It also holds the ambient mesh that the model's ``shard_map`` paths
read: :func:`set_mesh` / :func:`get_active_mesh`.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Mapping, Optional, Tuple

import jax
from jax.sharding import Mesh, PartitionSpec as P

__all__ = [
    "set_mesh",
    "get_active_mesh",
    "batch_axes",
    "data_spec",
    "axes_size",
    "postings_spec",
    "plan_specs",
    "validate_spec",
    "lm_param_specs",
    "pna_param_specs",
    "recsys_param_specs",
    "opt_state_specs",
    "batch_specs",
    "cache_specs",
    "shard_rows",
    "device_count",
]


# ---------------------------------------------------------------------------
# Ambient mesh
# ---------------------------------------------------------------------------

_ACTIVE_MESH: Optional[Mesh] = None


def set_mesh(mesh: Optional[Mesh]) -> Optional[Mesh]:
    """Make ``mesh`` the ambient mesh the model's ``shard_map`` paths
    read through :func:`get_active_mesh` (``None`` clears it).

    The mesh is only recorded here, never passed to ``jax.set_mesh``:
    that would switch tracing into explicit-sharding mode, which the
    model code (auto-sharded jit plus explicit ``shard_map``) is not
    written for."""
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh
    return mesh


def get_active_mesh() -> Optional[Mesh]:
    """The mesh recorded by :func:`set_mesh`, or None."""
    return _ACTIVE_MESH


# ---------------------------------------------------------------------------
# Axis helpers
# ---------------------------------------------------------------------------


def batch_axes(mesh) -> Tuple[str, ...]:
    """The data-parallel axes: every mesh axis except ``model``."""
    return tuple(a for a in mesh.axis_names if a != "model")


def data_spec(mesh):
    """The data axes as a single PartitionSpec entry (str or tuple)."""
    dp = batch_axes(mesh)
    if not dp:
        return None
    return dp if len(dp) > 1 else dp[0]


def axes_size(mesh, entry) -> int:
    """Product of mesh-axis sizes named by one PartitionSpec entry."""
    if entry is None:
        return 1
    names = entry if isinstance(entry, tuple) else (entry,)
    size = 1
    for a in names:
        size *= int(mesh.shape[a])
    return size


def postings_spec(mesh) -> P:
    """Spec of the sharded engine's stacked postings matrix (S, W): the
    shard dim over the data axes, each shard's postings row unsplit."""
    return P(data_spec(mesh), None)


def plan_specs(mesh) -> Tuple[P, P]:
    """Specs of a sharded lowered plan's two stacks — cells (S, 4, C)
    and stage segments (S, 2, n_stages * group_width): shard dim over
    the data axes, per-shard layout unsplit."""
    dp = data_spec(mesh)
    return P(dp, None, None), P(dp, None, None)


def validate_spec(mesh, spec, shape) -> P:
    """Clamp ``spec`` to ``shape``: any entry whose axis-size product does
    not evenly divide the dim (or that names an axis the mesh lacks) is
    replaced by None (replicated).  Raises if the spec is longer than the
    shape — that is a real rank bug, not a divisibility issue."""
    entries = tuple(spec)
    if len(entries) > len(shape):
        raise ValueError(f"spec {spec} has more entries than shape {shape}")
    entries = entries + (None,) * (len(shape) - len(entries))
    out = []
    names = set(mesh.axis_names)
    # A PartitionSpec may legally be shorter than the array rank (the
    # trailing dims are replicated), so this zip must not be strict.
    for dim, entry in zip(shape, entries, strict=False):
        if entry is None:
            out.append(None)
            continue
        req = entry if isinstance(entry, tuple) else (entry,)
        if not set(req) <= names:
            out.append(None)
            continue
        size = axes_size(mesh, entry)
        out.append(entry if size > 1 and dim % size == 0 else None)
    # Drop trailing Nones for a canonical form (P() == fully replicated).
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def _path_keys(path) -> Tuple[str, ...]:
    keys = []
    for p in path:
        if hasattr(p, "key"):
            keys.append(str(p.key))
        elif hasattr(p, "name"):
            keys.append(str(p.name))
    return tuple(keys)


def _with_fsdp(entries: list, shape, mesh, dp) -> list:
    """ZeRO-3 flavor: shard the largest still-replicated dim over data."""
    if dp is None:
        return entries
    size = axes_size(mesh, dp)
    free = [
        i for i, e in enumerate(entries)
        if e is None and shape[i] % size == 0 and shape[i] >= size
    ]
    if free:
        best = max(free, key=lambda i: shape[i])
        entries[best] = dp
    return entries


# ---------------------------------------------------------------------------
# Parameter trees
# ---------------------------------------------------------------------------

_COLUMN_PARALLEL = {"q", "k", "v", "up", "gate", "encode", "router"}
_ROW_PARALLEL = {"o", "down", "decode"}


def _lm_rule(keys: Tuple[str, ...], shape, mesh, fsdp: bool, dp) -> P:
    """Megatron placement for one LM leaf; ``keys`` is the dict-key path."""
    stacked = "layers" in keys  # stacked leaves carry a leading (L,) axis
    lead = 1 if stacked else 0
    name = keys[-1] if keys else ""
    owner = keys[-2] if len(keys) >= 2 else ""
    entries = [None] * len(shape)

    if name == "embed":
        entries[0] = "model"  # vocab-dim sharded
    elif name == "lm_head":
        entries[-1] = "model"
    elif owner == "moe" and len(shape) - lead >= 2:
        entries[lead] = "model"  # experts over model
    elif owner in _COLUMN_PARALLEL or name in _COLUMN_PARALLEL:
        if name == "kernel" or name == "bias" or owner in _COLUMN_PARALLEL:
            entries[-1] = "model"  # output dim
    elif owner in _ROW_PARALLEL or name in _ROW_PARALLEL:
        if len(shape) - lead >= 2:
            entries[-2] = "model"  # input dim; bias stays replicated
    # norms / scalars: replicated.

    if fsdp:
        entries = _with_fsdp(entries, shape, mesh, dp)
    return validate_spec(mesh, P(*entries), shape)


def lm_param_specs(params, mesh, fsdp: bool = False):
    """PartitionSpec tree for an LM parameter tree (Megatron + opt. ZeRO-3)."""
    dp = data_spec(mesh)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _lm_rule(_path_keys(path), leaf.shape, mesh, fsdp, dp),
        params,
    )


def _generic_rule(keys: Tuple[str, ...], shape, mesh) -> P:
    """Column-parallel kernels, vocab-sharded embedding tables, replicated
    norms — the rule shared by the GNN and recsys families."""
    name = keys[-1] if keys else ""
    owner = keys[-2] if len(keys) >= 2 else ""
    entries = [None] * len(shape)
    if any("emb" in k for k in (name, owner)) and len(shape) >= 2:
        entries[-2] = "model"  # (vocab, dim) tables: shard the vocab dim
    elif name in _ROW_PARALLEL or owner in _ROW_PARALLEL:
        if len(shape) >= 2:
            entries[-2] = "model"
    elif len(shape) >= 2:
        entries[-1] = "model"
    return validate_spec(mesh, P(*entries), shape)


def pna_param_specs(params, mesh):
    """PartitionSpec tree for the PNA GNN parameter tree."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _generic_rule(_path_keys(path), leaf.shape, mesh),
        params,
    )


def recsys_param_specs(params, mesh):
    """PartitionSpec tree for a recsys parameter tree (embedding tables
    vocab-sharded over ``model``, towers column-parallel)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _generic_rule(_path_keys(path), leaf.shape, mesh),
        params,
    )


def opt_state_specs(param_specs):
    """AdamW state specs: moments follow the params, step is replicated."""
    return {"mu": param_specs, "nu": param_specs, "step": P()}


# ---------------------------------------------------------------------------
# Batches and caches
# ---------------------------------------------------------------------------


def batch_specs(
    shapes: Mapping[str, Tuple[int, ...]],
    mesh,
    field_rules: Optional[Dict[str, Any]] = None,
) -> Dict[str, P]:
    """Specs for a batch dict: leading dim over the data axes unless a
    field rule says otherwise.  ``shapes`` maps field -> shape tuple."""
    dp = data_spec(mesh)
    out = {}
    for name, shape in shapes.items():
        rule = (field_rules or {}).get(name)
        if rule is None:
            rule = P(dp) if shape else P()
        out[name] = validate_spec(mesh, rule, shape)
    return out


def cache_specs(cache, mesh):
    """Specs for a stacked KV cache (leading ``n_layers`` axis), mirroring
    the split-K flash-decode layout of ``repro.models.layers``:

    * batch divisible by the data axes → batch over data, sequence over
      ``model``;
    * batch == 1 (long context) → sequence over every axis;
    * anything else → replicated (the dense cached-attention path).

    ``None`` leaves (absent int8 scales) map to ``None`` so the result
    tree-maps against the cache itself with ``is_leaf=lambda x: x is None``.
    """
    dp = data_spec(mesh)
    dp_size = axes_size(mesh, dp)
    model = int(mesh.shape["model"]) if "model" in mesh.axis_names else 1
    all_axes = tuple(mesh.axis_names)
    all_spec = all_axes if len(all_axes) > 1 else (all_axes[0] if all_axes else None)

    def one(leaf):
        if leaf is None:
            return None
        shape = leaf.shape
        if len(shape) < 4:  # length scalar and friends
            return P()
        b, s = shape[1], shape[2]  # (L, B, S, H[, D])
        if dp_size > 1 and b % dp_size == 0 and model > 1 and s % model == 0:
            b_spec, s_spec = dp, "model"
        elif b == 1 and s % (model * dp_size) == 0 and model * dp_size > 1:
            b_spec, s_spec = None, all_spec
        else:
            return validate_spec(mesh, P(), shape)
        return validate_spec(
            mesh, P(None, b_spec, s_spec, *([None] * (len(shape) - 3))), shape
        )

    return jax.tree.map(one, cache, is_leaf=lambda x: x is None)


def shard_rows(n_rows: int, mesh) -> int:
    """Rows of padding needed to split ``n_rows`` evenly over the data axes."""
    dp_size = axes_size(mesh, data_spec(mesh))
    return (-n_rows) % max(dp_size, 1)


def device_count(mesh) -> int:
    return int(math.prod(int(mesh.shape[a]) for a in mesh.axis_names))
