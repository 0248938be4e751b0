"""Logical-axis -> mesh sharding rules (DESIGN.md §6).

Every parameter leaf carries logical axis names (``models.layers.mk``);
this module maps them onto mesh axes.  The contract:

  * a logical axis maps to a mesh axis only when that mesh axis exists,
    has size > 1, and divides the dimension — otherwise the dim is
    replicated (``None`` in the ``PartitionSpec``);
  * a mesh axis is consumed at most once per leaf (first dim wins);
  * with no active mesh every helper degrades to a no-op / replication,
    so single-device code paths never pay a constraint.

``current_mesh`` reads the global mesh that ``jax.set_mesh`` installs.
"""
from __future__ import annotations

from typing import Any, Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# mesh axes that carry the batch dimension of activations / inputs
BATCH_AXES = ("pod", "data")

# profile -> logical axis -> mesh axis preference (first admissible wins)
_RULES: dict[str, dict[str, tuple[str, ...]]] = {
    # tensor-parallel heads/ffn + FSDP over data for the embed axis
    "tp_fsdp": {
        "ffn": ("model",),
        "heads": ("model",),
        "kv_heads": ("model",),
        "expert": ("model",),
        "vocab": ("model",),
        "embed": ("data",),
        "q_lora": ("model",),
        "kv_lora": ("model",),
    },
    # pure ZeRO-3: shard the largest axis over every data-like mesh axis
    "fsdp": {
        "embed": ("data",),
        "ffn": ("data",),
        "vocab": ("data",),
        "expert": ("data",),
    },
    # serving tensor-parallel layout: weights split over model only
    "serve_tp": {
        "ffn": ("model",),
        "heads": ("model",),
        "kv_heads": ("model",),
        "expert": ("model",),
        "vocab": ("model",),
    },
}


def rules_for(profile: str) -> Mapping[str, tuple[str, ...]]:
    if profile not in _RULES:
        raise ValueError(f"unknown sharding profile {profile!r}")
    return _RULES[profile]


def current_mesh():
    """The mesh set by ``jax.set_mesh`` (an ``AbstractMesh``), or None."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def _mesh_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(mesh.shape)


def spec_for_leaf(shape: Sequence[int], axes: Sequence[str | None], mesh: Mesh,
                  rules: Mapping[str, tuple[str, ...]] | None = None) -> P:
    """PartitionSpec for one leaf; mesh axes of size 1 are dropped entirely."""
    if rules is None:
        rules = _RULES["tp_fsdp"]
    sizes = _mesh_sizes(mesh)
    used: set[str] = set()
    entries: list[str | None] = []
    for dim, name in zip(shape, axes):
        placed = None
        for mesh_axis in rules.get(name or "", ()):
            sz = sizes.get(mesh_axis, 1)
            if sz > 1 and mesh_axis not in used and dim % sz == 0:
                placed = mesh_axis
                used.add(mesh_axis)
                break
        entries.append(placed)
    while entries and entries[-1] is None:  # trailing Nones are implicit
        entries.pop()
    return P(*entries)


def param_shardings(values: Any, axes: Any, mesh: Mesh,
                    rules: Mapping[str, tuple[str, ...]] | None = None) -> Any:
    """values/axes pytrees (from ``layers.split``) -> NamedSharding pytree."""
    return jax.tree.map(
        lambda v, a: NamedSharding(mesh, spec_for_leaf(v.shape, a, mesh, rules)),
        values, axes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x),
    )


def _batch_entry(mesh: Mesh, batch_size: int | None) -> tuple[str, ...] | None:
    sizes = _mesh_sizes(mesh)
    picked = tuple(a for a in BATCH_AXES if sizes.get(a, 1) > 1)
    if not picked:
        return None
    total = 1
    for a in picked:
        total *= sizes[a]
    if batch_size is not None and batch_size % total:
        return None
    return picked


def batch_spec(mesh: Mesh, ndim: int, batch_size: int | None = None) -> P:
    """Shard dim 0 over the (pod, data) axes; replicate the rest."""
    entry = _batch_entry(mesh, batch_size)
    if entry is None:
        return P()
    return P(entry, *(None,) * (ndim - 1))


def batch_shardings(specs: Any, mesh: Mesh, profile: str | None = None) -> Any:
    """NamedSharding pytree for a batch of input ShapeDtypeStructs."""
    del profile  # batch layout is profile-independent in this build
    return jax.tree.map(
        lambda s: NamedSharding(
            mesh, batch_spec(mesh, s.ndim, batch_size=s.shape[0] if s.ndim else None)
        ),
        specs,
    )


def cache_shardings(cache_sds: Any, mesh: Mesh, batch_size: int | None = None) -> Any:
    """KV caches shard over batch (dim 0); non-batch leaves replicate."""

    def one(s):
        if s.ndim >= 1 and batch_size is not None and s.shape[0] == batch_size:
            return NamedSharding(mesh, batch_spec(mesh, s.ndim, batch_size=batch_size))
        return NamedSharding(mesh, P())

    return jax.tree.map(one, cache_sds)


def _constrain(x, spec: P):
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:  # spec not applicable on this mesh/shape
        return x


def constrain_act(x, profile: str | None = None, vocab_dim: bool = False):
    """Constrain an activation's batch dim over (pod, data); no-op off-mesh.

    ``vocab_dim=True`` marks logits: the last dim additionally shards over
    ``model`` when divisible (the unembed projection's natural layout).
    """
    del profile
    mesh = current_mesh()
    if mesh is None or x.ndim == 0:
        return x
    sizes = _mesh_sizes(mesh)
    entry = _batch_entry(mesh, x.shape[0])
    last = None
    if vocab_dim and x.ndim >= 2 and sizes.get("model", 1) > 1 \
            and x.shape[-1] % sizes["model"] == 0:
        last = "model"
    if entry is None and last is None:
        return x
    entries = [entry] + [None] * (x.ndim - 1)
    if last is not None:
        entries[-1] = last
    return _constrain(x, P(*entries))


def constrain_seq(x):
    """Megatron-SP residual layout: batch over (pod, data), seq over model."""
    mesh = current_mesh()
    if mesh is None or x.ndim < 3:
        return x
    sizes = _mesh_sizes(mesh)
    entry = _batch_entry(mesh, x.shape[0])
    seq = "model" if sizes.get("model", 1) > 1 and x.shape[1] % sizes["model"] == 0 \
        else None
    if entry is None and seq is None:
        return x
    return _constrain(x, P(entry, seq, *(None,) * (x.ndim - 2)))
