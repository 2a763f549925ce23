"""Logical-axis sharding rules (MaxText-style) -> PartitionSpecs.

Every parameter / cache initializer exposes a parallel ``*_axes`` tree of
logical axis names; this module maps them to physical mesh axes with
divisibility-checked fallback to replication (MQA kv_heads=1 cannot shard
16 ways — it replicates instead of erroring).

Default layout (the baseline recorded in EXPERIMENTS.md §Roofline):

  batch/frames        -> ("pod", "data")       data parallel across pods
  vocab/heads/mlp/experts -> "model"           tensor + expert parallel
  embed (weight d_model)  -> "data"            FSDP/ZeRO-3: params+optimizer
                                               sharded over the data axis
  decode kv cache seq -> "model"               long caches sharded along seq
  decode cache batch  -> ("pod", "data")

Alternative layouts for §Perf hillclimbing are expressed as rule overrides
(see ``make_rules(overrides=...)``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]


DEFAULT_RULES: Dict[str, Axis] = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": "data",                 # FSDP axis for weight d_model dims
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "experts": "model",
    "experts_router": None,
    "expert_embed": "data",          # FSDP like "embed"; override to None
    #                                  to replicate expert d_model (MoE perf)
    "heads_d": "model",              # rwkv square mixing matrices (out dim)
    "inner": "model",                # mamba d_inner
    "inner2": "model",
    "layers": None,
    "cache_batch": ("pod", "data"),
    "cache_seq": "model",
    "cache_kv": None,
    "stream": "stream",              # fleet serving: leading camera-stream
    #                                  axis of stacked per-stream batches
    #                                  (distributed.multistream)
}


def stream_mesh(n_devices: Optional[int] = None) -> Mesh:
    """One-axis ``("stream",)`` mesh over the local devices, for sharding
    stacked per-stream batches (``distributed.multistream``).  ``n_devices``
    takes a prefix of ``jax.devices()`` (default: all of them)."""
    devs = jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(f"asked for {n_devices} devices, "
                             f"have {len(devs)}")
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), ("stream",))


def make_rules(overrides: Optional[Mapping[str, Axis]] = None) -> Dict[str, Axis]:
    rules = dict(DEFAULT_RULES)
    if overrides:
        rules.update(overrides)
    return rules


def _mesh_axes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _resolve_axis(ax: Axis, dim: int, mesh_axes: Dict[str, int]) -> Axis:
    """Divisibility-checked physical axis (or partial tuple prefix)."""
    if ax is None:
        return None
    axes = (ax,) if isinstance(ax, str) else tuple(ax)
    axes = tuple(a for a in axes if a in mesh_axes)
    if not axes:
        return None
    size = int(np.prod([mesh_axes[a] for a in axes]))
    if size and dim % size == 0:
        return axes if len(axes) > 1 else axes[0]
    # try shrinking the tuple (e.g. batch=1 cannot shard at all)
    for end in range(len(axes) - 1, 0, -1):
        size = int(np.prod([mesh_axes[a] for a in axes[:end]]))
        if dim % size == 0:
            return axes[:end] if end > 1 else axes[0]
    return None


def spec_for(logical: Sequence[Union[str, None]], shape: Sequence[int],
             mesh: Mesh, rules: Mapping[str, Axis]) -> P:
    """One PartitionSpec from logical axis names + the actual shape."""
    ma = _mesh_axes(mesh)
    used: set = set()
    out = []
    for name, dim in zip(logical, shape):
        ax = _resolve_axis(rules.get(name) if name else None, dim, ma)
        # a mesh axis may appear at most once in a spec
        if ax is not None:
            axs = (ax,) if isinstance(ax, str) else ax
            if any(a in used for a in axs):
                ax = None
            else:
                used.update(axs)
        out.append(ax)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def tree_specs(axes_tree: Any, shape_tree: Any, mesh: Mesh,
               rules: Optional[Mapping[str, Axis]] = None) -> Any:
    """Map a logical-axes tree + matching shape tree -> PartitionSpec tree."""
    rules = rules or DEFAULT_RULES

    def one(ax, leaf):
        shape = leaf.shape if hasattr(leaf, "shape") else tuple(leaf)
        assert len(ax) == len(shape), (ax, shape)
        return spec_for(ax, shape, mesh, rules)

    return jax.tree.map(one, axes_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and
                        all(isinstance(e, (str, type(None))) for e in x))


def tree_shardings(axes_tree: Any, shape_tree: Any, mesh: Mesh,
                   rules: Optional[Mapping[str, Axis]] = None) -> Any:
    specs = tree_specs(axes_tree, shape_tree, mesh, rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def batch_specs(kind: str, mesh: Mesh,
                rules: Optional[Mapping[str, Axis]] = None,
                batch: int = 0) -> P:
    """Spec for a (batch, ...) input array."""
    rules = rules or DEFAULT_RULES
    ma = _mesh_axes(mesh)
    ax = _resolve_axis(rules["batch"], batch, ma) if batch else rules["batch"]
    return P(ax)
