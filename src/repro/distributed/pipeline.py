"""Pipeline parallelism (GPipe-style) over a mesh axis via shard_map.

The layer stack (params stacked on the leading L axis) is split into
``n_stages`` contiguous stages, sharded over the pipeline mesh axis.
A microbatched schedule streams activations stage-to-stage with
``jax.lax.ppermute`` — compute on microbatch m overlaps the transfer of
microbatch m-1 (XLA schedules the collective-permute asynchronously).

This maps the multi-pod topology naturally: the ``pod`` axis becomes the
pipeline axis (inter-pod links are the slow ones; pipeline transfers are
the smallest inter-pod traffic pattern: one activation tensor per
microbatch per boundary, vs all-reduce traffic for DP-across-pods).
Selectable per-config (``pipeline_stages`` in launch/train.py); the
dry-run exercises DP-across-pods by default and PP as an override.

Bubble fraction = (S-1)/(M+S-1) for S stages, M microbatches.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import model as M
from repro.models.config import ModelConfig


def pipeline_forward(stack: Any, x: jax.Array, cfg: ModelConfig, *,
                     axis_name: str, n_stages: int, n_micro: int,
                     positions=None) -> jax.Array:
    """Inside shard_map: run the full layer stack across pipeline stages.

    ``stack`` holds this stage's layer slice (L/n_stages layers); ``x`` is
    this stage's microbatch shard of shape (n_micro, mb, S, D) — only
    stage 0's content matters, later stages receive via ppermute.
    Returns the final activations (valid on the last stage).
    """
    stage = jax.lax.axis_index(axis_name)
    total = n_micro + n_stages - 1     # schedule ticks

    def run_stage(xx):
        out, _, _ = M.run_layers(stack, xx, cfg, positions=positions)
        return out

    def tick(carry, t):
        buf, out_acc = carry           # buf: (mb, S, D) current input
        y = run_stage(buf)
        # pass to next stage (last stage's output accumulates)
        y_next = jax.lax.ppermute(
            y, axis_name, [(i, i + 1) for i in range(n_stages - 1)])
        # stage 0 feeds the next microbatch in
        mb_idx = jnp.clip(t + 1, 0, n_micro - 1)
        fresh = x[mb_idx]
        buf_next = jnp.where(stage == 0, fresh, y_next)
        # last stage stores finished microbatch t - (n_stages - 1)
        done_idx = t - (n_stages - 1)
        store = (stage == n_stages - 1) & (done_idx >= 0)
        out_acc = jax.lax.cond(
            store,
            lambda acc: jax.lax.dynamic_update_index_in_dim(
                acc, y, jnp.maximum(done_idx, 0), 0),
            lambda acc: acc, out_acc)
        return (buf_next, out_acc), None

    buf0 = x[0]
    out0 = jnp.zeros_like(x)
    (_, outs), _ = jax.lax.scan(tick, (buf0, out0), jnp.arange(total))
    # only the last stage accumulated results; psum replicates them so the
    # shard_map output (out_specs P()) is well defined on every stage
    return jax.lax.psum(outs, axis_name)


def make_pipelined_forward(cfg: ModelConfig, mesh: Mesh, *,
                           pipe_axis: str = "pod", n_micro: int = 4):
    """Wrap the trunk in a shard_map pipeline over ``pipe_axis``.

    Returns fn(stacked_params_sharded, x) -> activations; params must be
    sharded with layers -> pipe_axis (contiguous stage slices).
    """
    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[pipe_axis]
    assert cfg.n_layers % n_stages == 0

    pspec = P(pipe_axis)               # layer axis sharded into stages

    def fn(stack, x):
        # x: (n_micro, mb, S, D) replicated over pipe axis
        run = functools.partial(pipeline_forward, cfg=cfg,
                                axis_name=pipe_axis, n_stages=n_stages,
                                n_micro=n_micro)
        return jax.shard_map(
            run, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: pspec, stack,
                                   is_leaf=lambda v: hasattr(v, "shape")),
                      P()),
            out_specs=P(),
            check_vma=False)(stack, x)

    return fn
