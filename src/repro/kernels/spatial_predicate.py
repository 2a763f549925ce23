"""Spatial-predicate statistics — Pallas TPU kernel (CLF hot path).

Evaluating ORDER()/Region constraints needs, per frame and per class, the
occupancy extrema of the thresholded CAM: min/max row, min/max column, and
the occupied-cell count.  Those five statistics are *sufficient* for every
pairwise relation the query language supports (see
repro.core.query.spatial_relation), so the kernel reduces the (g, g, C)
grid once in VMEM and emits a tiny (C, 5) tensor per frame — turning the
per-predicate full-grid scans (one per query leaf) into a single fused
reduction shared by all predicates.

Grid (B,): one frame per step; the (g^2 x C) logits tile lives in VMEM
(56*56*128 f32 = 1.6 MB), reductions are VPU element-wise ops over lanes.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, o_ref, *, tau: float, g: int):
    x = x_ref[0].astype(jnp.float32)                   # (g2, C)
    occ = x > tau                        # raw-value threshold (paper: 0.2)
    g2 = g * g
    cell = jax.lax.broadcasted_iota(jnp.int32, (g2, x.shape[1]), 0)
    rows = (cell // g).astype(jnp.float32)
    cols = (cell % g).astype(jnp.float32)
    big = jnp.float32(g)
    min_row = jnp.min(jnp.where(occ, rows, big), axis=0)
    max_row = jnp.max(jnp.where(occ, rows, -1.0), axis=0)
    min_col = jnp.min(jnp.where(occ, cols, big), axis=0)
    max_col = jnp.max(jnp.where(occ, cols, -1.0), axis=0)
    n = jnp.sum(occ.astype(jnp.float32), axis=0)
    o_ref[0] = jnp.stack([min_row, max_row, min_col, max_col, n],
                         axis=-1).astype(o_ref.dtype)


def spatial_stats_bgc(grid_logits: jax.Array, *, tau: float = 0.2,
                      interpret: bool = False) -> jax.Array:
    """grid_logits: (B, g, g, C) -> stats (B, C, 5) float32."""
    B, g, g2_, C = grid_logits.shape
    assert g == g2_
    flat = grid_logits.reshape(B, g * g, C)
    kernel = functools.partial(_kernel, tau=tau, g=g)
    return pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, g * g, C), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, C, 5), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, C, 5), jnp.float32),
        interpret=interpret,
        name="spatial_stats",
    )(flat)


def _rows_kernel(rows_ref, x_ref, o_ref, *, tau: float, g: int):
    del rows_ref        # consumed by the BlockSpec index maps, not the body
    _kernel(x_ref, o_ref, tau=tau, g=g)


def spatial_stats_rows_bgc(grid_logits: jax.Array, rows: jax.Array, *,
                           tau: float = 0.2,
                           interpret: bool = False) -> jax.Array:
    """Stats reduction over a gathered row subset.

    grid_logits: (B, g, g, C); rows: (R,) int32 frame indices (duplicates
    allowed — the staged planner pads its undecided-row buckets by
    repeating the last survivor) -> (R, C, 5) float32.

    The gather happens in the BlockSpec index map: ``rows`` is
    scalar-prefetched, so each grid step DMAs exactly the one frame it
    reduces straight from the full (B, g^2, C) tensor in HBM — the
    compacted (R, g, g, C) intermediate is never materialized.  This is
    the kernel behind row-level short-circuiting: the expensive tiers of
    ``repro.core.plan.StagedQueryPlan`` touch only the frames the cheap
    tiers left undecided.
    """
    B, g, g2_, C = grid_logits.shape
    assert g == g2_
    R = rows.shape[0]
    flat = grid_logits.reshape(B, g * g, C)
    kernel = functools.partial(_rows_kernel, tau=tau, g=g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R,),
        in_specs=[pl.BlockSpec((1, g * g, C),
                               lambda r, rows_ref: (rows_ref[r], 0, 0))],
        out_specs=pl.BlockSpec((1, C, 5), lambda r, rows_ref: (r, 0, 0)))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, C, 5), jnp.float32),
        interpret=interpret,
        name="spatial_stats_rows",
    )(rows.astype(jnp.int32), flat)


def stage_class_slice(cls_a: np.ndarray, cls_b: np.ndarray
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Stage-sliced leaf evaluation: compact the class set a stage touches.

    The staged planner (repro.core.plan.StagedQueryPlan) evaluates the
    spatial tier as its own stage; when the registered population only
    references a few of the C classes, reducing the full (B, g, g, C) grid
    wastes VMEM bandwidth on planes no leaf reads.  Returns
    ``(classes, a_idx, b_idx)``: the sorted unique class ids the stage's
    leaves mention, and the leaf arrays remapped into that compact set.
    The caller gathers ``grid[..., classes]`` *before* the stats reduction
    (so the kernel reduces C' <= C planes) and feeds ``a_idx``/``b_idx`` to
    ``eval_spatial_leaves`` — per-class statistics are independent, so the
    sliced evaluation is bit-identical to the full one.
    """
    classes, inv = np.unique(np.concatenate([cls_a, cls_b]),
                             return_inverse=True)
    a_idx = inv[:len(cls_a)].astype(np.int32)
    b_idx = inv[len(cls_a):].astype(np.int32)
    return classes.astype(np.int32), a_idx, b_idx


def eval_spatial_leaves(stats: jax.Array, cls_a: jax.Array, cls_b: jax.Array,
                        use_row: jax.Array, radius: jax.Array, *,
                        grid: int) -> jax.Array:
    """Batched-leaf evaluation of L canonical ORDER() predicates at once.

    stats: (B, C, 5) from ``spatial_stats_bgc``; cls_a/cls_b/use_row/radius:
    (L,) per-leaf arrays (canonical LEFT/ABOVE spelling, see
    repro.core.query.canonicalize_leaf) -> (B, L) bool.

    Manhattan dilation by r shifts the occupancy extrema exactly
    (min - r clamped to 0, max + r clamped to g-1) and never changes
    emptiness, so CLF-k relaxations are evaluated analytically from the one
    shared (C, 5) reduction — no per-leaf grid rescan, no dilated grids.
    """
    sa = stats[:, cls_a]                               # (B, L, 5)
    sb = stats[:, cls_b]
    any_a = sa[..., 4] > 0
    any_b = sb[..., 4] > 0
    r = radius.astype(stats.dtype)
    min_a = jnp.where(use_row, sa[..., 0], sa[..., 2])   # min row | col of a
    max_b = jnp.where(use_row, sb[..., 1], sb[..., 3])   # max row | col of b
    min_a = jnp.maximum(min_a - r, 0.0)
    max_b = jnp.minimum(max_b + r, float(grid - 1))
    return any_a & any_b & (min_a < max_b)
