"""Fused CAM head (paper Eq. 1) — Pallas TPU kernel.

The paper's per-frame filter hot path is: GAP over the g x g feature map,
a fully-connected count head, and the class-activation-map contraction
``M_c(i,j) = sum_d w_d^c a_d(i,j)``.  Because GAP and the FC are linear,
``counts = relu(mean_ij CAM + b)`` — so one fused pass computes the CAM
tile in VMEM and derives the counts from its running mean, instead of
three separate HBM round-trips (feat -> pooled, pooled -> counts,
feat -> cam).  Arithmetic intensity triples for the same FLOPs.

Grid (B, nD): accumulate ``cam += feat_tile @ w_tile`` over D tiles
(d_block x C matmuls on the MXU); emit counts + CAM on the last tile.
VMEM budget: (g^2 x C) f32 accumulator — 56x56x128 = 1.6 MB, well inside
the ~16 MB/core v5e VMEM next to the (g^2 x d_block) feature tile.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(f_ref, w_ref, b_ref, counts_ref, cam_ref, acc_ref, *,
            n_d: int, g2: int):
    idx = pl.program_id(1)

    @pl.when(idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    f = f_ref[0].astype(jnp.float32)                   # (g2, dT)
    w = w_ref[...].astype(jnp.float32)                 # (dT, C)
    acc_ref[...] += jax.lax.dot_general(
        f, w, (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(idx == n_d - 1)
    def _finish():
        cam = acc_ref[...]
        cam_ref[0] = cam.astype(cam_ref.dtype)
        pooled = cam.sum(axis=0, keepdims=True) / g2   # (1, C)
        counts_ref[0] = jax.nn.relu(
            pooled + b_ref[...].astype(jnp.float32)).astype(counts_ref.dtype)


def cam_head_bgd(feat: jax.Array, w: jax.Array, b: jax.Array, *,
                 d_block: int = 512,
                 interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """feat: (B, g2, D); w: (D, C); b: (C,) -> (counts (B,C), cam (B,g2,C)).

    ``d_block`` must divide D and, on TPU, be a multiple of 128 or D
    itself (``ops.cam_head_block`` picks one)."""
    B, g2, D = feat.shape
    C = w.shape[1]
    d_block = min(d_block, D)
    assert D % d_block == 0, (D, d_block)
    n_d = D // d_block

    kernel = functools.partial(_kernel, n_d=n_d, g2=g2)
    counts, cam = pl.pallas_call(
        kernel,
        grid=(B, n_d),
        in_specs=[
            pl.BlockSpec((1, g2, d_block), lambda b_, id_: (b_, 0, id_)),
            pl.BlockSpec((d_block, C), lambda b_, id_: (id_, 0)),
            pl.BlockSpec((1, C), lambda b_, id_: (0, 0)),
        ],
        # counts ride a (B, 1, C) array so each block's trailing dims equal
        # the array's: TPU blocks must tile by (8, 128) or span the dims
        out_specs=[
            pl.BlockSpec((1, 1, C), lambda b_, id_: (b_, 0, 0)),
            pl.BlockSpec((1, g2, C), lambda b_, id_: (b_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, C), jnp.float32),
            jax.ShapeDtypeStruct((B, g2, C), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((g2, C), jnp.float32)],
        interpret=interpret,
    )(feat, w, b.reshape(1, C))
    return counts.reshape(B, C), cam
