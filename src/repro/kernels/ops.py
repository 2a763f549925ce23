"""Public jit'd wrappers over the Pallas kernels.

On TPU every wrapper calls the compiled kernel.  tests/test_chip_compile.py
compiles the served path's kernels (CAM head, both spatial reductions) for
a described v5e at real widths, and chip_smoke.py checks them on the chip
against their references.  On the CPU backend the CAM head and the
language-model kernels run in ``interpret=True`` mode (the kernel body
executed op-by-op; tests/test_kernels.py checks that against ``ref``),
while the spatial statistics take a pure-JAX projection reduction and
never enter the kernel.  The language-model wrappers (flash, decode,
rwkv6) fall back to the ``ref`` oracle when a sequence does not tile by
the block size; ``cam_head`` raises instead.  Layout adapters live here so
the model code keeps its natural (B, S, H, hd) activations.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.cam_head import cam_head_bgd
from repro.kernels.decode_attention import decode_attention_bkgd
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.rwkv6_scan import rwkv6_scan_bhtk
from repro.kernels.spatial_predicate import (spatial_stats_bgc,
                                             spatial_stats_rows_bgc)


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "sliding_window",
                                             "block_q", "block_k"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    sliding_window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    """q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd) -> (B, Sq, H, hd)."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    if Sq % bq or Sk % bk:
        return ref.flash_attention_ref(q, k, v, causal=causal,
                                       sliding_window=sliding_window)
    out = flash_attention_bhsd(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, sliding_window=sliding_window,
        block_q=bq, block_k=bk, interpret=_interpret())
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("block_k",))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_len: jax.Array, *, block_k: int = 256) -> jax.Array:
    """q: (B, H, hd); k, v: (B, S, KV, hd); kv_len: () -> (B, H, hd)."""
    B, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    bk = min(block_k, Sk)
    if Sk % bk:
        return ref.decode_attention_ref(q, k, v, kv_len)
    out = decode_attention_bkgd(
        q.reshape(B, KV, G, hd), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), jnp.asarray(kv_len).reshape(1),
        block_k=bk, interpret=_interpret())
    return out.reshape(B, H, hd)


def cam_head_block(D: int, d_block: int = 512) -> int:
    """Feature-axis tile of ``cam_head_bgd``: all of D when it fits in
    ``d_block``, else the largest multiple of 128 up to ``d_block`` that
    divides D (a TPU block's lane dim must tile by 128 or span the array).
    Raises ValueError when no such tile exists."""
    if D <= d_block:
        return D
    for db in range(d_block - d_block % 128, 0, -128):
        if D % db == 0:
            return db
    raise ValueError(f"cam_head: no feature tile <= {d_block} that is a "
                     f"multiple of 128 divides D={D}")


@functools.partial(jax.jit, static_argnames=("d_block",))
def cam_head(feat: jax.Array, w: jax.Array, b: jax.Array, *,
             d_block: int = 512) -> Tuple[jax.Array, jax.Array]:
    """feat: (B, g, g, D); w: (D, C); b: (C,) -> (counts, cam (B,g,g,C))."""
    B, g, _, D = feat.shape
    C = w.shape[1]
    db = cam_head_block(D, d_block)
    counts, cam = cam_head_bgd(feat.reshape(B, g * g, D), w, b,
                               d_block=db, interpret=_interpret())
    return counts, cam.reshape(B, g, g, C)


def _spatial_stats_proj(grid_logits: jax.Array, tau: float) -> jax.Array:
    """Fast pure-JAX spatial stats via row/column occupancy projections.

    Extrema only need ``any`` along the opposite axis, so after one
    threshold pass the min/max reductions run on (B, g, C) projections
    instead of four (B, g, g, C) temporaries (ref.spatial_stats_ref is the
    clarity oracle; this is the CPU hot path, parity-tested against it)."""
    B, g, _, C = grid_logits.shape
    occ = grid_logits.astype(jnp.float32) > tau
    prow = occ.any(2)                               # (B, g, C) row occupied
    pcol = occ.any(1)                               # (B, g, C) col occupied
    idx = jnp.arange(g, dtype=jnp.float32)[None, :, None]
    min_row = jnp.where(prow, idx, float(g)).min(1)
    max_row = jnp.where(prow, idx, -1.0).max(1)
    min_col = jnp.where(pcol, idx, float(g)).min(1)
    max_col = jnp.where(pcol, idx, -1.0).max(1)
    n = occ.sum((1, 2)).astype(jnp.float32)
    return jnp.stack([min_row, max_row, min_col, max_col, n], axis=-1)


def spatial_stats_inline(grid_logits: jax.Array,
                         tau: float = 0.2) -> jax.Array:
    """Un-jitted spatial stats, for callers that are already inside a jit
    (repro.core.plan traces this next to the occupancy threshold so XLA
    CSEs the shared ``grid > tau`` pass; a nested jit would block that).

    This is the multi-query filter hot path (every ORDER() leaf of every
    registered query reads these stats), so on CPU the numerically
    identical projection reduction is used directly: the interpreted
    kernel walks the (B,) grid step-by-step in the Pallas interpreter
    (~ms per call) and would dominate end-to-end throughput.
    Interpreter-vs-reference parity is covered in tests/test_kernels.py."""
    if _interpret():
        return _spatial_stats_proj(grid_logits, tau)
    return spatial_stats_bgc(grid_logits, tau=tau, interpret=False)


def spatial_stats_rows_inline(grid_logits: jax.Array, rows: jax.Array,
                              tau: float = 0.2) -> jax.Array:
    """Spatial stats over a gathered row subset: (B, g, g, C) x (R,) ->
    (R, C, 5).  Un-jitted for the same CSE reason as
    ``spatial_stats_inline`` — the staged planner traces this inside its
    per-stage step functions.  On TPU the gather rides the kernel's
    scalar-prefetched index map (no (R, g, g, C) intermediate); every
    other backend uses the projection reduction on the explicitly
    gathered rows, which XLA fuses with the threshold pass
    (``pltpu.PrefetchScalarGridSpec`` is TPU-only — the GPU Pallas
    backend cannot lower it, so gating on "not CPU" would crash there)."""
    if jax.default_backend() == "tpu":
        return spatial_stats_rows_bgc(grid_logits, rows, tau=tau,
                                      interpret=False)
    return _spatial_stats_proj(grid_logits[rows], tau)


@functools.partial(jax.jit, static_argnames=("tau",))
def spatial_stats(grid_logits: jax.Array, *, tau: float = 0.2) -> jax.Array:
    """grid_logits: (B, g, g, C) -> per-class stats (B, C, 5)."""
    return spatial_stats_inline(grid_logits, tau)


@functools.partial(jax.jit, static_argnames=("chunk",))
def rwkv6_scan(r, k, v, lw, u, s0, *, chunk: int = 32):
    """r,k,v,lw: (B,H,T,K); u: (H,K); s0: (B,H,K,V)."""
    T = r.shape[2]
    c = min(chunk, T)
    if T % c:
        return ref.rwkv6_scan_ref(r, k, v, lw, u, s0)
    return rwkv6_scan_bhtk(r, k, v, lw, u, s0, chunk=c,
                           interpret=_interpret())
