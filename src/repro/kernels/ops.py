"""Public jit'd wrappers over the Pallas kernels.

On TPU every wrapper calls the compiled kernel.  tests/test_chip_compile.py
compiles the served path's kernels (CAM head, both spatial reductions, the
flash attention at the filter trunks' widths) for a described v5e at real
widths, and chip_smoke.py checks them on the chip against their
references.  On the CPU backend the CAM head and the language-model
kernels run in ``interpret=True`` mode (the kernel body executed
op-by-op; tests/test_kernels.py checks that against ``ref``), while the
spatial statistics take a pure-JAX projection reduction and never enter
the kernel.

``flash_attention`` takes any sequence lengths: its tiles come from the
shapes (``flash_tiles``), and where no tile divides a length the queries
and keys are padded to whole tiles (padded keys masked, padded rows
dropped); it never falls back to the S x S reference.  It is
differentiable (``jax.custom_vjp``; the backward is that of the XLA
flash scan, ``kernels.xla_flash``, recomputed).  The model reaches it
through ``models.layers._attend`` for uncached self-attention (no KV
cache, a static zero offset, no soft-cap, no prefix) when the kernels
run compiled and the step is not partitioned over a mesh; cached
prefill and decode, soft-capped and prefix-LM calls, partitioned steps
and every call on the CPU take the XLA flash scan.  The decode and rwkv6
wrappers still fall back to the ``ref`` oracle when a sequence does not
tile by the block size; ``cam_head`` raises instead.  Layout adapters
live here so the model code keeps its natural (B, S, H, hd) activations.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.cam_head import cam_head_bgd
from repro.kernels.decode_attention import decode_attention_bkgd
from repro.kernels.flash_attention import flash_attention_bsd
from repro.kernels.rwkv6_scan import rwkv6_scan_bhtk
from repro.kernels.spatial_predicate import (spatial_stats_bgc,
                                             spatial_stats_rows_bgc)
from repro.kernels.xla_flash import flash_attention_xla


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def kernels_compiled() -> bool:
    """Whether the wrappers run the kernels compiled: on every backend but
    the CPU, where they run in interpret mode.  A described-chip compile
    steers this through ``_interpret``."""
    return not _interpret()


# Flash tiles: all keys in one step up to FLASH_KEYS (no mask, no online
# rescaling), else blocks of FLASH_BLOCK_K keys; query rows as many as keep
# one float32 score tile within FLASH_SCORE_BYTES, at most FLASH_ROWS.
FLASH_KEYS, FLASH_BLOCK_K = 4096, 512
FLASH_ROWS, FLASH_SCORE_BYTES = 512, 3 * 2**20


def _tile(n: int, cap: int, align: int) -> Tuple[int, int]:
    """(block, padded n) for an axis of length n cut into blocks of at
    most ``cap``: n whole if it fits; else the largest multiple of
    ``align`` in [cap/2, cap] that divides n; else n over ceil(n / cap)
    equal blocks rounded up to ``align``, n padded to a whole number."""
    if n <= cap:
        return n, n
    for b in range(cap - cap % align, cap // 2 - 1, -align):
        if n % b == 0:
            return b, n
    nb = -(-n // cap)
    b = -(-n // (nb * align)) * align
    return b, nb * b


def flash_tiles(Sq: int, Sk: int, masked: bool) -> Tuple[int, int, int, int]:
    """(block_q, padded Sq, block_k, padded Sk) of the flash kernel.  A
    causal or windowed call (``masked``) takes key blocks of
    FLASH_BLOCK_K, so that the kernel can skip the masked ones."""
    if not masked and Sk <= FLASH_KEYS:
        bk, sk = Sk, Sk
    else:
        bk, sk = _tile(Sk, FLASH_BLOCK_K, 128)
    lanes = -(-bk // 128) * 128
    cap = max(16, min(FLASH_ROWS, FLASH_SCORE_BYTES // (4 * lanes)) // 16 * 16)
    bq, sq = _tile(Sq, cap, 16)
    return bq, sq, bk, sk


def _flash_kernel(q, k, v, causal, sliding_window):
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    if hd % 128 and 128 % hd:
        raise ValueError(f"flash_attention: head dim {hd} neither divides "
                         f"nor is a multiple of a 128-lane tile")
    bq, sq, bk, sk = flash_tiles(Sq, Sk, causal or sliding_window is not None)

    def lanes(x, S):                   # (B, S0, n, hd) -> (B, S, lanes)
        n = x.shape[2] * hd            # narrow heads fill whole lane tiles
        return jnp.pad(x.reshape(B, x.shape[1], n),
                       ((0, 0), (0, S - x.shape[1]), (0, -n % 128)))

    out = flash_attention_bsd(
        lanes(q, sq), lanes(k, sk).transpose(0, 2, 1), lanes(v, sk),
        head_dim=hd, group=H // KV, kv_len=Sk, scale=1.0 / math.sqrt(hd),
        causal=causal, sliding_window=sliding_window, block_q=bq,
        block_k=bk, interpret=_interpret())
    return out[:, :Sq, :H * hd].reshape(B, Sq, H, hd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal, sliding_window):
    return _flash_kernel(q, k, v, causal, sliding_window)


def _flash_fwd(q, k, v, causal, sliding_window):
    return _flash_kernel(q, k, v, causal, sliding_window), (q, k, v)


def _flash_bwd(causal, sliding_window, res, g):
    """The VJP of the XLA flash path, recomputed from q, k, v."""
    _, vjp = jax.vjp(functools.partial(flash_attention_xla, causal=causal,
                                       sliding_window=sliding_window), *res)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "sliding_window"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    sliding_window: Optional[int] = None) -> jax.Array:
    """q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd) -> (B, Sq, H, hd).

    Any lengths: tiles come from ``flash_tiles``, with the queries and
    keys padded to whole tiles where no tile divides them (padded keys
    are masked, padded query rows dropped).  Differentiable: the backward
    is that of ``flash_attention_xla``, recomputed."""
    return _flash(q, k, v, causal, sliding_window)


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
