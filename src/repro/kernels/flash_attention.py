"""Blocked online-softmax (flash) attention — Pallas TPU kernel.

Target: TPU v5e.  Heads stay in lanes, as the projections lay them out:
q is (B, Sq, H * hd) and v is (B, Sk, KV * hd), so the model's
(B, S, H, hd) activations reach the kernel by a reshape, with no
transpose.  K comes transposed, (B, KV * hd, Sk), so Q·Kᵀ is a plain
(rows, lanes) x (lanes, keys) matmul.  Grid (B, nQ, nK) with the kv axis
innermost — on TPU the last grid axis is sequential per core, so each
head's (m, l) and the (rows, H * hd) accumulator live in VMEM scratch
across kv steps and no score tile ever leaves VMEM.  One grid step
serves every head, so each K/V tile is read from HBM once for all the
query heads that share it (GQA: head h reads KV head h // group).

Heads narrower than a lane tile (hd < 128, e.g. 64) share a 128-lane
window of q and of the output with their neighbours: head h's lanes are
rolled onto its KV head's lanes within K's window and masked, so each
matmul contracts one head, and P·V's window is rolled back onto head h's
lanes and masked before it is added.  The zeroed lanes add exact zeros.

Precision is the XLA path's (``kernels.xla_flash``): both
matmuls take their operands in the inputs' dtype (bf16 when serving) and
accumulate in float32, P is cast to V's dtype before P·V, and the softmax
state is float32.

Keys at or past ``kv_len`` (padding up to a whole number of blocks) are
masked, as are keys outside the causal or sliding window.  Fully masked
kv blocks are skipped with ``pl.when`` (no FLOPs spent), and the mask is
applied only in blocks that cross a boundary.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
LANES = 128
# Scoped VMEM the kernel may use: a v5e core has 128 MiB, and the largest
# tiles ``kernels.ops.flash_tiles`` picks need under half of this.
VMEM_LIMIT = 64 * 2**20


def _lanes(x, lo, hd: int):
    """x (rows, 128) with lanes [lo, lo + hd) kept and the rest zeroed."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= lo) & (lane < lo + hd), x, jnp.zeros_like(x))


def _roll(x, shift):
    """Lanes rotated by ``shift`` (32-bit: Mosaic rotates no narrower)."""
    return pltpu.roll(x.astype(jnp.float32), shift % LANES,
                      1).astype(x.dtype)


def _kernel(q_ref, kt_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, sliding_window: Optional[int],
            group: int, hd: int, block_q: int, block_k: int, n_k: int,
            kv_len: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    width = max(hd, LANES)         # lanes of one q/output window
    per = width // hd              # heads in a window
    n_win = q_ref.shape[2] // width
    kv_heads = kt_ref.shape[1] // hd   # with a lane tile's padding

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = ik * block_k
    q_last = q_start + block_q - 1
    run, edge = True, False        # python bools stay static
    if causal:
        run = run & (k_start <= q_last)
        edge = edge | (k_start + block_k - 1 > q_start)
    if sliding_window is not None:
        run = run & (k_start + block_k - 1 > q_start - sliding_window)
        edge = edge | (k_start <= q_last - sliding_window)
    if n_k * block_k > kv_len:
        edge = edge | (ik == n_k - 1)

    def step(masked: bool):
        bias = keep = None
        if masked and (causal or sliding_window is not None):
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            keep = k_pos < kv_len
            if causal:
                keep &= k_pos <= q_pos
            if sliding_window is not None:
                keep &= q_pos - k_pos < sliding_window
        elif masked:               # padded keys only: one bias row
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            bias = jnp.where(k_pos < kv_len, 0.0, NEG_INF)

        def window(w, carry):
            lo = pl.multiple_of(w * width, LANES)
            q_win = q_ref[0, :, pl.ds(lo, width)]
            corrs, pvs = [], []
            for i in range(per):   # the heads sharing this window
                h = w * per + i
                src = jnp.minimum(h // group, kv_heads - 1) * hd
                ws = pl.multiple_of(src // LANES * LANES, LANES)
                kt = kt_ref[0, pl.ds(ws, width), :]
                v = v_ref[0, :, pl.ds(ws, width)]
                q = q_win
                if per > 1:        # move head i onto its KV head's lanes
                    so = src % LANES
                    q = _lanes(_roll(q_win, so - i * hd), so, hd)
                s = jax.lax.dot_general(
                    q, kt, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale  # (bq, bk)
                if keep is not None:
                    s = jnp.where(keep, s, NEG_INF)
                elif bias is not None:
                    s = s + bias
                m_prev = m_ref[h]                               # (bq, 1)
                m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_ref[h] = l_ref[h] * corr + p.sum(axis=-1, keepdims=True)
                m_ref[h] = m_new
                pv = jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)       # (bq, width)
                if per > 1:        # and P·V back onto head i's lanes
                    pv = _lanes(_roll(pv, i * hd - so), i * hd, hd)
                corrs.append(corr)
                pvs.append(pv)
            acc_ref[:, pl.ds(lo, width)] = (
                acc_ref[:, pl.ds(lo, width)] * _by_lane(corrs, hd)
                + functools.reduce(jnp.add, pvs))
            return carry

        jax.lax.fori_loop(0, n_win, window, 0)

    if edge is False and run is True:
        step(False)
    elif edge is False:
        pl.when(run)(lambda: step(False))
    else:
        pl.when(run & edge)(lambda: step(True))
        pl.when(run & jnp.logical_not(edge))(lambda: step(False))

    @pl.when(ik == n_k - 1)
    def _finish():
        def window(w, carry):
            lo = pl.multiple_of(w * width, LANES)
            l = _by_lane([l_ref[w * per + i] for i in range(per)], hd)
            o_ref[0, :, pl.ds(lo, width)] = (
                acc_ref[:, pl.ds(lo, width)] /
                jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, n_win, window, 0)


def _by_lane(cols, hd: int):
    """The (rows, 1) columns of the heads sharing a window, head i's over
    its lanes [i * hd, (i + 1) * hd)."""
    if len(cols) == 1:
        return cols[0]
    rows = cols[0].shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    out = jnp.broadcast_to(cols[0], (rows, LANES))
    for i, c in enumerate(cols[1:], 1):
        out = jnp.where(lane >= i * hd, c, out)
    return out


def flash_attention_bsd(q: jax.Array, kt: jax.Array, v: jax.Array, *,
                        head_dim: int, group: int, kv_len: int, scale: float,
                        causal: bool = True,
                        sliding_window: Optional[int] = None,
                        block_q: int, block_k: int,
                        interpret: bool = False) -> jax.Array:
    """q: (B, Sq, QL) with head h at lanes [h * hd, (h + 1) * hd);
    kt: (B, KL, Sk), K transposed, KV head j at rows [j * hd, (j + 1) * hd);
    v: (B, Sk, KL) -> (B, Sq, QL).  Where hd < 128, QL and KL are whole
    lane tiles.  Keys at or past ``kv_len`` are masked; ``block_q``
    divides Sq and ``block_k`` divides Sk; scores are scaled by
    ``scale``."""
    B, Sq, QL = q.shape
    KL, Sk = kt.shape[1:]
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    n_q, n_k = Sq // block_q, Sk // block_k
    kernel = functools.partial(
        _kernel, scale=scale, causal=causal,
        sliding_window=sliding_window, group=group, hd=head_dim,
        block_q=block_q, block_k=block_k, n_k=n_k, kv_len=kv_len)
    return pl.pallas_call(
        kernel,
        grid=(B, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, QL), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, KL, block_k), lambda b, iq, ik: (b, 0, ik)),
            pl.BlockSpec((1, block_k, KL), lambda b, iq, ik: (b, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, QL), lambda b, iq, ik: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((QL // head_dim, block_q, 1), jnp.float32),   # m
            pltpu.VMEM((QL // head_dim, block_q, 1), jnp.float32),   # l
            pltpu.VMEM((block_q, QL), jnp.float32),                  # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name="flash_attention",
        interpret=interpret,
    )(q, kt, v)
