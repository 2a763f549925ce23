"""The XLA flash attention: a macro-blocked, chunk-scanned online softmax
in plain JAX, for every backend and every kind of attention call.

It never materialises the S x S score matrix and skips fully masked
causal blocks (a static python loop over query macro-blocks, so the
lowered FLOPs approach the causal cost).  ``models.layers._attend`` runs
it for cached prefill and decode, soft-capped and prefix-LM calls, every
call of a partitioned step and every call on the CPU; the Pallas kernel
(``kernels.flash_attention``) serves the rest and takes this path's VJP
as its backward (``kernels.ops.flash_attention``).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def chunk_mask(q_pos, k_pos, *, causal, sliding_window, prefix_len,
               k_valid=None):
    """Boolean (..., Sq, Sk) mask: True = attend."""
    m = jnp.ones(q_pos.shape + k_pos.shape, bool)
    if causal:
        c = q_pos[:, None] >= k_pos[None, :]
        if prefix_len:
            c = c | (k_pos[None, :] < prefix_len)       # PaliGemma prefix-LM
        m = m & c
    if sliding_window is not None:
        m = m & (q_pos[:, None] - k_pos[None, :] < sliding_window)
    if k_valid is not None:
        m = m & k_valid[None, :]
    return m


def flash_attention_xla(
    q: jax.Array,                 # (B, Sq, H, hd)
    k: jax.Array,                 # (B, Sk, KV, hd)
    v: jax.Array,                 # (B, Sk, KV, hd)
    *,
    causal: bool = True,
    chunk: int = 512,
    n_macro: int = 8,
    sliding_window: Optional[int] = None,
    prefix_len: int = 0,
    q_offset: int = 0,
    kv_len: Optional[jax.Array] = None,   # dynamic valid kv length (decode)
    kv_pos: Optional[jax.Array] = None,   # explicit kv positions (ring cache)
    softcap: float = 0.0,
) -> jax.Array:
    """Macro-blocked online-softmax attention.

    Outer *static* python loop over ``n_macro`` q blocks lets each block scan
    only its causal kv prefix (and only its sliding window), so lowered HLO
    FLOPs approach the true causal cost instead of the full S^2.
    """
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, Sq, KV, G, hd)

    n_macro = max(1, min(n_macro, Sq))
    while Sq % n_macro:
        n_macro -= 1
    mq = Sq // n_macro
    chunk = min(chunk, Sk)
    while Sk % chunk:
        chunk -= 1

    static_offset = q_offset if isinstance(q_offset, int) else None

    def one_macro(qi: int):
        qb = lax.dynamic_slice_in_dim(qg, qi * mq, mq, axis=1)      # (B,mq,KV,G,hd)
        q_pos = q_offset + qi * mq + jnp.arange(mq)
        if causal and kv_len is None and static_offset is not None:
            hi = min(Sk, ((static_offset + (qi + 1) * mq + chunk - 1) // chunk) * chunk)
        else:
            hi = Sk
        lo = 0
        if sliding_window is not None and prefix_len == 0 and static_offset is not None:
            lo = max(0, ((static_offset + qi * mq - sliding_window) // chunk) * chunk)
        n_chunks = (hi - lo) // chunk
        kv_slice_k = lax.dynamic_slice_in_dim(k, lo, hi - lo, axis=1)
        kv_slice_v = lax.dynamic_slice_in_dim(v, lo, hi - lo, axis=1)
        ks = kv_slice_k.reshape(B, n_chunks, chunk, KV, hd)
        vs = kv_slice_v.reshape(B, n_chunks, chunk, KV, hd)

        def body(carry, inp):
            m, l, acc = carry
            kc, vc, ci = inp                                        # (B,chunk,KV,hd)
            if kv_pos is not None:
                k_pos = jnp.take(kv_pos, lo + ci * chunk + jnp.arange(chunk))
                k_valid = k_pos >= 0
            else:
                k_pos = lo + ci * chunk + jnp.arange(chunk)
                k_valid = None
            s = jnp.einsum("bqngd,bsnd->bnqgs", qb, kc,
                           preferred_element_type=jnp.float32) * scale
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            mask = chunk_mask(q_pos, k_pos, causal=causal,
                               sliding_window=sliding_window,
                               prefix_len=prefix_len, k_valid=k_valid)
            if kv_len is not None and kv_pos is None:
                mask = mask & (k_pos[None, :] < kv_len)
            # s: (B, KV, mq, G, chunk); mask broadcasts over B, KV, G
            s = jnp.where(mask[None, None, :, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bnqgs,bsnd->bnqgd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, mq, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, mq, G), jnp.float32)
        a0 = jnp.zeros((B, KV, mq, G, hd), jnp.float32)
        ks_t = ks.swapaxes(0, 1)
        vs_t = vs.swapaxes(0, 1)
        (m, l, acc), _ = lax.scan(
            body, (m0, l0, a0),
            (ks_t, vs_t, jnp.arange(n_chunks)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]                 # (B,KV,mq,G,hd)
        return out.transpose(0, 2, 1, 3, 4).reshape(B, mq, H, hd)

    outs = [one_macro(i) for i in range(n_macro)]
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    return out.astype(q.dtype)
