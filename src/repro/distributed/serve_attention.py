"""Sequence-sharded decode attention (shard_map) — the serving fast path.

Problem (visible in the baseline dry-run, qwen2-72b decode_32k):
the KV cache must be sharded along *sequence* (batch x kv_heads shards
don't cover 256 chips: kv=8 < model=16, batch/data leaves 5.4 GB/dev),
but writing one token at a dynamic index into a seq-sharded buffer makes
the SPMD partitioner rematerialise the cache (all-gather -> update ->
re-slice): ~16.5 GB of all-gather per decode step vs a 27 ms memory
roofline.

Fix: shard_map over the model axis.  Each shard owns a contiguous
S_local = S/n slice of the cache:

- the new token is written shard-locally (masked dynamic_update_slice:
  only the shard whose range contains ``idx`` commits the write);
- each shard computes partial attention (m, l, acc) over its slice;
- shards combine with the online-softmax reduction: global max via pmax,
  rescale, psum of (l, acc) — wire cost per layer is O(B x H x hd), i.e.
  ~0.3 MB instead of gigabytes.

This is the standard TPU serving layout (seq-parallel cache, softmax-
combine), integrated here behind ``ctx.decode_shard`` so the generic
model stack picks it up without mesh plumbing.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _body(q, k_new, v_new, kc, vc, idx, *, axis: str, s_local: int,
          scale: float):
    """Per-shard: local cache write + partial attention + psum combine.

    q: (B, 1, KV, G, hd) replicated; k_new/v_new: (B, 1, KV, hd);
    kc/vc: (B, S_local, KV, hd) local slices; idx: () current length.
    """
    shard = lax.axis_index(axis)
    base = shard * s_local
    slot = idx - base
    ok = (slot >= 0) & (slot < s_local)
    cs = jnp.clip(slot, 0, s_local - 1)
    kc_w = lax.dynamic_update_slice(kc, k_new.astype(kc.dtype),
                                    (0, cs, 0, 0))
    vc_w = lax.dynamic_update_slice(vc, v_new.astype(vc.dtype),
                                    (0, cs, 0, 0))
    kc = jnp.where(ok, kc_w, kc)
    vc = jnp.where(ok, vc_w, vc)

    s = jnp.einsum("bqngd,bsnd->bnqgs", q.astype(jnp.float32),
                   kc.astype(jnp.float32)) * scale    # (B,KV,1,G,S_local)
    pos = base + jnp.arange(s_local)
    valid = pos <= idx                                # causal: <= new token
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)

    m = jnp.max(s, axis=-1)                           # (B,KV,1,G)
    gm = lax.pmax(m, axis)
    p = jnp.exp(s - gm[..., None])
    l = lax.psum(jnp.sum(p, axis=-1), axis)
    acc = lax.psum(jnp.einsum("bnqgs,bsnd->bnqgd", p.astype(vc.dtype), vc,
                              preferred_element_type=jnp.float32), axis)
    out = acc / jnp.maximum(l, 1e-30)[..., None]      # (B,KV,1,G,hd)
    return out, kc, vc


def sharded_decode_attention(
    q: jax.Array,            # (B, 1, H, hd)   (any sharding; gathered)
    k_new: jax.Array,        # (B, 1, KV, hd)
    v_new: jax.Array,
    cache_k: jax.Array,      # (B, S, KV, hd)  seq sharded over `seq_axis`
    cache_v: jax.Array,
    idx: jax.Array,          # () int32 — current cache length
    *,
    mesh: Mesh,
    seq_axis: str = "model",
    batch_axes=("pod", "data"),
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (attn_out (B,1,H,hd), new_cache_k, new_cache_v)."""
    B, _, H, hd = q.shape
    _, S, KV, _ = cache_k.shape
    G = H // KV
    ma = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = ma[seq_axis]
    assert S % n == 0
    s_local = S // n
    b_axes = tuple(a for a in batch_axes if a in ma and
                   B % ma[a] == 0)
    # shrink batch axes tuple until divisible
    while b_axes and B % math.prod(ma[a] for a in b_axes):
        b_axes = b_axes[:-1]
    bspec = b_axes if len(b_axes) > 1 else (b_axes[0] if b_axes else None)

    qg = q.reshape(B, 1, KV, G, hd)
    body = functools.partial(_body, axis=seq_axis, s_local=s_local,
                             scale=1.0 / math.sqrt(hd))
    cache_spec = P(bspec, seq_axis)
    out, kc, vc = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec), P(bspec), P(bspec),
                  cache_spec, cache_spec, P()),
        out_specs=(P(bspec), cache_spec, cache_spec),
        check_vma=False,
    )(qg, k_new, v_new, cache_k, cache_v, idx)
    return out.reshape(B, 1, H, hd).astype(q.dtype), kc, vc
