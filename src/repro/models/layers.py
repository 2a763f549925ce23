"""Core neural layers in pure functional JAX.

Every layer is an (init, apply) pair operating on plain dict pytrees.
Initializers return ``{name: array}``; a parallel ``*_axes`` function
returns the logical sharding axes with the identical tree structure
(consumed by ``repro.distributed.sharding``).

Attention has two flash paths, chosen per call by ``_attend``: on a TPU,
uncached self-attention of a step that is not partitioned runs in the
Pallas kernel (``repro.kernels.flash_attention``); every other call runs
the XLA flash scan (``repro.kernels.xla_flash``).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed import ctx
from repro.kernels.xla_flash import NEG_INF, chunk_mask, flash_attention_xla
from repro.models.config import Activation, ModelConfig

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# dtype / init helpers
# --------------------------------------------------------------------------

def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def dense_init(key, d_in: int, shape, dtype) -> jax.Array:
    """Truncated-normal-ish fan-in init."""
    return _normal(key, shape, 1.0 / math.sqrt(max(d_in, 1)), dtype)


def activation_fn(act: Activation):
    return {Activation.SILU: jax.nn.silu,
            Activation.GELU: functools.partial(jax.nn.gelu, approximate=True),
            Activation.RELU: jax.nn.relu}[act]


# --------------------------------------------------------------------------
# Normalisation
# --------------------------------------------------------------------------

def norm_init(cfg: ModelConfig) -> Params:
    p = {"w": jnp.ones((cfg.d_model,), dtype_of(cfg))}
    if cfg.layernorm:
        p["b"] = jnp.zeros((cfg.d_model,), dtype_of(cfg))
    return p


def norm_axes(cfg: ModelConfig) -> Params:
    a = {"w": ("embed",)}
    if cfg.layernorm:
        a["b"] = ("embed",)
    return a


def apply_norm(p: Params, x: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    if "b" in p:  # LayerNorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * lax.rsqrt(var + eps)
        return (y * p["w"].astype(jnp.float32) + p["b"].astype(jnp.float32)).astype(x.dtype)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * lax.rsqrt(ms + eps)
    return (y * p["w"].astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# Rotary / sinusoidal position embeddings
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoid_pos(seq_len: int, d_model: int, dtype=jnp.float32) -> jax.Array:
    pos = jnp.arange(seq_len, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d_model, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, dim / d_model)
    pe = jnp.zeros((seq_len, d_model), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(ang)).at[:, 1::2].set(jnp.cos(ang))
    return pe.astype(dtype)


# --------------------------------------------------------------------------
# Attention — reference
# --------------------------------------------------------------------------

def naive_attention(q, k, v, *, causal=True, sliding_window=None, prefix_len=0,
                    q_offset=0, kv_len=None, kv_pos=None, softcap: float = 0.0):
    """Reference full-softmax attention (tests / tiny shapes)."""
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    s = jnp.einsum("bqngd,bsnd->bnqgs", qg, k,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(Sk) if kv_pos is None else kv_pos
    k_valid = None if kv_pos is None else kv_pos >= 0
    mask = chunk_mask(q_pos, k_pos, causal=causal,
                       sliding_window=sliding_window, prefix_len=prefix_len,
                       k_valid=k_valid)
    if kv_len is not None and kv_pos is None:
        mask = mask & (k_pos[None, :] < kv_len)
    s = jnp.where(mask[None, None, :, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    out = jnp.einsum("bnqgs,bsnd->bnqgd", p, v)      # (B, KV, Sq, G, hd)
    out = out.transpose(0, 2, 1, 3, 4).reshape(B, Sq, H, hd)
    return out.astype(q.dtype)


# --------------------------------------------------------------------------
# Attention block (QKV proj + rope + attend + out proj), with KV cache
# --------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig, cross: bool = False) -> Params:
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, (d, H, hd), dt),
        "wk": dense_init(ks[1], d, (d, KV, hd), dt),
        "wv": dense_init(ks[2], d, (d, KV, hd), dt),
        "wo": dense_init(ks[3], H * hd, (H, hd, d), dt),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((H, hd), dt)
        p["bk"] = jnp.zeros((KV, hd), dt)
        p["bv"] = jnp.zeros((KV, hd), dt)
    return p


def attn_axes(cfg: ModelConfig, cross: bool = False) -> Params:
    a = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if cfg.qkv_bias and not cross:
        a["bq"] = ("heads", "head_dim")
        a["bk"] = ("kv_heads", "head_dim")
        a["bv"] = ("kv_heads", "head_dim")
    return a


def attention_block(
    p: Params,
    x: jax.Array,                       # (B, S, D)
    cfg: ModelConfig,
    *,
    causal: bool = True,
    positions: Optional[jax.Array] = None,
    cache: Optional[Params] = None,     # {"k","v","len"} -> returns updated
    kv_source: Optional[jax.Array] = None,   # cross-attention memory (B, Sm, D)
    use_rope: Optional[bool] = None,
    prefix_len: int = 0,
    sliding_window: Optional[int] = None,
) -> Tuple[jax.Array, Optional[Params]]:
    B, S, D = x.shape
    use_rope = cfg.use_rope if use_rope is None else use_rope
    src = x if kv_source is None else kv_source
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    kk = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
    vv = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    if "bq" in p:
        q, kk, vv = q + p["bq"], kk + p["bk"], vv + p["bv"]
    if positions is None:
        positions = jnp.arange(S)[None, :]
    if use_rope and kv_source is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        kk = apply_rope(kk, positions, cfg.rope_theta)

    q_offset = 0
    kv_len = None
    kv_pos = None
    sw = sliding_window if sliding_window is not None else cfg.sliding_window
    ds = ctx.get_decode_shard()
    if (ds is not None and cache is not None and kv_source is None and
            S == 1 and "pos" not in cache and
            cache["k"].shape[1] % dict(zip(ds["mesh"].axis_names,
                                           ds["mesh"].devices.shape)
                                       )[ds["seq_axis"]] == 0):
        # serving fast path: shard-local cache write + psum softmax combine
        from repro.distributed.serve_attention import sharded_decode_attention
        idx = cache["len"]
        out, kc, vc = sharded_decode_attention(
            q, kk, vv, cache["k"], cache["v"], idx, **ds)
        cache = {"k": kc, "v": vc, "len": idx + 1}
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
        return y.astype(x.dtype), cache
    if cache is not None and kv_source is None:
        idx = cache["len"]
        cap = cache["k"].shape[1]
        if "pos" in cache:
            # ring buffer (sliding-window archs): capacity << max positions
            if S == 1:
                slot = idx % cap
                kc = _dyn_update(cache["k"], kk, slot)
                vc = _dyn_update(cache["v"], vv, slot)
                pc = lax.dynamic_update_slice(cache["pos"], positions[:1, 0]
                                              .astype(jnp.int32), (slot,))
            else:
                # fresh prefill into a ring cache: keep the last `cap` tokens
                keep = min(S, cap)
                kc = _dyn_update(cache["k"], kk[:, -keep:], 0)
                vc = _dyn_update(cache["v"], vv[:, -keep:], 0)
                pc = lax.dynamic_update_slice(
                    cache["pos"], positions[0, -keep:].astype(jnp.int32), (0,))
            cache = {"k": kc, "v": vc, "pos": pc, "len": idx + S}
            kk, vv, kv_pos = kc, vc, pc
        else:
            kc = _dyn_update(cache["k"], kk, idx)
            vc = _dyn_update(cache["v"], vv, idx)
            cache = {"k": kc, "v": vc, "len": idx + S}
            kk, vv = kc, vc
            kv_len = cache["len"]
        q_offset = idx

    out = _attend(cfg, q, kk, vv, causal=causal, kv_len=kv_len, kv_pos=kv_pos,
                  q_offset=q_offset if cache is not None else 0,
                  sliding_window=sw, prefix_len=prefix_len)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y.astype(x.dtype), cache


def _dyn_update(buf, new, idx):
    return lax.dynamic_update_slice(buf, new.astype(buf.dtype),
                                    (0, idx) + (0,) * (buf.ndim - 2))


def _attend(cfg, q, k, v, **kw):
    """Route one attention call.  Uncached self-attention (no KV cache, a
    static zero offset, no soft-cap, no prefix) runs in the Pallas flash
    kernel where the kernels run compiled (a TPU) and the step is not
    partitioned over a mesh (GSPMD cannot split the kernel's custom call;
    it would gather q, k and v onto every chip).  Every other call takes
    the XLA flash scan; ``attn_impl="xla_naive"`` and tiny shapes take
    the reference."""
    if cfg.attn_impl == "xla_naive" or q.shape[1] * k.shape[1] <= 256 * 256:
        return naive_attention(q, k, v, softcap=cfg.logits_softcap, **kw)
    from repro.kernels import ops as kops
    q_offset = kw.pop("q_offset")
    if (kw.get("kv_len") is None and kw.get("kv_pos") is None and
            isinstance(q_offset, int) and q_offset == 0 and
            kw.get("prefix_len", 0) == 0 and cfg.logits_softcap == 0.0 and
            kops.kernels_compiled() and not ctx.partitioned()):
        return kops.flash_attention(q, k, v, causal=kw["causal"],
                                    sliding_window=kw.get("sliding_window"))
    # dynamic q_offset (cached prefill/decode) -> single macro block
    n_macro = 8 if isinstance(q_offset, int) else 1
    return flash_attention_xla(q, k, v, chunk=cfg.attn_chunk, n_macro=n_macro,
                               q_offset=q_offset, softcap=cfg.logits_softcap, **kw)


# --------------------------------------------------------------------------
# MLP (dense, gated or plain)
# --------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d, f, dt = cfg.d_model, d_ff or cfg.d_ff, dtype_of(cfg)
    ks = jax.random.split(key, 3)
    p = {"wi": dense_init(ks[0], d, (d, f), dt),
         "wo": dense_init(ks[1], f, (f, d), dt)}
    if cfg.glu:
        p["wg"] = dense_init(ks[2], d, (d, f), dt)
    return p


def mlp_axes(cfg: ModelConfig) -> Params:
    a = {"wi": ("embed", "mlp"), "wo": ("mlp", "embed")}
    if cfg.glu:
        a["wg"] = ("embed", "mlp")
    return a


def apply_mlp(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    act = activation_fn(cfg.activation)
    h = jnp.einsum("bsd,df->bsf", x, p["wi"])
    if "wg" in p:
        h = act(jnp.einsum("bsd,df->bsf", x, p["wg"])) * h
    else:
        h = act(h)
    return jnp.einsum("bsf,fd->bsd", h, p["wo"]).astype(x.dtype)


# --------------------------------------------------------------------------
# Mixture-of-Experts (sort/gather capacity routing, grouped for locality)
# --------------------------------------------------------------------------

def moe_init(key, cfg: ModelConfig) -> Params:
    d, f, E, dt = cfg.d_model, cfg.d_ff, cfg.n_experts, dtype_of(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], d, (d, E), jnp.float32),
        "wi": dense_init(ks[1], d, (E, d, f), dt),
        "wo": dense_init(ks[2], f, (E, f, d), dt),
    }
    if cfg.glu:
        p["wg"] = dense_init(ks[3], d, (E, d, f), dt)
    return p


def moe_axes(cfg: ModelConfig) -> Params:
    # expert weight d_model gets its own logical axis: FSDP-sharding it
    # (default) conflicts with the token-group axis inside the routed
    # einsums and the partitioner falls back to huge all-reduces of the
    # expert hidden activations; overriding expert_embed -> None
    # (replicate) removes them when the expert stack fits (granite).
    a = {"router": ("embed", "experts_router"),
         "wi": ("experts", "expert_embed", "mlp"),
         "wo": ("experts", "mlp", "expert_embed")}
    if cfg.glu:
        a["wg"] = ("experts", "expert_embed", "mlp")
    return a


def _route_group(p: Params, xt, router_logits, cfg: ModelConfig, capacity: int):
    """Route one token group. xt: (T, D); returns (out (T, D), aux loss)."""
    T, D = xt.shape
    E, K = cfg.n_experts, cfg.experts_per_token
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)  # (T,E)
    gate, eidx = lax.top_k(probs, K)                                    # (T,K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # ---- sort-based dispatch into per-expert capacity buffers ----------
    flat_e = eidx.reshape(-1)                           # (T*K,)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_g = gate.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    # position within expert: rank among equal expert ids
    pos = jnp.arange(T * K) - jnp.searchsorted(se, se, side="left")
    keep = pos < capacity
    slot = jnp.where(keep, se * capacity + pos, E * capacity)  # overflow slot
    buf_tok = jnp.full((E * capacity + 1,), T, jnp.int32).at[slot].set(
        jnp.where(keep, st, T).astype(jnp.int32))[:-1]
    buf_gate = jnp.zeros((E * capacity + 1,), jnp.float32).at[slot].set(
        jnp.where(keep, sg, 0.0))[:-1]

    xe = jnp.take(xt, jnp.minimum(buf_tok, T - 1), axis=0)
    xe = jnp.where((buf_tok < T)[:, None], xe, 0).reshape(E, capacity, D)

    act = activation_fn(cfg.activation)
    h = jnp.einsum("ecd,edf->ecf", xe, p["wi"])
    if "wg" in p:
        h = act(jnp.einsum("ecd,edf->ecf", xe, p["wg"])) * h
    else:
        h = act(h)
    oe = jnp.einsum("ecf,efd->ecd", h, p["wo"]).reshape(E * capacity, D)
    oe = oe * buf_gate[:, None].astype(oe.dtype)
    # combine in the activation dtype (bf16): the scatter-add feeds an
    # all-reduce over the model axis when d_ff is tensor-sharded — fp32
    # accumulation here doubles that wire for no accuracy benefit (the
    # residual add upcasts anyway)
    out = jnp.zeros((T + 1, D), xt.dtype).at[buf_tok].add(
        oe.astype(xt.dtype))[:T]

    # load-balance aux loss (Switch): E * mean(frac_tokens * mean_prob)
    assign = jnp.zeros((E,), jnp.float32).at[flat_e].add(1.0) / (T * K)
    aux = E * jnp.sum(assign * probs.mean(0))
    return out, aux


def apply_moe(p: Params, x: jax.Array, cfg: ModelConfig,
              groups: int = 1) -> Tuple[jax.Array, jax.Array]:
    """Grouped sort-based MoE. x: (B, S, D) -> (out, aux_loss).

    Tokens are split into ``groups`` routing groups (aligned with the data
    mesh axis) so sort/dispatch stays shard-local under pjit; the combine
    over the expert(model) axis lowers to one activation all-reduce.
    """
    B, S, D = x.shape
    T = B * S
    groups = max(1, min(groups, T))
    while T % groups:
        groups -= 1
    tg = T // groups
    E, K = cfg.n_experts, cfg.experts_per_token
    capacity = max(int(math.ceil(tg * K / E * cfg.capacity_factor)), K)
    capacity = min(capacity, tg)

    xt = ctx.constrain(x.reshape(groups, tg, D))
    logits = jnp.einsum("gtd,de->gte", xt, p["router"].astype(x.dtype))
    out, aux = jax.vmap(
        functools.partial(_route_group, cfg=cfg, capacity=capacity),
        in_axes=(None, 0, 0))(p, xt, logits)
    return out.reshape(B, S, D), jnp.mean(aux)
