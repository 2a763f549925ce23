"""Unit tests for attention / MLP / MoE building blocks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L
from repro.models.config import ModelConfig


def test_flash_matches_naive_causal(rng):
    q = jax.random.normal(rng, (2, 256, 8, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 2, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 256, 2, 32))
    o1 = L.flash_attention_xla(q, k, v, causal=True, chunk=64, n_macro=4)
    o2 = L.naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(o1, o2, atol=2e-5)


@pytest.mark.parametrize("sw", [16, 64])
def test_flash_sliding_window(rng, sw):
    q = jax.random.normal(rng, (1, 128, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 128, 4, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 128, 4, 16))
    o1 = L.flash_attention_xla(q, k, v, causal=True, chunk=32, n_macro=4,
                               sliding_window=sw)
    o2 = L.naive_attention(q, k, v, causal=True, sliding_window=sw)
    np.testing.assert_allclose(o1, o2, atol=2e-5)


def test_attention_causality(rng, tiny_dense):
    p = L.attn_init(rng, tiny_dense)
    x = jax.random.normal(rng, (1, 16, 64))
    y_full, _ = L.attention_block(p, x, tiny_dense, causal=True)
    y_half, _ = L.attention_block(p, x[:, :8], tiny_dense, causal=True)
    np.testing.assert_allclose(y_full[:, :8], y_half, atol=1e-5)


def test_gqa_grouping_matches_repeated_kv(rng):
    """GQA == MHA with kv heads repeated per group."""
    B, S, H, KV, hd = 1, 32, 4, 2, 16
    q = jax.random.normal(rng, (B, S, H, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, hd))
    o1 = L.naive_attention(q, k, v, causal=True)
    k_rep = jnp.repeat(k, H // KV, axis=2)
    v_rep = jnp.repeat(v, H // KV, axis=2)
    o2 = L.naive_attention(q, k_rep, v_rep, causal=True)
    np.testing.assert_allclose(o1, o2, atol=1e-5)


def test_rope_relative_shift_invariance():
    """RoPE attention scores depend only on relative positions."""
    hd = 16
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 1, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 1, hd))
    def scores(offset):
        pos = jnp.arange(4)[None, :] + offset
        qr = L.apply_rope(q, pos, 10000.0)
        kr = L.apply_rope(k, pos, 10000.0)
        return jnp.einsum("bqhd,bkhd->bqk", qr, kr)
    np.testing.assert_allclose(scores(0), scores(37), atol=1e-3)


def test_moe_capacity_drops_and_gates(rng, tiny_moe):
    import dataclasses
    cfg = dataclasses.replace(tiny_moe, capacity_factor=1.0)
    p = L.moe_init(rng, cfg)
    x = jax.random.normal(rng, (2, 16, 64))
    out, aux = L.apply_moe(p, x, cfg, groups=2)
    assert out.shape == x.shape
    assert jnp.isfinite(out).all() and jnp.isfinite(aux)
    # aux loss is >= 1 (perfect balance) by Switch construction
    assert aux >= 0.99


def test_moe_no_drop_equals_dense_expert_sum(rng, tiny_moe):
    """With capacity >= tokens, output == explicit per-token expert mix."""
    p = L.moe_init(rng, tiny_moe)
    x = jax.random.normal(rng, (1, 8, 64))
    out, _ = L.apply_moe(p, x, tiny_moe, groups=1)

    xt = x.reshape(8, 64)
    logits = xt @ p["router"].astype(x.dtype)
    probs = jax.nn.softmax(logits, -1)
    gate, eidx = jax.lax.top_k(probs, 2)
    gate = gate / gate.sum(-1, keepdims=True)
    act = jax.nn.silu
    ref = []
    for t in range(8):
        acc = 0
        for j in range(2):
            e = int(eidx[t, j])
            h = act(xt[t] @ p["wg"][e]) * (xt[t] @ p["wi"][e])
            acc = acc + float(gate[t, j]) * (h @ p["wo"][e])
        ref.append(acc)
    np.testing.assert_allclose(out.reshape(8, 64), jnp.stack(ref), atol=2e-4)


def test_norms(tiny_dense):
    import dataclasses
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 64)) * 10 + 3
    p = L.norm_init(tiny_dense)
    y = L.apply_norm(p, x, 1e-6)
    ms = jnp.mean(jnp.square(y), -1)
    np.testing.assert_allclose(ms, jnp.ones_like(ms), rtol=0.2)
    cfg_ln = dataclasses.replace(tiny_dense, layernorm=True)
    p2 = L.norm_init(cfg_ln)
    y2 = L.apply_norm(p2, x, 1e-6)
    np.testing.assert_allclose(jnp.mean(y2, -1), jnp.zeros((2, 4)), atol=1e-4)


# _attend's routing, case -> (config changes, call changes, path on a TPU)
ATTEND_ROUTES = {
    "bidirectional": ({}, {}, "kernel"),
    "causal": ({}, {"causal": True}, "kernel"),
    "cached": ({}, {"kv_len": 384, "q_offset": jnp.int32(0)}, "xla"),
    "ring_cache": ({}, {"kv_pos": jnp.arange(384)}, "xla"),
    "softcap": ({"logits_softcap": 30.0}, {}, "xla"),
    "prefix_lm": ({}, {"causal": True, "prefix_len": 16}, "xla"),
    "xla_naive": ({"attn_impl": "xla_naive"}, {}, "naive"),
    # traced under a step factory's activation sharder: partitioned
    "partitioned": ({}, {}, "xla"),
}


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
@pytest.mark.parametrize("case", list(ATTEND_ROUTES))
def test_attend_routing(monkeypatch, tiny_dense, case, backend):
    """Uncached self-attention takes the Pallas flash kernel on a TPU;
    cached, soft-capped, prefix-LM and ``xla_naive`` calls, calls of a
    partitioned step, and every call on the CPU, keep their XLA paths."""
    import contextlib
    import dataclasses
    from repro.distributed import ctx
    from repro.kernels import ops
    cfg_kw, call_kw, on_tpu = ATTEND_ROUTES[case]
    cfg = dataclasses.replace(tiny_dense, **{"attn_impl": "flash", **cfg_kw})
    taken = []

    def spy(path):
        return lambda q, k, v, **kw: taken.append(path) or jnp.zeros_like(q)
    monkeypatch.setattr(ops, "flash_attention", spy("kernel"))
    monkeypatch.setattr(L, "flash_attention_xla", spy("xla"))
    monkeypatch.setattr(L, "naive_attention", spy("naive"))
    if backend == "tpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jnp.zeros((1, 384, 4, 16))
    k = jnp.zeros((1, 384, 2, 16))
    kw = dict(causal=False, kv_len=None, kv_pos=None, q_offset=0,
              sliding_window=None, prefix_len=0)
    sharder = (ctx.activation_sharder(lambda x, kind: x)
               if case == "partitioned" else contextlib.nullcontext())
    with sharder:
        L._attend(cfg, q, k, k, **{**kw, **call_kw})
    want = on_tpu if backend == "tpu" or on_tpu == "naive" else "xla"
    assert taken == [want]
