"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


ATTN_SHAPES = [
    # (B, Sq, Sk, H, KV, hd)
    (1, 128, 128, 4, 4, 32),
    (2, 256, 256, 8, 2, 64),
    (1, 512, 512, 4, 1, 128),
    # unaligned sequences (not multiples of 128) at the served GQA groups:
    # Qwen2-0.5B (7 query heads per KV head of 64), StarCoder2-3B (12 of 128)
    (1, 196, 196, 14, 2, 64),
    (1, 392, 392, 24, 2, 128),
    # narrow heads that do not fill their last lane tile (5 x 64 lanes)
    (1, 256, 256, 5, 1, 64),
    # heads wider than one lane tile (PaliGemma-3B's 256)
    (1, 384, 384, 8, 1, 256),
]


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(shape, dtype, causal):
    B, Sq, Sk, H, KV, hd = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (B, Sq, H, hd), dtype)
    k = _rand(ks[1], (B, Sk, KV, hd), dtype)
    v = _rand(ks[2], (B, Sk, KV, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=causal)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    atol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), atol=atol)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_grad_is_xla_flash_grad(causal):
    """The kernel's custom_vjp: its backward is that of the XLA flash path."""
    from repro.models.layers import flash_attention_xla
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = _rand(ks[0], (1, 96, 4, 64), jnp.float32)
    k = _rand(ks[1], (1, 96, 2, 64), jnp.float32)
    v = _rand(ks[2], (1, 96, 2, 64), jnp.float32)
    w = _rand(ks[3], (1, 96, 4, 64), jnp.float32)

    def grads(attn):
        return jax.grad(lambda q, k, v: jnp.sum(attn(q, k, v, causal=causal)
                                                * w), argnums=(0, 1, 2))(
            q, k, v)

    for got, want in zip(grads(ops.flash_attention),
                         grads(flash_attention_xla)):
        assert float(jnp.max(jnp.abs(want))) > 0
        np.testing.assert_allclose(got, want, atol=1e-6)


def test_flash_attention_rejects_ragged_head_dim():
    q = jnp.zeros((1, 256, 2, 80))
    with pytest.raises(ValueError, match="head dim 80"):
        ops.flash_attention(q, q, q)


@pytest.mark.parametrize("sw,S,H,KV,hd", [
    (32, 256, 4, 2, 32),
    (128, 256, 4, 2, 32),
    # 600 keys pad to two blocks of 384: the window and the padded keys
    # masked together, at the served GQA groups and both lane layouts
    (128, 600, 14, 2, 64),
    (256, 600, 24, 2, 128),
])
def test_flash_attention_sliding(sw, S, H, KV, hd):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(ks[0], (1, S, H, hd), jnp.float32)
    k = _rand(ks[1], (1, S, KV, hd), jnp.float32)
    v = _rand(ks[2], (1, S, KV, hd), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, sliding_window=sw)
    want = ref.flash_attention_ref(q, k, v, causal=True, sliding_window=sw)
    np.testing.assert_allclose(out, want, atol=1e-4)


@pytest.mark.parametrize("S,klen", [(256, 256), (256, 100), (512, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(S, klen, dtype):
    B, H, KV, hd = 2, 8, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand(ks[0], (B, H, hd), dtype)
    k = _rand(ks[1], (B, S, KV, hd), dtype)
    v = _rand(ks[2], (B, S, KV, hd), dtype)
    out = ops.decode_attention(q, k, v, jnp.int32(klen))
    want = ref.decode_attention_ref(q, k, v, jnp.int32(klen))
    atol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), atol=atol)


@pytest.mark.parametrize("g,D,C", [(8, 256, 8), (16, 512, 16), (8, 1024, 128)])
def test_cam_head_sweep(g, D, C):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    feat = _rand(ks[0], (2, g, g, D), jnp.float32)
    w = _rand(ks[1], (D, C), jnp.float32) * 0.05
    b = _rand(ks[2], (C,), jnp.float32) * 0.1
    c1, m1 = ops.cam_head(feat, w, b)
    c2, m2 = ref.cam_head_ref(feat, w, b)
    np.testing.assert_allclose(c1, c2, atol=1e-3)
    np.testing.assert_allclose(m1, m2, atol=1e-3)


@pytest.mark.parametrize("D,d_block,want", [
    (32, 512, 32), (256, 512, 256), (1024, 512, 512), (768, 512, 384),
    (640, 512, 128), (1000, 512, None)])
def test_cam_head_block(D, d_block, want):
    """The feature tile divides D and tiles the lane axis by 128 (or is
    all of D); with no such tile the wrapper raises instead of silently
    taking the reference path."""
    if want is None:
        with pytest.raises(ValueError, match="D=1000"):
            ops.cam_head_block(D, d_block)
    else:
        assert ops.cam_head_block(D, d_block) == want


@pytest.mark.parametrize("g,C", [(8, 4), (16, 8), (56, 8)])
def test_spatial_stats_sweep(g, C):
    gl = jax.random.normal(jax.random.PRNGKey(4), (3, g, g, C)) * 3
    s1 = ops.spatial_stats(gl)
    s2 = ref.spatial_stats_ref(gl)
    np.testing.assert_allclose(s1, s2)


def test_spatial_stats_empty_class():
    gl = jnp.full((1, 8, 8, 2), -50.0)  # below tau -> empty everywhere
    s = ops.spatial_stats(gl)
    np.testing.assert_allclose(s[0, :, 0], 8.0)   # min_row = g
    np.testing.assert_allclose(s[0, :, 1], -1.0)  # max_row = -1
    np.testing.assert_allclose(s[0, :, 4], 0.0)   # count = 0


@pytest.mark.parametrize("seed", range(4))
def test_spatial_stats_interpret_parity_random_occupancy(seed):
    """Interpret-mode Pallas kernel vs pure-JAX reference on randomized
    sparse occupancy grids, with whole classes knocked out per frame so
    the empty-class sentinels (min=g, max=-1, n=0) mix with live classes
    inside one batch."""
    from repro.kernels.spatial_predicate import spatial_stats_bgc

    rng = np.random.default_rng(seed)
    B, g, C = 4, 12, 6
    occ = rng.random((B, g, g, C)) < 0.08
    dead = rng.random((B, C)) < 0.3
    occ &= ~dead[:, None, None, :]
    gl = jnp.where(jnp.asarray(occ), 5.0, -5.0)
    s_kernel = np.asarray(spatial_stats_bgc(gl, interpret=True))
    s_ref = np.asarray(ref.spatial_stats_ref(gl))
    np.testing.assert_array_equal(s_kernel, s_ref)
    empty = ~occ.any((1, 2))                          # (B, C)
    np.testing.assert_allclose(s_kernel[..., 0][empty], g)    # min sentinel
    np.testing.assert_allclose(s_kernel[..., 1][empty], -1.0)  # max sentinel
    np.testing.assert_allclose(s_kernel[..., 4][empty], 0.0)


@pytest.mark.parametrize("seed", range(3))
def test_spatial_stats_rows_gathered_subset_parity(seed):
    """The scalar-prefetched row-gather kernel (row-level
    short-circuiting's stats reduction) equals gather-then-reduce for
    arbitrary row subsets — out-of-order, duplicated (bucket padding),
    and smaller or larger than the batch — in both the Pallas interpreter
    and the CPU projection path used under jit."""
    from repro.kernels.spatial_predicate import (spatial_stats_bgc,
                                                 spatial_stats_rows_bgc)

    rng = np.random.default_rng(100 + seed)
    B, g, C = 6, 8, 4
    gl = jnp.asarray(rng.normal(0, 0.7, (B, g, g, C)).astype(np.float32))
    for rows in ([4, 1, 1, 3], [0], list(rng.integers(0, B, 2 * B))):
        rows_j = jnp.asarray(np.asarray(rows, np.int32))
        want = np.asarray(spatial_stats_bgc(gl, interpret=True))[rows]
        got = np.asarray(spatial_stats_rows_bgc(gl, rows_j, interpret=True))
        np.testing.assert_array_equal(got, want)
        got_inline = np.asarray(ops.spatial_stats_rows_inline(gl, rows_j))
        np.testing.assert_array_equal(got_inline, want)


def test_eval_spatial_leaves_matches_per_leaf_eval():
    """Batched-leaf ORDER() evaluation over kernel stats == scalar
    ``eval_filters`` on each Spatial leaf (all relations, with dilation)."""
    from repro.core import query as Q
    from repro.core.filters import FilterOutputs
    from repro.kernels.spatial_predicate import (eval_spatial_leaves,
                                                 spatial_stats_bgc)

    rng = np.random.default_rng(11)
    B, g, C = 5, 10, 4
    gl = jnp.asarray(rng.normal(0, 1, (B, g, g, C)).astype(np.float32))
    out = FilterOutputs(counts=jnp.zeros((B, C)), grid=gl)
    stats = spatial_stats_bgc(gl, interpret=True)

    leaves, want = [], []
    for a in range(C):
        for b in range(C):
            for rel in Q.Rel:
                for radius in (0, 1, 2):
                    leaf = Q.canonicalize_leaf(Q.Spatial(a, rel, b, radius))
                    leaves.append(leaf)
                    want.append(np.asarray(
                        Q.eval_filters(leaf, out)))
    got = np.asarray(eval_spatial_leaves(
        stats,
        jnp.asarray([l.cls_a for l in leaves]),
        jnp.asarray([l.cls_b for l in leaves]),
        jnp.asarray([l.rel == Q.Rel.ABOVE for l in leaves]),
        jnp.asarray([l.radius for l in leaves]), grid=g))
    np.testing.assert_array_equal(got, np.stack(want, axis=1))


@pytest.mark.parametrize("T,K", [(64, 16), (128, 64), (96, 32)])
def test_rwkv6_scan_sweep(T, K):
    B, H = 2, 3
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    r = _rand(ks[0], (B, H, T, K), jnp.float32)
    k = _rand(ks[1], (B, H, T, K), jnp.float32)
    v = _rand(ks[2], (B, H, T, K), jnp.float32)
    lw = jnp.clip(-jnp.exp(_rand(ks[3], (B, H, T, K), jnp.float32) * 0.3),
                  -2.0, -1e-6)
    u = _rand(ks[4], (H, K), jnp.float32) * 0.1
    s0 = _rand(ks[5], (B, H, K, K), jnp.float32) * 0.1
    o1, st1 = ops.rwkv6_scan(r, k, v, lw, u, s0)
    o2, st2 = ref.rwkv6_scan_ref(r, k, v, lw, u, s0)
    np.testing.assert_allclose(o1, o2, atol=5e-3)
    np.testing.assert_allclose(st1, st2, atol=5e-3)


def test_rwkv6_state_continuation():
    """Two half-sequences with carried state == one full sequence."""
    B, H, T, K = 1, 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    r = _rand(ks[0], (B, H, T, K), jnp.float32)
    k = _rand(ks[1], (B, H, T, K), jnp.float32)
    v = _rand(ks[2], (B, H, T, K), jnp.float32)
    lw = jnp.clip(-jnp.exp(_rand(ks[3], (B, H, T, K), jnp.float32) * 0.3),
                  -2.0, -1e-6)
    u = jnp.zeros((H, K))
    s0 = jnp.zeros((B, H, K, K))
    o_full, st_full = ops.rwkv6_scan(r, k, v, lw, u, s0)
    h = T // 2
    o1, st1 = ops.rwkv6_scan(r[:, :, :h], k[:, :, :h], v[:, :, :h],
                             lw[:, :, :h], u, s0)
    o2, st2 = ops.rwkv6_scan(r[:, :, h:], k[:, :, h:], v[:, :, h:],
                             lw[:, :, h:], u, st1)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 2), o_full,
                               atol=5e-3)
    np.testing.assert_allclose(st2, st_full, atol=5e-3)
