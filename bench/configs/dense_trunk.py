"""Plain reference of a dense-trunk filter: the configuration's trunk
prefix and its branch head, in float32, from the published equations.

It follows the source's architecture and the paper, not the program:
patch embeddings -> input projection + learned positions -> L
pre-norm blocks (RMSNorm or LayerNorm; grouped-query attention with
rotary positions over the whole frame, no causal mask; SwiGLU or
tanh-GELU MLP) -> the branch head on the g x g grid of the tap:

- ``ic`` (paper section II-A): ReLU(1x1 projection), then the class
  activation map M_c = sum_d w_dc a_d (Eq. 1) and counts =
  ReLU(mean M_c + b_c);
- ``od`` (section II-B): 1x1, 3x3, 1x1 convolutions with leaky ReLU
  (slope 0.1), counts = ReLU(mean-pooled features . w + b) and a
  per-cell class grid.

The architecture (gating, norm, biases) is what the source's keys say
(``bench/arch.py``).  Weights come by path name (``trunk/layers/attn/wq``, ...) from the
benchmark's own draws.  Every matmul runs at ``highest`` precision.
``quant`` rounds each matmul operand before it is used: the identity
for the reference, a lower precision for the control.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from bench.arch import trunk

F32 = jnp.float32


def _norm(x, w, b, arch):
    eps = arch["norm_eps"]
    if arch["layernorm"]:
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * w + b
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotary positions, rotate-half convention; x: (S, H, hd)."""
    S, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None]          # (S, hd/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                   * (x + 0.044715 * x ** 3)))


def _frame(w: Dict[str, jax.Array], cfg, quant: Callable, emb):
    """One frame (S, d_in) -> (counts (C,), grid (g, g, C))."""
    q = quant
    arch = trunk(cfg)
    if arch["proj_bias"]:
        raise ValueError("no weights are drawn for biases on the output "
                         "and MLP projections")
    mm = functools.partial(jnp.einsum, precision="highest",
                           preferred_element_type=F32)
    S = emb.shape[0]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // H
    x = mm("sd,de->se", q(emb), q(w["proj"])) + w["pos"][:S]
    lay = "trunk/layers/"
    for i in range(cfg["num_hidden_layers"]):
        p = {k[len(lay):]: v[i] for k, v in w.items() if k.startswith(lay)}
        h = _norm(x, p["ln1/w"], p.get("ln1/b"), arch)
        qh = mm("sd,dhk->shk", q(h), q(p["attn/wq"]))
        kh = mm("sd,dhk->shk", q(h), q(p["attn/wk"]))
        vh = mm("sd,dhk->shk", q(h), q(p["attn/wv"]))
        if arch["qkv_bias"]:
            qh, kh, vh = (qh + p["attn/bq"], kh + p["attn/bk"],
                          vh + p["attn/bv"])
        qh, kh = _rope(qh, cfg["rope_theta"]), _rope(kh, cfg["rope_theta"])
        kh = jnp.repeat(kh, H // KV, axis=1)
        vh = jnp.repeat(vh, H // KV, axis=1)
        s = mm("qhk,shk->hqs", q(qh), q(kh)) / math.sqrt(hd)
        a = jax.nn.softmax(s, axis=-1)
        o = mm("hqs,shk->qhk", q(a), q(vh))
        x = x + mm("qhk,hkd->qd", q(o), q(p["attn/wo"]))
        h = _norm(x, p["ln2/w"], p.get("ln2/b"), arch)
        up = mm("sd,df->sf", q(h), q(p["mlp/wi"]))
        if arch["gated_mlp"]:
            up = jax.nn.silu(mm("sd,df->sf", q(h), q(p["mlp/wg"]))) * up
        else:
            up = _gelu_tanh(up)
        x = x + mm("sf,fd->sd", q(up), q(p["mlp/wo"]))
    f = cfg["filter"]
    g = f["grid"]
    tap = x.reshape(g, g, -1)
    b = {k[len("branch/"):]: v for k, v in w.items()
         if k.startswith("branch/")}
    if f["head"] == "ic":
        feat = jax.nn.relu(mm("ijd,de->ije", q(tap), q(b["proj"])))
        cam = mm("ije,ec->ijc", q(feat), q(b["w"]))
        return jax.nn.relu(cam.mean((0, 1)) + b["b"]), cam
    if f["head"] == "od":
        def conv(x, k):
            return jax.lax.conv_general_dilated(
                q(x)[None], q(k), (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision="highest")[0]

        def lrelu(x):
            return jnp.where(x >= 0, x, 0.1 * x)

        h = lrelu(conv(tap, b["c1"]))
        h = lrelu(conv(h, b["c2"]))
        h = lrelu(conv(h, b["c3"]))
        counts = jax.nn.relu(mm("e,ec->c", q(h.mean((0, 1))), q(b["w"]))
                             + b["b"])
        grid = mm("ije,ec->ijc", q(h), q(b["grid_w"])) + b["grid_b"]
        return counts, grid
    raise ValueError(f"unknown head {f['head']!r}")


def make_forward(cfg, *, control: bool = False):
    """A jitted reference over one frame: (weights, (S, d_in)) ->
    (counts, grid), all float32; with ``control``, every matmul operand
    is rounded to float8 first."""
    quant = fp8 if control else (lambda a: a)
    return jax.jit(lambda w, emb: _frame(
        {k: v.astype(F32) for k, v in w.items()}, cfg, quant,
        emb.astype(F32)))


def fp8(a: jax.Array) -> jax.Array:
    """The control's rounding: each operand scaled to the float8 (e4m3)
    range by its largest magnitude, rounded to float8, scaled back."""
    a = a.astype(F32)
    scale = jnp.maximum(jnp.abs(a).max(), 1e-30) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def run(fwd, weights, frames) -> Tuple[jax.Array, jax.Array]:
    """(counts (B, C), grid (B, g, g, C)) of ``make_forward``'s function
    over frames (B, S, d_in), one frame at a time so that it fits."""
    outs = [fwd(weights, frames[i]) for i in range(frames.shape[0])]
    return (jnp.stack([o[0] for o in outs]), jnp.stack([o[1] for o in outs]))
