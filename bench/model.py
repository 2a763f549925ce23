"""A configuration file's filter model: the program's config, its weights
and the served filter step.

The configuration file (``bench/configs/<name>.json``) keeps the source's
own key names (a Hugging Face ``config.json``) for the trunk, its serving
precision (``serve_dtype``) and a ``filter`` group for the branch the paper
taps after layer k.  Its architecture file (``bench/archs/<model_type>.py``,
loaded by ``bench/arch.py``) maps it onto the program's
``ModelConfig``/``BranchSpec``; a dense trunk's mapping is
``dense_config`` here.  This module makes the weights itself, on the device, in one jitted call from the seed: the
program's parameter tree (its layout, dtypes and shapes, read with
``jax.eval_shape``) filled with the benchmark's own draws, so the plain
reference can use the very same weights without taking anything the
program made.  Leaves the filter path never reads (the token embedding,
an untied output head) are not made.
"""
from __future__ import annotations

import math
import zlib
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from bench import arch as A
from bench.seeds import seed32
from repro.models.config import Activation, BranchSpec, ModelConfig
from repro.train.filter_train import filter_forward, init_filter_model

ACTIVATIONS = {"silu": Activation.SILU, "gelu_pytorch_tanh": Activation.GELU}
UNUSED = {("trunk", "embed"), ("trunk", "lm_head")}
NORMS = ("ln1", "ln2", "final_norm")


def model_config(cfg: Dict[str, Any], root: Path = A.ROOT) -> ModelConfig:
    """The program's config for the layers the filter runs, from the
    configuration's architecture file under ``root``."""
    return A.load(cfg, root).model_config(cfg)


def dense_config(cfg: Dict[str, Any], arch: Dict[str, Any]) -> ModelConfig:
    """A dense trunk's ``ModelConfig`` (``family="dense"``) from the
    published keys and the options ``arch`` its architecture implies."""
    f = cfg["filter"]
    heads = cfg["num_attention_heads"]
    if arch["proj_bias"]:
        raise ValueError("the program has no bias on the output and MLP "
                         "projections")
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=heads, n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // heads,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        activation=ACTIVATIONS[cfg["hidden_act"]], glu=arch["gated_mlp"],
        qkv_bias=arch["qkv_bias"], layernorm=arch["layernorm"],
        norm_eps=arch["norm_eps"], rope_theta=cfg["rope_theta"],
        tie_embeddings=True, max_seq_len=f["grid"] ** 2 + 8,
        dtype=cfg["serve_dtype"], remat="none",
        branch=BranchSpec(layer=cfg["num_hidden_layers"], grid=f["grid"],
                          n_classes=f["n_classes"], kind=f["head"],
                          head_dim=f["head_dim"]))


def _scale(path, shape, arch: Optional[ModuleType] = None) -> float:
    """Standard deviation of a leaf's draw: the architecture's own
    ``scale`` where it gives one, else 1/sqrt(fan-in) for weights, small
    for biases and positions (norm gains are 1 +- 0.1)."""
    names = [p.key for p in path]
    leaf = names[-1]
    stacked = names[:2] == ["trunk", "layers"]
    dims = shape[1:] if stacked else shape
    own = getattr(arch, "scale", None)
    s = own(names, dims) if own is not None else None
    if s is not None:
        return s
    if leaf in ("wq", "wk", "wv", "wi", "wg") or (leaf == "wo" and
                                                   "mlp" in names):
        return 1.0 / math.sqrt(dims[0])
    if leaf == "wo":                                   # (H, hd, d)
        return 1.0 / math.sqrt(dims[0] * dims[1])
    if leaf in ("c1", "c2", "c3"):                     # (k, k, cin, cout)
        return 1.0 / math.sqrt(dims[0] * dims[1] * dims[2])
    if leaf in ("proj", "w", "grid_w"):
        return 1.0 / math.sqrt(dims[0])
    if leaf in ("bq", "bk", "bv"):
        return 0.1
    return 0.02                                        # pos, head biases


def make_params(mcfg: ModelConfig, d_in: int, seed: int,
                arch: Optional[ModuleType] = None):
    """The filter's weights on the default device, from the seed; ``arch``
    is the configuration's architecture module (``bench/arch.load``),
    asked for the draw of the leaves it adds."""
    spec = mcfg.branch
    shapes = jax.eval_shape(
        lambda k: init_filter_model(k, mcfg, spec, d_in),
        jax.random.PRNGKey(0))
    for unused in UNUSED:
        shapes[unused[0]].pop(unused[1], None)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        out = []
        for path, s in leaves:
            name = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, zlib.crc32(name.encode()))
            names = [p.key for p in path]
            if path[-1].key == "w" and names[-2] in NORMS:
                x = 1.0 + 0.1 * jax.random.normal(k, s.shape, jnp.float32)
            else:
                x = _scale(path, s.shape, arch) * jax.random.normal(
                    k, s.shape, jnp.float32)
            out.append(x.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(seed32(seed, "weights")))


def filter_step_fn(mcfg: ModelConfig):
    """The served filter step: a chunk's frames, each moved to the device
    on its own, stacked and run through the program's ``filter_forward``
    with the compiled CAM head.  Its module is named ``filter_step`` in
    traces."""
    spec = mcfg.branch

    def filter_step(params, frames):
        return filter_forward(params, mcfg, spec, jnp.stack(frames),
                              use_kernel=True)

    return jax.jit(filter_step)


def flat_params(params) -> Dict[str, jax.Array]:
    """The weights by path, for the reference (``trunk/layers/attn/wq``)."""
    return {"/".join(p.key for p in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}

