"""What a configuration's published keys say about its trunk.

A configuration file keeps the source's own keys (a Hugging Face
``config.json``); the architecture they imply (MLP gating, the norm and
its epsilon, which projections carry biases) is read here by
``model_type``, so that no file repeats a published key under a second
name.  The program's mapping (``bench/model.py``), the plain reference and
the FLOP counts all read it from here.
"""
from __future__ import annotations

from typing import Any, Dict


def trunk(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``gated_mlp``, ``layernorm``, ``norm_eps``, ``qkv_bias`` (biases on
    the Q, K, V projections) and ``proj_bias`` (on the output and MLP
    projections)."""
    kind = cfg["model_type"]
    if kind == "qwen2":         # SwiGLU MLP, RMSNorm, biases on Q, K, V
        return {"gated_mlp": True, "layernorm": False,
                "norm_eps": cfg["rms_norm_eps"], "qkv_bias": True,
                "proj_bias": False}
    if kind == "starcoder2":    # c_fc -> GELU -> c_proj, LayerNorm, a bias
        #                         on every projection where use_bias
        bias = bool(cfg["use_bias"])
        return {"gated_mlp": False, "layernorm": True,
                "norm_eps": cfg["norm_epsilon"], "qkv_bias": bias,
                "proj_bias": bias}
    raise ValueError(f"no trunk architecture for model_type {kind!r}")
