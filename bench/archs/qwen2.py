"""``model_type`` qwen2: a SwiGLU MLP, RMSNorm with eps =
``rms_norm_eps``, biases on the Q, K and V projections only."""
from bench.flops import trunk_layer_flops
from bench.model import dense_config


def trunk(cfg):
    return {"gated_mlp": True, "layernorm": False,
            "norm_eps": cfg["rms_norm_eps"], "qkv_bias": True,
            "proj_bias": False}


def model_config(cfg):
    return dense_config(cfg, trunk(cfg))


def trunk_flops(cfg, tokens):
    return cfg["num_hidden_layers"] * trunk_layer_flops(
        cfg, tokens, trunk(cfg)["gated_mlp"])
