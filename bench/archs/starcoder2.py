"""``model_type`` starcoder2: c_fc -> GELU -> c_proj (not gated),
LayerNorm with eps = ``norm_epsilon``, a bias on every projection where
``use_bias``."""
from bench.flops import trunk_layer_flops
from bench.model import dense_config


def trunk(cfg):
    bias = bool(cfg["use_bias"])
    return {"gated_mlp": False, "layernorm": True,
            "norm_eps": cfg["norm_epsilon"], "qkv_bias": bias,
            "proj_bias": bias}


def model_config(cfg):
    return dense_config(cfg, trunk(cfg))


def trunk_flops(cfg, tokens):
    return cfg["num_hidden_layers"] * trunk_layer_flops(
        cfg, tokens, trunk(cfg)["gated_mlp"])
