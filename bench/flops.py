"""Operations and bytes the algorithms need, from their shapes.

Counts are of the algorithm, not of what the compiler emits: a
multiply-add is 2 operations, elementwise work and softmax are left out
of the model count (they are a few percent at these widths), and bytes
are what a kernel must read and write in HBM at least once.
"""
from __future__ import annotations

from pathlib import Path
from typing import Any, Dict

from bench import arch as A


def trunk_layer_flops(cfg: Dict[str, Any], tokens: int,
                      gated_mlp: bool) -> float:
    """One dense pre-norm block over ``tokens`` positions attending to all
    of them: the Q/K/V/O projections, scores and weighted values, the MLP
    (three matmuls where ``gated_mlp``, else two)."""
    d = cfg["hidden_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // H
    f = cfg["intermediate_size"]
    proj = 2 * d * (H * hd + 2 * KV * hd) + 2 * H * hd * d
    attend = 2 * 2 * tokens * H * hd
    mlp = 2 * d * f * (3 if gated_mlp else 2)
    return float(tokens * (proj + attend + mlp))


def head_flops(cfg: Dict[str, Any]) -> float:
    """The branch head over one frame's g x g tap."""
    fl = cfg["filter"]
    g2, C, h = fl["grid"] ** 2, fl["n_classes"], fl["head_dim"]
    d = cfg["hidden_size"]
    if fl["head"] == "ic":
        return float(g2 * (2 * d * h + 2 * h * C))
    if fl["head"] == "od":
        return float(g2 * (2 * d * 2 * h + 2 * 9 * 2 * h * h
                           + 2 * h * 2 * h + 2 * 2 * h * C))
    raise ValueError(fl["head"])


def filter_flops_per_frame(cfg: Dict[str, Any], d_in: int,
                           root: Path = A.ROOT) -> float:
    """The filter forward of one frame: input projection, the trunk's
    layers up to the tap (counted by the configuration's architecture
    file under ``root``), the head."""
    g2 = cfg["filter"]["grid"] ** 2
    return (2.0 * g2 * d_in * cfg["hidden_size"]
            + A.load(cfg, root).trunk_flops(cfg, g2)
            + head_flops(cfg))


def cam_head_cost(frames: int, g2: int, D: int, C: int) -> Dict[str, float]:
    """``kernels/cam_head.py``: CAM = feat @ w over (frames, g2, D) f32
    features, counts from its mean.  Reads the features and weights,
    writes the CAM and counts."""
    return {"flops": 2.0 * frames * g2 * D * C + 2.0 * frames * g2 * C,
            "bytes": 4.0 * (frames * g2 * D + D * C + C
                            + frames * g2 * C + frames * C)}


def spatial_stats_cost(frames: int, g2: int, C: int) -> Dict[str, float]:
    """``kernels/spatial_predicate.py``: per frame and class, threshold
    the (g2, C) f32 map and reduce it to five statistics.  About ten
    elementwise operations per cell (compare, four selects, four
    min/max, a sum); reads the map, writes (C, 5)."""
    return {"flops": 10.0 * frames * g2 * C,
            "bytes": 4.0 * (frames * g2 * C + frames * C * 5)}


def roofline_s(cost: Dict[str, float], peak: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of operations over
    peak operations per second and bytes over peak bandwidth."""
    return max(cost["flops"] / peak["bf16_flops_per_s"],
               cost["bytes"] / peak["hbm_bytes_per_s"])
