"""A smoke-size copy of a cell for the CPU tests: the same files, with the
widths, grid, cameras and queries shrunk so that a run takes seconds."""
import dataclasses

from bench import harness as H

TINY_CONFIG = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
}
TINY_FILTER = {"grid": 8, "n_classes": 8, "head_dim": 32, "d_embed": 16}


def tiny_queries(n_classes: int = 8):
    def region(cls, rect, m):
        return {"kind": "region", "cls": cls, "rect": rect, "min_count": m}
    return [
        region(0, [0, 0, 4, 4], 6),
        region(1, [4, 4, 8, 8], 4),
        {"kind": "class_count", "cls": 2, "op": ">=", "value": 1},
        {"kind": "count", "op": "<=", "value": 3, "tolerance": 1},
        {"kind": "spatial", "a": 0, "rel": "left", "b": 1, "radius": 1},
        {"kind": "and", "terms": [{"kind": "count", "op": ">=", "value": 1},
                                  region(3, [0, 0, 8, 4], 8)]},
        {"kind": "duration", "pred": region(1, [4, 4, 8, 8], 4),
         "min_frames": 3},
        {"kind": "sliding_count", "pred": region(0, [0, 0, 4, 4], 6),
         "window": 4, "op": ">=", "value": 2},
    ]


def tiny_cell(name: str, **traffic) -> H.Cell:
    cell = H.load_cell(name)
    config = dict(cell.config, **TINY_CONFIG,
                  filter=dict(cell.config["filter"], **TINY_FILTER))
    t = dict(cell.traffic, pool_frames=32, window=16, chunk=8,
             scene=dict(cell.traffic["scene"], d_embed=16,
                        speed_cells=0.4, mean_objects=3.0), **traffic)
    t["cameras"] = min(t["cameras"], 4)
    # limits for this size: the bf16 program reads about 0.008 (CAM) and
    # 0.005 (counts) against the float32 reference here
    w = dict(cell.workload, check_frames=8,
             limits={"cam_gap": 0.05, "count_rms_gap": 0.05})
    if cell.live:
        w["rate_fps"] = 64.0
    return dataclasses.replace(cell, config=config, traffic=t, workload=w,
                               queries=tiny_queries())
