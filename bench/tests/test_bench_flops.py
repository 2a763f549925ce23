"""The FLOP and byte counts against counts worked out by hand from the
shapes of the configurations as run."""
import pytest

from bench import arch as A
from bench import flops as FL
from bench import harness as H

QWEN = H.read_json(H.ROOT / "bench" / "configs" / "qwen2-0.5b.json")
STAR = H.read_json(H.ROOT / "bench" / "configs" / "starcoder2-3b.json")


def test_qwen2_filter_flops():
    # per token of a 3136-position frame, one layer (d 896, 14 heads of 64,
    # 2 KV heads, d_ff 4864, SwiGLU):
    #   Q,K,V,O projections 2*896*(896+128+128) + 2*896*896 =  3,670,016
    #   scores and values   2*2*3136*896                    = 11,239,424
    #   MLP                 3 * 2*896*4864                  = 26,148,864
    assert FL.trunk_layer_flops(QWEN, 3136, True) == 3136 * 41_058_304
    # input projection 2*3136*64*896 = 359,661,568; 5 layers; IC head
    # 3136*(2*896*256 + 2*256*8) = 1,451,491,328
    assert FL.filter_flops_per_frame(QWEN, 64) == \
        359_661_568 + 5 * 128_758_841_344 + 1_451_491_328


def test_starcoder2_filter_flops():
    # per token, one layer (d 3072, 24 heads of 128, 2 KV heads, d_ff
    # 12288, plain GELU MLP): 40,894,464 + 38,535,168 + 150,994,944
    assert FL.trunk_layer_flops(STAR, 3136, False) == 3136 * 230_424_576
    # OD head per frame: 3136 * (2*3072*512 + 2*9*512*256 + 2*256*512
    # + 2*512*8) = 18,111,528,960
    assert FL.head_flops(STAR) == 18_111_528_960
    assert FL.filter_flops_per_frame(STAR, 64) == \
        2 * 3136 * 64 * 3072 + 6 * 3136 * 230_424_576 + 18_111_528_960


@pytest.mark.parametrize("cfg", [QWEN, STAR], ids=["qwen2", "starcoder2"])
def test_dense_trunk_flops_are_layers_times_one_layer(cfg):
    """A dense architecture file counts its trunk as its layers times one
    layer of the shared helper, gated as its ``trunk`` says."""
    arch = A.load(cfg)
    for tokens in (1, 3136):
        assert arch.trunk_flops(cfg, tokens) == cfg["num_hidden_layers"] \
            * FL.trunk_layer_flops(cfg, tokens, arch.trunk(cfg)["gated_mlp"])


def test_kernel_costs_and_roofline():
    # CAM head over 8 frames of (3136, 256) f32 features, 8 classes:
    # 2*8*3136*256*8 + 2*8*3136*8 flops; reads 8*3136*256 + 256*8 + 8
    # floats, writes 8*3136*8 + 8*8 floats
    c = FL.cam_head_cost(8, 3136, 256, 8)
    assert c["flops"] == 102_760_448 + 401_408
    assert c["bytes"] == 4 * (6_422_528 + 2_048 + 8 + 200_704 + 64)
    s = FL.spatial_stats_cost(32, 3136, 3)
    assert s == {"flops": 10 * 32 * 3136 * 3,
                 "bytes": 4 * (32 * 3136 * 3 + 32 * 3 * 5)}
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # memory-bound: 26,501,376 bytes / 819 GB/s
    assert FL.roofline_s(c, peak) == c["bytes"] / 819e9
