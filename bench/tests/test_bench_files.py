"""Each cell's files load, and a new cell, configuration, traffic, query
mix, per-layer metric and trunk architecture are picked up from new files
alone; the architectures already there give what they gave before they
were files of their own."""
import hashlib
import json
import math
import re
import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import answers as A
from bench import arch as AR
from bench import flops as FL
from bench import harness as H
from bench import model as M
from repro.models.config import Activation, BlockKind, BranchSpec, ModelConfig

ROOT = H.ROOT
BENCH = H.read_json(ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_load(name):
    cell = H.load_cell(name)
    assert cell.chips in (1, 4)
    assert {"setup_s", "frames_per_s"} <= {m["name"] for m in cell.end_to_end}
    for q in cell.queries:
        A.to_query(q)
    for m in cell.per_layer:
        assert callable(H.load_reader(m["name"]))
        assert m["moves"] in {e["name"] for e in cell.end_to_end}
    ref = H.load_reference(cell)
    assert callable(ref.make_forward)
    assert cell.traffic["mode"] in ("live", "archive")
    assert ("rate_fps" in cell.workload) == cell.live
    assert set(cell.workload["limits"]) == {"cam_gap", "count_rms_gap"}


def test_benchmark_names_and_bounds():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics] + CELLS + \
        [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for c in BENCH["configs"]:
        cfg = H.read_json(ROOT / c["file"])
        assert c["file"].startswith("bench/")
        assert set(c["reduced"]) == set(cfg["published"])


def _digest(root: Path):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def _copy(tmp_path: Path) -> Path:
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path / "bench"


def test_new_cell_is_files_only(tmp_path):
    """A cell of a new configuration, traffic and query mix, with a new
    per-layer metric, added to a copy: only new files and new entries in
    BENCHMARK.json, and the harness finds them all by name."""
    b = _copy(tmp_path)
    before = _digest(b)
    cfg = H.read_json(b / "configs" / "qwen2-0.5b.json")
    cfg.update(name="qwen2-1.5b", hidden_size=1536, intermediate_size=8960,
               num_attention_heads=12)
    (b / "configs" / "qwen2-1.5b.json").write_text(json.dumps(cfg))
    traffic = H.read_json(b / "traffic" / "live-detrac.json")
    traffic.update(cameras=2, mix="jackson-2")
    (b / "traffic" / "live-jackson.json").write_text(json.dumps(traffic))
    (b / "mixes" / "jackson-2.json").write_text(json.dumps({"queries": [
        {"kind": "count", "op": ">=", "value": 1},
        {"kind": "duration", "min_frames": 4, "pred": {
            "kind": "region", "cls": 1, "rect": [0, 0, 28, 28]}}]}))
    (b / "workloads" / "qwen2-1.5b.live-jackson.json").write_text(
        json.dumps({"rate_fps": 30, "check_frames": 8,
                    "limits": {"cam_gap": 0.1, "count_rms_gap": 0.1}}))
    (b / "metrics" / "chunks_answered.py").write_text(
        "def read(run):\n    return float(len(run.rec.chunks)) or None\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "qwen2-1.5b", "source": "x",
                             "file": "bench/configs/qwen2-1.5b.json",
                             "reduced": ["num_hidden_layers"], "why": "x"})
    bench["workloads"].append({"name": "qwen2-1.5b.live-jackson",
                               "config": "qwen2-1.5b",
                               "traffic": "live-jackson", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "chunks_answered", "unit": "chunks",
                               "better": "higher",
                               "source": "program_counter", "layer": "x",
                               "moves": "frames_per_s",
                               "workloads": ["qwen2-1.5b.live-jackson"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = H.load_cell("qwen2-1.5b.live-jackson", root=tmp_path)
    assert cell.config["hidden_size"] == 1536
    assert cell.traffic["cameras"] == 2 and len(cell.queries) == 2
    assert cell.workload["rate_fps"] == 30
    assert [m["name"] for m in cell.per_layer] == ["chunks_answered"]
    read = H.load_reader("chunks_answered", root=tmp_path)

    class Rec:
        chunks = [object()] * 3

    class FakeRun:
        rec = Rec()

    assert read(FakeRun()) == 3.0
    after = _digest(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before


@pytest.mark.parametrize("name,want", [
    ("qwen2-0.5b", {"gated_mlp": True, "layernorm": False, "norm_eps": 1e-6,
                    "qkv_bias": True, "proj_bias": False}),
    ("starcoder2-3b", {"gated_mlp": False, "layernorm": True,
                       "norm_eps": 1e-5, "qkv_bias": False,
                       "proj_bias": False})])
def test_trunk_from_source_keys(name, want):
    """The trunk's options come from the source's keys alone, and a bias
    the program cannot serve is refused, not dropped."""
    from bench.arch import trunk
    from bench.model import model_config
    cfg = H.read_json(ROOT / "bench" / "configs" / f"{name}.json")
    assert trunk(cfg) == want
    m = model_config(cfg)
    assert (m.glu, m.layernorm, m.qkv_bias, m.norm_eps) == (
        want["gated_mlp"], want["layernorm"], want["qkv_bias"],
        want["norm_eps"])
    published = dict(cfg, **cfg["published"])
    if trunk(published)["proj_bias"]:
        with pytest.raises(ValueError):
            model_config(published)


TOY_ARCH = '''"""A toy mixture-of-experts trunk: a dense block whose MLP is
num_local_experts SwiGLU experts, num_experts_per_tok per token."""
import dataclasses
import math

from bench.model import dense_config
from repro.models.config import BlockKind


def trunk(cfg):
    return {"gated_mlp": True, "layernorm": False,
            "norm_eps": cfg["rms_norm_eps"], "qkv_bias": False,
            "proj_bias": False}


def model_config(cfg):
    return dataclasses.replace(
        dense_config(cfg, trunk(cfg)), family="moe", block=BlockKind.MOE,
        n_experts=cfg["num_local_experts"],
        experts_per_token=cfg["num_experts_per_tok"])


def trunk_flops(cfg, tokens):
    return 1000.0 * tokens * cfg["num_hidden_layers"]


def scale(names, dims):
    if "moe" not in names:
        return None
    if names[-1] == "router":                          # (d, E)
        return 1.0 / math.sqrt(dims[0])
    return 1.0 / math.sqrt(dims[1])                    # (E, d, f), (E, f, d)
'''

TOY_REFERENCE = '''"""The toy trunk's plain reference (not run here)."""


def make_forward(cfg, *, control=False):
    raise NotImplementedError


def run(fwd, weights, frames):
    raise NotImplementedError
'''


def test_new_architecture_is_files_only(tmp_path):
    """A configuration of a new ``model_type``, added to a copy with its
    architecture file, its reference and a cell: only new files and new
    entries in BENCHMARK.json.  The program's config, the FLOP count and
    the weight draw all come from the new architecture file."""
    b = _copy(tmp_path)
    before = _digest(b)
    (b / "archs" / "toymoe.py").write_text(TOY_ARCH)
    (b / "configs" / "toymoe_ref.py").write_text(TOY_REFERENCE)
    cfg = dict(H.read_json(b / "configs" / "qwen2-0.5b.json"),
               name="toymoe-tiny", model_type="toymoe", reference="toymoe_ref",
               hidden_size=64, intermediate_size=32, num_attention_heads=4,
               num_key_value_heads=2, num_hidden_layers=2,
               num_local_experts=4, num_experts_per_tok=2)
    cfg["filter"] = dict(cfg["filter"], grid=8, head_dim=32, d_embed=16)
    (b / "configs" / "toymoe-tiny.json").write_text(json.dumps(cfg))
    (b / "workloads" / "toymoe-tiny.archive-detrac.json").write_text(
        json.dumps({"check_frames": 8,
                    "limits": {"cam_gap": 0.1, "count_rms_gap": 0.1}}))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toymoe-tiny", "source": "x",
                             "file": "bench/configs/toymoe-tiny.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "toymoe-tiny.archive-detrac",
                               "config": "toymoe-tiny",
                               "traffic": "archive-detrac", "chips": 1,
                               "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = H.load_cell("toymoe-tiny.archive-detrac", root=tmp_path)
    assert callable(H.load_reference(cell).make_forward)
    with pytest.raises(ValueError, match="toymoe"):
        M.model_config(cell.config)             # not in the repository
    mcfg = M.model_config(cell.config, tmp_path)
    assert (mcfg.block, mcfg.n_experts, mcfg.experts_per_token,
            mcfg.d_ff) == (BlockKind.MOE, 4, 2, 32)
    assert AR.trunk(cell.config, tmp_path)["qkv_bias"] is False
    assert FL.filter_flops_per_frame(cell.config, 16, tmp_path) == \
        2.0 * 64 * 16 * 64 + 1000.0 * 64 * 2 + FL.head_flops(cell.config)

    arch = AR.load(cell.config, tmp_path)
    shapes = jax.eval_shape(lambda: M.make_params(mcfg, 16, 0, arch))
    moe = shapes["trunk"]["layers"]["moe"]
    assert moe["wi"].shape == (2, 4, 64, 32) and moe["router"].shape == \
        (2, 64, 4)
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    # each expert stack and the router at 1/sqrt(fan-in): d = 64 in, or
    # f = 32 for the down-projection (the default would take E = 4)
    want = {"wi": 1 / 8, "wg": 1 / 8, "router": 1 / 8,
            "wo": 1 / math.sqrt(32)}
    for path, s in leaves:
        names = [p.key for p in path]
        got = M._scale(path, s.shape, arch)
        if "moe" in names:
            assert got == want[names[-1]]
        else:
            assert got == M._scale(path, s.shape)
    params = M.make_params(mcfg, 16, 2 ** 31 + 3, arch)
    for leaf, sd in want.items():
        x = np.asarray(params["trunk"]["layers"]["moe"][leaf], np.float64)
        assert x.std() == pytest.approx(sd, rel=0.05), leaf
    after = _digest(b)
    assert {k: v for k, v in after.items() if k in before} == before


@pytest.mark.parametrize("model_type", ["granitemoe", "../configs/x", ""])
def test_unknown_model_type_raises(model_type):
    cfg = dict(H.read_json(ROOT / "bench" / "configs" / "qwen2-0.5b.json"),
               model_type=model_type)
    with pytest.raises(ValueError, match="no file .*archs"):
        AR.load(cfg)
    with pytest.raises(ValueError):
        M.model_config(cfg)
    with pytest.raises(ValueError):
        FL.filter_flops_per_frame(cfg, 64)


def _fan_in(n):
    return 1.0 / math.sqrt(n)


# What the tree before architecture files gave, per configuration: the
# program's config, and each leaf's draw (a norm gain, or the standard
# deviation, most as 1/sqrt(fan-in)).
PINNED = {
    "qwen2-0.5b": (
        ModelConfig(
            name="qwen2-0.5b", family="dense", n_layers=5, d_model=896,
            n_heads=14, n_kv_heads=2, head_dim=64, d_ff=4864,
            vocab_size=151936, activation=Activation.SILU, glu=True,
            qkv_bias=True, layernorm=False, norm_eps=1e-6, rope_theta=1e6,
            tie_embeddings=True, max_seq_len=3144, dtype="bfloat16",
            remat="none", branch=BranchSpec(layer=5, grid=56, n_classes=8,
                                            kind="ic", head_dim=256)),
        {"branch/b": 0.02, "branch/proj": _fan_in(896),
         "branch/w": _fan_in(256), "pos": 0.02, "proj": _fan_in(64),
         "trunk/final_norm/w": "norm", "trunk/layers/attn/bk": 0.1,
         "trunk/layers/attn/bq": 0.1, "trunk/layers/attn/bv": 0.1,
         "trunk/layers/attn/wk": _fan_in(896),
         "trunk/layers/attn/wo": _fan_in(896),
         "trunk/layers/attn/wq": _fan_in(896),
         "trunk/layers/attn/wv": _fan_in(896),
         "trunk/layers/ln1/w": "norm", "trunk/layers/ln2/w": "norm",
         "trunk/layers/mlp/wg": _fan_in(896),
         "trunk/layers/mlp/wi": _fan_in(896),
         "trunk/layers/mlp/wo": _fan_in(4864)},
        "d85199e27ccbc7cd5f9329488e7c36435e8b9fec3ea5d2fc37a5e55a381c760a"),
    "starcoder2-3b": (
        ModelConfig(
            name="starcoder2-3b", family="dense", n_layers=6, d_model=3072,
            n_heads=24, n_kv_heads=2, head_dim=128, d_ff=12288,
            vocab_size=49152, activation=Activation.GELU, glu=False,
            qkv_bias=False, layernorm=True, norm_eps=1e-5,
            rope_theta=999999.4420358813, tie_embeddings=True,
            max_seq_len=3144, dtype="bfloat16", remat="none",
            branch=BranchSpec(layer=6, grid=56, n_classes=8, kind="od",
                              head_dim=256)),
        {"branch/b": 0.02, "branch/c1": _fan_in(3072),
         "branch/c2": _fan_in(9 * 512), "branch/c3": _fan_in(256),
         "branch/grid_b": 0.02, "branch/grid_w": _fan_in(512),
         "branch/w": _fan_in(512), "pos": 0.02, "proj": _fan_in(64),
         "trunk/final_norm/b": 0.02, "trunk/final_norm/w": "norm",
         "trunk/layers/attn/wk": _fan_in(3072),
         "trunk/layers/attn/wo": _fan_in(3072),
         "trunk/layers/attn/wq": _fan_in(3072),
         "trunk/layers/attn/wv": _fan_in(3072),
         "trunk/layers/ln1/b": 0.02, "trunk/layers/ln1/w": "norm",
         "trunk/layers/ln2/b": 0.02, "trunk/layers/ln2/w": "norm",
         "trunk/layers/mlp/wi": _fan_in(3072),
         "trunk/layers/mlp/wo": _fan_in(12288)},
        "87bf08925d0d31e5b2e7056e3f85497686e8b50d101f1defb5b6139cfc9c3d0c"),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_existing_architecture_unchanged(name):
    """The two dense architectures, now files of their own, give the
    program's config, each leaf's draw and (at the smoke size the CPU
    tests run) the very weights that the tree before them gave."""
    from bench.tests.tiny import tiny_cell
    want_cfg, want_scales, want_digest = PINNED[name]
    cfg = H.read_json(ROOT / "bench" / "configs" / f"{name}.json")
    arch = AR.load(cfg)
    assert not hasattr(arch, "scale")
    assert M.model_config(cfg) == arch.model_config(cfg) == want_cfg
    d_in = cfg["filter"]["d_embed"]
    shapes = jax.eval_shape(lambda: M.make_params(want_cfg, d_in, 0, arch))
    got = {}
    for path, s in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        names = [p.key for p in path]
        got["/".join(names)] = "norm" if names[-1] == "w" and \
            names[-2] in M.NORMS else M._scale(path, s.shape, arch)
    assert got == want_scales
    cell = tiny_cell(next(c for c in CELLS if c.startswith(name)))
    params = M.make_params(M.model_config(cell.config), d_in=16,
                           seed=2 ** 31 + 5, arch=arch)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == want_digest
