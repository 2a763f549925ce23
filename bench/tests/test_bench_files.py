"""Each cell's files load, and a new cell, configuration, traffic, query
mix and per-layer metric are picked up from new files alone."""
import hashlib
import json
import re
import shutil
from pathlib import Path

import pytest

from bench import answers as A
from bench import harness as H

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


def test_new_cell_is_files_only(tmp_path):
    """A cell of a new configuration, traffic and query mix, with a new
    per-layer metric, added to a copy: only new files and new entries in
    BENCHMARK.json, and the harness finds them all by name."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = _digest(tmp_path / "bench")
    b = tmp_path / "bench"
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
