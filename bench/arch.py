"""What a configuration's published keys say about its trunk.

A configuration file keeps the source's own keys (a Hugging Face
``config.json``).  The architecture they imply is a file of its own,
``bench/archs/<model_type>.py``, found by the configuration's
``model_type``, so that a new architecture comes as new files.  Each such
file gives

- ``trunk(cfg)``: the options the published keys imply, as a dict (for a
  dense trunk ``gated_mlp``, ``layernorm``, ``norm_eps``, ``qkv_bias`` and
  ``proj_bias``), read by the plain reference;
- ``model_config(cfg)``: the program's ``ModelConfig`` for the layers the
  filter runs, refusing what the program cannot serve;
- ``trunk_flops(cfg, tokens)``: the model operations of all the trunk
  layers the filter runs, for one frame of ``tokens`` positions;
- optionally ``scale(names, dims)``: the standard deviation of the weight
  draw for a leaf the architecture adds (``names`` its path, ``dims`` its
  shape without the stacked layer axis), or None for the default draw
  (``bench/model.py``).
"""
from __future__ import annotations

import importlib.util
import re
from pathlib import Path
from types import ModuleType
from typing import Any, Dict

ROOT = Path(__file__).resolve().parents[1]
MODEL_TYPE = re.compile(r"^[A-Za-z0-9_-]+$")


def load_module(path: Path, name: str) -> ModuleType:
    """The Python file ``path``, executed as a module named ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(cfg: Dict[str, Any], root: Path = ROOT) -> ModuleType:
    """The architecture file of ``cfg["model_type"]`` under ``root``."""
    kind = cfg["model_type"]
    path = Path(root) / "bench" / "archs" / f"{kind}.py"
    if not MODEL_TYPE.match(kind) or not path.is_file():
        raise ValueError(f"no trunk architecture for model_type {kind!r}: "
                         f"no file {path}")
    return load_module(path, f"bench_arch_{kind}")


def trunk(cfg: Dict[str, Any], root: Path = ROOT) -> Dict[str, Any]:
    """The trunk options the configuration's keys imply."""
    return load(cfg, root).trunk(cfg)
