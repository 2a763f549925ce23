"""Compile the benchmark's programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/compile_for_chip.py

Run by hand before spending chip time on a new configuration or path:
the TPU compiler refuses here what it would refuse on the chip (tiling,
fast-memory limits, programs that do not fit), at no chip cost.  It
compiles, for one described v5e chip,

- each cell's served filter step at the cell's chunk (trunk prefix, its
  branch head; the IC head's CAM kernel), and
- the full 24-layer Qwen2-0.5B forward over a bucket of 8 frames, the
  model oracle a cascade cell would run,

and prints each program's compile time, its memory analysis and whether
a Pallas kernel (``tpu_custom_call``) is in it.  Nothing runs, so it says
nothing about results or times.
"""
from __future__ import annotations

import dataclasses
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench.harness import load_cell, read_json  # noqa: E402
from bench.model import filter_step_fn, make_params, model_config  # noqa
from repro.kernels import ops  # noqa: E402
from repro.models import model as M  # noqa: E402

BATCH = 8


def _on(tree, sharding):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                       sharding=sharding),
                        tree)


def _report(name: str, lowered) -> None:
    t0 = time.perf_counter()
    compiled = lowered.compile()
    dt = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    kernel = "tpu_custom_call" in compiled.as_text()
    print(f"{name}: compiled in {dt:.1f} s; temp "
          f"{mem.temp_size_in_bytes / 2**30:.3f} GiB, arguments "
          f"{mem.argument_size_in_bytes / 2**30:.3f} GiB, outputs "
          f"{mem.output_size_in_bytes / 2**30:.3f} GiB; Pallas kernel: "
          f"{kernel}", flush=True)


def main() -> int:
    jax.config.update("jax_enable_compilation_cache", False)
    ops._interpret = lambda: False      # this backend is the CPU; the
    #                                     programs are for the chip
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    for w in read_json(ROOT / "BENCHMARK.json")["workloads"]:
        cell = load_cell(w["name"])
        cfg, batch = cell.config, int(cell.traffic["chunk"])
        mcfg = model_config(cfg)
        d_in, g = cfg["filter"]["d_embed"], cfg["filter"]["grid"]
        params = jax.eval_shape(lambda: make_params(mcfg, d_in, 0))
        frames = [jax.ShapeDtypeStruct((g * g, d_in), jnp.float32,
                                       sharding=one)] * batch
        _report(f"{cell.name} filter step, batch {batch}",
                filter_step_fn(mcfg).lower(_on(params, one), frames))
    cfg = read_json(ROOT / "bench" / "configs" / "qwen2-0.5b.json")
    full = dataclasses.replace(model_config(cfg),
                               n_layers=cfg["published"]["num_hidden_layers"])
    trunk = jax.eval_shape(lambda k: M.init_params(k, full),
                           jax.random.PRNGKey(0))
    g2 = cfg["filter"]["grid"] ** 2
    x = jax.ShapeDtypeStruct((BATCH, g2, full.d_model), jnp.bfloat16,
                             sharding=one)

    def oracle(p, e):
        return M.forward(p, full, embeds=e, causal=False,
                         tap_layer=full.n_layers, stop_at_tap=True).tap

    _report(f"qwen2-0.5b 24-layer oracle forward, bucket {BATCH}",
            jax.jit(oracle).lower(_on(trunk, one), x))
    return 0


if __name__ == "__main__":
    sys.exit(main())
