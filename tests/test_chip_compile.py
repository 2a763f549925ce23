"""Compile the served path's device programs for a described TPU v5e.

Interpret mode never enforces the TPU's block-tiling rules, and on the CPU
backend ``kernels.ops`` never reaches the spatial kernels at all, so these
tests call the kernels and steps directly with ``interpret=False`` and
compile them at real widths (Qwen2-0.5B's branch tap: g=56, C=8, head
width 256) for one chip of a ``v5e:2x2`` topology that the TPU compiler
describes without a chip attached.  Nothing runs: a pass means the chip's
compiler accepts the program, not that it computes the right answer
(chip_smoke.py checks that on the chip).

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and xdist workers
import every test file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import costmodel as CM
from repro.core import query as Q
from repro.core.filters import FilterOutputs
from repro.core.stats import SlotStats
from repro.core.temporal import TemporalProgram
from repro.distributed.multistream import (ShardedPlanGroupEngine,
                                           route_streams)
from repro.kernels import ops
from repro.kernels.cam_head import cam_head_bgd
from repro.kernels.spatial_predicate import (spatial_stats_bgc,
                                             spatial_stats_rows_bgc)

G, C, D, B = 56, 8, 256, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile is written to a persistent cache but can
    # never be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_spatial_stats_bgc_compiles(one_chip):
    grid = _shape((B, G, G, C), jnp.float32, one_chip)
    hlo = _compile_hlo(lambda x: spatial_stats_bgc(x, interpret=False), grid)
    assert "tpu_custom_call" in hlo
    assert "%spatial_stats." in hlo         # the kernel's name in the trace


def test_spatial_stats_rows_bgc_compiles(one_chip):
    grid = _shape((B, G, G, C), jnp.float32, one_chip)
    rows = _shape((B // 2,), jnp.int32, one_chip)
    hlo = _compile_hlo(
        lambda x, r: spatial_stats_rows_bgc(x, r, interpret=False),
        grid, rows)
    assert "tpu_custom_call" in hlo
    assert "%spatial_stats_rows." in hlo


def test_cam_head_bgd_compiles(one_chip):
    feat = _shape((B, G * G, D), jnp.float32, one_chip)
    w = _shape((D, C), jnp.float32, one_chip)
    b = _shape((C,), jnp.float32, one_chip)
    hlo = _compile_hlo(lambda f, w_, b_: cam_head_bgd(
        f, w_, b_, d_block=D, interpret=False), feat, w, b)
    assert "tpu_custom_call" in hlo


def test_temporal_scan_step_compiles(one_chip):
    prog = TemporalProgram((
        Q.Duration(Q.Spatial(0, Q.Rel.LEFT, 1, radius=1), 3),
        Q.SlidingCount(Q.ClassCount(2, Q.Op.GE, 1), 5, Q.Op.GE, 2),
        Q.Sequence(Q.Count(Q.Op.GE, 2), Q.Region(3, (0, 0, 28, 28)), 4)))
    prog.start_window(32)
    state = tuple(_shape(np.shape(x), np.asarray(x).dtype, one_chip)
                  for x in prog._state_tuple())
    signals = _shape((B, prog.n_signals), jnp.bool_, one_chip)
    jax.jit(prog.build_scan_fn()).lower(state, signals).compile()


def test_sharded_group_spatial_step_compiles(topo, monkeypatch):
    """The fleet's spatial tier for 16 streams, vmapped over the stream
    axis and shard_map-ed over a ("stream",) mesh of four chips.  The
    plan reaches the kernel through ``kernels.ops``, which asks the
    backend: steer it to the TPU branch here."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    S, n_dev = 16, 4
    mesh = Mesh(np.asarray(topo.devices[:n_dev]), ("stream",))
    eng = ShardedPlanGroupEngine(
        (Q.Spatial(0, Q.Rel.LEFT, 1, radius=1),),
        route_streams([f"cam{i}" for i in range(S)], n_dev), None,
        slot_stats=SlotStats(), mesh=mesh, cost_model=CM.static_cost_model())
    assert eng.shard_wrap is not None
    st = eng.staged
    si = next(i for i in st.order if st.stages[i].name == "spatial")
    step = st._get_group_step(si, frozenset(), None, "batch", S,
                              eng.shard_wrap, eng.wrap_sig)
    sh = NamedSharding(mesh, P("stream"))
    outs = FilterOutputs(counts=_shape((S, B, C), jnp.float32, sh),
                         grid=_shape((S, B, G, G, C), jnp.float32, sh))
    hlo = step.lower(outs, _shape((S, B, st.plan.n_slot_cols), jnp.bool_, sh),
                     _shape((S, st.plan.n_distinct), jnp.bool_, sh)
                     ).compile().as_text()
    assert "tpu_custom_call" in hlo
    # the names the device trace shows: the tier's program, the kernel
    assert hlo.startswith("HloModule jit_plan_spatial")
    assert "spatial_stats" in hlo


# Temporary bytes of the XLA flash scan's call at S = 3136 (bidirectional,
# chunk 512, eight query macro-blocks), compiled for the same chip: its
# float32 score blocks, for (head dim, batch).
XLA_FLASH_TEMP_BYTES = {(64, 8): 60e6, (64, 32): 600e6,
                        (128, 8): 292e6, (128, 32): 1422e6}


@pytest.mark.parametrize("B_", [8, 32])
@pytest.mark.parametrize("H,KV,hd", [(14, 2, 64), (24, 2, 128)])
def test_flash_attention_compiles_at_filter_widths(one_chip, monkeypatch,
                                                   H, KV, hd, B_):
    """The filter trunk's attention (Qwen2-0.5B: 14 heads over 2 KV heads
    of 64; StarCoder2-3B: 24 over 2 of 128) at its 56 x 56 patches, as the
    projections hand it over: the Pallas kernel, with none of the XLA
    path's score temporaries.  ``kernels.ops`` asks the backend: steer it
    to the compiled kernel here."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    S = 3136

    def attend(q, k, v):
        split = lambda x, n: x.reshape(B_, S, n, hd)
        return ops.flash_attention(split(q, H), split(k, KV), split(v, KV),
                                   causal=False).reshape(B_, S, H * hd)

    q = _shape((B_, S, H * hd), jnp.bfloat16, one_chip)
    kv = _shape((B_, S, KV * hd), jnp.bfloat16, one_chip)
    compiled = jax.jit(attend).lower(q, kv, kv).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert "%flash_attention" in hlo        # the kernel's name in the trace
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < XLA_FLASH_TEMP_BYTES[(hd, B_)] / 10, temp


@pytest.mark.parametrize("partitioned", [True, False])
def test_train_step_attention_path(topo, monkeypatch, partitioned):
    """A decoder train step at 512 tokens.  Partitioned over a
    (data, model) mesh of the four chips and traced under its sharder, as
    the dry-run and ``launch/train.py`` trace it, the attention stays on
    the XLA scan, which GSPMD splits over batch and heads: no Pallas
    kernel, whose custom call it would replicate.  The same step on one
    chip, with no sharder, runs the kernel forward and the scan's VJP
    back."""
    import contextlib
    from jax.sharding import Mesh, SingleDeviceSharding
    from repro.models.config import ModelConfig, ShapeCell
    from repro.optim import adamw
    from repro.train import step as TS
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = ModelConfig(name="t", n_layers=2, d_model=256, n_heads=4,
                      n_kv_heads=2, head_dim=64, d_ff=512, vocab_size=512,
                      max_seq_len=512, dtype="bfloat16")
    cell, opt = ShapeCell("t", 512, 8, "train"), adamw(1e-3)
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(2, 2),
                ("data", "model"))
    jitted, plan = TS.jit_step_for_cell(cfg, cell, mesh, opt)
    state, inputs = plan.abstract_state, plan.abstract_inputs
    if partitioned:
        sharder = plan.sharder()
    else:
        chip = SingleDeviceSharding(topo.devices[0])
        state, inputs = jax.tree.map(
            lambda a: _shape(a.shape, a.dtype, chip), (state, inputs))
        jitted, sharder = jax.jit(TS.build_train_step(cfg, opt)), \
            contextlib.nullcontext()
    with sharder:
        hlo = jitted.lower(state, inputs).compile().as_text()
    assert ("%flash_attention" in hlo) is not partitioned
