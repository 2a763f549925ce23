"""Distributed machinery: sharding rules, compression, pipeline, loader."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import compression as COMP
from repro.distributed import sharding as SH


# ---------------------------------------------------------------------------
# Sharding rules
# ---------------------------------------------------------------------------

class _FakeMesh:
    def __init__(self, shape, names):
        import numpy as _np
        self.axis_names = names
        self.devices = _np.empty(shape)


def test_spec_divisibility_fallback():
    mesh = _FakeMesh((16, 16), ("data", "model"))
    # kv_heads=8 not divisible by model=16 -> replicate
    s = SH.spec_for(("embed", "kv_heads", "head_dim"), (8192, 8, 128),
                    mesh, SH.DEFAULT_RULES)
    assert s == P("data")
    # heads=64 divisible -> sharded
    s2 = SH.spec_for(("embed", "heads", "head_dim"), (8192, 64, 128),
                     mesh, SH.DEFAULT_RULES)
    assert s2 == P("data", "model")


def test_spec_batch_tuple_shrink():
    mesh = _FakeMesh((2, 16, 16), ("pod", "data", "model"))
    # batch=256 divisible by pod*data=32
    s = SH.spec_for(("batch", None), (256, 10), mesh, SH.DEFAULT_RULES)
    assert s == P(("pod", "data"))
    # batch=2: only the pod axis fits
    s2 = SH.spec_for(("batch", None), (2, 10), mesh, SH.DEFAULT_RULES)
    assert s2 == P("pod")
    # batch=1: replicate
    s3 = SH.spec_for(("batch", None), (1, 10), mesh, SH.DEFAULT_RULES)
    assert s3 == P()


def test_no_axis_reuse_within_spec():
    mesh = _FakeMesh((16, 16), ("data", "model"))
    rules = SH.make_rules({"a": "model", "b": "model"})
    s = SH.spec_for(("a", "b"), (16, 16), mesh, rules)
    assert s == P("model")        # second use dropped


def test_rules_overrides():
    r = SH.make_rules({"embed": None})
    assert r["embed"] is None and SH.DEFAULT_RULES["embed"] == "data"


def test_resolve_axis_tuple_shrink_fallback():
    ma = {"pod": 2, "data": 16, "model": 16}
    assert SH._resolve_axis(None, 128, ma) is None
    assert SH._resolve_axis("model", 64, ma) == "model"
    assert SH._resolve_axis("model", 10, ma) is None      # 10 % 16 != 0
    assert SH._resolve_axis(("pod", "data"), 64, ma) == ("pod", "data")
    # dim=2 can't cover pod*data=32: shrink to the ("pod",) prefix
    assert SH._resolve_axis(("pod", "data"), 2, ma) == "pod"
    # dim=1 shards nowhere: replicate
    assert SH._resolve_axis(("pod", "data"), 1, ma) is None
    # axes absent from the mesh drop out before the divisibility check
    assert SH._resolve_axis(("ghost", "data"), 32, ma) == "data"
    assert SH._resolve_axis(("ghost",), 32, ma) is None


def test_spec_duplicate_axis_suppression_tuples():
    mesh = _FakeMesh((2, 16), ("pod", "data"))
    rules = SH.make_rules({"a": ("pod", "data"), "b": "data", "c": "pod"})
    # b and c resolve to mesh axes a already consumed: both suppressed
    s = SH.spec_for(("a", "b", "c"), (32, 16, 2), mesh, rules)
    assert s == P(("pod", "data"))
    # a tuple whose *any* member is taken is dropped whole, and the
    # resulting trailing None is trimmed from the spec
    s2 = SH.spec_for(("b", "a"), (16, 32), mesh, rules)
    assert s2 == P("data")


def test_stream_mesh():
    m = SH.stream_mesh()
    assert m.axis_names == ("stream",)
    assert m.devices.size == jax.device_count()
    assert SH.stream_mesh(1).devices.size == 1
    with pytest.raises(ValueError, match="devices"):
        SH.stream_mesh(jax.device_count() + 1)


# ---------------------------------------------------------------------------
# Gradient compression
# ---------------------------------------------------------------------------

def test_compress_roundtrip_small_error():
    g = {"w": jnp.linspace(-1, 1, 100).reshape(10, 10)}
    err = COMP.init_error_state(g)
    q, scales, new_err = COMP.compress(g, err)
    deq = COMP.decompress(q, scales)
    max_err = float(jnp.max(jnp.abs(deq["w"] - g["w"])))
    assert max_err <= float(scales["w"]) * 0.5 + 1e-7
    # error feedback stores exactly the residual
    np.testing.assert_allclose(new_err["w"], g["w"] - deq["w"], atol=1e-7)


def test_error_feedback_unbiased_over_steps():
    """Constant gradient: error feedback makes the *sum* of dequantised
    grads converge to the sum of true grads."""
    g = {"w": jnp.array([0.301, -0.7003, 0.11])}
    err = COMP.init_error_state(g)
    acc = jnp.zeros(3)
    for _ in range(50):
        q, s, err = COMP.compress(g, err)
        acc = acc + COMP.decompress(q, s)["w"]
    np.testing.assert_allclose(acc / 50, g["w"], atol=1e-3)


def test_allreduce_compressed_single_device():
    mesh = jax.make_mesh((1,), ("data",))
    g = {"w": jnp.arange(8.0) / 7 - 0.5}
    err = COMP.init_error_state(g)

    def f(gg, ee):
        return COMP.allreduce_compressed(gg, ee, "data")

    out, new_err = jax.shard_map(
        f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False)(g, err)
    np.testing.assert_allclose(out["w"], g["w"], atol=0.01)


# ---------------------------------------------------------------------------
# Pipeline parallelism (multi-device subprocess: 4 fake CPU devices)
# ---------------------------------------------------------------------------

PIPE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import make_pipelined_forward
from repro.models.config import ModelConfig
from repro.models import model as M

cfg = ModelConfig(name="p", n_layers=4, d_model=32, n_heads=4, n_kv_heads=2,
                  head_dim=8, d_ff=64, vocab_size=64, dtype="float32",
                  attn_impl="xla_naive", scan_layers=False)
rng = jax.random.PRNGKey(0)
params = M.init_params(rng, cfg)
mesh = jax.make_mesh((4,), ("pod",))
x = jax.random.normal(rng, (4, 2, 8, 32))          # (n_micro, mb, S, D)

ref, _, _ = M.run_layers(params["layers"], x.reshape(8, 8, 32), cfg,
                         positions=jnp.arange(8)[None])
fn = make_pipelined_forward(cfg, mesh, pipe_axis="pod", n_micro=4)
out = fn(params["layers"], x)
err = float(jnp.max(jnp.abs(out.reshape(8, 8, 32) - ref)))
print("PIPE_ERR", err)
assert err < 1e-4, err
print("PIPE_OK")
"""


def test_pipeline_parallel_4stage_subprocess():
    r = subprocess.run([sys.executable, "-c", PIPE_SCRIPT],
                       cwd=os.path.join(os.path.dirname(__file__), ".."),
                       capture_output=True, text=True, timeout=600)
    assert "PIPE_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


# ---------------------------------------------------------------------------
# Prefetching loader fault tolerance
# ---------------------------------------------------------------------------

def test_sharded_loader_skips_corrupt_batches():
    from repro.data.pipeline import ShardedLoader

    # iterator that raises on some next() calls (corrupt shard reads)
    class FlakyIter:
        def __init__(self):
            self.i = 0
        def __iter__(self):
            return self
        def __next__(self):
            self.i += 1
            if self.i > 10:
                raise StopIteration
            if self.i % 3 == 1:
                raise ValueError("corrupt shard")
            return {"x": np.full((2, 2), self.i, np.float32)}

    sh = {"x": NamedSharding(jax.make_mesh((1,), ("data",)), P())}
    loader = ShardedLoader(FlakyIter(), sh, prefetch=2)
    got = [int(b["x"][0, 0]) for b in loader]
    assert got == [2, 3, 5, 6, 8, 9]
    assert loader.skipped == 4
