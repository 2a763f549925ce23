"""CPU rehearsal of ``bench/run.py``: a smoke-size run of each path prints
a well-formed result; ``correct`` comes out false under each fault the
served path can have; the command refuses to run without a TPU.

The tests skip the command's look for a chip and steer the run from here:
a smoke-size copy of each cell (``tiny.py``), Pallas kernels in interpret
mode (the CPU backend's default), the four-chip cell on four forced host
devices in a child process.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import bench.run as R
from bench import harness as H
from bench.tests.tiny import tiny_cell

ROOT = H.ROOT
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
ONE_CHIP = ["qwen2-0.5b.live-detrac", "starcoder2-3b.archive-detrac"]


@pytest.fixture
def cpu_peaks(monkeypatch):
    monkeypatch.setattr(H, "peaks", lambda kind, root=None: PEAK)


def _well_formed(res, cell, trace):
    json.dumps(res)
    assert list(res)[:3] == ["correct", "attempted", "failed"]
    assert list(res)[-1] == "checks"
    assert res["device"]["count"] >= 1 and res["device"]["kind"]
    want = cell.per_layer if trace else cell.end_to_end
    assert set(res["metrics"]) <= {m["name"] for m in want}
    for m in res["metrics"].values():
        assert np.isfinite(m["value"])
    if trace:
        assert {"busy_s", "window_s"} <= set(res["device"])
    else:
        assert set(res["metrics"]) == {m["name"] for m in want}
        assert all(m["value"] > 0 for m in res["metrics"].values())


@pytest.mark.parametrize("name", ONE_CHIP)
@pytest.mark.parametrize("trace", [0, 1])
def test_smoke_run(name, trace, cpu_peaks):
    cell = tiny_cell(name)
    res = R.measure(cell, 2 ** 31 + 11, 1.0, bool(trace),
                    time.perf_counter())
    _well_formed(res, cell, trace)
    assert res["correct"], res["checks"]
    assert res["checks"]["answer_mismatches"]["value"] == 0


def _first_answer(kind, x):
    if kind != "answers":
        return x
    x = np.array(x)
    x[0, 0, 0] = ~x[0, 0, 0]
    return x


class _Stale:
    """A step that returns its state unchanged: every chunk's answers
    are the first chunk's."""

    def __init__(self):
        self.first = None

    def __call__(self, kind, x):
        if kind != "answers":
            return x
        if self.first is None:
            self.first = np.array(x)
        return self.first


def _half_batch(kind, x):
    """Half of the batch left out: the second half of each chunk's
    filter outputs repeats the first half."""
    if kind != "outputs":
        return x
    import jax.numpy as jnp
    h = x.counts.shape[0] // 2
    return type(x)(counts=jnp.concatenate([x.counts[:h]] * 2),
                   grid=jnp.concatenate([x.grid[:h]] * 2))


@pytest.mark.parametrize("fault", [_first_answer, _Stale, _half_batch],
                         ids=["answer_altered", "state_unchanged",
                              "half_batch"])
def test_fault_fails_correct(fault, cpu_peaks, monkeypatch):
    fault = fault() if isinstance(fault, type) else fault
    real = H.Fleet.serve

    def broken(self, n, schedule, deadline_s=None, record=True, fault=None):
        return real(self, n, schedule, deadline_s, record,
                    fault=broken_fault)

    broken_fault = fault
    monkeypatch.setattr(H.Fleet, "serve", broken)
    cell = tiny_cell("qwen2-0.5b.live-detrac")
    res = R.measure(cell, 5, 1.0, False, time.perf_counter())
    assert not res["correct"], res["checks"]


FOUR_CHIP = r"""
import json, os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [sys.argv[1], os.path.join(sys.argv[1], "src")]
import jax
import numpy as np
import bench.run as R
from bench import harness as H
from bench.tests.tiny import tiny_cell
assert jax.device_count() == 4
H.peaks = lambda kind, root=None: {"bf16_flops_per_s": 197e12,
                                   "hbm_bytes_per_s": 819e9}
cell = tiny_cell("qwen2-0.5b.archive-detrac-4chip")
out = {"sound": R.measure(cell, 3, 1.0, True, time.perf_counter())}
real = H.Fleet.serve

def no_exchange(kind, x):
    # every chip answers its first block: the exchange that places each
    # stream's block on its own chip is left out
    if kind != "answers":
        return x
    x = np.array(x)
    q = x.shape[0] // 4
    return np.concatenate([x[:q]] * 4)

H.Fleet.serve = lambda self, n, s, deadline_s=None, record=True, fault=None: \
    real(self, n, s, deadline_s, record, fault=no_exchange)
out["broken"] = R.measure(cell, 3, 1.0, False, time.perf_counter())
print(json.dumps(out))
"""


def test_four_chip_path_and_exchange_fault():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", FOUR_CHIP, str(ROOT)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    cell = tiny_cell("qwen2-0.5b.archive-detrac-4chip")
    _well_formed(out["sound"], cell, True)
    assert out["sound"]["correct"], out["sound"]["checks"]
    assert out["sound"]["device"]["count"] == 4
    assert not out["broken"]["correct"]


def test_command_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         ONE_CHIP[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
