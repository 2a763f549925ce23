"""CPU rehearsal of chip_smoke.py: its phases at a smoke-sized config.

The script itself refuses to run without a TPU, so these tests import its
phase functions and steer them from here: the Qwen2-0.5B smoke config
(in bf16, so the precision check compares two different dtypes), Pallas
kernels in interpret mode, and the four-chip phase on four forced host
devices in a child process.
"""
import dataclasses
import importlib.util
import os
import subprocess
import sys

import pytest

from repro.configs import get_smoke_config

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod     # its dataclasses resolve through it
    spec.loader.exec_module(mod)
    return mod


def _smoke_cfg():
    return dataclasses.replace(get_smoke_config("qwen2_0p5b"),
                               dtype="bfloat16")


def test_main_refuses_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert out.out == "" and "TPU" in out.err


def test_one_chip_phases_rehearsal(chip_smoke, capsys):
    chip_smoke.one_chip_phases(_smoke_cfg(), 0, on_chip=False, n_streams=2,
                               n_frames=32)
    out = capsys.readouterr().out
    assert "check fleet: staged == exhaustive" in out
    assert "check oracle path: staged == exhaustive" in out
    assert '"source": "static"' in out


FOUR_CHIP_SCRIPT = r"""
import dataclasses, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, ".")
import chip_smoke as cs
import jax
from repro.configs import get_smoke_config
assert jax.device_count() == 4
cfg = dataclasses.replace(get_smoke_config("qwen2_0p5b"), dtype="bfloat16")
cs.four_chip_phase(cfg, 0, n_devices=4, n_streams=8, n_frames=32)
print("FOUR_CHIP_OK")
"""


def test_four_chip_phase_rehearsal_4dev_subprocess():
    r = subprocess.run([sys.executable, "-c", FOUR_CHIP_SCRIPT], cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert "FOUR_CHIP_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
    assert "identical to the unsharded reference" in r.stdout
