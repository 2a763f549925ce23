"""Activation-sharding context.

Models stay mesh-agnostic; the step factories install a sharder around
tracing so intermediate activations get ``with_sharding_constraint``s
(batch -> ("pod","data")) without threading mesh objects through model
code.  Install happens at trace time (inside ``.lower()``), so there is
no runtime cost.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Callable, Optional

import jax

_state = threading.local()


def _get() -> Optional[Callable]:
    return getattr(_state, "sharder", None)


@contextlib.contextmanager
def activation_sharder(fn: Callable[[jax.Array, str], jax.Array]):
    prev = _get()
    _state.sharder = fn
    try:
        yield
    finally:
        _state.sharder = prev


def constrain(x: jax.Array, kind: str = "act") -> jax.Array:
    fn = _get()
    return fn(x, kind) if fn is not None else x


def partitioned() -> bool:
    """Whether the step being traced is partitioned over a mesh: its
    factory installed a sharder (every partitioned step is traced under
    one).  Model code keeps such steps off single-device kernels."""
    return _get() is not None


# --- sequence-sharded decode attention (serving fast path) ----------------

def _get_ds() -> Optional[dict]:
    return getattr(_state, "decode_shard", None)


@contextlib.contextmanager
def decode_shard(mesh, seq_axis: str = "model",
                 batch_axes=("pod", "data")):
    """Route single-token cached attention through the shard_map path
    (repro.distributed.serve_attention) during tracing."""
    prev = _get_ds()
    _state.decode_shard = {"mesh": mesh, "seq_axis": seq_axis,
                           "batch_axes": batch_axes}
    try:
        yield
    finally:
        _state.decode_shard = prev


def get_decode_shard() -> Optional[dict]:
    return _get_ds()
