"""Every random draw of a run, derived from ``--seed`` and a tag.

``--seed`` may exceed 32 bits; it is hashed with the tag, so the host
generator and the device key of one (seed, tag) never depend on anything
else, and two tags never share a stream.
"""
from __future__ import annotations

import hashlib

import jax
import numpy as np


def _digest(seed: int, tags) -> bytes:
    text = repr((int(seed),) + tuple(tags)).encode()
    return hashlib.blake2b(text, digest_size=16).digest()


def host_rng(seed: int, *tags) -> np.random.Generator:
    return np.random.default_rng(int.from_bytes(_digest(seed, tags), "big"))


def seed32(seed: int, *tags) -> int:
    return int.from_bytes(_digest(seed, tags)[:4], "big") & 0x7FFFFFFF


def device_key(seed: int, *tags) -> jax.Array:
    return jax.random.PRNGKey(seed32(seed, *tags))
