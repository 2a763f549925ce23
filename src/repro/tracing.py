"""Spans, host-fetch counting and device program names for the fleet path.

Spans are ``jax.profiler.TraceAnnotation``s: with a profiler capture
running they land in the same trace as the device ops, on the
profiler's clock, so a device idle gap can be put down to the innermost
``repro.`` span open at that moment; with no capture running a span records
nothing, so they are always on.  Names start ``repro.``:
``repro.executor.*``, ``repro.engine.*``, ``repro.plan.*``,
``repro.temporal.*``, and ``repro.sync.<site>`` around every
device-to-host fetch (``to_host``).

``EngineCounters`` are plain cumulative integers owned by the fleet
engine (``ShardedPlanGroupEngine.counters``); a reader takes their
difference over the interval it cares about.

Device programs and kernels carry stable names too: the plan's tier
steps ``jit_plan_<stage>``, the scan ``jit_temporal_scan``, the spatial
kernels ``spatial_stats``/``spatial_stats_rows``, and the attention
kernel ``flash_attention`` (an op of the filter step ``jit_filter_step``;
its presence there is how a trace shows that the filter trunk's
attention ran in the Pallas kernel rather than the XLA scan).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Optional

import jax
import numpy as np


def span(name: str, **meta: Any) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` (``repro.<layer>.<what>``) with metadata."""
    return jax.profiler.TraceAnnotation(name, **meta)


@dataclasses.dataclass
class EngineCounters:
    """Cumulative counts of one fleet engine's work."""
    chunks: int = 0             # run_chunk calls
    host_fetches: int = 0       # device arrays fetched to the host
    prefetch_hits: int = 0      # chunks whose stack was staged ahead
    prefetch_misses: int = 0    # chunks stacked on arrival
    steps_built: int = 0        # jitted plan and scan steps built


def to_host(x, site: str, counters: Optional[EngineCounters] = None):
    """Fetch a device array (or a list or tuple of them) to numpy under
    the span ``repro.sync.<site>``, counting one host fetch per array."""
    many = isinstance(x, (list, tuple))
    with span(f"repro.sync.{site}"):
        out = [np.asarray(a) for a in x] if many else np.asarray(x)
    if counters is not None:
        counters.host_fetches += len(out) if many else 1
    return out


def named(fn: Callable, name: str) -> Callable:
    """``fn`` under the name ``name``, sanitised to an identifier:
    ``jax.jit`` names its device program ``jit_<name>``, which is what a
    profiler trace's "XLA Modules" line shows."""
    def program(*args, **kwargs):
        return fn(*args, **kwargs)
    program.__name__ = program.__qualname__ = re.sub(r"\W", "_", name)
    return program
