"""Fleet-scale multi-stream serving: S camera streams, one staged plan.

The single-stream executors (repro.core.streaming) drive one camera
through the shared multi-query cascade; a production monitor serves
hundreds of cameras x thousands of registered queries.  This module
multiplexes S streams through ONE ``StagedQueryPlan`` by stacking their
per-chunk frame batches on a leading stream axis and running the staged
stage steps as single fused programs over the stack
(``StagedQueryPlan.evaluate_group``):

- **Hash routing.**  Streams are ordered by a stable hash of their ids
  (``route_streams``) and assigned to contiguous mesh-slot blocks, so a
  stream keeps its stack position — and therefore its device — across
  chunks and registry epochs: the per-(stage, prefix, bucket) jit caches
  and device-resident state stay hot, and adjacent camera ids spread
  across devices instead of clustering.

- **shard_map over the stream axis.**  With a ``("stream",)`` device
  mesh (``distributed.sharding.stream_mesh``), each group step is
  wrapped in ``jax.shard_map``: device d
  evaluates its block of streams, one dispatch for the whole fleet
  slice.  The PartitionSpec comes from the ordinary sharding rules
  (``spec_for`` — so an S not divisible by the device count falls back
  to replication, with a warning, instead of erroring: the same
  divisibility discipline as every other axis).

- **Double-buffered prefetch.**  ``run_chunk(idx, next_idx)`` stages
  chunk k+1's stacked ``FilterOutputs`` onto the mesh with
  ``jax.device_put`` *before* blocking on chunk k's answers — JAX
  dispatch is async, so host->device transfer of the next chunk overlaps
  evaluation of the current one.

- **Fleet warm-start (gossip).**  The engine's ``SlotStats`` store
  typically comes from ``QueryRegistry(gossip_paths=[...])`` —
  ``SlotStats.load_merged`` folds peer workers' snapshots so stage
  ordering and restage decisions start from the fleet's pooled
  selectivity priors, and the ``CostModel`` prices the group steps with
  the same per-backend calibration as single-device bodies.

- **Fleet-wide temporal short-circuiting.**  When any registered query
  carries a temporal operator, the engine compiles the set through ONE
  shared ``TemporalProgram`` structure with per-stream automaton state,
  stages the *deduped frame signals* through the group plan, and
  advances all S windows at once with ``temporal.advance_group`` (one
  vmapped — and mesh-sharded, when a mesh is given — ``lax.scan`` step
  over the stream axis).  Each stream's window-decided signal columns
  feed ``evaluate_group(presumed_decided=...)`` so decided streams stop
  paying for stages only they needed; a chunk where EVERY stream's
  every query is window-decided skips fetch, stacking, and the staged
  plan outright (frame skipping in time, fleet-wide).  The executor
  fires ``on_window_start`` at hopping-window boundaries exactly as the
  single-stream loop does (including for engines rebuilt mid-window by
  registry churn, which cold-restart their automata — the documented
  single-stream semantics).

Per-stream answers are bit-identical to running each stream serially
through ``MultiQueryStreamExecutor`` (property-pinned in
tests/test_multistream.py), including under mid-stream register/retire
and per-stream skew — group staging only ever evaluates more than a
stream's solo staging would, which monotone decidedness makes harmless.
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import query as Q
from repro.core.filters import FilterOutputs
from repro.core.plan import QueryPlan
from repro.core.streaming import (HoppingWindow, QueryRegistry,
                                  StragglerPolicy, StreamStats, _accepts_kw,
                                  stream_seed)
from repro.core.temporal import TemporalProgram, TemporalStats, advance_group
from repro.distributed import sharding as SH


# --------------------------------------------------------------------------
# Stream routing
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StreamContext:
    """One stream's fixed identity within the fleet executor.

    ``position`` is the stream's index on the stacked stream axis (fixed
    across chunks — jit caches and placement stay stable), ``slot`` the
    mesh-slot block it is routed to, ``seed`` the per-stream sampling
    seed derived via ``streaming.stream_seed`` so parallel streams never
    sample identical frame offsets."""
    stream_id: Any
    position: int
    slot: int
    seed: int


def _stream_hash(stream_id: Any) -> int:
    h = hashlib.blake2b(str(stream_id).encode(), digest_size=8)
    return int.from_bytes(h.digest(), "big")


def route_streams(stream_ids: Sequence[Any], n_slots: int, *,
                  base_seed: int = 0) -> List[StreamContext]:
    """Hash-route streams to fixed mesh slots.

    Streams are ordered by a stable hash of their ids and cut into
    ``n_slots`` contiguous, balanced blocks: block b holds the streams
    whose stack positions map to mesh slot b, so a stream-axis
    ``shard_map`` places each block on one device.  The hash (not the
    raw id) decides adjacency, so consecutively-numbered cameras spread
    across devices; because it depends only on the id, a stream keeps
    its slot across restarts and across workers — the routing is the
    fleet's consistent-hashing layer."""
    if len(set(stream_ids)) != len(stream_ids):
        raise ValueError("duplicate stream ids")
    n_slots = max(1, int(n_slots))
    ordered = sorted(stream_ids, key=lambda sid: (_stream_hash(sid),
                                                  str(sid)))
    S = len(ordered)
    return [StreamContext(stream_id=sid, position=i,
                          slot=i * n_slots // max(S, 1),
                          seed=stream_seed(base_seed, sid))
            for i, sid in enumerate(ordered)]


# --------------------------------------------------------------------------
# Group engine: stacked staged-plan evaluation
# --------------------------------------------------------------------------

class ShardedPlanGroupEngine:
    """Evaluates S streams' chunks through one shared staged plan.

    ``fetch(stream_ctx, idx) -> FilterOutputs`` supplies one stream's
    filter outputs for a chunk's frame indices (all streams advance in
    lockstep over the same stream-local frame schedule).  ``run_chunk``
    stacks them on the stream axis, places the stack on the mesh, and
    runs ``StagedQueryPlan.evaluate_group`` — group-uniform staging, one
    fused sharded step per executed tier.

    ``mesh`` (a ``("stream",)`` mesh from ``sharding.stream_mesh``)
    turns the group steps into ``shard_map`` programs; without it (or,
    with a warning, when S doesn't divide over the mesh axis —
    ``spec_for`` falls back to replication) the steps run as plain
    vmapped programs on the default device, which is also the
    bit-identity reference path.

    ``slot_stats`` is the shared population ledger (typically the
    registry's, possibly gossip-warm-started): it orders the stages at
    construction and keeps learning from the group's full-batch tiers;
    every ``restage_every`` chunks the engine re-sorts its stage order
    from the live ledger.  ``cost_model`` prices the group steps
    (default: the per-backend ``default_cost_model()``).

    ``leaf_table`` / ``step_cache`` are the registry's plan-lifecycle
    stores (repro.core.stepcache): with both, a registry-epoch rebuild
    of this engine keeps its slot ids stable and re-hits every compiled
    group step whose stage signature didn't change — mid-stream
    register/retire stops cold-starting the untouched stages' sharded
    steps.  The mesh identity in those step keys is a *content* digest
    of the device assignment (``wrap_sig``), not the wrap closure's
    object identity, precisely so rebuilt engines over the same mesh
    share steps.

    ``counters`` (``tracing.EngineCounters``) counts the engine's chunks,
    its device-to-host fetches (the plan's and the temporal scan's
    included), prefetch hits and misses, and the jitted steps it built;
    each chunk runs under the span ``repro.engine.run_chunk``."""

    def __init__(self, queries: Sequence, streams: Sequence[StreamContext],
                 fetch: Callable[[StreamContext, np.ndarray], FilterOutputs],
                 *, slot_stats=None, mesh=None, tau: float = 0.2,
                 cost_model=None, min_bucket: Optional[int] = None,
                 spatial_body: str = "auto", restage_every: int = 16,
                 leaf_table=None, step_cache=None):
        from repro.core import costmodel as CM
        self.streams = sorted(streams, key=lambda c: c.position)
        if [c.position for c in self.streams] != list(range(len(streams))):
            raise ValueError("stream positions must be 0..S-1 "
                             "(use route_streams)")
        self.fetch = fetch
        self.slot_stats = slot_stats
        self.mesh = mesh
        self.restage_every = restage_every
        self.queries = tuple(queries)
        self._step_cache = step_cache
        self.counters = tracing.EngineCounters()
        # temporal queries: plan over the deduped frame signals, keep
        # per-stream automaton state (shared structure, one window per
        # stream), advance all windows with one vmapped scan step
        if any(Q.has_temporal(q) for q in self.queries):
            self.temporal: Optional[List[TemporalProgram]] = [
                TemporalProgram(self.queries, step_cache=step_cache)
                for _ in self.streams]
            self.temporal_stats = TemporalStats()
            plan_queries = tuple(self.temporal[0].frame_queries)
        else:
            self.temporal = None
            self.temporal_stats = None
            plan_queries = self.queries
        self.plan = QueryPlan(plan_queries, tau=tau,
                              leaf_table=leaf_table)
        cm = cost_model if cost_model is not None \
            else CM.default_cost_model()
        self.cost_model = cm
        self.staged = self.plan.build_staged(
            slot_stats, min_bucket=min_bucket, cost_model=cm,
            spatial_body=spatial_body, step_cache=step_cache)
        self.staged.counters = self.counters
        self._chunks = 0
        self._next: Optional[Tuple[Tuple[int, int, int], FilterOutputs]] = \
            None
        self._sharding = None
        self.shard_wrap: Optional[Callable] = None
        self.wrap_sig: Optional[Tuple] = None
        if mesh is not None:
            S = len(self.streams)
            spec = SH.spec_for(("stream",), (S,), mesh, SH.DEFAULT_RULES)
            if len(spec) and spec[0] is not None:
                from jax.sharding import NamedSharding
                self._sharding = NamedSharding(mesh, spec)
                self.shard_wrap = lambda fn: jax.shard_map(
                    fn, mesh=mesh, in_specs=spec, out_specs=spec,
                    check_vma=False)
                self.wrap_sig = ("mesh",
                                 tuple(d.id for d in mesh.devices.flat),
                                 tuple(mesh.axis_names),
                                 tuple(mesh.devices.shape), repr(spec))
            else:
                warnings.warn(f"{S} streams do not divide over the "
                              f"{mesh.devices.size}-device stream mesh: "
                              f"the group steps run unsharded on one "
                              f"device")

    @staticmethod
    def _key(idx: np.ndarray) -> Tuple[int, int, int]:
        return (int(idx[0]), int(idx[-1]), int(idx.size))

    def _stack(self, idx: np.ndarray) -> FilterOutputs:
        """Stack per-stream chunk outputs on the stream axis and place
        them on the mesh (stream-axis NamedSharding when sharded)."""
        outs = [self.fetch(ctx, idx) for ctx in self.streams]
        counts = jnp.stack([o.counts for o in outs])
        grid = None if outs[0].grid is None \
            else jnp.stack([o.grid for o in outs])
        stacked = FilterOutputs(counts=counts, grid=grid)
        if self._sharding is not None:
            stacked = jax.device_put(stacked, self._sharding)
        return stacked

    def prefetch(self, idx: np.ndarray) -> None:
        """Stage a chunk's stacked inputs ahead of time (device_put is
        async — the transfer overlaps whatever is currently computing)."""
        with tracing.span("repro.engine.prefetch", frame=int(idx[0])):
            self._next = (self._key(idx), self._stack(idx))

    def _take(self, idx: np.ndarray) -> FilterOutputs:
        """This chunk's stacked inputs: the prefetched ones when they are
        this chunk's, else stacked now."""
        nxt, self._next = self._next, None
        if nxt is not None and nxt[0] == self._key(idx):
            self.counters.prefetch_hits += 1
            return nxt[1]
        self.counters.prefetch_misses += 1
        with tracing.span("repro.engine.stack", frame=int(idx[0])):
            return self._stack(idx)

    def _answer(self, value: jax.Array, next_idx: Optional[np.ndarray]
                ) -> np.ndarray:
        """Stage ``next_idx``'s inputs, then block on this chunk's answer
        and fold the plan's statistics (re-staging every
        ``restage_every`` chunks)."""
        if next_idx is not None and next_idx.size:
            self.prefetch(next_idx)         # overlaps the block below
        ans = tracing.to_host(value, "answer", self.counters)
        if self.slot_stats is not None:
            self.staged.flush_stats(self.slot_stats)
            self._chunks += 1
            if self.restage_every and \
                    self._chunks % self.restage_every == 0:
                with tracing.span("repro.engine.restage"):
                    self.staged.restage(self.slot_stats)
        return ans

    def stage_order(self) -> List[str]:
        """Current stage execution order (warm-start observability)."""
        return [self.staged.stages[si].name for si in self.staged.order]

    def on_window_start(self, lo: int, hi: int) -> None:
        """Hopping-window boundary: restart every stream's automaton
        window (no-op without temporal queries).  ``MultiStreamExecutor``
        fires this once per (window, engine) pair — including engines
        rebuilt mid-window by registry churn, which restart their
        automata from the current batch (the single-stream contract)."""
        if self.temporal is None:
            return
        for prog in self.temporal:
            prog.start_window(hi - lo)
        self.temporal_stats.windows += 1

    def run_chunk(self, idx: np.ndarray,
                  next_idx: Optional[np.ndarray] = None) -> np.ndarray:
        """(S, B, N) bool answers for one chunk; double-buffers
        ``next_idx``'s transfer behind this chunk's evaluation."""
        self.counters.chunks += 1
        with tracing.span("repro.engine.run_chunk", frame=int(idx[0])):
            if self.temporal is not None:
                return self._run_chunk_temporal(idx, next_idx)
            value = self.staged.evaluate_group(self._take(idx),
                                               shard_wrap=self.shard_wrap,
                                               wrap_sig=self.wrap_sig)
            return self._answer(value, next_idx)

    def _run_chunk_temporal(self, idx: np.ndarray,
                            next_idx: Optional[np.ndarray]) -> np.ndarray:
        """Temporal chunk path: staged frame signals (with per-stream
        ``presumed_decided`` suppression) -> one vmapped/sharded scan
        step advancing all S windows at once.  The fleet path has no
        oracle tier — filter masks ARE the per-frame signal verdicts
        (the engine's standing masks-as-answers semantics), so the
        automata consume them directly."""
        progs = self.temporal
        S, B = len(progs), int(idx.size)
        M = progs[0].n_signals
        ts = self.temporal_stats
        ts.frames_in += S * B
        tc = self.cost_model.temporal_cost(frames=B, batch=B)
        if tc is not None:
            ts.cost_temporal_model += S * tc
        if all(p.all_decided for p in progs):
            # every stream's every query is window-decided: skip fetch,
            # stacking, and the whole staged plan for this chunk
            self._next = None
            ts.frames_skipped += S * B
            ts.cost_saved_model += S * self.plan.exhaustive_cost_model(
                self.cost_model, batch=B)
            return advance_group(
                progs, np.zeros((S, B, M), bool),
                step_cache=self._step_cache, shard_wrap=self.shard_wrap,
                wrap_sig=self.wrap_sig, counters=self.counters)
        suppressed = np.stack([p.suppressed_signals() for p in progs])
        ts.signal_evals_skipped += B * int(suppressed.sum())
        value = self.staged.evaluate_group(
            self._take(idx), shard_wrap=self.shard_wrap,
            wrap_sig=self.wrap_sig,
            presumed_decided=suppressed if suppressed.any() else None)
        rep = self.staged.last_report
        if rep is not None:
            ts.cost_saved_model += rep.cost_presumed_saved
        masks = self._answer(value, next_idx)
        # suppressed columns carry UNSPECIFIED mask values (the staged
        # plan stopped evaluating them) — zero them before the automata;
        # every consumer of a suppressed signal is frozen or decided, so
        # the value is semantically irrelevant but must be deterministic
        signals = masks & ~suppressed[:, None, :]
        return advance_group(
            progs, signals, step_cache=self._step_cache,
            shard_wrap=self.shard_wrap, wrap_sig=self.wrap_sig,
            counters=self.counters)


def plan_group_engine_factory(fetch, **engine_kw) -> Callable:
    """Adapter: a ``MultiStreamExecutor`` engine factory around
    ``ShardedPlanGroupEngine`` (``fetch(stream_ctx, idx)`` as above;
    ``engine_kw`` forwarded — mesh, tau, cost_model, ...)."""
    def factory(queries, streams, slot_stats=None, leaf_table=None,
                step_cache=None):
        return ShardedPlanGroupEngine(queries, streams, fetch,
                                      slot_stats=slot_stats,
                                      leaf_table=leaf_table,
                                      step_cache=step_cache, **engine_kw)
    return factory


# --------------------------------------------------------------------------
# The fleet executor
# --------------------------------------------------------------------------

@dataclasses.dataclass
class MultiWindowResult:
    span: Tuple[int, int]
    hits: Dict[Any, Dict[int, int]]     # stream id -> qid -> hit frames
    frames: int                         # per-stream frames in the window


class MultiStreamExecutor:
    """Windowed serving loop for S concurrent streams over one registry.

    The fleet analogue of ``MultiQueryStreamExecutor``: all streams
    advance in lockstep through the hopping-window schedule, and each
    chunk (one batch interval across every stream) is evaluated by a
    *group engine* built by
    ``engine_factory(queries, streams, slot_stats=...) -> engine`` with
    ``engine.run_chunk(idx, next_idx) -> (S, B, N)`` — see
    ``plan_group_engine_factory``.  The factory is re-invoked only when
    the registry epoch moves, so mid-stream register/retire takes effect
    at the next chunk boundary exactly as in the single-stream executor
    (``slot_stats`` opt-in is by parameter name, same contract).

    Per-stream ``StreamStats`` (frames seen/processed/dropped) are kept
    exactly as ``StreamExecutor`` does for one stream.  Each chunk runs
    under the span ``repro.executor.chunk`` (its first frame index as
    ``frame``), and an engine rebuild under ``repro.executor.rebuild``;
    a profiler capture times them (``repro.tracing``).  With a
    ``StragglerPolicy``, drop accounting runs per stream against the
    arrival clock (each stream is charged an equal 1/S share of the
    chunk's wall time); a behind stream's chunk results are discarded —
    its rows still ride the stacked step (group shapes are uniform), but
    stale answers are never reported, which is the monitoring semantics
    that matters at the ingest boundary.

    ``on_window(result)`` fires after each window with per-stream hit
    counts and may mutate the registry (mid-stream multiplexing).
    """

    def __init__(self, registry: QueryRegistry, engine_factory: Callable,
                 window: HoppingWindow, batch: int,
                 stream_ids: Sequence[Any], *, n_slots: Optional[int] = None,
                 base_seed: int = 0,
                 policy: Optional[StragglerPolicy] = None):
        self.registry = registry
        self.engine_factory = engine_factory
        self.window = window
        self.batch = batch
        self.policy = policy
        if n_slots is None:
            n_slots = jax.device_count()
        self.streams = route_streams(stream_ids, n_slots,
                                     base_seed=base_seed)
        self.stats: Dict[Any, StreamStats] = {
            c.stream_id: StreamStats() for c in self.streams}
        self.rebuilds = 0
        self._epoch = -1
        self._engine = None
        self._qids: Tuple[int, ...] = ()
        self._factory_takes_stats = _accepts_kw(engine_factory,
                                                "slot_stats")
        self._factory_takes_table = _accepts_kw(engine_factory,
                                                "leaf_table")
        self._factory_takes_cache = _accepts_kw(engine_factory,
                                                "step_cache")

    def _refresh(self):
        if self.registry.epoch != self._epoch:
            with tracing.span("repro.executor.rebuild",
                              epoch=self.registry.epoch):
                self._rebuild()
        return self._engine, self._qids

    def _rebuild(self) -> None:
        items = self.registry.active()
        self._qids = tuple(qid for qid, _ in items)
        if not items:
            self._engine = None
        else:
            queries = tuple(q for _, q in items)
            kw = {}
            if self._factory_takes_stats:
                kw["slot_stats"] = self.registry.slot_stats
            if self._factory_takes_table:
                kw["leaf_table"] = self.registry.leaf_table
            if self._factory_takes_cache:
                kw["step_cache"] = self.registry.step_cache
            self._engine = self.engine_factory(queries, self.streams,
                                               **kw)
        self._epoch = self.registry.epoch
        self.rebuilds += 1

    def run(self, n_frames: int,
            on_window: Optional[Callable[[MultiWindowResult], None]] = None
            ) -> List[MultiWindowResult]:
        t_run = time.perf_counter()
        arrival = (self.batch / self.policy.fps * self.policy.slack
                   if self.policy is not None else 0.0)
        budget = {c.stream_id: 0.0 for c in self.streams}
        results = []
        for lo, hi in self.window.windows(n_frames):
            chunks = [np.arange(b0, min(b0 + self.batch, hi))
                      for b0 in range(lo, hi, self.batch)]
            hits: Dict[Any, Dict[int, int]] = {
                c.stream_id: {} for c in self.streams}
            # window-scoped engine hook (temporal automata): fired once
            # per (window, engine) pair — a mid-window rebuild gets the
            # hook too and cold-restarts its state, exactly as the
            # single-stream executor documents
            started = None
            for k, idx in enumerate(chunks):
                with tracing.span("repro.executor.chunk",
                                  frame=int(idx[0])):
                    engine, qids = self._refresh()
                    if engine is None:
                        continue
                    if engine is not started:
                        hook = getattr(engine, "on_window_start", None)
                        if hook is not None:
                            hook(lo, hi)
                        started = engine
                    # drop decision at chunk arrival, against slack
                    # accrued so far — the StreamExecutor discipline, per
                    # stream
                    dropped = set()
                    for c in self.streams:
                        st = self.stats[c.stream_id]
                        st.frames_seen += idx.size
                        if self.policy is not None \
                                and budget[c.stream_id] < 0:
                            dropped.add(c.stream_id)
                            st.frames_dropped += idx.size
                        budget[c.stream_id] += arrival
                    # the engine was possibly rebuilt this chunk: only
                    # hand it a prefetch target it will recognise next call
                    nxt = chunks[k + 1] if k + 1 < len(chunks) else None
                    t0 = time.perf_counter()
                    ans = engine.run_chunk(idx, nxt)  # (S, B, n_active)
                    share = (time.perf_counter() - t0) \
                        / max(len(self.streams), 1)
                    for c in self.streams:
                        sid = c.stream_id
                        if sid in dropped:
                            continue        # stale results discarded
                        budget[sid] -= share
                        st = self.stats[sid]
                        st.frames_processed += idx.size
                        h = hits[sid]
                        for qk, qid in enumerate(qids):
                            h[qid] = h.get(qid, 0) \
                                + int(ans[c.position, :, qk].sum())
            for c in self.streams:
                self.stats[c.stream_id].windows += 1
            res = MultiWindowResult(span=(lo, hi), hits=hits,
                                    frames=hi - lo)
            results.append(res)
            if on_window is not None:
                on_window(res)          # may mutate the registry
        wall = time.perf_counter() - t_run
        for st in self.stats.values():
            st.wall_s = wall
        return results
