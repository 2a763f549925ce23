"""Fleet-scale multi-stream serving (repro.distributed.multistream).

The load-bearing pin: multi-stream answers are bit-identical to running
each stream serially through the single-stream ``MultiQueryStreamExecutor``
— group-uniform staging, stream-axis stacking, and the shard_map path may
change *work*, never *answers* — including under mid-stream
register/retire and mixed per-stream skew.
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import query as Q
from repro.core.filters import FilterOutputs
from repro.core.plan import QueryPlan
from repro.core.stats import SlotStats
from repro.core.streaming import (FrameSampler, HoppingWindow,
                                  MultiQueryStreamExecutor, QueryRegistry,
                                  stream_seed)
from repro.distributed.multistream import (MultiStreamExecutor,
                                           ShardedPlanGroupEngine,
                                           plan_group_engine_factory,
                                           route_streams)

QUERIES = (
    Q.And((Q.ClassCount(0, Q.Op.GE, 3), Q.Spatial(0, Q.Rel.LEFT, 1))),
    Q.ClassCount(1, Q.Op.LE, 1),
    Q.Or((Q.Count(Q.Op.GE, 10), Q.Region(2, (0, 0, 4, 4), 1))),
    Q.Not(Q.ClassCount(2, Q.Op.GE, 2)),
)
C, G = 6, 8


def _stream_data(seed, n_frames, rate):
    """Per-stream synthetic filter outputs with controllable skew."""
    r = np.random.default_rng(seed)
    counts = jnp.asarray(r.poisson(rate, (n_frames, C)).astype(np.float32))
    grid = jnp.asarray((r.random((n_frames, G, G, C)) < 0.05)
                       .astype(np.float32))
    return counts, grid


def _make_fetch(data):
    def fetch(ctx, idx):
        c, g = data[ctx.stream_id]
        return FilterOutputs(counts=c[idx], grid=g[idx])
    return fetch


# ---------------------------------------------------------------------------
# Plan-level: evaluate_group == per-stream evaluate, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("spatial_body", ["auto", "rows", "full"])
def test_evaluate_group_bit_identical_per_stream(seed, spatial_body):
    rng = np.random.default_rng(seed)
    S, B = 4, 32
    # mixed skew: stream s's count rate scales with s, so the count tier
    # decides very different row fractions per stream (group bucketing
    # must cover the worst stream without corrupting the others)
    streams = [_stream_data(100 + s, B, 0.3 + 0.8 * s) for s in range(S)]
    plan = QueryPlan(QUERIES, tau=0.2)
    serial = []
    for c, g in streams:
        st = plan.build_staged(SlotStats(), spatial_body=spatial_body)
        serial.append(np.asarray(st.evaluate(
            FilterOutputs(counts=c, grid=g))))
    grp_plan = plan.build_staged(SlotStats(), spatial_body=spatial_body)
    grp = np.asarray(grp_plan.evaluate_group(FilterOutputs(
        counts=jnp.stack([c for c, _ in streams]),
        grid=jnp.stack([g for _, g in streams]))))
    for s in range(S):
        np.testing.assert_array_equal(grp[s], serial[s])
    # the group walked real tiers and the ledger feedback path works
    assert grp_plan.last_report.ran
    assert grp_plan.last_report.batch == S * B
    st2 = SlotStats()
    grp_plan.flush_stats(st2)
    assert len(st2) > 0
    del rng


def test_evaluate_group_extreme_skew_zero_undecided_stream():
    """A stream whose first tier decides every row still rides the
    group's later compacted steps (padded rows) without corruption."""
    S, B = 3, 32
    streams = [_stream_data(7 + s, B, 1.0) for s in range(S)]
    # stream 0: all-zero counts -> count tier decides everything
    streams[0] = (jnp.zeros((B, C), jnp.float32), streams[0][1])
    plan = QueryPlan(QUERIES, tau=0.2)
    grp = np.asarray(plan.build_staged(SlotStats()).evaluate_group(
        FilterOutputs(counts=jnp.stack([c for c, _ in streams]),
                      grid=jnp.stack([g for _, g in streams]))))
    for s in range(S):
        ref = np.asarray(plan.build_staged(SlotStats()).evaluate(
            FilterOutputs(counts=streams[s][0], grid=streams[s][1])))
        np.testing.assert_array_equal(grp[s], ref)


def test_evaluate_group_count_only_heads():
    """OD-COF streams (no grid): count-only queries evaluate; a
    grid-needing stage for an undecided query raises, same as serial."""
    S, B = 2, 16
    counts = jnp.stack([_stream_data(s, B, 2.0)[0] for s in range(S)])
    plan = QueryPlan((Q.Count(Q.Op.GE, 8), Q.ClassCount(0, Q.Op.GE, 1)),
                     tau=0.2)
    grp = np.asarray(plan.build_staged(SlotStats()).evaluate_group(
        FilterOutputs(counts=counts)))
    for s in range(S):
        ref = np.asarray(plan.build_staged(SlotStats()).evaluate(
            FilterOutputs(counts=counts[s])))
        np.testing.assert_array_equal(grp[s], ref)
    plan2 = QueryPlan(QUERIES, tau=0.2)
    with pytest.raises(ValueError, match="no grid"):
        plan2.build_staged(SlotStats()).evaluate_group(
            FilterOutputs(counts=counts))


# ---------------------------------------------------------------------------
# Executor-level: MultiStreamExecutor == serial MultiQueryStreamExecutor,
# including mid-stream register/retire (the acceptance property)
# ---------------------------------------------------------------------------

def _serial_reference(stream_ids, data, n_frames, window, batch, schedule):
    """Each stream run alone through the single-stream executor, with the
    same register/retire schedule replayed per stream."""
    out = {}
    for sid in stream_ids:
        registry = QueryRegistry()
        qids = [registry.register(q) for q in QUERIES[:3]]

        def factory(queries, slot_stats=None):
            plan = QueryPlan(tuple(queries), tau=0.2)
            staged = plan.build_staged(slot_stats)
            c, g = data[sid]

            def engine(idx):
                val = staged.evaluate(FilterOutputs(counts=c[idx],
                                                    grid=g[idx]))
                staged.flush_stats(slot_stats)
                return np.asarray(val)
            return engine

        ex = MultiQueryStreamExecutor(registry, factory, window, batch)

        def on_window(res, registry=registry, qids=qids):
            schedule(res.span, registry, qids)

        out[sid] = ex.run(n_frames, on_window)
    return out


def test_multistream_equals_serial_with_churn():
    S, n_frames, batch = 4, 96, 16
    window = HoppingWindow(size=32, advance=32)
    stream_ids = [f"cam{i}" for i in range(S)]
    ctxs = route_streams(stream_ids, 2)
    # mixed skew: per-stream rates differ wildly
    data = {c.stream_id: _stream_data(c.seed % 2**32, n_frames,
                                      0.3 + 0.7 * c.position)
            for c in ctxs}

    def schedule(span, registry, qids):
        lo, _ = span
        if lo == 0:                          # mid-stream registration
            qids.append(registry.register(QUERIES[3]))
        if lo == 32:                         # mid-stream retirement
            registry.retire(qids[1])

    serial = _serial_reference(stream_ids, data, n_frames, window, batch,
                               schedule)

    registry = QueryRegistry()
    qids = [registry.register(q) for q in QUERIES[:3]]
    engines = []
    base = plan_group_engine_factory(_make_fetch(data))

    def factory(queries, streams, **kw):
        engines.append(base(queries, streams, **kw))
        return engines[-1]

    ex = MultiStreamExecutor(registry, factory, window, batch, stream_ids,
                             n_slots=2)
    results = ex.run(n_frames,
                     lambda res: schedule(res.span, registry, qids))

    assert len(results) == 3 and ex.rebuilds >= 3
    for sid in stream_ids:
        for w, res in enumerate(results):
            assert res.span == serial[sid][w].span
            assert res.hits[sid] == serial[sid][w].hits, \
                f"stream {sid} window {w}"
    # per-stream accounting preserved from StreamExecutor
    for sid in stream_ids:
        st = ex.stats[sid]
        assert st.frames_seen == st.frames_processed == 96
        assert st.frames_dropped == 0 and st.windows == 3
    # the engines' counters: every chunk served once, its stack either
    # prefetched or built on arrival, each answered with at least the
    # answer fetch and one tier's undecided fetch
    counters = [e.counters for e in engines]
    assert len(engines) == ex.rebuilds
    assert sum(c.chunks for c in counters) == 6
    for c in counters:
        assert c.prefetch_hits + c.prefetch_misses == c.chunks
        assert c.host_fetches >= 2 * c.chunks
    assert counters[0].steps_built > 0


def test_multistream_empty_registry_serves_nothing():
    S, n_frames, batch = 2, 32, 16
    stream_ids = ["a", "b"]
    ctxs = route_streams(stream_ids, 1)
    data = {c.stream_id: _stream_data(1, n_frames, 1.0) for c in ctxs}
    ex = MultiStreamExecutor(
        QueryRegistry(), plan_group_engine_factory(_make_fetch(data)),
        HoppingWindow(size=32, advance=32), batch, stream_ids, n_slots=1)
    res = ex.run(n_frames)
    assert res[0].hits == {"a": {}, "b": {}}


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------

def test_route_streams_stable_balanced_fixed():
    ids = [f"cam{i}" for i in range(16)]
    ctxs = route_streams(ids, 8)
    again = route_streams(ids, 8)
    assert [(c.stream_id, c.position, c.slot) for c in ctxs] == \
           [(c.stream_id, c.position, c.slot) for c in again]
    # balanced contiguous blocks: every slot serves exactly S/n_slots
    slots = [c.slot for c in sorted(ctxs, key=lambda c: c.position)]
    assert slots == sorted(slots)
    assert all(slots.count(s) == 2 for s in range(8))
    # hash routing: stack order is not the id order (adjacent cameras
    # spread), but each id keeps its slot when the fleet is rebuilt
    assert [c.stream_id for c in sorted(ctxs, key=lambda c: c.position)] \
        != ids
    with pytest.raises(ValueError, match="duplicate"):
        route_streams(["x", "x"], 2)


# ---------------------------------------------------------------------------
# Per-stream sampling independence (satellite: seeds from (base, id) hash)
# ---------------------------------------------------------------------------

def test_stream_seed_derivation_and_sampler_independence():
    assert stream_seed(7, "cam0") != stream_seed(7, "cam1")
    assert stream_seed(7, "cam0") == stream_seed(7, "cam0")
    assert stream_seed(7, "cam0") != stream_seed(8, "cam0")
    s0 = FrameSampler(seed=7, stream_id="cam0")
    s1 = FrameSampler(seed=7, stream_id="cam1")
    a = [s0.sample(i * 100, i * 100 + 100, 20) for i in range(4)]
    b = [s1.sample(i * 100, i * 100 + 100, 20) for i in range(4)]
    assert not all(np.array_equal(x, y) for x, y in zip(a, b))
    # legacy single-stream behaviour unchanged: no stream_id -> base seed
    np.testing.assert_array_equal(
        FrameSampler(seed=7).sample(0, 100, 20),
        FrameSampler(seed=7).sample(0, 100, 20))


# ---------------------------------------------------------------------------
# Gossip warm-start (satellite: SlotStats.load_merged + registry wiring)
# ---------------------------------------------------------------------------

def test_load_merged_roundtrip_and_partial_corruption(tmp_path):
    a, b = SlotStats(), SlotStats()
    a.observe(QUERIES[1], 10, 40)
    a.observe_stage_rows("counts", 8, 64)
    b.observe(QUERIES[1], 30, 60)
    b.observe(Q.Count(Q.Op.GE, 5), 1, 50)
    pa, pb = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    a.save(pa)
    b.save(pb)
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        f.write("{not json")
    with pytest.warns(UserWarning, match="bad.json"):
        merged = SlotStats.load_merged([pa, bad, pb])
    # counts add across peers; the corrupt peer is skipped, not fatal
    assert merged.seen(QUERIES[1]) == 100.0
    assert merged.pass_rate(QUERIES[1]) == pytest.approx(
        (40 + 1) / (100 + 2))
    assert merged.seen(Q.Count(Q.Op.GE, 5)) == 50.0
    assert merged.stage_row_frac("counts") == a.stage_row_frac("counts")
    # all peers corrupt -> cold store, never an exception
    with pytest.warns(UserWarning):
        cold = SlotStats.load_merged([bad, str(tmp_path / "missing.json")])
    assert len(cold) == 0


def test_registry_gossip_warm_start(tmp_path):
    peers = []
    for i in range(2):
        st = SlotStats()
        st.observe(QUERIES[1], 5 + 10 * i, 50)
        p = str(tmp_path / f"peer{i}.json")
        st.save(p)
        peers.append(p)
    reg = QueryRegistry(gossip_paths=peers)
    assert reg.slot_stats.seen(QUERIES[1]) == 100.0
    # merged on top of an own-snapshot resume, not replacing it
    own = SlotStats()
    own.observe(Q.Count(Q.Op.GE, 5), 1, 10)
    own_p = str(tmp_path / "own.json")
    own.save(own_p)
    reg2 = QueryRegistry(stats_path=own_p, gossip_paths=peers)
    assert reg2.slot_stats.seen(QUERIES[1]) == 100.0
    assert reg2.slot_stats.seen(Q.Count(Q.Op.GE, 5)) == 10.0


def test_gossip_warm_start_changes_stage_order(tmp_path):
    """A worker warm-started from fleet snapshots stages from the
    fleet's learned selectivities: feed a peer ledger where the spatial
    slots pass ~always (useless tier) and region fails often, and the
    warm stage order must differ from the cold one."""
    peer = SlotStats()
    for q in (Q.Spatial(0, Q.Rel.LEFT, 1),):
        peer.observe(q, 990, 1000)
    peer.observe(Q.Region(2, (0, 0, 4, 4), 1), 5, 1000)
    p = str(tmp_path / "peer.json")
    peer.save(p)
    ids = ["cam0", "cam1"]
    ctxs = route_streams(ids, 1)
    data = {c.stream_id: _stream_data(3, 32, 1.0) for c in ctxs}
    cold = ShardedPlanGroupEngine(QUERIES, ctxs, _make_fetch(data),
                                  slot_stats=SlotStats())
    warm = ShardedPlanGroupEngine(
        QUERIES, ctxs, _make_fetch(data),
        slot_stats=SlotStats.load_merged([p]))
    assert cold.stage_order() != warm.stage_order()


# ---------------------------------------------------------------------------
# shard_map path under forced multi-device CPU (subprocess)
# ---------------------------------------------------------------------------

SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["REPRO_CALIBRATION"] = "off"
import sys
sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from repro.core import query as Q
from repro.core.plan import QueryPlan
from repro.core.filters import FilterOutputs
from repro.core.stats import SlotStats
from repro.distributed import sharding as SH
from repro.distributed.multistream import (ShardedPlanGroupEngine,
                                           route_streams)

assert jax.device_count() == 8
QUERIES = (
    Q.And((Q.ClassCount(0, Q.Op.GE, 3), Q.Spatial(0, Q.Rel.LEFT, 1))),
    Q.ClassCount(1, Q.Op.LE, 1),
)
S, B, C, G = 16, 16, 6, 8
streams = route_streams([f"cam{i}" for i in range(S)], 8)
data = {}
for ctx in streams:
    r = np.random.default_rng(ctx.seed % 2**32)
    data[ctx.stream_id] = (
        jnp.asarray(r.poisson(0.4 + 0.2 * ctx.position,
                              (64, C)).astype(np.float32)),
        jnp.asarray((r.random((64, G, G, C)) < 0.05).astype(np.float32)))

def fetch(ctx, idx):
    c, g = data[ctx.stream_id]
    return FilterOutputs(counts=c[idx], grid=g[idx])

eng = ShardedPlanGroupEngine(QUERIES, streams, fetch,
                             slot_stats=SlotStats(),
                             mesh=SH.stream_mesh())
assert eng.shard_wrap is not None            # 16 streams / 8 devices
idx = np.arange(0, B)
ans = eng.run_chunk(idx, np.arange(B, 2 * B))
assert eng._next is not None                 # chunk k+1 staged
plan = QueryPlan(QUERIES, tau=0.2)
for ctx in streams:
    ref = np.asarray(plan.build_staged(SlotStats()).evaluate(
        fetch(ctx, idx)))
    assert np.array_equal(ans[ctx.position], ref), ctx.stream_id
ans2 = eng.run_chunk(np.arange(B, 2 * B))    # consumes the prefetch
for ctx in streams:
    ref = np.asarray(plan.build_staged(SlotStats()).evaluate(
        fetch(ctx, np.arange(B, 2 * B))))
    assert np.array_equal(ans2[ctx.position], ref), ctx.stream_id
print("SHARDED_OK")
"""


def test_sharded_group_engine_8dev_subprocess():
    r = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT],
                       cwd=os.path.join(os.path.dirname(__file__), ".."),
                       capture_output=True, text=True, timeout=600)
    assert "SHARDED_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


# ---------------------------------------------------------------------------
# Fleet-wide temporal short-circuiting: group scan path == serial
# MultiQueryStreamExecutor, including mid-WINDOW register/retire churn
# ---------------------------------------------------------------------------

TQUERIES = (
    Q.Duration(Q.ClassCount(0, Q.Op.GE, 1), 3),
    Q.Or((Q.SlidingCount(Q.ClassCount(1, Q.Op.GE, 1), 5, Q.Op.GE, 2),
          Q.Not(Q.Count(Q.Op.GE, 9)))),
    # completeness not before relative frame 29 of a 32-window and the
    # stream rate makes early death implausible: this query keeps every
    # stream undecided through the churn chunks, so the fleet engine
    # never takes the all-decided skip path while a fetch-side trigger
    # is still pending
    Q.SlidingCount(Q.Count(Q.Op.GE, 1), 30, Q.Op.GE, 8),
)
TNEW = Q.Sequence(Q.ClassCount(0, Q.Op.GE, 1), Q.ClassCount(2, Q.Op.GE, 1),
                  4)


class _SerialTemporalEngine:
    """Masks-as-answers serial reference: the fleet temporal path has no
    oracle tier (filter masks ARE the per-frame signal verdicts), so the
    per-stream reference computes exact plan verdicts for the deduped
    frame signals and advances a numpy-backend ``TemporalProgram`` —
    suppressed columns zeroed exactly as the fleet engine does."""

    def __init__(self, queries, data):
        from repro.core.temporal import TemporalProgram
        self.prog = TemporalProgram(tuple(queries), backend="numpy")
        c, g = data
        self.masks = np.asarray(QueryPlan(
            tuple(self.prog.frame_queries), tau=0.2).evaluate(
                FilterOutputs(counts=c, grid=g)))

    def on_window_start(self, lo, hi):
        self.prog.start_window(hi - lo)

    def __call__(self, idx):
        sup = self.prog.suppressed_signals()
        return self.prog.advance(
            self.masks[np.asarray(idx)] & ~sup[None, :])


def test_fleet_temporal_equals_serial_with_midwindow_churn():
    """Sharded fleet-temporal answers == serial per-stream runs, with a
    query REGISTERED mid-window-2 and one RETIRED mid-window-3 (both
    rebuilds land at the same chunk boundary on both paths, and both
    cold-restart their automata via ``on_window_start`` — the documented
    mid-window churn semantics)."""
    S, n_frames, batch = 4, 96, 8
    window = HoppingWindow(size=32, advance=32)
    stream_ids = [f"tcam{i}" for i in range(S)]
    ctxs = route_streams(stream_ids, 2)
    data = {c.stream_id: _stream_data(c.seed % 2**32, n_frames,
                                      0.8 + 0.4 * c.position)
            for c in ctxs}

    # serial: per-stream registry, same schedule — the engine-call
    # trigger at chunk t fires one chunk BEFORE the fleet's fetch-side
    # trigger because the fleet prefetches chunk t+1's inputs during
    # chunk t; both paths then rebuild at the same chunk boundary
    serial = {}
    for sid in stream_ids:
        registry = QueryRegistry()
        qids = [registry.register(q) for q in TQUERIES]
        fired = set()

        class _Engine(_SerialTemporalEngine):
            def __call__(self, idx, registry=registry, qids=qids,
                         fired=fired):
                t0 = int(np.asarray(idx)[0])
                if t0 == 40 and "reg" not in fired:
                    fired.add("reg")
                    qids.append(registry.register(TNEW))
                if t0 == 72 and "ret" not in fired:
                    fired.add("ret")
                    registry.retire(qids[1])
                return super().__call__(idx)

        factory = (lambda queries, sid=sid, cls=_Engine:
                   cls(queries, data[sid]))
        serial[sid] = MultiQueryStreamExecutor(
            registry, factory, window, batch).run(n_frames)

    registry = QueryRegistry()
    qids = [registry.register(q) for q in TQUERIES]
    fired = set()
    base_fetch = _make_fetch(data)

    def fetch(ctx, idx):
        t0 = int(np.asarray(idx)[0])
        if t0 == 48 and "reg" not in fired:      # prefetched during 40
            fired.add("reg")
            qids.append(registry.register(TNEW))
        if t0 == 80 and "ret" not in fired:      # prefetched during 72
            fired.add("ret")
            registry.retire(qids[1])
        return base_fetch(ctx, idx)

    ex = MultiStreamExecutor(registry, plan_group_engine_factory(fetch),
                             window, batch, stream_ids, n_slots=2)
    results = ex.run(n_frames)
    assert fired == {"reg", "ret"} and ex.rebuilds >= 3
    assert ex._engine is not None and ex._engine.temporal is not None
    for sid in stream_ids:
        for w, res in enumerate(results):
            assert res.span == serial[sid][w].span
            assert res.hits[sid] == serial[sid][w].hits, \
                f"stream {sid} window {w}"


def test_group_engine_temporal_skip_and_stats():
    """Queries that latch on frame 0 window-decide every stream after
    chunk 0: later chunks must skip fetch/stacking/plan outright while
    the answers stay the latched constants."""
    S, B, W = 3, 8, 32
    ctxs = route_streams([f"s{i}" for i in range(S)], 1)
    data = {c.stream_id: _stream_data(5 + c.position, W, 1.0)
            for c in ctxs}
    calls = {"fetch": 0}
    base_fetch = _make_fetch(data)

    def fetch(ctx, idx):
        calls["fetch"] += 1
        return base_fetch(ctx, idx)

    queries = (Q.SlidingCount(Q.Count(Q.Op.GE, 0), 1, Q.Op.GE, 0),
               Q.Duration(Q.Not(Q.Count(Q.Op.GE, 10 ** 6)), 1))
    eng = ShardedPlanGroupEngine(queries, ctxs, fetch,
                                 slot_stats=SlotStats())
    assert eng.temporal is not None
    eng.on_window_start(0, W)
    outs = [eng.run_chunk(np.arange(b0, b0 + B)) for b0 in range(0, W, B)]
    ans = np.concatenate(outs, axis=1)
    assert ans.all()                        # both queries latch True
    # chunk 0 fetched every stream once; chunks 1..3 skipped entirely
    assert calls["fetch"] == S
    ts = eng.temporal_stats
    assert ts.frames_in == S * W
    assert ts.frames_skipped == S * (W - B)
    assert ts.cost_saved_model > 0.0 and ts.windows == 1



# ---------------------------------------------------------------------------
# Tracing: the engine's counters and the fleet path's spans
# ---------------------------------------------------------------------------

# frame-level queries are never window-decided, so no chunk takes the
# temporal all-decided skip: every chunk runs the plan and the scan
TRACED = QUERIES + (TQUERIES[0],)


def _traced_fleet(S=3, n_frames=64, seed=21):
    ctxs = route_streams([f"t{i}" for i in range(S)], 1)
    data = {c.stream_id: _stream_data(seed + c.position, n_frames, 1.0)
            for c in ctxs}
    return ctxs, _make_fetch(data)


def test_engine_counts_every_host_fetch_and_prefetch():
    """Per chunk the engine fetches, counted where it happens: one
    undecided-rows array per tier run, the plan's pass counts once when
    a tier ran, the answer, and the scan's output and each of its state
    leaves.  Within a window every chunk but the first was prefetched."""
    B, W = 8, 32
    ctxs, fetch = _traced_fleet()
    # a bucket floor of B: every tier that runs is a full-batch step, so
    # the pass counts are flushed whenever any tier ran
    eng = ShardedPlanGroupEngine(TRACED, ctxs, fetch,
                                 slot_stats=SlotStats(), min_bucket=B)
    c = eng.counters
    state_leaves = len(eng.temporal[0]._state_tuple())
    eng.on_window_start(0, W)
    chunks = [np.arange(b0, b0 + B) for b0 in range(0, W, B)]
    tiers = 0
    for k, idx in enumerate(chunks):
        before = c.host_fetches
        eng.run_chunk(idx, chunks[k + 1] if k + 1 < len(chunks) else None)
        ran = len(eng.staged.last_report.ran)
        tiers += ran
        want = ran + (1 if ran else 0) + 1 + 1 + state_leaves
        assert c.host_fetches - before == want, k
    assert tiers > 0 and eng.temporal_stats.frames_skipped == 0
    assert c.chunks == len(chunks)
    assert c.prefetch_hits == len(chunks) - 1 and c.prefetch_misses == 1


def test_second_identical_pass_builds_no_step():
    """Every jitted step the fleet path needs is built on the first pass
    over the footage; serving it again builds none."""
    ctxs, fetch = _traced_fleet()
    registry = QueryRegistry()
    registry.register_many(TRACED)
    ex = MultiStreamExecutor(registry, plan_group_engine_factory(fetch),
                             HoppingWindow(size=32, advance=32), 8,
                             [c.stream_id for c in ctxs], n_slots=1)
    ex.run(64)
    eng = ex._engine
    built = eng.counters.steps_built
    assert built >= 2          # plan tiers and the temporal scan
    ex.run(64)
    assert ex._engine is eng and eng.counters.steps_built == built
    # 16 chunks in 4 windows; each window's first chunk was not prefetched
    assert eng.counters.chunks == 16
    assert eng.counters.prefetch_hits == 12
    assert eng.counters.prefetch_misses == 4


def test_fleet_path_span_nesting(monkeypatch):
    """The spans the fleet path opens, each under the span of the layer
    that calls it, with the chunk's first frame on the chunk spans."""
    import contextlib

    from repro import tracing
    opened, stack = [], []

    @contextlib.contextmanager
    def span(name, **meta):
        opened.append((stack[-1] if stack else None, name, meta))
        stack.append(name)
        try:
            yield
        finally:
            stack.pop()

    monkeypatch.setattr(tracing, "span", span)
    ctxs, fetch = _traced_fleet()
    registry = QueryRegistry()
    registry.register_many(TRACED)
    ex = MultiStreamExecutor(
        registry, plan_group_engine_factory(fetch, restage_every=2),
        HoppingWindow(size=32, advance=32), 8,
        [c.stream_id for c in ctxs], n_slots=1)
    ex.run(32)
    assert not stack
    parents = {}
    for parent, name, _ in opened:
        if name.startswith("repro.plan.tier."):
            name = "repro.plan.tier.*"
        if parent is not None and parent.startswith("repro.plan.tier."):
            parent = "repro.plan.tier.*"
        parents.setdefault(name, set()).add(parent)
    run = "repro.engine.run_chunk"
    assert parents == {
        "repro.executor.chunk": {None},
        "repro.executor.rebuild": {"repro.executor.chunk"},
        run: {"repro.executor.chunk"},
        "repro.engine.stack": {run},
        "repro.engine.prefetch": {run},
        "repro.plan.tier.*": {run},
        "repro.sync.plan_undecided": {"repro.plan.tier.*"},
        "repro.sync.answer": {run},
        "repro.plan.flush_stats": {run},
        "repro.sync.plan_counts": {"repro.plan.flush_stats"},
        "repro.engine.restage": {run},
        "repro.temporal.advance": {run},
        "repro.sync.temporal_state": {"repro.temporal.advance"},
    }
    frames = [m["frame"] for _, n, m in opened
              if n == "repro.executor.chunk"]
    assert frames == [0, 8, 16, 24]
    assert [m["frame"] for _, n, m in opened if n == run] == frames
    assert {n for _, n, _ in opened if n.startswith("repro.plan.tier.")} \
        == {f"repro.plan.tier.{s}" for s in ex._engine.stage_order()}


def test_fleet_steps_are_named_by_stage():
    """The jitted steps carry the names the device trace shows:
    ``jit_plan_<stage>`` per plan tier, ``jit_temporal_scan``."""
    ctxs, fetch = _traced_fleet()
    eng = ShardedPlanGroupEngine(TRACED, ctxs, fetch,
                                 slot_stats=SlotStats())
    eng.on_window_start(0, 32)
    eng.run_chunk(np.arange(8))
    names = {cache._entries[k].__name__
             for cache in (eng.staged.step_cache, eng.temporal[0]._step_cache)
             for k in cache.keys()}
    stages = {"plan_" + s.replace("@", "_") for s in eng.stage_order()}
    assert names == stages | {"temporal_scan"}
    assert {"plan_counts", "plan_spatial", "plan_region_r0"} <= names

TEMPORAL_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["REPRO_CALIBRATION"] = "off"
import sys
sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from repro.core import query as Q
from repro.core.plan import QueryPlan
from repro.core.filters import FilterOutputs
from repro.core.streaming import (HoppingWindow, MultiQueryStreamExecutor,
                                  QueryRegistry)
from repro.core.temporal import TemporalProgram
from repro.distributed import sharding as SH
from repro.distributed.multistream import (MultiStreamExecutor,
                                           plan_group_engine_factory,
                                           route_streams)

assert jax.device_count() == 8
TQUERIES = (
    Q.Duration(Q.ClassCount(0, Q.Op.GE, 1), 3),
    Q.Or((Q.SlidingCount(Q.ClassCount(1, Q.Op.GE, 1), 5, Q.Op.GE, 2),
          Q.Not(Q.Count(Q.Op.GE, 9)))),
    Q.SlidingCount(Q.Count(Q.Op.GE, 1), 30, Q.Op.GE, 8),
)
TNEW = Q.Sequence(Q.ClassCount(0, Q.Op.GE, 1), Q.ClassCount(2, Q.Op.GE, 1),
                  4)
S, N, W, B, C, G = 16, 96, 32, 8, 6, 8
stream_ids = [f"cam{i}" for i in range(S)]
streams = route_streams(stream_ids, 8)
data = {}
for ctx in streams:
    r = np.random.default_rng(ctx.seed % 2**32)
    data[ctx.stream_id] = (
        jnp.asarray(r.poisson(0.8 + 0.1 * ctx.position,
                              (N, C)).astype(np.float32)),
        jnp.asarray((r.random((N, G, G, C)) < 0.05).astype(np.float32)))

class SerialEngine:
    def __init__(self, queries, sid):
        self.prog = TemporalProgram(tuple(queries), backend="numpy")
        c, g = data[sid]
        self.masks = np.asarray(QueryPlan(
            tuple(self.prog.frame_queries), tau=0.2).evaluate(
                FilterOutputs(counts=c, grid=g)))
    def on_window_start(self, lo, hi):
        self.prog.start_window(hi - lo)
    def __call__(self, idx):
        sup = self.prog.suppressed_signals()
        return self.prog.advance(
            self.masks[np.asarray(idx)] & ~sup[None, :])

serial = {}
for sid in stream_ids:
    registry = QueryRegistry()
    qids = [registry.register(q) for q in TQUERIES]
    fired = set()
    class Engine(SerialEngine):
        def __call__(self, idx, registry=registry, qids=qids, fired=fired):
            t0 = int(np.asarray(idx)[0])
            if t0 == 40 and "reg" not in fired:
                fired.add("reg"); qids.append(registry.register(TNEW))
            if t0 == 72 and "ret" not in fired:
                fired.add("ret"); registry.retire(qids[1])
            return super().__call__(idx)
    factory = lambda queries, sid=sid, cls=Engine: cls(queries, sid)
    serial[sid] = MultiQueryStreamExecutor(
        registry, factory, HoppingWindow(size=W, advance=W), B).run(N)

registry = QueryRegistry()
qids = [registry.register(q) for q in TQUERIES]
fired = set()

def fetch(ctx, idx):
    t0 = int(np.asarray(idx)[0])
    if t0 == 48 and "reg" not in fired:          # prefetched during 40
        fired.add("reg"); qids.append(registry.register(TNEW))
    if t0 == 80 and "ret" not in fired:          # prefetched during 72
        fired.add("ret"); registry.retire(qids[1])
    c, g = data[ctx.stream_id]
    return FilterOutputs(counts=c[idx], grid=g[idx])

ex = MultiStreamExecutor(
    registry, plan_group_engine_factory(fetch, mesh=SH.stream_mesh()),
    HoppingWindow(size=W, advance=W), B, stream_ids, n_slots=8)
results = ex.run(N)
assert fired == {"reg", "ret"}
assert ex.rebuilds >= 3, ex.rebuilds
assert ex._engine is not None and ex._engine.temporal is not None
assert ex._engine.shard_wrap is not None     # 16 streams / 8 devices
for sid in stream_ids:
    for w, res in enumerate(results):
        assert res.span == serial[sid][w].span
        assert res.hits[sid] == serial[sid][w].hits, (sid, w)
print("TEMPORAL_SHARDED_OK")
"""


def test_sharded_fleet_temporal_8dev_subprocess():
    r = subprocess.run([sys.executable, "-c", TEMPORAL_SHARDED_SCRIPT],
                       cwd=os.path.join(os.path.dirname(__file__), ".."),
                       capture_output=True, text=True, timeout=600)
    assert "TEMPORAL_SHARDED_OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-2000:]
