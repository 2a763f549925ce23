"""The plain references and the control, at a size a test run holds.

- The float32 reference forward agrees with the program's filter forward
  run in float32 at ``highest`` precision: two independent writings of
  one model.
- The control (the reference with float8 matmul operands) reads a gap to
  the float32 reference well above the bf16 program's, and above the
  limit, so a run with it in the program's place is not correct.
- The reference answers equal the program's exhaustive plan with its
  temporal replay specification on the same filter outputs.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import answers as A
from bench import harness as H
from bench.harness import gap
from bench.model import flat_params
from bench.tests.tiny import tiny_cell, tiny_queries


@pytest.fixture(scope="module", params=["qwen2-0.5b.live-detrac",
                                        "starcoder2-3b.archive-detrac"])
def fleet(request):
    return H.Fleet(tiny_cell(request.param), 7)


def _reference(fleet, frames, control=False):
    ref = H.load_reference(fleet.cell)
    fwd = ref.make_forward(fleet.cell.config, control=control)
    return [np.asarray(x) for x in ref.run(fwd, flat_params(fleet.params),
                                           frames)]


def test_reference_matches_program_in_float32(fleet):
    from repro.train.filter_train import filter_forward
    cfg32 = dataclasses.replace(fleet.mcfg, dtype="float32")
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), fleet.params)
    frames = fleet.footage.pools[0][:8]
    with jax.default_matmul_precision("highest"):
        out = jax.jit(lambda p, e: filter_forward(
            p, cfg32, cfg32.branch, e, use_kernel=True))(p32, frames)
    want_c, want_g = _reference(fleet, frames)
    assert gap(np.asarray(out.grid), want_g) < 1e-4
    assert gap(np.asarray(out.counts), want_c) < 1e-4


def test_control_fails_where_program_passes(fleet):
    frames = fleet.footage.pools[1][:8]
    want_c, want_g = _reference(fleet, frames)
    got = fleet.forward(1, 0)
    ctl_c, ctl_g = _reference(fleet, frames, control=True)
    limit = fleet.cell.workload["limits"]["cam_gap"]
    assert gap(np.asarray(got.grid), want_g) < limit
    assert gap(ctl_g, want_g) > limit
    assert gap(ctl_g, want_g) > 3 * gap(np.asarray(got.grid), want_g)


def test_reference_answers_match_program_specification():
    from repro.core.plan import QueryPlan
    from repro.core.temporal import replay_reference
    from repro.core import query as Q
    from repro.core.filters import FilterOutputs
    rng = np.random.default_rng(0)
    F, g, C = 40, 8, 8
    counts = rng.gamma(1.0, 1.0, (F, C)).astype(np.float32)
    grid = rng.normal(0.0, 0.4, (F, g, g, C)).astype(np.float32)
    # neighbouring frames alike, so temporal queries hold on runs
    grid[1::2] = grid[::2] + 0.05 * rng.normal(size=grid[::2].shape)
    qs = tiny_queries()
    windows = [(0, 16), (16, 32), (32, 40)]
    got = A.reference_answers(qs, counts, grid, windows)

    def frame_level(q):
        """Every frame-level subtree the replay may ask about."""
        own = [] if Q.has_temporal(q) else [q]
        kids = getattr(q, "terms", ()) or [getattr(q, a) for a in
                                           ("pred", "term") if hasattr(q, a)]
        return own + sum((frame_level(t) for t in kids), [])

    progs = [A.to_query(q) for q in qs]
    preds = sorted({p for q in progs for p in frame_level(q)}, key=repr)
    masks = np.asarray(QueryPlan(tuple(preds), tau=A.TAU).evaluate(
        FilterOutputs(jnp.asarray(counts), jnp.asarray(grid))))
    col = {p: k for k, p in enumerate(preds)}
    for k, q in enumerate(progs):
        for lo, hi in windows:
            want = replay_reference(
                q, lambda p, t: masks[lo + t, col[p]], hi - lo)
            assert got[lo:hi, k].tolist() == want, (k, lo)
    assert 0 < got.sum() < got.size
