"""Control-variate estimators (paper §III): property tests.

``hypothesis`` is optional (see tests/conftest.py and
tests/requirements-test.txt): when installed the property tests explore
random inputs; in a bare environment they fall back to a fixed seeded
sweep of the same properties so the module always collects and runs green.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import HAS_HYPOTHESIS   # optional dep — see tests/conftest.py

if HAS_HYPOTHESIS:
    from hypothesis import given, settings, strategies as st

from repro.core import aggregates as AGG


def test_cv_matches_theory():
    rng = np.random.default_rng(0)
    y = rng.normal(5, 2, 20000)
    x = y + rng.normal(0, 0.5, 20000)
    est = AGG.cv_estimate(y, x, mu_x=float(x.mean()))
    rho2 = np.corrcoef(y, x)[0, 1] ** 2
    assert abs(est.variance_reduction - 1 / (1 - rho2)) / (1 / (1 - rho2)) < 0.1


def test_cv_unbiased_with_known_mu():
    """Monte-Carlo check: E[Y_cv] == E[Y] when mu_X is the true mean."""
    rng = np.random.default_rng(1)
    means = []
    for _ in range(200):
        x = rng.normal(0, 1, 200)
        y = 2 * x + rng.normal(3, 1, 200)
        means.append(AGG.cv_estimate(y, x, mu_x=0.0).mean)
    assert abs(np.mean(means) - 3.0) < 0.05


def test_mcv_beats_single_cv():
    rng = np.random.default_rng(2)
    z1 = rng.normal(0, 1, 5000)
    z2 = rng.normal(0, 1, 5000)
    y = z1 + z2 + rng.normal(0, 0.3, 5000)
    single = AGG.cv_estimate(y, z1, mu_x=0.0)
    multi = AGG.mcv_estimate(y, np.stack([z1, z2], 1), mu_z=np.zeros(2))
    assert multi.var < single.var
    assert multi.variance_reduction > single.variance_reduction


def _check_cv_variance_never_worse(n, noise, seed):
    """Property: the CV estimator variance <= naive variance (+eps)."""
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 1, n)
    y = x + rng.normal(0, noise + 1e-3, n)
    est = AGG.cv_estimate(y, x)
    assert est.var <= est.naive_var * (1 + 1e-9)


def _check_accumulator_merge_associative(n1, n2, seed):
    """merge(A, B) == batch estimate on concatenated data (Chan et al.)."""
    rng = np.random.default_rng(seed)
    y = rng.normal(1, 2, n1 + n2)
    z = (y + rng.normal(0, 1, n1 + n2))[:, None]

    a = AGG.CVAccumulator.init(1).update(jnp.array(y[:n1]), jnp.array(z[:n1]))
    b = AGG.CVAccumulator.init(1).update(jnp.array(y[n1:]), jnp.array(z[n1:]))
    merged = a.merge(b)
    whole = AGG.CVAccumulator.init(1).update(jnp.array(y), jnp.array(z))
    np.testing.assert_allclose(merged.mean, whole.mean, atol=1e-4)
    np.testing.assert_allclose(merged.M2, whole.M2, atol=1e-2)
    e1, e2 = merged.estimate(), whole.estimate()
    np.testing.assert_allclose(e1.mean, e2.mean, atol=1e-4)


if HAS_HYPOTHESIS:
    @settings(deadline=None)   # example budget: profile-governed (conftest)
    @given(st.integers(10, 200), st.floats(0.0, 3.0),
           st.integers(0, 2 ** 31 - 1))
    def test_cv_variance_never_worse_hypothesis(n, noise, seed):
        _check_cv_variance_never_worse(n, noise, seed)

    @settings(deadline=None)   # example budget: profile-governed (conftest)
    @given(st.integers(4, 64), st.integers(4, 64),
           st.integers(0, 2 ** 31 - 1))
    def test_accumulator_merge_associative(n1, n2, seed):
        _check_accumulator_merge_associative(n1, n2, seed)
else:
    @pytest.mark.parametrize("seed", range(10))
    def test_cv_variance_never_worse_seeded(seed):
        rng = np.random.default_rng(seed + 1000)
        _check_cv_variance_never_worse(int(rng.integers(10, 200)),
                                       float(rng.uniform(0, 3)), seed)

    @pytest.mark.parametrize("seed", range(10))
    def test_accumulator_merge_associative_seeded(seed):
        rng = np.random.default_rng(seed + 2000)
        _check_accumulator_merge_associative(int(rng.integers(4, 64)),
                                             int(rng.integers(4, 64)), seed)


def test_distributed_reduce_matches_merge():
    """psum-based reduction == sequential merges (on a 1-device mesh the
    psum is identity; algebra checked by constructing the same moments)."""
    rng = np.random.default_rng(3)
    y = rng.normal(0, 1, 64)
    z = (y + rng.normal(0, 0.5, 64))[:, None]
    acc = AGG.CVAccumulator.init(1).update(jnp.array(y), jnp.array(z))

    def f(a_n, a_mean, a_M2):
        acc_in = AGG.CVAccumulator(n=a_n, mean=a_mean, M2=a_M2)
        out = AGG.distributed_reduce(acc_in, "i")
        return out.n, out.mean, out.M2

    from jax.sharding import Mesh, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("i",))
    g = jax.shard_map(f, mesh=mesh, in_specs=(P(), P(), P()),
                  out_specs=(P(), P(), P()), check_vma=False)
    n2, m2, M22 = g(acc.n, acc.mean, acc.M2)
    np.testing.assert_allclose(m2, acc.mean, atol=1e-6)
    np.testing.assert_allclose(M22, acc.M2, atol=1e-4)


def test_accumulator_init_dtypes_consistent():
    """n, mean, M2 share one dtype (the former init mixed an x64-gated n
    with always-f32 moments)."""
    acc = AGG.CVAccumulator.init(2)
    assert acc.n.dtype == acc.mean.dtype == acc.M2.dtype
    with jax.enable_x64(True):
        acc64 = AGG.CVAccumulator.init(2)
        assert acc64.n.dtype == acc64.mean.dtype == acc64.M2.dtype
        assert acc64.n.dtype == jnp.float64


def test_accumulator_long_stream_matches_mcv():
    """Long-stream regression (satellite, ISSUE 3): streaming moments in
    float64 agree with the one-shot float64 ``mcv_estimate`` on identical
    data — the float32 accumulator drifted (Welford co-moments cancel
    catastrophically once mean*n dwarfs the per-batch deltas) and lost
    exact integer counting of n past 2^24."""
    rng = np.random.default_rng(7)
    n_chunks, chunk = 60, 4096                       # ~250k frames
    # large common mean maximizes f32 cancellation in the co-moments
    x = rng.normal(0, 1, n_chunks * chunk)
    y = 1e4 + 0.8 * x + rng.normal(0, 0.5, n_chunks * chunk)
    z = (1e4 + x)[:, None]
    with jax.enable_x64(True):
        acc = AGG.CVAccumulator.init(1)
        for k in range(n_chunks):
            sl = slice(k * chunk, (k + 1) * chunk)
            acc = acc.update(jnp.asarray(y[sl]), jnp.asarray(z[sl]))
        assert float(acc.n) == n_chunks * chunk      # exact count
        est = acc.estimate()
    ref = AGG.mcv_estimate(y, z)
    assert est.mean == pytest.approx(ref.mean, rel=1e-9, abs=1e-6)
    assert est.beta[0] == pytest.approx(ref.beta[0], rel=1e-6)
    assert est.var == pytest.approx(ref.var, rel=1e-6)
    assert est.naive_var == pytest.approx(ref.naive_var, rel=1e-6)


def test_ci95_student_t_widens_small_n():
    """At the small n the API admits (n >= 3), the CI uses the Student-t
    quantile — wider than the fixed z=1.96 — and converges back to the
    normal quantile for large n."""
    import math

    def width(n, seed=0):
        rng = np.random.default_rng(seed)
        x = rng.normal(0, 1, n)
        y = x + rng.normal(0, 1, n)
        est = AGG.cv_estimate(y, x)
        lo, hi = est.ci95()
        assert hi >= lo
        return (hi - lo) / (2 * math.sqrt(est.var))  # the applied quantile

    assert width(3) == pytest.approx(12.706, rel=1e-3)    # t_{.975}(df=1)
    assert width(5) == pytest.approx(3.182, rel=1e-3)     # df=3
    assert width(20000) == pytest.approx(1.96, rel=1e-2)  # -> normal z
    assert width(3) > width(5) > width(20000)


def test_ci_covers_truth():
    rng = np.random.default_rng(4)
    hits = 0
    for i in range(100):
        x = rng.normal(0, 1, 400)
        y = x * 0.8 + rng.normal(1.0, 0.5, 400)
        est = AGG.cv_estimate(y, x, mu_x=0.0)
        lo, hi = est.ci95()
        hits += (lo <= 1.0 <= hi)
    assert hits >= 85     # ~95% nominal coverage


# ---------------------------------------------------------------------------
# degenerate-sample handling (regression: these crashed or assert-failed
# before typed errors / the d=0 naive fallback existed)
# ---------------------------------------------------------------------------

def test_mcv_estimate_small_n_typed_error():
    """n < 3 raises DegenerateSampleError (a ValueError carrying the
    count), not a bare AssertionError."""
    y = np.array([1.0, 2.0])
    Z = np.array([[0.1], [0.2]])
    with pytest.raises(AGG.DegenerateSampleError) as ei:
        AGG.mcv_estimate(y, Z, mu_z=np.array([0.15]))
    assert isinstance(ei.value, ValueError)
    assert ei.value.n == 2
    assert "2" in str(ei.value)


def test_accumulator_estimate_small_n_typed_error():
    acc = AGG.CVAccumulator.init(1)
    acc = acc.update(jnp.array([1.0, 2.0]), jnp.array([[0.1], [0.2]]))
    with pytest.raises(AGG.DegenerateSampleError) as ei:
        acc.estimate()
    assert ei.value.n == 2


def test_mcv_estimate_shape_mismatch_typed_error():
    with pytest.raises(ValueError, match="3 samples but"):
        AGG.mcv_estimate(np.ones(3), np.ones((4, 1)), mu_z=np.zeros(1))


def test_mcv_estimate_d0_naive_fallback():
    """No control variates (d=0): falls back to the naive mean instead of
    crashing in np.linalg.solve on a 0x0 system."""
    rng = np.random.default_rng(7)
    y = rng.normal(3.0, 1.0, 50)
    est = AGG.mcv_estimate(y, np.zeros((50, 0)), mu_z=np.zeros(0))
    assert est.mean == pytest.approx(float(y.mean()))
    assert est.var == pytest.approx(float(y.var(ddof=1)) / 50)
    assert est.var == pytest.approx(est.naive_var)
    assert est.beta.shape == (0,)


def test_accumulator_estimate_d0_naive_fallback():
    rng = np.random.default_rng(8)
    y = rng.normal(-1.0, 2.0, 64)
    acc = AGG.CVAccumulator.init(0)
    acc = acc.update(jnp.asarray(y), jnp.zeros((64, 0)))
    est = acc.estimate()
    assert est.mean == pytest.approx(float(y.mean()), rel=1e-6)
    assert est.var == pytest.approx(float(y.var(ddof=1)) / 64, rel=1e-5)
    assert est.beta.shape == (0,)


# ---------------------------------------------------------------------------
# allocator state: ChunkPosteriors + BudgetLedger (contracts tier plumbing)
# ---------------------------------------------------------------------------

def test_chunk_posteriors_moments_match_numpy():
    post = AGG.ChunkPosteriors(3)
    rng = np.random.default_rng(3)
    batches = {0: [], 2: []}
    for _ in range(5):
        for j in (0, 2):
            y = rng.normal(j, 1 + j, 7)
            batches[j].append(y)
            post.update(j, y)
    for j in (0, 2):
        all_y = np.concatenate(batches[j])
        assert post.means()[j] == pytest.approx(all_y.mean())
        assert post.variances()[j] == pytest.approx(all_y.var(ddof=1))
    assert post.n[1] == 0 and post.variances()[1] == 0.0


def test_chunk_posteriors_rate_draws_favor_hot_chunk():
    post = AGG.ChunkPosteriors(2)
    post.update(0, np.zeros(50))
    post.update(1, np.ones(50))
    rng = np.random.default_rng(0)
    wins = sum(np.argmax(post.draw_rates(rng)) == 1 for _ in range(100))
    assert wins > 90


def test_chunk_posteriors_var_draws_positive_for_unseen_chunk():
    """The pooled-variance prior keeps unexplored chunks in the race: an
    unseen chunk's variance draw must not collapse to zero."""
    post = AGG.ChunkPosteriors(2)
    post.update(0, np.random.default_rng(0).normal(0, 2, 100))
    draws = post.draw_vars(np.random.default_rng(1))
    assert draws[1] > 0


def test_budget_ledger_charges_and_price():
    led = AGG.BudgetLedger()
    assert led.oracle_us_per_frame() is None
    led.charge_oracle(10, 500.0)
    led.charge_oracle(5, 100.0)
    led.charge_filter(100, 50.0)
    assert led.oracle_calls == 15
    assert led.oracle_us == pytest.approx(600.0)
    assert led.filter_frames == 100
    assert led.oracle_us_per_frame() == pytest.approx(40.0)
    d = led.describe()
    assert d["oracle_calls"] == 15 and d["filter_us"] == pytest.approx(50.0)
