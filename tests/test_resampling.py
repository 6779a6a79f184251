"""Property tests for the local resampling schemes (paper Alg. 1 line 17)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need the dev extra: pip install -e .[dev]")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import resampling as R
from repro.core.particles import normalized_weights

SCHEMES = list(R.RESAMPLERS)


@st.composite
def weights_and_n(draw):
    n_in = draw(st.integers(4, 200))
    lw = draw(st.lists(st.floats(-30, 5, allow_nan=False), min_size=n_in,
                       max_size=n_in))
    n_out = draw(st.integers(1, 256))
    seed = draw(st.integers(0, 2 ** 16))
    return jnp.asarray(lw, jnp.float32), n_out, seed


@pytest.mark.parametrize("scheme", SCHEMES)
@given(args=weights_and_n())
@settings(max_examples=30, deadline=None)
def test_counts_sum_to_n_out(scheme, args):
    """Σ offspring counts == n_out — particle-count conservation."""
    lw, n_out, seed = args
    counts = R.RESAMPLERS[scheme](jax.random.key(seed), lw, n_out,
                                  capacity=max(n_out, lw.shape[0]))
    assert int(counts.sum()) == n_out
    assert int(counts.min()) >= 0


@pytest.mark.parametrize("scheme", SCHEMES)
@given(args=weights_and_n())
@settings(max_examples=20, deadline=None)
def test_zero_weight_never_resampled(scheme, args):
    lw, n_out, seed = args
    lw = lw.at[0].set(-jnp.inf)
    counts = R.RESAMPLERS[scheme](jax.random.key(seed), lw, n_out,
                                  capacity=max(n_out, lw.shape[0]))
    assert int(counts[0]) == 0


def test_counts_ancestors_roundtrip():
    counts = jnp.asarray([3, 0, 2, 1, 0, 2], jnp.int32)
    anc = R.counts_to_ancestors(counts, 8)
    back = R.ancestors_to_counts(anc, 6)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(counts))


@pytest.mark.parametrize("scheme", ["systematic", "stratified", "residual",
                                    "multinomial"])
def test_unbiasedness(scheme):
    """E[counts_i] ≈ n_out · w_i over many seeds (resampling unbiasedness)."""
    lw = jnp.log(jnp.asarray([0.05, 0.1, 0.15, 0.3, 0.4]))
    n_out = 64
    total = np.zeros(5)
    reps = 300
    for s in range(reps):
        c = R.RESAMPLERS[scheme](jax.random.key(s), lw, n_out, capacity=64)
        total += np.asarray(c)
    emp = total / (reps * n_out)
    w = np.asarray(normalized_weights(lw))
    np.testing.assert_allclose(emp, w, atol=0.01)


def test_systematic_variance_lower_than_multinomial():
    """Systematic resampling is a variance-reduction over multinomial."""
    lw = jnp.log(jnp.linspace(0.1, 1.0, 32))
    n_out = 128

    def var_of(scheme):
        counts = np.stack([
            np.asarray(R.RESAMPLERS[scheme](jax.random.key(s), lw, n_out,
                                            capacity=128))
            for s in range(200)])
        return counts.var(axis=0).mean()

    assert var_of("systematic") < var_of("multinomial")


def _search_counts(cdf, u, n_out, capacity):
    """The systematic comb by ``searchsorted``: every point's ancestor is
    the first index whose cumulative weight exceeds it, the excess goes
    to the last particle, points past ``n_out`` count nothing."""
    n_in = cdf.shape[0]
    n_out_f = jnp.asarray(n_out, jnp.float32)
    pts = (jnp.arange(capacity, dtype=jnp.float32) + u) / jnp.maximum(
        n_out_f, 1.0)
    valid = jnp.arange(capacity) < n_out
    anc = jnp.searchsorted(cdf, jnp.where(valid, pts, 2.0), side="right")
    anc = jnp.clip(anc, 0, n_in - 1)
    return jnp.zeros((n_in,), jnp.int32).at[jnp.where(valid, anc, 0)].add(
        jnp.where(valid, 1, 0))


def _log_weights(case, n, key):
    lw = jax.random.normal(key, (n,)) * 3.0
    if case == "neg_inf":
        lw = lw.at[n // 2].set(-jnp.inf)
    if case == "one_hot":
        lw = jnp.full((n,), -jnp.inf).at[n // 3].set(0.0)
    return lw


@pytest.mark.parametrize("n,case,n_out", [
    (1, "normal", None), (7, "normal", None), (128, "normal", None),
    (1000, "normal", None), (12345, "normal", None), (16384, "normal", None),
    (1000, "normal", 613), (1000, "normal", 0), (131072, "normal", 100000),
    (128, "neg_inf", None),
    (1000, "one_hot", None), (16384, "one_hot", 9000)])
def test_systematic_closed_form_matches_search(n, case, n_out):
    """The closed-form systematic comb gives the counts a search of every
    comb point gives, on a non-decreasing CDF: at every size, with a
    traced ``n_out`` below capacity, a dead particle, and all weight on
    one particle.  Where ``n_out`` is no power of two the points' own
    rounding moves some counts off the ``ceil`` estimate (12345,
    100000), which the correction rounds take back."""
    traced = n_out is not None
    n_out = n if n_out is None else n_out
    k_w, k_u = jax.random.split(jax.random.key(n + n_out))
    lw = _log_weights(case, n, k_w)
    count = jax.jit(lambda lw, m: R.systematic_counts(k_u, lw, m, capacity=n))
    got = np.asarray(count(lw, jnp.int32(n_out) if traced else n_out))
    w = normalized_weights(lw)
    cdf = jax.lax.cummax(jnp.cumsum(w / jnp.sum(w)))
    want = np.asarray(_search_counts(cdf, jax.random.uniform(k_u, ()),
                                     n_out, n))
    np.testing.assert_array_equal(got, want)
    assert got.sum() == n_out and got.min() >= 0
    if case == "neg_inf":
        assert got[n // 2] == 0
    if case == "one_hot":
        assert got[n // 3] == n_out


@pytest.mark.parametrize("n_out", [5, 64, 1000])
def test_systematic_closed_form_on_a_dipping_cdf(n_out):
    """Where a float32 CDF dips by rounding a search has no single
    answer; the closed form's counts stay non-negative, sum to ``n_out``
    and leave the dipped entry none."""
    cdf = jnp.asarray([0.1, 0.30000004, 0.3, 0.3, 0.65, 0.64999998, 1.0],
                      jnp.float32)
    assert bool(jnp.any(jnp.diff(cdf) < 0))
    for u in (0.0, 0.37, 0.999):
        got = np.asarray(R.systematic_comb_counts(cdf, jnp.float32(u),
                                                  n_out, n_out))
        assert got.min() >= 0 and got.sum() == n_out
        assert got[2] == got[3] == got[5] == 0


@pytest.mark.parametrize("counts,n_out", [
    ([3, 0, 2, 1, 0, 2], 8),            # zero counts inside
    ([0, 0, 4, 1, 3, 0, 0], 8),         # zero counts at both ends
    ([2, 0, 1, 0], 8),                  # sum < n_out, last particle dead
    ([0, 1, 0, 2], 6),                  # sum < n_out, last particle live
    ([0, 0, 0], 4),                     # no offspring at all
    ([5], 5), ([1] * 9, 9), ([0, 9, 0], 9)])
def test_counts_to_ancestors_matches_repeat(counts, n_out):
    """The sorted scatter and running max expand counts exactly as
    ``jnp.repeat`` does, padding a short total with ``n_in - 1``."""
    c = jnp.asarray(counts, jnp.int32)
    want = jnp.repeat(jnp.arange(c.shape[0], dtype=jnp.int32), c,
                      total_repeat_length=n_out)
    got = jax.jit(R.counts_to_ancestors, static_argnums=1)(c, n_out)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("scheme,searches", [("systematic", False),
                                             ("stratified", True)])
def test_only_irregular_combs_search(scheme, searches):
    """The systematic resampling step holds no loop (no search); the
    stratified comb, whose points are not regular, keeps its search, a
    ``scan`` of bisection rounds that compiles to a ``while``."""
    from repro.core import smc
    n = 4096
    jaxpr = str(jax.make_jaxpr(lambda k, lw: smc.ess_resample(
        k, lw, ess_frac=0.5, resampler=scheme).ancestors)(
        jax.random.key(0), jnp.zeros((n,))))
    assert any(loop in jaxpr for loop in ("while[", "scan[")) == searches
