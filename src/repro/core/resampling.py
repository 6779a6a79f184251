"""Local resampling algorithms (paper Alg. 1, line 17).

Four classical comb/CDF schemes plus the two *collective-free* schemes
of McAlinn–Nakatsuma (arXiv:1212.1639) and Murray–Lee–Jacob
(arXiv:1301.4019) — Metropolis and rejection resampling — which need no
global prefix sum: every output slot runs an independent chain of
weight-ratio comparisons, so the algorithms map onto parallel hardware
with no cross-lane dependency at all (DESIGN.md §13.2).  Each scheme
has two output forms:

* ``*_ancestors``: ``(n_out,)`` int32 ancestor indices — the materialized
  form used by single-device SIR.
* ``*_counts``: ``(n_in,)`` int32 multiplicities — the *compressed
  particles* form (paper §V): how many offspring each input particle
  spawns.  ``sum(counts) == n_out``.  Routing in the distributed
  resamplers moves counts, never replicas.

``counts_to_ancestors`` / ``ancestors_to_counts`` convert between the two
losslessly (up to offspring ordering, which is exchangeable).

The systematic comb is search-free: its points ``(k + u) / n_out`` are
regular, so the number of them below each CDF entry has a closed form
(``systematic_comb_counts``), and particle ``j``'s offspring fill the
slots between two neighbouring entries.  The CDF goes through
``lax.cummax`` first: a float32 ``cumsum`` of non-negative weights can
dip by rounding at large ``n``, where a search has no single answer,
and the running max keeps every count non-negative.  Stratified,
multinomial and residual points are not a regular comb and keep
``searchsorted`` (DESIGN.md §2.3).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.particles import normalized_weights

Array = jax.Array


# ---------------------------------------------------------------------------
# Representation conversions (compression layer contract)
# ---------------------------------------------------------------------------

def counts_to_ancestors(counts: Array, n_out: int) -> Array:
    """Expand multiplicities to ancestor indices.

    Particle ``j``'s offspring occupy slots ``[starts_j, starts_j +
    counts_j)``: one sorted scatter-max writes ``j`` at ``starts_j`` and
    a running max fills the rest of each run.  Zero-count particles share
    their start with the next particle that has offspring, which the max
    lets win.  The static total length keeps SPMD shapes fixed; if
    ``sum(counts) < n_out`` the tail is padded with ``n_in - 1`` (the
    trailing particles write their index at the total, as
    ``jnp.repeat`` pads), and callers track the logical size separately.
    """
    n_in = counts.shape[0]
    starts = jnp.cumsum(counts) - counts
    slots = jnp.zeros((n_out,), jnp.int32).at[starts].max(
        jnp.arange(n_in, dtype=jnp.int32), indices_are_sorted=True,
        mode="drop")
    return jax.lax.cummax(slots)


def ancestors_to_counts(ancestors: Array, n_in: int) -> Array:
    """Histogram ancestor indices back to multiplicities."""
    return jnp.zeros((n_in,), jnp.int32).at[ancestors].add(1)


# ---------------------------------------------------------------------------
# Comb-based schemes (systematic / stratified) — shared machinery
# ---------------------------------------------------------------------------

def _comb_counts(weights: Array, u: Array, n_out: Array | int, capacity: int) -> Array:
    """Offspring counts for a comb of ``n_out`` points with offsets ``u``.

    ``u`` is either a scalar (systematic) or ``(capacity,)`` (stratified)
    uniform in [0,1).  ``n_out`` may be a *traced* scalar ≤ ``capacity`` —
    this is what lets RPA allocate a data-dependent number of offspring per
    shard while every shape stays static (DESIGN.md §2.1).  A scalar ``u``
    takes the closed form (``systematic_comb_counts``); per-point offsets
    search.
    """
    w = weights / jnp.maximum(jnp.sum(weights), 1e-38)
    cdf = jnp.cumsum(w)
    if jnp.ndim(u) == 0:
        return systematic_comb_counts(cdf, u, n_out, capacity)
    n_out_f = jnp.asarray(n_out, jnp.float32)
    pts = (jnp.arange(capacity, dtype=jnp.float32) + u) / jnp.maximum(n_out_f, 1.0)
    valid = jnp.arange(capacity) < n_out
    # searchsorted over the CDF: ancestor of comb point p is the first index
    # whose cumulative weight exceeds p.
    anc = jnp.searchsorted(cdf, jnp.where(valid, pts, 2.0), side="right")
    anc = jnp.clip(anc, 0, weights.shape[0] - 1).astype(jnp.int32)
    counts = jnp.zeros((weights.shape[0],), jnp.int32)
    counts = counts.at[jnp.where(valid, anc, weights.shape[0] - 1)].add(
        jnp.where(valid, 1, 0)
    )
    return counts


def systematic_comb_counts(cdf: Array, u: Array, n_out: Array | int,
                           capacity: int) -> Array:
    """Offspring counts of the systematic comb over ``cdf``, with no search.

    The comb's points ``(k + u) / n_out`` are regular, so the number of
    them below ``cdf_j``, ``c_j``, is ``ceil(n_out·cdf_j − u)`` up to
    float32 rounding, and elementwise comparisons against the points
    themselves, evaluated as the stratified search evaluates its points,
    correct it; particle ``j`` has ``c_j − c_{j−1}`` offspring.  Each
    round moves an entry by one; the rounds grow with float32's spacing
    of integers above 2^22.  The count runs on the CDF's running max, so
    it is non-negative and sums to ``n_out`` even where ``cdf`` dips by
    rounding; where it does not dip, the counts are those of a
    ``searchsorted`` of every point, the last particle taking every
    point past the CDF's end as the search's clip does.
    """
    cdf = jax.lax.cummax(cdf)
    n_out_f = jnp.asarray(n_out, jnp.float32)
    n_out_i = jnp.asarray(n_out, jnp.int32)
    scale = jnp.maximum(n_out_f, 1.0)

    def below(k):
        return (k.astype(jnp.float32) + u) / scale < cdf

    c = jnp.clip(jnp.ceil(cdf * scale - u), 0.0, n_out_f).astype(jnp.int32)
    for _ in range(1 + capacity // 2 ** 22):
        c = jnp.where((c > 0) & ~below(c - 1), c - 1, c)
        c = jnp.where((c < n_out_i) & below(c), c + 1, c)
    return jnp.diff(c.at[-1].set(n_out_i), prepend=0)


def systematic_counts(key: Array, log_weights: Array, n_out, capacity: int | None = None) -> Array:
    """Systematic resampling — a single shared uniform offset."""
    capacity = capacity or log_weights.shape[0]
    w = normalized_weights(log_weights)
    u = jax.random.uniform(key, ())
    return _comb_counts(w, u, n_out, capacity)


def stratified_counts(key: Array, log_weights: Array, n_out, capacity: int | None = None) -> Array:
    """Stratified resampling — one uniform per stratum."""
    capacity = capacity or log_weights.shape[0]
    w = normalized_weights(log_weights)
    u = jax.random.uniform(key, (capacity,))
    return _comb_counts(w, u, n_out, capacity)


def multinomial_counts(key: Array, log_weights: Array, n_out, capacity: int | None = None) -> Array:
    """Multinomial resampling via inverse-CDF of sorted uniforms.

    Uses the exponential-spacings trick to generate sorted uniforms in O(n)
    so a single searchsorted pass suffices (the paper's *tools* module sorts
    explicitly; this is the allocation-free equivalent).
    """
    capacity = capacity or log_weights.shape[0]
    w = normalized_weights(log_weights)
    # sorted U[0,1) variates via exponential spacings.  The normalizer must
    # be the sum of the first n_out+1 spacings (n_out may be traced and
    # < capacity); dividing by the full sum would bias the first n_out
    # order statistics toward 0.
    e = jax.random.exponential(key, (capacity + 1,))
    cs = jnp.cumsum(e)
    denom = cs[jnp.clip(jnp.asarray(n_out, jnp.int32), 1, capacity)]
    sorted_u = cs[:-1] / denom
    return _multinomial_from_sorted(w, sorted_u, n_out, capacity)


def _multinomial_from_sorted(w: Array, sorted_u: Array, n_out, capacity: int) -> Array:
    cdf = jnp.cumsum(w / jnp.maximum(jnp.sum(w), 1e-38))
    valid = jnp.arange(capacity) < n_out
    anc = jnp.searchsorted(cdf, jnp.where(valid, sorted_u, 2.0), side="right")
    anc = jnp.clip(anc, 0, w.shape[0] - 1).astype(jnp.int32)
    counts = jnp.zeros((w.shape[0],), jnp.int32)
    return counts.at[jnp.where(valid, anc, w.shape[0] - 1)].add(jnp.where(valid, 1, 0))


def residual_counts(key: Array, log_weights: Array, n_out, capacity: int | None = None) -> Array:
    """Residual resampling: deterministic floor(n·w) copies + multinomial rest."""
    capacity = capacity or log_weights.shape[0]
    w = normalized_weights(log_weights)
    n_out_f = jnp.asarray(n_out, jnp.float32)
    det = jnp.floor(n_out_f * w).astype(jnp.int32)
    n_det = jnp.sum(det)
    resid = n_out_f * w - det.astype(jnp.float32)
    resid_lw = jnp.log(jnp.maximum(resid, 1e-38))
    rest = multinomial_counts(key, resid_lw, jnp.asarray(n_out, jnp.int32) - n_det, capacity)
    return det + rest


# ---------------------------------------------------------------------------
# Collective-free schemes (Metropolis / rejection) — no prefix sum
# ---------------------------------------------------------------------------

# Default draw budget per lane (chain length / tries).  Both schemes
# leave every lane within total-variation distance
# ``(1 − 1/(N·w_max))^B`` of the target law (the Dobrushin bound for
# Metropolis; acceptance mass for rejection — derivation in
# ``tests/stats.py::chain_bias_ceiling``), so the bias decays
# geometrically in the budget but NEVER reaches zero for skewed
# weights: unlike the comb schemes these are asymptotically, not
# exactly, unbiased, and the statistical gates carry an explicit bias
# term (tests/test_ssm_contract.py, tests/test_ssm_oracle.py).  32
# keeps the residual below those gates at every tested weight profile
# while the precomputed-draw arrays stay ``(N, 32)`` — the memory knob.
METROPOLIS_ITERS = 32
REJECTION_TRIES = 32


def _dead_slot_guard(ancestors: Array, log_weights: Array) -> Array:
    """Redirect lanes whose final slot has zero weight to the argmax slot.

    A chain that never saw a finite-weight proposal (possible only under
    extreme degeneracy — e.g. all mass on one particle, where the
    uniform proposal almost never finds it) would otherwise keep its
    dead starting slot; the stationary law puts zero mass there, so the
    redirect can only shrink the bias, and it makes the all-mass-on-one
    limit exact.
    """
    hot = jnp.argmax(log_weights).astype(jnp.int32)
    return jnp.where(jnp.isfinite(log_weights[ancestors]), ancestors, hot)


def metropolis_ancestors_from_draws(log_weights: Array, proposals: Array,
                                    log_us: Array) -> Array:
    """Metropolis-chain ancestors from precomputed draws.

    Lane ``l`` starts at ancestor ``l % n_in`` and runs ``B`` Metropolis
    steps with uniform proposals: accept proposal ``j`` over the current
    ancestor ``a`` iff ``log u < lw[j] - lw[a]`` (the ratio ``w_j/w_a``
    in log space — weight *normalization never enters*, which is what
    makes the scheme collective-free).  ``proposals``/``log_us`` are
    ``(lanes, B)``; passing the draws explicitly is what lets the Pallas
    kernel (``repro.kernels.resample.metropolis_ancestors_kernel``)
    reproduce this reference exactly, comparison for comparison.
    Lanes still sitting on a zero-weight slot after the chain take the
    argmax slot (``_dead_slot_guard``).
    """
    n_in = log_weights.shape[0]
    lanes = jnp.arange(proposals.shape[0], dtype=jnp.int32)
    a0 = jnp.remainder(lanes, n_in)

    def body(b, a):
        j = proposals[:, b]
        accept = log_us[:, b] < log_weights[j] - log_weights[a]
        return jnp.where(accept, j, a)

    a = jax.lax.fori_loop(0, proposals.shape[1], body, a0)
    return _dead_slot_guard(a, log_weights)


def rejection_ancestors_from_draws(log_weights: Array, proposals: Array,
                                   log_us: Array) -> Array:
    """Rejection-sampling ancestors from precomputed draws.

    The first half of the draw budget runs pure rejection: lane ``l``
    accepts the first proposal ``j`` with ``log u < lw[j] − max(lw)``
    (i.e. ``u < w_j / w_max`` — only the *max* weight is needed, a
    cheap reduction, never a prefix sum); accepted lanes are exact
    multinomial draws.  Lanes that exhaust their tries switch to a
    Metropolis chain over the second half of the draws (Murray, Lee &
    Jacob's practical cap for the unbounded sampler, arXiv:1301.4019
    §4) — the independent fallback keeps the combined per-lane TV bias
    at ``(1 − ā)^B`` for the FULL budget ``B`` while avoiding the
    ensemble collapse an argmax fallback causes at low acceptance
    rates ``ā = 1/(N·w_max)`` (DESIGN.md §13.2).  Dead final slots
    redirect to argmax exactly as in the Metropolis scheme.
    """
    m = jnp.max(log_weights)
    n_in = log_weights.shape[0]
    lanes = jnp.arange(proposals.shape[0], dtype=jnp.int32)
    budget = proposals.shape[1]
    tries = budget // 2

    def rej_body(r, carry):
        a, accepted = carry
        j = proposals[:, r]
        acc = log_us[:, r] < log_weights[j] - m
        a = jnp.where(jnp.logical_and(acc, jnp.logical_not(accepted)), j, a)
        return a, jnp.logical_or(accepted, acc)

    a_rej, accepted = jax.lax.fori_loop(
        0, tries, rej_body,
        (jnp.zeros(lanes.shape, jnp.int32), jnp.zeros(lanes.shape, bool)))

    def mh_body(b, a):
        j = proposals[:, b]
        accept = log_us[:, b] < log_weights[j] - log_weights[a]
        return jnp.where(accept, j, a)

    a_mh = jax.lax.fori_loop(tries, budget, mh_body,
                             jnp.remainder(lanes, n_in))
    return _dead_slot_guard(jnp.where(accepted, a_rej, a_mh), log_weights)


def resampling_draws(key: Array, n_in: int, lanes: int,
                     iters: int) -> tuple[Array, Array]:
    """The ``(proposals, log_us)`` pair consumed by the collective-free
    schemes: ``(lanes, iters)`` uniform slot indices and log-uniforms.
    Shared by the jnp references and the Pallas kernel entry points so
    both consume identical randomness."""
    kp, ku = jax.random.split(key)
    proposals = jax.random.randint(kp, (lanes, iters), 0, n_in, jnp.int32)
    log_us = jnp.log(jax.random.uniform(ku, (lanes, iters)))
    return proposals, log_us


def _lanes_to_counts(ancestors: Array, n_in: int, n_out,
                     capacity: int) -> Array:
    """Histogram per-lane ancestors into counts, masking lanes ≥ n_out
    (``n_out`` may be traced ≤ capacity, DESIGN.md §2.1)."""
    valid = jnp.arange(capacity) < n_out
    counts = jnp.zeros((n_in,), jnp.int32)
    return counts.at[jnp.where(valid, ancestors, 0)].add(
        jnp.where(valid, 1, 0))


def metropolis_counts(key: Array, log_weights: Array, n_out,
                      capacity: int | None = None, *,
                      iters: int = METROPOLIS_ITERS) -> Array:
    """Metropolis resampling (collective-free, arXiv:1212.1639 §3).

    Asymptotically unbiased in the chain length ``iters``; the default
    keeps the bias far below the repo's 5-sigma gates (see
    ``METROPOLIS_ITERS``).  No CDF, no prefix sum, no normalization.
    """
    capacity = capacity or log_weights.shape[0]
    proposals, log_us = resampling_draws(key, log_weights.shape[0],
                                         capacity, iters)
    anc = metropolis_ancestors_from_draws(log_weights, proposals, log_us)
    return _lanes_to_counts(anc, log_weights.shape[0], n_out, capacity)


def rejection_counts(key: Array, log_weights: Array, n_out,
                     capacity: int | None = None, *,
                     tries: int = REJECTION_TRIES) -> Array:
    """Rejection resampling (collective-free, arXiv:1301.4019 §4).

    Exactly multinomial on every lane whose try budget hits; exhausted
    lanes run a Metropolis fallback chain on the remaining draws
    (``rejection_ancestors_from_draws``).  Needs only ``max(lw)`` — a
    cheap reduction, never a prefix sum.
    """
    capacity = capacity or log_weights.shape[0]
    proposals, log_us = resampling_draws(key, log_weights.shape[0],
                                         capacity, tries)
    anc = rejection_ancestors_from_draws(log_weights, proposals, log_us)
    return _lanes_to_counts(anc, log_weights.shape[0], n_out, capacity)


# ---------------------------------------------------------------------------
# Ancestor-form wrappers (single-device SIR path)
# ---------------------------------------------------------------------------

def _as_ancestors(counts_fn):
    def f(key: Array, log_weights: Array, n_out: int) -> Array:
        counts = counts_fn(key, log_weights, n_out, capacity=max(n_out, log_weights.shape[0]))
        return counts_to_ancestors(counts, n_out)

    return f


systematic_ancestors = _as_ancestors(systematic_counts)
stratified_ancestors = _as_ancestors(stratified_counts)
multinomial_ancestors = _as_ancestors(multinomial_counts)
residual_ancestors = _as_ancestors(residual_counts)
metropolis_ancestors = _as_ancestors(metropolis_counts)
rejection_ancestors = _as_ancestors(rejection_counts)

RESAMPLERS = {
    "systematic": systematic_counts,
    "stratified": stratified_counts,
    "multinomial": multinomial_counts,
    "residual": residual_counts,
    "metropolis": metropolis_counts,
    "rejection": rejection_counts,
}

# Schemes with no cross-lane dependency (no CDF / prefix sum): eligible
# for the fused-step fast path and the standalone Pallas kernels in
# ``repro.kernels.resample`` (DESIGN.md §13.2).
COLLECTIVE_FREE = ("metropolis", "rejection")
