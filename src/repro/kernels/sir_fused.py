"""Fused SIR weight-phase step — one pass instead of four ops.

The composed SIR step (``repro.core.smc.make_sir_step``) runs reweight →
estimate → ESS/log-Z → resample as separate XLA ops, each re-deriving
the normalized weights (max-shift, exp, sum) and re-reading the
log-weight vector from HBM; the resampler additionally materializes a
counts histogram (scatter-add) and expands it back to ancestors
(``jnp.repeat``).  This module fuses everything downstream of the
model's two callbacks (transition sample + observation log-prob, which
are arbitrary user code and therefore stay outside) into ONE weight
phase that normalizes once and shares the result (DESIGN.md §13):

    lw' = lw + log_lik           (−inf slots stay dead)
    w   = softmax(lw')           (single max/exp/sum)
    estimate = Σ w·x             (f32 accumulation, state may be bf16)
    ESS, log Z, resample decision
    ancestors — the systematic comb in closed form over this CDF, or the
    collective-free Metropolis/rejection chains, which need no CDF at
    all (both in repro.core.resampling)

Three backends, same contract as the rest of the kernel layer:

* ``xla``       — the jnp reference below under plain XLA: the path on
  every platform (BENCH_kernels.json records the fused-vs-composed
  ratio on CPU);
* ``pallas``    — the megakernel compiled for TPU: log-weights, CDF, and
  the moment accumulators live in VMEM across the (sequential) grid, so
  the weight phase reads the state exactly once from HBM and the weight
  vector never makes an HBM round-trip between ops.  Mosaic does not
  lower it yet (``resample.CUMSUM_REFUSED``; the chain kernels hit
  ``resample.GATHER_REFUSED``), so this backend raises;
* ``interpret`` — the Pallas kernel emulated on CPU (correctness CI).

VMEM capacity, for when it lowers: two N-f32 scratch vectors (shifted
log-weights + CDF) plus an N×D state block stream — N ≤ ~1.5M f32 fits a
v5e core's 16 MB alongside blocks, same envelope as
``repro.kernels.resample``.
"""
from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import resampling
from repro.kernels import resample as resample_kernels

Array = jax.Array

DEFAULT_BLOCK = 1024

# Resampling schemes the fused weight phase can commit on-chip: the
# systematic comb (CDF in VMEM) and the two collective-free chains.
FUSED_RESAMPLERS = ("systematic", "metropolis", "rejection")


class FusedDecision(NamedTuple):
    """Everything the SIR step needs downstream of the model callbacks.

    ``ancestors`` already folds the ESS decision in (identity when not
    resampled); ``new_log_weights`` is the post-step weight vector
    *before* the ancestor gather (the caller gathers state and weights
    together, exactly like the composed path).
    """

    ancestors: Array        # (N,) int32
    estimate: Any           # state pytree sans leading dim (w·x, f32 acc)
    ess: Array              # scalar N_eff before resampling
    log_z: Array            # scalar logsumexp of the post-reweight weights
    resampled: Array        # scalar bool
    new_log_weights: Array  # (N,) f32 — uniform if resampled, shifted else
    weight_skew: Array      # scalar N·max(w) — 1 uniform, N collapsed


def fused_applicable(resampler: str) -> bool:
    """Whether ``make_sir_step(step_backend="fused")`` can honor the
    configured resampler; callers fall back to the composed step
    otherwise (DESIGN.md §13.1)."""
    return resampler in FUSED_RESAMPLERS


# ---------------------------------------------------------------------------
# XLA reference (the CPU fast path and the kernel's ground truth)
# ---------------------------------------------------------------------------

def fused_weight_step_ref(log_weights: Array, log_lik: Array, state: Any,
                          key: Array, *, resampler: str = "systematic",
                          ess_frac: float = 0.5,
                          always: bool = False) -> FusedDecision:
    """Single-normalization weight phase in pure jnp.

    Numerics vs the composed path: the softmax (max-shift, exp, sum) is
    computed once and shared by the estimate, ESS, log-Z, and the comb
    CDF, where the composed ops each re-derive it — every shared
    quantity agrees with the composed path to ≤ 1 ulp, and the
    systematic ancestors come from the closed-form comb over the
    *singly*-normalized CDF, not the composed path's re-normalized one
    (drift bound measured and pinned by tests/test_ssm_parity.py; DESIGN.md
    §13.3).  The estimate keeps ``weighted_mean``'s multiply+sum form so
    bank slots stay vmap-bitwise-stable (DESIGN.md §11.2).
    """
    n = log_weights.shape[0]
    lw = jnp.where(jnp.isfinite(log_weights), log_weights + log_lik,
                   -jnp.inf)
    m = jnp.max(lw)
    mg = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.exp(lw - mg)
    s = jnp.sum(e)
    w = jnp.where(s > 0, e / s, jnp.ones_like(e) / n)
    ess = 1.0 / jnp.sum(jnp.square(w))
    log_z = mg + jnp.log(s)

    def _mean(x):
        wx = jnp.reshape(w.astype(x.dtype), w.shape + (1,) * (x.ndim - 1))
        return jnp.sum(wx * x, axis=0)

    estimate = jax.tree_util.tree_map(_mean, state)
    resampled = jnp.logical_or(ess < ess_frac * n, jnp.asarray(always))
    anc = _ref_ancestors(w, lw, key, resampler)
    lane = jnp.arange(n, dtype=jnp.int32)
    anc = jnp.where(resampled, anc, lane)
    new_lw = jnp.where(resampled, jnp.full_like(lw, -jnp.log(float(n))),
                       lw - log_z)
    skew = n * jnp.max(w)
    return FusedDecision(anc, estimate, ess, log_z, resampled, new_lw, skew)


def _ref_ancestors(w: Array, lw: Array, key: Array, resampler: str) -> Array:
    """Scheme dispatch for the reference weight phase.  Systematic draws
    the same single uniform offset as ``resampling.systematic_counts``
    (one ``uniform(key, ())``); the collective-free schemes consume
    ``resampling_draws`` — identical randomness to the composed path."""
    n = w.shape[0]
    if resampler == "systematic":
        u = jax.random.uniform(key, ())
        counts = resampling.systematic_comb_counts(jnp.cumsum(w), u, n, n)
        return resampling.counts_to_ancestors(counts, n)
    if resampler in resampling.COLLECTIVE_FREE:
        iters = (resampling.METROPOLIS_ITERS if resampler == "metropolis"
                 else resampling.REJECTION_TRIES)
        proposals, log_us = resampling.resampling_draws(key, n, n, iters)
        fn = (resampling.metropolis_ancestors_from_draws
              if resampler == "metropolis"
              else resampling.rejection_ancestors_from_draws)
        return fn(lw, proposals, log_us)
    raise ValueError(f"fused step does not support resampler={resampler!r} "
                     f"(supported: {FUSED_RESAMPLERS})")


# ---------------------------------------------------------------------------
# Pallas megakernel
# ---------------------------------------------------------------------------
# Grid step 0 builds the whole weight picture into VMEM scratch (shifted
# log-weights, CDF, scalar stats); every grid step then accumulates its
# state block into the f32 moment output and commits its ancestor /
# new-log-weight block — state is read from HBM exactly once, the weight
# vector never leaves VMEM.

def _fused_kernel(u_ref, lw_ref, ll_ref, state_ref, anc_ref, newlw_ref,
                  est_ref, stats_ref, lwpost_ref, cdf_ref, scal_ref, *,
                  n: int, d: int, block: int, ess_frac: float, always: bool,
                  comb: bool):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _build():
        lw0 = lw_ref[...]
        lw = jnp.where(jnp.isfinite(lw0), lw0 + ll_ref[...], -jnp.inf)
        lwpost_ref[...] = lw
        m = jnp.max(lw)
        mg = jnp.where(jnp.isfinite(m), m, 0.0)
        e = jnp.exp(lw - mg)
        s = jnp.sum(e)
        w = jnp.where(s > 0, e / s, 1.0 / n)
        cdf_ref[...] = jnp.cumsum(w)
        ess = 1.0 / jnp.sum(w * w)
        resampled = jnp.logical_or(ess < ess_frac * n, always)
        scal_ref[0] = ess
        scal_ref[1] = mg + jnp.log(s)            # log Z
        scal_ref[2] = resampled.astype(jnp.float32)
        scal_ref[3] = mg
        scal_ref[4] = s
        scal_ref[5] = n * jnp.max(w)             # weight skew N·max(w)
        est_ref[...] = jnp.zeros((1, d), jnp.float32)

    ess, log_z, resampled_f = scal_ref[0], scal_ref[1], scal_ref[2]
    mg, s = scal_ref[3], scal_ref[4]
    resampled = resampled_f > 0.0

    # moment accumulation: one f32 FMA pass over this state block
    lw_b = lwpost_ref[pl.ds(i * block, block)]
    w_b = jnp.where(s > 0, jnp.exp(lw_b - mg) / s, 1.0 / n)
    x_b = state_ref[...].astype(jnp.float32)
    est_ref[...] += jnp.dot(w_b.reshape(1, block), x_b)

    # resampling commit (systematic comb via bisection over the VMEM CDF;
    # collective-free schemes run their own kernels and comb=False here)
    lane = i * block + jax.lax.iota(jnp.int32, block)
    if comb:
        u = u_ref[0]
        cdf = cdf_ref[...]
        pos = (lane.astype(jnp.float32) + u) / n
        lo = jnp.zeros((block,), jnp.int32)
        hi = jnp.full((block,), n, jnp.int32)
        for _ in range(max(1, math.ceil(math.log2(n + 1)))):
            mid = (lo + hi) // 2
            go_right = cdf[mid] <= pos
            lo = jnp.where(go_right, mid + 1, lo)
            hi = jnp.where(go_right, hi, mid)
        anc = jnp.minimum(lo, n - 1)
        anc_ref[...] = jnp.where(resampled, anc, lane)
    else:
        anc_ref[...] = lane

    newlw_ref[...] = jnp.where(resampled,
                               jnp.full((block,), -math.log(n), jnp.float32),
                               lw_b - log_z)
    stats_ref[...] = scal_ref[0:6]


@functools.partial(jax.jit, static_argnames=("block", "ess_frac", "always",
                                             "comb", "interpret"))
def fused_weight_step_kernel(log_weights: Array, log_lik: Array,
                             state_mat: Array, u: Array, *,
                             block: int = DEFAULT_BLOCK,
                             ess_frac: float = 0.5, always: bool = False,
                             comb: bool = True, interpret: bool = False):
    """The megakernel on a flattened ``(N, D)`` f32/bf16 state matrix.

    Returns ``(ancestors, new_log_weights, estimate_(D,), stats_(6,))``
    with ``stats = [ess, log_z, resampled, max_shift, exp_sum, weight_skew]``.  With
    ``comb=False`` the ancestor output is the identity permutation (the
    caller commits a collective-free scheme's ancestors instead).
    """
    n = log_weights.shape[0]
    d = state_mat.shape[1]
    assert n % block == 0, (n, block)
    kernel = functools.partial(_fused_kernel, n=n, d=d, block=block,
                               ess_frac=ess_frac, always=always, comb=comb)
    grid = (n // block,)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),            # u
            pl.BlockSpec((n,), lambda i: (0,)),            # log-weights
            pl.BlockSpec((n,), lambda i: (0,)),            # log-likelihood
            pl.BlockSpec((block, d), lambda i: (i, 0)),    # state stream
        ],
        out_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),        # ancestors
            pl.BlockSpec((block,), lambda i: (i,)),        # new log-weights
            pl.BlockSpec((1, d), lambda i: (0, 0)),        # moment acc
            pl.BlockSpec((6,), lambda i: (0,)),            # scalar stats
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((6,), jnp.float32),
        ],
        scratch_shapes=[
            resample_kernels.pltpu_vmem((n,), jnp.float32),   # lw_post
            resample_kernels.pltpu_vmem((n,), jnp.float32),   # cdf
            resample_kernels.pltpu_vmem((8,), jnp.float32),   # scalars
        ],
        interpret=interpret,
    )(u.reshape(1), log_weights, log_lik, state_mat)


# ---------------------------------------------------------------------------
# State flattening (pytree <-> (N, D) matrix for the kernel path)
# ---------------------------------------------------------------------------

def state_matrix(state: Any) -> tuple[Array, Any]:
    """Flatten a state pytree into an ``(N, D)`` matrix + an unflattener
    for the ``(D,)`` moment row the kernel accumulates."""
    leaves, treedef = jax.tree_util.tree_flatten(state)
    n = leaves[0].shape[0]
    mats = [x.reshape(n, -1) for x in leaves]
    dims = [m.shape[1] for m in mats]
    shapes = [x.shape[1:] for x in leaves]
    dtypes = [x.dtype for x in leaves]

    def unflatten_moments(row: Array) -> Any:
        outs, off = [], 0
        for dim, shape, dtype in zip(dims, shapes, dtypes):
            outs.append(row[off:off + dim].reshape(shape).astype(dtype))
            off += dim
        return jax.tree_util.tree_unflatten(treedef, outs)

    mat = mats[0] if len(mats) == 1 else jnp.concatenate(mats, axis=1)
    return mat, unflatten_moments


# ---------------------------------------------------------------------------
# Backend dispatcher (the entry point the SIR step builder calls)
# ---------------------------------------------------------------------------

def default_backend() -> str:
    """``xla`` on every platform: Mosaic refuses the megakernel on TPU
    (``resample.CUMSUM_REFUSED``), and interpret mode is for tests."""
    return "xla"


def fused_weight_step(log_weights: Array, log_lik: Array, state: Any,
                      key: Array, *, resampler: str = "systematic",
                      ess_frac: float = 0.5, always: bool = False,
                      backend: str | None = None) -> FusedDecision:
    """Run the fused weight phase on the best backend available.

    The Pallas path (interpret mode only: it raises for ``"pallas"`` and
    on a TPU) additionally requires a block-divisible N
    (``resample.pick_block``) and a flattenable float state; anything
    else silently takes the XLA reference, so callers never branch on
    platform (DESIGN.md §13.1).
    """
    backend = backend or default_backend()
    n = log_weights.shape[0]
    if backend == "xla" or not resample_kernels.kernel_applicable(n):
        return fused_weight_step_ref(log_weights, log_lik, state, key,
                                     resampler=resampler, ess_frac=ess_frac,
                                     always=always)
    refusal = (resample_kernels.CUMSUM_REFUSED if resampler == "systematic"
               else resample_kernels.GATHER_REFUSED)
    interpret = resample_kernels.interpret_only("fused SIR weight-phase",
                                                refusal, backend)
    block = resample_kernels.pick_block(n)
    mat, unflatten = state_matrix(state)
    comb = resampler == "systematic"
    if comb:
        u = jax.random.uniform(key, ())
    else:
        u = jnp.zeros(())            # comb unused; ancestors from chains
    anc, new_lw, est, stats = fused_weight_step_kernel(
        log_weights, log_lik, mat.astype(jnp.float32), u, block=block,
        ess_frac=ess_frac, always=always, comb=comb, interpret=interpret)
    ess, log_z, resampled = stats[0], stats[1], stats[2] > 0.0
    skew = stats[5]
    if not comb:
        lw_post = jnp.where(jnp.isfinite(log_weights),
                            log_weights + log_lik, -jnp.inf)
        iters = (resampling.METROPOLIS_ITERS if resampler == "metropolis"
                 else resampling.REJECTION_TRIES)
        proposals, log_us = resampling.resampling_draws(key, n, n, iters)
        chain = resample_kernels.COLLECTIVE_FREE_KERNELS[resampler](
            lw_post, proposals, log_us, block=block, interpret=interpret)
        anc = jnp.where(resampled, chain, anc)
    return FusedDecision(anc, unflatten(est[0]), ess, log_z, resampled,
                         new_lw, skew)
