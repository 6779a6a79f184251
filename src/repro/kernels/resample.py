"""Pallas TPU kernel: systematic resampling ancestor selection.

The resampling step is the only part of SIR that is not embarrassingly
parallel (paper §II) — it needs the global weight CDF.  The kernel fuses:

  1. weight normalization (max-shift + exp) and an inclusive prefix-sum of
     the weights, computed once into a VMEM scratch buffer;
  2. the stratified-comb binary search, blocked over output positions.

The CDF lives in VMEM across all (sequential) grid steps, so the search
pass never touches HBM for it.  Mosaic does not lower this kernel yet
(``CUMSUM_REFUSED`` below), so it runs in interpret mode off the chip
only.  Capacity: N f32 ≤ ~2M fits the 16 MB VMEM of a v5e core alongside
blocks; per-shard ensembles in the distributed resamplers are far below
that (global N scales with the mesh, per-shard N does not — that is the
point of the PPF library).

Binary search is expressed as a fixed ``ceil(log2(N))``-step vectorized
bisection (Pallas has no searchsorted primitive on TPU).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

DEFAULT_BLOCK = 1024


def _kernel(u_ref, lw_ref, anc_ref, cdf_ref, *, n_in: int, n_out: int,
            block: int):
    i = pl.program_id(0)

    # --- pass 0: build the normalized CDF once (sequential grid on TPU) ---
    @pl.when(i == 0)
    def _build():
        lw = lw_ref[...]
        m = jnp.max(lw)
        w = jnp.exp(lw - m)
        w = w / jnp.sum(w)      # normalize BEFORE cumsum: bit-matches ref.py
        cdf_ref[...] = jnp.cumsum(w)

    # --- per-block: stratified comb points + vectorized bisection ---------
    u = u_ref[0]
    cdf = cdf_ref[...]
    pos = (i * block + jax.lax.iota(jnp.float32, block) + u) / n_out

    lo = jnp.zeros((block,), jnp.int32)
    hi = jnp.full((block,), n_in, jnp.int32)
    # invariant: cdf[lo-1] <= pos < cdf[hi]; find first index with cdf > pos.
    # the candidate range [0, n_in] holds n_in+1 values, so the bisection
    # needs ceil(log2(n_in+1)) steps — one short leaves a 2-wide range and
    # returns an ancestor one below the correct index.
    for _ in range(max(1, math.ceil(math.log2(n_in + 1)))):
        mid = (lo + hi) // 2
        cm = cdf[mid]
        go_right = cm <= pos
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, mid)
    anc_ref[...] = jnp.minimum(lo, n_in - 1)


@functools.partial(jax.jit,
                   static_argnames=("n_out", "block", "interpret"))
def systematic_ancestors_kernel(log_weights: Array, u: Array, *,
                                n_out: int | None = None,
                                block: int = DEFAULT_BLOCK,
                                interpret: bool = False) -> Array:
    """Systematic-resampling ancestors.  u is the shared U[0,1) offset."""
    n_in = log_weights.shape[0]
    n_out = n_out or n_in
    assert n_out % block == 0, (n_out, block)
    grid = (n_out // block,)

    kernel = functools.partial(_kernel, n_in=n_in, n_out=n_out, block=block)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),          # u (scalar-ish)
            pl.BlockSpec((n_in,), lambda i: (0,)),       # full weights
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_out,), jnp.int32),
        scratch_shapes=[pltpu_vmem((n_in,), jnp.float32)],
        interpret=interpret,
    )(u.reshape(1), log_weights)


def pltpu_vmem(shape, dtype):
    """VMEM scratch allocation (kept separate for interpret-mode fallback)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def pick_block(n_out: int, max_block: int = DEFAULT_BLOCK) -> int:
    """Largest power-of-two block ≤ ``max_block`` dividing ``n_out``
    (the kernel's grid requires ``n_out % block == 0``)."""
    b = 1
    while b * 2 <= max_block and n_out % (b * 2) == 0:
        b *= 2
    return b


def kernel_applicable(n_out: int) -> bool:
    """Whether the kernel grid is worth launching for this output size.
    A tiny block (odd / small n_out) degenerates to a per-element grid."""
    return pick_block(n_out) >= 8


# What Mosaic (jax 0.9.0, libtpu 0.0.34) says when asked to compile these
# kernels for a TPU v5e.  Every kernel of this module, of
# ``repro.kernels.sir_fused`` and of ``repro.kernels.patch_likelihood`` is
# refused, although each passes its interpret-mode tests; so no TPU path
# selects them, and they run in interpret mode off the chip only.
CUMSUM_REFUSED = ("NotImplementedError: Unimplemented primitive in Pallas "
                  "TPU lowering for KernelType.TC: cumsum")
GATHER_REFUSED = "NotImplementedError: Only 2D gather is supported"


def interpret_only(kernel: str, refusal: str, backend: str = "interpret"
                   ) -> bool:
    """``interpret=`` for a kernel Mosaic refuses: always ``True``.

    Raises instead where the kernel would have to run compiled — an
    explicit ``backend="pallas"`` request, or any call on a TPU, where
    interpret mode would quietly emulate the kernel on the chip.
    """
    if backend == "pallas" or jax.default_backend() == "tpu":
        raise NotImplementedError(
            f"the Pallas {kernel} kernel does not lower for TPU "
            f"({refusal}); use the XLA path")
    return True


def systematic_ancestors_auto(log_weights: Array, u: Array, *,
                              n_out: int | None = None) -> Array:
    """Kernel entry point in interpret mode (CPU CI, the simulated-device
    harness), block size picked to divide ``n_out``; raises on a TPU
    (``CUMSUM_REFUSED``)."""
    n_out = n_out or log_weights.shape[0]
    return systematic_ancestors_kernel(
        log_weights, u, n_out=n_out, block=pick_block(n_out),
        interpret=interpret_only("systematic resampling", CUMSUM_REFUSED))


# ---------------------------------------------------------------------------
# Collective-free resamplers (Metropolis / rejection, DESIGN.md §13.2)
#
# Neither scheme needs the global CDF, so — unlike the systematic kernel
# above — there is NO sequential build pass and NO prefix sum: every
# output lane runs an independent chain of weight-ratio comparisons
# against the full log-weight vector resident in VMEM.  The random draws
# (proposal indices + log-uniforms) are precomputed by the caller with
# ``repro.core.resampling.resampling_draws`` so the kernels reproduce the
# jnp references *exactly*, comparison for comparison (pinned by
# tests/test_resampling_prop.py).
# ---------------------------------------------------------------------------


def _metropolis_kernel(lw_ref, prop_ref, logu_ref, anc_ref, *, n_in: int,
                       block: int, iters: int):
    i = pl.program_id(0)
    lw = lw_ref[...]
    lane = i * block + jax.lax.iota(jnp.int32, block)
    a = jax.lax.rem(lane, n_in)
    for b in range(iters):        # static chain length — fully unrolled
        j = prop_ref[:, b]
        accept = logu_ref[:, b] < lw[j] - lw[a]
        a = jnp.where(accept, j, a)
    hot = jnp.argmax(lw).astype(jnp.int32)
    anc_ref[...] = jnp.where(jnp.isfinite(lw[a]), a, hot)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def metropolis_ancestors_kernel(log_weights: Array, proposals: Array,
                                log_us: Array, *, block: int = DEFAULT_BLOCK,
                                interpret: bool = False) -> Array:
    """Metropolis-resampling ancestors (arXiv:1212.1639 §3).

    ``proposals``/``log_us`` are the ``(n_out, iters)`` draws from
    ``resampling_draws``; matches
    ``resampling.metropolis_ancestors_from_draws`` bit for bit.
    """
    n_in = log_weights.shape[0]
    n_out, iters = proposals.shape
    assert n_out % block == 0, (n_out, block)
    kernel = functools.partial(_metropolis_kernel, n_in=n_in, block=block,
                               iters=iters)
    return pl.pallas_call(
        kernel,
        grid=(n_out // block,),
        in_specs=[
            pl.BlockSpec((n_in,), lambda i: (0,)),        # full weights
            pl.BlockSpec((block, iters), lambda i: (i, 0)),
            pl.BlockSpec((block, iters), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_out,), jnp.int32),
        interpret=interpret,
    )(log_weights, proposals, log_us)


def _rejection_kernel(lw_ref, prop_ref, logu_ref, anc_ref, *, n_in: int,
                      block: int, tries: int):
    i = pl.program_id(0)
    lw = lw_ref[...]
    m = jnp.max(lw)
    a = jnp.zeros(anc_ref.shape, jnp.int32)
    accepted = jnp.zeros(anc_ref.shape, jnp.bool_)
    half = tries // 2
    for r in range(half):         # rejection phase — fully unrolled
        j = prop_ref[:, r]
        acc = logu_ref[:, r] < lw[j] - m
        a = jnp.where(jnp.logical_and(acc, jnp.logical_not(accepted)), j, a)
        accepted = jnp.logical_or(accepted, acc)
    lane = i * block + jax.lax.iota(jnp.int32, block)
    b = jax.lax.rem(lane, n_in)
    for r in range(half, tries):  # Metropolis fallback chain
        j = prop_ref[:, r]
        acc = logu_ref[:, r] < lw[j] - lw[b]
        b = jnp.where(acc, j, b)
    a = jnp.where(accepted, a, b)
    hot = jnp.argmax(lw).astype(jnp.int32)
    anc_ref[...] = jnp.where(jnp.isfinite(lw[a]), a, hot)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def rejection_ancestors_kernel(log_weights: Array, proposals: Array,
                               log_us: Array, *, block: int = DEFAULT_BLOCK,
                               interpret: bool = False) -> Array:
    """Rejection-resampling ancestors (arXiv:1301.4019 §4).

    First half of the draw budget is pure rejection, second half the
    Metropolis fallback chain for exhausted lanes, dead final slots
    redirect to argmax — exactly as
    ``resampling.rejection_ancestors_from_draws`` does.
    """
    n_in = log_weights.shape[0]
    n_out, tries = proposals.shape
    assert n_out % block == 0, (n_out, block)
    kernel = functools.partial(_rejection_kernel, n_in=n_in, block=block,
                               tries=tries)
    return pl.pallas_call(
        kernel,
        grid=(n_out // block,),
        in_specs=[
            pl.BlockSpec((n_in,), lambda i: (0,)),
            pl.BlockSpec((block, tries), lambda i: (i, 0)),
            pl.BlockSpec((block, tries), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_out,), jnp.int32),
        interpret=interpret,
    )(log_weights, proposals, log_us)


COLLECTIVE_FREE_KERNELS = {
    "metropolis": metropolis_ancestors_kernel,
    "rejection": rejection_ancestors_kernel,
}
