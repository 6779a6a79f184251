"""Pallas TPU kernel: Gaussian-PSF image-patch log-likelihood (paper §VI.E).

The paper's dominant compute cost is evaluating Eq. 4 for every particle.
Its two CPU optimizations map onto the TPU memory hierarchy as:

* *image patches* (§VI.E — load only the ±3σ window)  →  the full frame is
  pinned in VMEM once (512×512 f32 = 1 MB ≪ 16 MB VMEM) and each particle
  touches only its (2R+1)² window of it; patches never round-trip to HBM.
* *checkerboard thread balancing* (§VI.D)  →  the grid tiles the PARTICLE
  index space, not the image: a converged (spatially clustered) posterior
  still fills every grid step with exactly ``block_n`` particles, so load
  balance is structural rather than adaptive (DESIGN.md §2.4).

Layout: struct-of-arrays (y, x, i0 as separate (N,) vectors) so a particle
block occupies the lane dimension; the (2R+1)² patch loop is a compile-time
unrolled accumulation in vector registers.

The matched-filter form  (ΣZ·I − ½ΣI²)/σ_ξ²  and the paper's Eq. 4 form
−Σ(Z−I)²/2σ_ξ²  are both supported (see ``repro.models.tracking``).

Mosaic does not lower this kernel yet: the per-offset ``img[py, px]``
gather fails in its gather lowering rule (``repro.kernels.ops``), so it
runs in interpret mode off the chip only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

DEFAULT_BLOCK_N = 1024


def _kernel(y_ref, x_ref, i0_ref, img_ref, geom_ref, out_ref, *,
            radius: int, sigma_psf: float, sigma_like: float, i_bg: float,
            matched: bool, h: int, w: int):
    y = y_ref[...]
    x = x_ref[...]
    i0 = i0_ref[...]
    img = img_ref[...]
    # (6,) geometry: center clamp lo_y/hi_y/lo_x/hi_x + frame origin oy/ox
    # of img[0, 0] (all frame coordinates; domain slabs, DESIGN.md §10.2)
    g = geom_ref[...]

    cy = jnp.clip(jnp.round(y).astype(jnp.int32), g[0], g[1])
    cx = jnp.clip(jnp.round(x).astype(jnp.int32), g[2], g[3])

    inv2s2 = 0.5 / (sigma_psf * sigma_psf)
    acc = jnp.zeros_like(y)
    # Unrolled accumulation over the (2R+1)^2 patch: one vectorized gather
    # per offset, running sums held in VREGs.
    for dy in range(-radius, radius + 1):
        for dx in range(-radius, radius + 1):
            py = cy + dy
            px = cx + dx
            z = img[py - g[4], px - g[5]]
            d2 = (py.astype(y.dtype) - y) ** 2 + (px.astype(x.dtype) - x) ** 2
            model = i0 * jnp.exp(-d2 * inv2s2) + i_bg
            if matched:
                acc += z * model - 0.5 * model * model
            else:
                r = z - model
                acc += -0.5 * r * r
    out_ref[...] = acc / (sigma_like * sigma_like)


@functools.partial(
    jax.jit,
    static_argnames=("radius", "sigma_psf", "sigma_like", "i_bg", "matched",
                     "block_n", "interpret"))
def patch_log_likelihood_kernel(y: Array, x: Array, i0: Array, image: Array,
                                *, radius: int = 4, sigma_psf: float = 1.16,
                                sigma_like: float = 2.0, i_bg: float = 0.0,
                                matched: bool = True,
                                block_n: int = DEFAULT_BLOCK_N,
                                center_bounds: Array | None = None,
                                frame_origin: Array | None = None,
                                interpret: bool = False) -> Array:
    """(N,) log-likelihoods for N particles against one (H, W) frame.

    ``center_bounds`` is an optional (4,) int32 clamp (lo_y, hi_y, lo_x,
    hi_x) for the patch-center pixel in frame coordinates, defaulting to
    the frame interior ``[R, dim-1-R]``; ``frame_origin`` is an optional
    (2,) int32 frame coordinate of ``image[0, 0]``, for evaluating
    against a halo *slab* of a larger frame (DESIGN.md §10.2 — only the
    gather is offset; all float math stays in frame coordinates).  Both
    ride along as one tiny vector operand so they may be traced (inside
    ``shard_map`` the slab origin derives from the shard index).
    """
    n = y.shape[0]
    h, w = image.shape
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    if center_bounds is None:
        center_bounds = jnp.asarray(
            [radius, h - 1 - radius, radius, w - 1 - radius], jnp.int32)
    if frame_origin is None:
        frame_origin = jnp.zeros((2,), jnp.int32)
    geom = jnp.concatenate([jnp.asarray(center_bounds, jnp.int32).reshape(4),
                            jnp.asarray(frame_origin, jnp.int32).reshape(2)])

    vec_spec = pl.BlockSpec((block_n,), lambda i: (i,))
    img_spec = pl.BlockSpec((h, w), lambda i: (0, 0))
    geom_spec = pl.BlockSpec((6,), lambda i: (0,))

    kernel = functools.partial(_kernel, radius=radius, sigma_psf=sigma_psf,
                               sigma_like=sigma_like, i_bg=i_bg,
                               matched=matched, h=h, w=w)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[vec_spec, vec_spec, vec_spec, img_spec, geom_spec],
        out_specs=vec_spec,
        out_shape=jax.ShapeDtypeStruct((n,), y.dtype),
        interpret=interpret,
    )(y, x, i0, image, geom)
