"""Public, jit'd entry points for the kernel layer.

Each op dispatches between the Pallas TPU kernel and the pure-jnp oracle:

* ``backend="pallas"``     — compile for TPU (production target);
* ``backend="interpret"``  — Pallas interpret mode (CPU correctness runs);
* ``backend="xla"``        — the ref.py oracle under plain XLA (this is
  what the multi-pod dry-run lowers, since it compiles for CPU).

The default is resolved from the actual backend so user code never
branches on platform: attention takes its kernel on TPU (compiled for
v5e by tests/test_tpu_compile.py); the patch likelihood and systematic
resampling kernels take XLA everywhere, because Mosaic refuses them
(``_GATHER_ASSERT`` below, ``resample.CUMSUM_REFUSED``), and raise when
asked for ``"pallas"`` or run on a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash_kernel
from repro.kernels.patch_likelihood import patch_log_likelihood_kernel
from repro.kernels import resample as resample_kernels
from repro.kernels.resample import systematic_ancestors_kernel

Array = jax.Array

# Mosaic's refusal of the patch-likelihood kernel on v5e (jax 0.9.0,
# libtpu 0.0.34): the per-offset ``img[py, px]`` vector gather fails
_GATHER_ASSERT = ("AssertionError in pallas/mosaic/lowering.py "
                  "_gather_lowering_rule: indices_aval.shape == "
                  "in_aval.shape + (1,)")


def default_backend() -> str:
    """Attention's backend: its kernel on TPU, XLA elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def patch_log_likelihood(y: Array, x: Array, i0: Array, image: Array, *,
                         radius: int = 4, sigma_psf: float = 1.16,
                         sigma_like: float = 2.0, i_bg: float = 0.0,
                         matched: bool = True, block_n: int = 1024,
                         center_bounds: Array | None = None,
                         frame_origin: Array | None = None,
                         backend: str | None = None) -> Array:
    backend = backend or "xla"
    if backend == "xla":
        return ref.patch_log_likelihood_ref(
            y, x, i0, image, radius=radius, sigma_psf=sigma_psf,
            sigma_like=sigma_like, i_bg=i_bg, matched=matched,
            center_bounds=center_bounds, frame_origin=frame_origin)
    return patch_log_likelihood_kernel(
        y, x, i0, image, radius=radius, sigma_psf=sigma_psf,
        sigma_like=sigma_like, i_bg=i_bg, matched=matched,
        block_n=min(block_n, y.shape[0]),
        center_bounds=center_bounds, frame_origin=frame_origin,
        interpret=resample_kernels.interpret_only(
            "patch likelihood", _GATHER_ASSERT, backend))


def systematic_ancestors(log_weights: Array, u: Array, *,
                         n_out: int | None = None, block: int = 1024,
                         backend: str | None = None) -> Array:
    backend = backend or "xla"
    n_out = n_out or log_weights.shape[0]
    if backend == "xla":
        return ref.systematic_ancestors_ref(log_weights, u, n_out)
    return systematic_ancestors_kernel(
        log_weights, u, n_out=n_out, block=min(block, n_out),
        interpret=resample_kernels.interpret_only(
            "systematic resampling", resample_kernels.CUMSUM_REFUSED,
            backend))


def attention(q: Array, k: Array, v: Array, *, causal: bool = True,
              scale: float | None = None, logit_softcap: float = 0.0,
              backend: str | None = None) -> Array:
    backend = backend or default_backend()
    if backend == "xla":
        return ref.mha_ref(q, k, v, causal=causal, scale=scale,
                           logit_softcap=logit_softcap)
    return _flash_kernel(q, k, v, causal=causal, scale=scale,
                         logit_softcap=logit_softcap,
                         interpret=(backend == "interpret"))
