"""Plain reference for the tracking configuration: a straightforward SIR
particle filter over the same model, written from the paper (arXiv:1310.5045
Alg. 1 and section VII) in plain ``jax.numpy``.

It imports nothing of the program under test and takes nothing the program
made: it gets the generated frames, a particle count and its own PRNG key.
``dtype`` is the precision everything is held in: ``float32`` is the
reference, ``bfloat16`` its control (the nearest precision below the one
the configuration states).  The likelihood is evaluated in blocks of
particles so that the reference fits beside nothing larger than its own
ensemble, at any particle count.

``compare`` turns a program trajectory and a reference trajectory into the
numbers that decide ``correct``: two independent particle filters of the
same model differ by Monte Carlo error alone, which shrinks with N.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 1 << 16   # particles per likelihood block
CONTROL = jnp.bfloat16


def _init(key, n, cfg, h, w, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    pos = jax.random.uniform(k1, (n, 2)) * jnp.asarray([h, w], jnp.float32)
    vel = jax.random.normal(k2, (n, 2)) * cfg["v_init"]
    inten = jnp.abs(cfg["i_peak"] + 0.5 * jax.random.normal(k3, (n, 1)))
    return jnp.concatenate([pos, vel, inten], axis=1).astype(dtype)


def _advance(key, x, cfg, h, w):
    eps = jax.random.normal(key, x.shape).astype(x.dtype)
    pos = x[:, 0:2] + x[:, 2:4] + cfg["sigma_pos"] * eps[:, 0:2]
    vel = x[:, 2:4] + cfg["sigma_vel"] * eps[:, 2:4]
    inten = jnp.abs(x[:, 4:5] + cfg["sigma_int"] * eps[:, 4:5])
    hi = jnp.asarray([h - 1.0, w - 1.0], x.dtype)
    pos = jnp.clip(pos, jnp.zeros((), x.dtype), hi)
    return jnp.concatenate([pos, vel, inten], axis=1)


def _loglik_block(x, frame, cfg):
    """Matched-filter log-likelihood of each particle of ``x`` on its own
    (2R+1)^2 patch, the patch centre clamped to the frame interior."""
    r = cfg["patch_radius"]
    h, w = frame.shape
    offs = jnp.arange(-r, r + 1)
    cy = jnp.clip(jnp.round(x[:, 0]).astype(jnp.int32), r, h - 1 - r)
    cx = jnp.clip(jnp.round(x[:, 1]).astype(jnp.int32), r, w - 1 - r)
    rows = cy[:, None] + offs[None, :]                    # (B, 2R+1)
    cols = cx[:, None] + offs[None, :]
    # one flat index per patch pixel: an element gather, which the TPU
    # runs ~3x faster than a windowed one (PERF.md section 5)
    flat = rows[:, :, None] * w + cols[:, None, :]         # (B, 2R+1, 2R+1)
    patch = frame.reshape(-1)[flat]
    dy2 = (rows.astype(x.dtype) - x[:, 0:1]) ** 2
    dx2 = (cols.astype(x.dtype) - x[:, 1:2]) ** 2
    two_s2 = 2.0 * cfg["sigma_psf"] ** 2
    model = x[:, 4, None, None] * jnp.exp(
        -(dy2[:, :, None] + dx2[:, None, :]) / two_s2) + cfg["i_bg"]
    if cfg["likelihood_form"] == "eq4":
        resid = patch - model
        return -0.5 * jnp.sum(resid * resid, axis=(1, 2)) / cfg["sigma_like"] ** 2
    return (jnp.sum(patch * model, axis=(1, 2))
            - 0.5 * jnp.sum(model * model, axis=(1, 2))) / cfg["sigma_like"] ** 2


def _loglik(x, frame, cfg):
    n = x.shape[0]
    block = min(BLOCK, n)
    blocks = x.reshape(n // block, block, x.shape[1])
    return jax.lax.map(lambda b: _loglik_block(b, frame, cfg), blocks).reshape(n)


def _systematic(key, w, n):
    cdf = jnp.cumsum(w)
    u = (jax.random.uniform(key, (), w.dtype) + jnp.arange(n, dtype=w.dtype)) / n
    return jnp.clip(jnp.searchsorted(cdf, u * cdf[-1]), 0, n - 1)


@functools.partial(jax.jit, static_argnames=("n", "cfg_items", "dtype",
                                              "ess_frac"))
def _run(key, frames, n, cfg_items, dtype, ess_frac):
    cfg = dict(cfg_items)
    h, w = frames.shape[1:]
    frames = frames.astype(dtype)
    k_init, key = jax.random.split(key)
    x = _init(k_init, n, cfg, h, w, dtype)
    lw = jnp.full((n,), -np.log(n), dtype)

    def step(carry, frame):
        key, x, lw = carry
        key, k_dyn, k_res = jax.random.split(key, 3)
        x = _advance(k_dyn, x, cfg, h, w)
        lw = lw + _loglik(x, frame, cfg).astype(dtype)
        log_z = jax.nn.logsumexp(lw)
        wn = jnp.exp(lw - log_z)
        est = jnp.sum(wn[:, None] * x, axis=0)
        ess = 1.0 / jnp.sum(wn * wn)
        resample = ess < ess_frac * n
        anc = _systematic(k_res, wn, n)
        x = jnp.where(resample, x[anc], x)
        lw = jnp.where(resample, jnp.full_like(lw, -np.log(n)), lw - log_z)
        return (key, x, lw), (est, ess, log_z)

    _, (est, ess, log_z) = jax.lax.scan(step, (key, x, lw), frames)
    return (est.astype(jnp.float32), ess.astype(jnp.float32),
            log_z.astype(jnp.float32))


def reference(cfg: dict, traffic: dict, frames, n: int, key,
              dtype=jnp.float32):
    """Per-frame ``(estimates (K, 5), ess (K,), log_marginal (K,))`` of a
    plain N-particle SIR filter (the mix's ESS threshold, systematic
    resampling) over ``frames`` ``(K, H, W)``, as host arrays."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str))))
    with jax.default_matmul_precision("highest"):
        out = _run(key, jnp.asarray(frames), n, items, jnp.dtype(dtype),
                   float(traffic["ess_frac"]))
    return tuple(np.asarray(a, np.float64) for a in out)


def compare(cfg: dict, traffic: dict, program, ref) -> dict:
    """The numbers compared for one trajectory pair.

    ``program`` and ``ref`` are ``(estimates, ess, log_marginal)`` over the
    same frames.  Positions are compared from the mix's ``warmup_frames``
    on: acquisition from the uniform prior takes several frames, during
    which the posterior is spread over the frame, and on some movies the
    two filters' means still differ by tenths of a pixel for a few frames
    after it (PERF.md section 4).  ``pos_med_px``: the median distance
    between the two position estimates over those frames.
    ``pos_gap_px``: the widest, which swings with how late the spot is
    acquired.  ``logz_gap``: the gap of
    the summed log marginal likelihoods.  ``ess_gap``: the median
    relative gap of the effective sample sizes, which sees a particle
    count that is not the one stated.
    """
    warmup = traffic["warmup_frames"]
    est, ess, lz = (np.asarray(a, np.float64) for a in program)
    r_est, r_ess, r_lz = (np.asarray(a, np.float64) for a in ref)
    k = min(len(est), len(r_est))
    pos = np.linalg.norm(est[warmup:k, :2] - r_est[warmup:k, :2], axis=1)
    finite = all(np.isfinite(a[:k]).all() for a in (est, ess, lz))
    if not finite or not len(pos):
        return dict.fromkeys(("pos_med_px", "pos_gap_px", "logz_gap",
                              "ess_gap"), float("inf"))
    ess_rel = np.abs(ess[warmup:k] / r_ess[warmup:k] - 1.0)
    return {"pos_med_px": float(np.median(pos)),
            "pos_gap_px": float(pos.max()),
            "logz_gap": float(abs(lz[:k].sum() - r_lz[:k].sum())),
            "ess_gap": float(np.median(ess_rel))}
