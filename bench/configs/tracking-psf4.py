"""The tracking configuration: the paper's section VII microscopy model
built through the program's own constructors, the seeded movies it is fed,
and the work one filter step needs.

The movie generator is the benchmark's own (traffic generation belongs to
the yardstick): one spot per movie moving at about ``v_init`` px/frame
toward a random far point, rendered with the Gaussian PSF plus Gaussian
read-out noise, as ``repro.data.synthetic_movie`` does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

STATE_DIM = 5          # (y, x, v_y, v_x, I_0)
F32 = 4                # bytes


def tracking_config(cfg: dict, img: int):
    """The program's ``TrackingConfig`` for this configuration at
    ``img`` x ``img`` frames."""
    from repro.models.tracking import TrackingConfig
    return TrackingConfig(
        img_size=(img, img), sigma_psf=cfg["sigma_psf"],
        sigma_noise=cfg["sigma_noise"], sigma_like=cfg["sigma_like"],
        i_peak=cfg["i_peak"], i_bg=cfg["i_bg"],
        likelihood_form=cfg["likelihood_form"], sigma_pos=cfg["sigma_pos"],
        sigma_vel=cfg["sigma_vel"], sigma_int=cfg["sigma_int"],
        v_init=cfg["v_init"], patch_radius=cfg["patch_radius"])


@functools.partial(jax.jit, static_argnames=("count", "frames", "img",
                                              "sigma_psf", "i_peak",
                                              "i_bg", "sigma_noise", "speed"))
def _movies(key, count, frames, img, sigma_psf, i_peak, i_bg, sigma_noise,
            speed):
    margin = 8.0 * sigma_psf
    lo, hi = margin, img - margin

    def one(k):
        k_pos, k_tgt, k_noise = jax.random.split(k, 3)
        pos0 = lo + jax.random.uniform(k_pos, (2,)) * (hi - lo)
        target = lo + jax.random.uniform(k_tgt, (2,)) * (hi - lo)
        heading = target - pos0
        dist = jnp.linalg.norm(heading)
        vel = heading / jnp.maximum(dist, 1e-6) * jnp.minimum(speed,
                                                              dist / frames)
        t = jnp.arange(1, frames + 1, dtype=jnp.float32)[:, None]
        traj = jnp.clip(pos0 + t * vel, lo, hi)                  # (K, 2)
        grid = jnp.arange(img, dtype=jnp.float32)
        dy2 = (grid[None, :] - traj[:, 0:1]) ** 2                # (K, H)
        dx2 = (grid[None, :] - traj[:, 1:2]) ** 2                # (K, W)
        clean = i_peak * jnp.exp(-(dy2[:, :, None] + dx2[:, None, :])
                                 / (2.0 * sigma_psf ** 2)) + i_bg
        noise = sigma_noise * jax.random.normal(k_noise, clean.shape)
        return clean + noise, traj

    return jax.vmap(one)(jax.random.split(key, count))


def make_inputs(cfg: dict, traffic: dict, key, count: int):
    """``count`` seeded movies of the mix's ``frames`` frames at ``img`` x
    ``img``, made on the device in one call: frames ``(count, K, img,
    img)`` float32 and true positions ``(count, K, 2)``."""
    return _movies(key, count, traffic["frames"], traffic["img"],
                   cfg["sigma_psf"], cfg["i_peak"], cfg["i_bg"],
                   cfg["sigma_noise"], cfg["v_init"])


def build_filter(cfg: dict, traffic: dict, n_particles: int, chips: int):
    """The program's offline entry, ``jax.jit`` of
    ``ParallelParticleFilter.run``, over ``n_particles`` in all; returns
    ``(run, place)``, ``place`` putting the frames where it reads them.

    On one chip the filter runs unsharded.  On more, the particles are
    sharded over a 1-D mesh of the ``chips`` devices and resampled by the
    distributed resampler that the mix's ``dra`` object configures (the
    fields of the program's ``DRAConfig``, e.g. ``{"kind": "rpa"}``); with
    the mix's ``domain`` true the frame is also split into one tile per
    chip (input-space domain decomposition)."""
    from repro.core import DRAConfig, ParallelParticleFilter, SIRConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models.tracking import make_domain_spec, make_tracking_model
    tcfg = tracking_config(cfg, traffic["img"])
    model = make_tracking_model(tcfg)
    sir = SIRConfig(n_particles=n_particles, ess_frac=traffic["ess_frac"])
    if chips == 1:
        pf = ParallelParticleFilter(model=model, sir=sir)
    else:
        if "dra" not in traffic:
            raise ValueError(f"a {chips}-chip cell needs a 'dra' object in "
                             "its traffic mix")
        domain = (make_domain_spec(tcfg, chips)
                  if traffic.get("domain") else None)
        pf = ParallelParticleFilter(model=model, sir=sir,
                                    dra=DRAConfig(**traffic["dra"]),
                                    mesh=make_host_mesh(chips),
                                    domain=domain)
    return jax.jit(pf.run), jax.device_put


def build_server(cfg: dict, traffic: dict):
    """The program's served entry: a ``ParticleSessionServer`` of the
    mix's ``capacity`` slots, ``n_particles_per_session`` each."""
    from repro.core import SIRConfig
    from repro.models.tracking import make_tracking_model
    from repro.serve import ParticleSessionServer
    model = make_tracking_model(tracking_config(cfg, traffic["img"]))
    sir = SIRConfig(n_particles=traffic["n_particles_per_session"],
                    ess_frac=traffic["ess_frac"])
    return ParticleSessionServer(model=model, sir=sir,
                                 capacity=traffic["capacity"])


def work(cfg: dict, traffic: dict, n_particles: int,
         resample_share: float) -> tuple[float, float]:
    """FLOPs and HBM bytes one SIR step over ``n_particles`` needs at
    least, whatever the implementation (PERF.md section 3 gives the
    arithmetic).

    Bytes per particle: the state read once and the advanced state written
    once (2 x 20), its log-weight written and read back for the
    normalisation (2 x 4); where the step resamples, the ancestor index
    written and read (2 x 4) and the gathered state read and written
    (2 x 20).  The frame is read once.  FLOPs per particle: the advance
    (12), the patch likelihood (8 per pixel of the (2R+1)^2 patch: two
    squared distances summed, the scaled exponent, the PSF value, and a
    multiply-add for each of the two sums, with the exponential counted
    as one), the weight update and normalisation (4), the weighted mean
    (2 per state entry) and the ESS (2).
    """
    pix = (2 * cfg["patch_radius"] + 1) ** 2
    flops = 12 + 8 * pix + 4 + 2 * STATE_DIM + 2
    state = STATE_DIM * F32
    bytes_ = 2 * state + 2 * F32 + resample_share * (2 * F32 + 2 * state)
    img = traffic["img"]
    return (float(n_particles * flops),
            float(n_particles * bytes_ + img * img * F32))
