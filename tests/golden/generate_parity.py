"""Regenerate the before/after parity goldens (tests/golden/sir_parity.json).

The recorded trajectories pin the *numerical behaviour* of the SIR core and
all four DRA paths across refactors: any change to RNG consumption order,
weight algebra, or resampling math shows up as a >1e-5 deviation in
tests/test_parity.py (local SIR) and tests/test_distributed.py (DRAs).

The file records the JAX version that wrote it: JAX's PRNG defaults fix
the random stream, so a JAX upgrade can move every trajectory.  Only
regenerate it for a *deliberate* numerical change or such an upgrade,
and say so in the commit.

    PYTHONPATH=src python tests/golden/generate_parity.py
"""
import json
import os
import sys

from repro.core import runtime

runtime.simulate_host_devices(8)

import jax                      # noqa: E402
import jax.numpy as jnp        # noqa: E402
import numpy as np             # noqa: E402

from repro.core import SIRConfig, ParallelParticleFilter   # noqa: E402
from repro.core import domain as domain_mod                # noqa: E402
from repro.core.distributed import DRAConfig               # noqa: E402
from repro.core.smc import StateSpaceModel, run_sir        # noqa: E402
from repro.launch.mesh import make_host_mesh               # noqa: E402
from repro.models.tracking import (TrackingConfig,         # noqa: E402
                                   make_domain_spec,
                                   make_tracking_model)
from repro.data.synthetic_movie import generate_movie      # noqa: E402

A, Q, H, R0 = 0.9, 0.5, 1.0, 0.4


def lg_model() -> StateSpaceModel:
    """The linear-Gaussian model of tests/test_smc.py (Kalman-checkable)."""
    def init_sampler(key, n):
        return jax.random.normal(key, (n, 1)) * 2.0

    def dynamics_sample(key, state):
        return A * state + jnp.sqrt(Q) * jax.random.normal(key, state.shape)

    def log_likelihood(state, z):
        return -0.5 * (z - H * state[:, 0]) ** 2 / R0

    return StateSpaceModel(init_sampler, dynamics_sample, log_likelihood,
                           state_dim=1)


def lg_observations(n: int = 24):
    return jnp.asarray(np.asarray(
        jax.random.normal(jax.random.key(7), (n,))) * 0.8)


def sir_golden() -> dict:
    zs = lg_observations()
    out = {}
    for resampler in ("systematic", "stratified", "residual"):
        cfg = SIRConfig(n_particles=256, ess_frac=0.6, resampler=resampler)
        _, outs = run_sir(jax.random.key(42), lg_model(), cfg, zs)
        out[resampler] = {
            "estimates": np.asarray(outs.estimate).tolist(),
            "ess": np.asarray(outs.ess).tolist(),
            "log_marginal": np.asarray(outs.log_marginal).tolist(),
            "resampled": np.asarray(outs.resampled).astype(int).tolist(),
        }
    return out


def dra_golden() -> dict:
    cfg = TrackingConfig(img_size=(48, 48), v_init=1.5)
    model = make_tracking_model(cfg)
    movie = generate_movie(jax.random.key(0), cfg, n_frames=8)
    mesh = make_host_mesh(8)
    out = {}
    for kind, extra in [("mpf", {}), ("rna", {"exchange_ratio": 0.25}),
                        ("arna", {}), ("rpa", {"scheduler": "lgs"})]:
        pf = ParallelParticleFilter(
            model=model, sir=SIRConfig(n_particles=1024, ess_frac=0.5),
            dra=DRAConfig(kind=kind, **extra), mesh=mesh)
        res = pf.run(jax.random.key(1), movie.frames)
        out[kind] = {
            "estimates": np.asarray(res.estimates).tolist(),
            "ess": np.asarray(res.ess).tolist(),
            "log_marginal": np.asarray(res.log_marginal).tolist(),
        }
    return out


def domain_golden() -> dict:
    """Replicated-frame reference trajectories for the domain-decomposition
    parity configs (DESIGN.md §10.3): the domain-decomposed filter on the
    8-shard mesh must reproduce these within 1e-5
    (tests/test_distributed.py::test_domain_matches_golden).  The exact
    configuration is single-sourced in domain_config.DOMAIN_PARITY,
    shared with the worker that re-runs it; ``tiles_visited`` records how
    many distinct owner tiles the true trajectory touches, asserted ≥ 2
    so the pin can't go vacuous."""
    from domain_config import DOMAIN_PARITY as dp   # sibling module

    cfg = TrackingConfig(img_size=(dp["img"], dp["img"]),
                         v_init=dp["v_init"],
                         patch_radius=dp["patch_radius"])
    model = make_tracking_model(cfg)
    movie = generate_movie(jax.random.key(dp["movie_seed"]), cfg,
                           n_frames=dp["n_frames"])
    spec = make_domain_spec(cfg, dp["tiles"])
    owners = np.asarray(domain_mod.owner_of(spec,
                                            movie.trajectories[:, 0, 0],
                                            movie.trajectories[:, 0, 1]))
    mesh = make_host_mesh(dp["tiles"])
    out = {"tiles_visited": len(set(owners.tolist())), "grid": list(spec.grid)}
    for kind, extra in dp["dras"]:
        pf = ParallelParticleFilter(
            model=model, sir=SIRConfig(n_particles=dp["n_particles"],
                                       ess_frac=dp["ess_frac"]),
            dra=DRAConfig(kind=kind, **extra), mesh=mesh)
        res = pf.run(jax.random.key(dp["run_seed"]), movie.frames)
        out[kind] = {
            "estimates": np.asarray(res.estimates).tolist(),
            "ess": np.asarray(res.ess).tolist(),
            "log_marginal": np.asarray(res.log_marginal).tolist(),
        }
    return out


if __name__ == "__main__":
    dest = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "sir_parity.json")
    # the PRNG stream (and with it every trajectory) follows the JAX
    # version's defaults, so the file records which one produced it
    data = {"jax_version": jax.__version__, "sir": sir_golden(),
            "dra": dra_golden(), "domain": domain_golden()}
    with open(dest, "w") as f:
        json.dump(data, f)
    print(f"wrote {dest}", file=sys.stderr)
