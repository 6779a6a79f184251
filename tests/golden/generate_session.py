"""Regenerate tests/golden/session_parity.json — the pinned trajectory of
one session served by ``ParticleSessionServer`` under a scripted churn
pattern (other slots attaching/detaching midstream).

Run only for DELIBERATE numerical changes to the serving path:

    PYTHONPATH=src python tests/golden/generate_session.py

The golden pins the resident-session numerics across refactors; the
*bitwise* session-vs-standalone contract is additionally checked live by
tests/test_sessions.py (machine-independent, no golden needed).
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import SIRConfig
from repro.core.smc import StateSpaceModel
from repro.serve import ParticleSessionServer

HERE = os.path.dirname(os.path.abspath(__file__))
DEST = os.path.join(HERE, "session_parity.json")

A, Q, H, R0 = 0.9, 0.5, 1.0, 0.4
N_PARTICLES, N_FRAMES, CAPACITY = 256, 24, 4


def lg_model() -> StateSpaceModel:
    """The linear-Gaussian benchmark model shared with tests/test_parity."""
    def init_sampler(key, n):
        return jax.random.normal(key, (n, 1)) * 2.0

    def dynamics_sample(key, state):
        return A * state + jnp.sqrt(Q) * jax.random.normal(key, state.shape)

    def log_likelihood(state, z):
        return -0.5 * (z - H * state[:, 0]) ** 2 / R0

    return StateSpaceModel(init_sampler, dynamics_sample, log_likelihood,
                           state_dim=1)


def churn_run():
    """The scripted churn schedule the golden (and its test) replays."""
    zs = np.asarray(jax.random.normal(jax.random.key(7),
                                      (N_FRAMES,))) * 0.8
    srv = ParticleSessionServer(model=lg_model(),
                                sir=SIRConfig(n_particles=N_PARTICLES,
                                              ess_frac=0.6),
                                capacity=CAPACITY)
    h = srv.attach(jax.random.key(42))
    other = srv.attach(jax.random.key(5))
    for t in range(N_FRAMES):
        srv.submit(h, zs[t])
        if other is not None:
            srv.submit(other, np.float32(0.1 * t))
        if t == 8:
            srv.detach(other)
            other = None
        if t == 14:
            other = srv.attach(jax.random.key(9))
        srv.step()
    return srv, h, zs


def main() -> None:
    srv, h, _ = churn_run()
    res = srv.result(h)
    with open(DEST, "w") as f:
        json.dump({"jax_version": jax.__version__, "session": {
            "estimates": np.asarray(res.estimates).tolist(),
            "ess": np.asarray(res.ess).tolist(),
            "log_marginal": np.asarray(res.log_marginal).tolist(),
            "resampled": np.asarray(res.resampled).astype(int).tolist(),
        }}, f, indent=1)
    print(f"wrote {DEST}")


if __name__ == "__main__":
    main()
