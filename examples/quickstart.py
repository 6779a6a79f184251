"""Quickstart: track a fluorescent spot with the PPF library in ~20 lines,
then track a whole bank of targets with one compiled program, then run
the same filter domain-decomposed — each shard owning one tile of the
frame — on a 4-device mesh (simulated on the CPU; on an accelerator,
as many chips as the host has, up to 4).

    PYTHONPATH=src python examples/quickstart.py
"""
from repro.core import runtime

# before any device use (DESIGN.md §6)
N_DEVICES = min(runtime.simulate_host_devices(4), 4)
runtime.enable_compile_cache()

import jax                           # noqa: E402
import jax.numpy as jnp              # noqa: E402

from repro.core import FilterBank, SIRConfig, ParallelParticleFilter  # noqa: E402
from repro.core.distributed import DRAConfig                          # noqa: E402
from repro.data.synthetic_movie import generate_movie, tracking_rmse  # noqa: E402
from repro.launch.mesh import make_host_mesh                          # noqa: E402
from repro.models.tracking import (TrackingConfig,                    # noqa: E402
                                   make_domain_spec, make_tracking_model)


def main() -> None:
    # the paper's imaging model (§VII): Gaussian PSF, SNR 2
    cfg = TrackingConfig(img_size=(128, 128), v_init=1.0)
    model = make_tracking_model(cfg)
    movie = generate_movie(jax.random.key(0), cfg, n_frames=40)

    pf = ParallelParticleFilter(
        model=model, sir=SIRConfig(n_particles=16384, ess_frac=0.5))
    result = pf.run(jax.random.key(1), movie.frames)

    rmse = tracking_rmse(result.estimates, movie.trajectories[:, 0],
                         warmup=10)
    print(f"tracked {movie.frames.shape[0]} frames; "
          f"RMSE = {float(rmse):.3f} px "
          f"(paper reports ~0.063 px at 38.4M particles)")
    print(f"mean ESS = {float(result.ess.mean()):.0f} / 16384, "
          f"resampled on {int(result.resampled.sum())} frames")

    # --- FilterBank: B independent targets, ONE jitted program -----------
    # each bank member gets its own movie (its own target) and PRNG stream;
    # member i reproduces ParallelParticleFilter.run(keys[i], frames[i])
    # exactly — see DESIGN.md §9.1
    bank_cfg = TrackingConfig(img_size=(64, 64), v_init=1.0)
    bank_model = make_tracking_model(bank_cfg)
    movies = [generate_movie(jax.random.key(10 + i), bank_cfg, n_frames=20)
              for i in range(4)]
    keys = jnp.stack([jax.random.key(100 + i) for i in range(4)])
    frames = jnp.stack([m.frames for m in movies])

    bank = FilterBank(model=bank_model,
                      sir=SIRConfig(n_particles=4096, ess_frac=0.5))
    res = bank.run(keys, frames)
    for i, m in enumerate(movies):
        rmse_i = tracking_rmse(res.estimates[i], m.trajectories[:, 0],
                               warmup=5)
        print(f"bank member {i}: RMSE = {float(rmse_i):.3f} px, "
              f"mean ESS = {float(res.ess[i].mean()):.0f} / 4096")

    # --- Domain decomposition: each shard owns one tile of the frame ------
    # The paper's input-space decomposition (DESIGN.md §10): observations
    # are tile-sharded halo slabs, particles migrate to their tile owners
    # after every dynamics step, and the trajectories are EXACTLY those of
    # the replicated-frame filter — only the frame memory placement changes.
    spec = make_domain_spec(cfg, tiles=N_DEVICES)  # halo = cfg.patch_radius
    dpf = ParallelParticleFilter(
        model=model, sir=SIRConfig(n_particles=16384, ess_frac=0.5),
        dra=DRAConfig(kind="rna"), mesh=make_host_mesh(N_DEVICES),
        domain=spec)
    dres = dpf.run(jax.random.key(1), movie.frames)
    drmse = tracking_rmse(dres.estimates, movie.trajectories[:, 0], warmup=10)
    print(f"domain-decomposed on a {spec.grid} tile grid: "
          f"RMSE = {float(drmse):.3f} px, "
          f"per-shard frame bytes {spec.slab_bytes()} "
          f"vs {spec.frame_bytes()} replicated "
          f"({spec.slab_bytes() / spec.frame_bytes():.2f}x), "
          f"{int(jnp.asarray(dres.diag['mig_moved']).sum())} particle "
          f"migrations over {movie.frames.shape[0]} frames")


if __name__ == "__main__":
    main()
