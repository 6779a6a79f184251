"""End-to-end driver for the paper's application (§VII): parallel particle
filtering of fluorescence-microscopy movies on a device mesh.

Reproduces the experimental pipeline at container scale:
  synthetic 512×512 movie (Fig 4) → distributed SIR with a selectable DRA
  (RNA / ARNA / RPA × GS/SGS/LGS, or the DESIGN.md §14 butterfly) →
  trajectory + RMSE + DLB / comm-volume diagnostics.

    PYTHONPATH=src python examples/tracking_microscopy.py \
        --devices 8 --dra rpa --scheduler lgs --particles 262144

``--devices N`` runs on N simulated host devices on the CPU, and on the
first N chips of an accelerator host (it fails if fewer exist).
"""
import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--dra", default="arna",
                    choices=["mpf", "rna", "arna", "rpa", "butterfly"])
    ap.add_argument("--scheduler", default="lgs",
                    choices=["gs", "sgs", "lgs"])
    ap.add_argument("--exchange-ratio", type=float, default=0.10)
    ap.add_argument("--particles", type=int, default=262144)
    ap.add_argument("--frames", type=int, default=50)
    ap.add_argument("--img", type=int, default=512)
    args = ap.parse_args()

    from repro.core import runtime
    if args.devices > 1:
        runtime.simulate_host_devices(args.devices)
    runtime.enable_compile_cache()

    import jax
    from repro.core import SIRConfig, ParallelParticleFilter
    from repro.core.distributed import DRAConfig
    from repro.data.synthetic_movie import generate_movie, tracking_rmse
    from repro.launch.mesh import make_host_mesh
    from repro.models.tracking import TrackingConfig, make_tracking_model

    cfg = TrackingConfig(img_size=(args.img, args.img), v_init=1.0)
    model = make_tracking_model(cfg)
    print(f"generating {args.frames}-frame {args.img}² movie (Fig 4)...")
    movie = generate_movie(jax.random.key(0), cfg, n_frames=args.frames)

    mesh = make_host_mesh(args.devices) if args.devices > 1 else None
    pf = ParallelParticleFilter(
        model=model,
        sir=SIRConfig(n_particles=args.particles, ess_frac=0.5),
        dra=DRAConfig(kind=args.dra, scheduler=args.scheduler,
                      exchange_ratio=args.exchange_ratio),
        mesh=mesh)

    print(f"running {args.dra.upper()} on {args.devices} device(s), "
          f"{args.particles:,} particles...")
    t0 = time.time()
    res = pf.run(jax.random.key(1), movie.frames)
    jax.block_until_ready(res.estimates)
    dt = time.time() - t0

    rmse = float(tracking_rmse(res.estimates, movie.trajectories[:, 0],
                               warmup=10))
    print(f"wall-clock {dt:.2f}s  ({dt / args.frames * 1e3:.1f} ms/frame)")
    print(f"RMSE = {rmse:.4f} px   (paper §VII.E: ~0.063 px)")
    print(f"mean ESS = {float(res.ess.mean()):,.0f}")
    if "comm_bytes" in res.diag:
        import numpy as np
        print(f"comm volume (DESIGN.md §14.3): "
              f"{int(np.asarray(res.diag['comm_bytes']).ravel()[0]):,} B/frame "
              f"per shard, "
              f"{int(np.asarray(res.diag['comm_stages']).ravel()[0])} collective stages")
    if args.dra == "rpa":
        import numpy as np
        print(f"DLB links/frame (max) = {int(np.asarray(res.diag['links']).max())}, "
              f"units moved total = {int(np.asarray(res.diag['units_moved']).sum())}, "
              f"overflow = {int(np.asarray(res.diag['overflow']).sum())}")
    if args.dra == "arna":
        import numpy as np
        print(f"ARNA adaptive q: min {float(np.asarray(res.diag['q']).min()):.3f} "
              f"max {float(np.asarray(res.diag['q']).max()):.3f}; "
              f"P_eff mean {float(np.asarray(res.diag['p_eff']).mean()):.2f}")


if __name__ == "__main__":
    main()
